"""One run of one cell:

    python3 chipbench/run.py --workload <name> --seed <n> --seconds <s> \
        --trace <0|1>

prints, as the last line of standard output, one JSON object with
``correct``, ``attempted``, ``failed``, ``metrics``, ``device`` (and with
``--trace 1`` a ``breakdown``), and as the last lines of standard error
each number that decided ``correct`` beside its limit; the same numbers
close the JSON line under ``checks``.  With ``--trace 0`` the metrics are
the cell's end-to-end metrics, with ``--trace 1`` its per-layer metrics.

A run without the chips the cell asks for, of a kind in the peaks table,
exits with code 3 and prints no result.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import shutil
import sys
import time
from typing import Callable, Dict, List, Optional

from chipbench import spec
from chipbench.device import NoChip


def process_start_epoch() -> Optional[float]:
    """When this process started, by the kernel's clock (Linux)."""
    try:
        with open("/proc/self/stat") as f:
            ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/stat") as f:
            btime = next(int(l.split()[1]) for l in f
                         if l.startswith("btime"))
        return btime + ticks / os.sysconf("SC_CLK_TCK")
    except (OSError, ValueError, IndexError, StopIteration):
        return None


@dataclasses.dataclass
class Run:
    """Everything a metric reader may read."""
    cell: spec.Cell
    setup_s: float
    peaks: object = None             # device.Peaks
    shapes: object = None            # counts.Shapes
    window: object = None            # serving.Window
    reduced: object = None           # trace.Reduced (``--trace 1`` only)
    spans: object = None             # spans.Spans (``--trace 1`` only)
    counters: object = None          # the engine's repro.obs
                                     # MetricsRegistry (``--trace 1`` only)


def use_compile_cache() -> str:
    """JAX's persistent cache: ``JAX_COMPILATION_CACHE_DIR`` where set,
    else a fixed directory inside the checkout; every program cached."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or \
        os.path.join(spec.ROOT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


def read_metrics(run: Run, metrics: List[spec.Metric],
                 root: str = spec.ROOT) -> Dict:
    out = {}
    for m in metrics:
        v = spec.reader(m.name, root)(run)
        if v is not None:
            out[m.name] = {"value": float(v), "unit": m.unit}
    return out


def emit(result: Dict, checks: Dict) -> None:
    """Checks last on standard error, the JSON line last on standard out."""
    result = dict(result, checks=checks)
    for name, c in checks.items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)


def run_cell(cell: spec.Cell, seed: int, seconds: float, trace: bool,
             t_start: float, devices: Optional[list] = None,
             check_chip: Callable = None, control: bool = False,
             on_trace: Callable = None) -> Dict:
    """Set up, measure and check one run; returns the result line's
    fields (``result``), the numbers compared (``checks``: each limit of
    the configuration's ``limits``, and no request left unfinished),
    what each side read against the reference (``readings``,
    ``reference.numbers``) and what the metric readers read (``run``).
    ``check_chip``
    (``device.check``) refuses a run without the chip; a test that drives
    the rest of a run on the CPU passes a stand-in.  ``control`` puts the
    int8 control in the program's place in the comparison: the same
    sample, the same limit, the control's first choices compared.
    ``on_trace`` is handed a traced run's profile directory before it is
    removed.
    """
    import jax
    from chipbench import counts, device
    devices = jax.devices() if devices is None else devices
    peaks = (check_chip or device.check)(devices, cell.chips)
    shapes = counts.Shapes.from_config(cell.config)
    from chipbench import reference, serving, spans, traffic
    from chipbench import trace as tmod
    from chipbench import weights as wmod
    sv = serving.Serving(cell, seed)
    sv.warm_up()
    setup_s = time.time() - t_start
    w = sv.window(seconds, trace)
    dev = device.describe(devices, cell.chips)
    run = Run(cell=cell, setup_s=setup_s, peaks=peaks, shapes=shapes,
              window=w, counters=w.counters)
    result: Dict = {}
    if w.trace_dir is not None:
        if on_trace is not None:
            on_trace(w.trace_dir)
        prof = tmod.profile(w.trace_dir)
        shutil.rmtree(w.trace_dir, ignore_errors=True)
        run.reduced = tmod.reduce(prof, cell.chips)
        run.spans = spans.reduce(prof)
        dev["busy_s"] = run.reduced.busy_s
        dev["window_s"] = run.reduced.window_s
        result["breakdown"] = tmod.breakdown(run.reduced)
    metrics = read_metrics(run, cell.per_layer if trace else cell.end_to_end)

    failed = sum(1 for r in w.recs if len(r.req.out_tokens) < r.req.osl)
    seqs = sv.sample(w)
    shapes_flat = wmod.flat_shapes(sv.abstract)
    sv.release()
    isl, osl = traffic.longest(cell.traffic)
    t_ref = time.time()
    gaps = reference.served_gaps(
        cell.config, shapes_flat, seed, seqs, control=control,
        length=isl - 1 + osl, out_len=osl) if seqs else {}
    readings = {side: reference.numbers(g) for side, g in gaps.items()}
    print(f"# reference: {sum(len(o) for _, o in seqs)} served tokens of "
          f"{len(seqs)} requests compared in {time.time() - t_ref:.1f} s",
          file=sys.stderr)
    judged = readings.get("control" if control else "program", {})
    checks = {"unfinished_requests": {"value": failed, "limit": 0}}
    for name, limit in cell.config["limits"].items():
        checks[name] = {"value": judged.get(name, float("inf")),
                        "limit": float(limit)}
    correct = bool(seqs) and all(c["value"] <= c["limit"]
                                 for c in checks.values())
    result.update(correct=correct, attempted=len(w.recs), failed=failed,
                  metrics=metrics, device=dev)
    return {"result": result, "checks": checks, "readings": readings,
            "run": run}


def main(argv=None, t_start: Optional[float] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    t_start = process_start_epoch() or t_start or time.time()
    cell = spec.load_cell(args.workload)
    use_compile_cache()
    try:
        out = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                       t_start)
    except NoChip as e:
        print(f"chipbench: {e}; nothing was measured", file=sys.stderr)
        return 3
    emit(out["result"], out["checks"])
    return 0
