"""Trace a serving cell's window with the engine's spans on the trace, and
put its idle device time down to the engine's phases.

    python3 chipbench/record_spans.py --workload internlm2-1.8b.chat \
        --seed 5 --seconds 51 [--out tests/chipbench/data/NAME.xplane.pb.gz]

Sets the cell up as a run does, then installs a ``repro.obs`` tracer on
the wall clock, bridged to the profiler, and a metrics registry, for the
window alone; traces the window's last ``serving.TRACE_S`` seconds (all of
a short one) and prints one JSON line: the trace's reduction
(``trace.reduce``), the idle split by engine phase (``spans.reduce``), the
cell's per-layer metrics read from that run, and the engine's
``repro_engine_*`` counters beside the numbers the harness reads for the
same quantities.  With ``--out`` it also writes the ``.xplane.pb`` gzipped
there and the traced steps beside it, as ``record_trace.py`` does, for the
reductions' tests.  The benchmark's runs never call this.
"""
from __future__ import annotations

import argparse
import gzip
import json
import os
import shutil
import sys

sys.path[:0] = [os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                os.path.join(os.path.dirname(os.path.dirname(
                    os.path.abspath(__file__))), "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--out", default="")
    args = ap.parse_args(argv)
    import jax
    from chipbench import counts, device, harness, serving, spans, spec
    from chipbench import trace as tmod
    from repro.obs import (Tracer, disable_metrics, disable_tracing,
                           enable_metrics, enable_tracing)
    cell = spec.load_cell(args.workload)
    harness.use_compile_cache()
    peaks = device.check(jax.devices(), cell.chips)
    sv = serving.Serving(cell, args.seed)
    sv.warm_up()
    enable_tracing(Tracer(timebase="wall", profiler=True))
    registry = enable_metrics()
    try:
        w = sv.window(args.seconds, trace=True)
    finally:
        disable_tracing()
        disable_metrics()
    with open(tmod.find_xplane(w.trace_dir), "rb") as f:
        raw = f.read()
    shutil.rmtree(w.trace_dir, ignore_errors=True)
    prof = jax.profiler.ProfileData.from_serialized_xspace(raw)
    r = tmod.reduce(prof, cell.chips)
    s = spans.reduce(prof)
    run = harness.Run(cell=cell, setup_s=0.0, peaks=peaks,
                      shapes=counts.Shapes.from_config(cell.config),
                      window=w, reduced=r)
    metrics = harness.read_metrics(run, cell.per_layer)
    n_dec = sum(registry.counter_value("repro_engine_steps_total", kind=k)
                for k in ("decode", "mixed"))
    print(json.dumps({
        "window_s": r.window_s, "busy_s": r.busy_s,
        "idle_share": 100.0 * (1.0 - r.busy_s / r.window_s),
        "idle_share.readback": s.share("readback"),
        "idle_share.host": s.share("host"),
        "idle_share.outside": s.share(spans.OUTSIDE),
        "idle_by_span": s.idle, "host_by_span": s.host,
        "modules": r.modules,
        "metrics": {k: v["value"] for k, v in metrics.items()},
        "counters": registry.to_dict()["counters"],
        "queue_wait_ms_p50.registry": registry.quantile(
            "repro_engine_queue_wait_ms", 0.5),
        "decode_occupancy.registry": 100.0 * registry.counter_value(
            "repro_engine_decode_slot_steps_total") / (n_dec * w.slots)
        if n_dec else None,
    }), flush=True)
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)),
                    exist_ok=True)
        with gzip.open(args.out, "wb", compresslevel=9) as f:
            f.write(raw)
        steps = [{"decode_lens": st.decode_lens,
                  "prefill_lens": st.prefill_lens}
                 for st in w.steps if st.traced]
        with open(args.out.replace(".xplane.pb.gz", ".steps.json"),
                  "w") as f:
            json.dump({"workload": args.workload, "seed": args.seed,
                       "device_kind": jax.devices()[0].device_kind,
                       "steps": steps}, f)
        print(f"wrote {args.out}: {len(raw)} bytes raw, "
              f"{os.path.getsize(args.out)} gzipped; {len(steps)} traced "
              f"steps", file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
