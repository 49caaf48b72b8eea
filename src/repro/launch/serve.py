"""Serving driver — consumes the configurator's Generator output.

    # from a generated launch file:
    PYTHONPATH=src python -m repro.launch.serve --launch-config out.json

    # or directly:
    PYTHONPATH=src python -m repro.launch.serve --model internlm2-1.8b \
        --max-batch 4 --requests 8 --isl 16 --osl 8

Runs the real continuous-batching engine over a synthetic workload on the
device JAX picks, and reports TTFT/TPOT/throughput — the measured
counterpart to the configurator's projections.  The model is the reduced
config unless ``--full-config`` asks for the published widths (the TPU
run; tests and the CPU rehearsal use the reduced default with
``JAX_PLATFORMS=cpu``).  The engine runs on one device: a launch config
with tp·pp·ep·dp > 1, or a disaggregated one, is refused (exit 2).

:func:`serve` is the callable form: it takes the parsed arguments and
returns the measurements, so a script can run it in its own process.

Observability: ``--trace-out PATH`` writes the engine's ``repro.obs`` spans
(``engine.step`` and its phases, on the wall clock; ``.chrome.json`` for
the Chrome export), ``--metrics-out PATH`` its ``repro_engine_*`` counters
(JSON, or Prometheus text with ``.prom``), and ``--profile-dir DIR`` a
``jax.profiler`` trace of the timed window with the engine's spans on its
host plane, beside the device's programs (open it in TensorBoard or
Perfetto).
"""
from __future__ import annotations

import argparse
import json
import math
import sys
import time
from typing import Dict, List, Optional, Sequence

import jax
import numpy as np

from repro import models
from repro.configs import get_config
from repro.core.jaxenv import use_compile_cache
from repro.obs import ObsCapture, Tracer
from repro.serving.engine import Engine, EngineConfig
from repro.serving.request import Request


class UnservableLaunch(ValueError):
    """A launch this one-device engine cannot run as asked."""


def parse_args(argv: Optional[Sequence[str]] = None) -> argparse.Namespace:
    ap = argparse.ArgumentParser(
        description="serve a synthetic workload on the real engine")
    ap.add_argument("--launch-config", default="")
    ap.add_argument("--model", default="internlm2-1.8b")
    ap.add_argument("--full-config", action="store_true",
                    help="serve the published widths, not the reduced "
                         "config — TPU scale")
    ap.add_argument("--tp", type=int, default=1)
    ap.add_argument("--pp", type=int, default=1)
    ap.add_argument("--ep", type=int, default=1)
    ap.add_argument("--max-batch", type=int, default=4)
    ap.add_argument("--max-num-tokens", type=int, default=8192)
    ap.add_argument("--kv-cache-hbm-fraction", type=float, default=0.9)
    ap.add_argument("--chunked-prefill", action="store_true")
    ap.add_argument("--decode-bucketing", action="store_true")
    # the Generator's disaggregated command line; refused, see serve()
    ap.add_argument("--disaggregated", action="store_true")
    ap.add_argument("--prefill", default="")
    ap.add_argument("--decode", default="")
    ap.add_argument("--decode-batch", type=int, default=0)
    ap.add_argument("--kv-frac", type=float, default=0.9)
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--isl", type=int, default=16)
    ap.add_argument("--osl", type=int, default=8)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--trace-out", default="", metavar="PATH",
                    help="write the engine's repro.obs spans here (JSONL "
                         "TraceArtifact on the wall clock; '-' streams it "
                         "to stdout; a .chrome.json suffix writes the "
                         "Chrome trace_event export)")
    ap.add_argument("--metrics-out", default="", metavar="PATH",
                    help="write the engine's repro_engine_* counters here "
                         "(JSON, or Prometheus text with a .prom suffix)")
    ap.add_argument("--profile-dir", default="", metavar="DIR",
                    help="capture a jax.profiler trace of the timed window "
                         "here, with the engine's spans on its host plane")
    return ap.parse_args(argv)


def _check_one_device(launch: Optional[Dict], args) -> None:
    if args.disaggregated or (launch or {}).get("mode") == "disaggregated":
        raise UnservableLaunch(
            "disaggregated launch: the engine runs one device and has no "
            "separate prefill and decode pools")
    par = (launch.get("parallel", {}) if launch else
           {"tp": args.tp, "pp": args.pp, "ep": args.ep})
    width = math.prod(int(par.get(k, 1)) for k in ("tp", "pp", "ep", "dp"))
    if width > 1:
        raise UnservableLaunch(
            f"launch needs tp·pp·ep·dp = {width} devices ({par}); the engine "
            "runs one device")


def _pct(xs: List[float], q: float) -> float:
    return float(np.percentile(np.asarray(xs), q))


def serve(args: argparse.Namespace, launch: Optional[Dict] = None,
          cfg=None, params=None) -> Dict:
    """Serve ``args.requests`` synthetic requests; return the measurements.

    ``launch`` is a Generator launch config (``LaunchConfig.raw``); when
    absent, ``args.launch_config`` names a JSON file holding one.  ``cfg``
    and ``params`` override the model and its random weights.  Raises
    :class:`UnservableLaunch` for a launch the engine cannot run.

    Before the timed window one 2-token request per distinct prompt
    length compiles the prefill and decode programs; ``compile_s`` is that
    warm-up's wall time, kept apart from TTFT.
    """
    if launch is None and args.launch_config:
        with open(args.launch_config) as f:
            launch = json.load(f)
    _check_one_device(launch, args)
    model, max_batch = args.model, args.max_batch
    if launch:
        model, max_batch = launch["model"], launch["batch_size"]
    if cfg is None:
        cfg = get_config(model)
        if not args.full_config:
            cfg = cfg.reduced()
    if params is None:
        params = models.init_params(cfg, jax.random.PRNGKey(args.seed))
    # no more decode slots than requests: an idle slot still costs a
    # full cache row in memory and in every decode step
    slots = max(1, min(max_batch, args.requests))
    eng = Engine(cfg, params, EngineConfig(
        max_batch=slots,
        max_seq=max(args.isl + args.osl + 8, 64),
        kv_cache_hbm_fraction=args.kv_cache_hbm_fraction,
        decode_bucketing=args.decode_bucketing,
        max_num_tokens=args.max_num_tokens))

    rng = np.random.default_rng(args.seed)
    prompts = [rng.integers(0, cfg.vocab_size, args.isl).tolist()
               for _ in range(args.requests)]

    t0 = time.perf_counter()
    for i, isl in enumerate(sorted({len(p) for p in prompts})):
        eng.add_request(Request(rid=-1 - i, isl=isl, osl=2,
                                arrival=time.perf_counter(),
                                prompt=prompts[0][:isl]))
    eng.run_until_drained()
    compile_s = time.perf_counter() - t0

    profile_dir = getattr(args, "profile_dir", "")
    if profile_dir:
        jax.profiler.start_trace(profile_dir)
    t_start = time.perf_counter()
    for i, prompt in enumerate(prompts):
        eng.add_request(Request(rid=i, isl=len(prompt), osl=args.osl,
                                arrival=time.perf_counter(), prompt=prompt))
    done = eng.run_until_drained()
    wall = time.perf_counter() - t_start
    if profile_dir:
        jax.profiler.stop_trace()

    ttfts = [r.ttft for r in done if r.ttft is not None]
    tpots = [r.tpot for r in done if r.tpot is not None]
    gen = sum(len(r.out_tokens) for r in done)
    dev = jax.devices()[0]
    stats = dev.memory_stats() or {}
    return {
        "model": cfg.name, "requests": len(done), "isl": args.isl,
        "osl": args.osl, "slots": slots, "launch_batch": max_batch,
        "compile_s": compile_s, "wall_s": wall,
        "ttft_ms_p50": 1e3 * _pct(ttfts, 50),
        "ttft_ms_p99": 1e3 * _pct(ttfts, 99),
        "tpot_ms_p50": 1e3 * _pct(tpots, 50) if tpots else None,
        "tpot_ms_p99": 1e3 * _pct(tpots, 99) if tpots else None,
        "tokens_per_s": gen / wall,
        "peak_bytes_in_use": stats.get("peak_bytes_in_use"),
        "device": {"platform": dev.platform, "kind": dev.device_kind,
                   "count": len(jax.devices())},
    }


def main(argv: Optional[Sequence[str]] = None) -> int:
    args = parse_args(argv)
    use_compile_cache()
    tracer = None
    if args.trace_out or args.profile_dir:
        tracer = Tracer(timebase="wall", profiler=bool(args.profile_dir))
    obs = ObsCapture(args, command="serve", tracer=tracer)
    try:
        m = serve(args)
    except UnservableLaunch as e:
        print(f"error: {e}", file=sys.stderr)
        return 2
    finally:
        obs.finish()
    d = m["device"]
    print(f"served {m['requests']} requests ({m['model']}, {m['slots']} "
          f"slots) in {m['wall_s']:.2f}s on {d['platform']} {d['kind']}")
    print(f"compile {m['compile_s']:.2f}s (warm-up, outside the window)")
    tpot = ("n/a" if m["tpot_ms_p50"] is None else
            f"{m['tpot_ms_p50']:.2f}/{m['tpot_ms_p99']:.2f}ms")
    print(f"TTFT p50/p99 {m['ttft_ms_p50']:.1f}/{m['ttft_ms_p99']:.1f}ms  "
          f"TPOT p50/p99 {tpot}  "
          f"throughput {m['tokens_per_s']:.1f} tok/s")
    return 0


if __name__ == "__main__":
    sys.exit(main())
