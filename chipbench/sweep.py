"""Knee sweep of an open-loop serving cell, on the chip, in one process.

    python3 chipbench/sweep.py --workload internlm2-1.8b.chat --seed 11 \
        --seconds 20 --rates 2,3,4,5

Sets the cell up once, then runs one window per offered rate (the mix's
own lengths, its rate replaced) and prints one JSON line per rate: tokens
per second completed, TTFT and ITL tails, and the backlog at the close
(requests due but not yet scheduled).  The knee is the highest rate whose
TTFT tail stays flat and whose backlog does not grow; cells run at 0.8 x
the knee.  Open-loop (``poisson``) mixes only: a closed loop's load
follows the engine, and has no rate to sweep.  The benchmark's runs never
call this.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys

sys.path[:0] = [os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                os.path.join(os.path.dirname(os.path.dirname(
                    os.path.abspath(__file__))), "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args(argv)
    import jax
    from chipbench import device, harness, serving, spec
    cell = spec.load_cell(args.workload)
    if cell.traffic["arrivals"]["kind"] != "poisson":
        ap.error(f"{args.workload} is not an open-loop cell; only a "
                 f"poisson mix has a rate to sweep")
    harness.use_compile_cache()
    peaks = device.check(jax.devices(), cell.chips)
    sv = serving.Serving(cell, args.seed)
    sv.warm_up()
    for rate in (float(r) for r in args.rates.split(",")):
        mix = dict(cell.traffic, arrivals=dict(cell.traffic["arrivals"],
                                               rate_rps=rate))
        sv.mix = mix
        w = sv.window(args.seconds, trace=False)
        run = harness.Run(cell=dataclasses.replace(cell, traffic=mix),
                          setup_s=0.0, peaks=peaks, window=w)
        close = w.t0 + w.seconds
        backlog = sum(1 for r in w.recs if r.due <= close and (
            r.req.t_first_sched is None or r.req.t_first_sched > close))
        row = {"rate_rps": rate, "requests": len(w.recs),
               "backlog_at_close": backlog,
               "offered_tok_s": sum(r.req.osl for r in w.recs) / w.seconds}
        for m in ("output_tok_s", "ttft_p90_ms", "itl_p95_ms",
                  "queue_wait_ms_p50"):
            row[m] = spec.reader(m)(run)
        print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
