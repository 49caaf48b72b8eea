"""Run a serving cell as ``--trace 1`` does, and print its idle split by
engine span and its engine counters in full.

    python3 chipbench/record_spans.py --workload internlm2-1.8b.chat \
        --seed 5 --seconds 51 [--out tests/chipbench/data/NAME.xplane.pb.gz]

Drives ``harness.run_cell`` with the trace on and prints one JSON line:
the run's result line, the idle and host seconds of each ``engine.*``
span (``spans.Spans``), the idle outside any engine span, and the
engine's ``repro_engine_*`` counters.  With ``--out`` it also writes the
``.xplane.pb`` gzipped there and the traced steps beside it
(``NAME.steps.json``): the recorded traces that the reductions' tests
read (``tests/chipbench/data/``).  The benchmark's runs never call this.
"""
from __future__ import annotations

import argparse
import gzip
import json
import os
import sys
import time

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
    from chipbench import harness, spans, spec
    from chipbench import trace as tmod
    cell = spec.load_cell(args.workload)
    harness.use_compile_cache()
    raw = []

    def keep(trace_dir: str) -> None:
        if args.out:
            with open(tmod.find_xplane(trace_dir), "rb") as f:
                raw.append(f.read())
    out = harness.run_cell(cell, args.seed, args.seconds, True, time.time(),
                           on_trace=keep)
    run = out["run"]
    print(json.dumps({
        "result": out["result"],
        "idle_by_span": run.spans.idle, "host_by_span": run.spans.host,
        "idle_share.outside": run.spans.share(spans.OUTSIDE),
        "counters": run.counters.to_dict()["counters"],
    }), flush=True)
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)),
                    exist_ok=True)
        with gzip.open(args.out, "wb", compresslevel=9) as f:
            f.write(raw[0])
        steps = [{"decode_lens": st.decode_lens,
                  "prefill_lens": st.prefill_lens}
                 for st in run.window.steps if st.traced]
        with open(args.out.replace(".xplane.pb.gz", ".steps.json"),
                  "w") as f:
            json.dump({"workload": args.workload, "seed": args.seed,
                       "device_kind": jax.devices()[0].device_kind,
                       "steps": steps}, f)
        print(f"wrote {args.out}: {len(raw[0])} bytes raw, "
              f"{os.path.getsize(args.out)} gzipped; {len(steps)} traced "
              f"steps", file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
