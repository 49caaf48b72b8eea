"""Readings that a cell's limits (``logit_gap_mean``...) are set from, on
the chip.

    python3 chipbench/calibrate.py --workload internlm2-1.8b.chat \
        --seeds 201,202,...,212 --seconds 15

For each seed, in one process: a whole run of the cell through
``harness.run_cell`` with ``control=True`` (weights from the seed, a
short window at the cell's own load, long enough to finish its longest
requests, the sample a run compares), so the int8 control is judged by
the run's own check at the cell's own limits.  One JSON line per seed:
what the program's served tokens read against the reference (the lower
readings), what the control's first choices read (the upper readings),
each as ``reference.numbers``, and whether the run with the control in
the program's place came out correct.  The
benchmark's runs never call this.
"""
from __future__ import annotations

import argparse
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
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    from chipbench import harness, spec
    cell = spec.load_cell(args.workload)
    harness.use_compile_cache()
    for seed in (int(s) for s in args.seeds.split(",")):
        t = time.time()
        out = harness.run_cell(cell, seed, args.seconds, False, t,
                               control=True)
        r = out["result"]
        print(json.dumps({
            "seed": seed, "requests": r["attempted"], "failed": r["failed"],
            "program": out["readings"].get("program"),
            "control": out["readings"].get("control"),
            "checks": out["checks"],
            "control_run_correct": r["correct"],
            "memory_peak_bytes": r["device"]["memory_peak_bytes"],
            "seconds": time.time() - t}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
