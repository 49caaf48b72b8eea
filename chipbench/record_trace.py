"""Record a short profiler trace of a serving cell, for the reduction's test.

    python3 chipbench/record_trace.py --workload internlm2-1.8b.chat \
        --seed 5 --seconds 1.2 --out tests/chipbench/data/v5e_chat.xplane.pb.gz

Sets the cell up as a run does, traces its window's last
``serving.TRACE_S`` seconds (all of a short one), writes the
``.xplane.pb`` gzipped to
``--out``, and prints every plane with its lines and event counts, the
reduction, and the engine steps the window recorded in the traced slice
(as JSON beside ``--out``), so the test can check program calls against
steps.  The benchmark's runs never call this.
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
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)
    import jax
    from chipbench import device, harness, serving, spec
    from chipbench import trace as tmod
    cell = spec.load_cell(args.workload)
    harness.use_compile_cache()
    device.check(jax.devices(), cell.chips)
    sv = serving.Serving(cell, args.seed)
    sv.warm_up()
    w = sv.window(args.seconds, trace=True)
    path = tmod.find_xplane(w.trace_dir)
    with open(path, "rb") as f:
        raw = f.read()
    shutil.rmtree(w.trace_dir, ignore_errors=True)
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    with gzip.open(args.out, "wb", compresslevel=9) as f:
        f.write(raw)
    prof = jax.profiler.ProfileData.from_serialized_xspace(raw)
    for plane in prof.planes:
        lines = [(l.name, sum(1 for _ in l.events)) for l in plane.lines]
        print(f"plane {plane.name!r}: {lines}")
        for l in plane.lines:
            names = []
            for e in l.events:
                if e.name not in names:
                    names.append(e.name)
                if len(names) >= 6:
                    break
            print(f"    line {l.name!r} first names {names}")
    r = tmod.reduce(prof, cell.chips)
    print("reduced:", json.dumps({
        "window_s": r.window_s, "busy_s": r.busy_s,
        "modules": r.modules, "idle": r.idle}))
    steps = [{"decode_lens": s.decode_lens, "prefill_lens": s.prefill_lens}
             for s in w.steps if s.traced]
    with open(args.out.replace(".xplane.pb.gz", ".steps.json"), "w") as f:
        json.dump({"workload": args.workload, "seed": args.seed,
                   "device_kind": jax.devices()[0].device_kind,
                   "steps": steps}, f)
    print(f"wrote {args.out}: {len(raw)} bytes raw, "
          f"{os.path.getsize(args.out)} gzipped; {len(steps)} traced steps")
    return 0


if __name__ == "__main__":
    sys.exit(main())
