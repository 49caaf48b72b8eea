"""Least time of the traced decode steps over the decode program's device
time.  Least time is bytes over HBM bandwidth: every weight once, K/V at
the valid lengths of the active slots, the new K/V written
(``counts.decode_bytes``); never the allocated width or empty slots."""
from chipbench import counts, trace


def read(run):
    if run.reduced is None or run.window is None or run.shapes.experts:
        return None
    traced = [s for s in run.window.steps if s.traced]
    w = trace.step_work(run.reduced, traced)
    if not w.decode_calls:
        return None
    p = run.peaks
    least = sum(counts.least_seconds(
        counts.decode_flops(run.shapes, s.decode_lens),
        counts.decode_bytes(run.shapes, s.decode_lens),
        p.flops_per_s, p.bytes_per_s) for s in traced if s.decode_lens)
    return 100.0 * least / w.decode_s
