"""Least time of the traced prefills over the device time of the steps'
prefill work (``trace.step_work``): per prompt the larger of its
operations over the peak and its least bytes over the bandwidth
(``counts.prefill_flops/bytes``)."""
from chipbench import counts, trace


def read(run):
    if run.reduced is None or run.window is None:
        return None
    traced = [s for s in run.window.steps if s.traced]
    lens = [n for s in traced for n in s.prefill_lens]
    w = trace.step_work(run.reduced, traced)
    if not lens or w.prefill_s <= 0:
        return None
    p = run.peaks
    least = sum(counts.least_seconds(
        counts.prefill_flops(run.shapes, n),
        counts.prefill_bytes(run.shapes, n),
        p.flops_per_s, p.bytes_per_s) for n in lens)
    return 100.0 * least / w.prefill_s
