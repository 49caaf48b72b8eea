"""The share of the chip's peak of the window's steps that prefilled,
whose time every waiting request's first token pays: their model
operations (the prefills and the decode step riding along,
``counts.prefill_flops`` and ``decode_flops``) over their summed wall time
(host clock around ``Engine.step``) times the peak."""
from chipbench import counts
from chipbench.stats import in_window


def read(run):
    if run.window is None:
        return None
    steps = [s for s in run.window.steps
             if s.prefill_lens and in_window(run, s.t0)]
    wall = sum(s.t1 - s.t0 for s in steps)
    if not steps or wall <= 0:
        return None
    flops = sum(counts.decode_flops(run.shapes, s.decode_lens)
                if s.decode_lens else 0.0 for s in steps)
    flops += sum(counts.prefill_flops(run.shapes, n)
                 for s in steps for n in s.prefill_lens)
    return 100.0 * flops / (wall * run.peaks.flops_per_s)
