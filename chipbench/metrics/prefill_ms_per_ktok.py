"""Device time of the traced steps' prefill work per 1,000 prompt tokens
prefilled in them (``trace.step_work``: the prefill programs, the writes
of their K/V into the slots and the first token's read-back)."""
from chipbench import trace


def read(run):
    if run.reduced is None or run.window is None:
        return None
    traced = [s for s in run.window.steps if s.traced]
    toks = sum(sum(s.prefill_lens) for s in traced)
    w = trace.step_work(run.reduced, traced)
    return 1e6 * w.prefill_s / toks if toks and w.prefill_s > 0 else None
