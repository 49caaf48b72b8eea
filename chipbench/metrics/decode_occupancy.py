"""Mean share of the engine's slots decoding, over the window's decode
steps (counted by the benchmark around ``Engine.step``)."""
from chipbench.stats import in_window


def read(run):
    if run.window is None:
        return None
    n = [len(s.decode_lens) for s in run.window.steps
         if s.decode_lens and in_window(run, s.t0)]
    if not n:
        return None
    return 100.0 * sum(n) / (len(n) * run.window.slots)
