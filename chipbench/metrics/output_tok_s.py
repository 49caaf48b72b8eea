"""Output tokens delivered inside the window over the window's seconds
(host clock)."""
from chipbench.stats import in_window


def read(run):
    if run.window is None:
        return None
    n = sum(1 for r in run.window.recs for t in r.times
            if in_window(run, t))
    return n / run.window.seconds
