"""95th percentile of every gap between consecutive output tokens, over
all requests due in the window (host clock)."""
import numpy as np

from chipbench.stats import percentile


def read(run):
    if run.window is None:
        return None
    gaps = [g for r in run.window.recs if len(r.times) > 1
            for g in np.diff(r.times)]
    return percentile([1e3 * g for g in gaps], 95)
