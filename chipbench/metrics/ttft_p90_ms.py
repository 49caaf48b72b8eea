"""90th percentile of time to first token, from each request's due time,
over every request due in the window that finished (host clock)."""
from chipbench.stats import percentile


def read(run):
    if run.window is None:
        return None
    v = percentile([1e3 * (r.times[0] - r.due) for r in run.window.recs
                    if r.times], 90)
    return v
