"""Median wait from a request's due time to its first scheduling by the
engine's scheduler (``Request.t_first_sched``; host clock)."""
from chipbench.stats import percentile


def read(run):
    if run.window is None:
        return None
    return percentile([1e3 * (r.req.t_first_sched - r.due)
                       for r in run.window.recs
                       if r.req.t_first_sched is not None], 50)
