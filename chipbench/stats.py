"""Exact statistics over every sample (no histograms, no buckets)."""
from __future__ import annotations

from typing import Iterable, Optional

import numpy as np


def percentile(values: Iterable[float], q: float) -> Optional[float]:
    """The ``q``-th percentile, linear between order statistics; None for
    no samples."""
    v = np.asarray(list(values), np.float64)
    if v.size == 0:
        return None
    return float(np.percentile(v, q))


def in_window(run, t: float) -> bool:
    w = run.window
    return w.t0 <= t <= w.t0 + w.seconds
