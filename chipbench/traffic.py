"""Seeded traffic: one general generator driven by a mix's data file.

A mix file (``traffic/<mix>.json``) is

    {"kind": "open", "arrivals": {...}, "lengths": {...}, "engine": {...}}

The schedule (when each request is due, and its prompt and output
lengths) is a pure function of ``(mix, seconds)``: every run of a cell,
whatever its seed, offers the same work at the same times.  The seed
draws only what the requests say (:func:`prompt`) and, elsewhere, the
weights.  Were the order drawn from the seed, which long requests fall
near the close would move the tokens delivered inside a window by up to
6.4% from seed to seed, against at most 0.6% between two runs of one
seed (TPU v5e, internlm2-1.8b chat at 3.2 req/s, 45 s).

Arrivals (``arrivals.kind``):
  ``poisson``  rate ``rate_rps``: exponential gaps, as
               ``repro/workloads/generators.py`` draws them.  The window
               holds ``round(rate * seconds)`` requests whose gaps are the
               exponential distribution's quantiles, in a fixed shuffled
               order, scaled to fill the window.

Lengths: ``isl`` is ``{"choices": [...], "weights": [...]}`` or
``{"lo": a, "hi": b}`` (uniform integers); ``osl`` likewise.  Each window's
lengths are the distribution's strata (weights rounded by largest
remainder; uniform ranges at evenly spaced quantiles), in a fixed shuffled
order.
"""
from __future__ import annotations

import dataclasses
import math
import random
from typing import Dict, List, Sequence, Tuple


@dataclasses.dataclass(frozen=True)
class Arrival:
    due_s: float                 # offset from the window's start
    isl: int
    osl: int


SCHEDULE = 0             # what every run's schedule is drawn from


def _rng(seed: int, salt: str) -> random.Random:
    return random.Random(f"{int(seed)}:{salt}")


def _apportion(weights: Sequence[float], n: int) -> List[int]:
    """Split ``n`` by ``weights``, largest remainder first."""
    total = float(sum(weights))
    exact = [w * n / total for w in weights]
    counts = [int(math.floor(x)) for x in exact]
    order = sorted(range(len(weights)), key=lambda i: exact[i] - counts[i],
                   reverse=True)
    for i in order[: n - sum(counts)]:
        counts[i] += 1
    return counts


def _strata(dist: Dict, n: int) -> List[int]:
    """``n`` values that cover ``dist`` evenly, in ascending order."""
    if "choices" in dist:
        counts = _apportion(dist.get("weights", [1] * len(dist["choices"])),
                            n)
        return [v for v, c in zip(dist["choices"], counts) for _ in range(c)]
    lo, hi = int(dist["lo"]), int(dist["hi"])
    return [min(hi, lo + int((i + 0.5) / n * (hi - lo + 1)))
            for i in range(n)]


def lengths(mix: Dict, n: int) -> List[Tuple[int, int]]:
    """``n`` (isl, osl) pairs: the strata of each, in a fixed shuffled
    order."""
    isl = _strata(mix["lengths"]["isl"], n)
    osl = _strata(mix["lengths"]["osl"], n)
    _rng(SCHEDULE, "isl").shuffle(isl)
    _rng(SCHEDULE, "osl").shuffle(osl)
    return list(zip(isl, osl))


def prompt_lengths(mix: Dict) -> List[int]:
    """Every prompt length the mix can send: the shapes to warm up."""
    d = mix["lengths"]["isl"]
    if "choices" in d:
        return sorted(set(int(x) for x in d["choices"]))
    return list(range(int(d["lo"]), int(d["hi"]) + 1))


def longest(mix: Dict) -> Tuple[int, int]:
    """The longest prompt and the longest output the mix can send."""
    top = lambda d: max(d["choices"]) if "choices" in d else d["hi"]
    return int(top(mix["lengths"]["isl"])), int(top(mix["lengths"]["osl"]))


def _poisson_gaps(rng: random.Random, n: int, seconds: float) -> List[float]:
    gaps = [-math.log(1.0 - (i + 0.5) / n) for i in range(n)]
    rng.shuffle(gaps)
    scale = seconds / sum(gaps)
    return [g * scale for g in gaps]


def open_loop(mix: Dict, seconds: float) -> List[Arrival]:
    """Every request due in a window of ``seconds``, by due time."""
    a = mix["arrivals"]
    rng = _rng(SCHEDULE, "arrivals")
    if a["kind"] == "poisson":
        n = max(1, int(round(float(a["rate_rps"]) * seconds)))
        gaps = _poisson_gaps(rng, n, seconds)
        times, t = [], 0.0               # due at 0 and then gap after gap
        for g in gaps:
            times.append(t)
            t += g
    else:
        raise ValueError(f"unknown arrival kind {a['kind']!r}")
    sizes = lengths(mix, len(times))
    return [Arrival(t, i, o) for t, (i, o) in zip(times, sizes)]


def prompt(seed: int, idx: int, isl: int, vocab: int) -> List[int]:
    """Request ``idx``'s prompt: token ids uniform over the vocabulary."""
    import numpy as np
    rng = np.random.default_rng([int(seed) & 0xFFFFFFFF, int(seed) >> 32,
                                 int(idx)])
    return rng.integers(0, vocab, int(isl)).tolist()

