"""Seeded traffic: one general generator driven by a mix's data file.

A mix file (``traffic/<mix>.json``) is

    {"kind": "open"|"closed", "arrivals": {...}, "lengths": {...},
     "engine": {...}}

The schedule (when each request is due, and its prompt and output
lengths) is a pure function of ``(mix, seconds)``: every run of a cell,
whatever its seed, offers the same work at the same times.  In a closed
loop the send times follow the engine, but each client's sequence of
lengths is a pure function of the mix and the client's index.  The seed
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
  ``closed``   ``clients`` clients, each with at most one request in
               flight (:func:`clients`).  Client ``i`` sends its first
               request ``i * stagger_s / clients`` seconds into the
               window and each next one in the engine step after its
               previous one finished; a request is due when it is sent.
               Sending stops at the window's close.  A client's lengths
               are a cycle of ``cycle`` (default 64) strata pairs, in an
               order shuffled per client, repeated.

:func:`arrivals` gives the window one object per kind (``OpenArrivals``,
``ClosedArrivals``) with ``due(el)``, ``finished(client)`` and
``wake(el)``; a new kind is a new class in ``ARRIVALS``.

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
from typing import Dict, Iterator, List, Optional, Sequence, Tuple


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


@dataclasses.dataclass(frozen=True)
class Client:
    """One closed-loop client: when it first sends, and the (isl, osl)
    pairs it sends, in order, over and over."""
    start_s: float               # offset from the window's start
    cycle: Tuple[Tuple[int, int], ...]

    def request(self, k: int) -> Tuple[int, int]:
        """The lengths of the client's ``k``-th request (from 0)."""
        return self.cycle[k % len(self.cycle)]


def clients(mix: Dict) -> List[Client]:
    """The clients of a closed-loop mix."""
    a = mix["arrivals"]
    if a["kind"] != "closed":
        raise ValueError(f"arrival kind {a['kind']!r} is not closed")
    n, cycle = int(a["clients"]), int(a.get("cycle", 64))
    out = []
    for c in range(n):
        isl = _strata(mix["lengths"]["isl"], cycle)
        osl = _strata(mix["lengths"]["osl"], cycle)
        _rng(SCHEDULE, f"client{c}:isl").shuffle(isl)
        _rng(SCHEDULE, f"client{c}:osl").shuffle(osl)
        out.append(Client(start_s=c * float(a["stagger_s"]) / n,
                          cycle=tuple(zip(isl, osl))))
    return out


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


@dataclasses.dataclass(frozen=True)
class Send:
    """A request to send now."""
    due_s: float                 # offset from the window's start
    isl: int
    osl: int
    key: int                     # which prompt (:func:`prompt`'s ``idx``)
    client: Optional[int] = None


class OpenArrivals:
    """An open loop: each request is due at its time in the schedule."""

    def __init__(self, mix: Dict, seconds: float):
        self.plan = open_loop(mix, seconds)
        self.next = 0

    def due(self, el: float) -> Iterator[Send]:
        """The requests due by ``el`` seconds into the window."""
        while self.next < len(self.plan) and self.plan[self.next].due_s <= el:
            a = self.plan[self.next]
            yield Send(a.due_s, a.isl, a.osl, key=self.next)
            self.next += 1

    def finished(self, client: Optional[int]) -> None:
        """A request has finished; an open loop sends regardless."""

    def wake(self, el: float) -> Optional[float]:
        """When, with nothing in flight, the next request is due; None
        when none will be."""
        return self.plan[self.next].due_s if self.next < len(self.plan) \
            else None


class ClosedArrivals:
    """A closed loop: each client sends once it has started and has no
    request in flight, until the window closes."""

    def __init__(self, mix: Dict, seconds: float):
        self.clients = clients(mix)
        self.seconds = seconds
        self.idle = set(range(len(self.clients)))   # none in flight
        self.sent = [0] * len(self.clients)         # requests each sent

    def due(self, el: float) -> Iterator[Send]:
        if el >= self.seconds:
            return
        n = len(self.clients)
        for c in sorted(self.idle):
            if self.clients[c].start_s <= el:
                k = self.sent[c]
                self.sent[c] += 1
                self.idle.discard(c)
                # a client's k-th prompt, whatever order the clients
                # happen to send in
                yield Send(el, *self.clients[c].request(k), key=c + n * k,
                           client=c)

    def finished(self, client: Optional[int]) -> None:
        self.idle.add(client)

    def wake(self, el: float) -> Optional[float]:
        starts = [self.clients[c].start_s for c in self.idle
                  if self.clients[c].start_s > el]
        return min(starts) if starts and el < self.seconds else None


ARRIVALS = {"poisson": OpenArrivals, "closed": ClosedArrivals}


def arrivals(mix: Dict, seconds: float):
    """The mix's arrival process over a window of ``seconds``: ``due(el)``
    yields what to send ``el`` seconds into the window, ``finished`` is
    told each request's client as it finishes, and ``wake(el)`` says when
    to look again while nothing is in flight."""
    kind = mix["arrivals"]["kind"]
    if kind not in ARRIVALS:
        raise ValueError(f"unknown arrival kind {kind!r}")
    return ARRIVALS[kind](mix, seconds)


def prompt(seed: int, idx: int, isl: int, vocab: int) -> List[int]:
    """Request ``idx``'s prompt: token ids uniform over the vocabulary."""
    import numpy as np
    rng = np.random.default_rng([int(seed) & 0xFFFFFFFF, int(seed) >> 32,
                                 int(idx)])
    return rng.integers(0, vocab, int(isl)).tolist()

