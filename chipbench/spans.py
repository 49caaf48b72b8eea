"""Idle device time put down to the serving engine's phases.

Under a ``repro.obs`` tracer built with ``profiler=True`` the engine's
spans (``engine.step`` and the phases inside it, see
``repro/serving/engine.py``) land on the profiler trace's ``/host:CPU``
plane beside the benchmark's ``bench.*`` annotations.  :func:`reduce`
takes the same window as ``trace.reduce`` (the extent of the ``bench.*``
annotations) and the same busy intervals (the union of ``/device:TPU:0``'s
ops in it), and splits each idle gap across the innermost ``engine.*``
spans it overlaps, by the overlap: a gap between two steps runs through
``engine.commit``, the harness's bookkeeping, ``engine.schedule`` and
``engine.decode.inputs``, and each gets its part.  Idle time inside no
engine span is charged to ``OUTSIDE``.

So ``idle`` adds up to the window less its busy time, and

    idle_share = readback + host + outside

where ``readback`` is the idle time under ``engine.prefill.sample`` and
``engine.decode.sample`` (the argmax and its copy to the host), ``host``
the idle time under any other engine span, and ``outside`` the harness's
own time between steps.
"""
from __future__ import annotations

import dataclasses
from collections import defaultdict
from typing import Dict, List, Tuple

from chipbench import trace

ENGINE_PREFIX = "engine."
READBACK = ("engine.prefill.sample", "engine.decode.sample")
OUTSIDE = "outside"


@dataclasses.dataclass
class Spans:
    window_s: float
    busy_s: float                    # /device:TPU:0 only
    idle: Dict[str, float]           # innermost engine span -> idle s
    host: Dict[str, Tuple[int, float]]  # engine span -> (count, host s)

    def share(self, which: str) -> float:
        """Percent of the window idle under the read-back spans
        (``readback``), the other engine spans (``host``) or none
        (``outside``)."""
        if which == "readback":
            s = sum(v for k, v in self.idle.items() if k in READBACK)
        elif which == "host":
            s = sum(v for k, v in self.idle.items()
                    if k not in READBACK and k != OUTSIDE)
        elif which == OUTSIDE:
            s = self.idle.get(OUTSIDE, 0.0)
        else:
            raise ValueError(f"no idle share {which!r}")
        return 100.0 * s / self.window_s if self.window_s > 0 else 0.0


def innermost(events: List[Tuple[int, int, str]]
              ) -> List[Tuple[int, int, str]]:
    """Nested ``(start, end, name)`` intervals cut into disjoint pieces,
    each named by the innermost interval covering it; time under none is
    left out.  An interval that outlasts its parent is cut at the
    parent's end."""
    out: List[Tuple[int, int, str]] = []
    stack: List[Tuple[int, str]] = []        # (end, name), innermost last
    t = None

    def close_until(x):
        nonlocal t
        while stack and stack[-1][0] <= x:
            end, name = stack.pop()
            if end > t:
                out.append((t, end, name))
                t = end

    for a, b, name in sorted(events, key=lambda e: (e[0], -e[1])):
        if t is not None:
            close_until(a)
            if stack and a > t:
                out.append((t, a, stack[-1][1]))
        t = a
        if stack:
            b = min(b, stack[-1][0])
        stack.append((b, name))
    if stack:
        close_until(stack[0][0])
    return out


def reduce(profile) -> Spans:
    """Reduce a ``ProfileData`` of a run whose engine ran under a
    profiler-bridged tracer.  Raises ``trace.TraceError`` where the trace
    holds no ``engine.*`` span or no device op."""
    bench, engine, ops = [], [], []
    for plane in profile.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(trace.HOST_PREFIX):
                        bench.append((e.start_ns, e.start_ns + e.duration_ns))
                    elif e.name.startswith(ENGINE_PREFIX):
                        engine.append((e.start_ns, e.start_ns + e.duration_ns,
                                       e.name))
        elif plane.name == "/device:TPU:0":
            for line in plane.lines:
                if line.name == trace.OPS_LINE:
                    ops.extend((e.start_ns, e.start_ns + e.duration_ns)
                               for e in line.events)
    if not bench:
        raise trace.TraceError(f"no {trace.HOST_PREFIX}* annotation on a "
                               f"host plane")
    if not engine:
        raise trace.TraceError(f"no {ENGINE_PREFIX}* span on a host plane: "
                               f"was the engine traced with a "
                               f"profiler-bridged tracer?")
    if not ops:
        raise trace.TraceError(f"no {trace.OPS_LINE!r} events on "
                               f"/device:TPU:0")
    lo, hi = min(a for a, _ in bench), max(b for _, b in bench)
    busy = trace.union(trace._clip(ops, lo, hi))
    edges = [lo] + [x for iv in busy for x in iv] + [hi]
    gaps = [(a, b) for a, b in zip(edges[::2], edges[1::2]) if b > a]
    pieces = [(max(a, lo), min(b, hi), n) for a, b, n in innermost(engine)
              if b > lo and a < hi]
    idle: Dict[str, float] = defaultdict(float)
    j = 0
    for a, b in gaps:
        covered = 0
        while j < len(pieces) and pieces[j][1] <= a:
            j += 1
        k = j
        while k < len(pieces) and pieces[k][0] < b:
            pa, pb, name = pieces[k]
            overlap = min(b, pb) - max(a, pa)
            if overlap > 0:
                idle[name] += overlap * 1e-9
                covered += overlap
            k += 1
        idle[OUTSIDE] += (b - a - covered) * 1e-9
    host: Dict[str, List] = defaultdict(lambda: [0, 0.0])
    for a, b, name in engine:
        if b > lo and a < hi:
            host[name][0] += 1
            host[name][1] += (min(b, hi) - max(a, lo)) * 1e-9
    return Spans(window_s=(hi - lo) * 1e-9,
                 busy_s=sum(b - a for a, b in busy) * 1e-9,
                 idle=dict(idle),
                 host={k: (int(c), s) for k, (c, s) in host.items()})
