"""Date-free tracing spans and the versioned ``TraceArtifact``.

A :class:`Tracer` records nested spans opened with
``tracer.span(name, **attrs)``.  Each span carries two timebases:

* **virtual** — ``tracer.virtual_time``, the simulated-seconds clock the
  simulators already advance deterministically.  Spans snapshot it at
  enter/exit (``v_start``/``v_end``), so a seeded run serializes
  byte-identically every time.
* **wallclock** — ``wall_s``, measured with ``time.perf_counter``.
  Wall durations are for live introspection (benchmark phase breakdowns,
  ``Tracer.wall_by_name``) and stay **out** of the canonical artifact
  bytes unless explicitly requested, because they are the one
  non-deterministic thing a trace holds.

A tracer built with ``timebase="wall"`` snapshots its own clock instead,
as seconds since the tracer was made, so a span around real work (the
serving engine's steps) records when it ran and its Chrome export shows
the served timeline.  With ``profiler=True`` every span also enters a
``jax.profiler.TraceAnnotation`` of the same name, which lands on the
profiler trace's ``/host:CPU`` plane on the device trace's clock; jax is
imported only then, so this module stays import-light.

The default tracer is :data:`NULL_TRACER`: ``span()`` hands back a shared
no-op context manager, so instrumentation in the pricing hot paths costs
one attribute call when tracing is off and never perturbs results.

``TraceArtifact`` follows the house JSONL artifact style (see
``repro.autoscale.timeline.ClusterTimeline``): a header line with a
schema version, one ``json.dumps(..., sort_keys=True)`` record per span,
a 16-hex sha256 ``digest()``, and a strict ``from_jsonl`` that
round-trips losslessly.
"""
from __future__ import annotations

import dataclasses
import hashlib
import json
import time
from typing import Dict, List, Optional, Tuple

__all__ = [
    "NULL_TRACER", "SUPPORTED_TRACE_SCHEMA_VERSIONS",
    "TRACE_SCHEMA_VERSION", "NullTracer", "Span", "SpanRecord",
    "TraceArtifact", "Tracer", "disable_tracing", "enable_tracing",
    "get_tracer", "set_tracer",
]

TRACE_SCHEMA_VERSION = 1
SUPPORTED_TRACE_SCHEMA_VERSIONS = (1,)


# ---------------------------------------------------------------------------
# frozen artifact records
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class SpanRecord:
    """One completed span, as serialized into a :class:`TraceArtifact`."""
    seq: int                       # start order, 0-based, == artifact index
    name: str
    parent: Optional[int]          # seq of the enclosing span, None at root
    depth: int
    v_start: float                 # virtual-clock seconds at enter/exit
    v_end: float
    attrs: Dict                    # JSON-able, deterministic span payload
    wall_ms: Optional[float] = None  # only with include_wall=True

    def to_dict(self) -> Dict:
        d = {"seq": self.seq, "name": self.name, "parent": self.parent,
             "depth": self.depth, "v_start": self.v_start,
             "v_end": self.v_end, "attrs": dict(self.attrs)}
        if self.wall_ms is not None:
            d["wall_ms"] = self.wall_ms
        return d

    @classmethod
    def from_dict(cls, d: Dict) -> "SpanRecord":
        return cls(seq=d["seq"], name=d["name"], parent=d["parent"],
                   depth=d["depth"], v_start=d["v_start"],
                   v_end=d["v_end"], attrs=dict(d["attrs"]),
                   wall_ms=d.get("wall_ms"))


@dataclasses.dataclass(frozen=True)
class TraceArtifact:
    """Versioned, digestable JSONL serialization of one trace."""
    spans: Tuple[SpanRecord, ...]
    meta: Dict = dataclasses.field(default_factory=dict)

    def __post_init__(self):
        object.__setattr__(self, "spans", tuple(self.spans))
        object.__setattr__(self, "meta", dict(self.meta))
        for i, s in enumerate(self.spans):
            if s.seq != i:
                raise ValueError(
                    f"span seq {s.seq} out of order at position {i}")
            if s.parent is not None and not 0 <= s.parent < i:
                raise ValueError(
                    f"span {i} references parent {s.parent} not yet open")

    @property
    def n_spans(self) -> int:
        return len(self.spans)

    def wall_by_name(self) -> Dict[str, float]:
        """Total wall seconds per span name (empty without wall data)."""
        out: Dict[str, float] = {}
        for s in self.spans:
            if s.wall_ms is not None:
                out[s.name] = out.get(s.name, 0.0) + s.wall_ms / 1e3
        return out

    def to_jsonl(self) -> str:
        header = {"type": "header",
                  "schema_version": TRACE_SCHEMA_VERSION,
                  "n_spans": len(self.spans), "meta": self.meta}
        lines = [json.dumps(header, sort_keys=True)]
        lines.extend(json.dumps(s.to_dict(), sort_keys=True)
                     for s in self.spans)
        return "\n".join(lines) + "\n"

    @classmethod
    def from_jsonl(cls, text: str) -> "TraceArtifact":
        lines = [ln for ln in text.splitlines() if ln.strip()]
        if not lines:
            raise ValueError("empty trace artifact")
        header = json.loads(lines[0])
        if header.get("type") != "header":
            raise ValueError("trace artifact must start with a header record")
        version = header.get("schema_version")
        if version not in SUPPORTED_TRACE_SCHEMA_VERSIONS:
            raise ValueError(f"unsupported trace schema version {version!r}")
        spans = []
        for ln in lines[1:]:
            try:
                spans.append(SpanRecord.from_dict(json.loads(ln)))
            except (KeyError, TypeError) as e:
                raise ValueError(f"malformed trace span record: {e}") from e
        declared = header.get("n_spans")
        if declared is not None and declared != len(spans):
            raise ValueError(f"trace header declares {declared} spans, "
                             f"found {len(spans)}")
        return cls(spans=tuple(spans), meta=dict(header.get("meta") or {}))

    def digest(self) -> str:
        return hashlib.sha256(self.to_jsonl().encode()).hexdigest()[:16]

    # -- Chrome trace_event / Perfetto export ---------------------------
    def _lane(self, span: SpanRecord) -> str:
        """The thread lane a span renders on: the nearest ancestor
        (including itself) carrying a request id gets a per-request
        lane, a replica attribute gets a per-replica lane, everything
        else shares the component's main lane."""
        s: Optional[SpanRecord] = span
        while s is not None:
            if "rid" in s.attrs:
                return f"request {s.attrs['rid']}"
            if "replica" in s.attrs:
                return f"replica {s.attrs['replica']}"
            s = self.spans[s.parent] if s.parent is not None else None
        return "main"

    def _component(self, span: SpanRecord) -> str:
        """The process a span renders under: the first dot-segment of
        its root ancestor's name, so request spans nested inside
        ``serving.replay`` stay in the ``serving`` process group."""
        s = span
        while s.parent is not None:
            s = self.spans[s.parent]
        return s.name.split(".", 1)[0]

    def to_chrome_trace(self) -> Dict:
        """Map the trace to the Chrome ``trace_event`` JSON object
        format (opens directly in Perfetto / ``chrome://tracing``).

        Every span becomes one ``ph:"X"`` complete event on the virtual
        timebase (microsecond ``ts``/``dur``); integer pids number the
        component processes, integer tids the lanes inside them, and
        ``ph:"M"`` metadata events carry the human names.  Wall times
        never enter the export, so seeded runs serialize
        byte-identically.
        """
        pids: Dict[str, int] = {}
        tids: Dict[Tuple[int, str], int] = {}
        events = []
        for s in self.spans:
            comp = self._component(s)
            pid = pids.setdefault(comp, len(pids) + 1)
            lane = self._lane(s)
            tid = tids.setdefault((pid, lane),
                                  1 + sum(1 for p, _ in tids if p == pid))
            events.append({
                "name": s.name, "cat": comp, "ph": "X",
                "ts": s.v_start * 1e6,
                "dur": max(0.0, (s.v_end - s.v_start) * 1e6),
                "pid": pid, "tid": tid,
                "args": dict(s.attrs),
            })
        meta_events = [
            {"name": "process_name", "ph": "M", "pid": pid, "tid": 0,
             "args": {"name": comp}}
            for comp, pid in sorted(pids.items(), key=lambda kv: kv[1])
        ] + [
            {"name": "thread_name", "ph": "M", "pid": pid, "tid": tid,
             "args": {"name": lane}}
            for (pid, lane), tid in sorted(tids.items(),
                                           key=lambda kv: (kv[0][0], kv[1]))
        ]
        return {
            "traceEvents": meta_events + events,
            "displayTimeUnit": "ms",
            "otherData": {"trace_schema_version": TRACE_SCHEMA_VERSION,
                          "digest": self.digest(), "meta": dict(self.meta)},
        }

    def save(self, path: str) -> str:
        with open(path, "w") as f:
            f.write(self.to_jsonl())
        return path

    @classmethod
    def load(cls, path: str) -> "TraceArtifact":
        with open(path) as f:
            return cls.from_jsonl(f.read())


# ---------------------------------------------------------------------------
# live spans
# ---------------------------------------------------------------------------

class Span:
    """A live span; also its own context manager."""
    __slots__ = ("name", "seq", "parent", "depth", "attrs",
                 "v_start", "v_end", "wall_s", "_tracer", "_t0", "_note")

    def __init__(self, tracer: "Tracer", name: str, attrs: Dict):
        self.name = name
        self.attrs = attrs
        self._tracer = tracer
        self.seq = -1
        self.parent: Optional[int] = None
        self.depth = 0
        self.v_start = 0.0
        self.v_end = 0.0
        self.wall_s = 0.0
        self._t0 = 0.0
        self._note = None

    def set(self, **attrs) -> "Span":
        """Attach attributes mid-span (e.g. counts known only at exit)."""
        self.attrs.update(attrs)
        return self

    def __enter__(self) -> "Span":
        t = self._tracer
        self.seq = len(t.spans)
        self.parent = t._stack[-1].seq if t._stack else None
        self.depth = len(t._stack)
        t.spans.append(self)
        t._stack.append(self)
        if t._annotation is not None:
            self._note = t._annotation(self.name)
            self._note.__enter__()
        self._t0 = t._clock()
        self.v_start = (self._t0 - t._origin if t._origin is not None
                        else t.virtual_time)
        return self

    def __exit__(self, exc_type, exc, tb):
        t = self._tracer
        end = t._clock()
        self.wall_s = end - self._t0
        self.v_end = (end - t._origin if t._origin is not None
                      else t.virtual_time)
        if self._note is not None:
            self._note.__exit__(exc_type, exc, tb)
            self._note = None
        if t._stack and t._stack[-1] is self:
            t._stack.pop()
        else:                       # tolerate mis-nested exits
            t._stack = [s for s in t._stack if s is not self]
        return False

    def record(self, include_wall: bool = False) -> SpanRecord:
        return SpanRecord(
            seq=self.seq, name=self.name, parent=self.parent,
            depth=self.depth, v_start=self.v_start, v_end=self.v_end,
            attrs=dict(self.attrs),
            wall_ms=self.wall_s * 1e3 if include_wall else None)


class Tracer:
    """Collects nested spans against a virtual + wallclock timebase.

    ``timebase="wall"`` makes a span's ``v_start``/``v_end`` read the
    tracer's clock (seconds since the tracer was made) instead of
    ``virtual_time``; ``profiler=True`` bridges every span to a
    ``jax.profiler.TraceAnnotation`` of the same name."""

    #: real tracers record spans; the flight recorder checks this one
    #: attribute before materializing per-request span payloads, so
    #: replays under :data:`NULL_TRACER` build nothing at all
    records_spans = True

    def __init__(self, clock=time.perf_counter, timebase: str = "virtual",
                 profiler: bool = False):
        if timebase not in ("virtual", "wall"):
            raise ValueError(f"timebase must be 'virtual' or 'wall', "
                             f"got {timebase!r}")
        self._clock = clock
        self.spans: List[Span] = []
        self._stack: List[Span] = []
        self.virtual_time = 0.0     # simulators advance this (sim seconds)
        self._origin = clock() if timebase == "wall" else None
        self._annotation = None
        if profiler:
            import jax
            self._annotation = jax.profiler.TraceAnnotation

    def span(self, name: str, **attrs) -> Span:
        return Span(self, name, attrs)

    def wall_by_name(self) -> Dict[str, float]:
        """Total wall seconds per span name, for live phase breakdowns."""
        out: Dict[str, float] = {}
        for s in self.spans:
            out[s.name] = out.get(s.name, 0.0) + s.wall_s
        return out

    def artifact(self, meta: Optional[Dict] = None,
                 include_wall: bool = False) -> TraceArtifact:
        """Freeze collected spans; deterministic bytes unless
        ``include_wall=True`` opts into wallclock durations."""
        if self._stack:
            raise ValueError(
                f"cannot serialize with {len(self._stack)} span(s) open "
                f"(innermost: {self._stack[-1].name!r})")
        return TraceArtifact(
            spans=tuple(s.record(include_wall) for s in self.spans),
            meta=dict(meta or {}))


class _NullSpan:
    """Shared no-op span: enter/exit/set do nothing, allocate nothing.
    ``v_start``/``v_end`` read 0.0 so instrumented code can compute
    against them (``tracer.virtual_time = sp.v_start + dt``) without
    branching on whether tracing is enabled."""
    __slots__ = ()
    v_start = 0.0
    v_end = 0.0

    def __enter__(self):
        return self

    def __exit__(self, exc_type, exc, tb):
        return False

    def set(self, **attrs):
        return self


_NULL_SPAN = _NullSpan()


class NullTracer:
    """Zero-cost default: every span() returns the shared no-op span."""
    __slots__ = ("virtual_time",)

    records_spans = False

    def __init__(self):
        self.virtual_time = 0.0

    def span(self, name: str, **attrs) -> _NullSpan:
        return _NULL_SPAN

    def wall_by_name(self) -> Dict[str, float]:
        return {}


NULL_TRACER = NullTracer()

_TRACER = NULL_TRACER


def get_tracer():
    """The installed tracer (the shared :class:`NullTracer` by default)."""
    return _TRACER


def set_tracer(tracer) -> None:
    global _TRACER
    _TRACER = tracer if tracer is not None else NULL_TRACER


def enable_tracing(tracer: Optional[Tracer] = None) -> Tracer:
    """Install (and return) a real tracer."""
    t = tracer if tracer is not None else Tracer()
    set_tracer(t)
    return t


def disable_tracing() -> None:
    set_tracer(None)
