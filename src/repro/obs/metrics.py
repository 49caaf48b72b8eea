"""Process-local metrics registry: counters, gauges, histograms.

The hot paths (``TaskRunner``, ``PerfDatabase``, the simulators) report
into whatever registry is installed via :func:`enable_metrics`; with no
registry installed (the default) every instrumentation site is a single
``get_metrics() is None`` check, so pricing-path numerics and CLI output
bytes are untouched.

Metric identity is ``(name, sorted labels)``, Prometheus-style:

    m = enable_metrics()
    m.inc("repro_db_ops_total", 128, family="gemm", path="grid",
          mode="batched")
    m.to_dict()        # JSON-able snapshot, deterministically keyed
    m.to_prometheus()  # text exposition format, hand-rolled (no deps)

Counters only ever increase, gauges hold the last value set, histograms
use fixed log-spaced buckets (seconds-scale by default) and expose
``_bucket``/``_sum``/``_count`` in the Prometheus rendering.  All
exports sort by (name, labels) so two runs with identical workloads
serialize byte-identically.
"""
from __future__ import annotations

import math
from typing import Dict, List, Optional, Sequence, Tuple

__all__ = [
    "DEFAULT_BUCKETS", "LATENCY_MS_BUCKETS", "MetricsRegistry",
    "disable_metrics", "enable_metrics", "get_metrics",
    "histogram_quantile",
]

# log-spaced seconds: 1us .. 100s, the span of a kernel to a whole search
DEFAULT_BUCKETS: Tuple[float, ...] = (
    1e-6, 1e-5, 1e-4, 1e-3, 1e-2, 1e-1, 1.0, 10.0, 100.0)

#: Fixed log2-spaced milliseconds for request-latency histograms:
#: 0.25 ms .. ~35 min (0.25 * 2**i, i < 24).  One shared schema means
#: every replay's TTFT/TPOT/queue-wait/e2e distribution is directly
#: comparable (and diffable) bucket-for-bucket.
LATENCY_MS_BUCKETS: Tuple[float, ...] = tuple(
    0.25 * 2.0 ** i for i in range(24))

_LabelKey = Tuple[Tuple[str, str], ...]


def _labels_key(labels: Dict[str, object]) -> _LabelKey:
    return tuple(sorted((k, str(v)) for k, v in labels.items()))


def _flat_name(name: str, key: _LabelKey) -> str:
    if not key:
        return name
    inner = ",".join(f"{k}={v}" for k, v in key)
    return f"{name}{{{inner}}}"


def _escape_label_value(v: str) -> str:
    """Prometheus text exposition format: label values escape backslash,
    double-quote, and line-feed (in that order — backslash first, or the
    other escapes would be double-escaped)."""
    return v.replace("\\", "\\\\").replace('"', '\\"').replace("\n", "\\n")


def _prom_labels(key: _LabelKey, extra: Tuple[Tuple[str, str], ...] = ()
                 ) -> str:
    pairs = key + extra
    if not pairs:
        return ""
    inner = ",".join(f'{k}="{_escape_label_value(v)}"' for k, v in pairs)
    return f"{{{inner}}}"


def histogram_quantile(buckets: Sequence[float], counts: Sequence[int],
                       p: float) -> Optional[float]:
    """Estimate the p-quantile (p in [0, 1]) of a bucketed histogram.

    ``counts`` has ``len(buckets) + 1`` entries (the last is the +Inf
    overflow).  The rank is ``p * (count - 1)``, the convention of the
    exact :func:`repro.serving.sim.percentile`: the samples at
    ``floor(rank)`` and ``ceil(rank)`` are each placed inside their own
    bucket (the j-th of a bucket's c samples at ``(j + 0.5) / c`` of its
    width), and the estimate interpolates between them by the rank's
    fraction.  So it is monotone in ``p`` and lands within one bucket of
    the exact sample percentile.  The first bucket interpolates from 0,
    the overflow bucket clamps to the last finite edge.  Empty histograms
    return None (never NaN).
    """
    if not 0.0 <= p <= 1.0:
        raise ValueError(f"quantile p must be in [0, 1], got {p}")
    if len(counts) != len(buckets) + 1:
        raise ValueError(f"expected {len(buckets) + 1} counts "
                         f"(+Inf overflow slot), got {len(counts)}")
    total = sum(counts)
    if total == 0:
        return None

    def sample(k: int) -> float:
        cum = 0
        for i, c in enumerate(counts):
            if cum + c > k:
                if i >= len(buckets):      # overflow: clamp, no far edge
                    return float(buckets[-1])
                lo = float(buckets[i - 1]) if i > 0 else 0.0
                hi = float(buckets[i])
                return lo + (hi - lo) * ((k - cum + 0.5) / c)
            cum += c
        return float(buckets[-1])

    rank = p * (total - 1)
    below = math.floor(rank)
    a = sample(below)
    frac = rank - below
    return a if frac == 0 else a + (sample(below + 1) - a) * frac


def _fmt(v: float) -> str:
    if v != v:                       # NaN never serializes silently
        return "NaN"
    if v == int(v) and abs(v) < 1e15:
        return str(int(v))
    return repr(v)


class MetricsRegistry:
    """Counters / gauges / histograms keyed by (name, labels)."""

    def __init__(self, buckets: Tuple[float, ...] = DEFAULT_BUCKETS):
        if not buckets or any(b <= a for a, b in zip(buckets, buckets[1:])):
            raise ValueError("histogram buckets must be strictly increasing")
        self.buckets = tuple(float(b) for b in buckets)
        self._counters: Dict[Tuple[str, _LabelKey], float] = {}
        self._gauges: Dict[Tuple[str, _LabelKey], float] = {}
        # histogram value: [[bucket counts..., +Inf count], sum, count,
        # bucket schema] — the schema is pinned per metric key at first
        # observation (registry default unless ``observe(buckets=...)``)
        self._hists: Dict[Tuple[str, _LabelKey], List] = {}

    # -- write side ------------------------------------------------------
    def inc(self, name: str, value: float = 1.0, **labels) -> None:
        if value < 0:
            raise ValueError(f"counter {name!r} cannot decrease")
        k = (name, _labels_key(labels))
        self._counters[k] = self._counters.get(k, 0.0) + value

    def set_gauge(self, name: str, value: float, **labels) -> None:
        self._gauges[(name, _labels_key(labels))] = float(value)

    def observe(self, name: str, value: float,
                buckets: Optional[Sequence[float]] = None,
                **labels) -> None:
        k = (name, _labels_key(labels))
        h = self._hists.get(k)
        if h is None:
            schema = (self.buckets if buckets is None
                      else tuple(float(b) for b in buckets))
            if not schema or any(b <= a
                                 for a, b in zip(schema, schema[1:])):
                raise ValueError(
                    "histogram buckets must be strictly increasing")
            h = [[0] * (len(schema) + 1), 0.0, 0, schema]
            self._hists[k] = h
        elif buckets is not None and tuple(float(b) for b in buckets) \
                != h[3]:
            raise ValueError(
                f"histogram {name!r} already pinned to a different "
                f"bucket schema")
        v = float(value)
        for i, le in enumerate(h[3]):
            if v <= le:
                h[0][i] += 1
                break
        else:
            h[0][-1] += 1
        h[1] += v
        h[2] += 1

    def reset(self) -> None:
        self._counters.clear()
        self._gauges.clear()
        self._hists.clear()

    # -- read side -------------------------------------------------------
    def counter_value(self, name: str, **labels) -> float:
        return self._counters.get((name, _labels_key(labels)), 0.0)

    def counter_total(self, name: str) -> float:
        """Sum of a counter across every label combination."""
        return sum(v for (n, _), v in self._counters.items() if n == name)

    def quantile(self, name: str, p: float, **labels) -> Optional[float]:
        """Estimate the p-quantile of a recorded histogram via
        :func:`histogram_quantile`; None when the histogram does not
        exist or holds no observations."""
        h = self._hists.get((name, _labels_key(labels)))
        if h is None:
            return None
        return histogram_quantile(h[3], h[0], p)

    def to_dict(self) -> Dict:
        counters = {_flat_name(n, k): self._counters[(n, k)]
                    for n, k in sorted(self._counters)}
        gauges = {_flat_name(n, k): self._gauges[(n, k)]
                  for n, k in sorted(self._gauges)}
        hists = {}
        for n, k in sorted(self._hists):
            cum, total, count, schema = self._hists[(n, k)]
            hists[_flat_name(n, k)] = {
                "buckets": list(schema), "counts": list(cum),
                "sum": total, "count": count}
        return {"counters": counters, "gauges": gauges,
                "histograms": hists}

    def to_prometheus(self) -> str:
        """Prometheus text exposition format (version 0.0.4)."""
        lines: List[str] = []
        seen_type: Dict[str, str] = {}

        def typed(name: str, kind: str):
            if seen_type.get(name) is None:
                lines.append(f"# TYPE {name} {kind}")
                seen_type[name] = kind

        for n, k in sorted(self._counters):
            typed(n, "counter")
            lines.append(f"{n}{_prom_labels(k)} "
                         f"{_fmt(self._counters[(n, k)])}")
        for n, k in sorted(self._gauges):
            typed(n, "gauge")
            lines.append(f"{n}{_prom_labels(k)} {_fmt(self._gauges[(n, k)])}")
        for n, k in sorted(self._hists):
            typed(n, "histogram")
            per_bucket, total, count, schema = self._hists[(n, k)]
            cum = 0
            for le, c in zip(schema, per_bucket[:-1]):
                cum += c
                lines.append(f"{n}_bucket{_prom_labels(k, (('le', _fmt(le)),))}"
                             f" {cum}")
            cum += per_bucket[-1]
            lines.append(f"{n}_bucket{_prom_labels(k, (('le', '+Inf'),))}"
                         f" {cum}")
            lines.append(f"{n}_sum{_prom_labels(k)} {_fmt(total)}")
            lines.append(f"{n}_count{_prom_labels(k)} {count}")
        return "\n".join(lines) + ("\n" if lines else "")

    def finite(self) -> bool:
        """Every exported value is finite (CI sanity probe)."""
        vals = list(self._counters.values()) + list(self._gauges.values())
        for _, total, _, _ in self._hists.values():
            vals.append(total)
        return all(math.isfinite(v) for v in vals)


# -- process-local installation ---------------------------------------------

_REGISTRY: Optional[MetricsRegistry] = None


def get_metrics() -> Optional[MetricsRegistry]:
    """The installed registry, or None when metrics are disabled."""
    return _REGISTRY


def enable_metrics(registry: Optional[MetricsRegistry] = None
                   ) -> MetricsRegistry:
    """Install (and return) a process-local registry."""
    global _REGISTRY
    _REGISTRY = registry if registry is not None else MetricsRegistry()
    return _REGISTRY


def disable_metrics() -> None:
    """Back to the zero-cost default: instrumentation sites become no-ops."""
    global _REGISTRY
    _REGISTRY = None
