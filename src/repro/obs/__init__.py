"""repro.obs — observability for the configurator: tracing spans, a
process-local metrics registry, and per-candidate cost attribution.

Three layers, all zero-cost until installed:

* :mod:`repro.obs.trace` — ``Tracer`` / ``span(name, **attrs)`` with a
  deterministic virtual clock plus wallclock timers, frozen into a
  versioned JSONL ``TraceArtifact`` (sha256 digest, lossless
  round-trip).  The default :data:`NULL_TRACER` makes every span a
  shared no-op.
* :mod:`repro.obs.metrics` — ``MetricsRegistry`` counters / gauges /
  histograms threaded through ``TaskRunner``, ``PerfDatabase`` and the
  simulators; exports JSON and Prometheus text format.
* :mod:`repro.obs.flight` — the request-level flight recorder:
  per-request lifecycle spans (arrival → queued → prefill → decode)
  and fixed log2-bucket latency histograms, emitted by every replay
  simulator and sampled through ``configure_flight_recorder``.
* :mod:`repro.obs.capture` — ``ObsCapture``, the ``--trace-out``/
  ``--metrics-out`` capture that the configurator's CLI and the serve
  entry point share.
* :mod:`repro.obs.diff` — telemetry diffing: counter/gauge deltas and
  per-histogram distribution shifts between two snapshots (surfaced as
  the ``obs diff`` CLI subcommand).
* :mod:`repro.obs.bench` — the performance-regression sentinel:
  versioned ``BenchArtifact`` suite runs (repeat timings, work-counter
  snapshots, phase breakdowns, environment fingerprints) and the
  two-tier hard/soft comparator behind ``obs bench run|compare|gate|
  trend``.  Loads lazily — it reaches into the pricing stack for the
  PerfDatabase fingerprint.
* :mod:`repro.obs.explain` — the operator-family latency waterfall per
  serving phase, and a two-candidate diff (surfaced as
  ``Configurator.explain`` and the ``explain`` CLI subcommand).

``trace``/``metrics``/``flight``/``diff``/``capture`` are import-light (stdlib
only); ``explain`` pulls in the pricing stack and loads lazily so the
core modules can import this package without a cycle.
"""
from repro.obs.capture import ObsCapture
from repro.obs.diff import diff_metrics, format_diff, load_metrics_snapshot
from repro.obs.flight import (FlightRecorderConfig,
                              configure_flight_recorder,
                              emit_engine_request_spans, emit_request_spans,
                              flight_config, latency_histograms,
                              request_latencies_ms)
from repro.obs.metrics import (LATENCY_MS_BUCKETS, MetricsRegistry,
                               disable_metrics, enable_metrics, get_metrics,
                               histogram_quantile)
from repro.obs.trace import (NULL_TRACER, SUPPORTED_TRACE_SCHEMA_VERSIONS,
                             TRACE_SCHEMA_VERSION, NullTracer, SpanRecord,
                             TraceArtifact, Tracer, disable_tracing,
                             enable_tracing, get_tracer, set_tracer)

_EXPLAIN_NAMES = ("CandidateExplanation", "Explanation", "ExplanationDiff",
                  "PhaseWaterfall", "diff_explanations", "explain_candidate",
                  "explain_spec")

_BENCH_NAMES = ("BenchArtifact", "BenchRecord", "BenchTiming",
                "EnvironmentMismatch", "GateResult", "compare_artifacts",
                "environment_fingerprint", "gate_artifacts", "soft_exceeds",
                "trend_summary")

__all__ = [
    "FlightRecorderConfig", "LATENCY_MS_BUCKETS", "MetricsRegistry",
    "NULL_TRACER", "NullTracer", "ObsCapture", "SpanRecord",
    "SUPPORTED_TRACE_SCHEMA_VERSIONS", "TRACE_SCHEMA_VERSION",
    "TraceArtifact", "Tracer", "configure_flight_recorder",
    "diff_metrics", "disable_metrics", "disable_tracing",
    "emit_engine_request_spans", "emit_request_spans", "enable_metrics",
    "enable_tracing",
    "flight_config", "format_diff", "get_metrics", "get_tracer",
    "histogram_quantile", "latency_histograms", "load_metrics_snapshot",
    "request_latencies_ms", "set_tracer", "telemetry_section",
    *_EXPLAIN_NAMES,
    *_BENCH_NAMES,
]


def telemetry_section(tracer=None, metrics=None) -> dict:
    """The schema-v6 ``telemetry`` report section: deterministic trace
    identity (digest + span count, no wall times) and a metrics snapshot."""
    section = {"trace": None, "metrics": None}
    if tracer is not None and tracer is not NULL_TRACER:
        art = tracer.artifact()
        section["trace"] = {"schema_version": TRACE_SCHEMA_VERSION,
                            "digest": art.digest(),
                            "n_spans": art.n_spans}
    if metrics is not None:
        section["metrics"] = metrics.to_dict()
    return section


def __getattr__(name):
    if name in _EXPLAIN_NAMES:
        from repro.obs import explain as _explain
        return getattr(_explain, name)
    if name in _BENCH_NAMES:
        from repro.obs import bench as _bench
        return getattr(_bench, name)
    raise AttributeError(f"module 'repro.obs' has no attribute {name!r}")
