"""Tests for repro.obs — tracing spans, trace artifacts, metrics registry."""
import json
import math

import pytest

from repro.obs.metrics import (DEFAULT_BUCKETS, MetricsRegistry,
                               disable_metrics, enable_metrics, get_metrics)
from repro.obs.trace import (NULL_TRACER, TRACE_SCHEMA_VERSION, NullTracer,
                             SpanRecord, TraceArtifact, Tracer,
                             disable_tracing, enable_tracing, get_tracer,
                             set_tracer)


@pytest.fixture(autouse=True)
def _clean_globals():
    """Every test starts and ends with tracing/metrics disabled."""
    disable_tracing()
    disable_metrics()
    yield
    disable_tracing()
    disable_metrics()


# ---------------------------------------------------------------------------
# Tracer / Span
# ---------------------------------------------------------------------------

def _fake_clock():
    """Deterministic wallclock: each call advances 1 ms."""
    t = [0.0]

    def clock():
        t[0] += 1e-3
        return t[0]
    return clock


def test_span_nesting_parent_depth_and_virtual_time():
    tr = Tracer(clock=_fake_clock())
    with tr.span("outer", kind="a") as outer:
        tr.virtual_time = 2.0
        with tr.span("inner") as inner:
            tr.virtual_time = 5.0
        with tr.span("inner") as inner2:
            tr.virtual_time = 7.5
    assert outer.seq == 0 and outer.parent is None and outer.depth == 0
    assert inner.seq == 1 and inner.parent == 0 and inner.depth == 1
    assert inner2.seq == 2 and inner2.parent == 0 and inner2.depth == 1
    assert outer.v_start == 0.0 and outer.v_end == 7.5
    assert inner.v_start == 2.0 and inner.v_end == 5.0
    assert inner2.v_start == 5.0 and inner2.v_end == 7.5
    assert not tr._stack


def test_span_set_attaches_attrs_mid_span():
    tr = Tracer()
    with tr.span("s", a=1) as sp:
        sp.set(b=2)
    assert sp.attrs == {"a": 1, "b": 2}


def test_wall_by_name_aggregates_wall_seconds():
    tr = Tracer(clock=_fake_clock())
    with tr.span("x"):
        pass
    with tr.span("x"):
        pass
    with tr.span("y"):
        pass
    wall = tr.wall_by_name()
    assert set(wall) == {"x", "y"}
    assert wall["x"] == pytest.approx(2e-3)
    assert wall["y"] == pytest.approx(1e-3)


def test_artifact_refuses_open_spans():
    tr = Tracer()
    sp = tr.span("open-me")
    sp.__enter__()
    with pytest.raises(ValueError, match="1 span\\(s\\) open"):
        tr.artifact()
    sp.__exit__(None, None, None)
    assert tr.artifact().n_spans == 1


def test_misnested_exit_is_tolerated():
    tr = Tracer()
    a = tr.span("a").__enter__()
    b = tr.span("b").__enter__()
    a.__exit__(None, None, None)        # out of order
    b.__exit__(None, None, None)
    assert not tr._stack
    assert tr.artifact().n_spans == 2


def test_wall_ms_excluded_by_default_included_on_request():
    tr = Tracer(clock=_fake_clock())
    with tr.span("s"):
        pass
    bare = tr.artifact()
    assert bare.spans[0].wall_ms is None
    assert "wall_ms" not in bare.spans[0].to_dict()
    assert bare.wall_by_name() == {}
    walled = tr.artifact(include_wall=True)
    assert walled.spans[0].wall_ms == pytest.approx(1.0)
    assert walled.wall_by_name()["s"] == pytest.approx(1e-3)


def test_artifact_bytes_deterministic_without_wall():
    def build():
        tr = Tracer(clock=_fake_clock())
        with tr.span("a", n=3):
            tr.virtual_time += 1.25
            with tr.span("b"):
                tr.virtual_time += 0.5
        return tr.artifact(meta={"run": "x"})
    one, two = build(), build()
    assert one.to_jsonl() == two.to_jsonl()
    assert one.digest() == two.digest()


# ---------------------------------------------------------------------------
# TraceArtifact serialization
# ---------------------------------------------------------------------------

def _sample_artifact():
    tr = Tracer()
    with tr.span("root", model="m"):
        tr.virtual_time = 1.0
        with tr.span("child", n=2):
            tr.virtual_time = 3.0
    return tr.artifact(meta={"command": "test"})


def test_jsonl_round_trip_lossless():
    art = _sample_artifact()
    back = TraceArtifact.from_jsonl(art.to_jsonl())
    assert back == art
    assert back.digest() == art.digest()
    assert back.meta == {"command": "test"}


def test_jsonl_header_shape():
    art = _sample_artifact()
    lines = art.to_jsonl().splitlines()
    header = json.loads(lines[0])
    assert header == {"type": "header",
                      "schema_version": TRACE_SCHEMA_VERSION,
                      "n_spans": 2, "meta": {"command": "test"}}
    # span lines are sorted-key JSON
    for ln in lines[1:]:
        assert ln == json.dumps(json.loads(ln), sort_keys=True)


def test_save_load_round_trip(tmp_path):
    art = _sample_artifact()
    path = str(tmp_path / "trace.jsonl")
    art.save(path)
    assert TraceArtifact.load(path) == art


def test_from_jsonl_rejects_empty():
    with pytest.raises(ValueError, match="empty trace artifact"):
        TraceArtifact.from_jsonl("\n  \n")


def test_from_jsonl_rejects_missing_header():
    span = json.dumps({"seq": 0, "name": "x", "parent": None, "depth": 0,
                       "v_start": 0.0, "v_end": 0.0, "attrs": {}})
    with pytest.raises(ValueError, match="must start with a header"):
        TraceArtifact.from_jsonl(span + "\n")


def test_from_jsonl_rejects_unknown_version():
    bad = json.dumps({"type": "header", "schema_version": 99,
                      "n_spans": 0, "meta": {}})
    with pytest.raises(ValueError, match="unsupported trace schema version"):
        TraceArtifact.from_jsonl(bad + "\n")


def test_from_jsonl_rejects_malformed_span():
    header = json.dumps({"type": "header",
                         "schema_version": TRACE_SCHEMA_VERSION,
                         "n_spans": 1, "meta": {}})
    with pytest.raises(ValueError, match="malformed trace span record"):
        TraceArtifact.from_jsonl(header + "\n" + json.dumps({"seq": 0}) + "\n")


def test_from_jsonl_rejects_span_count_mismatch():
    art = _sample_artifact()
    lines = art.to_jsonl().splitlines()
    with pytest.raises(ValueError, match="declares 2 spans, found 1"):
        TraceArtifact.from_jsonl("\n".join(lines[:2]) + "\n")


def test_artifact_validates_seq_order_and_parent():
    rec = SpanRecord(seq=1, name="x", parent=None, depth=0,
                     v_start=0.0, v_end=0.0, attrs={})
    with pytest.raises(ValueError, match="out of order"):
        TraceArtifact(spans=(rec,))
    root = SpanRecord(seq=0, name="r", parent=None, depth=0,
                      v_start=0.0, v_end=0.0, attrs={})
    fwd = SpanRecord(seq=1, name="c", parent=1, depth=1,
                     v_start=0.0, v_end=0.0, attrs={})
    with pytest.raises(ValueError, match="parent 1 not yet open"):
        TraceArtifact(spans=(root, fwd))


# ---------------------------------------------------------------------------
# null tracer + global install
# ---------------------------------------------------------------------------

def test_null_tracer_is_default_and_shares_one_span():
    assert get_tracer() is NULL_TRACER
    sp1 = NULL_TRACER.span("anything", n=1)
    sp2 = NULL_TRACER.span("other")
    assert sp1 is sp2                       # no allocation per call
    with sp1 as s:
        assert s.set(x=1) is s
    # instrumented code reads v_start off the null span without branching
    assert sp1.v_start == 0.0 and sp1.v_end == 0.0
    NULL_TRACER.virtual_time = 4.0          # writable, ignored
    assert NULL_TRACER.wall_by_name() == {}
    NULL_TRACER.virtual_time = 0.0


def test_enable_disable_tracing_round_trip():
    t = enable_tracing()
    assert isinstance(t, Tracer) and get_tracer() is t
    disable_tracing()
    assert get_tracer() is NULL_TRACER
    mine = Tracer()
    assert enable_tracing(mine) is mine and get_tracer() is mine
    set_tracer(None)
    assert get_tracer() is NULL_TRACER
    assert isinstance(NULL_TRACER, NullTracer)


# ---------------------------------------------------------------------------
# MetricsRegistry
# ---------------------------------------------------------------------------

def test_counter_inc_and_lookup():
    reg = MetricsRegistry()
    reg.inc("ops_total")
    reg.inc("ops_total", 2.5)
    assert reg.counter_value("ops_total") == pytest.approx(3.5)
    assert reg.counter_value("missing") == 0.0


def test_counter_labels_and_totals():
    reg = MetricsRegistry()
    reg.inc("ops_total", 2, family="gemm", path="grid")
    reg.inc("ops_total", 3, family="attn_decode", path="grid")
    reg.inc("ops_total", 5, path="grid", family="gemm")   # order-insensitive
    assert reg.counter_value("ops_total", family="gemm", path="grid") == 7
    assert reg.counter_total("ops_total") == 10
    flat = reg.to_dict()["counters"]
    assert flat["ops_total{family=gemm,path=grid}"] == 7
    assert flat["ops_total{family=attn_decode,path=grid}"] == 3


def test_counter_rejects_negative():
    reg = MetricsRegistry()
    with pytest.raises(ValueError, match="cannot decrease"):
        reg.inc("ops_total", -1)


def test_gauges_overwrite():
    reg = MetricsRegistry()
    reg.set_gauge("replicas", 2)
    reg.set_gauge("replicas", 4)
    assert reg.to_dict()["gauges"]["replicas"] == 4


def test_histogram_buckets_sum_count_and_overflow():
    reg = MetricsRegistry(buckets=(0.1, 1.0, 10.0))
    for v in (0.05, 0.5, 5.0, 50.0):
        reg.observe("latency_s", v)
    h = reg.to_dict()["histograms"]["latency_s"]
    assert h["buckets"] == [0.1, 1.0, 10.0]
    assert h["counts"] == [1, 1, 1, 1]      # last slot is +Inf overflow
    assert h["count"] == 4
    assert h["sum"] == pytest.approx(55.55)


def test_default_buckets_strictly_increasing():
    assert list(DEFAULT_BUCKETS) == sorted(DEFAULT_BUCKETS)
    assert len(set(DEFAULT_BUCKETS)) == len(DEFAULT_BUCKETS)
    with pytest.raises(ValueError):
        MetricsRegistry(buckets=(1.0, 1.0))


def test_to_dict_deterministic_and_sorted():
    def build():
        reg = MetricsRegistry()
        reg.inc("b_total", 1, z=1, a=2)
        reg.inc("a_total", 2)
        reg.set_gauge("g", 3.0)
        reg.observe("h", 0.2)
        return reg
    one, two = build().to_dict(), build().to_dict()
    assert json.dumps(one, sort_keys=True) == json.dumps(two, sort_keys=True)
    assert list(one["counters"]) == sorted(one["counters"])


def test_to_prometheus_format():
    reg = MetricsRegistry(buckets=(1.0, 2.0))
    reg.inc("ops_total", 3, path="grid")
    reg.set_gauge("replicas", 2)
    reg.observe("lat_s", 0.5)
    reg.observe("lat_s", 1.5)
    text = reg.to_prometheus()
    assert "# TYPE ops_total counter" in text
    assert 'ops_total{path="grid"} 3' in text
    assert "# TYPE replicas gauge" in text
    assert "replicas 2" in text
    assert "# TYPE lat_s histogram" in text
    # cumulative buckets
    assert 'lat_s_bucket{le="1"} 1' in text
    assert 'lat_s_bucket{le="2"} 2' in text
    assert 'lat_s_bucket{le="+Inf"} 2' in text
    assert "lat_s_sum 2" in text
    assert "lat_s_count 2" in text
    assert text.endswith("\n")


def test_finite_and_reset():
    reg = MetricsRegistry()
    reg.inc("ops_total", 1)
    reg.set_gauge("g", 2.0)
    reg.observe("h", 0.1)
    assert reg.finite()
    reg.set_gauge("bad", math.inf)
    assert not reg.finite()
    reg.reset()
    d = reg.to_dict()
    assert d == {"counters": {}, "gauges": {}, "histograms": {}}


def test_enable_disable_metrics_round_trip():
    assert get_metrics() is None
    reg = enable_metrics()
    assert isinstance(reg, MetricsRegistry) and get_metrics() is reg
    disable_metrics()
    assert get_metrics() is None
    mine = MetricsRegistry()
    assert enable_metrics(mine) is mine and get_metrics() is mine


# ---------------------------------------------------------------------------
# wall timebase, profiler bridge, and the shared CLI capture
# ---------------------------------------------------------------------------

def test_wall_timebase_spans_read_the_tracers_clock():
    tr = Tracer(clock=_fake_clock(), timebase="wall")   # origin at 1 ms
    with tr.span("outer"):
        with tr.span("inner"):
            pass
    outer, inner = tr.artifact().spans
    assert (outer.v_start, inner.v_start) == pytest.approx((1e-3, 2e-3))
    assert (inner.v_end, outer.v_end) == pytest.approx((3e-3, 4e-3))
    tr.virtual_time = 99.0                  # ignored on the wall clock
    with tr.span("late") as sp:
        pass
    assert sp.v_start == pytest.approx(5e-3)


def test_unknown_timebase_is_refused():
    with pytest.raises(ValueError, match="timebase"):
        Tracer(timebase="device")


def test_profiler_bridge_enters_one_annotation_per_span(monkeypatch):
    import jax
    log = []

    class Note:
        def __init__(self, name):
            self.name = name

        def __enter__(self):
            log.append(("enter", self.name))

        def __exit__(self, *exc):
            log.append(("exit", self.name))
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Note)
    tr = Tracer(timebase="wall", profiler=True)
    with tr.span("engine.step"):
        with tr.span("engine.decode", active=3):
            pass
    assert log == [("enter", "engine.step"), ("enter", "engine.decode"),
                   ("exit", "engine.decode"), ("exit", "engine.step")]
    assert [s.name for s in tr.artifact().spans] == ["engine.step",
                                                     "engine.decode"]
    plain = Tracer()
    with plain.span("x"):
        pass
    assert len(log) == 4                    # no bridge unless asked


def test_obs_capture_lives_in_repro_obs(tmp_path):
    import argparse
    from repro.core import cli
    from repro.obs import ObsCapture
    assert not hasattr(cli, "ObsCapture") or cli.ObsCapture is ObsCapture
    assert not hasattr(cli, "_ObsCapture")
    args = argparse.Namespace(trace_out="", metrics_out="")
    tr = Tracer(timebase="wall")
    cap = ObsCapture(args, command="serve", tracer=tr)
    assert get_tracer() is tr and get_metrics() is None
    with get_tracer().span("engine.step"):
        pass
    cap.finish()
    assert get_tracer() is NULL_TRACER and not list(tmp_path.iterdir())
    out = tmp_path / "t.jsonl"
    cap = ObsCapture(argparse.Namespace(trace_out=str(out), metrics_out=""),
                     command="serve", tracer=Tracer(timebase="wall"))
    with get_tracer().span("engine.step"):
        pass
    cap.finish()
    art = TraceArtifact.load(str(out))
    assert art.meta == {"command": "serve", "model": None}
    assert [s.name for s in art.spans] == ["engine.step"]
