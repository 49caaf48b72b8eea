"""The serving engine's spans, counters and program names, at the tiny
size of the benchmark's CPU cell (``tests/chipbench/_tiny.py``)."""
import glob
import os
import sys
import time

import jax
import numpy as np
import pytest

sys.path.insert(0, os.path.abspath(os.path.join(os.path.dirname(__file__),
                                                "..")))
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "chipbench"))

import _tiny  # noqa: E402
from chipbench.serving import model_config  # noqa: E402
from repro import models  # noqa: E402
from repro.obs import trace as obs_trace  # noqa: E402
from repro.obs.metrics import (  # noqa: E402
    LATENCY_MS_BUCKETS, MetricsRegistry, disable_metrics, enable_metrics)
from repro.obs.trace import (  # noqa: E402
    Tracer, disable_tracing, enable_tracing)
from repro.serving.engine import Engine, EngineConfig  # noqa: E402
from repro.serving.request import Request  # noqa: E402

CFG = model_config(_tiny.DENSE)
PARAMS = models.init_params(CFG, jax.random.PRNGKey(0))


@pytest.fixture(autouse=True)
def _clean_globals():
    disable_tracing()
    disable_metrics()
    yield
    disable_tracing()
    disable_metrics()


def _engine(slots=2):
    return Engine(CFG, PARAMS, EngineConfig(max_batch=slots, max_seq=48))


def _request(rid, isl, osl=4):
    rng = np.random.default_rng(rid)
    return Request(rid=rid, isl=isl, osl=osl, arrival=time.perf_counter(),
                   prompt=rng.integers(0, CFG.vocab_size, isl).tolist())


def _tree(art):
    """(name, parent's name, attrs) of each span, in start order."""
    return [(s.name, art.spans[s.parent].name if s.parent is not None
             else None, s.attrs) for s in art.spans]


def test_one_step_makes_the_span_tree():
    """A step that prefills one prompt and decodes another opens exactly
    the documented tree, with its attrs, on the wall clock."""
    eng = _engine()
    eng.add_request(_request(0, 16))
    eng.step()                                   # prefill rid 0
    eng.add_request(_request(1, 32))
    tracer = enable_tracing(Tracer(timebase="wall"))
    eng.step()                                   # prefill rid 1, decode 0
    disable_tracing()
    art = tracer.artifact()
    assert _tree(art) == [
        ("engine.step", None,
         {"prefills": 1, "decodes": 1, "new_programs": 2}),
        ("engine.schedule", "engine.step", {}),
        ("engine.prefill", "engine.step", {"rid": 1, "isl": 32, "slot": 1}),
        ("engine.prefill.run", "engine.prefill", {}),
        ("engine.prefill.write", "engine.prefill", {}),
        ("engine.prefill.sample", "engine.prefill", {}),
        ("engine.decode", "engine.step", {"active": 1}),
        ("engine.decode.inputs", "engine.decode", {}),
        ("engine.decode.run", "engine.decode", {}),
        ("engine.decode.sample", "engine.decode", {}),
        ("engine.commit", "engine.step", {}),
    ]
    for s in art.spans:
        assert 0 < s.v_start <= s.v_end
        if s.parent is not None:
            p = art.spans[s.parent]
            assert p.v_start <= s.v_start and s.v_end <= p.v_end


def test_null_tracer_opens_nothing_and_tokens_match(monkeypatch):
    """Under the defaults a step opens no span, makes no profiler
    annotation and touches no registry; the served tokens are those of a
    run traced with the profiler bridge and counted."""
    def served(engine):
        for rid, isl in enumerate((16, 32, 16)):
            engine.add_request(_request(rid, isl, osl=5))
        return {r.rid: r.out_tokens for r in engine.run_until_drained()}

    traced_eng = _engine()
    enable_tracing(Tracer(timebase="wall", profiler=True))
    enable_metrics()
    traced = served(traced_eng)
    disable_tracing()
    disable_metrics()

    def refuse(*a, **k):
        raise AssertionError("touched under the defaults")
    monkeypatch.setattr(obs_trace.Span, "__init__", refuse)
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", refuse)
    for name in ("inc", "observe", "set_gauge"):
        monkeypatch.setattr(MetricsRegistry, name, refuse)
    assert served(_engine()) == traced
    assert len(traced) == 3


def test_counters_match_the_step_plans():
    eng = _engine(slots=2)
    plans = []
    real_plan = eng.sched.plan

    def plan(now):
        p = real_plan(now)
        plans.append(p)
        return p
    eng.sched.plan = plan
    m = enable_metrics()
    tracer = enable_tracing(Tracer(timebase="wall"))
    reqs = [_request(rid, isl, osl=3) for rid, isl in
            enumerate((16, 32, 16, 24))]
    for r in reqs:
        eng.add_request(r)
    eng.run_until_drained()
    plans = [p for p in plans if not p.empty]
    kinds = {"prefill": 0, "decode": 0, "mixed": 0}
    for p in plans:
        kinds["mixed" if p.prefill and p.decode else
              "prefill" if p.prefill else "decode"] += 1
    for kind, n in kinds.items():
        assert m.counter_value("repro_engine_steps_total", kind=kind) == n
    assert m.counter_value("repro_engine_decode_slot_steps_total") == \
        sum(len(p.decode) for p in plans)
    assert m.counter_value("repro_engine_prefill_tokens_total") == \
        sum(r.isl for r in reqs)
    h = m.to_dict()["histograms"]["repro_engine_queue_wait_ms"]
    assert h["count"] == len(reqs) and h["buckets"] == \
        list(LATENCY_MS_BUCKETS)
    assert h["sum"] == pytest.approx(sum(
        1e3 * (r.t_first_sched - r.arrival) for r in reqs))
    new = {k: v for k, v in m.to_dict()["counters"].items()
           if k.startswith("repro_engine_new_programs_total")}
    assert new == {
        "repro_engine_new_programs_total{program=decode_step}": 1.0,
        "repro_engine_new_programs_total{program=prefill:16}": 1.0,
        "repro_engine_new_programs_total{program=prefill:24}": 1.0,
        "repro_engine_new_programs_total{program=prefill:32}": 1.0,
        "repro_engine_new_programs_total{program=slot_write}": 1.0}
    # a step counts the programs it launches for the first time: a new
    # prompt length, and the first slot write and decode step
    seen, steps = set(), [s for s in tracer.spans if s.name == "engine.step"]
    assert len(steps) == len(plans)
    for p, s in zip(plans, steps):
        used = {f"prefill:{c.req.isl}" for c in p.prefill}
        used |= {"slot_write"} if p.prefill else set()
        used |= {"decode_step"} if p.decode else set()
        assert s.attrs["new_programs"] == len(used - seen)
        seen |= used


def test_programs_are_named_and_slot_write_lowers_once():
    from jax._src import monitoring
    lowered = []

    def on(event, duration, **kw):
        if event == "/jax/core/compile/jaxpr_to_mlir_module_duration":
            lowered.append(kw.get("fun_name"))
    monitoring.register_event_duration_secs_listener(on)
    try:
        eng = _engine(slots=3)
        reqs = [_request(rid, 16, osl=2) for rid in range(3)]
        for r in reqs:
            eng.add_request(r)
        eng.run_until_drained()
    finally:
        monitoring.unregister_event_duration_listener(on)
    assert sorted(r.slot for r in reqs) == [0, 1, 2]   # a write into each
    for name in ("jit(prefill)", "jit(decode_step)", "jit(slot_write)"):
        assert lowered.count(name) == 1, lowered
    toks = jax.numpy.zeros((1, 16), jax.numpy.int32)
    assert "module @jit_prefill " in \
        eng._prefill_fn(16).lower(PARAMS, tokens=toks).as_text()
    token = jax.numpy.zeros((3, 1), jax.numpy.int32)
    assert "module @jit_decode_step " in eng._decode_fn.lower(
        params=PARAMS, token=token, cache=eng.cache).as_text()
    _, cache = eng._prefill_fn(16)(PARAMS, tokens=toks)
    assert "module @jit_slot_write " in eng._slot_write.lower(
        eng.cache["k"], cache["k"], np.int32(2)).as_text()


def test_profiler_bridge_puts_engine_spans_on_the_host_plane(tmp_path):
    eng = _engine()
    eng.add_request(_request(0, 16, osl=3))
    eng.step()                            # build the programs first
    enable_tracing(Tracer(timebase="wall", profiler=True))
    jax.profiler.start_trace(str(tmp_path))
    try:
        eng.run_until_drained()
    finally:
        jax.profiler.stop_trace()
        disable_tracing()
    path = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                     recursive=True)[0]
    prof = jax.profiler.ProfileData.from_file(path)
    names = {e.name for plane in prof.planes if plane.name == "/host:CPU"
             for line in plane.lines for e in line.events
             if e.name.startswith("engine.")}
    assert {"engine.step", "engine.schedule", "engine.decode",
            "engine.decode.inputs", "engine.decode.run",
            "engine.decode.sample", "engine.commit"} <= names
