"""Idle device time put down to the engine's phases (``chipbench/spans.py``)
and the metrics that find the engine's programs by name, on hand-built
traces and on one recorded on a TPU v5e with the engine's spans on it."""
import gzip
import json
import os
import sys
import types

sys.path.insert(0, os.path.abspath(os.path.join(os.path.dirname(__file__),
                                                "..", "..")))
sys.path.insert(0, os.path.dirname(__file__))

import pytest  # noqa: E402

import _tiny  # noqa: E402
from chipbench import spans, spec, trace  # noqa: E402

DATA = os.path.join(os.path.dirname(__file__), "data")


def test_innermost_names_each_piece_by_the_deepest_span():
    events = [(0, 10, "a"), (2, 8, "b"), (3, 5, "c"), (12, 14, "d"),
              (6, 11, "e")]                # e outlasts its parent b
    assert spans.innermost(events) == [
        (0, 2, "a"), (2, 3, "b"), (3, 5, "c"), (5, 6, "b"), (6, 8, "e"),
        (8, 10, "a"), (12, 14, "d")]


def _engine_profile():
    """A 100 ns window: one engine step from 0 to 58 under ``bench.step``,
    then the harness's bookkeeping; the device busy 6-38 (the decode
    program) and 44-46 (the argmax)."""
    E, L, P = _tiny.Event, _tiny.Line, _tiny.Plane
    host = P("/host:CPU", [L("python", [
        E("bench.step", 0, 60), E("bench.record", 60, 40),
        E("engine.step", 0, 58), E("engine.schedule", 0, 4),
        E("engine.decode", 4, 48), E("engine.decode.inputs", 4, 4),
        E("engine.decode.run", 8, 32), E("engine.decode.sample", 40, 10),
        E("engine.commit", 52, 6)])])
    dev = P("/device:TPU:0", [
        L("XLA Modules", [E("jit_decode_step(3)", 6, 32),
                          E("jit__argmax(9)", 44, 2)]),
        L("XLA Ops", [E("fusion.1", 6, 32), E("argmax", 44, 2)])])
    return _tiny.Profile([host, dev])


def test_idle_gaps_split_across_engine_spans_by_overlap():
    """Each gap is shared among the innermost spans it runs through, not
    charged whole to the one at its middle: the gap from 46 to 100 runs
    through the decode read-back, the decode span's tail, the commit and
    the harness's bookkeeping."""
    s = spans.reduce(_engine_profile())
    assert s.window_s == pytest.approx(100e-9)
    assert s.busy_s == pytest.approx(34e-9)
    assert s.idle == pytest.approx({
        "engine.schedule": 4e-9, "engine.decode.inputs": 2e-9,
        "engine.decode.run": 2e-9, "engine.decode.sample": 8e-9,
        "engine.decode": 2e-9, "engine.commit": 6e-9,
        spans.OUTSIDE: 42e-9})
    assert s.share("readback") == pytest.approx(8.0)
    assert s.share("host") == pytest.approx(16.0)
    assert s.share(spans.OUTSIDE) == pytest.approx(42.0)
    idle_share = 100.0 * (1.0 - trace.reduce(_engine_profile()).busy_s
                          / s.window_s)
    assert s.share("readback") + s.share("host") + \
        s.share(spans.OUTSIDE) == pytest.approx(idle_share)
    assert s.host["engine.decode.run"] == (1, pytest.approx(32e-9))


def test_a_trace_without_engine_spans_is_refused():
    with pytest.raises(trace.TraceError, match="profiler-bridged"):
        spans.reduce(_tiny.hand_built_profile())


def _run(modules, steps, traced):
    r = trace.Reduced(window_s=1.0, busy_s=0.5, modules=modules,
                      steps=steps, ops={}, idle={}, gaps=[])
    return types.SimpleNamespace(reduced=r, window=types.SimpleNamespace(
        steps=[types.SimpleNamespace(traced=True, decode_lens=d,
                                     prefill_lens=p) for d, p in traced]))


def test_program_metrics_read_the_named_programs():
    run = _run({"jit_decode_step(1)": (2, 0.08),
                "jit_prefill(5)": (1, 0.02),
                "jit_slot_write(6)": (2, 0.01),
                "jit__argmax(7)": (3, 0.003)},
               [{"jit_decode_step(1)": 0.04, "jit__argmax(7)": 0.001},
                {"jit_prefill(5)": 0.02, "jit_slot_write(6)": 0.01,
                 "jit_decode_step(1)": 0.04, "jit__argmax(7)": 0.002}],
               [([10], []), ([11], [500])])
    assert spec.reader("decode_program_ms")(run) == pytest.approx(40.0)
    assert spec.reader("prefill_program_ms_per_ktok")(run) == \
        pytest.approx(60.0)


def test_program_metrics_read_nothing_where_programs_are_unnamed():
    """An engine that jits unnamed functions (every program
    ``jit__unknown``) gives these metrics nothing to read, and no error."""
    steps = [types.SimpleNamespace(decode_lens=[5], prefill_lens=[16])]
    r = trace.reduce(_tiny.profile_for_steps(steps), devices=1)
    run = types.SimpleNamespace(reduced=r, window=types.SimpleNamespace(
        steps=[types.SimpleNamespace(traced=True, decode_lens=[5],
                                     prefill_lens=[16])]))
    assert spec.reader("decode_program_ms")(run) is None
    assert spec.reader("prefill_program_ms_per_ktok")(run) is None


def test_recorded_v5e_trace_with_engine_spans():
    """A trace recorded on one TPU v5e with the engine's spans on it
    (``chipbench/record_spans.py``, internlm2-1.8b chat, 32 slots): the
    engine's three programs trace by name, none as ``jit__unknown``, and
    the idle time under the engine's spans is part of the idle share."""
    import jax
    with gzip.open(os.path.join(DATA, "v5e_chat_spans.xplane.pb.gz")) as f:
        prof = jax.profiler.ProfileData.from_serialized_xspace(f.read())
    with open(os.path.join(DATA, "v5e_chat_spans.steps.json")) as f:
        rec = json.load(f)
    assert rec["device_kind"] == "TPU v5 lite"
    r = trace.reduce(prof, devices=1)
    names = list(r.modules)
    for prefix in ("jit_decode_step(", "jit_prefill(", "jit_slot_write("):
        assert any(n.startswith(prefix) for n in names), names
    assert not any(n.startswith("jit__unknown") for n in names), names
    steps = [types.SimpleNamespace(decode_lens=s["decode_lens"],
                                   prefill_lens=s["prefill_lens"])
             for s in rec["steps"]]
    w = trace.step_work(r, steps)
    assert w.decode_program.startswith("jit_decode_step(")
    s = spans.reduce(prof)
    assert s.window_s == pytest.approx(r.window_s)
    assert s.busy_s == pytest.approx(r.busy_s)
    idle_share = 100.0 * (1.0 - r.busy_s / r.window_s)
    assert 0 < s.share("readback") and 0 < s.share("host")
    assert s.share("readback") + s.share("host") <= idle_share + 1e-9
    assert s.share("readback") + s.share("host") + \
        s.share(spans.OUTSIDE) == pytest.approx(idle_share)
    assert s.host["engine.step"][0] == len(steps)


def test_idle_split_metrics_add_up_on_recorded_v5e_trace():
    """On the recorded v5e trace, the two engine idle metrics and the
    idle outside any engine span add up to ``idle_share``, all read by
    the benchmark's own readers from one profile."""
    import jax
    with gzip.open(os.path.join(DATA, "v5e_chat_spans.xplane.pb.gz")) as f:
        prof = jax.profiler.ProfileData.from_serialized_xspace(f.read())
    run = types.SimpleNamespace(reduced=trace.reduce(prof, devices=1),
                                spans=spans.reduce(prof))
    readback = spec.reader("idle_share.readback")(run)
    host = spec.reader("idle_share.host")(run)
    assert readback > 0 and host > 0
    assert readback + host + run.spans.share(spans.OUTSIDE) == \
        pytest.approx(spec.reader("idle_share")(run), abs=0.1)


def test_idle_split_metrics_read_nothing_without_spans():
    run = types.SimpleNamespace(reduced=None, spans=None)
    assert spec.reader("idle_share.readback")(run) is None
    assert spec.reader("idle_share.host")(run) is None
