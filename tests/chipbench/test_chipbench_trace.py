"""The reduction from a profiler trace to busy time, program time and
idle gaps, on hand-built traces and on one recorded on a TPU v5e."""
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
from chipbench import trace  # noqa: E402

_hand_built = _tiny.hand_built_profile


def test_union_merges_overlaps():
    assert trace.union([(5, 8), (0, 2), (1, 3), (8, 9)]) == [(0, 3), (5, 9)]


def test_hand_built_trace():
    r = trace.reduce(_hand_built(), devices=1)
    assert r.window_s == pytest.approx(100e-9)
    assert r.busy_s == pytest.approx(35e-9)          # 10-40 and 50-55
    assert r.modules == {"jit_decode_step(3)": (1, pytest.approx(30e-9)),
                         "jit_prefill(7)": (1, pytest.approx(5e-9))}
    assert r.steps == [{"jit_decode_step(3)": pytest.approx(30e-9),
                        "jit_prefill(7)": pytest.approx(5e-9)}]
    assert r.ops["fusion.1"] == pytest.approx(20e-9)
    # gaps 0-10 and 40-50 in the step, 55-100 waiting for an arrival
    assert r.idle["step"] == pytest.approx(20e-9)
    assert r.idle["wait"] == pytest.approx(45e-9)
    assert r.busy_s + sum(r.idle.values()) == pytest.approx(r.window_s)
    b = trace.breakdown(r)
    assert b["idle_gaps"][0] == ["wait", pytest.approx(45e-9)]
    assert len(b["device_ops"]) <= 10


def test_busy_is_averaged_over_the_devices_used():
    r = trace.reduce(_hand_built(), devices=2)
    assert r.busy_s == pytest.approx((35e-9 + 100e-9) / 2)


def test_no_host_window_reads_nothing():
    with pytest.raises(trace.TraceError, match="annotation"):
        trace.reduce(_tiny.Profile([]), devices=1)


def _step(decode, prefill=()):
    return types.SimpleNamespace(decode_lens=list(decode),
                                 prefill_lens=list(prefill))


def test_step_work_tells_decode_from_prefill_by_fingerprint():
    """Every program traces as ``jit__unknown``; the decode program is the
    one that ran once in each decoding step, and a step's prefill work is
    what it spent beyond that."""
    steps = [_step([5, 9]), _step([6, 10], [16]), _step([7, 11]),
             _step([], [32, 16]), _step([8])]
    r = trace.reduce(_tiny.profile_for_steps(steps), devices=1)
    w = trace.step_work(r, steps)
    assert w.decode_program == "jit__unknown(1)"
    assert w.decode_calls == 4
    assert w.decode_s == pytest.approx(4 * 30e-3)
    assert w.prefill_s == pytest.approx(3 * 2e-3)


def test_step_work_refuses_steps_the_trace_lacks():
    steps = [_step([5]), _step([6])]
    r = trace.reduce(_tiny.profile_for_steps(steps), devices=1)
    with pytest.raises(trace.TraceError, match="annotations"):
        trace.step_work(r, steps + [_step([7])])


DATA = os.path.join(os.path.dirname(__file__), "data")


def test_recorded_v5e_trace():
    """A trace recorded on one TPU v5e (a 1.2 s window of internlm2-1.8b
    chat, 32 slots; ``chipbench/record_spans.py --out`` writes such a
    fixture): the names the reduction reads are the
    chip's, every traced engine step is in it, and the decode program runs
    once in each."""
    import jax
    with gzip.open(os.path.join(DATA, "v5e_chat.xplane.pb.gz")) as f:
        prof = jax.profiler.ProfileData.from_serialized_xspace(f.read())
    with open(os.path.join(DATA, "v5e_chat.steps.json")) as f:
        rec = json.load(f)
    assert rec["device_kind"] == "TPU v5 lite"
    steps = [_step(s["decode_lens"], s["prefill_lens"])
             for s in rec["steps"]]
    r = trace.reduce(prof, devices=1)
    assert len(r.steps) == len(steps)
    assert 0 < r.busy_s <= r.window_s
    w = trace.step_work(r, steps)
    assert w.decode_program.startswith("jit_")
    assert w.decode_calls == sum(1 for s in steps if s.decode_lens)
    # a decode step at 32 slots reads the 3.4 GB of weights at least
    # (4.2 ms at 819 GB/s) and took about 39 ms on the chip
    assert 4e-3 < w.decode_s / w.decode_calls < 0.2
    assert any(s.prefill_lens for s in steps) and w.prefill_s > 0
    assert sum(r.idle.values()) + r.busy_s == pytest.approx(r.window_s)
