"""A whole run on the CPU, past the look for a chip: sound, it is correct;
with the timed path broken underneath, ``correct`` comes out false."""
import os
import sys
import time

sys.path.insert(0, os.path.abspath(os.path.join(os.path.dirname(__file__),
                                                "..", "..")))
sys.path.insert(0, os.path.dirname(__file__))

import jax.numpy as jnp  # noqa: E402
import pytest  # noqa: E402

import _tiny  # noqa: E402
from chipbench import harness  # noqa: E402

SEED = 2 ** 32 + 7


def _run(config=_tiny.DENSE, mix=_tiny.OPEN, trace=False):
    return harness.run_cell(_tiny.cell(config, mix), SEED, 1.5, trace,
                            time.time(), check_chip=_tiny.cpu_peaks)


@pytest.mark.parametrize("config,mix", [(_tiny.DENSE, _tiny.OPEN),
                                        (_tiny.MOE, _tiny.OPEN),
                                        (_tiny.DENSE, _tiny.CLOSED)],
                         ids=["dense-open", "moe-open", "dense-closed"])
def test_sound_run_is_correct(config, mix):
    out = _run(config, mix)
    r = out["result"]
    assert r["correct"], out["checks"]
    assert r["failed"] == 0 and r["attempted"] > 0
    names = set(r["metrics"])
    assert {"ttft_p90_ms", "itl_p95_ms", "output_tok_s", "setup_s"} <= names
    assert out["checks"]["logit_gap_mean"]["value"] <= \
        config["limits"]["logit_gap_mean"]


@pytest.fixture()
def cpu_trace(monkeypatch):
    """A CPU trace has no TPU plane, so the reductions read one built from
    the traced steps in its place; the rest of the traced run is the
    run's own."""
    from chipbench import serving, trace
    seen = {}
    real = serving.Serving.window

    def window(self, seconds, traced):
        seen["w"] = real(self, seconds, traced)
        return seen["w"]
    monkeypatch.setattr(serving.Serving, "window", window)
    monkeypatch.setattr(trace, "profile", lambda d: _tiny.profile_for_steps(
        [s for s in seen["w"].steps if s.traced]))


def test_traced_run_reports_host_layer_metrics(cpu_trace):
    out = _run(trace=True)
    r = out["result"]
    assert r["correct"]
    assert {"queue_wait_ms_p50", "decode_occupancy", "mfu", "idle_share",
            "decode_step_ms", "prefill_ms_per_ktok", "mfu.prefill",
            "idle_share.readback", "idle_share.host"} <= set(r["metrics"])
    assert r["metrics"]["decode_step_ms"]["value"] == pytest.approx(30.0)
    assert "ttft_p90_ms" not in r["metrics"]
    assert r["device"]["busy_s"] > 0 and r["device"]["window_s"] > 0
    assert r["breakdown"]["idle_gaps"]


@pytest.mark.parametrize("mix", [_tiny.OPEN, _tiny.CLOSED],
                         ids=["open", "closed"])
def test_traced_run_hands_counters_and_spans_to_readers(cpu_trace, mix):
    """A traced window counts into a registry of its own and puts the
    engine's spans on the trace; both reach the metric readers, and
    neither outlives the window."""
    from repro.obs import NULL_TRACER, get_metrics, get_tracer
    out = _run(mix=mix, trace=True)
    run, m = out["run"], out["result"]["metrics"]
    assert run.counters.counter_total("repro_engine_steps_total") == \
        len(run.window.steps) > 0
    assert get_tracer() is NULL_TRACER and get_metrics() is None
    assert m["idle_share.readback"]["value"] > 0
    assert m["idle_share.readback"]["value"] + m["idle_share.host"]["value"] \
        + run.spans.share("outside") == \
        pytest.approx(m["idle_share"]["value"], abs=0.1)


def test_closed_loop_clients_keep_one_request_in_flight():
    """Each client sends its own cycle of lengths, one request at a time,
    the next only after the last token of the one before; ``attempted``
    counts every request the clients sent.  Two seeds send the same
    lengths.  An untraced run installs no tracer or registry."""
    from chipbench import traffic
    clients = traffic.clients(_tiny.CLOSED)
    sent = {}
    for seed in (SEED, 2 ** 31 + 11):
        out = harness.run_cell(_tiny.cell(mix=_tiny.CLOSED), seed, 1.5,
                               False, time.time(),
                               check_chip=_tiny.cpu_peaks)
        recs = out["run"].window.recs
        assert out["result"]["correct"], out["checks"]
        assert out["run"].counters is None and out["run"].spans is None
        assert out["result"]["attempted"] == len(recs)
        assert {r.client for r in recs} == set(range(len(clients)))
        for c in range(len(clients)):
            mine = [r for r in recs if r.client == c]
            assert [(r.req.isl, r.req.osl) for r in mine] == \
                [clients[c].request(k) for k in range(len(mine))]
            for a, b in zip(mine, mine[1:]):
                assert b.due >= a.times[-1]
        sent[seed] = {c: [(r.req.isl, r.req.osl) for r in recs
                          if r.client == c] for c in range(len(clients))}
    a, b = sent.values()
    for c in a:
        n = min(len(a[c]), len(b[c]))
        assert a[c][:n] == b[c][:n] and n > 0


def _token_altered(monkeypatch):
    from repro.serving.engine import Engine
    real = Engine._run_decode

    def altered(self, active):
        real(self, active)
        r = active[0]
        r.out_tokens[-1] = (r.out_tokens[-1] + 1) % self.cfg.vocab_size
        self._last_tok[r.slot] = r.out_tokens[-1]
    monkeypatch.setattr(Engine, "_run_decode", altered)


def _state_unchanged(monkeypatch):
    """The decode step hands back the cache it was given."""
    from repro import models
    real = models.decode_step

    def stale(params, cfg, token, cache, **kw):
        logits, new = real(params, cfg, token, cache, **kw)
        return logits, dict(cache, pos=new["pos"])
    monkeypatch.setattr(models, "decode_step", stale)


def _half_batch(monkeypatch):
    """Half of the slots (the first, which every run fills) never reach
    the decode step: their tokens arrive as zeros."""
    from repro import models
    real = models.decode_step

    def half(params, cfg, token, cache, **kw):
        B = token.shape[0]
        token = token.at[:B // 2].set(jnp.zeros_like(token[:B // 2]))
        return real(params, cfg, token, cache, **kw)
    monkeypatch.setattr(models, "decode_step", half)


@pytest.mark.parametrize("fault,mix", [
    pytest.param(_token_altered, _tiny.OPEN, id="token-altered"),
    pytest.param(_state_unchanged, _tiny.OPEN, id="state-unchanged"),
    pytest.param(_half_batch, _tiny.OPEN, id="half-batch"),
    pytest.param(_token_altered, _tiny.CLOSED, id="token-altered-closed")])
def test_broken_path_is_not_correct(monkeypatch, fault, mix):
    fault(monkeypatch)
    out = _run(mix=mix)
    assert not out["result"]["correct"], out["checks"]
    assert out["checks"]["logit_gap_mean"]["value"] > \
        out["checks"]["logit_gap_mean"]["limit"]


@pytest.mark.parametrize("seed", [1, 3])
def test_int8_control_fails_the_limit(seed):
    """The control (the reference in int8, put in the program's place)
    fails the run's own check at the limit, on the sample a run compares,
    where the program's reading passes it."""
    out = harness.run_cell(_tiny.cell(_tiny.CONTROL), seed, 1.5, False,
                           time.time(), check_chip=_tiny.cpu_peaks,
                           control=True)
    limit = out["checks"]["logit_gap_mean"]["limit"]
    assert not out["result"]["correct"], out["checks"]
    got = {side: out["readings"][side]["logit_gap_mean"]
           for side in ("program", "control")}
    assert out["checks"]["logit_gap_mean"]["value"] == got["control"]
    assert got["program"] <= limit < got["control"]
