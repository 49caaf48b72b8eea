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
                                        (_tiny.MOE, _tiny.OPEN)],
                         ids=["dense-open", "moe-open"])
def test_sound_run_is_correct(config, mix):
    out = _run(config, mix)
    r = out["result"]
    assert r["correct"], out["checks"]
    assert r["failed"] == 0 and r["attempted"] > 0
    names = set(r["metrics"])
    assert {"ttft_p90_ms", "itl_p95_ms", "output_tok_s", "setup_s"} <= names
    assert out["checks"]["logit_gap_mean"]["value"] <= \
        config["limits"]["logit_gap_mean"]


def test_traced_run_reports_host_layer_metrics(monkeypatch):
    """A CPU trace has no TPU plane, so the reduction reads one built from
    the traced steps in its place; the rest of the traced run is the
    run's own."""
    from chipbench import serving, trace
    seen = {}
    real = serving.Serving.window

    def window(self, seconds, traced):
        seen["w"] = real(self, seconds, traced)
        return seen["w"]
    monkeypatch.setattr(serving.Serving, "window", window)
    monkeypatch.setattr(trace, "load", lambda d, n: trace.reduce(
        _tiny.profile_for_steps([s for s in seen["w"].steps if s.traced]),
        n))
    out = _run(trace=True)
    r = out["result"]
    assert r["correct"]
    assert {"queue_wait_ms_p50", "decode_occupancy", "mfu", "idle_share",
            "decode_step_ms", "prefill_ms_per_ktok",
            "mfu.prefill"} <= set(r["metrics"])
    assert r["metrics"]["decode_step_ms"]["value"] == pytest.approx(30.0)
    assert "ttft_p90_ms" not in r["metrics"]
    assert r["device"]["busy_s"] > 0 and r["device"]["window_s"] > 0
    assert r["breakdown"]["idle_gaps"]


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


@pytest.mark.parametrize("fault", [_token_altered, _state_unchanged,
                                   _half_batch],
                         ids=["token-altered", "state-unchanged",
                              "half-batch"])
def test_broken_path_is_not_correct(monkeypatch, fault):
    fault(monkeypatch)
    out = _run()
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
