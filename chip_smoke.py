"""One-chip smoke of the main path: search → launch config → engine → kernels.

    python chip_smoke.py

Runs in ONE process on one TPU chip (it never starts a child: a child
could not take the chip from a parent that holds it):

  device       jax.devices() must hold a TPU; its device_kind maps to the
               configurator's platform key (core/hardware.py).  Any other
               platform exits non-zero at once — there is no CPU fallback.
  search       Configurator for internlm2-1.8b, isl 1024 / osl 128, one
               chip, aggregated → SearchReport and its launch config.
  serve        launch/serve.serve() at full width with that launch config:
               32 requests of isl 1024 / osl 128, random weights from a seed.
  logits       the engine's prefill and first-decode logits for two
               requests against a float32 forward on the same weights.
  kernels      the four Pallas kernels compiled (interpret=False) at real
               widths, each against kernels/ref.py, then one WallClockTimer
               sample per family through calibrate.harness.

Every phase prints one ``<phase> {json}`` line; a failing phase raises,
names itself on stderr, and the script exits non-zero.  The last line of
stdout is ``{"ok": true, "device": {...}}``.  Measured numbers are one
smoke run, not a benchmark; the projection is printed beside them for
information only.  The compile cache follows core/jaxenv.use_compile_cache.
"""
from __future__ import annotations

import dataclasses
import functools
import json
import math
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

MODEL = "internlm2-1.8b"
ISL, OSL, REQUESTS = 1024, 128, 32
SEED = 0

#: bf16 keeps 8 significant bits, so one rounding is off by at most
#: u = 2**-8 relative.  Along the engine's path each layer rounds its
#: residual stream at about four points (attention out, residual add, FFN
#: out, residual add); independent roundings add in quadrature, so the
#: logits' relative L2 error is about sqrt(4·L)·u.  The bound allows twice
#: that for amplification through the layers.
def logit_tolerance(num_layers: int) -> float:
    return 2.0 * math.sqrt(4 * num_layers) * 2.0 ** -8


def _emit(phase: str, record: dict) -> None:
    print(f"{phase} {json.dumps(record, sort_keys=True)}", flush=True)


def _phase(name: str, fn, *args, **kwargs):
    t0 = time.perf_counter()
    try:
        out = fn(*args, **kwargs)
    except BaseException:
        print(f"chip_smoke: phase {name!r} failed", file=sys.stderr,
              flush=True)
        raise
    print(f"# {name} done in {time.perf_counter() - t0:.1f}s", flush=True)
    return out


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------

def check_device():
    """The first device must be a TPU whose kind has a platform profile."""
    from repro.core.hardware import platform_for_device_kind
    devs = jax.devices()
    d = devs[0]
    if d.platform != "tpu":
        raise SystemExit(f"chip_smoke: no TPU — JAX found {d.platform} "
                         f"({d.device_kind}); nothing was run")
    device = {"platform": d.platform, "kind": d.device_kind,
              "count": len(devs)}
    platform = platform_for_device_kind(d.device_kind)
    _emit("device", dict(device, platform_key=platform))
    return device, platform


def search(platform: str, model: str = MODEL, isl: int = ISL,
           osl: int = OSL) -> dict:
    """The configurator's search on one chip; returns the launch config."""
    from repro.api import Configurator
    t0 = time.perf_counter()
    report = (Configurator.for_model(model).traffic(isl=isl, osl=osl)
              .cluster(chips=1, platform=platform).backend("repro-jax")
              .dtype("bf16").modes("aggregated").search())
    wall = time.perf_counter() - t0
    if report.launch is None:
        raise RuntimeError(f"search found no deployment for {model} on "
                           f"one {platform} chip")
    launch = report.launch.raw
    _emit("search", {"search_wall_s": wall, "command": report.launch.command,
                     "mode": launch["mode"], "parallel": launch["parallel"],
                     "batch_size": launch["batch_size"],
                     "projected": launch["projection"]})
    return launch


def serve_launch(launch: dict, cfg, params, requests: int = REQUESTS,
                 isl: int = ISL, osl: int = OSL) -> dict:
    """launch/serve.serve() on ``launch`` with the given model and weights."""
    from repro.launch import serve as serve_mod
    args = serve_mod.parse_args(
        ["--requests", str(requests), "--isl", str(isl), "--osl", str(osl),
         "--seed", str(SEED)])
    m = serve_mod.serve(args, launch=launch, cfg=cfg, params=params)
    if m["requests"] != requests:
        raise RuntimeError(f"served {m['requests']} of {requests} requests")
    for k in ("ttft_ms_p50", "ttft_ms_p99", "tpot_ms_p50", "tpot_ms_p99",
              "tokens_per_s"):
        if not (m[k] is not None and math.isfinite(m[k]) and m[k] > 0):
            raise RuntimeError(f"serve measured {k}={m[k]!r}")
    m["projected (information only)"] = launch["projection"]
    _emit("serve", m)
    return m


def _rel_l2(a: np.ndarray, ref: np.ndarray) -> float:
    return float(np.linalg.norm(a - ref) / max(np.linalg.norm(ref), 1e-30))


def check_logits(cfg, params, isl: int = ISL, osl: int = OSL, n: int = 2,
                 seed: int = SEED + 1) -> dict:
    """Engine logits vs a float32 forward on the same weights.

    The engine serves ``n`` requests in ``n`` slots; its prefill logits
    (last prompt position) and its first decode step's logits are compared
    with ``forward_train`` + ``lm_logits`` on the weights cast to float32,
    at the highest matmul precision, over the prompt plus the engine's
    first token.  Also counts the greedy tokens on which the slot-batched
    engine and static one-request generation agree (informational)."""
    from repro import models
    from repro.serving.engine import Engine, EngineConfig
    from repro.serving.request import Request

    rng = np.random.default_rng(seed)
    reqs = [Request(rid=i, isl=isl, osl=osl, arrival=time.perf_counter(),
                    prompt=rng.integers(0, cfg.vocab_size, isl).tolist())
            for i in range(n)]
    max_seq = isl + osl + 8
    eng = Engine(cfg, params, EngineConfig(max_batch=n, max_seq=max_seq))
    for r in reqs:
        eng.add_request(r)
    eng.step()                                   # all prefills
    pre = {rid: np.asarray(x, np.float32)
           for rid, x in eng.step_logits["prefill"].items()}
    if sorted(pre) != [r.rid for r in reqs]:
        raise RuntimeError(f"first step prefilled {sorted(pre)}")
    eng.step()                                   # first decode step
    dec = np.asarray(eng.step_logits["decode"], np.float32)
    eng.run_until_drained()
    W = eng.cache["k"].shape[2]
    del eng

    cfg32 = dataclasses.replace(cfg, dtype="float32")
    p32 = jax.tree.map(lambda x: x.astype(jnp.float32), params)

    @jax.jit
    def ref_logits(p, toks):
        h, _ = models.forward_train(p, cfg32, toks)
        return models.lm_logits(p["embed"], h[:, -2:], cfg32)[0]

    tol = logit_tolerance(cfg.num_layers)
    errs, top1 = [], 0
    with jax.default_matmul_precision("highest"):
        for r in reqs:
            toks = jnp.asarray([r.prompt + r.out_tokens[:1]], jnp.int32)
            ref = np.asarray(ref_logits(p32, toks), np.float32)
            for got, want in ((pre[r.rid], ref[0]), (dec[r.slot], ref[1])):
                errs.append(_rel_l2(got, want))
                top1 += int(np.argmax(got) == np.argmax(want))
    del p32

    prefill = jax.jit(functools.partial(models.prefill, cfg=cfg, max_len=W))
    decode = jax.jit(functools.partial(models.decode_step, cfg=cfg))
    agree = []
    for r in reqs:
        lg, cache = prefill(params,
                            tokens=jnp.asarray([r.prompt], jnp.int32))
        cache = dict(cache, pos=jnp.asarray([r.isl], jnp.int32))
        seq = [int(jnp.argmax(lg[0, -1]))]
        for _ in range(r.osl - 1):
            lg, cache = decode(params, token=jnp.asarray([[seq[-1]]],
                                                         jnp.int32),
                               cache=cache)
            seq.append(int(jnp.argmax(lg[0, -1])))
        same = 0
        while same < len(seq) and seq[same] == r.out_tokens[same]:
            same += 1
        agree.append(same)

    rec = {"rel_l2_max": max(errs), "rel_l2": errs, "tolerance": tol,
           "top1_match": f"{top1}/{len(errs)}",
           "greedy_prefix_agree (information only)":
               [f"{a}/{osl}" for a in agree],
           "passed": max(errs) <= tol}
    _emit("logits", rec)
    if not rec["passed"]:
        raise RuntimeError(f"engine logits off the float32 reference: "
                           f"rel L2 {max(errs):.4g} > {tol:.4g}")
    return rec


def check_kernels() -> dict:
    """The four Pallas kernels, compiled, at real widths vs kernels/ref.py."""
    from repro.configs import get_config
    from repro.kernels import ops, ref

    dense = get_config(MODEL)
    moe = get_config("qwen3-moe-30b-a3b")
    rec_cfg = get_config("recurrentgemma-2b")
    H, K, D = dense.num_heads, dense.num_kv_heads, dense.head_dim
    ks = jax.random.split(jax.random.PRNGKey(SEED), 12)
    bf = jnp.bfloat16
    n = lambda k, shape, dt=bf, s=1.0: (
        jax.random.normal(k, shape, jnp.float32) * s).astype(dt)

    q = n(ks[0], (1, ISL, H, D))
    k = n(ks[1], (1, ISL, K, D))
    v = n(ks[2], (1, ISL, K, D))
    B, Wc = 8, 2048
    qd = n(ks[3], (B, H, D))
    kc = n(ks[4], (B, Wc, K, D))
    vc = n(ks[5], (B, Wc, K, D))
    vl = jax.random.randint(ks[6], (B,), 1, Wc + 1, jnp.int32)
    E, C = 8, 128
    xe = n(ks[7], (E, C, moe.d_model), s=0.1)
    we = n(ks[8], (E, moe.d_model, moe.moe_d_ff), s=0.1)
    Br, Sr, Wr = 4, ISL, rec_cfg.lru_width
    a = jax.nn.sigmoid(n(ks[9], (Br, Sr, Wr), jnp.float32))
    b = n(ks[10], (Br, Sr, Wr), jnp.float32)
    h0 = n(ks[11], (Br, Wr), jnp.float32)

    cases = {
        "flash_attention": (ops.flash_attention, (q, k, v),
                            ref.flash_attention_ref, bf),
        "decode_attention": (ops.decode_attention, (qd, kc, vc, vl),
                             ref.decode_attention_ref, bf),
        "moe_gemm": (ops.moe_gemm, (xe, we), ref.moe_gemm_ref, bf),
        "rglru_scan": (ops.rglru_scan, (a, b, h0), ref.rglru_scan_ref,
                       jnp.float32),
    }
    # the tolerances of tests/test_kernels.py
    tols = {bf: (2e-2, 3e-2), jnp.float32: (2e-5, 2e-5)}
    out = {}
    for name, (kernel, args, oracle, dt) in cases.items():
        text = kernel.lower(*args, interpret=False).compile().as_text()
        if "tpu_custom_call" not in text:
            raise RuntimeError(f"{name}: no tpu_custom_call in the compiled "
                               "program")
        got = np.asarray(kernel(*args, interpret=False), np.float32)
        with jax.default_matmul_precision("highest"):
            want = np.asarray(oracle(*args), np.float32)
        rtol, atol = tols[dt]
        np.testing.assert_allclose(got, want, rtol=rtol, atol=atol,
                                   err_msg=name)
        out[name] = {"shape": list(got.shape),
                     "max_abs_err": float(np.max(np.abs(got - want)))}
    _emit("kernels", out)
    return out


def calibrate_once(platform: str) -> dict:
    """One WallClockTimer sample per family, kernels compiled."""
    from repro.calibrate import WallClockTimer
    from repro.calibrate.harness import MeasurementHarness
    h = MeasurementHarness(platform, timer=WallClockTimer(reps=3, trials=3),
                           points_per_axis=1)
    rec = {s.family: {"coords": list(s.coords), "measured_s": s.measured_s,
                      "predicted_s": s.predicted_s}
           for s in h.measure_all()}
    _emit("calibrate", rec)
    return rec


# ---------------------------------------------------------------------------

def main() -> int:
    device, platform = _phase("device", check_device)
    from repro import models
    from repro.configs import get_config
    from repro.core.jaxenv import use_compile_cache
    cache = use_compile_cache()
    _emit("compile_cache", {"dir": cache, "entries_at_start": len(
        os.listdir(cache)) if os.path.isdir(cache) else 0})

    cfg = get_config(MODEL)
    launch = _phase("search", search, platform)
    params = models.init_params(cfg, jax.random.PRNGKey(SEED))
    _phase("serve", serve_launch, launch, cfg, params)
    _phase("logits", check_logits, cfg, params)
    del params
    _phase("kernels", check_kernels)
    _phase("calibrate", calibrate_once, platform)
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
