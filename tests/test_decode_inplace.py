"""The decode step updates the K/V cache in place.

The engine's compiled decode program holds no copy, and no dynamic-slice
or dynamic-update-slice outside a fusion, whose result has the shape of
the whole cache stack or of one layer of it, and it aliases the donated
cache to the returned one.  The step writes exactly one position per row,
at ``pos % W`` of every layer, and its logits equal a plain per-layer loop.
"""
import dataclasses
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import models
from repro.configs import get_config
from repro.models import common as cm
from repro.serving.engine import Engine, EngineConfig

# B and W chosen so that no other array of the program has a cache shape.
B, MAX_SEQ = 3, 40

_OP = re.compile(r"^\s*(?:ROOT )?%\S+ = (\w+)\[([\d,]*)\]\S* ([\w-]+)\(")
_ALIAS = re.compile(r"\{(\d+)\}: \((\d+), \{\}")


def _config(case):
    arch = {"bf16": "internlm2-1.8b", "kv_quant": "internlm2-1.8b",
            "window": "h2o-danube-3-4b", "moe": "qwen3-moe-30b-a3b"}[case]
    cfg = get_config(arch).reduced()
    if case == "kv_quant":
        cfg = dataclasses.replace(cfg, sharding=dataclasses.replace(
            cfg.sharding, kv_quant=True))
    return cfg


def _abstract_cache(cfg, W, quant):
    L, K, D = cfg.num_layers, cfg.num_kv_heads, cfg.head_dim
    sds = jax.ShapeDtypeStruct
    kv = jnp.int8 if quant else models.param_dtype(cfg)
    cache = {"k": sds((L, B, W, K, D), kv), "v": sds((L, B, W, K, D), kv),
             "pos": sds((B,), jnp.int32)}
    if quant:
        cache["k_scale"] = sds((L, B, W, K), jnp.float32)
        cache["v_scale"] = sds((L, B, W, K), jnp.float32)
    return cache


def _unfused_ops(hlo: str):
    """(op, dims without unit dims) of every instruction outside fused
    computations: a layer read as ``[1, B, W, K, D]`` is a layer too."""
    out, fused = [], False
    for line in hlo.splitlines():
        if line and not line[0].isspace():        # a computation's header
            fused = line.lstrip("%").startswith("fused")
        m = _OP.match(line)
        if m and not fused:
            dims = tuple(int(d) for d in m.group(2).split(",")
                         if d and d != "1")
            out.append((m.group(3), dims))
    return out


@pytest.mark.parametrize("case", ["bf16", "kv_quant", "window", "moe"])
def test_engine_decode_program_updates_cache_in_place(case):
    cfg = _config(case)
    params = models.init_params(cfg, jax.random.PRNGKey(0))
    eng = Engine(cfg, params, EngineConfig(max_batch=B, max_seq=MAX_SEQ))
    W = eng._W
    if case == "window":
        assert W < MAX_SEQ            # the sliding-window ring buffer
    quant = case == "kv_quant"
    cache = _abstract_cache(cfg, W, quant)
    compiled = eng._decode_fn.lower(
        params=params, token=jax.ShapeDtypeStruct((B, 1), jnp.int32),
        cache=cache).compile()
    hlo = compiled.as_text()

    L, K, D = cfg.num_layers, cfg.num_kv_heads, cfg.head_dim
    cache_shapes = {(L, B, W, K, D), (B, W, K, D)}
    if quant:
        cache_shapes |= {(L, B, W, K), (B, W, K)}
    moved = [(op, dims) for op, dims in _unfused_ops(hlo)
             if dims in cache_shapes
             and op in ("copy", "dynamic-slice", "dynamic-update-slice")]
    assert moved == [], f"{case}: the decode program moves the cache: {moved}"

    # input_output_alias: each cache array's parameter is its output's.
    token = jax.ShapeDtypeStruct((B, 1), jnp.int32)
    kwargs = {"params": params, "token": token, "cache": cache}
    path = jax.tree_util.keystr
    param_of = {path(p): i for i, (p, _) in enumerate(
        jax.tree_util.tree_flatten_with_path(((), kwargs))[0])}
    output_of = {path(p): i for i, (p, _) in enumerate(
        jax.tree_util.tree_flatten_with_path(
            jax.eval_shape(eng._decode_fn, **kwargs))[0])}
    header = next(l for l in hlo.splitlines() if l.startswith("HloModule"))
    aliases = {int(o): int(p) for o, p in _ALIAS.findall(header)}
    for name in cache:
        if name != "pos":
            assert aliases.get(output_of[f"[1]['{name}']"]) == \
                param_of[f"[1]['cache']['{name}']"], (case, name, header)


def _plain_decode(params, cfg, token, cache):
    """One decode step as a Python loop over layers, one row at a time."""
    pos = np.broadcast_to(np.asarray(cache["pos"]), (token.shape[0],))
    W = cache["k"].shape[2]
    H, K, D = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    x = jnp.take(params["embed"]["tok_embed"], token, axis=0)
    ks, vs = [], []
    for l in range(cfg.num_layers):
        lp = jax.tree.map(lambda a: a[l], params["layers"])
        h = cm.rms_norm(x, lp["norm0"], cfg.norm_eps)
        q, k, v = cm.qkv_project(lp, h, cfg, jnp.asarray(pos)[:, None])
        kc, vc = cache["k"][l], cache["v"][l]
        rows = []
        for b in range(token.shape[0]):
            kb = kc[b].at[pos[b] % W].set(k[b, 0])
            vb = vc[b].at[pos[b] % W].set(v[b, 0])
            kc, vc = kc.at[b].set(kb), vc.at[b].set(vb)
            n = min(int(pos[b]) + 1, W)
            kr = jnp.repeat(kb[:n], H // K, axis=1)          # (n, H, D)
            vr = jnp.repeat(vb[:n], H // K, axis=1)
            s = jnp.einsum("hd,nhd->hn", q[b, 0], kr) * D ** -0.5
            rows.append(jnp.einsum("hn,nhd->hd", jax.nn.softmax(s, -1), vr))
        ks.append(kc)
        vs.append(vc)
        attn = jnp.stack(rows)[:, None].reshape(token.shape[0], 1, H * D)
        x = x + attn @ lp["wo"]
        x = x + cm.swiglu(cm.rms_norm(x, lp["norm1"], cfg.norm_eps),
                          lp["w_gate"], lp["w_up"], lp["w_down"])
    return cm.lm_logits(params["embed"], x, cfg), jnp.stack(ks), jnp.stack(vs)


@pytest.mark.parametrize("pos_kind", ["per_row", "scalar"])
def test_decode_step_writes_one_token_per_row(pos_kind):
    cfg = get_config("internlm2-1.8b").reduced()
    params = models.init_params(cfg, jax.random.PRNGKey(0))
    L, K, D, W = cfg.num_layers, cfg.num_kv_heads, cfg.head_dim, 16
    rk, rv, rt = jax.random.split(jax.random.PRNGKey(1), 3)
    k = jax.random.normal(rk, (L, B, W, K, D), jnp.float32)
    v = jax.random.normal(rv, (L, B, W, K, D), jnp.float32)
    # one row wrapped past W (per-row), or every row (scalar)
    pos = (jnp.asarray([3, W + 5, W - 1], jnp.int32) if pos_kind == "per_row"
           else jnp.int32(W + 2))
    token = jax.random.randint(rt, (B, 1), 0, cfg.vocab_size)
    cache = {"k": k, "v": v, "pos": pos}

    logits, new = models.decode_step(params, cfg, token, cache)
    want, want_k, want_v = _plain_decode(params, cfg, token, cache)

    slot = np.broadcast_to(np.asarray(pos), (B,)) % W
    written = np.zeros((L, B, W), bool)
    written[:, np.arange(B), slot] = True
    for name, old, ref in (("k", k, want_k), ("v", v, want_v)):
        got = np.asarray(new[name])
        changed = np.any(got != np.asarray(old), axis=(-2, -1))
        np.testing.assert_array_equal(changed, written, err_msg=name)
        np.testing.assert_allclose(got, ref, rtol=1e-5, atol=1e-5,
                                   err_msg=name)
    np.testing.assert_array_equal(np.asarray(new["pos"]), np.asarray(pos) + 1)
    np.testing.assert_allclose(np.asarray(logits), np.asarray(want),
                               rtol=1e-4, atol=1e-5)
