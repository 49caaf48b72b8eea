"""MoE decoder family (qwen3-moe-30b-a3b: 128e top-8; mixtral-8x22b: 8e top-2
with SWA).

Routing uses the capacity-based dispatch with *index* gathers/scatters
(GShard semantics) instead of dense (S, E, C) one-hot einsums, so the
dispatch transients stay O(S*K*E) int32 for the position cumsum and
O(E*C*D) for the dispatched activations.  Under expert-parallel sharding
(experts on the 'model' mesh axis) GSPMD turns the gathers into the
dispatch/combine collectives the paper models (§4.3, Fig. 4).
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.configs.base import ModelConfig
from repro.models import common as cm

def capacity(cfg: ModelConfig, tokens_per_group: int) -> int:
    c = int(-(-tokens_per_group * cfg.top_k * cfg.capacity_factor
              // cfg.num_experts))
    return max(1, c)


# ---------------------------------------------------------------------------
# Schema
# ---------------------------------------------------------------------------

def schema(cfg: ModelConfig) -> Dict:
    L, d, f, E = cfg.num_layers, cfg.d_model, cfg.moe_d_ff, cfg.num_experts
    layers = {}
    layers.update(cm.attn_schema(cfg, L))
    layers.update(cm.norm_schema(L, d, 2))
    layers["router"] = cm.ParamSpec((L, d, E), ("layers", "embed", None))
    layers["we_gate"] = cm.ParamSpec((L, E, d, f), ("layers", "experts", "embed", "ffn"))
    layers["we_up"] = cm.ParamSpec((L, E, d, f), ("layers", "experts", "embed", "ffn"))
    layers["we_down"] = cm.ParamSpec((L, E, f, d), ("layers", "experts", "ffn", "embed"))
    if cfg.n_shared_experts:
        fs = cfg.n_shared_experts * f
        layers["ws_gate"] = cm.ParamSpec((L, d, fs), ("layers", "embed", "ffn"))
        layers["ws_up"] = cm.ParamSpec((L, d, fs), ("layers", "embed", "ffn"))
        layers["ws_down"] = cm.ParamSpec((L, fs, d), ("layers", "ffn", "embed"))
    return {"embed": cm.embed_schema(cfg), "layers": layers}


# ---------------------------------------------------------------------------
# Routing + expert compute
# ---------------------------------------------------------------------------

def route(cfg: ModelConfig, router_w: jax.Array, x: jax.Array):
    """x: (B, S, d) -> (top-k weights, expert ids, router probs).

    Weights are renormalized over the selected k (qwen3/mixtral convention).
    """
    logits = jnp.einsum("bsd,de->bse", x.astype(jnp.float32),
                        router_w.astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)
    topw, tope = lax.top_k(probs, cfg.top_k)
    topw = topw / jnp.sum(topw, axis=-1, keepdims=True)
    return topw, tope, probs


def load_balance_loss(cfg: ModelConfig, probs: jax.Array, tope: jax.Array) -> jax.Array:
    """Switch-style aux loss: E * <fraction routed to e> . <mean prob of e>.

    Computed via scatter-add (O(S*K)), not a (B,S,K,E) one-hot."""
    E = cfg.num_experts
    B, S, K = tope.shape
    counts = jnp.zeros((E,), jnp.float32).at[tope.reshape(-1)].add(1.0)
    frac = counts / (B * S)                                      # (E,)
    mean_prob = jnp.mean(probs, axis=(0, 1))                     # (E,)
    return E * jnp.sum(frac * mean_prob)


def moe_ffn(cfg: ModelConfig, lp: Dict, x: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """Capacity-dispatched expert FFN.  x: (B, S, d) -> (out, aux_loss)."""
    B, S, D = x.shape
    E, K = cfg.num_experts, cfg.top_k
    C = capacity(cfg, S)

    topw, tope, probs = route(cfg, lp["router"], x)
    aux = load_balance_loss(cfg, probs, tope)

    # position-in-expert via sort-based ranking: O(S*K log S*K) memory-lean
    # (a dense (S*K, E) one-hot cumsum would be terabytes at 32k x 128e)
    flat_e = tope.reshape(B, S * K)                              # (B, S*K)
    order = jnp.argsort(flat_e, axis=1, stable=True)
    sorted_e = jnp.take_along_axis(flat_e, order, axis=1)
    sk_idx = jnp.arange(S * K, dtype=jnp.int32)
    grp_start = jax.vmap(
        lambda se: jnp.searchsorted(se, se, side="left"))(sorted_e)
    rank_sorted = sk_idx[None, :] - grp_start
    inv_order = jnp.argsort(order, axis=1)
    pos = jnp.take_along_axis(rank_sorted, inv_order, axis=1)    # (B, S*K)
    keep = pos < C
    safe_pos = jnp.where(keep, pos, C)                           # C -> dropped
    tok_idx = jnp.repeat(jnp.arange(S, dtype=jnp.int32), K)      # (S*K,)

    def scatter_one(fe, p, kp):
        idx = jnp.zeros((E, C), jnp.int32).at[fe, p].set(tok_idx, mode="drop")
        val = jnp.zeros((E, C), jnp.bool_).at[fe, p].set(kp, mode="drop")
        return idx, val

    idx, valid = jax.vmap(scatter_one)(flat_e, safe_pos, keep)   # (B,E,C)

    # Dispatch: gather tokens into per-expert slots.
    xe = jax.vmap(lambda xb, ib: xb[ib])(x, idx)                 # (B,E,C,D)
    xe = xe * valid[..., None].astype(x.dtype)

    # ---- explicit sharding pins (no-ops off-mesh) -------------------------
    # Preference order: dedicated 'expert' mesh axis (perf-iteration 3-axis
    # mesh) > EP on the model axis when expert count divides > TP on the
    # per-expert FFN dim.  Weights are gathered over the FSDP 'data' axis
    # at use site (MaxText pattern).
    if cm.axis_size("expert") > 1 and E % cm.axis_size("expert") == 0:
        ep = True
        e_ax = "expert"
        f_ax = ("model" if cm.axis_size("model") > 1
                and cfg.sharding.moe_ffn_tp else None)
    elif (cfg.sharding.moe_mode == "expert"
          and E % cm.axis_size("model") == 0 and cm.axis_size("model") > 1):
        ep = True
        e_ax, f_ax = "model", None
    else:
        ep = False
        e_ax, f_ax = None, "model"
    xe = cm.constrain(xe, "batch", e_ax, None, None)
    wg = cm.constrain(lp["we_gate"], e_ax, None, f_ax)
    wu = cm.constrain(lp["we_up"], e_ax, None, f_ax)
    wd = cm.constrain(lp["we_down"], e_ax, f_ax, None)

    # Expert FFN (einsum batched over experts; E-sharded under EP).
    g = jax.nn.silu(jnp.einsum("becd,edf->becf", xe, wg))
    u = jnp.einsum("becd,edf->becf", xe, wu)
    g = cm.constrain(g, "batch", e_ax, None, f_ax)
    ye = jnp.einsum("becf,efd->becd", g * u, wd)                 # (B,E,C,D)
    d_ax = ("model" if (not ep and cfg.sharding.moe_down_rs
                        and D % cm.axis_size("model") == 0) else None)
    ye = cm.constrain(ye, "batch", e_ax, None, d_ax)

    # Combine: gather each assignment's expert output, weight, and sum over k.
    gpos = jnp.where(keep, pos, 0)
    yk = jax.vmap(lambda yb, fe, p: yb[fe, p])(ye, flat_e, gpos)  # (B,S*K,D)
    yk = yk * keep[..., None].astype(x.dtype)
    yk = yk.reshape(B, S, K, D)
    out = jnp.sum(yk * topw[..., None].astype(x.dtype), axis=2)
    return out, aux


# ---------------------------------------------------------------------------
# Blocks / entry points
# ---------------------------------------------------------------------------

def _block(cfg: ModelConfig, x: jax.Array, lp: Dict, positions: jax.Array):
    B, S, _ = x.shape
    h = cm.rms_norm(x, lp["norm0"], cfg.norm_eps)
    q, k, v = cm.qkv_project(lp, h, cfg, positions)
    attn = cm.attention(q, k, v, None, causal=True, window=cfg.sliding_window,
                        q_shard=cfg.sharding.blockwise_q_shard)
    x = x + jnp.einsum("bse,ed->bsd", attn.reshape(B, S, -1), lp["wo"])
    h = cm.rms_norm(x, lp["norm1"], cfg.norm_eps)
    y, aux = moe_ffn(cfg, lp, h)
    if cfg.n_shared_experts:
        # DeepSeek-style always-on shared expert(s) alongside the routed ones
        y = y + cm.swiglu(h, lp["ws_gate"], lp["ws_up"], lp["ws_down"])
    return x + y, aux, k, v


def _stack(cfg, x, layers, positions, remat: str, collect_kv: bool = False):
    def body(carry, lp):
        y, aux_acc = carry
        y, aux, k, v = _block(cfg, y, lp, positions)
        return (cm.seq_shard(y), aux_acc + aux), (
            (cm.kv_shard(k), cm.kv_shard(v)) if collect_kv else None)

    if remat == "full":
        body = jax.checkpoint(body)
    elif remat == "dots":
        body = jax.checkpoint(
            body, policy=jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims)
    (x, aux), ys = lax.scan(body, (x, jnp.float32(0.0)), layers)
    if collect_kv:
        return x, aux / cfg.num_layers, ys[0], ys[1]
    return x, aux / cfg.num_layers, None, None


def forward_train(params: Dict, cfg: ModelConfig, tokens: jax.Array,
                  **_) -> Tuple[jax.Array, jax.Array]:
    """Returns (hidden, aux_loss)."""
    B, S = tokens.shape
    x = jnp.take(params["embed"]["tok_embed"], tokens, axis=0)
    positions = jnp.arange(S)[None, :]
    x, aux, _, _ = _stack(cfg, x, params["layers"], positions, cfg.sharding.remat)
    return x, aux


def cache_width(cfg: ModelConfig, max_len: int) -> int:
    win = cfg.sliding_window
    return min(max_len, win) if win else max_len


def prefill(params: Dict, cfg: ModelConfig, tokens: jax.Array, max_len: int,
            **_) -> Tuple[jax.Array, Dict]:
    B, S = tokens.shape
    x = jnp.take(params["embed"]["tok_embed"], tokens, axis=0)
    positions = jnp.arange(S)[None, :]
    x, _, ks, vs = _stack(cfg, x, params["layers"], positions, "none",
                          collect_kv=True)
    W = cache_width(cfg, max_len)
    if W >= S:
        ks = jnp.pad(ks, ((0, 0), (0, 0), (0, W - S), (0, 0), (0, 0)))
        vs = jnp.pad(vs, ((0, 0), (0, 0), (0, W - S), (0, 0), (0, 0)))
    else:
        ks = jnp.roll(ks[:, :, S - W:], shift=S % W, axis=2)
        vs = jnp.roll(vs[:, :, S - W:], shift=S % W, axis=2)
    logits = cm.lm_logits(params["embed"], x[:, -1:], cfg)
    return logits, {"k": ks, "v": vs, "pos": jnp.int32(S)}


def decode_step(params: Dict, cfg: ModelConfig, token: jax.Array, cache: Dict,
                **_) -> Tuple[jax.Array, Dict]:
    """One decode step.  token: (B, 1) int32.  Returns (logits, new cache).

    Each layer writes the new token's K/V, one per row at ``pos % W``, into
    the stacked cache carried through the layer loop
    (``common.decode_layers``); the step never copies the cache.
    """
    x = jnp.take(params["embed"]["tok_embed"], token, axis=0)

    def ffn(lp, h):
        mo, _ = moe_ffn(cfg, lp, h)
        if cfg.n_shared_experts:
            mo = mo + cm.swiglu(h, lp["ws_gate"], lp["ws_up"], lp["ws_down"])
        return mo

    x, stacks = cm.decode_layers(cfg, x, params["layers"], cache, ffn)
    logits = cm.lm_logits(params["embed"], x, cfg)
    return logits, {**stacks, "pos": cache["pos"] + 1}
