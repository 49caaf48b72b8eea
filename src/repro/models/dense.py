"""Dense GQA decoder family.

Covers: qwen3-14b (qk_norm), qwen2-7b (qkv bias), internlm2-1.8b,
h2o-danube-3-4b (sliding-window), qwen2-vl-2b (M-RoPE + stub patch
embeddings).  One schema + three entry points:

  ``forward_train``  full causal forward -> logits (or loss via train_step)
  ``prefill``        forward + KV-cache write-out (ring-buffer layout)
  ``decode_step``    ONE token against a cache of ``seq_len`` (ring buffer),
                     written in place: one token per row, no cache copy
"""
from __future__ import annotations

import functools
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.configs.base import ModelConfig
from repro.models import common as cm

# ---------------------------------------------------------------------------
# Schema
# ---------------------------------------------------------------------------

def schema(cfg: ModelConfig) -> Dict:
    L = cfg.num_layers
    layers = {}
    layers.update(cm.attn_schema(cfg, L))
    layers.update(cm.ffn_schema(cfg, L))
    layers.update(cm.norm_schema(L, cfg.d_model, 2))
    return {"embed": cm.embed_schema(cfg), "layers": layers}


# ---------------------------------------------------------------------------
# Blocks
# ---------------------------------------------------------------------------

def _block(cfg: ModelConfig, x: jax.Array, lp: Dict, positions: jax.Array,
           mrope_positions: Optional[jax.Array]) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """One decoder layer (full sequence).  Returns (x, k, v) for caching."""
    B, S, _ = x.shape
    h = cm.rms_norm(x, lp["norm0"], cfg.norm_eps)
    q, k, v = cm.qkv_project(lp, h, cfg, positions, mrope_positions=mrope_positions)
    attn = cm.attention(q, k, v, None, causal=True, window=cfg.sliding_window,
                        q_shard=cfg.sharding.blockwise_q_shard)
    x = x + jnp.einsum("bse,ed->bsd", attn.reshape(B, S, -1), lp["wo"])
    h = cm.rms_norm(x, lp["norm1"], cfg.norm_eps)
    x = x + cm.swiglu(h, lp["w_gate"], lp["w_up"], lp["w_down"])
    return x, k, v


def _embed_inputs(params: Dict, cfg: ModelConfig, tokens: jax.Array,
                  image_embeds: Optional[jax.Array]) -> jax.Array:
    x = jnp.take(params["embed"]["tok_embed"], tokens, axis=0)
    if cfg.family == "vlm" and image_embeds is not None:
        # Stub ViT frontend: precomputed patch embeddings occupy the first
        # num_image_tokens slots of the prompt (image-first layout).
        x = lax.dynamic_update_slice(x, image_embeds.astype(x.dtype), (0, 0, 0))
    return x


def _stack(cfg: ModelConfig, x: jax.Array, layers: Dict, positions: jax.Array,
           mrope_positions: Optional[jax.Array], remat: str,
           collect_kv: bool = False):
    """Scan the layer stack; returns (x, per-layer k, per-layer v).

    collect_kv=False (training) drops the per-layer KV outputs — stacking
    them is an O(L*B*S*K*D) buffer only prefill needs."""
    def body(carry, lp):
        y, k, v = _block(cfg, carry, lp, positions, mrope_positions)
        return cm.seq_shard(y), ((cm.kv_shard(k), cm.kv_shard(v))
                                 if collect_kv else None)

    if remat == "full":
        body = jax.checkpoint(body)
    elif remat == "dots":
        body = jax.checkpoint(
            body, policy=jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims)
    x, ys = lax.scan(body, x, layers)
    if collect_kv:
        return x, ys[0], ys[1]
    return x, None, None


# ---------------------------------------------------------------------------
# Entry points
# ---------------------------------------------------------------------------

def forward_train(params: Dict, cfg: ModelConfig, tokens: jax.Array,
                  image_embeds: Optional[jax.Array] = None,
                  mrope_positions: Optional[jax.Array] = None) -> jax.Array:
    """(B, S) tokens -> final hidden states (B, S, d)."""
    B, S = tokens.shape
    x = _embed_inputs(params, cfg, tokens, image_embeds)
    positions = jnp.arange(S)[None, :]
    x, _, _ = _stack(cfg, x, params["layers"], positions, mrope_positions,
                     cfg.sharding.remat)
    return x


def init_cache(cfg: ModelConfig, batch: int, width: int, dtype) -> Dict:
    """Ring-buffer KV cache: width = sliding window (SWA) or max_len."""
    L, K, D = cfg.num_layers, cfg.num_kv_heads, cfg.head_dim
    return {
        "k": jnp.zeros((L, batch, width, K, D), dtype),
        "v": jnp.zeros((L, batch, width, K, D), dtype),
        "pos": jnp.zeros((), jnp.int32),
    }


def cache_width(cfg: ModelConfig, max_len: int) -> int:
    win = cfg.sliding_window
    return min(max_len, win) if win else max_len


def prefill(params: Dict, cfg: ModelConfig, tokens: jax.Array,
            max_len: int,
            image_embeds: Optional[jax.Array] = None,
            mrope_positions: Optional[jax.Array] = None) -> Tuple[jax.Array, Dict]:
    """Process the whole prompt; return (last-token logits, cache)."""
    B, S = tokens.shape
    x = _embed_inputs(params, cfg, tokens, image_embeds)
    positions = jnp.arange(S)[None, :]
    x, ks, vs = _stack(cfg, x, params["layers"], positions, mrope_positions,
                       "none", collect_kv=True)
    W = cache_width(cfg, max_len)
    if W >= S:
        pad = W - S
        ks = jnp.pad(ks, ((0, 0), (0, 0), (0, pad), (0, 0), (0, 0)))
        vs = jnp.pad(vs, ((0, 0), (0, 0), (0, pad), (0, 0), (0, 0)))
    else:
        # keep last W positions, laid out ring-buffer style (slot = pos % W)
        ks = jnp.roll(ks[:, :, S - W:], shift=S % W, axis=2)
        vs = jnp.roll(vs[:, :, S - W:], shift=S % W, axis=2)
    if cfg.sharding.kv_quant:
        ks, ks_s = cm.kv_quantize(ks)
        vs, vs_s = cm.kv_quantize(vs)
        cache = {"k": ks, "v": vs, "k_scale": ks_s, "v_scale": vs_s,
                 "pos": jnp.int32(S)}
    else:
        cache = {"k": ks, "v": vs, "pos": jnp.int32(S)}
    logits = cm.lm_logits(params["embed"], x[:, -1:], cfg)
    return logits, cache


def decode_step(params: Dict, cfg: ModelConfig, token: jax.Array, cache: Dict,
                mrope_positions: Optional[jax.Array] = None) -> Tuple[jax.Array, Dict]:
    """One decode step.  token: (B, 1) int32.  Returns (logits, new cache).

    Each layer writes the new token's K/V, one per row at ``pos % W``, into
    the stacked cache carried through the layer loop
    (``common.decode_layers``); the step never copies the cache, bf16 or
    int8 with scales.
    """
    x = jnp.take(params["embed"]["tok_embed"], token, axis=0)  # (B,1,d)

    def ffn(lp, h):
        return cm.swiglu(h, lp["w_gate"], lp["w_up"], lp["w_down"])

    x, stacks = cm.decode_layers(cfg, x, params["layers"], cache, ffn,
                                 mrope_positions=mrope_positions)
    logits = cm.lm_logits(params["embed"], x, cfg)
    return logits, {**stacks, "pos": cache["pos"] + 1}
