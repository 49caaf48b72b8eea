"""GQA decode attention (flash-decode) — Pallas TPU kernel.

One query token per sequence against a ring-buffer KV cache.  Grid
(B, num_kv_blocks): each program owns one batch row, all K kv heads and
the G = H/K query heads that share each; the KV block index is the minor
(sequential) dimension with running (max, sum, acc) in VMEM scratch —
i.e. the memory-bound phase streams the cache exactly once at HBM speed.

Validity masking uses the per-row ``valid_len`` (ring buffers are valid on
a prefix of slots; see models/common.KV semantics).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BK = 512
NEG_INF = -1e30


def _kernel(vl_ref, q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
            scale: float, bk: int, nk: int, width: int):
    b = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    _, K, G, _ = q_ref.shape
    pos = j * bk + jax.lax.broadcasted_iota(jnp.int32, (G, bk), 1)
    mask = (pos < vl_ref[b]) & (pos < width)
    for h in range(K):                 # static: all KV heads in one block
        q = q_ref[0, h].astype(jnp.float32)              # (G, D)
        k = k_ref[0, :, h, :].astype(jnp.float32)        # (bk, D)
        v = v_ref[0, :, h, :].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ()))) * scale
        s = jnp.where(mask, s, NEG_INF)                  # (G, bk)

        m_prev = m_ref[h]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
        p = jnp.where(mask, jnp.exp(s - m_new[:, None]), 0.0)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[h] = l_ref[h] * alpha + jnp.sum(p, axis=1)
        acc_ref[h] = acc_ref[h] * alpha[:, None] + jax.lax.dot(p, v)
        m_ref[h] = m_new

    @pl.when(j == nk - 1)
    def _finish():
        l = jnp.maximum(l_ref[...], 1e-20)
        o_ref[0] = (acc_ref[...] / l[..., None]).astype(o_ref.dtype)


def decode_attention(q: jax.Array, k_cache: jax.Array, v_cache: jax.Array,
                     valid_len: jax.Array, *, block_k: int = DEFAULT_BK,
                     interpret: bool = False) -> jax.Array:
    """q: (B, H, D); k_cache/v_cache: (B, W, K, D); valid_len: (B,) int32.

    Returns (B, H, D)."""
    B, H, D = q.shape
    W, K = k_cache.shape[1], k_cache.shape[2]
    assert H % K == 0
    G = H // K
    bk = min(block_k, W)
    pad = (-W) % bk
    kc, vc = k_cache, v_cache
    if pad:
        kc = jnp.pad(kc, ((0, 0), (0, pad), (0, 0), (0, 0)))
        vc = jnp.pad(vc, ((0, 0), (0, pad), (0, 0), (0, 0)))
    nk = kc.shape[1] // bk

    qg = q.reshape(B, K, G, D)
    kernel = functools.partial(_kernel, scale=D ** -0.5, bk=bk, nk=nk,
                               width=W)
    # (K, D) and (G, D) are whole trailing dims of their arrays, which the
    # TPU lowering accepts at any head count; valid_len rides in SMEM.
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B, nk),
            in_specs=[
                pl.BlockSpec((1, K, G, D), lambda b, j, vl: (b, 0, 0, 0)),
                pl.BlockSpec((1, bk, K, D), lambda b, j, vl: (b, j, 0, 0)),
                pl.BlockSpec((1, bk, K, D), lambda b, j, vl: (b, j, 0, 0)),
            ],
            out_specs=pl.BlockSpec((1, K, G, D),
                                   lambda b, j, vl: (b, 0, 0, 0)),
            scratch_shapes=[pltpu.VMEM((K, G, D), jnp.float32),
                            pltpu.VMEM((K, G), jnp.float32),
                            pltpu.VMEM((K, G), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct(qg.shape, q.dtype),
        interpret=interpret,
    )(valid_len.astype(jnp.int32), qg, kc, vc)
    return out.reshape(B, H, D)
