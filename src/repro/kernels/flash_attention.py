"""Causal GQA flash attention (prefill) — Pallas TPU kernel.

Blockwise online-softmax attention: grid (B, H, num_q_blocks, num_kv_blocks)
with the KV block index as the minor (sequential) grid dimension; running
(max, sum, acc) live in VMEM scratch across KV iterations.  GQA is handled
in the BlockSpec index maps (kv head = q head // group), sliding windows by
masking and by skipping fully-out-of-window KV blocks.

VMEM working set per step: q (bq, D) + k,v (bk, D) + acc (bq, D) fp32 +
logits (bq, bk) fp32 — with bq = bk = 512, D = 128 that is ~1.4 MiB, well
inside the ~16 MiB v5e VMEM budget, and the (8, 128)-aligned block shapes
keep the MXU fed.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BQ = 512
DEFAULT_BK = 512
NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
            scale: float, causal: bool, window: int, sq: int, sk: int,
            bq: int, bk: int, nk: int):
    i = pl.program_id(2)      # q block
    j = pl.program_id(3)      # kv block (sequential, minor)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q = q_ref[0, 0].astype(jnp.float32)          # (bq, D)
    k = k_ref[0, 0].astype(jnp.float32)          # (bk, D)
    v = v_ref[0, 0].astype(jnp.float32)

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ()))) * scale  # (bq, bk)

    qpos = i * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0) \
        + (sk - sq)                               # align q to the END of k
    kpos = j * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    mask = (kpos <= qpos) if causal else (kpos >= 0)
    mask &= kpos < sk                             # key padding
    if window:
        mask &= kpos > qpos - window
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
    p = jnp.where(mask, jnp.exp(s - m_new[:, None]), 0.0)
    alpha = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1)
    acc_ref[...] = acc_ref[...] * alpha[:, None] + jax.lax.dot(p, v)
    m_ref[...] = m_new

    @pl.when(j == nk - 1)
    def _finish():
        l = jnp.maximum(l_ref[...], 1e-20)
        o_ref[0, 0] = (acc_ref[...] / l[:, None]).astype(o_ref.dtype)


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, window: int = 0,
                    block_q: int = DEFAULT_BQ, block_k: int = DEFAULT_BK,
                    interpret: bool = False) -> jax.Array:
    """q: (B, Sq, H, D); k, v: (B, Sk, K, D).  Returns (B, Sq, H, D)."""
    B, Sq, H, D = q.shape
    Sk, K = k.shape[1], k.shape[2]
    assert H % K == 0, (H, K)
    G = H // K
    bq, bk = min(block_q, Sq), min(block_k, Sk)

    pad_q = (-Sq) % bq
    pad_k = (-Sk) % bk
    qt = jnp.moveaxis(q, 2, 1)                    # (B, H, Sq, D)
    kt = jnp.moveaxis(k, 2, 1)                    # (B, K, Sk, D)
    vt = jnp.moveaxis(v, 2, 1)
    if pad_q:
        qt = jnp.pad(qt, ((0, 0), (0, 0), (0, pad_q), (0, 0)))
    if pad_k:
        kt = jnp.pad(kt, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
        vt = jnp.pad(vt, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
    nq = qt.shape[2] // bq
    nk = kt.shape[2] // bk

    kernel = functools.partial(
        _kernel, scale=D ** -0.5, causal=causal, window=window,
        sq=Sq, sk=Sk, bq=bq, bk=bk, nk=nk)

    out = pl.pallas_call(
        kernel,
        grid=(B, H, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, bq, D), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, bk, D), lambda b, h, i, j: (b, h // G, j, 0)),
            pl.BlockSpec((1, 1, bk, D), lambda b, h, i, j: (b, h // G, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, D), lambda b, h, i, j: (b, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct(qt.shape, q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, D), jnp.float32),
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq,), jnp.float32)],
        interpret=interpret,
    )(qt, kt, vt)

    if pad_q:
        out = out[:, :, :Sq]
    return jnp.moveaxis(out, 1, 2)
