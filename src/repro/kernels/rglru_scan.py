"""RG-LRU gated linear recurrence — Pallas TPU kernel.

h_t = a_t * h_{t-1} + b_t over the sequence, blocked (batch, width) with the
sequence-block index as the minor (sequential) grid dimension; the running
state h lives in VMEM scratch across sequence blocks, so each (B, W) tile
streams its gates once from HBM — the recurrence is purely memory-bound,
matching the RecurrentOp model in core/operators.py.

(The pure-jnp path uses ``lax.associative_scan`` — log-depth but 3x the HBM
traffic; the kernel is the linear-traffic alternative the paper's operator
DB would profile.)
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BB = 8
DEFAULT_BS = 128
DEFAULT_BW = 128


def _kernel(a_ref, b_ref, h0_ref, o_ref, h_ref, *, bs: int):
    s = pl.program_id(2)

    @pl.when(s == 0)
    def _init():
        h_ref[...] = h0_ref[0].astype(jnp.float32)      # (1, bw)

    def step(t, h):
        row = pl.ds(t, 1)
        h = (a_ref[0, row, :].astype(jnp.float32) * h
             + b_ref[0, row, :].astype(jnp.float32))    # (1, bw)
        o_ref[0, row, :] = h.astype(o_ref.dtype)
        return h

    h_ref[...] = jax.lax.fori_loop(0, bs, step, h_ref[...])


def rglru_scan(a: jax.Array, b: jax.Array, h0: jax.Array, *,
               block_s: int = DEFAULT_BS, block_w: int = DEFAULT_BW,
               interpret: bool = False) -> jax.Array:
    """a, b: (B, S, W); h0: (B, W).  Returns all states (B, S, W).

    Batch is handled one row per program (bb=1) so the inner loop is a pure
    (bw,)-vector recurrence on the VPU."""
    B, S, W = a.shape
    bs = min(block_s, S)
    bw = min(block_w, W)
    pad_s = (-S) % bs
    pad_w = (-W) % bw
    if pad_s or pad_w:
        a = jnp.pad(a, ((0, 0), (0, pad_s), (0, pad_w)))
        b = jnp.pad(b, ((0, 0), (0, pad_s), (0, pad_w)))
    if pad_w:
        h0 = jnp.pad(h0, ((0, 0), (0, pad_w)))
    ns = a.shape[1] // bs
    nw = a.shape[2] // bw

    # h0 as (B, 1, W): a (1, bw) block's last two dims then meet the TPU
    # lowering's tiling rule at any batch size.
    out = pl.pallas_call(
        functools.partial(_kernel, bs=bs),
        grid=(B, nw, ns),
        in_specs=[
            pl.BlockSpec((1, bs, bw), lambda i, w, s: (i, s, w)),
            pl.BlockSpec((1, bs, bw), lambda i, w, s: (i, s, w)),
            pl.BlockSpec((1, 1, bw), lambda i, w, s: (i, 0, w)),
        ],
        out_specs=pl.BlockSpec((1, bs, bw), lambda i, w, s: (i, s, w)),
        out_shape=jax.ShapeDtypeStruct(a.shape, a.dtype),
        scratch_shapes=[pltpu.VMEM((1, bw), jnp.float32)],
        interpret=interpret,
    )(a, b, h0[:, None, :])
    if pad_s or pad_w:
        out = out[:, :S, :W]
    return out
