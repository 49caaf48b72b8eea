"""Grouped (per-expert) GEMM — Pallas TPU kernel.

ye[e] = xe[e] @ we[e] for E experts at once, the compute core of the
capacity-dispatched MoE layer (models/moe.py).  Grid
(E, C/bc, F/bf, D/bd) with the contraction block as the minor sequential
dimension accumulating into fp32 VMEM scratch; block shapes are
(8, 128)-aligned for the MXU.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BC = 128
DEFAULT_BF = 128
DEFAULT_BD = 512


def _kernel(x_ref, w_ref, o_ref, acc_ref, *, nd: int):
    l = pl.program_id(3)

    @pl.when(l == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = x_ref[0]                                   # (bc, bd)
    w = w_ref[0]                                   # (bd, bf)
    acc_ref[...] += jax.lax.dot(
        x, w, preferred_element_type=jnp.float32)

    @pl.when(l == nd - 1)
    def _finish():
        o_ref[0] = acc_ref[...].astype(o_ref.dtype)


def moe_gemm(xe: jax.Array, we: jax.Array, *, block_c: int = DEFAULT_BC,
             block_f: int = DEFAULT_BF, block_d: int = DEFAULT_BD,
             interpret: bool = False) -> jax.Array:
    """xe: (E, C, D); we: (E, D, F) -> (E, C, F)."""
    E, C, D = xe.shape
    F = we.shape[2]
    bc, bf, bd = min(block_c, C), min(block_f, F), min(block_d, D)
    pc, pf, pd = (-C) % bc, (-F) % bf, (-D) % bd
    if pc or pd:
        xe = jnp.pad(xe, ((0, 0), (0, pc), (0, pd)))
    if pd or pf:
        we = jnp.pad(we, ((0, 0), (0, pd), (0, pf)))
    nc, nf, nd = xe.shape[1] // bc, we.shape[2] // bf, xe.shape[2] // bd

    out = pl.pallas_call(
        functools.partial(_kernel, nd=nd),
        grid=(E, nc, nf, nd),
        in_specs=[
            pl.BlockSpec((1, bc, bd), lambda e, i, j, l: (e, i, l)),
            pl.BlockSpec((1, bd, bf), lambda e, i, j, l: (e, l, j)),
        ],
        out_specs=pl.BlockSpec((1, bc, bf), lambda e, i, j, l: (e, i, j)),
        out_shape=jax.ShapeDtypeStruct((E, xe.shape[1], we.shape[2]), xe.dtype),
        scratch_shapes=[pltpu.VMEM((bc, bf), jnp.float32)],
        interpret=interpret,
    )(xe, we)
    if pc or pf:
        out = out[:, :C, :F]
    return out
