"""Jit'd public wrappers for the Pallas kernels.

The kernels target the TPU and run compiled by default.  ``interpret`` is
an explicit argument: callers on a CPU host (the tests, a wall-clock
calibration run without a chip) pass ``interpret=True`` themselves and get
the Pallas interpreter, validated against ref.py.
"""
from __future__ import annotations

import functools

import jax

from repro.kernels import flash_attention as _fa
from repro.kernels import decode_attention as _da
from repro.kernels import moe_gemm as _mg
from repro.kernels import rglru_scan as _rs


@functools.partial(jax.jit, static_argnames=("causal", "window", "block_q",
                                             "block_k", "interpret"))
def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    block_q: int = _fa.DEFAULT_BQ,
                    block_k: int = _fa.DEFAULT_BK,
                    interpret: bool = False):
    return _fa.flash_attention(q, k, v, causal=causal, window=window,
                               block_q=block_q, block_k=block_k,
                               interpret=interpret)


@functools.partial(jax.jit, static_argnames=("block_k", "interpret"))
def decode_attention(q, k_cache, v_cache, valid_len, *,
                     block_k: int = _da.DEFAULT_BK,
                     interpret: bool = False):
    return _da.decode_attention(q, k_cache, v_cache, valid_len,
                                block_k=block_k, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("block_s", "block_w", "interpret"))
def rglru_scan(a, b, h0, *, block_s: int = _rs.DEFAULT_BS,
               block_w: int = _rs.DEFAULT_BW,
               interpret: bool = False):
    return _rs.rglru_scan(a, b, h0, block_s=block_s, block_w=block_w,
                          interpret=interpret)


@functools.partial(jax.jit, static_argnames=("block_c", "block_f", "block_d",
                                             "interpret"))
def moe_gemm(xe, we, *, block_c: int = _mg.DEFAULT_BC,
             block_f: int = _mg.DEFAULT_BF, block_d: int = _mg.DEFAULT_BD,
             interpret: bool = False):
    return _mg.moe_gemm(xe, we, block_c=block_c, block_f=block_f,
                        block_d=block_d, interpret=interpret)
