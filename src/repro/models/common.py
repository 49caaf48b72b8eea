"""Shared model components: param schemas, norms, RoPE/M-RoPE, GQA attention
(full / sliding-window / cross), SwiGLU FFN, embeddings.

All models are functional: params are nested dicts of arrays, layers are
stacked on a leading axis and iterated with ``lax.scan`` so the HLO stays
small and compile times stay tractable for the 512-device dry-run.

Param schemas double as the sharding source of truth: ``init`` builds the
arrays, ``specs`` builds the matching ``PartitionSpec`` tree from the same
schema, so the two can never drift.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig

# ---------------------------------------------------------------------------
# Param schema machinery
# ---------------------------------------------------------------------------

# Logical axis names used in schemas.  The launch layer maps these to mesh
# axes via ShardingRules (see repro/launch/sharding.py).
#   'layers'  — scan-stacking axis, never sharded
#   'embed'   — d_model
#   'vocab'   — vocabulary
#   'heads'   — flattened q heads
#   'kv'      — kv heads
#   'ffn'     — FFN hidden
#   'experts' — MoE expert axis
#   None      — replicated dim


@dataclasses.dataclass(frozen=True)
class ParamSpec:
    shape: Tuple[int, ...]
    axes: Tuple[Optional[str], ...]
    init: str = "normal"          # normal | zeros | ones
    scale: float = 1.0

    def __post_init__(self):
        assert len(self.shape) == len(self.axes), (self.shape, self.axes)


def materialize(schema: Dict[str, Any], rng: jax.Array, dtype: jnp.dtype):
    """Instantiate a schema tree into a param tree of arrays."""
    leaves, treedef = jax.tree.flatten(
        schema, is_leaf=lambda x: isinstance(x, ParamSpec))
    rngs = jax.random.split(rng, len(leaves))
    out = []
    for spec, key in zip(leaves, rngs):
        if spec.init == "zeros":
            arr = jnp.zeros(spec.shape, dtype)
        elif spec.init == "ones":
            arr = jnp.ones(spec.shape, dtype)
        else:
            fan_in = spec.shape[-2] if len(spec.shape) >= 2 else spec.shape[-1]
            std = spec.scale / (fan_in ** 0.5)
            arr = (jax.random.normal(key, spec.shape, jnp.float32) * std).astype(dtype)
        out.append(arr)
    return jax.tree.unflatten(treedef, out)


def abstract_params(schema: Dict[str, Any], dtype: jnp.dtype):
    """ShapeDtypeStruct tree for dry-run lowering (no allocation)."""
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, dtype),
        schema, is_leaf=lambda x: isinstance(x, ParamSpec))


def schema_axes(schema: Dict[str, Any]):
    """Tree of logical-axes tuples mirroring the schema."""
    return jax.tree.map(lambda s: s.axes, schema,
                        is_leaf=lambda x: isinstance(x, ParamSpec))


# ---------------------------------------------------------------------------
# Sharding-constraint context
# ---------------------------------------------------------------------------
# The launch layer announces the mesh axes it lowers under; model code then
# pins activation shardings at propagation-fragile points (loss boundary,
# logits).  Empty axes (smoke tests, single-device engine) -> no-op.

_MESH_AXES: Dict[str, int] = {}


def set_mesh_axes(axes, sizes=None) -> None:
    """axes: mesh axis names; sizes: matching sizes (or a Mesh)."""
    global _MESH_AXES
    if hasattr(axes, "axis_names"):          # a Mesh
        _MESH_AXES = dict(zip(axes.axis_names, axes.devices.shape))
    elif sizes is not None:
        _MESH_AXES = dict(zip(axes, sizes))
    else:
        _MESH_AXES = {a: 0 for a in axes}    # sizes unknown: no div checks
    if not axes:
        _MESH_AXES = {}


def _fits(dim: int, axes) -> bool:
    n = 1
    for a in (axes if isinstance(axes, tuple) else (axes,)):
        n *= _MESH_AXES.get(a, 1) or 1
    return n > 0 and dim % n == 0


def constrain(x: jax.Array, *logical: Optional[str]) -> jax.Array:
    """Pin a sharding: 'batch' -> ('pod','data') axes present, 'model' ->
    model axis, None -> replicated dim.  Skips axes that don't divide."""
    if not _MESH_AXES:
        return x
    spec = []
    for dim, l in zip(x.shape, logical):
        if l == "batch":
            axes = tuple(a for a in ("pod", "data") if a in _MESH_AXES)
            spec.append(axes if axes and _fits(dim, axes) else None)
        elif l == "tp":
            axes = model_axes()
            spec.append(axes if axes and _fits(dim, axes) else None)
        elif l is not None and l in _MESH_AXES and _fits(dim, l):
            spec.append(l)
        else:
            spec.append(None)
    return lax.with_sharding_constraint(x, P(*spec))


def model_axes() -> Tuple[str, ...]:
    """The tensor-parallel axes: ('expert', 'model') on the 3-axis
    perf-iteration mesh (attention/FFN TP spans both; MoE splits them),
    ('model',) otherwise."""
    return tuple(a for a in ("expert", "model") if a in _MESH_AXES)


def tp_size() -> int:
    n = 1
    for a in model_axes():
        n *= _MESH_AXES.get(a, 1) or 1
    return n


def axis_size(name: str) -> int:
    return _MESH_AXES.get(name, 1) or 1


def constrain_spec(x: jax.Array, spec: P) -> jax.Array:
    """Raw with_sharding_constraint guarded by the mesh context."""
    if not _MESH_AXES:
        return x
    return lax.with_sharding_constraint(x, spec)


def seq_shard(x: jax.Array) -> jax.Array:
    """Megatron-style sequence parallelism for the residual stream:
    (B, S, d) -> batch on data axes, S on 'model'.  Remat-saved layer
    boundaries shrink by the model-axis size; GSPMD inserts the
    all-gather / reduce-scatter pairs around attention and FFN."""
    if x.ndim != 3 or x.shape[1] <= 1:
        return x
    return constrain(x, "batch", "tp", None)


def kv_shard(k: jax.Array) -> jax.Array:
    """Pin a (B, S, K, D) KV tensor to the decode-cache layout (KV heads on
    'model' when divisible, else head_dim) so the prefill write-out lands
    sharded instead of being assembled replicated and resharded."""
    if not _MESH_AXES or k.ndim != 4:
        return k
    B, S, K, D = k.shape
    n = tp_size()
    if n > 1 and K % n == 0:
        return constrain(k, "batch", None, "tp", None)
    if n > 1 and D % n == 0:
        return constrain(k, "batch", None, None, "tp")
    return constrain(k, "batch", None, None, None)


# ---------------------------------------------------------------------------
# KV-cache quantization (int8 per-token-per-head scales)
# ---------------------------------------------------------------------------

def kv_quantize(k: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """(..., D) bf16/f32 -> (int8 values, f32 scale over the last dim).

    Per-(token, head) absmax scaling: the decode memory term is dominated
    by streaming the cache, so int8 storage halves it vs bf16; dequant is
    elementwise and fuses into the attention kernel on TPU."""
    scale = jnp.max(jnp.abs(k.astype(jnp.float32)), axis=-1) / 127.0
    scale = jnp.maximum(scale, 1e-8)
    q = jnp.round(k.astype(jnp.float32) / scale[..., None])
    return jnp.clip(q, -127, 127).astype(jnp.int8), scale


def kv_dequantize(q: jax.Array, scale: jax.Array, dtype) -> jax.Array:
    return (q.astype(jnp.float32) * scale[..., None]).astype(dtype)


# ---------------------------------------------------------------------------
# Basic layers
# ---------------------------------------------------------------------------

def rms_norm(x: jax.Array, gamma: jax.Array, eps: float) -> jax.Array:
    dt = x.dtype
    x32 = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
    return (x32 * lax.rsqrt(var + eps)).astype(dt) * gamma


def layer_norm(x, gamma, beta, eps):
    dt = x.dtype
    x32 = x.astype(jnp.float32)
    mu = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.var(x32, axis=-1, keepdims=True)
    return ((x32 - mu) * lax.rsqrt(var + eps)).astype(dt) * gamma + beta


def swiglu(x, w_gate, w_up, w_down):
    """SwiGLU FFN: down( silu(x@gate) * (x@up) )."""
    g = jax.nn.silu(jnp.einsum("...d,df->...f", x, w_gate))
    u = jnp.einsum("...d,df->...f", x, w_up)
    return jnp.einsum("...f,fd->...d", g * u, w_down)


# ---------------------------------------------------------------------------
# RoPE (standard + M-RoPE)
# ---------------------------------------------------------------------------

def rope_freqs(head_dim: int, theta: float) -> jax.Array:
    """(head_dim/2,) inverse frequencies."""
    half = head_dim // 2
    return 1.0 / (theta ** (jnp.arange(0, half, dtype=jnp.float32) / half))


def apply_rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: (..., S, H, D); positions: broadcastable to (..., S)."""
    half = x.shape[-1] // 2
    freqs = rope_freqs(x.shape[-1], theta)                      # (half,)
    angles = positions[..., None].astype(jnp.float32) * freqs   # (..., S, half)
    cos = jnp.cos(angles)[..., None, :]                         # (..., S, 1, half)
    sin = jnp.sin(angles)[..., None, :]
    x1, x2 = x[..., :half], x[..., half:]
    out = jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def apply_mrope(x: jax.Array, positions: jax.Array, theta: float,
                sections: Tuple[int, int, int]) -> jax.Array:
    """Qwen2-VL multimodal RoPE.

    x: (B, S, H, D); positions: (3, B, S) = (temporal, height, width) ids.
    Frequency slots are partitioned into ``sections`` (t, h, w); each section
    rotates by its own position component.
    """
    half = x.shape[-1] // 2
    assert sum(sections) == half, (sections, half)
    freqs = rope_freqs(x.shape[-1], theta)                       # (half,)
    # (3, B, S, half)
    angles = positions[..., None].astype(jnp.float32) * freqs
    sec_id = jnp.concatenate([
        jnp.full((s,), i, dtype=jnp.int32) for i, s in enumerate(sections)])
    # pick the per-slot component: (B, S, half)
    angles = jnp.take_along_axis(
        jnp.moveaxis(angles, 0, -1),                             # (B,S,half,3)
        sec_id[None, None, :, None], axis=-1)[..., 0]
    cos = jnp.cos(angles)[..., None, :]
    sin = jnp.sin(angles)[..., None, :]
    x1, x2 = x[..., :half], x[..., half:]
    out = jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Attention
# ---------------------------------------------------------------------------

NEG_INF = -1e30

# Above this many logits per (batch*head) the plain einsum path would
# materialize an infeasible S x S tensor; switch to the blockwise
# (flash-style) scan.  4096^2 keeps train_4k-sized plain paths for tests.
BLOCKWISE_THRESHOLD = 2048 * 2048
BLOCK_Q = 512
BLOCK_K = 1024


def repeat_kv(k: jax.Array, groups: int) -> jax.Array:
    """(B, S, K, D) -> (B, S, K*groups, D) for GQA."""
    if groups == 1:
        return k
    return jnp.repeat(k, groups, axis=2)


def _plain_attention(q5, k, v, causal, window, q_offset):
    """Grouped-GQA einsum attention (no KV head expansion).

    q5: (B, Sq, K, G, D); k, v: (B, Sk, K, D)."""
    B, Sq, K, G, D = q5.shape
    Sk = k.shape[1]
    scale = D ** -0.5
    s = jnp.einsum("bqkgd,bskd->bkgqs", q5, k).astype(jnp.float32) * scale
    if causal:
        qpos = jnp.arange(Sq) + q_offset
        kpos = jnp.arange(Sk)
        m = kpos[None, :] <= qpos[:, None]
        if window:
            m &= kpos[None, :] > qpos[:, None] - window
        s = jnp.where(m, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1).astype(q5.dtype)
    return jnp.einsum("bkgqs,bskd->bqkgd", p, v)


def _blockwise_attention(q5, k, v, causal, window, q_offset,
                         bq=BLOCK_Q, bk=BLOCK_K, q_shard=False):
    """Flash-style two-level blocked attention (scan over q and kv chunks);
    O(bq*bk) logits transient instead of O(Sq*Sk).  Differentiable.

    q_shard=True (ShardingRules.blockwise_q_shard): shard each q block's
    row dim on the model axis and keep the K/V chunks model-replicated, so
    all per-block math is local — no partial-logit all-reduces when the
    head count doesn't divide the mesh axis."""
    B, Sq, K, G, D = q5.shape
    Sk = k.shape[1]
    bq = min(bq, Sq)
    bk = min(bk, Sk)
    pq, pk = (-Sq) % bq, (-Sk) % bk
    if pq:
        q5 = jnp.pad(q5, ((0, 0), (0, pq), (0, 0), (0, 0), (0, 0)))
    if pk:
        k = jnp.pad(k, ((0, 0), (0, pk), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pk), (0, 0), (0, 0)))
    nq, nk = q5.shape[1] // bq, k.shape[1] // bk
    qc = jnp.moveaxis(q5.reshape(B, nq, bq, K, G, D), 1, 0)
    kc = jnp.moveaxis(k.reshape(B, nk, bk, K, D), 1, 0)
    vc = jnp.moveaxis(v.reshape(B, nk, bk, K, D), 1, 0)
    scale = D ** -0.5

    n_model = tp_size() if _MESH_AXES else 1
    do_qshard = q_shard and n_model > 1 and bq % n_model == 0
    if do_qshard:
        # replicate K/V across the model axis ONCE (outside both scans);
        # constraining inside the kv loop would re-gather every block
        kc = constrain(kc, None, "batch", None, None, None)
        vc = constrain(vc, None, "batch", None, None, None)
        qc = constrain(qc, None, "batch", "tp", None, None, None)

    def q_step(_, qi):
        qblk, i = qi                                      # (B,bq,K,G,D)
        qpos = i * bq + jnp.arange(bq) + q_offset
        if do_qshard:
            qblk = constrain(qblk, "batch", "tp", None, None, None)

        @jax.checkpoint
        def kv_step(carry, kj):
            kblk, vblk, j = kj

            def compute(carry):
                m_run, l_run, acc = carry
                kpos = j * bk + jnp.arange(bk)
                s = jnp.einsum("bqkgd,bskd->bkgqs", qblk,
                               kblk).astype(jnp.float32) * scale
                if do_qshard:
                    s = constrain(s, "batch", None, None, "tp", None)
                msk = kpos[None, :] < Sk
                if causal:
                    msk &= kpos[None, :] <= qpos[:, None]
                    if window:
                        msk &= kpos[None, :] > qpos[:, None] - window
                s = jnp.where(msk, s, NEG_INF)
                m_new = jnp.maximum(m_run, jnp.max(s, axis=-1))
                p = jnp.where(msk, jnp.exp(s - m_new[..., None]), 0.0)
                alpha = jnp.exp(m_run - m_new)
                l_new = l_run * alpha + jnp.sum(p, axis=-1)
                upd = jnp.einsum("bkgqs,bskd->bkgqd", p.astype(qblk.dtype),
                                 vblk).astype(jnp.float32)
                acc = acc * alpha[..., None] + upd
                return (m_new, l_new, acc)

            if causal:
                # triangular skip: blocks entirely above the causal diagonal
                # (and entirely left of the window) do no work at runtime
                needed = j * bk <= i * bq + (bq - 1) + q_offset
                if window:
                    needed &= (j + 1) * bk - 1 > i * bq + q_offset - window
                carry = lax.cond(needed, compute, lambda c: c, carry)
            else:
                carry = compute(carry)
            return carry, None

        init = (jnp.full((B, K, G, bq), NEG_INF, jnp.float32),
                jnp.zeros((B, K, G, bq), jnp.float32),
                jnp.zeros((B, K, G, bq, D), jnp.float32))
        (m_f, l_f, acc), _ = lax.scan(
            kv_step, init, (kc, vc, jnp.arange(nk)))
        out = acc / jnp.maximum(l_f, 1e-20)[..., None]
        return None, jnp.moveaxis(out, 3, 1).astype(qblk.dtype)  # (B,bq,K,G,D)

    # checkpoint both scan levels: residuals stay O(block) instead of
    # O(Sq*Sk) during the backward pass (flash-attention remat semantics)
    q_step = jax.checkpoint(q_step)
    _, chunks = lax.scan(q_step, None, (qc, jnp.arange(nq)))
    out = jnp.moveaxis(chunks, 0, 1).reshape(B, nq * bq, K, G, D)
    if pq:
        out = out[:, :Sq]
    return out


def attention(q: jax.Array, k: jax.Array, v: jax.Array,
              mask: Optional[jax.Array], *, causal: bool,
              window: int = 0, q_offset: int = 0,
              q_shard: bool = False) -> jax.Array:
    """Softmax attention with GQA grouping.

    q: (B, Sq, H, D); k, v: (B, Sk, K, D) with H % K == 0.
    mask: optional (Sq, Sk)-broadcastable bool mask (plain path only).
    window: if >0, sliding-window causal attention of that width.
    q_offset: absolute position of q[0] relative to k[0].

    Dispatches to a flash-style blockwise scan when Sq*Sk is too large to
    materialize (prefill_32k/train paths on the production mesh).
    """
    B, Sq, H, D = q.shape
    Sk, K = k.shape[1], k.shape[2]
    q5 = q.reshape(B, Sq, K, H // K, D)
    if mask is None and Sq * Sk > BLOCKWISE_THRESHOLD:
        out = _blockwise_attention(q5, k, v, causal, window, q_offset,
                                   q_shard=q_shard)
        return out.reshape(B, Sq, H, D)
    if mask is not None:
        s = jnp.einsum("bqkgd,bskd->bkgqs", q5, k).astype(jnp.float32) \
            * (D ** -0.5)
        if causal:
            qpos = jnp.arange(Sq) + q_offset
            m = jnp.arange(Sk)[None, :] <= qpos[:, None]
            s = jnp.where(m, s, NEG_INF)
        s = jnp.where(mask, s, NEG_INF)
        p = jax.nn.softmax(s, axis=-1).astype(q.dtype)
        out = jnp.einsum("bkgqs,bskd->bqkgd", p, v)
        return out.reshape(B, Sq, H, D)
    out = _plain_attention(q5, k, v, causal, window, q_offset)
    return out.reshape(B, Sq, H, D)


def decode_attention(q: jax.Array, k_cache: jax.Array, v_cache: jax.Array,
                     valid_len: jax.Array, pin: bool = False,
                     seq_shard: bool = False) -> jax.Array:
    """Single-token decode attention against a (ring-buffer) cache.

    q: (B, 1, H, D); k_cache/v_cache: (B, W, K, D); valid_len: () or (B,)
    count of valid cache slots.  Grouped einsum — the KV cache is never
    expanded across query heads.

    pin=True (ShardingRules.decode_attn_pin) aligns q's (K, D) sharding
    with the cache layout so the contraction runs on the resident shards
    (partial logits + a small all-reduce) instead of GSPMD involuntarily
    rematerializing the whole cache every step.
    """
    B, W, K, D = k_cache.shape
    H = q.shape[2]
    q5 = q.reshape(B, 1, K, H // K, D)
    n = tp_size() if _MESH_AXES else 1
    if seq_shard and n > 1 and W % n == 0:
        # context-parallel decode: cache sharded on the sequence dim, q
        # replicated across the TP axes; softmax/out reductions over the
        # sharded axis cross the ICI as REDUCED tensors only (flash-decode
        # split-K combine semantics, cf. kernels/decode_attention.py)
        q5 = constrain(q5, "batch", None, None, None, None)
        k_cache = constrain(k_cache, "batch", "tp", None, None)
        v_cache = constrain(v_cache, "batch", "tp", None, None)
    elif pin and n > 1:
        if K % n == 0:
            q5 = constrain(q5, "batch", None, "tp", None, None)
        elif D % n == 0:
            q5 = constrain(q5, "batch", None, None, None, "tp")
    s = jnp.einsum("bqkgd,bskd->bkgqs", q5,
                   k_cache).astype(jnp.float32) * (D ** -0.5)
    if seq_shard and n > 1 and W % n == 0:
        s = constrain(s, "batch", None, None, None, "tp")
    elif pin and n > 1:
        kax = "tp" if K % n == 0 else None
        s = constrain(s, "batch", kax, None, None, None)
    valid = jnp.arange(W)[None] < jnp.reshape(valid_len, (-1, 1))   # (B, W)
    s = jnp.where(valid[:, None, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1).astype(q.dtype)
    out = jnp.einsum("bkgqs,bskd->bqkgd", p, v_cache)
    return out.reshape(B, 1, H, D)


def cache_update(kc: jax.Array, vc: jax.Array, k: jax.Array, v: jax.Array,
                 pos: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """Write one token into a ring-buffer cache.

    kc/vc: (B, W, K, D); k/v: (B, 1, K, D); pos: () uniform or (B,) per-row
    absolute positions (continuous batching serves slots at different
    depths).  Slot = pos % W.
    """
    W = kc.shape[1]
    slot = pos % W
    if pos.ndim == 0:
        kc = lax.dynamic_update_slice(kc, k, (0, slot, 0, 0))
        vc = lax.dynamic_update_slice(vc, v, (0, slot, 0, 0))
    else:
        rows = jnp.arange(kc.shape[0])
        kc = kc.at[rows, slot].set(k[:, 0])
        vc = vc.at[rows, slot].set(v[:, 0])
    return kc, vc


def decode_pos_vec(pos: jax.Array, batch: int) -> jax.Array:
    """(B, 1) position matrix from scalar or per-row pos."""
    return jnp.broadcast_to(jnp.reshape(pos, (-1, 1)), (batch, 1)).astype(jnp.int32)


def decode_layers(cfg: ModelConfig, x: jax.Array, layers: Dict,
                  cache: Dict, ffn: Callable[[Dict, jax.Array], jax.Array],
                  mrope_positions: Optional[jax.Array] = None
                  ) -> Tuple[jax.Array, Dict]:
    """The decode step's layer loop against a stacked ring-buffer cache.

    x: (B, 1, d) embedded tokens; layers: the stacked layer params; cache:
    ``k``/``v`` (L, B, W, K, D), with ``k_scale``/``v_scale`` (L, B, W, K)
    when they hold int8, and ``pos``, () uniform or (B,) per row.
    ``ffn(lp, h)`` is the layer's FFN on the normed residual ``h``.
    Returns (x, the cache's arrays).

    The stacked caches ride in the scan's carry with the layer index, not
    through its xs/ys: layer ``l`` writes its new token, one per row, at
    ``(l, row, pos % W)`` of the carried stack and attends over layer
    ``l`` read back from it.  A step writes one token per row and layer
    and never copies the cache, so a caller that donates the cache gets it
    updated in place.
    """
    B = x.shape[0]
    pos = cache["pos"]
    W = cache["k"].shape[2]
    positions = decode_pos_vec(pos, B)
    valid_len = jnp.minimum(pos + 1, W)
    slot = pos % W
    quant = "k_scale" in cache
    names = ("k", "v", "k_scale", "v_scale") if quant else ("k", "v")

    def write(stack, l, new):
        """new: (B, 1, ...) -> stack[l, row, slot[row]]."""
        if pos.ndim == 0:
            start = (l, 0, slot) + (0,) * (stack.ndim - 3)
            return lax.dynamic_update_slice(stack, new[None], start)
        return stack.at[l, jnp.arange(B), slot].set(new[:, 0])

    def body(carry, lp):
        y, stacks, l = carry
        h = rms_norm(y, lp["norm0"], cfg.norm_eps)
        q, k, v = qkv_project(lp, h, cfg, positions,
                              mrope_positions=mrope_positions)
        if quant:
            (k, k_s), (v, v_s) = kv_quantize(k), kv_quantize(v)
            new = {"k": k, "v": v, "k_scale": k_s, "v_scale": v_s}
        else:
            new = {"k": k, "v": v}
        stacks = {n: write(stacks[n], l, new[n]) for n in names}
        view = {n: lax.dynamic_index_in_dim(stacks[n], l, keepdims=False)
                for n in names}
        if quant:
            k_l = kv_dequantize(view["k"], view["k_scale"], y.dtype)
            v_l = kv_dequantize(view["v"], view["v_scale"], y.dtype)
        else:
            k_l, v_l = view["k"], view["v"]
        attn = decode_attention(q, k_l, v_l, valid_len,
                                pin=cfg.sharding.decode_attn_pin,
                                seq_shard=cfg.sharding.shard_kv_seq)
        y = y + jnp.einsum("bse,ed->bsd", attn.reshape(B, 1, -1), lp["wo"])
        y = y + ffn(lp, rms_norm(y, lp["norm1"], cfg.norm_eps))
        return (y, stacks, l + 1), None

    (x, stacks, _), _ = lax.scan(
        body, (x, {n: cache[n] for n in names}, jnp.int32(0)), layers)
    return x, stacks


# ---------------------------------------------------------------------------
# Attention block parameter schema (shared by all transformer families)
# ---------------------------------------------------------------------------

def attn_schema(cfg: ModelConfig, layers: int, cross: bool = False) -> Dict[str, ParamSpec]:
    d, hq, hkv, hd = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    L = layers
    sch: Dict[str, ParamSpec] = {
        "wq": ParamSpec((L, d, hq * hd), ("layers", "embed", "heads")),
        "wk": ParamSpec((L, d, hkv * hd), ("layers", "embed", "kv")),
        "wv": ParamSpec((L, d, hkv * hd), ("layers", "embed", "kv")),
        "wo": ParamSpec((L, hq * hd, d), ("layers", "heads", "embed")),
    }
    if cfg.qkv_bias:
        sch["bq"] = ParamSpec((L, hq * hd), ("layers", "heads"), init="zeros")
        sch["bk"] = ParamSpec((L, hkv * hd), ("layers", "kv"), init="zeros")
        sch["bv"] = ParamSpec((L, hkv * hd), ("layers", "kv"), init="zeros")
    if cfg.qk_norm:
        sch["q_norm"] = ParamSpec((L, hd), ("layers", None), init="ones")
        sch["k_norm"] = ParamSpec((L, hd), ("layers", None), init="ones")
    return sch


def qkv_project(p: Dict[str, jax.Array], x: jax.Array, cfg: ModelConfig,
                positions: jax.Array, *, rope: bool = True,
                mrope_positions: Optional[jax.Array] = None):
    """x: (B, S, d) -> q (B,S,H,D), k/v (B,S,K,D), RoPE applied."""
    B, S, _ = x.shape
    H, K, D = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    q = jnp.einsum("bsd,de->bse", x, p["wq"])
    k = jnp.einsum("bsd,de->bse", x, p["wk"])
    v = jnp.einsum("bsd,de->bse", x, p["wv"])
    if cfg.qkv_bias:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    q = q.reshape(B, S, H, D)
    k = k.reshape(B, S, K, D)
    v = v.reshape(B, S, K, D)
    if cfg.qk_norm:
        q = rms_norm(q, p["q_norm"], cfg.norm_eps)
        k = rms_norm(k, p["k_norm"], cfg.norm_eps)
    if rope:
        if cfg.mrope and mrope_positions is not None:
            q = apply_mrope(q, mrope_positions, cfg.rope_theta, cfg.mrope_sections)
            k = apply_mrope(k, mrope_positions, cfg.rope_theta, cfg.mrope_sections)
        else:
            q = apply_rope(q, positions, cfg.rope_theta)
            k = apply_rope(k, positions, cfg.rope_theta)
    return q, k, v


def ffn_schema(cfg: ModelConfig, layers: int) -> Dict[str, ParamSpec]:
    d, f, L = cfg.d_model, cfg.d_ff, layers
    return {
        "w_gate": ParamSpec((L, d, f), ("layers", "embed", "ffn")),
        "w_up": ParamSpec((L, d, f), ("layers", "embed", "ffn")),
        "w_down": ParamSpec((L, f, d), ("layers", "ffn", "embed")),
    }


def norm_schema(layers: int, d: int, name_count: int = 2) -> Dict[str, ParamSpec]:
    return {f"norm{i}": ParamSpec((layers, d), ("layers", None), init="ones")
            for i in range(name_count)}


def embed_schema(cfg: ModelConfig) -> Dict[str, ParamSpec]:
    sch = {
        "tok_embed": ParamSpec((cfg.vocab_size, cfg.d_model), ("vocab", "embed"),
                               scale=1.0),
        "final_norm": ParamSpec((cfg.d_model,), (None,), init="ones"),
    }
    if not cfg.tie_embeddings:
        sch["lm_head"] = ParamSpec((cfg.d_model, cfg.vocab_size), ("embed", "vocab"))
    return sch


def lm_logits(params: Dict[str, jax.Array], x: jax.Array, cfg: ModelConfig) -> jax.Array:
    x = constrain(x, "batch", None, None)
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    w = params["lm_head"] if not cfg.tie_embeddings else params["tok_embed"].T
    out = jnp.einsum("bsd,dv->bsv", x, w)
    return constrain(out, "batch", None, "tp")


def cross_entropy(logits: jax.Array, labels: jax.Array) -> jax.Array:
    """Mean token cross-entropy, fp32 accumulation.

    The gold logit is extracted with an iota-compare masked sum rather than
    take_along_axis: under a vocab-sharded LM head, gather-by-label forces
    GSPMD to replicate the full logits; the masked sum stays a per-shard
    fused reduce + tiny all-reduce."""
    logits = logits.astype(jnp.float32)
    logz = jax.nn.logsumexp(logits, axis=-1)
    vocab_iota = jax.lax.broadcasted_iota(jnp.int32, logits.shape,
                                          logits.ndim - 1)
    gold = jnp.sum(jnp.where(vocab_iota == labels[..., None], logits, 0.0),
                   axis=-1)
    return jnp.mean(logz - gold)


def chunked_loss(params, x, labels, cfg: ModelConfig, chunk: int) -> jax.Array:
    """Cross-entropy computed in vocab-preserving sequence chunks to bound the
    (B, S, vocab) logits transient (hillclimb knob: ShardingRules.loss_chunk)."""
    B, S, _ = x.shape
    n = max(1, S // chunk)
    xs = x.reshape(B, n, S // n, -1)
    ls = labels.reshape(B, n, S // n)

    def body(c, inp):
        xc, lc = inp
        logits = lm_logits(params, xc, cfg)
        return c + cross_entropy(logits, lc) * (1.0 / n), None

    total, _ = lax.scan(body, jnp.float32(0.0),
                        (jnp.moveaxis(xs, 1, 0), jnp.moveaxis(ls, 1, 0)))
    return total
