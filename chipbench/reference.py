"""Plain float32 reference of the served models, and its int8 control.

Straightforward ``jax.numpy`` at ``Precision.HIGHEST``: no kernels, no
cache, no capacity.  Two blocks, chosen by the configuration's keys:

    dense GQA     RMSNorm -> q,k,v -> RoPE (rotate-half) -> causal softmax
                  attention -> o; RMSNorm -> SwiGLU
    Qwen3-MoE     the same attention with a per-head RMSNorm on q and k
                  (``qk_norm``) before RoPE; the FFN is a softmax router,
                  the top ``num_experts_per_tok`` renormalised to sum to 1,
                  and the weighted sum of those experts' SwiGLU outputs.
                  Every token reaches its experts: nothing is dropped.

It imports nothing of the program and takes none of its arrays: the
weights are made again from the seed by :mod:`chipbench.weights`, one
layer at a time, so that a reference over long prompts fits beside its
activations.  Leaf names (``wq``, ``we_gate``, ...) are the names under
which the benchmark made them.

``quant=True`` is the control: every matmul's operands rounded to int8
(weights per output channel, activations per row, symmetric, float32
accumulation) — the precision one step below the served bfloat16.
"""
from __future__ import annotations

import functools
from typing import Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from chipbench import weights as wmod

HI = lax.Precision.HIGHEST


def _q8(x: jax.Array, axis: int) -> jax.Array:
    """Symmetric int8 rounding along ``axis`` (the contracted one)."""
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 127.0
    s = jnp.maximum(s, 1e-30)
    return jnp.clip(jnp.round(x / s), -127, 127) * s


def mm(x: jax.Array, w: jax.Array, quant: bool) -> jax.Array:
    """x (..., k) @ w (k, n)."""
    if quant:
        x, w = _q8(x, -1), _q8(w, 0)
    return jnp.einsum("...k,kn->...n", x, w, precision=HI)


def rms(x: jax.Array, g: jax.Array, eps: float) -> jax.Array:
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * g


def rope(x: jax.Array, theta: float) -> jax.Array:
    """x (S, H, D), positions 0..S-1, rotate-half convention."""
    S, _, D = x.shape
    half = D // 2
    inv = 1.0 / theta ** (np.arange(half, dtype=np.float32) / half)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention_one(q, k, v):
    """One sequence: q (S, H, D), k/v (S, K, D); causal, GQA by repeat."""
    S, H, D = q.shape
    g = H // k.shape[1]
    k, v = jnp.repeat(k, g, axis=1), jnp.repeat(v, g, axis=1)
    s = jnp.einsum("qhd,khd->hqk", q, k, precision=HI) / np.sqrt(D)
    mask = jnp.tril(jnp.ones((S, S), bool))
    s = jnp.where(mask[None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("hqk,khd->qhd", p, v, precision=HI)


def _swiglu(x, wg, wu, wd, quant):
    return mm(jax.nn.silu(mm(x, wg, quant)) * mm(x, wu, quant), wd, quant)


def _moe(c: Dict, x: jax.Array, w: Dict, quant: bool) -> jax.Array:
    """x (T, d): route every token to its top-k experts, no capacity."""
    E, k = c["num_experts"], c["num_experts_per_tok"]
    probs = jax.nn.softmax(jnp.einsum("td,de->te", x, w["router"],
                                      precision=HI), axis=-1)
    topw, tope = lax.top_k(probs, k)
    if c.get("norm_topk_prob", True):
        topw = topw / jnp.sum(topw, axis=-1, keepdims=True)
    gates = jnp.zeros_like(probs).at[
        jnp.arange(x.shape[0])[:, None], tope].set(topw)        # (T, E)

    def expert(acc, e):
        y = _swiglu(x, w["we_gate"][e], w["we_up"][e], w["we_down"][e],
                    quant)
        return acc + gates[:, e][:, None] * y, None

    out, _ = lax.scan(expert, jnp.zeros_like(x), jnp.arange(E))
    return out


def block(c: Dict, h: jax.Array, w: Dict, quant: bool) -> jax.Array:
    """One decoder layer over a batch of sequences h (N, S, d)."""
    N, S, d = h.shape
    H, K = c["num_attention_heads"], c["num_key_value_heads"]
    D = c.get("head_dim") or d // H
    eps, theta = c["rms_norm_eps"], c["rope_theta"]
    x = rms(h, w["norm0"], eps)
    q = mm(x, w["wq"], quant).reshape(N, S, H, D)
    k = mm(x, w["wk"], quant).reshape(N, S, K, D)
    v = mm(x, w["wv"], quant).reshape(N, S, K, D)
    if "q_norm" in w:
        q, k = rms(q, w["q_norm"], eps), rms(k, w["k_norm"], eps)
    rp = functools.partial(rope, theta=theta)
    q, k = jax.vmap(rp)(q), jax.vmap(rp)(k)
    a = lax.map(lambda qkv: _attention_one(*qkv), (q, k, v))
    h = h + mm(a.reshape(N, S, H * D), w["wo"], quant)
    x = rms(h, w["norm1"], eps)
    if c.get("num_experts"):
        y = _moe(c, x.reshape(N * S, d), w, quant).reshape(N, S, d)
    else:
        y = _swiglu(x, w["w_gate"], w["w_up"], w["w_down"], quant)
    return h + y


def _logits(c: Dict, top: Dict, h: jax.Array, quant: bool) -> jax.Array:
    x = rms(h, top["final_norm"], c["rms_norm_eps"])
    head = top["lm_head"] if "lm_head" in top else top["tok_embed"].T
    return mm(x, head, quant)


def _gaps(ref: jax.Array, served: jax.Array, ctrl: jax.Array):
    """Per position: how far the served token's and the control's first
    token's reference logits lie below the reference's best."""
    best = jnp.max(ref, axis=-1)
    got = jnp.take_along_axis(ref, served[:, None], axis=-1)[:, 0]
    pick = jnp.argmax(ctrl, axis=-1)
    alt = jnp.take_along_axis(ref, pick[:, None], axis=-1)[:, 0]
    return best - got, best - alt


def served_gaps(c: Dict, shapes: Dict, seed: int,
                seqs: Sequence[Tuple[List[int], List[int]]],
                control: bool = False, rows_per_block: int = 8192,
                length: int = 0, out_len: int = 0
                ) -> Dict[str, List[np.ndarray]]:
    """Run the reference over each (prompt, served tokens) and return, per
    sequence, the gap of each served token below the reference's best at
    its position (``"program"``), and with ``control`` the same for the
    int8 control's first choice there (``"control"``).

    Sequences are padded to ``length`` positions (a cell's longest prompt
    and output; at least the longest given) and run in blocks of at most
    ``rows_per_block`` positions, layer by layer: each layer's weights are
    made once and applied to every block.  ``out_len`` (a cell's longest
    output) fixes the rows read per sequence, so that every run of a cell
    compiles the same programs."""
    key = wmod.seed_key(seed)
    L = c["num_hidden_layers"]
    O = max([out_len] + [len(o) for _, o in seqs])
    S = max([length] + [len(p) - 1 + O for p, _ in seqs])
    per = max(1, rows_per_block // S)
    blocks = [list(range(i, min(i + per, len(seqs))))
              for i in range(0, len(seqs), per)]
    toks = np.zeros((len(seqs), S), np.int32)
    for i, (p, o) in enumerate(seqs):
        full = list(p) + list(o[:-1])
        toks[i, :len(full)] = full

    top = jax.jit(functools.partial(wmod.top_leaves, shapes))(key)
    layer_w = jax.jit(functools.partial(wmod.layer_leaves, shapes))
    run_block = jax.jit(functools.partial(block, c), static_argnames="quant")
    pad = lambda b: b + [b[-1]] * (per - len(b))      # one compiled shape
    hs = [jnp.take(top["tok_embed"], toks[pad(b)], axis=0) for b in blocks]
    hq = list(hs) if control else None
    for layer in range(L):
        w = layer_w(key, jnp.uint32(layer))
        hs = [run_block(h, w, quant=False) for h in hs]
        if control:
            hq = [run_block(h, w, quant=True) for h in hq]
        del w

    @functools.partial(jax.jit, static_argnames="quant")
    def rows(top, h, start, served, quant_h, quant):
        """The O positions from ``start`` that predict the served tokens
        (``top`` is an argument: closed over, its 1.5 GB would be baked
        into the program as constants)."""
        pick = lambda x: lax.dynamic_slice(x, (start, 0), (O, x.shape[1]))
        ref = _logits(c, top, pick(h), False)
        ctrl = _logits(c, top, pick(quant_h), True) if quant else ref
        return _gaps(ref, served, ctrl)

    out: Dict[str, List[np.ndarray]] = {"program": [], "control": []}
    for bi, b in enumerate(blocks):
        for j, i in enumerate(b):
            p, o = seqs[i]
            served = np.zeros(O, np.int32)
            served[:len(o)] = o
            g, gc = rows(top, hs[bi][j], len(p) - 1, jnp.asarray(served),
                         hq[bi][j] if control else hs[bi][j], quant=control)
            out["program"].append(np.asarray(g[:len(o)], np.float64))
            out["control"].append(np.asarray(gc[:len(o)], np.float64))
    if not control:
        del out["control"]
    return out


def numbers(gaps: List[np.ndarray]) -> Dict[str, float]:
    """What a side's per-token gaps are judged by: the widest
    (``logit_gap``), the mean over every compared token
    (``logit_gap_mean``), and the share of tokens that are not the
    reference's first choice (``off_top_share``)."""
    g = np.concatenate(gaps) if gaps else np.zeros(0)
    if not g.size:
        return {}
    return {"logit_gap": float(g.max()), "logit_gap_mean": float(g.mean()),
            "off_top_share": float(np.mean(g > 0))}
