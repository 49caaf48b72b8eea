"""Operations and bytes that any implementation must spend, from shapes.

Counted from the configuration's own keys (the file under ``configs/``),
never from the program.  Only necessary work counts: no padded cache
width, no empty slots, no dropped-token or capacity waste, so a later
change that skips such work cannot read above its roofline.

Conventions: a multiply-add is 2 operations; weights and cache are
``dtype_bytes`` each (bfloat16: 2); causal attention over S positions
costs 2*S^2*q_dim per layer (QK^T and PV, each half of the full square).
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Iterable


@dataclasses.dataclass(frozen=True)
class Shapes:
    layers: int
    d: int
    heads: int
    kv_heads: int
    head_dim: int
    ffn: int                 # dense FFN width (0 in an all-expert model)
    vocab: int
    experts: int = 0
    top_k: int = 0
    expert_ffn: int = 0
    tied: bool = False
    dtype_bytes: int = 2

    @property
    def q_dim(self) -> int:
        return self.heads * self.head_dim

    @property
    def kv_dim(self) -> int:
        return self.kv_heads * self.head_dim

    @classmethod
    def from_config(cls, c: Dict) -> "Shapes":
        heads = c["num_attention_heads"]
        experts = int(c.get("num_experts", 0) or 0)
        return cls(layers=c["num_hidden_layers"], d=c["hidden_size"],
                   heads=heads, kv_heads=c["num_key_value_heads"],
                   head_dim=c.get("head_dim") or c["hidden_size"] // heads,
                   ffn=0 if experts else c["intermediate_size"],
                   vocab=c["vocab_size"], experts=experts,
                   top_k=int(c.get("num_experts_per_tok", 0) or 0),
                   expert_ffn=int(c.get("moe_intermediate_size", 0) or 0),
                   tied=bool(c.get("tie_word_embeddings", False)))


# -- parameters ---------------------------------------------------------------

def attn_params(s: Shapes) -> int:
    return 2 * s.d * s.q_dim + 2 * s.d * s.kv_dim


def ffn_params(s: Shapes, experts: int) -> int:
    """FFN parameters of one layer when ``experts`` experts are counted."""
    if s.experts:
        return experts * 3 * s.d * s.expert_ffn + s.d * s.experts
    return 3 * s.d * s.ffn


def layer_params_active(s: Shapes) -> int:
    """Parameters one token touches in one layer."""
    return attn_params(s) + ffn_params(s, s.top_k)


def head_params(s: Shapes) -> int:
    return s.d * s.vocab


# -- prefill ------------------------------------------------------------------

def prefill_flops(s: Shapes, S: int) -> float:
    """One prompt of S tokens: every token through the active weights,
    causal attention, and the LM head for the last position only."""
    return (2.0 * s.layers * layer_params_active(s) * S
            + 2.0 * S * S * s.q_dim * s.layers
            + 2.0 * head_params(s))


def prefill_bytes(s: Shapes, S: int) -> float:
    """Weights read once (in an expert layer, only the top-k experts any
    token is certain to need), the prompt's embedding rows, and the K/V
    the prompt writes."""
    weights = s.layers * (attn_params(s) + ffn_params(s, s.top_k)) \
        + head_params(s)
    return s.dtype_bytes * (weights + S * s.d
                            + 2.0 * s.layers * S * s.kv_dim)


# -- decode -------------------------------------------------------------------

def decode_flops(s: Shapes, valid_lens: Iterable[int]) -> float:
    """One decode step over the active slots, each attending to its own
    valid length (the new token included)."""
    lens = list(valid_lens)
    n = len(lens)
    return (2.0 * n * (s.layers * layer_params_active(s) + head_params(s))
            + 4.0 * s.q_dim * s.layers * float(sum(lens)))


def decode_bytes(s: Shapes, valid_lens: Iterable[int]) -> float:
    """A dense model's decode step: every weight read once, K/V read at
    the valid lengths of the active slots, and the new K/V written.
    Not defined for an expert model, whose experts hit per step no
    counter reports."""
    if s.experts:
        raise ValueError("decode bytes of an expert layer need a routing "
                         "counter")
    lens = list(valid_lens)
    kv_row = 2.0 * s.layers * s.kv_dim          # K and V, every layer
    weights = s.layers * layer_params_active(s) + head_params(s)
    return s.dtype_bytes * (weights + len(lens) * s.d
                            + kv_row * float(sum(lens))
                            + kv_row * len(lens))


def least_seconds(flops: float, nbytes: float, flops_per_s: float,
                  bytes_per_s: float) -> float:
    """The roofline's least time: the larger of the two bounds."""
    return max(flops / flops_per_s, nbytes / bytes_per_s)
