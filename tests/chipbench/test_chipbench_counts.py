"""FLOP and byte counts of the benchmark against hand-worked numbers."""
import os
import sys

sys.path.insert(0, os.path.abspath(os.path.join(os.path.dirname(__file__),
                                                "..", "..")))

import pytest  # noqa: E402

from chipbench import counts, spec  # noqa: E402


# Qwen3-30B-A3B's published widths (hf:Qwen/Qwen3-30B-A3B), 8 of its 48
# layers: the counts of an expert model, which no cell serves yet.
QWEN3_MOE = {"hidden_size": 2048, "moe_intermediate_size": 768,
             "num_attention_heads": 32, "num_key_value_heads": 4,
             "head_dim": 128, "num_hidden_layers": 8, "num_experts": 128,
             "num_experts_per_tok": 8, "vocab_size": 151_936,
             "tie_word_embeddings": False}


def _shapes(name):
    if name == "qwen3-moe-30b-a3b":
        return counts.Shapes.from_config(QWEN3_MOE)
    return counts.Shapes.from_config(spec.load_json(os.path.join(
        spec.HERE, "configs", name + ".json")))


def test_internlm2_parameters():
    s = _shapes("internlm2-1.8b")
    # attention: wq + wo = 2 * 2048 * 2048, wk + wv = 2 * 2048 * 1024
    assert counts.attn_params(s) == 8_388_608 + 4_194_304
    # SwiGLU: 3 * 2048 * 8192
    assert counts.ffn_params(s, 0) == 50_331_648
    assert counts.layer_params_active(s) == 62_914_560
    assert counts.head_params(s) == 2048 * 92_544
    # whole model in bf16: 24 layers + head + embedding = 3.78 GB
    total = 24 * 62_914_560 + 2 * 2048 * 92_544
    assert 2 * total == pytest.approx(3.778e9, rel=1e-3)


def test_qwen3_moe_parameters():
    s = _shapes("qwen3-moe-30b-a3b")
    assert (s.q_dim, s.kv_dim) == (4096, 512)
    assert counts.attn_params(s) == 2 * 2048 * 4096 + 2 * 2048 * 512
    # top-8 experts of width 768 plus the 128-wide router
    assert counts.ffn_params(s, 8) == 8 * 3 * 2048 * 768 + 2048 * 128
    # all 128 experts of one layer plus attention: 1.246 GB in bf16
    whole = counts.attn_params(s) + counts.ffn_params(s, 128)
    assert 2 * whole == pytest.approx(1.246e9, rel=1e-3)


def test_prefill_flops_internlm2_1024():
    s = _shapes("internlm2-1.8b")
    S = 1024
    want = (2 * 24 * 62_914_560 * S          # weights, every token
            + 2 * S * S * 2048 * 24          # causal QK^T and PV
            + 2 * 2048 * 92_544)             # LM head, last position
    assert counts.prefill_flops(s, S) == want
    assert want == pytest.approx(3.1959e12, rel=1e-4)


def test_prefill_bytes_moe_counts_only_certain_experts():
    s = _shapes("qwen3-moe-30b-a3b")
    S = 512
    layer = counts.attn_params(s) + 8 * 3 * 2048 * 768 + 2048 * 128
    want = 2 * (8 * layer + 2048 * 151_936 + S * 2048
                + 2 * 8 * S * 512)
    assert counts.prefill_bytes(s, S) == want


def test_decode_bytes_count_valid_lengths_not_width():
    s = _shapes("internlm2-1.8b")
    lens = [300, 1000]
    kv_row = 2 * 24 * 1024                    # K and V of one position
    want = 2 * (24 * 62_914_560 + 2048 * 92_544 + 2 * 2048
                + kv_row * 1300 + kv_row * 2)
    assert counts.decode_bytes(s, lens) == want
    # two slots of 1,288 allocated positions would read more: not counted
    assert counts.decode_bytes(s, lens) < counts.decode_bytes(s, [1288] * 2)


def test_decode_flops_and_moe_bytes_refused():
    s = _shapes("internlm2-1.8b")
    want = 2 * 3 * (24 * 62_914_560 + 2048 * 92_544) + 4 * 2048 * 24 * 30
    assert counts.decode_flops(s, [5, 10, 15]) == want
    with pytest.raises(ValueError):
        counts.decode_bytes(_shapes("qwen3-moe-30b-a3b"), [10])


def test_least_seconds_is_the_larger_bound():
    assert counts.least_seconds(197e12, 1.0, 197e12, 819e9) == 1.0
    assert counts.least_seconds(1.0, 819e9 * 2, 197e12, 819e9) == 2.0
