"""Static VMEM-budget checks: every Pallas kernel's default BlockSpec
working set must fit TPU v5e VMEM (~16 MiB usable) with headroom for
double buffering — the structural reasoning the §Perf Pallas hints call
for (no wall-clock trace available off-TPU)."""
import pytest

VMEM_BUDGET = 16 * 2 ** 20
DOUBLE_BUFFER = 2           # pallas pipelines in/out blocks


def test_flash_attention_vmem():
    from repro.kernels.flash_attention import DEFAULT_BQ, DEFAULT_BK
    D = 128
    working = (
        DEFAULT_BQ * D * 2            # q block bf16
        + 2 * DEFAULT_BK * D * 2      # k, v blocks
        + DEFAULT_BQ * D * 4          # acc scratch f32
        + 2 * DEFAULT_BQ * 4          # m, l
        + DEFAULT_BQ * DEFAULT_BK * 4  # logits transient
    ) * DOUBLE_BUFFER
    assert working < VMEM_BUDGET, working
    # and MXU alignment
    assert DEFAULT_BQ % 8 == 0 and DEFAULT_BK % 128 == 0


def test_decode_attention_vmem():
    from repro.kernels.decode_attention import DEFAULT_BK
    # one block holds every KV head: qwen3-32b's K=8 heads, G=8 each, D=128
    K, G, D = 8, 8, 128
    working = (
        K * G * D * 2 + 2 * DEFAULT_BK * K * D * 2
        + K * G * D * 4 + 2 * K * G * 4 + K * G * DEFAULT_BK * 4
    ) * DOUBLE_BUFFER
    assert working < VMEM_BUDGET, working


def test_rglru_scan_vmem():
    from repro.kernels.rglru_scan import DEFAULT_BS, DEFAULT_BW
    working = (3 * DEFAULT_BS * DEFAULT_BW * 4 + DEFAULT_BW * 4) \
        * DOUBLE_BUFFER
    assert working < VMEM_BUDGET
    assert DEFAULT_BW % 128 == 0


def test_moe_gemm_vmem():
    from repro.kernels.moe_gemm import DEFAULT_BC, DEFAULT_BD, DEFAULT_BF
    working = (DEFAULT_BC * DEFAULT_BD * 2 + DEFAULT_BD * DEFAULT_BF * 2
               + DEFAULT_BC * DEFAULT_BF * 4) * DOUBLE_BUFFER
    assert working < VMEM_BUDGET
    assert DEFAULT_BC % 8 == 0 and DEFAULT_BF % 128 == 0 \
        and DEFAULT_BD % 128 == 0
