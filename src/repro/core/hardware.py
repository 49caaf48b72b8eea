"""Hardware platform profiles (§4.4 "Hardware specifications").

The paper ships per-GPU-SKU profiles (Ampere..Blackwell).  Our primary
target is TPU v5e (the constants given for the roofline deliverable);
v5p and an H100-like profile are kept so the multi-platform machinery of
the PerfDatabase is real, not vestigial.
"""
from __future__ import annotations

import dataclasses
from typing import Dict


@dataclasses.dataclass(frozen=True)
class Platform:
    name: str
    peak_flops_bf16: float          # FLOP/s per chip
    peak_flops_fp8: float
    hbm_bw: float                   # bytes/s
    hbm_capacity: float             # bytes
    link_bw: float                  # bytes/s per ICI/NVLink link (one dir)
    links_per_axis: int             # links usable along one mesh axis
    inter_pod_bw: float             # bytes/s per chip across pods / nodes
    launch_overhead: float          # seconds per kernel launch
    hop_latency: float              # seconds per interconnect hop
    # matmul tile geometry for the alignment-efficiency curve (MXU on TPU:
    # 8 sublanes x 128 lanes; SIMD CPUs are ~8x8)
    tile_m: int = 8
    tile_n: int = 128

    def matmul_peak(self, dtype: str) -> float:
        return self.peak_flops_fp8 if dtype in ("fp8", "int8") else self.peak_flops_bf16


TPU_V5E = Platform(
    name="tpu_v5e",
    peak_flops_bf16=197e12,
    peak_flops_fp8=394e12,
    hbm_bw=819e9,
    hbm_capacity=16 * 2**30,
    link_bw=50e9,
    links_per_axis=2,               # bidirectional ring on a torus axis
    inter_pod_bw=25e9,              # DCI per chip (conservative)
    launch_overhead=2e-6,
    hop_latency=1e-6,
)

TPU_V5P = Platform(
    name="tpu_v5p",
    peak_flops_bf16=459e12,
    peak_flops_fp8=918e12,
    hbm_bw=2765e9,
    hbm_capacity=95 * 2**30,
    link_bw=100e9,
    links_per_axis=2,
    inter_pod_bw=25e9,
    launch_overhead=2e-6,
    hop_latency=1e-6,
)

H100_SXM = Platform(
    name="h100_sxm",
    peak_flops_bf16=989e12,
    peak_flops_fp8=1979e12,
    hbm_bw=3350e9,
    hbm_capacity=80 * 2**30,
    link_bw=450e9,                  # NVLink aggregate per GPU
    links_per_axis=1,
    inter_pod_bw=50e9,              # IB per GPU
    launch_overhead=4e-6,
    hop_latency=2e-6,
)

PLATFORMS: Dict[str, Platform] = {
    p.name: p for p in (TPU_V5E, TPU_V5P, H100_SXM)
}


def get_platform(name: str) -> Platform:
    return PLATFORMS[name]


#: ``jax.Device.device_kind`` -> platform key, for the devices this repo
#: has a profile for.
DEVICE_KINDS: Dict[str, str] = {
    "TPU v5 lite": "tpu_v5e",
    "TPU v5e": "tpu_v5e",
    "TPU v5p": "tpu_v5p",
}


def platform_for_device_kind(kind: str) -> str:
    """The platform key of a JAX device kind; an unknown kind is an error."""
    try:
        return DEVICE_KINDS[kind]
    except KeyError:
        raise ValueError(
            f"no platform profile for device kind {kind!r}; known kinds: "
            f"{', '.join(sorted(DEVICE_KINDS))}") from None
