"""The chip a run measures: its peaks, and the refusal of anything else.

The peaks live in ``peaks.json`` beside this file, keyed by JAX's
``device_kind``, with their source.  A device that is not in the table is
an error, never a default, and a platform other than the accelerator is
refused before any work: a CPU number is never reported under a device
metric's name.
"""
from __future__ import annotations

import dataclasses
import os
from typing import Dict, List

from chipbench.spec import HERE, load_json


class NoChip(RuntimeError):
    """JAX found no accelerator of a known kind, or too few of them."""


@dataclasses.dataclass(frozen=True)
class Peaks:
    kind: str
    flops_per_s: float          # dense bf16 matmul peak
    bytes_per_s: float          # HBM bandwidth


def peaks_table(path: str = os.path.join(HERE, "peaks.json")) -> Dict:
    return load_json(path)["devices"]


def peaks_for(kind: str, platform: str, table: Dict = None) -> Peaks:
    table = peaks_table() if table is None else table
    if kind not in table:
        raise NoChip(f"device kind {kind!r} has no entry in the peaks "
                     f"table ({', '.join(sorted(table))})")
    row = table[kind]
    if platform != row["platform"]:
        raise NoChip(f"device kind {kind!r} reports platform {platform!r}, "
                     f"the table expects {row['platform']!r}")
    return Peaks(kind=kind, flops_per_s=float(row["bf16_flops_per_s"]),
                 bytes_per_s=float(row["hbm_bytes_per_s"]))


def check(devices: List, chips: int) -> Peaks:
    """Refuse a run without ``chips`` accelerators of a known kind."""
    if not devices:
        raise NoChip("JAX reports no devices")
    d = devices[0]
    if d.platform in ("cpu", "interpreter"):
        raise NoChip(f"no accelerator: JAX runs on {d.platform} "
                     f"({d.device_kind})")
    if len(devices) < chips:
        raise NoChip(f"the cell asks for {chips} chips, JAX found "
                     f"{len(devices)}")
    return peaks_for(d.device_kind, d.platform)


def describe(devices: List, chips: int) -> Dict:
    """The result line's ``device``: as JAX reports it, peak of the fullest
    chip among those the cell uses."""
    used = devices[:chips]
    peak = 0
    for d in used:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    d = used[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(used), "memory_peak_bytes": peak}
