"""Production mesh construction.

A FUNCTION (not a module-level constant) so importing this module never
touches jax device state; only dryrun.py sets the 512-placeholder-device
XLA flag, and only before its first jax import.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _auto_mesh(shape, axes):
    """``jax.make_mesh`` with every axis ``Auto``: the model code pins
    layouts with bare ``PartitionSpec`` constraints, which JAX accepts only
    on Auto axes (its default is now Explicit)."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False, expert_axis: int = 0):
    """16x16 single-pod (256 chips) or 2x16x16 two-pod (512 chips) mesh.

    expert_axis > 0 splits the model axis into (expert, model) — the
    perf-iteration mesh for MoE archs whose expert count doesn't divide 16
    (e.g. mixtral 8e -> (16, 8, 2)); same chip count, different collective
    structure (see EXPERIMENTS.md §Perf)."""
    if expert_axis:
        assert 16 % expert_axis == 0
        if multi_pod:
            return _auto_mesh((2, 16, expert_axis, 16 // expert_axis),
                              ("pod", "data", "expert", "model"))
        return _auto_mesh((16, expert_axis, 16 // expert_axis),
                          ("data", "expert", "model"))
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_host_mesh():
    """Degenerate 1x1 mesh on the real local device (CPU smoke runs)."""
    return _auto_mesh((1, 1), ("data", "model"))
