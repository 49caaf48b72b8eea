"""Weights made from the seed, on the device, in the type they are served in.

The rule is the benchmark's own, so that the plain reference can make the
same numbers again without taking anything from the program: each leaf is
named by its path (``layers/wq``, ``embed/tok_embed``), and each layer of
a stacked leaf by its index, and

    norm gains        1 + 0.1 * N(0, 1)
    token embedding   N(0, 1)
    every matrix      N(0, 1) / sqrt(fan_in),  fan_in = shape[-2]

drawn in float32 from ``fold_in(fold_in(key(seed), crc32(path)), layer)``
and rounded once to the served dtype.  :func:`device_params` makes the
whole tree in one jitted call (a ``lax.map`` over the layers of each
stacked leaf, so only one layer's float32 draw is live at a time);
:func:`layer_leaves` makes one layer again for the reference.
"""
from __future__ import annotations

import zlib
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
from jax import lax


def seed_key(seed: int) -> jax.Array:
    """A key from any whole number up to 64 bits."""
    seed = int(seed)
    k = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(k, (seed >> 32) & 0xFFFFFFFF)


def _draw(key: jax.Array, path: str, shape: Tuple[int, ...]) -> jax.Array:
    """One leaf (or one layer of a stacked leaf) in float32."""
    z = jax.random.normal(key, shape, jnp.float32)
    name = path.rsplit("/", 1)[-1]
    if "norm" in name:
        return 1.0 + 0.1 * z
    if name == "tok_embed":
        return z
    return z * (1.0 / float(shape[-2]) ** 0.5)


def _leaf_key(key: jax.Array, path: str) -> jax.Array:
    return jax.random.fold_in(key, zlib.crc32(path.encode()) & 0x7FFFFFFF)


def flat_shapes(abstract: Dict, prefix: str = "") -> Dict[str, Tuple]:
    """``{"layers/wq": (shape, dtype), ...}`` of an abstract param tree."""
    out = {}
    for k, v in abstract.items():
        path = f"{prefix}{k}"
        if isinstance(v, dict):
            out.update(flat_shapes(v, path + "/"))
        else:
            out[path] = (tuple(v.shape), v.dtype)
    return out


def _unflatten(flat: Dict[str, jax.Array]) -> Dict:
    tree: Dict = {}
    for path, arr in flat.items():
        node = tree
        parts = path.split("/")
        for p in parts[:-1]:
            node = node.setdefault(p, {})
        node[parts[-1]] = arr
    return tree


def _make(shapes: Dict[str, Tuple], key: jax.Array) -> Dict:
    flat = {}
    for path, (shape, dtype) in shapes.items():
        lk = _leaf_key(key, path)
        if path.startswith("layers/"):
            flat[path] = lax.map(
                lambda l, lk=lk, path=path, shape=shape, dtype=dtype: _draw(
                    jax.random.fold_in(lk, l), path, shape[1:]).astype(dtype),
                jnp.arange(shape[0], dtype=jnp.uint32))
        else:
            flat[path] = _draw(lk, path, shape).astype(dtype)
    return _unflatten(flat)


def device_params(abstract: Dict, seed: int) -> Dict:
    """The program's parameter tree, made on the device in one call."""
    shapes = flat_shapes(abstract)
    fn = jax.jit(lambda key: _make(shapes, key))
    return fn(seed_key(seed))


def layer_leaves(shapes: Dict[str, Tuple], key: jax.Array,
                 layer: jax.Array) -> Dict:
    """Layer ``layer`` of every stacked leaf, as served, in float32;
    ``key`` is :func:`seed_key` of the run's seed.  Jittable."""
    out = {}
    for path, (shape, dtype) in shapes.items():
        if not path.startswith("layers/"):
            continue
        lk = jax.random.fold_in(_leaf_key(key, path),
                                jnp.asarray(layer, jnp.uint32))
        out[path.split("/", 1)[1]] = _draw(lk, path, shape[1:]).astype(
            dtype).astype(jnp.float32)
    return out


def top_leaves(shapes: Dict[str, Tuple], key: jax.Array) -> Dict:
    """The leaves outside the layer stack, as served, in float32."""
    return {path.split("/")[-1]: _draw(_leaf_key(key, path), path,
                                       shape).astype(dtype).astype(
                                           jnp.float32)
            for path, (shape, dtype) in shapes.items()
            if not path.startswith("layers/")}
