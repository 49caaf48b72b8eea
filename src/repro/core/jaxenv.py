"""jax/array-backend environment plumbing for the batched pricing path.

The whole-space pricing kernel (OpGrid.query_batch +
PerfDatabase.sequence_latency_batch) runs on numpy by default and on
jax.numpy under ``jit`` when asked to.  This module owns the env-var
surface that selects the path and the ``jax.config`` knobs the jnp
variant needs (x64 precision, platform, host device count) — the same
helpers research codebases ship for reproducible jax setup.

Environment variables
---------------------
REPRO_BATCHED_PRICING   "0"/"false" forces the scalar per-candidate path
                        (default: batched pricing on)
REPRO_PRICING_BACKEND   "np" (default) or "jax" — array backend for the
                        fused interpolation kernel
REPRO_PRICING_CHUNK     candidates per pricing batch in the streaming
                        cursor (default 64; must stay small enough that
                        early-exit consumers skip real work)
REPRO_JAX_X64           when set truthy, enable 64-bit jax arrays before
                        the first jax pricing call
REPRO_JAX_PLATFORM      force jax_platform_name (e.g. "cpu")
REPRO_HOST_DEVICES      --xla_force_host_platform_device_count value
JAX_COMPILATION_CACHE_DIR
                        JAX's own persistent compile cache setting; where it
                        is unset, the entry points put the cache at
                        ``<checkout>/.jax_cache`` (:func:`use_compile_cache`)
"""
from __future__ import annotations

import os

_FALSY = {"0", "false", "no", "off", ""}

DEFAULT_CHUNK = 64


def _env_flag(name: str, default: bool) -> bool:
    raw = os.environ.get(name)
    if raw is None:
        return default
    return raw.strip().lower() not in _FALSY


def batched_pricing_default() -> bool:
    """Whether iter_search should price through the batched cursor."""
    return _env_flag("REPRO_BATCHED_PRICING", True)


def pricing_backend() -> str:
    """Array backend for the fused pricing kernel: 'np' or 'jax'."""
    raw = os.environ.get("REPRO_PRICING_BACKEND", "np").strip().lower()
    return "jax" if raw in ("jax", "jnp") else "np"


def pricing_chunk(default: int = DEFAULT_CHUNK) -> int:
    """Candidates per pricing batch in the streaming cursor."""
    try:
        n = int(os.environ.get("REPRO_PRICING_CHUNK", default))
    except (TypeError, ValueError):
        return default
    return max(n, 1)


# ---------------------------------------------------------------------------
# jax.config knobs (imported lazily so numpy-only runs never touch jax)
# ---------------------------------------------------------------------------

def enable_x64(use_x64: bool = True) -> None:
    """Set the default jax float precision to 64 (or back to 32) bits."""
    import jax
    jax.config.update("jax_enable_x64", bool(use_x64))


def x64_enabled() -> bool:
    import jax
    return bool(jax.config.read("jax_enable_x64"))


def set_platform(platform: str = "cpu") -> None:
    """Pin the jax platform; only effective before the first jax op."""
    import jax
    jax.config.update("jax_platform_name", platform)


def set_host_device_count(n: int) -> None:
    """Expose ``n`` host devices; only effective before jax initializes."""
    flags = os.environ.get("XLA_FLAGS", "")
    kept = [f for f in flags.split()
            if not f.startswith("--xla_force_host_platform_device_count")]
    kept.append(f"--xla_force_host_platform_device_count={int(n)}")
    os.environ["XLA_FLAGS"] = " ".join(kept)


#: Fixed in-checkout compile-cache path: the cache key includes the path,
#: so a directory that moves between runs never hits.
CHECKOUT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))), ".jax_cache")


def use_compile_cache() -> str:
    """Turn on JAX's persistent compile cache; returns its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is JAX's own setting and
    stands.  Otherwise the cache goes to :data:`CHECKOUT_CACHE_DIR`.
    Called by the entry points, never at import."""
    import jax
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", CHECKOUT_CACHE_DIR)
    return CHECKOUT_CACHE_DIR


def configure_from_env() -> None:
    """Apply REPRO_JAX_* / REPRO_HOST_DEVICES before a jax pricing run."""
    if os.environ.get("REPRO_HOST_DEVICES"):
        set_host_device_count(int(os.environ["REPRO_HOST_DEVICES"]))
    if os.environ.get("REPRO_JAX_PLATFORM"):
        set_platform(os.environ["REPRO_JAX_PLATFORM"])
    if _env_flag("REPRO_JAX_X64", False):
        enable_x64(True)
