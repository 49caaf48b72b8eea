"""Pluggable kernel timers for the measurement harness.

A timer maps ``(operator descriptor, kernel thunk) -> seconds``.  Two
implementations ship:

* :class:`WallClockTimer` — actually executes the kernel thunk (compiled
  on a TPU, or through the Pallas interpreter when the harness is asked
  for ``interpret=True``) and returns a median-of-trials wall-clock
  measurement.  This is the timer a real-TPU calibration run swaps in; on
  a CPU host it exercises the same code path through the interpreter.

* :class:`DeterministicTimer` — the CI timer.  It never executes the
  kernel: it derives a pseudo-measurement from the analytical model with a
  fixed per-family efficiency skew plus a small content-hashed jitter, so
  a CI run is bit-for-bit reproducible while still presenting the fitting
  layer with exactly the estimation problem real silicon poses (the
  analytical prediction is off by family-specific factors the fit must
  recover).

Both stamp a ``name`` recorded in the artifact's provenance, so a loaded
artifact always says how its numbers were obtained.
"""
from __future__ import annotations

import hashlib
import statistics
import time
from typing import Callable, Dict, Optional

from repro.core import analytical
from repro.core import operators as ops
from repro.core.hardware import Platform, get_platform

#: A kernel thunk: zero-arg callable running the kernel once and returning
#: something blockable (a jax array) or None.
Thunk = Callable[[], object]


def median_time(thunk: Thunk, reps: int = 3, trials: int = 3) -> float:
    """Median-of-trials wall-clock timing of ``thunk`` (seconds per call).

    The first call warms the jit (compile/trace time excluded); each trial
    then runs ``reps`` back-to-back calls and blocks on the last result.
    Single-shot CPU measurements swing ~35%, hence median-of-trials — the
    same discipline benchmarks/cpu_silicon_fidelity.py always used, now
    shared through the calibration subsystem.
    """
    out = thunk()
    _block(out)
    results = []
    for _ in range(trials):
        t0 = time.perf_counter()
        for _ in range(reps):
            out = thunk()
        _block(out)
        results.append((time.perf_counter() - t0) / reps)
    return statistics.median(results)


def _block(out) -> None:
    block = getattr(out, "block_until_ready", None)
    if block is not None:
        block()


class WallClockTimer:
    """Times the real kernel via :func:`median_time`."""

    name = "wallclock"

    def __init__(self, reps: int = 3, trials: int = 3):
        self.reps = reps
        self.trials = trials

    def time(self, op, thunk: Thunk) -> float:
        return median_time(thunk, reps=self.reps, trials=self.trials)


class DeterministicTimer:
    """Deterministic CI stand-in for silicon.

    ``measured = analytical.latency(platform, op) · skew[family] ·
    exp(jitter · u)`` with ``u ∈ [-1, 1]`` derived from a content hash of
    (family, op) — stable across runs, machines, and Python hash seeds.
    The default skews model a silicon whose flash attention runs hotter
    than the efficiency curves assume and whose decode path runs cooler;
    any profile can be injected to build test scenarios.
    """

    name = "deterministic"

    #: Family-specific "silicon disagrees with analytics by this factor".
    DEFAULT_SKEW: Dict[str, float] = {
        "gemm": 1.18,
        "attn_prefill": 1.34,
        "attn_decode": 0.91,
        "moe": 1.27,
        "recurrent": 1.12,
        "comm": 1.05,
    }

    def __init__(self, platform: "str | Platform",
                 skew: Optional[Dict[str, float]] = None,
                 jitter: float = 0.03):
        self.platform = (platform if isinstance(platform, Platform)
                         else get_platform(platform))
        self.skew = dict(self.DEFAULT_SKEW if skew is None else skew)
        self.jitter = jitter

    def time(self, op, thunk: Thunk) -> float:
        family = ops.op_family(op)
        base = analytical.latency(self.platform, op)
        factor = self.skew.get(family, 1.0)
        if self.jitter:
            digest = hashlib.sha256(
                f"{family}|{op!r}".encode()).digest()
            u = int.from_bytes(digest[:8], "big") / float(1 << 64)  # [0, 1)
            factor *= pow(2.718281828459045, self.jitter * (2.0 * u - 1.0))
        return base * factor


def make_timer(name: str, platform: "str | Platform",
               **kwargs) -> "WallClockTimer | DeterministicTimer":
    """Timer factory the CLI uses: ``deterministic`` or ``wallclock``."""
    if name == "deterministic":
        return DeterministicTimer(platform, **kwargs)
    if name == "wallclock":
        return WallClockTimer(**kwargs)
    raise ValueError(
        f"unknown timer {name!r}; valid choices: deterministic, wallclock")
