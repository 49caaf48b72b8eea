"""Measurement harness: times the real Pallas kernels over the same grid
axes the PerfDatabase interpolates on.

For each operator family the harness walks a (subsampled) measurement grid,
builds the operator descriptor the analytical executor prices AND a kernel
thunk that runs the matching real kernel (`repro.kernels.ops` wrappers:
flash_attention / decode_attention / moe_gemm / rglru_scan, plain jnp for
dense GEMM), then asks the pluggable timer for a latency.  The timer
decides whether the thunk actually executes: :class:`WallClockTimer` runs
it (compiled, or through the Pallas interpreter when the harness is built
with ``interpret=True`` on a host with no TPU), :class:`DeterministicTimer`
prices the descriptor analytically with a fixed skew — same harness, same
samples schema, CI-deterministic.

Thunk construction is fully lazy: the jit wrapper and its input arrays
are built on the thunk's first call and cached, so input materialization
never lands inside a timed rep — and a deterministic run, which never
calls the thunk, neither imports jax through the harness nor allocates
anything.
"""
from __future__ import annotations

import dataclasses
import itertools
import math
from typing import Callable, Dict, List, Optional, Sequence, Tuple

from repro.calibrate.artifact import Sample
from repro.calibrate.timers import DeterministicTimer, Thunk
from repro.core import analytical
from repro.core import operators as ops
from repro.core.hardware import Platform, get_platform

_POW2 = lambda lo, hi: tuple(
    2 ** i for i in range(int(math.log2(lo)), int(math.log2(hi)) + 1))

#: Measurement axes: the PerfDatabase's grid axes, capped to shapes a
#: wall-clock interpret-mode run can execute in reasonable time.  The fit
#: is a per-family global correction, so a subgrid suffices.
DEFAULT_AXES: Dict[str, Tuple[Tuple[float, ...], ...]] = {
    "gemm": (_POW2(1, 1024), _POW2(128, 2048), _POW2(128, 2048)),
    "attn_prefill": (_POW2(64, 1024), _POW2(64, 1024)),   # q_len, kv_len
    "attn_decode": (_POW2(1, 16), _POW2(128, 2048)),      # batch, kv_len
    "moe": (_POW2(8, 512),),                              # hot-rank tokens
    "recurrent": (_POW2(64, 1024),),                      # tokens
}

MEASURED_FAMILIES = tuple(DEFAULT_AXES)

# fixed kernel geometry for the shape-rich families (one representative
# head/expert config; the database's per-config grids share the family fit)
ATTN_HEADS = 4
ATTN_KV_HEADS = 2
ATTN_HEAD_DIM = 64
MOE_EXPERTS = 4
MOE_D_MODEL = 256
MOE_D_FF = 512
REC_WIDTH = 256


def subsample(axis: Sequence[float], n: int) -> Tuple[float, ...]:
    """n log-evenly spaced points of ``axis`` including both endpoints."""
    if n <= 0:
        raise ValueError(f"points_per_axis must be >= 1, got {n}")
    if n >= len(axis):
        return tuple(axis)
    if n == 1:
        return (axis[len(axis) // 2],)
    idx = sorted({round(i * (len(axis) - 1) / (n - 1)) for i in range(n)})
    return tuple(axis[i] for i in idx)


@dataclasses.dataclass(frozen=True)
class FamilySpec:
    """One operator family's measurement recipe."""
    family: str
    axes: Tuple[Tuple[float, ...], ...]
    build_op: Callable[..., object]       # coords -> operator descriptor
    make_thunk: Callable[..., Thunk]      # (coords, interpret) -> runner


# -- per-family op builders + kernel thunks ---------------------------------

def _gemm_op(m, n, k):
    return ops.GEMM(int(m), int(n), int(k), "bf16")


def _gemm_thunk(m, n, k, interpret=False):   # plain jnp: nothing to interpret
    import jax
    import jax.numpy as jnp
    mm = jax.jit(lambda a, b: a @ b)
    a = jnp.ones((int(m), int(k)), jnp.bfloat16)
    b = jnp.ones((int(k), int(n)), jnp.bfloat16)
    return lambda: mm(a, b)


def _attn_prefill_op(q_len, kv_len):
    return ops.Attention(
        phase="prefill", batch=1, q_len=int(q_len), kv_len=int(kv_len),
        heads=ATTN_HEADS, kv_heads=ATTN_KV_HEADS, head_dim=ATTN_HEAD_DIM,
        kind="gqa", dtype="bf16")


def _attn_prefill_thunk(q_len, kv_len, interpret=False):
    import jax
    import jax.numpy as jnp
    from repro.kernels import ops as kops

    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(
        ks[0], (1, int(q_len), ATTN_HEADS, ATTN_HEAD_DIM), jnp.bfloat16)
    k = jax.random.normal(
        ks[1], (1, int(kv_len), ATTN_KV_HEADS, ATTN_HEAD_DIM), jnp.bfloat16)
    v = jax.random.normal(
        ks[2], (1, int(kv_len), ATTN_KV_HEADS, ATTN_HEAD_DIM), jnp.bfloat16)
    return lambda: kops.flash_attention(q, k, v, causal=True,
                                        block_q=128, block_k=128,
                                        interpret=interpret)


def _attn_decode_op(batch, kv_len):
    return ops.Attention(
        phase="decode", batch=int(batch), q_len=1, kv_len=int(kv_len),
        heads=ATTN_HEADS, kv_heads=ATTN_KV_HEADS, head_dim=ATTN_HEAD_DIM,
        kind="gqa", dtype="bf16")


def _attn_decode_thunk(batch, kv_len, interpret=False):
    import jax
    import jax.numpy as jnp
    from repro.kernels import ops as kops
    b, w = int(batch), int(kv_len)

    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    q = jax.random.normal(ks[0], (b, ATTN_HEADS, ATTN_HEAD_DIM),
                          jnp.bfloat16)
    kc = jax.random.normal(
        ks[1], (b, w, ATTN_KV_HEADS, ATTN_HEAD_DIM), jnp.bfloat16)
    vc = jax.random.normal(
        ks[2], (b, w, ATTN_KV_HEADS, ATTN_HEAD_DIM), jnp.bfloat16)
    vl = jnp.full((b,), w, jnp.int32)
    return lambda: kops.decode_attention(q, kc, vc, vl, block_k=128,
                                         interpret=interpret)


def _moe_op(rank_tokens):
    return ops.MoEOp(
        tokens=int(rank_tokens), d_model=MOE_D_MODEL, d_ff=MOE_D_FF,
        num_experts=MOE_EXPERTS, top_k=1, ep=1,
        hot_rank_tokens=int(rank_tokens), dtype="bf16")


def _moe_thunk(rank_tokens, interpret=False):
    import jax
    import jax.numpy as jnp
    from repro.kernels import ops as kops
    c = max(int(rank_tokens) // MOE_EXPERTS, 1)

    ks = jax.random.split(jax.random.PRNGKey(2), 4)
    xe = jax.random.normal(ks[0], (MOE_EXPERTS, c, MOE_D_MODEL),
                           jnp.bfloat16)
    w_gate = jax.random.normal(
        ks[1], (MOE_EXPERTS, MOE_D_MODEL, MOE_D_FF), jnp.bfloat16)
    w_up = jax.random.normal(
        ks[2], (MOE_EXPERTS, MOE_D_MODEL, MOE_D_FF), jnp.bfloat16)
    w_down = jax.random.normal(
        ks[3], (MOE_EXPERTS, MOE_D_FF, MOE_D_MODEL), jnp.bfloat16)

    def run():
        # the operator's 3 expert GEMMs (gate/up/down), end to end
        g = kops.moe_gemm(xe, w_gate, interpret=interpret)
        u = kops.moe_gemm(xe, w_up, interpret=interpret)
        return kops.moe_gemm(g * u, w_down, interpret=interpret)

    return run


def _recurrent_op(tokens):
    return ops.RecurrentOp(kind="rglru", batch=1, seq=int(tokens),
                           width=REC_WIDTH, heads=1, dtype="bf16")


def _recurrent_thunk(tokens, interpret=False):
    import jax
    import jax.numpy as jnp
    from repro.kernels import ops as kops
    s = int(tokens)

    ks = jax.random.split(jax.random.PRNGKey(3), 2)
    a = jax.nn.sigmoid(
        jax.random.normal(ks[0], (1, s, REC_WIDTH), jnp.float32))
    b = jax.random.normal(ks[1], (1, s, REC_WIDTH), jnp.float32)
    h0 = jnp.zeros((1, REC_WIDTH), jnp.float32)
    return lambda: kops.rglru_scan(a, b, h0, interpret=interpret)


_SPEC_BUILDERS = {
    "gemm": (_gemm_op, _gemm_thunk),
    "attn_prefill": (_attn_prefill_op, _attn_prefill_thunk),
    "attn_decode": (_attn_decode_op, _attn_decode_thunk),
    "moe": (_moe_op, _moe_thunk),
    "recurrent": (_recurrent_op, _recurrent_thunk),
}


class MeasurementHarness:
    """Sweep the measurement grids for one (platform, backend)."""

    def __init__(self, platform: "str | Platform" = "tpu_v5e",
                 backend: str = "repro-jax",
                 timer=None, points_per_axis: int = 3,
                 families: Optional[Sequence[str]] = None,
                 axes_override: Optional[Dict[str, Sequence[Sequence[float]]]]
                 = None, interpret: bool = False):
        self.platform = (platform if isinstance(platform, Platform)
                         else get_platform(platform))
        self.backend = backend
        self.timer = timer or DeterministicTimer(self.platform)
        self.points_per_axis = points_per_axis
        families = tuple(families) if families else MEASURED_FAMILIES
        unknown = set(families) - set(MEASURED_FAMILIES)
        if unknown:
            raise ValueError(
                f"unknown measurement families {sorted(unknown)}; "
                f"measurable: {', '.join(MEASURED_FAMILIES)}")
        self.families = families
        self._axes_override = dict(axes_override or {})
        self.interpret = interpret

    def spec(self, family: str) -> FamilySpec:
        build_op, make_thunk = _SPEC_BUILDERS[family]
        full_axes = self._axes_override.get(family, DEFAULT_AXES[family])
        axes = tuple(subsample(a, self.points_per_axis) for a in full_axes)
        return FamilySpec(family=family, axes=axes,
                          build_op=build_op, make_thunk=make_thunk)

    def measure_family(self, family: str) -> List[Sample]:
        spec = self.spec(family)
        samples = []
        for coords in itertools.product(*spec.axes):
            op = spec.build_op(*coords)
            predicted = analytical.latency(self.platform, op)
            measured = self.timer.time(op, _deferred(
                spec.make_thunk, coords, self.interpret))
            samples.append(Sample(
                family=family, coords=tuple(float(c) for c in coords),
                predicted_s=predicted, measured_s=measured))
        return samples

    def measure_all(self) -> List[Sample]:
        out: List[Sample] = []
        for family in self.families:
            out.extend(self.measure_family(family))
        return out


def _deferred(make_thunk, coords, interpret: bool) -> Thunk:
    """Defer even thunk CONSTRUCTION (jax import, jit wrapper) to the
    first call: a timer that never executes the kernel never pays it."""
    state: dict = {}

    def thunk():
        if "t" not in state:
            state["t"] = make_thunk(*coords, interpret=interpret)
        return state["t"]()

    return thunk
