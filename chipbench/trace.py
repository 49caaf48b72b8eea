"""From a profiler trace to device busy time, program time and idle gaps.

The JAX profiler writes ``<dir>/plugins/profile/<run>/*.xplane.pb``;
``jax.profiler.ProfileData`` reads it.  The planes used:

    /device:TPU:<n>   line "XLA Ops"      one event per device operation
                      line "XLA Modules"  one event per program execution,
                                          named ``jit_<fn>(<fingerprint>)``
    /host:CPU         the benchmark's own ``bench.*`` annotations

The traced window is the span of the host's ``bench.*`` annotations (the
loop is always inside one).  Busy time is the union of a device's op
intervals inside it, averaged over the devices used; every stretch of the
window with no op is an idle gap, charged to the annotation the host was
in at the gap's middle.

A program is known by its whole module name, fingerprint included: the
engine jits ``functools.partial`` objects, so its decode step and every
prefill length all trace as ``jit__unknown`` and differ only there.  Each
program execution is charged to the ``bench.step`` annotation (one engine
step) in which it starts; the device's clock in the trace runs up to a few
tenths of a millisecond off the host's, so a step's annotation is taken to
start ``SKEW_NS`` early.  :func:`step_work` then splits the traced steps'
device time into decode and prefill work, by what the harness recorded of
each step.

A trace that lacks any of these names raises :class:`TraceError`: a name
that is not the chip's must fail the run, not read as an idle device.
"""
from __future__ import annotations

import bisect
import dataclasses
import glob
import os
import re
from collections import defaultdict
from typing import Dict, List, Optional, Sequence, Tuple

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PREFIX = "bench."
STEP = "step"                    # the annotation around ``Engine.step``
SKEW_NS = 1_000_000
_DEVICE = re.compile(r"^/device:TPU:(\d+)$")
_INSTR = re.compile(r"^%?([\w.\-]+) = (\S+)")


class TraceError(RuntimeError):
    """The trace lacks a plane, line or program the reduction reads."""


@dataclasses.dataclass
class Reduced:
    window_s: float
    busy_s: float                                  # mean over devices
    modules: Dict[str, Tuple[int, float]]          # program -> (calls, s)
    steps: List[Dict[str, float]]                  # per step: program -> s
    ops: Dict[str, float]                          # op name -> seconds
    idle: Dict[str, float]                         # host activity -> s
    gaps: List[Tuple[str, float]]                  # longest idle gaps


@dataclasses.dataclass(frozen=True)
class StepWork:
    """The traced engine steps' device time, split by the work done."""
    decode_program: Optional[str]
    decode_calls: int
    decode_s: float              # the decode program's executions
    prefill_s: float             # what steps that prefilled spent beyond
                                 # their decode work


def step_work(r: Reduced, steps: Sequence) -> StepWork:
    """Split the device time of the traced steps (the harness's
    ``serving.Step`` records of them, in order) into decode and prefill
    work.

    The decode program is the one that ran exactly once in every traced
    step that decoded and took the most device time among such (the
    argmax after it also runs once a step).  A step's prefill work is all
    its device time but its decode program's and, where it also decoded,
    the mean that steps which only decoded spent beyond their decode
    program (reading the new tokens back)."""
    if len(steps) != len(r.steps):
        raise TraceError(f"the harness traced {len(steps)} engine steps, "
                         f"the trace holds {len(r.steps)} {HOST_PREFIX}"
                         f"{STEP} annotations")
    n_dec = sum(1 for s in steps if s.decode_lens)
    prog = None
    if n_dec:
        fits = [k for k, (c, _) in r.modules.items() if c == n_dec
                and sum(1 for d in r.steps if k in d) == n_dec]
        if not fits:
            raise TraceError(f"{n_dec} traced steps decoded, but no program "
                             f"ran once in each; programs: {r.modules}")
        prog = max(fits, key=lambda k: r.modules[k][1])
    rest = [sum(v for k, v in d.items() if k != prog)
            for s, d in zip(steps, r.steps)]
    only = [x for s, x in zip(steps, rest) if s.decode_lens
            and not s.prefill_lens]
    helper = sum(only) / len(only) if only else 0.0
    prefill = sum(x - (helper if s.decode_lens else 0.0)
                  for s, x in zip(steps, rest) if s.prefill_lens)
    calls, sec = r.modules.get(prog, (0, 0.0))
    return StepWork(decode_program=prog, decode_calls=calls, decode_s=sec,
                    prefill_s=max(0.0, prefill))


def union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[List[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def op_name(event_name: str) -> str:
    """An op event is named by its whole HLO instruction; keep its name
    and the start of its result type: ``copy.75 bf16[24,32,1288,8,128]``."""
    m = _INSTR.match(event_name)
    return f"{m.group(1)} {m.group(2).split('{')[0][:40]}" if m \
        else event_name[:80]


def self_times(events: List[Tuple[float, float, str]]):
    """``(name, seconds)`` of each op less the ops nested inside it (a
    ``while`` op holds its body's ops on the same line)."""
    out, stack = [], []            # stack: [end, index into out]
    for a, b, name in sorted(events, key=lambda e: (e[0], -e[1])):
        while stack and stack[-1][0] <= a:
            stack.pop()
        if stack and b <= stack[-1][0]:
            out[stack[-1][1]][1] -= (b - a) * 1e-9
        out.append([name, (b - a) * 1e-9])
        stack.append((b, len(out) - 1))
    return out


def _clip(iv, lo, hi):
    return [(max(a, lo), min(b, hi)) for a, b in iv if b > lo and a < hi]


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def reduce(profile, devices: int = 1) -> Reduced:
    """Reduce a ``ProfileData`` of a run on ``devices`` chips."""
    host: List[Tuple[float, float, str]] = []
    planes: List[str] = []
    dev_ops: Dict[int, List[Tuple[float, float, str]]] = defaultdict(list)
    mods: List[Tuple[float, float, str]] = []
    for plane in profile.planes:
        planes.append(plane.name)
        m = _DEVICE.match(plane.name)
        if m and int(m.group(1)) < devices:
            for line in plane.lines:
                if line.name == OPS_LINE:
                    for e in line.events:
                        dev_ops[int(m.group(1))].append(
                            (e.start_ns, e.start_ns + e.duration_ns, e.name))
                elif line.name == MODULES_LINE and m.group(1) == "0":
                    mods.extend((e.start_ns, e.duration_ns * 1e-9, e.name)
                                for e in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(HOST_PREFIX):
                        host.append((e.start_ns, e.start_ns + e.duration_ns,
                                     e.name[len(HOST_PREFIX):]))
    if not host:
        raise TraceError(f"no {HOST_PREFIX}* annotation on a host plane; "
                         f"planes: {planes}")
    for d in range(devices):
        if not dev_ops.get(d):
            raise TraceError(f"no {OPS_LINE!r} events on /device:TPU:{d}; "
                             f"planes: {planes}")
    if not mods:
        raise TraceError(f"no {MODULES_LINE!r} events on /device:TPU:0")
    lo, hi = min(h[0] for h in host), max(h[1] for h in host)
    window = (hi - lo) * 1e-9
    busy, ops = [], defaultdict(float)
    for d in range(devices):
        iv = union(_clip([(a, b) for a, b, _ in dev_ops.get(d, [])], lo, hi))
        busy.append(sum(b - a for a, b in iv) * 1e-9)
        if d == 0:
            busy0 = iv
    for name, sec in self_times(dev_ops.get(0, [])):
        ops[op_name(name)] += sec
    gaps, idle = [], defaultdict(float)
    host.sort()                    # the annotations follow one another
    starts = [h[0] for h in host]
    edges = [lo] + [x for iv in busy0 for x in iv] + [hi]
    for a, b in zip(edges[::2], edges[1::2]):
        if b <= a:
            continue
        mid = 0.5 * (a + b)
        i = bisect.bisect_right(starts, mid) - 1
        what = host[i][2] if i >= 0 and mid < host[i][1] else "other"
        idle[what] += (b - a) * 1e-9
        gaps.append((what, (b - a) * 1e-9))
    gaps.sort(key=lambda g: -g[1])
    modules: Dict[str, List] = defaultdict(lambda: [0, 0.0])
    step_iv = [(a, b) for a, b, what in host if what == STEP]
    step_starts = [a - SKEW_NS for a, _ in step_iv]
    steps: List[Dict[str, float]] = [defaultdict(float) for _ in step_iv]
    for t, sec, name in mods:
        modules[name][0] += 1
        modules[name][1] += sec
        i = bisect.bisect_right(step_starts, t) - 1
        if i >= 0 and t <= step_iv[i][1] + SKEW_NS:
            steps[i][name] += sec
    return Reduced(window_s=window, busy_s=sum(busy) / devices,
                   modules={k: (int(c), s) for k, (c, s) in modules.items()},
                   steps=[dict(d) for d in steps], ops=dict(ops),
                   idle=dict(idle), gaps=gaps[:10])


def profile(trace_dir: str):
    """The ``ProfileData`` of the trace written under ``trace_dir``."""
    import jax
    return jax.profiler.ProfileData.from_file(find_xplane(trace_dir))


def breakdown(r: Reduced) -> Dict:
    """The result line's ``breakdown``: the ten device ops that took most
    time, and idle time by what the host was doing."""
    ops = sorted(r.ops.items(), key=lambda kv: -kv[1])[:10]
    idle = sorted(r.idle.items(), key=lambda kv: -kv[1])[:10]
    return {"device_ops": [[k, v] for k, v in ops],
            "idle_gaps": [[k, v] for k, v in idle]}
