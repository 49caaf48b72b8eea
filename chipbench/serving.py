"""Serving cells: the program's engine under open- or closed-loop traffic.

The window drives ``Engine.add_request`` / ``Engine.step`` and nothing
else, from one thread.  Timestamps are the host clock after each
``step()`` returns: a token is delivered when the step that made it ends.
In an open loop requests are due at the mix's arrival times; in a closed
loop each client's next request is due when it is sent, in the engine
step after its previous one finished.  Each request is timed from when
it was due, so a stall counts against every request waiting behind it.

A traced window (``--trace 1``) runs the engine under a wall-clock
``repro.obs`` tracer bridged to the profiler, so its ``engine.*`` spans
land on the trace, and counts into a fresh metrics registry
(``Window.counters``); both are installed after warm-up and removed
after the drain.  An untraced window installs neither.

Every request due in the window is served: after the close the loop
steps on until each has finished, up to ``DRAIN_S`` past the close; one
still unfinished then counts as failed.
"""
from __future__ import annotations

import contextlib
import dataclasses
import gc
import sys
import tempfile
import time
from typing import Dict, List, Optional

import jax
import numpy as np

from chipbench import traffic as tmod
from chipbench import weights as wmod

DRAIN_S = 60.0
TRACE_S = 10.0          # the traced slice: the window's last 10 s, so that
                        # stopping the profiler, which holds the host for
                        # about as long as it traced, falls after the close
WARM_IDX = 1 << 40                     # warm-up prompts: ids no request has


def model_config(c: Dict):
    """The program's ModelConfig for a configuration file, as run."""
    from repro.configs.base import ModelConfig
    e = c["engine"]
    experts = int(c.get("num_experts", 0) or 0)
    kw = dict(name=c["name"], family=e["family"],
              num_layers=c["num_hidden_layers"], d_model=c["hidden_size"],
              num_heads=c["num_attention_heads"],
              num_kv_heads=c["num_key_value_heads"],
              d_ff=c["intermediate_size"], vocab_size=c["vocab_size"],
              head_dim=c.get("head_dim") or 0,
              rope_theta=float(c["rope_theta"]),
              norm_eps=float(c["rms_norm_eps"]),
              tie_embeddings=bool(c.get("tie_word_embeddings", False)),
              qk_norm=bool(e.get("qk_norm", False)),
              dtype=e.get("dtype", "bfloat16"), source=c["source"])
    if experts:
        kw.update(num_experts=experts, top_k=c["num_experts_per_tok"],
                  moe_d_ff=c["moe_intermediate_size"],
                  capacity_factor=float(e["capacity_factor"]))
    return ModelConfig(**kw)


@dataclasses.dataclass
class Rec:
    """One request as the client saw it."""
    req: object
    due: float                       # host clock
    admitted: float = 0.0
    times: List[float] = dataclasses.field(default_factory=list)
    client: Optional[int] = None     # closed loop: the client that sent it


@dataclasses.dataclass
class Step:
    t0: float
    t1: float
    decode_lens: List[int]           # valid K/V length of each active slot
    prefill_lens: List[int]          # prompt length of each prefill
    traced: bool = False


@dataclasses.dataclass
class Window:
    """What one run's window recorded (the metric readers' input)."""
    t0: float
    seconds: float
    slots: int
    recs: List[Rec]
    steps: List[Step]
    lowerings: int
    trace_dir: Optional[str] = None
    counters: object = None          # repro.obs MetricsRegistry (traced)


class _Lowerings:
    """Counts programs lowered (compiled or fetched from the cache)."""
    EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"

    def __init__(self):
        self.n = 0
        from jax._src import monitoring
        monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event == self.EVENT:
            self.n += 1


@contextlib.contextmanager
def _engine_observed(on: bool):
    """While on, the engine's spans go to the profiler's trace and its
    counters to a fresh registry, which this yields; else it yields
    None and installs nothing."""
    if not on:
        yield None
        return
    from repro.obs import (Tracer, disable_metrics, disable_tracing,
                           enable_metrics, enable_tracing)
    enable_tracing(Tracer(timebase="wall", profiler=True))
    try:
        yield enable_metrics()
    finally:
        disable_tracing()
        disable_metrics()


def _trace_options():
    """Device ops and the benchmark's annotations; no Python call tracer,
    whose every-call events would swamp the host and the trace."""
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    return opts


class Serving:
    """Set-up, window and check of one serving cell."""

    def __init__(self, cell, seed: int):
        from repro import models
        from repro.serving.engine import Engine, EngineConfig
        self.cell, self.seed = cell, seed
        self.mix = cell.traffic
        self.mcfg = model_config(cell.config)
        self.vocab = self.mcfg.vocab_size
        self.abstract = models.abstract_params(self.mcfg)
        params = wmod.device_params(self.abstract, seed)
        jax.block_until_ready(params)
        e = self.mix["engine"]
        self.slots = int(e["slots"])
        self.engine = Engine(self.mcfg, params, EngineConfig(
            max_batch=self.slots, max_seq=int(e["max_seq"])))
        self.lowerings = _Lowerings()

    def warm_up(self) -> None:
        """Every prompt length of the mix through prefill, and the decode
        step, in the engine itself: exactly the window's shapes."""
        from repro.serving.request import Request
        for i, isl in enumerate(tmod.prompt_lengths(self.mix)):
            self.engine.add_request(Request(
                rid=-1 - i, isl=isl, osl=3, arrival=time.perf_counter(),
                prompt=tmod.prompt(self.seed, WARM_IDX + i, isl,
                                   self.vocab)))
        self.engine.run_until_drained()

    # ------------------------------------------------------------------
    def window(self, seconds: float, trace: bool) -> Window:
        with _engine_observed(trace) as counters:
            w = self._drive(seconds, trace)
        w.counters = counters
        return w

    def _drive(self, seconds: float, trace: bool) -> Window:
        from repro.serving.request import Phase, Request
        eng, mix, seed = self.engine, self.mix, self.seed
        arr = tmod.arrivals(mix, seconds)
        recs: List[Rec] = []
        steps: List[Step] = []
        live: Dict[int, Rec] = {}
        note = jax.profiler.TraceAnnotation if trace else \
            (lambda name: contextlib.nullcontext())
        trace_dir, tracing = None, False
        t_trace = (max(0.0, seconds - TRACE_S), seconds)

        def send(due: float, s: tmod.Send) -> None:
            idx = len(recs)
            req = Request(rid=idx, isl=s.isl, osl=s.osl, arrival=due,
                          prompt=tmod.prompt(seed, s.key, s.isl, self.vocab))
            rec = Rec(req=req, due=due, admitted=time.perf_counter(),
                      client=s.client)
            recs.append(rec)
            live[idx] = rec
            eng.add_request(req)

        low0 = self.lowerings.n
        t0 = time.perf_counter()
        while True:
            now = time.perf_counter()
            el = now - t0
            if trace and not tracing and trace_dir is None \
                    and el >= t_trace[0]:
                trace_dir = tempfile.mkdtemp(prefix="chipbench-trace-")
                jax.profiler.start_trace(trace_dir,
                                         profiler_options=_trace_options())
                tracing = True
            elif tracing and el >= t_trace[1]:
                jax.profiler.stop_trace()
                tracing = False
            with note("bench.admit"):
                for a in arr.due(el):
                    send(t0 + a.due_s, a)
            if not live:
                wake = arr.wake(el)
                if wake is None:
                    break
                with note("bench.wait"):
                    time.sleep(max(0.0, t0 + wake - time.perf_counter()))
                continue
            if el > seconds + DRAIN_S:
                break
            before = {i: len(r.req.out_tokens) for i, r in live.items()}
            dec = [r.req.isl + n for i, r in live.items()
                   for n in (before[i],) if r.req.phase == Phase.DECODE]
            s0 = time.perf_counter()
            with note("bench.step"):
                eng.step()
            s1 = time.perf_counter()
            with note("bench.record"):
                pre = []
                for i in list(live):
                    r = live[i]
                    n = len(r.req.out_tokens)
                    if n > before[i]:
                        if before[i] == 0:
                            pre.append(r.req.isl)
                        r.times.extend([s1] * (n - before[i]))
                    if r.req.phase == Phase.DONE:
                        del live[i]
                        arr.finished(r.client)
                steps.append(Step(s0, s1, dec, pre, traced=tracing))
        if tracing:
            jax.profiler.stop_trace()
        w = Window(t0=t0, seconds=seconds, slots=self.slots, recs=recs,
                   steps=steps, lowerings=self.lowerings.n - low0,
                   trace_dir=trace_dir)
        late = [r.admitted - r.due for r in recs]
        print(f"# generator: {len(recs)} requests sent, late by p50 "
              f"{1e3 * float(np.median(late)):.3f} ms, max "
              f"{1e3 * max(late):.3f} ms; programs lowered inside the "
              f"window: {w.lowerings}", file=sys.stderr, flush=True)
        return w

    # ------------------------------------------------------------------
    def sample(self, w: Window, tokens: int = 512, most: int = 24) -> list:
        """Finished requests drawn from the seed, the longest among them,
        until ``tokens`` served tokens are covered."""
        done = [r for r in w.recs
                if len(r.req.out_tokens) == r.req.osl]
        if not done:
            return []
        longest = max(done, key=lambda r: (r.req.isl + r.req.osl, r.req.rid))
        rest = [r for r in done if r is not longest]
        tmod._rng(self.seed, "sample").shuffle(rest)
        pick, n = [longest], longest.req.osl
        for r in rest:
            if n >= tokens or len(pick) >= most:
                break
            pick.append(r)
            n += r.req.osl
        return [(list(r.req.prompt), list(r.req.out_tokens)) for r in pick]

    def release(self) -> None:
        """Free the engine's weights and cache before the reference runs."""
        self.engine = None
        gc.collect()
