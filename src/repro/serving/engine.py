"""Real JAX serving engine (the "repro-jax" backend).

Continuous batching over fixed decode slots with a ring-buffer KV cache:

  - ONE compiled decode step for the whole slot array (fixed shapes +
    donated cache = the TPU-idiomatic analogue of CUDA-graph capture;
    flag: ``decode_bucketing``),
  - whole-prompt prefill compiled per distinct prompt length (the engine
    serves real tokens; the simulator models chunked prefill),
  - per-row positions so slots at different depths decode together,
  - greedy sampling; wall-clock TTFT/TPOT per request.

Every device program the engine launches from its own code has a stable
name, so a profiler trace reads ``jit_decode_step``, ``jit_prefill`` and
``jit_slot_write``.  Under an installed ``repro.obs`` tracer each step
opens this span tree (attrs in brackets):

  engine.step [prefills, decodes, new_programs]
    engine.schedule           the scheduler's plan
    engine.prefill [rid, isl, slot]       one per prompt
      engine.prefill.run      the prefill program, dispatched
      engine.prefill.write    the K and V writes into the slot
      engine.prefill.sample   argmax of the last logits, read back
    engine.decode [active]
      engine.decode.inputs    positions and last tokens uploaded
      engine.decode.run       the decode program, run to completion
      engine.decode.sample    argmax of the logits, copied to the host
    engine.commit             the scheduler's commit

and an installed metrics registry counts ``repro_engine_*`` (steps by
kind, decode slot-steps, prefill tokens, queue wait at admission, and the
programs first launched).  Under the defaults (``NULL_TRACER``, no
registry) a step opens no span and counts nothing.

The configurator's Generator emits a ``LaunchConfig`` this engine consumes
directly (see repro/core/generator.py) — the paper's technique wired in as
a first-class feature.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any, Dict, List, Set

import jax
import jax.numpy as jnp
import numpy as np

from repro import models
from repro.configs.base import ModelConfig
from repro.obs.metrics import LATENCY_MS_BUCKETS, get_metrics
from repro.obs.trace import NULL_TRACER, get_tracer
from repro.serving.request import Phase, Request
from repro.serving.scheduler import ContinuousBatchingScheduler, SchedulerConfig


@dataclasses.dataclass(frozen=True)
class EngineConfig:
    max_batch: int = 8                 # decode slots
    max_seq: int = 256                 # KV allocation per slot
    kv_cache_hbm_fraction: float = 0.9  # resolved by the Generator
    decode_bucketing: bool = True      # fixed-shape compiled decode step
    max_num_tokens: int = 8192


def slot_write(cache_array: jax.Array, new: jax.Array,
               slot: jax.Array) -> jax.Array:
    """One prompt's K or V, ``new`` (L, 1, W, K, D) from prefill, written
    into decode slot ``slot`` of the (L, B, W, K, D) cache.  ``slot`` is
    traced, so one program serves every slot."""
    return cache_array.at[:, slot].set(new[:, 0])


class Engine:
    def __init__(self, cfg: ModelConfig, params: Any, ecfg: EngineConfig):
        if cfg.family not in ("dense", "vlm", "moe", "hybrid", "ssm"):
            raise ValueError(f"engine does not serve family {cfg.family!r}")
        self.cfg = cfg
        self.ecfg = ecfg
        self.params = params
        self.sched = ContinuousBatchingScheduler(SchedulerConfig(
            max_batch=ecfg.max_batch, max_num_tokens=ecfg.max_num_tokens,
            chunked_prefill=False))
        mod = models.module_for(cfg)
        W = mod.cache_width(cfg, ecfg.max_seq) if hasattr(mod, "cache_width") \
            else ecfg.max_seq
        self._W = W
        dt = models.param_dtype(cfg)
        B = ecfg.max_batch
        if cfg.family in ("dense", "vlm", "moe"):
            L, K, D = cfg.num_layers, cfg.num_kv_heads, cfg.head_dim
            self.cache = {
                "k": jnp.zeros((L, B, W, K, D), dt),
                "v": jnp.zeros((L, B, W, K, D), dt),
                "pos": jnp.zeros((B,), jnp.int32),
            }
        else:
            raise NotImplementedError(
                "batched slots for recurrent families use the static path")
        self._pos_host = np.zeros(B, np.int32)
        self._last_tok = np.zeros(B, np.int32)

        def decode_step(params, token, cache):
            return models.decode_step(params, cfg, token, cache)

        self._decode_fn = jax.jit(decode_step, donate_argnames=("cache",))
        self._slot_write = jax.jit(slot_write)
        self._prefill_cache: Dict[int, Any] = {}
        self._launched: Set[str] = set()   # programs launched at least once
        self._new_programs: List[str] = []  # first launched this step
        self._tracer = NULL_TRACER          # the step's, from get_tracer()
        # device logits of the latest step: {"prefill": {rid: (V,)},
        # "decode": (B, V) by slot or None} — for checks against a reference
        self.step_logits: Dict[str, Any] = {"prefill": {}, "decode": None}

    # ------------------------------------------------------------------
    def _prefill_fn(self, isl: int):
        if isl not in self._prefill_cache:
            cfg, W = self.cfg, self._W

            def prefill(params, tokens):
                return models.prefill(params, cfg, tokens, max_len=W)

            self._prefill_cache[isl] = jax.jit(prefill)
        return self._prefill_cache[isl]

    def _launch(self, program: str) -> None:
        """Note a program's launch; its first one builds it."""
        if program not in self._launched:
            self._launched.add(program)
            self._new_programs.append(program)

    # ------------------------------------------------------------------
    def add_request(self, req: Request) -> None:
        assert req.prompt is not None and len(req.prompt) == req.isl
        self.sched.add(req)

    def _run_prefill(self, req: Request) -> int:
        slot, tracer = req.slot, self._tracer
        with tracer.span("engine.prefill", rid=req.rid, isl=req.isl,
                         slot=slot):
            with tracer.span("engine.prefill.run"):
                self._launch(f"prefill:{req.isl}")
                toks = jnp.asarray(np.asarray(req.prompt, np.int32)[None])
                logits, cache = self._prefill_fn(req.isl)(self.params,
                                                          tokens=toks)
            with tracer.span("engine.prefill.write"):
                self._launch("slot_write")
                at = np.int32(slot)
                self.cache["k"] = self._slot_write(self.cache["k"],
                                                   cache["k"], at)
                self.cache["v"] = self._slot_write(self.cache["v"],
                                                   cache["v"], at)
                self._pos_host[slot] = req.isl
            with tracer.span("engine.prefill.sample"):
                last = self.step_logits["prefill"][req.rid] = logits[0, -1]
                tok = int(jnp.argmax(last))
        self._last_tok[slot] = tok
        req.out_tokens.append(tok)
        return tok

    def _run_decode(self, active: List[Request]) -> None:
        tracer = self._tracer
        with tracer.span("engine.decode", active=len(active)):
            with tracer.span("engine.decode.inputs"):
                self.cache["pos"] = jnp.asarray(self._pos_host)
                tokens = jnp.asarray(self._last_tok[:, None])
            with tracer.span("engine.decode.run"):
                self._launch("decode_step")
                logits, self.cache = self._decode_fn(
                    params=self.params, token=tokens, cache=self.cache)
                logits.block_until_ready()
            with tracer.span("engine.decode.sample"):
                last = self.step_logits["decode"] = logits[:, -1]
                new = np.asarray(jnp.argmax(last, axis=-1), np.int32)
        for req in active:
            self._pos_host[req.slot] += 1
            self._last_tok[req.slot] = new[req.slot]
            req.out_tokens.append(int(new[req.slot]))

    # ------------------------------------------------------------------
    def step(self) -> List[Request]:
        """One engine iteration; returns requests finished this step."""
        tracer = self._tracer = get_tracer()
        with tracer.span("engine.step") as sp:
            with tracer.span("engine.schedule"):
                now = time.perf_counter()
                plan = self.sched.plan(now)
            if plan.empty:
                sp.set(prefills=0, decodes=0, new_programs=0)
                return []
            self.step_logits = {"prefill": {}, "decode": None}
            self._new_programs = []
            for chunk in plan.prefill:     # whole prompts (chunked=False)
                self._run_prefill(chunk.req)
            if plan.decode:
                self._run_decode(plan.decode)
            with tracer.span("engine.commit"):
                now = time.perf_counter()
                done = self.sched.commit(plan, now)
            sp.set(prefills=len(plan.prefill), decodes=len(plan.decode),
                   new_programs=len(self._new_programs))
        m = get_metrics()
        if m is not None:
            self._count(m, plan)
        return done

    def _count(self, m, plan) -> None:
        """The step's ``repro_engine_*`` counters."""
        kind = ("mixed" if plan.prefill and plan.decode else
                "prefill" if plan.prefill else "decode")
        m.inc("repro_engine_steps_total", kind=kind)
        m.inc("repro_engine_decode_slot_steps_total", len(plan.decode))
        m.inc("repro_engine_prefill_tokens_total", plan.ctx_tokens)
        for chunk in plan.prefill:
            req = chunk.req
            if chunk.start == 0:       # admitted this step
                m.observe("repro_engine_queue_wait_ms",
                          1e3 * (req.t_first_sched - req.arrival),
                          buckets=LATENCY_MS_BUCKETS)
        for program in self._new_programs:
            m.inc("repro_engine_new_programs_total", program=program)

    def run_until_drained(self, max_steps: int = 100_000) -> List[Request]:
        done: List[Request] = []
        for _ in range(max_steps):
            done.extend(self.step())
            if self.sched.active == 0:
                break
        return done
