"""Real JAX serving engine (the "repro-jax" backend).

Continuous batching over fixed decode slots with a ring-buffer KV cache:

  - ONE compiled decode step for the whole slot array (fixed shapes +
    donated cache = the TPU-idiomatic analogue of CUDA-graph capture;
    flag: ``decode_bucketing``),
  - whole-prompt prefill compiled per distinct prompt length (the engine
    serves real tokens; the simulator models chunked prefill),
  - per-row positions so slots at different depths decode together,
  - greedy sampling; wall-clock TTFT/TPOT per request.

The configurator's Generator emits a ``LaunchConfig`` this engine consumes
directly (see repro/core/generator.py) — the paper's technique wired in as
a first-class feature.
"""
from __future__ import annotations

import dataclasses
import functools
import time
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro import models
from repro.configs.base import ModelConfig
from repro.serving.request import Phase, Request
from repro.serving.scheduler import ContinuousBatchingScheduler, SchedulerConfig


@dataclasses.dataclass(frozen=True)
class EngineConfig:
    max_batch: int = 8                 # decode slots
    max_seq: int = 256                 # KV allocation per slot
    kv_cache_hbm_fraction: float = 0.9  # resolved by the Generator
    decode_bucketing: bool = True      # fixed-shape compiled decode step
    max_num_tokens: int = 8192


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
        self._decode_fn = jax.jit(
            functools.partial(models.decode_step, cfg=self.cfg),
            static_argnames=(), donate_argnames=("cache",))
        self._prefill_cache: Dict[int, Any] = {}
        # device logits of the latest step: {"prefill": {rid: (V,)},
        # "decode": (B, V) by slot or None} — for checks against a reference
        self.step_logits: Dict[str, Any] = {"prefill": {}, "decode": None}

    # ------------------------------------------------------------------
    def _prefill_fn(self, isl: int):
        if isl not in self._prefill_cache:
            self._prefill_cache[isl] = jax.jit(
                functools.partial(models.prefill, cfg=self.cfg,
                                  max_len=self._W))
        return self._prefill_cache[isl]

    # ------------------------------------------------------------------
    def add_request(self, req: Request) -> None:
        assert req.prompt is not None and len(req.prompt) == req.isl
        self.sched.add(req)

    def _run_prefill(self, req: Request) -> int:
        toks = jnp.asarray(np.asarray(req.prompt, np.int32)[None])
        logits, cache = self._prefill_fn(req.isl)(self.params, tokens=toks)
        slot = req.slot
        self.cache["k"] = self.cache["k"].at[:, slot].set(cache["k"][:, 0])
        self.cache["v"] = self.cache["v"].at[:, slot].set(cache["v"][:, 0])
        self._pos_host[slot] = req.isl
        last = self.step_logits["prefill"][req.rid] = logits[0, -1]
        tok = int(jnp.argmax(last))
        self._last_tok[slot] = tok
        req.out_tokens.append(tok)
        return tok

    def _run_decode(self, active: List[Request]) -> None:
        self.cache["pos"] = jnp.asarray(self._pos_host)
        tokens = jnp.asarray(self._last_tok[:, None])
        logits, self.cache = self._decode_fn(
            params=self.params, token=tokens, cache=self.cache)
        logits.block_until_ready()
        last = self.step_logits["decode"] = logits[:, -1]
        new = np.asarray(jnp.argmax(last, axis=-1), np.int32)
        for req in active:
            self._pos_host[req.slot] += 1
            self._last_tok[req.slot] = new[req.slot]
            req.out_tokens.append(int(new[req.slot]))

    # ------------------------------------------------------------------
    def step(self) -> List[Request]:
        """One engine iteration; returns requests finished this step."""
        now = time.perf_counter()
        plan = self.sched.plan(now)
        if plan.empty:
            return []
        self.step_logits = {"prefill": {}, "decode": None}
        for chunk in plan.prefill:     # whole prompts (chunked=False)
            self._run_prefill(chunk.req)
        if plan.decode:
            self._run_decode(plan.decode)
        now = time.perf_counter()
        return self.sched.commit(plan, now)

    def run_until_drained(self, max_steps: int = 100_000) -> List[Request]:
        done: List[Request] = []
        for _ in range(max_steps):
            done.extend(self.step())
            if self.sched.active == 0:
                break
        return done
