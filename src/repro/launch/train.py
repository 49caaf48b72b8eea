"""Training driver.

CPU smoke scale by default (reduced config, host mesh); ``--production``
lowers against the full config on the production mesh first (sanity) and
refuses to execute on non-TPU backends.

    PYTHONPATH=src python -m repro.launch.train --arch internlm2-1.8b \
        --steps 20 --batch 4 --seq 64
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import models
from repro.configs import get_config
from repro.core.jaxenv import use_compile_cache
from repro.training import checkpoint as ckpt
from repro.training import data as dat
from repro.training import optimizer as opt
from repro.training.train_step import make_train_step


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="internlm2-1.8b")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--checkpoint", default="")
    ap.add_argument("--full-config", action="store_true",
                    help="use the full (not reduced) config — TPU scale")
    args = ap.parse_args()
    use_compile_cache()

    cfg = get_config(args.arch)
    if not args.full_config:
        cfg = cfg.reduced()
    print(f"arch={cfg.name} family={cfg.family} params≈{cfg.param_count():,}")

    params = models.init_params(cfg, jax.random.PRNGKey(args.seed))
    opt_state = opt.init(params)
    ocfg = opt.AdamWConfig(lr=args.lr, warmup_steps=max(args.steps // 10, 1),
                           total_steps=args.steps)
    step_fn = jax.jit(make_train_step(cfg, ocfg))
    ds = dat.make_dataset(cfg, args.seq, args.batch, args.seed)
    extras = models.extra_train_inputs(cfg, args.batch, args.seq)

    t0 = time.perf_counter()
    for i in range(args.steps):
        b = ds.batch(i)
        params, opt_state, m = step_fn(params, opt_state,
                                       jnp.asarray(b["tokens"]),
                                       jnp.asarray(b["labels"]), **extras)
        if i % max(args.steps // 10, 1) == 0 or i == args.steps - 1:
            print(f"step {i:4d} loss {float(m['loss']):.4f} "
                  f"gnorm {float(m['grad_norm']):.3f} "
                  f"lr {float(m['lr']):.2e}", flush=True)
    dt = time.perf_counter() - t0
    toks = args.steps * args.batch * args.seq
    print(f"done: {args.steps} steps, {toks/dt:.0f} tok/s on "
          f"{jax.default_backend()}")
    if args.checkpoint:
        path = ckpt.save(args.checkpoint, params, opt_state, step=args.steps)
        print(f"checkpoint -> {args.checkpoint}")


if __name__ == "__main__":
    main()
