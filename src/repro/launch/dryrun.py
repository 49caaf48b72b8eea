import os
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=512")
# The two lines above MUST run before any other import (jax locks the device
# count at first init).  512 host-platform placeholder devices let
# jax.make_mesh build the production meshes; nothing is ever executed.

# Multi-pod dry-run: lower + compile every (architecture x input shape) on
# the production meshes and extract the roofline inputs.
#
#   PYTHONPATH=src python -m repro.launch.dryrun --arch qwen3-14b --shape train_4k
#   PYTHONPATH=src python -m repro.launch.dryrun --all --out results/dryrun.jsonl
#
# Per pair and mesh this records: per-device memory analysis (proves fit),
# HLO FLOPs/bytes from compiled.cost_analysis(), per-collective byte sums
# parsed from the partitioned HLO (all-gather / all-reduce / reduce-scatter /
# all-to-all / collective-permute), and lower/compile wall times.

import argparse
import dataclasses
import json
import re
import time
import traceback
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro import models
from repro.configs import INPUT_SHAPES, dryrun_pairs, get_config
from repro.configs.base import InputShape, ModelConfig
from repro.launch import sharding as shd
from repro.launch.mesh import make_production_mesh
from repro.models.common import ParamSpec
from repro.training import optimizer as opt
from repro.training.train_step import make_train_step


# ---------------------------------------------------------------------------
# abstract inputs
# ---------------------------------------------------------------------------

def abstract_opt_state(cfg: ModelConfig):
    ap = models.abstract_params(cfg)
    f32 = jax.tree.map(lambda s: jax.ShapeDtypeStruct(s.shape, jnp.float32), ap)
    return opt.OptState(mu=f32, nu=jax.tree.map(lambda s: s, f32),
                        step=jax.ShapeDtypeStruct((), jnp.int32))


def abstract_cache(cfg: ModelConfig, batch: int, seq: int):
    """ShapeDtypeStruct tree matching each family's decode cache."""
    dt = models.param_dtype(cfg)
    i32 = jnp.int32
    sds = jax.ShapeDtypeStruct
    K, D = cfg.num_kv_heads, cfg.head_dim
    if cfg.family in ("dense", "vlm", "moe"):
        W = cfg.kv_cache_len(seq)
        L = cfg.num_layers
        if cfg.sharding.kv_quant and cfg.family != "moe":
            return {"k": sds((L, batch, W, K, D), jnp.int8),
                    "v": sds((L, batch, W, K, D), jnp.int8),
                    "k_scale": sds((L, batch, W, K), jnp.float32),
                    "v_scale": sds((L, batch, W, K), jnp.float32),
                    "pos": sds((), i32)}
        return {"k": sds((L, batch, W, K, D), dt),
                "v": sds((L, batch, W, K, D), dt),
                "pos": sds((), i32)}
    if cfg.family == "audio":
        L, H = cfg.num_layers, cfg.num_heads
        return {"k": sds((L, batch, seq, K, D), dt),
                "v": sds((L, batch, seq, K, D), dt),
                "ck": sds((L, batch, cfg.num_source_positions, H, D), dt),
                "cv": sds((L, batch, cfg.num_source_positions, H, D), dt),
                "pos": sds((), i32)}
    if cfg.family == "hybrid":
        from repro.models import hybrid
        G, T = hybrid.n_groups(cfg), hybrid.n_tail(cfg)
        W = min(seq, cfg.local_window)
        w, cw = cfg.lru_width, cfg.conv_width
        return {"k": sds((G, batch, W, K, D), dt),
                "v": sds((G, batch, W, K, D), dt),
                "h_group": sds((G, 2, batch, w), jnp.float32),
                "conv_group": sds((G, 2, batch, cw - 1, w), dt),
                "h_tail": sds((T, batch, w), jnp.float32),
                "conv_tail": sds((T, batch, cw - 1, w), dt),
                "pos": sds((), i32)}
    if cfg.family == "ssm":
        from repro.models import xlstm
        G = xlstm.n_pairs(cfg)
        nh, u, d = cfg.num_heads, xlstm.up_dim(cfg), cfg.d_model
        dhm, dhs = u // nh, d // nh
        return {"m": {"C": sds((G, batch, nh, dhm, dhm), jnp.float32),
                      "n": sds((G, batch, nh, dhm), jnp.float32),
                      "m": sds((G, batch, nh), jnp.float32),
                      "conv": sds((G, batch, cfg.conv_width - 1, u), dt)},
                "s": {"c": sds((G, batch, nh, dhs), jnp.float32),
                      "n": sds((G, batch, nh, dhs), jnp.float32),
                      "m": sds((G, batch, nh, dhs), jnp.float32),
                      "h": sds((G, batch, nh, dhs), jnp.float32)},
                "pos": sds((), i32)}
    raise ValueError(cfg.family)


def input_specs(arch: str, shape_name: str,
                cfg: Optional[ModelConfig] = None) -> Dict[str, Any]:
    """ShapeDtypeStruct stand-ins for every model input of this step."""
    cfg = cfg or get_config(arch)
    shape = INPUT_SHAPES[shape_name]
    B, S = shape.global_batch, shape.seq_len
    sds = jax.ShapeDtypeStruct
    if shape.kind == "train":
        out = {"tokens": sds((B, S), jnp.int32),
               "labels": sds((B, S), jnp.int32)}
        out.update(models.extra_train_inputs(cfg, B, S, abstract=True))
        return out
    if shape.kind == "prefill":
        out = {"tokens": sds((B, S), jnp.int32)}
        out.update(models.extra_train_inputs(cfg, B, S, abstract=True))
        return out
    # decode
    out = {"token": sds((B, 1), jnp.int32),
           "cache": abstract_cache(cfg, B, S)}
    if cfg.family == "vlm":
        out["mrope_positions"] = sds((3, B, 1), jnp.int32)
    return out


# ---------------------------------------------------------------------------
# step builders: (fn, args, in_shardings, out_shardings)
# ---------------------------------------------------------------------------

def build_step(cfg: ModelConfig, shape: InputShape, mesh: Mesh):
    specs = input_specs(cfg.name, shape.name, cfg=cfg)
    mode = "train" if shape.kind == "train" else "serve"
    p_sh = shd.param_shardings(cfg, mode, mesh)
    rep = NamedSharding(mesh, P())
    bsh = lambda nd: NamedSharding(
        mesh, shd.batch_spec(mesh, shape.global_batch, nd))
    extras_sh = {}
    for k in ("frames", "image_embeds"):
        if k in specs:
            extras_sh[k] = bsh(2)
    if "mrope_positions" in specs:
        extras_sh["mrope_positions"] = NamedSharding(
            mesh, P(None, *shd.batch_spec(mesh, shape.global_batch, 1)))

    if shape.kind == "train":
        ap = models.abstract_params(cfg)
        ostate = abstract_opt_state(cfg)
        o_sh = opt.OptState(mu=p_sh, nu=jax.tree.map(lambda s: s, p_sh),
                            step=rep)
        step = make_train_step(cfg)
        extras = {k: v for k, v in specs.items()
                  if k not in ("tokens", "labels")}

        def fn(params, opt_state, tokens, labels, ex):
            return step(params, opt_state, tokens, labels, **ex)

        args = (ap, ostate, specs["tokens"], specs["labels"], extras)
        in_sh = (p_sh, o_sh, bsh(1), bsh(1), extras_sh)
        out_sh = (p_sh, o_sh, None)
        return fn, args, in_sh, out_sh

    if shape.kind == "prefill":
        cache_sh = shd.cache_shardings(cfg, mesh, shape.global_batch)
        extras = {k: v for k, v in specs.items() if k != "tokens"}

        def fn(params, tokens, ex):
            return models.prefill(params, cfg, tokens, max_len=shape.seq_len,
                                  **ex)

        args = (models.abstract_params(cfg), specs["tokens"], extras)
        in_sh = (shd.param_shardings(cfg, "serve", mesh), bsh(1), extras_sh)
        out_sh = (None, cache_sh)
        return fn, args, in_sh, out_sh

    # decode
    cache_sh = shd.cache_shardings(cfg, mesh, shape.global_batch)
    extras = {k: v for k, v in specs.items() if k not in ("token", "cache")}

    def fn(params, token, cache, ex):
        return models.decode_step(params, cfg, token, cache, **ex)

    args = (models.abstract_params(cfg), specs["token"], specs["cache"],
            extras)
    in_sh = (shd.param_shardings(cfg, "serve", mesh), bsh(1), cache_sh,
             extras_sh)
    out_sh = (None, cache_sh)
    return fn, args, in_sh, out_sh


# ---------------------------------------------------------------------------
# HLO collective parsing
# ---------------------------------------------------------------------------

_SHAPE_RE = re.compile(r"(bf16|f32|f16|s32|u32|s8|u8|pred|f8\w*)\[([\d,]*)\]")
_DTYPE_BYTES = {"bf16": 2, "f16": 2, "f32": 4, "s32": 4, "u32": 4,
                "s8": 1, "u8": 1, "pred": 1}
_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")


def _shape_bytes(text: str) -> float:
    """Bytes of the first shape literal in an HLO result/type string
    (tuple shapes: sum all element shapes)."""
    total = 0.0
    for m in _SHAPE_RE.finditer(text):
        dt, dims = m.group(1), m.group(2)
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * _DTYPE_BYTES.get(dt, 2)
    return total


def collective_bytes(hlo_text: str) -> Dict[str, float]:
    """Sum result-shape bytes of every collective op, by kind."""
    out = {k: 0.0 for k in _COLLECTIVES}
    for line in hlo_text.splitlines():
        s = line.strip()
        eq = s.find(" = ")
        if eq < 0:
            continue
        rhs = s[eq + 3:]
        for kind in _COLLECTIVES:
            # match op name: "bf16[...] all-reduce(" etc.
            if f" {kind}(" in rhs or rhs.startswith(kind + "("):
                out[kind] += _shape_bytes(rhs[:rhs.find(kind)] or s[:eq])
                break
    return out


# ---------------------------------------------------------------------------
# per-pair dry run
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class DryrunResult:
    arch: str
    shape: str
    mesh: str
    ok: bool
    flops: float = 0.0                 # raw cost_analysis (loop bodies x1!)
    bytes_accessed: float = 0.0        # raw cost_analysis
    flops_corrected: float = 0.0       # trip-count-aware HLO accounting
    bytes_corrected: float = 0.0
    collectives: Dict[str, float] = dataclasses.field(default_factory=dict)
    unresolved_loops: int = 0
    mem: Dict[str, float] = dataclasses.field(default_factory=dict)
    lower_s: float = 0.0
    compile_s: float = 0.0
    error: str = ""

    def to_json(self) -> str:
        return json.dumps(dataclasses.asdict(self))


def run_pair(arch: str, shape_name: str, multi_pod: bool,
             verbose: bool = True, hlo_dir: str = "",
             sharding_overrides: Optional[Dict] = None,
             expert_axis: int = 0) -> DryrunResult:
    cfg = get_config(arch)
    if sharding_overrides:
        cfg = dataclasses.replace(
            cfg, sharding=dataclasses.replace(cfg.sharding,
                                              **sharding_overrides))
    shape = INPUT_SHAPES[shape_name]
    mesh_name = "2x16x16" if multi_pod else "16x16"
    res = DryrunResult(arch=arch, shape=shape_name, mesh=mesh_name, ok=False)
    try:
        mesh = make_production_mesh(multi_pod=multi_pod,
                                    expert_axis=expert_axis)
        from repro.models import common as _cm
        _cm.set_mesh_axes(mesh)
        fn, args, in_sh, out_sh = build_step(cfg, shape, mesh)
        jitted = jax.jit(fn, in_shardings=in_sh, out_shardings=out_sh)
        t0 = time.perf_counter()
        with jax.set_mesh(mesh):
            lowered = jitted.lower(*args)
            res.lower_s = time.perf_counter() - t0
            t1 = time.perf_counter()
            compiled = lowered.compile()
            res.compile_s = time.perf_counter() - t1
        ca = compiled.cost_analysis()
        if isinstance(ca, (list, tuple)):
            ca = ca[0] if ca else {}
        res.flops = float(ca.get("flops", 0.0))
        res.bytes_accessed = float(ca.get("bytes accessed", 0.0))
        ma = compiled.memory_analysis()
        if ma is not None:
            for field in ("argument_size_in_bytes", "output_size_in_bytes",
                          "temp_size_in_bytes", "generated_code_size_in_bytes",
                          "alias_size_in_bytes"):
                v = getattr(ma, field, None)
                if v is not None:
                    res.mem[field] = float(v)
        from repro.launch import hlo_analysis
        hlo_text = compiled.as_text()
        if hlo_dir:
            import gzip
            os.makedirs(hlo_dir, exist_ok=True)
            fn_out = os.path.join(
                hlo_dir, f"{arch}_{shape_name}_{mesh_name}.hlo.gz")
            with gzip.open(fn_out, "wt") as f:
                f.write(hlo_text)
        st = hlo_analysis.analyze(hlo_text)
        res.flops_corrected = st.flops
        res.bytes_corrected = st.bytes_accessed
        res.collectives = st.collectives
        res.unresolved_loops = st.unresolved_loops
        res.ok = True
        if verbose:
            peak = (res.mem.get("argument_size_in_bytes", 0)
                    + res.mem.get("temp_size_in_bytes", 0)
                    - res.mem.get("alias_size_in_bytes", 0))
            print(f"[OK] {arch} x {shape_name} on {mesh_name}: "
                  f"flops={res.flops:.3e} bytes={res.bytes_accessed:.3e} "
                  f"mem/device≈{peak/2**30:.2f}GiB "
                  f"(lower {res.lower_s:.1f}s compile {res.compile_s:.1f}s)",
                  flush=True)
    except Exception as e:  # noqa: BLE001 — recorded, rerun fails loudly
        res.error = f"{type(e).__name__}: {e}\n{traceback.format_exc()[-2000:]}"
        if verbose:
            print(f"[FAIL] {arch} x {shape_name} on {mesh_name}: "
                  f"{type(e).__name__}: {str(e)[:400]}", flush=True)
    finally:
        from repro.models import common as _cm
        _cm.set_mesh_axes(())
    return res


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--mesh", choices=["single", "multi", "both"],
                    default="single")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default="results/dryrun.jsonl")
    ap.add_argument("--hlo-dir", default="",
                    help="archive partitioned HLO (gzip) for offline "
                         "re-analysis without recompiling")
    ap.add_argument("--sharding", default="",
                    help="ShardingRules overrides for perf iteration, "
                         "e.g. 'remat=dots,moe_mode=ffn,microbatches=2'")
    ap.add_argument("--expert-axis", type=int, default=0,
                    help="split the model axis into (expert, model) of this "
                         "expert width (perf-iteration 3-axis mesh)")
    args = ap.parse_args()
    overrides = {}
    for kv in filter(None, args.sharding.split(",")):
        k, v = kv.split("=")
        overrides[k] = (int(v) if v.lstrip("-").isdigit()
                        else v == "true" if v in ("true", "false") else v)

    pairs = dryrun_pairs() if args.all else [(args.arch, args.shape)]
    meshes = {"single": [False], "multi": [True], "both": [False, True]}[args.mesh]

    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    n_ok = n_fail = 0
    with open(args.out, "a") as f:
        for arch, shape_name in pairs:
            for mp in meshes:
                r = run_pair(arch, shape_name, mp, hlo_dir=args.hlo_dir,
                             sharding_overrides=overrides or None,
                             expert_axis=args.expert_axis)
                f.write(r.to_json() + "\n")
                f.flush()
                n_ok += r.ok
                n_fail += not r.ok
    print(f"\ndry-run complete: {n_ok} ok, {n_fail} failed -> {args.out}")
    raise SystemExit(1 if n_fail else 0)


if __name__ == "__main__":
    main()
