"""AIConfigurator command-line interface — a thin shell over ``repro.api``.

The paper's user workflow (Fig. 2) as subcommands:

    python -m repro.core.cli search   --model qwen3-32b --isl 4000 --osl 500 \\
        --ttft 1200 --min-speed 60 --chips 16 --dtype fp8 --backend repro-jax
    python -m repro.core.cli generate --from-report report.json --out launch.json
    python -m repro.core.cli compare  --model qwen3-32b --chips 16 \\
        --shapes 4000:200:60,512:1024:30
    python -m repro.core.cli list     backends
    python -m repro.core.cli calibrate run --timer deterministic \\
        --out cal.json
    python -m repro.core.cli calibrate report --artifact cal.json
    python -m repro.core.cli calibrate apply  --artifact cal.json \\
        --model qwen3-32b --isl 4000 --osl 500
    python -m repro.core.cli workload generate --arrivals bursty --rate 2 \\
        --n 200 --lengths sharegpt --seed 7 --out trace.jsonl
    python -m repro.core.cli workload describe --trace trace.jsonl
    python -m repro.core.cli workload replay --trace trace.jsonl \\
        --model qwen3-32b --tp 4 --batch 64 --slo-ttft-p99 2000 \\
        --slo-tpot-p99 80
    python -m repro.core.cli search --model qwen3-32b --isl 4000 --osl 500 \\
        --chips 16 --trace trace.jsonl --slo-ttft-p99 2000 \\
        --slo-tpot-p99 80 --replay-top-k 3
    python -m repro.core.cli capacity sweep --trace trace.jsonl \\
        --model qwen3-32b --tp 4 --batch 64 --ladder 1,2,4 \\
        --routing least_outstanding --json
    python -m repro.core.cli capacity plan --model qwen3-32b --isl 4000 \\
        --osl 500 --chips 16 --trace trace.jsonl --ladder 1,2,4 --top-k 3
    python -m repro.core.cli autoscale run --trace trace.jsonl \\
        --model qwen3-32b --tp 4 --batch 64 --policy target_queue_depth \\
        --max-replicas 4 --save-timeline timeline.jsonl
    python -m repro.core.cli autoscale compare --trace trace.jsonl \\
        --model qwen3-32b --tp 4 --batch 64 --ladder 1,2,4 --json

Every subcommand accepts ``--json`` to emit machine-readable output
(``search --json`` prints the schema-versioned SearchReport) on stdout,
with human chatter kept off it.  Exit codes are stable: 0 success, 1 no
configuration satisfies the SLA, 2 usage or validation error.

Streaming: ``search --stream`` rides ``Configurator.search_iter`` and
emits one JSON-lines record per priced projection plus a terminal
``{"type": "summary", ...}`` record; ``--first-n N`` stops the search as
soon as N SLA-valid configurations are found (works with or without
``--stream``, exit codes unchanged).  A consumer that closes the pipe
early (``head``, an interactive UI) shuts the search down cleanly.

The pre-subcommand flat-flag invocation (``python -m repro.core.cli
--model ... --isl ...``) still works through a deprecation shim and prints
byte-identical results to the ``search`` subcommand.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

from repro.api import (Comparison, Configurator, SearchReport,
                       stop_after_n_valid)
from repro.capacity.routing import ROUTING_POLICIES
from repro.configs import list_archs
from repro.core.backends.base import all_backends, backend_capabilities
from repro.core.generator import generate
from repro.core.hardware import PLATFORMS
from repro.obs.capture import ObsCapture

EXIT_OK = 0
EXIT_NO_CONFIG = 1
EXIT_USAGE = 2

_SUBCOMMANDS = ("search", "generate", "compare", "list", "calibrate",
                "workload", "capacity", "autoscale", "explain", "obs")


# ---------------------------------------------------------------------------
# argument plumbing
# ---------------------------------------------------------------------------

def _add_workload_args(ap: argparse.ArgumentParser, traffic: bool = True,
                       required: bool = True):
    ap.add_argument("--model", required=required, default=None,
                    help=f"one of {', '.join(list_archs(True))}")
    if traffic:
        ap.add_argument("--isl", type=int, required=required, default=None)
        ap.add_argument("--osl", type=int, required=required, default=None)
        ap.add_argument("--prefix-len", type=int, default=0)
    ap.add_argument("--ttft", type=float, default=1000.0,
                    help="TTFT SLA in ms")
    ap.add_argument("--min-speed", type=float, default=None,
                    help="min tokens/s/user SLA")
    ap.add_argument("--chips", type=int, default=8)
    ap.add_argument("--platform", default="tpu_v5e")
    ap.add_argument("--backend", default="repro-jax",
                    help=f"one of {', '.join(all_backends())} "
                         "(or any registered plugin)")
    ap.add_argument("--dtype", default="bf16",
                    choices=["bf16", "fp16", "fp8"])
    ap.add_argument("--modes", default="aggregated,disaggregated")
    ap.add_argument("--moe-alpha", type=float, default=1.2)


def _configurator(args, isl=None, osl=None, prefix_len=0) -> Configurator:
    return (Configurator.for_model(args.model)
            .traffic(isl if isl is not None else args.isl,
                     osl if osl is not None else args.osl,
                     prefix_len or getattr(args, "prefix_len", 0))
            .sla(ttft_ms=args.ttft, min_tokens_per_s_user=args.min_speed)
            .cluster(chips=args.chips, platform=args.platform)
            .backend(args.backend)
            .dtype(args.dtype)
            .modes(*args.modes.split(","))
            .moe_alpha(args.moe_alpha))


# ---------------------------------------------------------------------------
# search
# ---------------------------------------------------------------------------

def _print_search_report(report: SearchReport, args) -> int:
    """The classic human-readable search output (legacy-compatible)."""
    print(report.summary())
    print(f"\ntop {args.top} SLA-valid configurations:")
    for p in report.top_k(args.top):
        print(f"  [{p.mode:13s}] {p.tokens_per_s_per_chip:9.1f} tok/s/chip  "
              f"{p.tokens_per_s_user:7.1f} tok/s/user  "
              f"TTFT {p.ttft_ms:8.1f}ms  {p.config.get('describe', '')}")

    if report.best is None:
        print("\nno configuration satisfies the SLA on this cluster")
        return EXIT_NO_CONFIG
    print(f"\nlaunch command:\n  {report.launch.command}")
    if args.save_launch:
        with open(args.save_launch, "w") as f:
            f.write(report.launch.to_json())
        print(f"launch config -> {args.save_launch}")

    if report.speculative:
        s = report.speculative
        print(f"\nspeculative decoding ({s['draft_model']}, "
              f"acceptance {s['acceptance']}): best gamma={s['gamma']} -> "
              f"{s['speedup_vs_autoregressive']:.2f}x "
              f"({s['tokens_per_s_user']:.0f} tok/s/user)")

    we = report.workload_eval
    if we:
        print(f"\nworkload replay (trace {we['trace']['digest']}, "
              f"{we['trace']['n_requests']} requests) — goodput ranking:")
        by_index = {c["index"]: c for c in we["candidates"]}
        for rank, idx in enumerate(we["ranking"]):
            c = by_index[idx]
            r = c["replay"]
            print(f"  #{rank + 1} [{c['mode']:11s}] {c['describe']:20s} "
                  f"goodput {r['goodput_tok_s']:9.1f} tok/s  "
                  f"attainment {100 * r['slo_attainment']:5.1f}%  "
                  f"p99 TTFT {r['ttft_ms']['p99']:8.1f}ms  "
                  f"(analytical #{c['analytical_rank'] + 1})")
        skipped = [c for c in we["candidates"] if c["skipped"]]
        for c in skipped:
            print(f"  -- [{c['mode']:11s}] {c['describe']:20s} "
                  f"skipped: {c['skipped']}")
        if we["reranked"]:
            print("  note: goodput ranking differs from the analytical "
                  "(static) ranking")
    return EXIT_OK


def _search_policies(args) -> list:
    first_n = getattr(args, "first_n", 0)
    return [stop_after_n_valid(first_n)] if first_n else []


def _attach_speculative(report: SearchReport, cfg: Configurator, args) -> None:
    draft = getattr(args, "draft_model", "")
    if draft and report.best is not None:
        best, _ = cfg.speculative(draft, acceptance=args.acceptance,
                                  report=report)
        report.speculative = {
            "draft_model": draft, "acceptance": args.acceptance,
            "gamma": best.gamma, "tpot_ms": best.tpot_ms,
            "tokens_per_s_user": best.tokens_per_s_user,
            "speedup_vs_autoregressive": best.speedup_vs_autoregressive,
        }


def _attach_workload_eval(report: SearchReport, cfg: Configurator,
                          args) -> None:
    """``--trace``: replay the frontier's top-K under the trace and record
    the goodput re-ranking in the report's ``workload_eval`` section."""
    trace = getattr(args, "trace", "")
    if trace:
        cfg.evaluate_frontier(trace, _slo_from_args(args),
                              top_k=args.replay_top_k, report=report)


def _run_search(args) -> "tuple[SearchReport, Configurator]":
    cfg = _configurator(args)
    # --first-n rides the same policy surface library users get: the
    # iterator stops early and the report records why under early_exit
    report = cfg.search(policies=_search_policies(args))
    _attach_speculative(report, cfg, args)
    _attach_workload_eval(report, cfg, args)
    return report, cfg


def _silence_broken_pipe() -> None:
    """The stream consumer closed the pipe (head, early-exiting UI): point
    stdout at devnull so the interpreter's exit flush cannot raise again.
    An early-exiting consumer is the intended use, so callers exit 0."""
    try:
        devnull = os.open(os.devnull, os.O_WRONLY)
        os.dup2(devnull, sys.stdout.fileno())
    except (OSError, ValueError):
        pass   # stdout has no real fd (captured stream): nothing to salvage


class _JsonLines:
    """JSON-lines emitter shared by every streaming subcommand.

    The first BrokenPipeError marks the emitter ``broken`` and silences
    stdout; ``emit`` then refuses further records so the caller can stop
    producing, still write its save files, and exit 0 — an early-exiting
    consumer (``head``, an interactive UI) is the intended use."""

    def __init__(self):
        self.broken = False

    def emit(self, obj, **dumps_kw) -> bool:
        """Print one record; False once the consumer is gone."""
        if self.broken:
            return False
        try:
            print(json.dumps(obj, **dumps_kw), flush=True)
            return True
        except BrokenPipeError:
            self.broken = True
            _silence_broken_pipe()
            return False

    def emit_text(self, text: str) -> bool:
        """Print pre-serialized lines (e.g. a JSONL artifact) verbatim."""
        if self.broken:
            return False
        try:
            sys.stdout.write(text)
            sys.stdout.flush()
            return True
        except BrokenPipeError:
            self.broken = True
            _silence_broken_pipe()
            return False


def _obs_capture(args) -> ObsCapture:
    """The ``repro.obs`` capture of one command; ``--trace-out -``
    streams through the BrokenPipe-safe emitter."""
    return ObsCapture(args, emit_text=_JsonLines().emit_text)


def _stream_search(args) -> int:
    """``search --stream``: JSON-lines progress records + summary record.

    Honors the same post-search flags as the batch path (--draft-model,
    --save-launch, --save-report); a consumer that closes the pipe early
    still gets the report/launch files written before the clean exit.
    """
    cfg = _configurator(args)
    stream = cfg.search_iter(policies=_search_policies(args))
    em = _JsonLines()
    for ev in stream:
        p = ev.projection
        if not em.emit({
                "type": "candidate", "index": ev.index, "mode": p.mode,
                "describe": p.config.get("describe", ""),
                "tokens_per_s_per_chip": p.tokens_per_s_per_chip,
                "tokens_per_s_user": p.tokens_per_s_user,
                "ttft_ms": p.ttft_ms,
                "mem_bytes_per_chip": p.mem_bytes_per_chip,
                "meets_sla": ev.meets_sla, "n_priced": ev.n_priced,
                "n_valid": ev.n_valid, "frontier_size": ev.frontier_size,
        }):
            stream.close()
            break
    report = stream.report(generate_launch=bool(args.save_launch))
    _attach_speculative(report, cfg, args)
    _attach_workload_eval(report, cfg, args)
    if not em.broken:
        best = report.best
        em.emit({
            "type": "summary", "schema_version": report.schema_version,
            "n_candidates": report.n_candidates,
            "n_valid": stream.n_valid,
            "elapsed_s": report.elapsed_s,
            "early_exit": report.early_exit,
            "database": report.fingerprint,
            "speculative": report.speculative,
            "workload_eval": (None if report.workload_eval is None else {
                "trace": report.workload_eval["trace"]["digest"],
                "ranking": report.workload_eval["ranking"],
                "reranked": report.workload_eval["reranked"],
            }),
            "best": (None if best is None else {
                "mode": best.mode,
                "describe": best.config.get("describe", ""),
                "tokens_per_s_per_chip": best.tokens_per_s_per_chip,
                "tokens_per_s_user": best.tokens_per_s_user,
                "ttft_ms": best.ttft_ms,
            }),
        })
    if args.save_report:
        report.save(args.save_report)
    if args.save_launch and report.launch is not None:
        with open(args.save_launch, "w") as f:
            f.write(report.launch.to_json())
    if em.broken:
        return EXIT_OK
    return EXIT_OK if report.best is not None else EXIT_NO_CONFIG


def cmd_search(args) -> int:
    obs = _obs_capture(args)
    try:
        if args.stream:
            return _stream_search(args)
        report, _ = _run_search(args)
    finally:
        obs.finish()
    if args.save_report:
        report.save(args.save_report)
    if args.json:
        if args.save_launch and report.launch is not None:
            with open(args.save_launch, "w") as f:
                f.write(report.launch.to_json())
        print(report.to_json())
        return EXIT_OK if report.best is not None else EXIT_NO_CONFIG
    return _print_search_report(report, args)


# ---------------------------------------------------------------------------
# generate
# ---------------------------------------------------------------------------

def cmd_generate(args) -> int:
    if args.from_report:
        report = SearchReport.load(args.from_report)
        launch = report.launch
        if launch is None and report.best is not None:
            launch = generate(report.workload, report.best)
    else:
        if args.model is None or args.isl is None or args.osl is None:
            print("error: generate needs --from-report or "
                  "--model/--isl/--osl", file=sys.stderr)
            return EXIT_USAGE
        report, _ = _run_search(args)
        launch = report.launch
    if launch is None:
        print("no configuration satisfies the SLA on this cluster",
              file=sys.stderr)
        return EXIT_NO_CONFIG
    if args.out:
        with open(args.out, "w") as f:
            f.write(launch.to_json())
    if args.json:
        print(launch.to_json())
    else:
        print(launch.command)
        if args.out:
            print(f"launch config -> {args.out}")
    return EXIT_OK


# ---------------------------------------------------------------------------
# compare
# ---------------------------------------------------------------------------

def _parse_shapes(text: str):
    """``isl:osl[:min_speed],...`` -> list of compare-variant dicts."""
    variants = []
    for part in text.split(","):
        bits = part.split(":")
        if len(bits) not in (2, 3):
            raise ValueError(
                f"bad shape {part!r}; expected isl:osl or isl:osl:min_speed")
        v = {"isl": int(bits[0]), "osl": int(bits[1])}
        if len(bits) == 3:
            v["min_tokens_per_s_user"] = float(bits[2])
        variants.append(v)
    return variants


def cmd_compare(args) -> int:
    variants = _parse_shapes(args.shapes)
    cfg = _configurator(args, isl=variants[0]["isl"], osl=variants[0]["osl"])
    comparison: Comparison = cfg.compare(variants)
    if args.json:
        print(comparison.to_json())
    else:
        print(comparison.summary())
    return EXIT_OK if any(r.best for r in comparison.reports) \
        else EXIT_NO_CONFIG


# ---------------------------------------------------------------------------
# calibrate
# ---------------------------------------------------------------------------

def cmd_calibrate_run(args) -> int:
    """Measure kernels, fit per-family corrections, write the artifact."""
    from repro.calibrate import (accuracy_report, format_accuracy,
                                 make_timer, run_calibration)
    created_at = args.timestamp
    if not created_at:
        import datetime
        created_at = datetime.datetime.now(datetime.timezone.utc) \
            .isoformat(timespec="seconds")
    families = args.families.split(",") if args.families else None
    art = run_calibration(
        platform=args.platform, backend=args.backend,
        timer=make_timer(args.timer, args.platform),
        created_at=created_at, points_per_axis=args.points,
        families=families, notes=args.notes, interpret=args.interpret)
    art.save(args.out)
    report = accuracy_report(art)
    if args.json:
        print(json.dumps({"artifact": args.out, "report": report}, indent=2))
    else:
        print(format_accuracy(report))
        print(f"calibration artifact -> {args.out}")
    return EXIT_OK


def cmd_calibrate_report(args) -> int:
    """Audit an artifact: per-family MAPE, calibrated vs uncalibrated."""
    from repro.calibrate import (CalibrationArtifact, accuracy_report,
                                 format_accuracy)
    report = accuracy_report(CalibrationArtifact.load(args.artifact))
    if args.json:
        print(json.dumps(report, indent=2))
    else:
        print(format_accuracy(report))
    return EXIT_OK


def cmd_calibrate_apply(args) -> int:
    """Load an artifact into a PerfDatabase; with a workload, run the
    calibrated search — without one, print the calibrated fingerprint."""
    from repro.calibrate import CalibrationArtifact
    art = CalibrationArtifact.load(args.artifact)
    workload_args = (args.model, args.isl, args.osl)
    if any(a is not None for a in workload_args) \
            and not all(a is not None for a in workload_args):
        print("error: calibrate apply needs all of --model/--isl/--osl "
              "for a calibrated search (or none, to print the calibrated "
              "fingerprint)", file=sys.stderr)
        return EXIT_USAGE
    if args.model is not None:
        # the apply parser defaults platform/backend to None (sentinel):
        # any explicitly passed value that mismatches the artifact earns
        # a note before the artifact's calibrated pair wins
        explicit = [(flag, got) for flag, got, want in
                    (("--platform", args.platform, art.platform),
                     ("--backend", args.backend, art.backend))
                    if got is not None and got != want]
        if explicit:
            print(f"note: using the artifact's calibrated pair "
                  f"({art.platform}, {art.backend}); ignoring "
                  + ", ".join(f"{f} {g}" for f, g in explicit),
                  file=sys.stderr)
        args.platform = art.platform
        args.backend = art.backend
        cfg = _configurator(args).with_calibration(art)
        report = cfg.search(policies=_search_policies(args))
        if args.save_report:
            report.save(args.save_report)
        if args.json:
            print(report.to_json())
        else:
            print(report.summary())
            fp = report.fingerprint or {}
            print(f"calibration: {json.dumps(fp.get('calibration'))}")
        return EXIT_OK if report.best is not None else EXIT_NO_CONFIG
    from repro.core.perf_database import PerfDatabase
    db = PerfDatabase(art.platform, art.backend, calibration=art)
    fp = db.fingerprint()
    if args.json:
        print(json.dumps(fp, indent=2))
    else:
        print(f"calibrated PerfDatabase ({art.platform}, {art.backend}):")
        print(json.dumps(fp, indent=2))
    return EXIT_OK


# ---------------------------------------------------------------------------
# workload
# ---------------------------------------------------------------------------

def _parse_tenants(text: str, lengths) -> tuple:
    """``name:weight[:priority],...`` -> TenantSpec tuple (shared lengths)."""
    from repro.workloads import TenantSpec
    tenants = []
    for part in text.split(","):
        bits = part.split(":")
        if len(bits) not in (2, 3):
            raise ValueError(f"bad tenant {part!r}; expected "
                             "name:weight or name:weight:priority")
        tenants.append(TenantSpec(
            name=bits[0], weight=float(bits[1]),
            priority=int(bits[2]) if len(bits) == 3 else 0,
            lengths=lengths))
    return tuple(tenants)


def _trace_spec_from_args(args):
    from repro.workloads import ArrivalSpec, LengthSpec, TenantSpec, TraceSpec
    if args.spec:
        with open(args.spec) as f:
            return TraceSpec.from_dict(json.load(f))
    isl_lo, isl_hi = (int(b) for b in args.isl_range.split(":"))
    osl_lo, osl_hi = (int(b) for b in args.osl_range.split(":"))
    lengths = LengthSpec(kind=args.lengths, isl=args.isl, osl=args.osl,
                         isl_lo=isl_lo, isl_hi=isl_hi,
                         osl_lo=osl_lo, osl_hi=osl_hi, sigma=args.sigma)
    tenants = (_parse_tenants(args.tenants, lengths) if args.tenants
               else (TenantSpec(lengths=lengths),))
    arrivals = ArrivalSpec(kind=args.arrivals, rate_rps=args.rate,
                           burst_factor=args.burst_factor,
                           period_s=args.period, amplitude=args.amplitude)
    return TraceSpec(n_requests=args.n, arrivals=arrivals, tenants=tenants)


def cmd_workload_generate(args) -> int:
    from repro.workloads import generate_trace
    spec = _trace_spec_from_args(args)
    trace = generate_trace(spec, seed=args.seed)
    trace.save(args.out)
    desc = trace.describe()
    if args.json:
        print(json.dumps({"out": args.out, "describe": desc}, indent=2))
    else:
        print(f"trace -> {args.out}  ({desc['n_requests']} requests, "
              f"{desc['duration_s']:.1f}s, {desc['arrival_rate_rps']:.2f} "
              f"req/s, digest {desc['digest']})")
    return EXIT_OK


def cmd_workload_describe(args) -> int:
    from repro.workloads import WorkloadTrace
    desc = WorkloadTrace.load(args.trace).describe()
    if args.json:
        print(json.dumps(desc, indent=2))
    else:
        print(f"trace {args.trace}: {desc['n_requests']} requests over "
              f"{desc['duration_s']:.1f}s ({desc['arrival_rate_rps']:.2f} "
              f"req/s), digest {desc['digest']}")
        for name, n in sorted(desc["tenants"].items()):
            print(f"  tenant {name}: {n} requests")
        for axis in ("isl", "osl"):
            if axis in desc:
                d = desc[axis]
                print(f"  {axis}: mean {d['mean']:.0f}  p50 {d['p50']:.0f}  "
                      f"p95 {d['p95']:.0f}  max {d['max']:.0f}")
    return EXIT_OK


def _slo_from_args(args):
    from repro.workloads import SLOSpec
    return SLOSpec(ttft_p99_ms=args.slo_ttft_p99,
                   tpot_p99_ms=args.slo_tpot_p99)


def _explicit_candidate(args, trace, n_chips=None):
    """One explicit serving candidate from ``--tp/--pp/--ep/--batch``
    flags plus a trace-shaped workload descriptor — shared by
    ``workload replay`` and ``capacity sweep``."""
    from repro.core.config import (CandidateConfig, ClusterSpec,
                                   ParallelismConfig, RuntimeFlags, SLA,
                                   WorkloadDescriptor)
    w = WorkloadDescriptor(
        model=args.model, isl=trace.mean_isl(), osl=trace.mean_osl(),
        sla=SLA(), cluster=ClusterSpec(
            n_chips=n_chips if n_chips is not None else args.tp * args.pp,
            platform=args.platform),
        backend=args.backend, modes=("aggregated",), dtype=args.dtype)
    cand = CandidateConfig(
        parallel=ParallelismConfig(tp=args.tp, pp=args.pp, ep=args.ep),
        batch_size=args.batch,
        flags=RuntimeFlags(max_num_tokens=args.max_num_tokens))
    return w, cand


def cmd_workload_replay(args) -> int:
    """Replay a trace against one explicit serving configuration."""
    from repro.core.task_runner import TaskRunner
    from repro.workloads import WorkloadTrace
    trace = WorkloadTrace.load(args.trace)
    w, cand = _explicit_candidate(args, trace)
    runner = TaskRunner(w)
    sim = runner.simulator(cand, priority_admission=True,
                           max_queue=args.max_queue)
    obs = _obs_capture(args)
    try:
        metrics = sim.replay(trace, slo=_slo_from_args(args),
                             max_steps=args.max_steps)
    finally:
        obs.finish()
    payload = {"trace": {"path": args.trace, "digest": trace.digest()},
               "config": {"model": args.model, "describe": cand.describe(),
                          "platform": args.platform,
                          "backend": args.backend, "dtype": args.dtype},
               "metrics": metrics.to_dict()}
    if args.json:
        print(json.dumps(payload, indent=2))
    else:
        m = metrics
        print(f"replayed {m.n_requests} requests ({cand.describe()}): "
              f"{m.completed} completed, {m.rejected} rejected, "
              f"{m.unfinished} unfinished in {m.duration_s:.1f}s virtual")
        print(f"  TTFT ms  p50 {m.ttft_ms['p50']:.1f}  "
              f"p95 {m.ttft_ms['p95']:.1f}  p99 {m.ttft_ms['p99']:.1f}")
        print(f"  TPOT ms  p50 {m.tpot_ms['p50']:.1f}  "
              f"p95 {m.tpot_ms['p95']:.1f}  p99 {m.tpot_ms['p99']:.1f}")
        print(f"  queue depth mean {m.queue_depth_mean:.1f} "
              f"max {m.queue_depth_max}")
        print(f"  throughput {m.throughput_tok_s:.1f} tok/s; goodput "
              f"{m.goodput_tok_s:.1f} tok/s at "
              f"{100 * m.slo_attainment:.1f}% SLO attainment")
    return EXIT_OK if metrics.completed > 0 else EXIT_NO_CONFIG


# ---------------------------------------------------------------------------
# capacity
# ---------------------------------------------------------------------------

def _parse_ladder(text: str) -> tuple:
    """``1,2,4`` -> ascending replica-count ladder."""
    try:
        return tuple(int(b) for b in text.split(","))
    except ValueError:
        raise ValueError(f"bad ladder {text!r}; expected a comma list of "
                         "replica counts, e.g. 1,2,4") from None


def cmd_capacity_sweep(args) -> int:
    """Ladder sweep of one explicit candidate: stream-friendly per-rung
    records (JSON-lines with ``--json``) plus a min-chip summary."""
    from repro.capacity import iter_ladder
    from repro.core.task_runner import TaskRunner
    from repro.workloads import WorkloadTrace
    trace = WorkloadTrace.load(args.trace)
    ladder = _parse_ladder(args.ladder)
    w, cand = _explicit_candidate(args, trace,
                                  n_chips=args.tp * args.pp * max(ladder))
    runner = TaskRunner(w)
    best = None
    records = []
    em = _JsonLines()
    obs = _obs_capture(args)
    try:
        for rec in iter_ladder(runner, [cand], trace, _slo_from_args(args),
                               ladder=ladder, routing=args.routing,
                               attain_target=args.attain_target,
                               max_steps=args.max_steps,
                               max_queue=args.max_queue):
            records.append(rec)
            if rec["attains"] and (best is None or rec["total_chips"]
                                   < best["total_chips"]):
                best = rec
            if args.json:
                m = rec["metrics"]
                # "describe" is always the string form; the summary
                # record's "deployment" is always the full dict — one
                # shape per key
                if not em.emit({
                        "type": "rung", "replicas": rec["replicas"],
                        "describe": rec["deployment"]["describe"],
                        "total_chips": rec["total_chips"],
                        "pruned": rec["pruned"], "attains": rec["attains"],
                        "goodput_tok_s": m["goodput_tok_s"] if m else None,
                        "slo_attainment": m["slo_attainment"] if m else None,
                        "p99_ttft_ms": m["ttft_ms"]["p99"] if m else None,
                        "imbalance": m["imbalance"] if m else None,
                }):
                    break           # consumer gone: stop sweeping rungs
            else:
                if rec["pruned"]:
                    print(f"  {rec['deployment']['describe']:>16s} "
                          f"{rec['total_chips']:4d} chips  pruned "
                          f"({rec['pruned']})")
                else:
                    m = rec["metrics"]
                    print(f"  {rec['deployment']['describe']:>16s} "
                          f"{rec['total_chips']:4d} chips  goodput "
                          f"{m['goodput_tok_s']:9.1f} tok/s  attainment "
                          f"{100 * m['slo_attainment']:5.1f}%  p99 TTFT "
                          f"{m['ttft_ms']['p99']:8.1f}ms  "
                          f"{'ATTAINS' if rec['attains'] else 'misses SLO'}")
    finally:
        obs.finish()
    if args.json:
        em.emit({
            "type": "summary", "trace": trace.digest(),
            "routing": args.routing, "ladder": list(ladder),
            "attain_target": args.attain_target,
            "n_rungs": len(records),
            "plan": (None if best is None else {
                "deployment": best["deployment"],
                "total_chips": best["total_chips"],
                "goodput_tok_s": best["metrics"]["goodput_tok_s"],
                "slo_attainment": best["metrics"]["slo_attainment"],
            }),
        })
        if em.broken:
            return EXIT_OK
    elif best is None:
        print(f"no rung on ladder {list(ladder)} attains "
              f"{100 * args.attain_target:.0f}% of the SLO")
    else:
        print(f"min-chip plan: {best['deployment']['describe']} = "
              f"{best['total_chips']} chips "
              f"({100 * best['metrics']['slo_attainment']:.1f}% attainment)")
    return EXIT_OK if best is not None else EXIT_NO_CONFIG


def cmd_capacity_plan(args) -> int:
    """Search, then size the deployment: analytical top-K × ladder →
    min-chip plan, recorded in the schema-v4 SearchReport."""
    cfg = _configurator(args)
    obs = _obs_capture(args)
    try:
        report = cfg.plan_capacity(
            args.trace, _slo_from_args(args),
            ladder=_parse_ladder(args.ladder),
            top_k=args.top_k, routing=args.routing,
            attain_target=args.attain_target, max_steps=args.max_steps)
    finally:
        obs.finish()
    if args.save_report:
        report.save(args.save_report)
    if args.json:
        print(report.to_json())
        return (EXIT_OK if report.capacity["plan"]["attained"]
                else EXIT_NO_CONFIG)
    cap = report.capacity
    print(report.summary())
    print(f"\nladder {cap['ladder']} (routing {cap['routing']}, target "
          f"{100 * cap['attain_target']:.0f}% attainment, trace "
          f"{cap['trace']['digest']}):")
    for rec in cap["rungs"]:
        if rec["pruned"]:
            print(f"  {rec['deployment']['describe']:>16s} "
                  f"{rec['total_chips']:4d} chips  pruned ({rec['pruned']})")
            continue
        m = rec["metrics"]
        print(f"  {rec['deployment']['describe']:>16s} "
              f"{rec['total_chips']:4d} chips  goodput "
              f"{m['goodput_tok_s']:9.1f} tok/s  attainment "
              f"{100 * m['slo_attainment']:5.1f}%  "
              f"{'ATTAINS' if rec['attains'] else 'misses SLO'}")
    for s in cap.get("skipped", []):
        print(f"  -- [{s['mode']}] {s['describe']} skipped: {s['reason']}")
    return EXIT_OK if cap["plan"]["attained"] else EXIT_NO_CONFIG


# ---------------------------------------------------------------------------
# autoscale
# ---------------------------------------------------------------------------

def _policy_from_args(args):
    """Build the AutoscalerPolicy selected by ``--policy`` plus its
    tuning flags (policy-specific knobs only reach their own policy)."""
    from repro.autoscale import get_policy
    kw = dict(min_replicas=args.min_replicas,
              max_replicas=args.max_replicas,
              scale_up_step=args.up_step,
              scale_down_step=args.down_step,
              up_cooldown_s=args.up_cooldown,
              down_cooldown_s=args.down_cooldown,
              window_s=args.window)
    if args.policy == "target_queue_depth":
        kw["target_depth"] = args.target_depth
    elif args.policy == "slo_attainment":
        kw["attain_target"] = args.attain_target
        kw["scale_down_util"] = args.scale_down_util
    return get_policy(args.policy, **kw)


def _emit_timeline(timeline, args, em: _JsonLines) -> None:
    """Stream the timeline (JSON-lines sample records with ``--json``)
    and honor ``--save-timeline``.  A broken pipe stops the sample
    stream but never the save file."""
    if args.json:
        for s in timeline.samples:
            if not em.emit({"type": "sample", **s.to_dict()},
                           sort_keys=True):
                break
    if args.save_timeline:
        timeline.save(args.save_timeline)


def cmd_autoscale_run(args) -> int:
    """Autoscaled replay of one explicit candidate: JSON-lines timeline
    samples plus a summary record."""
    from repro.core.task_runner import TaskRunner
    from repro.workloads import WorkloadTrace
    trace = WorkloadTrace.load(args.trace)
    policy = _policy_from_args(args)
    w, cand = _explicit_candidate(
        args, trace, n_chips=args.tp * args.pp * policy.max_replicas)
    runner = TaskRunner(w)
    sim = runner.autoscale_simulator(
        cand, policy, routing=args.routing,
        initial_replicas=args.initial_replicas, tick_s=args.tick,
        cold_start_s=args.cold_start, max_queue=args.max_queue)
    obs = _obs_capture(args)
    try:
        report = sim.run(trace, slo=_slo_from_args(args),
                         max_steps=args.max_steps)
    finally:
        obs.finish()
    em = _JsonLines()
    _emit_timeline(report.timeline, args, em)
    if args.json:
        em.emit({"type": "summary",
                 "trace": {"path": args.trace,
                           "digest": trace.digest()},
                 "config": {"model": args.model,
                            "describe": cand.describe()},
                 **report.to_dict()}, sort_keys=True)
        if em.broken:
            return EXIT_OK
    else:
        m = report.metrics
        print(report.summary())
        print(f"  {m.completed} completed, {m.rejected} rejected, "
              f"{m.unfinished} unfinished"
              + (" (budget truncated)" if m.truncated else ""))
        print(f"  goodput {m.goodput_tok_s:.1f} tok/s; events: "
              f"{len(report.events)} "
              f"({report.n_scale_ups} up, {report.n_scale_downs} down)")
        for ev in report.events:
            extra = (f" {ev['from']}->{ev['to']} ({ev['reason']})"
                     if "from" in ev else f" replica {ev['replica']}")
            print(f"    t={ev['t_s']:8.1f}s {ev['action']:<10s}{extra}")
    return EXIT_OK if report.metrics.completed > 0 else EXIT_NO_CONFIG


def cmd_autoscale_compare(args) -> int:
    """Autoscaled run vs the static min-chip plan on the same trace,
    candidate, and SLO — the chip-seconds savings view."""
    from repro.autoscale import build_autoscale_section
    from repro.core.task_runner import TaskRunner
    from repro.workloads import WorkloadTrace
    trace = WorkloadTrace.load(args.trace)
    ladder = _parse_ladder(args.ladder)
    policy = _policy_from_args(args)
    w, cand = _explicit_candidate(
        args, trace,
        n_chips=args.tp * args.pp * max(max(ladder), policy.max_replicas))
    runner = TaskRunner(w)
    obs = _obs_capture(args)
    try:
        section, run = build_autoscale_section(
            runner, cand, trace, _slo_from_args(args), policy,
            ladder=ladder, routing=args.routing,
            attain_target=args.attain_target,
            tick_s=args.tick, cold_start_s=args.cold_start,
            initial_replicas=args.initial_replicas,
            max_steps=args.max_steps, max_queue=args.max_queue)
    finally:
        obs.finish()
    em = _JsonLines()
    _emit_timeline(run.timeline, args, em)
    ok = (section["static"] is not None
          and section["savings"]["holds_attainment"])
    if args.json:
        # the histogram block travels in the schema-v7 report (and
        # --metrics-out); the JSON-lines stream stays pre-v7 stable
        section["run"]["metrics"].pop("histograms", None)
        em.emit({"type": "summary", **section}, sort_keys=True)
        return EXIT_OK if (ok or em.broken) else EXIT_NO_CONFIG
    static = section["static"]
    if static is None:
        print(f"no rung on ladder {list(ladder)} attains "
              f"{100 * args.attain_target:.0f}% of the SLO; no static "
              f"baseline to compare against")
        print(run.summary())
        return EXIT_NO_CONFIG
    print(f"static plan: {static['deployment']['describe']} = "
          f"{static['total_chips']} chips x {static['duration_s']:.1f}s "
          f"= {static['chip_seconds']:.1f} chip-s "
          f"({100 * static['slo_attainment']:.1f}% attainment)")
    print(run.summary())
    sv = section["savings"]
    verdict = ("holds attainment" if sv["holds_attainment"]
               else "DROPS below target")
    print(f"savings: {sv['chip_seconds']:.1f} chip-s "
          f"({sv['chip_seconds_pct']:.1f}%), {verdict} "
          f"({100 * args.attain_target:.0f}% target)")
    return EXIT_OK if ok else EXIT_NO_CONFIG


# ---------------------------------------------------------------------------
# obs
# ---------------------------------------------------------------------------

def cmd_obs_export(args) -> int:
    """Re-encode a saved TraceArtifact: Chrome ``trace_event`` JSON for
    chrome://tracing / Perfetto, or the canonical JSONL."""
    from repro.obs import TraceArtifact
    art = TraceArtifact.load(args.trace)
    if args.format == "chrome":
        text = json.dumps(art.to_chrome_trace(), sort_keys=True) + "\n"
    else:
        text = art.to_jsonl()
    if args.out == "-":
        _JsonLines().emit_text(text)
    else:
        with open(args.out, "w") as f:
            f.write(text)
        print(f"{args.format} export ({art.n_spans} spans) -> {args.out}")
    return EXIT_OK


def cmd_obs_diff(args) -> int:
    """Diff two telemetry snapshots (registry dumps, SearchReports with
    telemetry, or bare replay histogram sections).  Exit 0 when
    identical, 1 when they differ — diff semantics."""
    from repro.obs import diff_metrics, format_diff
    d = diff_metrics(args.a, args.b)
    if args.json:
        _JsonLines().emit_text(json.dumps(d, indent=2, sort_keys=True)
                               + "\n")
    else:
        _JsonLines().emit_text(format_diff(d) + "\n")
    return EXIT_OK if d["identical"] else EXIT_NO_CONFIG


def cmd_obs_bench_run(args) -> int:
    """Run the benchmark suite through ``benchmarks/run.py`` and emit a
    versioned BenchArtifact (plus the history append).  The benchmarks
    package is not installed — it lives at the repo root — so this
    resolves it from the current directory when needed."""
    try:
        from benchmarks.run import main as bench_run_main
    except ImportError:
        if os.path.isdir(os.path.join(os.getcwd(), "benchmarks")):
            sys.path.insert(0, os.getcwd())
        try:
            from benchmarks.run import main as bench_run_main
        except ImportError:
            raise ValueError(
                "obs bench run needs the repo's benchmarks/ package on "
                "sys.path — run from the repo root")
    argv = []
    if args.quick:
        argv.append("--quick")
    if args.only:
        argv += ["--only", args.only]
    argv += ["--repeat", str(args.repeat)]
    if args.out:
        argv += ["--out", args.out]
    if args.history is not None:
        argv += ["--history", args.history]
    if args.timestamp:
        argv += ["--timestamp", args.timestamp]
    return EXIT_NO_CONFIG if bench_run_main(argv) else EXIT_OK


def cmd_obs_bench_compare(args) -> int:
    """Strict determinism check between two suite runs: identical work
    counters -> exit 0, any drift -> exit 1, mismatched environment
    fingerprints -> exit 2 (refusing to produce a misleading delta)."""
    from repro.obs.bench import (BenchArtifact, compare_artifacts,
                                 format_compare)
    a = BenchArtifact.load(args.a)
    b = BenchArtifact.load(args.b)
    # EnvironmentMismatch is a ValueError: main() maps it to exit 2.
    cmp = compare_artifacts(a, b)
    if args.json:
        _JsonLines().emit_text(json.dumps(cmp, indent=2, sort_keys=True)
                               + "\n")
    else:
        _JsonLines().emit_text(format_compare(cmp) + "\n")
    return EXIT_OK if cmp["identical"] else EXIT_NO_CONFIG


def cmd_obs_bench_gate(args) -> int:
    """Gate a current run against a baseline artifact: hard gates on
    work counters always run (a ``REPRO_*`` knob regression is exactly
    what they hunt); soft wallclock gates run only when the environment
    fingerprints match.  Exit 0 pass / 1 fail."""
    from repro.obs.bench import (DEFAULT_ABS_TOL_US, DEFAULT_REL_TOL,
                                 BenchArtifact, gate_artifacts)
    baseline = BenchArtifact.load(args.baseline)
    current = BenchArtifact.load(args.current)
    res = gate_artifacts(
        baseline, current,
        rel_tol=DEFAULT_REL_TOL if args.rel_tol is None else args.rel_tol,
        abs_tol_us=(DEFAULT_ABS_TOL_US if args.abs_tol_us is None
                    else args.abs_tol_us),
        hard_only=args.hard_only)
    if args.json:
        _JsonLines().emit_text(
            json.dumps(res.to_dict(), indent=2, sort_keys=True) + "\n")
    else:
        _JsonLines().emit_text(res.format() + "\n")
    return EXIT_OK if res.ok else EXIT_NO_CONFIG


def cmd_obs_bench_trend(args) -> int:
    """Summarize the append-only bench history: per-benchmark wallclock
    trajectory and how often the work-counter digest changed."""
    from repro.obs.bench import format_trend, load_history, trend_summary
    entries = load_history(args.history)
    summary = trend_summary(entries, suite=args.suite or None)
    if args.json:
        _JsonLines().emit_text(
            json.dumps(summary, indent=2, sort_keys=True) + "\n")
    else:
        _JsonLines().emit_text(format_trend(summary) + "\n")
    return EXIT_OK


# ---------------------------------------------------------------------------
# explain
# ---------------------------------------------------------------------------

def _configurator_from_workload(w) -> Configurator:
    """Rebuild a Configurator from a report's workload descriptor so
    ``explain --from-report`` prices through the exact same workload."""
    return (Configurator.for_model(w.model)
            .traffic(w.isl, w.osl, w.prefix_len)
            .sla(ttft_ms=w.sla.ttft_ms,
                 min_tokens_per_s_user=w.sla.min_tokens_per_s_user,
                 tpot_ms=w.sla.tpot_ms)
            .cluster(chips=w.cluster.n_chips, platform=w.cluster.platform,
                     chips_per_host=w.cluster.chips_per_host)
            .backend(w.backend).dtype(w.dtype)
            .modes(*w.modes).moe_alpha(w.moe_alpha))


def cmd_explain(args) -> int:
    """Per-candidate cost attribution: the operator-family latency
    waterfall for an analytical leader, optionally diffed against a
    second leader rank."""
    if args.from_report:
        report = SearchReport.load(args.from_report)
        cfg = _configurator_from_workload(report.workload)
    else:
        if args.model is None or args.isl is None or args.osl is None:
            print("error: explain needs --from-report or "
                  "--model/--isl/--osl", file=sys.stderr)
            return EXIT_USAGE
        cfg = _configurator(args)
        report = None
    try:
        ex = cfg.explain(rank=args.rank, baseline=args.baseline,
                         report=report, top_k=args.top_k)
    except ValueError as e:
        if "explainable candidate" not in str(e):
            raise
        print(f"error: {e}", file=sys.stderr)
        return EXIT_NO_CONFIG
    if args.json:
        print(json.dumps(ex.to_dict(), indent=2))
    else:
        print(ex.summary())
    return EXIT_OK


# ---------------------------------------------------------------------------
# list
# ---------------------------------------------------------------------------

def cmd_list(args) -> int:
    inventory = {
        "models": list_archs(True),
        "backends": {name: sorted(backend_capabilities(name))
                     for name in all_backends()},
        "platforms": sorted(PLATFORMS),
    }
    wanted = (inventory if args.what == "all"
              else {args.what: inventory[args.what]})
    if args.json:
        print(json.dumps(wanted, indent=2))
        return EXIT_OK
    for section, items in wanted.items():
        print(f"{section}:")
        if isinstance(items, dict):
            for name, caps in items.items():
                print(f"  {name}  ({', '.join(caps)})")
        else:
            for name in items:
                print(f"  {name}")
    return EXIT_OK


# ---------------------------------------------------------------------------
# entry point
# ---------------------------------------------------------------------------

def _add_slo_args(ap: argparse.ArgumentParser):
    ap.add_argument("--slo-ttft-p99", type=float, default=2000.0,
                    help="tail SLO: p99 TTFT target in ms")
    ap.add_argument("--slo-tpot-p99", type=float, default=100.0,
                    help="tail SLO: p99 TPOT target in ms")


def _add_candidate_args(ap: argparse.ArgumentParser):
    """The explicit-candidate flag block `workload replay` and
    `capacity sweep` share (consumed by ``_explicit_candidate``)."""
    ap.add_argument("--model", required=True,
                    help=f"one of {', '.join(list_archs(True))}")
    ap.add_argument("--tp", type=int, default=1)
    ap.add_argument("--pp", type=int, default=1)
    ap.add_argument("--ep", type=int, default=1)
    ap.add_argument("--batch", type=int, default=64,
                    help="decode slot count (max_batch)")
    ap.add_argument("--max-num-tokens", type=int, default=8192)
    ap.add_argument("--max-queue", type=int, default=100_000)
    ap.add_argument("--platform", default="tpu_v5e")
    ap.add_argument("--backend", default="repro-jax")
    ap.add_argument("--dtype", default="bf16",
                    choices=["bf16", "fp16", "fp8"])


def _add_obs_args(ap: argparse.ArgumentParser):
    """The ``repro.obs`` capture flags every instrumented command shares
    (search plus the replay family: workload replay, capacity
    sweep/plan, autoscale run/compare)."""
    ap.add_argument("--trace-out", default="", metavar="PATH",
                    help="record repro.obs spans and write the "
                         "TraceArtifact JSONL here ('-' streams it to "
                         "stdout; a .chrome.json suffix writes the Chrome "
                         "trace_event export for chrome://tracing / "
                         "Perfetto); deterministic across seeded runs")
    ap.add_argument("--metrics-out", default="", metavar="PATH",
                    help="collect repro.obs counters during the command "
                         "and write the registry snapshot here (JSON, or "
                         "Prometheus text format with a .prom suffix)")
    ap.add_argument("--span-sample-every", type=int, default=None,
                    metavar="N",
                    help="flight recorder: keep every N-th request's "
                         "lifecycle spans (default 1 = all sampled "
                         "requests; histograms always see every request)")
    ap.add_argument("--max-request-spans", type=int, default=None,
                    metavar="N",
                    help="flight recorder: cap request span trees per "
                         "replay (default 512)")


def _build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser(
        prog="repro.core.cli",
        description="AIConfigurator: find the optimal serving configuration")
    sub = ap.add_subparsers(dest="command")

    sp = sub.add_parser("search", help="search the configuration space")
    _add_workload_args(sp)
    sp.add_argument("--top", type=int, default=5)
    sp.add_argument("--save-launch", default="")
    sp.add_argument("--save-report", default="",
                    help="write the SearchReport JSON here")
    sp.add_argument("--draft-model", default="",
                    help="also project speculative decoding with this draft")
    sp.add_argument("--acceptance", type=float, default=0.8)
    sp.add_argument("--json", action="store_true",
                    help="print the SearchReport JSON on stdout")
    sp.add_argument("--stream", action="store_true",
                    help="emit JSON-lines progress records as candidates "
                         "are priced, then a terminal summary record")
    sp.add_argument("--first-n", type=int, default=0, metavar="N",
                    help="stop as soon as N SLA-valid configurations are "
                         "found (early exit; prices fewer candidates)")
    sp.add_argument("--trace", default="",
                    help="workload trace JSONL (from `workload generate`): "
                         "replay the frontier's top-K under it open-loop "
                         "(queueing delay counts into TTFT) and re-rank "
                         "by goodput (SearchReport workload_eval section)")
    _add_slo_args(sp)
    sp.add_argument("--replay-top-k", type=int, default=3, metavar="K",
                    help="how many analytical leaders to replay "
                         "(disaggregated composites are skipped, not "
                         "replayed)")
    _add_obs_args(sp)
    sp.set_defaults(func=cmd_search)

    gp = sub.add_parser("generate", help="emit the launch artifact")
    gp.add_argument("--from-report", default="",
                    help="SearchReport JSON from `search --save-report`")
    gp.add_argument("--out", default="", help="write launch JSON here")
    gp.add_argument("--json", action="store_true")
    _add_workload_args(gp, required=False)
    gp.set_defaults(func=cmd_generate)

    cp = sub.add_parser("compare",
                        help="sweep traffic shapes (scenario diversity)")
    _add_workload_args(cp, traffic=False)
    cp.add_argument("--shapes", required=True,
                    help="comma list of isl:osl[:min_speed]")
    cp.add_argument("--json", action="store_true")
    cp.set_defaults(func=cmd_compare)

    cal = sub.add_parser(
        "calibrate",
        help="measured-kernel calibration: run | apply | report")
    calsub = cal.add_subparsers(dest="action")

    cr = calsub.add_parser("run", help="measure kernels and fit corrections")
    cr.add_argument("--platform", default="tpu_v5e",
                    help=f"one of {', '.join(sorted(PLATFORMS))}")
    cr.add_argument("--backend", default="repro-jax")
    cr.add_argument("--timer", default="deterministic",
                    choices=["deterministic", "wallclock"],
                    help="deterministic: CI-reproducible analytical-skew "
                         "timer; wallclock: execute the real kernels "
                         "(compiled; add --interpret on a CPU host)")
    cr.add_argument("--interpret", action="store_true",
                    help="run the wallclock timer's Pallas kernels in "
                         "interpret mode (needed where no TPU is attached)")
    cr.add_argument("--points", type=int, default=3,
                    help="measurement points per grid axis")
    cr.add_argument("--families", default="",
                    help="comma list (default: all measured families)")
    cr.add_argument("--out", required=True,
                    help="write the calibration artifact JSON here")
    cr.add_argument("--timestamp", default="",
                    help="ISO-8601 provenance timestamp (default: now UTC)")
    cr.add_argument("--notes", default="")
    cr.add_argument("--json", action="store_true")
    cr.set_defaults(func=cmd_calibrate_run)

    ca = calsub.add_parser(
        "apply", help="search through a calibrated PerfDatabase")
    ca.add_argument("--artifact", required=True)
    ca.add_argument("--save-report", default="")
    ca.add_argument("--json", action="store_true")
    _add_workload_args(ca, required=False)
    # sentinel defaults: the artifact supplies the calibrated pair, and
    # an EXPLICIT mismatching flag is detectable (and warned about)
    ca.set_defaults(func=cmd_calibrate_apply, platform=None, backend=None)

    crep = calsub.add_parser(
        "report", help="per-family accuracy audit of an artifact")
    crep.add_argument("--artifact", required=True)
    crep.add_argument("--json", action="store_true")
    crep.set_defaults(func=cmd_calibrate_report)

    wl = sub.add_parser(
        "workload",
        help="dynamic workload traces: generate | replay | describe")
    wlsub = wl.add_subparsers(dest="action")

    wg = wlsub.add_parser("generate",
                          help="expand a seeded (spec, seed) into a trace")
    wg.add_argument("--arrivals", default="poisson",
                    choices=["poisson", "bursty", "diurnal"])
    wg.add_argument("--rate", type=float, default=1.0,
                    help="mean arrival rate, requests/s")
    wg.add_argument("--burst-factor", type=float, default=4.0,
                    help="bursty: ON-phase rate multiplier")
    wg.add_argument("--period", type=float, default=120.0,
                    help="diurnal: modulation period, seconds")
    wg.add_argument("--amplitude", type=float, default=0.8,
                    help="diurnal: modulation amplitude in [0, 1)")
    wg.add_argument("--n", type=int, default=100, help="request count")
    wg.add_argument("--lengths", default="fixed",
                    choices=["fixed", "uniform", "lognormal", "sharegpt"])
    wg.add_argument("--isl", type=int, default=512,
                    help="fixed/lognormal input-length (median)")
    wg.add_argument("--osl", type=int, default=128,
                    help="fixed/lognormal output-length (median)")
    wg.add_argument("--isl-range", default="64:2048", metavar="LO:HI",
                    help="uniform input-length bounds")
    wg.add_argument("--osl-range", default="16:512", metavar="LO:HI",
                    help="uniform output-length bounds")
    wg.add_argument("--sigma", type=float, default=0.5,
                    help="lognormal spread")
    wg.add_argument("--tenants", default="",
                    help="comma list of name:weight[:priority] "
                         "(default: one 'default' tenant)")
    wg.add_argument("--spec", default="",
                    help="TraceSpec JSON file (overrides the flags above)")
    wg.add_argument("--seed", type=int, default=0)
    wg.add_argument("--out", required=True,
                    help="write the trace JSONL here")
    wg.add_argument("--json", action="store_true")
    wg.set_defaults(func=cmd_workload_generate)

    wd = wlsub.add_parser("describe", help="summarize a trace file")
    wd.add_argument("--trace", required=True)
    wd.add_argument("--json", action="store_true")
    wd.set_defaults(func=cmd_workload_describe)

    wr = wlsub.add_parser(
        "replay", help="open-loop replay against one serving config "
                       "(arrival-time-driven: queueing delay counts "
                       "into TTFT)")
    wr.add_argument("--trace", required=True)
    _add_candidate_args(wr)
    wr.add_argument("--max-steps", type=int, default=200_000)
    _add_slo_args(wr)
    wr.add_argument("--json", action="store_true")
    _add_obs_args(wr)
    wr.set_defaults(func=cmd_workload_replay)

    cap = sub.add_parser(
        "capacity",
        help="multi-replica capacity planning: plan | sweep")
    capsub = cap.add_subparsers(dest="action")

    def _add_capacity_args(p):
        p.add_argument("--trace", required=True,
                       help="workload trace JSONL (from `workload generate`)")
        p.add_argument("--ladder", default="1,2,4", metavar="N,N,...",
                       help="ascending replica-count ladder to sweep")
        p.add_argument("--routing", default="round_robin",
                       choices=list(ROUTING_POLICIES),
                       help="how requests are routed across replicas")
        p.add_argument("--attain-target", type=float, default=0.95,
                       help="fraction of requests that must meet the SLO "
                            "for a rung to attain")
        p.add_argument("--max-steps", type=int, default=200_000,
                       help="total iteration budget across all replicas")
        _add_slo_args(p)
        p.add_argument("--json", action="store_true")
        _add_obs_args(p)

    cs = capsub.add_parser(
        "sweep", help="replay one explicit candidate up the replica "
                      "ladder; per-rung records (JSON-lines with --json)")
    _add_capacity_args(cs)
    _add_candidate_args(cs)
    cs.set_defaults(func=cmd_capacity_sweep)

    cpl = capsub.add_parser(
        "plan", help="search, then find the minimum-chip deployment "
                     "whose goodput attains the SLO (schema-v4 report)")
    _add_workload_args(cpl)
    _add_capacity_args(cpl)
    cpl.add_argument("--top-k", type=int, default=1, metavar="K",
                     help="try the analytical top-K replayable candidates "
                          "at every rung (disaggregated composites are "
                          "skipped)")
    cpl.add_argument("--save-report", default="",
                     help="write the schema-v4 SearchReport JSON here")
    cpl.set_defaults(func=cmd_capacity_plan)

    asc = sub.add_parser(
        "autoscale",
        help="reactive autoscaling over the cluster simulator: "
             "run | compare")
    ascsub = asc.add_subparsers(dest="action")

    def _add_autoscale_args(p):
        from repro.autoscale import AUTOSCALER_POLICIES
        p.add_argument("--trace", required=True,
                       help="workload trace JSONL (from `workload "
                            "generate`)")
        p.add_argument("--routing", default="round_robin",
                       choices=list(ROUTING_POLICIES))
        p.add_argument("--policy", default="target_queue_depth",
                       choices=list(AUTOSCALER_POLICIES),
                       help="autoscaler policy evaluated each tick")
        p.add_argument("--target-depth", type=float, default=4.0,
                       help="target_queue_depth: outstanding requests "
                            "per replica to aim for")
        p.add_argument("--attain-target", type=float, default=0.95,
                       help="fraction of requests that must meet the SLO "
                            "(slo_attainment policy target; also the "
                            "static plan's bar under `compare`)")
        p.add_argument("--scale-down-util", type=float, default=0.5,
                       help="slo_attainment: scale down only below this "
                            "mean utilization")
        p.add_argument("--min-replicas", type=int, default=1)
        p.add_argument("--max-replicas", type=int, default=8)
        p.add_argument("--up-step", type=int, default=1,
                       help="max replicas added per scale-up")
        p.add_argument("--down-step", type=int, default=1,
                       help="max replicas drained per scale-down")
        p.add_argument("--up-cooldown", type=float, default=5.0,
                       help="seconds between scale-ups")
        p.add_argument("--down-cooldown", type=float, default=30.0,
                       help="seconds between scale-downs")
        p.add_argument("--window", type=float, default=10.0,
                       help="rolling metrics window the policy sees (s)")
        p.add_argument("--tick", type=float, default=1.0,
                       help="control-loop tick width (virtual s)")
        p.add_argument("--cold-start", type=float, default=5.0,
                       help="spawn-to-route-eligible delay (virtual s)")
        p.add_argument("--initial-replicas", type=int, default=None,
                       help="starting fleet size (default: policy "
                            "min-replicas; under `compare`, the static "
                            "plan's replica count)")
        p.add_argument("--max-steps", type=int, default=200_000,
                       help="total iteration budget across all replicas")
        p.add_argument("--save-timeline", default="",
                       help="write the ClusterTimeline JSONL here")
        _add_slo_args(p)
        p.add_argument("--json", action="store_true",
                       help="JSON-lines: one record per timeline sample, "
                            "then a terminal summary record")
        _add_obs_args(p)

    ar = ascsub.add_parser(
        "run", help="autoscaled replay of one explicit candidate; "
                    "timeline samples as JSON-lines with --json")
    _add_autoscale_args(ar)
    _add_candidate_args(ar)
    ar.set_defaults(func=cmd_autoscale_run)

    ac = ascsub.add_parser(
        "compare", help="autoscaled run vs the static min-chip plan on "
                        "the same trace (chip-seconds savings)")
    _add_autoscale_args(ac)
    _add_candidate_args(ac)
    ac.add_argument("--ladder", default="1,2,4", metavar="N,N,...",
                    help="replica ladder for the static baseline plan")
    ac.set_defaults(func=cmd_autoscale_compare)

    ep = sub.add_parser(
        "explain",
        help="attribute a candidate's projected latency to operator "
             "families (per-phase waterfall, optional two-rank diff)")
    _add_workload_args(ep, required=False)
    ep.add_argument("--from-report", default="",
                    help="SearchReport JSON from `search --save-report` "
                         "(skips the fresh search)")
    ep.add_argument("--rank", type=int, default=0,
                    help="analytical-leader rank to explain (0 = best "
                         "explainable candidate)")
    ep.add_argument("--baseline", type=int, default=None, metavar="RANK",
                    help="second leader rank to diff against (per-family "
                         "deltas + the parallelism changes behind them)")
    ep.add_argument("--top-k", type=int, default=5,
                    help="how many analytical leaders to consider")
    ep.add_argument("--json", action="store_true")
    ep.set_defaults(func=cmd_explain)

    lp = sub.add_parser("list", help="enumerate models/backends/platforms")
    lp.add_argument("what", nargs="?", default="all",
                    choices=["models", "backends", "platforms", "all"])
    lp.add_argument("--json", action="store_true")
    lp.set_defaults(func=cmd_list)

    ob = sub.add_parser(
        "obs", help="observability artifacts: export | diff | bench")
    obsub = ob.add_subparsers(dest="action")

    oe = obsub.add_parser(
        "export", help="re-encode a saved TraceArtifact (Chrome "
                       "trace_event JSON or canonical JSONL)")
    oe.add_argument("--trace", required=True,
                    help="TraceArtifact JSONL (from --trace-out)")
    oe.add_argument("--format", default="chrome",
                    choices=["chrome", "jsonl"],
                    help="chrome: trace_event JSON for chrome://tracing "
                         "and Perfetto; jsonl: the canonical artifact")
    oe.add_argument("--out", default="-",
                    help="output path ('-' = stdout)")
    oe.set_defaults(func=cmd_obs_export)

    od = obsub.add_parser(
        "diff", help="diff two telemetry snapshots: counter/gauge "
                     "deltas, per-histogram distribution shifts, the "
                     "SLO-attainment delta (exit 1 when they differ)")
    od.add_argument("a", help="baseline: metrics snapshot JSON, a "
                              "SearchReport with telemetry, or a replay "
                              "histogram section")
    od.add_argument("b", help="comparison snapshot (same shapes)")
    od.add_argument("--json", action="store_true")
    od.set_defaults(func=cmd_obs_diff)

    obb = obsub.add_parser(
        "bench", help="performance-regression sentinel: "
                      "run | compare | gate | trend")
    bsub = obb.add_subparsers(dest="bench_action")

    br = bsub.add_parser(
        "run", help="run the benchmark suite and emit a versioned "
                    "BenchArtifact (work counters, phase breakdown, "
                    "repeat timings, environment fingerprint)")
    br.add_argument("--quick", action="store_true",
                    help="CI-sized variants of every benchmark")
    br.add_argument("--only", default="",
                    help="comma-separated substrings of benchmark names")
    br.add_argument("--repeat", type=int, default=1,
                    help="timing repeats per benchmark")
    br.add_argument("--out", default="",
                    help="artifact path (default results/bench_<suite>"
                         ".json)")
    br.add_argument("--history", default=None, metavar="JSONL",
                    help="history file to append ('' disables; default "
                         "results/bench_history.jsonl)")
    br.add_argument("--timestamp", default="",
                    help="created_at override for deterministic artifacts")
    br.set_defaults(func=cmd_obs_bench_run)

    bc = bsub.add_parser(
        "compare", help="strict determinism check between two suite "
                        "runs (exit 0 identical work, 1 drift, 2 "
                        "mismatched environments)")
    bc.add_argument("a", help="first BenchArtifact JSON")
    bc.add_argument("b", help="second BenchArtifact JSON")
    bc.add_argument("--json", action="store_true")
    bc.set_defaults(func=cmd_obs_bench_compare)

    bg = bsub.add_parser(
        "gate", help="two-tier regression gate vs a baseline artifact: "
                     "hard (exact work counters) + soft (min-of-k "
                     "wallclock under tolerance); exit 1 on violation")
    bg.add_argument("--baseline", required=True,
                    help="baseline BenchArtifact (e.g. "
                         "results/baselines/bench_quick.json)")
    bg.add_argument("--current", required=True,
                    help="current-run BenchArtifact")
    bg.add_argument("--rel-tol", type=float, default=None,
                    help="soft-gate relative tolerance (default 0.5)")
    bg.add_argument("--abs-tol-us", type=float, default=None,
                    help="soft-gate absolute slack in us (default 5000)")
    bg.add_argument("--hard-only", action="store_true",
                    help="skip the wallclock tier (deterministic "
                         "cross-machine gating)")
    bg.add_argument("--json", action="store_true")
    bg.set_defaults(func=cmd_obs_bench_gate)

    bt = bsub.add_parser(
        "trend", help="summarize the append-only bench history "
                      "(wallclock trajectory + work-digest changes)")
    bt.add_argument("--history", default="results/bench_history.jsonl")
    bt.add_argument("--suite", default="",
                    help="filter to one suite (quick | full)")
    bt.add_argument("--json", action="store_true")
    bt.set_defaults(func=cmd_obs_bench_trend)
    return ap


def _legacy_argv_to_search(argv) -> list:
    """Deprecation shim: flat-flag invocation -> `search` subcommand argv."""
    print("deprecated: flat-flag invocation; use "
          "`python -m repro.core.cli search ...` (same flags)",
          file=sys.stderr)
    return ["search"] + list(argv)


def main(argv=None) -> int:
    argv = list(sys.argv[1:] if argv is None else argv)
    if argv and argv[0] not in _SUBCOMMANDS \
            and not argv[0] in ("-h", "--help"):
        argv = _legacy_argv_to_search(argv)
    ap = _build_parser()
    args = ap.parse_args(argv)
    if getattr(args, "func", None) is None:
        ap.print_help()
        return EXIT_USAGE
    try:
        return args.func(args)
    except (ValueError, OSError, KeyError) as e:
        # bad inputs (validation, unreadable/corrupt report files,
        # unregistered backends referenced by a loaded report) -> 2
        msg = e.args[0] if isinstance(e, KeyError) and e.args else e
        print(f"error: {msg}", file=sys.stderr)
        return EXIT_USAGE


if __name__ == "__main__":
    sys.exit(main())
