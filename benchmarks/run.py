"""Benchmark harness — one entry per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run            # full
    PYTHONPATH=src python -m benchmarks.run --quick    # CI-sized

Prints one ``name,us_per_call,derived`` CSV line per benchmark, writes
detailed CSVs under results/, and emits one versioned
:class:`repro.obs.bench.BenchArtifact` per suite run (``--out``) with
per-bench repeat timings, work-counter snapshots, and tracer-span phase
breakdowns — the file ``obs bench compare|gate|trend`` consume.  Runs
are appended to the ``results/bench_history.jsonl`` trajectory unless
``--history ''`` disables it.

The harness assumes ``repro`` is importable (run with ``PYTHONPATH=src``
from the repo root, matching pyproject's ``pythonpath``); there is no
import-time sys.path patching.
"""
from __future__ import annotations

import argparse
import os
import time
from typing import Callable, Dict, List, Optional, Sequence, Tuple

from benchmarks import (ablation_sol, autoscale_diurnal, capacity_ladder,
                        cpu_silicon_fidelity, engine_calibration, fig1_pareto,
                        fig5_powerlaw, fig6_fidelity, fig7_disagg_fidelity,
                        roofline, spec_decode, table1_search_efficiency,
                        table2_case_study, workload_goodput)
from benchmarks.common import RESULTS_DIR, bench_environment

Bench = Tuple[str, Callable[..., Optional[Dict]], Callable[[Dict], str]]

BENCHES: List[Bench] = [
    ("table1_search_efficiency", table1_search_efficiency.run,
     lambda r: f"median_ms_per_config={r.get('per_config_ms', 0):.2f}"),
    ("fig6_aggregated_fidelity", fig6_fidelity.run,
     lambda r: ";".join(f"{s[0]}/{s[1]}:tpot_mape={s[3]}%"
                        for s in r.get("summary", []))),
    ("fig7_disagg_fidelity", fig7_disagg_fidelity.run,
     lambda r: f"thru_mape={r.get('thru_mape', 0):.1f}%"
               f";speed_mape={r.get('speed_mape', 0):.1f}%"),
    ("fig1_pareto_qwen235b", fig1_pareto.run,
     lambda r: f"disagg_gain={r.get('gain_pct', float('nan')):.1f}%"),
    ("table2_case_study", table2_case_study.run,
     lambda r: f"disagg_gain={r.get('gain_pct', float('nan')):.1f}%"),
    ("fig5_powerlaw_alpha", fig5_powerlaw.run, lambda r: "see csv"),
    ("roofline_from_dryrun", roofline.run,
     lambda r: str(r.get("dominants", ""))),
    ("engine_overhead_calibration", engine_calibration.run,
     lambda r: f"overhead_us={r.get('overhead_us', 0):.0f}"),
    ("spec_decode_extension", spec_decode.run,
     lambda r: f"best_speedup={r.get('best_speedup', 0):.2f}x"),
    ("cpu_silicon_fidelity", cpu_silicon_fidelity.run,
     lambda r: f"tpot_mape={r.get('tpot_mape', 0):.1f}%"
               f";ttft_mape={r.get('ttft_mape', 0):.1f}%"),
    ("ablation_calibrated_vs_sol", ablation_sol.run,
     lambda r: f"step_margin={r.get('step_ratio_calibrated', 0):.2f}x"
               f";sol_check={r.get('step_ratio_sol', 0):.2f}x"),
    ("workload_goodput_rerank", workload_goodput.run,
     lambda r: f"reranked={r.get('n_reranked', 0)}"
               f"/{r.get('n_points', 0)}"),
    ("capacity_ladder", capacity_ladder.run,
     lambda r: f"min_chips={r.get('min_chips')}"
               f";n_points={r.get('n_points', 0)}"),
    ("autoscale_diurnal", autoscale_diurnal.run,
     lambda r: f"best_saved_pct={r.get('best_saved_pct')}"
               f";n_points={r.get('n_points', 0)}"),
]


def select_benches(only: str,
                   benches: Optional[Sequence[Bench]] = None) -> List[Bench]:
    """``--only`` filter: comma-separated tokens, substring match each
    (so ``--only capacity`` and ``--only table1,fig1`` both work)."""
    pool = list(BENCHES if benches is None else benches)
    if not only:
        return pool
    tokens = [t.strip() for t in only.split(",") if t.strip()]
    return [b for b in pool if any(t in b[0] for t in tokens)]


def run_suite(quick: bool = False, only: str = "", repeat: int = 1,
              created_at: str = "", benches: Optional[Sequence[Bench]] = None,
              emit=print):
    """Run the (selected) suite and return ``(BenchArtifact, failures)``.

    Each repeat of each benchmark runs under a fresh process-local
    ``MetricsRegistry`` and ``Tracer`` so its work counters and phase
    breakdown are isolated; counters/phases are taken from the first
    repeat (they are deterministic — asserting exactly that is the
    comparator's job), timing stats pool all repeats.
    """
    from repro.obs import (MetricsRegistry, Tracer, disable_metrics,
                           disable_tracing, enable_metrics, enable_tracing)
    from repro.obs.bench import BenchArtifact, BenchRecord, BenchTiming

    selected = select_benches(only, benches)
    emit("name,us_per_call,derived")
    records: List[BenchRecord] = []
    failures = 0
    for name, fn, derive in selected:
        emit(f"# --- {name} ---")
        samples_us: List[float] = []
        counters: Dict[str, float] = {}
        phases: Dict[str, float] = {}
        derived = error = ""
        status = "ok"
        for rep in range(max(1, repeat)):
            registry, tracer = MetricsRegistry(), Tracer()
            enable_metrics(registry)
            enable_tracing(tracer)
            t0 = time.perf_counter()
            try:
                result = fn(quick=quick) or {}
            except Exception as e:  # noqa: BLE001 — keep the harness running
                samples_us.append(1e6 * (time.perf_counter() - t0))
                status, error = "error", f"{type(e).__name__}:{e}"
            finally:
                disable_metrics()
                disable_tracing()
            if status == "error":
                counters = dict(registry.to_dict()["counters"])
                break
            samples_us.append(1e6 * (time.perf_counter() - t0))
            if rep == 0:
                counters = dict(registry.to_dict()["counters"])
                phases = tracer.wall_by_name()
                derived = derive(result)
        timing = BenchTiming.from_samples(samples_us)
        if status == "error":
            failures += 1
            emit(f"{name},{timing.min_us:.0f},ERROR:{error}")
        else:
            emit(f"{name},{timing.median_us:.0f},{derived}")
        records.append(BenchRecord(name=name, status=status, timing=timing,
                                   counters=counters, phases=phases,
                                   derived=derived, error=error))
    artifact = BenchArtifact(suite="quick" if quick else "full",
                             created_at=created_at,
                             environment=bench_environment(),
                             records=records)
    return artifact, failures


def _utc_now() -> str:
    from datetime import datetime, timezone
    return datetime.now(timezone.utc).strftime("%Y-%m-%dT%H:%M:%SZ")


def main(argv: Optional[Sequence[str]] = None) -> int:
    from repro.obs.bench import append_history

    ap = argparse.ArgumentParser(
        description="Run the benchmark suite and emit a BenchArtifact.")
    ap.add_argument("--quick", action="store_true",
                    help="CI-sized variants of every benchmark")
    ap.add_argument("--only", default="",
                    help="comma-separated substrings of benchmark names")
    ap.add_argument("--repeat", type=int, default=1,
                    help="timing repeats per benchmark (min-of-k feeds "
                         "the soft gate)")
    ap.add_argument("--out", default="",
                    help="artifact path (default results/bench_<suite>.json)")
    ap.add_argument("--history",
                    default=os.path.join(RESULTS_DIR, "bench_history.jsonl"),
                    help="append-only run trajectory ('' disables)")
    ap.add_argument("--timestamp", default="",
                    help="created_at override for deterministic artifacts")
    args = ap.parse_args(argv)

    artifact, failures = run_suite(quick=args.quick, only=args.only,
                                   repeat=args.repeat,
                                   created_at=args.timestamp or _utc_now())
    out = args.out or os.path.join(RESULTS_DIR,
                                   f"bench_{artifact.suite}.json")
    parent = os.path.dirname(out)
    if parent:
        os.makedirs(parent, exist_ok=True)
    artifact.save(out)
    print(f"# artifact {out} digest {artifact.digest()} "
          f"({len(artifact.records)} benches, suite={artifact.suite})")
    if args.history:
        append_history(args.history, artifact)
    return 1 if failures else 0


if __name__ == "__main__":
    # the process-wide compile cache belongs to the command line run, not
    # to in-process callers such as `repro obs bench run`
    from repro.core.jaxenv import use_compile_cache
    use_compile_cache()
    raise SystemExit(main())
