"""repro.calibrate — measure → fit → persist → load, end to end.

Covers: the measurement harness over both timers, the log-space fitting
layer (skew recovery, degenerate fallbacks, exponent clamping), lossless
artifact round-trips (including the golden fixture under
tests/fixtures/), the PerfDatabase correction layer + fingerprint
surfacing, the Configurator.with_calibration hook, the accuracy report's
"calibrated MAPE <= uncalibrated MAPE" guarantee, and the calibrate CLI.
"""
import json
import math

import pytest

from repro.calibrate import (CalibrationArtifact, DeterministicTimer,
                             FamilyFit, Sample, WallClockTimer,
                             accuracy_report, fit_families, fit_family,
                             format_accuracy, grid_digest, make_timer,
                             run_calibration)
from repro.calibrate.harness import (DEFAULT_AXES, MEASURED_FAMILIES,
                                     MeasurementHarness, subsample)
from repro.core import operators as ops
from repro.core.cli import main as cli_main
from repro.core.perf_database import PerfDatabase

CREATED = "2026-07-28T00:00:00Z"
GOLDEN = "tests/fixtures/calibration_tpu_v5e_repro-jax.json"

#: tiny axes so wall-clock (interpret-mode) measurement stays cheap
TINY_AXES = {
    "gemm": ((64, 128), (128, 256), (128, 256)),
    "attn_prefill": ((64, 128), (64, 128)),
    "attn_decode": ((1, 2), (128, 256)),
    "moe": ((16, 64),),
    "recurrent": ((64, 128),),
}


@pytest.fixture(scope="module")
def artifact():
    return run_calibration("tpu_v5e", "repro-jax",
                           timer=DeterministicTimer("tpu_v5e"),
                           created_at=CREATED, points_per_axis=2)


# ---------------------------------------------------------------------------
# harness + timers
# ---------------------------------------------------------------------------

def test_harness_covers_every_family(artifact):
    assert set(s.family for s in artifact.samples) == set(MEASURED_FAMILIES)
    assert set(artifact.fits) == set(MEASURED_FAMILIES)
    for s in artifact.samples:
        assert s.predicted_s > 0 and s.measured_s > 0


def test_harness_axes_subsample_matches_database_axes():
    h = MeasurementHarness("tpu_v5e", points_per_axis=2)
    for family in MEASURED_FAMILIES:
        spec = h.spec(family)
        for axis, full in zip(spec.axes, DEFAULT_AXES[family]):
            assert set(axis) <= set(full)
            assert axis[0] == full[0] and axis[-1] == full[-1]


def test_subsample_endpoints_and_bounds():
    axis = (1, 2, 4, 8, 16, 32)
    assert subsample(axis, 99) == axis
    assert subsample(axis, 2) == (1, 32)
    assert len(subsample(axis, 3)) == 3
    assert subsample(axis, 1) == (8,)
    with pytest.raises(ValueError):
        subsample(axis, 0)


def test_deterministic_timer_is_deterministic():
    t1 = DeterministicTimer("tpu_v5e")
    t2 = DeterministicTimer("tpu_v5e")
    op = ops.GEMM(64, 256, 256)
    thunk_calls = []
    v1 = t1.time(op, lambda: thunk_calls.append(1))
    v2 = t2.time(op, lambda: thunk_calls.append(1))
    assert v1 == v2 > 0
    assert not thunk_calls          # the CI timer never runs the kernel


def test_deterministic_run_reproduces_artifact_bit_for_bit(artifact):
    again = run_calibration("tpu_v5e", "repro-jax",
                            timer=DeterministicTimer("tpu_v5e"),
                            created_at=CREATED, points_per_axis=2)
    assert again == artifact
    assert again.digest() == artifact.digest()


def test_wallclock_timer_times_the_real_kernels():
    art = run_calibration(
        "tpu_v5e", "repro-jax", timer=WallClockTimer(reps=1, trials=1),
        created_at=CREATED, points_per_axis=2,
        families=["gemm"], axes_override=TINY_AXES, interpret=True)
    assert all(s.measured_s > 0 for s in art.samples)
    assert art.timer == "wallclock"
    fit = art.fits["gemm"]
    assert fit.mape_calibrated <= fit.mape_uncalibrated


@pytest.mark.slow
def test_wallclock_full_pipeline_all_families():
    """The real measurement path: every family's Pallas kernel executed in
    interpret mode on tiny grids — artifact round-trips and calibration
    improves (or at worst matches) the per-family MAPE."""
    art = run_calibration(
        "tpu_v5e", "repro-jax", timer=WallClockTimer(reps=1, trials=1),
        created_at=CREATED, points_per_axis=2, axes_override=TINY_AXES,
        interpret=True)
    assert set(art.fits) == set(MEASURED_FAMILIES)
    assert CalibrationArtifact.from_json(art.to_json()) == art
    rep = accuracy_report(art)
    for family, row in rep["families"].items():
        assert math.isfinite(row["mape_calibrated"]), family
        assert row["mape_calibrated"] <= row["mape_uncalibrated"], family


def test_make_timer_factory():
    assert make_timer("deterministic", "tpu_v5e").name == "deterministic"
    assert make_timer("wallclock", "tpu_v5e").name == "wallclock"
    with pytest.raises(ValueError, match="unknown timer"):
        make_timer("sundial", "tpu_v5e")


def test_created_at_is_required_provenance():
    with pytest.raises(ValueError, match="created_at"):
        run_calibration("tpu_v5e", timer=DeterministicTimer("tpu_v5e"))


def test_unknown_family_rejected():
    with pytest.raises(ValueError, match="unknown measurement families"):
        MeasurementHarness("tpu_v5e", families=["warp_drive"])


# ---------------------------------------------------------------------------
# fitting layer
# ---------------------------------------------------------------------------

def _samples(family, pairs):
    return [Sample(family=family, coords=(float(i),), predicted_s=p,
                   measured_s=m) for i, (p, m) in enumerate(pairs)]


def test_fit_recovers_pure_scale():
    pairs = [(p, 1.3 * p) for p in (1e-6, 1e-5, 1e-4, 1e-3)]
    fit = fit_family("gemm", _samples("gemm", pairs))
    assert fit.scale == pytest.approx(1.3, rel=1e-6)
    assert fit.exponent == pytest.approx(1.0, abs=1e-9)
    assert fit.mape_calibrated < 1e-6
    assert fit.r2 == pytest.approx(1.0)


def test_fit_recovers_power_law():
    pairs = [(p, 2.0 * p ** 1.1) for p in (1e-6, 1e-5, 1e-4, 1e-3)]
    fit = fit_family("moe", _samples("moe", pairs))
    assert fit.exponent == pytest.approx(1.1, rel=1e-6)
    assert fit.mape_calibrated < 1e-6


def test_fit_clamps_runaway_exponent():
    pairs = [(p, p ** 3) for p in (1e-3, 1e-2, 1e-1)]
    fit = fit_family("gemm", _samples("gemm", pairs))
    assert fit.exponent == 2.0          # EXPONENT_MAX


def test_fit_degenerate_falls_back_to_scale():
    # two samples: slope unidentifiable by policy -> exponent pinned to 1
    fit = fit_family("recurrent",
                     _samples("recurrent", [(1e-4, 2e-4), (1e-3, 3e-3)]))
    assert fit.exponent == 1.0
    # one predictor value repeated: zero variance -> scale only
    fit = fit_family("comm", _samples("comm", [(1e-4, 2e-4)] * 5))
    assert fit.exponent == 1.0
    assert fit.scale == pytest.approx(2.0, rel=1e-6)


def test_fit_families_groups_and_fit_recovers_timer_skew(artifact):
    # the deterministic timer's skew is exactly what the fit must recover
    for family, fit in artifact.fits.items():
        skew = DeterministicTimer.DEFAULT_SKEW[family]
        assert fit.scale == pytest.approx(skew, rel=0.15)
        assert fit.mape_calibrated <= fit.mape_uncalibrated
        assert math.isfinite(fit.r2) and math.isfinite(fit.residual_std)


def test_fit_empty_family_raises():
    with pytest.raises(ValueError, match="no samples"):
        fit_family("gemm", [])
    assert fit_families([]) == {}


# ---------------------------------------------------------------------------
# artifact: schema + lossless round-trip + golden fixture
# ---------------------------------------------------------------------------

def test_artifact_roundtrip_lossless(artifact):
    blob = artifact.to_json()
    again = CalibrationArtifact.from_json(blob)
    assert again == artifact
    assert again.to_json() == blob
    assert again.corrections() == artifact.corrections()
    assert again.digest() == artifact.digest()


def test_artifact_save_load_lossless(tmp_path, artifact):
    path = artifact.save(str(tmp_path / "cal.json"))
    assert CalibrationArtifact.load(path) == artifact


def test_artifact_rejects_wrong_kind_and_version(artifact):
    d = artifact.to_dict()
    bad_kind = dict(d, kind="search-report")
    with pytest.raises(ValueError, match="not a calibration artifact"):
        CalibrationArtifact.from_dict(bad_kind)
    bad_ver = dict(d, schema_version=99)
    with pytest.raises(ValueError, match="unsupported calibration"):
        CalibrationArtifact.from_dict(bad_ver)


def test_grid_digest_tracks_grid_not_latencies(artifact):
    moved = [Sample(s.family, s.coords, s.predicted_s, s.measured_s * 2)
             for s in artifact.samples]
    assert grid_digest(moved) == artifact.grid_digest
    dropped = artifact.samples[1:]
    assert grid_digest(dropped) != artifact.grid_digest


def test_golden_fixture_loads_and_roundtrips(artifact):
    golden = CalibrationArtifact.load(GOLDEN)
    assert golden.schema_version == 1
    assert (golden.platform, golden.backend) == ("tpu_v5e", "repro-jax")
    with open(GOLDEN) as f:
        raw = json.load(f)
    assert CalibrationArtifact.from_dict(raw).to_dict() == raw
    # the deterministic pipeline still reproduces the committed artifact
    # (modulo the fixture's free-text provenance note)
    assert dict(golden.to_dict(), notes="") \
        == dict(artifact.to_dict(), notes="")


# ---------------------------------------------------------------------------
# PerfDatabase correction layer
# ---------------------------------------------------------------------------

def test_database_applies_family_corrections(artifact):
    plain = PerfDatabase("tpu_v5e", "repro-jax")
    cal = PerfDatabase("tpu_v5e", "repro-jax", calibration=artifact)
    g = ops.GEMM(256, 1024, 1024)
    scale, exponent = artifact.corrections()["gemm"]
    t = plain.op_latency(g)
    assert cal.op_latency(g) == pytest.approx(scale * t ** exponent,
                                              rel=1e-9)
    # decode attention goes through its own family
    a = ops.Attention(phase="decode", batch=8, q_len=1, kv_len=2048,
                      heads=8, kv_heads=2, head_dim=64)
    s2, e2 = artifact.corrections()["attn_decode"]
    t2 = plain.op_latency(a)
    assert cal.op_latency(a) == pytest.approx(s2 * t2 ** e2, rel=1e-9)


def test_apply_calibration_invalidates_memo(artifact):
    db = PerfDatabase("tpu_v5e", "repro-jax")
    g = ops.GEMM(512, 512, 512)
    before = db.op_latency(g)
    db.apply_calibration(artifact)
    after = db.op_latency(g)
    assert after != before          # memoized value did not leak through


def test_apply_calibration_rejects_foreign_silicon(artifact):
    with pytest.raises(ValueError, match="tpu_v5p"):
        PerfDatabase("tpu_v5p", "repro-jax").apply_calibration(artifact)
    with pytest.raises(ValueError, match="vllm"):
        PerfDatabase("tpu_v5e", "vllm").apply_calibration(artifact)


def test_fingerprint_surfaces_calibration(artifact):
    plain = PerfDatabase("tpu_v5e", "repro-jax")
    assert plain.fingerprint()["calibration"] is None
    cal = PerfDatabase("tpu_v5e", "repro-jax", calibration=artifact)
    ident = cal.fingerprint()["calibration"]
    assert ident == artifact.identity()
    assert ident["digest"] == artifact.digest()
    assert ident["created_at"] == CREATED


def test_database_save_load_keeps_calibration(tmp_path, artifact):
    db = PerfDatabase("tpu_v5e", "repro-jax", calibration=artifact)
    g = ops.GEMM(128, 1024, 4096)
    want = db.op_latency(g)
    path = db.save(str(tmp_path / "db.json"))
    again = PerfDatabase.load(path)
    assert again.op_latency(g) == pytest.approx(want, rel=1e-12)
    assert again.fingerprint()["calibration"] == artifact.identity()


def test_load_calibration_from_path(tmp_path, artifact):
    path = artifact.save(str(tmp_path / "cal.json"))
    db = PerfDatabase("tpu_v5e", "repro-jax").load_calibration(path)
    assert db.fingerprint()["calibration"]["digest"] == artifact.digest()


# ---------------------------------------------------------------------------
# accuracy report
# ---------------------------------------------------------------------------

def test_accuracy_report_calibrated_beats_uncalibrated(artifact):
    rep = accuracy_report(artifact)
    assert set(rep["families"]) == set(MEASURED_FAMILIES)
    for row in rep["families"].values():
        assert math.isfinite(row["mape_calibrated"])
        assert row["mape_calibrated"] <= row["mape_uncalibrated"]
    o = rep["overall"]
    assert o["mape_calibrated"] <= o["mape_uncalibrated"]
    assert o["n_samples"] == len(artifact.samples)
    text = format_accuracy(rep)
    assert "overall" in text and artifact.digest() in text


def test_accuracy_report_recomputes_from_samples(artifact):
    # strip the fits: uncorrected predictions must audit as-is
    bare = CalibrationArtifact.from_dict(
        dict(artifact.to_dict(), fits={}))
    rep = accuracy_report(bare)
    for row in rep["families"].values():
        assert row["mape_calibrated"] == row["mape_uncalibrated"]


# ---------------------------------------------------------------------------
# Configurator.with_calibration
# ---------------------------------------------------------------------------

def _configurator():
    from repro.api import Configurator
    return (Configurator.for_model("llama3.1-8b")
            .traffic(isl=256, osl=64)
            .sla(ttft_ms=2000, min_tokens_per_s_user=10)
            .cluster(chips=8).backend("repro-jax")
            .modes("aggregated"))


def test_with_calibration_flows_into_search_report(tmp_path, artifact):
    path = artifact.save(str(tmp_path / "cal.json"))
    report = _configurator().with_calibration(path).search(
        generate_launch=False)
    assert report.fingerprint["calibration"] == artifact.identity()
    plain = _configurator().search(generate_launch=False)
    assert plain.fingerprint["calibration"] is None
    # corrections actually moved the projections
    assert plain.best.tpot_ms != report.best.tpot_ms


def test_with_calibration_validates_target_pair(artifact):
    c = _configurator().cluster(chips=8, platform="tpu_v5p")
    with pytest.raises(ValueError, match="tpu_v5p"):
        c.with_calibration(artifact)


def test_compare_variants_off_the_calibrated_pair_price_uncalibrated(
        artifact):
    """A compare sweep must not abort when a variant steers off the
    calibrated (platform, backend): that variant prices uncalibrated and
    its report says so."""
    comparison = _configurator().with_calibration(artifact).compare(
        [{"isl": 128}, {"backend": "trtllm"}], generate_launch=False)
    calibrated, foreign = comparison.reports
    assert calibrated.fingerprint["calibration"] == artifact.identity()
    assert foreign.fingerprint["backend"] == "trtllm"
    assert foreign.fingerprint["calibration"] is None


def test_op_family_is_the_correction_key(artifact):
    """The mapping the database corrects by is the mapping the harness
    measures and the fit keys by — locked via ops.op_family."""
    reps = {
        "gemm": ops.GEMM(64, 256, 256),
        "attn_prefill": ops.Attention(phase="prefill", batch=1, q_len=64,
                                      kv_len=64, heads=4, kv_heads=2,
                                      head_dim=64),
        "attn_decode": ops.Attention(phase="decode", batch=4, q_len=1,
                                     kv_len=256, heads=4, kv_heads=2,
                                     head_dim=64),
        "moe": ops.MoEOp(tokens=32, d_model=256, d_ff=512, num_experts=4,
                         top_k=1),
        "recurrent": ops.RecurrentOp(kind="rglru", batch=1, seq=64,
                                     width=256),
    }
    assert set(reps) == set(MEASURED_FAMILIES)
    plain = PerfDatabase("tpu_v5e", "repro-jax")
    cal = PerfDatabase("tpu_v5e", "repro-jax", calibration=artifact)
    for family, op in reps.items():
        assert ops.op_family(op) == family
        # every measured family's correction actually lands on its ops
        assert cal.op_latency(op) != plain.op_latency(op), family


# ---------------------------------------------------------------------------
# CLI: calibrate run | report | apply
# ---------------------------------------------------------------------------

def test_cli_calibrate_run_report_apply(tmp_path, capsys, artifact):
    out = str(tmp_path / "cal.json")
    rc = cli_main(["calibrate", "run", "--timer", "deterministic",
                   "--points", "2", "--out", out,
                   "--timestamp", CREATED])
    assert rc == 0
    assert CalibrationArtifact.load(out) == artifact
    capsys.readouterr()

    rc = cli_main(["calibrate", "report", "--artifact", out, "--json"])
    assert rc == 0
    rep = json.loads(capsys.readouterr().out)
    assert rep["overall"]["mape_calibrated"] \
        <= rep["overall"]["mape_uncalibrated"]

    rc = cli_main(["calibrate", "apply", "--artifact", out, "--json"])
    assert rc == 0
    fp = json.loads(capsys.readouterr().out)
    assert fp["calibration"]["digest"] == artifact.digest()


def test_cli_calibrate_apply_with_workload(tmp_path, capsys, artifact):
    out = str(tmp_path / "cal.json")
    artifact.save(out)
    rc = cli_main(["calibrate", "apply", "--artifact", out,
                   "--model", "llama3.1-8b", "--isl", "256", "--osl", "64",
                   "--modes", "aggregated", "--dtype", "fp8",
                   "--ttft", "2000", "--min-speed", "10", "--json"])
    assert rc == 0
    report = json.loads(capsys.readouterr().out)
    assert report["database"]["calibration"]["digest"] == artifact.digest()


def test_cli_calibrate_apply_partial_workload_exits_2(tmp_path, capsys,
                                                      artifact):
    out = str(tmp_path / "cal.json")
    artifact.save(out)
    rc = cli_main(["calibrate", "apply", "--artifact", out,
                   "--model", "llama3.1-8b", "--isl", "256"])  # no --osl
    assert rc == 2
    assert "--model/--isl/--osl" in capsys.readouterr().err


def test_cli_calibrate_bad_artifact_exits_2(tmp_path, capsys):
    bad = tmp_path / "bad.json"
    bad.write_text(json.dumps({"kind": "nonsense"}))
    rc = cli_main(["calibrate", "report", "--artifact", str(bad)])
    assert rc == 2
