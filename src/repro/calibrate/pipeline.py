"""measure → fit → persist, and the accuracy report that audits it.

``run_calibration`` is the whole offline pipeline in one call: sweep the
measurement grids with the chosen timer, fit per-family corrections, and
package a versioned :class:`CalibrationArtifact`.  ``accuracy_report``
recomputes predicted-vs-measured MAPE from the artifact's embedded samples
(it does NOT trust the stats stored in the fits), so a tampered or stale
artifact audits honestly.
"""
from __future__ import annotations

from typing import Dict, Optional, Sequence

from repro.calibrate.artifact import CalibrationArtifact, grid_digest
from repro.calibrate.fit import fit_families, group_by_family, mape


def run_calibration(platform: str = "tpu_v5e", backend: str = "repro-jax",
                    timer=None, created_at: str = "",
                    points_per_axis: int = 3,
                    families: Optional[Sequence[str]] = None,
                    notes: str = "",
                    axes_override: Optional[Dict] = None,
                    interpret: bool = False) -> CalibrationArtifact:
    """Run the full calibration pipeline and return the artifact.

    ``created_at`` is required provenance supplied by the caller (an
    ISO-8601 timestamp) — the pipeline never reads ambient wall-clock
    time, so the same sweep with the deterministic timer reproduces the
    artifact byte-for-byte.  ``interpret`` runs the wall-clock timer's
    Pallas kernels in interpret mode (a host with no TPU).
    """
    if not created_at:
        raise ValueError(
            "created_at is required provenance: pass an ISO-8601 timestamp "
            "(the pipeline never stamps ambient time)")
    # keep the harness (and, on wallclock runs, jax + the Pallas kernels
    # its thunks pull in) out of module import so artifact consumers
    # (PerfDatabase) stay light
    from repro.calibrate.harness import MeasurementHarness
    harness = MeasurementHarness(
        platform=platform, backend=backend, timer=timer,
        points_per_axis=points_per_axis, families=families,
        axes_override=axes_override, interpret=interpret)
    samples = harness.measure_all()
    return CalibrationArtifact(
        platform=harness.platform.name, backend=backend,
        timer=harness.timer.name, created_at=created_at,
        grid_digest=grid_digest(samples),
        fits=fit_families(samples), samples=samples, notes=notes)


def accuracy_report(artifact: CalibrationArtifact) -> Dict:
    """Per-family + overall MAPE, calibrated vs uncalibrated, recomputed
    from the artifact's raw samples."""
    families: Dict[str, Dict] = {}
    all_pred, all_corr, all_meas = [], [], []
    for family, group in sorted(group_by_family(artifact.samples).items()):
        fit = artifact.fits.get(family)
        pred = [s.predicted_s for s in group]
        meas = [s.measured_s for s in group]
        corr = [fit.correct(p) if fit is not None else p for p in pred]
        families[family] = {
            "n_samples": len(group),
            "scale": fit.scale if fit else 1.0,
            "exponent": fit.exponent if fit else 1.0,
            "r2": fit.r2 if fit else float("nan"),
            "mape_uncalibrated": mape(pred, meas),
            "mape_calibrated": mape(corr, meas),
        }
        all_pred.extend(pred)
        all_corr.extend(corr)
        all_meas.extend(meas)
    return {
        "platform": artifact.platform,
        "backend": artifact.backend,
        "timer": artifact.timer,
        "created_at": artifact.created_at,
        "grid_digest": artifact.grid_digest,
        "digest": artifact.digest(),
        "families": families,
        "overall": {
            "n_samples": len(all_meas),
            "mape_uncalibrated": mape(all_pred, all_meas),
            "mape_calibrated": mape(all_corr, all_meas),
        },
    }


def format_accuracy(report: Dict) -> str:
    """Human-readable table for ``calibrate report``."""
    lines = [
        f"calibration {report['digest']} — {report['platform']} / "
        f"{report['backend']} (timer: {report['timer']}, "
        f"created {report['created_at']})",
        f"{'family':<14} {'n':>4} {'scale':>8} {'exp':>6} {'r2':>6} "
        f"{'MAPE uncal':>11} {'MAPE cal':>9}",
    ]
    for family, row in report["families"].items():
        lines.append(
            f"{family:<14} {row['n_samples']:>4} {row['scale']:>8.3f} "
            f"{row['exponent']:>6.3f} {row['r2']:>6.3f} "
            f"{row['mape_uncalibrated']:>10.1f}% "
            f"{row['mape_calibrated']:>8.1f}%")
    o = report["overall"]
    lines.append(
        f"{'overall':<14} {o['n_samples']:>4} {'':>8} {'':>6} {'':>6} "
        f"{o['mape_uncalibrated']:>10.1f}% {o['mape_calibrated']:>8.1f}%")
    return "\n".join(lines)
