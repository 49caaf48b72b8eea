"""chip_smoke.py's phases on the CPU at the reduced width, and the serve
entry point's refusals.  The chip run itself is ``python chip_smoke.py``."""
import json
import os
import sys

import jax
import pytest

from repro import models
from repro.configs import get_config
from repro.core import jaxenv
from repro.launch import serve as serve_mod

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
import chip_smoke  # noqa: E402


def _records(out: str, phase: str) -> dict:
    lines = [l for l in out.splitlines() if l.startswith(phase + " {")]
    assert len(lines) == 1, out
    return json.loads(lines[0][len(phase) + 1:])


@pytest.fixture(scope="module")
def reduced():
    cfg = get_config(chip_smoke.MODEL).reduced()
    return cfg, models.init_params(cfg, jax.random.PRNGKey(0))


def test_smoke_phases_on_reduced_config(reduced, capsys):
    cfg, params = reduced
    launch = chip_smoke.search("tpu_v5e")
    search = _records(capsys.readouterr().out, "search")
    assert search["mode"] == "aggregated"
    assert search["parallel"] == {"tp": 1, "pp": 1, "ep": 1, "dp": 1}
    assert search["search_wall_s"] > 0
    assert set(search["projected"]) == {"ttft_ms", "tpot_ms",
                                        "tokens_per_s_per_chip"}

    chip_smoke.serve_launch(launch, cfg, params, requests=4, isl=16, osl=6)
    served = _records(capsys.readouterr().out, "serve")
    for key in ("ttft_ms_p50", "ttft_ms_p99", "tpot_ms_p50", "tpot_ms_p99",
                "tokens_per_s", "compile_s", "peak_bytes_in_use", "device",
                "projected (information only)"):
        assert key in served, key
    assert served["requests"] == 4 and served["slots"] == 4
    assert served["launch_batch"] == launch["batch_size"]

    rec = chip_smoke.check_logits(cfg, params, isl=16, osl=6)
    assert rec["passed"] and rec["rel_l2_max"] <= rec["tolerance"]
    assert len(rec["rel_l2"]) == 4
    assert _records(capsys.readouterr().out, "logits")["passed"]


def test_logit_tolerance_is_bf16_rounding():
    # sqrt(4 * 24) * 2**-8 * 2 for internlm2-1.8b's 24 layers
    assert chip_smoke.logit_tolerance(24) == pytest.approx(0.0765, abs=1e-4)


def test_smoke_refuses_a_cpu_backend(capsys):
    assert jax.devices()[0].platform != "tpu"
    with pytest.raises(SystemExit) as e:
        chip_smoke.main()
    assert e.value.code not in (0, None)
    out = capsys.readouterr().out
    assert '"ok"' not in out


@pytest.mark.parametrize("launch,argv", [
    ({"mode": "aggregated", "model": "internlm2-1.8b", "batch_size": 4,
      "parallel": {"tp": 2, "pp": 1, "ep": 1, "dp": 1}}, []),
    ({"mode": "aggregated", "model": "internlm2-1.8b", "batch_size": 4,
      "parallel": {"tp": 1, "pp": 1, "ep": 1, "dp": 2}}, []),
    ({"mode": "disaggregated", "model": "internlm2-1.8b",
      "decode_workers": {"batch_size": 4}}, []),
    (None, ["--tp", "4"]),
    (None, ["--disaggregated"]),
])
def test_serve_refuses_multi_device_launch(tmp_path, capsys, launch, argv):
    if launch is not None:
        path = tmp_path / "launch.json"
        path.write_text(json.dumps(launch))
        argv = argv + ["--launch-config", str(path)]
    assert serve_mod.main(argv) == 2
    assert "engine runs one device" in capsys.readouterr().err


def test_serve_cli_reduced_default(tmp_path, monkeypatch, capsys):
    # an outside cache dir keeps main() off this process's jax config
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert serve_mod.main(["--requests", "2", "--isl", "8", "--osl", "3",
                           "--max-batch", "2"]) == 0
    out = capsys.readouterr().out
    assert "served 2 requests" in out and "compile" in out


def test_compile_cache_env_stands(tmp_path, monkeypatch):
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert jaxenv.use_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_defaults_to_ignored_checkout_dir(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    before = jax.config.jax_compilation_cache_dir
    try:
        path = jaxenv.use_compile_cache()
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
    assert path == os.path.join(REPO, ".jax_cache")
    with open(os.path.join(REPO, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()


@pytest.mark.parametrize("kind,key", [("TPU v5 lite", "tpu_v5e"),
                                      ("TPU v5p", "tpu_v5p")])
def test_device_kind_maps_to_platform(kind, key):
    from repro.core.hardware import PLATFORMS, platform_for_device_kind
    assert platform_for_device_kind(kind) == key and key in PLATFORMS


def test_unknown_device_kind_is_an_error():
    from repro.core.hardware import platform_for_device_kind
    with pytest.raises(ValueError, match="no platform profile"):
        platform_for_device_kind("cpu")
