"""The serve entry point's capture flags: the engine's spans, counters and a
profiler trace with the spans on its host plane (reduced config, CPU)."""
import glob
import json

import jax

from repro.launch import serve as serve_mod
from repro.obs import TraceArtifact, get_metrics, get_tracer
from repro.obs.trace import NULL_TRACER


def test_serve_writes_engine_spans_counters_and_profile(tmp_path,
                                                        monkeypatch, capsys):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "cache"))
    trace_out, metrics_out = tmp_path / "t.jsonl", tmp_path / "m.json"
    profile = tmp_path / "profile"
    assert serve_mod.main([
        "--requests", "2", "--isl", "8", "--osl", "3", "--max-batch", "2",
        "--trace-out", str(trace_out), "--metrics-out", str(metrics_out),
        "--profile-dir", str(profile)]) == 0
    assert "served 2 requests" in capsys.readouterr().out
    assert get_tracer() is NULL_TRACER and get_metrics() is None

    art = TraceArtifact.load(str(trace_out))
    assert art.meta["command"] == "serve"
    steps = [s for s in art.spans if s.name == "engine.step"]
    assert steps and all(s.v_end > s.v_start > 0 for s in steps)
    # the warm-up step built the programs; the timed steps built none
    assert steps[0].attrs["new_programs"] >= 2
    counters = json.loads(metrics_out.read_text())["counters"]
    # one warm-up prompt and two timed ones, 8 tokens each
    assert counters["repro_engine_prefill_tokens_total"] == 24
    assert counters[
        "repro_engine_new_programs_total{program=decode_step}"] == 1

    path = glob.glob(str(profile / "**" / "*.xplane.pb"), recursive=True)
    assert len(path) == 1
    prof = jax.profiler.ProfileData.from_file(path[0])
    host = {e.name for plane in prof.planes if plane.name == "/host:CPU"
            for line in plane.lines for e in line.events}
    assert {"engine.step", "engine.prefill.run",
            "engine.decode.run"} <= host


def test_serve_without_capture_flags_installs_nothing(tmp_path,
                                                      monkeypatch):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    seen = {}
    real = serve_mod.serve

    def serve(args, **kw):
        seen["tracer"], seen["metrics"] = get_tracer(), get_metrics()
        return real(args, **kw)
    monkeypatch.setattr(serve_mod, "serve", serve)
    assert serve_mod.main(["--requests", "1", "--isl", "8", "--osl", "2",
                           "--max-batch", "1"]) == 0
    assert seen == {"tracer": NULL_TRACER, "metrics": None}
