"""A configuration, a mix and a metric added as new files are found by
name, with no edit to any file that is already there."""
import json
import os
import shutil
import sys

sys.path.insert(0, os.path.abspath(os.path.join(os.path.dirname(__file__),
                                                "..", "..")))

import pytest  # noqa: E402

from chipbench import spec  # noqa: E402


@pytest.fixture()
def root(tmp_path):
    """A copy of the benchmark's data, to which files are only added."""
    shutil.copy(os.path.join(spec.ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(spec.HERE, tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    return tmp_path


def _snapshot(root):
    out = {}
    for d, _, files in os.walk(root):
        for f in files:
            p = os.path.join(d, f)
            with open(p, "rb") as fh:
                out[os.path.relpath(p, root)] = fh.read()
    return out


def test_every_cell_resolves():
    bench = spec.load_json(os.path.join(spec.ROOT, "BENCHMARK.json"))
    for w in bench["workloads"]:
        cell = spec.load_cell(w["name"])
        assert cell.config["name"] == w["config"]
        assert cell.end_to_end and cell.per_layer
        assert any(m.name == "setup_s" for m in cell.end_to_end)
        for m in cell.end_to_end + cell.per_layer:
            assert callable(spec.reader(m.name))


def test_added_files_are_found_without_editing(root):
    before = _snapshot(root)
    cfg = spec.load_json(str(root / "chipbench/configs/internlm2-1.8b.json"))
    cfg.update(name="newmodel", num_hidden_layers=12, reduced=[
        "num_hidden_layers"])
    (root / "chipbench/configs/newmodel.json").write_text(json.dumps(cfg))
    mix = spec.load_json(str(root / "chipbench/traffic/chat.json"))
    mix["arrivals"] = {"kind": "poisson", "rate_rps": 2.0}
    (root / "chipbench/traffic/slow.json").write_text(json.dumps(mix))
    (root / "chipbench/metrics/new_counter.py").write_text(
        "def read(run):\n    return 42.0\n")
    # the entries a later PR adds to BENCHMARK.json
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "newmodel", "source": "x",
                             "file": "chipbench/configs/newmodel.json",
                             "reduced": ["num_hidden_layers"], "why": "x"})
    bench["workloads"].append({"name": "newmodel.slow",
                               "config": "newmodel", "traffic": "slow",
                               "chips": 1, "why": "x"})
    for m in bench["end_to_end"]:
        if "workloads" in m and "internlm2-1.8b.chat" in m["workloads"]:
            m["workloads"].append("newmodel.slow")
    bench["per_layer"].append({"name": "new_counter", "unit": "1",
                               "better": "higher", "source":
                               "program_counter", "layer": "engine",
                               "moves": "output_tok_s"})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))

    cell = spec.load_cell("newmodel.slow", root=str(root))
    assert cell.config["num_hidden_layers"] == 12
    assert cell.traffic["arrivals"]["rate_rps"] == 2.0
    assert "new_counter" in [m.name for m in cell.per_layer]
    assert spec.reader("new_counter", root=str(root))(None) == 42.0
    # a metric with no ``workloads`` key reaches every cell that reports
    # the end-to-end metric it moves, the old cells too
    old = spec.load_cell("internlm2-1.8b.chat", root=str(root))
    assert "new_counter" in [m.name for m in old.per_layer]

    after = _snapshot(root)
    changed = [p for p in before if p != "BENCHMARK.json"
               and before[p] != after.get(p)]
    assert changed == []


def test_unknown_names_are_refused(root):
    with pytest.raises(KeyError):
        spec.load_cell("no-such.cell", root=str(root))
    with pytest.raises(FileNotFoundError):
        spec.reader("no_such_metric", root=str(root))
