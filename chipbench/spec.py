"""What a cell is made of, found by name in files of their own.

``BENCHMARK.json`` (at the checkout's root) names each cell's
configuration and traffic mix.  Everything else is a file under this
directory, found by the name alone:

    configs/<config>.json     sizes as run, source, cuts, limits
    traffic/<mix>.json        arrival process (an open loop at a rate, or
                              closed-loop clients; ``traffic.py``),
                              lengths, engine slots
    metrics/<metric>.py       one reader per metric: ``read(run) -> float|None``

A reader gets a ``harness.Run``: the cell, ``setup_s``, the chip's peaks,
the model's counted shapes and the window's requests and steps; with
``--trace 1`` also the trace's reduction (``reduced``), the idle time
under the engine's ``engine.*`` spans (``spans``, ``spans.Spans``) and the
engine's ``repro_engine_*`` counters over the window (``counters``, a
``repro.obs`` ``MetricsRegistry``: ``run.counters.counter_total(name)``).
A reader with nothing to read returns None.

Adding a configuration, a mix or a metric is adding files and entries;
no existing file changes.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
from typing import Callable, Dict, List, Optional

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


@dataclasses.dataclass(frozen=True)
class Metric:
    name: str
    unit: str
    workloads: Optional[tuple]       # None: every cell that reports ``moves``
    moves: Optional[str] = None


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config: Dict
    traffic: Dict
    end_to_end: List[Metric]
    per_layer: List[Metric]


def load_json(path: str) -> Dict:
    with open(path) as f:
        return json.load(f)


def _metrics(bench: Dict, key: str) -> List[Metric]:
    return [Metric(name=m["name"], unit=m["unit"],
                   workloads=tuple(m["workloads"]) if "workloads" in m
                   else None, moves=m.get("moves"))
            for m in bench[key]]


def metrics_for(cell_name: str, bench: Dict) -> tuple:
    """(end-to-end, per-layer) metrics reported by one cell."""
    e2e = [m for m in _metrics(bench, "end_to_end")
           if m.workloads is None or cell_name in m.workloads]
    names = {m.name for m in e2e}
    layer = [m for m in _metrics(bench, "per_layer")
             if (cell_name in m.workloads if m.workloads is not None
                 else m.moves in names)]
    return e2e, layer


def load_cell(name: str, root: str = ROOT) -> Cell:
    """Resolve a workload name to its cell, reading only data files."""
    bench = load_json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; "
                       f"known: {', '.join(sorted(cells))}")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    cfg_entry = configs[w["config"]]
    config = load_json(os.path.join(root, cfg_entry["file"]))
    traffic = load_json(os.path.join(root, "chipbench", "traffic",
                                     w["traffic"] + ".json"))
    e2e, layer = metrics_for(name, bench)
    return Cell(name=name, chips=int(w["chips"]),
                config=config, traffic=traffic, end_to_end=e2e,
                per_layer=layer)


def reader(metric: str, root: str = ROOT) -> Callable:
    """The ``read`` function of ``chipbench/metrics/<metric>.py``."""
    path = os.path.join(root, "chipbench", "metrics", metric + ".py")
    if not os.path.isfile(path):
        raise FileNotFoundError(f"metric {metric!r} has no reader at {path}")
    spec = importlib.util.spec_from_file_location(
        f"chipbench_metric_{metric.replace('.', '_').replace('-', '_')}",
        path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read
