"""Cell specs: ``BENCHMARK.json`` plus the files each entry names.

A cell (an entry of ``workloads``) names a configuration and a traffic mix.
Both are found by name: ``bench/configs/<config>.json`` and
``bench/traffic/<traffic>.json``; the traffic file names the generator
that reads it (``bench/traffic/<generator>.py``), and the configuration
file its plain
reference (``bench/reference/<reference>.py``). Per-layer metrics are
``bench/metrics/<metric>.py``. Adding any of them is adding files plus
``BENCHMARK.json`` entries; nothing here changes. A configuration reaches
the program through ``bench/traffic/program.py``'s table of published keys
and its ``"program"`` object, and is counted by ``harness/flops.py`` (GQA
or latent attention, dense or routed-expert layers), so a model the program
builds needs no edit either; one of another architecture brings its
reference as a new file. A cell's CPU twin is
``bench/tests/data/tiny/<cell>.json``.
"""

from __future__ import annotations

import importlib.util
import json
import os
import sys
from dataclasses import dataclass, field
from typing import Any, Dict, List

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)


@dataclass
class Cell:
    name: str
    chips: int
    config: Dict[str, Any]
    traffic: Dict[str, Any]
    end_to_end: List[Dict[str, Any]] = field(default_factory=list)
    per_layer: List[Dict[str, Any]] = field(default_factory=list)


def _read_json(path: str) -> Dict[str, Any]:
    with open(path) as f:
        return json.load(f)


def load_module(path: str, name: str):
    """Import a file of the benchmark by path (names may hold dots)."""
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or spec.loader is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def reports(metric: Dict[str, Any], cell: str,
            e2e_names: List[str]) -> bool:
    """Whether ``cell`` reports ``metric``: listed in its ``workloads``, or,
    without that key, every cell that reports what it moves (per-layer) or
    every cell (end-to-end)."""
    if "workloads" in metric:
        return cell in metric["workloads"]
    moves = metric.get("moves")
    return moves is None or moves in e2e_names


def load_cell(name: str, bench: Dict[str, Any] | None = None,
              bench_dir: str = BENCH) -> Cell:
    """The cell ``name`` with its configuration and traffic files read."""
    if bench is None:
        bench = _read_json(os.path.join(os.path.dirname(bench_dir),
                                        "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json "
                       f"(have {sorted(cells)})")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    entry = configs[w["config"]]
    config = _read_json(os.path.join(os.path.dirname(bench_dir),
                                     entry["file"]))
    config.setdefault("name", entry["name"])
    traffic = _read_json(os.path.join(bench_dir, "traffic",
                                      w["traffic"] + ".json"))
    traffic.setdefault("name", w["traffic"])
    e2e = [m for m in bench["end_to_end"] if reports(m, name, [])]
    e2e_names = [m["name"] for m in e2e]
    layer = [m for m in bench["per_layer"] if reports(m, name, e2e_names)]
    return Cell(name=name, chips=int(w["chips"]), config=config,
                traffic=traffic, end_to_end=e2e, per_layer=layer)


def generator(cell: Cell, bench_dir: str = BENCH):
    """The generator that runs the traffic mix
    (``bench/traffic/<generator>.py``); generators import their sibling
    modules by name."""
    g = cell.traffic["generator"]
    traffic = os.path.join(bench_dir, "traffic")
    if traffic not in sys.path:
        sys.path.insert(0, traffic)
    return load_module(os.path.join(traffic, g + ".py"), f"bench_gen_{g}")


def reference(name: str, bench_dir: str = BENCH):
    """A plain reference (``bench/reference/<name>.py``): a configuration
    names its model's, a generator its own."""
    return load_module(os.path.join(bench_dir, "reference", name + ".py"),
                       f"bench_reference_{name}")


def metric_reader(name: str, bench_dir: str = BENCH):
    """The per-layer metric's reader (``bench/metrics/<name>.py``)."""
    return load_module(os.path.join(bench_dir, "metrics", name + ".py"),
                       "bench_metric_" + name.replace(".", "_"))
