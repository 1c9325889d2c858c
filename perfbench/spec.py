"""Finding a cell's files by the names in ``BENCHMARK.json``.

A cell names a configuration and a traffic mix. The configuration is the
JSON file that ``BENCHMARK.json`` gives for it; the traffic mix is
``traffic/<traffic>.json`` and every metric is read by
``metrics/<metric>.py``, both under the benchmark's directory. Adding a
cell, a configuration, a traffic mix or a metric is adding such files and
entries: nothing here names one.
"""
from __future__ import annotations

import importlib.util
import json
from pathlib import Path
from typing import Callable, NamedTuple

ROOT = Path(__file__).resolve().parent
BENCHMARK = ROOT.parent / "BENCHMARK.json"


class Cell(NamedTuple):
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: list[dict]     # the metric entries that apply to this cell
    per_layer: list[dict]
    root: Path                 # the directory holding traffic/ and metrics/


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def cell(name: str, benchmark: Path = BENCHMARK, root: Path = ROOT) -> Cell:
    """The cell ``name`` of the benchmark file, with its configuration and
    traffic loaded. Paths in the benchmark file are relative to its
    directory."""
    bench = load_json(benchmark)
    found = [w for w in bench["workloads"] if w["name"] == name]
    if len(found) != 1:
        known = ", ".join(w["name"] for w in bench["workloads"])
        raise KeyError(f"no workload {name!r} (known: {known})")
    w = found[0]
    cfg_entry = next(c for c in bench["configs"] if c["name"] == w["config"])
    config = load_json(benchmark.parent / cfg_entry["file"])
    traffic = load_json(root / "traffic" / f"{w['traffic']}.json")
    return Cell(name=name, chips=int(w["chips"]), config=config,
                traffic=traffic,
                end_to_end=[m for m in bench["end_to_end"] if _applies(m, name)],
                per_layer=[m for m in bench["per_layer"] if _applies(m, name)],
                root=root)


def reader(root: Path, metric: str) -> Callable:
    """The ``read(run)`` function of ``metrics/<metric>.py``."""
    path = root / "metrics" / f"{metric}.py"
    mod_spec = importlib.util.spec_from_file_location(
        f"perfbench_metric_{metric.replace('.', '_').replace('-', '_')}", path)
    module = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(module)
    return module.read
