"""Resolve a cell of BENCHMARK.json into its parts, by name.

A cell names a configuration and a traffic mix; its metrics are the
entries of `end_to_end` and `per_layer` that apply to it. Every part is
a file of its own, found from the name alone:

  configuration   the `file` its `configs` entry names (JSON)
  traffic mix     chip_bench/traffic/<traffic>.json
  per-layer metric  chip_bench/metrics/<name>.py, with `read(ctx)`
  driver          chip_bench/drivers/<traffic's "driver">.py, with `run`

So a later change adds a configuration, a mix or a metric by adding one
file and one entry, and edits nothing that is here.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
import sys


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: list       # metric entries that this cell reports
    per_layer: list
    run_seconds: int
    root: str              # the checkout the benchmark runs from


def _load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def resolve(cell_name: str, root: str) -> Cell:
    """The cell of `root`/BENCHMARK.json called `cell_name`."""
    bench = _load_json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if cell_name not in cells:
        raise KeyError(f"no workload {cell_name!r}; known: {sorted(cells)}")
    w = cells[cell_name]
    configs = {c["name"]: c for c in bench["configs"]}
    entry = configs[w["config"]]
    config = _load_json(os.path.join(root, entry["file"]))
    config.setdefault("name", entry["name"])
    traffic = _load_json(traffic_path(root, w["traffic"]))
    traffic.setdefault("name", w["traffic"])
    return Cell(
        name=cell_name, chips=int(w["chips"]), config=config,
        traffic=traffic,
        end_to_end=[m for m in bench["end_to_end"] if _applies(m, cell_name)],
        per_layer=[m for m in bench["per_layer"] if _applies(m, cell_name)],
        run_seconds=int(bench["run_seconds"]), root=root)


def traffic_path(root: str, name: str) -> str:
    return os.path.join(root, "chip_bench", "traffic", f"{name}.json")


def _load_module(path: str, name: str):
    if not os.path.isfile(path):
        raise FileNotFoundError(path)
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


def metric_reader(root: str, name: str):
    """`read(ctx)` of the per-layer metric `name`."""
    path = os.path.join(root, "chip_bench", "metrics", f"{name}.py")
    return _load_module(path, f"chip_bench_metric_{name}").read


def driver(root: str, name: str):
    """The module that drives a traffic mix's `driver` kind."""
    path = os.path.join(root, "chip_bench", "drivers", f"{name}.py")
    return _load_module(path, f"chip_bench_driver_{name}")
