"""Finds a cell's files by the names in ``BENCHMARK.json``.

A cell (an entry of ``workloads``) names a configuration and a traffic
mix.  The configuration's file is the one its ``configs`` entry gives; the
traffic mix is ``traffic/<traffic>.json`` and each metric is read by
``metrics/<name>.py`` beside this file.  So a new configuration, mix, cell
or metric is added by adding files and entries alone.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
from typing import Callable, Dict, List

HERE = os.path.dirname(os.path.abspath(__file__))


@dataclasses.dataclass(frozen=True)
class Metric:
    name: str
    unit: str
    read: Callable


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config: Dict
    traffic: Dict
    end_to_end: List[Metric]
    per_layer: List[Metric]


def load_metric(entry: Dict, bench_dir: str = HERE) -> Metric:
    path = os.path.join(bench_dir, "metrics", entry["name"] + ".py")
    modspec = importlib.util.spec_from_file_location(
        "sgebench_metric_" + entry["name"].replace(".", "_").replace("-", "_"),
        path)
    if modspec is None or not os.path.isfile(path):
        raise FileNotFoundError(f"metric {entry['name']!r}: no reader {path}")
    mod = importlib.util.module_from_spec(modspec)
    modspec.loader.exec_module(mod)
    return Metric(name=entry["name"], unit=entry["unit"], read=mod.read)


def _applies(entry: Dict, cell: str) -> bool:
    return "workloads" not in entry or cell in entry["workloads"]


def load_cell(root: str, workload: str, bench_dir: str = HERE) -> Cell:
    """The cell ``workload`` of ``<root>/BENCHMARK.json`` with its
    configuration, traffic mix and metric readers."""
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r}; known: {sorted(cells)}")
    w = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    with open(os.path.join(root, configs[w["config"]]["file"])) as f:
        config = json.load(f)
    with open(os.path.join(bench_dir, "traffic", w["traffic"] + ".json")) as f:
        traffic = json.load(f)
    return Cell(
        name=workload, chips=int(w["chips"]), config=config, traffic=traffic,
        end_to_end=[load_metric(m, bench_dir) for m in bench["end_to_end"]
                    if _applies(m, workload)],
        per_layer=[load_metric(m, bench_dir) for m in bench["per_layer"]
                   if _applies(m, workload)],
    )
