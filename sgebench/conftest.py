"""Fixtures of the benchmark's CPU tests: a copy of the benchmark in a
temporary checkout, with tiny cells added as files and entries alone."""

from __future__ import annotations

import json
import os
import shutil

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)

TINY_CONFIGS = {
    "tiny-dense": {
        "name": "tiny-dense", "source": "test",
        "target": {"n": 400, "m": 2400, "labels": 16,
                   "label_dist": "uniform", "edge_labels": 1,
                   "directed": False},
        "index": "dense", "variant": "ri-ds-si-fc",
        "engine": {"n_workers": 4, "expand_width": 4},
        "service": {"max_lanes": 2, "batch_window_s": 0.01},
        "collect": 1024, "assumed": [], "reduced": []},
    "tiny-csr": {
        "name": "tiny-csr", "source": "test",
        "target": {"n": 600, "m": 2400, "labels": 16,
                   "label_dist": "uniform", "edge_labels": 1,
                   "directed": False},
        "index": "csr", "variant": "ri-ds-si-acfc",
        "engine": {"n_workers": 4, "expand_width": 4, "step_backend": "csr",
                   "use_pallas": True},
        "service": {"max_lanes": 1, "batch_window_s": 0.01},
        "collect": 1024, "assumed": [], "reduced": []},
}
TINY_TRAFFIC = {"loop": "closed", "clients": 2, "pattern_arcs": [4, 8],
                "queries_per_client": 300}
TINY_COUNT = dict(TINY_TRAFFIC, stream=False)


def copy_benchmark(dest: str) -> str:
    """A checkout holding only ``BENCHMARK.json`` and the benchmark."""
    os.makedirs(dest, exist_ok=True)
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), dest)
    shutil.copytree(HERE, os.path.join(dest, "sgebench"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    return dest


def add_cell(root: str, config: dict, traffic_name: str, traffic: dict,
             cell: str) -> None:
    """Add a configuration, a traffic mix and a cell as new files and
    entries; the cell joins every metric's ``workloads`` list."""
    bench = os.path.join(root, "sgebench")
    cfg_file = f"sgebench/configs/{config['name']}.json"
    with open(os.path.join(root, cfg_file), "w") as f:
        json.dump(config, f)
    with open(os.path.join(bench, "traffic", traffic_name + ".json"), "w") as f:
        json.dump(traffic, f)
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        spec = json.load(f)
    if config["name"] not in {c["name"] for c in spec["configs"]}:
        spec["configs"].append({"name": config["name"], "source": "test",
                                "file": cfg_file, "reduced": [], "why": "test"})
    spec["workloads"].append({"name": cell, "config": config["name"],
                              "traffic": traffic_name, "chips": 1,
                              "why": "test"})
    for metric in spec["end_to_end"] + spec["per_layer"]:
        if "workloads" in metric:
            metric["workloads"].append(cell)
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(spec, f)


@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory):
    root = copy_benchmark(str(tmp_path_factory.mktemp("checkout")))
    for name, cfg in TINY_CONFIGS.items():
        add_cell(root, cfg, "tiny", TINY_TRAFFIC, f"{name}.tiny")
    add_cell(root, TINY_CONFIGS["tiny-dense"], "tinycount", TINY_COUNT,
             "tiny-dense.tinycount")
    return root
