"""The harness on the CPU: cells resolve to their files, new cells come
from files and entries alone, traffic follows the seed, the result line
keeps the contract, the run refuses a machine without a TPU, and faults
planted under the timed path make ``correct`` false."""

from __future__ import annotations

import dataclasses
import hashlib
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from sgebench import gen, run, spec
from sgebench.conftest import (ROOT, TINY_CONFIGS, TINY_TRAFFIC, add_cell,
                               copy_benchmark)

CELLS = ["tiny-dense.tiny", "tiny-csr.tiny", "tiny-dense.tinycount"]


def _bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


@pytest.mark.parametrize("cell", [w["name"] for w in _bench()["workloads"]])
def test_every_cell_resolves(cell):
    c = spec.load_cell(ROOT, cell)
    assert c.chips in (1, 4)
    assert c.config["target"]["n"] > 0 and c.traffic["clients"] > 0
    names = {m.name for m in c.end_to_end}
    assert "setup_s" in names and len(names) >= 2 and c.per_layer
    assert all(callable(m.read) for m in c.end_to_end + c.per_layer)


def _digest(root):
    out = {}
    for d, _, files in os.walk(root):
        for f in files:
            p = os.path.join(d, f)
            with open(p, "rb") as fh:
                out[os.path.relpath(p, root)] = hashlib.sha256(fh.read()).hexdigest()
    return out


def test_cell_added_by_new_files_and_entries(tmp_path):
    root = copy_benchmark(str(tmp_path / "checkout"))
    before = _digest(os.path.join(root, "sgebench"))
    add_cell(root, TINY_CONFIGS["tiny-dense"], "tiny", TINY_TRAFFIC,
             "tiny-dense.tiny")
    with open(os.path.join(root, "sgebench", "metrics", "pack_count.py"), "w") as f:
        f.write("def read(run):\n    return float(len(run.window_packs()))\n")
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["per_layer"].append({
        "name": "pack_count", "unit": "packs", "better": "higher",
        "source": "program_counter", "layer": "engine",
        "moves": "queries_per_s", "workloads": ["tiny-dense.tiny"]})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    after = _digest(os.path.join(root, "sgebench"))
    assert all(after[k] == v for k, v in before.items())  # nothing edited
    cell = spec.load_cell(root, "tiny-dense.tiny",
                          bench_dir=os.path.join(root, "sgebench"))
    assert cell.config["name"] == "tiny-dense"
    assert cell.traffic == TINY_TRAFFIC
    assert "pack_count" in [m.name for m in cell.per_layer]


def _key(p):
    return (p.n, p.src.tobytes(), p.dst.tobytes(), p.labels.tobytes())


@pytest.mark.parametrize("seed", [0, 2**31 + 17])
def test_traffic_follows_the_seed_and_never_repeats(seed):
    cfg = TINY_CONFIGS["tiny-dense"]

    def draw(s):
        rng = np.random.default_rng(s)
        g = gen.target(cfg["target"], rng)
        return g, gen.patterns(g, TINY_TRAFFIC, rng)

    g1, (q1, w1) = draw(seed)
    g2, (q2, w2) = draw(seed)
    assert np.array_equal(g1.src, g2.src) and np.array_equal(g1.labels, g2.labels)
    assert [[_key(p) for p in q] for q in q1] == [[_key(p) for p in q] for q in q2]
    sent = [_key(p) for q in q1 for p in q]
    assert len(sent) == len(set(sent))
    assert not set(sent) & {_key(p) for p in w1}
    sizes = [p.m >= 4 for q in q1 for p in q]
    assert all(sizes)
    shapes = {(p.n, p.m) for q in q1 for p in q}
    assert shapes == {(p.n, p.m) for p in w1}
    _, (q3, _) = draw(seed + 1)
    assert [_key(p) for p in q3[0]] != [_key(p) for p in q1[0]]


def _cli(args, cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.update(env_extra or {})
    return subprocess.run([sys.executable, "sgebench/run.py"] + args, cwd=cwd,
                          env=env, capture_output=True, text=True, timeout=300)


def test_no_tpu_no_result():
    r = _cli(["--workload", "human-dense.count", "--seed", "1",
              "--seconds", "1", "--trace", "0"], cwd=ROOT)
    assert r.returncode != 0
    assert r.stdout.strip() == ""
    assert "not a TPU" in r.stderr


def test_benchmark_alone_no_result(tmp_path):
    root = copy_benchmark(str(tmp_path / "alone"))
    r = _cli(["--workload", "hprd-csr.mixed", "--seed", "3",
              "--seconds", "1", "--trace", "0"], cwd=root,
              env_extra={"PYTHONPATH": ""})
    assert r.returncode != 0
    assert r.stdout.strip() == ""
    with pytest.raises(SystemExit):
        run.import_program(root)


def _run(root, cell, seed=11, seconds=1.5, after_pack=None):
    import jax

    c = spec.load_cell(root, cell, bench_dir=os.path.join(root, "sgebench"))
    return run.run_cell(c, seed, seconds, False, jax.devices()[0],
                        after_pack=after_pack)


@pytest.mark.parametrize("cell", CELLS)
def test_sound_run_is_correct_and_keeps_the_contract(tiny_root, cell):
    res = _run(tiny_root, cell)
    assert res["correct"] is True, res["checks"]
    keys = list(res)
    assert {"correct", "attempted", "failed", "metrics", "device"} <= set(keys)
    assert keys[-1] == "checks"
    assert set(res["device"]) >= {"platform", "kind", "count",
                                  "memory_peak_bytes"}
    assert res["attempted"] >= 1 and res["failed"] == 0
    c = spec.load_cell(tiny_root, cell,
                       bench_dir=os.path.join(tiny_root, "sgebench"))
    assert set(res["metrics"]) == {m.name for m in c.end_to_end}
    assert {"queries_per_s", "setup_s"} <= set(res["metrics"])
    for m in res["metrics"].values():
        assert set(m) == {"value", "unit"} and m["value"] > 0
    assert res["latency_samples"] >= 1
    assert res["engine_compiles_in_window"] == 0
    assert res["checks"]["checked"]["value"] >= 1
    json.dumps(res)


def _alter_one_mapping(queries, out):
    for ms in out:
        maps = ms.mappings()
        if maps:
            first = maps[0]
            maps[0] = (first[0] + 1,) + tuple(first[1:])
            break
    return out


def _alter_one_count(queries, out):
    out[0] = dataclasses.replace(out[0], matches=out[0].matches + 1)
    return out


def _empty(ms):
    return dataclasses.replace(ms, matches=0, _mappings=[], _match_buf=None,
                               per_worker_matches=None)


def _leave_out_half(queries, out):
    half = (len(out) + 1) // 2
    return out[:len(out) - half] + [_empty(ms) for ms in out[len(out) - half:]]


def _state_unchanged(queries, out):
    return [_empty(ms) for ms in out]


FAULTS = {"answer_altered": _alter_one_mapping,
          "count_altered": _alter_one_count,
          "half_left_out": _leave_out_half,
          "state_unchanged": _state_unchanged}
# a counting mix streams no mapping to alter
FAULT_CASES = [("tiny-dense.tiny", f) for f in FAULTS] + [
    ("tiny-dense.tinycount", f) for f in FAULTS if f != "answer_altered"]


@pytest.mark.parametrize("cell,fault", FAULT_CASES,
                         ids=[f"{c}-{f}" for c, f in FAULT_CASES])
def test_fault_under_the_timed_path_is_not_correct(tiny_root, cell, fault):
    res = _run(tiny_root, cell, seed=21, after_pack=FAULTS[fault])
    assert res["correct"] is False
    c = res["checks"]
    assert c["wrong_counts"]["value"] + c["wrong_mappings"]["value"] >= 1


def test_dropped_chunk_is_not_correct(tiny_root, monkeypatch):
    from repro.serve.stream import ResultStream

    push = ResultStream._push_chunk

    def drop_final(self, chunk):
        if not chunk.final:
            push(self, chunk)

    monkeypatch.setattr(ResultStream, "_push_chunk", drop_final)
    res = _run(tiny_root, "tiny-dense.tiny", seed=22)
    assert res["correct"] is False
    assert res["checks"]["wrong_mappings"]["value"] >= 1


@pytest.mark.parametrize("breaks", ["one-to-one", "last-edges"])
@pytest.mark.parametrize("cell", CELLS)
def test_control_is_not_correct(tiny_root, cell, breaks):
    from sgebench import control

    c = spec.load_cell(tiny_root, cell,
                       bench_dir=os.path.join(tiny_root, "sgebench"))
    out = control.control_numbers(c, seed=5, queries=24, breaks=breaks)
    assert out["queries"] == 24
    assert out["correct"] is False
    assert out["checks"]["wrong_counts"]["value"] >= 1


def test_traced_run_reports_span_metrics(tiny_root):
    import jax

    c = spec.load_cell(tiny_root, "tiny-dense.tiny",
                       bench_dir=os.path.join(tiny_root, "sgebench"))
    res = run.run_cell(c, 31, 1.5, True, jax.devices()[0], trace_seconds=0.5)
    assert res["correct"] is True
    assert {"queue_wait_p95_s", "lane_occupancy", "prepare_s_mean",
            "dispatch_s_mean"} <= set(res["metrics"])
    assert "device_idle_share" not in res["metrics"]  # no TPU plane here
    assert 0 < res["metrics"]["lane_occupancy"]["value"] <= 100
    assert 0.4 < res["device"]["window_s"] < 1.5
    assert set(res["breakdown"]) == {"device_ops", "idle_gaps"}
