"""The trace reduction on two short traces recorded on one TPU v5e while
the service ran a 33,067-node CSR target (n_t 33,067, w 1,034 words,
64 workers x 64 lanes; ``--trace-seconds`` slices, stored gzipped):
one while an engine pack ran (``csr_extend_bucketed`` calls), one while a
client's ``prepare`` ran its AC fixpoint (a ``csr_arc_sweep`` call)."""

from __future__ import annotations

import gzip
import os

import pytest

from sgebench import drive, roofline, spec, xplane

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "testdata")
KERNEL_SHAPES = {"w": 1034, "n_t": 33067, "n_planes": 2, "nnz_plane": 264_536}


def _load(name):
    from jax.profiler import ProfileData

    with open(os.path.join(DATA, name), "rb") as f:
        pd = ProfileData.from_serialized_xspace(gzip.decompress(f.read()))
    return xplane.reduce_profile(pd)


@pytest.fixture(scope="module")
def engine():
    return _load("pdbsv1_engine.xplane.pb.gz")


@pytest.fixture(scope="module")
def prepare():
    return _load("pdbsv1_prepare.xplane.pb.gz")


def _read(summary, metric):
    span = (max(c.start_ns + c.dur_ns for c in summary.calls)
            - min(c.start_ns for c in summary.calls)) / 1e9
    run = drive.Run(seconds=span, setup_s=0.0, t0=0.0, t1=span, records=[],
                    packs=[], device_kind="TPU v5 lite", trace=summary,
                    trace_window_s=span, kernel_shapes=KERNEL_SHAPES)
    return spec.load_metric({"name": metric, "unit": "%"}).read(run)


@pytest.mark.parametrize("name", ["engine", "prepare"])
def test_busy_time_and_breakdown(name, request):
    s = request.getfixturevalue(name)
    assert s.n_devices == 1 and s.busy_s > 0
    span = (max(c.start_ns + c.dur_ns for c in s.calls)
            - min(c.start_ns for c in s.calls)) / 1e9
    assert s.busy_s <= span
    bd = s.breakdown()
    assert 1 <= len(bd["device_ops"]) <= 10 and len(bd["idle_gaps"]) <= 10
    assert all("/" in op and sec > 0 for op, sec in bd["device_ops"])
    assert sum(sec for _, sec in bd["device_ops"]) <= s.busy_s * 1.0001
    labels = {"host", "prepare", "run_pack", "prepare+run_pack"}
    assert all(what in labels and sec >= 0 for what, sec in bd["idle_gaps"])
    assert 0 <= _read(s, "device_idle_share") < 100


def test_extend_calls_and_roofline(engine):
    calls = xplane.kernel_calls(engine, "csr_extend")
    assert len(calls) == 16
    assert {xplane.extend_shape(c) for c in calls} == {4096}
    assert {xplane.extend_parents(c, 4096) for c in calls} == {8}
    for c in calls:  # each call's least time is under its device time
        least = roofline.least_seconds(
            roofline.csr_extend_work(4096, 1034, 8, 264_536, 33067),
            "TPU v5 lite")
        assert least < c.dur_ns / 1e9
    assert 0 < _read(engine, "csr_extend_roofline") < 100
    assert _read(engine, "csr_arc_sweep_roofline") is None
    top = dict(engine.breakdown()["device_ops"])
    assert "jit__engine_loop/csr_extend_bucketed" in top


def test_arc_sweep_calls_and_roofline(prepare):
    calls = xplane.kernel_calls(prepare, "csr_arc_sweep")
    assert len(calls) == 1
    assert 1 <= xplane.sweep_arcs(calls[0], 33067) <= 256
    assert 0 < _read(prepare, "csr_arc_sweep_roofline") < 100
    assert _read(prepare, "csr_extend_roofline") is None


def test_op_names_and_shapes():
    assert xplane.op_name("%csr_extend_bucketed.8 = (u32[4096,1,1152]") == \
        "csr_extend_bucketed"
    assert xplane.op_name("%while.274 = (s32[2,64,1164]") == "while"
    assert xplane.shapes("s32[16,1,33280]{2,1,0:T(1,128)} f(u32[4])") == [
        ("s32", (16, 1, 33280)), ("u32", (4,))]
