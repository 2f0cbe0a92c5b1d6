"""The span recorder (`repro.trace`): off by default and free while off,
nesting, counts and request ids while on, a bounded buffer, compile spans,
and the spans the served path records at each layer boundary."""

import collections
import threading
import warnings

import jax
import numpy as np
import pytest

from repro import trace
from repro.core import EngineConfig, Enumerator, SubgraphIndex
from repro.serve import EnumerationService, ServiceConfig
from tests.conftest import extract_connected_pattern, random_graph


@pytest.fixture
def recorder():
    """The recorder switched on for one test, and off and empty after."""
    trace.drain()
    trace.enable()
    try:
        yield trace
    finally:
        trace.disable()
        trace.drain()


class _CountingAnnotation:
    entered = 0

    def __init__(self, name):
        self.name = name

    def __enter__(self):
        type(self).entered += 1
        return self

    def __exit__(self, *exc):
        return False


def test_off_records_nothing_and_enters_no_annotation(monkeypatch):
    assert not trace.enabled()
    trace.drain()
    _CountingAnnotation.entered = 0
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", _CountingAnnotation)
    a = trace.span("pack.device", occupied=4)
    b = trace.span("serve.wait")
    assert a is b is trace.NULL
    with a as sp:
        assert sp is None
    trace.record("serve.admission_wait", 0.0, 1.0, "q1")
    jax.jit(lambda x: x * 5)(np.arange(7.0))  # a compile while off
    assert trace.drain() == []
    assert _CountingAnnotation.entered == 0


def test_nested_spans_carry_parent_and_requests(recorder, monkeypatch):
    _CountingAnnotation.entered = 0
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", _CountingAnnotation)
    recorder.enable()  # binds the patched annotation
    with recorder.span("serve.execute"):
        with recorder.span("pack.device", occupied=2) as sp:
            sp.add(steps_max=8, steps_sum=12)
        with recorder.span("serve.deliver"):
            pass
    recorder.record("serve.admission_wait", 1.0, 2.5, "a")
    spans = {s.name: s for s in recorder.drain()}
    outer, inner = spans["serve.execute"], spans["pack.device"]
    assert outer.parent is None and outer.counts == {}
    assert inner.parent == outer.id and spans["serve.deliver"].parent == outer.id
    assert inner.counts == {"occupied": 2, "steps_max": 8, "steps_sum": 12}
    assert outer.t0 <= inner.t0 <= inner.t1 <= outer.t1
    assert outer.req is inner.req is None
    assert inner.thread == threading.get_ident()
    wait = spans["serve.admission_wait"]
    assert (wait.t0, wait.t1, wait.req, wait.parent, wait.thread) == (
        1.0, 2.5, "a", None, None)
    assert _CountingAnnotation.entered == 3


def test_spans_nest_per_thread(recorder):
    seen = {}

    def worker(k):
        with recorder.span("prepare.domains", worker=k):
            with recorder.span("prepare.plan", worker=k):
                pass

    threads = [threading.Thread(target=worker, args=(k,)) for k in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(30)
    assert not any(t.is_alive() for t in threads)
    for s in recorder.drain():
        seen.setdefault(s.counts["worker"], {})[s.name] = s
    assert len(seen) == 4
    for pair in seen.values():
        assert pair["prepare.plan"].parent == pair["prepare.domains"].id
        assert pair["prepare.domains"].parent is None


def test_buffer_is_bounded(recorder):
    n = recorder.CAPACITY + 2
    for k in range(n):
        recorder.record("serve.coalesce_wait", float(k), float(k) + 1, f"q{k}")
    kept = recorder.drain()
    assert len(kept) == recorder.CAPACITY
    assert [kept[0].req, kept[-1].req] == ["q2", f"q{n - 1}"]
    assert recorder.drain() == []


def test_fresh_jit_yields_one_compile_span(recorder):
    def scaled_by_eleven(x):
        return x * 11

    jax.jit(scaled_by_eleven)(np.arange(13.0))
    compiles = [s for s in recorder.drain() if s.name == "compile"]
    mine = [s for s in compiles
            if "scaled_by_eleven" in s.counts["fun_name"]]
    assert len(mine) == 1
    assert mine[0].t0 <= mine[0].t1


def _served(rng, n_queries=6):
    tgt = random_graph(rng, 40, 120, n_labels=2)
    index = SubgraphIndex.build(tgt)
    pats = []
    while len(pats) < n_queries:
        p = extract_connected_pattern(rng, tgt, int(rng.integers(3, 5)))
        if p.m > 0:
            pats.append(p)
    enum = Enumerator(index, config=EngineConfig(n_workers=4, expand_width=2))
    return enum, pats


def test_served_path_spans(recorder, rng):
    """Each completed query has one admission and one coalescer wait and
    one domains and one plan span; each pack its build, device and decode
    spans under one ``serve.execute``."""
    enum, pats = _served(rng)
    queries = []
    for k, p in enumerate(pats):
        q = enum.prepare(p, name=f"q{k}")
        prep = collections.Counter(s.name for s in recorder.drain())
        assert prep == {"prepare.domains": 1, "prepare.plan": 1}
        queries.append(q)
    svc = EnumerationService(
        enumerator=enum,
        service=ServiceConfig(max_lanes=2, batch_window_s=0.01))
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", RuntimeWarning)
        with svc:
            handles = [svc.submit(q, tenant=f"t{k % 2}", name=q.name)
                       for k, q in enumerate(queries)]
            done = [h.status(timeout=240.0) for h in handles]
    ok = {q.name for q, st in zip(queries, done) if st.ok}
    assert ok == {q.name for q in queries if q.plan.satisfiable}
    spans = recorder.drain()
    for name in ("serve.admission_wait", "serve.coalesce_wait"):
        per_query = collections.Counter(
            s.req for s in spans if s.name == name)
        assert per_query == {n: 1 for n in ok}, name
    by_id = {s.id: s for s in spans}
    executes = [s for s in spans if s.name == "serve.execute"]
    devices = [s for s in spans if s.name == "pack.device"]
    assert executes and sum(s.counts["occupied"] for s in devices) == len(ok)
    for ex in executes:
        kids = collections.Counter(s.name for s in spans if s.parent == ex.id)
        assert kids == {"pack.build": 1, "pack.device": 1, "pack.decode": 1,
                        "serve.deliver": 1}
    for s in spans:
        if s.name in ("pack.build", "pack.device", "pack.decode"):
            assert by_id[s.parent].name == "serve.execute"
        if s.name == "pack.device":
            c = s.counts
            assert 0 < c["steps_max"] <= c["steps_sum"] <= (
                c["steps_max"] * c["occupied"])
    waits = [s for s in spans if s.name == "serve.wait"]
    assert waits and all(s.parent is None for s in waits)
    # the operator's queue wait is submit -> the start of the pack
    stats = svc.stats()
    assert 0 <= stats["queue_wait_p50_s"] <= stats["queue_wait_p99_s"]
    assert "uptime_s" not in stats and "latency_mean_s" not in stats


def test_pack_build_counts_seed_bytes(recorder, rng):
    """Under vertex seeding a pack hands the engine one root bitmap per
    worker for each occupied lane: ``seed_bytes`` on ``pack.build`` is
    occupied lanes × V × w × 4, whatever the pack's width."""
    enum, pats = _served(rng, n_queries=1)
    q = enum.prepare(pats[0])
    assert q.plan.satisfiable
    recorder.drain()
    v, w = enum.config.n_workers, q.plan.w
    for occupied, width in ((1, 1), (1, 4), (3, 4)):
        enum.run_pack([q] * occupied, pack_size=width)
        spans = recorder.drain()
        (build,) = [s for s in spans if s.name == "pack.build"]
        (device,) = [s for s in spans if s.name == "pack.device"]
        assert device.counts["occupied"] == occupied
        assert build.counts["seed_bytes"] == occupied * v * w * 4
