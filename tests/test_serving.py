"""The always-on serving layer (DESIGN.md §7): coalescer/admission units,
streaming bit-identity, overflow retry under concurrency, quotas,
shutdown, and the mixed dense/CSR multi-client integration case (the CI
step sets SGE_SERVE_INTEGRATION=1 to include the big one)."""

import os
import threading
import warnings

import pytest

from repro.core import EngineConfig, Enumerator, Query, SubgraphIndex
from repro.core.plan import build_csr_plan
from repro.serve import (
    Backpressure,
    Coalescer,
    EnumerationService,
    QuotaExceeded,
    ServiceConfig,
    ServiceError,
)
from repro.serve.admission import AdmissionQueue, Request
from tests.conftest import extract_connected_pattern, random_graph

CFG = EngineConfig(n_workers=4, expand_width=2)


class FakeClock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t


def _corpus(rng, n_pats=6, n=40, m=120):
    tgt = random_graph(rng, n, m, n_labels=3)
    pats = []
    while len(pats) < n_pats:
        p = extract_connected_pattern(rng, tgt, int(rng.integers(2, 5)))
        if p.m > 0:
            pats.append(p)
    return tgt, pats


# ---------------------------------------------------------------------------
# Coalescer (pure unit, fake clock)
# ---------------------------------------------------------------------------

def test_coalescer_lane_budget_dispatches_immediately():
    clk = FakeClock()
    c = Coalescer(max_lanes=3, window_s=10.0, clock=clk)
    assert c.add("k", 1) is None
    assert c.add("k", 2) is None
    key, items = c.add("k", 3)  # budget filled: no window wait
    assert (key, items) == ("k", [1, 2, 3])
    assert c.pending() == 0 and c.ripe() == []


def test_coalescer_window_ripens_oldest_first():
    clk = FakeClock()
    c = Coalescer(max_lanes=8, window_s=1.0, clock=clk)
    c.add("a", 1)
    clk.t = 0.5
    c.add("b", 2)
    c.add("a", 3)          # does not reset bucket a's window
    assert c.ripe() == []
    assert c.next_deadline() == pytest.approx(1.0)  # bucket a's oldest + window
    clk.t = 1.0
    assert c.ripe() == [("a", [1, 3])]   # b not due yet
    clk.t = 1.5
    assert c.ripe() == [("b", [2])]
    assert c.next_deadline() is None


def test_coalescer_flush_and_fifo_order():
    c = Coalescer(max_lanes=8, window_s=1.0, clock=FakeClock())
    for i in range(3):
        c.add("x", i)
    c.add("y", 99)
    assert c.flush() == [("x", [0, 1, 2]), ("y", [99])]
    assert c.pending() == 0


# ---------------------------------------------------------------------------
# Admission queue (pure unit)
# ---------------------------------------------------------------------------

def _req(tenant="t"):
    return Request(query=None, tenant=tenant, stream=None, collect=0,
                   submitted_at=0.0)


def test_admission_quota_rejects_immediately():
    q = AdmissionQueue(max_depth=16, max_outstanding_per_tenant=2)
    q.admit(_req("a"))
    q.admit(_req("a"))
    with pytest.raises(QuotaExceeded):
        q.admit(_req("a"), timeout=5.0)  # quota never blocks, even w/ timeout
    q.admit(_req("b"))  # other tenants unaffected
    assert q.outstanding("a") == 2 and q.outstanding("b") == 1
    # quota frees on release (terminal status), not on pop (execution start)
    assert len(q.pop(timeout=0)) == 3
    assert q.outstanding("a") == 2
    q.release("a")
    q.admit(_req("a"))


def test_admission_backpressure_blocks_then_rejects():
    q = AdmissionQueue(max_depth=1, max_outstanding_per_tenant=8)
    q.admit(_req("a"))
    with pytest.raises(Backpressure):
        q.admit(_req("b"), timeout=None)  # no timeout = no blocking
    with pytest.raises(Backpressure):
        q.admit(_req("b"), timeout=0.01)
    # a concurrent pop() frees space and unblocks the waiter
    done = []

    def late_pop():
        q.pop(timeout=0)
        done.append(True)

    t = threading.Timer(0.05, late_pop)
    t.start()
    q.admit(_req("b"), timeout=5.0)
    t.join()
    assert done and q.depth() == 1


# ---------------------------------------------------------------------------
# Service: streaming identity + determinism
# ---------------------------------------------------------------------------

def test_stream_chunks_concatenate_bit_identically(rng):
    """Chunks arrive in deterministic seq order and concatenate to exactly
    the one-shot run's mappings; counts match too."""
    tgt, pats = _corpus(rng, n_pats=4)
    index = SubgraphIndex.build(tgt)
    ref = Enumerator(index, config=CFG)
    svc = EnumerationService(
        index, config=CFG,
        service=ServiceConfig(max_lanes=4, batch_window_s=0.001, chunk_size=3),
    )
    with svc:
        handles = [svc.submit(p, collect=64) for p in pats]
        for p, h in zip(pats, handles):
            chunks = h.chunks(timeout=120.0)
            ms = h.result()
            one = ref.run(ref.prepare(p), collect_matches=64)
            assert (ms.matches, ms.states) == (one.matches, one.states)
            assert [c.seq for c in chunks] == list(range(len(chunks)))
            assert all(len(c.mappings) <= 3 for c in chunks)
            if chunks:
                assert chunks[-1].final and not any(c.final for c in chunks[:-1])
            concat = [m for c in chunks for m in c.mappings]
            assert concat == one.mappings()
            assert h.status().n_chunks == len(chunks)
    # a second identical service run streams the identical chunk sequence
    svc2 = EnumerationService(
        index, config=CFG,
        service=ServiceConfig(max_lanes=4, batch_window_s=0.001, chunk_size=3),
    )
    with svc2:
        h2 = svc2.submit(pats[0], collect=64)
        assert h2.chunks(timeout=120.0) == handles[0].chunks()


def test_counting_mode_streams_no_chunks(rng):
    tgt, pats = _corpus(rng, n_pats=2)
    index = SubgraphIndex.build(tgt)
    ref = Enumerator(index, config=CFG)
    with EnumerationService(index, config=CFG) as svc:
        h = svc.submit(pats[0], collect=0)
        assert h.chunks(timeout=120.0) == []
        assert h.result().matches == ref.run(ref.prepare(pats[0])).matches


def test_concurrent_clients_match_standalone_runs(rng):
    """Many client threads, coalesced packs: every streamed result equals
    a standalone run; metrics add up."""
    tgt, pats = _corpus(rng, n_pats=8)
    index = SubgraphIndex.build(tgt)
    ref = Enumerator(index, config=CFG)
    expected = [ref.run(ref.prepare(p)) for p in pats]
    svc = EnumerationService(
        index, config=CFG,
        service=ServiceConfig(max_lanes=4, batch_window_s=0.005),
    )
    results = [None] * len(pats)
    errors = []

    def client(i):
        try:
            h = svc.submit(pats[i], tenant=f"t{i % 3}", collect=0, timeout=30.0)
            results[i] = h.result(timeout=120.0)
        except BaseException as e:
            errors.append(e)

    with svc:
        threads = [threading.Thread(target=client, args=(i,)) for i in range(len(pats))]
        for t in threads:
            t.start()
        for t in threads:
            t.join(120.0)
    assert not errors, errors
    for ms, exp in zip(results, expected):
        assert (ms.matches, ms.states) == (exp.matches, exp.states)
    stats = svc.stats()
    assert stats["completed"] == len(pats)
    assert stats["dispatches"] >= 1
    assert 0 < stats["batch_occupancy"] <= 1
    assert stats["latency_p99_s"] >= stats["latency_p50_s"] > 0
    assert stats["cache_compiles"] >= 1 and stats["cache_hit_rate"] >= 0


def test_unsatisfiable_short_circuits(rng):
    from tests.conftest import bump_edge_label

    tgt, pats = _corpus(rng, n_pats=1)
    index = SubgraphIndex.build(tgt)
    bad = bump_edge_label(pats[0], 0, 9)
    svc = EnumerationService(index, config=CFG)  # not even started
    h = svc.submit(bad, collect=8)
    assert h.done  # answered at submit time, no queue slot, no engine
    assert h.result().matches == 0 and h.chunks() == []
    assert svc.stats()["unsat"] == 1
    assert svc.enumerator.cache_stats()["compiles"] == 0
    svc.stop()


# ---------------------------------------------------------------------------
# Service: overflow retry under concurrency
# ---------------------------------------------------------------------------

def test_overflow_retry_reported_with_concurrent_inflight(rng):
    """Several in-flight queries whose stacks overflow: each rides the
    PR-4 doubled-stack_cap retry, reports retries=1 in its terminal
    status, and still counts exactly like a roomy run."""
    tgt = random_graph(rng, 40, 120, n_labels=2)
    index = SubgraphIndex.build(tgt)
    bigs = [extract_connected_pattern(rng, tgt, 6) for _ in range(3)]
    small = extract_connected_pattern(rng, tgt, 3)
    roomy = Enumerator(index, n_workers=2, expand_width=2)
    expected = {i: roomy.run(roomy.prepare(p)) for i, p in enumerate(bigs + [small])}

    tight_cfg = EngineConfig(n_workers=2, expand_width=2, stack_cap=8)
    svc = EnumerationService(
        index, config=tight_cfg,
        service=ServiceConfig(max_lanes=4, batch_window_s=0.001),
    )
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", RuntimeWarning)  # the retry warns
        with svc:
            handles = [svc.submit(p, tenant=f"t{i}", collect=0, timeout=30.0)
                       for i, p in enumerate(bigs + [small])]
            statuses = [h.status(timeout=240.0) for h in handles]
    for i, st in enumerate(statuses):
        assert st.ok
        assert st.matchset.matches == expected[i].matches
        assert st.retries == st.matchset.retries
    assert [st.retries for st in statuses[:3]] == [1, 1, 1], (
        "every overflowed in-flight query must report its retry"
    )
    assert statuses[3].retries == 0, "the small query must not report a retry"
    assert svc.stats()["retries"] == 3


# ---------------------------------------------------------------------------
# Service: quotas, backpressure, shutdown
# ---------------------------------------------------------------------------

def test_service_quota_and_backpressure(rng):
    tgt, pats = _corpus(rng, n_pats=1)
    index = SubgraphIndex.build(tgt)
    svc = EnumerationService(
        index, config=CFG,
        service=ServiceConfig(max_queue_depth=3, max_outstanding_per_tenant=2),
    )
    # dispatcher not started: submissions stay queued deterministically
    svc.submit(pats[0], tenant="a")
    svc.submit(pats[0], tenant="a")
    with pytest.raises(QuotaExceeded):
        svc.submit(pats[0], tenant="a")
    svc.submit(pats[0], tenant="b")          # queue now full (depth 3)
    with pytest.raises(Backpressure):
        svc.submit(pats[0], tenant="c", timeout=0.01)
    stats = svc.stats()
    assert stats["rejected_quota"] == 1 and stats["rejected_backpressure"] == 1
    assert stats["queue_depth"] == 3
    # draining stop executes what was admitted
    svc.stop(drain=True)
    assert svc.stats()["completed"] == 3


def test_service_stop_without_drain_fails_pending(rng):
    tgt, pats = _corpus(rng, n_pats=1)
    index = SubgraphIndex.build(tgt)
    svc = EnumerationService(index, config=CFG)
    h = svc.submit(pats[0])
    svc.stop(drain=False)
    st = h.status(timeout=10.0)
    assert not st.ok and "stopped" in st.error
    with pytest.raises(ServiceError):
        h.result()
    # the tenant's quota slot was released with the failure
    assert svc.admission.outstanding("default") == 0


def test_queue_wait_counts_the_coalescer_hold(rng):
    """The operator's queue wait runs from submit to the start of the pack
    that carried the query, so a lone query's wait includes the batch
    window it was held for, not only the admission queue."""
    tgt, pats = _corpus(rng, n_pats=1)
    index = SubgraphIndex.build(tgt)
    window = 0.3
    svc = EnumerationService(
        index, config=CFG,
        service=ServiceConfig(max_lanes=4, batch_window_s=window))
    with svc:
        svc.submit(pats[0]).result(timeout=240.0)
    stats = svc.stats()
    assert stats["queue_wait_p50_s"] >= window
    assert "wait_p50=" in metrics_mod.format_snapshot(stats)


# ---------------------------------------------------------------------------
# Integration: N clients, mixed dense/CSR targets (own CI step)
# ---------------------------------------------------------------------------

@pytest.mark.skipif(
    not os.environ.get("SGE_SERVE_INTEGRATION"),
    reason="serving integration case runs in its own CI step "
    "(SGE_SERVE_INTEGRATION=1)",
)
def test_integration_mixed_dense_csr_clients(rng):
    """One service, step_backend='auto', 12 client threads with dense AND
    CSR-only queries in flight at once: the coalescer must keep the
    buckets apart (compile count == bucket count) while every client's
    streamed result stays bit-identical to a standalone run."""
    dense_tgt, dense_pats = _corpus(rng, n_pats=8, n=50, m=160)
    sparse_tgt = random_graph(rng, 200, 420, n_labels=3)
    cfg = EngineConfig(n_workers=4, expand_width=2, step_backend="auto")
    index = SubgraphIndex.build(dense_tgt)

    queries = []
    for i in range(12):
        if i % 3 == 2:
            pat = extract_connected_pattern(rng, sparse_tgt, 3)
            queries.append(Query(pattern=pat, plan=build_csr_plan(pat, sparse_tgt),
                                 variant="ri", name=f"csr{i}", prepare_s=0.0))
        else:
            queries.append(None)  # dense: prepared by the service from the raw pattern

    svc = EnumerationService(
        index, config=cfg,
        service=ServiceConfig(max_lanes=4, batch_window_s=0.005),
    )
    ref = Enumerator(config=cfg)
    results = [None] * len(queries)
    errors = []

    def client(i):
        try:
            q = queries[i] if queries[i] is not None else dense_pats[i % len(dense_pats)]
            h = svc.submit(q, tenant=f"t{i % 4}", collect=16, timeout=30.0)
            results[i] = (h.result(timeout=240.0), h.mappings())
        except BaseException as e:
            errors.append(e)

    with svc:
        threads = [threading.Thread(target=client, args=(i,)) for i in range(len(queries))]
        for t in threads:
            t.start()
        for t in threads:
            t.join(240.0)
    assert not errors, errors

    prep = Enumerator(index, config=cfg)
    n_buckets = len({
        prep.coalesce_key(q if q is not None else prep.prepare(dense_pats[i % len(dense_pats)]))
        for i, q in enumerate(queries)
    })
    assert svc.enumerator.cache_stats()["compiles"] == n_buckets
    assert n_buckets >= 2, "dense and csr queries must occupy distinct buckets"
    for i, (ms, maps) in enumerate(results):
        q = queries[i] if queries[i] is not None else prep.prepare(dense_pats[i % len(dense_pats)])
        one = ref.run(q, collect_matches=16)
        assert (ms.matches, ms.states) == (one.matches, one.states)
        assert maps == one.mappings()
    stats = svc.stats()
    assert stats["completed"] == len(queries)
    assert stats["dispatches"] >= n_buckets


# ---------------------------------------------------------------------------
# metrics: sliding windows (direct unit tests)
# ---------------------------------------------------------------------------

from repro.serve import metrics as metrics_mod  # noqa: E402


def test_latency_window_empty_and_single():
    """Empty windows report 0.0 everywhere (no NaNs, no exceptions); one
    observation is every percentile."""
    w = metrics_mod.LatencyWindow(cap=8)
    assert len(w) == 0
    assert w.percentile(50) == 0.0
    assert w.percentile(99) == 0.0
    assert w.max() == 0.0
    w.record(0.25)
    for p in (0, 50, 99, 100):
        assert w.percentile(p) == 0.25
    assert w.max() == 0.25


def test_latency_window_nearest_rank_exact():
    """Nearest-rank percentiles on a known population, unsorted insertion
    order."""
    w = metrics_mod.LatencyWindow(cap=16)
    for v_ in (5.0, 1.0, 3.0, 2.0, 4.0):  # sorted: [1..5]
        w.record(v_)
    assert w.percentile(50) == 2.0   # round(0.5*5)=2 -> index 1
    assert w.percentile(90) == 4.0   # round(4.5)=4  -> index 3
    assert w.percentile(99) == 5.0
    assert w.percentile(0) == 1.0
    assert w.percentile(100) == 5.0


def test_latency_window_wraparound_keeps_most_recent():
    """Past cap, old observations fall out: percentiles cover exactly the
    last cap records."""
    w = metrics_mod.LatencyWindow(cap=100)
    for v_ in range(250):
        w.record(float(v_))
    assert len(w) == 100            # retained: [150.0 .. 249.0]
    assert w.max() == 249.0
    assert w.percentile(0) == 150.0    # the oldest retained
    assert w.percentile(50) == 199.0   # rank round(50)=50 -> index 49
    assert w.percentile(99) == 248.0   # rank round(99)=99 -> index 98
    assert w.percentile(100) == 249.0


def test_service_metrics_concurrent_record():
    """Counters and windows stay consistent under concurrent observers
    (client threads + dispatcher thread in the real service)."""
    m = metrics_mod.ServiceMetrics(window=4096)
    n_threads, per_thread = 8, 400

    def observer(tid):
        for i in range(per_thread):
            m.observe_queue_wait(0.001 * tid)
            m.observe_completion(1.0, retries=(i % 2), ok=(i % 10 != 0))
            m.inc("submitted")

    threads = [threading.Thread(target=observer, args=(t,))
               for t in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()

    total = n_threads * per_thread
    snap = m.snapshot()
    assert snap["submitted"] == total
    assert snap["completed"] + snap["failed"] == total
    assert snap["failed"] == n_threads * (per_thread // 10)
    assert snap["retries"] == n_threads * (per_thread // 2)
    assert snap["latency_p50_s"] == 1.0 and snap["latency_max_s"] == 1.0
    assert snap["qps"] >= 0.0


def test_snapshot_schema_stable_and_formats():
    """Every COUNTERS name appears in the snapshot (zeros included) and
    format_snapshot renders without KeyError."""
    m = metrics_mod.ServiceMetrics()
    snap = m.snapshot()
    for name in metrics_mod.COUNTERS:
        assert name in snap
    assert "warmup_compiles" in snap
    assert isinstance(metrics_mod.format_snapshot(snap), str)


# ---------------------------------------------------------------------------
# warmup profile: compiles move to start(), first submits are cache hits
# ---------------------------------------------------------------------------

def test_warmup_profile_precompiles_dispatch_engines(rng):
    """ServiceConfig.warmup_profile pre-traces the pack engines during
    start(); the first real submits then compile nothing new."""
    tgt, pats = _corpus(rng, n_pats=3)
    index = SubgraphIndex.build(tgt)
    svc = EnumerationService(
        index, config=CFG,
        service=ServiceConfig(max_lanes=4, batch_window_s=0.001,
                              warmup_profile=tuple(pats)),
    )
    with svc:
        warm_spent = svc.stats()["warmup_compiles"]
        assert warm_spent >= 1
        compiles = svc.enumerator.cache_stats()["compiles"]
        handles = [svc.submit(p) for p in pats]
        for h in handles:
            assert h.result(timeout=240.0).states >= 0
        assert svc.enumerator.cache_stats()["compiles"] == compiles
        assert svc.stats()["warmup_compiles"] == warm_spent
    # start() is idempotent: re-entering does not re-warm
    with svc:
        assert svc.stats()["warmup_compiles"] == warm_spent
