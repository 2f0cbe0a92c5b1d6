"""Prepared-query session API: compile-cache behaviour, run/run_batch/stream
agreement with the sequential oracle, and wrapper-vs-session equivalence."""

import functools
import pickle

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    EngineConfig,
    Enumerator,
    SubgraphIndex,
    enumerate_subgraphs,
    prepare_query,
    snap_p_pad,
)
from repro.core import engine as eng
from repro.core import frontier
from repro.core.graph import Graph, PackedGraph, bitmap_from_indices
from repro.core.multi import enumerate_many
from repro.core.plan import build_plan
from repro.core.ref import ref_enumerate
from tests.conftest import extract_connected_pattern, power_law_target, random_graph

CFG = EngineConfig(n_workers=4, expand_width=2)


def _corpus(rng, n_pats=5):
    tgt = random_graph(rng, 40, 120, n_labels=3)
    pats = []
    while len(pats) < n_pats:
        p = extract_connected_pattern(rng, tgt, int(rng.integers(2, 5)))
        if p.m > 0:
            pats.append(p)
    return tgt, pats


def test_snap_p_pad_buckets():
    assert snap_p_pad(1) == 16
    assert snap_p_pad(16) == 16
    assert snap_p_pad(17) == 32
    assert snap_p_pad(33) == 64
    assert snap_p_pad(128) == 128
    assert snap_p_pad(129) == 256  # escape hatch beyond the last bucket


def test_compile_cache_hits_same_bucket(rng):
    """N same-bucket patterns through one session -> exactly one compile."""
    tgt, pats = _corpus(rng, n_pats=6)
    session = Enumerator(SubgraphIndex.build(tgt), config=CFG)
    for i, p in enumerate(pats):
        session.run(session.prepare(p, name=f"q{i}"))
    info = session.cache_info()
    assert info["compiles"] == 1, info
    assert info["cache_hits"] == len(pats) - 1, info


def test_run_matches_oracle(rng):
    tgt, pats = _corpus(rng)
    session = Enumerator(SubgraphIndex.build(tgt), config=CFG)
    for p in pats:
        ms = session.run(session.prepare(p))
        ref = ref_enumerate(p, tgt, variant="ri-ds-si-fc")
        assert (ms.matches, ms.states) == (ref.matches, ref.states)
        assert ms.matches >= 1  # extracted patterns always occur


def test_run_batch_and_stream_agree_with_run(rng):
    tgt, pats = _corpus(rng, n_pats=7)
    session = Enumerator(SubgraphIndex.build(tgt), config=CFG)
    queries = [session.prepare(p, name=f"q{i}") for i, p in enumerate(pats)]
    singles = [session.run(q) for q in queries]

    batch = session.run_batch(queries, pack_size=3)
    assert len(batch) == len(queries)
    assert [ms.query_index for ms in batch] == list(range(len(queries)))
    assert [ms.name for ms in batch] == [q.name for q in queries]
    for s, b in zip(singles, batch):
        assert (s.matches, s.states) == (b.matches, b.states)

    streamed = {ms.query_index: ms for ms in session.stream(queries, pack_size=3)}
    assert sorted(streamed) == list(range(len(queries)))
    for i, s in enumerate(singles):
        assert (streamed[i].matches, streamed[i].states) == (s.matches, s.states)


def test_run_batch_keeps_unsatisfiable_aligned(rng):
    """The old enumerate_many dropped queries; the session must return one
    result per query, in order, including unsatisfiable ones."""
    tgt, pats = _corpus(rng, n_pats=3)
    # a pattern whose label does not exist in the target: unsatisfiable
    bad = Graph.from_edges(2, [(0, 1)], labels=[99, 0], undirected=True)
    mixed = [pats[0], bad, pats[1], bad, pats[2]]
    session = Enumerator(SubgraphIndex.build(tgt), config=CFG)
    results = session.run_batch([session.prepare(p, name=f"m{i}")
                                 for i, p in enumerate(mixed)], pack_size=2)
    assert len(results) == len(mixed)
    assert [r.name for r in results] == [f"m{i}" for i in range(len(mixed))]
    assert results[1].matches == results[3].matches == 0
    assert results[0].matches >= 1

    # ... and the compat wrapper inherits the fix with its old signature.
    qrs = enumerate_many(mixed, tgt, cfg=CFG, pack_size=2,
                         names=[f"m{i}" for i in range(len(mixed))])
    assert [r.name for r in qrs] == [f"m{i}" for i in range(len(mixed))]
    assert [r.matches for r in qrs] == [r.matches for r in results]


@pytest.mark.parametrize("variant", ["ri", "ri-ds", "ri-ds-si", "ri-ds-si-fc"])
def test_wrapper_equals_session_all_variants(rng, variant):
    tgt, pats = _corpus(rng, n_pats=2)
    session = Enumerator(SubgraphIndex.build(tgt), config=CFG, variant=variant)
    for p in pats:
        ms = session.run(session.prepare(p))
        res = enumerate_subgraphs(p, tgt, variant=variant, config=CFG)
        assert (res.matches, res.states) == (ms.matches, ms.states)


def test_matchset_lazy_mappings(rng):
    tgt, pats = _corpus(rng, n_pats=1)
    session = Enumerator(SubgraphIndex.build(tgt), config=CFG)
    ms = session.run(session.prepare(pats[0]))
    assert ms._match_buf is None  # counting mode: nothing materialized yet
    maps = ms.mappings()
    assert len(maps) == ms.matches
    for m in maps:
        assert len(set(m)) == len(m)  # injective
    assert ms.mappings() is maps  # cached


def test_prepare_batch_matches_per_query_prepare(rng):
    """Batched device domain preprocessing (one vmapped fixpoint call per
    shape bucket) must produce plans identical to per-query numpy prepare,
    and key its jitted fixpoints into the session compile cache."""
    tgt, pats = _corpus(rng, n_pats=8)
    index = SubgraphIndex.build(tgt)
    dev = Enumerator(index, config=CFG)  # domain_backend='device' default
    host = Enumerator(index, config=CFG, domain_backend="numpy")

    qs_dev = dev.prepare_batch(pats, names=[f"q{i}" for i in range(len(pats))])
    qs_host = [host.prepare(p) for p in pats]
    assert [q.name for q in qs_dev] == [f"q{i}" for i in range(len(pats))]
    for a, b in zip(qs_dev, qs_host):
        np.testing.assert_array_equal(a.plan.dom_bits, b.plan.dom_bits)
        assert a.plan.satisfiable == b.plan.satisfiable
        assert a.plan.order.tolist() == b.plan.order.tolist()
    # domain fixpoints live in the same compile cache ('domains' entries)
    info = dev.cache_info()
    assert info["compiles"] >= 1
    # a second same-bucket batch is all cache hits, no new compiles
    before = dev.cache_info()["compiles"]
    dev.prepare_batch(pats)
    assert dev.cache_info()["compiles"] == before

    # raw Graphs through run_batch route through prepare_batch and agree
    res_dev = dev.run_batch(pats, pack_size=3)
    res_host = host.run_batch(qs_host, pack_size=3)
    assert [(m.matches, m.states) for m in res_dev] == [
        (m.matches, m.states) for m in res_host
    ]


def test_prepare_batch_selfloops_and_unsat(rng):
    """Self-loop patterns and unsatisfiable (overflow-label) patterns keep
    their order and results through the batched path."""
    tgt = random_graph(rng, 20, 50, n_labels=2, selfloops=3)
    index = SubgraphIndex.build(tgt)
    session = Enumerator(index, config=CFG)
    good = extract_connected_pattern(rng, tgt, 3)
    if good.m == 0:
        pytest.skip("empty pattern")
    from tests.conftest import bump_edge_label

    bad = bump_edge_label(good, 0, 9)  # label overflow: unsatisfiable
    results = session.run_batch([good, bad, good], pack_size=2)
    assert results[0].matches == results[2].matches >= 1
    assert results[1].matches == 0


def test_index_picklable_and_reusable(rng):
    tgt, pats = _corpus(rng, n_pats=1)
    index = SubgraphIndex.build(tgt)
    index2 = pickle.loads(pickle.dumps(index))
    np.testing.assert_array_equal(index.packed.adj_bits, index2.packed.adj_bits)
    a = Enumerator(index, config=CFG)
    b = Enumerator(index2, config=CFG)
    pa, pb = a.prepare(pats[0]), b.prepare(pats[0])
    assert (a.run(pa).matches, a.run(pa).states) == (b.run(pb).matches, b.run(pb).states)


def test_cache_lru_eviction_bounded(rng):
    """A bounded session must cap its engine cache: LRU entries evict,
    the evictions counter records them, and evicted engines recompile
    correctly on reuse (counts unchanged)."""
    tgt_a = random_graph(rng, 40, 120, n_labels=2)
    tgt_b = random_graph(rng, 30, 80, n_labels=2)  # different n_t: own bucket
    pa = extract_connected_pattern(rng, tgt_a, 3)
    pb = extract_connected_pattern(rng, tgt_b, 3)
    s = Enumerator(config=CFG, max_cache_entries=1)
    qa = prepare_query(pa, tgt_a)
    qb = prepare_query(pb, tgt_b)
    first = s.run(qa)
    assert s.cache_stats() == {"compiles": 1, "cache_hits": 0, "evictions": 0,
                               "entries": 1, "max_entries": 1}
    s.run(qb)  # second bucket evicts the first engine
    assert s.cache_stats()["evictions"] == 1
    assert s.cache_stats()["entries"] == 1
    again = s.run(qa)  # evicted: recompiles, same result
    stats = s.cache_stats()
    assert stats["compiles"] == 3 and stats["cache_hits"] == 0
    assert stats["evictions"] == 2 and stats["entries"] == 1
    assert (again.matches, again.states) == (first.matches, first.states)


def test_cache_lru_hit_refreshes_recency(rng):
    """A cache hit must move the entry to most-recent: with capacity 2,
    touching A before inserting C evicts B, not A."""
    tgts = [random_graph(rng, 30 + 10 * i, 80 + 20 * i, n_labels=2)
            for i in range(3)]
    qs = [prepare_query(extract_connected_pattern(rng, t, 3), t) for t in tgts]
    s = Enumerator(config=CFG, max_cache_entries=2)
    s.run(qs[0])           # cache: [A]
    s.run(qs[1])           # cache: [A, B]
    s.run(qs[0])           # hit refreshes A -> cache: [B, A]
    s.run(qs[2])           # evicts B      -> cache: [A, C]
    compiles_before = s.cache_stats()["compiles"]
    s.run(qs[0])           # must still be a hit
    stats = s.cache_stats()
    assert stats["compiles"] == compiles_before == 3
    assert stats["cache_hits"] == 2 and stats["evictions"] == 1


def test_cache_unbounded_by_default(rng):
    s = Enumerator(config=CFG)
    assert s.max_cache_entries == 0
    assert s.cache_stats()["max_entries"] == 0
    with pytest.raises(ValueError, match="max_cache_entries"):
        Enumerator(config=CFG, max_cache_entries=-1)


def test_run_pack_hook_matches_run(rng):
    """The serving layer's batch-submission hook: one padded pack, results
    in input order, identical to per-query run(); mixed coalesce keys are
    refused."""
    tgt, pats = _corpus(rng, n_pats=5)
    index = SubgraphIndex.build(tgt)
    s = Enumerator(index, config=CFG)
    qs = [s.prepare(p, name=f"q{i}") for i, p in enumerate(pats)]
    singles = [s.run(q) for q in qs]
    packed = s.run_pack(qs, pack_size=4)
    assert [ms.query_index for ms in packed] == list(range(len(qs)))
    for one, ms in zip(singles, packed):
        assert (one.matches, one.states) == (ms.matches, ms.states)

    other = random_graph(rng, 25, 60, n_labels=3)
    qo = prepare_query(extract_connected_pattern(rng, other, 3), other)
    with pytest.raises(ValueError, match="coalesce_key"):
        s.run_pack([qs[0], qo])

    # unsatisfiable lanes come back empty, order preserved, engine untouched
    bad = Graph.from_edges(2, [(0, 1)], labels=[99, 0], undirected=True)
    mixed = s.run_pack([qs[0], s.prepare(bad), qs[1]], pack_size=4)
    assert [ms.query_index for ms in mixed] == [0, 1, 2]
    assert mixed[1].matches == 0
    assert (mixed[0].matches, mixed[2].matches) == (singles[0].matches, singles[1].matches)


def test_overflow_retries_once_with_doubled_cap(rng):
    """A stack_cap too small for the query must not silently undercount:
    run() aborts the overflowed run, warns, retries once with a doubled
    cap, and reports identical counts to a roomy run (retries=1)."""
    tgt = random_graph(rng, 40, 120, n_labels=2)
    pat = extract_connected_pattern(rng, tgt, 6)
    index = SubgraphIndex.build(tgt)
    roomy = Enumerator(index, n_workers=2, expand_width=2)
    ref = roomy.run(roomy.prepare(pat))
    assert ref.retries == 0

    tight = Enumerator(index, n_workers=2, expand_width=2, stack_cap=8)
    with pytest.warns(RuntimeWarning, match="overflowed"):
        ms = tight.run(tight.prepare(pat))
    assert ms.retries == 1
    assert (ms.matches, ms.states) == (ref.matches, ref.states)


def test_overflow_retry_in_batch_path(rng):
    """An overflowed pack lane goes straight to the doubled-cap single
    retry; its MatchSet reports retries=1 and correct counts."""
    tgt = random_graph(rng, 40, 120, n_labels=2)
    pat = extract_connected_pattern(rng, tgt, 6)
    small = extract_connected_pattern(rng, tgt, 3)
    index = SubgraphIndex.build(tgt)
    roomy = Enumerator(index, n_workers=2, expand_width=2)
    ref = {q.name: roomy.run(q).matches
           for q in [roomy.prepare(pat, name="big"), roomy.prepare(small, name="small")]}

    tight = Enumerator(index, n_workers=2, expand_width=2, stack_cap=8)
    qs = [tight.prepare(pat, name="big"), tight.prepare(small, name="small")]
    with pytest.warns(RuntimeWarning, match="overflowed"):
        out = tight.run_batch(qs)
    by_name = {ms.name: ms for ms in out}
    assert by_name["big"].retries == 1
    assert {n: ms.matches for n, ms in by_name.items()} == ref


def test_overflow_raises_when_doubled_cap_still_too_small(rng):
    """If the doubled cap overflows too, the session refuses to guess
    further and demands an explicit budget."""
    tgt = random_graph(rng, 40, 120, n_labels=2)
    pat = extract_connected_pattern(rng, tgt, 6)
    s = Enumerator(SubgraphIndex.build(tgt), n_workers=2, expand_width=2,
                   stack_cap=3)
    with pytest.warns(RuntimeWarning, match="overflowed"):
        with pytest.raises(RuntimeError, match="stack overflow persists"):
            s.run(s.prepare(pat))


# ---------------------------------------------------------------------------
# device-seeded packs against the state built whole on the host
# ---------------------------------------------------------------------------

def _host_built_state(plan, cfg):
    """The initial state built whole in numpy, rings and all, as the engine
    was seeded before packs seeded on the device: the reference here."""
    v, p_pad, w = cfg.n_workers, plan.p_pad, plan.w
    s_cap = cfg.resolved_stack_cap(p_pad)
    st_depth = np.zeros((v, s_cap), np.int32)
    st_map = np.full((v, s_cap, p_pad), -1, np.int32)
    st_used = np.zeros((v, s_cap, w if cfg.store_used else 1), np.uint32)
    st_cand = np.zeros((v, s_cap, w), np.uint32)
    size = np.zeros(v, np.int32)
    mode = cfg.root_seeding
    if mode == "auto":
        mode = "edge" if plan.seed_edge is not None else "vertex"
    root_mask = None
    if mode == "edge":
        sd, sm, sc = frontier.root_seed_entries(plan)
        if -(-len(sd) // v) > s_cap - 1:
            root_mask = bitmap_from_indices(sm[:, 0].astype(np.int64), plan.n_t, w)
        else:
            for i in range(len(sd)):
                wk = i % v
                slot = size[wk]
                st_depth[wk, slot] = sd[i]
                st_map[wk, slot] = sm[i]
                st_cand[wk, slot] = sc[i]
                if cfg.store_used:
                    prefix = sm[i, : sd[i]].astype(np.int64)
                    st_used[wk, slot] = bitmap_from_indices(
                        prefix[prefix >= 0], plan.n_t, w)
                size[wk] = slot + 1
    if mode == "vertex" or root_mask is not None:
        splits = np.linspace(0, plan.n_t, v + 1).astype(np.int64)
        for k in range(v):
            idx = np.arange(splits[k], splits[k + 1])
            if idx.size:
                st_cand[k, 0] = bitmap_from_indices(idx, plan.n_t, w) & plan.dom_bits[0]
        if root_mask is not None:
            st_cand[:, 0] &= root_mask
        if not plan.satisfiable:
            st_cand[:, 0] = 0
        size = st_cand[:, 0].any(axis=1).astype(np.int32)
    zeros = np.zeros(v, np.int32)
    return eng.EngineState(
        st_depth=st_depth, st_map=st_map, st_used=st_used, st_cand=st_cand,
        base=zeros, size=size, matches=zeros, states=zeros, exp_depth=zeros,
        steals=zeros, steal_depth=zeros, steal_rounds=np.int32(0),
        steps=np.int32(0), overflow=np.bool_(False),
        match_buf=np.full((v, max(1, cfg.collect_matches), p_pad), -1, np.int32),
    )


def _host_result(final):
    """An EngineResult reduced in numpy from a whole final state."""
    f = jax.device_get(final)
    steals, states = int(f.steals.sum()), int(f.states.sum())
    return eng.EngineResult(
        matches=int(f.matches.sum()), states=states, steps=int(f.steps),
        steals=steals, steal_rounds=int(f.steal_rounds),
        mean_steal_depth=int(f.steal_depth.sum()) / steals if steals else 0.0,
        mean_expand_depth=int(f.exp_depth.sum()) / states if states else 0.0,
        per_worker_states=f.states, per_worker_matches=f.matches,
        overflow=bool(f.overflow), match_buf=f.match_buf,
        per_worker_steals=f.steals,
    )


def _pack_scenario(rng, scenario):
    """``(plans of one shape, cfg keywords, pack width)``."""
    shape = dict(p_pad=16, max_parents=8)
    if scenario in ("root_mask_fallback", "edge_seeded"):
        tgt = power_law_target(rng, 420, avg_deg=3.5, alpha=1.7, n_labels=8)
    else:
        tgt = random_graph(rng, 40, 120, n_labels=3)
    pk = PackedGraph.from_graph(tgt)
    pats = [extract_connected_pattern(rng, tgt, 4) for _ in range(3)]
    plans = [build_plan(p, pk, **shape) for p in pats]
    if scenario == "one_lane":
        return plans[:1], {}, 1
    if scenario == "two_lanes_one_inert":
        return plans[:1], {}, 2
    if scenario == "four_lanes_one_inert":
        return plans, {}, 4
    if scenario == "unsatisfiable_lane":
        bad = Graph.from_edges(3, [(0, 1), (1, 2)], labels=[99, 0, 1],
                               undirected=True)
        unsat = build_plan(bad, pk, **shape)
        assert not unsat.satisfiable
        return [plans[0], unsat], {}, 2
    eplans = [build_plan(p, pk, seed_edge="auto", **shape) for p in pats[:2]]
    if scenario == "edge_seeded":
        # a dealt-rows lane beside a vertex-seeded one, and an inert lane
        return [eplans[0], plans[1], eplans[1]], {"root_seeding": "auto"}, 4
    # two workers whose rings hold fewer rows than the seed class deals
    # each: seeding falls back to the root split under the seed-class mask
    k = len(frontier.root_seed_entries(eplans[0])[0])
    assert k >= 4
    kw = {"root_seeding": "edge", "n_workers": 2, "stack_cap": -(-k // 2)}
    return eplans[:1], kw, 2


@pytest.mark.parametrize("scenario", [
    "one_lane", "two_lanes_one_inert", "four_lanes_one_inert",
    "unsatisfiable_lane", "root_mask_fallback", "edge_seeded"])
@pytest.mark.parametrize("backend", ["jnp", "csr"])
def test_device_seeded_pack_equals_host_built_state(rng, backend, scenario):
    """A pack seeded on the device from seed rows and reduced there gives,
    lane for lane, every EngineResult field of a run from the state built
    whole on the host, match buffer order included; its seeded state equals
    that state field by field, and its inert lanes do nothing."""
    plans, kw, pack = _pack_scenario(rng, scenario)
    cfg = EngineConfig(**{"n_workers": 4, "expand_width": 2, "step_backend": backend,
                          "collect_matches": 64, **kw})
    lanes = [frontier.seed_rows(p, cfg) for p in plans]
    if scenario == "root_mask_fallback":
        assert lanes[0].depth is None
    if scenario == "edge_seeded":
        assert [s.depth is None for s in lanes] == [False, True, False]
    loop = jax.jit(functools.partial(eng._engine_loop, cfg))
    want = []
    for p in plans:
        host = _host_built_state(p, cfg)
        jax.tree.map(np.testing.assert_array_equal,
                     jax.device_get(frontier.init_state(p, cfg)), host)
        want.append(_host_result(loop(eng.plan_arrays_for(cfg, p), host)))

    arrays = [eng.plan_arrays_for(cfg, p) for p in plans]
    arrays += [arrays[0]] * (pack - len(plans))
    stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *arrays)
    seeds = frontier.stack_seeds(lanes, pack)
    counters = jax.device_get(
        eng.make_pack_engine_fn(cfg, plans[0].p_pad)(stacked, seeds))
    for row in range(pack):
        got = eng.result_from_counters(jax.tree.map(lambda x: x[row], counters))
        if row >= len(plans):
            assert (got.matches, got.states, got.steps) == (0, 0, 0)
            continue
        for field in eng.EngineResult._fields:
            np.testing.assert_array_equal(
                getattr(got, field), getattr(want[row], field), err_msg=field)
