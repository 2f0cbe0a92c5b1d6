"""The plain reference against the program's own numpy oracle, on small
seeded targets without self-loops."""

from __future__ import annotations

import numpy as np
import pytest

from sgebench import gen
from sgebench.reference import matches_of, reference_target

TARGETS = {
    "dense": {"n": 60, "m": 420, "labels": 4, "label_dist": "normal"},
    "sparse": {"n": 300, "m": 600, "labels": 3, "label_dist": "uniform"},
}


def oracle(plain_target, p):
    """``repro.core.ref.ref_enumerate`` on the same inputs, mappings
    indexed by pattern node."""
    from repro.core.graph import Graph, PackedGraph
    from repro.core.plan import build_plan
    from repro.core.ref import ref_enumerate

    def prog(g):
        return Graph(n=g.n, src=g.src, dst=g.dst, labels=g.labels,
                     edge_labels=g.elab)

    t = prog(plain_target)
    plan = build_plan(prog(p), PackedGraph.from_graph(t), variant="ri-ds-si-fc")
    r = ref_enumerate(prog(p), t, plan=plan, record_mappings=True)
    order = [int(x) for x in plan.order[: plan.n_p]]
    maps = []
    for m in r.mappings:
        row = [0] * p.n
        for pos, node in enumerate(order):
            row[node] = int(m[pos])
        maps.append(tuple(row))
    return r.matches, sorted(maps)


@pytest.mark.parametrize("kind", sorted(TARGETS))
@pytest.mark.parametrize("seed", [0, 1, 2, 3])
def test_reference_matches_oracle(kind, seed):
    rng = np.random.default_rng(seed)
    g = gen.target(TARGETS[kind], rng)
    t = reference_target(g)
    adj = gen.adjacency(g)
    for arcs in (4, 8, 12):
        p = gen.pattern(g, adj, arcs, rng)
        count, maps = matches_of(p, t)
        want_count, want_maps = oracle(g, p)
        assert count == want_count >= 1
        assert sorted(maps) == want_maps


def _induced(g, adj, k, rng):
    """The subgraph of ``g`` induced by ``k`` nodes grown from a random
    start: on a dense target, a pattern with cycles."""
    nodes = [int(rng.integers(g.n))]
    while len(nodes) < k:
        nbrs = [int(v) for u in nodes for v in adj.row(u) if v not in nodes]
        nodes.append(nbrs[int(rng.integers(len(nbrs)))])
    idx = {u: i for i, u in enumerate(sorted(nodes))}
    arcs = [(idx[u], idx[v]) for u, v in zip(g.src.tolist(), g.dst.tolist())
            if u in idx and v in idx]
    return gen.Graph(n=k, src=np.array([a for a, _ in arcs], np.int32),
                     dst=np.array([b for _, b in arcs], np.int32),
                     labels=g.labels[np.array(sorted(nodes))].astype(np.int32),
                     elab=np.zeros(len(arcs), np.int32))


@pytest.mark.parametrize("seed", [0, 1])
def test_reference_matches_oracle_on_cyclic_patterns(seed):
    rng = np.random.default_rng(seed)
    g = gen.target(TARGETS["dense"], rng)
    t, adj = reference_target(g), gen.adjacency(g)
    cyclic = 0
    for k in (4, 6, 8):
        p = _induced(g, adj, k, rng)
        cyclic += p.m // 2 >= p.n
        count, maps = matches_of(p, t)
        want_count, want_maps = oracle(g, p)
        assert count == want_count >= 1
        assert sorted(maps) == want_maps
    assert cyclic >= 1


def test_control_breaks_injectivity():
    """The control (one-to-one mapping dropped) answers a path whose ends
    share a label with extra, non-injective mappings."""
    g = gen.Graph(n=3, src=np.array([0, 1, 1, 2]), dst=np.array([1, 0, 2, 1]),
                  labels=np.array([0, 1, 0]), elab=np.zeros(4, np.int32))
    p = g  # the path 0-1-2 in itself
    t = reference_target(g)
    assert matches_of(p, t)[0] == 2
    assert matches_of(p, t, injective=False)[0] == 4


def test_control_drops_last_edges():
    """The control with the last node's edges unchecked maps it by label
    alone: in a path 0-1-2 plus a far node 3 of the end label, the end
    that is placed last may land on node 3 as well."""
    g = gen.Graph(n=4, src=np.array([0, 1, 1, 2]), dst=np.array([1, 0, 2, 1]),
                  labels=np.array([0, 1, 2, 2]), elab=np.zeros(4, np.int32))
    p = gen.Graph(n=3, src=np.array([0, 1, 1, 2]), dst=np.array([1, 0, 2, 1]),
                  labels=np.array([0, 1, 2]), elab=np.zeros(4, np.int32))
    t = reference_target(g)
    assert matches_of(p, t)[0] == 1
    assert matches_of(p, t, last_edges=False)[0] == 2


def test_reference_refuses_self_loops():
    g = gen.Graph(n=1, src=np.array([0]), dst=np.array([0]),
                  labels=np.array([0]), elab=np.array([0]))
    with pytest.raises(ValueError):
        matches_of(g, reference_target(g))
