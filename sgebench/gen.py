"""Targets and query patterns made from a seed.

The generator belongs to the benchmark, not to the program: a change to
``repro.data.graphgen`` must not change what a cell measures.  Everything
here is numpy and returns plain arrays; ``run.py`` wraps them in the
program's graph type.

A target is undirected with one edge label, stored as both arcs, with no
self-loops and no parallel edges.  Node labels are drawn from a normal
distribution or uniformly.

A pattern is a tree of edges extracted from the target by a random walk
(as subgraph-matching studies draw their queries) until it holds the
requested number of arcs (both arcs of each undirected edge count).
Being taken from the target, every pattern has at least one match.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, List, Sequence, Tuple

import numpy as np


@dataclasses.dataclass(frozen=True)
class Graph:
    """A labelled graph as arrays: arcs ``src[i] -> dst[i]`` with edge label
    ``elab[i]``; node ``u`` has label ``labels[u]``."""

    n: int
    src: np.ndarray
    dst: np.ndarray
    labels: np.ndarray
    elab: np.ndarray

    @property
    def m(self) -> int:
        return int(self.src.shape[0])


@dataclasses.dataclass(frozen=True)
class Adjacency:
    """Sorted undirected neighbour lists of a target (CSR)."""

    indptr: np.ndarray
    indices: np.ndarray

    def row(self, u: int) -> np.ndarray:
        return self.indices[self.indptr[u]:self.indptr[u + 1]]


def node_labels(rng: np.random.Generator, n: int, n_labels: int,
                dist: str) -> np.ndarray:
    if dist == "normal":
        raw = rng.normal(n_labels / 2.0, n_labels / 6.0, n)
        return np.clip(np.round(raw), 0, n_labels - 1).astype(np.int32)
    if dist == "uniform":
        return rng.integers(0, n_labels, n).astype(np.int32)
    raise ValueError(f"label_dist {dist!r}: expected 'normal' or 'uniform'")


def target(spec: Dict, rng: np.random.Generator) -> Graph:
    """The undirected target of a configuration's ``target`` block:
    ``n`` nodes, ``m`` distinct edges drawn uniformly, ``labels`` node
    labels distributed as ``label_dist``."""
    n, m = int(spec["n"]), int(spec["m"])
    if m > n * (n - 1) // 2:
        raise ValueError(f"{m} edges do not fit {n} nodes")
    keys = np.zeros(0, np.int64)
    while keys.size < m:
        k = int((m - keys.size) * 1.2) + 64
        u = rng.integers(0, n, k, dtype=np.int64)
        v = rng.integers(0, n, k, dtype=np.int64)
        keep = u != v
        lo, hi = np.minimum(u, v)[keep], np.maximum(u, v)[keep]
        cand = np.concatenate([keys, lo * n + hi])
        _, first = np.unique(cand, return_index=True)
        keys = cand[np.sort(first)]  # first drawn wins; order of drawing kept
    keys = keys[:m]
    lo, hi = (keys // n).astype(np.int32), (keys % n).astype(np.int32)
    labels = node_labels(rng, n, int(spec["labels"]), spec["label_dist"])
    src = np.concatenate([lo, hi])
    dst = np.concatenate([hi, lo])
    return Graph(n=n, src=src, dst=dst, labels=labels,
                 elab=np.zeros(src.shape[0], np.int32))


def adjacency(g: Graph) -> Adjacency:
    order = np.lexsort((g.dst, g.src))
    indptr = np.zeros(g.n + 1, np.int64)
    np.cumsum(np.bincount(g.src, minlength=g.n), out=indptr[1:])
    return Adjacency(indptr=indptr, indices=g.dst[order].astype(np.int64))


def pattern(g: Graph, adj: Adjacency, n_arcs: int,
            rng: np.random.Generator) -> Graph:
    """A tree of ``n_arcs // 2`` edges extracted from ``g`` by a random
    walk: from a random start node, each step moves to a random neighbour
    of the current node, and the edge that first reaches a node is kept.
    So every pattern of a size has the same shape (``n_arcs // 2 + 1``
    nodes, ``n_arcs`` arcs) and, taken from the target, at least one
    match.  It comes out smaller only where the walk cannot reach enough
    nodes in ``64 * n_arcs`` steps; the traffic draws again then."""
    cur = int(rng.integers(g.n))
    nodes: List[int] = [cur]
    in_set = {cur}
    edges: List[Tuple[int, int]] = []
    for _ in range(64 * n_arcs):
        if 2 * len(edges) >= n_arcs:
            break
        row = adj.row(cur)
        if row.shape[0] == 0:
            break
        nxt = int(row[rng.integers(row.shape[0])])
        if nxt not in in_set:
            edges.append((cur, nxt))
            nodes.append(nxt)
            in_set.add(nxt)
        cur = nxt
    keep = sorted(nodes)
    idx = {u: i for i, u in enumerate(keep)}
    src = [idx[u] for u, _ in edges] + [idx[v] for _, v in edges]
    dst = [idx[v] for _, v in edges] + [idx[u] for u, _ in edges]
    return Graph(n=len(keep), src=np.asarray(src, np.int32),
                 dst=np.asarray(dst, np.int32),
                 labels=g.labels[np.asarray(keep)].astype(np.int32),
                 elab=np.zeros(len(src), np.int32))


def max_degree(p: Graph) -> int:
    """The largest number of neighbours of one pattern node."""
    return int(np.bincount(p.src, minlength=p.n).max()) if p.m else 0


def pattern_key(p: Graph) -> tuple:
    """Identity of a pattern as the program receives it."""
    return (p.n, p.src.tobytes(), p.dst.tobytes(), p.labels.tobytes(),
            p.elab.tobytes())


def client_sizes(sizes: Sequence[int], n: int,
                 rng: np.random.Generator) -> List[int]:
    """``n`` pattern sizes for one client: whole shuffled rounds of
    ``sizes``, so every seed sends each size equally often, in another
    order."""
    out: List[int] = []
    while len(out) < n:
        out.extend(int(sizes[i]) for i in rng.permutation(len(sizes)))
    return out[:n]


def patterns(g: Graph, traffic: Dict, rng: np.random.Generator
             ) -> Tuple[List[List[Graph]], List[Graph]]:
    """The run's queries, one list per client, and warm-up patterns.

    Each client gets ``queries_per_client`` patterns, more than a window
    completes, in whole shuffled rounds of the traffic's ``pattern_arcs``.
    No two patterns of a run are the same.  The warm-up patterns are one
    per size, drawn apart from the clients' (so no query of the window is
    ever prepared twice): every pattern of a size has one shape, so they
    compile every shape the window will prepare and dispatch, and every
    seed warms the same number.
    """
    adj = adjacency(g)
    sizes = [int(s) for s in traffic["pattern_arcs"]]
    per_client = int(traffic["queries_per_client"])
    seen = set()

    def draw(arcs: int) -> Graph:
        for _ in range(1000):
            p = pattern(g, adj, arcs, rng)
            key = pattern_key(p)
            if p.m == arcs and key not in seen:
                seen.add(key)
                return p
        raise RuntimeError(f"no new {arcs}-arc pattern after 1000 draws")

    queues = [[draw(s) for s in client_sizes(sizes, per_client, rng)]
              for _ in range(int(traffic["clients"]))]
    warm = [draw(s) for s in sorted(set(sizes))]
    return queues, warm
