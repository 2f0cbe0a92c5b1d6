"""Plain subgraph enumeration: the reference that decides ``correct``.

A straightforward backtracking search with label filtering and neighbour
sets.  It shares nothing with the program under test (no plan, ordering,
domains or bitmaps) and imports only numpy, so a change to the program's
preprocessing or engine cannot move it.

Semantics are those of the paper's problem: a match maps every pattern node
to a distinct target node of the same label such that every pattern arc
``u -> v`` with edge label ``l`` lands on a target arc with edge label
``l`` (non-induced).  Mappings are tuples indexed by pattern node.
"""

from __future__ import annotations

from typing import Dict, List, Tuple

import numpy as np


class Target:
    """Neighbour sets of a target, per edge label and direction."""

    def __init__(self, n: int, src: np.ndarray, dst: np.ndarray,
                 labels: np.ndarray, elab: np.ndarray):
        self.n = int(n)
        self.labels = np.asarray(labels).tolist()
        self.out: Dict[int, List[set]] = {}
        self.inn: Dict[int, List[set]] = {}
        for u, v, l in zip(np.asarray(src).tolist(), np.asarray(dst).tolist(),
                           np.asarray(elab).tolist()):
            if l not in self.out:
                self.out[l] = [set() for _ in range(self.n)]
                self.inn[l] = [set() for _ in range(self.n)]
            self.out[l][u].add(v)
            self.inn[l][v].add(u)
        self.by_label: Dict[int, List[int]] = {}
        for u, lab in enumerate(self.labels):
            self.by_label.setdefault(lab, []).append(u)


def _order(n: int, arcs: List[Tuple[int, int, int]], labels: List[int],
           t: Target) -> List[int]:
    """Rarest label first, then always the node with most ordered
    neighbours (ties: higher degree, then lower id)."""
    nbrs = [set() for _ in range(n)]
    for u, v, _ in arcs:
        nbrs[u].add(v)
        nbrs[v].add(u)
    first = min(range(n), key=lambda u: (len(t.by_label.get(labels[u], ())),
                                         -len(nbrs[u]), u))
    order, placed = [first], {first}
    while len(order) < n:
        rest = [u for u in range(n) if u not in placed]
        u = max(rest, key=lambda u: (len(nbrs[u] & placed), len(nbrs[u]), -u))
        order.append(u)
        placed.add(u)
    return order


def enumerate_matches(pattern_n: int, pattern_src, pattern_dst,
                      pattern_labels, pattern_elab, t: Target,
                      injective: bool = True, last_edges: bool = True,
                      ) -> Tuple[int, List[Tuple[int, ...]]]:
    """``(count, mappings)`` of the pattern in ``t``.  ``injective=False``
    drops the one-to-one guarantee, ``last_edges=False`` the edge checks
    of the last node placed; both exist only as the benchmark's controls,
    which have to fail the comparison."""
    n = int(pattern_n)
    labels = np.asarray(pattern_labels).tolist()
    arcs = list(zip(np.asarray(pattern_src).tolist(),
                    np.asarray(pattern_dst).tolist(),
                    np.asarray(pattern_elab).tolist()))
    for u, v, _ in arcs:
        if u == v:
            raise ValueError("self-loops are not supported by the reference")
    if n == 0:
        return 0, []
    order = _order(n, arcs, labels, t)
    pos = {u: i for i, u in enumerate(order)}
    # per position: the neighbour sets (of already-mapped nodes) that the
    # candidate must lie in, as (earlier position, table) pairs
    checks: List[List[Tuple[int, List[set]]]] = [[] for _ in range(n)]
    for u, v, l in arcs:
        if l not in t.out:
            return 0, []
        if pos[u] > pos[v]:  # u placed later: u must be an in-neighbour of m(v)
            checks[pos[u]].append((pos[v], t.inn[l]))
        else:  # v placed later: v must be an out-neighbour of m(u)
            checks[pos[v]].append((pos[u], t.out[l]))
    if not last_edges:
        checks[n - 1] = []
    want = [labels[u] for u in order]
    roots = t.by_label.get(want[0], [])

    mapping = [0] * n
    used = set()
    found: List[Tuple[int, ...]] = []

    def rec(i: int) -> None:
        if i == n:
            row = [0] * n
            for p, u in enumerate(order):
                row[u] = mapping[p]
            found.append(tuple(row))
            return
        if checks[i]:
            sets = sorted((table[mapping[j]] for j, table in checks[i]), key=len)
            cand = sets[0]
            for s in sets[1:]:
                cand = cand & s
        else:
            cand = roots if i == 0 else t.by_label.get(want[i], [])
        lab = want[i]
        for c in cand:
            if t.labels[c] != lab or (injective and c in used):
                continue
            mapping[i] = c
            if injective:
                used.add(c)
                rec(i + 1)
                used.discard(c)
            else:
                rec(i + 1)

    rec(0)
    return len(found), found


def reference_target(g) -> Target:
    """:class:`Target` of a graph with ``n``, ``src``, ``dst``, ``labels``
    and ``elab`` arrays (``sgebench.gen.Graph``)."""
    return Target(g.n, g.src, g.dst, g.labels, g.elab)


def matches_of(p, t: Target, injective: bool = True,
               last_edges: bool = True,
               ) -> Tuple[int, List[Tuple[int, ...]]]:
    """:func:`enumerate_matches` of a pattern given as a graph like
    :func:`reference_target`'s."""
    return enumerate_matches(p.n, p.src, p.dst, p.labels, p.elab, t,
                             injective=injective, last_edges=last_edges)
