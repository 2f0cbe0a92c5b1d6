"""RI-DS domain assignment: initial compatibility domains, arc-consistency
filtering, and the paper's singleton forward checking (FC).

Domains are packed ``[n_p, w]`` uint32 bitmaps over target nodes — the same
representation RI-DS uses ("domains are implemented as bitmasks", paper
§4.2.2), which makes every filtering step a dense bitwise sweep.

Two implementations of the same pipeline live here (DESIGN.md §5):

* the **numpy oracle** — ``initial_domains`` / ``arc_consistency`` /
  ``forward_check_singletons`` / ``fixpoint_domains``, a host-side loop over
  constraint arcs.  Slow but transparent; every device result is validated
  against it bit-for-bit.
* the **device engine** — a jitted ``lax.while_loop`` fixpoint
  (:func:`device_fixpoint`) that sweeps *all* constraint arcs at once against
  ``adj_bits[n_elab, 2, n_t, w]``, optionally routing the row-AND-any
  reduction and popcounts through the Pallas kernels
  (`repro.kernels.domain_ac.adjacency_any` / `arc_any_sweep`,
  `repro.kernels.popcount_reduce.popcount_rows`), and vmappable across a
  padded pattern batch (:func:`compute_domains_batch` — the
  ``Enumerator.prepare_batch`` backend, DESIGN.md §5).

The same fixpoint engine also runs **CSR-native** (DESIGN.md §11): hand it a
:class:`CsrTargetDomainArrays` instead of a :class:`TargetDomainArrays` and
every AC sweep walks `repro.core.graph.CsrPlanes` segments ("some neighbor
of ``v`` in ``D(child)``" via per-row segment bit tests) instead of dense
adjacency bitmaps — the ``[n_elab, 2, n_t, w]`` planes are never
materialized, which is what lets every ``ri-ds*`` variant run on CSR-only
plans (`repro.core.plan.build_csr_plan`) at the >33k-node scale the sparse
step backend unlocked.  Kernel: `repro.kernels.domain_ac.csr_arc_sweep`
(scalar-prefetch — single-query only); jnp/vmap path:
`repro.kernels.ref.csr_arc_sweep_ref`.

Pipeline (paper §4.1 / §4.2.2):

  1. ``initial_domains``    — label equality + degree dominance + **self-loop
     dominance**: a pattern node carrying a self-loop with edge label ``l``
     can only map to target nodes carrying a same-label self-loop.  Pattern
     self-loops are inexpressible as parent constraints (the ordering skips
     ``u == v`` edges), so this unary constraint is their single enforcement
     point; the engine/ref candidate checks inherit it because candidates are
     always intersected with the domain bitmap.
  2. ``arc_consistency``    — drop ``t`` from ``D(p)`` if some pattern edge
     ``(p, q)`` has no counterpart ``(t, t')`` with ``t' ∈ D(q)`` and a
     compatible edge label.  Iterated to a fixpoint (each removal can expose
     more inconsistency).
  3. ``forward_check_singletons`` — every pattern node with ``|D(p)| == 1``
     *will* consume its target node; remove that node from all other domains,
     repeating on newly created singletons.  Detects unsatisfiability when a
     domain empties or two singletons collide.
  4. ``fixpoint_domains`` (variant ``ri-ds-si-acfc``) — interleave 2 and 3
     until a *joint* fixpoint: FC removals re-trigger AC, reaching prunings
     the sequential AC→FC pipeline leaves on the table (paper §4.2.2's
     "improved pruning" taken to closure).  The joint fixpoint is unique
     (both rules are monotone prunings), so iteration order never changes
     the result — only how fast it is reached.

Contracts:

* ``DomainResult.satisfiable is False`` ⇒ ``bits`` is **all-zero**.  Early
  unsat exits used to leak partially-filtered bitmaps; callers must never be
  able to enumerate from a half-pruned plan.
* A pattern edge label with no adjacency plane in the target
  (``elab >= target.n_edge_labels``) makes the query unsatisfiable in every
  variant — it used to raise ``IndexError`` (arcs) or silently clamp to a
  wrong label plane (engine gathers).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import List, NamedTuple, Optional, Sequence, Tuple

import numpy as np

from repro.core.graph import (
    Graph,
    PackedGraph,
    WORD_BITS,
    bitmap_from_indices,
    n_words,
    popcount,
)


@dataclasses.dataclass
class DomainResult:
    """Packed domains plus satisfiability flag.

    Invariant: ``satisfiable is False`` implies ``bits`` is all-zero, so an
    unsatisfiable result can never seed a search.
    """

    bits: np.ndarray  # [n_p, w] uint32
    satisfiable: bool

    def sizes(self) -> np.ndarray:
        return popcount(self.bits)


def _unsat(bits: np.ndarray) -> DomainResult:
    """The canonical unsatisfiable result: zeroed bits (see class invariant)."""
    return DomainResult(np.zeros_like(bits), False)


# ---------------------------------------------------------------------------
# pattern constraint extraction
# ---------------------------------------------------------------------------

def _self_loops(pattern: Graph) -> List[Tuple[int, int]]:
    """All pattern self-loop constraints ``(u, elab)``.

    Self-loops cannot be parent constraints (both endpoints are the same
    ordering position), so they are enforced as unary domain constraints in
    :func:`initial_domains` / the device engine's initial phase."""
    return [
        (int(u), int(l))
        for u, v, l in zip(pattern.src.tolist(), pattern.dst.tolist(),
                           pattern.edge_labels.tolist())
        if u == v
    ]


def _pattern_arcs(pattern: Graph) -> np.ndarray:
    """All directed constraint arcs ``(p, q, dir, elab)``.

    For pattern edge ``(p -> q)`` with label ``l`` we emit two arcs:
      * ``(p, q, dir=0, l)``: every ``t ∈ D(p)`` needs an out-edge with label
        ``l`` to some ``t' ∈ D(q)``;
      * ``(q, p, dir=1, l)``: every ``t ∈ D(q)`` needs an in-edge from some
        ``t' ∈ D(p)``.

    Self-loops (``u == v``) are excluded: their binary form ("some D(u) node
    is an out-neighbor") is strictly weaker than the true unary constraint
    ("t itself carries the loop"), which :func:`initial_domains` enforces.
    """
    arcs = []
    for u, v, l in zip(pattern.src.tolist(), pattern.dst.tolist(),
                       pattern.edge_labels.tolist()):
        if u == v:
            continue
        arcs.append((u, v, 0, l))
        arcs.append((v, u, 1, l))
    return np.asarray(arcs, dtype=np.int32).reshape(-1, 4)


def target_self_loop_bits(target: PackedGraph) -> np.ndarray:
    """``[n_elab, w]`` bitmaps: bit ``t`` set iff the target has a self-loop
    ``(t, t)`` with edge label ``l`` — the diagonal of each adjacency plane."""
    n, w = target.n, target.w
    out = np.zeros((target.n_edge_labels, w), dtype=np.uint32)
    if n == 0:
        return out
    t = np.arange(n)
    word = t // WORD_BITS
    shift = (t % WORD_BITS).astype(np.uint32)
    for l in range(target.n_edge_labels):
        diag = (target.adj_bits[l, 0, t, word] >> shift) & np.uint32(1)
        idx = np.nonzero(diag)[0]
        if idx.size:
            out[l] = bitmap_from_indices(idx, n, w)
    return out


# ---------------------------------------------------------------------------
# numpy oracle
# ---------------------------------------------------------------------------

def initial_domains(pattern: Graph, target: PackedGraph) -> np.ndarray:
    """``D0(p) = { t : lab(t) == lab(p), deg_out(t) >= deg_out(p),
    deg_in(t) >= deg_in(p), self-loops of p ⊆ self-loops of t }``
    as ``[n_p, w]`` bitmaps.

    The self-loop clause is the bugfix for patterns with loop edges: a loop
    with a label the target lacks empties the domain outright."""
    p_out = pattern.out_degrees()
    p_in = pattern.in_degrees()
    w = target.w
    bits = np.zeros((pattern.n, w), dtype=np.uint32)
    for p in range(pattern.n):
        ok = (
            (target.labels == pattern.labels[p])
            & (target.deg_out >= p_out[p])
            & (target.deg_in >= p_in[p])
        )
        idx = np.nonzero(ok)[0]
        if idx.size:
            bits[p] = bitmap_from_indices(idx, target.n, w)
    loops = _self_loops(pattern)
    if loops:
        loop_bits = target_self_loop_bits(target)
        for p, l in loops:
            if l >= target.n_edge_labels:
                bits[p] = 0  # label overflow: no target loop can match
            else:
                bits[p] &= loop_bits[l]
    return bits


def arc_consistency(
    pattern: Graph,
    target: PackedGraph,
    bits: np.ndarray,
    max_iters: Optional[int] = None,
) -> DomainResult:
    """Filter domains to (iterated) arc consistency.

    For arc ``(p, q, dir, l)``: keep ``t`` in ``D(p)`` only if
    ``adj_bits[l, dir, t] & D(q)`` is non-empty — a row-wise AND + any-bit
    test over the target adjacency bitmaps, vectorized over all ``t``.
    A label ``l`` with no adjacency plane (``l >= n_elab``) is treated as an
    all-empty plane, so the arc's domain empties (label-overflow bugfix —
    this used to raise ``IndexError``).
    """
    bits = bits.copy()
    arcs = _pattern_arcs(pattern)
    if arcs.size == 0:
        if np.all(popcount(bits) > 0):
            return DomainResult(bits, True)
        return _unsat(bits)
    n_elab = target.adj_bits.shape[0]
    it = 0
    while True:
        it += 1
        changed = False
        for p, q, d, l in arcs.tolist():
            if l >= n_elab:
                rows_any = np.zeros(target.n, dtype=bool)
            else:
                rows = target.adj_bits[l, d]  # [n_t, w]
                rows_any = np.any(rows & bits[q][None, :], axis=-1)  # [n_t]
            mask = (
                bitmap_from_indices(np.nonzero(rows_any)[0], target.n, target.w)
                if rows_any.any()
                else np.zeros(target.w, np.uint32)
            )
            nb = bits[p] & mask
            if not np.array_equal(nb, bits[p]):
                bits[p] = nb
                changed = True
                if not nb.any():
                    return _unsat(bits)
        if not changed or (max_iters is not None and it >= max_iters):
            break
    if np.all(popcount(bits) > 0):
        return DomainResult(bits, True)
    return _unsat(bits)


def forward_check_singletons(bits: np.ndarray) -> DomainResult:
    """The paper's FC (§4.2.2): propagate injectivity from singleton domains.

    Pattern nodes with ``|D(p)| == 1`` are guaranteed to be assigned their
    single target node; remove that node from every *other* domain, and
    iterate on newly created singletons.
    """
    bits = bits.copy()
    n_p = bits.shape[0]
    sizes = popcount(bits)
    if np.any(sizes == 0):
        return _unsat(bits)
    processed = np.zeros(n_p, dtype=bool)
    while True:
        new = np.nonzero((sizes == 1) & ~processed)[0]
        if new.size == 0:
            break
        # Union bitmap of all newly discovered singleton targets.  Collision
        # (two singletons sharing a target) surfaces as an emptied domain.
        union = np.zeros(bits.shape[1], dtype=np.uint32)
        for p in new.tolist():
            if (union & bits[p]).any():
                return _unsat(bits)  # two singletons collide
            union |= bits[p]
            processed[p] = True
        keep = ~processed
        bits[keep] &= ~union[None, :]
        sizes = popcount(bits)
        if np.any(sizes == 0):
            return _unsat(bits)
    return DomainResult(bits, True)


def fixpoint_domains(
    pattern: Graph,
    target: PackedGraph,
    bits: np.ndarray,
    max_iters: Optional[int] = None,
) -> DomainResult:
    """AC ⇄ FC joint fixpoint (numpy oracle for the device engine).

    Alternates arc consistency and singleton forward checking until neither
    removes a candidate: FC removals re-trigger AC.  Both rules are monotone
    prunings, so the joint fixpoint is unique and iteration order does not
    affect the result (DESIGN.md §5).
    """
    res = DomainResult(bits.copy(), True)
    while True:
        res = arc_consistency(pattern, target, res.bits, max_iters=max_iters)
        if not res.satisfiable:
            return res
        nxt = forward_check_singletons(res.bits)
        if not nxt.satisfiable or np.array_equal(nxt.bits, res.bits):
            return nxt
        res = nxt


def initial_domains_sparse(pattern: Graph, target: Graph, w: int) -> np.ndarray:
    """:func:`initial_domains` computed from a host :class:`Graph` directly
    — no :class:`PackedGraph` (hence no ``O(n_t² / 32)`` dense adjacency
    bitmaps) is ever materialized.  Bit-identical to the packed form for the
    same target; the entry point for CSR-only plans
    (`repro.core.plan.build_csr_plan`, DESIGN.md §6.4)."""
    t_out = target.out_degrees()
    t_in = target.in_degrees()
    p_out = pattern.out_degrees()
    p_in = pattern.in_degrees()
    bits = np.zeros((pattern.n, w), dtype=np.uint32)
    for p in range(pattern.n):
        ok = (
            (target.labels == pattern.labels[p])
            & (t_out >= p_out[p])
            & (t_in >= p_in[p])
        )
        idx = np.nonzero(ok)[0]
        if idx.size:
            bits[p] = bitmap_from_indices(idx, target.n, w)
    loops = _self_loops(pattern)
    if loops:
        n_elab = target.n_edge_labels
        loop_mask = target.src == target.dst
        loop_bits = np.zeros((n_elab, w), dtype=np.uint32)
        for l in range(n_elab):
            idx = target.src[loop_mask & (target.edge_labels == l)]
            if idx.size:
                loop_bits[l] = bitmap_from_indices(idx, target.n, w)
        for p, l in loops:
            if l >= n_elab:
                bits[p] = 0  # label overflow: no target loop can match
            else:
                bits[p] &= loop_bits[l]
    return bits


def compute_domains_sparse(
    pattern: Graph,
    target: Graph,
    w: int,
    use_ac: bool = False,
    use_fc: bool = False,
    interleave: bool = False,
    use_pallas: bool = False,
    ac_iters: Optional[int] = None,
    tgt_arrays: Optional["CsrTargetDomainArrays"] = None,
) -> DomainResult:
    """Domain pipeline over a host :class:`Graph` — dense adjacency bitmaps
    are never materialized, for any variant (DESIGN.md §11).

    With the default flags (variant ``ri``) this is
    :func:`initial_domains_sparse` plus the same label-overflow /
    empty-domain unsat rules as :func:`compute_domains`, computed entirely on
    host.  Any of ``use_ac`` / ``use_fc`` routes through the CSR-native
    device fixpoint (:func:`compute_domains_csr`) instead — the same jitted
    AC ⇄ FC engine as the dense path, sweeping `CsrPlanes` segments.
    Bit-identical to :func:`compute_domains` on the packed form of the same
    target with the same flags (property-tested)."""
    if use_ac or use_fc:
        return compute_domains_csr(
            pattern, target, w, use_ac=use_ac, use_fc=use_fc,
            interleave=interleave, use_pallas=use_pallas, ac_iters=ac_iters,
            tgt_arrays=tgt_arrays,
        )
    bits = initial_domains_sparse(pattern, target, w)
    if pattern.m and int(pattern.edge_labels.max()) >= target.n_edge_labels:
        return _unsat(bits)
    if not np.all(popcount(bits) > 0):
        return _unsat(bits)
    return DomainResult(bits, True)


def compute_domains(
    pattern: Graph,
    target: PackedGraph,
    use_ac: bool = True,
    use_fc: bool = False,
    ac_iters: Optional[int] = None,
    interleave: bool = False,
) -> DomainResult:
    """Full RI-DS domain pipeline (numpy oracle).

    ``use_ac=False`` yields RI's implicit domains (label + degree + self-loop
    compat only); ``use_fc=True`` adds the paper's singleton forward checking;
    ``interleave=True`` (with both) runs AC and FC to their joint fixpoint
    (variant ``ri-ds-si-acfc``) instead of the sequential AC → FC pass.

    A pattern edge label outside the target's label range makes the query
    unsatisfiable in **every** variant (label-overflow bugfix): without this,
    variant ``ri`` plans would hand the engine out-of-range adjacency plane
    indices that jnp gathers silently clamp to the wrong label.
    """
    bits = initial_domains(pattern, target)
    if pattern.m and int(pattern.edge_labels.max()) >= target.n_edge_labels:
        return _unsat(bits)
    if not np.all(popcount(bits) > 0):
        return _unsat(bits)
    if use_ac and use_fc and interleave:
        return fixpoint_domains(pattern, target, bits, max_iters=ac_iters)
    res = DomainResult(bits, True)
    if use_ac:
        res = arc_consistency(pattern, target, res.bits, max_iters=ac_iters)
        if not res.satisfiable:
            return res
    if use_fc:
        res = forward_check_singletons(res.bits)
    return res


# ---------------------------------------------------------------------------
# device-resident fixpoint engine (DESIGN.md §5)
# ---------------------------------------------------------------------------

class TargetDomainArrays(NamedTuple):
    """Device-resident target-side inputs to the fixpoint engine.

    Built once per target (:func:`target_domain_arrays`) and shared by every
    pattern in a batch; the session layer caches it per index."""

    adj_flat: "jnp.ndarray"  # [n_elab * 2, n_t, w] uint32 (label-major planes)
    labels: "jnp.ndarray"  # [n_t] int32
    deg_out: "jnp.ndarray"  # [n_t] int32
    deg_in: "jnp.ndarray"  # [n_t] int32
    loop_bits: "jnp.ndarray"  # [n_elab, w] uint32 self-loop diagonals


class CsrTargetDomainArrays(NamedTuple):
    """CSR-layout target-side inputs to the **same** fixpoint engine
    (DESIGN.md §11) — the sparse twin of :class:`TargetDomainArrays`.

    ``seg_start[p, t] / seg_len[p, t]`` bound target node ``t``'s neighbor
    segment of plane ``p = elab * 2 + dir`` inside the flat ``indices``
    array (`repro.core.graph.CsrPlanes`, global offsets); ``indices`` is
    sentinel-tailed and over-padded by ``deg_cap`` so kernel segment slices
    never clamp.  ``seg_iota`` is a ``[deg_cap]`` iota whose *shape* carries
    the static ``deg_cap`` through jit.  Peak footprint is
    ``O(nnz + n_planes · n_t)`` words vs the dense form's
    ``n_elab · 2 · n_t · w`` — the whole point of the CSR path."""

    seg_start: "jnp.ndarray"  # [n_planes, n_t] int32 global segment offsets
    seg_len: "jnp.ndarray"  # [n_planes, n_t] int32 row lengths
    indices: "jnp.ndarray"  # [n_idx] int32 flat CSR columns (sentinel tail)
    seg_iota: "jnp.ndarray"  # [deg_cap] int32 (shape = static deg_cap)
    labels: "jnp.ndarray"  # [n_t] int32
    deg_out: "jnp.ndarray"  # [n_t] int32
    deg_in: "jnp.ndarray"  # [n_t] int32
    loop_bits: "jnp.ndarray"  # [n_elab, w] uint32 self-loop diagonals


class PatternDomainArrays(NamedTuple):
    """Per-pattern padded inputs to the fixpoint engine (host numpy).

    Shapes ``[p_pad] / [a_pad] / [l_pad]`` define the compile bucket; invalid
    slots are neutral (``valid == False``)."""

    labels: np.ndarray  # [p_pad] int32 (-1 pad: matches no target label)
    deg_out: np.ndarray  # [p_pad] int32
    deg_in: np.ndarray  # [p_pad] int32
    valid: np.ndarray  # [p_pad] bool
    arc_p: np.ndarray  # [a_pad] int32
    arc_q: np.ndarray  # [a_pad] int32
    arc_dir: np.ndarray  # [a_pad] int32
    arc_lab: np.ndarray  # [a_pad] int32
    arc_valid: np.ndarray  # [a_pad] bool
    loop_p: np.ndarray  # [l_pad] int32
    loop_lab: np.ndarray  # [l_pad] int32
    loop_valid: np.ndarray  # [l_pad] bool


def target_domain_arrays(target: PackedGraph) -> TargetDomainArrays:
    """Ship a packed target to the device for domain preprocessing."""
    import jax.numpy as jnp

    ne = target.n_edge_labels
    return TargetDomainArrays(
        adj_flat=jnp.asarray(
            target.adj_bits.reshape(ne * 2, target.n, target.w), jnp.uint32
        ),
        labels=jnp.asarray(target.labels, jnp.int32),
        deg_out=jnp.asarray(target.deg_out, jnp.int32),
        deg_in=jnp.asarray(target.deg_in, jnp.int32),
        loop_bits=jnp.asarray(target_self_loop_bits(target), jnp.uint32),
    )


def csr_target_domain_arrays(
    target: Graph,
    w: int,
    planes=None,  # Optional[repro.core.graph.CsrPlanes]
) -> CsrTargetDomainArrays:
    """Ship a host :class:`Graph`'s CSR planes to the device for sparse
    domain preprocessing — the :func:`target_domain_arrays` twin that never
    materializes dense adjacency bitmaps (DESIGN.md §11).

    Padding (``deg_cap`` up to a multiple of 8, ``nnz`` up to 1024-multiples,
    plus a ``deg_cap`` sentinel tail) matches
    `repro.core.extend.make_csr_plan_arrays` so domain preprocessing and the
    CSR step backend share shape buckets."""
    import jax.numpy as jnp

    from repro.core.extend import CSR_SENTINEL, _pad_deg_cap, _pad_nnz

    if planes is None:
        planes = target.csr_planes(target.n_edge_labels)
    indptr = np.asarray(planes.indptr)
    seg_start = np.ascontiguousarray(indptr[:, :-1]).astype(np.int32)
    seg_len = np.diff(indptr, axis=1).astype(np.int32)
    deg_cap = _pad_deg_cap(int(planes.deg_cap))
    nnz = int(planes.nnz)
    n_idx = _pad_nnz(nnz) + deg_cap
    indices = np.full(n_idx, CSR_SENTINEL, np.int32)
    indices[:nnz] = np.asarray(planes.indices)

    n_elab = planes.n_edge_labels
    loop_mask = target.src == target.dst
    loop_bits = np.zeros((n_elab, w), dtype=np.uint32)
    for l in range(n_elab):
        idx = target.src[loop_mask & (target.edge_labels == l)]
        if idx.size:
            loop_bits[l] = bitmap_from_indices(idx, target.n, w)

    return CsrTargetDomainArrays(
        seg_start=jnp.asarray(seg_start),
        seg_len=jnp.asarray(seg_len),
        indices=jnp.asarray(indices),
        seg_iota=jnp.arange(deg_cap, dtype=jnp.int32),
        labels=jnp.asarray(target.labels, jnp.int32),
        deg_out=jnp.asarray(target.out_degrees(), jnp.int32),
        deg_in=jnp.asarray(target.in_degrees(), jnp.int32),
        loop_bits=jnp.asarray(loop_bits),
    )


def pattern_domain_arrays(
    pattern: Graph,
    p_pad: Optional[int] = None,
    arc_pad: Optional[int] = None,
    loop_pad: Optional[int] = None,
) -> PatternDomainArrays:
    """Pad a pattern's unary + binary constraints into a compile bucket."""
    arcs = _pattern_arcs(pattern)
    loops = _self_loops(pattern)
    n_p, n_a, n_l = pattern.n, arcs.shape[0], len(loops)
    p_pad = max(p_pad or n_p, n_p, 1)
    a_pad = max(arc_pad or n_a, n_a, 1)
    l_pad = max(loop_pad or n_l, n_l, 1)

    labels = np.full(p_pad, -1, dtype=np.int32)
    labels[:n_p] = pattern.labels
    deg_out = np.zeros(p_pad, dtype=np.int32)
    deg_out[:n_p] = pattern.out_degrees()
    deg_in = np.zeros(p_pad, dtype=np.int32)
    deg_in[:n_p] = pattern.in_degrees()
    valid = np.zeros(p_pad, dtype=bool)
    valid[:n_p] = True

    arc = np.zeros((a_pad, 4), dtype=np.int32)
    arc[:n_a] = arcs
    arc_valid = np.zeros(a_pad, dtype=bool)
    arc_valid[:n_a] = True

    loop_p = np.zeros(l_pad, dtype=np.int32)
    loop_lab = np.zeros(l_pad, dtype=np.int32)
    loop_valid = np.zeros(l_pad, dtype=bool)
    for j, (p, l) in enumerate(loops):
        loop_p[j], loop_lab[j], loop_valid[j] = p, l, True

    return PatternDomainArrays(
        labels=labels, deg_out=deg_out, deg_in=deg_in, valid=valid,
        arc_p=arc[:, 0], arc_q=arc[:, 1], arc_dir=arc[:, 2], arc_lab=arc[:, 3],
        arc_valid=arc_valid,
        loop_p=loop_p, loop_lab=loop_lab, loop_valid=loop_valid,
    )


def domain_bucket(pattern: Graph) -> Tuple[int, int, int]:
    """Un-padded bucket dimensions ``(n_p, n_arcs, n_loops)`` of a pattern
    (the session snaps each up to its shape bucket)."""
    n_loops = int(np.sum(pattern.src == pattern.dst))
    return pattern.n, 2 * (pattern.m - n_loops), n_loops


# Pallas routing modes for the device engine (DESIGN.md §5):
#   "off"     — pure-jnp reductions (kernels/ref.py oracles);
#   "sweep"   — one scalar-prefetched `arc_any_sweep` kernel call per AC
#               sweep (single-query path);
#   "per-arc" — `adjacency_any` / `popcount_rows` per arc, which (unlike the
#               scalar-prefetch sweep kernel) compose with vmap for the
#               batched path.
PALLAS_MODES = ("off", "sweep", "per-arc")


def _device_fixpoint(
    use_ac: bool,
    use_fc: bool,
    interleave: bool,
    pallas_mode: str,
    max_iters: Optional[int],
    tgt: TargetDomainArrays,
    pat: PatternDomainArrays,
):
    """Jitted AC ⇄ FC fixpoint over one (padded) pattern.

    Returns ``(bits [p_pad, w] uint32, satisfiable bool)``; bits are zeroed
    when unsatisfiable (the :class:`DomainResult` invariant, on device).
    All control flow is static except the ``lax.while_loop`` fixpoint
    iteration; the function vmaps over a pattern batch (``pat`` axis 0).

    ``tgt`` selects the layout: a :class:`TargetDomainArrays` sweeps dense
    adjacency planes, a :class:`CsrTargetDomainArrays` walks CSR segments
    (DESIGN.md §11) — only the arc-support mask differs; the initial
    domains, loop/overflow unsat rules, FC step, and fixpoint loops are the
    same traced code.
    """
    import jax
    import jax.numpy as jnp
    from jax import lax

    from repro.kernels import ref as kref

    use_pallas = pallas_mode != "off"
    if use_pallas:
        from repro.kernels import ops as kops

    is_csr = isinstance(tgt, CsrTargetDomainArrays)
    if is_csr:
        n_elab, w = tgt.loop_bits.shape
        n_t = tgt.labels.shape[0]
        deg_cap = tgt.seg_iota.shape[0]
    else:
        n_planes, n_t, w = tgt.adj_flat.shape
        n_elab = n_planes // 2
    p_pad = pat.labels.shape[0]
    a_pad = pat.arc_p.shape[0]
    l_pad = pat.loop_p.shape[0]
    ones_row = jnp.full((w,), jnp.uint32(0xFFFFFFFF))
    zeros_row = jnp.zeros((w,), jnp.uint32)

    def pop_rows(bits):  # [n, w] -> [n]
        if use_pallas:
            return kops.popcount_rows(bits)
        return kref.popcount_rows_ref(bits)

    # ---- initial domains: label + degree + self-loop dominance ------------
    flags = (
        (tgt.labels[None, :] == pat.labels[:, None])
        & (tgt.deg_out[None, :] >= pat.deg_out[:, None])
        & (tgt.deg_in[None, :] >= pat.deg_in[:, None])
        & pat.valid[:, None]
    )  # [p_pad, n_t]
    bits = jax.vmap(kref.pack_bits_ref, (0, None))(flags.astype(jnp.int32), w)

    def apply_loop(j, b):
        lab = pat.loop_lab[j]
        m = tgt.loop_bits[jnp.clip(lab, 0, n_elab - 1)]
        m = jnp.where(lab < n_elab, m, zeros_row)  # overflow: no loop matches
        m = jnp.where(pat.loop_valid[j], m, ones_row)  # pad slot: no-op
        p = pat.loop_p[j]
        return b.at[p].set(b[p] & m)

    bits = lax.fori_loop(0, l_pad, apply_loop, bits)

    # label overflow on any constraint (arc or loop) ⇒ unsatisfiable in every
    # variant, matching `compute_domains` (the engine would otherwise gather
    # a clamped — wrong — adjacency plane).
    overflow = jnp.any(pat.arc_valid & (pat.arc_lab >= n_elab)) | jnp.any(
        pat.loop_valid & (pat.loop_lab >= n_elab)
    )
    empty0 = jnp.any(pat.valid & (pop_rows(bits) == 0))
    unsat = overflow | empty0

    # ---- one AC sweep: all arcs at once (Jacobi) ---------------------------
    arc_row = jnp.clip(pat.arc_lab, 0, n_elab - 1) * 2 + pat.arc_dir  # [a_pad]
    arc_dead = pat.arc_valid & (pat.arc_lab >= n_elab)

    def arc_masks_jnp(bits):
        def one(a):
            rows = tgt.adj_flat[arc_row[a]]  # [n_t, w]
            if pallas_mode == "per-arc":
                ok = kops.adjacency_any(rows, bits[pat.arc_q[a]])
            else:
                ok = kref.adjacency_any_ref(rows, bits[pat.arc_q[a]])
            return kref.pack_bits_ref(ok, w)

        return lax.map(one, jnp.arange(a_pad))  # [a_pad, w]

    def arc_masks_pallas(bits):
        ok = kops.arc_any_sweep(tgt.adj_flat, arc_row, bits[pat.arc_q])
        return jax.vmap(kref.pack_bits_ref, (0, None))(ok, w)

    def arc_masks_csr_jnp(bits):
        # the oracle doubles as the (vmappable) jnp compute path; "per-arc"
        # has no CSR kernel, so it lands here too.
        ok = kref.csr_arc_sweep_ref(
            tgt.seg_start, tgt.seg_len, tgt.indices, arc_row,
            bits[pat.arc_q], deg_cap=deg_cap,
        )
        return jax.vmap(kref.pack_bits_ref, (0, None))(ok, w)

    def arc_masks_csr_pallas(bits):
        ok = kops.csr_arc_sweep(
            tgt.seg_start, tgt.seg_len, tgt.indices, arc_row,
            bits[pat.arc_q], deg_cap=deg_cap,
        )
        return jax.vmap(kref.pack_bits_ref, (0, None))(ok, w)

    if is_csr:
        arc_masks = (
            arc_masks_csr_pallas if pallas_mode == "sweep" else arc_masks_csr_jnp
        )
    else:
        arc_masks = arc_masks_pallas if pallas_mode == "sweep" else arc_masks_jnp

    def ac_sweep(bits):
        masks = arc_masks(bits)
        # neutralize pad slots, kill overflow arcs, then AND per pattern node
        masks = jnp.where(pat.arc_valid[:, None], masks, ones_row[None, :])
        masks = jnp.where(arc_dead[:, None], zeros_row[None, :], masks)

        def comb(a, allowed):
            p = pat.arc_p[a]
            return allowed.at[p].set(allowed[p] & masks[a])

        allowed = lax.fori_loop(
            0, a_pad, comb, jnp.broadcast_to(ones_row, (p_pad, w)).astype(jnp.uint32)
        )
        return bits & allowed, jnp.asarray(False)

    # ---- one FC step: all singletons at once -------------------------------
    def fc_step(bits):
        sizes = pop_rows(bits)
        single = (sizes == 1) & pat.valid
        sel = jnp.where(single[:, None], bits, jnp.uint32(0))
        union = lax.reduce(sel, jnp.uint32(0), lax.bitwise_or, (0,))  # [w]
        # collision: two singletons share a target ⇔ OR loses a bit
        collide = jnp.sum(jnp.where(single, sizes, 0)) > jnp.sum(
            lax.population_count(union)
        )
        new = jnp.where(single[:, None], bits, bits & ~union[None, :])
        return new, collide

    # ---- fixpoint loops ----------------------------------------------------
    mi = max_iters if max_iters is not None else p_pad * w * WORD_BITS + 2

    def run_loop(step, bits, unsat):
        def cond(c):
            b, u, changed, it = c
            return changed & ~u & (it < mi)

        def body(c):
            b, u, _, it = c
            nb, step_unsat = step(b)
            u2 = u | step_unsat | jnp.any(pat.valid & (pop_rows(nb) == 0))
            return nb, u2, jnp.any(nb != b), it + 1

        bits, unsat, _, _ = lax.while_loop(
            cond, body, (bits, unsat, jnp.asarray(True), jnp.asarray(0))
        )
        return bits, unsat

    if use_ac and use_fc and interleave:
        def both(b):
            b1, u1 = ac_sweep(b)
            b2, u2 = fc_step(b1)
            return b2, u1 | u2

        bits, unsat = run_loop(both, bits, unsat)
    else:
        if use_ac:
            bits, unsat = run_loop(ac_sweep, bits, unsat)
        if use_fc:
            bits, unsat = run_loop(fc_step, bits, unsat)

    bits = jnp.where(unsat, jnp.uint32(0), bits)
    return bits, ~unsat


@functools.lru_cache(maxsize=None)
def device_fixpoint(
    use_ac: bool = True,
    use_fc: bool = False,
    interleave: bool = False,
    pallas_mode: str = "off",
    max_iters: Optional[int] = None,
    batched: bool = False,
):
    """The jitted device fixpoint ``(TargetDomainArrays, PatternDomainArrays)
    -> (bits, satisfiable)`` for one static flag combination.

    ``batched=True`` vmaps over a leading pattern-batch axis (target arrays
    broadcast).  Cached per flag tuple; XLA adds per-shape caching below.
    """
    import jax

    if pallas_mode not in PALLAS_MODES:
        raise ValueError(f"pallas_mode {pallas_mode!r} not in {PALLAS_MODES}")
    if batched and pallas_mode == "sweep":
        # the scalar-prefetch sweep kernel has no vmap batching rule; the
        # per-arc kernels do (DESIGN.md §5).
        raise ValueError("pallas_mode='sweep' does not compose with batching; "
                         "use 'per-arc'")
    fn = functools.partial(
        _device_fixpoint, use_ac, use_fc, interleave, pallas_mode, max_iters
    )
    if batched:
        fn = jax.vmap(fn, in_axes=(None, 0))
    fn.__name__ = "domain_fixpoint"  # the device program jit_domain_fixpoint
    return jax.jit(fn)


def _to_device(pat: PatternDomainArrays):
    import jax.numpy as jnp

    return PatternDomainArrays(*(jnp.asarray(x) for x in pat))


def compute_domains_device(
    pattern: Graph,
    target: PackedGraph,
    use_ac: bool = True,
    use_fc: bool = False,
    interleave: bool = False,
    use_pallas: bool = False,
    ac_iters: Optional[int] = None,
    tgt_arrays: Optional[TargetDomainArrays] = None,
) -> DomainResult:
    """Single-query device preprocessing; bit-identical to
    :func:`compute_domains` with the same flags (property-tested) **when run
    to convergence** (``ac_iters=None``, the default).  A finite ``ac_iters``
    bounds *Jacobi whole-sweeps* here but *Gauss-Seidel passes* (each arc
    applied against already-updated domains) in the numpy oracle, so
    truncated runs may differ — both remain sound over-approximations of
    the fixpoint."""
    import jax
    import numpy as _np

    tgt = tgt_arrays if tgt_arrays is not None else target_domain_arrays(target)
    pat = _to_device(pattern_domain_arrays(pattern))
    fn = device_fixpoint(
        use_ac=use_ac, use_fc=use_fc, interleave=interleave,
        pallas_mode="sweep" if use_pallas else "off",
        max_iters=ac_iters, batched=False,
    )
    bits, sat = jax.block_until_ready(fn(tgt, pat))
    return DomainResult(_np.asarray(bits)[: pattern.n].copy(), bool(sat))


def compute_domains_csr(
    pattern: Graph,
    target: Graph,
    w: int,
    use_ac: bool = True,
    use_fc: bool = False,
    interleave: bool = False,
    use_pallas: bool = False,
    ac_iters: Optional[int] = None,
    tgt_arrays: Optional[CsrTargetDomainArrays] = None,
) -> DomainResult:
    """Single-query CSR-native device preprocessing (DESIGN.md §11):
    :func:`compute_domains_device` without a :class:`PackedGraph` — the AC
    sweeps walk `CsrPlanes` segments, so dense adjacency bitmaps are never
    materialized.  Bit-identical to :func:`compute_domains` on the packed
    form of the same target with the same flags when run to convergence
    (``ac_iters=None``; finite ``ac_iters`` bounds Jacobi whole-sweeps, as
    in the dense engine).  ``use_pallas`` routes each sweep through the
    scalar-prefetch `csr_arc_sweep` kernel."""
    import jax
    import numpy as _np

    tgt = (
        tgt_arrays if tgt_arrays is not None
        else csr_target_domain_arrays(target, w)
    )
    pat = _to_device(pattern_domain_arrays(pattern))
    fn = device_fixpoint(
        use_ac=use_ac, use_fc=use_fc, interleave=interleave,
        pallas_mode="sweep" if use_pallas else "off",
        max_iters=ac_iters, batched=False,
    )
    bits, sat = jax.block_until_ready(fn(tgt, pat))
    return DomainResult(_np.asarray(bits)[: pattern.n].copy(), bool(sat))


def compute_domains_batch(
    patterns: Sequence[Graph],
    target: PackedGraph,
    use_ac: bool = True,
    use_fc: bool = False,
    interleave: bool = False,
    use_pallas: bool = False,
    p_pad: Optional[int] = None,
    arc_pad: Optional[int] = None,
    loop_pad: Optional[int] = None,
    batch_pad: Optional[int] = None,
    tgt_arrays: Optional[TargetDomainArrays] = None,
    fn: Optional[callable] = None,
) -> List[DomainResult]:
    """Batched device preprocessing: one vmapped fixpoint call for a padded
    pattern batch (the ``Enumerator.prepare_batch`` backend, DESIGN.md §5).

    All patterns share one compile bucket ``(p_pad, arc_pad, loop_pad,
    batch_pad)``; unspecified pads snap to the batch maxima.  ``batch_pad``
    lanes beyond ``len(patterns)`` replicate lane 0 and are discarded.
    ``fn`` overrides the jitted batched fixpoint (the session passes its
    cached one); it must have been built with matching flags.
    """
    import jax
    import jax.numpy as jnp
    import numpy as _np

    patterns = list(patterns)
    if not patterns:
        return []
    dims = [domain_bucket(p) for p in patterns]
    p_pad = p_pad or max(d[0] for d in dims)
    arc_pad = arc_pad or max(d[1] for d in dims)
    loop_pad = loop_pad or max(d[2] for d in dims)
    arrs = [
        pattern_domain_arrays(p, p_pad=p_pad, arc_pad=arc_pad, loop_pad=loop_pad)
        for p in patterns
    ]
    b_pad = max(batch_pad or len(arrs), len(arrs))
    arrs = arrs + [arrs[0]] * (b_pad - len(arrs))
    stacked = PatternDomainArrays(
        *(jnp.asarray(_np.stack(cols)) for cols in zip(*arrs))
    )
    tgt = tgt_arrays if tgt_arrays is not None else target_domain_arrays(target)
    if fn is None:
        fn = device_fixpoint(
            use_ac=use_ac, use_fc=use_fc, interleave=interleave,
            pallas_mode="per-arc" if use_pallas else "off",
            max_iters=None, batched=True,
        )
    bits, sat = jax.block_until_ready(fn(tgt, stacked))
    bits = _np.asarray(bits)
    sat = _np.asarray(sat)
    return [
        DomainResult(bits[i, : p.n].copy(), bool(sat[i]))
        for i, p in enumerate(patterns)
    ]
