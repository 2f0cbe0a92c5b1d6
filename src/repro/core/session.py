"""Prepared-query session API for subgraph enumeration.

The paper's workloads are collections of *thousands* of patterns per target
(PPIS32: 420, PDBSv1: 1760 queries).  The one-shot
:func:`repro.core.api.enumerate_subgraphs` re-packs the target, rebuilds the
plan and re-traces the engine on every call; this module is the
session-oriented surface that amortizes all three:

* :class:`SubgraphIndex` — a prepared target: the :class:`PackedGraph`
  bitmaps plus label/degree metadata, built once, reusable across queries
  and picklable (pure numpy — ship it to another process, load it in a
  server).
* :class:`Query` — a pattern compiled against an index into a
  :class:`SearchPlan` whose padding is snapped to **shape buckets**
  (``p_pad ∈ {16, 32, 64, 128}``, fixed ``max_parents``), so thousands of
  patterns lower to a handful of XLA compilations.
* :class:`Enumerator` — the session object: an :class:`EngineConfig`, an
  optional device mesh (``mesh=`` shards the worker axis over the mesh
  ``data`` axis via ``shard_map``; ``n_workers`` snaps up to a multiple of
  the device count — see DESIGN.md §2.4), a keyed compile cache ``(kind,
  mesh signature, p_pad, max_parents, n_t, w, …) → jitted engine`` with
  ``compiles`` / ``cache_hits`` counters, and three execution methods
  sharing one code path:

    - ``run(query)``                 — one query, one engine invocation;
    - ``run_batch(queries)``         — LPT-balanced vmapped packs (the
      former ``core/multi.py`` driver), exactly one result per query, in
      input order;
    - ``stream(queries)``            — generator yielding a
      :class:`MatchSet` per query as packs drain (the serving path).

  Preprocessing batches too (DESIGN.md §5): ``prepare_batch(patterns)``
  runs the AC ⇄ FC domain fixpoint for a whole padded pattern batch as one
  vmapped jitted call on device, keyed into the same compile cache; raw
  ``Graph`` inputs to ``run_batch``/``stream`` route through it
  automatically (``domain_backend='numpy'`` restores the host loop).

Results unify into :class:`MatchSet`: counts, per-worker statistics, and
lazy match materialization (``mappings()`` re-runs the prepared query with
a match buffer only when asked).

Typical use::

    index = SubgraphIndex.build(target)             # once per target
    enum = Enumerator(index, n_workers=16)          # once per session
    q = enum.prepare(pattern)                       # per pattern (cheap)
    ms = enum.run(q)                                # engine reused
    for ms in enum.stream([enum.prepare(p) for p in patterns]):
        print(ms.name, ms.matches)
    enum.cache_info()   # {'compiles': 1, 'cache_hits': 419, ...}
"""

from __future__ import annotations

import collections
import dataclasses
import functools
import hashlib
import threading
import time
import warnings
from typing import Callable, Dict, Iterable, Iterator, List, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro import trace
from repro.core import delta as delta_mod
from repro.core import domains as dom_mod
from repro.core import engine as eng
from repro.core import extend
from repro.core import frontier
from repro.core.delta import DeltaMatchSet, GraphDelta
from repro.core.engine import EngineConfig, EngineResult
from repro.core.graph import (
    WORD_BITS,
    CsrPlanes,
    CsrPlaneSet,
    Graph,
    PackedGraph,
    bitmap_to_indices,
    n_words,
    popcount,
)
from repro.core.plan import SearchPlan, build_csr_plan, build_plan, variant_flags
from repro.core.scheduler import balance_assignment

# Padded pattern-position buckets: every plan's ``p_pad`` snaps up to one of
# these, so patterns of size 3..16 share one engine compilation, 17..32 the
# next, and so on.  Beyond the last bucket we round up to multiples of it.
SHAPE_BUCKETS: Tuple[int, ...] = (16, 32, 64, 128)

# Fixed parent-slot padding for bucketed plans (the ordering expands it when
# a dense pattern genuinely needs more; that pattern then lands in its own —
# rare — bucket).
DEFAULT_MAX_PARENTS = 8

# Cap on the lazily materialized match buffer (per worker).
_MATERIALIZE_CAP = 1 << 17


def snap_p_pad(n_p: int) -> int:
    """Smallest shape bucket that holds ``n_p`` pattern positions."""
    for b in SHAPE_BUCKETS:
        if n_p <= b:
            return b
    top = SHAPE_BUCKETS[-1]
    return ((n_p + top - 1) // top) * top


def snap_arc_pad(n_arcs: int) -> int:
    """Arc-slot bucket for the device domain engine: multiples of 8."""
    return max(8, ((n_arcs + 7) // 8) * 8)


def snap_loop_pad(n_loops: int) -> int:
    """Self-loop-slot bucket: 1 (the loop-free common case) or multiples
    of 4."""
    return 1 if n_loops == 0 else ((n_loops + 3) // 4) * 4


def _match_count(old) -> int:
    """Prior-match count without materializing mappings: a MatchSet-like
    object carries it as ``.matches`` (an int); anything else is a
    sequence of mappings."""
    m = getattr(old, "matches", None)
    if isinstance(m, int):
        return m
    try:
        return len(old)
    except TypeError:
        return len(list(old))


def snap_batch_pad(n: int) -> int:
    """Pattern-batch lane bucket: next power of two (inert lanes replicate
    lane 0 and are discarded), so B patterns cost O(log B) compilations."""
    return 1 << max(n - 1, 0).bit_length()


# ---------------------------------------------------------------------------
# SubgraphIndex — a prepared target
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class SubgraphIndex:
    """A target graph prepared for repeated querying.

    Holds the packed adjacency bitmaps plus the label/degree metadata the
    preprocessing (domains, ordering) consults.  Pure numpy — picklable and
    shareable across processes; build once per target, reuse for every
    pattern.

    Indexes are **versioned** (DESIGN.md §8): :meth:`update` produces a new
    index with incrementally patched bitmaps/CSR planes, ``version + 1``,
    and a content ``fingerprint`` chained through the edit — the
    fingerprint keys engine-compile caches and serving coalesce buckets, so
    a post-update run can never alias a stale compiled plan.
    """

    packed: PackedGraph
    n_labels: int
    label_counts: np.ndarray  # [n_labels] int64
    max_degree: int
    build_s: float
    version: int = 0
    fingerprint: str = ""
    # CSR-only index (DESIGN.md §11): build(target, sparse=True) never
    # materializes the dense adjacency bitmaps — ``packed`` is a metadata
    # shell whose ``adj_bits`` has a zero node axis, ``graph`` retains the
    # host Graph for CSR-native preprocessing, and plans built against the
    # index come from build_csr_plan (only the csr/auto/partitioned step
    # backends can run them).
    sparse: bool = False
    graph: Optional[Graph] = dataclasses.field(
        default=None, repr=False, compare=False
    )
    # lazily built sparse adjacency, shared across versions per plane
    # (update() patches only touched planes — see graph.CsrPlaneSet)
    _plane_set: Optional[CsrPlaneSet] = dataclasses.field(
        default=None, repr=False, compare=False
    )
    _csr_flat: Optional[CsrPlanes] = dataclasses.field(
        default=None, repr=False, compare=False
    )

    @staticmethod
    def build(
        target: Union[Graph, PackedGraph, "SubgraphIndex"],
        sparse: bool = False,
    ) -> "SubgraphIndex":
        if isinstance(target, SubgraphIndex):
            return target
        if sparse:
            return SubgraphIndex._build_sparse(target)
        t0 = time.perf_counter()
        packed = target if isinstance(target, PackedGraph) else PackedGraph.from_graph(target)
        n_labels = int(packed.labels.max()) + 1 if packed.n else 0
        counts = np.bincount(packed.labels, minlength=max(n_labels, 1)).astype(np.int64)
        degs = packed.deg_out + packed.deg_in
        max_deg = int(degs.max()) if packed.n else 0
        return SubgraphIndex(
            packed=packed,
            n_labels=n_labels,
            label_counts=counts,
            max_degree=max_deg,
            build_s=time.perf_counter() - t0,
            version=0,
            fingerprint=_fingerprint_packed(packed),
        )

    @staticmethod
    def _build_sparse(target: Graph) -> "SubgraphIndex":
        """CSR-only index of a host :class:`Graph`: the packed form is a
        metadata shell (labels/degrees plus an ``adj_bits`` placeholder with
        a zero node axis) and the canonical :class:`CsrPlanes` are built
        eagerly — they *are* the adjacency."""
        if not isinstance(target, Graph):
            raise TypeError(
                "SubgraphIndex.build(sparse=True) needs a host Graph — a "
                f"{type(target).__name__} has already materialized (or "
                "implies) the dense bitmaps"
            )
        t0 = time.perf_counter()
        w = n_words(target.n)
        nl = target.n_edge_labels
        planes = target.csr_planes(nl)
        labels = np.asarray(target.labels, dtype=np.int32)
        packed = PackedGraph(
            n=target.n,
            w=w,
            adj_bits=np.zeros((nl, 2, 0, w), dtype=np.uint32),
            labels=labels,
            deg_out=target.out_degrees(),
            deg_in=target.in_degrees(),
        )
        n_labels = int(labels.max()) + 1 if target.n else 0
        counts = np.bincount(labels, minlength=max(n_labels, 1)).astype(np.int64)
        degs = packed.deg_out + packed.deg_in
        return SubgraphIndex(
            packed=packed,
            n_labels=n_labels,
            label_counts=counts,
            max_degree=int(degs.max()) if target.n else 0,
            build_s=time.perf_counter() - t0,
            version=0,
            fingerprint=_fingerprint_sparse(planes, labels, target.n, w),
            sparse=True,
            graph=target,
            _csr_flat=planes,
        )

    @property
    def n(self) -> int:
        return self.packed.n

    @property
    def w(self) -> int:
        return self.packed.w

    @property
    def n_edge_labels(self) -> int:
        return self.packed.n_edge_labels

    # -- sparse adjacency (shared with plans via SearchPlan.csr_factory) ---

    def plane_set(self) -> CsrPlaneSet:
        """Per-plane CSR adjacency, built lazily and patched (not rebuilt)
        by :meth:`update` — untouched planes share buffers across versions."""
        if self.sparse:
            raise ValueError(
                "sparse SubgraphIndex has no per-plane set derived from "
                "dense bitmaps; use csr_planes() for the flat adjacency"
            )
        if self._plane_set is None:
            object.__setattr__(
                self, "_plane_set", CsrPlaneSet.from_bitmaps(self.packed.adj_bits)
            )
        return self._plane_set

    def csr_planes(self) -> CsrPlanes:
        """Canonical flat :class:`CsrPlanes` of this index version (cached);
        plans built against this index consume it through their
        ``csr_factory`` so the csr step backend never re-derives planes from
        the dense bitmaps."""
        if self._csr_flat is None:
            object.__setattr__(self, "_csr_flat", self.plane_set().to_planes())
        return self._csr_flat

    # -- incremental update (DESIGN.md §8) ---------------------------------

    def update(
        self,
        add_edges: Iterable = (),
        remove_edges: Iterable = (),
    ) -> Tuple["SubgraphIndex", GraphDelta]:
        """Apply an edge edit, returning ``(new_index, delta)``.

        Edits are ``(u, v)`` or ``(u, v, elab)`` arc triples with set
        semantics: duplicate inserts and removals of absent arcs are
        dropped, and an arc both inserted and removed in the *same* call
        cancels before anything is applied (no-op delta ≡ empty).  A true
        no-op returns ``self`` unchanged (same object, same version).

        The new index patches copies of the dense bitmaps in place (bit
        flips on touched rows), re-sorts only the touched rows of the
        touched CSR planes (untouched planes share buffers by reference),
        recomputes degrees for touched nodes only, and shares the label
        arrays.  Node set and node labels are immutable; inserting an arc
        with a new edge label grows the plane axis.

        Degrees are recomputed from the patched bitmaps, i.e. as
        *distinct-arc* counts — for an index built from an arc list with
        duplicates (``Graph.from_edges(undirected=True)`` doubles
        self-loop arcs) a touched node's degree normalizes to its
        distinct count.  Both counts are sound for the domain filters;
        build from a deduped arc list when exact degree parity with a
        fresh build matters.
        """
        if self.sparse:
            raise NotImplementedError(
                "incremental update of a sparse (CSR-only) SubgraphIndex is "
                "not supported — rebuild with SubgraphIndex.build(graph, "
                "sparse=True), or build a dense index when deltas are needed"
            )
        t0 = time.perf_counter()
        adds = delta_mod.normalize_edges(add_edges)
        rems = delta_mod.normalize_edges(remove_edges)
        cancel = set(adds) & set(rems)
        packed = self.packed
        n, w, nl = packed.n, packed.w, packed.n_edge_labels
        for (u, v, l) in tuple(adds) + tuple(rems):
            if not (0 <= u < n and 0 <= v < n):
                raise ValueError(f"edit arc ({u}, {v}) out of range for n={n}")
            if l < 0:
                raise ValueError(f"negative edge label {l}")

        def present(t) -> bool:
            u, v, l = t
            if l >= nl:
                return False
            return bool((int(packed.adj_bits[l, 0, u, v // WORD_BITS])
                         >> (v % WORD_BITS)) & 1)

        eff_add = tuple(t for t in adds if t not in cancel and not present(t))
        eff_rem = tuple(t for t in rems if t not in cancel and present(t))
        if not eff_add and not eff_rem:
            return self, GraphDelta(
                added=(), removed=(),
                old_version=self.version, new_version=self.version,
                old_fingerprint=self.fingerprint,
                new_fingerprint=self.fingerprint,
            )

        nl_new = max(nl, 1 + max((l for (_, _, l) in eff_add), default=-1))
        if nl_new > nl:
            adj = np.zeros((nl_new, 2, n, w), dtype=np.uint32)
            adj[:nl] = packed.adj_bits
        else:
            adj = packed.adj_bits.copy()
        for (u, v, l) in eff_add:
            adj[l, 0, u, v // WORD_BITS] |= np.uint32(1) << np.uint32(v % WORD_BITS)
            adj[l, 1, v, u // WORD_BITS] |= np.uint32(1) << np.uint32(u % WORD_BITS)
        for (u, v, l) in eff_rem:
            adj[l, 0, u, v // WORD_BITS] &= ~(np.uint32(1) << np.uint32(v % WORD_BITS))
            adj[l, 1, v, u // WORD_BITS] &= ~(np.uint32(1) << np.uint32(u % WORD_BITS))

        # degrees: recompute touched endpoints from the patched bitmaps
        # (set semantics — identical to a fresh build of the edited graph)
        deg_out = packed.deg_out.copy()
        deg_in = packed.deg_in.copy()
        touched_src = np.fromiter(
            {u for (u, _, _) in eff_add + eff_rem}, dtype=np.int64)
        touched_dst = np.fromiter(
            {v for (_, v, _) in eff_add + eff_rem}, dtype=np.int64)
        if len(touched_src):
            deg_out[touched_src] = popcount(adj[:, 0, touched_src, :]).sum(axis=0)
        if len(touched_dst):
            deg_in[touched_dst] = popcount(adj[:, 1, touched_dst, :]).sum(axis=0)

        new_packed = PackedGraph(
            n=n, w=w, adj_bits=adj, labels=packed.labels,
            deg_out=deg_out, deg_in=deg_in,
        )

        # CSR plane set: patch only touched (plane, row) pairs; untouched
        # plane buffers are shared by reference (satellite aliasing test)
        new_plane_set = None
        if self._plane_set is not None:
            rows_of: Dict[int, Dict[int, np.ndarray]] = {}
            for (u, v, l) in eff_add + eff_rem:
                rows_of.setdefault(l * 2, {})[u] = None
                rows_of.setdefault(l * 2 + 1, {})[v] = None
            for p, rows in rows_of.items():
                for r in rows:
                    rows[r] = bitmap_to_indices(adj[p // 2, p % 2, r])
            new_plane_set = self.plane_set().grown(2 * nl_new).patched(rows_of)

        h = hashlib.blake2b(digest_size=16)
        h.update(self.fingerprint.encode())
        h.update(repr((eff_add, eff_rem)).encode())
        new_fp = h.hexdigest()

        degs = deg_out + deg_in
        new_index = SubgraphIndex(
            packed=new_packed,
            n_labels=self.n_labels,
            label_counts=self.label_counts,
            max_degree=int(degs.max()) if n else 0,
            build_s=time.perf_counter() - t0,
            version=self.version + 1,
            fingerprint=new_fp,
            _plane_set=new_plane_set,
        )
        delta = GraphDelta(
            added=eff_add,
            removed=eff_rem,
            old_version=self.version,
            new_version=new_index.version,
            old_fingerprint=self.fingerprint,
            new_fingerprint=new_fp,
        )
        return new_index, delta


def _fingerprint_packed(packed: PackedGraph) -> str:
    """Content fingerprint of a packed target: shapes + adjacency bits +
    node labels.  Chain-extended by :meth:`SubgraphIndex.update` so every
    index version has a distinct, deterministic identity."""
    h = hashlib.blake2b(digest_size=16)
    h.update(repr((packed.n, packed.w, packed.adj_bits.shape)).encode())
    h.update(np.ascontiguousarray(packed.adj_bits).tobytes())
    h.update(np.ascontiguousarray(packed.labels).tobytes())
    return h.hexdigest()


def _fingerprint_sparse(planes: CsrPlanes, labels: np.ndarray, n: int, w: int) -> str:
    """Content fingerprint of a sparse (CSR-only) index: shapes + CSR
    adjacency + node labels — same role as :func:`_fingerprint_packed`
    without ever touching dense bitmaps."""
    h = hashlib.blake2b(digest_size=16)
    h.update(repr((n, w, planes.n_planes, planes.nnz, "csr")).encode())
    h.update(np.ascontiguousarray(planes.indptr).tobytes())
    h.update(np.ascontiguousarray(planes.indices).tobytes())
    h.update(np.ascontiguousarray(labels).tobytes())
    return h.hexdigest()


# ---------------------------------------------------------------------------
# Query — a pattern compiled against an index
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Query:
    """A pattern prepared against a :class:`SubgraphIndex`.

    ``plan`` is padded to a shape bucket so that same-bucket queries share
    one jitted engine inside an :class:`Enumerator`.
    """

    pattern: Graph
    plan: SearchPlan
    variant: str
    name: str
    prepare_s: float
    # The index this query was prepared against (None for hand-built
    # queries): run_delta needs it for anchor plans, and its fingerprint
    # versions the engine-cache / coalesce keys (DESIGN.md §8).
    index: Optional[SubgraphIndex] = dataclasses.field(default=None, repr=False)
    # per-anchor plan cache for run_delta: {(pa, pb, elab): SearchPlan}
    _anchors: Dict[Tuple[int, int, int], SearchPlan] = dataclasses.field(
        default_factory=dict, repr=False, compare=False
    )
    _anchor_domains: Optional[dom_mod.DomainResult] = dataclasses.field(
        default=None, repr=False, compare=False
    )

    @property
    def bucket(self) -> Tuple[int, int, int, int, int]:
        """The compile-cache shape key: (p_pad, max_parents, n_t, w, n_elab).

        Shape-only on purpose — same-shape queries against one index share
        compiled engines; the *content* identity rides separately as
        :attr:`index_fingerprint` in the engine-cache and coalesce keys.
        """
        p = self.plan
        return (p.p_pad, p.max_parents, p.n_t, p.w, p.n_edge_labels)

    @property
    def index_fingerprint(self) -> str:
        """Fingerprint of the index version this query binds to ("" for
        hand-built queries with no index)."""
        return self.index.fingerprint if self.index is not None else ""

    @property
    def satisfiable(self) -> bool:
        return self.plan.satisfiable


def prepare_query(
    pattern: Graph,
    index: Union[SubgraphIndex, Graph, PackedGraph],
    variant: str = "ri-ds-si-fc",
    name: Optional[str] = None,
    p_pad: Optional[int] = None,
    max_parents: Optional[int] = None,
    seed_edge=None,
    use_pallas: bool = False,
) -> Query:
    """Compile ``pattern`` against ``index`` into a bucketed :class:`Query`.

    ``seed_edge`` (``"auto"`` or an explicit ``(u, v, elab)`` pattern-edge
    triple) enables edge-centric root seeding (DESIGN.md §10): the plan
    anchors the edge's endpoints at positions 0/1 so engines with
    ``root_seeding="edge"``/``"auto"`` can seed from the rare target edge
    class.  Selection reuses the index's cached CSR planes.

    Preparation routes by the index layout (DESIGN.md §11): a **sparse**
    index (``SubgraphIndex.build(graph, sparse=True)``) compiles through
    :func:`~repro.core.plan.build_csr_plan` — domains come from the
    CSR-native fixpoint (through the `csr_arc_sweep` kernel when
    ``use_pallas``) and the resulting plan is CSR-only.
    """
    index = SubgraphIndex.build(index)
    t0 = time.perf_counter()
    if index.sparse:
        plan = build_csr_plan(
            pattern,
            index.graph,
            variant=variant,
            p_pad=p_pad if p_pad is not None else snap_p_pad(pattern.n),
            max_parents=max_parents if max_parents is not None else DEFAULT_MAX_PARENTS,
            w=index.w,
            seed_edge=seed_edge,
            planes=index.csr_planes(),
            use_pallas=use_pallas,
        )
    else:
        plan = build_plan(
            pattern,
            index.packed,
            variant=variant,
            p_pad=p_pad if p_pad is not None else snap_p_pad(pattern.n),
            max_parents=max_parents if max_parents is not None else DEFAULT_MAX_PARENTS,
            csr_factory=index.csr_planes,
            seed_edge=seed_edge,
        )
    return Query(
        pattern=pattern,
        plan=plan,
        variant=variant,
        name=name or _default_name(pattern),
        prepare_s=time.perf_counter() - t0,
        index=index,
    )


def _default_name(pattern: Graph) -> str:
    """Default query name, shared by prepare_query and prepare_batch."""
    return f"q{pattern.n}n{pattern.m}m"


# ---------------------------------------------------------------------------
# MatchSet — the unified result
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class MatchSet:
    """Result of enumerating one query: counts, per-worker stats, lazy matches."""

    name: str
    query_index: int
    matches: int
    states: int
    steps: int
    steals: int
    steal_rounds: int
    mean_steal_depth: float
    mean_expand_depth: float
    per_worker_states: Optional[np.ndarray]
    per_worker_matches: Optional[np.ndarray]
    per_worker_steals: Optional[np.ndarray]
    preprocess_s: float
    match_s: float
    plan: SearchPlan
    engine: EngineResult
    retries: int = 0  # overflow retries spent (stack_cap doubled each time)
    _match_buf: Optional[np.ndarray] = dataclasses.field(default=None, repr=False)
    _materialize: Optional[Callable[[], Optional[np.ndarray]]] = dataclasses.field(
        default=None, repr=False
    )
    _mappings: Optional[List[Tuple[int, ...]]] = dataclasses.field(default=None, repr=False)

    @property
    def total_s(self) -> float:
        return self.preprocess_s + self.match_s

    def mappings(self) -> List[Tuple[int, ...]]:
        """Materialized match mappings (order position -> target node).

        Lazy: if the engine ran in counting mode (the benchmarked mode), the
        prepared query is re-run once with a match buffer sized to hold every
        match; the result is cached on the MatchSet.
        """
        if self._mappings is not None:
            return self._mappings
        if self.matches == 0:
            self._mappings = []
            return self._mappings
        if self.matches > _MATERIALIZE_CAP and self._match_buf is None:
            raise RuntimeError(
                f"{self.matches} matches exceed the materialization cap "
                f"({_MATERIALIZE_CAP}); re-run with an explicit "
                "collect_matches budget and consume engine.match_buf directly"
            )
        buf = self._match_buf
        if buf is None and self._materialize is not None:
            buf = self._materialize()
        out: List[Tuple[int, ...]] = []
        if buf is not None:
            n_p = self.plan.n_p
            rows = buf.reshape(-1, buf.shape[-1])[:, :n_p]
            valid = (rows >= 0).all(axis=1)
            out = [tuple(int(x) for x in r) for r in rows[valid]]
        self._mappings = out
        return out


def _empty_engine_result() -> EngineResult:
    return EngineResult(
        matches=0, states=0, steps=0, steals=0, steal_rounds=0,
        mean_steal_depth=0.0, mean_expand_depth=0.0,
        per_worker_states=None, per_worker_matches=None,
        overflow=False, match_buf=None,
    )


# ---------------------------------------------------------------------------
# Enumerator — the session
# ---------------------------------------------------------------------------

class Enumerator:
    """A subgraph-enumeration session with a shape-bucketed compile cache.

    Holds an :class:`EngineConfig` and a dict of jitted engines keyed by
    ``(cfg, kind, pack, bucket)``.  All three execution methods go through
    the same cache, so any mix of ``run`` / ``run_batch`` / ``stream`` over
    same-bucket queries costs at most one compilation per (kind, pack
    width).  ``compiles`` and ``cache_hits`` counters let benchmarks prove
    recompilation is gone.

    ``Enumerator(..., step_backend="auto")`` defers the expansion-backend
    choice to the target size: queries against targets beyond
    ``extend.CSR_AUTO_NT`` (32,768) nodes run the sparse CSR backend
    (DESIGN.md §6.4), smaller ones the dense ``jnp`` step.  An explicit
    ``step_backend=`` always wins.  The cache key carries the cfg *and*
    the bucket's ``n_t``, so one session can mix resolutions without
    collisions.

    ``Enumerator(..., memory_budget_bytes=N)`` selects the **out-of-core
    partitioned** backend (DESIGN.md §9): each target is row-partitioned
    into the smallest count whose padded resident planes fit ``N`` bytes,
    and enumeration streams the partitions through the device
    (``step_backend="partitioned"`` with ``EngineConfig.n_partitions``
    picks the count explicitly instead).  Results are bit-identical to the
    monolithic backends; compile-cache and coalesce keys carry the
    partition identity, and :meth:`warm` pre-traces hot buckets.
    """

    def __init__(
        self,
        index: Union[SubgraphIndex, Graph, PackedGraph, None] = None,
        config: Optional[EngineConfig] = None,
        variant: str = "ri-ds-si-fc",
        mesh: Union["jax.sharding.Mesh", int, None] = None,
        domain_backend: str = "device",
        max_cache_entries: int = 0,
        memory_budget_bytes: Optional[int] = None,
        **config_kwargs,
    ):
        cfg = config or EngineConfig(**config_kwargs)
        if config is not None and config_kwargs:
            cfg = dataclasses.replace(config, **config_kwargs)
        if memory_budget_bytes is not None:
            if memory_budget_bytes <= 0:
                raise ValueError(
                    f"memory_budget_bytes must be positive, got {memory_budget_bytes}"
                )
            # an explicit budget implies the out-of-core backend: the
            # partition count is derived per target so the resident padded
            # planes fit the budget (DESIGN.md §9)
            cfg = dataclasses.replace(cfg, step_backend="partitioned")
        self.memory_budget_bytes = memory_budget_bytes
        self.mesh = _coerce_mesh(mesh)
        if self.mesh is not None:
            axis = eng.mesh_worker_axis(self.mesh)
            n_dev = int(self.mesh.shape[axis])
            if cfg.n_workers % n_dev:
                # snap up so every device owns the same number of stacks
                cfg = dataclasses.replace(
                    cfg, n_workers=((cfg.n_workers + n_dev - 1) // n_dev) * n_dev
                )
        if domain_backend not in ("device", "numpy"):
            raise ValueError(
                f"domain_backend must be 'device' or 'numpy', got {domain_backend!r}"
            )
        self.config = cfg
        self.variant = variant
        self.domain_backend = domain_backend
        if max_cache_entries < 0:
            raise ValueError(f"max_cache_entries must be >= 0, got {max_cache_entries}")
        self.max_cache_entries = max_cache_entries
        self.index = SubgraphIndex.build(index) if index is not None else None
        # LRU-ordered compile cache: hits move entries to the back, inserts
        # evict from the front once max_cache_entries is exceeded (0 = no
        # bound — batch scripts; servers set a bound, DESIGN.md §7).
        self._engines: "collections.OrderedDict[tuple, Callable]" = collections.OrderedDict()
        # shape-keyed XLA trace pool backing the fingerprinted entries in
        # _engines: index versions of one shape share a single trace
        # (bounded by shape diversity, not by version count)
        self._traces: Dict[tuple, Callable] = {}
        # entries per trace shape: LRU-evicting the last entry of a shape
        # drops its trace too, so max_cache_entries still bounds compiled
        # memory.  invalidate_index decrements but keeps zero-ref traces:
        # an index update never changes array shapes (n is immutable), so
        # the next version re-uses the trace immediately — dropping it
        # there would recreate the per-version retrace the pool exists to
        # avoid (DESIGN.md §8).
        self._trace_refs: Dict[tuple, int] = {}
        # sticky high-water match-buffer size for seeded delta runs (see
        # _run_seeded): grow-retries fold into one steady-state shape
        self._delta_mcap = self._DELTA_MCAP
        # device-resident adjacency bitmaps keyed by index fingerprint:
        # the dominant host→device transfer, shared by a version's query
        # plan and every delta anchor plan (kept to the two most recent
        # versions — old + new during an update handoff)
        self._adj_device: "collections.OrderedDict[str, jnp.ndarray]" = (
            collections.OrderedDict()
        )
        # guards _engines: the serving dispatcher thread runs engines while
        # service.update_index() invalidates stale entries from a client
        # thread (DESIGN.md §8)
        self._cache_lock = threading.Lock()
        # target-side device arrays for batched domain preprocessing, keyed
        # by the packed target's identity (pinned so ids can't be recycled);
        # values are dense TargetDomainArrays or CsrTargetDomainArrays per
        # the index layout
        self._dom_targets: Dict[int, Tuple[PackedGraph, tuple]] = {}
        self.compiles = 0
        self.cache_hits = 0
        self.evictions = 0

    # -- cache -------------------------------------------------------------

    def cache_stats(self) -> Dict[str, int]:
        """Compile-cache counters: ``compiles`` / ``cache_hits`` /
        ``evictions`` plus current ``entries`` and the configured
        ``max_entries`` bound (0 = unbounded).  The serving metrics layer
        snapshots this to report cache hit rate."""
        return {
            "compiles": self.compiles,
            "cache_hits": self.cache_hits,
            "evictions": self.evictions,
            "entries": len(self._engines),
            "max_entries": self.max_cache_entries,
        }

    # kept name from PR 1; same counters, cache_stats() is the full view
    cache_info = cache_stats

    def _cache_put(self, key: tuple, fn: Callable) -> None:
        """Insert a jitted engine, LRU-evicting past ``max_cache_entries``."""
        with self._cache_lock:
            if key not in self._engines:
                sk = key[:-1]
                self._trace_refs[sk] = self._trace_refs.get(sk, 0) + 1
            self._engines[key] = fn
            if self.max_cache_entries:
                while len(self._engines) > self.max_cache_entries:
                    old_key, _ = self._engines.popitem(last=False)
                    self.evictions += 1
                    self._release_trace_locked(old_key, drop_if_unused=True)

    def _release_trace_locked(self, key: tuple, drop_if_unused: bool) -> None:
        """One engine-cache entry for ``key`` went away; decrement its
        trace shape's refcount and (for LRU eviction) drop an unreferenced
        trace so the entry bound still bounds compiled memory."""
        sk = key[:-1]
        n = self._trace_refs.get(sk, 0) - 1
        if n > 0:
            self._trace_refs[sk] = n
        else:
            self._trace_refs.pop(sk, None)
            if drop_if_unused:
                self._traces.pop(sk, None)

    def _cache_get(self, key: tuple) -> Optional[Callable]:
        with self._cache_lock:
            fn = self._engines.get(key)
            if fn is not None:
                self._engines.move_to_end(key)
                self.cache_hits += 1
            return fn

    def invalidate_index(self, fingerprint: str) -> int:
        """Drop every compile-cache entry keyed to ``fingerprint`` (an
        index version retired by ``SubgraphIndex.update``) and return the
        number dropped.  The serving layer calls this on index swap so
        stale engines stop occupying the LRU; correctness never depends on
        it — the fingerprint in the key already prevents false hits."""
        if not fingerprint:
            return 0
        with self._cache_lock:
            stale = [k for k in self._engines if fingerprint in k]
            for k in stale:
                del self._engines[k]
                # keep zero-ref traces: the successor version has the same
                # shapes and re-uses them without a retrace
                self._release_trace_locked(k, drop_if_unused=False)
            self._adj_device.pop(fingerprint, None)
            return len(stale)

    def _engine_fn(self, cfg: EngineConfig, kind: str, pack: int, query: Query,
                   seed_shape: tuple = ()) -> Callable:
        # layout check first: an explicitly dense backend against a
        # CSR-only plan must raise *before* a compile is spent/counted
        extend.validate_backend_for_plan(cfg, query.plan)
        shape_key = ((cfg, kind, pack, eng.mesh_signature(self.mesh))
                     + query.bucket + seed_shape)
        resolved = eng.resolve_step_backend_for_plan(cfg, query.plan)
        if resolved == "csr":
            # csr plan arrays carry density-dependent shapes (deg_cap, nnz);
            # without them in the key, a same-bucket different-density query
            # would count as a cache hit while jit silently retraces
            shape_key = shape_key + extend.csr_shape_bucket(query.plan)
        elif resolved == "partitioned":
            # partition identity: same-bucket targets with different
            # partitionings (count or padded per-partition shapes) must not
            # share a compiled partitioned engine
            shape_key = shape_key + extend.partitioned_shape_bucket(
                query.plan, max(1, cfg.n_partitions)
            )
        # the trailing fingerprint versions the entry to one index content:
        # after an index update, same-shape queries get a fresh entry (no
        # false hit on a retired version, and retired versions can be
        # evicted by invalidate_index — see the incremental conformance
        # suite).  The engine itself is content-agnostic (plan arrays are
        # call arguments), so entries for different versions of one shape
        # share a single XLA trace from the pool below — an update never
        # re-traces, which is what keeps run_delta's per-version cost
        # proportional to the delta (DESIGN.md §8).
        key = shape_key + (query.index_fingerprint,)
        fn = self._cache_get(key)
        if fn is not None:
            return fn
        with self._cache_lock:
            fn = self._traces.get(shape_key)
        if fn is None:
            self.compiles += 1
            if kind == "part":
                fn = eng.make_partitioned_engine_fn(cfg, self.mesh)
            elif kind == "single":
                if self.mesh is not None:
                    fn = eng.make_sharded_engine_fn(
                        cfg, self.mesh, n_t=query.plan.n_t,
                        csr_only=eng.is_csr_only(query.plan),
                    )
                else:
                    loop = functools.partial(eng._engine_loop, cfg)
                    loop.__name__ = "_engine_loop"  # device program jit__engine_loop
                    fn = jax.jit(loop)
            else:
                fn = eng.make_pack_engine_fn(cfg, query.plan.p_pad)
            with self._cache_lock:
                self._traces[shape_key] = fn
        self._cache_put(key, fn)
        return fn

    # -- preparation -------------------------------------------------------

    def prepare(
        self,
        pattern: Graph,
        variant: Optional[str] = None,
        name: Optional[str] = None,
        index: Union[SubgraphIndex, Graph, PackedGraph, None] = None,
        seed_edge=None,
    ) -> Query:
        """Compile a pattern into a bucketed :class:`Query` for this session.

        ``seed_edge`` is forwarded to :func:`prepare_query` (edge-centric
        seeding, DESIGN.md §10).  A sparse index yields a CSR-only plan;
        if this session's step backend is explicitly dense
        (``"jnp"``/``"pallas"``), that combination can never run, so it
        raises here — before any engine is compiled."""
        idx = index if index is not None else self.index
        if idx is None:
            raise ValueError(
                "Enumerator has no default SubgraphIndex; pass index= to "
                "prepare() or construct Enumerator(index, ...)"
            )
        q = prepare_query(
            pattern, idx, variant=variant or self.variant, name=name,
            seed_edge=seed_edge, use_pallas=self.config.use_pallas,
        )
        extend.validate_backend_for_plan(self.config, q.plan)
        return q

    def prepare_batch(
        self,
        patterns: Sequence[Graph],
        variant: Optional[str] = None,
        names: Optional[Sequence[str]] = None,
        index: Union[SubgraphIndex, Graph, PackedGraph, None] = None,
        backend: Optional[str] = None,
    ) -> List[Query]:
        """Prepare a batch of patterns with **device-resident** domain
        preprocessing (DESIGN.md §5): patterns are grouped by domain shape
        bucket ``(p_pad, arc_pad, loop_pad)``, each group's AC ⇄ FC fixpoint
        runs as **one vmapped jitted call** (padded to a power-of-two lane
        count), and the jitted fixpoints are keyed into this session's
        compile cache alongside the engines.  Results are bit-identical to
        per-query :meth:`prepare` (the numpy oracle) — only the wall-clock
        changes.  ``backend='numpy'`` (or ``Enumerator(domain_backend=
        'numpy')``) falls back to per-query host preprocessing.

        A **sparse** index routes the same grouped fixpoint through the
        CSR-layout target arrays (DESIGN.md §11) and assembles CSR-only
        plans — dense adjacency bitmaps never exist on host or device.
        """
        idx = index if index is not None else self.index
        if idx is None:
            raise ValueError(
                "Enumerator has no default SubgraphIndex; pass index= to "
                "prepare_batch() or construct Enumerator(index, ...)"
            )
        idx = SubgraphIndex.build(idx)
        variant = variant or self.variant
        patterns = list(patterns)
        if names is not None and len(names) != len(patterns):
            raise ValueError(
                f"names has {len(names)} entries for {len(patterns)} patterns"
            )
        name_of = lambda i, p: (
            names[i] if names is not None else _default_name(p)
        )
        backend = backend or self.domain_backend
        if backend == "numpy":
            return [
                self.prepare(p, variant=variant, name=name_of(i, p), index=idx)
                for i, p in enumerate(patterns)
            ]

        flags = variant_flags(variant)
        groups: Dict[tuple, List[int]] = {}
        for i, p in enumerate(patterns):
            n_p, n_a, n_l = dom_mod.domain_bucket(p)
            key = (snap_p_pad(n_p), snap_arc_pad(n_a), snap_loop_pad(n_l))
            groups.setdefault(key, []).append(i)

        out: List[Optional[Query]] = [None] * len(patterns)
        tgt_arrays = self._target_domain_arrays(idx)
        for (p_pad, a_pad, l_pad), idxs in groups.items():
            b_pad = snap_batch_pad(len(idxs))
            fn = self._domain_fn(flags, b_pad, p_pad, a_pad, l_pad, idx)
            t0 = time.perf_counter()
            doms = dom_mod.compute_domains_batch(
                [patterns[i] for i in idxs],
                idx.packed,
                use_ac=flags["use_ac"],
                use_fc=flags["use_fc"],
                interleave=flags["interleave"],
                use_pallas=self.config.use_pallas,
                p_pad=p_pad,
                arc_pad=a_pad,
                loop_pad=l_pad,
                batch_pad=b_pad,
                tgt_arrays=tgt_arrays,
                fn=fn,
            )
            dom_s = (time.perf_counter() - t0) / max(len(idxs), 1)
            for i, dres in zip(idxs, doms):
                t1 = time.perf_counter()
                if idx.sparse:
                    plan = build_csr_plan(
                        patterns[i],
                        idx.graph,
                        variant=variant,
                        p_pad=snap_p_pad(patterns[i].n),
                        max_parents=DEFAULT_MAX_PARENTS,
                        w=idx.w,
                        domains=dres,
                        planes=idx.csr_planes(),
                    )
                else:
                    plan = build_plan(
                        patterns[i],
                        idx.packed,
                        variant=variant,
                        p_pad=snap_p_pad(patterns[i].n),
                        max_parents=DEFAULT_MAX_PARENTS,
                        domains=dres,
                        csr_factory=idx.csr_planes,
                    )
                extend.validate_backend_for_plan(self.config, plan)
                out[i] = Query(
                    pattern=patterns[i],
                    plan=plan,
                    variant=variant,
                    name=name_of(i, patterns[i]),
                    prepare_s=dom_s + (time.perf_counter() - t1),
                    index=idx,
                )
        assert all(q is not None for q in out)
        return out  # type: ignore[return-value]

    # targets whose device-resident domain arrays stay cached; adjacency
    # bitmaps dominate the footprint, so keep only a few (FIFO-evicted).
    _DOM_TARGET_CACHE = 4

    def _target_domain_arrays(
        self, index: SubgraphIndex
    ) -> Union[dom_mod.TargetDomainArrays, dom_mod.CsrTargetDomainArrays]:
        """Device-resident target arrays for domain preprocessing, built
        once per index and cached (bounded) on the session.  The cache
        entry pins the PackedGraph so its id() cannot be recycled.  A
        sparse index gets the CSR-layout arrays (DESIGN.md §11) — the
        fixpoint engine dispatches on the tuple type."""
        key = id(index.packed)
        hit = self._dom_targets.get(key)
        if hit is not None:
            return hit[1]
        if index.sparse:
            arrays = dom_mod.csr_target_domain_arrays(
                index.graph, index.w, planes=index.csr_planes()
            )
        else:
            arrays = dom_mod.target_domain_arrays(index.packed)
        while len(self._dom_targets) >= self._DOM_TARGET_CACHE:
            self._dom_targets.pop(next(iter(self._dom_targets)))
        self._dom_targets[key] = (index.packed, arrays)
        return arrays

    def _domain_fn(
        self, flags: Dict[str, bool], b_pad: int, p_pad: int, a_pad: int,
        l_pad: int, index: SubgraphIndex,
    ) -> Callable:
        """The jitted batched domain fixpoint for one shape bucket, keyed
        into the session compile cache (kind='domains').  For a sparse
        index the key carries the CSR domain-array shape components
        (padded ``nnz`` and ``deg_cap``) — two same-``(n_t, w)`` targets of
        different density trace differently shaped fixpoints and must not
        collide."""
        pallas_mode = "per-arc" if self.config.use_pallas else "off"
        key = (
            "domains", flags["use_ac"], flags["use_fc"], flags["interleave"],
            pallas_mode, b_pad, p_pad, a_pad, l_pad,
            index.n, index.w, index.n_edge_labels,
        )
        if index.sparse:
            cp = index.csr_planes()
            key = key + (
                "csr",
                extend._pad_nnz(int(cp.nnz)),
                extend._pad_deg_cap(int(cp.deg_cap)),
            )
        fn = self._cache_get(key)
        if fn is not None:
            return fn
        self.compiles += 1
        fn = dom_mod.device_fixpoint(
            use_ac=flags["use_ac"], use_fc=flags["use_fc"],
            interleave=flags["interleave"], pallas_mode=pallas_mode,
            batched=True,
        )
        self._cache_put(key, fn)
        return fn

    def _coerce(self, q: Union[Query, Graph]) -> Query:
        return q if isinstance(q, Query) else self.prepare(q)

    def _coerce_all(self, queries: Iterable[Union[Query, Graph]]) -> List[Query]:
        """Coerce a mixed Query/Graph sequence; raw patterns go through the
        batched device preprocessing path in one sweep."""
        qs = list(queries)
        todo = [i for i, q in enumerate(qs) if not isinstance(q, Query)]
        if todo:
            prepared = self.prepare_batch([qs[i] for i in todo])
            for i, q in zip(todo, prepared):
                qs[i] = q
        return qs  # type: ignore[return-value]

    # -- execution: single -------------------------------------------------

    def run(self, query: Union[Query, Graph], collect_matches: int = 0) -> MatchSet:
        """Run one prepared query through the (cached) engine.

        A run whose stack high-watermark breached its ring capacity has
        *undercounted* (full workers freeze instead of expanding), so an
        ``overflow`` result is never returned silently: the query is
        retried once with a doubled ``stack_cap`` (with a warning;
        ``MatchSet.retries`` records it).  If the doubled cap still
        overflows, a ``RuntimeError`` asks for an explicit budget.
        """
        query = self._coerce(query)
        if not query.plan.satisfiable:
            return self._matchset(query, -1, _empty_engine_result(), 0.0)
        cfg = self.config
        if collect_matches:
            cfg = dataclasses.replace(cfg, collect_matches=collect_matches)
        t0 = time.perf_counter()
        res = self._run_single(cfg, query)
        retries = 0
        if res.overflow:
            res = self._retry_overflowed(cfg, query)
            retries = 1
        match_s = time.perf_counter() - t0
        return self._matchset(query, -1, res, match_s, retries=retries)

    def _run_single(self, cfg: EngineConfig, query: Query) -> EngineResult:
        """One engine invocation through the compile cache (no retry).

        Plan arrays follow the resolved step backend: dense
        :class:`~repro.core.extend.PlanArrays`, or
        :class:`~repro.core.extend.CsrPlanArrays` for ``step_backend="csr"``
        — including ``"auto"``, which flips to the sparse layout past
        ``extend.CSR_AUTO_NT`` target nodes (the cache key carries both the
        cfg and ``n_t``, so the resolution is stable per entry)."""
        if eng.resolve_step_backend_for_plan(cfg, query.plan) == "partitioned":
            return self._run_partitioned(cfg, query)
        fn = self._engine_fn(cfg, "single", 1, query)
        arrays = self._plan_arrays(cfg, query)
        state = eng.init_state(query.plan, cfg)
        final = jax.block_until_ready(fn(arrays, state))
        return eng.result_from_state(final, cfg)

    # -- execution: out-of-core partitioned (DESIGN.md §9) ------------------

    def _partition_count(self, cfg: EngineConfig, plan: SearchPlan) -> int:
        """Partition count for a plan under this session: an explicit
        ``EngineConfig.n_partitions`` wins; otherwise the session's
        ``memory_budget_bytes`` derives the smallest count whose padded
        resident planes fit; otherwise 1 (degenerate — the whole target is
        one resident partition)."""
        if cfg.n_partitions > 0:
            return cfg.n_partitions
        if self.memory_budget_bytes is not None:
            return extend.plan_partitions_budget(
                plan, self.memory_budget_bytes
            ).n_parts
        return 1

    def _run_partitioned(self, cfg: EngineConfig, query: Query) -> EngineResult:
        """One out-of-core run: the host scheduling loop of
        :func:`repro.core.engine.run_partitioned`, with every inner-engine
        (re)build routed through this session's compile cache — warm legs
        and repeat queries are cache hits, and the counters stay honest."""
        runc = dataclasses.replace(
            cfg,
            step_backend="partitioned",
            n_partitions=self._partition_count(cfg, query.plan),
        )
        return eng.run_partitioned(
            query.plan,
            runc,
            mesh=self.mesh,
            engine_factory=lambda c: self._engine_fn(c, "part", 1, query),
        )

    def warm(
        self,
        queries: Iterable[Union[Query, Graph]],
        collect_matches: int = 0,
        lanes: int = 1,
    ) -> Dict[str, int]:
        """Pre-trace the engines the given queries will need (PR-6
        follow-up: proactive compile-cache warmup).

        Each query's engine is resolved through the normal compile cache
        and invoked once on an **inert** state (zero stack sizes — the
        device loop exits immediately), which forces the XLA compile without
        enumerating anything.  Subsequent :meth:`run` / :meth:`run_pack`
        submits of same-key queries are then pure cache hits, so a serving
        process can move every compile stall to startup
        (``ServiceConfig.warmup_profile``).  Pass the ``collect_matches``
        budget the later submits will use — the buffer size is part of the
        traced shapes.  ``lanes > 1`` warms the vmapped *pack* engine of
        that width instead of the single-query path (what
        :meth:`run_pack` dispatches actually invoke; ignored where packs
        route singly — mesh and partitioned sessions).

        Returns ``{"warmed": queries traced, "compiles": fresh XLA
        compilations spent}`` (0 fresh compiles means everything was
        already warm).
        """
        before = self.compiles
        warmed = 0
        for q in self._coerce_all(queries):
            if not q.plan.satisfiable:
                continue
            cfg = self.config
            if collect_matches:
                cfg = dataclasses.replace(cfg, collect_matches=collect_matches)
            if eng.resolve_step_backend_for_plan(cfg, q.plan) == "partitioned":
                runc = dataclasses.replace(
                    cfg,
                    step_backend="partitioned",
                    n_partitions=self._partition_count(cfg, q.plan),
                )
                fn = self._engine_fn(runc, "part", 1, q)
                pp = extend.plan_partitions(q.plan, runc.n_partitions)
                arrays = extend.make_part_plan_arrays(q.plan, pp, 0)
                st = frontier.seeded_state(runc, q.plan.p_pad, frontier.without_rows(
                    frontier.seed_rows(q.plan, runc)))
                spill = frontier.init_spill_state(
                    runc.n_workers,
                    runc.resolved_spill_cap(q.plan.p_pad),
                    q.plan.p_pad,
                    q.plan.w,
                )
                jax.block_until_ready(fn(arrays, st, spill))
            elif lanes > 1 and self.mesh is None:
                # an all-inert pack of the dispatch width, seeded in the
                # query's own form, traces the same vmapped engine
                seeds = frontier.stack_seeds(
                    [frontier.without_rows(frontier.seed_rows(q.plan, cfg))], lanes)
                fn = self._engine_fn(cfg, "batch", lanes, q, frontier.seed_shape(seeds))
                arrays = eng.plan_arrays_for(cfg, q.plan)
                stacked = jax.tree.map(lambda x: jnp.stack([x] * lanes), arrays)
                jax.block_until_ready(fn(stacked, seeds))
            else:
                fn = self._engine_fn(cfg, "single", 1, q)
                arrays = self._plan_arrays(cfg, q)
                st = frontier.seeded_state(cfg, q.plan.p_pad, frontier.without_rows(
                    frontier.seed_rows(q.plan, cfg)))
                jax.block_until_ready(fn(arrays, st))
            warmed += 1
        return {"warmed": warmed, "compiles": self.compiles - before}

    def _plan_arrays(self, cfg: EngineConfig, query: Query,
                     plan: Optional[SearchPlan] = None):
        """:func:`~repro.core.extend.plan_arrays_for` with the adjacency
        transfer cached per index fingerprint (``_adj_device``): the query
        plan and its delta anchor plans all reference one version's bitmap
        object, so only the first run of a version ships it to device."""
        plan = plan or query.plan
        fp = query.index_fingerprint
        if not fp or eng.resolve_step_backend_for_plan(cfg, plan) == "csr":
            return eng.plan_arrays_for(cfg, plan)
        dev = self._adj_device.get(fp)
        if dev is None or tuple(dev.shape) != tuple(plan.adj_bits.shape):
            dev = jnp.asarray(plan.adj_bits, jnp.uint32)
            self._adj_device[fp] = dev
            self._adj_device.move_to_end(fp)
            while len(self._adj_device) > 2:
                self._adj_device.popitem(last=False)
        return eng.plan_arrays_for(cfg, plan, adj_bits=dev)

    def _retry_overflowed(self, cfg: EngineConfig, query: Query) -> EngineResult:
        """``cfg``'s run of ``query`` overflowed (undercounted): warn and
        re-run once with a doubled ``stack_cap``; raise if even that
        overflows.  Shared by run() and the pack path."""
        cap = cfg.resolved_stack_cap(query.plan.p_pad)
        warnings.warn(
            f"query {query.name!r} overflowed its worker stacks "
            f"(stack_cap={cap}); retrying once with stack_cap={2 * cap} — "
            "set EngineConfig.stack_cap to avoid the duplicated work",
            RuntimeWarning,
            stacklevel=3,
        )
        res = self._run_single(
            dataclasses.replace(cfg, stack_cap=2 * cap), query
        )
        if res.overflow:
            raise RuntimeError(
                f"engine stack overflow persists at stack_cap={2 * cap} "
                f"for query {query.name!r} — set an explicit "
                "EngineConfig.stack_cap budget"
            )
        return res

    # -- execution: delta (DESIGN.md §8) -----------------------------------

    def run_delta(
        self,
        query: Union[Query, Graph],
        old_matches,
        delta: GraphDelta,
    ) -> DeltaMatchSet:
        """Incrementally maintain ``old_matches`` across one index update.

        ``query`` must be prepared against the delta's **new** index
        version (after ``new_index, delta = index.update(...)``, call
        ``enum.prepare(pattern, index=new_index)``); ``old_matches`` is the
        prior result for the old version — a :class:`MatchSet` or a list of
        node-indexed mappings.  Work is restricted to the delta:

        * removals invalidate prior matches by membership test (no
          enumeration at all);
        * insertions are enumerated by anchoring each distinct pattern
          edge onto each compatible inserted target arc and running the
          engine from those seeds only
          (`repro.core.frontier.init_delta_state`), deduplicated by the
          max-inserted-edge-index rule (`repro.core.delta`).

        Returns a :class:`DeltaMatchSet`; ``result.apply(old_matches)`` is
        bit-identical to a fresh enumeration's sorted mappings — the
        standing gate in ``tests/test_incremental_conformance.py``.
        """
        query = self._coerce(query)
        if delta.new_fingerprint and query.index_fingerprint != delta.new_fingerprint:
            raise ValueError(
                "run_delta: query is not prepared against the delta's new "
                "index version (fingerprint mismatch) — after "
                "SubgraphIndex.update(), prepare the query against the "
                "returned index"
            )
        t0 = time.perf_counter()
        removed: List[Tuple[int, ...]] = []
        if delta.removed:
            old_arr = delta_mod.as_mapping_array(old_matches)
            n_old = len(old_arr)
            removed = delta_mod.invalidated_mappings(
                query.pattern, old_arr, delta.removed
            )
        else:
            n_old = _match_count(old_matches)
        added: List[Tuple[int, ...]] = []
        states = seeds = anchors = retries = 0
        if delta.added and query.plan.satisfiable:
            for anchor, aplan in self._anchor_plans(query):
                sd, sm, sc = delta_mod.build_anchor_seeds(aplan, anchor, delta.added)
                if not sd.shape[0]:
                    continue
                anchors += 1
                seeds += int(sd.shape[0])
                rows, st, rt = self._run_seeded(query, aplan, sd, sm, sc)
                states += st
                retries += rt
                added.extend(
                    delta_mod.filter_new_matches(
                        query.pattern,
                        delta_mod.canonical_mappings(aplan, rows),
                        delta.added,
                        anchor,
                    )
                )
        return DeltaMatchSet(
            name=query.name,
            added=sorted(added),
            removed=sorted(removed),
            n_old=n_old,
            states=states,
            n_seeds=seeds,
            n_anchors=anchors,
            preprocess_s=query.prepare_s,
            match_s=time.perf_counter() - t0,
            retries=retries,
            delta=delta,
        )

    def _anchor_plans(self, query: Query) -> Iterator[Tuple[Tuple[int, int, int], SearchPlan]]:
        """``(anchor, plan)`` per distinct pattern edge triple, cached on
        the query.  Domains are ordering-independent, so one DomainResult
        is computed once and shared by every anchor plan; anchor plans keep
        the query's padding so same-shape anchors share compiled engines."""
        if query.index is None:
            raise ValueError(
                "run_delta needs a query bound to a SubgraphIndex "
                "(prepare it through an Enumerator / prepare_query)"
            )
        idx = query.index
        flags = variant_flags(query.variant)
        if query._anchor_domains is None:
            # The query plan retains the node-indexed domain fixpoint it
            # was assembled from; reuse it (AC/FC is by far the dominant
            # host cost per version) and only recompute for plans built
            # by older paths that did not stash it.
            query._anchor_domains = query.plan.domains
        if query._anchor_domains is None:
            query._anchor_domains = dom_mod.compute_domains(
                query.pattern,
                idx.packed,
                use_ac=flags["use_ac"],
                use_fc=flags["use_fc"],
                interleave=flags["interleave"],
            )
        for anchor in delta_mod.pattern_edge_triples(query.pattern):
            aplan = query._anchors.get(anchor)
            if aplan is None:
                if query.plan.seed_edge == anchor:
                    # An edge-seeded query plan *is* this anchor's plan:
                    # _assemble_plan already forced the seed edge's
                    # endpoints to positions 0/1 with the same domains and
                    # padding, so the anchor seeds stay aligned with the
                    # query's own seed-edge ordering instead of rebuilding
                    # an identical plan (PR-9 follow-up).
                    aplan = query.plan
                else:
                    pa, pb, _ = anchor
                    aplan = build_plan(
                        query.pattern,
                        idx.packed,
                        variant=query.variant,
                        p_pad=query.plan.p_pad,
                        max_parents=query.plan.max_parents,
                        domains=query._anchor_domains,
                        anchor=(pa,) if pa == pb else (pa, pb),
                        csr_factory=idx.csr_planes,
                    )
                query._anchors[anchor] = aplan
            yield anchor, aplan

    # first match-buffer size for seeded runs; grown (pow2) if any worker's
    # per-run match count wraps its ring
    _DELTA_MCAP = 256

    def _run_seeded(
        self,
        query: Query,
        aplan: SearchPlan,
        sd: np.ndarray,
        sm: np.ndarray,
        sc: np.ndarray,
    ) -> Tuple[np.ndarray, int, int]:
        """Run the engine from delta seed entries, in worker-capacity
        chunks; returns ``(match rows in aplan position space [K, n_p],
        states, retries)``.  Seeded runs always collect matches (the delta
        result is the mappings); a run whose per-worker match count wraps
        the collect ring, or that overflows its stacks, is retried with a
        doubled buffer / stack cap."""
        cfg0 = self.config
        aq = Query(
            pattern=query.pattern, plan=aplan, variant=query.variant,
            name=f"{query.name}~delta", prepare_s=0.0, index=query.index,
        )
        v = cfg0.n_workers
        cap0 = cfg0.resolved_stack_cap(aplan.p_pad)
        chunk = v * max(cap0 // 2, 1)
        rows_out: List[np.ndarray] = []
        states = retries = 0
        for j in range(0, int(sd.shape[0]), chunk):
            cs, cm, cc = sd[j:j + chunk], sm[j:j + chunk], sc[j:j + chunk]
            # start from the largest buffer any prior seeded run needed:
            # growth is sticky on the enumerator so a steady-state edit
            # stream settles on one traced shape instead of paying a
            # grow-retry (and an XLA compile) per call
            mcap = max(self._DELTA_MCAP, self._delta_mcap)
            cap = cap0
            while True:
                cfg = dataclasses.replace(
                    cfg0, collect_matches=mcap, stack_cap=cap
                )
                fn = self._engine_fn(cfg, "single", 1, aq)
                arrays = self._plan_arrays(cfg, aq, aplan)
                state = frontier.init_delta_state(aplan, cfg, cs, cm, cc)
                final = jax.block_until_ready(fn(arrays, state))
                res = eng.result_from_state(final, cfg)
                if res.overflow:
                    if cap >= cap0 * 4:
                        raise RuntimeError(
                            f"delta run for {query.name!r} still overflows "
                            f"at stack_cap={cap} — set an explicit "
                            "EngineConfig.stack_cap budget"
                        )
                    cap *= 2
                    retries += 1
                    continue
                pw = res.per_worker_matches
                top = int(np.max(pw)) if pw is not None and pw.size else res.matches
                if top > mcap:
                    mcap = 1 << (top - 1).bit_length()
                    self._delta_mcap = max(self._delta_mcap, mcap)
                    retries += 1
                    continue
                break
            states += res.states
            if res.match_buf is not None and res.matches:
                buf = np.asarray(res.match_buf)
                rows = buf.reshape(-1, buf.shape[-1])
                valid = (rows[:, : aplan.n_p] >= 0).all(axis=1)
                rows_out.append(rows[valid][:, : aplan.n_p])
        if rows_out:
            return np.concatenate(rows_out, axis=0), states, retries
        return np.zeros((0, aplan.n_p), dtype=np.int32), states, retries

    # -- execution: batch / stream ----------------------------------------

    def coalesce_key(self, query: Query, cfg: Optional[EngineConfig] = None) -> tuple:
        """The pack-compatibility key of a query: queries with equal keys
        can stack lane-for-lane into one vmapped pack (same jitted engine,
        same array shapes).  ``stream``/``run_batch`` group by it, and the
        serving layer's continuous coalescer (`repro.serve`) buckets
        pending queries by exactly this key, so concurrent heterogeneous
        load rides the compile cache at one compilation per key.

        The key is the shape bucket ``(p_pad, max_parents, n_t, w,
        n_elab)`` plus the query's index fingerprint — queries against
        different *contents* (two targets, or two versions of one updated
        index) never share a pack, since their plan arrays differ
        (DESIGN.md §8).  Under the csr backend it also carries the plan's
        padded ``(deg_cap, nnz)`` — two same-bucket targets of different
        density have differently shaped
        :class:`~repro.core.extend.CsrPlanArrays` and cannot share a pack
        lane.
        """
        cfg = cfg or self.config
        key = query.bucket + (query.index_fingerprint,)
        resolved = eng.resolve_step_backend_for_plan(cfg, query.plan)
        if resolved == "csr":
            key = key + extend.csr_shape_bucket(query.plan)
        elif resolved == "partitioned":
            # partition identity: two targets sharing a bucket but not a
            # partitioning (count or padded per-partition shapes) run
            # different compiled engines and must not coalesce
            key = key + extend.partitioned_shape_bucket(
                query.plan, self._partition_count(cfg, query.plan)
            )
        return key

    def run_pack(
        self,
        queries: Sequence[Union[Query, Graph]],
        pack_size: Optional[int] = None,
        cfg: Optional[EngineConfig] = None,
    ) -> List[MatchSet]:
        """Batch-submission hook for the serving layer: execute queries
        that share one :meth:`coalesce_key` as padded vmapped packs of
        ``pack_size`` lanes, returning one :class:`MatchSet` per query in
        input order (``query_index`` is the input position).

        Unlike :meth:`run_batch` this does **no** grouping or LPT
        balancing — the caller (the `repro.serve` coalescer) has already
        decided the pack; mixed keys raise.  Unsatisfiable queries get
        empty results without touching the engine.  ``cfg`` overrides the
        session config (the service uses it to thread per-request
        ``collect_matches`` budgets); overflowed lanes go through the
        usual doubled-``stack_cap`` single retry.  Under a mesh, queries
        route singly through the sharded engine (pack-vmap over
        ``shard_map`` is an open ROADMAP item).
        """
        cfg = cfg or self.config
        qs = self._coerce_all(queries)
        pack_size = pack_size or max(len(qs), 1)
        out: List[Optional[MatchSet]] = [None] * len(qs)
        live: List[int] = []
        for i, q in enumerate(qs):
            if q.plan.satisfiable:
                live.append(i)
            else:
                out[i] = self._matchset(q, i, _empty_engine_result(), 0.0)
        if live:
            keys = {self.coalesce_key(qs[i], cfg) for i in live}
            if len(keys) > 1:
                raise ValueError(
                    f"run_pack requires one coalesce_key per pack, got {len(keys)}: "
                    f"{sorted(keys)}"
                )
            if self.mesh is not None or cfg.step_backend == "partitioned":
                # sharded and out-of-core engines run queries singly (the
                # pack vmap composes with neither shard_map nor the host
                # partition-scheduling loop); the coalesce key still
                # grouped them, so the compile cache is shared
                for i in live:
                    ms = self.run(qs[i], collect_matches=cfg.collect_matches)
                    ms.query_index = i
                    out[i] = ms
            else:
                for j in range(0, len(live), pack_size):
                    for ms in self._run_pack(live[j:j + pack_size], qs, cfg, pack_size):
                        out[ms.query_index] = ms
        assert all(m is not None for m in out), "run_pack dropped a query"
        return out  # type: ignore[return-value]

    def stream(
        self,
        queries: Iterable[Union[Query, Graph]],
        pack_size: int = 4,
    ) -> Iterator[MatchSet]:
        """Yield one :class:`MatchSet` per query as vmapped packs drain.

        Queries are grouped by shape bucket, LPT-balanced into packs of
        ``pack_size`` (padded with inert lanes so every pack shares one
        compilation), and executed pack by pack; each completed pack yields
        its per-query results immediately.  ``MatchSet.query_index`` carries
        the position in the input sequence.
        """
        qs: List[Query] = self._coerce_all(queries)
        cfg = self.config

        if self.mesh is not None or cfg.step_backend == "partitioned":
            # The pack vmap composes with neither shard_map engines nor the
            # out-of-core host scheduling loop: each query runs through the
            # (cached) single-query path, yielding in input order.
            for i, q in enumerate(qs):
                if not q.plan.satisfiable:
                    yield self._matchset(q, i, _empty_engine_result(), 0.0)
                else:
                    ms = self.run(q)
                    ms.query_index = i
                    yield ms
            return

        groups: Dict[tuple, List[int]] = {}
        for i, q in enumerate(qs):
            if not q.plan.satisfiable:
                yield self._matchset(q, i, _empty_engine_result(), 0.0)
            else:
                groups.setdefault(self.coalesce_key(q, cfg), []).append(i)

        for idxs in groups.values():
            weights = [_predict_work(qs[i].plan) for i in idxs]
            n_packs = max(1, (len(idxs) + pack_size - 1) // pack_size)
            assignment = balance_assignment(weights, n_packs)
            for pack_id in range(n_packs):
                members = [i for i, a in zip(idxs, assignment) if a == pack_id]
                # LPT balances weight, not count: an overloaded pack is split
                # into pack_size chunks so every engine call has the same lane
                # width (one compilation per bucket, counters stay honest).
                for j in range(0, len(members), pack_size):
                    yield from self._run_pack(members[j:j + pack_size], qs, cfg, pack_size)

    def run_batch(
        self,
        queries: Sequence[Union[Query, Graph]],
        pack_size: int = 4,
    ) -> List[MatchSet]:
        """Run a batch of queries; exactly one result per query, in order."""
        queries = list(queries)
        out: List[Optional[MatchSet]] = [None] * len(queries)
        for ms in self.stream(queries, pack_size=pack_size):
            out[ms.query_index] = ms
        assert all(r is not None for r in out), "stream dropped a query"
        return out  # type: ignore[return-value]

    def _run_pack(
        self, members: List[int], qs: List[Query], cfg: EngineConfig, pack_size: int
    ) -> List[MatchSet]:
        """Execute one padded pack of same-bucket queries: only the seed
        rows go to the device, only the reduced counters come back."""
        t0 = time.perf_counter()
        n = len(members)
        with trace.span("pack.build") as sp:
            plans = [qs[i].plan for i in members]
            # lanes past the members hold no seed rows: every pack of this
            # bucket shares one compilation, and those lanes leave the
            # vmapped while_loop at once
            seeds = frontier.stack_seeds(
                [frontier.seed_rows(p, cfg) for p in plans], pack_size)
            fn = self._engine_fn(cfg, "batch", pack_size, qs[members[0]],
                                 frontier.seed_shape(seeds))
            arrays = [eng.plan_arrays_for(cfg, p) for p in plans]
            arrays += [arrays[0]] * (pack_size - n)
            stacked_plan = jax.tree.map(lambda *xs: jnp.stack(xs), *arrays)
            if sp is not None:
                sp.add(seed_bytes=sum(x[:n].nbytes for x in jax.tree.leaves(seeds)))
        with trace.span("pack.device") as sp:
            counters = jax.block_until_ready(fn(stacked_plan, seeds))
            if sp is not None:
                # loop rounds each lane ran; the vmapped loop runs until
                # its slowest lane stops
                steps = np.asarray(counters.steps)[:n]
                sp.add(occupied=n, steps_max=int(steps.max()),
                       steps_sum=int(steps.sum()))
        match_s = (time.perf_counter() - t0) / max(n, 1)
        out = []
        with trace.span("pack.decode"):
            host = jax.device_get(counters)
            for row, i in enumerate(members):
                res = eng.result_from_counters(
                    jax.tree.map(lambda x, r=row: x[r], host))
                if res.overflow:
                    # the pack undercounted this lane; go straight to the
                    # doubled-stack_cap single retry (re-running at the
                    # original cap would deterministically overflow again)
                    with trace.span("pack.retry"):
                        res = self._retry_overflowed(cfg, qs[i])
                    out.append(self._matchset(qs[i], i, res, match_s, retries=1))
                else:
                    out.append(self._matchset(qs[i], i, res, match_s))
        return out

    # -- result assembly ---------------------------------------------------

    def _matchset(
        self, query: Query, idx: int, res: EngineResult, match_s: float,
        retries: int = 0,
    ) -> MatchSet:
        materialize = None
        if res.match_buf is None and query.plan.satisfiable:
            def materialize(q: Query = query, m: int = res.matches):
                # round the buffer up to a power of two so re-materializations
                # of different queries share a handful of engine configs
                cap = min(1 << max(m - 1, 1).bit_length(), _MATERIALIZE_CAP)
                return self.run(q, collect_matches=cap).engine.match_buf

        return MatchSet(
            name=query.name,
            query_index=idx,
            matches=res.matches,
            states=res.states,
            steps=res.steps,
            steals=res.steals,
            steal_rounds=res.steal_rounds,
            mean_steal_depth=res.mean_steal_depth,
            mean_expand_depth=res.mean_expand_depth,
            per_worker_states=res.per_worker_states,
            per_worker_matches=res.per_worker_matches,
            per_worker_steals=res.per_worker_steals,
            preprocess_s=query.prepare_s,
            match_s=match_s,
            plan=query.plan,
            engine=res,
            retries=retries,
            _match_buf=res.match_buf,
            _materialize=materialize,
        )


def _coerce_mesh(mesh) -> Optional["jax.sharding.Mesh"]:
    """Accept a ``jax.sharding.Mesh``, an int device count (first ``n``
    local devices on a 1-D ``data`` axis), or ``None``."""
    if mesh is None or isinstance(mesh, jax.sharding.Mesh):
        return mesh
    if isinstance(mesh, int):
        devs = jax.local_devices()
        if mesh > len(devs):
            raise ValueError(
                f"mesh={mesh} devices requested but only {len(devs)} local "
                "devices exist (on CPU set XLA_FLAGS="
                "--xla_force_host_platform_device_count=N before importing jax)"
            )
        return jax.make_mesh((mesh,), ("data",), devices=devs[:mesh])
    raise TypeError(f"mesh must be a Mesh, int, or None, got {type(mesh)!r}")


# Process-wide sessions for the compatibility wrappers and benchmark
# harness: one Enumerator (and thus one engine-compile cache) per config.
_SHARED: Dict[EngineConfig, Enumerator] = {}


def shared_enumerator(cfg: EngineConfig) -> Enumerator:
    """The process-wide session for ``cfg`` (created on first use)."""
    s = _SHARED.get(cfg)
    if s is None:
        s = _SHARED[cfg] = Enumerator(config=cfg)
    return s


def _predict_work(plan: SearchPlan) -> float:
    """Cheap work proxy: product of the first few domain sizes (the former
    ``core/multi.py`` heuristic feeding LPT pack balancing)."""
    sizes = popcount(plan.dom_bits[: min(plan.n_p, 4)])
    return float(np.prod(np.maximum(sizes, 1), dtype=np.float64))
