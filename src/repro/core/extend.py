"""The expansion step — candidate bitmaps, lowest-untried-bit extraction,
child emission, match counting — behind the ``StepBackend`` seam
(DESIGN.md §6.2).

One expansion step, for every popped lane: extract the lowest untried
candidate bit ``v``, extend the mapping, build the child's candidate
bitmap ``dom[pos+1] ∧ ¬used' ∧ ⋀ adj_rows(mapped parents)`` (the paper's
check-consistency-before-spawning rule, §3.1), and flag matches at full
depth.  The work is *lane-flat*: the step function flattens all
``V·expand_width`` lanes of its worker shard into one batch, so a backend
sees a single dense batch regardless of worker count or mesh shard — and a
Pallas backend gets one big grid instead of ``V`` vmapped kernel calls.

Backends (selected by ``EngineConfig.step_backend``):

* ``"jnp"`` — :class:`JnpStepBackend`, the loose-ops reference: pure jnp
  phases with full HBM round-trips between them; with
  ``EngineConfig.use_pallas`` the candidate-bitmap AND routes through the
  `repro.kernels.candidate_mask` kernel (the pre-seam behavior, kept as
  the mask-only kerneling point of comparison).
* ``"pallas"`` — :class:`PallasStepBackend`, the fused
  `repro.kernels.extend_step` kernel: adjacency-row gathers
  (scalar-prefetched), the ``dom ∧ ¬used ∧ parents`` AND-tree, per-lane
  lowest-bit extraction and match flagging in **one** kernel invocation
  (DESIGN.md §6.3) — subsuming ``candidate_mask`` on the engine path.
* ``"csr"`` — :class:`CsrStepBackend`, the sparse layout for targets far
  beyond paper scale (DESIGN.md §6.4): instead of ANDing dense
  ``[n_t, w]`` adjacency bitmap rows, it gathers each mapped parent's CSR
  neighbor segment (:class:`CsrPlanArrays`) and **sorted-intersects** the
  lists — ``O(parents · deg)`` work against the sparse structure, with the
  dense ``O(n_planes · n_t · w)`` bitmaps never resident.  With
  ``cfg.use_pallas`` the walk routes through the
  `repro.kernels.csr_extend` kernel (scalar-prefetched ``indptr`` row
  bounds, ``pl.ds`` neighbor loads).
* ``"auto"`` — not a backend: resolves per plan to ``"csr"`` when
  ``n_t > CSR_AUTO_NT`` and ``"jnp"`` otherwise
  (:func:`resolve_step_backend`).

All backends are bit-identical on every :class:`StepLanes` field the
engine consumes (the conformance matrix in
``tests/test_backend_conformance.py`` gates this for every current and
future entry of ``STEP_BACKENDS``); the driver (`repro.core.engine`)
never knows which one ran.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Protocol, Tuple, TYPE_CHECKING, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import PartitionSpec

from repro.core import frontier
from repro.core.frontier import EngineState, SpillState
from repro.core.graph import (
    WORD_BITS,
    CsrPlanes,
    PartitionedPlanes,
    bitmap_from_indices,
    csr_planes_from_bitmaps,
    deg_bucket_caps,
    partition_csr_planes,
)
from repro.core.plan import SearchPlan

if TYPE_CHECKING:  # engine imports extend; annotations only
    from repro.core.engine import EngineConfig

STEP_BACKENDS = ("jnp", "pallas", "csr")

# "auto" resolution threshold: beyond this many target nodes the dense
# [n_elab, 2, n_t, w] bitmaps cost O(n_t²/32) words (sge_pdbsv1's 33,067
# nodes ⇒ ~273 MB) and the sparse layout takes over.
CSR_AUTO_NT = 32768

# int32 sentinel for padded CSR segment slots: larger than any node id, so
# sentinel-masked segments stay sorted for the binary-search membership test.
CSR_SENTINEL = np.int32(2**31 - 1)


def resolve_step_backend(cfg: "EngineConfig", n_t: int) -> str:
    """Resolve ``cfg.step_backend`` for a plan with ``n_t`` target nodes:
    ``"auto"`` picks ``"csr"`` past :data:`CSR_AUTO_NT` (an explicit backend
    always wins).  Deterministic per (cfg, n_t), so session compile-cache
    keys — which carry both — stay unambiguous."""
    if cfg.step_backend != "auto":
        return cfg.step_backend
    return "csr" if n_t > CSR_AUTO_NT else "jnp"


class PlanArrays(NamedTuple):
    """Device-resident static plan arrays (see SearchPlan)."""

    order_valid: jnp.ndarray  # [p_pad] bool (True for real positions)
    parent_pos: jnp.ndarray  # [p_pad, mp] int32
    parent_dir: jnp.ndarray  # [p_pad, mp]
    parent_elab: jnp.ndarray  # [p_pad, mp]
    dom_bits: jnp.ndarray  # [p_pad, w] uint32
    adj_bits: jnp.ndarray  # [n_elab, 2, n_t, w] uint32
    n_p: jnp.ndarray  # scalar int32 (actual pattern size)


def make_plan_arrays(plan: SearchPlan, adj_bits=None) -> PlanArrays:
    """``adj_bits`` optionally supplies an already device-resident
    adjacency buffer (the dominant transfer) so same-version plans — a
    query plan and its delta anchor plans — share one host→device copy."""
    return PlanArrays(
        order_valid=jnp.asarray(plan.order >= 0),
        parent_pos=jnp.asarray(plan.parent_pos, jnp.int32),
        parent_dir=jnp.asarray(plan.parent_dir, jnp.int32),
        parent_elab=jnp.asarray(plan.parent_elab, jnp.int32),
        dom_bits=jnp.asarray(plan.dom_bits, jnp.uint32),
        adj_bits=(jnp.asarray(plan.adj_bits, jnp.uint32)
                  if adj_bits is None else adj_bits),
        n_p=jnp.asarray(plan.n_p, jnp.int32),
    )


def abstract_plan_arrays(
    n_t: int, w: int, p_pad: int, max_parents: int, n_elab: int = 1
) -> PlanArrays:
    sds = jax.ShapeDtypeStruct
    return PlanArrays(
        order_valid=sds((p_pad,), jnp.bool_),
        parent_pos=sds((p_pad, max_parents), jnp.int32),
        parent_dir=sds((p_pad, max_parents), jnp.int32),
        parent_elab=sds((p_pad, max_parents), jnp.int32),
        dom_bits=sds((p_pad, w), jnp.uint32),
        adj_bits=sds((n_elab, 2, n_t, w), jnp.uint32),
        n_p=sds((), jnp.int32),
    )


PLAN_LOGICAL = PlanArrays(
    order_valid=(None,),
    parent_pos=(None, None),
    parent_dir=(None, None),
    parent_elab=(None, None),
    dom_bits=(None, "tensor"),
    adj_bits=(None, None, None, "tensor"),
    n_p=(),
)


def plan_partition_specs() -> PlanArrays:
    """PartitionSpecs for :class:`PlanArrays`: fully replicated (every
    device needs the whole domain/adjacency bitmaps to expand its workers)."""
    P = PartitionSpec
    return PlanArrays(
        order_valid=P(None),
        parent_pos=P(None, None),
        parent_dir=P(None, None),
        parent_elab=P(None, None),
        dom_bits=P(None, None),
        adj_bits=P(None, None, None, None),
        n_p=P(),
    )


# ---------------------------------------------------------------------------
# CSR plan arrays (the sparse twin of PlanArrays, DESIGN.md §6.4)
# ---------------------------------------------------------------------------

class CsrPlanArrays(NamedTuple):
    """Device-resident static plan arrays in CSR adjacency layout.

    The shared fields mirror :class:`PlanArrays`; the dense ``adj_bits``
    are replaced by flattened per-``(elab, dir)`` CSR planes
    (`repro.core.graph.CsrPlanes`).  ``seg_iota`` exists to carry the
    static segment-gather width ``deg_cap`` in its *shape* (plan arrays are
    traced under jit, so structural constants must be shape-derived);
    ``indices`` is over-padded by ``deg_cap`` sentinel entries so a
    ``deg_cap``-wide dynamic slice starting at any real offset never
    clamps.
    """

    order_valid: jnp.ndarray  # [p_pad] bool (True for real positions)
    parent_pos: jnp.ndarray  # [p_pad, mp] int32
    parent_dir: jnp.ndarray  # [p_pad, mp]
    parent_elab: jnp.ndarray  # [p_pad, mp]
    dom_bits: jnp.ndarray  # [p_pad, w] uint32
    indptr: jnp.ndarray  # [n_planes, n_t + 1] int32, global offsets
    indices: jnp.ndarray  # [nnz_pad + deg_cap] int32, sentinel-padded tail
    seg_iota: jnp.ndarray  # [deg_cap] int32 (0..deg_cap-1)
    n_p: jnp.ndarray  # scalar int32 (actual pattern size)


def _pad_deg_cap(deg_cap: int) -> int:
    """Segment-gather width: max row degree snapped up to a multiple of 8
    (min 8), so near-identical targets share a compile shape."""
    return max(8, ((deg_cap + 7) // 8) * 8)


def _pad_nnz(nnz: int) -> int:
    """nnz shape bucket (multiples of 1024) — keeps re-prepared same-target
    queries on one compiled engine."""
    return max(1024, ((nnz + 1023) // 1024) * 1024)


def _plan_csr(plan: SearchPlan) -> CsrPlanes:
    """The plan's CSR planes, resolved once and cached on the plan:
    explicit ``plan.csr`` (CSR-only plans) wins, then ``plan.csr_factory``
    (session-built plans share the index's incrementally patched plane set,
    DESIGN.md §8), then a fresh dense→sparse conversion."""
    cp = plan.csr
    if cp is None:
        if plan.csr_factory is not None:
            cp = plan.csr_factory()
        else:
            cp = csr_planes_from_bitmaps(np.asarray(plan.adj_bits))
        plan.csr = cp  # cache: conversion is O(n_t · w) host work
    return cp


def make_csr_plan_arrays(plan: SearchPlan) -> CsrPlanArrays:
    """Build :class:`CsrPlanArrays` from a :class:`SearchPlan`.

    CSR-only plans (``plan.csr`` set by `repro.core.plan.build_csr_plan`)
    use their planes directly; dense-built plans derive (and cache) the
    planes from ``adj_bits`` — bit-for-bit the same adjacency relation
    (`repro.core.graph.csr_planes_from_bitmaps`), which is what lets the
    conformance suite run every backend on one plan.
    """
    cp = _plan_csr(plan)
    deg_cap = _pad_deg_cap(cp.deg_cap)
    nnz_pad = _pad_nnz(cp.nnz)
    indices = np.full(nnz_pad + deg_cap, CSR_SENTINEL, dtype=np.int32)
    indices[: cp.nnz] = cp.indices
    return CsrPlanArrays(
        order_valid=jnp.asarray(plan.order >= 0),
        parent_pos=jnp.asarray(plan.parent_pos, jnp.int32),
        parent_dir=jnp.asarray(plan.parent_dir, jnp.int32),
        parent_elab=jnp.asarray(plan.parent_elab, jnp.int32),
        dom_bits=jnp.asarray(plan.dom_bits, jnp.uint32),
        indptr=jnp.asarray(cp.indptr, jnp.int32),
        indices=jnp.asarray(indices),
        seg_iota=jnp.arange(deg_cap, dtype=jnp.int32),
        n_p=jnp.asarray(plan.n_p, jnp.int32),
    )


def abstract_csr_plan_arrays(
    n_t: int, w: int, p_pad: int, max_parents: int, n_elab: int = 1,
    nnz: int = 0, deg_cap: int = 8,
) -> CsrPlanArrays:
    sds = jax.ShapeDtypeStruct
    deg_cap = _pad_deg_cap(deg_cap)
    return CsrPlanArrays(
        order_valid=sds((p_pad,), jnp.bool_),
        parent_pos=sds((p_pad, max_parents), jnp.int32),
        parent_dir=sds((p_pad, max_parents), jnp.int32),
        parent_elab=sds((p_pad, max_parents), jnp.int32),
        dom_bits=sds((p_pad, w), jnp.uint32),
        indptr=sds((n_elab * 2, n_t + 1), jnp.int32),
        indices=sds((_pad_nnz(nnz) + deg_cap,), jnp.int32),
        seg_iota=sds((deg_cap,), jnp.int32),
        n_p=sds((), jnp.int32),
    )


CSR_PLAN_LOGICAL = CsrPlanArrays(
    order_valid=(None,),
    parent_pos=(None, None),
    parent_dir=(None, None),
    parent_elab=(None, None),
    dom_bits=(None, "tensor"),
    indptr=(None, None),
    indices=(None,),
    seg_iota=(None,),
    n_p=(),
)


def csr_plan_partition_specs() -> CsrPlanArrays:
    """PartitionSpecs for :class:`CsrPlanArrays`: fully replicated, like the
    dense plan (any worker may map any target node, so every device needs
    the whole — small — CSR structure)."""
    P = PartitionSpec
    return CsrPlanArrays(
        order_valid=P(None),
        parent_pos=P(None, None),
        parent_dir=P(None, None),
        parent_elab=P(None, None),
        dom_bits=P(None, None),
        indptr=P(None, None),
        indices=P(None),
        seg_iota=P(None),
        n_p=P(),
    )


# ---------------------------------------------------------------------------
# partitioned plan arrays (out-of-core targets, DESIGN.md §9)
# ---------------------------------------------------------------------------

class PartPlanArrays(NamedTuple):
    """Device-resident plan arrays for **one resident partition** of a
    row-partitioned target (`repro.core.graph.PartitionedPlanes`).

    Mirrors :class:`CsrPlanArrays` with the plane rows restricted to the
    resident partition: ``indptr`` is over partition-**local** rows (global
    row ``t`` ↦ ``t - part_lo``); ``indices`` keep **global** column ids.
    Every partition of a target is padded to the *same* shapes
    (``max_local`` rows, ``max_nnz`` entries), so one compiled engine serves
    all partitions and swapping partitions is a pure data transfer —
    ``part_lo`` / ``part_hi`` bound the resident global-row range and
    ``part_starts`` routes spill entries to the partition owning their
    first pending parent.
    """

    order_valid: jnp.ndarray  # [p_pad] bool
    parent_pos: jnp.ndarray  # [p_pad, mp] int32
    parent_dir: jnp.ndarray  # [p_pad, mp]
    parent_elab: jnp.ndarray  # [p_pad, mp]
    dom_bits: jnp.ndarray  # [p_pad, w] uint32
    indptr: jnp.ndarray  # [n_planes, max_loc_pad + 1] int32, local rows
    indices: jnp.ndarray  # [nnz_pad + deg_cap] int32, global columns
    seg_iota: jnp.ndarray  # [deg_cap] int32
    part_starts: jnp.ndarray  # [n_parts + 1] int32 global row boundaries
    part_lo: jnp.ndarray  # [] int32 resident range start (global row)
    part_hi: jnp.ndarray  # [] int32 resident range end (exclusive)
    n_p: jnp.ndarray  # [] int32


def _pad_rows(n: int) -> int:
    """Local-row shape bucket (multiples of 64, min 64) so all partitions of
    a target — and re-partitioned same-scale targets — share one compile."""
    return max(64, ((n + 63) // 64) * 64)


def plan_partitions(plan: SearchPlan, n_parts: int) -> PartitionedPlanes:
    """The plan's target partitioning at ``n_parts``, computed once and
    cached on the plan (partitioning is O(nnz) host work per count)."""
    cache = getattr(plan, "_partitions", None)
    if cache is None:
        cache = {}
        plan._partitions = cache
    pp = cache.get(n_parts)
    if pp is None:
        pp = partition_csr_planes(_plan_csr(plan), n_parts=n_parts)
        cache[n_parts] = pp
    return pp


def plan_partitions_budget(plan: SearchPlan, max_bytes: int) -> PartitionedPlanes:
    """Partitioning at the smallest count whose **padded** resident plane
    arrays (:func:`part_resident_nbytes` — what actually occupies the
    device) fit ``max_bytes``; cached on the plan under both the budget and
    the resulting count, so the engine's ``plan_partitions(plan,
    pp.n_parts)`` returns the same object."""
    cache = getattr(plan, "_partitions", None)
    if cache is None:
        cache = {}
        plan._partitions = cache
    key = ("budget", int(max_bytes))
    pp = cache.get(key)
    if pp is None:
        cp = _plan_csr(plan)
        pp = partition_csr_planes(cp, max_bytes=max_bytes)
        while part_resident_nbytes(pp) > max_bytes and pp.n_parts < cp.n_t:
            pp = partition_csr_planes(cp, n_parts=pp.n_parts + 1)
        if part_resident_nbytes(pp) > max_bytes:
            raise ValueError(
                f"memory_budget_bytes={max_bytes} cannot hold even a "
                f"single-row partition's padded planes "
                f"({part_resident_nbytes(pp)} bytes at n_parts={pp.n_parts})"
            )
        cache[key] = pp
        cache.setdefault(pp.n_parts, pp)
    return pp


def partitioned_shape_bucket(plan: SearchPlan, n_parts: int) -> Tuple[int, ...]:
    """``(n_parts, max_loc_pad, nnz_pad, *bucket_caps)`` — the partition
    identity the session folds into compile-cache and coalesce keys: two
    queries share a compiled partitioned engine iff these (plus the usual
    bucket) agree.  As in :func:`csr_shape_bucket`, the trailing entries are
    the pow2 degree-bucket ladder rather than one global ``deg_cap``."""
    pp = plan_partitions(plan, n_parts)
    return (
        pp.n_parts,
        _pad_rows(pp.max_local),
        _pad_nnz(pp.max_nnz),
    ) + deg_bucket_caps(_pad_deg_cap(pp.deg_cap))


def part_resident_nbytes(pp: PartitionedPlanes) -> int:
    """Device bytes of one resident partition's padded plane arrays
    (``indptr`` + ``indices`` + ``part_starts``) — what the memory budget
    bounds.  Slightly above ``PartitionedPlanes.max_resident_nbytes``
    because of the shared-compile shape padding."""
    max_loc_pad = _pad_rows(pp.max_local)
    nnz_pad = _pad_nnz(pp.max_nnz)
    deg_cap = _pad_deg_cap(pp.deg_cap)
    return 4 * (pp.n_planes * (max_loc_pad + 1) + nnz_pad + deg_cap + pp.n_parts + 1)


def make_part_plan_arrays(
    plan: SearchPlan, pp: PartitionedPlanes, pid: int
) -> PartPlanArrays:
    """Device arrays for partition ``pid`` — all partitions pad to common
    shapes (see :class:`PartPlanArrays`).  Padded local rows repeat the
    plane's end offset (zero-length rows); padded ``indices`` entries are
    :data:`CSR_SENTINEL`."""
    part = pp.parts[pid]
    max_loc_pad = _pad_rows(pp.max_local)
    nnz_pad = _pad_nnz(pp.max_nnz)
    deg_cap = _pad_deg_cap(pp.deg_cap)
    n_loc = part.n_t
    indptr = np.zeros((pp.n_planes, max_loc_pad + 1), dtype=np.int32)
    indptr[:, : n_loc + 1] = part.indptr
    indptr[:, n_loc + 1 :] = part.indptr[:, -1:]
    indices = np.full(nnz_pad + deg_cap, CSR_SENTINEL, dtype=np.int32)
    indices[: part.nnz] = part.indices
    return PartPlanArrays(
        order_valid=jnp.asarray(plan.order >= 0),
        parent_pos=jnp.asarray(plan.parent_pos, jnp.int32),
        parent_dir=jnp.asarray(plan.parent_dir, jnp.int32),
        parent_elab=jnp.asarray(plan.parent_elab, jnp.int32),
        dom_bits=jnp.asarray(plan.dom_bits, jnp.uint32),
        indptr=jnp.asarray(indptr),
        indices=jnp.asarray(indices),
        seg_iota=jnp.arange(deg_cap, dtype=jnp.int32),
        part_starts=jnp.asarray(pp.node_start, jnp.int32),
        part_lo=jnp.asarray(int(pp.node_start[pid]), jnp.int32),
        part_hi=jnp.asarray(int(pp.node_start[pid + 1]), jnp.int32),
        n_p=jnp.asarray(plan.n_p, jnp.int32),
    )


def part_plan_partition_specs() -> PartPlanArrays:
    """PartitionSpecs for :class:`PartPlanArrays`: fully replicated — under
    a mesh the *same* resident partition is swapped onto every device and
    workers shard over the ``data`` axis (partitions stream through time,
    not across devices)."""
    P = PartitionSpec
    return PartPlanArrays(
        order_valid=P(None),
        parent_pos=P(None, None),
        parent_dir=P(None, None),
        parent_elab=P(None, None),
        dom_bits=P(None, None),
        indptr=P(None, None),
        indices=P(None),
        seg_iota=P(None),
        part_starts=P(None),
        part_lo=P(),
        part_hi=P(),
        n_p=P(),
    )


AnyPlanArrays = Union[PlanArrays, CsrPlanArrays, PartPlanArrays]


def is_csr_only(plan: SearchPlan) -> bool:
    """True for plans built by ``build_csr_plan``: the dense adjacency was
    never materialized, so only the csr layout can run them."""
    return plan.csr is not None and plan.adj_bits.shape[2] == 0


def resolve_step_backend_for_plan(cfg: "EngineConfig", plan: SearchPlan) -> str:
    """:func:`resolve_step_backend` with the plan in hand: a CSR-only plan
    has no dense layout to fall back to, so ``"auto"`` always resolves to
    ``"csr"`` for it — whatever its ``n_t``."""
    if is_csr_only(plan) and cfg.step_backend == "auto":
        return "csr"
    return resolve_step_backend(cfg, plan.n_t)


def validate_backend_for_plan(cfg: "EngineConfig", plan: SearchPlan) -> None:
    """Fail fast when an **explicitly dense** step backend is asked to run
    a CSR-only plan.  :func:`plan_arrays_for` raises for the combination
    anyway, but only after the session has already traced (and counted) an
    engine for the doomed configuration — sessions call this at
    prepare/run entry instead, before any compile is spent."""
    if cfg.step_backend in ("jnp", "pallas") and is_csr_only(plan):
        raise ValueError(
            f"step_backend={cfg.step_backend!r} is a dense backend, but the "
            "plan is CSR-only (layout: csr — built by build_csr_plan, so "
            "dense adj_bits were never materialized); valid backends for "
            "this plan are 'csr', 'auto', or 'partitioned'"
        )


def plan_arrays_for(cfg: "EngineConfig", plan: SearchPlan,
                    adj_bits=None) -> AnyPlanArrays:
    """The one plan-array construction point for both drivers and the
    session: dense :class:`PlanArrays` or sparse :class:`CsrPlanArrays`
    per the resolved step backend.  ``adj_bits`` passes a pre-transferred
    device adjacency through to :func:`make_plan_arrays` (ignored by the
    CSR layout, which never ships the dense bitmaps)."""
    resolved = resolve_step_backend_for_plan(cfg, plan)
    if resolved == "partitioned":
        raise ValueError(
            "step_backend='partitioned' builds per-partition arrays inside "
            "repro.core.engine.run_partitioned (one PartPlanArrays per swap), "
            "not a single monolithic plan-array pytree"
        )
    if resolved == "csr":
        return make_csr_plan_arrays(plan)
    if is_csr_only(plan):
        raise ValueError(
            "plan is CSR-only (built by build_csr_plan: dense adj_bits were "
            "never materialized) — run it with step_backend='csr' or 'auto'"
        )
    return make_plan_arrays(plan, adj_bits=adj_bits)


def csr_shape_bucket(plan: SearchPlan) -> Tuple[int, ...]:
    """``(nnz, *bucket_caps)`` padded shape bucket of a plan's CSR arrays —
    the extra pack-grouping key the session needs under the csr backend: two
    same-``(n_t, w)`` targets of different density have differently shaped
    :class:`CsrPlanArrays` and cannot share a vmapped pack lane.  The former
    scalar ``deg_cap`` entry is now the full pow2 degree-bucket ladder
    (`repro.core.graph.deg_bucket_caps`, DESIGN.md §10): the bucketed walk's
    trip count is derived from the ladder, so targets agreeing on it share a
    compiled engine even when their raw max degrees differ."""
    cp = _plan_csr(plan)
    return (_pad_nnz(cp.nnz),) + deg_bucket_caps(_pad_deg_cap(cp.deg_cap))


def plan_partition_specs_for(cfg: "EngineConfig", n_t: int, csr_only: bool = False):
    """Replicated in-specs matching :func:`plan_arrays_for`'s pytree
    (``csr_only`` mirrors :func:`resolve_step_backend_for_plan`'s rule for
    plans that have no dense layout)."""
    if csr_only and cfg.step_backend == "auto":
        return csr_plan_partition_specs()
    if resolve_step_backend(cfg, n_t) == "csr":
        return csr_plan_partition_specs()
    return plan_partition_specs()


# ---------------------------------------------------------------------------
# bit helpers
# ---------------------------------------------------------------------------

def pop_lowest_bit(cand: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Extract the lowest set bit of a ``[W]`` uint32 bitmap.

    Returns ``(valid, v, cand_without_v)``; ``v`` is the global bit index.
    """
    nz = cand != 0
    valid = jnp.any(nz)
    widx = jnp.argmax(nz)  # first non-zero word (0 if none)
    word = cand[widx]
    # trailing zeros = popcount(~w & (w - 1)); word==0 guarded by `valid`.
    tz = lax.population_count(~word & (word - jnp.uint32(1)))
    v = widx.astype(jnp.int32) * WORD_BITS + tz.astype(jnp.int32)
    cand2 = cand.at[widx].set(word & (word - jnp.uint32(1)))
    return valid, v, cand2


def bit_row(v: jnp.ndarray, w: int) -> jnp.ndarray:
    """One-hot ``[w]`` uint32 bitmap with bit ``v`` set (all-zero when
    ``v`` is out of range).  A compare, not a scatter: vmapped over
    4,096 lanes of 1,034 words, the scatter form lost used-set bits on a
    TPU v5e, and matches repeated a target node."""
    word = v // WORD_BITS
    bit = jnp.uint32(1) << (v % WORD_BITS).astype(jnp.uint32)
    return jnp.where(jnp.arange(w) == word, bit, jnp.uint32(0))


def compute_cand_jnp(
    plan: PlanArrays, pos: jnp.ndarray, map_: jnp.ndarray, used: jnp.ndarray
) -> jnp.ndarray:
    """Candidate bitmap for order position ``pos`` given mapping/used.

    ``dom[pos] ∧ ¬used ∧ ⋀_parents adj_bits[elab, dir, mapped_parent]`` —
    the engine's hot loop; `repro.kernels.extend_step` is the fused Pallas
    form and `repro.kernels.candidate_mask` the mask-only one.
    """
    mp = plan.parent_pos.shape[1]
    safe_pos = jnp.clip(pos, 0, plan.dom_bits.shape[0] - 1)
    cand = plan.dom_bits[safe_pos] & ~used

    def body(j, c):
        pp = plan.parent_pos[safe_pos, j]
        pd = plan.parent_dir[safe_pos, j]
        pl = plan.parent_elab[safe_pos, j]
        t = jnp.where(pp >= 0, map_[jnp.maximum(pp, 0)], 0)
        row = plan.adj_bits[pl, pd, jnp.clip(t, 0, plan.adj_bits.shape[2] - 1)]
        return jnp.where(pp >= 0, c & row, c)

    return lax.fori_loop(0, mp, body, cand)


def host_cand_bitmap(plan: SearchPlan, pos: int, mapping: np.ndarray) -> np.ndarray:
    """Host (numpy) twin of :func:`compute_cand_jnp` for one entry.

    ``mapping`` is a ``[p_pad]`` int array whose positions ``< pos`` hold the
    partial embedding (-1 elsewhere); returns the ``[w]`` uint32 candidate
    bitmap ``dom[pos] ∧ ¬used ∧ ⋀_parents adj_row`` with exactly the
    engine's semantics.  The delta seeding path (DESIGN.md §8) uses this to
    pre-validate engine seeds — the engine trusts stored candidate bitmaps
    and never re-checks them.  Works for dense and CSR-only plans.
    """
    pos = int(pos)
    prefix = np.asarray(mapping[:pos], dtype=np.int64)
    used = bitmap_from_indices(prefix[prefix >= 0], plan.n_t, plan.w)
    cand = plan.dom_bits[pos] & ~used
    dense = plan.adj_bits.shape[2] > 0
    cp = None if dense else _plan_csr(plan)
    for j in range(plan.max_parents):
        pp = int(plan.parent_pos[pos, j])
        if pp < 0:
            continue
        t = int(mapping[pp])
        pd = int(plan.parent_dir[pos, j])
        pl = int(plan.parent_elab[pos, j])
        if dense:
            row = plan.adj_bits[pl, pd, t]
        else:
            plane = pl * 2 + pd
            s, e = int(cp.indptr[plane, t]), int(cp.indptr[plane, t + 1])
            row = bitmap_from_indices(cp.indices[s:e], plan.n_t, plan.w)
        cand = cand & row
    return cand


# ---------------------------------------------------------------------------
# the StepBackend seam
# ---------------------------------------------------------------------------

class StepLanes(NamedTuple):
    """Everything one expansion produces per flattened lane ``[B = V·E]``.

    ``v`` is informational (-1 or unspecified on invalid lanes; every
    consumer gates on ``valid``); the stack payloads are ``cand2`` (the
    parent's residual candidates), ``(map2, used2, child_cand)`` (the
    child entry), and the ``is_match`` / ``has_child`` flags the driver
    accumulates.
    """

    valid: jnp.ndarray  # [B] bool — lane had an untried candidate
    v: jnp.ndarray  # [B] int32 — extracted target node
    is_match: jnp.ndarray  # [B] bool — extension completed the pattern
    has_child: jnp.ndarray  # [B] bool — child has a non-empty candidate set
    cand2: jnp.ndarray  # [B, W] uint32 — parent candidates minus v
    map2: jnp.ndarray  # [B, P] int32 — mapping extended with v
    used2: jnp.ndarray  # [B, W] uint32 — used-bitmap with v set
    child_cand: jnp.ndarray  # [B, W] uint32 — zeroed unless a child is wanted


class StepBackend(Protocol):
    """One expansion over a flat batch of popped lanes (DESIGN.md §6.2).

    Implementations must be bit-identical on every field of
    :class:`StepLanes` that the engine consumes (all but ``v`` on invalid
    lanes); ``tests/test_extend_step.py`` property-tests this.
    """

    name: str

    def expand_lanes(
        self,
        depth: jnp.ndarray,  # [B] int32 (0 on off lanes)
        map_: jnp.ndarray,  # [B, P] int32
        used: jnp.ndarray,  # [B, W] uint32
        cand: jnp.ndarray,  # [B, W] uint32 (0 on off lanes)
    ) -> StepLanes:
        ...


class JnpStepBackend:
    """Reference backend: the loose-ops jnp step (optionally routing the
    candidate-bitmap AND through the ``candidate_mask`` kernel when
    ``cfg.use_pallas`` — the pre-seam kerneling point)."""

    name = "jnp"

    def __init__(self, cfg: "EngineConfig", plan: PlanArrays):
        self.plan = plan
        self.p_pad, self.w = plan.dom_bits.shape
        if cfg.use_pallas:
            from repro.kernels import ops as kops

            rows = kops.flatten_adj_rows(plan.adj_bits)
            n_rows = rows.shape[0] - 1
            n_t = plan.adj_bits.shape[2]
            p_max = self.p_pad - 1

            def compute_cand(pos, map2, used2):
                safe_pos = jnp.clip(pos, 0, p_max)
                row_idx = jax.vmap(
                    lambda p, m: kops.flat_row_index(
                        plan.parent_pos[p], plan.parent_dir[p], plan.parent_elab[p],
                        m, n_t, n_rows,
                    )
                )(safe_pos, map2)
                return kops.candidate_mask(rows, plan.dom_bits, safe_pos, row_idx, used2)
        else:
            compute_one = functools.partial(compute_cand_jnp, plan)

            def compute_cand(pos, map2, used2):
                return jax.vmap(compute_one)(pos, map2, used2)

        self._compute_cand = compute_cand

    def expand_lanes(self, depth, map_, used, cand) -> StepLanes:
        plan = self.plan
        b = depth.shape[0]
        valid, v, cand2 = jax.vmap(pop_lowest_bit)(cand)
        map2 = jnp.where(
            valid[:, None],
            map_.at[jnp.arange(b), jnp.clip(depth, 0, self.p_pad - 1)].set(v),
            map_,
        )
        used2 = jnp.where(
            valid[:, None], used | jax.vmap(bit_row, (0, None))(v, self.w), used
        )
        is_match = valid & (depth + 1 >= plan.n_p)
        want_child = valid & ~is_match
        child_cand = self._compute_cand(jnp.where(want_child, depth + 1, 0), map2, used2)
        child_cand = jnp.where(want_child[:, None], child_cand, jnp.uint32(0))
        has_child = want_child & jnp.any(child_cand != 0, axis=-1)
        return StepLanes(valid, v, is_match, has_child, cand2, map2, used2, child_cand)


class PallasStepBackend:
    """The fused step: one `repro.kernels.extend_step` invocation per
    expansion (DESIGN.md §6.3).

    jnp's only jobs here are scalar bookkeeping the scalar-prefetch
    machinery requires up front — the extracted ``v`` feeds the flattened
    adjacency-row table the kernel's DMA pipeline chases — and the cheap
    ``map2`` / ``used2`` payload updates.  All ``w``-wide work (extraction,
    the AND-tree, child zeroing, match/child flagging) happens inside the
    kernel without intermediate HBM round-trips.
    """

    name = "pallas"

    def __init__(self, cfg: "EngineConfig", plan: PlanArrays):
        from repro.kernels import ops as kops

        self._kops = kops
        self.plan = plan
        self.p_pad, self.w = plan.dom_bits.shape
        self.rows = kops.flatten_adj_rows(plan.adj_bits)
        self.n_rows = self.rows.shape[0] - 1
        self.n_t = plan.adj_bits.shape[2]

    def expand_lanes(self, depth, map_, used, cand) -> StepLanes:
        plan, kops = self.plan, self._kops
        b = depth.shape[0]
        valid_j, v_j, _ = jax.vmap(pop_lowest_bit)(cand)
        map2 = jnp.where(
            valid_j[:, None],
            map_.at[jnp.arange(b), jnp.clip(depth, 0, self.p_pad - 1)].set(v_j),
            map_,
        )
        used2 = jnp.where(
            valid_j[:, None], used | jax.vmap(bit_row, (0, None))(v_j, self.w), used
        )
        child_pos = jnp.clip(depth + 1, 0, self.p_pad - 1)
        row_idx = jax.vmap(
            lambda p, m: kops.flat_row_index(
                plan.parent_pos[p], plan.parent_dir[p], plan.parent_elab[p],
                m, self.n_t, self.n_rows,
            )
        )(child_pos, map2)
        cand2, child_cand, meta = kops.extend_step(
            self.rows, plan.dom_bits, child_pos, row_idx, depth, plan.n_p,
            used, cand,
        )
        valid = meta[:, 0] != 0
        return StepLanes(
            valid=valid,
            v=meta[:, 1],
            is_match=meta[:, 2] != 0,
            has_child=meta[:, 3] != 0,
            cand2=cand2,
            map2=map2,
            used2=used2,
            child_cand=child_cand,
        )


class CsrStepBackend:
    """The sparse backend (DESIGN.md §6.4): child candidates come from a
    CSR walk instead of the dense-row AND-tree.

    Per lane, the driver parent's neighbor segment (its ``indptr`` run,
    gathered ``deg_cap`` wide) proposes candidates; each survives iff its
    bit is set in ``dom[pos+1] ∧ ¬used'`` and a **binary search finds it in
    every other mapped parent's sorted segment** — the sorted-intersection
    of the paper's adjacency lists.  Survivors scatter back into the
    ``[w]`` candidate bitmap the stack stores, so every downstream
    structure (and therefore every result bit) is identical to the dense
    backends.  Parentless positions (disconnected patterns / roots) fall
    back to the plain ``dom ∧ ¬used`` bitmap.

    With ``cfg.use_pallas`` the whole walk (extraction included) runs as
    the `repro.kernels.csr_extend` kernel — scalar-prefetched segment
    bounds, ``pl.ds`` neighbor loads — mirroring how ``use_pallas`` routes
    the dense jnp backend through ``candidate_mask``.
    """

    name = "csr"

    def __init__(self, cfg: "EngineConfig", plan: CsrPlanArrays):
        self.plan = plan
        self.p_pad, self.w = plan.dom_bits.shape
        self.n_planes = plan.indptr.shape[0]
        self.n_t = plan.indptr.shape[1] - 1
        self.deg_cap = plan.seg_iota.shape[0]
        self.use_kernel = cfg.use_pallas
        bucketed = cfg.csr_walk == "bucketed"
        if self.use_kernel:
            from repro.kernels import ops as kops

            if bucketed:
                self._step = functools.partial(
                    kops.csr_extend_bucketed, deg_cap=self.deg_cap
                )
            else:
                self._step = functools.partial(kops.csr_extend, deg_cap=self.deg_cap)
        else:
            from repro.kernels import ref as kref

            step_ref = (
                kref.csr_extend_bucketed_ref if bucketed else kref.csr_extend_ref
            )
            self._step = jax.jit(functools.partial(step_ref, deg_cap=self.deg_cap))

    def _segments(self, pos: jnp.ndarray, map2: jnp.ndarray):
        """Per-lane CSR segment bounds for the child position's parents:
        ``(start, length)`` int32 ``[B, mp]``, length ``-1`` on unused
        parent slots."""
        plan = self.plan
        safe_pos = jnp.clip(pos, 0, self.p_pad - 1)
        pp = plan.parent_pos[safe_pos]  # [B, mp]
        pd = plan.parent_dir[safe_pos]
        pe = plan.parent_elab[safe_pos]
        t = jnp.take_along_axis(map2, jnp.maximum(pp, 0), axis=1)
        t = jnp.clip(jnp.where(pp >= 0, t, 0), 0, self.n_t - 1)
        plane = jnp.clip(pe * 2 + pd, 0, self.n_planes - 1)
        start = plan.indptr[plane, t]
        length = plan.indptr[plane, t + 1] - start
        return start, jnp.where(pp >= 0, length, -1)

    def expand_lanes(self, depth, map_, used, cand) -> StepLanes:
        plan = self.plan
        b = depth.shape[0]
        # scalar bookkeeping before the walk, as in PallasStepBackend: the
        # extracted v feeds map2, whose mapped targets select the CSR
        # segments (a child's parent constraint may reference the
        # just-extended position).
        valid_j, v_j, _ = jax.vmap(pop_lowest_bit)(cand)
        map2 = jnp.where(
            valid_j[:, None],
            map_.at[jnp.arange(b), jnp.clip(depth, 0, self.p_pad - 1)].set(v_j),
            map_,
        )
        used2 = jnp.where(
            valid_j[:, None], used | jax.vmap(bit_row, (0, None))(v_j, self.w), used
        )
        child_pos = jnp.clip(depth + 1, 0, self.p_pad - 1)
        start, length = self._segments(child_pos, map2)
        cand2, child_cand, meta = self._step(
            plan.indices, plan.dom_bits, start, length, child_pos,
            depth, plan.n_p, used, cand,
        )
        return StepLanes(
            valid=meta[:, 0] != 0,
            v=meta[:, 1],
            is_match=meta[:, 2] != 0,
            has_child=meta[:, 3] != 0,
            cand2=cand2,
            map2=map2,
            used2=used2,
            child_cand=child_cand,
        )


class PartStepLanes(NamedTuple):
    """:class:`StepLanes` plus the spill routing a partitioned expansion
    produces (DESIGN.md §9).  ``lanes.has_child`` is narrowed to *live*
    children (fully constrained: every real parent resident and applied);
    ``spill`` flags children with surviving partial candidates that still
    owe intersections to non-resident parents."""

    lanes: StepLanes
    spill: jnp.ndarray  # [B] bool — child parked for a non-resident partition
    pending: jnp.ndarray  # [B] int32 bitmask of unapplied parent slots
    spill_part: jnp.ndarray  # [B] int32 partition of first pending parent (-1)


class PartitionedCsrStepBackend(CsrStepBackend):
    """Partition-aware CSR walk (DESIGN.md §9): candidates are intersected
    with the rows of parents **resident** in the swapped-in partition; the
    remaining parents are recorded in a per-child ``pending`` bitmask and
    the child is flagged for the spill frontier instead of the live stack.

    The walk itself is :class:`CsrStepBackend`'s, with non-resident parent
    slots neutralized exactly like unused slots (segment length ``-1``):
    the driver is the first *resident* parent and membership is tested only
    against resident segments, so the partial candidate set is
    ``dom ∧ ¬used ∧ ⋂ resident parents`` — an over-approximation that the
    outer scheduling loop finishes constraining at intake, when the pending
    parents' partitions become resident.  Because only fully-constrained
    entries ever reach a live stack, every extraction — and therefore every
    match — is exactly a monolithic extraction: the match set is
    bit-identical to the unpartitioned run (the conformance suite gates
    counts *and* sorted mappings per partition count).
    """

    name = "partitioned"

    def __init__(self, cfg: "EngineConfig", plan: PartPlanArrays):
        super().__init__(cfg, plan)
        self.n_parts = plan.part_starts.shape[0] - 1

    def _segments(self, pos: jnp.ndarray, map2: jnp.ndarray):
        """Resident-masked segment bounds plus spill routing: ``(start,
        length, pending, spill_part)`` — length ``-1`` on unused *and*
        non-resident parent slots."""
        plan = self.plan
        mp = plan.parent_pos.shape[1]
        safe_pos = jnp.clip(pos, 0, self.p_pad - 1)
        pp = plan.parent_pos[safe_pos]  # [B, mp]
        pd = plan.parent_dir[safe_pos]
        pe = plan.parent_elab[safe_pos]
        real = pp >= 0
        t = jnp.take_along_axis(map2, jnp.maximum(pp, 0), axis=1)
        t = jnp.where(real, t, 0)
        resident = real & (t >= plan.part_lo) & (t < plan.part_hi)
        t_loc = jnp.clip(t - plan.part_lo, 0, self.n_t - 1)
        plane = jnp.clip(pe * 2 + pd, 0, self.n_planes - 1)
        start = plan.indptr[plane, t_loc]
        length = jnp.where(resident, plan.indptr[plane, t_loc + 1] - start, -1)

        pend_mask = real & ~resident
        pending = jnp.sum(
            pend_mask.astype(jnp.int32) << jnp.arange(mp, dtype=jnp.int32)[None, :],
            axis=1, dtype=jnp.int32,
        )
        first_j = jnp.argmax(pend_mask, axis=1)
        t_first = jnp.take_along_axis(t, first_j[:, None], axis=1)[:, 0]
        spill_part = jnp.searchsorted(plan.part_starts, t_first, side="right") - 1
        spill_part = jnp.where(pending != 0, spill_part.astype(jnp.int32), -1)
        return start, length, pending, spill_part

    def expand_lanes_part(self, depth, map_, used, cand) -> PartStepLanes:
        plan = self.plan
        b = depth.shape[0]
        valid_j, v_j, _ = jax.vmap(pop_lowest_bit)(cand)
        map2 = jnp.where(
            valid_j[:, None],
            map_.at[jnp.arange(b), jnp.clip(depth, 0, self.p_pad - 1)].set(v_j),
            map_,
        )
        used2 = jnp.where(
            valid_j[:, None], used | jax.vmap(bit_row, (0, None))(v_j, self.w), used
        )
        child_pos = jnp.clip(depth + 1, 0, self.p_pad - 1)
        start, length, pending, spill_part = self._segments(child_pos, map2)
        cand2, child_cand, meta = self._step(
            plan.indices, plan.dom_bits, start, length, child_pos,
            depth, plan.n_p, used, cand,
        )
        survived = meta[:, 3] != 0  # want_child ∧ partial candidates non-empty
        live = survived & (pending == 0)
        spill = survived & (pending != 0)
        lanes = StepLanes(
            valid=meta[:, 0] != 0,
            v=meta[:, 1],
            is_match=meta[:, 2] != 0,
            has_child=live,
            cand2=cand2,
            map2=map2,
            used2=used2,
            child_cand=child_cand,
        )
        return PartStepLanes(lanes=lanes, spill=spill, pending=pending,
                             spill_part=spill_part)

    def expand_lanes(self, depth, map_, used, cand) -> StepLanes:
        return self.expand_lanes_part(depth, map_, used, cand).lanes


def make_step_backend(cfg: "EngineConfig", plan: AnyPlanArrays) -> StepBackend:
    """Backend for ``cfg`` over ``plan`` — the array layout must match the
    resolved backend (``plan_arrays_for`` guarantees it; ``"auto"``
    resolves by layout here since the abstract path has no ``n_t``)."""
    if isinstance(plan, PartPlanArrays):
        if cfg.step_backend != "partitioned":
            raise ValueError(
                f"step_backend={cfg.step_backend!r} cannot run PartPlanArrays"
            )
        return PartitionedCsrStepBackend(cfg, plan)
    if cfg.step_backend == "partitioned":
        raise ValueError(
            "step_backend='partitioned' needs PartPlanArrays "
            "(build them with make_part_plan_arrays; run via "
            "repro.core.engine.run_partitioned)"
        )
    if isinstance(plan, CsrPlanArrays):
        if cfg.step_backend not in ("csr", "auto"):
            raise ValueError(
                f"step_backend={cfg.step_backend!r} cannot run CsrPlanArrays"
            )
        return CsrStepBackend(cfg, plan)
    if cfg.step_backend == "csr":
        raise ValueError(
            "step_backend='csr' needs CsrPlanArrays "
            "(build them with make_csr_plan_arrays / plan_arrays_for)"
        )
    if cfg.step_backend in ("jnp", "auto"):
        return JnpStepBackend(cfg, plan)
    if cfg.step_backend == "pallas":
        return PallasStepBackend(cfg, plan)
    raise ValueError(
        f"unknown step_backend {cfg.step_backend!r}; expected one of {STEP_BACKENDS}"
    )


# ---------------------------------------------------------------------------
# the shared expansion step (frontier pop -> backend -> counters -> push)
# ---------------------------------------------------------------------------

def make_step_fn(cfg: "EngineConfig", plan: PlanArrays):
    """Build one full expansion step ``EngineState -> EngineState`` over
    whatever worker axis the caller holds (all ``V`` workers single-device,
    or the local ``V / D`` shard under ``shard_map``) — the one step both
    engine paths share (DESIGN.md §6)."""
    backend = make_step_backend(cfg, plan)
    e = cfg.expand_width

    def step(st: EngineState) -> EngineState:
        v_loc, s_cap = st.st_depth.shape
        pop = frontier.pop_top_k(
            st.st_depth, st.st_map, st.st_used, st.st_cand,
            st.base, st.size, e, store_used=cfg.store_used,
        )

        b = v_loc * e
        lanes = backend.expand_lanes(
            pop.depth.reshape(b),
            pop.map.reshape(b, -1),
            pop.used.reshape(b, -1),
            pop.cand.reshape(b, -1),
        )
        sh2 = lambda x: x.reshape(v_loc, e)  # noqa: E731
        sh3 = lambda x: x.reshape((v_loc, e) + x.shape[1:])  # noqa: E731
        valid = sh2(lanes.valid) & pop.lane_on
        is_match = sh2(lanes.is_match) & pop.lane_on
        has_child = sh2(lanes.has_child) & pop.lane_on
        cand2 = sh3(lanes.cand2)
        map2 = sh3(lanes.map2)
        used2 = sh3(lanes.used2)
        child_cand = sh3(lanes.child_cand)

        states = st.states + jnp.sum(valid, axis=1, dtype=jnp.int32)
        exp_depth = st.exp_depth + jnp.sum(
            jnp.where(valid, pop.depth, 0), axis=1, dtype=jnp.int32
        )
        matches = st.matches + jnp.sum(is_match, axis=1, dtype=jnp.int32)

        mbuf = st.match_buf
        if cfg.collect_matches > 0:
            mcap = mbuf.shape[1]
            # per-lane match ordinal within this step, on top of the
            # pre-step per-worker match count
            m_prefix = jnp.cumsum(is_match.astype(jnp.int32), axis=1) - is_match
            m_slot = (st.matches[:, None] + m_prefix) % mcap
            m_slot = jnp.where(is_match, m_slot, mcap)  # drop non-matches
            vidx = jnp.arange(v_loc, dtype=jnp.int32)[:, None]
            mbuf = mbuf.at[vidx, m_slot].set(map2, mode="drop")

        parent_keep = pop.lane_on & jnp.any(cand2 != 0, axis=-1)
        st_depth, st_map, st_used, st_cand, new_size = frontier.push_entries(
            st.st_depth, st.st_map, st.st_used, st.st_cand, st.base, st.size,
            pop.k, parent_keep, has_child,
            pop.depth, pop.map, pop.used, cand2,
            pop.depth + 1, map2, used2, child_cand,
            store_used=cfg.store_used,
        )
        overflow = st.overflow | frontier.overflowed(new_size, s_cap)
        return st._replace(
            st_depth=st_depth, st_map=st_map, st_used=st_used, st_cand=st_cand,
            size=new_size, matches=matches, states=states,
            exp_depth=exp_depth, match_buf=mbuf, overflow=overflow,
        )

    return step


def make_partitioned_step_fn(cfg: "EngineConfig", plan: PartPlanArrays):
    """The partitioned expansion step ``(EngineState, SpillState) →
    (EngineState, SpillState)``: :func:`make_step_fn`'s pop → expand →
    counters → push pipeline, with children that owe intersections to
    non-resident partitions routed to the worker's spill ring instead of
    the live stack (DESIGN.md §9)."""
    backend = PartitionedCsrStepBackend(cfg, plan)
    e = cfg.expand_width

    def step(st: EngineState, spill: SpillState):
        v_loc, s_cap = st.st_depth.shape
        pop = frontier.pop_top_k(
            st.st_depth, st.st_map, st.st_used, st.st_cand,
            st.base, st.size, e, store_used=cfg.store_used,
        )

        b = v_loc * e
        part = backend.expand_lanes_part(
            pop.depth.reshape(b),
            pop.map.reshape(b, -1),
            pop.used.reshape(b, -1),
            pop.cand.reshape(b, -1),
        )
        lanes = part.lanes
        sh2 = lambda x: x.reshape(v_loc, e)  # noqa: E731
        sh3 = lambda x: x.reshape((v_loc, e) + x.shape[1:])  # noqa: E731
        valid = sh2(lanes.valid) & pop.lane_on
        is_match = sh2(lanes.is_match) & pop.lane_on
        has_child = sh2(lanes.has_child) & pop.lane_on
        do_spill = sh2(part.spill) & pop.lane_on
        cand2 = sh3(lanes.cand2)
        map2 = sh3(lanes.map2)
        used2 = sh3(lanes.used2)
        child_cand = sh3(lanes.child_cand)

        states = st.states + jnp.sum(valid, axis=1, dtype=jnp.int32)
        exp_depth = st.exp_depth + jnp.sum(
            jnp.where(valid, pop.depth, 0), axis=1, dtype=jnp.int32
        )
        matches = st.matches + jnp.sum(is_match, axis=1, dtype=jnp.int32)

        mbuf = st.match_buf
        if cfg.collect_matches > 0:
            mcap = mbuf.shape[1]
            m_prefix = jnp.cumsum(is_match.astype(jnp.int32), axis=1) - is_match
            m_slot = (st.matches[:, None] + m_prefix) % mcap
            m_slot = jnp.where(is_match, m_slot, mcap)
            vidx = jnp.arange(v_loc, dtype=jnp.int32)[:, None]
            mbuf = mbuf.at[vidx, m_slot].set(map2, mode="drop")

        spill = frontier.push_spill(
            spill, do_spill,
            pop.depth + 1, map2, child_cand,
            sh2(part.pending), sh2(part.spill_part),
        )

        parent_keep = pop.lane_on & jnp.any(cand2 != 0, axis=-1)
        st_depth, st_map, st_used, st_cand, new_size = frontier.push_entries(
            st.st_depth, st.st_map, st.st_used, st.st_cand, st.base, st.size,
            pop.k, parent_keep, has_child,
            pop.depth, pop.map, pop.used, cand2,
            pop.depth + 1, map2, used2, child_cand,
            store_used=cfg.store_used,
        )
        overflow = st.overflow | frontier.overflowed(new_size, s_cap)
        st = st._replace(
            st_depth=st_depth, st_map=st_map, st_used=st_used, st_cand=st_cand,
            size=new_size, matches=matches, states=states,
            exp_depth=exp_depth, match_buf=mbuf, overflow=overflow,
        )
        return st, spill

    return step
