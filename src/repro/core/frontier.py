"""Ring-buffer frontier stacks: the SoA state layer of the engine
(DESIGN.md §6.1).

Each of ``V`` workers owns a ring-buffer stack of search-tree entries in
dense SoA arrays (:class:`EngineState`): an entry is ``(depth, mapping,
used-bitmap, candidate-bitmap)`` and a task is one candidate bit.  This
module owns everything that touches the *stack structure* — popping the
top ``expand_width`` entries, pushing surviving parents below freshly
created children, ring compaction, and overflow accounting — and knows
nothing about *what* an expansion computes (that is `repro.core.extend`,
behind the ``StepBackend`` seam) or how rounds are driven
(`repro.core.engine`).

All ops are batched over the leading worker axis (no ``vmap``): under
``shard_map`` the caller holds the local ``V / D`` shard and every op here
stays worker-local, so the same code serves the single-device and mesh
paths (DESIGN.md §2.4).
"""

from __future__ import annotations

from typing import NamedTuple, Optional, Sequence, Tuple, TYPE_CHECKING

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import PartitionSpec

from repro.core.graph import WORD_BITS, bitmap_from_indices
from repro.core.plan import SearchPlan

if TYPE_CHECKING:  # engine imports extend imports frontier; avoid the cycle
    from repro.core.engine import EngineConfig


class EngineState(NamedTuple):
    st_depth: jnp.ndarray  # [V, S] int32
    st_map: jnp.ndarray  # [V, S, P] int32
    st_used: jnp.ndarray  # [V, S, W] uint32
    st_cand: jnp.ndarray  # [V, S, W] uint32
    base: jnp.ndarray  # [V] int32 ring-buffer base
    size: jnp.ndarray  # [V] int32
    matches: jnp.ndarray  # [V] int32
    states: jnp.ndarray  # [V] int32
    exp_depth: jnp.ndarray  # [V] int32 summed depth of expanded entries
    steals: jnp.ndarray  # [V] int32 entries received
    steal_depth: jnp.ndarray  # [V] int32 summed depth of stolen entries
    steal_rounds: jnp.ndarray  # [] int32 rounds with any transfer
    steps: jnp.ndarray  # [] int32
    overflow: jnp.ndarray  # [] bool — stack high-watermark breached
    match_buf: jnp.ndarray  # [V, Mcap, P] int32 (Mcap >= 1)


class Popped(NamedTuple):
    """Top-of-stack lanes selected by :func:`pop_top_k`.

    Off lanes (``lane_on == False``) carry zeroed depth/candidates so the
    expansion backend never has to re-check the lane mask for validity.
    """

    depth: jnp.ndarray  # [V, E] int32 (0 on off lanes)
    map: jnp.ndarray  # [V, E, P] int32
    used: jnp.ndarray  # [V, E, W] uint32 (materialized even w/o store_used)
    cand: jnp.ndarray  # [V, E, W] uint32 (0 on off lanes)
    lane_on: jnp.ndarray  # [V, E] bool
    k: jnp.ndarray  # [V] int32 entries actually popped per worker


def used_from_map(map_: jnp.ndarray, depth: jnp.ndarray, w: int) -> jnp.ndarray:
    """Reconstruct one entry's used-bitmap from mapped targets at positions
    < depth (the ``store_used=False`` stack representation)."""
    p_pad = map_.shape[0]

    def body(j, u):
        valid = (j < depth) & (map_[j] >= 0)
        t = jnp.maximum(map_[j], 0)
        word = t // WORD_BITS
        bit = jnp.where(valid, jnp.uint32(1) << (t % WORD_BITS).astype(jnp.uint32),
                        jnp.uint32(0))
        return u.at[word].set(u[word] | bit)

    return lax.fori_loop(0, p_pad, body, jnp.zeros((w,), jnp.uint32))


def pop_top_k(
    st_depth: jnp.ndarray,
    st_map: jnp.ndarray,
    st_used: jnp.ndarray,
    st_cand: jnp.ndarray,
    base: jnp.ndarray,
    size: jnp.ndarray,
    expand_width: int,
    store_used: bool = True,
) -> Popped:
    """Select each worker's top ``expand_width`` entries (top-first lanes).

    ``k = min(size, expand_width, free_space)`` per worker — the capacity
    guard: a worker never pops more than it could push back (each popped
    entry re-emits at most a parent + a child, net growth ≤ k), so a full
    ring (``free_space == 0``) freezes rather than corrupts.  Popping is
    logical only — ``size`` is adjusted by the subsequent
    :func:`push_entries`, which reuses the vacated slots.
    """
    v_loc, s_cap = st_depth.shape
    w = st_cand.shape[2]
    e = expand_width

    space = s_cap - size
    k = jnp.minimum(jnp.minimum(size, e), space).astype(jnp.int32)
    lane = jnp.arange(e, dtype=jnp.int32)[None, :]
    lane_on = lane < k[:, None]
    pos = size[:, None] - 1 - lane  # top-first
    slot = jnp.where(lane_on, (base[:, None] + pos) % s_cap, 0)
    vidx = jnp.arange(v_loc, dtype=jnp.int32)[:, None]

    depth = jnp.where(lane_on, st_depth[vidx, slot], 0)
    cand = jnp.where(lane_on[..., None], st_cand[vidx, slot], jnp.uint32(0))
    map_ = st_map[vidx, slot]
    if store_used:
        used = st_used[vidx, slot]
    else:
        used = jax.vmap(jax.vmap(lambda m, d: used_from_map(m, d, w)))(map_, depth)
    return Popped(depth, map_, used, cand, lane_on, k)


def push_entries(
    st_depth: jnp.ndarray,
    st_map: jnp.ndarray,
    st_used: jnp.ndarray,
    st_cand: jnp.ndarray,
    base: jnp.ndarray,
    size: jnp.ndarray,
    k: jnp.ndarray,
    parent_keep: jnp.ndarray,  # [V, E] parents with remaining candidates
    has_child: jnp.ndarray,  # [V, E] lanes that emitted a live child
    p_depth: jnp.ndarray,  # parent re-push payload ([V, E] / [V, E, ...])
    p_map: jnp.ndarray,
    p_used: jnp.ndarray,
    p_cand: jnp.ndarray,
    c_depth: jnp.ndarray,  # child payload
    c_map: jnp.ndarray,
    c_used: jnp.ndarray,
    c_cand: jnp.ndarray,
    store_used: bool = True,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Push surviving parents below their fresh children, lanes k-1 .. 0.

    Emission is reversed-lane (lane k-1 first) so lane 0 — the deepest,
    top-of-stack entry — ends back on top: per-worker DFS order is
    preserved across steps.  Slots are assigned by a per-worker prefix sum
    over ``(parent_keep, has_child)``; invalid lanes address slot
    ``s_cap`` and are dropped by the scatter.  Returns the updated stack
    arrays and the new ``size``.
    """
    v_loc, s_cap = st_depth.shape
    e = parent_keep.shape[1]
    lane = jnp.arange(e, dtype=jnp.int32)
    rev = e - 1 - lane  # reversal is its own inverse
    pk_r = parent_keep[:, rev]
    hc_r = has_child[:, rev]
    per_lane = pk_r.astype(jnp.int32) + hc_r.astype(jnp.int32)
    offs = jnp.cumsum(per_lane, axis=1) - per_lane  # first push of lane rev[i]
    parent_out = jnp.where(pk_r, offs, -1)[:, rev]
    child_out = jnp.where(hc_r, offs + pk_r.astype(jnp.int32), -1)[:, rev]
    total_push = jnp.sum(per_lane, axis=1)

    new_size = size - k + total_push
    push_base = size - k  # logical position of first pushed entry

    def slots_for(out_pos):
        slot = (base[:, None] + push_base[:, None] + out_pos) % s_cap
        return jnp.where(out_pos >= 0, slot, s_cap)

    p_slots = slots_for(parent_out)
    c_slots = slots_for(child_out)
    vidx = jnp.arange(v_loc, dtype=jnp.int32)[:, None]

    st_depth = st_depth.at[vidx, p_slots].set(p_depth, mode="drop")
    st_map = st_map.at[vidx, p_slots].set(p_map, mode="drop")
    st_cand = st_cand.at[vidx, p_slots].set(p_cand, mode="drop")

    st_depth = st_depth.at[vidx, c_slots].set(c_depth, mode="drop")
    st_map = st_map.at[vidx, c_slots].set(c_map, mode="drop")
    st_cand = st_cand.at[vidx, c_slots].set(c_cand, mode="drop")

    if store_used:
        st_used = st_used.at[vidx, p_slots].set(p_used, mode="drop")
        st_used = st_used.at[vidx, c_slots].set(c_used, mode="drop")

    return st_depth, st_map, st_used, st_cand, new_size


def compact(
    st_depth: jnp.ndarray,
    st_map: jnp.ndarray,
    st_used: jnp.ndarray,
    st_cand: jnp.ndarray,
    base: jnp.ndarray,
    size: jnp.ndarray,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray, jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Rotate every ring so its logical bottom lands in slot 0 (base → 0).

    Entry order and contents are unchanged — only the physical layout.
    Steal rounds don't need this (they address slots modulo ``s_cap``),
    but backends that want contiguous stack segments (the sparse-CSR
    direction in ROADMAP.md) and state re-initialization do.
    """
    v_loc, s_cap = st_depth.shape
    j = jnp.arange(s_cap, dtype=jnp.int32)[None, :]
    slot = (base[:, None] + j) % s_cap
    vidx = jnp.arange(v_loc, dtype=jnp.int32)[:, None]
    return (
        st_depth[vidx, slot],
        st_map[vidx, slot],
        st_used[vidx, slot],
        st_cand[vidx, slot],
        jnp.zeros_like(base),
        size,
    )


def overflowed(size: jnp.ndarray, s_cap: int) -> jnp.ndarray:
    """High-watermark check: a completely full ring (``size == s_cap``)
    counts as overflow — the pop guard then freezes the worker, silently
    undercounting, which is why the session retries with a doubled cap
    (`repro.core.session.Enumerator.run`)."""
    return jnp.any(size > s_cap - 1)


# ---------------------------------------------------------------------------
# state construction / sharding metadata
# ---------------------------------------------------------------------------

class Seeds(NamedTuple):
    """The entries a run starts from, before any ring exists: the host's
    whole share of a run's initial state (:func:`state_from_seeds` builds
    the rings around them on the device).

    Worker ``v`` holds its first ``size[v]`` rows at ring slots ``0, 1,
    ...``; rows past ``size`` are padding (empty bitmap, nothing mapped).
    Dealt seeds (edge and delta seeding, :func:`deal_seeds`) carry their
    depth and mapping.  Vertex seeding carries only each worker's depth-0
    root bitmap (``R == 1``) and leaves ``depth``, ``map`` and ``size``
    None: depth 0, nothing mapped, and a worker holds its root iff the
    bitmap is not empty.  A pack stacks one :class:`Seeds` per lane on a
    leading axis (:func:`stack_seeds`).
    """

    cand: np.ndarray  # [V, R, W] uint32
    depth: Optional[np.ndarray] = None  # [V, R] int32
    map: Optional[np.ndarray] = None  # [V, R, P] int32
    size: Optional[np.ndarray] = None  # [V] int32


def seed_rows(plan: SearchPlan, cfg: "EngineConfig") -> Seeds:
    """Initial work distribution, dispatched on ``cfg.root_seeding``
    (DESIGN.md §10).

    ``"vertex"`` is the paper's §3.3 scheme — depth-0 candidates split into
    equal contiguous target-node ranges, one root entry per worker.
    ``"edge"`` enumerates the plan's seed edge class into depth-1 entries
    (:func:`root_seed_entries`) dealt round-robin across workers — the
    HiPerMotif-style injection that shrinks hub-heavy root frontiers by
    orders of magnitude; when the class is too populous for the stacks, it
    falls back to a depth-0 split restricted to the qualifying source
    nodes (a sound pruning — deterministic per ``(plan, cfg)``, so
    counters agree across step backends).  ``"auto"`` is ``"edge"`` iff
    the plan carries a seed edge.  Every execution path — ``engine.run``,
    ``run_sharded``, and the session — seeds through this one function,
    and the match set is identical in all modes.
    """
    mode = cfg.root_seeding
    if mode == "auto":
        mode = "edge" if plan.seed_edge is not None else "vertex"
    if mode == "edge":
        if plan.seed_edge is None:
            raise ValueError(
                "root_seeding='edge' requires a plan built with seed_edge= "
                "(plan.seed_edge is unset; see repro.core.plan.build_plan)"
            )
        sd, sm, sc = root_seed_entries(plan)
        v = cfg.n_workers
        s_cap = cfg.resolved_stack_cap(plan.p_pad)
        k = int(sd.shape[0])
        per_worker = -(-k // v) if k else 0
        if per_worker <= s_cap - 1:
            return deal_seeds(plan, cfg, sd, sm, sc)
        mask = bitmap_from_indices(
            sm[:, 0].astype(np.int64), plan.n_t, plan.w
        )
        return _vertex_seeds(plan, cfg, root_mask=mask)
    return _vertex_seeds(plan, cfg)


def init_state(plan: SearchPlan, cfg: "EngineConfig") -> EngineState:
    """The initial :class:`EngineState` of a run: :func:`seed_rows` on the
    host, the rings around them on the device."""
    return seeded_state(cfg, plan.p_pad, seed_rows(plan, cfg))


def root_seed_entries(plan: SearchPlan):
    """Depth-1 engine seeds for edge-centric root seeding (DESIGN.md §10).

    The seed edge's endpoints hold ordering positions 0/1, so each target
    arc of the seed class becomes one partial embedding: map position 0 to
    the arc's source ``t`` and store position 1's candidate bitmap
    (`repro.core.extend.host_cand_bitmap` — engine-valid, candidates are
    trusted downstream, exactly the PR-7 delta-seed contract).  Sources are
    drawn from ``dom[0]`` restricted to rows with a non-empty segment in
    the seed constraint's plane, so the work is proportional to the *rare
    class*, not the target.  Returns ``(seed_depth [K], seed_map [K,
    p_pad], seed_cand [K, w])`` sorted by source node — deterministic and
    backend-independent, which is what keeps per-backend counters identical
    under edge seeding.
    """
    from repro.core.extend import _plan_csr, host_cand_bitmap

    p_pad, w = plan.p_pad, plan.w
    empty = (
        np.zeros((0,), np.int32),
        np.zeros((0, p_pad), np.int32),
        np.zeros((0, w), np.uint32),
    )
    if not plan.satisfiable or plan.n_p < 2:
        return empty

    from repro.core.graph import bitmap_to_indices

    dom0_idx = bitmap_to_indices(plan.dom_bits[0])
    # the position-1 parent slot referencing position 0 IS the seed edge
    j0 = next(
        (j for j in range(plan.max_parents) if int(plan.parent_pos[1, j]) == 0),
        None,
    )
    if j0 is not None:
        plane = int(plan.parent_elab[1, j0]) * 2 + int(plan.parent_dir[1, j0])
        ptr = _plan_csr(plan).indptr[plane].astype(np.int64)
        lens = ptr[dom0_idx + 1] - ptr[dom0_idx]
        dom0_idx = dom0_idx[lens > 0]
    seeds_m, seeds_c = [], []
    m = np.full(p_pad, -1, dtype=np.int32)
    for t in dom0_idx.tolist():
        m[0] = t
        c1 = host_cand_bitmap(plan, 1, m)
        if c1.any():
            seeds_m.append(m.copy())
            seeds_c.append(c1)
    if not seeds_m:
        return empty
    return (
        np.ones(len(seeds_m), dtype=np.int32),
        np.stack(seeds_m).astype(np.int32),
        np.stack(seeds_c).astype(np.uint32),
    )


def _vertex_seeds(
    plan: SearchPlan, cfg: "EngineConfig", root_mask: Optional[np.ndarray] = None
) -> Seeds:
    """The classic depth-0 root split; ``root_mask`` optionally restricts
    the root candidates (edge seeding's capacity fallback)."""
    v, w = cfg.n_workers, plan.w
    splits = np.linspace(0, plan.n_t, v + 1).astype(np.int64)
    root_cands = np.zeros((v, w), dtype=np.uint32)
    for kk in range(v):
        idx = np.arange(splits[kk], splits[kk + 1])
        if idx.size:
            root_cands[kk] = bitmap_from_indices(idx, plan.n_t, w) & plan.dom_bits[0]
    if root_mask is not None:
        root_cands &= root_mask[None, :]
    if not plan.satisfiable:
        root_cands[:] = 0
    return Seeds(cand=root_cands[:, None, :])


def deal_seeds(
    plan: SearchPlan,
    cfg: "EngineConfig",
    seed_depth: np.ndarray,
    seed_map: np.ndarray,
    seed_cand: np.ndarray,
) -> Seeds:
    """Deal partial-embedding entries round-robin over the ``V`` workers.

    ``seed_depth [K]`` / ``seed_map [K, p_pad]`` / ``seed_cand [K, w]``
    must already be engine-valid (`repro.core.extend.host_cand_bitmap`
    semantics: candidate bits are trusted, never re-checked).  Each
    worker's block is padded to a power of two rows (at most the ring), so
    seed batches of similar size share one traced shape; the caller chunks
    ``K`` so no worker exceeds the stack capacity.
    """
    v = cfg.n_workers
    p_pad, w = plan.p_pad, plan.w
    s_cap = cfg.resolved_stack_cap(p_pad)
    k = int(seed_depth.shape[0])
    per_worker = -(-k // v) if k else 0
    if per_worker > s_cap - 1:
        raise ValueError(
            f"{k} delta seeds over {v} workers exceed stack_cap={s_cap}; "
            "chunk the seed batch"
        )
    rows = min(1 << (max(per_worker, 1) - 1).bit_length(), s_cap)
    i = np.arange(k)
    wk, slot = i % v, i // v
    depth = np.zeros((v, rows), dtype=np.int32)
    map_ = np.full((v, rows, p_pad), -1, dtype=np.int32)
    cand = np.zeros((v, rows, w), dtype=np.uint32)
    depth[wk, slot] = seed_depth
    map_[wk, slot] = seed_map
    cand[wk, slot] = seed_cand
    size = np.bincount(wk, minlength=v).astype(np.int32)
    return Seeds(cand=cand, depth=depth, map=map_, size=size)


def init_delta_state(
    plan: SearchPlan,
    cfg: "EngineConfig",
    seed_depth: np.ndarray,
    seed_map: np.ndarray,
    seed_cand: np.ndarray,
) -> EngineState:
    """Seeded :class:`EngineState` for delta enumeration (DESIGN.md §8).

    Instead of :func:`init_state`'s depth-0 root split, worker stacks start
    from the given partial-embedding entries — one per inserted target edge
    anchored onto a pattern edge — dealt by :func:`deal_seeds`.
    """
    seeds = deal_seeds(plan, cfg, seed_depth, seed_map, seed_cand)
    return seeded_state(cfg, plan.p_pad, seeds)


def without_rows(seeds: Seeds) -> Seeds:
    """``seeds``' shapes holding no entry: a run from them stops at once."""
    return seeds._replace(
        cand=np.zeros_like(seeds.cand),
        size=None if seeds.size is None else np.zeros_like(seeds.size),
    )


def stack_seeds(lanes: Sequence[Seeds], pack: int) -> Seeds:
    """One pack's seeds, lane ``i`` holding ``lanes[i]`` and the rest of
    the ``pack`` lanes none (inert lanes, which the vmapped loop leaves at
    once).  Lanes share one form: vertex roots, unless some lane is dealt
    rows; then every lane is dealt rows, as many as the widest lane's."""
    if all(s.depth is None for s in lanes):
        cand = np.zeros((pack,) + lanes[0].cand.shape, np.uint32)
        for i, s in enumerate(lanes):
            cand[i] = s.cand
        return Seeds(cand=cand)
    v, _, w = lanes[0].cand.shape
    rows = max(s.cand.shape[1] for s in lanes)
    p_pad = next(s.map.shape[-1] for s in lanes if s.map is not None)
    depth = np.zeros((pack, v, rows), np.int32)
    map_ = np.full((pack, v, rows, p_pad), -1, np.int32)
    cand = np.zeros((pack, v, rows, w), np.uint32)
    size = np.zeros((pack, v), np.int32)
    for i, s in enumerate(lanes):
        r = s.cand.shape[1]
        cand[i, :, :r] = s.cand
        if s.depth is None:
            size[i] = s.cand[:, 0].any(axis=1)
        else:
            depth[i, :, :r] = s.depth
            map_[i, :, :r] = s.map
            size[i] = s.size
    return Seeds(cand=cand, depth=depth, map=map_, size=size)


def seed_shape(seeds: Seeds) -> tuple:
    """What a pack's seeds add to its engine's shape: the dealt rows per
    worker, or nothing for vertex roots."""
    return () if seeds.depth is None else (seeds.cand.shape[-2],)


def state_from_seeds(cfg: "EngineConfig", p_pad: int, seeds: Seeds) -> EngineState:
    """Traceable: the rings of one run (unbatched :class:`Seeds`), empty
    but for the seed rows at slots ``0..R-1`` of each worker, and zeroed
    counters.  A dealt row's used-bitmap is its mapped prefix
    (:func:`used_from_map`)."""
    v, r, w = seeds.cand.shape
    s_cap = cfg.resolved_stack_cap(p_pad)
    mcap = max(1, cfg.collect_matches)
    st_depth = jnp.zeros((v, s_cap), jnp.int32)
    st_map = jnp.full((v, s_cap, p_pad), -1, jnp.int32)
    st_used = jnp.zeros((v, s_cap, w if cfg.store_used else 1), jnp.uint32)
    st_cand = jnp.zeros((v, s_cap, w), jnp.uint32).at[:, :r].set(seeds.cand)
    if seeds.depth is None:
        size = jnp.any(seeds.cand[:, 0] != 0, axis=1).astype(jnp.int32)
    else:
        st_depth = st_depth.at[:, :r].set(seeds.depth)
        st_map = st_map.at[:, :r].set(seeds.map)
        if cfg.store_used:
            used = jax.vmap(jax.vmap(lambda m, d: used_from_map(m, d, w)))(
                seeds.map, seeds.depth
            )
            st_used = st_used.at[:, :r].set(used)
        size = jnp.asarray(seeds.size, jnp.int32)
    return EngineState(
        st_depth=st_depth,
        st_map=st_map,
        st_used=st_used,
        st_cand=st_cand,
        base=jnp.zeros((v,), jnp.int32),
        size=size,
        matches=jnp.zeros((v,), jnp.int32),
        states=jnp.zeros((v,), jnp.int32),
        exp_depth=jnp.zeros((v,), jnp.int32),
        steals=jnp.zeros((v,), jnp.int32),
        steal_depth=jnp.zeros((v,), jnp.int32),
        steal_rounds=jnp.zeros((), jnp.int32),
        steps=jnp.zeros((), jnp.int32),
        overflow=jnp.zeros((), jnp.bool_),
        match_buf=jnp.full((v, mcap, p_pad), -1, jnp.int32),
    )


# state_from_seeds as one device program ``(cfg, p_pad, seeds)``, for the
# paths that hand the engine a whole state (single runs, meshes, legs)
seeded_state = jax.jit(state_from_seeds, static_argnums=(0, 1))


# ---------------------------------------------------------------------------
# spill frontier (out-of-core partitioned enumeration, DESIGN.md §9)
# ---------------------------------------------------------------------------

class SpillState(NamedTuple):
    """Per-worker ring of entries parked for a non-resident partition.

    A spill entry is a child whose candidate bitmap is only *partially*
    constrained: ``sp_pending`` bit ``j`` set means parent slot ``j``'s
    adjacency row lives outside the resident partition and has not been
    intersected yet.  ``sp_part`` is the owning partition of the first
    pending parent — the host drains rings at quiescence and routes entries
    into per-partition pools.  The used-bitmap is not stored; intake
    reconstructs it from the mapping prefix (``store_used=False``
    representation).  Same overflow-watermark semantics as the live stack:
    ``sp_overflow`` latches when a push would exceed capacity, and the
    driver treats a near-full ring as a yield point (drain, then resume).
    """

    sp_depth: jnp.ndarray  # [V, C] int32
    sp_map: jnp.ndarray  # [V, C, P] int32
    sp_cand: jnp.ndarray  # [V, C, W] uint32 partially-constrained candidates
    sp_pending: jnp.ndarray  # [V, C] int32 bitmask of unapplied parent slots
    sp_part: jnp.ndarray  # [V, C] int32 partition owning first pending parent
    sp_size: jnp.ndarray  # [V] int32
    sp_overflow: jnp.ndarray  # [] bool — ring watermark breached


def init_spill_state(v: int, spill_cap: int, p_pad: int, w: int) -> SpillState:
    return SpillState(
        sp_depth=jnp.zeros((v, spill_cap), jnp.int32),
        sp_map=jnp.full((v, spill_cap, p_pad), -1, jnp.int32),
        sp_cand=jnp.zeros((v, spill_cap, w), jnp.uint32),
        sp_pending=jnp.zeros((v, spill_cap), jnp.int32),
        sp_part=jnp.full((v, spill_cap), -1, jnp.int32),
        sp_size=jnp.zeros((v,), jnp.int32),
        sp_overflow=jnp.zeros((), jnp.bool_),
    )


def push_spill(
    spill: SpillState,
    flags: jnp.ndarray,  # [V, E] lanes that produced a spill entry
    e_depth: jnp.ndarray,  # [V, E] int32
    e_map: jnp.ndarray,  # [V, E, P] int32
    e_cand: jnp.ndarray,  # [V, E, W] uint32
    e_pending: jnp.ndarray,  # [V, E] int32
    e_part: jnp.ndarray,  # [V, E] int32
) -> SpillState:
    """Append flagged lanes to each worker's spill ring (worker-local, no
    cross-device traffic).  Slots are assigned by per-worker prefix sum;
    pushes past capacity are dropped and latch ``sp_overflow`` — the driver
    yields to the host for a drain well before that (watermark), so the
    latch only fires if a single round overshoots the drain margin.
    """
    v_loc, c_cap = spill.sp_depth.shape
    fl = flags.astype(jnp.int32)
    offs = jnp.cumsum(fl, axis=1) - fl
    slot = jnp.where(flags, spill.sp_size[:, None] + offs, c_cap)
    slot_c = jnp.where(slot < c_cap, slot, c_cap)
    vidx = jnp.arange(v_loc, dtype=jnp.int32)[:, None]
    new_size = spill.sp_size + jnp.sum(fl, axis=1)
    return SpillState(
        sp_depth=spill.sp_depth.at[vidx, slot_c].set(e_depth, mode="drop"),
        sp_map=spill.sp_map.at[vidx, slot_c].set(e_map, mode="drop"),
        sp_cand=spill.sp_cand.at[vidx, slot_c].set(e_cand, mode="drop"),
        sp_pending=spill.sp_pending.at[vidx, slot_c].set(e_pending, mode="drop"),
        sp_part=spill.sp_part.at[vidx, slot_c].set(e_part, mode="drop"),
        sp_size=jnp.minimum(new_size, c_cap).astype(jnp.int32),
        sp_overflow=spill.sp_overflow | jnp.any(new_size > c_cap),
    )


def spill_watermark(spill: SpillState, margin: int) -> jnp.ndarray:
    """True when any worker's ring is within ``margin`` pushes of capacity —
    the driver's cue to return control to the host for a drain."""
    c_cap = spill.sp_depth.shape[1]
    return jnp.any(spill.sp_size >= c_cap - margin)


def spill_partition_specs(axis: str) -> SpillState:
    """PartitionSpecs for :class:`SpillState` under the mesh ``data`` axis."""
    P = PartitionSpec
    return SpillState(
        sp_depth=P(axis, None),
        sp_map=P(axis, None, None),
        sp_cand=P(axis, None, None),
        sp_pending=P(axis, None),
        sp_part=P(axis, None),
        sp_size=P(axis),
        sp_overflow=P(),
    )


def state_partition_specs(axis: str) -> EngineState:
    """PartitionSpecs for :class:`EngineState`: worker-axis arrays sharded
    over ``axis``, loop scalars replicated."""
    P = PartitionSpec
    return EngineState(
        st_depth=P(axis, None),
        st_map=P(axis, None, None),
        st_used=P(axis, None, None),
        st_cand=P(axis, None, None),
        base=P(axis),
        size=P(axis),
        matches=P(axis),
        states=P(axis),
        exp_depth=P(axis),
        steals=P(axis),
        steal_depth=P(axis),
        steal_rounds=P(),
        steps=P(),
        overflow=P(),
        match_buf=P(axis, None, None),
    )


def abstract_engine_state(cfg: "EngineConfig", w: int, p_pad: int) -> EngineState:
    """ShapeDtypeStructs for dry-run lowering without allocation."""
    v = cfg.n_workers
    s_cap = cfg.resolved_stack_cap(p_pad)
    mcap = max(1, cfg.collect_matches)
    w_used = w if cfg.store_used else 1
    sds = jax.ShapeDtypeStruct
    return EngineState(
        st_depth=sds((v, s_cap), jnp.int32),
        st_map=sds((v, s_cap, p_pad), jnp.int32),
        st_used=sds((v, s_cap, w_used), jnp.uint32),
        st_cand=sds((v, s_cap, w), jnp.uint32),
        base=sds((v,), jnp.int32),
        size=sds((v,), jnp.int32),
        matches=sds((v,), jnp.int32),
        states=sds((v,), jnp.int32),
        exp_depth=sds((v,), jnp.int32),
        steals=sds((v,), jnp.int32),
        steal_depth=sds((v,), jnp.int32),
        steal_rounds=sds((), jnp.int32),
        steps=sds((), jnp.int32),
        overflow=sds((), jnp.bool_),
        match_buf=sds((v, mcap, p_pad), jnp.int32),
    )


STATE_LOGICAL = EngineState(
    st_depth=("worker", None),
    st_map=("worker", None, None),
    st_used=("worker", None, "tensor"),
    st_cand=("worker", None, "tensor"),
    base=("worker",),
    size=("worker",),
    matches=("worker",),
    states=("worker",),
    exp_depth=("worker",),
    steals=("worker",),
    steal_depth=("worker",),
    steal_rounds=(),
    steps=(),
    overflow=(),
    match_buf=("worker", None, None),
)
