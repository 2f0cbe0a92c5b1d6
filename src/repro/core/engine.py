"""Frontier-vectorized parallel RI/RI-DS search engine — the driver layer.

The TPU-native form of the paper's work-stealing DFS (DESIGN.md §2),
split into a layered pipeline (DESIGN.md §6): `repro.core.frontier` owns
the ring-buffer stack state and ops, `repro.core.extend` the expansion
step behind the ``StepBackend`` seam (``step_backend="jnp"`` loose-ops
reference / ``"pallas"`` fused `repro.kernels.extend_step` kernel), and
this module only the ``lax.while_loop`` drivers, the steal rounds
(`repro.core.scheduler` decides, this module moves entries), and the
``shard_map`` glue.  **Both** execution paths call the one shared step:

* **single device** (``run(plan, cfg)``): all ``V`` workers in one array
  program; the steal round is plain gathers/scatters over ``V``.
* **mesh-sharded** (``run(plan, cfg, mesh=...)``): the ``V`` axis shards
  over the mesh ``data`` axis via ``shard_map`` (DESIGN.md §2.4); steal
  rounds all-gather occupancy + donor rows, every device computes the
  *same* `repro.core.scheduler.plan_steals`, termination is a cross-device
  ``lax.psum``.  With ``D == 1`` the collectives are identities and
  results are bit-identical to the single-device path.

Counters are per-worker int32 (DESIGN.md §2.5); cross-query aggregation
happens on host in int64.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh

from repro.core import extend, frontier, scheduler
from repro.core.plan import SearchPlan

# Re-exports: the state/plan layers moved out in the §6 split but remain
# importable from the engine (configs/sge.py, session, tests, dryrun).
from repro.core.extend import (  # noqa: F401
    CSR_PLAN_LOGICAL, CsrPlanArrays, PLAN_LOGICAL, PartPlanArrays, PlanArrays,
    abstract_csr_plan_arrays, abstract_plan_arrays, is_csr_only,
    make_csr_plan_arrays, make_part_plan_arrays, make_plan_arrays,
    part_plan_partition_specs, part_resident_nbytes, plan_arrays_for,
    plan_partition_specs, plan_partition_specs_for, plan_partitions,
    resolve_step_backend, resolve_step_backend_for_plan,
)
from repro.core.frontier import (  # noqa: F401
    STATE_LOGICAL, EngineState, SpillState, abstract_engine_state, init_state,
    spill_partition_specs, state_partition_specs,
)
from repro.core.graph import bitmap_from_indices


@dataclasses.dataclass(frozen=True)
class EngineConfig:
    """Static engine parameters.

    Attributes:
      n_workers: number of (virtual) workers ``V``.  On a mesh, ``V`` is
        sharded over the ``data`` axis; on one device all ``V`` run vectorized
        (used by the CPU benchmarks to reproduce the paper's worker sweeps).
      expand_width: entries expanded per worker per step (SIMD lane count).
      steal_chunk: entries a donor offers per steal round — the paper's task
        group size (Fig. 4: 4 is best).
      keep_min: donors never drop below this size.
      recv_cap: max entries a receiver accepts per round.
      rebalance_interval: steps between steal rounds.
      work_stealing: disable to reproduce the paper's Fig. 3 ablation.
      stack_cap: ring-buffer capacity per worker; 0 = auto
        (``expand_width * (p_pad + 2) + steal_chunk + 8``).
      max_steps: safety bound on outer loop iterations (0 = 2**30).
      collect_matches: if > 0, materialize up to this many mappings per worker
        into a ring buffer (the paper's tools print matches; counting is the
        benchmarked mode).
      step_backend: which ``StepBackend`` expands lanes (DESIGN.md §6.2):
        ``"jnp"`` (loose-ops reference), ``"pallas"`` (the fused
        `repro.kernels.extend_step` kernel — interpret mode off-TPU),
        ``"csr"`` (sparse CSR adjacency walk for huge targets, DESIGN.md
        §6.4), or ``"auto"`` (``csr`` past ``extend.CSR_AUTO_NT`` target
        nodes, else ``jnp``).
      use_pallas: with ``step_backend="jnp"``, route only the
        candidate-bitmap AND through `repro.kernels.candidate_mask` (the
        pre-seam kerneling point; the fused backend subsumes it); with
        ``"csr"``, route the CSR walk through `repro.kernels.csr_extend`
        (and a sparse index's AC sweeps at ``prepare`` through
        `repro.kernels.domain_ac.csr_arc_sweep`).
      store_used: keep per-entry used-bitmaps on the stack (True) or
        recompute them from the mapping at expansion time (False; refuted
        as a default by §Perf iteration 7 — see EXPERIMENTS.md §Perf).
      n_partitions: with ``step_backend="partitioned"``, how many
        contiguous row partitions the target streams through (0 → 1).  The
        session derives it from ``memory_budget_bytes``
        (`repro.core.session.Enumerator`).
      spill_cap: per-worker spill-ring capacity under the partitioned
        backend; 0 = auto (see :meth:`resolved_spill_cap`).
      root_seeding: how worker stacks are first populated (DESIGN.md §10):
        ``"vertex"`` — the classic depth-0 root split over the first order
        position's domain; ``"edge"`` — enumerate the plan's seed edge
        class (``plan.seed_edge``, selected by
        `repro.core.ordering.select_seed_edge`) directly into depth-1
        entries, shrinking the root frontier by orders of magnitude on
        hub-heavy targets; ``"auto"`` — ``"edge"`` iff the plan carries a
        seed edge.  The match set is provably identical — seeding changes
        traversal order, never results (the conformance suite gates this).
      csr_walk: CSR driver-segment schedule (DESIGN.md §10): ``"bucketed"``
        (default) clamps each lane's walk to its row's pow2 degree-bucket
        cap (`repro.core.graph.deg_bucket_caps`); ``"flat"`` keeps the
        PR-5 global-``deg_cap`` walk (the benchmark baseline).  Ignored by
        the dense backends.
    """

    n_workers: int = 1
    expand_width: int = 8
    steal_chunk: int = 4
    keep_min: int = 2
    recv_cap: int = 4
    rebalance_interval: int = 8
    work_stealing: bool = True
    stack_cap: int = 0
    max_steps: int = 0
    collect_matches: int = 0
    step_backend: str = "jnp"
    use_pallas: bool = False
    store_used: bool = True
    n_partitions: int = 0
    spill_cap: int = 0
    root_seeding: str = "vertex"
    csr_walk: str = "bucketed"

    def __post_init__(self):
        # "partitioned" is deliberately NOT in STEP_BACKENDS: it is not a
        # drop-in StepBackend (it needs the outer scheduling loop of
        # run_partitioned), so the generic backend-matrix tests don't
        # parametrize over it — it has its own conformance cases.
        valid = extend.STEP_BACKENDS + ("auto", "partitioned")
        if self.step_backend not in valid:
            raise ValueError(
                f"step_backend={self.step_backend!r}; expected one of {valid}"
            )
        if self.root_seeding not in ("vertex", "edge", "auto"):
            raise ValueError(
                f"root_seeding={self.root_seeding!r}; expected "
                "'vertex', 'edge', or 'auto'"
            )
        if self.csr_walk not in ("bucketed", "flat"):
            raise ValueError(
                f"csr_walk={self.csr_walk!r}; expected 'bucketed' or 'flat'"
            )

    def resolved_stack_cap(self, p_pad: int) -> int:
        if self.stack_cap:
            return self.stack_cap
        return self.expand_width * (p_pad + 2) + self.steal_chunk + 8

    def resolved_spill_cap(self, p_pad: int) -> int:
        """Spill-ring capacity: at least 2× the per-round push bound (the
        drain watermark margin, :func:`part_spill_margin`) so the inner
        loop always yields to the host before the ring can overflow."""
        if self.spill_cap:
            return self.spill_cap
        return max(4 * self.resolved_stack_cap(p_pad),
                   2 * self.rebalance_interval * self.expand_width)


class EngineResult(NamedTuple):
    matches: int
    states: int
    steps: int
    steals: int
    steal_rounds: int
    mean_steal_depth: float
    mean_expand_depth: float
    per_worker_states: np.ndarray
    per_worker_matches: np.ndarray
    overflow: bool
    match_buf: Optional[np.ndarray]
    per_worker_steals: Optional[np.ndarray] = None


# ---------------------------------------------------------------------------
# steal round (cross-worker, pure array ops over the V axis)
# ---------------------------------------------------------------------------

def _steal_round(cfg: EngineConfig, state: EngineState) -> EngineState:
    policy = scheduler.StealPolicy(
        steal_chunk=cfg.steal_chunk, keep_min=cfg.keep_min, recv_cap=cfg.recv_cap
    )
    v_workers, s_cap = state.st_depth.shape
    c = cfg.steal_chunk

    donate, accepted, dest_rank, dest_pos = scheduler.plan_steals(state.size, policy)
    wor = scheduler.receiver_workers(state.size)  # [V] worker per rank

    any_transfer = jnp.sum(accepted) > 0

    # gather donated rows from stack bottoms: donor d slot j = logical pos j
    slot_j = jnp.broadcast_to(jnp.arange(c, dtype=jnp.int32), (v_workers, c))
    src_slot = (state.base[:, None] + slot_j) % s_cap  # [V, C]
    didx = jnp.arange(v_workers, dtype=jnp.int32)[:, None]
    don_depth = state.st_depth[didx, src_slot]  # [V, C]
    don_map = state.st_map[didx, src_slot]
    don_used = state.st_used[didx, src_slot]
    don_cand = state.st_cand[didx, src_slot]

    taken = slot_j < accepted[:, None]  # [V, C]
    dest_w = jnp.where(taken, wor[jnp.clip(dest_rank, 0, v_workers - 1)], -1)
    # receivers are empty (size==0) so intake slot = (base + pos) % S
    recv_base = jnp.where(dest_w >= 0, state.base[jnp.maximum(dest_w, 0)], 0)
    dst_slot = (recv_base + dest_pos) % s_cap
    dw = jnp.where(dest_w >= 0, dest_w, v_workers)  # drop invalid

    st_depth = state.st_depth.at[dw, dst_slot].set(don_depth, mode="drop")
    st_map = state.st_map.at[dw, dst_slot].set(don_map, mode="drop")
    st_used = state.st_used.at[dw, dst_slot].set(don_used, mode="drop")
    st_cand = state.st_cand.at[dw, dst_slot].set(don_cand, mode="drop")

    # intake counts / steal metrics per receiver
    flat_w = dw.reshape(-1)
    ones = jnp.where(dest_w.reshape(-1) >= 0, 1, 0)
    recv_cnt = jnp.zeros((v_workers,), jnp.int32).at[flat_w].add(ones, mode="drop")
    depth_add = jnp.zeros((v_workers,), jnp.int32).at[flat_w].add(
        jnp.where(dest_w.reshape(-1) >= 0, don_depth.reshape(-1), 0), mode="drop"
    )

    # donors advance base (accepted slots were their bottom prefix)
    new_base = (state.base + accepted) % s_cap
    new_size = state.size - accepted + recv_cnt

    return state._replace(
        st_depth=st_depth,
        st_map=st_map,
        st_used=st_used,
        st_cand=st_cand,
        base=new_base,
        size=new_size,
        steals=state.steals + recv_cnt,
        steal_depth=state.steal_depth + depth_add,
        steal_rounds=state.steal_rounds + any_transfer.astype(jnp.int32),
    )


# ---------------------------------------------------------------------------
# driver
# ---------------------------------------------------------------------------

def make_expand_fn(cfg: EngineConfig, plan: extend.AnyPlanArrays):
    """Build the purely worker-local part of one engine round:
    ``rebalance_interval`` shared expansion steps
    (`repro.core.extend.make_step_fn`), over whatever worker axis the
    caller holds (all ``V`` workers single-device, or the local ``V / D``
    shard under ``shard_map``).

    Under the CSR backend (:class:`~repro.core.extend.CsrPlanArrays`) each
    round ends with a ring compaction (`repro.core.frontier.compact`): the
    sparse walk's segment gathers want every worker's stack as one
    contiguous bottom-anchored block — the layout hook ``compact``'s
    docstring has anticipated since the §6 split.  Compaction only rotates
    physical slots, so results stay bit-identical (the conformance suite
    asserts this against the dense backends)."""
    step = extend.make_step_fn(cfg, plan)
    is_csr = isinstance(plan, extend.CsrPlanArrays)

    def expand(state: EngineState) -> EngineState:
        state = lax.fori_loop(
            0, cfg.rebalance_interval, lambda _, st: step(st), state
        )
        if is_csr:
            sd, sm, su, sc, base, size = frontier.compact(
                state.st_depth, state.st_map, state.st_used, state.st_cand,
                state.base, state.size,
            )
            state = state._replace(
                st_depth=sd, st_map=sm, st_used=su, st_cand=sc,
                base=base, size=size,
            )
        return state

    return expand


def make_round_fn(cfg: EngineConfig, plan: extend.AnyPlanArrays):
    """Build the body of the outer loop: ``rebalance_interval`` expansion
    steps followed by one steal round.  Exposed separately so the dry-run /
    roofline can lower exactly one round (stable cost accounting)."""
    expand = make_expand_fn(cfg, plan)

    def body(state: EngineState) -> EngineState:
        state = expand(state)
        if cfg.work_stealing and cfg.n_workers > 1:
            state = _steal_round(cfg, state)
        return state._replace(steps=state.steps + cfg.rebalance_interval)

    return body


def _engine_loop(
    cfg: EngineConfig, plan: extend.AnyPlanArrays, state: EngineState
) -> EngineState:
    max_steps = cfg.max_steps or (1 << 30)
    body = make_round_fn(cfg, plan)

    # ~overflow: a full ring freezes its worker (the pop guard yields k=0
    # while size > 0), so an overflowed run can never drain — abort it
    # promptly; the result is undercounted either way and the session
    # retries with a doubled stack_cap (`repro.core.session.Enumerator.run`).
    def cond(state: EngineState) -> jnp.ndarray:
        return (jnp.sum(state.size) > 0) & (state.steps < max_steps) & ~state.overflow

    return lax.while_loop(cond, body, state)


# ---------------------------------------------------------------------------
# mesh-sharded execution: shard_map over the worker axis (DESIGN.md §2.4)
# ---------------------------------------------------------------------------

def mesh_worker_axis(mesh: Mesh) -> str:
    """The mesh axis the worker dimension shards over: ``data`` by
    convention, else the mesh's first axis."""
    return "data" if "data" in mesh.axis_names else mesh.axis_names[0]


def mesh_signature(mesh: Optional[Mesh]) -> Optional[tuple]:
    """Hashable identity of a mesh for compile-cache keys: axis names,
    axis sizes, and the flat device ids."""
    if mesh is None:
        return None
    return (
        tuple(str(a) for a in mesh.axis_names),
        tuple(int(s) for s in mesh.shape.values()),
        tuple(int(d.id) for d in mesh.devices.flat),
    )


def _steal_round_sharded(cfg: EngineConfig, state: EngineState, axis: str) -> EngineState:
    """One steal round under ``shard_map``: ``state`` holds this device's
    ``V / D`` worker stacks.

    The collective form of :func:`_steal_round` (DESIGN.md §2.4):
    ``all_gather`` occupancy → every device runs the same deterministic
    :func:`repro.core.scheduler.plan_steals` (no coordinator) →
    ``all_gather`` each donor's bottom ``steal_chunk`` rows (the steal
    traffic, ``V·C·(1 + P + W_used + W)`` words/round) → each device
    scatters only entries addressed to its local receivers; donors advance
    base by the globally agreed accepted count.  Entry-for-entry identical
    to the unsharded round computed in one address space.
    """
    policy = scheduler.StealPolicy(
        steal_chunk=cfg.steal_chunk, keep_min=cfg.keep_min, recv_cap=cfg.recv_cap
    )
    v_loc, s_cap = state.st_depth.shape
    c = cfg.steal_chunk
    d = lax.axis_index(axis)

    sizes = lax.all_gather(state.size, axis, tiled=True)  # [V]
    v_tot = sizes.shape[0]
    donate, accepted, dest_rank, dest_pos = scheduler.plan_steals(sizes, policy)
    wor = scheduler.receiver_workers(sizes)  # [V] global worker per rank
    any_transfer = jnp.sum(accepted) > 0

    # gather local donors' bottom rows, then all-gather them to every device
    slot_j = jnp.broadcast_to(jnp.arange(c, dtype=jnp.int32), (v_loc, c))
    src_slot = (state.base[:, None] + slot_j) % s_cap  # [V_loc, C]
    lidx = jnp.arange(v_loc, dtype=jnp.int32)[:, None]
    don_depth = lax.all_gather(state.st_depth[lidx, src_slot], axis, tiled=True)
    don_map = lax.all_gather(state.st_map[lidx, src_slot], axis, tiled=True)
    don_used = lax.all_gather(state.st_used[lidx, src_slot], axis, tiled=True)
    don_cand = lax.all_gather(state.st_cand[lidx, src_slot], axis, tiled=True)

    # destination workers (global ids), restricted to this device's shard
    slot_g = jnp.broadcast_to(jnp.arange(c, dtype=jnp.int32), (v_tot, c))
    taken = slot_g < accepted[:, None]  # [V, C]
    dest_w = jnp.where(taken, wor[jnp.clip(dest_rank, 0, v_tot - 1)], -1)
    local_dest = dest_w - d * v_loc
    on_dev = (dest_w >= 0) & (local_dest >= 0) & (local_dest < v_loc)
    safe_dest = jnp.clip(local_dest, 0, v_loc - 1)
    # receivers are empty (size==0) so intake slot = (base + pos) % S
    recv_base = jnp.where(on_dev, state.base[safe_dest], 0)
    dst_slot = (recv_base + dest_pos) % s_cap
    dw = jnp.where(on_dev, safe_dest, v_loc)  # drop off-device slots

    st_depth = state.st_depth.at[dw, dst_slot].set(don_depth, mode="drop")
    st_map = state.st_map.at[dw, dst_slot].set(don_map, mode="drop")
    st_used = state.st_used.at[dw, dst_slot].set(don_used, mode="drop")
    st_cand = state.st_cand.at[dw, dst_slot].set(don_cand, mode="drop")

    # intake counts / steal metrics for local receivers only
    flat_w = dw.reshape(-1)
    on_flat = on_dev.reshape(-1)
    recv_cnt = jnp.zeros((v_loc,), jnp.int32).at[flat_w].add(
        jnp.where(on_flat, 1, 0), mode="drop"
    )
    depth_add = jnp.zeros((v_loc,), jnp.int32).at[flat_w].add(
        jnp.where(on_flat, don_depth.reshape(-1), 0), mode="drop"
    )

    # local donors advance base by their slice of the global accepted vector
    accepted_loc = lax.dynamic_slice_in_dim(accepted, d * v_loc, v_loc)
    new_base = (state.base + accepted_loc) % s_cap
    new_size = state.size - accepted_loc + recv_cnt

    return state._replace(
        st_depth=st_depth,
        st_map=st_map,
        st_used=st_used,
        st_cand=st_cand,
        base=new_base,
        size=new_size,
        steals=state.steals + recv_cnt,
        steal_depth=state.steal_depth + depth_add,
        steal_rounds=state.steal_rounds + any_transfer.astype(jnp.int32),
    )


def _sharded_device_loop(
    cfg: EngineConfig, axis: str, plan: extend.AnyPlanArrays, state: EngineState
) -> EngineState:
    """Per-device program run under ``shard_map``: local expansion rounds
    (the same shared step as the single-device path), collective steal
    rounds, and psum-based termination detection.

    The loop carries the psum'd global entry count so the `while` condition
    is collective-free; every device sees the same count and therefore runs
    the same number of rounds (SPMD lockstep).
    """
    max_steps = cfg.max_steps or (1 << 30)
    expand = make_expand_fn(cfg, plan)

    def global_size(st: EngineState) -> jnp.ndarray:
        return lax.psum(jnp.sum(st.size), axis)

    def global_overflow(st: EngineState) -> jnp.ndarray:
        return lax.psum(st.overflow.astype(jnp.int32), axis) > 0

    def body(carry):
        st, _, _ = carry
        st = expand(st)
        if cfg.work_stealing and cfg.n_workers > 1:
            st = _steal_round_sharded(cfg, st, axis)
        st = st._replace(steps=st.steps + cfg.rebalance_interval)
        return st, global_size(st), global_overflow(st)

    # ~overflow: abort promptly on any device's overflow (see _engine_loop);
    # the psum'd flag keeps every device exiting the same iteration.
    def cond(carry):
        st, gsize, govf = carry
        return (gsize > 0) & (st.steps < max_steps) & ~govf

    state, _, _ = lax.while_loop(
        cond, body, (state, global_size(state), global_overflow(state))
    )
    # overflow is device-local until here; replicate so the P() out-spec holds
    overflow = lax.psum(state.overflow.astype(jnp.int32), axis) > 0
    return state._replace(overflow=overflow)


def make_sharded_engine_fn(
    cfg: EngineConfig, mesh: Mesh, axis: Optional[str] = None, n_t: int = 0,
    csr_only: bool = False,
):
    """Jitted ``(PlanArrays | CsrPlanArrays, EngineState) -> EngineState``
    with the worker axis sharded over ``axis`` of ``mesh`` via ``shard_map``.

    ``cfg.n_workers`` must be a multiple of the axis size (the session API
    snaps it up; `repro.core.session.Enumerator`).  ``n_t`` / ``csr_only``
    feed the ``"auto"`` backend resolution (the plan in-specs pytree must
    match the array layout `plan_arrays_for` will build).
    """
    axis = axis or mesh_worker_axis(mesh)
    n_dev = int(mesh.shape[axis])
    if cfg.n_workers % n_dev:
        raise ValueError(
            f"n_workers={cfg.n_workers} not divisible by mesh axis "
            f"{axis!r} size {n_dev}; round up to a multiple"
        )
    specs = state_partition_specs(axis)
    fn = jax.shard_map(
        functools.partial(_sharded_device_loop, cfg, axis),
        mesh=mesh,
        in_specs=(plan_partition_specs_for(cfg, n_t, csr_only), specs),
        out_specs=specs,
        check_vma=False,
    )
    return jax.jit(fn)


@functools.lru_cache(maxsize=64)
def _sharded_fn_cached(
    cfg: EngineConfig, mesh: Mesh, axis: Optional[str], n_t: int, csr_only: bool
):
    # Mesh hashes by device set + axis names, so repeated direct eng.run()
    # calls over a collection reuse one jitted engine per (cfg, mesh) —
    # the module-level analogue of _run_jit; the session layer keeps its
    # own richer cache (shape buckets, counters).
    return make_sharded_engine_fn(cfg, mesh, axis, n_t=n_t, csr_only=csr_only)


def run_sharded(plan: SearchPlan, cfg: EngineConfig, mesh: Mesh) -> EngineResult:
    """Enumerate with worker stacks sharded over ``mesh`` (see :func:`run`)."""
    fn = _sharded_fn_cached(cfg, mesh, None, plan.n_t, extend.is_csr_only(plan))
    arrays = plan_arrays_for(cfg, plan)
    state = init_state(plan, cfg)
    final = jax.block_until_ready(fn(arrays, state))
    return result_from_state(final, cfg)


@functools.partial(jax.jit, static_argnums=(0,))
def _run_jit(
    cfg: EngineConfig, plan: extend.AnyPlanArrays, state: EngineState
) -> EngineState:
    return _engine_loop(cfg, plan, state)


def run(plan: SearchPlan, cfg: EngineConfig, mesh: Optional[Mesh] = None) -> EngineResult:
    """Enumerate all isomorphic subgraphs described by ``plan``.

    With ``mesh=None`` (the default) all ``V`` workers run in one device
    program — today's single-device behavior, unchanged.  With a mesh the
    worker axis shards over its ``data`` axis (:func:`run_sharded`).
    The plan arrays match the resolved step backend (dense bitmaps, or
    CSR planes for ``step_backend="csr"`` / large-``n_t`` ``"auto"``).
    ``step_backend="partitioned"`` routes to the out-of-core scheduling
    loop (:func:`run_partitioned`), which streams target partitions
    through device memory.
    """
    if cfg.step_backend == "partitioned":
        return run_partitioned(plan, cfg, mesh=mesh)
    if mesh is not None:
        return run_sharded(plan, cfg, mesh)
    arrays = plan_arrays_for(cfg, plan)
    state = init_state(plan, cfg)
    final = jax.block_until_ready(_run_jit(cfg, arrays, state))
    return result_from_state(final, cfg)


class Counters(NamedTuple):
    """What :class:`EngineResult` reads of a drained :class:`EngineState`,
    reduced on the device: sums over workers, the per-worker arrays, loop
    scalars and the match buffer (None unless collecting)."""

    matches: jnp.ndarray  # [] summed over workers
    states: jnp.ndarray  # []
    steals: jnp.ndarray  # []
    steal_depth: jnp.ndarray  # []
    exp_depth: jnp.ndarray  # []
    steps: jnp.ndarray  # []
    steal_rounds: jnp.ndarray  # []
    overflow: jnp.ndarray  # [] bool
    per_worker_states: jnp.ndarray  # [V]
    per_worker_matches: jnp.ndarray  # [V]
    per_worker_steals: jnp.ndarray  # [V]
    match_buf: Optional[jnp.ndarray]  # [V, Mcap, P]


def reduce_state(final: EngineState, cfg: EngineConfig) -> Counters:
    """Traceable: the :class:`Counters` of one drained (unbatched) state."""
    return Counters(
        matches=jnp.sum(final.matches),
        states=jnp.sum(final.states),
        steals=jnp.sum(final.steals),
        steal_depth=jnp.sum(final.steal_depth),
        exp_depth=jnp.sum(final.exp_depth),
        steps=final.steps,
        steal_rounds=final.steal_rounds,
        overflow=final.overflow,
        per_worker_states=final.states,
        per_worker_matches=final.matches,
        per_worker_steals=final.steals,
        match_buf=final.match_buf if cfg.collect_matches else None,
    )


def result_from_counters(c: Counters) -> EngineResult:
    """An :class:`EngineResult` from host :class:`Counters` of one run."""
    steals, sdepth = int(c.steals), int(c.steal_depth)
    states, edepth = int(c.states), int(c.exp_depth)
    return EngineResult(
        matches=int(c.matches),
        states=states,
        steps=int(c.steps),
        steals=steals,
        steal_rounds=int(c.steal_rounds),
        mean_steal_depth=(sdepth / steals) if steals else 0.0,
        mean_expand_depth=(edepth / states) if states else 0.0,
        per_worker_states=np.asarray(c.per_worker_states),
        per_worker_matches=np.asarray(c.per_worker_matches),
        overflow=bool(c.overflow),
        match_buf=None if c.match_buf is None else np.asarray(c.match_buf),
        per_worker_steals=np.asarray(c.per_worker_steals),
    )


def result_from_state(final: EngineState, cfg: EngineConfig) -> EngineResult:
    """Reduce a drained (unbatched) :class:`EngineState` to an
    :class:`EngineResult` with one transfer to the host."""
    return result_from_counters(jax.device_get(reduce_state(final, cfg)))


def make_pack_engine_fn(cfg: EngineConfig, p_pad: int):
    """Jitted ``(stacked plan arrays, stacked Seeds) -> stacked Counters``:
    a pack of same-shape queries, one per vmapped lane, seeded, run and
    reduced in one device program, so the rings never exist on the host
    (:func:`repro.core.frontier.stack_seeds` builds the seeds)."""

    def lane(plan: extend.AnyPlanArrays, seeds: frontier.Seeds) -> Counters:
        state = frontier.state_from_seeds(cfg, p_pad, seeds)
        return reduce_state(_engine_loop(cfg, plan, state), cfg)

    lane.__name__ = "_engine_loop"  # device program jit__engine_loop
    return jax.jit(jax.vmap(lane))


# ---------------------------------------------------------------------------
# out-of-core partitioned execution (DESIGN.md §9)
# ---------------------------------------------------------------------------
#
# The target's adjacency planes are row-partitioned (PartitionedPlanes); at
# any moment exactly ONE partition's planes are device-resident.  Children
# whose parent rows are all resident are fully constrained and go to the
# live stacks; children owing intersections to non-resident rows are
# *partially* constrained and parked in per-worker spill rings with a
# pending-parent bitmask.  The host drains rings into per-partition pools,
# enumerates the resident partition to quiescence, swaps in the partition
# with the deepest pool (round-robin under a mesh), finishes constraining
# its pooled entries at intake (dead / live seed / re-spill toward the next
# pending parent), and repeats until every pool is empty.  Only fully
# constrained entries are ever extracted, so the match set is bit-identical
# to the monolithic run — partitioning changes scheduling, never results.

def part_spill_margin(cfg: EngineConfig) -> int:
    """Max spill pushes per worker per round — the drain watermark: the
    inner loop yields to the host while at least this much ring headroom
    remains, so a round in flight can never overflow the ring."""
    return cfg.rebalance_interval * cfg.expand_width


def make_part_round_fn(cfg: EngineConfig, plan: extend.PartPlanArrays):
    """One partitioned engine round over ``(EngineState, SpillState)``:
    ``rebalance_interval`` partitioned steps, a ring compaction (the CSR
    layout hook), and a live-stack steal round (spill rings are worker-local
    and never stolen from — they hold parked, not runnable, work)."""
    step = extend.make_partitioned_step_fn(cfg, plan)

    def body(carry):
        st, spill = carry
        st, spill = lax.fori_loop(
            0, cfg.rebalance_interval, lambda _, c: step(*c), (st, spill)
        )
        sd, sm, su, sc, base, size = frontier.compact(
            st.st_depth, st.st_map, st.st_used, st.st_cand, st.base, st.size,
        )
        st = st._replace(
            st_depth=sd, st_map=sm, st_used=su, st_cand=sc, base=base, size=size,
        )
        if cfg.work_stealing and cfg.n_workers > 1:
            st = _steal_round(cfg, st)
        return st._replace(steps=st.steps + cfg.rebalance_interval), spill

    return body


def _part_engine_loop(
    cfg: EngineConfig, plan: extend.PartPlanArrays,
    st: EngineState, spill: SpillState,
):
    """Single-device partitioned inner loop: run rounds until the live
    stacks drain, a stack overflows, or a spill ring crosses its drain
    watermark (yield to the host, which drains the rings and re-enters
    with the same live state)."""
    max_steps = cfg.max_steps or (1 << 30)
    body = make_part_round_fn(cfg, plan)
    margin = part_spill_margin(cfg)

    def cond(carry):
        s, sp = carry
        return (
            (jnp.sum(s.size) > 0) & (s.steps < max_steps)
            & ~s.overflow & ~sp.sp_overflow
            & ~frontier.spill_watermark(sp, margin)
        )

    return lax.while_loop(cond, body, (st, spill))


def _part_sharded_device_loop(
    cfg: EngineConfig, axis: str, plan: extend.PartPlanArrays,
    st: EngineState, spill: SpillState,
):
    """Mesh form of :func:`_part_engine_loop`: the resident partition is
    replicated on every device, worker stacks and spill rings shard over
    ``axis``.  Termination (drain / overflow / watermark) is psum'd so all
    devices exit the same iteration and the host drains globally."""
    max_steps = cfg.max_steps or (1 << 30)
    body0 = make_part_round_fn(cfg, plan)
    margin = part_spill_margin(cfg)

    def gsize(s):
        return lax.psum(jnp.sum(s.size), axis)

    def gstop(s, sp):
        local = (
            s.overflow.astype(jnp.int32)
            + sp.sp_overflow.astype(jnp.int32)
            + frontier.spill_watermark(sp, margin).astype(jnp.int32)
        )
        return lax.psum(local, axis) > 0

    def body(carry):
        s, sp, _, _ = carry
        s, sp = body0((s, sp))
        return s, sp, gsize(s), gstop(s, sp)

    def cond(carry):
        s, sp, gs, stop = carry
        return (gs > 0) & (s.steps < max_steps) & ~stop

    st, spill, _, _ = lax.while_loop(
        cond, body, (st, spill, gsize(st), gstop(st, spill))
    )
    # overflow flags are device-local until here; replicate for P() out-specs
    ovf = lax.psum(st.overflow.astype(jnp.int32), axis) > 0
    spovf = lax.psum(spill.sp_overflow.astype(jnp.int32), axis) > 0
    return st._replace(overflow=ovf), spill._replace(sp_overflow=spovf)


def make_partitioned_engine_fn(
    cfg: EngineConfig, mesh: Optional[Mesh] = None, axis: Optional[str] = None
):
    """Jitted ``(PartPlanArrays, EngineState, SpillState) → (EngineState,
    SpillState)`` — the per-leg inner engine :func:`run_partitioned` drives.
    One compile serves every partition of a target: all partitions pad to
    common shapes and the resident row range rides in traced scalars."""
    if mesh is None:
        return jax.jit(functools.partial(_part_engine_loop, cfg))
    axis = axis or mesh_worker_axis(mesh)
    n_dev = int(mesh.shape[axis])
    if cfg.n_workers % n_dev:
        raise ValueError(
            f"n_workers={cfg.n_workers} not divisible by mesh axis "
            f"{axis!r} size {n_dev}; round up to a multiple"
        )
    st_specs = state_partition_specs(axis)
    sp_specs = spill_partition_specs(axis)
    fn = jax.shard_map(
        functools.partial(_part_sharded_device_loop, cfg, axis),
        mesh=mesh,
        in_specs=(extend.part_plan_partition_specs(), st_specs, sp_specs),
        out_specs=(st_specs, sp_specs),
        check_vma=False,
    )
    return jax.jit(fn)


@functools.lru_cache(maxsize=64)
def _part_fn_cached(cfg: EngineConfig, mesh: Optional[Mesh]):
    return make_partitioned_engine_fn(cfg, mesh)


def _intake_entry(plan: SearchPlan, pp, pid: int, depth: int,
                  map_row: np.ndarray, cand: np.ndarray, pending: int):
    """Apply the now-resident pending parents of one pooled entry: AND the
    partition's adjacency rows into ``cand`` and clear their pending bits.
    Returns the updated ``(cand, pending)``."""
    lo, hi = int(pp.node_start[pid]), int(pp.node_start[pid + 1])
    part = pp.parts[pid]
    j = 0
    rem = pending
    while rem:
        if rem & 1:
            ppos = int(plan.parent_pos[depth, j])
            t = int(map_row[ppos])
            if lo <= t < hi:
                plane = int(plan.parent_elab[depth, j]) * 2 + int(
                    plan.parent_dir[depth, j]
                )
                s = int(part.indptr[plane, t - lo])
                e = int(part.indptr[plane, t - lo + 1])
                row = bitmap_from_indices(
                    part.indices[s:e].astype(np.int64), plan.n_t, plan.w
                )
                cand = cand & row
                pending &= ~(1 << j)
        rem >>= 1
        j += 1
    return cand, pending


def _intake_chunk(plan: SearchPlan, pp, pid: int, pools, chunk_n: int):
    """Pop up to ``chunk_n`` entries from partition ``pid``'s pool and
    finish/advance their constraints: dead entries are dropped, still-
    pending entries are re-routed to the partition of their (new) first
    pending parent, fully constrained entries become live seeds.  Returns
    ``(seed_depth, seed_map, seed_cand, n_dead)`` — possibly zero seeds.
    """
    pool = pools[pid]
    sd, sm, sc = [], [], []
    n_dead = 0
    while pool and len(sd) < chunk_n:
        depth, map_row, cand, pending = pool.pop()
        cand, pending = _intake_entry(plan, pp, pid, depth, map_row, cand, pending)
        if not cand.any():
            n_dead += 1
            continue
        if pending:
            j0 = (pending & -pending).bit_length() - 1
            t = int(map_row[int(plan.parent_pos[depth, j0])])
            tgt = int(np.searchsorted(pp.node_start, t, side="right") - 1)
            pools[tgt].append((depth, map_row, cand, pending))
            continue
        sd.append(depth)
        sm.append(map_row)
        sc.append(cand)
    return (
        np.asarray(sd, dtype=np.int32),
        np.asarray(sm, dtype=np.int32).reshape(len(sm), plan.p_pad),
        np.asarray(sc, dtype=np.uint32).reshape(len(sc), plan.w),
        n_dead,
    )


def _drain_spill(spill: SpillState):
    """Pull every worker's spill-ring entries to host tuples
    ``(depth, map, cand, pending, part)`` (the rings' write cursor resets
    device-side; slots past ``sp_size`` are stale and never read)."""
    d_, m_, c_, pe_, pa_, sz_ = jax.device_get((
        spill.sp_depth, spill.sp_map, spill.sp_cand,
        spill.sp_pending, spill.sp_part, spill.sp_size,
    ))
    out = []
    for v in range(sz_.shape[0]):
        for i in range(int(sz_[v])):
            out.append((
                int(d_[v, i]), m_[v, i].copy(), c_[v, i].copy(),
                int(pe_[v, i]), int(pa_[v, i]),
            ))
    return out


_PART_MAX_ATTEMPTS = 4


def partition_root_entries(plan: SearchPlan, cfg: EngineConfig, pp):
    """Root pool entries for the partitioned driver, one batch per owning
    partition (DESIGN.md §10).

    Under vertex seeding each partition gets **one** entry ``(depth=0,
    map=[-1...], cand=dom[0] ∩ its row range, pending=0)`` — roots are
    enumerated while their own rows are resident instead of all seeding on
    the first-visited partition (which spilled nearly every depth-1 child
    whose parent row lived elsewhere).  Under edge seeding the plan's seed
    arcs (`repro.core.frontier.root_seed_entries`) become depth-1 entries
    routed to the partition owning ``map[0]``; they carry no pending
    parents (position 1's constraints all reference position 0 and are
    applied host-side at seed build).  Returns ``[(part, (depth, map_row,
    cand, pending)), ...]`` in deterministic partition/row order.
    """
    mode = cfg.root_seeding
    if mode == "auto":
        mode = "edge" if plan.seed_edge is not None else "vertex"
    entries = []
    if mode == "edge":
        if plan.seed_edge is None:
            raise ValueError(
                "root_seeding='edge' requires a plan built with seed_edge= "
                "(plan.seed_edge is unset; see repro.core.plan.build_plan)"
            )
        sd, sm, sc = frontier.root_seed_entries(plan)
        for i in range(sd.shape[0]):
            part = int(
                np.searchsorted(pp.node_start, int(sm[i, 0]), side="right") - 1
            )
            entries.append((part, (int(sd[i]), sm[i].copy(), sc[i].copy(), 0)))
        return entries
    if not plan.satisfiable:
        return entries
    m0 = np.full(plan.p_pad, -1, dtype=np.int32)
    for pid in range(pp.n_parts):
        lo, hi = int(pp.node_start[pid]), int(pp.node_start[pid + 1])
        if hi <= lo:
            continue
        cand = plan.dom_bits[0] & bitmap_from_indices(
            np.arange(lo, hi), plan.n_t, plan.w
        )
        if cand.any():
            entries.append((pid, (0, m0.copy(), cand, 0)))
    return entries


def run_partitioned(
    plan: SearchPlan,
    cfg: EngineConfig,
    mesh: Optional[Mesh] = None,
    engine_factory=None,
    stats: Optional[dict] = None,
) -> EngineResult:
    """Enumerate ``plan`` against a row-partitioned target streamed through
    device memory — the outer scheduling loop of the out-of-core path
    (DESIGN.md §9).

    ``cfg.n_partitions`` partitions (0 → 1; with 1 no extension can ever
    leave the resident range, degenerating to the CSR backend's behavior)
    are visited: the resident one is enumerated to quiescence in *legs*
    (seed → inner-loop to drain, with host ring-drains at the spill
    watermark), then the partition with the deepest spill pool is swapped
    in (round-robin under a mesh) and re-seeded from its pooled entries.
    Stack or spill-ring overflow retries the leg with the affected capacity
    doubled (the PR-4 watermark semantics, leg-scoped).

    ``engine_factory(cfg) → fn`` overrides the inner-engine builder (the
    session routes it through its compile cache); ``stats`` — if given — is
    filled with partition/scheduling counters (resident bytes, visits,
    legs, spills, deaths).
    """
    if cfg.step_backend != "partitioned":
        cfg = dataclasses.replace(cfg, step_backend="partitioned")
    n_parts = max(1, cfg.n_partitions)
    pp = extend.plan_partitions(plan, n_parts)
    p_pad, w, v = plan.p_pad, plan.w, cfg.n_workers
    mcap = max(1, cfg.collect_matches)
    if engine_factory is None:
        engine_factory = lambda c: _part_fn_cached(c, mesh)  # noqa: E731

    pools = [[] for _ in range(n_parts)]
    leg_cfg = cfg
    totals = dict(matches=0, states=0, steps=0, steals=0, steal_rounds=0,
                  steal_depth=0, exp_depth=0)
    pw_states = np.zeros(v, dtype=np.int64)
    pw_matches = np.zeros(v, dtype=np.int64)
    pw_steals = np.zeros(v, dtype=np.int64)
    match_rows = []
    n_visits = n_legs = n_rounds = n_spilled = n_dead = 0
    max_pool = 0

    def run_leg(arrays, seed):
        """One leg: seed → inner loop to quiescence (draining rings at the
        watermark); retries with doubled caps on overflow.  Returns the
        final state and this leg's staged spill entries."""
        nonlocal leg_cfg, n_rounds
        for _ in range(_PART_MAX_ATTEMPTS):
            fn = engine_factory(leg_cfg)
            st = frontier.init_delta_state(plan, leg_cfg, *seed)
            spill = frontier.init_spill_state(
                v, leg_cfg.resolved_spill_cap(p_pad), p_pad, w
            )
            staged = []
            retry = False
            while True:
                st, spill = jax.block_until_ready(fn(arrays, st, spill))
                n_rounds += 1
                if bool(st.overflow):
                    leg_cfg = dataclasses.replace(
                        leg_cfg, stack_cap=2 * leg_cfg.resolved_stack_cap(p_pad)
                    )
                    retry = True
                    break
                if bool(spill.sp_overflow):
                    leg_cfg = dataclasses.replace(
                        leg_cfg, spill_cap=2 * leg_cfg.resolved_spill_cap(p_pad)
                    )
                    retry = True
                    break
                staged.extend(_drain_spill(spill))
                spill = spill._replace(
                    sp_size=jnp.zeros_like(spill.sp_size),
                    sp_overflow=jnp.zeros_like(spill.sp_overflow),
                )
                max_steps = leg_cfg.max_steps or (1 << 30)
                if int(jnp.sum(st.size)) == 0 or int(st.steps) >= max_steps:
                    return st, staged
            if not retry:  # pragma: no cover — loop exits via return/break
                break
        raise RuntimeError(
            f"partitioned leg kept overflowing after {_PART_MAX_ATTEMPTS} "
            f"capacity doublings (stack_cap={leg_cfg.stack_cap}, "
            f"spill_cap={leg_cfg.spill_cap})"
        )

    def absorb(st, staged):
        """Fold a completed leg into the run totals and commit its spills."""
        nonlocal n_spilled, max_pool, pw_states, pw_matches, pw_steals
        totals["matches"] += int(jnp.sum(st.matches))
        totals["states"] += int(jnp.sum(st.states))
        totals["steps"] += int(st.steps)
        totals["steals"] += int(jnp.sum(st.steals))
        totals["steal_rounds"] += int(st.steal_rounds)
        totals["steal_depth"] += int(jnp.sum(st.steal_depth))
        totals["exp_depth"] += int(jnp.sum(st.exp_depth))
        pw_states += np.asarray(st.states, dtype=np.int64)
        pw_matches += np.asarray(st.matches, dtype=np.int64)
        pw_steals += np.asarray(st.steals, dtype=np.int64)
        if cfg.collect_matches:
            m = np.asarray(st.matches)
            buf = np.asarray(st.match_buf)
            for v_ in range(v):
                k = min(int(m[v_]), mcap)
                if k:
                    match_rows.append(buf[v_, :k])
        for depth, map_row, cand, pending, part in staged:
            pools[part].append((depth, map_row, cand, pending))
        n_spilled += len(staged)
        max_pool = max(max_pool, max((len(p) for p in pools), default=0))

    # Roots enter through the pools, each batch owned by the partition whose
    # rows it maps (DESIGN.md §10) — the first leg of every partition extends
    # against resident parent rows instead of spilling depth-1 children.
    for part, entry in partition_root_entries(plan, cfg, pp):
        pools[part].append(entry)

    current = next((pid for pid in range(n_parts) if pools[pid]), None)
    while current is not None:
        arrays = extend.make_part_plan_arrays(plan, pp, current)
        n_visits += 1
        while True:
            chunk_n = v * max(leg_cfg.resolved_stack_cap(p_pad) // 2, 1)
            sd, sm, sc, dead = _intake_chunk(plan, pp, current, pools, chunk_n)
            n_dead += dead
            if sd.shape[0] == 0:
                if pools[current]:
                    continue  # chunk was all dead/re-routed; keep draining
                break  # partition quiescent
            st, staged = run_leg(arrays, (sd, sm, sc))
            absorb(st, staged)
            n_legs += 1
        nxt = None
        if mesh is not None:  # round-robin partition rotation under a mesh
            for off in range(1, n_parts + 1):
                cand_p = (current + off) % n_parts
                if pools[cand_p]:
                    nxt = cand_p
                    break
        else:  # deepest spill pool first
            depth_best = 0
            for pid in range(n_parts):
                if len(pools[pid]) > depth_best:
                    nxt, depth_best = pid, len(pools[pid])
        if nxt is None:
            break
        current = nxt

    if stats is not None:
        stats.update(
            n_parts=n_parts,
            visits=n_visits,
            legs=n_legs,
            rounds=n_rounds,
            spilled=n_spilled,
            dead_spills=n_dead,
            max_pool=max_pool,
            cut_edges=pp.cut_edges,
            resident_plane_bytes=extend.part_resident_nbytes(pp),
            per_part_nbytes=[p.nbytes for p in pp.parts],
            final_stack_cap=leg_cfg.resolved_stack_cap(p_pad),
            final_spill_cap=leg_cfg.resolved_spill_cap(p_pad),
        )

    match_buf = None
    if cfg.collect_matches:
        rows = (
            np.concatenate(match_rows, axis=0)
            if match_rows else np.zeros((0, p_pad), np.int32)
        )
        match_buf = np.full((1, max(1, rows.shape[0]), p_pad), -1, np.int32)
        if rows.shape[0]:
            match_buf[0, : rows.shape[0]] = rows

    steals = totals["steals"]
    states = totals["states"]
    return EngineResult(
        matches=totals["matches"],
        states=states,
        steps=totals["steps"],
        steals=steals,
        steal_rounds=totals["steal_rounds"],
        mean_steal_depth=(totals["steal_depth"] / steals) if steals else 0.0,
        mean_expand_depth=(totals["exp_depth"] / states) if states else 0.0,
        per_worker_states=pw_states,
        per_worker_matches=pw_matches,
        overflow=False,
        match_buf=match_buf,
        per_worker_steals=pw_steals,
    )
