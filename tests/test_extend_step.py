"""The StepBackend seam (DESIGN.md §6.2) and the fused Pallas extend-step
kernel (§6.3).

Three layers of evidence that the fused step is the loose-ops step:

* kernel vs pure-jnp oracle (`extend_step_ref`), shape/dtype sweeps —
  bit-exact;
* jnp vs pallas-interpret **backends** produce bit-identical
  :class:`EngineState` pytrees (stacks, counters, match buffers) over
  random plans/configs — the hypothesis property test;
* whole-engine runs (single-device and mesh-sharded — the multi-device
  test runs in CI's 4-virtual-device job) agree counter-for-counter.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import EngineConfig, Enumerator, SubgraphIndex
from repro.core import engine as eng
from repro.core import extend
from repro.core.graph import PackedGraph
from repro.core.plan import build_plan
from repro.kernels import ops
from repro.kernels import ref as kref
from tests.conftest import extract_connected_pattern, random_graph

SHAPES_ES = [
    # (b, w, mp, n_rows, p_pad)
    (1, 1, 1, 2, 1),
    (4, 3, 2, 10, 5),
    (16, 130, 4, 64, 8),
    (8, 128, 8, 32, 64),
    (32, 257, 6, 100, 16),
    (64, 13, 0, 7, 4),  # mp == 0: degenerate parent-free plans
]


@pytest.mark.parametrize("b,w,mp,n_rows,p_pad", SHAPES_ES)
def test_extend_step_kernel_vs_oracle(rng, b, w, mp, n_rows, p_pad):
    rows = np.concatenate(
        [
            rng.integers(0, 2**32, (n_rows, w), dtype=np.uint32),
            np.full((1, w), 0xFFFFFFFF, np.uint32),
        ],
        0,
    )
    dom = rng.integers(0, 2**32, (p_pad, w), dtype=np.uint32)
    child_pos = rng.integers(0, p_pad, b).astype(np.int32)
    row_idx = rng.integers(0, n_rows + 1, (b, mp)).astype(np.int32)
    depth = rng.integers(0, p_pad, b).astype(np.int32)
    n_p = np.int32(p_pad // 2 + 1)
    used = rng.integers(0, 2**32, (b, w), dtype=np.uint32)
    # mix of empty, sparse, and dense candidate bitmaps
    cand = rng.integers(0, 2**32, (b, w), dtype=np.uint32)
    cand[:: 3] = 0
    args = [jnp.asarray(x) for x in (rows, dom, child_pos, row_idx, depth,
                                     n_p, used, cand)]
    got = ops.extend_step(*args)
    want = kref.extend_step_ref(*args)
    for g, wnt, name in zip(got, want, ("cand2", "child_cand", "meta")):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(wnt), err_msg=name)


def _case(rng, n=40, m=120, pat_n=5, **graph_kw):
    tgt = random_graph(rng, n, m, n_labels=3, **graph_kw)
    pat = extract_connected_pattern(rng, tgt, pat_n)
    return tgt, pat


def _cfg_pair(**kw):
    a = EngineConfig(step_backend="jnp", **kw)
    b = EngineConfig(step_backend="pallas", **kw)
    return a, b


def _assert_results_identical(a, b):
    assert (a.matches, a.states, a.steps, a.steals, a.steal_rounds) == (
        b.matches, b.states, b.steps, b.steals, b.steal_rounds,
    )
    np.testing.assert_array_equal(a.per_worker_states, b.per_worker_states)
    np.testing.assert_array_equal(a.per_worker_matches, b.per_worker_matches)
    np.testing.assert_array_equal(a.per_worker_steals, b.per_worker_steals)


def test_engine_backends_identical_end_to_end(rng):
    """Whole runs agree counter-for-counter, mappings included."""
    tgt, pat = _case(rng)
    plan = build_plan(pat, PackedGraph.from_graph(tgt))
    cfg_j, cfg_p = _cfg_pair(n_workers=4, expand_width=2, collect_matches=64)
    a = eng.run(plan, cfg_j)
    b = eng.run(plan, cfg_p)
    _assert_results_identical(a, b)
    np.testing.assert_array_equal(a.match_buf, b.match_buf)


def test_engine_backends_identical_store_used_false(rng):
    tgt, pat = _case(rng)
    plan = build_plan(pat, PackedGraph.from_graph(tgt))
    cfg_j, cfg_p = _cfg_pair(n_workers=4, expand_width=2, store_used=False)
    _assert_results_identical(eng.run(plan, cfg_j), eng.run(plan, cfg_p))


def test_session_threads_step_backend(rng):
    """step_backend= flows through Enumerator kwargs; configs with
    different backends must not share a compile-cache entry."""
    tgt, pat = _case(rng)
    idx = SubgraphIndex.build(tgt)
    a = Enumerator(idx, n_workers=2, expand_width=2)
    b = Enumerator(idx, n_workers=2, expand_width=2, step_backend="pallas")
    assert b.config.step_backend == "pallas"
    ra = a.run(a.prepare(pat))
    rb = b.run(b.prepare(pat))
    assert (ra.matches, ra.states, ra.steps) == (rb.matches, rb.states, rb.steps)


def test_unknown_step_backend_rejected():
    with pytest.raises(ValueError):
        EngineConfig(step_backend="bogus")


@pytest.mark.parametrize("v,w", [(0, 1), (31, 2), (32, 2), (33066, 1034), (-1, 3), (96, 3)])
def test_bit_row_one_hot(v, w):
    """Bit ``v`` set and nothing else; out-of-range ``v`` sets nothing."""
    got = np.asarray(jax.vmap(extend.bit_row, (0, None))(jnp.asarray([v]), w))[0]
    want = np.zeros(w, np.uint32)
    if 0 <= v < 32 * w:
        want[v // 32] = np.uint32(1) << np.uint32(v % 32)
    np.testing.assert_array_equal(got, want)


def test_resolve_interpret_follows_backend():
    """Interpret mode iff the backend is not a TPU; nothing overrides it."""
    assert ops.resolve_interpret() is (jax.default_backend() != "tpu")


@pytest.mark.parametrize("kernel", [
    "extend_step.extend_step", "csr_extend.csr_extend",
    "csr_extend.csr_extend_bucketed", "domain_ac.adjacency_any",
    "domain_ac.arc_any_sweep", "domain_ac.csr_arc_sweep",
    "popcount_reduce.popcount_rows", "candidate_mask.candidate_mask",
])
def test_kernels_take_interpret_explicitly(kernel):
    """A direct kernel call must say how it runs: no kernel defaults to
    interpret mode, so a caller on the chip cannot interpret by accident."""
    import importlib
    import inspect

    mod, name = kernel.split(".")
    fn = getattr(importlib.import_module(f"repro.kernels.{mod}"), name)
    param = inspect.signature(fn).parameters["interpret"]
    assert param.default is inspect.Parameter.empty


def _csr_target(rng, n_t, deg_cap, hubs, n_planes=2, avg=6):
    """Sorted CSR planes of a random target with ``hubs`` rows at
    ``deg_cap`` per plane; returns (indptr, sentinel-padded indices)."""
    degs = np.minimum(rng.poisson(avg, (n_planes, n_t)), deg_cap)
    degs[:, :hubs] = deg_cap
    indptr = np.zeros((n_planes, n_t + 1), np.int64)
    indptr[:, 1:] = np.cumsum(degs, axis=1)
    indptr += np.concatenate([[0], np.cumsum(degs.sum(axis=1))[:-1]])[:, None]
    rows = [np.sort(rng.choice(n_t, int(d), replace=False))
            for d in degs.reshape(-1)]
    nnz = int(degs.sum())
    indices = np.full(((nnz + 1023) // 1024) * 1024 + deg_cap, 2**31 - 1,
                      np.int32)
    indices[:nnz] = np.concatenate(rows)
    return indptr, indices


@pytest.mark.parametrize("walk", ["flat", "bucketed"])
@pytest.mark.parametrize("n_t,b,mp,deg_cap,hubs", [
    (300, 16, 3, 16, 0),
    (1500, 12, 4, 1500, 3),  # hub segments span several 1024-word DMAs
])
def test_csr_extend_kernels_vs_oracle(rng, walk, n_t, b, mp, deg_cap, hubs):
    """Both CSR walk kernels against their oracles, with unused parent
    slots, empty candidate sets, completed matches and hub rows."""
    w = (n_t + 31) // 32
    indptr, indices = _csr_target(rng, n_t, deg_cap, hubs)
    p_pad = 8
    plane = rng.integers(0, 2, (b, mp))
    t = rng.integers(0, n_t, (b, mp))
    t[:, 0] = rng.integers(0, max(hubs, 1), b)
    seg_start = indptr[plane, t].astype(np.int32)
    seg_len = (indptr[plane, t + 1] - indptr[plane, t]).astype(np.int32)
    seg_len[rng.random((b, mp)) < 0.25] = -1
    dom = rng.integers(0, 2**32, (p_pad, w), dtype=np.uint32)
    used = (rng.integers(0, 2**32, (b, w), dtype=np.uint32)
            & rng.integers(0, 2**32, (b, w), dtype=np.uint32))
    cand = rng.integers(0, 2**32, (b, w), dtype=np.uint32)
    cand[::4] = 0
    args = [jnp.asarray(x) for x in (
        indices, dom, seg_start, seg_len,
        rng.integers(0, p_pad, b).astype(np.int32),
        rng.integers(0, 5, b).astype(np.int32), np.int32(4), used, cand)]
    if walk == "flat":
        got = ops.csr_extend(*args, deg_cap=deg_cap)
        want = kref.csr_extend_ref(*args, deg_cap=deg_cap)
    else:
        got = ops.csr_extend_bucketed(*args, deg_cap=deg_cap)
        want = kref.csr_extend_bucketed_ref(*args, deg_cap=deg_cap)
    for g, wnt, name in zip(got, want, ("cand2", "child_cand", "meta")):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(wnt), err_msg=name)


def test_csr_arc_sweep_refills_its_window(rng):
    """Planes longer than the kernel's SMEM window (8,192 words) are swept
    in several refills, hub rows included; flags equal the oracle's."""
    n_t, deg_cap = 2000, 1200
    w = (n_t + 31) // 32
    indptr, indices = _csr_target(rng, n_t, deg_cap, hubs=4)
    masks = (rng.integers(0, 2**32, (5, w), dtype=np.uint32)
             & rng.integers(0, 2**32, (5, w), dtype=np.uint32)
             & rng.integers(0, 2**32, (5, w), dtype=np.uint32))
    args = [jnp.asarray(x) for x in (
        indptr[:, :-1].astype(np.int32), np.diff(indptr, axis=1).astype(np.int32),
        indices, np.array([0, 1, 1, 0, 1], np.int32), masks)]
    np.testing.assert_array_equal(
        np.asarray(ops.csr_arc_sweep(*args, deg_cap=deg_cap)),
        np.asarray(kref.csr_arc_sweep_ref(*args, deg_cap=deg_cap)),
    )


# ---------------------------------------------------------------------------
# property test: backends produce bit-identical step states
# ---------------------------------------------------------------------------

try:
    from hypothesis import given, settings, strategies as st

    HAVE_HYPOTHESIS = True
except ImportError:  # pragma: no cover - environment without hypothesis
    HAVE_HYPOTHESIS = False

if HAVE_HYPOTHESIS:

    @settings(max_examples=8, deadline=None)
    @given(
        seed=st.integers(0, 10_000),
        expand_width=st.integers(1, 4),
        n_workers=st.integers(1, 4),
        store_used=st.booleans(),
        collect=st.booleans(),
        n_steps=st.integers(1, 6),
    )
    def test_step_backends_bit_identical_states(
        seed, expand_width, n_workers, store_used, collect, n_steps
    ):
        """jnp and pallas-interpret step backends must produce bit-identical
        EngineState pytrees — stacks, ring bookkeeping, counters, and match
        buffers — after any number of shared expansion steps."""
        rng = np.random.default_rng(seed)
        tgt = random_graph(rng, 16, 40, n_labels=2,
                           selfloops=int(rng.integers(0, 3)))
        pat = extract_connected_pattern(rng, tgt, int(rng.integers(3, 6)))
        if pat.m == 0:
            return
        plan = build_plan(pat, PackedGraph.from_graph(tgt))
        kw = dict(
            n_workers=n_workers,
            expand_width=expand_width,
            store_used=store_used,
            collect_matches=8 if collect else 0,
        )
        cfg_j, cfg_p = _cfg_pair(**kw)
        arrays = eng.make_plan_arrays(plan)

        def run_steps(cfg):
            step = jax.jit(extend.make_step_fn(cfg, arrays))
            state = eng.init_state(plan, cfg)
            for _ in range(n_steps):
                state = step(state)
            return state

        sj = run_steps(cfg_j)
        sp = run_steps(cfg_p)
        for name, a, b in zip(eng.EngineState._fields, sj, sp):
            np.testing.assert_array_equal(
                np.asarray(a), np.asarray(b), err_msg=f"StepState field {name}"
            )


# ---------------------------------------------------------------------------
# mesh path through the shared step (runs in CI's 4-virtual-device job)
# ---------------------------------------------------------------------------

multi_device = pytest.mark.skipif(
    len(jax.devices()) < 2,
    reason="needs >=2 devices (XLA_FLAGS=--xla_force_host_platform_device_count=N)",
)


@multi_device
def test_mesh_path_uses_shared_step_both_backends(rng):
    """Sharding over 2 devices with either backend changes nothing: the
    mesh driver calls the same shared step as the single-device path."""
    tgt, pat = _case(rng, n=48, m=160)
    plan = build_plan(pat, PackedGraph.from_graph(tgt))
    mesh = jax.make_mesh((2,), ("data",), devices=jax.devices()[:2])
    for backend in ("jnp", "pallas"):
        cfg = EngineConfig(n_workers=4, expand_width=2, step_backend=backend)
        ref = eng.run(plan, cfg)
        sh = eng.run(plan, cfg, mesh=mesh)
        _assert_results_identical(ref, sh)
