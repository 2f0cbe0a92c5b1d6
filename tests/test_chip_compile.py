"""Compile the main path for a TPU v5e chip without one.

The TPU compiler is installed with JAX and compiles for a *described*
``v5e:2x2`` topology, so tiling, SMEM/VMEM and memory refusals that
interpret mode cannot see surface here, at the widths the chip smoke run
uses: phase B's dense target (n_t = 12,575, ppis32) and phase C's sparse
one (n_t = 33,067, pdbsv1).  Nothing runs; results are checked by the
interpret-mode kernel tests.  The topology is described inside a fixture,
never at import, so pytest-xdist workers that are not given this file do
not load the TPU library.
"""

from __future__ import annotations

import dataclasses
import functools
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.sge import ENGINE, MAX_PARENTS, SPARSE_AVG_DEG
from repro.core import engine as eng
from repro.core import extend, frontier
from repro.kernels import csr_extend, domain_ac, extend_step, popcount_reduce

LANES = ENGINE.n_workers * ENGINE.expand_width  # lanes per engine step
P_PAD = 32
NT_DENSE = 12575
NT_SPARSE = 33067
DEG_CAP = 24  # a uniform 33,067-node target at SPARSE_AVG_DEG
N_ARCS = 64
V5E_HBM = 16 * 2**30


def _words(n_t: int) -> int:
    return (n_t + 31) // 32


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means no compiler
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module", autouse=True)
def no_persistent_cache():
    """A chip compile is written to the persistent cache but cannot be read
    back without a chip; keep the cache off around these compiles."""
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compile(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    return jax.jit(fn).lower(*args).compile()


def _sparse_index_len() -> int:
    nnz = 2 * NT_SPARSE * SPARSE_AVG_DEG
    return extend._pad_nnz(nnz) + DEG_CAP


def _kernel_cases():
    u32, i32 = jnp.uint32, jnp.int32
    w_d, w_s = _words(NT_DENSE), _words(NT_SPARSE)
    csr_step = (
        ((_sparse_index_len(),), i32), ((P_PAD, w_s), u32),
        ((LANES, MAX_PARENTS), i32), ((LANES, MAX_PARENTS), i32),
        ((LANES,), i32), ((LANES,), i32), ((), i32),
        ((LANES, w_s), u32), ((LANES, w_s), u32),
    )
    return {
        "extend_step": (
            lambda *a: extend_step.extend_step(*a, interpret=False),
            ((2 * NT_DENSE + 1, w_d), u32), ((P_PAD, w_d), u32),
            ((LANES,), i32), ((LANES, MAX_PARENTS), i32), ((LANES,), i32),
            ((), i32), ((LANES, w_d), u32), ((LANES, w_d), u32),
        ),
        "csr_extend_bucketed": (
            lambda *a: csr_extend.csr_extend_bucketed(
                *a, deg_cap=DEG_CAP, interpret=False),
            *csr_step,
        ),
        "arc_any_sweep": (
            lambda *a: domain_ac.arc_any_sweep(*a, interpret=False),
            ((2, NT_DENSE, w_d), u32), ((N_ARCS,), i32), ((N_ARCS, w_d), u32),
        ),
        "csr_arc_sweep": (
            lambda *a: domain_ac.csr_arc_sweep(
                *a, deg_cap=DEG_CAP, interpret=False),
            ((2, NT_SPARSE), i32), ((2, NT_SPARSE), i32),
            ((_sparse_index_len(),), i32), ((N_ARCS,), i32),
            ((N_ARCS, w_s), u32),
        ),
        "popcount_rows": (
            lambda *a: popcount_reduce.popcount_rows(*a, interpret=False),
            ((P_PAD, w_s), u32),
        ),
    }


@pytest.mark.parametrize("name", sorted(_kernel_cases()))
def test_kernel_compiles_for_v5e(one_chip, name):
    fn, *shapes = _kernel_cases()[name]
    compiled = _compile(fn, one_chip, *shapes)
    assert "tpu_custom_call" in compiled.as_text()


def test_jnp_engine_loop_compiles_for_v5e(one_chip):
    """The default engine at ``configs.sge.ENGINE`` on phase A's target,
    with the match budget phase A streams, fits one chip's HBM."""
    w = _words(NT_DENSE)
    cfg = dataclasses.replace(ENGINE, collect_matches=16384)
    plan = extend.abstract_plan_arrays(NT_DENSE, w, P_PAD, MAX_PARENTS)
    state = frontier.abstract_engine_state(cfg, w, P_PAD)
    place = functools.partial(
        jax.tree.map,
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip))
    compiled = jax.jit(functools.partial(eng._engine_loop, cfg)).lower(
        place(plan), place(state)).compile()
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.temp_size_in_bytes
             + mem.output_size_in_bytes)
    assert total < V5E_HBM, total


def test_pack_engine_compiles_for_v5e(one_chip):
    """The served pack program at the Human target's width (4,674 nodes)
    over 4 lanes of ``configs.sge.ENGINE`` fits one chip's HBM, takes only
    plan arrays and one root bitmap per worker, and returns only counters:
    the worker rings live and die inside it."""
    n_t, lanes = 4674, 4
    w = _words(n_t)
    plan = extend.abstract_plan_arrays(n_t, w, P_PAD, MAX_PARENTS)
    seeds = frontier.Seeds(
        cand=jax.ShapeDtypeStruct((ENGINE.n_workers, 1, w), jnp.uint32))
    stack = functools.partial(
        jax.tree.map,
        lambda s: jax.ShapeDtypeStruct((lanes,) + tuple(s.shape), s.dtype,
                                       sharding=one_chip))
    compiled = eng.make_pack_engine_fn(ENGINE, P_PAD).lower(
        stack(plan), stack(seeds)).compile()
    mem = compiled.memory_analysis()
    nbytes = sum(s.size * s.dtype.itemsize
                 for s in jax.tree.leaves((stack(plan), stack(seeds))))
    assert mem.argument_size_in_bytes <= 1.05 * nbytes  # tiled layouts pad
    assert mem.output_size_in_bytes < lanes * ENGINE.n_workers * 64
    total = (mem.argument_size_in_bytes + mem.temp_size_in_bytes
             + mem.output_size_in_bytes)
    assert total < V5E_HBM, total
