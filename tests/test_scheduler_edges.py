"""Deterministic edge cases for the steal policy and the engine's steal
round (complementing the hypothesis sweep in test_scheduler.py, which is
skipped when hypothesis is absent): no donors, keep_min / recv_cap clamps,
single-worker no-op, and conservation of entries through a full round.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import engine as eng
from repro.core.engine import EngineConfig, EngineState
from repro.core.scheduler import StealPolicy, plan_steals, receiver_workers


def _plan(sizes, **kw):
    policy = StealPolicy(**kw)
    return tuple(np.asarray(x) for x in plan_steals(jnp.asarray(sizes, jnp.int32), policy))


def test_all_empty_stacks_no_donors():
    donate, accepted, dest_rank, _ = _plan([0, 0, 0, 0])
    assert donate.sum() == 0
    assert accepted.sum() == 0
    assert np.all(dest_rank == -1)


def test_no_receivers_no_transfers():
    donate, accepted, dest_rank, _ = _plan([10, 10, 10])
    assert donate.sum() > 0  # offers exist...
    assert accepted.sum() == 0  # ...but nobody is hungry
    assert np.all(dest_rank == -1)


def test_donor_clamped_at_keep_min():
    donate, accepted, _, _ = _plan([10, 4, 3, 0], steal_chunk=8, keep_min=3)
    assert donate.tolist() == [7, 1, 0, 0]  # never below keep_min
    assert np.all(accepted <= donate)


def test_receiver_clamped_at_recv_cap():
    # three eager donors, one receiver with cap 2: exactly 2 move
    donate, accepted, dest_rank, dest_pos = _plan(
        [9, 9, 9, 0], steal_chunk=4, keep_min=0, recv_cap=2
    )
    assert donate.tolist() == [4, 4, 4, 0]
    assert accepted.sum() == 2
    taken = dest_rank >= 0
    assert np.all(dest_rank[taken] == 0)
    assert sorted(dest_pos[taken].tolist()) == [0, 1]


def test_single_worker_noop():
    donate, accepted, dest_rank, _ = _plan([7])
    assert donate.tolist() == [4]  # offers, with nobody to take
    assert accepted.sum() == 0
    assert np.all(dest_rank == -1)
    # the engine additionally skips the round entirely at n_workers == 1
    cfg = EngineConfig(n_workers=1, expand_width=2)
    state = _toy_state([5], cfg)
    out = eng._steal_round(cfg, state)
    assert np.asarray(out.size).tolist() == [5]
    assert int(out.steal_rounds) == 0


def _toy_state(sizes, cfg, s_cap=8, p_pad=4, w=1):
    """An EngineState whose stack entries are tagged (worker, position) so
    conservation can be checked entry-for-entry; bases are staggered so the
    ring-buffer wraparound path is exercised."""
    v = len(sizes)
    st_depth = np.zeros((v, s_cap), np.int32)
    st_map = np.full((v, s_cap, p_pad), -1, np.int32)
    st_used = np.zeros((v, s_cap, w), np.uint32)
    st_cand = np.zeros((v, s_cap, w), np.uint32)
    base = np.asarray([(3 * k) % s_cap for k in range(v)], np.int32)
    for k, sz in enumerate(sizes):
        for j in range(sz):
            slot = (base[k] + j) % s_cap
            st_depth[k, slot] = 1 + j
            st_map[k, slot, 0] = 100 * k + j  # unique entry tag
            st_used[k, slot, 0] = np.uint32(1 + k)
            st_cand[k, slot, 0] = np.uint32(1 + j)
    return EngineState(
        st_depth=jnp.asarray(st_depth),
        st_map=jnp.asarray(st_map),
        st_used=jnp.asarray(st_used),
        st_cand=jnp.asarray(st_cand),
        base=jnp.asarray(base),
        size=jnp.asarray(sizes, jnp.int32),
        matches=jnp.zeros((v,), jnp.int32),
        states=jnp.zeros((v,), jnp.int32),
        exp_depth=jnp.zeros((v,), jnp.int32),
        steals=jnp.zeros((v,), jnp.int32),
        steal_depth=jnp.zeros((v,), jnp.int32),
        steal_rounds=jnp.zeros((), jnp.int32),
        steps=jnp.zeros((), jnp.int32),
        overflow=jnp.zeros((), jnp.bool_),
        match_buf=jnp.full((v, 1, p_pad), -1, jnp.int32),
    )


def _entries(state):
    """Multiset of live stack entries as (depth, tag, used, cand) tuples."""
    depth = np.asarray(state.st_depth)
    tag = np.asarray(state.st_map)[:, :, 0]
    used = np.asarray(state.st_used)[:, :, 0]
    cand = np.asarray(state.st_cand)[:, :, 0]
    base = np.asarray(state.base)
    size = np.asarray(state.size)
    s_cap = depth.shape[1]
    out = []
    for k in range(depth.shape[0]):
        for j in range(size[k]):
            slot = (base[k] + j) % s_cap
            out.append((int(depth[k, slot]), int(tag[k, slot]),
                        int(used[k, slot]), int(cand[k, slot])))
    return sorted(out)


def test_steal_round_conserves_entries():
    cfg = EngineConfig(n_workers=4, expand_width=2,
                       steal_chunk=3, keep_min=1, recv_cap=2)
    state = _toy_state([6, 0, 5, 0], cfg)
    before = _entries(state)
    out = eng._steal_round(cfg, state)
    after = _entries(out)
    assert int(np.asarray(out.size).sum()) == len(before)
    assert after == before  # same entries, just redistributed
    assert int(np.asarray(out.steals).sum()) > 0  # something actually moved
    # donors kept >= keep_min, receivers got <= recv_cap
    assert np.all(np.asarray(out.size)[[0, 2]] >= cfg.keep_min)
    assert np.all(np.asarray(out.steals) <= cfg.recv_cap)


def test_sharded_steal_round_matches_unsharded_on_one_device():
    """The shard_map round with D=1 (collectives are identities) must be
    state-for-state identical to the plain round."""
    cfg = EngineConfig(n_workers=4, expand_width=2,
                       steal_chunk=3, keep_min=1, recv_cap=2)
    state = _toy_state([6, 0, 5, 0], cfg)
    ref = eng._steal_round(cfg, state)

    mesh = jax.make_mesh((1,), ("data",), devices=jax.devices()[:1])
    specs = eng.state_partition_specs("data")
    fn = jax.shard_map(
        functools.partial(eng._steal_round_sharded, cfg, axis="data"),
        mesh=mesh, in_specs=(specs,), out_specs=specs, check_vma=False,
    )
    out = jax.jit(fn)(state)
    for name in EngineState._fields:
        np.testing.assert_array_equal(
            np.asarray(getattr(ref, name)), np.asarray(getattr(out, name)), err_msg=name
        )
