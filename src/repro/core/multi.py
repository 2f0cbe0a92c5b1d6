"""Deprecated multi-query driver — now a shim over `repro.core.session`.

The LPT pack balancing, plan stacking and vmapped engine execution that
lived here migrated into :class:`repro.core.session.Enumerator`
(``run_batch`` / ``stream``), which adds shape-bucketed compile caching on
top.  New code should use the session API::

    from repro.core.session import Enumerator, SubgraphIndex
    enum = Enumerator(SubgraphIndex.build(target), config=cfg)
    results = enum.run_batch([enum.prepare(p) for p in patterns])

:func:`enumerate_many` is kept with its original signature and now returns
**exactly one result per input pattern, in input order** (the old
implementation silently dropped unprocessed queries and lost name
alignment).  :func:`run_batch` over raw plans is kept for callers that
stack their own same-shaped plans.
"""

from __future__ import annotations

import dataclasses
from typing import List, Optional, Sequence

import jax
import jax.numpy as jnp

from repro.core import engine as eng
from repro.core import frontier
from repro.core.engine import EngineConfig
from repro.core.graph import Graph
from repro.core.plan import SearchPlan
from repro.core.session import Enumerator, SubgraphIndex


@dataclasses.dataclass
class QueryResult:
    name: str
    matches: int
    states: int
    steps: int


def _stack_plans(plans: Sequence[SearchPlan], cfg: EngineConfig):
    arrays = [eng.plan_arrays_for(cfg, p) for p in plans]
    return jax.tree.map(lambda *xs: jnp.stack(xs), *arrays)


def run_batch(plans: Sequence[SearchPlan], cfg: EngineConfig):
    """Run a pack of same-shaped plans; returns stacked final EngineStates.

    Deprecated: prefer :meth:`Enumerator.run_batch`, which adds LPT
    balancing, bucket grouping and compile caching."""
    stacked = _stack_plans(plans, cfg)
    seeds = frontier.stack_seeds([frontier.seed_rows(p, cfg) for p in plans], len(plans))
    p_pad = plans[0].p_pad

    @jax.jit
    def go(plan_arrays, sd):
        return jax.vmap(lambda pl, s: eng._engine_loop(
            cfg, pl, frontier.state_from_seeds(cfg, p_pad, s)))(plan_arrays, sd)

    return jax.block_until_ready(go(stacked, seeds))


def enumerate_many(
    patterns: Sequence[Graph],
    target: Graph,
    variant: str = "ri-ds-si-fc",
    cfg: Optional[EngineConfig] = None,
    pack_size: int = 4,
    names: Optional[Sequence[str]] = None,
) -> List[QueryResult]:
    """Enumerate every pattern against ``target`` in LPT-balanced packs.

    Compatibility wrapper over :meth:`Enumerator.run_batch`; returns one
    :class:`QueryResult` per pattern, aligned with the input order."""
    cfg = cfg or EngineConfig(n_workers=8, expand_width=4)
    names = list(names or [f"q{i}" for i in range(len(patterns))])
    if len(names) != len(patterns):
        raise ValueError(
            f"names has {len(names)} entries for {len(patterns)} patterns"
        )
    session = Enumerator(SubgraphIndex.build(target), config=cfg, variant=variant)
    queries = [session.prepare(p, name=n) for p, n in zip(patterns, names)]
    results = session.run_batch(queries, pack_size=pack_size)
    return [
        QueryResult(name=ms.name, matches=ms.matches, states=ms.states, steps=ms.steps)
        for ms in results
    ]
