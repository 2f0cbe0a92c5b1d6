#!/usr/bin/env python3
"""Smoke run of the enumeration service on one TPU chip.

    python3 chip_smoke.py             # phases A-C on one chip
    python3 chip_smoke.py --chips 4   # the mesh-sharded engine on four chips

Every phase goes through the public ``SubgraphIndex`` / ``Enumerator`` /
``EnumerationService`` API on a target generated from ``--seed`` by
``repro.data.graphgen``, and every count, state count and match mapping is
checked against the numpy reference ``repro.core.ref.ref_enumerate``, which
builds its own plan:

  A  served dense: 12,575 nodes at the ppis32-like density (the paper's
     ppis32 target size), ``configs.sge.ENGINE`` (64 workers x 64 lanes),
     default step backend; an ``EnumerationService`` answers 16 queries of
     4-32 pattern edges from 4 client threads.
  B  fused kernel: the same target and queries with
     ``step_backend="pallas"`` (the ``extend_step`` kernel).
  C  sparse: pdbsv1's 33,067 nodes as a CSR-only index with
     ``configs.sge.SPARSE_AVG_DEG`` and ``CSR_VARIANT``; ``step_backend=
     "csr"`` with ``use_pallas`` (the ``csr_arc_sweep`` and
     ``csr_extend_bucketed`` kernels).

``--chips 4`` runs only ``Enumerator(mesh=4)`` on phase A's target and
queries against a one-device run in the same process and the reference.

The last line of stdout is ``{"ok": true, "device": {...}}``.  The script
exits non-zero without printing it when the first device is not a TPU,
when the repository's ``src/`` is missing, or when any check fails.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time
import traceback
from typing import List, Optional, Sequence

ROOT = os.path.dirname(os.path.abspath(__file__))


class SmokeFailure(RuntimeError):
    """A phase's result disagrees with the reference."""


@dataclasses.dataclass(frozen=True)
class Workload:
    """One generated target and its query patterns."""

    n_t: int
    m: int
    n_labels: int
    label_dist: str
    pattern_edges: Sequence[int]
    n_queries: int


# ppis32 (paper Table 1): 12,575 nodes; graphgen's ppis32-like density is
# 10,000 edges per 800 nodes.  40 labels (the collection's own 33 nodes
# per label would give 381) keep 16 queries of 4-32 edges at ~4*10^4
# reference states, under 1.3*10^4 matches per query.
DENSE = Workload(n_t=12575, m=12575 * 10000 // 800, n_labels=40,
                 label_dist="normal", pattern_edges=(4, 8, 16, 32),
                 n_queries=16)
# pdbsv1 (paper Table 1): 33,067 nodes at SPARSE_AVG_DEG = 8; 8 uniform
# labels give ~10^4 reference states per query.
SPARSE_NT = 33067
SPARSE_LABELS = 8
SPARSE_QUERIES = 8
CLIENTS = 4
# Pack widths that fit one v5e (16 GiB), from the compile rehearsal of the
# vmapped engine at these sizes: a dense 2-lane pack needs ~6 GiB, a CSR
# 1-lane pack ~6 GiB (2 CSR lanes would need ~16 GiB).
DENSE_LANES = 2
SPARSE_LANES = 1


def generate(wl: Workload, seed: int):
    """Target graph and query patterns of ``wl``, made from ``seed``."""
    from repro.data import graphgen

    tgt = graphgen.random_graph(wl.n_t, wl.m, wl.n_labels, wl.label_dist,
                                seed=seed)
    pats = [
        graphgen.extract_pattern(
            tgt, wl.pattern_edges[i % len(wl.pattern_edges)],
            seed=seed * 1000 + i)
        for i in range(wl.n_queries)
    ]
    return tgt, pats


def reference(tgt, pats, variant: str) -> list:
    """``(RefResult, plan)`` per pattern from the numpy reference, on a
    dense plan it builds itself."""
    from repro.core.graph import PackedGraph
    from repro.core.plan import build_plan
    from repro.core.ref import ref_enumerate

    packed = PackedGraph.from_graph(tgt)
    out = []
    for p in pats:
        plan = build_plan(p, packed, variant=variant)
        out.append((ref_enumerate(p, tgt, plan=plan, record_mappings=True), plan))
    return out


def by_node(mappings, plan) -> list:
    """Mappings (position order of ``plan``) as sorted tuples indexed by
    pattern node, so plans with different orderings compare."""
    order = [int(x) for x in plan.order[: plan.n_p]]
    out = []
    for m in mappings:
        row = [0] * plan.n_p
        for pos, node in enumerate(order):
            row[node] = int(m[pos])
        out.append(tuple(row))
    return sorted(out)


def collect_budget(expect) -> int:
    """Per-worker match budget that holds every match of every query."""
    most = max([r.matches for r, _ in expect] + [1])
    return 1 << (most - 1).bit_length()


def check(name: str, got_matches: int, got_states: int, got_maps, got_plan,
          ref, ref_plan) -> None:
    if (got_matches, got_states) != (ref.matches, ref.states):
        raise SmokeFailure(
            f"{name}: (matches, states) = ({got_matches}, {got_states}), "
            f"reference ({ref.matches}, {ref.states})")
    if by_node(got_maps, got_plan) != by_node(ref.mappings, ref_plan):
        raise SmokeFailure(f"{name}: mappings differ from the reference")


def serve_phase(label: str, index, cfg, variant: str, pats, expect, *,
                clients: int, max_lanes: int, timeout: float,
                require_steals: bool) -> dict:
    """Serve ``pats`` from ``clients`` threads through one
    ``EnumerationService`` and check every result; returns the phase
    summary."""
    from repro.core import Enumerator
    from repro.launch.serve import drive
    from repro.serve import EnumerationService, ServiceConfig

    enum = Enumerator(index, config=cfg, variant=variant)
    t0 = time.perf_counter()
    queries = [enum.prepare(p, name=f"{label}{i}") for i, p in enumerate(pats)]
    prepare_s = time.perf_counter() - t0
    per_client: List[list] = [queries[c::clients] for c in range(clients)]
    expect_of = {q.name: e for q, e in zip(queries, expect)}
    svc = EnumerationService(
        enumerator=enum,
        service=ServiceConfig(max_lanes=max_lanes, batch_window_s=0.05),
    )
    t0 = time.perf_counter()
    with svc:
        results = drive(svc, per_client, collect=collect_budget(expect),
                        timeout=timeout)
    wall = time.perf_counter() - t0
    steals = 0
    for q, ms, maps in results:
        ref, ref_plan = expect_of[q.name]
        check(q.name, ms.matches, ms.states, maps, q.plan, ref, ref_plan)
        steals = max(steals, ms.steals)
    if require_steals and steals == 0:
        raise SmokeFailure(f"{label}: no run reported a steal")
    return {
        "phase": label,
        "prepare_s": prepare_s,
        "wall_s": wall,
        "engine_compiles": int(svc.stats()["cache_compiles"]),
        "queries": len(results),
        "matches": sum(ms.matches for _, ms, _ in results),
        "states": sum(ms.states for _, ms, _ in results),
        "max_steals": steals,
    }


def dense_phases(tgt, pats, engine, *, clients: int, max_lanes: int,
                 timeout: float, require_steals: bool) -> List[dict]:
    """Phases A (default step backend) and B (``extend_step`` kernel)."""
    from repro.core import SubgraphIndex

    variant = "ri-ds-si-fc"
    expect = reference(tgt, pats, variant)
    index = SubgraphIndex.build(tgt)
    out = []
    for label, backend in (("A", engine.step_backend), ("B", "pallas")):
        cfg = dataclasses.replace(engine, step_backend=backend)
        out.append(serve_phase(
            label, index, cfg, variant, pats, expect, clients=clients,
            max_lanes=max_lanes, timeout=timeout,
            require_steals=require_steals and label == "A"))
    return out


def sparse_phase(tgt, pats, engine, *, clients: int, max_lanes: int,
                 timeout: float) -> dict:
    """Phase C: a CSR-only index under the ``csr`` backend with kernels."""
    from repro.configs.sge import CSR_VARIANT
    from repro.core import SubgraphIndex

    expect = reference(tgt, pats, CSR_VARIANT)
    cfg = dataclasses.replace(engine, step_backend="csr", use_pallas=True)
    return serve_phase(
        "C", SubgraphIndex.build(tgt, sparse=True), cfg, CSR_VARIANT, pats,
        expect, clients=clients, max_lanes=max_lanes, timeout=timeout,
        require_steals=False)


def mesh_phase(tgt, pats, engine, n_dev: int) -> dict:
    """``Enumerator(mesh=n_dev)`` against a one-device run and the
    reference; returns the summary with the steals per device."""
    import numpy as np

    from repro.core import Enumerator, SubgraphIndex

    variant = "ri-ds-si-fc"
    expect = reference(tgt, pats, variant)
    collect = collect_budget(expect)
    index = SubgraphIndex.build(tgt)
    one = Enumerator(index, config=engine, variant=variant)
    mesh = Enumerator(index, config=engine, variant=variant, mesh=n_dev)
    per_dev = np.zeros(n_dev, np.int64)
    matches = states = 0
    t0 = time.perf_counter()
    for i, (p, (ref, ref_plan)) in enumerate(zip(pats, expect)):
        single = one.run(one.prepare(p, name=f"one{i}"), collect_matches=collect)
        sharded = mesh.run(mesh.prepare(p, name=f"mesh{i}"),
                           collect_matches=collect)
        for ms in (single, sharded):
            check(ms.name, ms.matches, ms.states, ms.mappings(), ms.plan,
                  ref, ref_plan)
        if sharded.per_worker_steals is not None:
            per_dev += sharded.per_worker_steals.reshape(n_dev, -1).sum(axis=1)
        matches += sharded.matches
        states += sharded.states
    return {
        "phase": f"mesh{n_dev}",
        "wall_s": time.perf_counter() - t0,
        "engine_compiles": one.cache_info()["compiles"]
        + mesh.cache_info()["compiles"],
        "queries": len(pats),
        "matches": matches,
        "states": states,
        "steals_per_device": [int(s) for s in per_dev],
    }


def require_tpu(n_chips: int):
    """The devices, or exit when the first one is not a TPU (checked before
    any work) or there are fewer than ``n_chips``."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise SystemExit(
            f"chip_smoke: the first device is {devices[0].platform!r}, "
            "not a TPU")
    if len(devices) < n_chips:
        raise SystemExit(
            f"chip_smoke: {n_chips} chips requested, {len(devices)} present")
    return devices


def import_repo() -> None:
    src = os.path.join(ROOT, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        raise SystemExit(f"chip_smoke: no repro package under {src}")
    sys.path.insert(0, src)


def main(argv: Optional[Sequence[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--timeout", type=float, default=900.0,
                    help="per-query service timeout, seconds")
    args = ap.parse_args(argv)

    devices = require_tpu(args.chips)
    import_repo()
    from repro.configs.sge import ENGINE, SPARSE_AVG_DEG
    from repro.kernels.ops import resolve_interpret
    from repro.launch.compile_cache import enable_compile_cache

    if resolve_interpret():
        raise SystemExit("chip_smoke: Pallas kernels would run interpreted")
    dev = devices[0]
    print(f"device: platform={dev.platform} kind={dev.device_kind} "
          f"count={len(devices)}", flush=True)
    print(f"compile cache: {enable_compile_cache()}", flush=True)

    t0 = time.perf_counter()
    tgt, pats = generate(DENSE, args.seed)
    print(f"dense target: n={tgt.n} directed edges={tgt.m} "
          f"queries={len(pats)} ({time.perf_counter() - t0:.1f}s)", flush=True)
    if args.chips > 1:
        summaries = [mesh_phase(tgt, pats, ENGINE, args.chips)]
    else:
        summaries = dense_phases(tgt, pats, ENGINE, clients=CLIENTS,
                                 max_lanes=DENSE_LANES, timeout=args.timeout,
                                 require_steals=True)
        for s in summaries:
            print(json.dumps(s), flush=True)
        sparse = Workload(
            n_t=SPARSE_NT, m=SPARSE_NT * SPARSE_AVG_DEG // 2,
            n_labels=SPARSE_LABELS, label_dist="uniform",
            pattern_edges=DENSE.pattern_edges, n_queries=SPARSE_QUERIES)
        tgt, pats = generate(sparse, args.seed + 1)
        summaries.append(sparse_phase(tgt, pats, ENGINE, clients=CLIENTS,
                                      max_lanes=SPARSE_LANES,
                                      timeout=args.timeout))
    print(json.dumps(summaries[-1]), flush=True)
    stats = dev.memory_stats() or {}
    if "peak_bytes_in_use" in stats:
        print(f"peak_bytes_in_use: {stats['peak_bytes_in_use']}", flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    try:
        sys.exit(main())
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        sys.exit(1)
    except SystemExit:
        raise
    except BaseException:
        traceback.print_exc()
        sys.exit(1)
