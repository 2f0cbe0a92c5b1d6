"""Subgraph-enumeration driver — the paper's tool, end to end.

  PYTHONPATH=src python -m repro.launch.sge_run --collection ppis32-like \
      --variant ri-ds-si-fc --workers 16 --scale 0.3

Generates (or loads) a collection, prepares one
:class:`~repro.core.session.SubgraphIndex` per target, and runs every
pattern through a single :class:`~repro.core.session.Enumerator` session —
so all instances share a handful of shape-bucketed engine compilations.
Three execution modes map to the session's three methods:

  * ``--mode single``   one engine invocation per query (default);
  * ``--mode packed``   LPT-balanced vmapped packs (``run_batch``; on the
    production mesh the pack axis maps to ``pod``);
  * ``--mode stream``   results printed as packs drain (``stream``; the
    serving path).

``--step-backend pallas`` swaps the engine's expansion step for the fused
Pallas ``extend_step`` kernel (DESIGN.md §6.2) — results are bit-identical
to the default ``jnp`` backend; off-TPU the kernel runs in interpret mode
(validation, not speed — see API.md).  ``--step-backend csr`` runs the
sparse CSR walk (DESIGN.md §6.4; also bit-identical), ``auto`` picks csr
past 32,768 target nodes.  ``--sparse-index`` goes further: targets are
indexed CSR-only (DESIGN.md §11), so dense adjacency bitmaps never exist
anywhere — any ``--variant`` works, with domains from the CSR-native
AC/FC fixpoint.

``--devices N`` runs the paper's worker sweep multi-device: the session's
worker stacks shard over a 1-D ``data`` mesh of ``N`` devices
(``shard_map``; DESIGN.md §2.4).  On a CPU-only host the flag forces ``N``
virtual XLA devices (``--xla_force_host_platform_device_count``) so the
scaling benchmarks run multi-"core" in CI; on a real backend it takes the
first ``N`` of ``jax.local_devices()``.

Reports per-instance matches / states / steps plus collection aggregates —
the shape of the paper's experiment tables — the session's compile cache
counters, and (multi-device) per-device steal traffic.
"""

from __future__ import annotations

import argparse
import os
import sys
import time


def _force_virtual_devices() -> None:
    """Honor ``--devices N`` before jax locks the platform: XLA device count
    is fixed at first backend initialization, so on CPU the flag must be in
    ``XLA_FLAGS`` before ``import jax`` (transitively below)."""
    n = None
    for i, tok in enumerate(sys.argv):
        if tok == "--devices" and i + 1 < len(sys.argv):
            n = sys.argv[i + 1]
        elif tok.startswith("--devices="):
            n = tok.split("=", 1)[1]
    if n is None:
        return
    try:
        n = int(n)
    except ValueError:
        return  # argparse will report the usage error
    flags = os.environ.get("XLA_FLAGS", "")
    if n > 1 and "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + f" --xla_force_host_platform_device_count={n}"
        ).strip()


_force_virtual_devices()

import jax  # noqa: E402  (after the XLA_FLAGS shim, deliberately)

from repro.core import EngineConfig, Enumerator, SubgraphIndex  # noqa: E402
from repro.data import graphgen  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402


def main() -> int:
    enable_compile_cache()
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--collection", default="ppis32-like",
                    choices=sorted(graphgen.COLLECTIONS))
    ap.add_argument("--variant", default="ri-ds-si-fc")
    ap.add_argument("--workers", type=int, default=16)
    ap.add_argument("--expand", type=int, default=4)
    ap.add_argument("--scale", type=float, default=0.3)
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--mode", choices=("single", "packed", "stream"),
                    default="single")
    ap.add_argument("--packed", action="store_true",
                    help="deprecated alias for --mode packed")
    ap.add_argument("--pack-size", type=int, default=4)
    ap.add_argument("--devices", type=int, default=0,
                    help="shard worker stacks over N devices (0 = no mesh; "
                    "on CPU forces N virtual XLA devices)")
    ap.add_argument("--step-backend",
                    choices=("jnp", "pallas", "csr", "auto", "partitioned"),
                    default="jnp",
                    help="expansion-step backend (DESIGN.md §6.2): 'jnp' "
                    "loose ops, 'pallas' the fused extend_step kernel "
                    "(interpret mode off-TPU — validation, not speed), "
                    "'csr' the sparse adjacency walk for huge targets "
                    "(§6.4), 'auto' = csr past 32,768 target nodes, "
                    "'partitioned' the out-of-core streaming walk (§9)")
    ap.add_argument("--mem-budget", type=int, default=0, metavar="BYTES",
                    help="device-memory budget for resident target planes "
                    "(DESIGN.md §9): partitions each target so its padded "
                    "resident CSR planes fit BYTES and streams the "
                    "partitions through the device (implies the "
                    "partitioned backend); 0 = whole target resident")
    ap.add_argument("--partitions", type=int, default=0, metavar="N",
                    help="explicit target partition count for the "
                    "partitioned backend (0 = derive from --mem-budget, "
                    "or 1 if neither is given)")
    ap.add_argument("--root-seeding", choices=("vertex", "edge", "auto"),
                    default="vertex",
                    help="root frontier construction (DESIGN.md §10): "
                    "'vertex' the depth-0 per-worker node split, 'edge' "
                    "depth-1 seeds enumerated from the rarest target edge "
                    "class (plans are built with seed_edge='auto'), "
                    "'auto' = edge whenever the plan carries a seed edge")
    ap.add_argument("--csr-walk", choices=("bucketed", "flat"),
                    default="bucketed",
                    help="CSR adjacency-walk schedule (DESIGN.md §10): "
                    "'bucketed' trips each lane at its row's pow2 "
                    "degree-bucket cap, 'flat' scans every lane to the "
                    "global deg_cap (the pre-bucketing behavior)")
    ap.add_argument("--sparse-index", action="store_true",
                    help="build CSR-only target indexes (SubgraphIndex."
                    "build(..., sparse=True), DESIGN.md §11): dense "
                    "adjacency bitmaps never exist — domains come from the "
                    "CSR-native AC/FC fixpoint and plans are CSR-only; "
                    "requires --step-backend csr, auto, or partitioned")
    args = ap.parse_args()
    if args.sparse_index and args.step_backend in ("jnp", "pallas"):
        raise SystemExit(
            f"--sparse-index builds CSR-only plans, which the dense "
            f"'{args.step_backend}' backend cannot run; use --step-backend "
            "csr, auto, or partitioned"
        )
    mode = "packed" if args.packed else args.mode
    if args.partitions and args.step_backend != "partitioned":
        args.step_backend = "partitioned"

    mesh = None
    if args.devices:
        if args.devices > len(jax.local_devices()):
            raise SystemExit(
                f"--devices {args.devices}: only {len(jax.local_devices())} "
                "local devices (is XLA_FLAGS set by another import?)"
            )
        mesh = args.devices

    instances = graphgen.make_collection(
        args.collection, pattern_edges=(8, 16, 24), patterns_per_target=2,
        scale=args.scale, seed=args.seed,
    )
    cfg = EngineConfig(n_workers=args.workers, expand_width=args.expand,
                       step_backend=args.step_backend,
                       n_partitions=args.partitions,
                       root_seeding=args.root_seeding,
                       csr_walk=args.csr_walk)
    session = Enumerator(
        config=cfg, variant=args.variant, mesh=mesh,
        memory_budget_bytes=args.mem_budget or None,
    )

    indices: dict = {}
    t0 = time.perf_counter()
    queries = []
    for inst in instances:
        key = id(inst.target)
        if key not in indices:
            indices[key] = SubgraphIndex.build(
                inst.target, sparse=args.sparse_index
            )
        queries.append(session.prepare(
            inst.pattern, name=inst.name, index=indices[key],
            seed_edge="auto" if args.root_seeding != "vertex" else None))

    matches = states = 0
    pw_steals = None

    def tally(ms):
        nonlocal matches, states, pw_steals
        matches += ms.matches
        states += ms.states
        if ms.per_worker_steals is not None:
            if pw_steals is None:
                pw_steals = ms.per_worker_steals.astype("int64").copy()
            else:
                pw_steals += ms.per_worker_steals

    if mode == "single":
        for q in queries:
            ms = session.run(q)
            print(f"{ms.name:40s} matches={ms.matches:<8d} states={ms.states:<9d} "
                  f"steps={ms.steps:<7d} steals={ms.steals:<5d} {ms.match_s:6.2f}s")
            tally(ms)
    elif mode == "packed":
        for ms in session.run_batch(queries, pack_size=args.pack_size):
            print(f"{ms.name:40s} matches={ms.matches:<8d} states={ms.states:<9d} "
                  f"steps={ms.steps}")
            tally(ms)
    else:  # stream: print in completion order, as the serving loop would
        for ms in session.stream(queries, pack_size=args.pack_size):
            print(f"{ms.name:40s} matches={ms.matches:<8d} states={ms.states:<9d} "
                  f"steps={ms.steps}")
            tally(ms)

    total = time.perf_counter() - t0
    info = session.cache_info()
    print(f"\n[{args.collection}/{mode}/{args.step_backend}] {len(queries)} queries, "
          f"{matches} matches, {states} states, {total:.1f}s "
          f"({states/max(total,1e-9):.0f} states/s); "
          f"engine compiles={info['compiles']} cache_hits={info['cache_hits']}")
    if args.devices and pw_steals is not None:
        v_per_dev = session.config.n_workers // args.devices
        per_dev = pw_steals.reshape(args.devices, v_per_dev).sum(axis=1)
        print(f"mesh: {args.devices} device(s) x {v_per_dev} workers; "
              "entries stolen into each device: "
              + " ".join(f"d{i}={int(s)}" for i, s in enumerate(per_dev)))
    return 0


if __name__ == "__main__":
    sys.exit(main())
