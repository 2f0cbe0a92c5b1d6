"""Engine-step backend benchmark: loose-ops jnp step vs an alternate
``step_backend`` — the fused Pallas kernel or the sparse CSR walk
(DESIGN.md §6 / §6.4).

  PYTHONPATH=src python benchmarks/bench_engine_step.py [--smoke]
      [--step-backend pallas|csr]

Two sections:

1. **Corpus sweep** — a ppis32-like collection through a ≥ 32-worker
   session twice, once per backend:

   * **bit-identity** (always asserted): matches, states, steps, and
     steals agree query-for-query between ``jnp`` and the alternate
     backend.  Off TPU the fused ``pallas`` kernel runs in *interpret
     mode* (Python kernel body — ~10-100× slower than jnp; see API.md),
     so its identity sweep covers the smallest-states slice of the corpus
     there and the full corpus on TPU; the ``csr`` backend's jnp-math
     walk is fast everywhere and always sweeps the full corpus.
   * **speedup** (asserted in compiled mode only): ``pallas`` must beat
     loose ops by ≥ 1.5× wall-clock.  Interpret mode is exempt by
     construction — it validates semantics, not speed — so on CPU the
     ratio is only reported.

2. **Sparse-target demo** (the csr headline: runs under ``--step-backend
   csr``, in both interpret and compiled modes) — a
   power-law target at pdbsv1 scale (``n_t = 33,067``) is enumerated
   through a **CSR-only plan**: the dense ``[n_elab, 2, n_t, w]``
   adjacency bitmaps are *never materialized*.  Asserted always: the CSR
   structure is ≥ 50× smaller than the dense working set the jnp backend
   would need (reported byte-for-byte, the dense side computed
   analytically since allocating it is exactly what this backend avoids),
   and the engine's counts equal the sequential reference oracle.
   Asserted in compiled mode only: the csr step is not slower than the
   dense jnp step on the same sparse target (interpret exempt).

Emits CSV rows (name, us_per_state, derived) and a JSON artifact.
"""

from __future__ import annotations

import argparse
import dataclasses
import time

import numpy as np

try:
    from benchmarks import common
except ImportError:  # executed from an arbitrary cwd
    import repro.bench  # noqa: F401  (puts the repo root on sys.path)
    from benchmarks import common

from repro.core import EngineConfig, Enumerator, SubgraphIndex
from repro.core import engine as eng
from repro.core.plan import build_csr_plan, build_plan
from repro.core.graph import PackedGraph
from repro.core.ref import ref_enumerate
from repro.data import graphgen
from repro.kernels import ops as kops

SPEEDUP_FLOOR = 1.5  # compiled-mode acceptance for pallas (interpret exempt)
# interpret mode: only identity-check pallas queries up to this many
# (jnp-counted) search states, so the Python kernel body finishes in CI time
INTERPRET_STATE_BUDGET = 60_000

SPARSE_NT = 33_067  # sge_pdbsv1 (Table 1) — the paper's largest target
SPARSE_MEM_FACTOR = 50  # csr structure must be >= this much smaller


def _corpus(smoke: bool, scale: float, seed: int):
    if smoke:
        return graphgen.make_collection(
            "ppis32-like", pattern_edges=(8,), patterns_per_target=1,
            scale=min(scale, 0.12), seed=seed,
        )
    return graphgen.make_collection(
        "ppis32-like", pattern_edges=(8, 16, 24), patterns_per_target=2,
        scale=scale, seed=seed,
    )


def _sweep(cfg: EngineConfig, instances, indices, names=None):
    """Run (a subset of) the collection; returns (per-query dict, wall_s).

    The compile pass is excluded from the timing: each query runs once to
    warm the session's shape-bucket cache, then once timed — the amortized
    regime the session API exists for.
    """
    session = Enumerator(config=cfg)
    queries = [
        session.prepare(inst.pattern, name=inst.name, index=indices[id(inst.target)])
        for inst in instances
        if names is None or inst.name in names
    ]
    for q in queries:  # warm-up: compile + first execution
        session.run(q)
    t0 = time.perf_counter()
    out = {}
    for q in queries:
        ms = session.run(q)
        out[q.name] = dict(matches=ms.matches, states=ms.states,
                           steps=ms.steps, steals=ms.steals)
    return out, time.perf_counter() - t0


def run_sparse_target(workers: int, seed: int, interpret: bool) -> dict:
    """The csr headline: enumerate a pdbsv1-scale power-law target through
    a CSR-only plan, with the dense working set never allocated."""
    tgt = graphgen.power_law_graph(
        SPARSE_NT, avg_deg=4.0, alpha=0.5, n_labels=32, seed=seed,
    )
    deg = tgt.out_degrees() + tgt.in_degrees()
    # start extraction at a busy node so the pattern is non-trivial
    pat = graphgen.extract_pattern(
        tgt, 6, seed=seed, start=int(np.argsort(deg)[-80]),
    )
    assert pat.m > 0, "sparse pattern extraction degenerated"
    plan = build_csr_plan(pat, tgt, variant="ri")
    assert plan.adj_bits.shape[2] == 0  # nothing dense was ever built

    # --- memory: byte-for-byte, the dense side analytic ------------------
    csr_bytes = plan.csr.nbytes
    dense_bytes = plan.n_edge_labels * 2 * plan.n_t * plan.w * 4
    mem_ratio = dense_bytes / max(csr_bytes, 1)
    assert mem_ratio >= SPARSE_MEM_FACTOR, (
        f"csr structure ({csr_bytes} B) must be >= {SPARSE_MEM_FACTOR}x "
        f"smaller than the dense adjacency working set ({dense_bytes} B); "
        f"measured {mem_ratio:.0f}x"
    )

    cfg = EngineConfig(n_workers=workers, expand_width=4, step_backend="csr")
    res = eng.run(plan, cfg)  # warm-up/compile
    t0 = time.perf_counter()
    res = eng.run(plan, cfg)
    t_csr = time.perf_counter() - t0

    # --- correctness at scale: the sequential oracle (also CSR-walking) --
    ref = ref_enumerate(pat, tgt, plan=plan)
    assert (res.matches, res.states) == (ref.matches, ref.states), (
        f"csr engine diverged from the sequential oracle on the sparse "
        f"target: engine=({res.matches}, {res.states}) "
        f"ref=({ref.matches}, {ref.states})"
    )

    # --- speed vs the dense jnp step: compiled mode only ------------------
    # (building the 273 MB dense plan is exactly what csr avoids, so the
    # comparison is opt-in to compiled mode where the gate applies)
    t_jnp = None
    sparse_speedup = None
    if not interpret:
        dense_plan = build_plan(pat, PackedGraph.from_graph(tgt), variant="ri")
        cfg_j = dataclasses.replace(cfg, step_backend="jnp")
        rj = eng.run(dense_plan, cfg_j)  # warm-up/compile
        t0 = time.perf_counter()
        rj = eng.run(dense_plan, cfg_j)
        t_jnp = time.perf_counter() - t0
        assert (rj.matches, rj.states) == (res.matches, res.states)
        sparse_speedup = t_jnp / max(t_csr, 1e-9)
        assert sparse_speedup >= 1.0, (
            f"csr step must not lose to the dense step on its home turf "
            f"(sparse n_t={SPARSE_NT}) in compiled mode; measured "
            f"{sparse_speedup:.2f}x ({t_jnp:.3f}s vs {t_csr:.3f}s)"
        )

    print(common.csv_row(
        "engine_step/csr_sparse_33k", t_csr * 1e6 / max(res.states, 1),
        f"n_t={SPARSE_NT};m={tgt.m};matches={res.matches};"
        f"states={res.states};csr_bytes={csr_bytes};"
        f"dense_bytes={dense_bytes};mem_ratio={mem_ratio:.0f}x;"
        f"ref_verified=True",
    ))
    return dict(
        n_t=SPARSE_NT,
        target_edges=int(tgt.m),
        matches=int(res.matches),
        states=int(res.states),
        csr_bytes=int(csr_bytes),
        dense_bytes=int(dense_bytes),
        mem_ratio=mem_ratio,
        csr_wall_s=t_csr,
        jnp_wall_s=t_jnp,
        sparse_speedup=sparse_speedup,
        speedup_asserted=not interpret,
        ref_verified=True,
    )


def run(smoke: bool = False, scale: float = 0.3, workers: int = 32,
        seed: int = 7, step_backend: str = "pallas") -> dict:
    assert workers >= 32, "the acceptance criterion is a >=32-worker run"
    assert step_backend in ("pallas", "csr")
    instances = _corpus(smoke, scale, seed)
    indices: dict = {}
    for inst in instances:
        indices.setdefault(id(inst.target), SubgraphIndex.build(inst.target))

    base = EngineConfig(n_workers=workers, expand_width=4)
    interpret = kops.resolve_interpret()

    jnp_res, t_jnp = _sweep(base, instances, indices)
    total_states = sum(r["states"] for r in jnp_res.values())

    # pick the alternate sweep's query set: everything in compiled mode or
    # for the csr backend (jnp-math walk — no interpret penalty), the
    # smallest-states prefix under the budget for interpret-mode pallas
    if interpret and step_backend == "pallas":
        by_states = sorted(jnp_res.items(), key=lambda kv: kv[1]["states"])
        picked, budget = [], INTERPRET_STATE_BUDGET
        for name, r in by_states:
            if r["states"] <= budget or not picked:
                picked.append(name)
                budget -= r["states"]
        names = set(picked)
    else:
        names = None

    alt_cfg = dataclasses.replace(base, step_backend=step_backend)
    alt_res, t_alt = _sweep(alt_cfg, instances, indices, names=names)

    # --- bit-identity: the seam's core contract ---------------------------
    for name, r in alt_res.items():
        assert r == jnp_res[name], (
            f"{name}: {step_backend} step diverged from loose-ops step — "
            f"{step_backend}={r} jnp={jnp_res[name]}"
        )
    checked_states = sum(jnp_res[n]["states"] for n in alt_res)

    # --- speed: compiled mode must win (pallas), interpret just reports ---
    # compare on the same query set the alternate sweep ran
    t_jnp_same = t_jnp
    if names is not None and len(names) < len(jnp_res):
        _, t_jnp_same = _sweep(base, instances, indices, names=names)
    speedup = t_jnp_same / max(t_alt, 1e-9)
    if not interpret and step_backend == "pallas":
        assert speedup >= SPEEDUP_FLOOR, (
            f"fused extend_step must be >= {SPEEDUP_FLOOR}x the loose-ops "
            f"step in compiled mode; measured {speedup:.2f}x "
            f"({t_jnp_same:.3f}s vs {t_alt:.3f}s)"
        )

    # the sparse 33k-target demo is the csr headline; the pallas sweep keeps
    # its pre-existing scope (CI runs both rows, so coverage is unchanged)
    sparse = (
        run_sparse_target(workers, seed, interpret)
        if step_backend == "csr" else None
    )

    mode = "interpret" if interpret else "compiled"
    print(common.csv_row(
        "engine_step/jnp", t_jnp * 1e6 / max(total_states, 1),
        f"queries={len(jnp_res)};states={total_states};wall={t_jnp:.3f}s",
    ))
    print(common.csv_row(
        f"engine_step/{step_backend}_{mode}",
        t_alt * 1e6 / max(checked_states, 1),
        f"queries={len(alt_res)};states={checked_states};wall={t_alt:.3f}s;"
        f"speedup={speedup:.2f}x;identical=True",
    ))
    payload = dict(
        mode=mode,
        workers=workers,
        step_backend=step_backend,
        queries=len(jnp_res),
        alt_queries=len(alt_res),
        total_states=total_states,
        checked_states=checked_states,
        jnp_wall_s=t_jnp,
        jnp_wall_same_set_s=t_jnp_same,
        alt_wall_s=t_alt,
        speedup_same_set=speedup,
        speedup_asserted=not interpret and step_backend == "pallas",
        bit_identical=True,
        sparse=sparse,
    )
    common.save_json("engine_step", payload)
    return payload


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--scale", type=float, default=0.3)
    ap.add_argument("--workers", type=int, default=32)
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--step-backend", choices=("pallas", "csr"),
                    default="pallas",
                    help="alternate backend to sweep against jnp")
    ap.add_argument("--smoke", action="store_true",
                    help="small corpus for CI (same assertions)")
    ap.add_argument("--json", metavar="PATH", default=None,
                    help="also write the JSON payload to PATH")
    args = ap.parse_args()
    out = run(smoke=args.smoke, scale=args.scale, workers=args.workers,
              seed=args.seed, step_backend=args.step_backend)
    common.write_json_path(args.json, out)
    verdict = (
        f"{out['speedup_same_set']:.2f}x (asserted >= {SPEEDUP_FLOOR}x)"
        if out["speedup_asserted"]
        else f"{out['speedup_same_set']:.2f}x (interpret/csr: reported only)"
    )
    print(
        f"\n[{out['mode']}] {out['queries']} queries, {out['workers']} workers: "
        f"loose-ops {out['jnp_wall_s']:.2f}s; {out['step_backend']} step on "
        f"{out['alt_queries']} queries ({out['checked_states']} states) "
        f"bit-identical; alt/loose = {verdict}"
    )
    sp = out["sparse"]
    if sp is not None:
        print(
            f"sparse n_t={sp['n_t']}: csr structure {sp['csr_bytes']/1e6:.1f} MB "
            f"vs dense {sp['dense_bytes']/1e6:.1f} MB ({sp['mem_ratio']:.0f}x), "
            f"{sp['matches']} matches / {sp['states']} states in "
            f"{sp['csr_wall_s']:.2f}s, ref-verified"
        )


if __name__ == "__main__":
    main()
