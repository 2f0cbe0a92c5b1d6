"""Incremental-maintenance benchmark: run_delta vs full recompute.

  PYTHONPATH=src python benchmarks/bench_incremental.py [--smoke]

Drives a stream of batched edge edits — each step touches ~1% of the arcs
(half removals, half insertions) of a power-law target (the regime of
Das et al.'s dynamic workloads: hubs, long sparse tail) — and maintains a
pattern's match set two ways:

  * **delta**: ``SubgraphIndex.update`` (incremental bitmap/CSR-plane
    patching) + ``Enumerator.run_delta`` (membership invalidation +
    edge-anchored seeded enumeration, DESIGN.md §8);
  * **recompute**: the same ``update`` followed by a fresh full
    ``Enumerator.run`` against the new version.

Gates (PR acceptance):

  (a) **Correctness**: the maintained match set is checked against the
      fresh enumeration at every step on counts, and on full sorted
      node-indexed mapping sets at spot-check steps plus the final
      version (the same differential identity as
      ``tests/test_incremental_conformance.py``).
  (b) **Speedup**: summed over the stream, delta maintenance beats full
      recompute by >= 5x wall-clock.  Both sides run warm: the shared
      XLA trace pool means neither pays a re-trace per version, so the
      comparison is enumeration work vs enumeration work.  The gate is
      asserted in compiled mode; a ``--use-pallas`` run under interpret
      mode is exempt and reports honestly.
"""

from __future__ import annotations

import argparse
import time
from typing import List, Tuple

import numpy as np

try:
    from benchmarks import common
except ImportError:  # executed from an arbitrary cwd
    import repro.bench  # noqa: F401  (puts the repo root on sys.path)
    from benchmarks import common

from repro.core import EngineConfig, Enumerator, SubgraphIndex
from repro.core.delta import as_mapping_array, as_node_mappings
from repro.data import graphgen
from repro.kernels import ops as kops

SPEEDUP_FLOOR = 5.0
EDIT_FRACTION = 0.01  # arcs edited per step (half removed, half inserted)


def build_stream(tgt, pat, n_steps: int, seed: int):
    """Reproducible stream of batched edits over ``tgt``, each touching
    ~EDIT_FRACTION of the arc set.  Removals are sampled from present
    arcs; insertions are sampled *pattern-relevant* (endpoint node labels
    and edge label drawn from the pattern's edge triples) so the delta
    side has to run real anchored enumeration, not just membership
    invalidation."""
    rng = np.random.default_rng(seed)
    pe = sorted(set(zip(
        pat.labels[pat.src].tolist(), pat.labels[pat.dst].tolist(),
        pat.edge_labels.tolist())))
    by_label = {l: np.nonzero(tgt.labels == l)[0]
                for l in {x for (a, b, _) in pe for x in (a, b)}}
    # the corpus is undirected (symmetric arc pairs); edits stay in that
    # regime by always touching both arcs of an edge
    present = set(zip(tgt.src.tolist(), tgt.dst.tolist(),
                      tgt.edge_labels.tolist()))
    k = max(4, int(len(present) * EDIT_FRACTION))
    steps: List[Tuple[list, list]] = []
    for _ in range(n_steps):
        pres_list = sorted((u, v, l) for (u, v, l) in present if u < v)
        rem_idx = rng.choice(len(pres_list), size=k // 4, replace=False)
        rems = []
        for i in rem_idx:
            u, v, l = pres_list[i]
            rems += [(u, v, l), (v, u, l)]
        adds = []
        while len(adds) < k - len(rems):
            la, lb, el = pe[int(rng.integers(len(pe)))]
            u = int(rng.choice(by_label[la]))
            v = int(rng.choice(by_label[lb]))
            t, tr = (u, v, int(el)), (v, u, int(el))
            if u != v and t not in present and t not in adds:
                adds += [t, tr]
        steps.append((adds, rems))
        present -= set(rems)
        present |= set(adds)
    return steps


def pick_pattern(enum, tgt, seed: int, min_matches: int, max_matches: int):
    """First extracted pattern whose standing match set is substantial
    (``min_matches`` floor, capped at ``max_matches`` so the maintained
    mapping set stays materializable).  Incremental maintenance targets
    standing queries whose full enumeration is expensive — a pattern the
    target barely matches would gate on fixed per-step overhead instead
    of enumeration work.  The tried seeds and the chosen pattern are
    deterministic in ``seed``."""
    best = None
    for s in range(seed + 1, seed + 17):
        pat = graphgen.extract_pattern(tgt, 4, seed=s)
        q = enum.prepare(pat)
        ms = enum.run(q)
        if ms.matches > max_matches:
            continue
        if best is None or ms.matches > best[2].matches:
            best = (pat, q, ms)
        if ms.matches >= min_matches:
            return pat, q, ms
    if best is None:
        raise RuntimeError(
            f"no extracted pattern had <= {max_matches} matches; "
            "lower --n-t or --avg-deg"
        )
    pat, q, ms = best
    print(f"  note: no tried pattern reached {min_matches} matches; "
          f"using the densest found ({ms.matches})")
    return pat, q, ms


def run(n_t: int, avg_deg: float, n_steps: int, seed: int,
        use_pallas: bool, check_every: int) -> dict:
    cfg = EngineConfig(n_workers=4, expand_width=2, step_backend="auto",
                       use_pallas=use_pallas)
    interpret = kops.resolve_interpret()
    gate = not (use_pallas and interpret)  # interpret-mode pallas is exempt

    tgt = graphgen.power_law_graph(
        n_t, avg_deg=avg_deg, alpha=2.0, n_labels=4, seed=seed,
    )
    idx0 = SubgraphIndex.build(tgt)
    idx0.plane_set()  # materialize once so updates patch instead of rebuild

    # -- warm both paths on version 0 (shared trace pool: no per-version
    # re-trace afterwards; what remains is enumeration work) -------------
    enum = Enumerator(idx0, config=cfg)
    pat, q0, ms0 = pick_pattern(enum, tgt, seed,
                                min_matches=5 * n_t, max_matches=120_000)
    steps = build_stream(tgt, pat, n_steps, seed)
    cur = as_mapping_array(ms0)  # maintained set stays an [M, n_p] array
    warm_add, warm_rem = steps[0]
    widx, wdelta = idx0.update(add_edges=warm_add, remove_edges=warm_rem)
    wq = enum.prepare(pat, index=widx)
    enum.run_delta(wq, cur, wdelta)  # traces the seeded-engine shapes
    enum.run(wq)

    # -- delta maintenance -------------------------------------------------
    idx = idx0
    t_update = t_delta = 0.0
    n_seeds = n_states_delta = 0
    counts_per_step: List[int] = []
    snapshots = {}  # step -> maintained mapping set (for the spot checks)
    for i, (adds, rems) in enumerate(steps):
        t0 = time.perf_counter()
        idx, delta = idx.update(add_edges=adds, remove_edges=rems)
        t_update += time.perf_counter() - t0
        q = enum.prepare(pat, index=idx)
        t0 = time.perf_counter()
        dm = enum.run_delta(q, cur, delta)
        t_delta += time.perf_counter() - t0
        cur = dm.apply_array(cur)
        n_seeds += dm.n_seeds
        n_states_delta += dm.states
        counts_per_step.append(len(cur))
        if i % check_every == 0 or i == len(steps) - 1:
            snapshots[i] = cur

    # -- full recompute baseline (same updates, fresh full run each step),
    # doubling as gate (a): counts verified at every step, full sorted
    # mapping sets at the spot-check steps and the final version ----------
    idx_b = idx0
    t_recompute = 0.0
    n_states_full = 0
    for i, (adds, rems) in enumerate(steps):
        idx_b, _ = idx_b.update(add_edges=adds, remove_edges=rems)
        q = enum.prepare(pat, index=idx_b)
        t0 = time.perf_counter()
        full = enum.run(q)
        t_recompute += time.perf_counter() - t0
        n_states_full += full.states
        assert full.matches == counts_per_step[i], (
            f"step {i}: maintained count {counts_per_step[i]} != fresh "
            f"recompute {full.matches}"
        )
        if i in snapshots:
            fresh = sorted(as_node_mappings(full))
            assert snapshots[i].tolist() == [list(t) for t in fresh], (
                f"step {i}: maintained mapping set diverged from fresh "
                "enumeration"
            )

    # -- (b) the speedup gate ----------------------------------------------
    t_incremental = t_update + t_delta
    speedup = t_recompute / t_incremental if t_incremental else float("inf")
    if gate:
        assert speedup >= SPEEDUP_FLOOR, (
            f"delta maintenance must beat full recompute {SPEEDUP_FLOOR}x "
            f"on a {EDIT_FRACTION:.0%}-edit stream in compiled mode; "
            f"measured {speedup:.2f}x ({t_incremental*1e3:.1f} ms vs "
            f"{t_recompute*1e3:.1f} ms over {len(steps)} steps)"
        )

    per_step = t_incremental / len(steps)
    print(common.csv_row(
        "incr_delta", per_step * 1e6,
        f"steps={len(steps)} k={len(steps[0][0]) + len(steps[0][1])} "
        f"seeds={n_seeds} states={n_states_delta}"))
    print(common.csv_row(
        "incr_recompute", t_recompute / len(steps) * 1e6,
        f"steps={len(steps)} states={n_states_full}"))
    print(f"  delta vs full recompute: {speedup:.2f}x "
          f"({'gated >= %.1fx' % SPEEDUP_FLOOR if gate else 'interpret mode: exempt'})")
    print(f"  update={t_update*1e3:.1f}ms run_delta={t_delta*1e3:.1f}ms "
          f"recompute={t_recompute*1e3:.1f}ms "
          f"states {n_states_delta} vs {n_states_full} "
          f"matches={len(cur)}")

    return dict(
        n_t=n_t, avg_deg=avg_deg, n_steps=len(steps),
        edits_per_step=len(steps[0][0]) + len(steps[0][1]),
        t_update_s=t_update, t_run_delta_s=t_delta,
        t_incremental_s=t_incremental, t_recompute_s=t_recompute,
        speedup=speedup, gated=gate,
        seeds=n_seeds, states_delta=n_states_delta, states_full=n_states_full,
        matches_final=len(cur), use_pallas=use_pallas, interpret=interpret,
    )


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="small stream for CI (same gates)")
    ap.add_argument("--n-t", type=int, default=None, help="target nodes")
    ap.add_argument("--steps", type=int, default=None, help="stream length")
    ap.add_argument("--avg-deg", type=float, default=4.0)
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--use-pallas", action="store_true")
    args = ap.parse_args()

    n_t = args.n_t or (4000 if args.smoke else 4500)
    n_steps = args.steps or (5 if args.smoke else 20)
    payload = run(n_t, args.avg_deg, n_steps, args.seed,
                  use_pallas=args.use_pallas,
                  check_every=max(1, n_steps // 3))
    common.save_json("bench_incremental", payload)


if __name__ == "__main__":
    main()
