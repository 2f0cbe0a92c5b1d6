#!/usr/bin/env python3
"""The control of ``correct``: the reference with one guarantee broken, put
in the program's place, has to come out as not correct.

    python3 sgebench/control.py --workload <cell> --seed <n> --queries <k> \
        [--breaks one-to-one|last-edges]

The configurations state exact enumeration of label- and edge-preserving
one-to-one mappings.  The control breaks one of those guarantees, a step
a faster search would be tempted to skip: ``one-to-one`` lets two
pattern nodes map to one target node (the fault a used-set shortcut
makes); ``last-edges`` leaves the edges of the last node placed
unchecked, matching it by label alone (the fault of a last step that
skips its parents' adjacency).  It answers the first ``k`` queries of the cell's traffic from ``--seed``, in
the order the clients would send them, and the harness's own comparison
(``check.compare``) judges those answers against the plain reference.
Prints the numbers compared as one JSON line.  Needs neither the program
nor a chip; the benchmark's own runs never run it.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
from typing import List, Optional

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from sgebench import check, drive, gen, reference, spec  # noqa: E402


BREAKS = {"one-to-one": {"injective": False},
          "last-edges": {"last_edges": False}}


def control_numbers(cell: spec.Cell, seed: int, queries: int,
                    breaks: str = "one-to-one") -> dict:
    import numpy as np

    rng = np.random.default_rng(seed)
    plain = gen.target(cell.config["target"], rng)
    queues, _ = gen.patterns(plain, cell.traffic, rng)
    t = reference.reference_target(plain)
    order = [(c, k) for k in range(max(map(len, queues)))
             for c in range(len(queues)) if k < len(queues[c])][:queries]
    stream = cell.traffic.get("stream", True)
    records = []
    for c, k in order:
        p = queues[c][k]
        count, maps = reference.matches_of(p, t, **BREAKS[breaks])
        records.append(drive.QueryRecord(
            name=f"c{c}q{k}", client=c, arcs=p.m, pattern=p, ok=True,
            count=count, rows=maps if stream else None,
            plan_order=tuple(range(p.n))))
    numbers = check.compare(records, t)
    return {"cell": cell.name, "seed": seed, "breaks": breaks,
            "queries": len(records),
            "correct": check.passed(numbers), "checks": check.report(numbers)}


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--queries", type=int, required=True)
    ap.add_argument("--breaks", choices=sorted(BREAKS), default="one-to-one")
    args = ap.parse_args(argv)
    cell = spec.load_cell(ROOT, args.workload)
    print(json.dumps(control_numbers(cell, args.seed, args.queries,
                                     args.breaks)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
