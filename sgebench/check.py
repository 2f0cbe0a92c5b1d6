"""The comparison that decides ``correct``.

Every query the clients started has to reach an ``ok`` terminal status,
and every one that did is compared with the plain reference:

* the count in the terminal status has to equal the reference's;
* where the query was streamed (not run in counting mode), the mappings
  streamed, as tuples indexed by pattern node, have to equal the
  reference's set, each once.

A streamed mix gets at most ``collect`` mappings per engine worker; each
configuration sets it above the largest answer its traffic was measured
to have, so every answer is due in full.  Each number compared has the
limit 0.
"""

from __future__ import annotations

from typing import Dict, Sequence

from sgebench.drive import by_node
from sgebench.reference import Target, matches_of

LIMITS = {"failed": 0, "wrong_counts": 0, "wrong_mappings": 0}


def compare(records: Sequence, target: Target) -> Dict[str, int]:
    """The numbers compared, each to be at or under its limit in
    :data:`LIMITS`, and how many queries were checked."""
    failed = sum(1 for r in records if not r.ok)
    checked = [r for r in records if r.ok]
    wrong_counts = wrong_mappings = 0
    for r in checked:
        count, maps = matches_of(r.pattern, target)
        if r.count != count:
            wrong_counts += 1
        if r.rows is not None:
            got = by_node(r.rows, r.plan_order or ())
            if len(got) != count or sorted(got) != sorted(maps):
                wrong_mappings += 1
    return {"failed": failed, "wrong_counts": wrong_counts,
            "wrong_mappings": wrong_mappings, "checked": len(checked)}


def passed(numbers: Dict[str, int]) -> bool:
    return numbers["checked"] > 0 and all(
        numbers[k] <= lim for k, lim in LIMITS.items())


def report(numbers: Dict[str, int]) -> Dict[str, Dict[str, int]]:
    """Each number beside its limit, for the result line and stderr;
    ``checked`` has to be at least 1."""
    out = {k: {"value": int(numbers[k]), "limit": lim}
           for k, lim in LIMITS.items()}
    out["checked"] = {"value": int(numbers["checked"]), "at_least": 1}
    return out
