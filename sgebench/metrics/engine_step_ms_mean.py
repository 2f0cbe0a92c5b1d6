"""Mean milliseconds per engine step: each pack's ``pack.device`` seconds
over the steps its slowest lane ran (``steps_max``, a program counter
read from the lanes' state; ``sgebench/spans.py``), over the packs
started inside the window."""

from sgebench import spans


def read(run):
    r = spans.of(run)
    per_step = [1e3 * (s.t1 - s.t0) / s.counts["steps_max"]
                for s in (r.started("pack.device") if r else ())
                if s.counts.get("steps_max")]
    return sum(per_step) / len(per_step) if per_step else None
