"""How much of its packs' loops the queries used, in percent: the steps
the occupied lanes ran (``steps_sum``) over ``steps_max`` times the
occupied lanes, summed over the packs started inside the window (program
counters on ``pack.device``, ``sgebench/spans.py``).  The vmapped loop
runs until its slowest lane stops; the rest is the lanes' idle tail."""

from sgebench import spans


def read(run):
    r = spans.of(run)
    packs = r.started("pack.device") if r else []
    used = sum(s.counts.get("steps_sum", 0) for s in packs)
    room = sum(s.counts.get("steps_max", 0) * s.counts.get("occupied", 0)
               for s in packs)
    return 100.0 * used / room if room else None
