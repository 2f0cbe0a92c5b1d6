"""95th percentile of the wait from a query's submit to the start of the
``run_pack`` that carried it (admission queue and coalescer), over the
packs started inside the window."""

import numpy as np


def read(run):
    submitted = {r.name: r.t_submit for r in run.records}
    waits = [p.t0 - submitted[n] for p in run.window_packs()
             for n in p.names if n in submitted]
    return float(np.percentile(waits, 95)) if waits else None
