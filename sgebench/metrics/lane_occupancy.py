"""Queries dispatched over lanes dispatched, in percent, over the packs
started inside the window."""


def read(run):
    packs = run.window_packs()
    lanes = sum(p.lanes for p in packs)
    return 100.0 * sum(len(p.names) for p in packs) / lanes if lanes else None
