"""Mean host seconds of one ``Enumerator.run_pack`` call, which returns
host results and so ends after the device has finished, over the packs
started inside the window."""


def read(run):
    packs = run.window_packs()
    return sum(p.t1 - p.t0 for p in packs) / len(packs) if packs else None
