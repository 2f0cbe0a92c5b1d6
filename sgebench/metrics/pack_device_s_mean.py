"""Mean seconds of ``pack.device``: the engine call of a pack through its
``block_until_ready``, so the device loop and any wait behind other device
work (program spans, ``sgebench/spans.py``), over the packs started inside
the window."""

from sgebench import spans


def read(run):
    r = spans.of(run)
    return r.mean("pack.device") if r else None
