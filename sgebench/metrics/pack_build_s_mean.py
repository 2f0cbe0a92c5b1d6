"""Mean seconds of ``pack.build``: a pack's plan arrays, initial states,
inert lanes and the stacking of every leaf, up to the engine call
(program spans, ``sgebench/spans.py``), over the packs started inside the
window."""

from sgebench import spans


def read(run):
    r = spans.of(run)
    return r.mean("pack.build") if r else None
