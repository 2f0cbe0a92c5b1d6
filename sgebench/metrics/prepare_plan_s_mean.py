"""Mean seconds of ``prepare.plan``: the seed edge, the ordering and the
padded plan arrays of one query (program spans, ``sgebench/spans.py``),
over the preparations started inside the window."""

from sgebench import spans


def read(run):
    r = spans.of(run)
    return r.mean("prepare.plan") if r else None
