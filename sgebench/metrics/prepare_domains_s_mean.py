"""Mean seconds of ``prepare.domains``: a query's domain fixpoint with its
target arrays, result on the host (program spans, ``sgebench/spans.py``),
over the preparations started inside the window."""

from sgebench import spans


def read(run):
    r = spans.of(run)
    return r.mean("prepare.domains") if r else None
