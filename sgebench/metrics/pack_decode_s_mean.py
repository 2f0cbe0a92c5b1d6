"""Mean seconds per pack of decoding its results: the self time of
``pack.decode`` (per-lane slices and host copies, any ``pack.retry``
left out) plus ``serve.deliver`` (mapping decode, chunking, finishing the
streams) of the same pack (program spans, ``sgebench/spans.py``), over
the packs started inside the window."""

from sgebench import spans


def read(run):
    r = spans.of(run)
    return r.decode_mean() if r else None
