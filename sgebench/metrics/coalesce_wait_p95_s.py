"""95th percentile of ``serve.coalesce_wait``, a request's pop off the
admission queue to the start of the pack that carried it: the coalescer's
hold and the dispatcher's previous packs (program spans,
``sgebench/spans.py``), over the requests whose pack started inside the
window."""

from sgebench import spans


def read(run):
    r = spans.of(run)
    return r.wait_p95("serve.coalesce_wait") if r else None
