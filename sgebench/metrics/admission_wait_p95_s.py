"""95th percentile of ``serve.admission_wait``, a request's submit to the
dispatcher's pop of it off the admission queue (program spans,
``sgebench/spans.py``), over the requests whose pack started inside the
window (matched by query name to their ``serve.coalesce_wait``), the
requests ``queue_wait_p95_s`` is taken over."""

from sgebench import spans


def read(run):
    r = spans.of(run)
    return r.wait_p95("serve.admission_wait") if r else None
