"""Share of the traced slice, in percent, in which the device is idle
while the dispatcher waits for work (``serve.wait``: no pack is ripe):
the device trace's idle gaps against the program spans on one clock
(``sgebench/spans.py``)."""

from sgebench import spans


def read(run):
    r = spans.of(run)
    return r.idle_share(spans.WAITING) if r else None
