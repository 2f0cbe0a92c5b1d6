"""Share of the traced slice, in percent, in which the device is idle
while the dispatcher builds a pack, decodes one or delivers its results
(``pack.build``, ``pack.decode``, ``serve.deliver``): the device trace's
idle gaps against the program spans on one clock (``sgebench/spans.py``)."""

from sgebench import spans


def read(run):
    r = spans.of(run)
    return r.idle_share(spans.PACK_HOST) if r else None
