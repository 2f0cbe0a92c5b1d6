"""Spans and counters inside the program, off by default.

    from repro import trace
    trace.enable()                       # nothing is recorded before this
    with trace.span("pack.device", occupied=2) as sp:
        ...                              # sp is None while tracing is off
        if sp is not None:
            sp.add(steps_max=12)         # counts known only at the end
    spans = trace.drain()                # the records, oldest first

A span records its name, start and end on :func:`clock`
(``time.perf_counter``), the id of the span enclosing it on the same
thread and integer counts.  While on, each span is also a
``jax.profiler.TraceAnnotation("repro.<name>")``, so a profiler trace
shows it on the host plane beside the device's operations.
:func:`record` stores an interval that crosses threads (a request's
submit to its pop) with the request's id (its query name), in memory
only.  While on, every XLA backend
compilation (``/jax/core/compile/backend_compile_duration``) becomes a
``compile`` span whose ``counts["fun_name"]`` names the program.

While off, :func:`span` returns one shared null context: no clock read,
no record.  Records go into a bounded buffer (the oldest fall out) until
:func:`drain` takes them.
"""

from __future__ import annotations

import collections
import itertools
import threading
import time
from typing import Dict, List, NamedTuple, Optional, Union

clock = time.perf_counter
CAPACITY = 1 << 16
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class Span(NamedTuple):
    name: str
    t0: float
    t1: float
    id: int
    parent: Optional[int]  # enclosing span on the same thread
    thread: Optional[int]  # ``threading.get_ident()``; None for record()
    req: Optional[str]  # the request of a record(); None for a span
    counts: Dict[str, Union[int, str]]


_on = False
_records: collections.deque = collections.deque(maxlen=CAPACITY)
_ids = itertools.count(1)
_local = threading.local()
_annotation = None  # jax.profiler.TraceAnnotation, bound by enable()
_listening = False


def _stack() -> List[int]:
    stack = getattr(_local, "stack", None)
    if stack is None:
        stack = _local.stack = []
    return stack


class _Null:
    __slots__ = ()

    def __enter__(self):
        return None

    def __exit__(self, *exc):
        return False


NULL = _Null()


class _Open:
    """One span while it runs; :meth:`add` sets counts before it ends."""

    __slots__ = ("name", "counts", "id", "parent", "t0", "_ann")

    def __init__(self, name: str, counts: dict):
        self.name = name
        self.counts = counts

    def add(self, **counts) -> None:
        self.counts.update(counts)

    def __enter__(self) -> "_Open":
        stack = _stack()
        self.parent = stack[-1] if stack else None
        self.id = next(_ids)
        stack.append(self.id)
        self._ann = _annotation("repro." + self.name)
        self._ann.__enter__()
        self.t0 = clock()
        return self

    def __exit__(self, *exc):
        t1 = clock()
        self._ann.__exit__(*exc)
        _stack().pop()
        _records.append(Span(self.name, self.t0, t1, self.id, self.parent,
                             threading.get_ident(), None, self.counts))
        return False


def span(name: str, **counts):
    """A context manager recording one span (see the module docstring)."""
    if not _on:
        return NULL
    return _Open(name, counts)


def record(name: str, t0: float, t1: float, req: str) -> None:
    """Store request ``req``'s interval measured elsewhere, on :func:`clock`."""
    if _on:
        _records.append(Span(name, t0, t1, next(_ids), None, None, req, {}))


def _on_duration(event: str, duration: float, **kwargs) -> None:
    if _on and event == COMPILE_EVENT:
        t1 = clock()
        stack = _stack()
        _records.append(Span(
            "compile", t1 - duration, t1, next(_ids),
            stack[-1] if stack else None, threading.get_ident(), None,
            {"fun_name": str(kwargs.get("fun_name", ""))}))


def enable() -> None:
    """Start recording; the buffer keeps the latest ``CAPACITY`` records."""
    global _on, _annotation, _listening
    import jax

    _annotation = jax.profiler.TraceAnnotation
    if not _listening:
        jax.monitoring.register_event_duration_secs_listener(_on_duration)
        _listening = True
    _on = True


def disable() -> None:
    """Stop recording; what was recorded stays until :func:`drain`."""
    global _on
    _on = False


def enabled() -> bool:
    return _on


def drain() -> List[Span]:
    """Every record kept, oldest first; the buffer is left empty."""
    out = []
    while True:
        try:
            out.append(_records.popleft())
        except IndexError:
            return out
