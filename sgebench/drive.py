"""The closed loop that drives one ``EnumerationService`` for a window.

Each client thread takes its next pattern, times ``Enumerator.prepare``,
submits the prepared query with the cell's collection budget (0 in
counting mode), reads the stream to its terminal status (every chunk
included) and starts its next
pattern at once.  Latency runs from the start of ``prepare`` to the
terminal status.  No pattern is submitted twice in a run.

:class:`TimedEnumerator` is the program's ``Enumerator`` with one host
span around each ``run_pack`` (which returns host results, so its span
ends after the device has finished); the service is handed it as its
enumerator.  Spans are host-clock records kept in memory; with
``annotate`` they are also written into the profiler's trace.
"""

from __future__ import annotations

import contextlib
import dataclasses
import threading
import time
from typing import Callable, List, Optional, Sequence, Tuple

clock = time.perf_counter


@dataclasses.dataclass
class PackSpan:
    """One ``run_pack`` call: host start and end, the queries and the pack
    width it was given."""

    t0: float
    t1: float
    names: Tuple[str, ...]
    lanes: int


@dataclasses.dataclass
class QueryRecord:
    """What one client saw of one query."""

    name: str
    client: int
    arcs: int
    pattern: object
    plan_order: Optional[Tuple[int, ...]] = None
    t_start: float = 0.0
    t_prepared: float = 0.0
    t_submit: float = 0.0
    t_end: Optional[float] = None
    ok: bool = False
    error: Optional[str] = None
    count: Optional[int] = None
    rows: Optional[list] = None  # mappings as streamed (plan position
    # order); None for a query run in counting mode


def span_annotation(annotate: bool, name: str):
    if not annotate:
        return contextlib.nullcontext()
    import jax

    return jax.profiler.TraceAnnotation(name)


def timed_enumerator_class(base):
    """A subclass of the program's ``Enumerator`` that records a
    :class:`PackSpan` per ``run_pack``."""

    class TimedEnumerator(base):
        def __init__(self, *args, **kwargs):
            super().__init__(*args, **kwargs)
            self.pack_spans: List[PackSpan] = []
            self.annotate = False
            self.after_pack: Optional[Callable] = None

        def run_pack(self, queries, pack_size=None, cfg=None):
            t0 = clock()
            with span_annotation(self.annotate, "sgebench.run_pack"):
                out = super().run_pack(queries, pack_size=pack_size, cfg=cfg)
            t1 = clock()
            self.pack_spans.append(PackSpan(
                t0=t0, t1=t1,
                names=tuple(getattr(q, "name", "") for q in queries),
                lanes=int(pack_size or len(queries))))
            if self.after_pack is not None:
                out = self.after_pack(queries, out)
            return out

    return TimedEnumerator


def by_node(rows, order: Sequence[int]) -> List[Tuple[int, ...]]:
    """Position-ordered mappings as tuples indexed by pattern node (done
    after the window, so the clients only collect what they are sent)."""
    n = len(order)
    out = []
    for r in rows:
        row = [0] * n
        for pos, node in enumerate(order):
            row[node] = int(r[pos])
        out.append(tuple(row))
    return out


def closed_loop(service, enum, queues: Sequence[Sequence[tuple]], *,
                collect: int, seconds: float, annotate: bool,
                drain_timeout: float,
                at: Sequence[Tuple[float, Callable]] = (),
                ) -> Tuple[List[QueryRecord], float, float]:
    """Run one client thread per queue for ``seconds``.

    ``queues[c]`` holds ``(name, arcs, program_pattern, plain_pattern)``
    tuples.  ``at`` lists ``(seconds into the window, callable)`` pairs
    that this thread calls in order while the clients run (the traced
    slice).  Returns every query record (those still running at the close
    are awaited, up to ``drain_timeout``) and the window's start and end
    on :func:`clock`.
    """
    records: List[List[QueryRecord]] = [[] for _ in queues]
    go = threading.Event()
    bounds = {}
    errors: List[BaseException] = []

    def client(c: int) -> None:
        go.wait()
        end = bounds["t1"]
        for name, arcs, prog_pat, plain in queues[c]:
            if clock() >= end:
                return
            rec = QueryRecord(name=name, client=c, arcs=arcs, pattern=plain)
            records[c].append(rec)
            rec.t_start = clock()
            try:
                with span_annotation(annotate, "sgebench.prepare"):
                    q = enum.prepare(prog_pat, name=name)
                rec.t_prepared = clock()
                rec.plan_order = tuple(int(x) for x in q.plan.order[: q.plan.n_p])
                rec.t_submit = clock()
                stream = service.submit(q, tenant=f"client{c}", name=name,
                                        collect=collect)
                rows = []
                for chunk in stream:
                    rows.extend(chunk.mappings)
                rec.rows = rows if collect else None
                st = stream.status(timeout=drain_timeout)
                rec.t_end = clock()
                rec.ok = bool(st.ok)
                rec.error = st.error
                if st.ok:
                    rec.count = int(st.matchset.matches)
            except Exception as e:  # noqa: BLE001 — recorded as a failure
                rec.t_end = clock()
                rec.error = f"{type(e).__name__}: {e}"
                errors.append(e)
                return
        errors.append(RuntimeError(f"client {c} ran out of queries"))

    threads = [threading.Thread(target=client, args=(c,), name=f"client{c}",
                                daemon=True) for c in range(len(queues))]
    for t in threads:
        t.start()
    t0 = clock()
    bounds["t1"] = t0 + seconds
    go.set()
    for offset, fn in sorted(at, key=lambda a: a[0]):
        time.sleep(max(0.0, t0 + offset - clock()))
        fn()
    for t in threads:
        t.join(max(0.0, bounds["t1"] + drain_timeout - clock()))
    alive = [t.name for t in threads if t.is_alive()]
    out = [r for rs in records for r in rs]
    for r in out:
        if r.t_end is None and r.error is None:
            r.error = "no terminal status"
    if alive:
        raise RuntimeError(f"clients still running after the drain: {alive}")
    exhausted = [e for e in errors if "ran out of queries" in str(e)]
    if exhausted:
        raise exhausted[0]
    return out, t0, bounds["t1"]


@dataclasses.dataclass
class Run:
    """What one run measured, as the metric readers see it."""

    seconds: float
    setup_s: float
    t0: float  # window start, on :func:`clock`
    t1: float  # window end
    records: List[QueryRecord]
    packs: List[PackSpan]
    device_kind: str
    trace: Optional[object] = None  # xplane.Summary of the traced run
    trace_window_s: float = 0.0
    kernel_shapes: Optional[dict] = None

    def completed(self) -> List[QueryRecord]:
        """Queries whose terminal status arrived inside the window, ok."""
        return [r for r in self.records
                if r.ok and r.t_end is not None and r.t_end <= self.t1]

    def window_share(self, r: QueryRecord) -> float:
        """The share of an ``ok`` query's life (``prepare`` start to
        terminal status) that lies inside the window: 1 for a query
        answered inside it, less for one still running at its close, 0
        for a failed one.  Rates count every query by this share, so all
        the work of the window counts and a rate is not a whole number of
        queries over the window."""
        if not r.ok or r.t_end is None:
            return 0.0
        life = r.t_end - r.t_start
        inside = min(r.t_end, self.t1) - max(r.t_start, self.t0)
        return max(0.0, inside) / life if life > 0 else 1.0

    def window_packs(self) -> List[PackSpan]:
        return [p for p in self.packs if self.t0 <= p.t0 < self.t1]
