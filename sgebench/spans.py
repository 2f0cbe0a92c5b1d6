"""Reduces the program's own spans and counters (``repro.trace``) to the
per-layer metrics of a ``--trace 1`` run.

The program records, while its recorder is on, a span at each layer
boundary on ``time.perf_counter`` (the clock of the closed loop's window):
``serve.wait``, ``serve.execute`` with ``pack.build``, ``pack.device``
(counts ``steps_max``, ``steps_sum``, ``occupied``), ``pack.decode``
(``pack.retry`` inside) and ``serve.deliver`` on the dispatcher;
``prepare.domains`` and ``prepare.plan`` on the clients; the intervals
``serve.admission_wait`` (submit to pop) and ``serve.coalesce_wait`` (pop
to the pack's start) per request, with its query name; a ``compile`` span
per XLA compile.

``run.py`` gives a metric reader no hook before the window, so this module
switches the recorder on as it is imported, when the process is ``run.py``
with ``--trace 1`` (:func:`switch_on_for`), and the first reader drains it
after the window (:func:`of`).  Where the program has no recorder (a
checkout from before it), every reader here returns None; where it has
one that kept nothing in a traced run (started some other way), every
reader raises ValueError, which ``run.py`` logs.

Device idle time is attributed to spans on one clock.  A profiler trace
gives device times from its own start, which the run does not keep, so
:func:`align` finds that start on ``perf_counter``: it is the offset that
puts most of the engine's device loops (``jit__engine_loop/while``) inside
the ``pack.device`` (or ``pack.retry``) spans that launched and awaited
them, nearest to the slice's scheduled start.
"""

from __future__ import annotations

import argparse
import collections
import functools
import os
import sys
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ENGINE_LOOP = "jit__engine_loop/while"
PACK_HOST = ("pack.build", "pack.decode", "serve.deliver")
WAITING = ("serve.wait",)
LAUNCHERS = ("pack.device", "pack.retry")  # spans that run an engine loop
MIN_ALIGNED = 0.9  # share of engine-loop device time inside LAUNCHERS

Interval = Tuple[float, float]


def _log(msg: str) -> None:
    print(f"sgebench: {msg}", file=sys.stderr, flush=True)


def _recorder():
    """The program's ``repro.trace`` module, or None where it has none."""
    src = os.path.join(ROOT, "src")
    if src not in sys.path:
        sys.path.insert(0, src)
    try:
        from repro import trace
    except ImportError:
        return None
    return trace


def switch_on_for(argv: Sequence[str]) -> bool:
    """Switch the program's recorder on if ``argv`` runs ``run.py`` with
    ``--trace 1``; returns whether it did."""
    if not argv or os.path.basename(argv[0]) != "run.py":
        return False
    ap = argparse.ArgumentParser(add_help=False)
    ap.add_argument("--trace", type=int, default=0)
    if ap.parse_known_args(list(argv[1:]))[0].trace != 1:
        return False
    recorder = _recorder()
    if recorder is None:
        return False
    recorder.enable()
    return True


def union(intervals: Iterable[Interval]) -> List[Interval]:
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def overlap(a: Sequence[Interval], b: Sequence[Interval]) -> float:
    """Length of the intersection of two sorted disjoint interval lists."""
    i = j = 0
    total = 0.0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            total += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def align(loops: Sequence[Interval], devices: Sequence[Interval],
          guess: float) -> Tuple[Optional[float], float]:
    """The offset ``off`` (``perf_counter = off + trace seconds``) that puts
    the largest share of ``loops`` (device intervals, trace seconds) inside
    ``devices`` (host spans, perf_counter), nearest to ``guess``; with that
    share.  ``(None, 0.0)`` when no loop fits in any span."""
    total = sum(e - s for s, e in loops)
    events: Dict[float, List[float]] = collections.defaultdict(lambda: [0.0, 0.0])
    for s, e in loops:
        for a, b in devices:
            lo, hi = a - s, b - e  # offsets that put [s, e] inside [a, b]
            if lo <= hi:
                events[lo][0] += e - s
                events[hi][1] += e - s
    if not events or total <= 0:
        return None, 0.0
    xs = sorted(events)
    segments = []  # (coverage, lo, hi)
    cover = 0.0
    for k, x in enumerate(xs):
        cover += events[x][0]
        segments.append((cover, x, x))
        cover -= events[x][1]
        if k + 1 < len(xs):
            segments.append((cover, x, xs[k + 1]))
    best = max(c for c, _, _ in segments)
    runs: List[List[float]] = []  # maximal runs of offsets at the best cover
    for c, lo, hi in segments:
        if c >= best - 1e-9 * total:
            if runs and lo <= runs[-1][1]:
                runs[-1][1] = max(runs[-1][1], hi)
            else:
                runs.append([lo, hi])
    lo, hi = min(runs, key=lambda r: max(r[0] - guess, guess - r[1], 0.0))
    return (lo + hi) / 2, best / total


class Reading:
    """One run's program spans, reduced for the metric readers."""

    def __init__(self, run, spans: Sequence):
        self.run = run
        self.spans = list(spans)

    def _inside(self, s, end: bool = False) -> bool:
        t = s.t1 if end else s.t0
        return self.run.t0 <= t < self.run.t1

    def durations(self, name: str, end: bool = False) -> List[float]:
        """Durations of the ``name`` spans that start (``end``: end) in
        the window."""
        return [s.t1 - s.t0 for s in self.spans
                if s.name == name and self._inside(s, end)]

    def wait_p95(self, name: str) -> Optional[float]:
        """95th percentile of the per-request ``name`` intervals of the
        requests whose pack started in the window (their
        ``serve.coalesce_wait`` ends there): the population of
        ``queue_wait_p95_s``, so each wait's p95 is at most that one's."""
        carried = {s.req for s in self.spans
                   if s.name == "serve.coalesce_wait" and self._inside(s, end=True)}
        d = [s.t1 - s.t0 for s in self.spans
             if s.name == name and s.req in carried]
        return float(np.percentile(d, 95)) if d else None

    def mean(self, name: str) -> Optional[float]:
        d = self.durations(name)
        return sum(d) / len(d) if d else None

    def started(self, name: str) -> list:
        """The ``name`` spans that start in the window."""
        return [s for s in self.spans if s.name == name and self._inside(s)]

    def decode_mean(self) -> Optional[float]:
        """Per pack: the self time of ``pack.decode`` (its ``pack.retry``
        excluded) plus the ``serve.deliver`` of the same ``serve.execute``."""
        decodes = self.started("pack.decode")
        if not decodes:
            return None
        ids = {s.id for s in decodes}
        parents = {s.parent for s in decodes if s.parent is not None}
        total = sum(s.t1 - s.t0 for s in decodes)
        for s in self.spans:
            if s.name == "pack.retry" and s.parent in ids:
                total -= s.t1 - s.t0
            elif s.name == "serve.deliver" and s.parent in parents:
                total += s.t1 - s.t0
        return total / len(decodes)

    def means(self) -> Dict[str, Tuple[int, float]]:
        """Per span name: how many started in the window, mean seconds."""
        d: Dict[str, List[float]] = collections.defaultdict(list)
        for s in self.spans:
            if self._inside(s):
                d[s.name].append(s.t1 - s.t0)
        return {k: (len(v), sum(v) / len(v)) for k, v in sorted(d.items())}

    def compiles(self) -> Dict[str, int]:
        """XLA compiles that ended inside the window, by program."""
        return dict(collections.Counter(
            s.counts.get("fun_name", "") for s in self.spans
            if s.name == "compile" and self._inside(s, end=True)))

    # -- device idle time, attributed to host spans ----------------------

    @functools.cached_property
    def idle(self) -> Optional[List[Interval]]:
        """The device's idle gaps inside the traced slice, on perf_counter;
        None without a device trace or where the spans do not align."""
        summary = self.run.trace
        if summary is None or summary.n_devices == 0 or not summary.calls:
            return None
        tops = [c for c in summary.calls if c.top]
        busy = union((c.start_ns / 1e9, (c.start_ns + c.dur_ns) / 1e9)
                     for c in tops)
        loops = [(c.start_ns / 1e9, (c.start_ns + c.dur_ns) / 1e9)
                 for c in tops if c.top == ENGINE_LOOP]
        devices = [(s.t0, s.t1) for s in self.spans if s.name in LAUNCHERS]
        guess = self.run.t0 + (self.run.seconds - self.run.trace_window_s) / 2
        off, share = align(loops, devices, guess)
        _log(f"spans: {100 * share:.1f}% of {len(loops)} engine loops' device "
             f"time inside pack.device/retry spans at offset "
             f"{'none' if off is None else f'{off - guess:+.6f}s'} from the "
             f"slice's scheduled start")
        if off is None or share < MIN_ALIGNED:
            return None
        return [(off + e0, off + s1) for (_, e0), (s1, _) in zip(busy, busy[1:])]

    def idle_share(self, names: Sequence[str]) -> Optional[float]:
        """Percent of the traced slice in which the device is idle and a
        span named in ``names`` runs."""
        gaps = self.idle
        if gaps is None or self.run.trace_window_s <= 0:
            return None
        inside = union((s.t0, s.t1) for s in self.spans if s.name in names)
        return 100.0 * overlap(gaps, inside) / self.run.trace_window_s

    def idle_by_span(self) -> Dict[str, float]:
        """Idle seconds of the slice by the innermost spans running then
        (names joined by ``+``; ``none`` where no span runs)."""
        gaps = self.idle
        if not gaps:
            return {}
        points = []  # (time, order, kind, payload); ends sort before starts
        for g0, g1 in gaps:
            points += [(g0, 1, "gap", 1), (g1, 0, "gap", -1)]
        for s in self.spans:
            if s.thread is not None and s.t1 > gaps[0][0] and s.t0 < gaps[-1][1]:
                points += [(s.t0, 1, "span", s), (s.t1, 0, "span", s)]
        points.sort(key=lambda p: (p[0], p[1]))
        out: Dict[str, float] = collections.defaultdict(float)
        active: Dict[int, object] = {}
        in_gap = 0
        prev = None
        for t, order, kind, payload in points:
            if in_gap > 0 and prev is not None and t > prev:
                parents = {s.parent for s in active.values()}
                names = sorted({s.name for s in active.values()
                                if s.id not in parents})
                out["+".join(names) or "none"] += t - prev
            prev = t
            if kind == "gap":
                in_gap += payload
            elif order == 1:
                active[payload.id] = payload
            else:
                active.pop(payload.id, None)
        return dict(sorted(out.items(), key=lambda kv: -kv[1]))


def of(run) -> Optional[Reading]:
    """The program spans of ``run``, drained from the recorder by the first
    reader of the run and kept on it for the others.  None where the
    program has no recorder, or the run was not traced; ValueError where
    a traced run's recorder kept nothing (it was not switched on)."""
    recorder = _recorder()
    if recorder is None:
        return None
    reading = getattr(run, "_program_spans", None)
    if reading is None:
        reading = run._program_spans = Reading(run, recorder.drain())
        if reading.spans:
            _log(f"span_s_mean {reading.means()}")
            _log(f"xla_compiles_in_window {reading.compiles()}")
            by_span = reading.idle_by_span()
            if by_span:
                _log(f"idle_by_span {by_span}")
    if reading.spans:
        return reading
    if run.trace_window_s > 0:
        raise ValueError("the program's recorder (repro.trace) kept no span "
                         "in this traced run: it is switched on only in a "
                         "`run.py --trace 1` process (spans.switch_on_for)")
    return None


switch_on_for(sys.argv)
