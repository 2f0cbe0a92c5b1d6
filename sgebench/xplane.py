"""Reduces a profiler trace (``.xplane.pb``) to device numbers.

On a TPU each ``/device:TPU:<i>`` plane has an ``XLA Modules`` line (one
event per program run) and an ``XLA Ops`` line (one event per HLO op, the
op's HLO text as its name; ops inside a loop body nest inside the loop's
event).  From them:

* ``busy_s``: the union of the ``XLA Ops`` intervals, averaged over the
  devices;
* every op call with its HLO text, so a kernel's calls and their shapes
  can be found (``kernel_calls``);
* a breakdown: the top-level ops with most device time, each named
  ``<program>/<op>`` (``jit__engine_loop/while``, ``jit_csr_arc_sweep/
  csr_arc_sweep``), and the idle gaps between busy intervals, each
  labelled with the benchmark's host spans (``sgebench.*`` trace
  annotations) that overlap it, or ``host`` where none does.

Reads the trace with ``jax.profiler.ProfileData`` and nothing else.
"""

from __future__ import annotations

import bisect
import collections
import dataclasses
import glob
import os
import re
from typing import Dict, List, Optional, Sequence, Tuple

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
SPAN_PREFIX = "sgebench."
OP_NAME = re.compile(r"^%?([A-Za-z_][A-Za-z0-9_\-]*?)(?:\.\d+)?(?:\s|=|$)")
MODULE_NAME = re.compile(r"^(.*?)(?:\(\d+\))?$")


@dataclasses.dataclass
class OpCall:
    name: str  # the op's HLO text
    start_ns: float
    dur_ns: float
    top: str = ""  # "<program>/<op>" for a top-level op, "" when nested


@dataclasses.dataclass
class Summary:
    busy_s: float
    n_devices: int
    calls: List[OpCall]
    gaps: List[Tuple[float, str]]  # (seconds, what the host was doing)

    def breakdown(self, top: int = 10) -> Dict[str, list]:
        ops: Dict[str, float] = collections.defaultdict(float)
        for c in self.calls:
            if c.top:
                ops[c.top] += c.dur_ns / 1e9
        by_time = sorted(ops.items(), key=lambda kv: -kv[1])[:top]
        gaps = sorted(self.gaps, key=lambda g: -g[0])[:top]
        return {"device_ops": [[n, s] for n, s in by_time],
                "idle_gaps": [[what, s] for s, what in gaps]}


def find(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def op_name(hlo: str) -> str:
    """``%csr_extend_bucketed.8 = (...) custom-call(...)`` ->
    ``csr_extend_bucketed``."""
    m = OP_NAME.match(hlo)
    return m.group(1) if m else hlo[:40]


def _union(intervals: Sequence[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _device(plane) -> Tuple[List[OpCall], List[Tuple[float, float]]]:
    modules: List[Tuple[float, float, str]] = []
    ops = []
    for line in plane.lines:
        if line.name == MODULES_LINE:
            for ev in line.events:
                name = MODULE_NAME.match(ev.name).group(1)
                modules.append((ev.start_ns, ev.start_ns + ev.duration_ns, name))
        elif line.name == OPS_LINE:
            ops = [OpCall(ev.name, ev.start_ns, ev.duration_ns)
                   for ev in line.events]
    modules.sort()
    starts = [m[0] for m in modules]
    ops.sort(key=lambda c: (c.start_ns, -c.dur_ns))
    end = float("-inf")
    for c in ops:
        if c.start_ns >= end:  # not inside the previous top-level op
            i = bisect.bisect_right(starts, c.start_ns) - 1
            prog = modules[i][2] if i >= 0 and modules[i][1] >= c.start_ns else "?"
            c.top = f"{prog}/{op_name(c.name)}"
            end = c.start_ns + c.dur_ns
    return ops, _union([(c.start_ns, c.start_ns + c.dur_ns) for c in ops])


def reduce_profile(pd) -> Summary:
    """A :class:`Summary` of a ``jax.profiler.ProfileData``."""
    devices = [p for p in pd.planes if p.name.startswith("/device:TPU:")]
    calls: List[OpCall] = []
    busy: List[List[Tuple[float, float]]] = []
    for plane in devices:
        ops, union = _device(plane)
        calls.extend(ops)
        busy.append(union)
    spans: List[Tuple[float, float, str]] = []
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(SPAN_PREFIX):
                    spans.append((ev.start_ns, ev.start_ns + ev.duration_ns,
                                  ev.name[len(SPAN_PREFIX):]))
    gaps: List[Tuple[float, str]] = []
    if busy and busy[0]:
        u = busy[0]
        for (_, e0), (s1, _) in zip(u, u[1:]):
            gaps.append(((s1 - e0) / 1e9, _host_label(spans, e0, s1)))
    per_device = [sum(e - s for s, e in u) / 1e9 for u in busy]
    return Summary(busy_s=sum(per_device) / len(per_device) if per_device else 0.0,
                   n_devices=len(devices), calls=calls, gaps=gaps)


def reduce(path: str) -> Summary:
    from jax.profiler import ProfileData

    return reduce_profile(ProfileData.from_file(path))


def _host_label(spans, s: float, e: float) -> str:
    names = sorted({n for a, b, n in spans if a < e and b > s})
    return "+".join(names) if names else "host"


SHAPE = re.compile(r"\b([a-z]+[0-9]*)\[([0-9,]*)\]")


def shapes(hlo: str) -> List[Tuple[str, Tuple[int, ...]]]:
    """The ``dtype[d0,d1,...]`` shapes written in an op's HLO text, in
    order (outputs first)."""
    return [(t, tuple(int(d) for d in dims.split(",") if d))
            for t, dims in SHAPE.findall(hlo)]


def kernel_calls(summary: Optional[Summary], kernel: str) -> List[OpCall]:
    """Device calls of the Pallas kernel named ``kernel`` (the HLO op is
    named after the kernel's jitted wrapper), top-level or nested."""
    if summary is None:
        return []
    return [c for c in summary.calls if op_name(c.name).startswith(kernel)]


def extend_shape(call: OpCall) -> int:
    """Lanes ``b`` of a CSR extend call, from its ``[b, 1, 4]`` meta
    output."""
    metas = [d for _, d in shapes(call.name) if len(d) >= 2 and d[-1] == 4]
    if not metas:
        raise ValueError(f"no [b, 1, 4] meta output in {call.name[:300]}")
    b = 1
    for d in metas[0][:-1]:
        b *= d
    return b


def extend_parents(call: OpCall, b: int) -> int:
    """Parent slots per lane ``mp`` of a CSR extend call with ``b`` lanes,
    from its flat ``[b * mp]`` segment-bound operands."""
    flat = [d[0] for t, d in shapes(call.name)
            if t == "s32" and len(d) == 1 and d[0] > b and d[0] % b == 0]
    if not flat:
        raise ValueError(f"no [b * mp] segment bounds in {call.name[:300]}")
    return min(flat) // b


def sweep_arcs(call: OpCall, n_t: int) -> int:
    """Arcs of a ``csr_arc_sweep`` call, from its ``[n_arcs, 1, n_pad]``
    output (``n_pad >= n_t``)."""
    outs = [d for _, d in shapes(call.name)
            if len(d) == 3 and d[1] == 1 and d[2] >= n_t]
    if not outs:
        raise ValueError(f"no [n_arcs, 1, n_pad] output in {call.name[:300]}")
    return outs[0][0]
