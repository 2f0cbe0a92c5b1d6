#!/usr/bin/env python3
"""Run one cell of the benchmark once, on the chips of this machine.

    python3 sgebench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

In order: check that the first device is a TPU (no CPU fallback); put
JAX's compile cache at ``.jax_cache/`` in the checkout; make the cell's
target and patterns from ``--seed``; build the index, compile and warm
every engine bucket and preparation shape the run's patterns use (all of
that is ``setup_s``); drive one ``EnumerationService`` with the cell's
closed loop for ``--seconds``; compare what the clients were answered
with the plain reference; print the result as the last line of stdout.

``--trace 0`` reports the cell's end-to-end metrics, ``--trace 1`` its
per-layer metrics from a profiler trace of the window.  The numbers
compared for ``correct`` are printed beside their limits as the last lines
of stderr and under ``checks``, the result's last key.
"""

import time

T_PROCESS = time.perf_counter()  # set-up is measured from here

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from typing import Callable, Dict, List, Optional  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
CACHE_DIR = os.path.join(ROOT, ".jax_cache")
DRAIN_TIMEOUT_S = 120.0
TRACE_SECONDS = 8.0  # length of the traced slice of a --trace 1 window

if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from sgebench import check, drive, gen, reference, spec, xplane  # noqa: E402


class Refused(SystemExit):
    """The run cannot measure here; exits non-zero with no result."""

    def __init__(self, msg: str):
        print(f"sgebench: {msg}", file=sys.stderr)
        super().__init__(2)


def require_tpu(chips: int) -> list:
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise Refused(f"the first device is {devices[0].platform!r}, not a TPU")
    if len(devices) < chips:
        raise Refused(f"{chips} chips asked for, {len(devices)} present")
    return devices[:chips]


def import_program(root: str = ROOT) -> None:
    src = os.path.join(root, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        raise Refused(f"no program under {src}")
    if src not in sys.path:
        sys.path.insert(0, src)


def enable_compile_cache() -> None:
    import jax

    os.makedirs(CACHE_DIR, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    # no eviction: it races between the threads that compile (clients'
    # prepare, the dispatcher) and leaves entries unwritten
    jax.config.update("jax_compilation_cache_max_size", -1)
    # every program, however quick to compile, comes from the cache after
    # the first run, so set-up does the same work each time
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)


def program_graph(g: gen.Graph):
    from repro.core.graph import Graph

    return Graph(n=g.n, src=g.src, dst=g.dst, labels=g.labels,
                 edge_labels=g.elab)


def _log(msg: str) -> None:
    print(f"sgebench: {msg}", file=sys.stderr, flush=True)


def run_cell(cell: spec.Cell, seed: int, seconds: float, trace: bool,
             device, keep_trace: Optional[str] = None,
             after_pack: Optional[Callable] = None,
             t_process: float = T_PROCESS,
             trace_seconds: float = TRACE_SECONDS) -> Dict:
    """One run of ``cell``; returns the result line as a dict.  ``device``
    is the first device the run uses.  ``after_pack`` (tests only) replaces
    each ``run_pack`` result, to plant a fault under the timed path."""
    import jax
    import numpy as np
    from repro.configs.sge import ENGINE
    from repro.core import Enumerator, SubgraphIndex, prepare_query
    from repro.serve import EnumerationService, ServiceConfig

    cfg, traffic = cell.config, cell.traffic
    if traffic["loop"] != "closed":
        raise Refused(f"traffic loop {traffic['loop']!r}: only 'closed' is driven")
    # a streamed mix gets every mapping within the configuration's budget
    # per engine worker; a counting mix (``"stream": false``) gets counts
    stream = traffic.get("stream", True)
    if stream and "collect" not in cfg:
        raise Refused(f"configuration {cfg['name']!r} sets no collect budget "
                      "for a streamed mix")
    collect = int(cfg["collect"]) if stream else 0
    rng = np.random.default_rng(seed)
    plain = gen.target(cfg["target"], rng)
    queues, warm = gen.patterns(plain, traffic, rng)
    _log(f"target n={plain.n} arcs={plain.m}; {sum(map(len, queues))} "
         f"patterns over {len(queues)} clients, {len(warm)} warm-up shapes "
         f"({time.perf_counter() - t_process:.1f}s)")

    index = SubgraphIndex.build(program_graph(plain),
                                sparse=cfg["index"] == "csr")
    _log(f"index built ({time.perf_counter() - t_process:.1f}s)")
    engine = dataclasses.replace(ENGINE, **cfg.get("engine", {}))
    enum = drive.timed_enumerator_class(Enumerator)(
        index, config=engine, variant=cfg["variant"])
    service_cfg = ServiceConfig(default_collect=collect, **cfg["service"])
    svc = EnumerationService(enumerator=enum, service=service_cfg)

    # warm-up: prepare one pattern of each size (each size has one shape,
    # and preparation compiles per shape on a CSR index) at every parent
    # slot count the run's patterns can need (a pattern node with d
    # neighbours placed before it needs 2 d slots, at least the program's
    # 8), then run one real pack per coalesce bucket through the service's
    # own entry, so no engine compiles inside the window
    run_cfg = dataclasses.replace(enum.config, collect_matches=collect)
    degree = max(gen.max_degree(p) for q in queues for p in q)
    slots = range(8, max(12, 2 * degree) + 1, 2)
    reps = {}
    for i, p in enumerate(warm):
        for mp in slots:
            q = prepare_query(program_graph(p), index, variant=cfg["variant"],
                              name=f"warm{i}.{mp}", max_parents=mp,
                              use_pallas=enum.config.use_pallas)
            if q.plan.satisfiable:
                reps.setdefault(enum.coalesce_key(q, run_cfg), q)
    _log(f"{len(warm)} warm-up shapes prepared at {len(slots)} parent "
         f"slot counts "
         f"({time.perf_counter() - t_process:.1f}s)")
    for q in reps.values():
        for ms in enum.run_pack([q], pack_size=service_cfg.max_lanes,
                                cfg=run_cfg):
            if collect:
                ms.mappings()
    _log(f"{len(reps)} engine buckets warmed, {enum.compiles} engine "
         f"compiles ({time.perf_counter() - t_process:.1f}s)")
    enum.pack_spans.clear()
    compiles_warm = enum.compiles

    named = [[(f"c{c}q{k}", p.m, program_graph(p), p)
              for k, p in enumerate(qs)] for c, qs in enumerate(queues)]
    enum.after_pack = after_pack
    enum.annotate = trace
    trace_dir = tempfile.mkdtemp(prefix="sgebench-trace-") if trace else None
    traced = {}

    def trace_start() -> None:
        traced["t0"] = time.perf_counter()
        jax.profiler.start_trace(trace_dir)

    def trace_stop() -> None:
        traced["t1"] = time.perf_counter()
        jax.profiler.stop_trace()

    at = []
    if trace:
        # a steady slice from the middle of the window
        length = min(trace_seconds, seconds)
        at = [((seconds - length) / 2, trace_start),
              ((seconds + length) / 2, trace_stop)]
    svc.start()
    try:
        records, t0, t1 = drive.closed_loop(
            svc, enum, named, collect=collect, seconds=seconds,
            annotate=trace, drain_timeout=DRAIN_TIMEOUT_S, at=at)
    finally:
        if "t0" in traced and "t1" not in traced:
            trace_stop()
        svc.stop(drain=True, timeout=DRAIN_TIMEOUT_S)
    setup_s = t0 - t_process
    compiles_window = enum.compiles - compiles_warm
    stats = device.memory_stats() or {}
    memory_peak = stats.get("peak_bytes_in_use")
    packs = list(enum.pack_spans)
    # each adjacency plane (out or in, per edge label) holds every arc of
    # its label once
    kernel_shapes = {"w": index.w, "n_t": index.n,
                     "n_planes": 2 * index.n_edge_labels,
                     "nnz_plane": plain.m // index.n_edge_labels}
    del svc, enum, index, reps
    gc.collect()

    summary = None
    if trace_dir is not None:
        path = xplane.find(trace_dir)
        if keep_trace:
            os.makedirs(keep_trace, exist_ok=True)
            shutil.copy(path, os.path.join(keep_trace, os.path.basename(path)))
        summary = xplane.reduce(path)
        shutil.rmtree(trace_dir, ignore_errors=True)
    run = drive.Run(seconds=seconds, setup_s=setup_s, t0=t0, t1=t1,
                    records=records, packs=packs,
                    device_kind=device.device_kind, trace=summary,
                    trace_window_s=traced["t1"] - traced["t0"] if trace else 0.0,
                    kernel_shapes=kernel_shapes)

    t_ref = time.perf_counter()
    numbers = check.compare(records, reference.reference_target(plain))
    _log(f"reference: {numbers['checked']} queries in "
         f"{time.perf_counter() - t_ref:.1f}s; {compiles_window} engine "
         f"compiles in the window")

    metrics = {}
    for m in (cell.per_layer if trace else cell.end_to_end):
        try:
            value = m.read(run)
        except ValueError as e:
            _log(f"metric {m.name}: {e}")
            value = None
        if value is not None:
            metrics[m.name] = {"value": value, "unit": m.unit}
    dev = {"platform": device.platform, "kind": device.device_kind,
           "count": cell.chips, "memory_peak_bytes": memory_peak}
    result = {"correct": check.passed(numbers), "attempted": len(records),
              "failed": numbers["failed"], "metrics": metrics, "device": dev}
    if summary is not None:
        dev["busy_s"] = summary.busy_s
        dev["window_s"] = run.trace_window_s
        result["breakdown"] = summary.breakdown()
    result["clients"] = client_lateness(records)
    result["by_arcs"] = by_arcs(run)
    result["engine_compiles_in_window"] = compiles_window
    result["latency_samples"] = len(run.completed())
    result["checks"] = check.report(numbers)
    return result


def by_arcs(run: drive.Run) -> Dict[str, list]:
    """Per pattern size (arcs), over the queries completed in the window:
    ``[queries, mean latency s, mean matches]``."""
    out: Dict[str, list] = {}
    for r in run.completed():
        out.setdefault(str(r.arcs), []).append(r)
    return {k: [len(rs),
                sum(r.t_end - r.t_start for r in rs) / len(rs),
                sum(r.count or 0 for r in rs) / len(rs)]
            for k, rs in sorted(out.items(), key=lambda kv: int(kv[0]))}


def client_lateness(records: List[drive.QueryRecord]) -> Dict[str, float]:
    """How late the closed loop ran: the gap between one query's terminal
    status and the start of the same client's next query."""
    gaps = []
    by_client: Dict[int, list] = {}
    for r in records:
        by_client.setdefault(r.client, []).append(r)
    for rs in by_client.values():
        for a, b in zip(rs, rs[1:]):
            if a.t_end is not None:
                gaps.append(b.t_start - a.t_end)
    return {"gap_ms_mean": 1e3 * sum(gaps) / len(gaps) if gaps else 0.0,
            "gap_ms_max": 1e3 * max(gaps) if gaps else 0.0}


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--keep-trace", default=None,
                    help="copy the traced run's .xplane.pb into this directory")
    ap.add_argument("--trace-seconds", type=float, default=TRACE_SECONDS,
                    help="length of the traced slice, from the window's middle")
    args = ap.parse_args(argv)
    if args.seed < 0:
        raise Refused("--seed must be a whole number >= 0")
    cell = spec.load_cell(ROOT, args.workload)
    devices = require_tpu(cell.chips)
    import_program()
    enable_compile_cache()
    dev = devices[0]
    _log(f"device platform={dev.platform} kind={dev.device_kind} "
         f"count={len(devices)}")
    result = run_cell(cell, args.seed, args.seconds, bool(args.trace), dev,
                      keep_trace=args.keep_trace,
                      trace_seconds=args.trace_seconds)
    for name, c in result["checks"].items():
        bound = (f"limit {c['limit']}" if "limit" in c
                 else f"at least {c['at_least']}")
        _log(f"check {name} = {c['value']} ({bound})")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
