"""The program-span metrics on the CPU: a traced tiny run reports them
(without the device's idle shares, as it has no TPU plane), the recorder
switches on only for ``run.py --trace 1``, and device idle time is
attributed to hand-made spans once the clocks are aligned."""

from __future__ import annotations

import os

import numpy as np
import pytest

from sgebench import drive, run, spec, spans, xplane

SPAN_METRICS = {"admission_wait_p95_s", "coalesce_wait_p95_s",
                "prepare_domains_s_mean", "prepare_plan_s_mean",
                "pack_build_s_mean", "pack_device_s_mean",
                "pack_decode_s_mean", "engine_step_ms_mean", "lane_step_use"}
IDLE_METRICS = {"idle_pack_host_share", "idle_waiting_share"}


@pytest.fixture
def recorder():
    from repro import trace

    trace.drain()
    try:
        yield trace
    finally:
        trace.disable()
        trace.drain()


def test_switch_on_only_for_a_traced_run(recorder):
    assert not spans.switch_on_for(["pytest", "--trace", "1"])
    assert not spans.switch_on_for(["sgebench/run.py", "--workload", "x",
                                    "--trace", "0"])
    assert not recorder.enabled()
    empty = drive.Run(seconds=1.0, setup_s=0.0, t0=0.0, t1=1.0, records=[],
                      packs=[], device_kind="cpu")
    assert spec.load_metric({"name": "pack_build_s_mean", "unit": "s"}).read(
        empty) is None
    assert spans.switch_on_for(["sgebench/run.py", "--seed", "3",
                                "--trace=1"])
    assert recorder.enabled()


def test_traced_run_with_nothing_recorded_raises(recorder):
    """A traced run whose recorder was never switched on fails every span
    reader loudly (``run.py`` logs the ValueError) instead of dropping
    the metric; an untraced run reads None."""
    assert not recorder.enabled()
    traced = drive.Run(seconds=1.0, setup_s=0.0, t0=0.0, t1=1.0, records=[],
                       packs=[], device_kind="cpu", trace_window_s=0.5)
    for name in sorted(SPAN_METRICS | IDLE_METRICS):
        metric = spec.load_metric({"name": name, "unit": "s"})
        with pytest.raises(ValueError, match="recorder"):
            metric.read(traced)


def test_wait_p95s_share_the_queue_waits_population():
    """Both wait p95s are over the requests whose pack started in the
    window, matched by name: a request popped in the window whose pack
    started after it does not count, one popped before it does."""
    from repro.trace import Span

    waits = [  # (request, submit, pop, pack start)
        ("early", 9.0, 9.5, 10.5),   # popped before the window
        ("a", 10.0, 10.1, 10.2),
        ("b", 10.0, 10.3, 11.0),
        ("late", 19.0, 19.9, 21.0),  # its pack starts after the window
    ]
    recorded = []
    for k, (q, t_sub, t_pop, t_pack) in enumerate(waits):
        recorded += [Span("serve.admission_wait", t_sub, t_pop, 2 * k, None,
                          None, q, {}),
                     Span("serve.coalesce_wait", t_pop, t_pack, 2 * k + 1,
                          None, None, q, {})]
    r = drive.Run(seconds=10.0, setup_s=0.0, t0=10.0, t1=20.0, records=[],
                  packs=[], device_kind="cpu")
    reading = spans.Reading(r, recorded)
    assert reading.wait_p95("serve.admission_wait") == pytest.approx(
        float(np.percentile([0.5, 0.1, 0.3], 95)))
    assert reading.wait_p95("serve.coalesce_wait") == pytest.approx(
        float(np.percentile([1.0, 0.1, 0.7], 95)))


@pytest.mark.parametrize("cell", ["tiny-dense.tiny", "tiny-csr.tiny"])
def test_traced_run_reports_program_span_metrics(tiny_root, recorder, cell):
    import jax

    assert spans.switch_on_for(["sgebench/run.py", "--workload", cell,
                                "--trace", "1"])
    c = spec.load_cell(tiny_root, cell,
                       bench_dir=os.path.join(tiny_root, "sgebench"))
    assert SPAN_METRICS | IDLE_METRICS <= {m.name for m in c.per_layer}
    res = run.run_cell(c, 41, 1.5, True, jax.devices()[0], trace_seconds=0.5)
    assert res["correct"] is True
    got = {k: v["value"] for k, v in res["metrics"].items()}
    assert SPAN_METRICS <= set(got)
    assert not IDLE_METRICS & set(got)  # no TPU plane here
    assert 0 < got["lane_step_use"] <= 100
    for name in SPAN_METRICS - {"lane_step_use"}:
        assert got[name] > 0, name
    # each wait is a part of the submit -> pack-start wait (its population
    # differs only at the window's edges)
    for name in ("admission_wait_p95_s", "coalesce_wait_p95_s"):
        assert got[name] <= got["queue_wait_p95_s"] + 0.05, name
    assert got["pack_build_s_mean"] + got["pack_device_s_mean"] < (
        got["dispatch_s_mean"])


def _ns(perf: float, offset: float) -> float:
    return (perf - offset) * 1e9


def test_idle_attribution_on_hand_made_spans():
    """Two packs and the waits between them; the profiler's clock starts
    10 ms after the slice was scheduled to."""
    from repro.trace import Span

    offset = 103.01
    host = [  # (name, t0, t1, id, parent) on perf_counter
        ("serve.execute", 103.10, 103.60, 1, None),
        ("pack.build", 103.10, 103.20, 2, 1),
        ("pack.device", 103.20, 103.50, 3, 1),
        ("pack.decode", 103.50, 103.55, 4, 1),
        ("serve.deliver", 103.55, 103.60, 5, 1),
        ("serve.wait", 103.60, 103.80, 6, None),
        ("serve.execute", 103.80, 104.40, 7, None),
        ("pack.build", 103.80, 103.85, 8, 7),
        ("pack.device", 103.85, 104.30, 9, 7),
        ("pack.decode", 104.30, 104.35, 10, 7),
        ("serve.deliver", 104.35, 104.40, 11, 7),
        ("serve.wait", 104.40, 105.00, 12, None),
    ]
    recorded = [Span(n, a, b, i, p, 1, None, {}) for n, a, b, i, p in host]
    recorded.append(Span("serve.admission_wait", 103.0, 103.9, 13, None,
                         None, "q", {}))  # crosses threads: not a holder
    device = [("jit_concatenate/fusion", 103.15, 103.18),
              ("jit__engine_loop/while", 103.21, 103.49),
              ("jit__engine_loop/while", 103.86, 104.29),
              ("jit_squeeze/copy", 104.31, 104.32)]
    calls = [xplane.OpCall("hlo", _ns(a, offset), _ns(b, offset) - _ns(a, offset),
                           top=name) for name, a, b in device]
    busy = sum(b - a for _, a, b in device)
    summary = xplane.Summary(busy_s=busy, n_devices=1, calls=calls, gaps=[])
    r = drive.Run(seconds=30.0, setup_s=0.0, t0=90.0, t1=120.0, records=[],
                  packs=[], device_kind="TPU v5 lite", trace=summary,
                  trace_window_s=4.0)
    loops = [(c.start_ns / 1e9, (c.start_ns + c.dur_ns) / 1e9)
             for c in calls if c.top == spans.ENGINE_LOOP]
    found, share = spans.align(loops, [(103.20, 103.50), (103.85, 104.30)],
                               guess=103.0)
    assert found == pytest.approx(offset, abs=1e-9) and share == 1.0

    reading = spans.Reading(r, recorded)
    # idle: [103.18, 103.21], [103.49, 103.86], [104.29, 104.31]
    assert reading.idle_share(spans.PACK_HOST) == pytest.approx(
        100 * (0.02 + 0.05 + 0.05 + 0.05 + 0.01) / 4.0)
    assert reading.idle_share(spans.WAITING) == pytest.approx(100 * 0.20 / 4.0)
    by_span = reading.idle_by_span()
    assert by_span == pytest.approx({
        "pack.build": 0.07, "pack.device": 0.04, "pack.decode": 0.06,
        "serve.deliver": 0.05, "serve.wait": 0.20})
    idle_share = 100 * (1 - busy / 4.0)
    assert (reading.idle_share(spans.PACK_HOST)
            + reading.idle_share(spans.WAITING)) <= idle_share
    # a device trace whose loops fit no pack.device span attributes nothing
    lost = spans.Reading(r, [s for s in recorded if s.name != "pack.device"])
    assert lost.idle_share(spans.WAITING) is None and lost.idle_by_span() == {}
