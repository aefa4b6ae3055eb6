"""The reduction from a device trace to the per-layer metrics."""

import pytest

from bench.tracing import (WINDOW, Event, breakdown, summarize, union_ns)

DEV = "/device:TPU:0"
HOST = "/host:CPU"
MS = 1e6


def _trace():
    """A 10 ms window: the LP program runs 2-5 ms (two ops), a placement
    host span covers 5-9 ms with the device idle, another program runs
    9-9.5 ms; one op starts before the window and is clipped."""
    return [
        Event(HOST, "python", WINDOW, 0.0, 10 * MS),
        Event(HOST, "python", "bench.lp", 1.5 * MS, 3.6 * MS),
        Event(HOST, "python", "bench.place", 5 * MS, 4 * MS),
        Event(DEV, "XLA Modules", "jit__pdhg_run_many_tol(42)", 2 * MS, 3 * MS),
        Event(DEV, "XLA Ops", "fusion.1", 2 * MS, 2 * MS),
        Event(DEV, "XLA Ops", "fusion.2", 4 * MS, 1 * MS),
        Event(DEV, "XLA Modules", "jit_other(7)", 9 * MS, 0.5 * MS),
        Event(DEV, "XLA Ops", "copy.3", 9 * MS, 0.5 * MS),
        Event(DEV, "XLA Ops", "fusion.1", -1 * MS, 1.5 * MS),
    ]


def test_union_merges_overlaps_and_clips():
    total, gaps = union_ns([(0, 4), (2, 6), (8, 9), (-5, 1)], 0, 10)
    assert total == 7
    assert gaps == [(6, 8), (9, 10)]


def test_union_of_nothing_is_one_gap():
    assert union_ns([], 0, 5) == (0.0, [(0, 5)])


def test_summary_busy_idle_and_program_time():
    s = summarize(_trace())
    assert s.window_s == pytest.approx(0.010)
    # 0-0.5 (clipped op) + 2-5 + 9-9.5 ms
    assert s.busy_s == pytest.approx(0.004)
    assert s.idle_pct() == pytest.approx(60.0)
    assert s.module_seconds("_pdhg_run_many_tol") == pytest.approx(0.003)
    assert s.module_seconds("no_such_program") == 0.0
    assert s.op_s["fusion.1"] == pytest.approx(0.0025)


def test_chips_are_averaged():
    ev = _trace() + [Event("/device:TPU:1", "XLA Ops", "fusion.9",
                           0.0, 10 * MS)]
    s = summarize(ev)
    assert s.chips == 2
    assert s.busy_s == pytest.approx((0.004 + 0.010) / 2)


def test_breakdown_labels_gaps_by_host_span():
    b = breakdown(summarize(_trace()))
    assert b["device_ops"][0][0] == "fusion.1"
    label, seconds = b["idle_gaps"][0]
    assert label == "bench.place" and seconds == pytest.approx(0.004)
    assert len(b["idle_gaps"]) <= 10


def test_no_window_span_is_an_error():
    with pytest.raises(ValueError):
        summarize([e for e in _trace() if e.name != WINDOW])


def test_host_only_trace_reads_no_device():
    s = summarize([e for e in _trace() if e.plane == HOST])
    assert s.chips == 0 and s.busy_s == 0.0 and s.idle_pct() is None


def test_a_recorded_trace_is_read(tmp_path):
    """A trace the profiler recorded (on the CPU here: a host plane and
    no device plane) loads, and its window span is found."""
    import jax
    import jax.numpy as jnp

    from bench.harness import Record
    from bench.tracing import Tracer

    tracer = Tracer(tmp_path / "trace")
    tracer.start()
    with Record().span("probe"):
        jnp.ones((64, 64)).sum().block_until_ready()
    tracer.stop()
    s = tracer.summary()
    assert 0.0 < s.window_s < 60.0
    assert any(name == "bench.probe" for name, _, _ in s.host_spans)
    if jax.default_backend() == "cpu":
        assert s.chips == 0 and s.idle_pct() is None
