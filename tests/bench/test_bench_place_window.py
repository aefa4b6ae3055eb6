"""``place_window_pct.offline``: the share of the timeline slots the
lockstep placement steps read, from the program's ``repro.place.pass``
spans on a traced window."""

import pytest

from bench import harness, program_trace
from bench.program_trace import RawEvent, reduce
from bench.tracing import WINDOW

HOST = "/host:CPU"
MS = 1e6
METRIC = "place_window_pct.offline"


def _span(name, start, dur, **stats):
    return RawEvent(HOST, "python", name, start * MS, dur * MS, stats)


def _trace(**pass_stats):
    """One plan of two placement passes; ``pass_stats`` maps a stat to
    the values the two passes carry."""
    passes = [{k: v[i] for k, v in pass_stats.items()} for i in range(2)]
    return [_span(WINDOW, 0, 100), _span("bench.plan", 0, 90),
            _span("repro.evaluate", 1, 88), _span("repro.place", 20, 60),
            _span("repro.place.pass", 20, 25, steps=10, wave_s=0.002,
                  **passes[0]),
            _span("repro.place.pass", 50, 25, steps=30, wave_s=0.006,
                  **passes[1])]


def _read(events, monkeypatch):
    monkeypatch.setattr(program_trace, "of", lambda record: reduce(events))
    return harness.metric_reader(METRIC)(harness.Record(trace=object()))


@pytest.mark.parametrize("window,slots,pct", [
    ((300, 900), (1000, 3000), 30.0),    # 1200 of 4000 slots
    ((1000, 3000), (1000, 3000), 100.0),  # every task spans T'
    ((10, 30), (1000, 3000), 1.0),        # one-slot tasks: a slot a step
])
def test_reads_the_window_share_of_the_pass_spans(window, slots, pct,
                                                   monkeypatch):
    events = _trace(window_slots=window, slots=slots)
    assert _read(events, monkeypatch) == pytest.approx(pct)


def test_a_program_without_the_counts_reads_nothing(monkeypatch):
    assert _read(_trace(), monkeypatch) is None


def test_no_trace_reads_nothing():
    assert harness.metric_reader(METRIC)(harness.Record()) is None


def test_reads_a_recorded_trace(tmp_path, monkeypatch):
    """A plan traced on the CPU reads the share its timings hold."""
    from repro.core import FleetEngine, SolverConfig
    from repro.workload import SyntheticSpec, synthetic_batch

    from bench.tracing import Tracer

    monkeypatch.setattr(harness, "TRACE_DIR", tmp_path)
    problems = synthetic_batch([SyntheticSpec(n=16, m=3, D=2, T=6, seed=s)
                                for s in (0, 1)])
    engine = FleetEngine(solver=SolverConfig(tol=5e-3, iters=400),
                         algos=("lp-map-f",))
    engine.evaluate(problems)
    rec = harness.Record()
    tracer = Tracer(tmp_path / "cell")
    tracer.start()
    with rec.span("plan"):
        result = engine.evaluate(problems)
    tracer.stop()
    rec.trace = tracer.summary()
    tel = result.timings["placement"]
    assert 0 < tel["window_slots"] <= tel["slots"]
    assert harness.metric_reader(METRIC)(rec) == pytest.approx(
        100.0 * tel["window_slots"] / tel["slots"])
