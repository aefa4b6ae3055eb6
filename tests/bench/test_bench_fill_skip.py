"""``fill_skip_pct.offline`` and ``fill_skip_pct.robust``: the share of
the cross-fill placement attempts skipped without a lockstep step, from
the program's ``repro.place.pass`` spans on a traced window."""

import pytest

from bench import harness, program_trace
from bench.program_trace import RawEvent, reduce
from bench.tracing import WINDOW

HOST = "/host:CPU"
MS = 1e6
METRICS = ("fill_skip_pct.offline", "fill_skip_pct.robust")


def _span(name, start, dur, **stats):
    return RawEvent(HOST, "python", name, start * MS, dur * MS, stats)


def _trace(**pass_stats):
    """One plan of two placement passes; ``pass_stats`` maps a stat to
    the values the two passes carry."""
    passes = [{k: v[i] for k, v in pass_stats.items()} for i in range(2)]
    return [_span(WINDOW, 0, 100), _span("bench.plan", 0, 90),
            _span("repro.place", 20, 60),
            _span("repro.place.pass", 20, 25, steps=10, wave_s=0.002,
                  **passes[0]),
            _span("repro.place.pass", 50, 25, steps=30, wave_s=0.006,
                  **passes[1])]


def _read(metric, events, monkeypatch):
    monkeypatch.setattr(program_trace, "of", lambda record: reduce(events))
    return harness.metric_reader(metric)(harness.Record(trace=object()))


@pytest.mark.parametrize("metric", METRICS)
@pytest.mark.parametrize("skipped,attempts,pct", [
    ((300, 200), (600, 400), 50.0),  # 500 of 1000 attempts
    ((0, 0), (600, 400), 0.0),        # every attempt fitted
    ((5, 0), (5, 0), 100.0),          # one pass tried nothing
])
def test_reads_the_skipped_share_of_the_pass_spans(metric, skipped,
                                                   attempts, pct,
                                                   monkeypatch):
    events = _trace(fill_skipped=skipped, fill_attempts=attempts)
    assert _read(metric, events, monkeypatch) == pytest.approx(pct)


@pytest.mark.parametrize("metric", METRICS)
def test_a_program_without_the_counts_reads_nothing(metric, monkeypatch):
    assert _read(metric, _trace(), monkeypatch) is None


@pytest.mark.parametrize("metric", METRICS)
def test_no_cross_fill_reads_nothing(metric, monkeypatch):
    events = _trace(fill_skipped=(0, 0), fill_attempts=(0, 0))
    assert _read(metric, events, monkeypatch) is None


@pytest.mark.parametrize("metric", METRICS)
def test_no_trace_reads_nothing(metric):
    assert harness.metric_reader(metric)(harness.Record()) is None


def test_reads_a_recorded_trace(tmp_path, monkeypatch):
    """A plan traced on the CPU reads the share its timings hold."""
    from repro.core import FleetEngine, SolverConfig
    from repro.workload import SyntheticSpec, synthetic_batch

    from bench.tracing import Tracer

    monkeypatch.setattr(harness, "TRACE_DIR", tmp_path)
    problems = synthetic_batch([SyntheticSpec(n=48, m=3, D=2, T=6, seed=s)
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
    assert 0 < tel["fill_skipped"] <= tel["fill_attempts"]
    for metric in METRICS:
        assert harness.metric_reader(metric)(rec) == pytest.approx(
            100.0 * tel["fill_skipped"] / tel["fill_attempts"])
