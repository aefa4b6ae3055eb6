"""The program's own spans, counts and scopes on a traced window, and the
per-layer metrics that read them."""

import pytest

from bench import harness, program_trace
from bench.program_trace import RawEvent, reduce
from bench.tracing import WINDOW, Event, breakdown, summarize

DEV = "/device:TPU:0"
HOST = "/host:CPU"
MS = 1e6
LP = "jit(_pdhg_run_many_tol)"
NEW = ("verify_ms.offline", "place_steps.offline", "place_step_us.offline",
       "lp_f64_pct.offline")


def _op(name, start, dur, scope_path=None):
    stats = {} if scope_path is None else {"tf_op": f"{LP}/{scope_path}:"}
    return RawEvent(DEV, "XLA Ops", name, start * MS, dur * MS, stats)


def _span(name, start, dur, **stats):
    return RawEvent(HOST, "python", name, start * MS, dur * MS, stats)


def _trace(program=True):
    """A 100 ms window holding two plans (0-40, 50-90 ms).  The LP
    program runs twice (5-15, 55-65 ms): its PDHG loop, then the
    certificate, the polish loop (whose body op nests inside it) and
    the unscale.  Another program runs 20-25 ms; an LP run before the
    window and spans after it are left out.  ``program=False`` is the
    same trace from a program without spans or scopes."""
    ev = [
        _span(WINDOW, 0, 100),
        _span("bench.plan", 0, 40), _span("bench.plan", 50, 40),
        _span("bench.plan", 95, 10),  # runs past the window
        RawEvent(DEV, "XLA Modules", "jit__pdhg_run_many_tol(1)", -10 * MS,
                 8 * MS, {}),
        RawEvent(DEV, "XLA Modules", "jit__pdhg_run_many_tol(1)", 5 * MS,
                 10 * MS, {}),
        RawEvent(DEV, "XLA Modules", "jit_other(2)", 20 * MS, 5 * MS, {}),
        RawEvent(DEV, "XLA Modules", "jit__pdhg_run_many_tol(1)", 55 * MS,
                 10 * MS, {}),
    ]
    scoped = [
        _op("fusion.2", -6, 2, "certificate"),
        _op("while.1", 5, 6, "pdhg/while"),
        _op("fusion.1", 6, 1, "pdhg/while/body/mul"),
        _op("fusion.2", 11, 1, "certificate/dot_general"),
        _op("while.2", 12, 2, "polish/while"),
        _op("fusion.3", 12.5, 1, "polish/while/body/closed_call/mul"),
        _op("copy.1", 14, 1, "unscale/div"),
        _op("fusion.9", 20, 2, "certificate/add"),  # not the LP program
        _op("while.1", 55, 7, "pdhg/while"),
        _op("fusion.2", 62, 1, "certificate/reduce_sum"),
        _op("while.2", 63, 1, "polish/while"),
    ]
    if not program:
        return ev + [e._replace(stats={}) for e in scoped]
    return ev + scoped + [
        _span("repro.evaluate", 1, 38), _span("repro.place", 20, 18),
        _span("repro.place.pass", 20, 5, steps=100, wave_s=0.004),
        _span("repro.place.pass", 25, 5, steps=200, wave_s=0.004),
        _span("repro.verify", 30, 2), _span("repro.verify", 33, 1),
        _span("repro.evaluate", 51, 38),
        _span("repro.place.pass", 70, 5, steps=300, wave_s=0.022),
        _span("repro.verify", 80, 1),
        _span("repro.verify", 110, 5),  # after the window
    ]


def _read(name, monkeypatch, events):
    monkeypatch.setattr(program_trace, "of", lambda record: reduce(events))
    return harness.metric_reader(name)(harness.Record(trace=object()))


def test_reduce_keeps_the_window():
    pt = reduce(_trace())
    assert pt.plans == 2
    assert pt.span_seconds("repro.verify") == pytest.approx(0.004)
    assert pt.span_stat("repro.place.pass", "steps") == 600
    assert pt.span_stat("repro.place.pass", "absent") is None


def test_scope_is_the_first_named_scope_of_the_op_name():
    assert program_trace.scope_of("jit(f)/pdhg/while/body/mul:") == "pdhg"
    assert program_trace.scope_of("jit(_pdhg_run_many_tol)/mul:") == ""


XSPACE = """
planes {
  id: 1 name: "/host:CPU"
  lines {
    id: 1 name: "python" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 0 duration_ps: 100000000000 }
    events { metadata_id: 2 offset_ps: 1000000000 duration_ps: 50000000000 }
    events { metadata_id: 3 offset_ps: 2000000000 duration_ps: 40000000000 }
    events { metadata_id: 4 offset_ps: 3000000000 duration_ps: 20000000000
             stats { metadata_id: 11 int64_value: 42 }
             stats { metadata_id: 12 double_value: 0.0021 } }
    events { metadata_id: 5 offset_ps: 5000000000 duration_ps: 1000000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "bench.traced_window" } }
  event_metadata { key: 2 value { id: 2 name: "bench.plan" } }
  event_metadata { key: 3 value { id: 3 name: "repro.evaluate" } }
  event_metadata { key: 4 value { id: 4 name: "repro.place.pass" } }
  event_metadata { key: 5 value { id: 5 name: "numpy.einsum" } }
  stat_metadata { key: 11 value { id: 11 name: "steps" } }
  stat_metadata { key: 12 value { id: 12 name: "wave_s" } }
}
planes {
  id: 2 name: "/device:TPU:0"
  lines {
    id: 1 name: "XLA Modules" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 30000000000 duration_ps: 10000000000 }
  }
  lines {
    id: 2 name: "XLA Ops" timestamp_ns: 0
    events { metadata_id: 2 offset_ps: 30000000000 duration_ps: 6000000000 }
    events { metadata_id: 3 offset_ps: 36000000000 duration_ps: 4000000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "jit__pdhg_run_many_tol(1)" } }
  event_metadata { key: 2 value { id: 2 name: "%while.1 = f32[2] while()"
    stats { metadata_id: 7 str_value: "jit(_pdhg_run_many_tol)/pdhg/while:" } } }
  event_metadata { key: 3 value { id: 3 name: "%fusion.4 = f32[2] fusion()"
    stats { metadata_id: 7 ref_value: 8 } } }
  stat_metadata { key: 7 value { id: 7 name: "tf_op" } }
  stat_metadata { key: 8 value { id: 8 name: "jit(_pdhg_run_many_tol)/polish/mul:" } }
}
"""


def test_a_serialized_trace_is_read_with_its_metadata(tmp_path):
    """Spans keep their stats, device operations their ``tf_op`` from
    the event metadata (as a string or a reference), and host events of
    no interest are dropped."""
    from jax.profiler import ProfileData

    path = tmp_path / "t.xplane.pb"
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace(XSPACE))
    raw = program_trace.load_raw(path)
    assert "numpy.einsum" not in {e.name for e in raw}
    ops = {e.name: e.stats["tf_op"] for e in raw if e.line == "XLA Ops"}
    assert ops == {"%while.1 = f32[2] while()":
                   "jit(_pdhg_run_many_tol)/pdhg/while:",
                   "%fusion.4 = f32[2] fusion()":
                   "jit(_pdhg_run_many_tol)/polish/mul:"}
    pt = reduce(raw)
    assert pt.plans == 1 and pt.has("repro.evaluate")
    assert pt.span_stat("repro.place.pass", "steps") == 42
    assert pt.span_stat("repro.place.pass", "wave_s") == pytest.approx(0.0021)
    assert pt.scope_pct(program_trace.F64_SCOPES) == pytest.approx(40.0)


@pytest.mark.parametrize("metric,value", [
    ("verify_ms.offline", 2.0),         # (2 + 1 + 1) ms over 2 plans
    ("place_steps.offline", 300.0),     # 600 steps over 2 plans
    ("place_step_us.offline", 50.0),    # 0.030 s over 600 steps
    # certificate 1 + 1 ms, polish 2 + 1 ms (the nested body op once)
    # over the LP program's 20 ms inside the window
    ("lp_f64_pct.offline", 25.0),
])
def test_new_readers_on_a_synthetic_trace(metric, value, monkeypatch):
    assert _read(metric, monkeypatch, _trace()) == pytest.approx(value)


@pytest.mark.parametrize("metric", NEW)
def test_a_program_without_spans_reads_nothing(metric, monkeypatch):
    assert _read(metric, monkeypatch, _trace(program=False)) is None


def test_every_scope_share_is_a_union():
    pt = reduce(_trace())
    shares = {s: pt.scope_pct((s,)) for s in program_trace.LP_SCOPES}
    assert shares["pdhg"] == pytest.approx(65.0)  # the loops, bodies once
    assert shares["unscale"] == pytest.approx(5.0)
    assert shares["ruiz"] == 0.0
    assert sum(shares.values()) <= 100.0


@pytest.mark.parametrize("metric", [
    "device_idle_pct.offline", "lp_roofline.offline", "compiles.offline",
    "lp_ms.offline"])
def test_existing_readers_ignore_program_spans(metric):
    def record(events):
        rec = harness.Record(
            trace=summarize([Event(*e[:5]) for e in events]),
            peaks=harness.peaks("TPU v5 lite"))
        rec.counts["compiles"] = 0
        rec.add("lp_bytes_traced", 2e9)
        rec.add("lp_flops_traced", 1e9)
        rec.add("lp_s", 0.2)
        return rec

    read = harness.metric_reader(metric)
    with_spans, without = record(_trace()), record(_trace(program=False))
    assert read(with_spans) == read(without)
    assert read(with_spans) is not None
    assert breakdown(with_spans.trace) == breakdown(without.trace)


def test_readers_find_a_recorded_trace(tmp_path, monkeypatch):
    """A plan traced on the CPU: the readers find the trace under the
    harness's directory and read the program's spans and counts; the
    CPU trace has no device plane, so the LP's share reads nothing."""
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
    t = result.timings
    read = {m: harness.metric_reader(m)(rec) for m in NEW}
    assert 0 < read["verify_ms.offline"] <= 1e3 * t["place_s"]
    assert read["place_steps.offline"] == t["placement"]["steps"]
    assert read["place_step_us.offline"] == pytest.approx(
        1e6 * t["placement"]["wave_s_total"] / t["placement"]["steps"])
    assert read["lp_f64_pct.offline"] is None
