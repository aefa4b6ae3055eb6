"""Program spans, the lockstep step counter and the LP's named scopes:
what puts ``FleetEngine.evaluate`` on the profiler's timeline."""

import contextlib
import re
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import FleetEngine, NodeTypes, Problem, SolverConfig, \
    place_many
from repro.core import batch as batch_mod
from repro.core.spans import span
from repro.workload import SyntheticSpec, synthetic_batch


def _problem(n, m):
    """n two-slot tasks on m node types that each hold any task."""
    rng = np.random.default_rng(n * 10 + m)
    return Problem(dem=rng.uniform(0.1, 0.4, (n, 2)),
                   start=np.arange(n) % 3, end=np.arange(n) % 3 + 1,
                   node_types=NodeTypes(cap=np.ones((m, 2)),
                                        cost=np.arange(1.0, m + 1)), T=4)


def _host_spans(directory):
    """(name, start_ns, end_ns, stats) of every host span of the newest
    trace under ``directory``."""
    from jax.profiler import ProfileData

    path = max(directory.rglob("*.xplane.pb"), key=lambda p: p.stat().st_mtime)
    return [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns,
             dict(ev.stats))
            for plane in ProfileData.from_file(str(path)).planes
            if plane.name.startswith("/host")
            for line in plane.lines for ev in line.events
            if ev.name.startswith(("repro.", "bench."))]


def _inside(inner, outer):
    return outer[1] <= inner[1] and inner[2] <= outer[2]


class TestSpan:
    def test_fills_timings_and_accumulates(self):
        timings = {}
        with span("a", timings, "a_s"):
            time.sleep(0.01)
        first = timings["a_s"]
        with span("a", timings, "a_s"):
            pass
        assert first >= 0.01 and timings["a_s"] >= first
        with span("b"):  # no dict: nothing recorded
            pass
        assert set(timings) == {"a_s"}

    def test_nests(self):
        timings = {}
        with span("outer", timings, "outer_s"):
            with span("inner", timings, "inner_s"):
                time.sleep(0.005)
        assert 0.005 <= timings["inner_s"] <= timings["outer_s"]

    def test_costs_a_few_microseconds_without_a_profiler(self):
        # ~2.5 us on one idle CPU core; the bound leaves room for a
        # loaded host and still refuses per-call work such as encoding
        # metadata or starting the profiler
        timings = {}
        best = float("inf")
        for _ in range(5):
            t0 = time.perf_counter()
            for _ in range(2000):
                with span("cost", timings, "s"):
                    pass
            best = min(best, (time.perf_counter() - t0) / 2000)
        assert best < 10e-6


class TestEvaluateOnTheTimeline:
    def _evaluate(self, directory, n):
        problems = synthetic_batch([SyntheticSpec(n=n, m=3, D=2, T=6,
                                                  seed=s) for s in (0, 1)])
        engine = FleetEngine(solver=SolverConfig(tol=5e-3, iters=400),
                             algos=("lp-map-f",))
        engine.evaluate(problems)  # compile outside the trace
        with jax.profiler.trace(str(directory)):
            with jax.profiler.TraceAnnotation("bench.plan"):
                result = engine.evaluate(problems)
        return result, _host_spans(directory)

    def test_spans_nest_inside_the_callers_span(self, tmp_path):
        result, spans = self._evaluate(tmp_path, 12)
        by = {}
        for s in spans:
            by.setdefault(s[0], []).append(s)
        (plan,), (ev,) = by["bench.plan"], by["repro.evaluate"]
        (place,) = by["repro.place"]
        assert _inside(ev, plan)
        for name in ("repro.pack", "repro.lp", "repro.place"):
            assert all(_inside(s, ev) for s in by[name])
        # one pass per fit policy; one verify per kept plan
        assert len(by["repro.place.pass"]) == 2
        assert len(by["repro.verify"]) == 2
        for name in ("repro.place.pass", "repro.verify"):
            assert all(_inside(s, place) for s in by[name])
        t = result.timings
        assert t["verify_s"] > 0
        assert t["verify_s"] + t["pack_s"] + t["lp_s"] <= t["total_s"]
        steps = sum(s[3]["steps"] for s in by["repro.place.pass"])
        wave_s = sum(s[3]["wave_s"] for s in by["repro.place.pass"])
        assert steps == t["placement"]["steps"] > 0
        assert wave_s == pytest.approx(t["placement"]["wave_s_total"])
        for key in ("window_slots", "slots"):
            total = sum(s[3][key] for s in by["repro.place.pass"])
            assert total == t["placement"][key] > 0
        assert t["placement"]["window_slots"] <= t["placement"]["slots"]

    def test_passes_carry_the_cross_fill_counts(self, tmp_path):
        result, spans = self._evaluate(tmp_path, 48)
        passes = [s for s in spans if s[0] == "repro.place.pass"]
        t = result.timings["placement"]
        for key in ("fill_attempts", "fill_skipped"):
            assert all(key in s[3] for s in passes)
            assert sum(s[3][key] for s in passes) == t[key]
        assert 0 < t["fill_skipped"] <= t["fill_attempts"]

    def test_span_count_does_not_grow_with_n(self, tmp_path):
        _, small = self._evaluate(tmp_path / "small", 12)
        _, large = self._evaluate(tmp_path / "large", 48)
        assert sorted(s[0] for s in small) == sorted(s[0] for s in large)


class TestRobustPlanOnTheTimeline:
    def test_spans_nest_inside_the_robust_span(self, tmp_path):
        from repro.stochastic import (StochasticConfig, gct_forecast,
                                      plan_stochastic)

        fc = gct_forecast(n=40, m=4, seed=3, burst_prob=0.1)
        config = StochasticConfig(scenarios=6, quantiles=3)
        plan_stochastic(fc, config)  # compile outside the trace
        with jax.profiler.trace(str(tmp_path)):
            res = plan_stochastic(fc, config)
        by = {}
        for s in _host_spans(tmp_path):
            by.setdefault(s[0], []).append(s)
        (robust,), (place,) = by["repro.robust"], by["repro.place"]
        for name in ("repro.fanout", "repro.lp", "repro.place",
                     "repro.select"):
            (inner,) = by[name]
            assert _inside(inner, robust)
        # one pass per fit policy; one verify per scenario's kept plan
        assert len(by["repro.place.pass"]) == 2
        assert len(by["repro.verify"]) == config.scenarios
        for name in ("repro.place.pass", "repro.verify"):
            assert all(_inside(s, place) for s in by[name])
        (select,) = by["repro.select"]
        assert select[3]["scenarios"] == config.scenarios
        assert select[3]["candidates"] >= 1
        t = res.timings
        for key in ("total_s", "fanout_s", "lp_s", "place_s", "verify_s",
                    "select_s"):
            assert t[key] > 0, key
        assert (t["fanout_s"] + t["lp_s"] + t["place_s"] + t["select_s"]
                <= t["total_s"])
        assert t["verify_s"] <= t["place_s"]
        passes = by["repro.place.pass"]
        assert t["placement"]["calls"] == len(passes)
        for key in ("steps", "window_slots", "slots"):
            assert sum(s[3][key] for s in passes) == t["placement"][key] > 0
        assert sum(s[3]["wave_s"] for s in passes) == pytest.approx(
            t["placement"]["wave_s_total"])

    def test_robust_passes_carry_the_cross_fill_counts(self, tmp_path):
        from repro.stochastic import (StochasticConfig, gct_forecast,
                                      plan_stochastic)

        fc = gct_forecast(n=40, m=4, seed=3, burst_prob=0.1)
        config = StochasticConfig(scenarios=6, quantiles=3)
        plan_stochastic(fc, config)  # compile outside the trace
        with jax.profiler.trace(str(tmp_path)):
            res = plan_stochastic(fc, config)
        passes = [s for s in _host_spans(tmp_path)
                  if s[0] == "repro.place.pass"]
        t = res.timings["placement"]
        for key in ("fill_attempts", "fill_skipped"):
            assert sum(s[3][key] for s in passes) == t[key]
        assert 0 < t["fill_skipped"] <= t["fill_attempts"]


class TestStepCounter:
    def test_steps_are_the_lockstep_iterations(self):
        # instance 0: three tasks on type 0; instance 1: two on each
        # type.  Wave 0 steps max(3, 2) = 3 times, wave 1 twice.
        problems = [_problem(3, 2), _problem(4, 2)]
        maps = [np.array([0, 0, 0]), np.array([0, 0, 1, 1])]
        tel = {}
        place_many(problems, maps, fit="first", telemetry=tel)
        assert tel["steps"] == 5

    def test_timings_count_every_pass(self):
        # one node type: each fit pass steps once per task of the
        # longer instance, and cross-fill has nothing to try
        problems = [_problem(3, 1), _problem(5, 1)]
        engine = FleetEngine(solver=SolverConfig(tol=5e-3, iters=400),
                             algos=("lp-map", "lp-map-f"))
        plain = engine.evaluate(problems).timings["placement"]
        assert plain["calls"] == 4 and plain["steps"] == 4 * 5

    def test_the_profiler_changes_no_count(self, tmp_path):
        problems = synthetic_batch([SyntheticSpec(n=20, m=3, D=2, T=6,
                                                  seed=s) for s in (0, 1)])
        engine = FleetEngine(solver=SolverConfig(tol=5e-3, iters=400),
                             algos=("lp-map-f",))
        off = engine.evaluate(problems).timings["placement"]["steps"]
        with jax.profiler.trace(str(tmp_path)):
            on = engine.evaluate(problems).timings["placement"]["steps"]
        assert on == off > 0


def _ops_only(text):
    """A compiled module's text without op metadata and the source
    tables it points into: what the compiled ops are."""
    lines = re.sub(r",? metadata=\{[^}]*\}", "", text).splitlines()
    first = next(i for i, line in enumerate(lines)
                 if line.startswith(("%", "ENTRY")))
    return "\n".join(lines[:1] + lines[first:])


def test_named_scopes_change_no_compiled_op(monkeypatch):
    problems = synthetic_batch([SyntheticSpec(n=12, m=3, D=2, T=6, seed=s)
                                for s in (0, 1)])
    b = batch_mod.pack_problems(problems)

    def compiled():
        jax.clear_caches()
        with jax.enable_x64(True):
            args = (jnp.asarray(b.weights(), jnp.float32),
                    jnp.asarray(b.start), jnp.asarray(b.end),
                    jnp.asarray(b.feas), jnp.asarray(b.cost, jnp.float32),
                    jnp.float32(0.9), jnp.float32(5e-3))
            return batch_mod._pdhg_run_many_tol.lower(
                *args, max_iters=200, check_every=16, Tp=b.Tp,
                operator="dense", scaling="ruiz",
                precision="mixed").compile().as_text()

    scoped = compiled()
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    plain = compiled()
    for scope in ("ruiz", "operators", "power", "pdhg", "certificate",
                  "polish", "unscale"):
        assert f"/{scope}/" in scoped and f"/{scope}/" not in plain
    assert _ops_only(scoped) == _ops_only(plain)
