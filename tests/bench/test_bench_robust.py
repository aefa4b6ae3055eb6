"""Robust day-ahead sizing: the cell's runner, its references, its
readers, and the faults that ``correct`` must catch.  CPU-sized copies
of the cell: 40 tasks, all 13 machine shapes, 6 scenarios."""

import dataclasses
import time

import numpy as np
import pytest

import bench_cells as bc
import repro.core.engine as engine_mod
import repro.stochastic as stochastic_mod
from bench import gct, harness, reference, robust_reference
from bench.runners import robust
from repro.core import FleetEngine

CELL = "robust.day2000.k16"


def robust_cell(seed=2**33 + 11, seconds=0.2, tasks=40, scenarios=6,
                trace=False, demand_cast=None):
    entry = harness.find(harness.benchmark()["workloads"], CELL, "workload")
    cfg = harness.config(entry["config"])
    cfg["tasks"] = tasks
    cfg["stochastic"] = dict(cfg["stochastic"], scenarios=scenarios)
    return harness.Cell(CELL, cfg, harness.traffic(entry["traffic"]), seed,
                        seconds, trace, demand_cast)


def test_sound_run_is_correct():
    out = bc.run(robust_cell())
    assert out["correct"], bc.failed_checks(out)
    assert [c.name for c in out["checks"]] == [
        "unplaced", "overload", "cost_gap", "lb_above_opt", "lb_below_opt",
        "selection"]
    plans = len(out["record"].samples["plan_s"])
    assert out["attempted"] == plans * (6 + 1) and out["failed"] == 0
    assert out["metrics"]["plan_s"]["value"] > 0


def test_bf16_control_is_not_correct():
    out = bc.run(robust_cell(tasks=300, demand_cast=bc.truncate_bf16))
    assert not out["correct"]
    assert "overload" in bc.failed_checks(out)


def test_an_overloaded_scenario_plan(monkeypatch):
    verify = engine_mod.verify

    def crowd(problem, solution, *args, **kwargs):
        verify(problem, solution, *args, **kwargs)
        solution.assign[:] = 0  # every task on the first node

    monkeypatch.setattr(engine_mod, "verify", crowd)
    out = bc.run(robust_cell())
    assert not out["correct"]
    assert "overload" in bc.failed_checks(out)


def test_a_shifted_lp_bound(monkeypatch):
    solve = FleetEngine._solve_bucket

    def shifted(self, bucket, init=None):
        results, stats = solve(self, bucket, init=init)
        return [dataclasses.replace(r, lower_bound=r.lower_bound * 1.02)
                for r in results], stats

    monkeypatch.setattr(FleetEngine, "_solve_bucket", shifted)
    out = bc.run(robust_cell())
    assert not out["correct"]
    assert bc.failed_checks(out) == ["lb_above_opt"]


def test_a_swapped_robust_fleet(monkeypatch):
    plan = stochastic_mod.plan_stochastic

    def swapped(*args, **kwargs):
        res = plan(*args, **kwargs)
        return dataclasses.replace(res, fleet=res.fleet + 1)

    monkeypatch.setattr(stochastic_mod, "plan_stochastic", swapped)
    out = bc.run(robust_cell())
    assert not out["correct"]
    assert bc.failed_checks(out) == ["selection"]


def test_fails_fast_on_a_program_without_scenario_bounds(monkeypatch):
    @dataclasses.dataclass
    class OlderResult:
        fleet: np.ndarray

    monkeypatch.setattr(stochastic_mod, "StochasticResult", OlderResult)
    t0 = time.perf_counter()
    with pytest.raises(SystemExit, match="scenario_lbs"):
        robust.Runner(robust_cell()).setup()
    assert time.perf_counter() - t0 < 5.0


def test_the_fan_out_is_the_programs():
    from repro.core import NodeTypes, Problem
    from repro.stochastic import DemandForecast, fan_out

    cfg = harness.config("gct2019-robust")
    days = robust.draw_days(dict(cfg, tasks=300), 2)
    for day in days:
        base = Problem(dem=day["dem"], start=day["start"], end=day["end"],
                       node_types=NodeTypes(cap=day["cap"],
                                            cost=day["cap"].sum(axis=1)),
                       T=day["T"])
        theirs = fan_out(DemandForecast(base=base, **cfg["forecast"]), 16,
                         cfg["seed_salt"])
        ours = robust.stated_scenarios(cfg, day)
        assert ours.shape == (16, 300, 2)
        for k, p in enumerate(theirs.problems):
            np.testing.assert_array_equal(ours[k], p.dem)


def test_every_seed_plans_the_same_work():
    runners = [robust.Runner(robust_cell(seed=seed)) for seed in (2**40 + 3, 9)]
    for runner in runners:
        runner.setup()
        runner.verified.close()
    for x, y in zip(*(r.forecasts for r in runners)):
        for key in ("dem", "start", "end", "T"):
            np.testing.assert_array_equal(getattr(x.base, key),
                                          getattr(y.base, key))
        np.testing.assert_array_equal(x.base.node_types.cap,
                                      y.base.node_types.cap)
    assert len({tuple(np.sort(d["start"])) for d in runners[0].days}) == 8


def _cell_lps():
    """(LP inputs, scenario key) of every scenario of the cell's days."""
    entry = harness.find(harness.benchmark()["workloads"], CELL, "workload")
    cfg = harness.config(entry["config"])
    out = []
    for d in robust.draw_days(cfg, harness.traffic(entry["traffic"])["grids"]):
        cost = gct.node_cost(d["cap"], cfg["cost_model"], cfg["gce_e"])
        out += [((dem, d["start"], d["end"], d["cap"], cost),
                 robust.scenario_key(cfg, d, cost, k))
                for k, dem in enumerate(robust.stated_scenarios(cfg, d))]
    return cfg, out


def test_every_scenario_lp_of_the_cell_has_a_stored_optimum():
    cfg, lps = _cell_lps()
    by_key = robust.stored_scenario_optima(cfg)
    table = reference.stored_optima()
    assert len(lps) == len(by_key) == 8 * 16
    assert all(key in by_key for _, key in lps)
    # the same optima under the fingerprints of the LPs' inputs
    assert sorted(by_key.values()) == sorted(
        table[fp] for fp in table if table[fp] in by_key.values())


def test_a_stored_scenario_optimum_is_the_references():
    cfg, lps = _cell_lps()
    lp, key = lps[21]
    stored = robust.stored_scenario_optima(cfg)[key]
    assert reference.lp_optimum(*lp) == pytest.approx(stored, rel=1e-7)


# a hand-worked case: four scenarios, two node types priced 1 and 2
PLANS = np.array([[1, 0], [2, 1], [0, 2], [3, 0]])
COST = np.array([1.0, 2.0])
SELECT = {"cvar_alpha": 0.75, "cvar_lambda": 1.0, "overload_premium": 3.0,
          "quantiles": 3}


def test_reference_cvar_by_hand():
    # the worst ceil((1 - alpha) * 4) values: one at 0.75, two at 0.5
    x = np.array([0.0, 4.0, 1.0, 2.0])
    assert robust_reference.cvar(x, 0.75) == 4.0
    assert robust_reference.cvar(x, 0.5) == 3.0
    assert robust_reference.cvar(x, 0.0) == 1.75


def test_reference_menu_and_argmin_by_hand():
    fleets = robust_reference.menu(PLANS, quantiles=3)
    # the scenarios' pairwise maxes (each with itself too), and the
    # chain at q = 0, 0.5, 1, sorted ranks 0, 2, 3: (0, 0), (2, 1), (3, 2)
    assert fleets.tolist() == [
        [0, 0], [1, 0], [0, 2], [1, 2], [2, 1], [3, 0], [2, 2], [3, 1],
        [3, 2]]
    ov = robust_reference.overload(PLANS, fleets, COST)
    # fleet (2, 1): short by one node of type 0 in scenario 3, by one
    # of type 1 in scenario 2
    assert ov[:, fleets.tolist().index([2, 1])].tolist() == [0, 0, 2, 1]
    # E[cost] + CVaR_0.75: (2, 1) costs 4 + 3 * 0.75 + 2 = 8.25;
    # (3, 2) costs 7 with no overload; (3, 1) costs 5 + 3 * 0.5 + 2 = 8.5
    fleet, best = robust_reference.select(PLANS, COST, SELECT)
    assert fleet.tolist() == [3, 2] and best == 7.0
    fleet, best = robust_reference.select(PLANS, COST,
                                          dict(SELECT, overload_premium=1.0,
                                               cvar_lambda=0.0))
    # premium 1, no tail term: buying nothing costs the mean overload,
    # (1 + 4 + 4 + 3) / 4 = 3, the least; (1, 0) costs 1 + 2.25
    assert fleet.tolist() == [0, 0] and best == 3.0


def test_reference_selection_is_the_programs():
    from repro.stochastic import candidate_fleets, overload_costs
    from repro.stochastic.select import _select

    rng = np.random.default_rng(5)
    for _ in range(50):
        K, m = int(rng.integers(2, 17)), int(rng.integers(1, 6))
        plans, cost = rng.integers(0, 5, (K, m)), rng.uniform(0.5, 2.0, m)
        sel = dict(SELECT, cvar_alpha=float(rng.choice([0.5, 0.9])),
                   quantiles=int(rng.integers(2, 10)))
        fleets = candidate_fleets(plans, quantiles=sel["quantiles"])
        np.testing.assert_array_equal(
            robust_reference.menu(plans, sel["quantiles"]), fleets)
        j = _select(fleets, overload_costs(plans, fleets, cost), cost,
                    alpha=sel["cvar_alpha"], lam=sel["cvar_lambda"],
                    premium=sel["overload_premium"], recfg_weight=0.0,
                    current=None)
        fleet, _ = robust_reference.select(plans, cost, sel)
        np.testing.assert_array_equal(fleet, fleets[j])


def _record():
    rec = harness.Record()
    for lp_s, place_s, verify_s, select_s, steps, wave_s in (
            (0.5, 10.0, 0.2, 0.1, 4000, 8.0), (0.7, 12.0, 0.4, 0.3, 6000, 10.0)):
        rec.add("lp_s", lp_s)
        rec.add("place_s", place_s)
        rec.add("verify_s", verify_s)
        rec.add("select_s", select_s)
        rec.add("place_steps", steps)
        rec.add("place_wave_s", wave_s)
    for i in (100, 120, 140):
        rec.add("lp_iters", i)
    rec.counts["compiles"] = 0
    return rec


@pytest.mark.parametrize("metric,want", [
    ("lp_ms.robust", 600.0), ("lp_iters.robust", 120.0),
    ("place_ms.robust", 11000.0), ("place_steps.robust", 5000.0),
    ("place_step_us.robust", 1800.0), ("verify_ms.robust", 300.0),
    ("select_ms.robust", 200.0), ("compiles.robust", 0)])
def test_each_record_reader_on_a_synthetic_record(metric, want):
    read = harness.metric_reader(metric)
    assert read(_record()) == pytest.approx(want)
    assert read(harness.Record()) is None


def test_trace_readers_on_a_synthetic_trace():
    from bench.lp_work import lp_work
    from bench.tracing import TraceSummary

    rec = _record()
    nbytes, flops = lp_work((16, 2000, 13, 2, 2000), [100] * 16)
    rec.add("lp_bytes_traced", nbytes)
    rec.add("lp_flops_traced", flops)
    rec.peaks = {"hbm_bytes_per_s": 819e9, "flops_per_s": 197e12}
    rec.trace = TraceSummary(
        window_s=20.0, busy_s=1.0, chips=1,
        module_s={"jit__pdhg_run_many_tol": 0.5, "jit_other": 0.5},
        op_s={}, gaps=[], host_spans=[])
    idle = harness.metric_reader("device_idle_pct.robust")(rec)
    roof = harness.metric_reader("lp_roofline.robust")(rec)
    assert idle == pytest.approx(95.0)
    assert roof == pytest.approx(100.0 * nbytes / 819e9 / 0.5)
    rec.trace = None
    assert harness.metric_reader("lp_roofline.robust")(rec) is None
