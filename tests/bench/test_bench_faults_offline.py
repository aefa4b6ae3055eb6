"""Offline: a run whose timed path is broken underneath comes out not
correct, once for each fault the cell can have (it runs on one chip, so
there is no exchange between chips to leave out)."""

import dataclasses

import bench_cells as bc
import repro.core.engine as engine_mod
from repro.core import FleetEngine


def test_a_solve_that_leaves_its_state_unchanged(monkeypatch):
    solve = engine_mod.solve_lp_many

    def one_step(*args, **kwargs):  # one PDHG step from the start point
        return solve(*args, **dict(kwargs, iters=1))

    monkeypatch.setattr(engine_mod, "solve_lp_many", one_step)
    out = bc.run(bc.offline_cell(seed=41, seconds=0.2))
    assert not out["correct"]
    assert "lb_below_opt" in bc.failed_checks(out)


def test_half_the_grid_left_out(monkeypatch):
    solve = FleetEngine._solve_bucket

    def half(self, bucket, init=None):
        results, stats = solve(self, bucket, init=init)
        k = (len(results) + 1) // 2
        return results[:k] + results[:k][: len(results) - k], stats

    monkeypatch.setattr(FleetEngine, "_solve_bucket", half)
    out = bc.run(bc.offline_cell(seed=42, seconds=0.2))
    assert not out["correct"]


def test_a_bound_altered_where_it_is_produced(monkeypatch):
    solve = FleetEngine._solve_bucket

    def altered(self, bucket, init=None):
        results, stats = solve(self, bucket, init=init)
        results = [dataclasses.replace(r, lower_bound=r.lower_bound * 1.02)
                   for r in results]
        return results, stats

    monkeypatch.setattr(FleetEngine, "_solve_bucket", altered)
    out = bc.run(bc.offline_cell(seed=43, seconds=0.2))
    assert not out["correct"]
    assert "lb_above_opt" in bc.failed_checks(out)


def test_a_price_altered_where_it_is_produced(monkeypatch):
    evaluate = FleetEngine.evaluate

    def cheaper(self, problems):
        result = evaluate(self, problems)
        for entry in result.entries:
            entry["costs"] = {a: c * 0.99 for a, c in entry["costs"].items()}
        return result

    monkeypatch.setattr(FleetEngine, "evaluate", cheaper)
    out = bc.run(bc.offline_cell(seed=44, seconds=0.2))
    assert not out["correct"]
    assert bc.failed_checks(out) == ["cost_gap"]


def test_a_task_left_off_its_node(monkeypatch):
    def unplace(problem, solution, *args, **kwargs):
        solution.assign[0] = len(solution.node_type)  # no such node

    monkeypatch.setattr(engine_mod, "verify", unplace)
    out = bc.run(bc.offline_cell(seed=45, seconds=0.2))
    assert not out["correct"]
    assert "unplaced" in bc.failed_checks(out)


def test_the_verified_plans_are_recorded_and_the_program_restored():
    from bench.runners.offline import VerifiedPlans

    verify = engine_mod.verify
    cell = bc.offline_cell(seed=46, tasks=120)
    spy = VerifiedPlans()
    try:
        from bench.runners.offline import draw_days
        from repro.core import NodeTypes, Problem, SolverConfig

        d = draw_days(cell.config, 1, 46)[0]
        problems = [Problem(dem=d["dem"], start=d["start"], end=d["end"],
                            node_types=NodeTypes(cap=d["cap"], cost=c),
                            T=d["T"])
                    for c in (d["cap"].sum(axis=1), d["cap"][:, 0] + 1.0)]
        result = FleetEngine(solver=SolverConfig(tol=5e-3, iters=4000),
                             algos=("lp-map-f",)).evaluate(problems)
        plans = spy.take()
    finally:
        spy.close()
    assert engine_mod.verify is verify
    assert len(plans) == 2 and spy.take() == []
    for entry, plan, p in zip(result.entries, plans, problems):
        assert entry["costs"]["lp-map-f"] == plan.cost(p)
