"""Robust day-ahead sizing: robust plans back to back.

A plan is the program's ``repro.stochastic.plan_stochastic`` of one
day: fan the day's tasks out into the configuration's K demand
scenarios, solve all K mapping LPs in one batched dispatch, place every
scenario by the placement protocol (its cheapest plan kept and
verified), and select one fleet by ``E[cost] + lambda CVaR``.  Set-up
draws the cell's ``grids`` days and plans the first, which compiles;
the window plans the others in turn, a different day each time, until
``seconds`` have passed.

Every seed plans the same work in the same order: the days are drawn
with the configuration's salt and fanned out in the salt's task order
with the salt as the fan-out seed, so their HiGHS optima are stored with
the benchmark (``bench.optima_robust``).  A window holds only two robust
plans, so an input that moved with the seed, even the order of the
machine shapes, would move ``plan_s`` by the spread of placement steps
between such inputs (up to 30%), far beyond the noise the cell has to
stay under; runs differ by host noise alone.

``correct`` compares every scenario of every plan of the window with
the references: the plan the program verified, against the capacities
of the nodes it buys under that scenario's demands as stated
(``bench.robust_reference.scenario_demands``); the price it reports,
against that plan's catalogue price; its certified LP bound, against
the HiGHS optimum (not above it, and close below it); and, per plan,
the robust fleet against the reference's selection from the verified
plans' node counts.
"""

from __future__ import annotations

import dataclasses
import hashlib
import json
import time

import jax
import numpy as np

from bench import gct, reference, robust_reference
from bench.harness import Check, Record
from bench.lp_work import lp_work
from bench.runners.offline import (COMPILE_EVENT, COST_GAP_LIMIT,
                                   LB_EXCESS_LIMIT, LB_GAP_LIMIT,
                                   OVERLOAD_LIMIT, VerifiedPlans,
                                   plan_seconds)

# the robust fleet's objective, recomputed from what the program reports
# of it, against the reference's least objective; both sum the same
# float64 terms, so only the order of the additions differs
SELECTION_LIMIT = 1e-9


def draw_days(cfg: dict, grids: int) -> list[dict]:
    """The cell's ``grids`` days, each of ``cfg["tasks"]`` tasks with
    distinct starts, drawn with the configuration's salt."""
    rng = np.random.default_rng(cfg["seed_salt"])
    return [gct.instance(cfg["tasks"], cfg["m"], rng, distinct_starts=True)
            for _ in range(grids)]


def stated_scenarios(cfg: dict, day: dict) -> np.ndarray:
    """(K, n, D) demands of a day's scenarios as the configuration states
    them."""
    return robust_reference.scenario_demands(
        day["dem"], day["start"], day["T"], day["cap"], cfg["forecast"],
        cfg["stochastic"]["scenarios"], cfg["seed_salt"])


def scenario_key(cfg: dict, day: dict, cost, k: int) -> str:
    """The key of scenario ``k`` of a day in the stored optima: the
    fingerprint of the day's own inputs, the fan-out's settings and k.
    The scenario's demands pass through sin, exp and pow, whose last bit
    can differ between CPUs, so their own fingerprint cannot find an
    optimum solved on another machine; a last-bit change moves the
    optimum by far less than the bound checks can see."""
    h = hashlib.sha256(reference.fingerprint(
        day["dem"], day["start"], day["end"], day["cap"], cost).encode())
    h.update(json.dumps([cfg["forecast"], cfg["seed_salt"], k],
                        sort_keys=True).encode())
    return h.hexdigest()[:24]


def stored_scenario_optima(cfg: dict) -> dict:
    path = reference.OPTIMA / f"{cfg['name']}.json"
    return json.loads(path.read_text()).get("scenarios", {}) \
        if path.is_file() else {}


def program_has_scenario_bounds() -> bool:
    from repro.stochastic import StochasticResult

    return "scenario_lbs" in {f.name for f in
                              dataclasses.fields(StochasticResult)}


class Runner:
    def __init__(self, cell):
        self.cell = cell
        self.cfg = cell.config
        self.record = Record()
        self.plans: list[dict] = []
        self.planned: list[int] = []
        self.attempted = self.failed = 0

    def setup(self) -> None:
        if not program_has_scenario_bounds():
            raise SystemExit(
                "bench.run: this program's plan_stochastic returns no "
                "per-scenario LP bounds (StochasticResult.scenario_lbs), "
                "so its robust plans cannot be checked")
        from repro.core import FleetEngine, NodeTypes, Problem, SolverConfig
        from repro.stochastic import DemandForecast, StochasticConfig

        cfg = self.cfg
        self.days = draw_days(cfg, self.cell.traffic["grids"])
        self.costs = [gct.node_cost(day["cap"], cfg["cost_model"],
                                    cfg["gce_e"]) for day in self.days]
        self.forecasts = []
        for day, cost in zip(self.days, self.costs):
            dem = day["dem"]
            if self.cell.demand_cast is not None:
                dem = self.cell.demand_cast(dem)
            base = Problem(dem=dem, start=day["start"], end=day["end"],
                           node_types=NodeTypes(cap=day["cap"], cost=cost),
                           T=day["T"])
            self.forecasts.append(DemandForecast(base=base, **cfg["forecast"]))
        self.config = StochasticConfig(seed=cfg["seed_salt"],
                                       **cfg["stochastic"])
        self.engine = FleetEngine(solver=SolverConfig(**cfg["solver"]),
                                  algos=(self.config.algo,))
        self.verified = VerifiedPlans()
        self._plan(0, Record())  # compiles the LP for the cell's one shape

    def _plan(self, g: int, rec: Record) -> dict:
        """``plan_stochastic`` of day ``g``; its phase times, placement
        counts and LP iterations go to ``rec``."""
        from repro.stochastic import plan_stochastic

        self.verified.take()
        result = plan_stochastic(self.forecasts[g], self.config,
                                 engine=self.engine)
        plans = self.verified.take()
        t = result.timings
        for key in ("lp_s", "place_s", "verify_s"):
            rec.add(key, t[key])
        rec.add("select_s", t["fanout_s"] + t["select_s"])
        rec.add("place_steps", t["placement"].get("steps", 0))
        rec.add("place_wave_s", t["placement"].get("wave_s_total", 0.0))
        iters = np.concatenate([np.asarray(s.iterations).reshape(-1)
                                for s in result.stats])
        for i in iters:
            rec.add("lp_iters", i)
        day = self.days[g]
        n, D = day["dem"].shape
        self.work = lp_work((result.K, n, len(day["cap"]), D,
                             len(np.unique(day["start"]))), iters)
        return {"fleet": np.asarray(result.fleet),
                "fleet_cost": float(result.fleet_cost),
                "overload": np.asarray(result.overload, np.float64),
                "costs": np.asarray(result.scenario_costs, np.float64),
                "lbs": np.asarray(result.scenario_lbs, np.float64),
                "plans": [(p.node_type, p.assign) for p in plans]}

    def window(self, tracer) -> dict:
        seconds = self.cell.seconds
        traced = self.cell.traffic["traced_plans"] if tracer else 0
        rec = self.record
        rec.counts["compiles"] = 0

        def count(event, **kwargs):  # a program compiled or loaded
            if event == COMPILE_EVENT:
                rec.counts["compiles"] += 1

        jax.monitoring.register_event_listener(count)
        t0 = end = time.perf_counter()
        while not self.plans or end - t0 < seconds:
            in_trace = len(self.plans) < traced
            if in_trace and not tracer.active:
                tracer.start()
            elif not in_trace and tracer is not None and tracer.active:
                tracer.stop()
            # day 0 compiled in set-up; the window never repeats a day
            g = 1 + len(self.plans) % (len(self.days) - 1)
            with rec.span("plan", "plan_s"):
                answer = self._plan(g, rec)
            if in_trace:  # the LP work that the traced programs did
                rec.add("lp_bytes_traced", self.work[0])
                rec.add("lp_flops_traced", self.work[1])
            self.plans.append(answer)
            self.planned.append(g)
            end = time.perf_counter()
        jax.monitoring.unregister_event_listener(count)
        self.verified.close()
        return {"plan_s": plan_seconds(t0, end, len(self.plans), 1)}

    def check(self) -> list[Check]:
        cfg = self.cfg
        overload, cost_gap, unplaced = 0.0, 0.0, 0
        below = above = selection = -np.inf
        bad = set()
        scenarios: dict[int, np.ndarray] = {}
        optima = stored_scenario_optima(cfg)
        for i, (g, answer) in enumerate(zip(self.planned, self.plans)):
            day, cost = self.days[g], np.asarray(self.costs[g])
            m = len(cost)
            if g not in scenarios:
                scenarios[g] = stated_scenarios(cfg, day)
            counts = []
            for s, dem in enumerate(scenarios[g]):
                self.attempted += 1
                if s >= len(answer["plans"]) or s >= len(answer["costs"]):
                    unplaced += len(dem)
                    bad.add((i, s))
                    continue
                node_type, assign = answer["plans"][s]
                over, lost = reference.plan_faults(
                    dem, day["start"], day["end"], day["cap"], node_type,
                    assign, m)
                price = float(cost[np.clip(node_type, 0, m - 1)].sum())
                gap = abs(answer["costs"][s] - price) / price
                overload = max(overload, over)
                cost_gap = max(cost_gap, gap)
                unplaced += lost
                if over > OVERLOAD_LIMIT or lost or gap > COST_GAP_LIMIT:
                    bad.add((i, s))
                counts.append(np.bincount(np.clip(node_type, 0, m - 1),
                                          minlength=m))
                opt = optima.get(scenario_key(cfg, day, cost, s))
                if opt is None:
                    opt = reference.optimum(dem, day["start"], day["end"],
                                            day["cap"], cost)
                lb = answer["lbs"][s] if s < len(answer["lbs"]) \
                    and np.isfinite(answer["lbs"][s]) else -np.inf
                gap_b, excess_b = (opt - lb) / abs(opt), (lb - opt) / abs(opt)
                below, above = max(below, gap_b), max(above, excess_b)
                if gap_b > LB_GAP_LIMIT or excess_b > LB_EXCESS_LIMIT:
                    bad.add((i, s))
            self.attempted += 1
            miss = self._selection(answer, counts, cost)
            selection = max(selection, miss)
            if miss > SELECTION_LIMIT:
                bad.add((i, "selection"))
        self.failed = len(bad)
        return [Check("unplaced", float(unplaced), 0.0),
                Check("overload", overload, OVERLOAD_LIMIT),
                Check("cost_gap", cost_gap, COST_GAP_LIMIT),
                Check("lb_above_opt", above, LB_EXCESS_LIMIT),
                Check("lb_below_opt", below, LB_GAP_LIMIT),
                Check("selection", selection, SELECTION_LIMIT)]

    def _selection(self, answer: dict, counts: list, cost) -> float:
        """0 up to rounding where the program's robust fleet is the
        reference's and the objective it reports is the reference's
        least; inf where a fleet differs or a scenario plan is missing."""
        sel = self.cfg["stochastic"]
        if len(counts) != sel["scenarios"]:
            return np.inf
        fleet, best = robust_reference.select(np.array(counts), cost, sel)
        if not np.array_equal(fleet, answer["fleet"]):
            return np.inf
        reported = robust_reference.objective(
            [answer["fleet_cost"]], answer["overload"][:, None], sel)[0]
        return abs(reported - best) / abs(best)
