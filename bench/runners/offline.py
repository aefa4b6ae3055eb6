"""Offline planning: what-if grids planned back to back.

A grid buys a fleet for each of the configuration's cost models: one
day's tasks priced by each, on the same machine shapes.  A plan is the
program's public entry ``FleetEngine.evaluate`` of one grid: pack
(lower, trim, bucket, pad), the batched tolerance-stopped mapping LP,
and the placement protocol (every fit policy, keeping each fleet's
cheapest plan, verified).  Set-up draws the cell's ``grids`` days, all
of one shape, and plans the first, which compiles; the window plans the
others in turn, a different day each time, until ``seconds`` have
passed.

``correct`` compares every fleet of every plan of the window with the
references: the placement that ``evaluate`` verified against the
capacities of the nodes it buys under the demands as stated, the price
it reports against that placement's, and its certified LP bound with
the HiGHS optimum of the same LP (not above it, and close below it).
The optima of the cell's days are stored with the benchmark
(``bench.optima``), since HiGHS takes some 20 s a fleet at n=2000.
"""

from __future__ import annotations

import time

import jax
import numpy as np

from bench import gct, reference
from bench.harness import Check, Record
from bench.lp_work import lp_work


def plan_seconds(start: float, end: float, plans: int, fleets: int) -> float:
    """Seconds per fleet planned: the window, from the start of the first
    plan to the end of the last plan that started inside it, over the
    fleets its plans bought."""
    return (end - start) / (plans * fleets)


def draw_days(cfg: dict, grids: int, seed: int) -> list[dict]:
    """The cell's ``grids`` days: the same days for every seed, drawn
    with the configuration's salt, each of ``cfg["tasks"]`` tasks with
    distinct starts (so every grid packs to one LP shape).  The seed
    orders each day's tasks and machine shapes, so every seed plans the
    same work, in another order."""
    fixed = np.random.default_rng(cfg["seed_salt"])
    rng = np.random.default_rng(np.random.SeedSequence(
        [abs(seed), cfg["seed_salt"]]))
    days = []
    for _ in range(grids):
        day = gct.instance(cfg["tasks"], cfg["m"], fixed, distinct_starts=True)
        rows = rng.permutation(len(day["dem"]))
        types = rng.permutation(len(day["cap"]))
        days.append(dict(day, dem=day["dem"][rows], start=day["start"][rows],
                         end=day["end"][rows], cap=day["cap"][types]))
    return days


class VerifiedPlans:
    """The placements ``FleetEngine.evaluate`` verifies, in the order it
    verifies them: its protocol checks each fleet's chosen plan with
    ``repro.core.solution.verify`` (``PlacementConfig.check``), and this
    records the plan on its way there.  It changes nothing the program
    computes."""

    def __init__(self):
        from repro.core import engine

        self._module = engine
        self._verify = engine.verify
        self.seen: list = []

        def verify(problem, solution, *args, **kwargs):
            self.seen.append(solution)
            return self._verify(problem, solution, *args, **kwargs)

        engine.verify = verify

    def take(self) -> list:
        seen, self.seen = self.seen, []
        return seen

    def close(self) -> None:
        self._module.verify = self._verify


class Runner:
    def __init__(self, cell):
        self.cell = cell
        self.cfg = cell.config
        self.record = Record()
        self.plans: list[list[dict]] = []
        self.attempted = self.failed = 0

    def setup(self) -> None:
        from repro.core import FleetEngine, NodeTypes, Problem, SolverConfig

        cfg = self.cfg
        self.days = draw_days(cfg, self.cell.traffic["grids"], self.cell.seed)
        self.costs = [[gct.node_cost(day["cap"], cm, cfg["gce_e"])
                       for cm in cfg["cost_models"]] for day in self.days]
        self.grids = []
        for day, costs in zip(self.days, self.costs):
            dem = day["dem"]
            if self.cell.demand_cast is not None:
                dem = self.cell.demand_cast(dem)
            self.grids.append([
                Problem(dem=dem, start=day["start"], end=day["end"],
                        node_types=NodeTypes(cap=day["cap"], cost=cost),
                        T=day["T"])
                for cost in costs])
        self.engine = FleetEngine(solver=SolverConfig(**cfg["solver"]),
                                  algos=(cfg["algo"],))
        self.verified = VerifiedPlans()
        self._plan(0, Record())  # compiles the LP for the cell's one shape
        self.order: list[int] = []

    def _plan(self, g: int, rec: Record) -> list[dict]:
        """``evaluate`` of grid ``g``; its phase times and LP iterations
        go to ``rec``."""
        self.verified.take()
        result = self.engine.evaluate(self.grids[g])
        plans = self.verified.take()
        if result.plan.n_buckets != 1:
            raise RuntimeError(f"grid {g} packed into "
                               f"{result.plan.n_buckets} buckets; the cell "
                               f"expects one LP shape")
        for key in ("pack_s", "lp_s", "place_s"):
            rec.add(key, result.timings[key])
        iters = np.concatenate([np.asarray(s.iterations).reshape(-1)
                                for s in result.stats])
        for i in iters:
            rec.add("lp_iters", i)
        batch = result.plan.buckets[0].batch
        self.work = lp_work((batch.B, *batch.shape), iters)
        algo = self.cfg["algo"]
        return [{"lb": e["lb"], "cost": e["costs"][algo],
                 "node_type": None if p is None else p.node_type,
                 "assign": None if p is None else p.assign}
                for e, p in zip(result.entries,
                                plans + [None] * (len(result.entries)
                                                  - len(plans)))]

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
            # grid 0 compiled in set-up; the window never repeats a day
            g = 1 + len(self.plans) % (len(self.grids) - 1)
            with rec.span("plan", "plan_s"):
                answers = self._plan(g, rec)
            if in_trace:  # the LP work that the traced programs did
                rec.add("lp_bytes_traced", self.work[0])
                rec.add("lp_flops_traced", self.work[1])
            self.plans.append(answers)
            self.order.append(g)
            end = time.perf_counter()
        jax.monitoring.unregister_event_listener(count)
        self.verified.close()
        return {"plan_s": plan_seconds(t0, end, len(self.plans),
                                       len(self.grids[0]))}

    def check(self) -> list[Check]:
        overload, cost_gap, unplaced = 0.0, 0.0, 0
        below = above = -np.inf
        bad = set()
        for i, (g, answers) in enumerate(zip(self.order, self.plans)):
            day = self.days[g]
            for b, cost in enumerate(self.costs[g]):
                self.attempted += 1
                a = answers[b] if b < len(answers) else None
                if a is None or a["assign"] is None:
                    unplaced += len(day["dem"])
                    bad.add((i, b))
                    continue
                over, lost = reference.plan_faults(
                    day["dem"], day["start"], day["end"], day["cap"],
                    a["node_type"], a["assign"], len(cost))
                price = float(np.asarray(cost)[np.clip(
                    a["node_type"], 0, len(cost) - 1)].sum())
                gap = abs(a["cost"] - price) / price
                overload = max(overload, over)
                cost_gap = max(cost_gap, gap)
                unplaced += lost
                if over > OVERLOAD_LIMIT or lost or gap > COST_GAP_LIMIT:
                    bad.add((i, b))
                opt = reference.optimum(day["dem"], day["start"], day["end"],
                                        day["cap"], cost)
                lb = a["lb"] if np.isfinite(a["lb"]) else -np.inf
                gap_b, excess_b = (opt - lb) / abs(opt), (lb - opt) / abs(opt)
                below, above = max(below, gap_b), max(above, excess_b)
                if gap_b > LB_GAP_LIMIT or excess_b > LB_EXCESS_LIMIT:
                    bad.add((i, b))
        self.failed = len(bad)
        return [Check("unplaced", float(unplaced), 0.0),
                Check("overload", overload, OVERLOAD_LIMIT),
                Check("cost_gap", cost_gap, COST_GAP_LIMIT),
                Check("lb_above_opt", above, LB_EXCESS_LIMIT),
                Check("lb_below_opt", below, LB_GAP_LIMIT)]


# JAX records this for every program it has to compile or load from its
# persistent cache, that is every program not already in memory
COMPILE_EVENT = "/jax/compilation_cache/compile_requests_use_cache"
# the program's own capacity slack (``repro.core.solution.EPS``): a plan
# may overfill a node by float accumulation noise, never by more
OVERLOAD_LIMIT = 1e-7
# the price a plan reports is the sum of its nodes' catalogue prices
COST_GAP_LIMIT = 1e-9
# a certified lower bound may not exceed the optimum; HiGHS solves to
# about 1e-7, so an excess under a millionth is the reference's noise
LB_EXCESS_LIMIT = 1e-6
# set from readings, see PERF.md
LB_GAP_LIMIT = 0.05
