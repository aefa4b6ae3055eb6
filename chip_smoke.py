"""On-chip smoke run of the planner's main path, in one process.

    python chip_smoke.py             # one TPU: offline, compiled, online,
                                     # robust and kernels phases
    python chip_smoke.py --chips 4   # four TPUs: only the sharded sweep
                                     # pipeline, against one device

It drives the three entry points a user calls, at the paper's scale,
with data generated from seeds by the repo's own generators:
``FleetEngine`` (offline grids, the compiled placement stepper),
``RightsizingService`` (an online GCT trace) and ``plan_stochastic``
(the K=64 golden burst grid), then the Pallas congestion kernel
against its ``kernels/ref.py`` oracle.  Each phase times its first call as
compile and its later call as steady; every timing ends in a host
sync.  These are the timings of one smoke run, not a benchmark.  Where
the run's time limit forced a smaller size, the phase prints the cut
first.

Every check raises, so any failed phase exits non-zero.  The script
exits non-zero before any work when JAX finds no TPU.  The last line
of standard output is the JSON verdict with the device as JAX reports
it.
"""

from __future__ import annotations

import argparse
import json
import math
import sys
import time
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parent

# the paper's runtime instance (n=2000, m=13) and the Fig. 11 family
OFFLINE_GRID = [(2000, m, s) for m in (4, 10, 13) for s in (0, 1)] \
    + [(500, 13, 0)]
HIGHS_REF = (500, 13, 0)      # HiGHS at n=2000 runs for minutes
RUNTIME_INSTANCE = (2000, 13, 0)
# the compiled stepper unrolls one scan chunk per slice of its chunk
# plan: at n=2000 its programs took 404 s to compile on a v5e host, a
# third of the run's time limit; n=500 takes about a sixth of that
COMPILED_INSTANCE = (500, 13, 0)
# the service compiles one LP per padded shape: the default quantum of
# 8 gives 21 shapes on this trace (347 s of replay on a v5e, nearly
# all compiles), 32 gives 7
SHAPE_QUANTUM = 32
SOLVER_TOL = 5e-3


class SmokeFailure(RuntimeError):
    """A check of the smoke run failed."""


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def say(phase: str, **fields) -> None:
    cells = " ".join(f"{k}={v}" for k, v in fields.items())
    print(f"[smoke run, not a benchmark] {phase}: {cells}", flush=True)


def timed(fn, *args, **kwargs):
    """(result, seconds); callers return host values, so the clock
    stops after the device finished."""
    t0 = time.perf_counter()
    out = fn(*args, **kwargs)
    return out, time.perf_counter() - t0


def _solver():
    from repro.core import SolverConfig

    return SolverConfig(tol=SOLVER_TOL, iters=4000)


def _gct(n, m, seed, **kw):
    from repro.workload import gct_like_instance

    return gct_like_instance(n=n, m=m, seed=seed, **kw)


def _audit(problems, plans, phase: str) -> None:
    """Every plan must pass the independent feasibility oracle.

    The oracle runs on the trimmed timeline the planner solved: the
    capacity profile only changes where a task starts, so its slots see
    every peak, while the original GCT horizon (86,400 slots) costs the
    pure-Python oracle ~50 s per plan at n=2000."""
    from repro.core import check_plan, trim_timeline

    for i, (p, sol) in enumerate(zip(problems, plans)):
        bad = check_plan(trim_timeline(p)[0], sol)
        check(not bad, f"{phase}: plan {i} fails check_plan: {bad[:3]}")


def phase_offline(grid=OFFLINE_GRID, highs_ref=HIGHS_REF) -> None:
    """A GCT grid through ``FleetEngine.evaluate`` (default placement).
    The steady call solves and places the same grid again and audits
    the plan each algorithm adopts; the n=500 dual bound is held
    against HiGHS."""
    from repro.core import (FIT_POLICIES, FleetEngine, SweepConfig,
                            penalty_map, solve_lp, trim_timeline)

    problems = [_gct(*spec) for spec in grid]
    engine = FleetEngine(solver=_solver(), sweep=SweepConfig(max_buckets=4))
    res, compile_s = timed(engine.evaluate, problems)
    check(all(e["solver"]["converged"] for e in res.entries),
          "offline: an LP lane did not converge")
    for algo in res.algos:
        norm = [e["normalized"][algo] for e in res.entries]
        check(all(math.isfinite(v) and v >= 1.0 for v in norm),
              f"offline: {algo} normalized cost outside [1, inf): {norm}")

    (lp_results, _), steady_lp_s = timed(engine.solve, problems)
    check(all(r.converged for r in lp_results),
          "offline: an LP lane did not converge on the steady solve")
    trimmed = [trim_timeline(p)[0] for p in problems]
    t0 = time.perf_counter()
    adopted = {}
    for algo in res.algos:
        if algo.startswith("penalty-map"):
            mapsets = [[penalty_map(t, kind) for t in trimmed]
                       for kind in ("avg", "max")]
        else:
            mapsets = [[r.mapping for r in lp_results]]
        best = [None] * len(problems)
        for maps in mapsets:
            for fit in FIT_POLICIES:
                plans = engine.place(problems, maps, fit=fit,
                                     filling=algo.endswith("-f"))
                best = [s if b is None or s.cost(p) < b.cost(p) else b
                        for b, s, p in zip(best, plans, problems)]
        adopted[algo] = best
    steady_place_s = time.perf_counter() - t0
    say("offline", instances=len(problems), buckets=res.plan.n_buckets,
        compile_s=compile_s, steady_lp_s=steady_lp_s,
        steady_place_s=steady_place_s)
    for algo, plans in adopted.items():
        costs = [s.cost(p) for s, p in zip(plans, problems)]
        check(costs == res.costs(algo),
              f"offline: re-placed {algo} costs {costs} differ from "
              f"evaluate's {res.costs(algo)}")
        _audit(problems, plans, f"offline {algo}")

    i = grid.index(highs_ref)
    opt = solve_lp(trimmed[i]).objective
    lb = res.entries[i]["lb"]
    say("offline", plans_audited=len(problems) * len(adopted),
        highs_instance=highs_ref, pdhg_dual_bound=lb, highs_optimum=opt,
        rel_gap=(opt - lb) / max(1.0, abs(opt)))
    check(lb <= opt + SOLVER_TOL * max(1.0, abs(opt)),
          f"offline: certified dual bound {lb} exceeds the HiGHS "
          f"optimum {opt}")


def phase_compiled(spec=COMPILED_INSTANCE) -> None:
    """The compiled placement stepper: no fallback, and its placements
    compared with the numpy engine's."""
    from repro.core import FleetEngine, PlacementConfig

    problem = _gct(*spec)
    # one fit policy and one algorithm: the stepper compiles one program
    # per sub-phase chunk plan
    say("compiled", cut=f"instance={spec} (runtime instance: "
        f"{RUNTIME_INSTANCE}, whose stepper programs compile for ~400 s)")
    say("compiled", cut="algos=lp-map-f fit=similarity "
        "(the protocol scans 4 algos x 2 fits)")
    engine = FleetEngine(
        solver=_solver(), algos=("lp-map-f",),
        placement=PlacementConfig(engine="compiled", fit="similarity"))
    res, compile_s = timed(engine.evaluate, [problem])
    tel = res.timings["placement"]
    check(tel["fallbacks"] == 0,
          f"compiled: the stepper fell back to numpy: {tel}")
    check(res.entries[0]["solver"]["converged"],
          "compiled: the LP lane did not converge")

    lp_results, _ = engine.solve([problem])
    maps = [lp_results[0].mapping]
    plans, place_s = {}, {}
    for name in ("compiled", "batched"):
        eng = engine.with_overrides(engine=name)
        plans[name], place_s[name] = timed(eng.place, [problem], maps,
                                           filling=True)
    say("compiled", instance=spec, compile_s=compile_s,
        steady_place_compiled_s=place_s["compiled"],
        steady_place_batched_s=place_s["batched"],
        dispatches=tel["dispatches"], modes=tel["modes"])
    a, b = plans["compiled"][0], plans["batched"][0]
    _audit([problem] * 2, [a, b], "compiled")
    say("compiled", mismatched_tasks=int((a.assign != b.assign).sum()),
        node_types_equal=bool(np.array_equal(a.node_type, b.node_type)),
        cost_compiled=a.cost(problem), cost_batched=b.cost(problem),
        evaluate_cost=res.costs("lp-map-f")[0])


def phase_online(fleets=4, requests=200, n0=512) -> None:
    """A GCT arrival trace replayed through ``RightsizingService``."""
    from repro.serve import RightsizingService, ServiceConfig
    from repro.serve.trace import TraceSpec, gct_trace, replay

    trace = gct_trace(TraceSpec(fleets=fleets, requests=requests, n0=n0,
                                seed=0))
    say("online", cut=f"shape_quantum={SHAPE_QUANTUM} (default 8: three "
        f"times the LP shapes to compile)")
    for label in ("compile", "steady"):
        svc = RightsizingService(
            config=ServiceConfig(shape_quantum=SHAPE_QUANTUM))
        rep, wall_s = timed(replay, svc, trace)
        say("online", run=label, wall_s=wall_s, ticks=rep["ticks"],
            p50_replan_s=rep["p50_replan_s"],
            p99_replan_s=rep["p99_replan_s"],
            dispatches_per_tick=rep["dispatches_per_tick"],
            warm_lanes=rep["warm_lanes"],
            converged_frac=rep["converged_frac"],
            quarantined=rep["quarantined"], total_cost=rep["total_cost"])
        check(rep["dispatches_per_tick"] == 1,
              f"online: {rep['dispatches_per_tick']} dispatches in a tick")
        check(rep["converged_frac"] == 1.0,
              f"online: converged_frac {rep['converged_frac']}")
        check(rep["warm_lanes"] > 0, "online: no warm re-solve")
        check(rep["quarantined"] == 0,
              f"online: quarantined requests: "
              f"{[q.error for q in svc.quarantined][:3]}")
        names = svc.fleets
        _audit([svc._fleets[f].problem for f in names],
               [svc.fleet(f).solution for f in names], "online")


def phase_robust(scenarios=None) -> None:
    """``plan_stochastic`` over the golden burst grid."""
    from benchmarks.check_stochastic import check as golden_findings
    from benchmarks.stochastic_smoke import stochastic_smoke

    blob, compile_s = timed(stochastic_smoke, scenarios)
    blob, steady_s = timed(stochastic_smoke, scenarios)
    say("robust", K=blob["K"], compile_s=compile_s, steady_s=steady_s,
        lp_dispatches=blob["lp_dispatches"], fleet=blob["fleet"],
        worst_overload=blob["worst_overload"],
        expected_fleet_worst_overload=blob[
            "expected_fleet_worst_overload"])
    check(blob["lp_dispatches"] == 1,
          f"robust: {blob['lp_dispatches']} LP dispatches for K scenarios")
    check(blob["worst_overload"] <= blob["expected_fleet_worst_overload"],
          "robust: the CVaR fleet's worst overload exceeds the "
          "expected-cost fleet's")
    golden = json.loads(
        (ROOT / "results" / "golden" / "stochastic.json").read_text())
    findings = golden_findings(blob, golden)
    say("robust", golden_findings=len(findings))
    for f in findings:
        print(f"  check_stochastic: {f}", flush=True)


def phase_kernels(spec=RUNTIME_INSTANCE, lanes=4) -> None:
    """``congestion_many`` against its oracle."""
    from repro.core import trim_timeline
    from repro.kernels import ops

    t = trim_timeline(_gct(*spec))[0]
    rng = np.random.default_rng(0)
    T, D = t.T, t.D
    start = np.broadcast_to(t.start, (lanes, t.n))
    end = np.broadcast_to(t.end, (lanes, t.n))
    cap = t.node_types.cap[:lanes]                         # (G, D)
    w = (t.dem[None] / cap[:, None, :]
         * rng.random((lanes, t.n, 1))).astype(np.float32)
    out, compile_s = timed(lambda: np.asarray(
        ops.congestion_many(start, end, w, T)))
    out, steady_s = timed(lambda: np.asarray(
        ops.congestion_many(start, end, w, T)))
    want = np.asarray(ops.congestion_many(start, end, w, T, use_ref=True))
    err = float(np.abs(out - want).max() / max(1.0, np.abs(want).max()))
    say("kernels", kernel="congestion_many", shape=(lanes, t.n, D, T),
        compile_s=compile_s, steady_s=steady_s, max_rel_err=err)
    check(err <= 1e-5, f"kernels: congestion_many off the oracle by {err}")


def _lane_reference(trimmed, group, solver):
    """The sharded sweep's exact one-device reference: each shard's
    lanes (one per chip here) run as their own warm chain on one device,
    at the whole sweep's padded shape.  A lane's f32 trajectory depends
    on how many lanes share its device (the CPU backend's batched dense
    product already differs between 1 and 4 lanes), so the one-device
    run of the whole group is not a bit-level reference."""
    from repro.core.batch import _sweep_pipeline

    pad = (max(t.n for t in trimmed), max(t.m for t in trimmed),
           max(t.D for t in trimmed), max(t.T for t in trimmed))
    out = [None] * len(trimmed)
    for j in range(group):
        lanes = list(range(j, len(trimmed), group))
        res, _ = _sweep_pipeline(
            [[trimmed[i]] for i in lanes], pad, tol=solver.tol,
            iters=solver.iters, step_scale=solver.step_scale,
            operator=solver.operator, adaptive=solver.adaptive,
            restart=solver.restart, check_every=solver.check_every,
            scaling=solver.scaling, precision=solver.precision,
            omega=solver.omega, devices=None)
        for i, r in zip(lanes, res):
            out[i] = r
    return out


def phase_sharded(devices=4, n=1000, seeds=4) -> None:
    """The warm-started sweep pipeline sharded over ``devices`` chips
    against the same sweep on one device (Fig. 11 family, one group of
    ``seeds`` replicas per (cost model, m) point, one lane per chip).

    Lane for lane, the sharded LP results must be bit-identical to the
    same chains on one device at the same per-device batch; against the
    whole group on one device, the certified bounds must agree, and the
    protocol costs that match are counted."""
    from repro.core import FleetEngine, SweepConfig, trim_timeline
    from repro.core.batch import dispatch_count

    flat = [_gct(n, m, s, cost_model=cm)
            for cm in ("homogeneous", "gce") for m in (4, 10, 13)
            for s in range(seeds)]
    say("sharded", cut=f"seeds={seeds} (paper: 5; the group size must "
        f"divide over {devices} chips)", instances=len(flat), n=n)
    costs, lp = {}, {}
    for devs in (devices, None):
        engine = FleetEngine(
            solver=_solver(), algos=("lp-map", "lp-map-f"),
            sweep=SweepConfig(warm_start=seeds, pipeline=True,
                              devices=devs))
        d0 = dispatch_count()
        res, compile_s = timed(engine.evaluate, flat)
        dispatches = dispatch_count() - d0
        (lp[devs], _), steady_s = timed(engine.solve, flat)
        check(dispatches == 1,
              f"sharded: devices={devs} took {dispatches} LP dispatches")
        check(all(e["solver"]["converged"] for e in res.entries),
              f"sharded: devices={devs} left an LP lane unconverged")
        say("sharded", devices=devs or 1, dispatches=dispatches,
            compile_s=compile_s, steady_lp_s=steady_s,
            median_iters=float(np.median(
                [e["solver"]["iters"] for e in res.entries])))
        costs[devs] = {a: res.costs(a) for a in res.algos}

    sharded = lp[devices]
    ref, ref_s = timed(_lane_reference,
                       [trim_timeline(p)[0] for p in flat], seeds,
                       _solver())
    same = [np.array_equal(a.mapping, r.mapping)
            and a.lower_bound == r.lower_bound and a.iters == r.iters
            for a, r in zip(sharded, ref)]
    say("sharded", reference="one device, lane by lane", wall_s=ref_s,
        lanes_bit_identical=f"{sum(same)}/{len(same)}")
    check(all(same), f"sharded: lanes {np.flatnonzero(~np.array(same))} "
          f"differ from their one-device chains")

    whole = lp[None]
    for a, b in zip(sharded, whole):
        check(a.lower_bound <= b.objective and b.lower_bound <= a.objective,
              f"sharded: certified bounds disagree with one device: "
              f"[{a.lower_bound}, {a.objective}] vs "
              f"[{b.lower_bound}, {b.objective}]")
    for algo, want in costs[None].items():
        got = costs[devices][algo]
        say("sharded", reference="one device, whole group", algo=algo,
            costs_identical=f"{sum(g == w for g, w in zip(got, want))}"
                            f"/{len(want)}",
            total_cost_sharded=sum(got), total_cost_one_device=sum(want))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs only the sharded sweep pipeline")
    args = ap.parse_args(argv)

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: needs a TPU; JAX found "
              f"{devices[0].platform!r}", file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX sees "
              f"{len(devices)} device(s)", file=sys.stderr)
        return 1
    sys.path.insert(0, str(ROOT / "src"))
    from repro.launch import enable_compile_cache

    enable_compile_cache()
    t0 = time.perf_counter()
    phases = ([phase_sharded] if args.chips == 4 else
              [phase_offline, phase_compiled, phase_online, phase_robust,
               phase_kernels])
    for phase in phases:
        t = time.perf_counter()
        phase()
        say(phase.__name__, wall_s=time.perf_counter() - t)
    say("total", wall_s=time.perf_counter() - t0)
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
