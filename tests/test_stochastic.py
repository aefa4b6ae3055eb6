"""Stochastic rightsizing: forecast, fan-out, and CVaR selection.

The load-bearing invariants of ``repro.stochastic``:

  * degeneracy — a zero-variance forecast at K=1 IS the paper's
    deterministic protocol, cost-exact against ``FleetEngine.evaluate``;
  * determinism — same seed twice gives bit-identical scenarios, and
    growing K appends scenarios without moving the first ones;
  * CVaR — monotone in alpha, mean at alpha=0, max as alpha -> 1, and
    the frontier's chosen fleet cost is non-decreasing in alpha on the
    fixed-seed grid;
  * batching — K same-shape scenarios solve in ONE compiled dispatch
    (``FleetEngine.solve_scenarios``), and ragged groups are rejected
    with a pointed error.
"""

import dataclasses

import numpy as np
import pytest

# guarded per-test (not module-level importorskip — most tests here
# are plain), matching tests/test_serve_snapshot.py's env
try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:
    HAVE_HYPOTHESIS = False

needs_hypothesis = pytest.mark.skipif(
    not HAVE_HYPOTHESIS,
    reason="hypothesis not installed in this environment")

from repro.core import FleetEngine, SolverConfig, SweepConfig
from repro.core.batch import dispatch_count
from repro.stochastic import (
    DemandForecast,
    ScenarioSet,
    StochasticConfig,
    candidate_fleets,
    cvar,
    fan_out,
    fit_forecast,
    gct_forecast,
    overload_costs,
    plan_stochastic,
)
from repro.workload import SyntheticSpec, synthetic_instance
from repro.workload.gct import gct_like_instance


def _forecast(seed: int, n: int = 12, **channels) -> DemandForecast:
    base = synthetic_instance(SyntheticSpec(n=n, m=3, D=2, T=10,
                                            seed=seed))
    return DemandForecast(base=base, **channels)


# -- degeneracy: zero variance at K=1 is the deterministic protocol ----

def _k1_zero_variance_body(seed):
    """A deterministic forecast's single scenario must price EXACTLY
    like ``FleetEngine.evaluate`` on the base instance — stochastic
    planning degenerates to the paper's point-forecast plan."""
    fc = _forecast(seed, load_sigma=0.0, diurnal_amp=0.0,
                   burst_prob=0.0)
    engine = FleetEngine(solver=SolverConfig(iters=600),
                         algos=("lp-map-f",))
    res = plan_stochastic(fc, StochasticConfig(scenarios=1, quantiles=2),
                          engine=engine)
    point = engine.evaluate([fc.base]).entries[0]["costs"]["lp-map-f"]
    assert res.scenario_costs[0] == point
    assert res.worst_overload == 0.0  # one scenario, fully covered


if HAVE_HYPOTHESIS:
    @settings(max_examples=8, deadline=None)
    @given(st.integers(0, 2**31 - 1))
    def test_k1_zero_variance_reproduces_deterministic_protocol(seed):
        _k1_zero_variance_body(seed)
else:
    def test_k1_zero_variance_reproduces_deterministic_protocol():
        _k1_zero_variance_body(0)


def test_deterministic_fan_out_is_bitwise_base():
    fc = _forecast(3, load_sigma=0.0, diurnal_amp=0.0, burst_prob=0.0)
    ss = fan_out(fc, K=4, seed=11)
    assert (ss.factors == 1.0).all()
    for p in ss.problems:
        assert (p.dem == fc.base.dem).all()
        assert p is not fc.base or True  # replaced instance, same data


# -- determinism: seeded streams ---------------------------------------

def test_fan_out_same_seed_twice_is_identical():
    fc = _forecast(0, burst_prob=0.3)
    a, b = fan_out(fc, K=5, seed=9), fan_out(fc, K=5, seed=9)
    assert (a.factors == b.factors).all()
    for pa, pb in zip(a.problems, b.problems):
        assert (pa.dem == pb.dem).all()


def test_fan_out_k_prefix_stability():
    """Growing K appends scenarios; the first ones do not move."""
    fc = _forecast(1, burst_prob=0.2)
    small, big = fan_out(fc, K=3, seed=4), fan_out(fc, K=7, seed=4)
    assert (big.factors[:3] == small.factors).all()
    for ps, pb in zip(small.problems, big.problems):
        assert (ps.dem == pb.dem).all()


def test_fan_out_scenarios_share_one_trimmed_shape():
    fc = _forecast(2, burst_prob=0.4)
    ss = fan_out(fc, K=6, seed=0)
    assert isinstance(ss, ScenarioSet) and ss.K == 6
    assert len(ss.shape) == 4  # the single (n, m, D, T') shape


def test_workload_generators_same_seed_twice():
    for make in (lambda: gct_like_instance(n=20, m=4, seed=5),
                 lambda: synthetic_instance(
                     SyntheticSpec(n=10, m=3, D=2, T=8, seed=5))):
        a, b = make(), make()
        assert (a.dem == b.dem).all()
        assert (a.start == b.start).all() and (a.end == b.end).all()
        assert (a.node_types.cap == b.node_types.cap).all()
        assert (a.node_types.cost == b.node_types.cost).all()


def test_workload_generators_explicit_rng_matches_seed():
    """``rng=default_rng(s)`` and ``seed=s`` are the same stream, and
    neither touches global numpy state."""
    np.random.seed(123)
    before = np.random.get_state()[1].copy()
    a = gct_like_instance(n=16, m=4, seed=7)
    b = gct_like_instance(n=16, m=4, rng=np.random.default_rng(7))
    assert (a.dem == b.dem).all()
    s = synthetic_instance(SyntheticSpec(n=8, m=2, D=2, T=6, seed=7))
    r = synthetic_instance(SyntheticSpec(n=8, m=2, D=2, T=6, seed=7),
                           rng=np.random.default_rng(7))
    assert (s.dem == r.dem).all()
    assert (np.random.get_state()[1] == before).all()


# -- CVaR ---------------------------------------------------------------

def _cvar_monotone_body(xs, a1, a2):
    x = np.asarray(xs)
    lo, hi = min(a1, a2), max(a1, a2)
    assert cvar(x, lo) <= cvar(x, hi) + 1e-9
    assert cvar(x, 0.0) == pytest.approx(float(x.mean()))
    assert cvar(x, 0.999) == pytest.approx(float(x.max()))


if HAVE_HYPOTHESIS:
    @settings(max_examples=30, deadline=None)
    @given(st.lists(st.floats(0.0, 1e6), min_size=1, max_size=40),
           st.floats(0.0, 0.999), st.floats(0.0, 0.999))
    def test_cvar_monotone_in_alpha(xs, a1, a2):
        _cvar_monotone_body(xs, a1, a2)
else:
    def test_cvar_monotone_in_alpha():
        _cvar_monotone_body([0.0, 1.0, 5.0, 2.0], 0.3, 0.8)


def test_cvar_validation():
    with pytest.raises(ValueError, match="alpha"):
        cvar(np.array([1.0]), 1.0)
    with pytest.raises(ValueError, match="non-empty"):
        cvar(np.array([]), 0.5)


def test_frontier_fleet_cost_nondecreasing_in_alpha():
    """On the fixed-seed burst grid, raising the tail level never buys
    a cheaper fleet: the frontier's lambda>0 rows are sorted by alpha
    and their purchase costs must be non-decreasing."""
    fc = _forecast(0, n=30, burst_prob=0.25, burst_alpha=1.5)
    res = plan_stochastic(
        fc, StochasticConfig(scenarios=12, cvar_lambda=2.0,
                             quantiles=5))
    rows = res.frontier[1:]  # row 0 is the lambda=0 comparison
    assert all(rows[i]["alpha"] < rows[i + 1]["alpha"]
               for i in range(len(rows) - 1))
    costs = [r["fleet_cost"] for r in rows]
    assert all(a <= b + 1e-9 for a, b in zip(costs, costs[1:]))


# -- selection machinery ------------------------------------------------

def test_candidate_fleets_brackets_and_pairwise_unions():
    plans = np.array([[2, 0, 1], [0, 3, 0], [1, 1, 1]])
    fleets = candidate_fleets(plans, quantiles=3)
    rows = {tuple(f) for f in fleets}
    assert {(2, 0, 1), (0, 3, 0), (1, 1, 1)} <= rows  # the plans
    assert (2, 3, 1) in rows                          # elementwise max
    assert (2, 3, 1) == tuple(fleets[-1])             # sorted by size
    # pairwise unions that per-type quantiles cannot express
    assert (2, 3, 1) in rows and (2, 1, 1) in rows
    node_cost = np.array([1.0, 2.0, 4.0])
    ov = overload_costs(plans, fleets, node_cost)
    assert ov.shape == (3, len(fleets))
    assert (ov[:, -1] == 0).all()  # the max fleet covers everything


def test_stochastic_config_validation():
    with pytest.raises(ValueError, match="scenarios"):
        StochasticConfig(scenarios=0)
    with pytest.raises(ValueError, match="cvar_alpha"):
        StochasticConfig(cvar_alpha=1.0)
    with pytest.raises(ValueError, match="cvar_lambda"):
        StochasticConfig(cvar_lambda=-0.1)
    with pytest.raises(ValueError, match="quantiles"):
        StochasticConfig(quantiles=1)
    with pytest.raises(ValueError, match="algo"):
        StochasticConfig(algo="lp-map-f+ls")


def test_forecast_validation():
    base = synthetic_instance(SyntheticSpec(n=4, m=2, D=2, T=6))
    with pytest.raises(ValueError, match="load_sigma"):
        DemandForecast(base=base, load_sigma=-0.1)
    with pytest.raises(ValueError, match="diurnal_amp"):
        DemandForecast(base=base, diurnal_amp=1.0)
    with pytest.raises(ValueError, match="burst_prob"):
        DemandForecast(base=base, burst_prob=1.5)
    with pytest.raises(ValueError, match="burst_cap"):
        DemandForecast(base=base, burst_cap=0.5)
    with pytest.raises(ValueError, match="K"):
        fan_out(DemandForecast(base=base), K=0)


# -- batching: the one-dispatch contract -------------------------------

def test_plan_stochastic_one_dispatch_one_bucket():
    fc = gct_forecast(n=24, m=4, seed=1, burst_prob=0.1)
    d0 = dispatch_count()
    res = plan_stochastic(fc, StochasticConfig(scenarios=8, quantiles=3))
    assert res.lp_dispatches == 1
    assert res.buckets == 1
    assert dispatch_count() - d0 >= 1
    assert res.K == 8 and len(res.scenario_plans) == 8
    s = res.summary()
    assert s["converged_frac"] == 1.0
    # the cost bracket the CI gate pins on the golden grid
    assert s["mean_scenario_cost"] <= s["fleet_cost"] + 1e-9
    assert s["fleet_cost"] <= s["max_fleet_cost"] + 1e-9


def test_plan_stochastic_keeps_each_scenarios_verified_protocol_plan(
        monkeypatch):
    from repro.core import FIT_POLICIES, trim_timeline, two_phase, verify
    from repro.core import engine as engine_mod

    fc = gct_forecast(n=40, m=4, seed=3, burst_prob=0.1)
    config = StochasticConfig(scenarios=6, quantiles=3)
    engine = FleetEngine(solver=SolverConfig(tol=5e-3, iters=4000),
                         algos=(config.algo,))
    verified = []

    def recording_verify(problem, solution, *args, **kwargs):
        verified.append(solution)
        return verify(problem, solution, *args, **kwargs)

    monkeypatch.setattr(engine_mod, "verify", recording_verify)
    res = plan_stochastic(fc, config, engine=engine)
    # every kept plan, and nothing else, went through verify
    assert [id(s) for s in verified] == [id(s) for s in
                                         res.scenario_solutions]

    problems = fan_out(fc, config.scenarios, config.seed).problems
    lp, _ = engine.solve_scenarios(list(problems))
    np.testing.assert_array_equal(res.scenario_lbs,
                                  [r.lower_bound for r in lp])
    for s, p in enumerate(problems):
        t = trim_timeline(p)[0]
        sols = [two_phase(t, lp[s].mapping, fit=fit, filling=True)
                for fit in FIT_POLICIES]
        costs = [sol.cost(t) for sol in sols]
        want = sols[int(np.argmin(costs))]  # the first of equal costs
        kept = res.scenario_solutions[s]
        assert res.scenario_costs[s] == min(costs) == kept.cost(t)
        np.testing.assert_array_equal(kept.node_type, want.node_type)
        np.testing.assert_array_equal(kept.assign, want.assign)
        np.testing.assert_array_equal(res.scenario_plans[s],
                                      want.nodes_per_type(t))
        verify(t, kept)


def test_solve_scenarios_rejects_ragged_shapes():
    a = synthetic_instance(SyntheticSpec(n=6, m=2, D=2, T=8, seed=0))
    b = synthetic_instance(SyntheticSpec(n=7, m=2, D=2, T=8, seed=0))
    engine = FleetEngine(solver=SolverConfig(iters=100))
    with pytest.raises(ValueError, match="ONE \\(n, m, D, T'\\) shape"):
        engine.solve_scenarios([a, b])


def test_solve_scenarios_rejects_warm_started_sweeps():
    p = synthetic_instance(SyntheticSpec(n=6, m=2, D=2, T=8, seed=0))
    engine = FleetEngine(solver=SolverConfig(tol=5e-3, iters=200),
                         sweep=SweepConfig(warm_start=2))
    with pytest.raises(ValueError, match="warm_start"):
        engine.solve_scenarios([p, p])


def test_sweep_config_devices_validated_against_visible_devices():
    import jax

    too_many = jax.local_device_count() + 1
    with pytest.raises(ValueError, match="local JAX device"):
        SweepConfig(warm_start=2, pipeline=True, devices=too_many)


# -- trace fitting ------------------------------------------------------

@dataclasses.dataclass
class _Req:
    kind: str
    fleet: str = "f0"
    dem: np.ndarray | None = None
    start: np.ndarray | None = None
    end: np.ndarray | None = None
    ids: tuple = ()
    factor: float = 1.0


def test_fit_forecast_estimates_burst_channel():
    base = synthetic_instance(SyntheticSpec(n=6, m=2, D=2, T=8))
    dem = np.full((4, 2), 0.2)
    reqs = [
        _Req("admit", dem=dem),
        _Req("burst", ids=(0, 1), factor=2.5),
        _Req("arrive", dem=dem),
        _Req("burst", ids=(2,), factor=4.0),
        _Req("depart", ids=(3,)),
    ]
    fc = fit_forecast(reqs, base)
    assert fc.base is base
    assert 0.0 < fc.burst_prob <= 1.0
    assert fc.burst_prob == pytest.approx(3 / 8)  # 3 bursted / 8 admits
    assert fc.burst_alpha > 0
    assert fc.load_sigma > 0  # the ledger total moved across events
    assert fc.diurnal_amp == 0.0  # never estimated from traces
    # overrides pin channels instead of estimating them
    assert fit_forecast(reqs, base, burst_prob=0.5).burst_prob == 0.5


def test_fit_forecast_empty_trace_is_deterministic():
    base = synthetic_instance(SyntheticSpec(n=4, m=2, D=2, T=6))
    assert fit_forecast([], base).deterministic


# -- the serving hook ---------------------------------------------------

def test_service_preprovision_grows_plan_and_logs_event():
    from repro.serve import RightsizingService, TraceSpec, gct_trace, replay

    svc = RightsizingService()
    replay(svc, gct_trace(TraceSpec(fleets=1, requests=10, seed=0)),
           push_per_tick=8)
    name = svc.fleets[0]
    before = svc.fleet(name)
    res = svc.preprovision(
        name, config=StochasticConfig(scenarios=4, quantiles=3))
    after = svc.fleet(name)
    assert res.K == 4 and res.lp_dispatches == 1
    assert (after.plan >= before.plan).all()  # growth-only adoption
    ev = svc.events[-1]
    assert ev.scope == "preprovision" and ev.fleet == name
    assert ev.cost_after >= ev.cost_before
