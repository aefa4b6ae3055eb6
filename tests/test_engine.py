"""FleetEngine tests: typed-config validation, the shape-bucket packing
planner, bucketed-vs-single-bucket protocol parity (a Hypothesis
property plus the B=32 acceptance gate: exact cost equality at >= 30%
padded-cell waste reduction), bucket-merge ordering round-trips,
structured ``FleetResult`` output, and the legacy ``evaluate_many`` shim
semantics (warm_start validation, trailing-group behavior).
"""

import dataclasses
import json
from types import SimpleNamespace

import numpy as np
import pytest

from repro.core import (
    FleetEngine,
    PlacementConfig,
    SolverConfig,
    SweepConfig,
    assert_feasible,
    evaluate_many,
    pack_problems,
    place_many,
    plan_buckets,
    trim_timeline,
)
from repro.core.batch import DEFAULT_TOL
from repro.workload import SyntheticSpec, synthetic_batch

try:
    from hypothesis import given, strategies as st

    _HAVE_HYPOTHESIS = True
except ImportError:  # pragma: no cover - the 'test' extra not installed
    _HAVE_HYPOTHESIS = False


def _shape(n, m, D, T):
    """A duck-typed trimmed instance for planner unit tests."""
    return SimpleNamespace(n=n, m=m, D=D, T=T)


def _ragged_grid(shapes=8, seeds=4):
    """The acceptance fixture: a B = shapes x seeds ragged sweep grid."""
    specs = [SyntheticSpec(n=30 + 6 * i, m=5, D=4, T=8 + 2 * i, seed=s)
             for i in range(shapes) for s in range(seeds)]
    return synthetic_batch(specs)


class TestConfigValidation:
    def test_defaults_construct(self):
        FleetEngine()  # every config default must be self-consistent

    def test_configs_are_frozen(self):
        for cfg in (SolverConfig(), PlacementConfig(), SweepConfig()):
            with pytest.raises(dataclasses.FrozenInstanceError):
                cfg.iters = 1  # type: ignore[misc]

    @pytest.mark.parametrize("kw", [
        {"tol": 0.0}, {"tol": -1e-3}, {"iters": 0},
        {"operator": "bogus"}, {"step_scale": 0.0}, {"check_every": 0},
    ])
    def test_solver_config_rejects(self, kw):
        with pytest.raises(ValueError):
            SolverConfig(**kw)

    @pytest.mark.parametrize("kw", [
        {"engine": "bogus"}, {"fit": "bogus"},
    ])
    def test_placement_config_rejects(self, kw):
        with pytest.raises(ValueError):
            PlacementConfig(**kw)

    def test_placement_fits_scan(self):
        from repro.core import FIT_POLICIES

        assert PlacementConfig().fits == FIT_POLICIES
        assert PlacementConfig(fit="first").fits == ("first",)

    @pytest.mark.parametrize("kw", [
        {"warm_start": 0}, {"warm_start": -3}, {"shard_size": 0},
        {"max_buckets": 0}, {"bucket_overhead": -0.1},
    ])
    def test_sweep_config_rejects(self, kw):
        with pytest.raises(ValueError):
            SweepConfig(**kw)

    def test_warm_start_excludes_bucketing(self):
        with pytest.raises(ValueError, match="mutually exclusive"):
            SweepConfig(warm_start=2, max_buckets=3)

    def test_warm_start_excludes_sharding(self):
        with pytest.raises(ValueError, match="mutually exclusive"):
            SweepConfig(warm_start=2, shard_size=4)

    def test_engine_warm_start_requires_tol(self):
        with pytest.raises(ValueError, match="warm_start"):
            FleetEngine(sweep=SweepConfig(warm_start=2))

    def test_engine_loop_rejects_fit_narrowing(self):
        with pytest.raises(ValueError, match="loop"):
            FleetEngine(placement=PlacementConfig(engine="loop",
                                                  fit="first"))


class TestPlanner:
    def test_single_bucket_when_capped(self):
        probs = [_shape(10 * (i + 1), 3, 2, 8) for i in range(5)]
        assert plan_buckets(probs, max_buckets=1) == [[0, 1, 2, 3, 4]]

    def test_uniform_shapes_stay_one_bucket(self):
        """Splitting identical shapes saves nothing — the overhead term
        (and the exact-tie preference for fewer buckets) keeps them
        together."""
        probs = [_shape(40, 4, 3, 12) for _ in range(6)]
        assert plan_buckets(probs, max_buckets=4) == [[0, 1, 2, 3, 4, 5]]
        assert plan_buckets(probs, max_buckets=4,
                            overhead=0.0) == [[0, 1, 2, 3, 4, 5]]

    def test_two_clusters_split_apart(self):
        """Interleaved small/large shapes must land in separate buckets
        (the planner sorts by footprint, so submission interleaving
        never defeats it)."""
        small = _shape(10, 2, 2, 4)
        large = _shape(100, 4, 4, 30)
        probs = [small, large, small, large, small, large]
        parts = plan_buckets(probs, max_buckets=4)
        assert sorted(map(tuple, parts)) == [(0, 2, 4), (1, 3, 5)]

    def test_partition_is_a_permutation(self):
        rng = np.random.default_rng(0)
        probs = [_shape(int(rng.integers(5, 200)), int(rng.integers(2, 8)),
                        int(rng.integers(1, 6)), int(rng.integers(4, 40)))
                 for _ in range(23)]
        for k in (1, 2, 3, 7):
            parts = plan_buckets(probs, max_buckets=k)
            assert len(parts) <= k
            flat = sorted(i for p in parts for i in p)
            assert flat == list(range(23))

    def test_more_buckets_never_pad_more(self):
        rng = np.random.default_rng(1)
        probs = [_shape(int(rng.integers(5, 200)), 4, 3,
                        int(rng.integers(4, 40))) for _ in range(17)]

        def packed_cells(parts):
            dims = np.array([(t.n, t.m, t.D, t.T) for t in probs])
            return sum(len(p) * dims[list(p)].max(axis=0).prod()
                       for p in parts)

        cells = [packed_cells(plan_buckets(probs, max_buckets=k,
                                           overhead=0.0))
                 for k in (1, 2, 4, 8)]
        assert all(a >= b for a, b in zip(cells, cells[1:]))

    def test_rejects_empty(self):
        with pytest.raises(ValueError):
            plan_buckets([])


class TestPackPlan:
    def test_round_trip_and_waste_metrics(self):
        problems = _ragged_grid(shapes=6, seeds=2)
        engine = FleetEngine(sweep=SweepConfig(max_buckets=3))
        plan = engine.pack(problems)
        flat = sorted(i for b in plan.buckets for i in b.indices)
        assert flat == list(range(len(problems)))
        trimmed = [trim_timeline(p)[0] for p in problems]
        n, m = max(t.n for t in trimmed), max(t.m for t in trimmed)
        D, T = max(t.D for t in trimmed), max(t.T for t in trimmed)
        assert plan.cells_single == len(problems) * n * m * D * T
        assert plan.cells_own == sum(t.n * t.m * t.D * t.T
                                     for t in trimmed)
        assert plan.cells_packed <= plan.cells_single
        assert 0.0 <= plan.waste_packed <= plan.waste_single < 1.0
        assert 0.0 <= plan.waste_reduction <= 1.0
        # bucket batches really are packed to their own maxima
        for bucket in plan.buckets:
            own = [trimmed[i] for i in bucket.indices]
            assert bucket.shape == (max(t.n for t in own),
                                    max(t.m for t in own),
                                    max(t.D for t in own),
                                    max(t.T for t in own))
        summary = plan.summary()
        assert summary["buckets"] == plan.n_buckets
        assert sum(summary["bucket_sizes"]) == len(problems)

    def test_prepacked_batch_passes_through(self):
        problems = _ragged_grid(shapes=3, seeds=1)
        batch = pack_problems(problems)
        plan = FleetEngine(sweep=SweepConfig(max_buckets=4)).pack(batch)
        assert plan.n_buckets == 1
        assert plan.buckets[0].batch is batch
        assert plan.waste_reduction == 0.0


class TestBucketedParity:
    """Bucketed FleetEngine.evaluate == single-bucket evaluate_many,
    cost-exactly, on ragged grids (the acceptance property)."""

    ALGOS = ("lp-map", "lp-map-f")
    ITERS = 300

    def test_acceptance_b32_exact_costs_and_waste_cut(self):
        """The PR gate: on a ragged B=32 grid the bucketed engine keeps
        every protocol cost exactly equal to single-bucket packing while
        eliminating >= 30% of the padded-cell waste."""
        problems = _ragged_grid(shapes=8, seeds=4)
        assert len(problems) == 32
        engine = FleetEngine(solver=SolverConfig(iters=self.ITERS),
                             sweep=SweepConfig(max_buckets=4),
                             algos=self.ALGOS)
        result = engine.evaluate(problems)
        legacy = evaluate_many(problems, algos=self.ALGOS,
                               lp_iters=self.ITERS)
        assert result.plan.n_buckets >= 2
        assert result.plan.waste_reduction >= 0.30, (
            f"bucketing eliminated only "
            f"{result.plan.waste_reduction:.1%} of the padded-cell "
            f"waste (< 30%)")
        assert len(result.entries) == len(legacy)
        for got, want in zip(result.entries, legacy):
            assert got["costs"] == want["costs"]  # EXACT, per instance
            assert got["lb"] == pytest.approx(want["lb"], rel=1e-5)

    def test_merge_restores_submission_order(self):
        """Instances are distinct per index, so any merge scramble
        would move a cost to the wrong entry."""
        problems = _ragged_grid(shapes=5, seeds=1)[::-1]  # descending
        engine = FleetEngine(solver=SolverConfig(iters=150),
                             sweep=SweepConfig(max_buckets=3,
                                               bucket_overhead=0.0),
                             algos=("lp-map",))
        result = engine.evaluate(problems)
        # planner must have reordered (ascending footprint) internally
        assert result.plan.n_buckets >= 2
        assert list(result.plan.buckets[0].indices) != [0]
        for i, p in enumerate(problems):
            want = evaluate_many([p], algos=("lp-map",), lp_iters=150)[0]
            assert result.entries[i]["costs"] == want["costs"]


if _HAVE_HYPOTHESIS:
    # shapes come from a small menu so padded bucket shapes repeat and
    # the JIT cache amortizes across examples
    _MENU = [(15, 6), (25, 12), (40, 6), (40, 12)]

    class TestBucketedParityProperty:
        @given(st.lists(
            st.tuples(st.sampled_from(_MENU), st.integers(0, 3)),
            min_size=3, max_size=8))
        def test_bucketed_costs_match_single_bucket(self, draws):
            problems = [synthetic_batch(
                [SyntheticSpec(n=n, m=4, D=3, T=T, seed=seed)])[0]
                for (n, T), seed in draws]
            engine = FleetEngine(
                solver=SolverConfig(iters=120),
                sweep=SweepConfig(max_buckets=3, bucket_overhead=0.0),
                algos=("lp-map-f",))
            result = engine.evaluate(problems)
            legacy = evaluate_many(problems, algos=("lp-map-f",),
                                   lp_iters=120)
            flat = sorted(i for b in result.plan.buckets
                          for i in b.indices)
            assert flat == list(range(len(problems)))
            for got, want in zip(result.entries, legacy):
                assert got["costs"] == want["costs"]


class TestShardedSolve:
    def test_shard_dispatch_keeps_costs_exact(self):
        problems = _ragged_grid(shapes=5, seeds=1)
        algos = ("lp-map",)
        whole = FleetEngine(solver=SolverConfig(iters=200),
                            algos=algos).evaluate(problems)
        sharded = FleetEngine(solver=SolverConfig(iters=200),
                              sweep=SweepConfig(shard_size=2),
                              algos=algos).evaluate(problems)
        for a, b in zip(whole.entries, sharded.entries):
            assert a["costs"] == b["costs"]

    def test_shard_stats_one_per_dispatch(self):
        problems = _ragged_grid(shapes=5, seeds=1)
        engine = FleetEngine(
            solver=SolverConfig(tol=DEFAULT_TOL, iters=4000),
            sweep=SweepConfig(shard_size=2), algos=("lp-map",))
        result = engine.evaluate(problems)
        assert len(result.stats) == 3  # ceil(5 / 2) dispatches
        assert all(s.converged.all() for s in result.stats)


class TestWarmStartShim:
    def _instances(self, k=5):
        return synthetic_batch([SyntheticSpec(n=30, m=4, D=3, T=8, seed=s)
                                for s in range(k)])

    def test_zero_warm_start_is_an_error_not_off(self):
        with pytest.raises(ValueError, match="warm_start"):
            evaluate_many(self._instances(1), warm_start=0,
                          lp_tol=DEFAULT_TOL)

    def test_negative_warm_start_rejected(self):
        with pytest.raises(ValueError, match="warm_start"):
            evaluate_many(self._instances(1), warm_start=-2,
                          lp_tol=DEFAULT_TOL)

    def test_warm_start_still_requires_tol(self):
        with pytest.raises(ValueError, match="warm_start"):
            evaluate_many(self._instances(1), warm_start=1)

    def test_trailing_group_smaller_and_cold_started(self):
        """warm_start=2 over B=5: groups of 2/2/1 — the trailing group
        is smaller, cold-starts, and everything still converges with
        entries in submission order."""
        problems = self._instances(5)
        entries, stats = evaluate_many(
            problems, algos=("lp-map",), lp_tol=DEFAULT_TOL,
            lp_iters=4000, warm_start=2, return_stats=True)
        assert len(entries) == 5
        assert len(stats) == 3
        assert [s.iterations.shape[0] for s in stats] == [2, 2, 1]
        for e in entries:
            assert e["solver"]["converged"]
        # entries stay in submission order: each entry's per-instance
        # iteration telemetry lines up with the concatenated group stats
        # (cost identity with an unchained solve is NOT asserted — at
        # tol, different trajectories may round degenerate instances to
        # different epsilon-optimal vertices; see README)
        merged = np.concatenate([s.iterations for s in stats])
        assert [e["solver"]["iters"] for e in entries] \
            == [int(i) for i in merged]


class TestPlaceAndBackends:
    def test_engine_place_matches_loop_engine(self):
        problems = _ragged_grid(shapes=4, seeds=1)
        lp, _ = FleetEngine(solver=SolverConfig(iters=200)).solve(problems)
        maps = [r.mapping for r in lp]
        batched = FleetEngine().place(problems, maps, fit="similarity",
                                      filling=True)
        looped = FleetEngine(
            placement=PlacementConfig(engine="loop")).place(
                problems, maps, fit="similarity", filling=True)
        for p, a, b in zip(problems, batched, looped):
            np.testing.assert_array_equal(a.assign, b.assign)
            np.testing.assert_array_equal(a.node_type, b.node_type)
            # independent oracle on the ORIGINAL (untrimmed) timeline:
            # assignments are time-coordinate-free, so the checker's
            # slot-by-slot capacity audit holds there too
            assert_feasible(p, a)


class TestCompiledPlacementEngine:
    """PlacementConfig(engine='compiled') routes the protocol through
    the on-device stepper: identical costs, telemetry in timings."""

    def test_unknown_engine_names_valid_set(self):
        with pytest.raises(ValueError,
                           match=r"batched.*compiled.*loop"):
            PlacementConfig(engine="warp")

    def test_unknown_stepper_names_valid_set(self):
        problems = [trim_timeline(p)[0]
                    for p in _ragged_grid(shapes=1, seeds=1)]
        maps = [np.zeros(t.n, np.int64) for t in problems]
        with pytest.raises(ValueError, match=r"lockstep.*compiled"):
            place_many(problems, maps, placement="warp")

    def test_engine_place_compiled_matches_loop(self):
        problems = _ragged_grid(shapes=3, seeds=1)
        lp, _ = FleetEngine(solver=SolverConfig(iters=150)).solve(problems)
        maps = [r.mapping for r in lp]
        comp = FleetEngine(
            placement=PlacementConfig(engine="compiled")).place(
                problems, maps, fit="similarity", filling=True)
        looped = FleetEngine(
            placement=PlacementConfig(engine="loop")).place(
                problems, maps, fit="similarity", filling=True)
        for a, b in zip(comp, looped):
            np.testing.assert_array_equal(a.assign, b.assign)
            np.testing.assert_array_equal(a.node_type, b.node_type)

    def test_compiled_protocol_costs_and_telemetry(self):
        problems = _ragged_grid(shapes=2, seeds=2)
        algos = ("lp-map", "lp-map-f")
        base = FleetEngine(solver=SolverConfig(iters=150),
                           algos=algos).evaluate(problems)
        comp = FleetEngine(solver=SolverConfig(iters=150), algos=algos,
                           placement=PlacementConfig(engine="compiled")
                           ).evaluate(problems)
        for a, b in zip(base.entries, comp.entries):
            assert a["costs"] == b["costs"]
        tel_b = base.timings["placement"]
        assert tel_b["engine"] == "batched" and tel_b["calls"] >= 1
        tel_c = comp.timings["placement"]
        assert tel_c["engine"] == "compiled"
        assert tel_c["dispatches"] >= 1
        assert tel_c["fallbacks"] == 0
        assert set(tel_c["modes"]) <= {"type-parallel",
                                       "wave-sequential"}
        json.dumps(comp.timings)  # telemetry must stay JSON-clean


class TestFleetResult:
    def test_structured_output(self):
        problems = _ragged_grid(shapes=3, seeds=1)
        engine = FleetEngine(
            solver=SolverConfig(tol=DEFAULT_TOL, iters=4000),
            sweep=SweepConfig(max_buckets=2), algos=("lp-map",))
        result = engine.evaluate(problems)
        assert len(result) == 3
        assert result.algos == ("lp-map",)
        assert result.costs("lp-map") == [
            e["costs"]["lp-map"] for e in result.entries]
        # telemetry attached per entry in tol mode
        for e in result.entries:
            assert e["solver"]["iters"] > 0
        rows = result.to_rows()
        assert [r["instance"] for r in rows] == [0, 1, 2]
        for row in rows:
            assert {"lb", "cost[lp-map]", "normalized[lp-map]",
                    "wall_s[lp-map]", "solver.iters",
                    "solver.converged"} <= set(row)
        t = result.timings
        assert {"pack_s", "lp_s", "place_s", "total_s",
                "bucket_lp_s", "bucket_place_s"} <= set(t)
        assert len(t["bucket_lp_s"]) == result.plan.n_buckets
        blob = json.loads(result.to_json())
        assert blob["plan"]["buckets"] == result.plan.n_buckets
        assert len(blob["entries"]) == 3
        assert len(blob["solver"]) == len(result.stats)

    def test_warm_path_has_no_plan(self):
        problems = synthetic_batch(
            [SyntheticSpec(n=30, m=4, D=3, T=8, seed=s)
             for s in range(4)])
        engine = FleetEngine(
            solver=SolverConfig(tol=DEFAULT_TOL, iters=4000),
            sweep=SweepConfig(warm_start=2), algos=("lp-map",))
        result = engine.evaluate(problems)
        assert result.plan is None
        assert len(result.stats) == 2
        blob = json.loads(result.to_json())
        assert blob["plan"] is None


class TestSweepConfigMessages:
    """The mutual-exclusivity errors must name the config fields AND
    point at the serving-loop alternative (the API that DOES combine
    warm starts with per-tick shape bucketing)."""

    def test_bucketing_conflict_names_fields_and_alternative(self):
        with pytest.raises(ValueError) as ei:
            SweepConfig(warm_start=2, max_buckets=3)
        msg = str(ei.value)
        assert "SweepConfig.warm_start" in msg
        assert "SweepConfig.max_buckets" in msg
        assert "mutually exclusive" in msg
        assert "repro.serve.RightsizingService" in msg

    def test_sharding_conflict_names_fields_and_alternative(self):
        with pytest.raises(ValueError) as ei:
            SweepConfig(warm_start=2, shard_size=4)
        msg = str(ei.value)
        assert "SweepConfig.warm_start" in msg
        assert "SweepConfig.shard_size" in msg
        assert "mutually exclusive" in msg
        assert "repro.serve.RightsizingService" in msg


class TestWithOverrides:
    def test_routes_fields_across_config_family(self):
        eng = FleetEngine(solver=SolverConfig(tol=5e-3, iters=900))
        eng2 = eng.with_overrides(tol=1e-2, fit="first", max_buckets=3,
                                  algos=("lp-map",))
        assert eng2.solver.tol == 1e-2
        assert eng2.solver.iters == 900        # untouched field survives
        assert eng2.placement.fits == ("first",)
        assert eng2.sweep.max_buckets == 3
        assert eng2.algos == ("lp-map",)
        # the base engine is immutable
        assert eng.solver.tol == 5e-3 and eng.sweep.max_buckets == 1

    def test_whole_config_replacement(self):
        eng = FleetEngine(solver=SolverConfig(tol=5e-3))
        eng2 = eng.with_overrides(sweep=SweepConfig(max_buckets=4))
        assert eng2.sweep.max_buckets == 4
        assert eng2.solver.tol == 5e-3

    def test_whole_config_plus_field_override_composes(self):
        eng = FleetEngine()
        eng2 = eng.with_overrides(solver=SolverConfig(tol=5e-3),
                                  iters=1234)
        assert eng2.solver.tol == 5e-3 and eng2.solver.iters == 1234

    def test_unknown_field_names_the_known_set(self):
        with pytest.raises(ValueError) as ei:
            FleetEngine().with_overrides(fuel="ion")
        msg = str(ei.value)
        assert "unknown field 'fuel'" in msg
        assert "solver=/placement=/sweep=/algos=" in msg
        assert "tol" in msg and "max_buckets" in msg

    def test_derived_engine_revalidates(self):
        eng = FleetEngine(solver=SolverConfig(tol=5e-3))
        with pytest.raises(ValueError, match="mutually exclusive"):
            eng.with_overrides(warm_start=2, max_buckets=3)


class TestSolveInitGuards:
    def test_init_conflicts_with_warm_started_sweep(self):
        eng = FleetEngine(solver=SolverConfig(tol=DEFAULT_TOL, iters=500),
                          sweep=SweepConfig(warm_start=2),
                          algos=("lp-map",))
        problems = synthetic_batch(
            [SyntheticSpec(n=20, m=3, D=2, T=6, seed=s) for s in range(2)])
        _, (st,) = FleetEngine(
            solver=SolverConfig(tol=DEFAULT_TOL, iters=500)).solve(
                problems[:1])
        with pytest.raises(ValueError, match="SweepConfig.warm_start"):
            eng.solve(problems, init=st.state)

    def test_init_needs_single_bucket_plan(self):
        small = synthetic_batch([SyntheticSpec(n=8, m=2, D=2, T=4,
                                               seed=0)])
        large = synthetic_batch([SyntheticSpec(n=120, m=5, D=4, T=30,
                                               seed=1)])
        eng = FleetEngine(solver=SolverConfig(tol=DEFAULT_TOL, iters=500),
                          sweep=SweepConfig(max_buckets=4))
        _, (st,) = FleetEngine(
            solver=SolverConfig(tol=DEFAULT_TOL, iters=500)).solve(small)
        plan = eng.pack(small + large)
        assert plan.n_buckets > 1
        with pytest.raises(ValueError, match="single-bucket plan"):
            eng.solve(plan, init=st.state)

    def test_init_warm_resolve_matches_cold_cost(self):
        problems = synthetic_batch(
            [SyntheticSpec(n=24, m=4, D=3, T=8, seed=s) for s in range(3)])
        eng = FleetEngine(solver=SolverConfig(tol=DEFAULT_TOL, iters=4000))
        cold, cold_stats = eng.solve(problems)
        warm, warm_stats = eng.solve(problems,
                                     init=cold_stats[-1].state)
        for c, w in zip(cold, warm):
            assert w.converged
            # same tolerance contract either way
            assert abs(w.objective - c.objective) <= \
                2 * DEFAULT_TOL * max(1.0, abs(c.objective))
        # re-solving the SAME batch from its own solution exits early
        assert sum(int(i) for s in warm_stats for i in s.iterations) <= \
            sum(int(i) for s in cold_stats for i in s.iterations)


class TestEvaluateManyDeprecation:
    def _one(self):
        return synthetic_batch([SyntheticSpec(n=16, m=3, D=2, T=6,
                                              seed=0)])

    def test_default_call_emits_no_warning(self):
        import warnings as _w

        with _w.catch_warnings():
            _w.simplefilter("error", DeprecationWarning)
            evaluate_many(self._one(), algos=("penalty-map-f",))

    def test_legacy_kwarg_warns_with_config_equivalent(self):
        with pytest.warns(DeprecationWarning,
                          match=r"lp_iters -> SolverConfig\(iters=\.\.\.\)"):
            evaluate_many(self._one(), algos=("penalty-map-f",), lp_iters=300)

    def test_warning_joins_every_passed_kwarg(self):
        with pytest.warns(DeprecationWarning) as rec:
            evaluate_many(self._one(), algos=("penalty-map-f",),
                          placement="loop", lp_iters=300)
        msg = str(rec[0].message)
        assert "placement -> PlacementConfig(engine=...)" in msg
        assert "lp_iters -> SolverConfig(iters=...)" in msg
        assert "FleetEngine" in msg

    def test_shim_is_bit_stable_vs_engine(self):
        problems = self._one()
        with pytest.warns(DeprecationWarning):
            entries = evaluate_many(problems, algos=("lp-map",),
                                    lp_iters=400)
        engine = FleetEngine(solver=SolverConfig(iters=400),
                             algos=("lp-map",))
        result = engine.evaluate(problems)
        assert entries[0]["costs"] == result.entries[0]["costs"]
        assert entries[0]["lb"] == result.entries[0]["lb"]
