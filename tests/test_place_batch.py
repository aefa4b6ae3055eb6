"""Batched greedy placement engine tests.

Covers the contracts of ``repro.core.place_batch`` and the compiled
on-device stepper ``repro.core.place_step``:

  * hypothesis property suite — on random ragged instance grids (mixed
    n, T, D, m) with random feasible mappings, ALL THREE engines agree
    exactly: ``place_many`` (numpy lockstep), ``place_many(placement=
    'compiled')`` (on-device stepper), and the looped ``two_phase``
    (same node purchases, same ``assign``, same cost) for all four
    {fit} x {filling} combos, and ``verify`` holds on every solution;
  * protocol parity — ``evaluate_many(placement='batched')`` produces
    the same costs as the per-instance placement loop;
  * stepper dispatch — unknown ``place_many(placement=...)`` values
    raise a ``ValueError`` naming the valid stepper set, telemetry
    reports the stepper actually used, and oversized pools fall back
    to the numpy engine with identical placements;
  * the acceptance gates — identical placements on a ragged B>=16 grid
    with the similarity-fit placement phase of a cold fleet sweep
    >=3x faster than the per-instance loop (numpy lockstep), and the
    compiled stepper bit-identical on a B>=64 quick fleet grid with
    its (warm) similarity phase >=2x faster than the per-instance
    loop, dispatching once per phase boundary instead of per step.
"""

import time

import numpy as np
import pytest

try:  # the property suite needs the 'test' extra; the rest runs without
    from hypothesis import given, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:
    HAVE_HYPOTHESIS = False

from repro.core import (  # noqa: E402
    assert_feasible,
    evaluate_many,
    pack_problems,
    penalty_map,
    place_many,
    solve_lp_many,
    trim_timeline,
    two_phase,
    verify,
)
from repro.core.placement import FIT_POLICIES  # noqa: E402
from repro.workload import SyntheticSpec, synthetic_batch, \
    synthetic_instance  # noqa: E402

RNG = np.random.default_rng(11)

ALL_COMBOS = [(fit, filling) for fit in FIT_POLICIES
              for filling in (False, True)]


def _ragged_problems(extra=()):
    """Mixed (n, m, D, T) instances — the ragged-batch fixture."""
    shapes = [(50, 3, 2, 12), (80, 5, 4, 24), (30, 2, 3, 8),
              (120, 6, 5, 30), (64, 4, 2, 16), (25, 3, 3, 10),
              *extra]
    return [synthetic_instance(SyntheticSpec(n=n, m=m, D=D, T=T, seed=s))
            for s, (n, m, D, T) in enumerate(shapes)]


def _assert_equal_solutions(got, want):
    np.testing.assert_array_equal(got.node_type, want.node_type)
    np.testing.assert_array_equal(got.assign, want.assign)


def _random_grid(seed):
    """A small ragged batch of instances plus random feasible mappings."""
    rng = np.random.default_rng(seed)
    problems, mappings = [], []
    for _ in range(int(rng.integers(2, 6))):
        n = int(rng.integers(1, 35))
        m = int(rng.integers(1, 5))
        D = int(rng.integers(1, 4))
        T = int(rng.integers(1, 14))
        spec = SyntheticSpec(n=n, m=m, D=D, T=T,
                             seed=int(rng.integers(0, 2**31 - 1)))
        p = synthetic_instance(spec)
        t, _ = trim_timeline(p)
        problems.append(t)
        # a random feasible node-type per task (demands in Table-I
        # ranges fit every type, but pick via the feasibility mask to
        # stay honest on degenerate draws)
        from repro.core.problem import feasible_types

        feas = feasible_types(t)
        pick = np.array([rng.choice(np.flatnonzero(row)) for row in feas])
        mappings.append(pick.astype(np.int64))
    return problems, mappings


@pytest.mark.skipif(not HAVE_HYPOTHESIS,
                    reason="install the 'test' extra")
class TestPlaceManyProperty:
    if HAVE_HYPOTHESIS:
        # example budget comes from the active profile (conftest.py)
        @given(st.integers(0, 2**31 - 1))
        def test_matches_looped_two_phase_exactly(self, seed):
            """All three engines (loop, numpy lockstep, compiled
            stepper) place bit-identically on random ragged grids."""
            problems, mappings = _random_grid(seed)
            batch = pack_problems(problems)
            for fit, filling in ALL_COMBOS:
                sols = place_many(batch, mappings, fit=fit,
                                  filling=filling)
                comp = place_many(batch, mappings, fit=fit,
                                  filling=filling,
                                  placement="compiled")
                for t, mp, got, got_c in zip(batch.problems, mappings,
                                             sols, comp):
                    want = two_phase(t, mp, fit=fit, filling=filling)
                    _assert_equal_solutions(got, want)
                    _assert_equal_solutions(got_c, want)
                    assert got.cost(t) == want.cost(t)
                    verify(t, got)
                    # independent oracle (repro.core.checker): shares
                    # no code with verify() or the engines
                    assert_feasible(t, got)


class TestPlaceManyFixtures:
    def test_ragged_grid_all_combos_and_mappings(self):
        """B>=16 ragged grid: every combo x {penalty-avg, penalty-max,
        LP} mapping family is bit-identical to the loop."""
        problems = _ragged_problems(
            extra=[(40 + 7 * i, 2 + i % 4, 1 + i % 5, 6 + i)
                   for i in range(12)])
        assert len(problems) >= 16
        batch = pack_problems(problems)
        mapsets = [[penalty_map(t, kind) for t in batch.problems]
                   for kind in ("avg", "max")]
        mapsets.append([r.mapping for r in
                        solve_lp_many(batch, iters=150)])
        for maps in mapsets:
            for fit, filling in ALL_COMBOS:
                sols = place_many(batch, maps, fit=fit, filling=filling)
                for t, mp, got in zip(batch.problems, maps, sols):
                    want = two_phase(t, mp, fit=fit, filling=filling)
                    _assert_equal_solutions(got, want)
                    verify(t, got)
                    assert_feasible(t, got)  # independent oracle

    def test_mapping_validation(self):
        t, _ = trim_timeline(synthetic_instance(SyntheticSpec(
            n=10, m=2, D=2, T=6, seed=0)))
        with pytest.raises(ValueError):
            place_many([t], [np.zeros(t.n, np.int64)], fit="worst")
        with pytest.raises(ValueError):
            place_many([t], [])

    def test_rejects_unknown_stepper(self):
        """Unknown placement= values raise a ValueError that names the
        valid stepper set (not just unknown backends)."""
        from repro.core.place_batch import PLACEMENT_STEPPERS

        t, _ = trim_timeline(synthetic_instance(SyntheticSpec(
            n=10, m=2, D=2, T=6, seed=0)))
        mp = [np.zeros(t.n, np.int64)]
        with pytest.raises(ValueError, match="lockstep.*compiled"):
            place_many([t], mp, placement="warp")
        for name in PLACEMENT_STEPPERS:  # every advertised name works
            place_many([t], mp, placement=name)

    def test_stepper_telemetry_and_fallback(self):
        """telemetry= reports the stepper actually used; a pool-cell
        budget of zero forces the compiled path back onto the numpy
        engine with identical placements."""
        from repro.core import place_step

        problems = _ragged_problems()[:3]
        batch = pack_problems(problems)
        maps = [penalty_map(t, "avg") for t in batch.problems]
        tel = {}
        sols_l = place_many(batch, maps, telemetry=tel)
        assert tel["engine"] == "lockstep" and tel["waves"] >= 1
        tel = {}
        sols_c = place_many(batch, maps, placement="compiled",
                            telemetry=tel)
        assert tel["engine"] == "compiled"
        assert tel["dispatches"] >= 1
        for a, b in zip(sols_l, sols_c):
            _assert_equal_solutions(a, b)
        old = place_step.MAX_POOL_CELLS
        try:
            place_step.MAX_POOL_CELLS = 0
            tel = {}
            sols_f = place_many(batch, maps, placement="compiled",
                                telemetry=tel)
        finally:
            place_step.MAX_POOL_CELLS = old
        assert tel["engine"] == "lockstep-fallback"
        assert "fallback" in tel
        for a, b in zip(sols_l, sols_f):
            _assert_equal_solutions(a, b)

    def test_infeasible_mapping_raises(self):
        """A mapping that sends a task to a type it cannot fit raises
        exactly like two_phase."""
        from repro.core import NodeTypes, Problem

        t = Problem(dem=np.array([[0.9], [0.4]]),
                    start=np.array([0, 0]), end=np.array([1, 1]),
                    node_types=NodeTypes(cap=np.array([[1.0], [0.5]]),
                                         cost=np.array([1.0, 0.4])),
                    T=2)
        bad = np.array([1, 1])  # task 0 (0.9) cannot fit type 1 (0.5)
        with pytest.raises(RuntimeError):
            two_phase(t, bad)
        with pytest.raises(RuntimeError):
            place_many([t], [bad])


def _window_grid():
    """Two instances on an 80-slot timeline whose narrow tasks (1-4
    slots) live at opposite ends in each node-type phase: instance 0
    maps its early tasks to the cheap type 0 and instance 1 its late
    ones, so every step's window is the union of two far-apart spans
    and, once instance 0's shorter list runs out, one narrow span,
    strictly inside T'.  Many overlapping tasks buy nodes in the middle
    of those windows, and later tasks score them outside."""
    from repro.core import NodeTypes, Problem

    rng = np.random.default_rng(5)
    T = 80
    nt = NodeTypes(cap=np.array([[1.0, 1.0], [1.2, 0.8]]),
                   cost=np.array([1.0, 1.6]))
    problems, maps = [], []
    for early in (True, False):
        start = np.sort(np.concatenate([np.arange(T),
                                        rng.integers(0, T, 40)]))
        end = np.minimum(start + rng.integers(0, 4, len(start)), T - 1)
        problems.append(Problem(dem=rng.uniform(0.1, 0.6, (len(start), 2)),
                                start=start, end=end, node_types=nt, T=T))
        cut = 25 if early else 50
        maps.append(np.where((start < cut) == early, 0, 1).astype(np.int64))
    return problems, maps


class TestStepWindow:
    @pytest.mark.parametrize("fit,filling", ALL_COMBOS)
    def test_windowed_steps_place_like_the_loop(self, fit, filling):
        problems, maps = _window_grid()
        batch = pack_problems(problems)
        assert batch.Tp >= 64
        tel = {}
        sols = place_many(batch, maps, fit=fit, filling=filling,
                          telemetry=tel)
        assert tel["window_slots"] < tel["slots"]
        for t, mp, got in zip(batch.problems, maps, sols):
            want = two_phase(t, mp, fit=fit, filling=filling)
            _assert_equal_solutions(got, want)
            assert got.cost(t) == want.cost(t)
            assert len(got.node_type) > 2  # nodes bought mid-phase

    @pytest.mark.parametrize("fit,filling", ALL_COMBOS)
    def test_window_counts(self, fit, filling):
        problems, maps = _window_grid()
        batch = pack_problems(problems)
        tel = {}
        place_many(batch, maps, fit=fit, filling=filling, telemetry=tel)
        assert 0 < tel["window_slots"] <= tel["slots"]
        assert tel["slots"] == tel["steps"] * batch.Tp

    def test_whole_timeline_tasks_read_every_slot(self):
        # the batch is packed as given: every task spans all 12 slots
        from repro.core import NodeTypes, Problem

        t = Problem(dem=RNG.uniform(0.1, 0.5, (9, 2)),
                    start=np.zeros(9, np.int64),
                    end=np.full(9, 11), T=12,
                    node_types=NodeTypes(cap=np.ones((2, 2)),
                                         cost=np.array([1.0, 2.0])))
        batch = pack_problems([t], assume_trimmed=True)
        tel = {}
        place_many(batch, [np.arange(9) % 2], fit="similarity",
                   filling=True, telemetry=tel)
        assert tel["window_slots"] == tel["slots"] == tel["steps"] * 12

    def test_single_slot_tasks_read_one_slot_a_step(self):
        from repro.core import NodeTypes, Problem

        t = Problem(dem=RNG.uniform(0.1, 0.9, (15, 1)),
                    start=np.arange(15), end=np.arange(15), T=15,
                    node_types=NodeTypes(cap=np.ones((3, 1)),
                                         cost=np.array([1.0, 2.0, 3.0])))
        tel = {}
        place_many([t], [np.arange(15) % 3], fit="first", filling=True,
                   telemetry=tel)
        assert tel["steps"] >= 15  # own-pack and cross-fill attempts
        assert tel["window_slots"] == tel["steps"]
        assert tel["slots"] == 15 * tel["steps"]


def _miss_grid(B):
    """B instances whose cross-fill candidates mostly cannot fit: the
    cheap-per-capacity type 0 is filled first, and most tasks mapped to
    the larger type 1 demand more than a type-0 node holds in some
    dimension, so they miss every node of type 0's pool; the rest fit
    wherever capacity is left."""
    from repro.core import NodeTypes, Problem

    nt = NodeTypes(cap=np.array([[1.0, 1.0], [2.0, 2.0]]),
                   cost=np.array([1.0, 2.5]))
    problems, maps = [], []
    for b in range(B):
        rng = np.random.default_rng(100 + b)
        n, T = 60 + 5 * b, 30
        start = rng.integers(0, T, n)
        end = np.minimum(start + rng.integers(0, 12, n), T - 1)
        dem = rng.uniform(0.05, 0.3, (n, 2))
        big = rng.random(n) < 0.4
        dem[big, rng.integers(0, 2, int(big.sum()))] = \
            rng.uniform(1.05, 1.9, int(big.sum()))
        mp = np.where(big | (rng.random(n) < 0.4), 1, 0).astype(np.int64)
        problems.append(Problem(dem=dem, start=start, end=end,
                                node_types=nt, T=T))
        maps.append(mp)
    return problems, maps


class TestCrossFillSkip:
    @pytest.mark.parametrize("fit", FIT_POLICIES)
    @pytest.mark.parametrize("B", [1, 2, 16])
    def test_skipping_misses_places_like_the_loop(self, B, fit):
        problems, maps = _miss_grid(B)
        batch = pack_problems(problems)
        tel = {}
        sols = place_many(batch, maps, fit=fit, filling=True,
                          telemetry=tel)
        for t, mp, got in zip(batch.problems, maps, sols):
            want = two_phase(t, mp, fit=fit, filling=True)
            _assert_equal_solutions(got, want)
            assert got.cost(t) == want.cost(t)
            assert_feasible(t, got)
        assert 0 < tel["fill_skipped"] <= tel["fill_attempts"]

    def test_every_cross_fill_step_places_a_task(self):
        # one instance: a step places one task, own-pack or cross-fill
        problems, maps = _miss_grid(1)
        tel = {}
        place_many(problems, maps, fit="first", filling=True,
                   telemetry=tel)
        assert tel["steps"] == problems[0].n
        assert tel["fill_skipped"] > 0

    @pytest.mark.parametrize("fit", FIT_POLICIES)
    def test_no_filling_counts_no_attempt(self, fit):
        problems, maps = _miss_grid(2)
        tel = {}
        place_many(problems, maps, fit=fit, filling=False, telemetry=tel)
        assert tel["fill_attempts"] == tel["fill_skipped"] == 0


def _tie_grid(B=16, T=31):
    """B instances on an odd 31-slot timeline in which a probe task
    meets two classes of tied type-0 nodes.  Nine or more openers
    (demand > 0.5, spans early and of random length) each buy a node;
    the mid tasks, equal to one another, then load nodes 0 .. n_mid - 1
    alike, and the probe's span starts inside theirs.  Over the probe's
    span the mid nodes hold bit-identical remaining capacity, and so do
    the untouched nodes n_mid, ...; outside it every node differs by
    its opener.  Skewed demands make either class win in some
    instances.  Type-1 tasks, early, cross-fill afterwards.  Returns
    the problems, mappings, probe ids and mid-task counts."""
    from repro.core import NodeTypes, Problem

    nt = NodeTypes(cap=np.array([[1.0, 1.0], [2.0, 2.0]]),
                   cost=np.array([1.0, 2.5]))
    problems, maps, probes, n_mids = [], [], [], []
    for b in range(B):
        rng = np.random.default_rng(400 + b)
        n_open = int(rng.integers(9, 15))
        n_mid = int(rng.integers(2, n_open - 2))
        s_mid = int(rng.integers(8, 12))
        e_mid = s_mid + int(rng.integers(3, 8))
        ps = int(rng.integers(s_mid + 1, e_mid))
        pe = int(rng.integers(e_mid + 1, T - 1))
        n_fill = 12
        fill_s = rng.integers(0, s_mid - 2, n_fill)
        start = np.concatenate([np.zeros(n_open, np.int64),
                                np.full(n_mid, s_mid), [ps], fill_s])
        end = np.concatenate([
            rng.integers(0, s_mid - 1, n_open), np.full(n_mid, e_mid),
            [pe], np.minimum(fill_s + rng.integers(0, 4, n_fill),
                             s_mid - 2)])
        mid = rng.permutation([rng.uniform(0.55, 0.75),
                               rng.uniform(0.0, 0.2)])
        probe = rng.permutation([rng.uniform(0.01, 0.05),
                                 rng.uniform(0.1, 0.25)])
        dem = np.concatenate([rng.uniform(0.51, 0.95, (n_open, 2)),
                              np.tile(mid, (n_mid, 1)), probe[None],
                              rng.uniform(0.05, 0.4, (n_fill, 2))])
        mp = np.zeros(len(start), np.int64)
        mp[-n_fill:] = 1
        problems.append(Problem(dem=dem, start=start, end=end,
                                node_types=nt, T=T))
        maps.append(mp)
        probes.append(n_open + n_mid)
        n_mids.append(n_mid)
    return problems, maps, probes, n_mids


class TestSimilarityScoring:
    def test_tied_nodes_pick_the_lowest_index_like_the_loop(self):
        problems, maps, probes, n_mids = _tie_grid()
        batch = pack_problems(problems, assume_trimmed=True)
        assert batch.Tp % 2 == 1
        sols = place_many(batch, maps, fit="similarity", filling=True)
        picked = []
        for t, mp, got, p, n_mid in zip(batch.problems, maps, sols,
                                        probes, n_mids):
            want = two_phase(t, mp, fit="similarity", filling=True)
            _assert_equal_solutions(got, want)
            assert got.cost(t) == want.cost(t)
            # the lowest index of the mid class or of the untouched one
            assert got.assign[p] in (0, n_mid)
            picked.append(got.assign[p] == 0)
        assert 0 < sum(picked) < len(picked)  # both classes win

    def test_robust_scenarios_place_like_the_loop(self):
        from repro.stochastic import fan_out, gct_forecast

        fc = gct_forecast(n=200, m=6, seed=3)
        assert fc.burst_prob > 0
        batch = pack_problems(fan_out(fc, K=16, seed=7).problems)
        maps = [r.mapping for r in solve_lp_many(batch, iters=2000,
                                                 tol=5e-3)]
        sols = place_many(batch, maps, fit="similarity", filling=True)
        for t, mp, got in zip(batch.problems, maps, sols):
            want = two_phase(t, mp, fit="similarity", filling=True)
            _assert_equal_solutions(got, want)
            assert got.cost(t) == want.cost(t)


class TestRangeMin:
    @pytest.mark.parametrize("T", [1, 2, 7, 32, 45])
    def test_queries_match_brute_force_after_updates(self, T):
        from repro.core.place_batch import _RangeMin

        rng = np.random.default_rng(T)
        N, D = 3, 2
        nodes = rng.uniform(0.5, 2.0, (N, T, D))
        table = _RangeMin(nodes.copy())
        s, e = np.triu_indices(T)  # every span [s, e]
        lo, hi = table.keys(s, e)
        for _ in range(25):
            i = int(rng.integers(N))
            a = int(rng.integers(T))
            z = int(rng.integers(a, T))
            dem = rng.uniform(0.0, 0.3, D)
            table.take(i, a, z, dem)
            nodes[i, a: z + 1] -= dem
            np.testing.assert_array_equal(table.nodes, nodes)
            for j in range(N):
                want = np.array([nodes[j, x: y + 1].min(axis=0)
                                 for x, y in zip(s, e)])
                np.testing.assert_array_equal(table.min(j, lo, hi), want)

    def test_reuses_a_large_enough_buffer(self):
        from repro.core.place_batch import _RangeMin

        rng = np.random.default_rng(0)
        big = _RangeMin(rng.uniform(size=(4, 20, 2)))
        small = _RangeMin(rng.uniform(size=(2, 9, 2)), big.buf)
        assert small.buf is big.buf
        assert _RangeMin(rng.uniform(size=(5, 20, 2)), big.buf).buf \
            is not big.buf


class TestEvaluateManyPlacement:
    def test_batched_placement_matches_loop(self):
        problems = _ragged_problems()[:4]
        got = evaluate_many(problems, lp_iters=250)
        want = evaluate_many(problems, lp_iters=250, placement="loop")
        for g, w in zip(got, want):
            assert g["costs"] == w["costs"]
            assert g["lb"] == w["lb"]
            for a in g["normalized"]:
                assert g["normalized"][a] == pytest.approx(
                    w["normalized"][a], rel=1e-12)
            assert set(g["wall_s"]) == set(w["wall_s"])

    def test_rejects_unknown_placement(self):
        with pytest.raises(ValueError):
            evaluate_many(_ragged_problems()[:1], placement="bogus")


class TestCompiledPlacementAcceptance:
    """ISSUE 5 acceptance: on a B>=64 quick fleet grid the compiled
    stepper places bit-identically to BOTH the numpy lockstep engine
    and ``two_phase`` on every {fit} x {filling} combo, dispatches to
    the device once per node-type phase boundary (once per CALL in the
    type-parallel non-filling plan) instead of once per placement
    step, and its warm similarity-fit phase runs >=2x faster than the
    per-instance loop.  (Against the numpy lockstep engine, CPU hosts
    sit near parity — XLA's elementwise kernels are ~2x slower per
    element than numpy's — so the 2x gate pins the per-step
    host-dispatch baseline; the lockstep ratio is benchmark telemetry,
    see docs/benchmarks.md.)"""

    def _fleet(self):
        rng = np.random.default_rng(5)
        specs = [SyntheticSpec(n=24 + 4 * i, m=4, D=3, T=8, seed=s)
                 for i in range(2) for s in range(32)]   # B = 64
        problems = [trim_timeline(p)[0] for p in synthetic_batch(specs)]
        batch = pack_problems(problems)
        from repro.core.problem import feasible_types

        maps = [np.array([rng.choice(np.flatnonzero(row))
                          for row in feasible_types(t)], np.int64)
                for t in batch.problems]
        return batch, maps

    def test_bit_identical_all_combos_b64(self):
        batch, maps = self._fleet()
        assert batch.B >= 64
        for fit, filling in ALL_COMBOS:
            lock = place_many(batch, maps, fit=fit, filling=filling)
            comp = place_many(batch, maps, fit=fit, filling=filling,
                              placement="compiled")
            for a, b in zip(lock, comp):
                _assert_equal_solutions(b, a)
            for b_i in range(0, batch.B, 16):  # spot-check the loop
                want = two_phase(batch.problems[b_i], maps[b_i],
                                 fit=fit, filling=filling)
                _assert_equal_solutions(comp[b_i], want)

    def _ratio(self, batch, maps, rounds=3):
        t_loop = t_comp = float("inf")
        for _ in range(rounds):  # interleaved: both sides share load
            t0 = time.perf_counter()
            looped = [two_phase(t, mp, fit="similarity")
                      for t, mp in zip(batch.problems, maps)]
            t_loop = min(t_loop, time.perf_counter() - t0)
            t0 = time.perf_counter()
            sols = place_many(batch, maps, fit="similarity",
                              placement="compiled")
            t_comp = min(t_comp, time.perf_counter() - t0)
        for got, want in zip(sols, looped):
            _assert_equal_solutions(got, want)
        return t_loop / max(t_comp, 1e-9)

    def test_one_dispatch_and_similarity_phase_2x(self):
        batch, maps = self._fleet()
        tel = {}
        place_many(batch, maps, fit="similarity", placement="compiled",
                   telemetry=tel)  # warmup: pay the XLA compiles here
        assert tel["engine"] == "compiled"
        # the whole non-filling placement is ONE device dispatch (the
        # type-parallel plan); the numpy engine re-enters Python every
        # step, i.e. ~max-tasks-per-type times per wave
        assert tel["mode"] == "type-parallel"
        assert tel["dispatches"] == 1
        tel_f = {}
        place_many(batch, maps, fit="similarity", filling=True,
                   placement="compiled", telemetry=tel_f)
        assert tel_f["mode"] == "wave-sequential"
        assert tel_f["dispatches"] <= 2 * tel_f["waves"]
        ratio = self._ratio(batch, maps)
        if ratio < 2.0:  # one retry: CI boxes share noisy cores
            ratio = max(ratio, self._ratio(batch, maps))
        assert ratio >= 2.0, (
            f"compiled similarity placement speedup {ratio:.1f}x < 2x "
            f"vs the per-instance loop")


class TestPlacementAcceptance:
    """The acceptance gate, analogous to PR 1's LP speedup smoke: on a
    seed-replicated fleet grid the lockstep engine must place exactly
    like the loop, and the similarity-fit scoring phase (the engine's
    dot-product/best-fit hot loop) must run >=3x faster cold.
    """

    def _fleet(self):
        specs = [SyntheticSpec(n=28 + 4 * i, m=3, D=4, T=10, seed=s)
                 for i in range(4) for s in range(64)]
        problems = [trim_timeline(p)[0] for p in synthetic_batch(specs)]
        batch = pack_problems(problems)
        maps = [penalty_map(t, "avg") for t in batch.problems]
        return batch, maps

    def _ratio(self, batch, maps, rounds=3):
        t_loop = t_batch = float("inf")
        for _ in range(rounds):  # interleaved: both sides share load
            t0 = time.perf_counter()
            looped = [two_phase(t, mp, fit="similarity")
                      for t, mp in zip(batch.problems, maps)]
            t_loop = min(t_loop, time.perf_counter() - t0)
            t0 = time.perf_counter()
            sols = place_many(batch, maps, fit="similarity")
            t_batch = min(t_batch, time.perf_counter() - t0)
        for got, want in zip(sols, looped):
            _assert_equal_solutions(got, want)
        return t_loop / max(t_batch, 1e-9)

    def test_identical_and_similarity_phase_3x(self):
        batch, maps = self._fleet()
        # all four combos place identically on the fleet grid
        for fit, filling in ALL_COMBOS:
            sols = place_many(batch, maps, fit=fit, filling=filling)
            spot = list(range(0, batch.B, 16))  # full loop is the slow
            # comparator; spot-check here, timing below re-checks all
            for b in spot:
                want = two_phase(batch.problems[b], maps[b], fit=fit,
                                 filling=filling)
                _assert_equal_solutions(sols[b], want)
        ratio = self._ratio(batch, maps)
        if ratio < 3.0:  # one retry: CI boxes share noisy cores
            ratio = max(ratio, self._ratio(batch, maps))
        assert ratio >= 3.0, (
            f"similarity placement phase speedup {ratio:.1f}x < 3x")
