"""The plain references against the program's own exact paths."""

import numpy as np
import pytest

from bench import gct, reference


def _small(seed=3, n=60, m=5):
    d = gct.instance(n, m, np.random.default_rng(seed))
    d["cost"] = gct.node_cost(d["cap"], "gce", 1.0)
    return d


@pytest.mark.parametrize("seed", [3, 4])
def test_lp_optimum_is_the_programs_highs_optimum(seed):
    from repro.core import NodeTypes, Problem, trim_timeline
    from repro.core.lp_map import solve_lp

    d = _small(seed)
    p = Problem(dem=d["dem"], start=d["start"], end=d["end"], T=d["T"],
                node_types=NodeTypes(cap=d["cap"], cost=d["cost"]))
    want = solve_lp(trim_timeline(p)[0]).objective
    got = reference.lp_optimum(d["dem"], d["start"], d["end"], d["cap"],
                               d["cost"])
    assert got == pytest.approx(want, rel=1e-7)


def test_plan_faults_sees_an_overfull_node():
    dem = np.array([[0.6, 0.1], [0.6, 0.1], [0.3, 0.1]])
    start, end = np.array([0, 5, 20]), np.array([10, 15, 30])
    cap = np.array([[1.0, 1.0]])
    ok = reference.plan_faults(dem, start, end, cap, [0, 0], [0, 1, 0], 1)
    assert ok == (0.0, 0)
    over, lost = reference.plan_faults(dem, start, end, cap, [0], [0, 0, 0],
                                       1)
    assert over == pytest.approx(0.2) and lost == 0
    # tasks that never overlap may share a node
    assert reference.plan_faults(dem, start, end, cap, [0, 0], [0, 1, 1],
                                 1)[0] == 0.0
    assert reference.plan_faults(dem, start, end, cap, [0], [0, 0, 3],
                                 1)[1] == 1
    assert reference.plan_faults(dem, start, end, cap, [0], [0, 0],
                                 1)[1] == 3


def test_fingerprint_ignores_order_and_sees_values():
    d = _small(6)
    key = reference.fingerprint(d["dem"], d["start"], d["end"], d["cap"],
                                d["cost"])
    rows = np.random.default_rng(1).permutation(len(d["dem"]))
    types = np.random.default_rng(2).permutation(len(d["cap"]))
    assert key == reference.fingerprint(
        d["dem"][rows], d["start"][rows], d["end"][rows], d["cap"][types],
        d["cost"][types])
    dem = d["dem"].copy()
    dem[0, 0] *= 1.001
    assert key != reference.fingerprint(dem, d["start"], d["end"], d["cap"],
                                        d["cost"])


def _cell_lps():
    from bench import harness
    from bench.runners.offline import draw_days

    entry = harness.find(harness.benchmark()["workloads"], "offline.day2000",
                         "workload")
    cfg = harness.config(entry["config"])
    days = draw_days(cfg, harness.traffic(entry["traffic"])["grids"], 2**35)
    return [(d["dem"], d["start"], d["end"], d["cap"],
             gct.node_cost(d["cap"], cm, cfg["gce_e"]))
            for d in days for cm in cfg["cost_models"]]


def test_every_lp_of_the_cell_has_a_stored_optimum():
    table = reference.stored_optima()
    assert all(reference.fingerprint(*lp) in table for lp in _cell_lps())


def test_a_stored_optimum_is_the_references():
    lp = _cell_lps()[3]
    stored = reference.stored_optima()[reference.fingerprint(*lp)]
    assert reference.lp_optimum(*lp) == pytest.approx(stored, rel=1e-7)
