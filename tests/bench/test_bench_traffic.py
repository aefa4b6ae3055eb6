"""The benchmark's data generators: deterministic by seed and faithful
to the program's pool."""

import numpy as np
from bench import gct

def test_pool_is_the_programs_pool():
    from repro.workload.gct import gct_pool

    ours, theirs = gct.pool(), gct_pool()
    for key in ("dem", "start", "end", "cap"):
        np.testing.assert_array_equal(ours[key], theirs[key])


def test_cost_models_are_the_programs():
    from repro.workload.cost_models import gce_like_cost, homogeneous_cost

    cap = gct.MACHINE_SHAPES
    np.testing.assert_allclose(gct.node_cost(cap, "gce", 0.9),
                               gce_like_cost(cap, e=0.9))
    np.testing.assert_allclose(gct.node_cost(cap, "homogeneous"),
                               homogeneous_cost(cap))


def test_day_is_deterministic_with_distinct_starts():
    a = gct.instance(2000, 13, np.random.default_rng(2**31 + 5),
                     distinct_starts=True)
    b = gct.instance(2000, 13, np.random.default_rng(2**31 + 5),
                     distinct_starts=True)
    c = gct.instance(2000, 13, np.random.default_rng(7), distinct_starts=True)
    np.testing.assert_array_equal(a["dem"], b["dem"])
    assert len(np.unique(a["start"])) == 2000
    assert len(np.unique(c["start"])) == 2000
    assert not np.array_equal(a["start"], c["start"])
    assert sorted(map(tuple, a["cap"])) == sorted(map(tuple, gct.MACHINE_SHAPES))


def test_every_seed_plans_the_same_days_in_another_order():
    from bench.harness import config
    from bench.reference import fingerprint
    from bench.runners.offline import draw_days

    cfg = dict(config("gct2019-day"), tasks=300)
    a = draw_days(cfg, 4, 2**40 + 3)
    b = draw_days(cfg, 4, 2**40 + 3)
    c = draw_days(cfg, 4, 9)
    for x, y, z in zip(a, b, c):
        np.testing.assert_array_equal(x["dem"], y["dem"])
        np.testing.assert_array_equal(x["start"], y["start"])
        assert not np.array_equal(x["start"], z["start"])
        assert fingerprint(x["dem"], x["start"], x["end"], x["cap"],
                           x["cap"].sum(axis=1)) == \
            fingerprint(z["dem"], z["start"], z["end"], z["cap"],
                        z["cap"].sum(axis=1))
    for day in a:
        assert len(np.unique(day["start"])) == 300 and len(day["cap"]) == 13
    # a different day each grid
    assert len({tuple(np.sort(day["start"])) for day in a}) == 4
