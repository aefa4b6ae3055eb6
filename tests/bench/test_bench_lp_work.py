"""The LP work function and the roofline share."""

import pytest

from bench.lp_work import (bytes_per_iteration, flops_per_iteration,
                           lp_work, roofline_pct)

V5E = {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def test_bytes_per_iteration_known_shape():
    # n=2000, m=13, D=2, T'=2000: x 26,000, y 52,000, w 52,000 elements
    assert bytes_per_iteration(2000, 13, 2, 2000) == \
        4 * (2 * 26_000 + 2 * 52_000 + 52_000) + 8 * 2000


def test_work_sums_lanes():
    b1, f1 = lp_work((1, 10, 3, 2, 8), [5])
    b2, f2 = lp_work((2, 10, 3, 2, 8), [5, 7])
    assert b2 == 12 * bytes_per_iteration(10, 3, 2, 8)
    assert f2 == 12 * flops_per_iteration(10, 3, 2, 8)
    assert (b1, f1) == (5 * bytes_per_iteration(10, 3, 2, 8),
                        5 * flops_per_iteration(10, 3, 2, 8))
    with pytest.raises(ValueError):
        lp_work((2, 10, 3, 2, 8), [5])


def test_lp_is_memory_bound():
    b, f = lp_work((2, 2000, 13, 2, 2000), [100, 100])
    assert b / V5E["hbm_bytes_per_s"] > f / V5E["flops_per_s"]


def test_roofline_share():
    b, f = lp_work((2, 2000, 13, 2, 2000), [100, 100])
    least = b / V5E["hbm_bytes_per_s"]
    assert roofline_pct(b, f, least, V5E) == pytest.approx(100.0)
    assert roofline_pct(b, f, 10 * least, V5E) == pytest.approx(10.0)
    assert roofline_pct(b, f, 0.0, V5E) is None
    assert roofline_pct(0, 0, 1.0, V5E) is None
