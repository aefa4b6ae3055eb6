"""End-to-end metric arithmetic: a time per fleet over the whole window."""

import pytest

from bench.runners.offline import plan_seconds


def test_plan_seconds_is_window_over_fleets():
    assert plan_seconds(10.0, 40.0, plans=5, fleets=2) == pytest.approx(3.0)


def test_a_slow_plan_moves_plan_seconds():
    # ten plans of 1 s, then one that stalls for 9 s: the stall is in
    # the window and in the mean, not dropped as an outlier
    steady = plan_seconds(0.0, 10.0, plans=10, fleets=2)
    stalled = plan_seconds(0.0, 19.0, plans=11, fleets=2)
    assert steady == pytest.approx(0.5)
    assert stalled == pytest.approx(19.0 / 22)
