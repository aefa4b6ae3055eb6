"""Offline: the sound program is correct, and the lower-precision
control (demands cast to bfloat16, rounded toward zero, before the
program sees them) is not: its plans overfill nodes under the demands
as stated.  At 500 tasks, a size a test run holds."""

import bench_cells as bc


def test_sound_run_is_correct():
    out = bc.run(bc.offline_cell(seed=21, tasks=500, seconds=0.5))
    assert out["correct"], bc.failed_checks(out)
    assert out["attempted"] >= 2 and out["failed"] == 0


def test_bf16_control_is_not_correct():
    out = bc.run(bc.offline_cell(seed=21, tasks=500, seconds=0.5,
                                 demand_cast=bc.truncate_bf16))
    assert not out["correct"]
    assert "overload" in bc.failed_checks(out)
