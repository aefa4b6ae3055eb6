"""Small CPU-sized copies of the benchmark's cells, for the tests."""

from __future__ import annotations

import time

from bench import harness
from bench.control import truncate_bf16  # noqa: F401 (used as bc.truncate_bf16)


def offline_cell(seed=12345678901, seconds=2.0, tasks=200, trace=False,
                 demand_cast=None):
    cfg = harness.config("gct2019-day")
    cfg["tasks"] = tasks
    return harness.Cell("offline.day2000", cfg, harness.traffic("back_to_back"),
                        seed, seconds, trace, demand_cast)


def run(cell):
    return harness.run_cell(cell, time.perf_counter())


def failed_checks(out):
    return [c.name for c in out["checks"] if not c.ok]
