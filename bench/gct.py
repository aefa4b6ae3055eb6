"""GCT-2019-like task pool and instance samplers, kept with the benchmark.

A copy of the pool and the paper-protocol sampler of
``repro.workload.gct`` (and of the two cost models it prices node
types with), so that the benchmark's data cannot move when the program
changes.  Same seed, same draws: ``pool()`` equals the program's
``gct_pool()`` value for value.

``day(n, m, seed, distinct_starts=True)`` is the offline configuration's
day: ``n`` tasks with pairwise distinct start seconds, so the trimmed
timeline always has ``n`` slots and the LP's padded shape does not
depend on the seed.
"""

from __future__ import annotations

import functools

import numpy as np

# Normalized (cpu, memory) machine shapes: the 13 distinct configs of
# GCT-2019 cell "a", normalized to the largest machine.
MACHINE_SHAPES = np.array([
    [1.000, 1.000],
    [1.000, 0.500],
    [0.500, 0.500],
    [0.500, 0.250],
    [0.500, 0.750],
    [0.500, 0.125],
    [0.250, 0.250],
    [0.708, 0.250],
    [0.500, 0.375],
    [1.000, 0.250],
    [0.250, 0.125],
    [0.708, 0.500],
    [0.958, 0.500],
])

POOL_TASKS = 13_000
HORIZON_S = 86_400  # one day at second resolution
POOL_SEED = 20190501

# GCE n2 on-demand-like ratios (vCPU-hour dominates, memory-GB secondary)
_GCE_COEFF_2D = np.array([0.88, 0.12])


@functools.lru_cache(maxsize=1)
def pool() -> dict:
    """The fixed processed pool: 13,000 tasks and the 13 machine shapes."""
    rng = np.random.default_rng(POOL_SEED)
    n = POOL_TASKS
    u = rng.random(n)
    start = np.where(
        u < 0.7,
        rng.uniform(0, HORIZON_S, n),
        np.where(
            u < 0.85,
            rng.normal(10 * 3600, 1.5 * 3600, n),
            rng.normal(20 * 3600, 1.5 * 3600, n),
        ),
    )
    start = np.clip(start, 0, HORIZON_S - 2).astype(np.int64)
    dur = np.exp(rng.normal(np.log(5400), 1.3, n))
    long_mask = rng.random(n) < 0.20
    dur = np.where(long_mask, rng.uniform(6 * 3600, 24 * 3600, n), dur)
    dur = np.clip(dur, 10, 24 * 3600).astype(np.int64)
    end = np.minimum(start + dur, HORIZON_S - 1)
    cpu_sizes = np.array([0.005, 0.01, 0.02, 0.04, 0.08, 0.16])
    cpu_probs = np.array([0.10, 0.20, 0.25, 0.20, 0.15, 0.10])
    mem_ratio = np.array([0.25, 0.5, 1.0, 2.0])
    ratio_probs = np.array([0.15, 0.40, 0.35, 0.10])
    cpu = rng.choice(cpu_sizes, size=n, p=cpu_probs)
    mem = np.clip(cpu * rng.choice(mem_ratio, size=n, p=ratio_probs),
                  1e-4, 0.5)
    return {"dem": np.stack([cpu, mem], axis=1), "start": start,
            "end": end, "cap": MACHINE_SHAPES.copy(),
            "horizon": HORIZON_S}


def node_cost(cap: np.ndarray, cost_model: str, e: float = 1.0):
    """Prices of node types: 'homogeneous' (sum of capacities) or 'gce'
    (GCE-like per-dimension coefficients, exponent ``e``)."""
    if cost_model == "homogeneous":
        return cap.sum(axis=1)
    if cost_model == "gce":
        return (_GCE_COEFF_2D[None, :] * cap ** e).sum(axis=1) * 2.0
    raise ValueError(f"unknown cost model {cost_model!r}")


def instance(n: int, m: int, rng: np.random.Generator,
             distinct_starts: bool = False) -> dict:
    """Paper protocol: ``n`` tasks and ``m`` node shapes drawn from the
    pool without replacement.  With ``distinct_starts`` the tasks are
    the first ``n`` of a random permutation whose start seconds are
    not yet taken."""
    p = pool()
    size = len(p["dem"])
    if distinct_starts:
        order = rng.permutation(size)
        _, first = np.unique(p["start"][order], return_index=True)
        ti = order[np.sort(first)[:n]]
        if len(ti) < n:
            raise ValueError(f"the pool has fewer than {n} distinct starts")
    else:
        ti = rng.choice(size, size=min(n, size), replace=False)
    mi = rng.choice(len(p["cap"]), size=min(m, len(p["cap"])),
                    replace=False)
    return {"dem": p["dem"][ti], "start": p["start"][ti],
            "end": p["end"][ti], "cap": p["cap"][mi], "T": p["horizon"]}
