"""Plain references of robust day-ahead sizing; they import nothing of the
program and take nothing it made.

* ``scenario_demands``: the K demand scenarios of a day as the
  configuration states them.  A copy of the program's Monte-Carlo
  fan-out (``repro.stochastic``): scenario k draws from its own stream
  ``default_rng([FANOUT_TAG, seed, k])``, in a fixed order (a day-wide
  lognormal load, a diurnal phase, a burst mask, Pareto burst tails),
  and each task's factor is clamped to the headroom of the node type
  that fits it best.  Same day, seed and channels, same draws: a test
  holds it equal to the program's value for value, so that the
  benchmark's stated demands cannot move when the program changes.
* ``menu``, ``overload``, ``cvar``, ``objective`` and ``select``: the
  robust fleet the configuration asks for, from each scenario's node
  counts and the catalogue prices: the argmin of
  ``E[cost] + lambda * CVaR_alpha(overload)`` over the stated menu of
  candidate fleets.

Per scenario, ``bench.reference`` checks the kept plan (``plan_faults``)
and the certified LP bound (``optimum``).
"""

from __future__ import annotations

import math

import numpy as np

# the program's namespace for the fan-out's seed streams
FANOUT_TAG = 0x5C3A


def factors(rng: np.random.Generator, start, T: int, channels: dict):
    """One scenario's per-task demand multipliers."""
    n = len(start)
    sigma = channels["load_sigma"]
    load = math.exp(rng.normal(-0.5 * sigma**2, sigma)) if sigma > 0 else 1.0
    phase = rng.uniform(0.0, 2.0 * math.pi)
    amp = channels["diurnal_amp"]
    diurnal = (1.0 + amp * np.sin(2.0 * math.pi * start / max(T, 1) - phase)
               if amp > 0 else np.ones(n))
    burst = np.ones(n)
    if channels["burst_prob"] > 0:
        hit = rng.random(n) < channels["burst_prob"]
        tail = (1.0 - rng.random(n)) ** (-1.0 / channels["burst_alpha"])
        burst = np.where(hit, np.minimum(tail, channels["burst_cap"]), 1.0)
    return load * diurnal * burst


def scenario_demands(dem, start, T: int, cap, channels: dict, K: int,
                     seed: int) -> np.ndarray:
    """(K, n, D) demands of a day's K scenarios, in the day's task order."""
    dem = np.asarray(dem, np.float64)
    start = np.asarray(start, np.int64)
    cap = np.asarray(cap, np.float64)
    # the largest factor under which a task still fits some node type
    with np.errstate(divide="ignore"):
        ratios = np.where(dem[:, None, :] > 0,
                          cap[None, :, :] / dem[:, None, :], np.inf)
    headroom = ratios.min(axis=2).max(axis=1)
    out = np.empty((K, *dem.shape))
    for k in range(K):
        f = factors(np.random.default_rng([FANOUT_TAG, seed, k]), start, T,
                    channels)
        out[k] = dem * np.minimum(f, headroom)[:, None]
    return out


def menu(plans, quantiles: int, current=None) -> np.ndarray:
    """The candidate fleets: every scenario fleet and every pair's
    elementwise max, the per-type quantile chain over the scenarios at
    ``quantiles`` evenly spaced levels from 0 to 1 (each level taking
    the next scenario count at or above it), and the current fleet,
    without repeats, ordered by node count and then by the counts."""
    plans = np.asarray(plans, np.int64)
    K, m = plans.shape
    rows = {tuple(int(v) for v in np.maximum(plans[a], plans[b]))
            for a in range(K) for b in range(K)}
    ranked = np.sort(plans, axis=0)
    for q in np.linspace(0.0, 1.0, quantiles):
        rows.add(tuple(int(v) for v in ranked[math.ceil(q * (K - 1))]))
    if current is not None:
        rows.add(tuple(int(v) for v in current))
    return np.array(sorted(rows, key=lambda r: (sum(r), r)), np.int64)


def overload(plans, fleets, cost) -> np.ndarray:
    """(K, J) price of the nodes each scenario needs beyond each fleet."""
    short = np.maximum(np.asarray(plans)[:, None, :]
                       - np.asarray(fleets)[None, :, :], 0)
    return (short * np.asarray(cost)[None, None, :]).sum(axis=2)


def cvar(x, alpha: float) -> float:
    """Mean of the worst ceil((1 - alpha) K) of K equally likely values."""
    x = np.sort(np.asarray(x, np.float64))
    k = max(1, math.ceil((1.0 - alpha) * len(x)))
    return float(x[len(x) - k:].mean())


def objective(prices, ov, config: dict) -> np.ndarray:
    """(J,) E[cost] + lambda CVaR_alpha(overload) of each fleet, from
    its price and its (K, J) overload in each scenario.  E[cost] is the
    price plus the premium on the mean overload.  There is no
    reconfiguration term: the configuration plans from no fleet."""
    ov = np.asarray(ov, np.float64)
    tail = [cvar(ov[:, j], config["cvar_alpha"]) for j in range(ov.shape[1])]
    return (np.asarray(prices, np.float64)
            + config["overload_premium"] * ov.mean(axis=0)
            + config["cvar_lambda"] * np.asarray(tail))


def select(plans, cost, config: dict) -> tuple[np.ndarray, float]:
    """(robust fleet, its objective): the candidate of least objective,
    ties going to the lower worst-scenario overload, then the lower
    price, then the smaller counts in order."""
    fleets = menu(plans, config["quantiles"])
    ov = overload(plans, fleets, cost)
    prices = (fleets * np.asarray(cost)[None, :]).sum(axis=1)
    obj = objective(prices, ov, config)
    keys = [(float(obj[j]), float(ov[:, j].max()), float(prices[j]),
             tuple(fleets[j])) for j in range(len(fleets))]
    j = min(range(len(fleets)), key=keys.__getitem__)
    return fleets[j], keys[j][0]
