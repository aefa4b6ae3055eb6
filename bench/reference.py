"""Plain references that decide ``correct``; they import nothing of the
program and take nothing it made.

* ``lp_optimum``: the mapping LP (paper §V, Eq. 4-7) solved exactly by
  HiGHS.  The program's formulation puts one congestion row per
  (type, slot, dimension) over every active task, which takes HiGHS
  minutes at n=2000; here the load of each type at slot k is a variable
  tied to slot k-1 by the tasks that start at k and those that ended
  at k-1 (each task enters two rows), and a load may only reach its
  type's peak at a slot some task leaves after.  The optimum is the
  same LP's.
* ``optimum``: ``lp_optimum`` looked up by the fingerprint of its
  inputs in the tables under ``bench/optima/`` (written by
  ``bench.optima``), and solved where no table holds it.
* ``plan_faults``: the capacity of every purchased node at every slot
  where a load can rise (a task's start), recomputed from the demands
  as stated, in float64.
"""

from __future__ import annotations

import functools
import hashlib
import json
from pathlib import Path

import numpy as np
import scipy.sparse as sp
from scipy.optimize import linprog

# a task may use a type only if it fits an empty node of it (the LP pins
# the other pairs to 0), up to this slack on the capacity
FIT_EPS = 1e-12


def trim(start: np.ndarray, end: np.ndarray):
    """Task spans on the slots where some task starts: (start', end', T')."""
    kept = np.unique(start)
    s = np.searchsorted(kept, start)
    e = np.searchsorted(kept, end, side="right") - 1
    return s, e, len(kept)


def lp_optimum(dem, start, end, cap, cost) -> float:
    """Optimum of the mapping LP: min sum_j cost_j alpha_j subject to
    every task spread over the types it fits (x >= 0, rows sum to 1)
    and, for every type j, slot t and dimension d, the load
    sum_{u active at t} x_uj dem_ud / cap_jd <= alpha_j."""
    dem = np.asarray(dem, np.float64)
    cap = np.asarray(cap, np.float64)
    n, D = dem.shape
    m = cap.shape[0]
    s, e, T = trim(np.asarray(start), np.asarray(end))
    fits = (dem[:, None, :] <= cap[None, :, :] + FIT_EPS).all(axis=2)
    if not fits.any(axis=1).all():
        raise ValueError("a task fits no node type")
    nx, nL = n * m, m * T * D
    ncol = nx + nL + m
    u = np.arange(n)

    def load(j, k, d):  # column (and equality row) of L[j, k, d]
        return (j * T + k) * D + d

    jj, kk, dd = (a.ravel() for a in np.meshgrid(
        np.arange(m), np.arange(T), np.arange(D), indexing="ij"))
    rows = [load(jj, kk, dd)]
    cols = [nx + load(jj, kk, dd)]
    vals = [np.ones(nL)]
    prev = kk > 0
    rows.append(load(jj[prev], kk[prev], dd[prev]))
    cols.append(nx + load(jj[prev], kk[prev] - 1, dd[prev]))
    vals.append(-np.ones(int(prev.sum())))
    ends_inside = e + 1 < T
    for j in range(m):
        w = dem / cap[j][None, :]
        for d in range(D):
            # L[j,k] = L[j,k-1] + (tasks starting at k) - (tasks that
            # ended at k-1)
            rows.append(load(j, s, d))
            cols.append(u * m + j)
            vals.append(-w[:, d])
            rows.append(load(j, e[ends_inside] + 1, d))
            cols.append(u[ends_inside] * m + j)
            vals.append(w[ends_inside, d])
    a_load = sp.csr_matrix(
        (np.concatenate(vals), (np.concatenate(rows), np.concatenate(cols))),
        shape=(nL, ncol))
    a_map = sp.csr_matrix((np.ones(nx), (np.repeat(u, m), np.arange(nx))),
                          shape=(n, ncol))
    # a load peaks only where a task leaves after it (or at the last slot)
    leaves = np.bincount(e, minlength=T) > 0
    peak_rows = np.flatnonzero(leaves[(np.arange(nL) // D) % T])
    r = np.arange(len(peak_rows))
    a_peak = sp.csr_matrix(
        (np.concatenate([np.ones(len(r)), -np.ones(len(r))]),
         (np.concatenate([r, r]),
          np.concatenate([nx + peak_rows, nx + nL + peak_rows // (T * D)]))),
        shape=(len(r), ncol))
    c = np.concatenate([np.zeros(nx + nL), np.asarray(cost, np.float64)])
    bounds = ([(0.0, 1.0 if f else 0.0) for f in fits.reshape(-1)]
              + [(None, None)] * nL + [(0.0, None)] * m)
    a_eq = sp.vstack([a_load, a_map]).tocsr()
    b_eq = np.concatenate([np.zeros(nL), np.ones(n)])
    # the interior point is the fastest here; where its presolve fails
    # (one day in 64 at n=2000), without presolve, then dual simplex
    for method, options in (("highs-ipm", {}),
                            ("highs-ipm", {"presolve": False}),
                            ("highs-ds", {})):
        res = linprog(c, A_ub=a_peak, b_ub=np.zeros(len(r)), A_eq=a_eq,
                      b_eq=b_eq, bounds=bounds, method=method,
                      options=options)
        if res.status == 0:
            return float(res.fun)
    raise RuntimeError(f"reference LP failed: {res.message}")


OPTIMA = Path(__file__).resolve().parent / "optima"


def fingerprint(dem, start, end, cap, cost) -> str:
    """A digest of one mapping LP's inputs that does not depend on the
    order of its tasks or of its node types."""
    dem = np.asarray(dem, np.float64)
    start, end = np.asarray(start, np.int64), np.asarray(end, np.int64)
    rows = np.lexsort((*dem.T[::-1], end, start))
    types = np.column_stack([np.asarray(cap, np.float64),
                             np.asarray(cost, np.float64)])
    types = types[np.lexsort(types.T[::-1])]
    h = hashlib.sha256()
    for a in (dem[rows], start[rows], end[rows], types):
        h.update(np.ascontiguousarray(a).tobytes())
    return h.hexdigest()[:24]


@functools.lru_cache(maxsize=1)
def stored_optima() -> dict:
    """Every stored optimum, by fingerprint."""
    table = {}
    for path in sorted(OPTIMA.glob("*.json")):
        table.update(json.loads(path.read_text())["optima"])
    return table


def optimum(dem, start, end, cap, cost) -> float:
    """``lp_optimum``, from the stored tables where they hold it."""
    key = fingerprint(dem, start, end, cap, cost)
    if key in stored_optima():
        return stored_optima()[key]
    return lp_optimum(dem, start, end, cap, cost)


def plan_faults(dem, start, end, cap, node_type, assign, m: int):
    """(overload, unplaced) of a plan against the stated demands.

    ``overload`` is the largest amount, in normalized capacity units, by
    which the tasks on one node exceed its capacity in one dimension at
    one slot (0 when every node holds its tasks); ``unplaced`` counts
    tasks without a valid node, and is the task count when the plan's
    shapes do not match the task set.
    """
    dem = np.asarray(dem, np.float64)
    n = dem.shape[0]
    node_type = np.asarray(node_type)
    assign = np.asarray(assign)
    if assign.shape != (n,) or node_type.ndim != 1:
        return 0.0, n
    N = len(node_type)
    ok = (assign >= 0) & (assign < N)
    bad_types = (node_type < 0) | (node_type >= m)
    if bad_types.any():
        ok &= ~bad_types[np.clip(assign, 0, max(N - 1, 0))]
    unplaced = int(n - ok.sum())
    if not ok.any():
        return 0.0, unplaced
    s, e, T = trim(np.asarray(start), np.asarray(end))
    delta = np.zeros((N, T + 1, dem.shape[1]))
    np.add.at(delta, (assign[ok], s[ok]), dem[ok])
    np.add.at(delta, (assign[ok], e[ok] + 1), -dem[ok])
    load = np.cumsum(delta, axis=1)[:, :T]
    node_cap = np.asarray(cap, np.float64)[np.clip(node_type, 0, m - 1)]
    over = float((load - node_cap[:, None, :]).max())
    return max(0.0, over), unplaced
