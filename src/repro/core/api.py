"""High-level TL-Rightsizing API (single-instance calls + legacy shims).

``rightsize(problem, algo)`` runs one named algorithm; ``evaluate(problem)``
reproduces the paper's §VI protocol:

  * PenaltyMap    — min cost over {h_avg, h_max} x {first, similarity}
  * PenaltyMap-F  — same four combos with cross-node-type filling
  * LP-map        — LP mapping, min over {first, similarity}
  * LP-map-F      — LP mapping + filling, min over {first, similarity}

The fleet-scale surface lives in ``core.engine``: a ``FleetEngine``
session configured by frozen dataclasses (``SolverConfig`` /
``PlacementConfig`` / ``SweepConfig``) packs a whole instance grid into
shape buckets, solves every mapping LP batched, advances every greedy
placement in lockstep, and returns a structured ``FleetResult``.
``evaluate_many`` in this module is kept as a thin kwarg shim over that
engine — it maps the legacy keyword arguments onto the typed configs
one-to-one, always runs single-bucket (so the committed golden tables
stay bit-identical), and returns the legacy list-of-entry-dicts.  New
code should construct a ``FleetEngine`` directly.

All problems are timeline-trimmed internally; solutions are expressed (and
verified) in trimmed coordinates, which preserves feasibility and cost
exactly (paper §II).
"""

from __future__ import annotations

import time
import warnings

from .constraints import expand_solution, lower_constraints
from .problem import Problem, trim_timeline
from .penalty import penalty_map
from .placement import two_phase, FIT_POLICIES
from .solution import Solution, verify
from .lp_map import solve_lp as _solve_lp

__all__ = ["rightsize", "evaluate", "evaluate_many", "ALGORITHMS"]

ALGORITHMS = ("penalty-map", "penalty-map-f", "lp-map", "lp-map-f")
# beyond-paper: any algorithm + node-elimination local search ("+ls")
EXTENDED_ALGORITHMS = ALGORITHMS + ("lp-map-f+ls", "penalty-map-f+ls")


def _penalty_solutions(problem: Problem, filling: bool):
    for kind in ("avg", "max"):
        mapping = penalty_map(problem, kind)
        for fit in FIT_POLICIES:
            yield two_phase(
                problem, mapping, fit=fit, filling=filling,
                meta={"algo": "penalty-map" + ("-f" if filling else ""),
                      "h": kind},
            )


def _lp_solutions(problem: Problem, filling: bool, lp_result=None):
    res = lp_result if lp_result is not None else _solve_lp(problem)
    for fit in FIT_POLICIES:
        sol = two_phase(
            problem, res.mapping, fit=fit, filling=filling,
            meta={"algo": "lp-map" + ("-f" if filling else ""),
                  "lp_objective": res.objective},
        )
        yield sol


def rightsize(
    problem: Problem,
    algo: str = "lp-map-f",
    check: bool = True,
    lp_result=None,
) -> Solution:
    """Solve one instance with one algorithm, taking the best fit policy
    (and, for PenaltyMap, the best relative-demand kind) per the paper.

    Constrained instances (``problem.constraints``) are lowered first
    (``repro.core.constraints``); the returned solution is expanded
    back to original task rows, and under ``check=True`` it is also
    validated against the ORIGINAL constraint semantics by the
    independent ``repro.core.checker`` oracle."""
    low = lower_constraints(problem)
    trimmed, _ = trim_timeline(low.lowered)
    t0 = time.perf_counter()
    local_search = algo.endswith("+ls")
    if local_search:
        algo = algo[: -len("+ls")]
    if algo == "penalty-map":
        sols = _penalty_solutions(trimmed, filling=False)
    elif algo == "penalty-map-f":
        sols = _penalty_solutions(trimmed, filling=True)
    elif algo == "lp-map":
        sols = _lp_solutions(trimmed, filling=False, lp_result=lp_result)
    elif algo == "lp-map-f":
        sols = _lp_solutions(trimmed, filling=True, lp_result=lp_result)
    else:
        raise ValueError(f"unknown algo {algo!r}; want one of {ALGORITHMS}")
    best = min(sols, key=lambda s: s.cost(trimmed))
    if local_search:
        from .local_search import eliminate_nodes

        best = eliminate_nodes(trimmed, best)
    best.meta["wall_s"] = time.perf_counter() - t0
    if check:
        verify(trimmed, best)
    best = expand_solution(low, best)
    if check and not low.identity:
        from .checker import assert_feasible

        assert_feasible(problem, best)
    return best


def _solve_lp_for(problem: Problem, lp_solver: str, lp_iters: int,
                  lp_tol: float | None = None):
    """(lp_result, certified lower bound) for one instance."""
    if lp_solver == "highs":
        res = _solve_lp(problem)
        return res, res.objective
    if lp_solver == "pdhg":
        from .lp_pdhg import solve_lp_pdhg

        res = solve_lp_pdhg(problem, iters=lp_iters, tol=lp_tol)
        return res, res.lower_bound
    raise ValueError(f"unknown lp_solver {lp_solver!r}; want 'highs'|'pdhg'")


def _protocol_entry(trimmed: Problem, lp_result, lb: float,
                    algos) -> dict:
    out: dict = {"lb": lb, "costs": {}, "normalized": {}, "wall_s": {}}
    for algo in algos:
        sol = rightsize(trimmed, algo, lp_result=lp_result)
        cost = sol.cost(trimmed)
        out["costs"][algo] = cost
        out["normalized"][algo] = cost / max(lb, 1e-12)
        out["wall_s"][algo] = sol.meta["wall_s"]
    return out


def evaluate(problem: Problem, algos=ALGORITHMS, lp_solver: str = "highs",
             lp_iters: int = 2000, lp_tol: float | None = None) -> dict:
    """Paper §VI protocol: per-algorithm best cost + the LP lower bound.

    ``lp_solver='highs'`` solves the mapping LP exactly (the paper's
    setup); ``'pdhg'`` uses the accelerator-native solver, normalizing by
    its certified dual lower bound instead of the exact LP optimum.
    ``lp_tol`` switches the PDHG solve to tolerance-based stopping
    (adaptive restarted engine; ``lp_iters`` caps the worst case).

    Returns {algo: cost, ..., 'lb': lowerbound, 'normalized': {algo: cost/lb}}.

    Constrained instances are lowered first; costs (and the lower
    bound) are those of the lowered instance, whose affinity rows
    reserve peak-over-hull demand — a conservative relaxation, so the
    reported ``lb`` may exceed the true constrained optimum's LP bound.
    """
    low = lower_constraints(problem)
    trimmed, _ = trim_timeline(low.lowered)
    lp_result, lb = _solve_lp_for(trimmed, lp_solver, lp_iters, lp_tol)
    return _protocol_entry(trimmed, lp_result, lb, algos)


_UNSET = object()  # sentinel: distinguishes "kwarg passed" from default

# legacy kwarg -> the typed-config equivalent named in the deprecation
# warning (behavior is bit-stable either way; only the spelling moves)
_LEGACY_KWARGS = {
    "lp_iters": "SolverConfig(iters=...)",
    "operator": "SolverConfig(operator=...)",
    "placement": "PlacementConfig(engine=...)",
    "lp_tol": "SolverConfig(tol=...)",
    "lp_adaptive": "SolverConfig(adaptive=...)",
    "lp_restart": "SolverConfig(restart=...)",
    "warm_start": "SweepConfig(warm_start=...)",
    "return_stats": "FleetEngine.evaluate(...).stats on the FleetResult",
}

_LEGACY_DEFAULTS = {
    "lp_iters": 2000, "operator": "auto",
    "placement": "batched", "lp_tol": None, "lp_adaptive": True,
    "lp_restart": True, "warm_start": None, "return_stats": False,
}


def evaluate_many(problems, algos=ALGORITHMS,
                  lp_iters=_UNSET, operator=_UNSET,
                  placement=_UNSET,
                  lp_tol=_UNSET,
                  lp_adaptive=_UNSET, lp_restart=_UNSET,
                  warm_start=_UNSET,
                  return_stats=_UNSET):
    """§VI protocol over a grid of instances, fully batched — the
    **legacy kwarg shim** over ``core.engine.FleetEngine``.

    .. deprecated::
        The kwarg surface is deprecated: passing any of the legacy
        keywords emits a ``DeprecationWarning`` naming its typed-config
        equivalent (``SolverConfig`` / ``PlacementConfig`` /
        ``SweepConfig``).  Behavior is bit-stable — only the spelling
        moves to ``FleetEngine``.

    Equivalent to ``[evaluate(p, algos, lp_solver='pdhg') for p in
    problems]`` — the batched engines pad ragged instances exactly, so
    costs match the per-instance loop — but the LP phase is a single
    compiled ``solve_lp_many`` call for the whole grid, and (with
    ``placement='batched'``, the default) the greedy placement phase
    advances all instances in lockstep through ``place_many``.
    ``placement='compiled'`` routes that lockstep through the on-device
    ``lax.scan`` stepper (``core.place_step``; one device dispatch per
    node-type phase instead of one numpy dispatch per step), and
    ``placement='loop'`` restores the per-instance placement loop;
    placements (and therefore costs) are identical all three ways.

    Every kwarg maps onto one typed-config field (see the README
    migration table): ``lp_iters/operator/lp_tol/lp_adaptive/lp_restart``
    -> ``SolverConfig``, ``placement`` -> ``PlacementConfig``,
    ``warm_start`` -> ``SweepConfig``.  The shim always runs
    single-bucket (``SweepConfig(max_buckets=1)``) so the committed
    golden tables stay bit-identical; shape-bucketed packing of very
    ragged grids is a ``FleetEngine`` feature
    (``SweepConfig(max_buckets=k)``).

    ``lp_tol=None`` (default) keeps the fixed-``lp_iters`` vanilla
    solve.  With ``lp_tol`` set the LP phase runs the adaptive restarted
    engine until every instance's normalized duality gap is below the
    tolerance (``lp_iters`` caps the worst case; ``lp_adaptive`` /
    ``lp_restart`` ablate the step-size and restart machinery), and each
    returned entry carries a ``'solver'`` telemetry dict — iterations-
    to-tolerance, restarts, final KKT residual, converged flag.

    ``warm_start=k`` treats ``problems`` as a sweep in grid-adjacent
    order (the order ``workload.sweep_specs`` emits) split into
    consecutive groups of k — one sweep point's seed replicas each — and
    solves the LP phase as a warm-started chain (``solve_lp_sweep``):
    every group starts from its predecessor's primal/dual solution.
    Requires ``lp_tol`` (warm starts only pay off with tolerance-based
    stopping).  ``warm_start=None`` (default) disables chaining; a
    non-positive k raises ``ValueError`` rather than being treated as
    falsy "off".  When k does not divide the grid size the trailing
    group is smaller and cold-starts (its lanes no longer align with
    the predecessor state) — costs are unaffected, only that group's
    iteration telemetry loses the warm-start advantage.
    ``return_stats=True`` additionally returns the ``SolveStats`` list
    (one per batched solve / warm-started group).

    >>> from repro.workload import SyntheticSpec, synthetic_instance
    >>> grid = [synthetic_instance(SyntheticSpec(n=8, m=2, D=2, T=5,
    ...                                          seed=s))
    ...         for s in (0, 1)]
    >>> entries = evaluate_many(grid, algos=("penalty-map",),
    ...                         lp_iters=30)
    >>> sorted(entries[0])
    ['costs', 'lb', 'normalized', 'plan', 'wall_s']
    >>> list(entries[1]["costs"])
    ['penalty-map']
    """
    from .engine import (FleetEngine, PlacementConfig, SolverConfig,
                         SweepConfig)

    passed = {name: val for name, val in [
        ("lp_iters", lp_iters),
        ("operator", operator), ("placement", placement),
        ("lp_tol", lp_tol), ("lp_adaptive", lp_adaptive),
        ("lp_restart", lp_restart), ("warm_start", warm_start),
        ("return_stats", return_stats)] if val is not _UNSET}
    if passed:
        hints = "; ".join(f"{k} -> {_LEGACY_KWARGS[k]}" for k in passed)
        warnings.warn(
            f"the evaluate_many kwarg surface is deprecated; build a "
            f"FleetEngine with the typed configs instead ({hints})",
            DeprecationWarning, stacklevel=2)
    resolved = dict(_LEGACY_DEFAULTS, **passed)
    lp_iters, operator, placement, lp_tol, lp_adaptive, \
        lp_restart, warm_start, return_stats = (
            resolved[k] for k in ("lp_iters", "operator",
                                  "placement", "lp_tol", "lp_adaptive",
                                  "lp_restart", "warm_start",
                                  "return_stats"))

    sweep = SweepConfig(warm_start=warm_start)  # rejects warm_start <= 0
    if warm_start is not None and lp_tol is None:
        raise ValueError("warm_start requires lp_tol (tolerance-stopped "
                         "solves); fixed-iteration solves gain nothing "
                         "from a warm start")
    engine = FleetEngine(
        solver=SolverConfig(tol=lp_tol, iters=lp_iters,
                            adaptive=lp_adaptive, restart=lp_restart,
                            operator=operator),
        placement=PlacementConfig(engine=placement),
        sweep=sweep,
        algos=algos,
    )
    result = engine.evaluate(problems)
    if return_stats:
        return result.entries, result.stats
    return result.entries
