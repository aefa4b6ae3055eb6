"""Benchmark driver: one function per paper table/figure.

    python -m benchmarks.run [--scale quick|paper] [--only fig8a,...]
                             [--lp pdhg|highs]
                             [--placement batched|loop]
                             [--lp-tol 5e-3] [--lp-max-iters 4000]
                             [--buckets 4] [--scenarios 64]
                             [--out results/paper]

Prints ``table,key=value,...`` CSV rows; writes JSON per table.  With the
default ``--lp pdhg`` every sweep table funnels its whole instance grid
through the adaptive restarted batched PDHG engine (repro.core.batch),
stopped at the ``--lp-tol`` normalized duality gap (``--lp-max-iters``
caps the worst case) and warm-started between grid-adjacent sweep
points; ``--placement batched`` (default) runs the greedy placement
phase as one lockstep ``place_many`` per protocol combo
(repro.core.place_batch).  ``--lp highs`` / ``--placement loop`` restore
the paper's per-instance loops (placements and costs are identical).

The ``fleet_sweep`` table additionally emits shape-bucketing telemetry
(bucket count, padded-cell waste fraction before/after the FleetEngine
packing planner, per-bucket compile+solve seconds; ``--buckets`` caps
the planner) and solver convergence telemetry (iterations-to-tolerance,
restarts, final KKT residuals for vanilla vs adaptive vs warm-started
solves), written next to the timing output as
``<out>/solver_stats.json`` — the file the CI convergence-
regression gate (benchmarks/check_convergence.py) diffs against
``results/golden/solver_stats.json``.
"""

from __future__ import annotations

import argparse
import inspect
import json
import os
import time


def main(argv=None) -> None:
    from benchmarks.paper_tables import ALL_TABLES
    from repro.launch import enable_compile_cache

    ap = argparse.ArgumentParser()
    ap.add_argument("--scale", choices=["quick", "default", "paper"],
                    default="default")
    ap.add_argument("--lp", choices=["pdhg", "highs"], default="pdhg",
                    help="LP backend: batched PDHG sweep engine (one "
                         "solve per table) or per-instance exact HiGHS")
    ap.add_argument("--placement", choices=["batched", "compiled", "loop"],
                    default="batched",
                    help="greedy placement phase: numpy lockstep engine "
                         "(place_many), the compiled on-device stepper "
                         "(place_step), or the per-instance two_phase "
                         "loop (identical placements all three ways)")
    ap.add_argument("--lp-tol", type=float, default=None,
                    help="normalized-duality-gap stopping tolerance of "
                         "the PDHG LP phase (default: the scale's "
                         "built-in tolerance, repro.core.batch."
                         "DEFAULT_TOL)")
    ap.add_argument("--lp-max-iters", type=int, default=None,
                    help="worst-case PDHG iteration cap under --lp-tol "
                         "(default: per-scale)")
    ap.add_argument("--buckets", type=int, default=None,
                    help="max shape buckets of the FleetEngine packing "
                         "planner in the fleet_sweep bucketing section "
                         "(default: per-scale); 1 forces legacy "
                         "single-bucket packing")
    ap.add_argument("--scenarios", type=int, default=None,
                    help="K of the stochastic robustness section in "
                         "fleet_sweep (benchmarks.stochastic_smoke's "
                         "golden burst grid; default: the committed "
                         "golden K) — the blob lands under the "
                         "'stochastic' key of <out>/solver_stats.json "
                         "for benchmarks.check_stochastic")
    ap.add_argument("--serve-trace", action="store_true",
                    help="also replay the serving-loop smoke trace "
                         "(benchmarks.serve_smoke: paired warm/cold "
                         "RightsizingService replays) and merge its "
                         "requests/sec + p99 telemetry under the "
                         "'serve' key of <out>/solver_stats.json")
    ap.add_argument("--only", default="")
    ap.add_argument("--out", default="results/paper")
    args = ap.parse_args(argv)

    if args.buckets is not None and args.buckets < 1:
        ap.error(f"--buckets must be >= 1, got {args.buckets}")
    enable_compile_cache()
    os.makedirs(args.out, exist_ok=True)
    only = set(args.only.split(",")) if args.only else None
    if only:
        unknown = only - set(ALL_TABLES)
        if unknown:
            ap.error(f"unknown table(s) {sorted(unknown)}; "
                     f"choose from {sorted(ALL_TABLES)}")
    for name, fn in ALL_TABLES.items():
        if only and name not in only:
            continue
        t0 = time.perf_counter()
        kwargs = {}
        if "buckets" in inspect.signature(fn).parameters:
            kwargs["buckets"] = args.buckets
        if "scenarios" in inspect.signature(fn).parameters:
            kwargs["scenarios"] = args.scenarios
        rows = fn(scale=args.scale, lp=args.lp, placement=args.placement,
                  lp_tol=args.lp_tol, lp_max_iters=args.lp_max_iters,
                  **kwargs)
        dt = time.perf_counter() - t0
        # solver telemetry rides on the row as a private blob: write it
        # as its own artifact next to the timing output
        stats = [row.pop("_solver_stats") for row in rows
                 if "_solver_stats" in row]
        if stats:
            path = os.path.join(args.out, "solver_stats.json")
            with open(path, "w") as f:
                json.dump(stats[0] if len(stats) == 1 else stats, f,
                          indent=1)
            print(f"# solver telemetry -> {path}")
        with open(os.path.join(args.out, f"{name}.json"), "w") as f:
            json.dump(rows, f, indent=1)
        for row in rows:
            cells = ",".join(f"{k}={v}" for k, v in row.items())
            print(f"{name},{cells}")
        print(f"{name},_wall_s={dt:.1f}", flush=True)

    if args.serve_trace:
        from benchmarks.serve_smoke import serve_smoke

        t0 = time.perf_counter()
        blob = serve_smoke(scale=args.scale)
        path = os.path.join(args.out, "solver_stats.json")
        stats = {}
        if os.path.exists(path):
            with open(path) as f:
                stats = json.load(f)
        stats["serve"] = blob
        with open(path, "w") as f:
            json.dump(stats, f, indent=1)
        print(f"# serve telemetry -> {path} ('serve' key)")
        print(f"serve_trace,requests={blob['requests']},"
              f"ticks={blob['ticks']},"
              f"requests_per_s={blob['requests_per_s']},"
              f"p99_replan_s={blob['p99_replan_s']},"
              f"dispatches_per_tick={blob['dispatches_per_tick']}")
        print(f"serve_trace,_wall_s={time.perf_counter() - t0:.1f}",
              flush=True)


if __name__ == "__main__":
    main()
