"""Store the HiGHS optimum of every scenario LP a robust cell's days hold.

    python3 -m bench.optima_robust --workload robust.day2000.k16 \
        --processes 6

A robust plan solves one mapping LP per demand scenario of its day.
The cell's days and their scenarios are the same for every seed
(``bench.runners.robust``), so their optima are solved once here, on
the CPU, and written to ``bench/optima/<config>.json`` twice: under
``scenarios`` by ``robust.scenario_key``, which the runner reads on any
machine, and under ``optima`` by the fingerprint of each LP's inputs,
where ``reference.optimum`` finds them on a machine whose math library
rounds the fan-out's sin, exp and pow as this one does.
"""

from __future__ import annotations

import argparse
import json
from concurrent.futures import ProcessPoolExecutor

from bench import gct, harness, reference
from bench.runners.robust import draw_days, scenario_key, stated_scenarios


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--processes", type=int, default=4)
    args = ap.parse_args(argv)
    entry = harness.find(harness.benchmark()["workloads"], args.workload,
                         "workload")
    cfg = harness.config(entry["config"])
    days = draw_days(cfg, harness.traffic(entry["traffic"])["grids"])
    lps, keys = [], []
    for d in days:
        cost = gct.node_cost(d["cap"], cfg["cost_model"], cfg["gce_e"])
        for k, dem in enumerate(stated_scenarios(cfg, d)):
            lps.append((dem, d["start"], d["end"], d["cap"], cost))
            keys.append(scenario_key(cfg, d, cost, k))
    with ProcessPoolExecutor(args.processes) as pool:
        optima = list(pool.map(reference.lp_optimum, *zip(*lps)))
    table = {"config": cfg["name"], "solver": "scipy linprog highs-ipm",
             "optima": {reference.fingerprint(*lp): opt
                        for lp, opt in zip(lps, optima)},
             "scenarios": dict(zip(keys, optima))}
    path = reference.OPTIMA / f"{cfg['name']}.json"
    path.write_text(json.dumps(table, indent=1) + "\n")
    print(f"{len(optima)} optima -> {path}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
