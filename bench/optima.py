"""Store the HiGHS optimum of every mapping LP a cell's days hold.

    python3 -m bench.optima --workload offline.day2000 --processes 6

HiGHS takes some 20 s a fleet at n=2000, too long to run for every
fleet a run plans.  The cell's days are the same for every seed (the
seed only orders them), so their optima are solved once here, on the
CPU, and written to ``bench/optima/<config>.json`` by the fingerprint
of each LP's inputs; ``reference.optimum`` looks them up, and solves
any LP no table holds.
"""

from __future__ import annotations

import argparse
import json
from concurrent.futures import ProcessPoolExecutor

from bench import gct, harness, reference
from bench.runners.offline import draw_days


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--processes", type=int, default=4)
    args = ap.parse_args(argv)
    entry = harness.find(harness.benchmark()["workloads"], args.workload,
                         "workload")
    cfg = harness.config(entry["config"])
    days = draw_days(cfg, harness.traffic(entry["traffic"])["grids"], 0)
    lps = [(d["dem"], d["start"], d["end"], d["cap"],
            gct.node_cost(d["cap"], cm, cfg["gce_e"]))
           for d in days for cm in cfg["cost_models"]]
    with ProcessPoolExecutor(args.processes) as pool:
        optima = list(pool.map(reference.lp_optimum, *zip(*lps)))
    table = {"config": cfg["name"], "solver": "scipy linprog highs-ipm",
             "optima": {reference.fingerprint(*lp): opt
                        for lp, opt in zip(lps, optima)}}
    path = reference.OPTIMA / f"{cfg['name']}.json"
    path.parent.mkdir(exist_ok=True)
    path.write_text(json.dumps(table, indent=1) + "\n")
    print(f"{len(optima)} optima -> {path}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
