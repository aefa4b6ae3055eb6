"""The control that ``correct`` must refuse, and sound readings beside it.

    python3 -m bench.control --workload offline.day2000 \
        --seeds 1,2,3 --seconds 5 --mode control

The configuration states its demands in float64, and the step below,
float32, stays inside the program's capacity slack (1e-7), so it cannot
fail.  The control breaks the guarantee that slack protects instead:
the program is given its demands in bfloat16, rounded toward zero, the
cast a later change might make to halve what it moves.  Every demand
then shrinks by up to 2**-8 of itself, and plans made for them overfill
nodes under the demands as stated.  ``--mode sound`` runs the seeds as
the benchmark does, ``--mode f32`` with float32 demands; ``lp_high``
and ``lp_default`` run the LP's float32 contractions at three and at
one bfloat16 pass instead of the HIGHEST the program pins, and
``one_step`` plants the fault of a solve that stops one step from its
start.  All seeds run in one process on the TPU it starts on, each
with a short window at the cell's own load; one JSON line per seed
gives every compared number beside its limit, the window's ``plan_s``
and the LP's mean iterations.  The benchmark's own runs never run
this.
"""

from __future__ import annotations

import argparse
import json
import sys
import time

import numpy as np

from bench import harness


def truncate_bf16(a):
    """``a`` cast to bfloat16 by dropping the low 16 bits of its float32
    form (rounding toward zero), returned as float64."""
    a32 = np.array(a, dtype=np.float32)
    return (a32.view(np.uint32) & np.uint32(0xFFFF0000)).view(
        np.float32).astype(np.float64)


def to_f32(a):
    """``a`` rounded to float32, returned as float64."""
    return np.array(a, dtype=np.float32).astype(np.float64)


def lp_precision(precision: str):
    """Run the LP's contractions at ``precision`` ('high': three bfloat16
    passes, the step below the HIGHEST the program pins; 'default': one
    pass) by dropping the precision the program asks ``jnp.matmul``
    for.  Only this process is changed."""
    import jax
    import jax.numpy as jnp

    matmul = jnp.matmul
    level = getattr(jax.lax.Precision, precision.upper())

    def lowered(a, b, *args, precision=None, **kwargs):
        return matmul(a, b, *args, precision=level, **kwargs)

    jnp.matmul = lowered


def one_step():
    """The fault of a solve that leaves its state all but unchanged: one
    PDHG step from its start point."""
    from repro.core import engine

    solve = engine.solve_lp_many
    engine.solve_lp_many = lambda *a, **k: solve(*a, **dict(k, iters=1))


# mode -> (cast given to the program's demands, change made to the process)
MODES = {"sound": (None, None), "control": (truncate_bf16, None),
         "f32": (to_f32, None),
         "lp_high": (None, lambda: lp_precision("high")),
         "lp_default": (None, lambda: lp_precision("default")),
         "one_step": (None, one_step)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--mode", choices=tuple(MODES), required=True)
    args = ap.parse_args(argv)
    entry = harness.find(harness.benchmark()["workloads"], args.workload,
                         "workload")
    sys.path.insert(0, str(harness.ROOT / "src"))
    import jax

    from repro.launch import enable_compile_cache

    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    if jax.devices()[0].platform != "tpu":
        print("bench.control: needs a TPU", file=sys.stderr)
        return 3
    cast, change = MODES[args.mode]
    if change is not None:
        change()
    for seed in (int(s) for s in args.seeds.split(",")):
        cell = harness.Cell(args.workload, harness.config(entry["config"]),
                            harness.traffic(entry["traffic"]), seed,
                            args.seconds, False, cast)
        out = harness.run_cell(cell, time.perf_counter())
        print(json.dumps({
            "mode": args.mode, "seed": seed, "correct": out["correct"],
            "attempted": out["attempted"], "failed": out["failed"],
            "checks": {c.name: [c.value, c.limit] for c in out["checks"]},
            "plan_s": out["metrics"]["plan_s"]["value"],
            "lp_iters": out["record"].mean("lp_iters")}),
            flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
