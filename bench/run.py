"""Run one benchmark cell once, on the chips of the machine it starts on.

    python3 -m bench.run --workload offline.day2000 --seed 7 \
        --seconds 30 --trace 0

It runs in one process, which holds the chips.  It exits non-zero and
prints no result when JAX finds no TPU, fewer chips than the cell asks
for, or no program beside the benchmark (``src/repro``).  JAX keeps its
compilation cache in ``<checkout>/.jax_cache``, so only the first run
of a cell in a checkout compiles.  The cell's inputs are made from
``--seed``; set-up warms up every program the window runs; the window
lasts ``--seconds``; then the plain references decide ``correct``.

The last lines of standard error are the compared numbers beside their
limits; the last line of standard output is the result as one JSON
object.  With ``--trace 1`` a few seconds of the window are traced and
the result holds the cell's per-layer metrics instead of its
end-to-end ones.
"""

from __future__ import annotations

import argparse
import json
import math
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def _parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def _fail(msg: str, code: int) -> int:
    print(f"bench.run: {msg}", file=sys.stderr, flush=True)
    return code


def _finite(x: float) -> float:
    """A JSON-safe number: a non-finite reading becomes +-1e300."""
    return x if math.isfinite(x) else math.copysign(1e300, x)


def emit(out: dict, device: dict) -> None:
    """The compared numbers on standard error, then the result line."""
    checks = {c.name: {"value": _finite(c.value), "limit": c.limit}
              for c in out["checks"]}
    for c in out["checks"]:
        print(f"check {c.name}: {c.value!r} (limit {c.limit!r}) "
              f"{'ok' if c.ok else 'FAILED'}", file=sys.stderr)
    sys.stderr.flush()
    line = {"correct": out["correct"], "attempted": out["attempted"],
            "failed": out["failed"], "metrics": out["metrics"],
            "device": device}
    if "breakdown" in out:
        line["breakdown"] = out["breakdown"]
    line["checks"] = checks
    print(json.dumps(line), flush=True)


def main(argv=None) -> int:
    t_start = time.perf_counter()
    args = _parse(argv)
    from bench import harness

    bench = harness.benchmark(ROOT)
    try:
        entry = harness.find(bench["workloads"], args.workload, "workload")
    except KeyError as e:
        return _fail(str(e), 2)
    src = ROOT / "src"
    if not (src / "repro" / "__init__.py").is_file():
        return _fail(f"no program at {src / 'repro'}", 2)
    sys.path.insert(0, str(src))

    import jax

    from repro.launch import enable_compile_cache

    enable_compile_cache()
    # cache every program, however fast it compiled: a later run of the
    # cell must find all of them
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    devices = jax.devices()
    if devices[0].platform != "tpu":
        return _fail(f"needs a TPU; JAX found {devices[0].platform!r}", 3)
    if len(devices) < entry["chips"]:
        return _fail(f"the cell needs {entry['chips']} chip(s); JAX sees "
                     f"{len(devices)}", 3)
    try:
        peaks = harness.peaks(devices[0].device_kind)
    except KeyError as e:
        return _fail(str(e), 4)
    cell = harness.Cell(
        workload=args.workload, config=harness.config(entry["config"]),
        traffic=harness.traffic(entry["traffic"]), seed=args.seed,
        seconds=args.seconds, trace=bool(args.trace))
    out = harness.run_cell(cell, t_start, device=devices[0],
                           device_peaks=peaks)
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": out["memory_peak_bytes"]}
    if cell.trace:
        device["busy_s"] = out["busy_s"]
        device["window_s"] = out["window_s"]
    emit(out, device)
    return 0


if __name__ == "__main__":
    sys.exit(main())
