"""The data-driven part of the benchmark: finding a cell's configuration,
traffic, runner and per-layer metrics by name, and running one cell.

Everything that belongs to one configuration, traffic mix or metric
lives in a file of its own, found by the name ``BENCHMARK.json`` gives:

    bench/configs/<config>.json      sizes, source, assumed, reduced
    bench/traffic/<traffic>.json     parameters; "runner" names the
                                     module bench/runners/<runner>.py
    bench/metrics/<metric>.py        read(record) -> float | None

A runner is a class ``Runner(cell)`` with ``setup()``, ``window(tracer)``
returning the end-to-end metrics, and ``check()`` returning the
comparisons that decide ``correct``; the harness runs them in that
order, reads the device's peak memory after the window and before the
reference, and assembles the result line.
"""

from __future__ import annotations

import dataclasses
import importlib
import importlib.util
import json
import time
from contextlib import contextmanager
from pathlib import Path

from bench.tracing import Tracer, breakdown

ROOT = Path(__file__).resolve().parents[1]
BENCH = ROOT / "bench"
TRACE_DIR = ROOT / ".bench_trace"


def load_json(path: Path) -> dict:
    return json.loads(Path(path).read_text())


def benchmark(root: Path = ROOT) -> dict:
    return load_json(root / "BENCHMARK.json")


def find(entries: list, name: str, what: str) -> dict:
    for entry in entries:
        if entry["name"] == name:
            return entry
    known = ", ".join(e["name"] for e in entries)
    raise KeyError(f"no {what} named {name!r} (known: {known})")


def config(name: str) -> dict:
    return load_json(BENCH / "configs" / f"{name}.json")


def traffic(name: str) -> dict:
    return load_json(BENCH / "traffic" / f"{name}.json")


def peaks(device_kind: str) -> dict:
    """The device's peaks; a device missing from the table is an error."""
    table = load_json(BENCH / "peaks.json")["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"bench/peaks.json (known: {sorted(table)})")
    return table[device_kind]


def metric_reader(name: str):
    """``read`` of ``bench/metrics/<name>.py`` (names may hold dots)."""
    path = BENCH / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        f"bench_metric_{name.replace('.', '_').replace('-', '_')}", path)
    if spec is None or not path.is_file():
        raise FileNotFoundError(f"no reader for metric {name!r} at {path}")
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


def cell_metrics(bench: dict, workload: str) -> tuple[list, list]:
    """(end-to-end, per-layer) metric entries a cell reports."""
    e2e = [m for m in bench["end_to_end"]
           if workload in m.get("workloads", [workload])]
    names = {m["name"] for m in e2e}
    layer = [m for m in bench["per_layer"]
             if workload in m.get("workloads", [workload])
             and m["moves"] in names]
    return e2e, layer


def runner_class(name: str):
    return importlib.import_module(f"bench.runners.{name}").Runner


@dataclasses.dataclass
class Cell:
    """What a runner is given: the cell's names and files, the run's
    seed and window, and (for controls) a cast applied to every demand
    the program is given."""

    workload: str
    config: dict
    traffic: dict
    seed: int
    seconds: float
    trace: bool
    demand_cast: object = None


@dataclasses.dataclass
class Record:
    """What the per-layer readers read: host-clock samples (seconds or
    counts) the runner took around its calls into each layer, counts,
    the trace's summary in traced runs, and the device's peaks."""

    samples: dict = dataclasses.field(default_factory=dict)
    counts: dict = dataclasses.field(default_factory=dict)
    trace: object = None
    peaks: dict | None = None

    def add(self, key: str, value: float) -> None:
        self.samples.setdefault(key, []).append(float(value))

    def mean(self, key: str) -> float | None:
        vals = self.samples.get(key)
        return sum(vals) / len(vals) if vals else None

    @contextmanager
    def span(self, name: str, key: str | None = None):
        """Time a call into one layer on the host clock and mark it on
        the profiler's host timeline (``bench.<name>``)."""
        import jax

        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation(f"bench.{name}"):
            yield
        if key is not None:
            self.add(key, time.perf_counter() - t0)


@dataclasses.dataclass
class Check:
    """One number compared with its limit (correct when value <= limit)."""

    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return self.value <= self.limit


def run_cell(cell: Cell, t_start: float, device=None,
             device_peaks: dict | None = None) -> dict:
    """Run one cell: set-up (timed from ``t_start``), window, peak
    memory, reference.  Returns the result line's fields (without the
    device's platform fields) plus the checks; ``device`` is read for
    peak memory when given."""
    bench = benchmark()
    e2e, per_layer = cell_metrics(bench, cell.workload)
    runner = runner_class(cell.traffic["runner"])(cell)
    runner.setup()
    setup_s = time.perf_counter() - t_start
    tracer = Tracer(TRACE_DIR / cell.workload) if cell.trace else None
    values = runner.window(tracer)
    if tracer is not None:
        tracer.stop()
    values["setup_s"] = setup_s
    memory = 0
    if device is not None:
        stats = device.memory_stats() or {}
        memory = int(stats.get("peak_bytes_in_use", 0))
    checks = runner.check()
    out = {"correct": all(c.ok for c in checks),
           "attempted": runner.attempted, "failed": runner.failed,
           "memory_peak_bytes": memory, "checks": checks,
           "record": runner.record}
    if not cell.trace:
        out["metrics"] = {m["name"]: {"value": values[m["name"]],
                                      "unit": m["unit"]} for m in e2e}
        return out
    record = runner.record
    record.trace = tracer.summary()
    record.peaks = device_peaks
    metrics = {}
    for m in per_layer:
        value = metric_reader(m["name"])(record)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    out["metrics"] = metrics
    out["busy_s"] = record.trace.busy_s
    out["window_s"] = record.trace.window_s
    out["breakdown"] = breakdown(record.trace)
    return out
