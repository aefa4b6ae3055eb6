"""Device traces: recording a short traced window, and reducing the
profiler's trace to the numbers the per-layer metrics read.

The reduction works on plain event tuples, so it is tested on recorded
and synthetic event lists alike.  On a TPU the profiler writes one plane
per chip (``/device:TPU:<i>``) whose ``XLA Ops`` line holds every
operation the chip ran and whose ``XLA Modules`` line holds every
program execution, named after the jitted function; the harness's own
spans (``bench.*``) sit on the host plane on the same clock.
"""

from __future__ import annotations

import dataclasses
import re
import shutil
from pathlib import Path
from typing import NamedTuple

WINDOW = "bench.traced_window"
SPAN_PREFIX = "bench."
DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


class Event(NamedTuple):
    plane: str
    line: str
    name: str
    start_ns: float
    dur_ns: float

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


@dataclasses.dataclass
class TraceSummary:
    """What a traced window shows: its length, the seconds in which an
    operation ran on the device (averaged over chips), the device
    seconds of each program and each operation (averaged over chips),
    the idle gaps of the first chip, and the harness's host spans."""

    window_s: float
    busy_s: float
    chips: int
    module_s: dict
    op_s: dict
    gaps: list            # [(start_ns, end_ns)] on the first chip
    host_spans: list      # [(name, start_ns, end_ns)]

    def idle_pct(self) -> float | None:
        if self.window_s <= 0 or self.chips == 0:
            return None
        return 100.0 * (1.0 - self.busy_s / self.window_s)

    def module_seconds(self, key: str) -> float:
        """Device seconds of the programs whose name contains ``key``."""
        return sum(s for name, s in self.module_s.items() if key in name)


def union_ns(intervals, lo: float, hi: float) -> tuple[float, list]:
    """Length of the union of ``(start, end)`` intervals clipped to
    [lo, hi], and the gaps of [lo, hi] that the union leaves."""
    clipped = sorted((max(s, lo), min(e, hi)) for s, e in intervals
                     if e > lo and s < hi)
    total, gaps, cursor = 0.0, [], lo
    for s, e in clipped:
        if s > cursor:
            gaps.append((cursor, s))
        if e > cursor:
            total += e - max(s, cursor)
            cursor = e
    if cursor < hi:
        gaps.append((cursor, hi))
    return total, gaps


def summarize(events, window: str = WINDOW) -> TraceSummary:
    """Reduce a trace's events to a ``TraceSummary`` over the host span
    named ``window``."""
    spans = [e for e in events if e.name.startswith(SPAN_PREFIX)
             and not DEVICE_PLANE.match(e.plane)]
    marks = [e for e in spans if e.name == window]
    if not marks:
        raise ValueError(f"the trace has no {window!r} span")
    lo, hi = marks[0].start_ns, marks[0].end_ns
    planes = sorted({e.plane for e in events if DEVICE_PLANE.match(e.plane)})
    busy, module_s, op_s, gaps = 0.0, {}, {}, []
    for i, plane in enumerate(planes):
        on = [e for e in events if e.plane == plane]
        ops = [e for e in on if e.line == OPS_LINE] or \
            [e for e in on if e.line == MODULES_LINE]
        b, g = union_ns([(e.start_ns, e.end_ns) for e in ops], lo, hi)
        busy += b
        if i == 0:
            gaps = g
        for line, acc in ((MODULES_LINE, module_s), (OPS_LINE, op_s)):
            for e in on:
                if e.line == line:
                    d = min(e.end_ns, hi) - max(e.start_ns, lo)
                    if d > 0:
                        acc[e.name] = acc.get(e.name, 0.0) + d
    chips = len(planes)
    scale = 1e-9 / max(chips, 1)
    return TraceSummary(
        window_s=(hi - lo) * 1e-9, busy_s=busy * scale, chips=chips,
        module_s={k: v * scale for k, v in module_s.items()},
        op_s={k: v * scale for k, v in op_s.items()},
        gaps=gaps,
        host_spans=[(e.name, e.start_ns, e.end_ns) for e in spans
                    if e.name != window])


def _label(gap, host_spans) -> str:
    """The host span that covers most of a gap (the innermost on a tie)."""
    s, e = gap
    best, best_key = "host: outside any span", (0.0, 0.0)
    for name, hs, he in host_spans:
        overlap = min(e, he) - max(s, hs)
        key = (overlap, -(he - hs))
        if overlap > 0 and key > best_key:
            best, best_key = name, key
    return best


def breakdown(summary: TraceSummary, top: int = 10) -> dict:
    """The device operations that took most time, and the longest idle
    gaps, each labelled by what the host was doing."""
    ops = sorted(summary.op_s.items(), key=lambda kv: -kv[1])[:top]
    gaps = sorted(summary.gaps, key=lambda g: g[0] - g[1])[:top]
    return {
        "device_ops": [[name, s] for name, s in ops],
        "idle_gaps": [[_label(g, summary.host_spans), (g[1] - g[0]) * 1e-9]
                      for g in gaps],
    }


def load_events(directory: Path) -> list[Event]:
    """Every event of the newest ``.xplane.pb`` under ``directory``."""
    from jax.profiler import ProfileData

    files = sorted(Path(directory).rglob("*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime)
    if not files:
        raise FileNotFoundError(f"no profiler trace under {directory}")
    data = ProfileData.from_file(str(files[-1]))
    return [Event(plane.name, line.name, ev.name, float(ev.start_ns),
                  float(ev.duration_ns))
            for plane in data.planes for line in plane.lines
            for ev in line.events]


class Tracer:
    """Starts and stops the profiler around a part of the window, in a
    fixed directory of the checkout that each traced run empties."""

    def __init__(self, directory: Path):
        self.directory = Path(directory)
        self._mark = None

    @property
    def active(self) -> bool:
        return self._mark is not None

    def start(self) -> None:
        import jax

        shutil.rmtree(self.directory, ignore_errors=True)
        self.directory.mkdir(parents=True)
        jax.profiler.start_trace(str(self.directory))
        self._mark = jax.profiler.TraceAnnotation(WINDOW)
        self._mark.__enter__()

    def stop(self) -> None:
        import jax

        if self._mark is None:
            return
        self._mark.__exit__(None, None, None)
        self._mark = None
        jax.profiler.stop_trace()

    def summary(self) -> TraceSummary:
        return summarize(load_events(self.directory))
