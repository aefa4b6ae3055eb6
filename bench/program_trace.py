"""What the program puts on a traced window itself: its ``repro.*`` host
spans, with the counts they carry, and the named scope of each device
operation.

``bench.tracing`` reduces a trace to the harness's ``bench.*`` spans and
the device's planes.  The per-layer metrics of the program's own layers
read the same trace again here, with what ``bench.tracing`` leaves out:
a span's stats (``repro.place.pass`` carries ``steps`` and ``wave_s``)
and each operation's ``tf_op`` (its ``op_name``, whose path holds the
``jax.named_scope`` it ran under).  ``jax.profiler.ProfileData`` gives
an event's own stats but not those of its metadata, where a TPU trace
keeps ``tf_op``, so those are read from the file's protobuf wire format
(``_op_names``).  A program that puts none of this on its trace reads as
nothing (``None``), never as 0.
"""

from __future__ import annotations

import bisect
import dataclasses
import functools
from pathlib import Path
from typing import NamedTuple

from bench import harness
from bench.tracing import (DEVICE_PLANE, MODULES_LINE, OPS_LINE, WINDOW,
                           union_ns)

PROGRAM_PREFIX = "repro."
PLAN = "bench.plan"
LP_PROGRAM = "_pdhg_run_many_tol"
# the named scopes of the tol-mode LP program (``repro.core.batch._tol_core``)
LP_SCOPES = ("ruiz", "operators", "power", "pdhg", "certificate", "polish",
             "unscale")
F64_SCOPES = ("certificate", "polish")


class RawEvent(NamedTuple):
    plane: str
    line: str
    name: str
    start_ns: float
    dur_ns: float
    stats: dict


def scope_of(name: str, scopes=LP_SCOPES) -> str:
    """The first component of an ``op_name`` path among ``scopes``."""
    for part in name.split("/"):
        if part in scopes:
            return part
    return ""


@dataclasses.dataclass
class ProgramTrace:
    """The traced window ``[lo, hi]``, the harness's plans inside it,
    the program's host spans inside it, and each device plane's
    operations (with their scope) and program executions."""

    lo: float
    hi: float
    plans: int
    spans: list     # [(name, start_ns, end_ns, stats)]
    ops: dict       # plane -> [(start_ns, end_ns, scope)]
    modules: dict   # plane -> [(name, start_ns, end_ns)]

    def has(self, name: str) -> bool:
        return any(s[0] == name for s in self.spans)

    def span_seconds(self, name: str) -> float:
        return 1e-9 * sum(e - s for n, s, e, _ in self.spans if n == name)

    def span_stat(self, name: str, key: str) -> float | None:
        """Sum of stat ``key`` over the spans ``name``; None where no
        such span carries it."""
        vals = [st[key] for n, _, _, st in self.spans
                if n == name and key in st]
        return float(sum(vals)) if vals else None

    def scope_pct(self, scopes, program: str = LP_PROGRAM) -> float | None:
        """Share, in %, of the device time of the programs named
        ``program`` in which an operation scoped in ``scopes`` ran:
        unions of intervals, never sums (a ``while`` covers its body),
        clipped to the window and summed over chips.  None where no
        operation of those programs carries an LP scope."""
        part = whole = 0.0
        scoped = False
        for plane, mods in self.modules.items():
            runs = sorted((s, e) for name, s, e in mods if program in name)
            starts = [s for s, _ in runs]

            def inside(t):
                i = bisect.bisect_right(starts, t) - 1
                return i >= 0 and t <= runs[i][1]

            mine = [(s, e, sc) for s, e, sc in self.ops.get(plane, ())
                    if inside(0.5 * (s + e))]
            scoped = scoped or any(sc for _, _, sc in mine)
            whole += union_ns(runs, self.lo, self.hi)[0]
            part += union_ns([(s, e) for s, e, sc in mine if sc in scopes],
                             self.lo, self.hi)[0]
        if not scoped or whole <= 0:
            return None
        return 100.0 * part / whole


def reduce(events) -> ProgramTrace:
    """A ``ProgramTrace`` of ``RawEvent``s over the ``WINDOW`` span."""
    host = [e for e in events if not DEVICE_PLANE.match(e.plane)]
    marks = [e for e in host if e.name == WINDOW]
    if not marks:
        raise ValueError(f"the trace has no {WINDOW!r} span")
    lo, hi = marks[0].start_ns, marks[0].start_ns + marks[0].dur_ns

    def within(e):
        return e.start_ns >= lo and e.start_ns + e.dur_ns <= hi

    ops, modules = {}, {}
    for e in events:
        if not DEVICE_PLANE.match(e.plane):
            continue
        end = e.start_ns + e.dur_ns
        if e.line == OPS_LINE:
            ops.setdefault(e.plane, []).append(
                (e.start_ns, end, scope_of(e.stats.get("tf_op", ""))))
        elif e.line == MODULES_LINE:
            modules.setdefault(e.plane, []).append((e.name, e.start_ns, end))
    return ProgramTrace(
        lo=lo, hi=hi,
        plans=sum(1 for e in host if e.name == PLAN and within(e)),
        spans=[(e.name, e.start_ns, e.start_ns + e.dur_ns, e.stats)
               for e in host
               if e.name.startswith(PROGRAM_PREFIX) and within(e)],
        ops=ops, modules=modules)


def load_raw(path: Path) -> list[RawEvent]:
    """The events of one ``.xplane.pb`` that the reduction reads: the
    window, the plans and the program's spans (with their stats) on the
    host, and the device's programs and operations (each with its
    ``tf_op``)."""
    from jax.profiler import ProfileData

    names = _op_names(Path(path).read_bytes())
    out = []
    for plane in ProfileData.from_file(str(path)).planes:
        device = bool(DEVICE_PLANE.match(plane.name))
        ops = names.get(plane.name, {})
        for line in plane.lines:
            if device and line.name not in (OPS_LINE, MODULES_LINE):
                continue
            for ev in line.events:
                name = ev.name
                if device:
                    stats = {"tf_op": ops[name]} if name in ops else {}
                elif name.startswith(PROGRAM_PREFIX):
                    stats = dict(ev.stats)
                elif name in (WINDOW, PLAN):
                    stats = {}
                else:
                    continue
                out.append(RawEvent(plane.name, line.name, name,
                                    float(ev.start_ns),
                                    float(ev.duration_ns), stats))
    return out


def _varint(buf: bytes, i: int) -> tuple[int, int]:
    shift = value = 0
    while True:
        b = buf[i]
        i += 1
        value |= (b & 0x7F) << shift
        if b < 0x80:
            return value, i
        shift += 7


def _fields(buf: bytes, lo: int, hi: int):
    """(field number, value) of one protobuf message in ``buf[lo:hi]``:
    an int, or the (start, end) of a length-delimited field."""
    i = lo
    while i < hi:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            n, i = _varint(buf, i)
            value, i = (i, i + n), i + n
        elif wire in (1, 5):
            value, i = None, i + (8 if wire == 1 else 4)
        else:
            raise ValueError(f"protobuf wire type {wire} at byte {i}")
        yield key >> 3, value


def _op_names(buf: bytes) -> dict:
    """{device plane: {event metadata name: tf_op}} of a serialized
    ``XSpace`` (tsl/profiler/protobuf/xplane.proto: XSpace.planes = 1;
    XPlane.name = 2, .event_metadata = 4, .stat_metadata = 5, map entries
    key = 1 and value = 2; XEventMetadata.name = 2, .stats = 5;
    XStat.metadata_id = 1, .str_value = 5, .ref_value = 7;
    XStatMetadata.id = 1, .name = 2)."""
    def text(span):
        return buf[span[0]:span[1]].decode("utf-8", "replace")

    def value(entry):  # a map entry's value message
        return next((v for f, v in _fields(buf, *entry) if f == 2), None)

    out = {}
    for f, plane in _fields(buf, 0, len(buf)):
        if f != 1:
            continue
        name, metas, stat_names = "", [], {}
        for pf, pv in _fields(buf, *plane):
            if pf == 2:
                name = text(pv)
            elif pf == 4:
                metas.append(pv)
            elif pf == 5 and (sm := value(pv)) is not None:
                fields = dict(_fields(buf, *sm))
                if 2 in fields:
                    stat_names[fields.get(1, 0)] = text(fields[2])
        if not DEVICE_PLANE.match(name):
            continue
        tf_op = {i for i, n in stat_names.items() if n == "tf_op"}
        ops = out.setdefault(name, {})
        for entry in metas:
            meta = value(entry)
            if meta is None:
                continue
            ev_name, op = None, None
            for mf, mv in _fields(buf, *meta):
                if mf == 2:
                    ev_name = text(mv)
                elif mf == 5:
                    stat = dict(_fields(buf, *mv))
                    if stat.get(1, 0) in tf_op:
                        op = (text(stat[5]) if 5 in stat
                              else stat_names.get(stat.get(7), ""))
            if ev_name is not None and op:
                ops[ev_name] = op
    return out


@functools.lru_cache(maxsize=1)
def _load(path: str, mtime_ns: int) -> ProgramTrace:
    return reduce(load_raw(Path(path)))


def of(record) -> ProgramTrace | None:
    """The program's part of a traced run's trace (the newest under the
    harness's trace directory); None for an untraced run."""
    if record.trace is None:
        return None
    files = sorted(Path(harness.TRACE_DIR).rglob("*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime_ns)
    if not files:
        return None
    return _load(str(files[-1]), files[-1].stat().st_mtime_ns)
