"""FleetEngine: the typed-config session API of the fleet-sweep path.

PRs 1-3 grew the fleet evaluation surface one keyword argument at a
time: ``evaluate_many`` ended up with ten kwargs spanning three
orthogonal concerns (how to solve the mapping LPs, how to run the greedy
placement phase, how to batch/chain the sweep) and returned bare lists
of nested dicts.  This module redesigns that surface around a session
object:

  * ``SolverConfig``    — LP phase: stopping regime (tol/iters), the
                          adaptive/restart machinery, operator form.
  * ``PlacementConfig`` — greedy phase: numpy-lockstep vs compiled
                          on-device vs per-instance engine, fit-policy
                          scan, filling override, plan check.
  * ``SweepConfig``     — fleet shape: shape-bucketed packing (this
                          module's planner), warm-started sweep
                          chaining, shard size of the LP dispatch.
  * ``FleetEngine``     — one configured session: ``pack(problems)``,
                          ``solve(...)``, ``place(...)``,
                          ``evaluate(...)`` -> ``FleetResult``.

Shape-bucketed packing (ROADMAP follow-on, landed here): a very ragged
grid padded to ONE worst-case ``(n, m, D, T')`` shape wastes most of its
padded FLOPs on zeros — e.g. a sweep whose instances span n=30..130 and
T=8..30 pads every instance to (130, 30).  ``plan_buckets`` partitions
the instances into a small number of shape buckets chosen by a cost
model (padded cells minimized, with a per-extra-bucket overhead term
standing in for the extra XLA compile), each bucket is packed/solved/
placed on its own padded shape, and results are merged back into
submission order.  Exactness rides on the engine's padding invariant
(padding never perturbs real coordinates — pinned by
``tests/test_batch.py::TestPack::test_pad_to_minimum_dims_is_exact``),
so bucketed costs equal single-bucket costs exactly while the padded-
cell waste drops measurably.

``core.api.evaluate_many`` / ``evaluate`` remain as thin shims mapping
the legacy kwargs onto these configs (single-bucket, so golden tables
stay bit-stable).
"""

from __future__ import annotations

import dataclasses
import json
import time

import numpy as np

from .api import ALGORITHMS
from .batch import (DEFAULT_CHECK_EVERY, PRECISIONS, SCALINGS,
                    ProblemBatch, _sweep_impl, pack_problems,
                    solve_lp_many)
from .lp_pdhg import PDHGResult, PDHGState, SolveStats
from .penalty import penalty_map
from .place_batch import place_many
from .placement import FIT_POLICIES, two_phase
from .constraints import expand_solution, lower_constraints
from .problem import Problem, trim_timeline
from .solution import Solution, verify
from .spans import span

__all__ = [
    "SolverConfig", "PlacementConfig", "SweepConfig", "FleetEngine",
    "FleetResult", "PackPlan", "Bucket", "plan_buckets",
    "DEFAULT_BUCKET_OVERHEAD",
]

_OPERATORS = ("auto", "dense", "cumsum", "pallas")
_PLACEMENT_ENGINES = ("batched", "compiled", "loop")
# PlacementConfig.engine -> place_many stepper name ('loop' bypasses
# place_many entirely)
_ENGINE_STEPPER = {"batched": "lockstep", "compiled": "compiled"}

# Planner cost of one extra shape bucket (one extra XLA compile of the
# fused stepper), expressed as a fraction of the single-bucket padded
# cell count: splitting must save at least this fraction of the whole
# grid's padded work per added bucket to pay for its compile.
DEFAULT_BUCKET_OVERHEAD = 0.03


# --- typed configs ---------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class SolverConfig:
    """Mapping-LP phase configuration (``core.batch.solve_lp_many``).

    tol=None runs the legacy fixed-step, fixed-``iters`` solve (bit-
    stable; the golden tables pin it); tol=<float> runs the adaptive
    restarted engine until every lane's normalized duality gap is below
    tol, with ``iters`` demoted to the worst-case cap.  ``adaptive`` /
    ``restart`` ablate the PDLP machinery; ``operator`` picks the
    congestion-operator form; ``check_every`` is the tol-mode
    convergence-check cadence (iteration telemetry quantizes to it).

    The speed-layer knobs (tol mode only; legacy mode ignores them):
    ``scaling='ruiz'`` equilibrates the packed operator by a Ruiz-style
    change of variables (fewer iterations on ill-conditioned
    heterogeneous-cost instances; cost semantics stay exact because the
    extraction rescales back); ``precision='mixed'`` iterates in f32
    with an f64 KKT certificate and a final f64 polish pass ('f64'
    runs the whole iterate in f64); ``omega`` enables PDLP-style
    primal-weight balancing next to the adaptive step machinery.

    >>> SolverConfig().tol is None        # legacy fixed-iteration mode
    True
    >>> SolverConfig(scaling="log")
    Traceback (most recent call last):
        ...
    ValueError: scaling must be one of ('none', 'ruiz'), got 'log'
    >>> SolverConfig(tol=5e-3).check_every == DEFAULT_CHECK_EVERY
    True
    >>> SolverConfig(iters=0)
    Traceback (most recent call last):
        ...
    ValueError: iters must be >= 1, got 0
    """

    tol: float | None = None
    iters: int = 2000
    adaptive: bool = True
    restart: bool = True
    operator: str = "auto"
    step_scale: float = 0.9
    check_every: int = DEFAULT_CHECK_EVERY
    scaling: str = "ruiz"
    precision: str = "mixed"
    omega: bool = True

    def __post_init__(self):
        if self.tol is not None and not self.tol > 0:
            raise ValueError(f"tol must be positive or None, got {self.tol!r}")
        if self.iters < 1:
            raise ValueError(f"iters must be >= 1, got {self.iters!r}")
        if self.operator not in _OPERATORS:
            raise ValueError(
                f"operator must be one of {_OPERATORS}, got {self.operator!r}")
        if not self.step_scale > 0:
            raise ValueError(
                f"step_scale must be positive, got {self.step_scale!r}")
        if self.check_every < 1:
            raise ValueError(
                f"check_every must be >= 1, got {self.check_every!r}")
        if self.scaling not in SCALINGS:
            raise ValueError(
                f"scaling must be one of {SCALINGS}, got {self.scaling!r}")
        if self.precision not in PRECISIONS:
            raise ValueError(
                f"precision must be one of {PRECISIONS}, "
                f"got {self.precision!r}")


@dataclasses.dataclass(frozen=True)
class PlacementConfig:
    """Greedy placement phase configuration.

    engine='batched' advances all instances in lockstep through the
    vectorized-numpy stepper (``place_many``); 'compiled' routes the
    same lockstep through the on-device ``lax.scan`` stepper
    (``place_step``) so the host dispatches once per node-type phase
    boundary — or once per *call* without filling — instead of once
    per placement step (oversized pools fall back to the numpy
    stepper); 'loop' restores the per-instance ``two_phase`` loop.
    Placements and costs are identical across all three engines.
    fit='best' scans every fit policy and keeps the per-instance
    minimum (the paper's §VI protocol); a concrete policy
    ('first'/'similarity') narrows the scan.  ``filling`` only applies
    to direct ``FleetEngine.place`` calls (the protocol derives
    filling from the algorithm name).  ``check`` verifies every
    returned placement against the instance constraints.

    >>> PlacementConfig().engine
    'batched'
    >>> PlacementConfig(engine="compiled", fit="similarity").fits
    ('similarity',)
    >>> PlacementConfig(engine="warp")
    Traceback (most recent call last):
        ...
    ValueError: placement engine must be one of ('batched', 'compiled', 'loop'), got 'warp'
    """

    engine: str = "batched"
    fit: str = "best"
    filling: bool = False
    check: bool = True

    def __post_init__(self):
        if self.engine not in _PLACEMENT_ENGINES:
            raise ValueError(
                f"placement engine must be one of {_PLACEMENT_ENGINES}, "
                f"got {self.engine!r}")
        if self.fit != "best" and self.fit not in FIT_POLICIES:
            raise ValueError(
                f"fit must be 'best' or one of {FIT_POLICIES}, "
                f"got {self.fit!r}")

    @property
    def fits(self) -> tuple[str, ...]:
        return FIT_POLICIES if self.fit == "best" else (self.fit,)


@dataclasses.dataclass(frozen=True)
class SweepConfig:
    """Fleet-shape configuration: bucketing, warm starts, sharding.

    max_buckets caps the shape-bucket partition of the packing planner
    (1 = legacy single-bucket packing); bucket_overhead is the planner's
    cost of one extra bucket (one extra compile), as a fraction of the
    single-bucket padded cell count.  warm_start=k treats the instances
    as a grid-adjacent sweep chained in consecutive groups of k (None =
    off; k <= 0 is an error, not "off" — and when k does not divide B
    the trailing group is smaller and COLD-starts, because its lanes no
    longer align with the predecessor state).  shard_size splits each
    bucket's LP solve into dispatches of at most that many instances
    (peak-memory knob; shards share the bucket's padded shape, so all
    equal-sized shards reuse one compile and results are unchanged).

    pipeline=True compiles the whole warm-started chain into ONE
    ``lax.scan`` over the groups — one host dispatch for the entire
    sweep instead of one per group (requires ``warm_start``, and the
    group size must divide the instance count so every scanned group
    stacks to one shape).  ``devices`` additionally shards the batch
    dim across that many local devices via ``shard_map`` (None = no
    sharding; the group size must divide by it, and the count is
    validated against ``jax.local_device_count()`` at config time —
    single-device CPU hosts fail HERE with a clear error instead of
    deep inside the shard_map dispatch).

    warm_start and max_buckets > 1 are mutually exclusive: the warm
    chain packs every group to one common shape so primal/dual states
    carry over lane-for-lane, which is the opposite trade of bucketing.

    >>> (SweepConfig(max_buckets=4).bucket_overhead
    ...  == DEFAULT_BUCKET_OVERHEAD)
    True
    >>> SweepConfig(warm_start=2, max_buckets=3)
    Traceback (most recent call last):
        ...
    ValueError: SweepConfig.warm_start and SweepConfig.max_buckets > 1 are mutually exclusive: ...
    """

    warm_start: int | None = None
    shard_size: int | None = None
    max_buckets: int = 1
    bucket_overhead: float = DEFAULT_BUCKET_OVERHEAD
    pipeline: bool = False
    devices: int | None = None

    def __post_init__(self):
        if self.warm_start is not None and self.warm_start <= 0:
            raise ValueError(
                f"warm_start must be a positive group size, got "
                f"{self.warm_start!r}; use warm_start=None to disable "
                f"warm-started sweep chaining")
        if self.shard_size is not None and self.shard_size <= 0:
            raise ValueError(
                f"shard_size must be a positive instance count, got "
                f"{self.shard_size!r}")
        if self.max_buckets < 1:
            raise ValueError(
                f"max_buckets must be >= 1, got {self.max_buckets!r}")
        if self.bucket_overhead < 0:
            raise ValueError(
                f"bucket_overhead must be >= 0, got {self.bucket_overhead!r}")
        if self.warm_start is not None and self.max_buckets > 1:
            raise ValueError(
                "SweepConfig.warm_start and SweepConfig.max_buckets > 1 "
                "are mutually exclusive: warm-started sweep chaining "
                "packs every group to one common shape (states must "
                "align lane-for-lane), while bucketing splits shapes "
                "apart.  To combine warm starts with shape-bucketed "
                "micro-batches online, use the serving loop "
                "(repro.serve.RightsizingService), which re-buckets per "
                "tick and carries per-fleet state across re-solves")
        if self.warm_start is not None and self.shard_size is not None:
            raise ValueError(
                "SweepConfig.warm_start and SweepConfig.shard_size are "
                "mutually exclusive: the warm chain already dispatches "
                "one group at a time (warm_start IS its shard size), so "
                "a separate shard size would be silently ignored.  For "
                "warm-started dispatches of bounded size, use the "
                "serving loop (repro.serve.RightsizingService), whose "
                "admission queue caps each tick's micro-batch")
        if self.pipeline and self.warm_start is None:
            raise ValueError(
                "SweepConfig.pipeline=True requires warm_start: the "
                "compiled pipeline IS the warm-started sweep chain "
                "fused into one lax.scan dispatch; set warm_start=<group "
                "size> to enable it")
        if self.devices is not None and not self.pipeline:
            raise ValueError(
                "SweepConfig.devices requires pipeline=True: the "
                "shard_map batch axis shards the compiled sweep "
                "pipeline's lanes; sequential dispatches don't shard")
        if self.devices is not None and self.devices < 1:
            raise ValueError(
                f"devices must be >= 1 or None, got {self.devices!r}")
        if self.devices is not None:
            import jax

            avail = jax.local_device_count()
            if self.devices > avail:
                raise ValueError(
                    f"SweepConfig(devices={self.devices}) but only "
                    f"{avail} local JAX device(s) are visible: the "
                    f"shard_map sweep pipeline places one batch shard "
                    f"per device, so the config would fail at dispatch "
                    f"time with a cryptic mesh error.  Use devices<="
                    f"{avail}, or (CPU hosts) set XLA_FLAGS="
                    f"--xla_force_host_platform_device_count=N before "
                    f"importing jax to expose N host devices")


# --- shape-bucketed packing planner ----------------------------------------

def _own_cells(t: Problem) -> int:
    return t.n * t.m * t.D * t.T


def plan_buckets(problems, max_buckets: int = 1,
                 overhead: float = DEFAULT_BUCKET_OVERHEAD) -> list[list[int]]:
    """Partition (trimmed) instances into <= max_buckets shape buckets.

    Minimizes total padded cells ``sum_b B_b * n̂_b * m̂_b * D̂_b * T̂_b``
    (hats = per-bucket dimension maxima — the padded footprint every
    batched array and operator apply scales with) plus ``overhead *
    single_bucket_cells`` per bucket beyond the first (the extra-compile
    cost).  Instances are sorted by their own cell count and the DP
    finds the optimal contiguous partition of that order, which captures
    the ragged-sweep structure (shapes grow along sweep axes) without a
    4-D clustering pass.  Ties prefer fewer buckets; each returned
    bucket lists its instance indices in ascending submission order.
    """
    B = len(problems)
    if B == 0:
        raise ValueError("plan_buckets needs at least one instance")
    dims = np.array([(t.n, t.m, t.D, t.T) for t in problems], np.int64)
    if max_buckets <= 1 or B == 1:
        return [list(range(B))]
    cells = dims.prod(axis=1)
    order = sorted(range(B), key=lambda i: (int(cells[i]),
                                            tuple(dims[i]), i))
    sd = dims[order]  # (B, 4) in planning order
    single = float(B * sd.max(axis=0).prod())
    pay = overhead * single

    K = min(max_buckets, B)
    INF = float("inf")
    # dp[j] = min padded cells of the first j planned instances split
    # into exactly k buckets; the last bucket [i, j) has its per-dim
    # maxima accumulated by walking i downward, so one layer is O(B^2)
    dp_prev = [0.0] + [INF] * B  # k=0 layer: only 0 instances coverable
    best_cost, best_k = INF, 1
    cuts: list[list[int | None]] = []
    for k in range(1, K + 1):
        dp: list[float] = [INF] * (B + 1)
        cut: list[int | None] = [None] * (B + 1)
        for j in range(k, B + 1):
            mx = sd[j - 1].copy()
            for i in range(j - 1, k - 2, -1):
                np.maximum(mx, sd[i], out=mx)
                if dp_prev[i] == INF:
                    continue
                cand = dp_prev[i] + float((j - i) * mx.prod())
                if cand < dp[j]:
                    dp[j] = cand
                    cut[j] = i
        cuts.append(cut)
        total = dp[B] + pay * (k - 1)
        if total < best_cost:  # strict: exact ties keep fewer buckets
            best_cost, best_k = total, k
        dp_prev = dp

    # reconstruct the best_k-bucket partition
    segs = []
    j, k = B, best_k
    while j > 0:
        i = cuts[k - 1][j]
        segs.append((i, j))
        j, k = i, k - 1
    segs.reverse()
    return [sorted(order[i:j]) for i, j in segs]


@dataclasses.dataclass(frozen=True)
class Bucket:
    """One shape bucket: submission-order indices + their packed batch."""

    indices: tuple[int, ...]
    batch: ProblemBatch

    @property
    def B(self) -> int:
        return self.batch.B

    @property
    def shape(self) -> tuple[int, int, int, int]:
        return self.batch.shape

    @property
    def cells(self) -> int:
        """Padded cells of this bucket's batched arrays."""
        b = self.batch
        return b.B * b.n * b.m * b.D * b.Tp

    @property
    def own_cells(self) -> int:
        return sum(_own_cells(t) for t in self.batch.problems)


@dataclasses.dataclass(frozen=True)
class PackPlan:
    """A bucketed packing of one fleet: the output of ``FleetEngine.pack``.

    ``buckets[b].indices`` are submission-order instance indices; their
    concatenation is a permutation of ``range(n_instances)`` (the merge
    key ``FleetEngine.evaluate`` uses to restore submission order).
    ``cells_single`` is the padded cell count of legacy single-bucket
    packing, the baseline every waste metric compares against.
    """

    buckets: tuple[Bucket, ...]
    n_instances: int
    cells_single: int

    @property
    def n_buckets(self) -> int:
        return len(self.buckets)

    @property
    def cells_packed(self) -> int:
        return sum(b.cells for b in self.buckets)

    @property
    def cells_own(self) -> int:
        return sum(b.own_cells for b in self.buckets)

    @property
    def waste_single(self) -> float:
        """Padded-cell waste fraction of single-bucket packing."""
        return 1.0 - self.cells_own / max(self.cells_single, 1)

    @property
    def waste_packed(self) -> float:
        """Padded-cell waste fraction of this bucketed packing."""
        return 1.0 - self.cells_own / max(self.cells_packed, 1)

    @property
    def waste_reduction(self) -> float:
        """Fraction of single-bucket WASTED cells this plan eliminates."""
        wasted_single = self.cells_single - self.cells_own
        wasted_packed = self.cells_packed - self.cells_own
        if wasted_single <= 0:
            return 0.0
        return 1.0 - wasted_packed / wasted_single

    def summary(self) -> dict:
        return {
            "buckets": self.n_buckets,
            "bucket_sizes": [b.B for b in self.buckets],
            "bucket_shapes": [list(b.shape) for b in self.buckets],
            "cells_single": int(self.cells_single),
            "cells_packed": int(self.cells_packed),
            "cells_own": int(self.cells_own),
            "waste_frac_single": round(self.waste_single, 4),
            "waste_frac_bucketed": round(self.waste_packed, 4),
            "waste_reduction": round(self.waste_reduction, 4),
        }


# --- structured results ----------------------------------------------------

@dataclasses.dataclass
class FleetResult:
    """Structured output of ``FleetEngine.evaluate``.

    entries: one §VI protocol dict per instance, in submission order —
        {'lb', 'costs': {algo: cost}, 'normalized': {algo: cost/lb},
        'wall_s': {algo: s}, 'plan': {algo: Solution}} plus a 'solver'
        telemetry block in tol mode (iters/restarts/kkt/converged per
        instance); 'plan' holds each algorithm's kept plan, verified
        when ``PlacementConfig.check`` is set (the batched and compiled
        engines).
    stats: the ``SolveStats`` of each batched LP dispatch (one per
        bucket shard, or one per warm-started group); empty in legacy
        fixed-iters mode.
    plan: the bucketed ``PackPlan`` (None on the warm-sweep path, which
        packs to one common shape by construction).
    timings: phase breakdown — pack_s / lp_s / place_s / total_s plus
        per-bucket lists bucket_lp_s / bucket_place_s and a
        ``placement`` block (which placement engine ran, stepper calls
        and waves, summed per-wave seconds; for the compiled stepper
        also device-dispatch counts, execution modes, and fallbacks).

    >>> r = FleetResult(
    ...     entries=[{"lb": 1.0, "costs": {"lp-map": 2.0},
    ...               "normalized": {"lp-map": 2.0},
    ...               "wall_s": {"lp-map": 0.1}}],
    ...     stats=[], plan=None, timings={})
    >>> r.algos, r.costs("lp-map")
    (('lp-map',), [2.0])
    >>> r.to_rows()[0]["cost[lp-map]"]
    2.0
    """

    entries: list[dict]
    stats: list[SolveStats]
    plan: PackPlan | None
    timings: dict

    def __len__(self) -> int:
        return len(self.entries)

    @property
    def algos(self) -> tuple[str, ...]:
        return tuple(self.entries[0]["costs"]) if self.entries else ()

    def costs(self, algo: str) -> list[float]:
        return [e["costs"][algo] for e in self.entries]

    def to_rows(self) -> list[dict]:
        """Flat benchmark rows, one per instance (JSON/CSV-ready)."""
        rows = []
        for i, e in enumerate(self.entries):
            row: dict = {"instance": i, "lb": e["lb"]}
            for algo in e["costs"]:
                row[f"cost[{algo}]"] = e["costs"][algo]
                row[f"normalized[{algo}]"] = e["normalized"][algo]
                row[f"wall_s[{algo}]"] = e["wall_s"][algo]
            for key, val in e.get("solver", {}).items():
                row[f"solver.{key}"] = val
            rows.append(row)
        return rows

    def to_json(self, indent: int | None = None) -> str:
        """Whole-result JSON: rows + plan summary + timings + solver
        aggregates (what the benchmark drivers persist)."""
        blob = {
            "entries": self.to_rows(),
            "timings": self.timings,
            "plan": self.plan.summary() if self.plan is not None else None,
            "solver": [s.summary() for s in self.stats],
        }
        return json.dumps(blob, indent=indent)


# --- the protocol engine ---------------------------------------------------

def _protocol_batched(batch: ProblemBatch, lp_results, algos, fits,
                      check: bool = True,
                      stepper: str = "lockstep",
                      tels: list | None = None,
                      timings: dict | None = None) -> list[dict]:
    """Batched placement protocol: every (mapping, fit, filling) combo of
    every algorithm runs as ONE lockstep ``place_many`` over the grid
    (through the ``stepper`` of the configured placement engine);
    per-call stepper telemetry is appended to ``tels``, and the seconds
    spent verifying kept plans are added to ``timings["verify_s"]``.
    Each pass is a ``repro.place.pass`` span carrying its lockstep step
    count, wave seconds, timeline slots read (windowed and not) and
    cross-fill attempts (all, and those skipped without a step) as
    trace metadata.  Each entry keeps every algorithm's chosen plan,
    verified when ``check`` is set, under ``"plan"``."""
    from .api import rightsize

    B = batch.B
    out = [{"lb": res.lower_bound, "costs": {}, "normalized": {},
            "wall_s": {}, "plan": {}} for res in lp_results]
    for algo in algos:
        t0 = time.perf_counter()
        filling = algo.endswith("-f")
        if algo in ("penalty-map", "penalty-map-f"):
            mapsets = [[penalty_map(t, kind) for t in batch.problems]
                       for kind in ("avg", "max")]
        elif algo in ("lp-map", "lp-map-f"):
            mapsets = [[res.mapping for res in lp_results]]
        else:
            # extended algos (e.g. "+ls") keep the per-instance path
            for b, t in enumerate(batch.problems):
                sol = rightsize(t, algo, lp_result=lp_results[b],
                                check=check)
                out[b]["costs"][algo] = sol.cost(t)
                out[b]["wall_s"][algo] = sol.meta["wall_s"]
                out[b]["plan"][algo] = sol
            continue
        best: list[Solution | None] = [None] * B
        best_cost = [float("inf")] * B
        for maps in mapsets:
            for fit in fits:
                tel: dict = {}
                with span("place.pass") as ann:
                    sols = place_many(batch, maps, fit=fit,
                                      filling=filling,
                                      meta={"algo": algo},
                                      placement=stepper, telemetry=tel)
                    ann.set_metadata(
                        steps=tel.get("steps", 0),
                        wave_s=sum(tel.get("wave_s", ())),
                        window_slots=tel.get("window_slots", 0),
                        slots=tel.get("slots", 0),
                        fill_attempts=tel.get("fill_attempts", 0),
                        fill_skipped=tel.get("fill_skipped", 0))
                if tels is not None:
                    tels.append(tel)
                for b, (t, s) in enumerate(zip(batch.problems, sols)):
                    c = s.cost(t)
                    if c < best_cost[b]:
                        best_cost[b], best[b] = c, s
        wall = (time.perf_counter() - t0) / B
        for b, t in enumerate(batch.problems):
            if check:
                with span("verify", timings, "verify_s"):
                    verify(t, best[b])
            out[b]["costs"][algo] = best_cost[b]
            out[b]["wall_s"][algo] = wall
            out[b]["plan"][algo] = best[b]
    for entry in out:
        lb = max(entry["lb"], 1e-12)
        entry["normalized"] = {a: c / lb
                               for a, c in entry["costs"].items()}
    return out


def _placement_telemetry(engine: str, tels: list) -> dict:
    """Aggregate per-call stepper telemetry into the ``FleetResult``
    timings block: which stepper actually ran, how many device
    dispatches the compiled stepper issued, how often it fell back,
    the summed per-phase (wave) seconds, and the numpy lockstep
    engine's step count, timeline slots read (``window_slots``, of
    ``slots``) and cross-fill attempts (``fill_attempts``, of them
    ``fill_skipped`` without a step)."""
    out: dict = {"engine": engine, "calls": len(tels)}
    if engine == "loop" or not tels:
        return out
    out["waves"] = max((t.get("waves", 0) for t in tels), default=0)
    out["wave_s_total"] = sum(sum(t.get("wave_s", ())) for t in tels)
    for key in ("steps", "window_slots", "slots", "fill_attempts",
                "fill_skipped"):
        out[key] = sum(t.get(key, 0) for t in tels)
    if engine == "compiled":
        out["dispatches"] = sum(t.get("dispatches", 0) for t in tels)
        out["fallbacks"] = sum(1 for t in tels
                               if t.get("engine") != "compiled")
        out["modes"] = sorted({t["mode"] for t in tels if "mode" in t})
    return out


class FleetEngine:
    """One configured fleet-evaluation session (the §VI protocol at
    fleet scale): ``pack`` plans the shape buckets, ``solve`` runs the
    mapping-LP phase, ``place`` runs one greedy placement pass, and
    ``evaluate`` runs the whole protocol into a ``FleetResult``.

        engine = FleetEngine(
            solver=SolverConfig(tol=5e-3, iters=4000),
            sweep=SweepConfig(max_buckets=4),
        )
        result = engine.evaluate(problems)
        result.entries[0]["normalized"]       # cost / LP lower bound
        result.plan.summary()                 # bucket shapes + waste
        result.to_rows()                      # flat benchmark rows
        result.timings["placement"]           # stepper telemetry

    The legacy ``evaluate_many`` kwargs map onto the configs one-to-one
    (see docs/architecture.md for the migration table); with the
    default single-bucket ``SweepConfig`` the engine executes exactly
    the legacy code path, so golden tables are bit-stable under the
    shim.

    >>> from repro.core import FleetEngine, SolverConfig
    >>> from repro.workload import SyntheticSpec, synthetic_instance
    >>> fleet = [synthetic_instance(SyntheticSpec(n=10, m=2, D=2, T=6,
    ...                                           seed=s))
    ...          for s in (0, 1)]
    >>> engine = FleetEngine(solver=SolverConfig(iters=40),
    ...                      algos=("penalty-map",))
    >>> result = engine.evaluate(fleet)
    >>> len(result), result.algos
    (2, ('penalty-map',))
    >>> result.timings["placement"]["engine"]
    'batched'
    """

    def __init__(self, solver: SolverConfig | None = None,
                 placement: PlacementConfig | None = None,
                 sweep: SweepConfig | None = None,
                 algos=ALGORITHMS):
        self.solver = solver if solver is not None else SolverConfig()
        self.placement = placement if placement is not None \
            else PlacementConfig()
        self.sweep = sweep if sweep is not None else SweepConfig()
        self.algos = tuple(algos)
        if self.sweep.warm_start is not None and self.solver.tol is None:
            raise ValueError(
                "warm_start requires a tolerance-stopped solver "
                "(SolverConfig(tol=...)); fixed-iteration solves gain "
                "nothing from a warm start")
        if self.placement.engine == "loop" and self.placement.fit != "best":
            raise ValueError(
                "the per-instance 'loop' placement engine always scans "
                "every fit policy (the legacy protocol); narrowing "
                "PlacementConfig.fit requires engine='batched'")

    def with_overrides(self, **changes) -> "FleetEngine":
        """Derive a new engine with field-level changes routed across
        the config family (``dataclasses.replace`` under the hood).

        Accepts any field of ``SolverConfig`` / ``PlacementConfig`` /
        ``SweepConfig`` by name (the three families share no field
        names), whole replacement configs via ``solver=`` /
        ``placement=`` / ``sweep=``, and ``algos=``.  The derived
        engine re-validates, so invalid combinations fail exactly as
        they would at construction.  The base engine is untouched.

        >>> eng = FleetEngine(solver=SolverConfig(tol=5e-3))
        >>> eng2 = eng.with_overrides(tol=1e-2, fit="first")
        >>> (eng2.solver.tol, eng2.placement.fit, eng.solver.tol)
        (0.01, 'first', 0.005)
        >>> eng.with_overrides(fuel="ion")
        Traceback (most recent call last):
            ...
        ValueError: with_overrides got unknown field 'fuel'; ...
        """
        changes = dict(changes)
        parts = {
            "solver": changes.pop("solver", self.solver),
            "placement": changes.pop("placement", self.placement),
            "sweep": changes.pop("sweep", self.sweep),
        }
        algos = changes.pop("algos", self.algos)
        owner = {f.name: g for g, cfg in parts.items()
                 for f in dataclasses.fields(cfg)}
        grouped: dict[str, dict] = {g: {} for g in parts}
        for name, value in changes.items():
            if name not in owner:
                known = ", ".join(sorted(owner))
                raise ValueError(
                    f"with_overrides got unknown field {name!r}; "
                    f"expected solver=/placement=/sweep=/algos= or one "
                    f"of the config fields: {known}")
            grouped[owner[name]][name] = value
        return FleetEngine(
            solver=dataclasses.replace(parts["solver"],
                                       **grouped["solver"]),
            placement=dataclasses.replace(parts["placement"],
                                          **grouped["placement"]),
            sweep=dataclasses.replace(parts["sweep"], **grouped["sweep"]),
            algos=algos)

    # -- phase 0: pack -------------------------------------------------

    def pack(self, problems) -> PackPlan:
        """Trim, bucket (``plan_buckets``), and pad-and-stack a fleet.

        A pre-packed ``ProblemBatch`` passes through as one bucket (its
        padding is taken as-is, so bucketing never re-pads a batch the
        caller already laid out).  Constrained instances are lowered
        here (``repro.core.constraints``) before trimming, so every
        downstream phase sees plain instances."""
        if isinstance(problems, ProblemBatch):
            bucket = Bucket(indices=tuple(range(problems.B)),
                            batch=problems)
            return PackPlan(buckets=(bucket,), n_instances=problems.B,
                            cells_single=bucket.cells)
        trimmed = [trim_timeline(lower_constraints(p).lowered)[0]
                   for p in problems]
        if not trimmed:
            raise ValueError("FleetEngine.pack needs at least one instance")
        parts = plan_buckets(trimmed, max_buckets=self.sweep.max_buckets,
                             overhead=self.sweep.bucket_overhead)
        buckets = tuple(
            Bucket(indices=tuple(idx),
                   batch=pack_problems([trimmed[i] for i in idx],
                                       assume_trimmed=True))
            for idx in parts)
        n_hat = max(t.n for t in trimmed)
        m_hat = max(t.m for t in trimmed)
        d_hat = max(t.D for t in trimmed)
        t_hat = max(t.T for t in trimmed)
        return PackPlan(
            buckets=buckets, n_instances=len(trimmed),
            cells_single=len(trimmed) * n_hat * m_hat * d_hat * t_hat)

    # -- phase 1: the mapping-LP solve ---------------------------------

    def _solve_batch(self, batch: ProblemBatch, init=None):
        """One LP dispatch under ``self.solver`` -> (results, [stats])."""
        cfg = self.solver
        if cfg.tol is None:
            res = solve_lp_many(batch, iters=cfg.iters,
                                step_scale=cfg.step_scale,
                                operator=cfg.operator, init=init)
            return res, []
        res, st = solve_lp_many(
            batch, iters=cfg.iters, step_scale=cfg.step_scale,
            operator=cfg.operator, tol=cfg.tol, adaptive=cfg.adaptive,
            restart=cfg.restart, check_every=cfg.check_every, init=init,
            scaling=cfg.scaling, precision=cfg.precision, omega=cfg.omega,
            full_output=True)
        return res, [st]

    @staticmethod
    def _slice_state(state: PDHGState | None, lo: int, hi: int):
        if state is None:
            return None
        return PDHGState(
            x=state.x[lo:hi], y=state.y[lo:hi],
            eta=None if state.eta is None else state.eta[lo:hi],
            omega=None if state.omega is None else state.omega[lo:hi])

    def _solve_bucket(self, bucket: Bucket, init: PDHGState | None = None):
        """Solve one bucket, sharded to ``sweep.shard_size`` instances
        per dispatch (shards share the bucket's padded shape, so every
        full shard reuses one compile and results are unchanged); an
        ``init`` state is sliced lane-for-lane across the shards."""
        shard = self.sweep.shard_size
        batch = bucket.batch
        if shard is None or batch.B <= shard:
            return self._solve_batch(batch, init=init)
        shape = batch.shape
        results: list[PDHGResult] = []
        stats: list[SolveStats] = []
        for i in range(0, batch.B, shard):
            sub = pack_problems(batch.problems[i : i + shard],
                                pad_to=shape, assume_trimmed=True)
            res, st = self._solve_batch(
                sub, init=self._slice_state(init, i, i + shard))
            results.extend(res)
            stats.extend(st)
        return results, stats

    def solve(self, problems, init: PDHGState | None = None):
        """Mapping-LP phase only: ``(results, stats)`` with one
        ``PDHGResult`` per instance in submission order.  Accepts a
        problem sequence, a ``ProblemBatch``, or a ``PackPlan``.

        ``init`` warm-starts lane b of the dispatch from lane b of a
        previous solve's ``PDHGState`` (the serving loop's per-tick
        re-solve path).  It requires a single-bucket plan — the state's
        lanes align with ONE dispatch — and is rejected on the
        warm-started sweep path, which manages its own state chain."""
        if self.sweep.warm_start is not None:
            if init is not None:
                raise ValueError(
                    "solve(init=...) conflicts with "
                    "SweepConfig.warm_start: the warm-started sweep "
                    "chain seeds each group from its predecessor")
            trimmed = self._trimmed(problems)
            return self._solve_warm(trimmed)
        plan = problems if isinstance(problems, PackPlan) \
            else self.pack(problems)
        if init is not None and plan.n_buckets > 1:
            raise ValueError(
                f"solve(init=...) needs a single-bucket plan (state "
                f"lanes align with one dispatch), got {plan.n_buckets} "
                f"buckets; pack to one bucket or pass a ProblemBatch")
        results: list[PDHGResult | None] = [None] * plan.n_instances
        stats: list[SolveStats] = []
        for bucket in plan.buckets:
            res, st = self._solve_bucket(bucket, init=init)
            for i, r in zip(bucket.indices, res):
                results[i] = r
            stats.extend(st)
        return results, stats

    def solve_scenarios(self, problems, init: PDHGState | None = None):
        """Same-shape scenario group: ONE batched LP dispatch for K
        instances sharing one trimmed ``(n, m, D, T')`` shape.

        This is the Monte-Carlo fan-out entry (``repro.stochastic``):
        K scenario instances drawn from one demand forecast differ
        only in their demand vectors, so they already share a padded
        shape — the bucket planner has nothing to decide and every
        lane belongs in the same dispatch.  The shape is validated
        eagerly (a mixed-shape group raises, naming the shapes) and
        the planner is bypassed, so the K-lane solve issues exactly
        one compiled dispatch regardless of ``SweepConfig.max_buckets``
        (``shard_size`` still bounds the dispatch if set).  Returns
        ``(results, stats)`` like :meth:`solve`.

        >>> from repro.workload import SyntheticSpec, synthetic_instance
        >>> fleet = [synthetic_instance(SyntheticSpec(n=8, m=2, D=2,
        ...                                           T=6, seed=0))] * 2
        >>> eng = FleetEngine(solver=SolverConfig(tol=1e-2, iters=400))
        >>> results, stats = eng.solve_scenarios(fleet)
        >>> len(results), results[0].mapping.shape
        (2, (8,))
        """
        if self.sweep.warm_start is not None:
            raise ValueError(
                "solve_scenarios conflicts with SweepConfig.warm_start: "
                "a scenario group is one same-shape batch solved in a "
                "single dispatch, not a grid-adjacent sweep chain; use "
                "a SweepConfig without warm_start")
        trimmed = self._trimmed(problems)
        if not trimmed:
            raise ValueError("solve_scenarios needs at least one instance")
        shapes = {(t.n, t.m, t.D, t.T) for t in trimmed}
        if len(shapes) > 1:
            raise ValueError(
                f"solve_scenarios needs every trimmed instance on ONE "
                f"(n, m, D, T') shape (that is what makes the group a "
                f"single batched dispatch), got {sorted(shapes)}; fan "
                f"scenarios out of one forecast base "
                f"(repro.stochastic.fan_out) or pad them yourself")
        batch = problems if isinstance(problems, ProblemBatch) \
            else pack_problems(trimmed, assume_trimmed=True)
        bucket = Bucket(indices=tuple(range(batch.B)), batch=batch)
        return self._solve_bucket(bucket, init=init)

    def _trimmed(self, problems) -> list[Problem]:
        if isinstance(problems, ProblemBatch):
            return list(problems.problems)
        if isinstance(problems, PackPlan):
            raise ValueError(
                "warm-started sweeps take the problem sequence itself "
                "(grid-adjacent order), not a PackPlan")
        return [trim_timeline(lower_constraints(p).lowered)[0]
                for p in problems]

    def _solve_warm(self, trimmed: list[Problem]):
        """Warm-started sweep chain over consecutive groups of
        ``sweep.warm_start`` instances.  When the group size does not
        divide B the trailing group is smaller and cold-starts (its
        lanes no longer align with the predecessor state) — documented
        behavior on the sequential path, but an error under
        ``pipeline=True``, whose single ``lax.scan`` needs every group
        stacked to one shape."""
        cfg, k = self.solver, self.sweep.warm_start
        if self.sweep.pipeline and len(trimmed) % k:
            raise ValueError(
                f"SweepConfig(pipeline=True) needs warm_start "
                f"({k}) to divide the instance count ({len(trimmed)}): "
                f"the compiled sweep scans equal-shaped groups; pad the "
                f"fleet or adjust the group size")
        groups = [trimmed[i : i + k] for i in range(0, len(trimmed), k)]
        return _sweep_impl(
            groups, tol=cfg.tol, iters=cfg.iters,
            step_scale=cfg.step_scale, operator=cfg.operator,
            adaptive=cfg.adaptive, restart=cfg.restart,
            check_every=cfg.check_every, scaling=cfg.scaling,
            precision=cfg.precision, omega=cfg.omega,
            pipeline=self.sweep.pipeline, devices=self.sweep.devices)

    # -- phase 2: greedy placement -------------------------------------

    def place(self, problems, mappings, fit: str | None = None,
              filling: bool | None = None) -> list[Solution]:
        """One placement pass of given mappings under
        ``self.placement`` (fit/filling overridable per call; fit
        defaults to the config's policy, or 'first' under 'best').

        Constrained instances are lowered first and the returned
        solutions expanded back to original task rows (resolved widths
        ride ``meta['widths']``); ``mappings[b]`` must therefore align
        with the LOWERED rows — which is exactly what :meth:`solve`
        produces for the same problems."""
        if isinstance(problems, PackPlan):
            raise ValueError(
                "place() takes a problem sequence or a ProblemBatch "
                "(mappings align with submission order), not a PackPlan")
        cfg = self.placement
        fit = fit if fit is not None else (
            "first" if cfg.fit == "best" else cfg.fit)
        filling = cfg.filling if filling is None else filling
        lows = None
        if not isinstance(problems, ProblemBatch):
            lows = [lower_constraints(p) for p in problems]
            problems = [low.lowered for low in lows]
        if cfg.engine == "loop":
            trimmed = self._trimmed(problems)
            sols = [two_phase(t, mp, fit=fit, filling=filling)
                    for t, mp in zip(trimmed, mappings)]
        else:
            batch = problems if isinstance(problems, ProblemBatch) \
                else pack_problems(self._trimmed(problems),
                                   assume_trimmed=True)
            sols = place_many(batch, mappings, fit=fit, filling=filling,
                              placement=_ENGINE_STEPPER[cfg.engine])
        if lows is not None:
            sols = [expand_solution(low, s)
                    for low, s in zip(lows, sols)]
        return sols

    def _evaluate_bucket(self, batch: ProblemBatch, lp_results,
                         tels: list | None = None,
                         timings: dict | None = None):
        """§VI protocol entries for one packed bucket."""
        cfg = self.placement
        if cfg.engine in _ENGINE_STEPPER:
            return _protocol_batched(batch, lp_results, self.algos,
                                     cfg.fits, check=cfg.check,
                                     stepper=_ENGINE_STEPPER[cfg.engine],
                                     tels=tels, timings=timings)
        from .api import _protocol_entry

        return [_protocol_entry(t, res, res.lower_bound, self.algos)
                for t, res in zip(batch.problems, lp_results)]

    # -- the full protocol ---------------------------------------------

    def evaluate(self, problems) -> FleetResult:
        """§VI protocol over a fleet: bucketed pack -> per-bucket LP
        solve -> per-bucket lockstep placement -> entries merged back
        into submission order, as a ``FleetResult``.  Each phase is a
        ``repro.*`` span (``repro.core.spans``) whose seconds go to
        ``timings``."""
        timings = {"verify_s": 0.0}
        with span("evaluate", timings, "total_s"):
            if self.sweep.warm_start is not None:
                return self._evaluate_warm(problems, timings)
            with span("pack", timings, "pack_s"):
                plan = problems if isinstance(problems, PackPlan) \
                    else self.pack(problems)
            entries: list[dict | None] = [None] * plan.n_instances
            stats: list[SolveStats] = []
            bucket_lp_s, bucket_place_s = [], []
            tels: list[dict] = []
            for bucket in plan.buckets:
                clock: dict = {}
                with span("lp", clock, "lp_s"):
                    lp_results, st = self._solve_bucket(bucket)
                stats.extend(st)
                with span("place", clock, "place_s"):
                    part = self._evaluate_bucket(bucket.batch, lp_results,
                                                 tels=tels, timings=timings)
                bucket_lp_s.append(clock["lp_s"])
                bucket_place_s.append(clock["place_s"])
                if self.solver.tol is not None:
                    self._attach_solver(part, lp_results)
                for i, entry in zip(bucket.indices, part):
                    entries[i] = entry
            timings.update(
                lp_s=sum(bucket_lp_s), place_s=sum(bucket_place_s),
                bucket_lp_s=bucket_lp_s, bucket_place_s=bucket_place_s,
                placement=_placement_telemetry(self.placement.engine,
                                               tels))
            return FleetResult(entries=entries, stats=stats, plan=plan,
                               timings=timings)

    def _evaluate_warm(self, problems, timings: dict) -> FleetResult:
        """The warm-started sweep path: one chained LP solve, then one
        single-shape placement pass over the whole grid."""
        trimmed = self._trimmed(problems)
        with span("lp", timings, "lp_s"):
            lp_results, stats = self._solve_warm(trimmed)
        with span("pack", timings, "pack_s"):
            batch = problems if isinstance(problems, ProblemBatch) \
                else pack_problems(trimmed, assume_trimmed=True)
        tels: list[dict] = []
        with span("place", timings, "place_s"):
            entries = self._evaluate_bucket(batch, lp_results, tels=tels,
                                            timings=timings)
        self._attach_solver(entries, lp_results)
        timings.update(
            bucket_lp_s=[timings["lp_s"]],
            bucket_place_s=[timings["place_s"]],
            placement=_placement_telemetry(self.placement.engine, tels))
        return FleetResult(entries=entries, stats=stats, plan=None,
                           timings=timings)

    @staticmethod
    def _attach_solver(entries, lp_results):
        for entry, res in zip(entries, lp_results):
            entry["solver"] = {"iters": res.iters,
                               "restarts": res.restarts,
                               "kkt": res.kkt,
                               "converged": res.converged}
