"""Batched many-instance LP engine: pad-and-stack + one fused PDHG solve.

The paper's §VI protocol (and every fleet-scale sweep in the related
work) evaluates algorithms over *grids* of instances.  This module packs
B ``Problem`` instances into ragged-safe ``(B, ...)`` arrays and runs the
matrix-free PDHG mapping LP for all of them in a single compiled solve —
the whole iteration (congestion operator, adjoint, both projections) is
batched, so one compiled stepper advances every instance at once instead
of B sequential solves.

Two stopping regimes share the packed operator machinery:

  * ``tol=None`` — the legacy fixed-step, fixed-``iters`` vanilla
    Chambolle–Pock ``lax.scan`` (bit-stable; the golden tables pin it);
  * ``tol=<float>`` — the PDLP-style engine: per-instance adaptive
    primal/dual step sizes via the backtracking ratio test (step-size
    state carried per batch lane, so each instance adapts independently
    inside the one fused solve), average-iterate restarts triggered by a
    per-instance normalized duality-gap criterion, a vectorized
    convergence mask that freezes converged lanes (masked updates) while
    stragglers keep iterating, and an early-exit ``lax.while_loop``
    outer stepper that stops as soon as the whole batch is converged.
    ``solve_lp_many(..., init=prev_state)`` warm-starts from a previous
    solve's primal/dual iterates, and ``solve_lp_sweep`` chains that
    across a grid-adjacent sequence of sweep groups so each sweep point
    starts from its neighbor's solution.  Per-instance telemetry
    (iterations-to-tolerance, restarts, final KKT residuals) comes back
    in a ``SolveStats``.

Padding scheme (exact — padded coordinates never perturb real ones):

  * tasks      — zero demand, span [0, 0]: zero operator weight, zero
                 congestion, zero dual contribution;
  * node-types — unit capacity but *zero operator weight* and an
                 effectively-infinite price (``PAD_COST``), masked
                 infeasible for every task so ``x`` never selects them;
  * dimensions — zero demand over unit capacity: zero weight;
  * timeline   — slots past an instance's trimmed T' have no active
                 task, so congestion and the (zero-initialized) dual
                 iterate stay identically zero there.

Both simplex projections are padding-exact as well: appended ``-inf``/
zero entries never enter the sorted-threshold count, so the projected
real coordinates match the unbatched projection bit-for-bit up to float
reassociation.  ``solve_lp_pdhg`` is the B=1 special case of this engine,
so the per-instance and batched paths share one implementation.

The forward map can run through the batch-dim-aware Pallas congestion
kernel (``operator='pallas'``, grid over B; see kernels/congestion.py),
the dense mask-matmul form it implements (``'dense'``), or the O((n+T)D)
difference-array form (``'cumsum'``, the default).

The fleet-scale speed layer (all tol-mode only; the legacy path is
untouched):

  * ``scaling='ruiz'`` — iterated Ruiz equilibration of the packed
    operator.  Per-task column scales ``c`` and per-type row scales
    ``r`` turn ``w`` into ``w * r / c``; the change of variables is
    exact (task simplices carry mass ``c``, dual caps become
    ``cost / r``), so certified objectives are original-scale values
    and only the iteration trajectory changes.
  * ``omega=True`` — PDLP-style primal-weight balancing: per-lane
    ``omega`` splits the step into ``tau = eta / omega`` and
    ``sigma = eta * omega`` and is re-estimated at every restart from
    the primal/dual movement ratio of the closing epoch.
  * ``precision='mixed'`` (default) / ``'f64'`` — mixed precision runs
    the f32 iterate with an f64 KKT certificate and a final f64 polish
    pass (kept per lane only where it tightens the gap); 'f64' runs the
    whole iterate in f64.  Both trace under a scoped
    ``jax.enable_x64(True)`` (the compiled placement stepper's
    discipline), so the process-wide precision default is untouched.
  * ``solve_lp_sweep(..., pipeline=True)`` — the warm-started sweep
    chain compiled into ONE ``lax.scan`` over groups (one device
    dispatch for the whole chain), optionally sharded over the batch
    dim across local devices via ``shard_map`` (``devices=``).
"""

from __future__ import annotations

import dataclasses
import functools
import warnings
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from .lp_pdhg import PDHGResult, PDHGState, SolveStats
from .problem import (Problem, feasible_types, require_lowered,
                      trim_timeline)

__all__ = ["ProblemBatch", "pack_problems", "solve_lp_many",
           "solve_lp_sweep", "PAD_COST", "DEFAULT_TOL",
           "DEFAULT_CHECK_EVERY", "SCALINGS", "PRECISIONS",
           "CANONICAL_MARGIN", "dispatch_count"]

# Padded node-types carry this price: they never accrue congestion (their
# operator weight is zeroed), so they contribute exactly 0 to the primal,
# but any accidental use would be unmissable in the objective.
PAD_COST = 1e9

# Default normalized-duality-gap tolerance of the adaptive engine: a 0.5%
# certified relative gap.  Near-integrality (paper Fig 5) keeps the argmax
# mapping — and therefore the §VI protocol costs — stable at this gap, so
# tolerance-stopped solves place identically to converged ones.
DEFAULT_TOL = 5e-3

# Default convergence-check cadence of the tol-mode engine: iteration
# counts quantize to this interval, so telemetry consumers (the CI gate's
# quantum slack, test tolerances) must read it from here, not hardcode it.
DEFAULT_CHECK_EVERY = 25

# Valid sets of the tol-mode speed-layer knobs (SolverConfig validates
# against these, so the solver and the config never disagree).
SCALINGS = ("none", "ruiz")
PRECISIONS = ("f64", "mixed")

# Ruiz equilibration sweeps: inf-norm Ruiz converges geometrically, so a
# handful of sweeps lands within a few percent of doubly-balanced.
_RUIZ_ITERS = 8

# Primal-weight clip: omega is dimensionless (1 = the classic symmetric
# tau = sigma = eta split), so an absolute band keeps a degenerate lane's
# movement ratio from running the split to extremes.
_OMEGA_CLIP = 1e2

# Final f64 polish pass of precision='mixed': a few plain PDHG steps at
# the adapted step size, computed in f64 from the f32 solution, kept per
# lane only where they tighten the certified gap.
_POLISH_ITERS = 10

# Canonical-rounding margin: a type whose relaxed mass is within this of
# the per-task max is treated as epsilon-optimal-equivalent, and the
# winner among those is picked by problem data (cheapest cost, then
# lowest index) instead of by trajectory-dependent float noise.  Must sit
# well above the cross-trajectory iterate noise at DEFAULT_TOL (~1e-2 on
# degenerate ties) and well below real argmax gaps (near-integrality,
# paper Fig 5: contested tasks split ~0.5 vs ~0.99 for settled ones).
CANONICAL_MARGIN = 0.05

# Host-side count of compiled-solver invocations (the pipelined sweep's
# "exactly ONE dispatch" claim is measured, not asserted by construction:
# benchmarks snapshot this counter around the call).
_DISPATCH_COUNT = 0


def dispatch_count() -> int:
    """Number of compiled LP-solver entry-point invocations so far in
    this process (legacy, tol, and pipeline steppers all count 1 per
    host-level call)."""
    return _DISPATCH_COUNT


def _count_dispatch() -> None:
    global _DISPATCH_COUNT
    _DISPATCH_COUNT += 1


@dataclasses.dataclass(frozen=True)
class ProblemBatch:
    """B timeline-trimmed instances padded to common (n, m, D, T') shapes.

    problems: the trimmed per-instance ``Problem``s (for unpacking).
    dem:   (B, n, D) float64, padded tasks/dims zero.
    start: (B, n) int32, padded tasks [0, 0].
    end:   (B, n) int32.
    cap:   (B, m, D) float64, padded types/dims one.
    cost:  (B, m) float64, padded types ``PAD_COST``.
    feas:  (B, n, m) bool — per-instance feasible pairs; padded tasks may
           use any *real* type (zero demand fits everywhere), padded
           types are feasible for no task.
    task_mask: (B, n) bool; type_mask: (B, m) bool.
    Tp: common (max) trimmed timeline length.
    """

    problems: tuple[Problem, ...]
    dem: np.ndarray
    start: np.ndarray
    end: np.ndarray
    cap: np.ndarray
    cost: np.ndarray
    feas: np.ndarray
    task_mask: np.ndarray
    type_mask: np.ndarray
    Tp: int

    @property
    def B(self) -> int:
        return self.dem.shape[0]

    @property
    def n(self) -> int:
        return self.dem.shape[1]

    @property
    def m(self) -> int:
        return self.cap.shape[1]

    @property
    def D(self) -> int:
        return self.dem.shape[2]

    @property
    def shape(self) -> tuple[int, int, int, int]:
        """The common padded (n, m, D, T') every instance was packed to
        — the ``pad_to`` that reproduces this batch's layout (what the
        engine's shard dispatch passes so shards share one compile)."""
        return (self.n, self.m, self.D, self.Tp)

    def weights(self) -> np.ndarray:
        """(B, n, m, D) operator weights dem/cap, zeroed on padding."""
        w = self.dem[:, :, None, :] / self.cap[:, None, :, :]
        return w * self.type_mask[:, None, :, None]


def pack_problems(problems, pad_to=None,
                  assume_trimmed: bool = False) -> ProblemBatch:
    """Trim each instance's timeline, then pad-and-stack the batch.

    ``pad_to=(n, m, D, Tp)`` sets *minimum* padded dims — warm-started
    sweeps pack every group to one common shape so all groups share one
    compiled solve and states align lane-for-lane without re-padding.
    ``assume_trimmed`` skips the (idempotent) per-instance trim for
    callers that already hold trimmed instances — e.g. the FleetEngine,
    which trims once up front to plan its shape buckets.
    """
    problems = list(problems)
    if not problems:
        raise ValueError("pack_problems needs at least one instance")
    trimmed = []
    for p in problems:
        if p.n == 0:
            raise ValueError("cannot batch an empty instance")
        require_lowered(p, "pack_problems")
        trimmed.append(p if assume_trimmed else trim_timeline(p)[0])
    n = max(t.n for t in trimmed)
    m = max(t.m for t in trimmed)
    D = max(t.D for t in trimmed)
    Tp = max(t.T for t in trimmed)
    if pad_to is not None:
        n, m, D, Tp = (max(n, pad_to[0]), max(m, pad_to[1]),
                       max(D, pad_to[2]), max(Tp, pad_to[3]))
    B = len(trimmed)

    dem = np.zeros((B, n, D))
    start = np.zeros((B, n), np.int32)
    end = np.zeros((B, n), np.int32)
    cap = np.ones((B, m, D))
    cost = np.full((B, m), PAD_COST)
    feas = np.zeros((B, n, m), bool)
    task_mask = np.zeros((B, n), bool)
    type_mask = np.zeros((B, m), bool)
    for b, t in enumerate(trimmed):
        dem[b, : t.n, : t.D] = t.dem
        start[b, : t.n] = t.start
        end[b, : t.n] = t.end
        cap[b, : t.m, : t.D] = t.node_types.cap
        cost[b, : t.m] = t.node_types.cost
        feas[b, : t.n, : t.m] = feasible_types(t)
        feas[b, t.n :, : t.m] = True  # zero-demand pad tasks fit anywhere
        task_mask[b, : t.n] = True
        type_mask[b, : t.m] = True
    return ProblemBatch(
        problems=tuple(trimmed), dem=dem, start=start, end=end, cap=cap,
        cost=cost, feas=feas, task_mask=task_mask, type_mask=type_mask,
        Tp=Tp,
    )


# --- projections -----------------------------------------------------------
# Water-filling thresholds found by Newton's method on the piecewise-linear
# residual instead of a sort: XLA's sort lowers to an element-serial
# comparator loop on CPU, which would put a batch-size-independent floor
# under every PDHG iteration, while Newton is pure element-wise arithmetic
# that vectorizes across everything the engine stacks.  Starting left of
# the root, the iteration is monotone; with <= m breakpoints it is *exact*
# for the task simplex after m steps.

_NEWTON_ITERS_Y = 12


def _project_simplex_masked(v, mask, mass=None):
    """Project rows (last axis) of v onto the simplex over mask==True.

    ``mass`` (broadcastable to v's row index, e.g. (B, n)) generalizes
    the target row sum from 1 to a per-row scaled simplex — the Ruiz-
    scaled primal feasible set, where task u's row carries mass c_u.
    The Newton start ``rowmax - mass`` is still left of the root, so
    the iteration stays monotone and exact in m+1 steps.  ``mass=None``
    keeps the legacy unit-simplex arithmetic bit-identical.
    """
    neg = jnp.finfo(v.dtype).min
    s = None if mass is None else mass[..., None]
    theta = (jnp.where(mask, v, neg).max(axis=-1, keepdims=True)
             - (1.0 if s is None else s))
    # unrolled so XLA fuses the whole chain into a handful of kernels
    # (a fori_loop would re-dispatch ~6 tiny ops per Newton step)
    for _ in range(v.shape[-1] + 1):  # piecewise-linear: exact in m+1 steps
        r = jnp.sum(jnp.where(mask, jnp.maximum(v - theta, 0.0), 0.0),
                    axis=-1, keepdims=True)
        k = jnp.sum(jnp.where(mask, (v > theta), False), axis=-1,
                    keepdims=True)
        theta = theta + (r - (1.0 if s is None else s)) / jnp.maximum(k, 1)
    out = jnp.where(mask, jnp.maximum(v - theta, 0.0), 0.0)
    if s is None:
        return out / (out.sum(axis=-1, keepdims=True) + 1e-30)
    return out * (s / (out.sum(axis=-1, keepdims=True) + 1e-30))


def _project_capped_simplex_td(y, cap):
    """Project y (B, T', m, D) onto {y >= 0, sum_{t,d} y <= cap} per (b, m).

    cap: (B, 1, m, 1).  Axis-aware so the dual iterate never needs a
    transpose inside the scan.
    """
    y = jnp.maximum(y, 0.0)
    total = jnp.sum(y, axis=(1, 3), keepdims=True)
    theta = jnp.zeros_like(total)
    for _ in range(_NEWTON_ITERS_Y):  # unrolled: see _project_simplex_masked
        r = jnp.sum(jnp.maximum(y - theta, 0.0), axis=(1, 3), keepdims=True)
        k = jnp.sum(y > theta, axis=(1, 3), keepdims=True)
        theta = theta + jnp.maximum(r - cap, 0.0) / jnp.maximum(k, 1)
    shrunk = jnp.maximum(y - theta, 0.0)
    # scale out any Newton residue: keeps sum <= cap exactly, so the dual
    # value G(y) stays a certified lower bound
    ssum = jnp.sum(shrunk, axis=(1, 3), keepdims=True)
    shrunk = shrunk * (cap / jnp.maximum(ssum, cap))
    return jnp.where(total <= cap, y, shrunk)


# --- congestion operator, three interchangeable forms ----------------------

def _make_operators(w_all, start, end, Tp: int, operator: str):
    """fwd_all: (B, n, m) -> (B, T', m, D); adj_all: its exact adjoint.

    All layouts are chosen so the scan body is transpose-free: the dual
    iterate lives as (B, T', m, D), weights as (B, n, m, D), and the two
    activity layouts are materialized once outside the scan.  Each form
    applies the whole batch in O(1) XLA ops — at sweep sizes per-op
    dispatch dominates, so the batch must live *inside* single ops for
    batching to pay off.
    """
    B, n, m, D = w_all.shape
    w_flat = w_all.reshape(B, n, m * D)

    if operator == "dense":
        # full f32 (or f64) products: the TPU's default single bf16
        # pass would leave fwd/adj inexact adjoints at ~1e-3, the
        # order of the stopping tolerance
        hi = jax.lax.Precision.HIGHEST
        t_ids = jnp.arange(Tp, dtype=jnp.int32)
        act_nt = ((start[:, :, None] <= t_ids[None, None, :])
                  & (t_ids[None, None, :] <= end[:, :, None])
                  ).astype(w_all.dtype)  # (B, n, T')
        act_tn = act_nt.transpose(0, 2, 1)  # (B, T', n)

        def fwd_all(xv):
            xw = (xv[..., None] * w_all).reshape(B, n, m * D)
            return jnp.matmul(act_tn, xw, precision=hi).reshape(
                B, Tp, m, D)

        def adj_all(yv):
            z = jnp.matmul(act_nt, yv.reshape(B, Tp, m * D),
                           precision=hi)
            return jnp.sum(z.reshape(B, n, m, D) * w_all, axis=3)
        return fwd_all, adj_all

    if operator == "cumsum":
        # O((n+T)D) difference-array form: scatter +xw at start, -xw past
        # end, prefix-sum over time; adjoint reads span sums off an
        # exclusive prefix-sum.  One batched scatter/gather per apply.
        def fwd_all(xv):
            xw = (xv[..., None] * w_all).reshape(B, n, m * D)

            def one(xw_b, s_b, e_b):
                delta = jnp.zeros((Tp + 1, m * D), xw_b.dtype)
                delta = delta.at[s_b].add(xw_b)
                delta = delta.at[e_b + 1].add(-xw_b)
                return jnp.cumsum(delta[:Tp], axis=0)

            return jax.vmap(one)(xw, start, end).reshape(B, Tp, m, D)

        def adj_all(yv):
            C = jnp.cumsum(yv.reshape(B, Tp, m * D), axis=1)
            Cx = jnp.concatenate([jnp.zeros_like(C[:, :1]), C], axis=1)

            def one(Cx_b, s_b, e_b):
                return Cx_b[e_b + 1] - Cx_b[s_b]  # (n, m*D) span sums

            span = jax.vmap(one)(Cx, start, end).reshape(B, n, m, D)
            return jnp.sum(span * w_all, axis=3)
        return fwd_all, adj_all

    if operator == "pallas":
        from repro.kernels import ops as kops

        # one (B*m)-group kernel launch per forward: group g = b*m + B
        start_g = jnp.repeat(start, m, axis=0)
        end_g = jnp.repeat(end, m, axis=0)
        w_g = w_all.transpose(0, 2, 1, 3).reshape(B * m, n, D)

        def fwd_all(xv):
            x_g = xv.transpose(0, 2, 1).reshape(B * m, n)
            cong = kops.congestion_many(start_g, end_g,
                                        w_g * x_g[:, :, None], Tp)
            return cong.reshape(B, m, Tp, D).transpose(0, 2, 1, 3)

        _, adj_cumsum = _make_operators(w_all, start, end, Tp, "cumsum")
        return fwd_all, adj_cumsum  # adjoint of the same linear map

    raise ValueError(f"unknown operator {operator!r}")


def _power_op_norm(fwd_all, adj_all, feas, power_iters: int):
    """||A||_2 per instance: power iteration on A^T A from the
    (nonnegative, deterministic, padding-invariant) feasibility pattern."""
    v = feas.astype(jnp.float32)
    norm = jnp.ones((feas.shape[0],), jnp.float32)
    for _ in range(power_iters):
        v2 = adj_all(fwd_all(v))
        norm = jnp.sqrt(jnp.sum(v2 * v2, axis=(1, 2)))
        v = v2 / (norm[:, None, None] + 1e-30)
    return jnp.sqrt(norm)


def _ruiz_scalings(w_all, iters: int = _RUIZ_ITERS):
    """Iterated Ruiz equilibration of the packed operator core.

    Returns per-task column scales ``c`` (B, n) and per-type row scales
    ``r`` (B, m) such that ``w * r / c`` has near-unit inf-norms along
    both scalable partitions.  Time slots share one row scale (activity
    is 0/1, so it never changes a row's inf-norm) and demand dimensions
    share their (b, n, m) entry's scale (the dual cap couples (t, d) per
    type, so a per-d scale would break the capped-simplex projection).
    Padded tasks/types have all-zero weight rows; their norms clamp to 1
    so their scales stay exactly 1.
    """
    B, n, m, D = w_all.shape
    c = jnp.ones((B, n), w_all.dtype)
    r = jnp.ones((B, m), w_all.dtype)
    for _ in range(iters):
        ws = w_all * (r[:, None, :, None] / c[:, :, None, None])
        col = jnp.max(ws, axis=(2, 3))  # (B, n) inf-norm over (m, d)
        row = jnp.max(ws, axis=(1, 3))  # (B, m) inf-norm over (n, d)
        c = c * jnp.sqrt(jnp.where(col > 0, col, 1.0))
        r = r / jnp.sqrt(jnp.where(row > 0, row, 1.0))
    return c, r


def _objectives(Ax, y, adj_all, cost, feas, mass=None, dt=None):
    """(primal, dual, normalized gap) per lane, from a cached forward
    apply.  The normalized gap is the KKT-residual proxy: both iterates
    are kept exactly feasible by their projections, so the duality gap is
    the full KKT error.

    Under Ruiz scaling ``cost`` is the scaled caps ``cost / r`` and
    ``mass`` the per-task simplex masses ``c``; the products below then
    cancel the scales, so both bounds are original-scale values.
    ``dt`` computes the certificate in a wider dtype than the iterate
    (the mixed-precision f64 certificate) — inputs are cast up, and the
    operator closures propagate the wider dtype through the adjoint.
    """
    if dt is not None:
        Ax, y, cost = Ax.astype(dt), y.astype(dt), cost.astype(dt)
    primal = jnp.sum(cost * Ax.max(axis=(1, 3)), axis=1)
    wty = jnp.where(feas, adj_all(y), jnp.inf)
    mins = wty.min(axis=2)
    if mass is not None:
        mins = mass.astype(mins.dtype) * mins
    dual = jnp.sum(mins, axis=1)
    rel = (primal - dual) / (1.0 + jnp.abs(primal) + jnp.abs(dual))
    return primal, dual, rel


# --- legacy fixed-step engine (tol=None; golden-table bit-stable) ----------

@functools.partial(jax.jit,
                   static_argnames=("iters", "Tp", "operator", "power_iters"))
def _pdhg_run_many(w_all, start, end, feas, cost, step_scale, iters: int,
                   Tp: int, operator: str = "cumsum", power_iters: int = 12,
                   x0=None, y0=None):
    B, n, m, D = w_all.shape
    fwd_all, adj_all = _make_operators(w_all, start, end, Tp, operator)

    op_norm = _power_op_norm(fwd_all, adj_all, feas, power_iters)
    tau = (step_scale / (op_norm + 1e-30))[:, None, None]        # vs (B,n,m)
    sigma = tau[..., None]                                    # vs (B,T',m,D)
    cap = cost[:, None, :, None]                              # vs (B,T',m,D)

    if x0 is None:
        x = feas.astype(jnp.float32)
        x = x / x.sum(axis=2, keepdims=True)
    else:
        x = _project_simplex_masked(x0, feas)
    if y0 is None:
        y = jnp.zeros((B, Tp, m, D), jnp.float32)
    else:
        y = _project_capped_simplex_td(y0, cap)

    def step(carry, _):
        x, y, x_prev = carry
        x_bar = 2.0 * x - x_prev
        y_new = _project_capped_simplex_td(y + sigma * fwd_all(x_bar), cap)
        x_new = _project_simplex_masked(x - tau * adj_all(y_new), feas)
        return (x_new, y_new, x), None

    (x, y, _), _ = jax.lax.scan(step, (x, y, x), None, length=iters)

    primal, dual, rel_gap = _objectives(fwd_all(x), y, adj_all, cost, feas)
    return x, y, primal, dual, rel_gap


# --- adaptive restarted engine (tol mode; PDLP-style) ----------------------
# Restart sufficient-decay factor: restart an epoch once the best of
# {current, average} iterate improves the normalized gap to below
# _RESTART_BETA x the gap at the last restart.
_RESTART_BETA = 0.5
# Adaptive step-size clip around the power-iteration baseline: the ratio
# test drives eta, these only stop a degenerate lane (zero interaction
# many checks in a row) from running eta to inf/0.
_ETA_CLIP = 1e4


class _TolCarry(NamedTuple):
    x: jnp.ndarray        # (B, n, m) primal iterate (scaled coordinates)
    x_prev: jnp.ndarray   # momentum partner
    Ax: jnp.ndarray       # (B, T', m, D) cached forward apply of x
    Ax_prev: jnp.ndarray
    y: jnp.ndarray        # (B, T', m, D) dual iterate (scaled coordinates)
    eta: jnp.ndarray      # (B,) per-lane step size (geometric mean)
    omega: jnp.ndarray    # (B,) primal weight: tau=eta/omega, sigma=eta*omega
    k: jnp.ndarray        # scalar: outer attempted-iteration count
    iters_b: jnp.ndarray  # (B,) per-lane iterations-to-tolerance
    conv: jnp.ndarray     # (B,) converged mask — frozen lanes
    restarts_b: jnp.ndarray  # (B,)
    gap_b: jnp.ndarray    # (B,) latest normalized gap per lane
    last_gap: jnp.ndarray  # (B,) gap at last restart (criterion anchor)
    sum_x: jnp.ndarray    # epoch average accumulators (restart mode)
    sum_y: jnp.ndarray
    sum_Ax: jnp.ndarray
    elen: jnp.ndarray     # (B,) epoch length
    dxs: jnp.ndarray      # (B,) epoch primal path length (omega estimator)
    dys: jnp.ndarray      # (B,) epoch dual path length


def _tol_core(w_all, start, end, feas, cost, step_scale, tol,
              max_iters: int, check_every: int, Tp: int, operator: str,
              adaptive: bool, restart: bool, power_iters: int,
              scaling: str, precision: str, omega_on: bool,
              x0=None, y0=None, eta_init=None, omega_init=None,
              use_init=None):
    """Adaptive restarted PDHG with per-lane tolerance stopping.

    One fused stepper for the whole batch: ``check_every`` inner PDHG
    iterations (adaptive per-lane step sizes via the PDLP backtracking
    ratio test — a rejected attempt keeps the iterate and shrinks that
    lane's step below the ratio bound, so backtracking unrolls across
    the loop instead of nesting one), then a convergence/restart check,
    inside an early-exit ``lax.while_loop`` that runs until every lane's
    normalized duality gap is <= tol (or ``max_iters``).  Converged
    lanes freeze via masked updates but keep riding along until the
    whole batch is done — that is the batched analogue of PDLP's
    per-problem termination.

    The speed-layer statics: ``scaling='ruiz'`` solves in Ruiz-scaled
    coordinates (warm inits are scaled on the way in, iterates unscaled
    on the way out — callers only ever see original coordinates);
    ``precision`` picks the iterate dtype (``'mixed'`` = f32 iterate,
    f64 certificate + final polish; ``'f64'`` = f64 throughout — both
    need the caller's ``jax.enable_x64(True)`` scope); ``omega_on``
    enables the primal-weight split.  ``use_init`` is a *traced* bool selecting the
    warm arrays over the default init — the sweep pipeline's scan body
    passes it so cold group 0 and warm groups 1.. share one trace.

    Each phase runs under a ``jax.named_scope`` (``ruiz``,
    ``operators``, ``power``, ``pdhg``, ``certificate``, ``polish``,
    ``unscale``), so the device trace can tell them apart; scopes change
    op metadata only, never the ops.

    This function is deliberately un-jitted: ``_pdhg_run_many_tol``
    wraps it for the one-batch entry point and ``_pipeline_fn`` scans it
    over sweep groups inside one jit.
    """
    B, n, m, D = w_all.shape
    if operator == "pallas" and precision == "f64":
        operator = "cumsum"  # the kernel is f32; cumsum is the same map
    it_dt = jnp.float64 if precision == "f64" else jnp.float32
    cert_dt = jnp.float64
    w_all = w_all.astype(it_dt)
    cost = cost.astype(it_dt)

    if scaling == "ruiz":
        with jax.named_scope("ruiz"):
            c_sc, r_sc = _ruiz_scalings(w_all)
            ws_all = w_all * (r_sc[:, None, :, None] / c_sc[:, :, None, None])
            cost_s = cost / r_sc   # scaled dual caps (padded types stay huge)
            mass = c_sc            # scaled primal simplex masses
    else:
        ws_all, cost_s, mass = w_all, cost, None

    with jax.named_scope("operators"):
        fwd_all, adj_all = _make_operators(ws_all, start, end, Tp, operator)
    with jax.named_scope("power"):
        op_norm = _power_op_norm(fwd_all, adj_all, feas,
                                 power_iters).astype(it_dt)
    eta0 = step_scale / (op_norm + 1e-30)                     # (B,)
    cap = cost_s[:, None, :, None]

    x_def = feas.astype(it_dt)
    x_def = x_def / x_def.sum(axis=2, keepdims=True)
    if mass is not None:
        x_def = x_def * mass[:, :, None]
    if x0 is None:
        x = x_def
    else:
        x_w = x0.astype(it_dt)
        if mass is not None:
            x_w = x_w * mass[:, :, None]
        x_w = _project_simplex_masked(x_w, feas, mass)
        x = x_w if use_init is None else jnp.where(use_init, x_w, x_def)
    if y0 is None:
        y = jnp.zeros((B, Tp, m, D), it_dt)
    else:
        y_w = y0.astype(it_dt)
        if scaling == "ruiz":
            y_w = y_w / r_sc[:, None, :, None]
        y_w = _project_capped_simplex_td(y_w, cap)
        y = (y_w if use_init is None
             else jnp.where(use_init, y_w, jnp.zeros_like(y_w)))
    Ax = fwd_all(x)

    eta_start = eta0
    if eta_init is not None:
        eta_w = jnp.clip(eta_init.astype(it_dt), eta0 / _ETA_CLIP,
                         eta0 * _ETA_CLIP)
        eta_start = (eta_w if use_init is None
                     else jnp.where(use_init, eta_w, eta0))
    ones_b = jnp.ones((B,), it_dt)
    omega_start = ones_b
    if omega_on and omega_init is not None:
        om_w = jnp.clip(omega_init.astype(it_dt), 1.0 / _OMEGA_CLIP,
                        _OMEGA_CLIP)
        omega_start = (om_w if use_init is None
                       else jnp.where(use_init, om_w, ones_b))

    def inner(_, c: _TolCarry) -> _TolCarry:
        active = ~c.conv
        if omega_on:
            sig = (c.eta * c.omega)[:, None, None, None]
            tau = (c.eta / c.omega)[:, None, None]
        else:
            sig = c.eta[:, None, None, None]
            tau = c.eta[:, None, None]
        # candidate step; fwd(2x - x_prev) folded through linearity onto
        # the cached applies, so each attempt costs one fwd + one adj
        y_c = _project_capped_simplex_td(
            c.y + sig * (2.0 * c.Ax - c.Ax_prev), cap)
        x_c = _project_simplex_masked(c.x - tau * adj_all(y_c), feas,
                                      mass)
        Ax_c = fwd_all(x_c)
        dx = x_c - c.x
        dy = y_c - c.y
        dxsq = jnp.sum(dx * dx, axis=(1, 2))
        dysq = jnp.sum(dy * dy, axis=(1, 2, 3))
        if adaptive:
            if omega_on:
                # ratio-test movement in the omega-weighted norm — the
                # norm the primal-dual step is a proximal step in
                move = 0.5 * (c.omega * dxsq + dysq / c.omega)
            else:
                move = 0.5 * (dxsq + dysq)
            inter = jnp.abs(jnp.sum(dy * (Ax_c - c.Ax), axis=(1, 2, 3)))
            eta_bar = jnp.where(inter > 1e-20,
                                move / jnp.maximum(inter, 1e-20), jnp.inf)
            accept = c.eta <= eta_bar
            # kk starts at 2 so the decay factor is never exactly 0 (a
            # k=0 reject would zero eta for good); a lane with no
            # interaction (eta_bar = inf, e.g. x pinned by single-type
            # feasibility) must fall through to the growth term, not
            # evaluate inf * factor (whose 0-factor case is NaN)
            kk = (c.k + 2).astype(jnp.float32)
            shrink = jnp.where(jnp.isfinite(eta_bar),
                               eta_bar * (1.0 - kk ** -0.3), jnp.inf)
            eta_next = jnp.minimum(shrink, c.eta * (1.0 + kk ** -0.6))
            eta_next = jnp.clip(eta_next, eta0 / _ETA_CLIP, eta0 * _ETA_CLIP)
        else:
            accept = jnp.ones((B,), bool)
            eta_next = c.eta
        upd = active & accept
        u3 = upd[:, None, None]
        u4 = upd[:, None, None, None]
        new = c._replace(
            x=jnp.where(u3, x_c, c.x),
            x_prev=jnp.where(u3, c.x, c.x_prev),
            Ax=jnp.where(u4, Ax_c, c.Ax),
            Ax_prev=jnp.where(u4, c.Ax, c.Ax_prev),
            y=jnp.where(u4, y_c, c.y),
            eta=jnp.where(active, eta_next, c.eta),
            k=c.k + 1,
            iters_b=c.iters_b + active.astype(jnp.int32),
        )
        if omega_on:
            new = new._replace(
                dxs=c.dxs + jnp.where(upd, jnp.sqrt(dxsq), 0.0),
                dys=c.dys + jnp.where(upd, jnp.sqrt(dysq), 0.0),
            )
        if restart:
            new = new._replace(
                sum_x=c.sum_x + jnp.where(u3, x_c, 0.0),
                sum_y=c.sum_y + jnp.where(u4, y_c, 0.0),
                sum_Ax=c.sum_Ax + jnp.where(u4, Ax_c, 0.0),
                elen=c.elen + upd.astype(c.elen.dtype),
            )
        return new

    def body(c: _TolCarry) -> _TolCarry:
        # never overshoot the cap: the final chunk shrinks to the
        # remaining budget (traced bound -> dynamic fori length)
        c = jax.lax.fori_loop(0, jnp.minimum(check_every, max_iters - c.k),
                              inner, c)
        _, _, gap_cur = _objectives(c.Ax, c.y, adj_all, cost_s, feas,
                                    mass=mass, dt=cert_dt)
        if restart:
            den = jnp.maximum(c.elen, 1.0)
            x_avg = c.sum_x / den[:, None, None]
            y_avg = c.sum_y / den[:, None, None, None]
            Ax_avg = c.sum_Ax / den[:, None, None, None]
            _, _, gap_avg = _objectives(Ax_avg, y_avg, adj_all, cost_s,
                                        feas, mass=mass, dt=cert_dt)
            gap_avg = jnp.where(c.elen > 0, gap_avg, jnp.inf)
            use_avg = gap_avg < gap_cur
            cand = jnp.minimum(gap_avg, gap_cur)
            do_r = (~c.conv) & ((cand <= _RESTART_BETA * c.last_gap)
                                | (cand <= tol))
            a3 = (do_r & use_avg)[:, None, None]
            a4 = (do_r & use_avg)[:, None, None, None]
            x = jnp.where(a3, x_avg, c.x)
            y = jnp.where(a4, y_avg, c.y)
            Ax = jnp.where(a4, Ax_avg, c.Ax)
            r3 = do_r[:, None, None]
            r4 = do_r[:, None, None, None]
            c = c._replace(
                x=x, y=y, Ax=Ax,
                # restarts reset momentum and the epoch average
                x_prev=jnp.where(r3, x, c.x_prev),
                Ax_prev=jnp.where(r4, Ax, c.Ax_prev),
                restarts_b=c.restarts_b + do_r.astype(jnp.int32),
                last_gap=jnp.where(do_r, cand, c.last_gap),
                sum_x=jnp.where(r3, 0.0, c.sum_x),
                sum_y=jnp.where(r4, 0.0, c.sum_y),
                sum_Ax=jnp.where(r4, 0.0, c.sum_Ax),
                elen=jnp.where(do_r, 0.0, c.elen),
            )
            if omega_on:
                # PDLP primal-weight update at the restart boundary:
                # log-space smoothing (theta = 0.5) toward the closing
                # epoch's dual/primal path-length ratio.  Only lanes
                # that actually moved in both spaces update.
                ratio = jnp.sqrt(c.dys / jnp.maximum(c.dxs, 1e-30))
                om_new = jnp.clip(jnp.sqrt(c.omega * ratio),
                                  1.0 / _OMEGA_CLIP, _OMEGA_CLIP)
                ok = do_r & (c.dxs > 0) & (c.dys > 0)
                c = c._replace(
                    omega=jnp.where(ok, om_new, c.omega),
                    dxs=jnp.where(do_r, 0.0, c.dxs),
                    dys=jnp.where(do_r, 0.0, c.dys),
                )
            gap_new = jnp.where(do_r, cand, gap_cur)
        else:
            gap_new = gap_cur
        gap_b = jnp.where(c.conv, c.gap_b, gap_new)
        return c._replace(gap_b=gap_b, conv=c.conv | (gap_b <= tol))

    def cond(c: _TolCarry):
        return jnp.logical_and(~jnp.all(c.conv), c.k < max_iters)

    zeros_b = jnp.zeros((B,), it_dt)
    c = _TolCarry(
        x=x, x_prev=x, Ax=Ax, Ax_prev=Ax, y=y,
        eta=eta_start, omega=omega_start, k=jnp.int32(0),
        iters_b=jnp.zeros((B,), jnp.int32),
        conv=jnp.zeros((B,), bool),
        restarts_b=jnp.zeros((B,), jnp.int32),
        gap_b=jnp.full((B,), jnp.inf, cert_dt),
        # normalized gap starts < 1 (dual of y=0 is 0), so 1.0 anchors
        # the first sufficient-decay restart check
        last_gap=jnp.ones((B,), cert_dt),
        sum_x=jnp.zeros_like(x), sum_y=jnp.zeros_like(y),
        sum_Ax=jnp.zeros_like(Ax), elen=zeros_b,
        dxs=zeros_b, dys=zeros_b,
    )
    with jax.named_scope("pdhg"):
        c = jax.lax.while_loop(cond, body, c)

    if precision == "mixed":
        # f64 certificate with f64 *weights* (the in-loop checks only
        # widen the accumulation), then a short plain-PDHG polish at the
        # adapted per-lane step split, kept per lane only where it
        # tightens the certified gap — kkt can only improve.
        with jax.named_scope("certificate"):
            pol_op = "cumsum" if operator == "pallas" else operator
            fwd64, adj64 = _make_operators(ws_all.astype(cert_dt), start, end,
                                           Tp, pol_op)
            x_fin = c.x.astype(cert_dt)
            y_fin = c.y.astype(cert_dt)
            primal, dual, rel_gap = _objectives(fwd64(x_fin), y_fin, adj64,
                                                cost_s, feas, mass=mass,
                                                dt=cert_dt)
        with jax.named_scope("polish"):
            cap64 = cap.astype(cert_dt)
            mass64 = None if mass is None else mass.astype(cert_dt)
            if omega_on:
                sig_p = (c.eta * c.omega).astype(cert_dt)[:, None, None, None]
                tau_p = (c.eta / c.omega).astype(cert_dt)[:, None, None]
            else:
                sig_p = c.eta.astype(cert_dt)[:, None, None, None]
                tau_p = c.eta.astype(cert_dt)[:, None, None]

            def pstep(carry, _):
                xp, yp, xpr = carry
                y_n = _project_capped_simplex_td(
                    yp + sig_p * fwd64(2.0 * xp - xpr), cap64)
                x_n = _project_simplex_masked(xp - tau_p * adj64(y_n), feas,
                                              mass64)
                return (x_n, y_n, xp), None

            (x_p, y_p, _), _ = jax.lax.scan(pstep, (x_fin, y_fin, x_fin),
                                            None, length=_POLISH_ITERS)
            p_p, d_p, r_p = _objectives(fwd64(x_p), y_p, adj64, cost_s, feas,
                                        mass=mass, dt=cert_dt)
            better = r_p < rel_gap
            x_fin = jnp.where(better[:, None, None], x_p, x_fin)
            y_fin = jnp.where(better[:, None, None, None], y_p, y_fin)
            primal = jnp.where(better, p_p, primal)
            dual = jnp.where(better, d_p, dual)
            rel_gap = jnp.where(better, r_p, rel_gap)
    else:
        x_fin, y_fin = c.x, c.y
        with jax.named_scope("certificate"):
            primal, dual, rel_gap = _objectives(c.Ax, c.y, adj_all, cost_s,
                                                feas, mass=mass, dt=cert_dt)

    if scaling == "ruiz":
        # back to original coordinates — callers never see the scales
        with jax.named_scope("unscale"):
            x_fin = x_fin / c_sc[:, :, None]
            y_fin = y_fin * r_sc[:, None, :, None]
    return (x_fin, y_fin, primal, dual, rel_gap, c.iters_b, c.restarts_b,
            c.conv, c.eta, c.omega)


@functools.partial(jax.jit,
                   static_argnames=("max_iters", "check_every", "Tp",
                                    "operator", "adaptive", "restart",
                                    "power_iters", "scaling", "precision",
                                    "omega_on"))
def _pdhg_run_many_tol(w_all, start, end, feas, cost, step_scale, tol,
                       max_iters: int, check_every: int, Tp: int,
                       operator: str = "cumsum", adaptive: bool = True,
                       restart: bool = True, power_iters: int = 12,
                       scaling: str = "none", precision: str = "mixed",
                       omega_on: bool = True,
                       x0=None, y0=None, eta_init=None, omega_init=None):
    """One-batch jitted entry point over ``_tol_core`` (see there)."""
    return _tol_core(w_all, start, end, feas, cost, step_scale, tol,
                     max_iters, check_every, Tp, operator, adaptive,
                     restart, power_iters, scaling, precision, omega_on,
                     x0=x0, y0=y0, eta_init=eta_init,
                     omega_init=omega_init)


# 'auto' picks the dense one-dot-per-application operator while the
# activity matrix fits comfortably in memory, else the O((n+T)D) form.
_DENSE_ACT_BUDGET = 64 * 1024 * 1024  # elements of (B, n, T')


def _align_state(state: PDHGState, batch: ProblemBatch):
    """Crop / zero-pad a previous solve's iterates to this batch's padded
    shape.  Lane b warm-starts lane b; the projections inside the engine
    re-feasibilize whatever lands outside the new feasible sets (a fresh
    task row starts uniform over its feasible types, a fresh time slot's
    dual starts at zero)."""
    if state.B != batch.B:
        raise ValueError(
            f"warm start needs matching batch sizes, got state B={state.B} "
            f"vs batch B={batch.B}")
    x0 = np.zeros((batch.B, batch.n, batch.m), np.float32)
    n_c = min(state.x.shape[1], batch.n)
    m_c = min(state.x.shape[2], batch.m)
    x0[:, :n_c, :m_c] = state.x[:, :n_c, :m_c]
    y0 = np.zeros((batch.B, batch.Tp, batch.m, batch.D), np.float32)
    T_c = min(state.y.shape[1], batch.Tp)
    D_c = min(state.y.shape[3], batch.D)
    y0[:, :T_c, :m_c, :D_c] = state.y[:, :T_c, :m_c, :D_c]
    return x0, y0, state.eta, state.omega


def _canonical_mapping(x_b, feas_b, cost_m):
    """Degeneracy-insensitive rounding of an epsilon-optimal LP vertex.

    A tolerance-stopped iterate resolves a degenerate tie (two types at
    identical cost-per-congestion) by trajectory noise: the raw argmax
    of a 0.5/0.5 split flips between runs (warm vs cold, scaled vs not)
    even though every winner prices identically.  Canonical rounding
    treats every feasible type within ``CANONICAL_MARGIN`` of the row
    max as epsilon-optimal-equivalent and picks the winner by problem
    data — cheapest cost, then lowest index — so any two solves that
    agree to tolerance round to the *same* mapping.  The argmax winner
    is always a candidate, so the pick never falls outside the support.
    """
    masked = np.where(feas_b, x_b, -np.inf)
    rowmax = masked.max(axis=1, keepdims=True)
    cand = feas_b & (masked >= rowmax - CANONICAL_MARGIN)
    pick = np.where(cand, cost_m[None, :], np.inf).argmin(axis=1)
    return pick.astype(np.int64)


def solve_lp_many(problems, iters: int = 2000, step_scale: float = 0.9,
                  operator: str = "auto", tol: float | None = None,
                  adaptive: bool = True, restart: bool = True,
                  check_every: int = DEFAULT_CHECK_EVERY, init: PDHGState | None = None,
                  full_output: bool = False, scaling: str = "ruiz",
                  precision: str = "mixed", omega: bool = True):
    """One fused PDHG solve of the mapping LP for B instances.

    ``problems`` is a sequence of ``Problem``s or an already-packed
    ``ProblemBatch``.  Returns one ``PDHGResult`` per instance, sliced
    back to its own (n, m) shapes: primal upper bound, certified dual
    lower bound, and the rounded mapping for the placement phase.

    ``tol=None`` runs the legacy fixed-step loop for exactly ``iters``
    iterations (bit-stable; ``scaling``/``precision``/``omega`` are
    tol-mode knobs and are ignored here).  ``tol=<float>`` switches to
    the adaptive restarted engine: per-lane PDLP-style step sizes
    (``adaptive``), average-iterate restarts (``restart``), early exit
    once every lane's normalized duality gap is <= tol — ``iters``
    becomes the cap, convergence is checked every ``check_every``
    iterations — plus the speed layer: Ruiz equilibration
    (``scaling='ruiz'``), primal-weight balancing (``omega=True``), and
    mixed-precision f32-iterate/f64-certificate solves with a final f64
    polish (``precision='mixed'``; ``'f64'`` solves in f64 throughout).
    Tol-mode mappings use degeneracy-insensitive canonical rounding
    (``_canonical_mapping``), so epsilon-optimal solves agree
    per-instance, not just in aggregate.

    ``init`` warm-starts from a previous solve's ``PDHGState`` (shapes
    are re-aligned; lane b seeds lane b).  ``full_output=True`` returns
    ``(results, SolveStats)`` — per-instance telemetry plus the final
    state for warm-starting the next solve.
    """
    if scaling not in SCALINGS:
        raise ValueError(
            f"scaling must be one of {SCALINGS}, got {scaling!r}")
    if precision not in PRECISIONS:
        raise ValueError(
            f"precision must be one of {PRECISIONS}, got {precision!r}")
    batch = problems if isinstance(problems, ProblemBatch) \
        else pack_problems(problems)
    if operator == "auto":
        operator = ("dense" if batch.B * batch.n * batch.Tp
                    <= _DENSE_ACT_BUDGET else "cumsum")
    if tol is None:
        x0 = y0 = None
        if init is not None:
            x0, y0, _, _ = _align_state(init, batch)
            x0, y0 = jnp.asarray(x0), jnp.asarray(y0)
        args = (jnp.asarray(batch.weights(), jnp.float32),
                jnp.asarray(batch.start), jnp.asarray(batch.end),
                jnp.asarray(batch.feas),
                jnp.asarray(batch.cost, jnp.float32),
                jnp.float32(step_scale))
        _count_dispatch()
        x, y, primal, dual, rel_gap = _pdhg_run_many(
            *args, iters=iters, Tp=batch.Tp, operator=operator,
            x0=x0, y0=y0)
        iters_b = np.full(batch.B, iters, np.int64)
        restarts_b = np.zeros(batch.B, np.int64)
        conv = np.ones(batch.B, bool)
        eta_np = omega_np = None
        x, y = np.asarray(x), np.asarray(y)
        primal, dual, rel_gap = (np.asarray(primal), np.asarray(dual),
                                 np.asarray(rel_gap))
    else:
        # the whole tol-mode call — array creation included — lives in
        # a scoped x64 context (place_step.py's discipline): f64 arrays
        # built outside it would silently downcast, and the jit cache
        # keys on the x64 flag so this never collides with f32 traces
        with jax.enable_x64(True):
            x0 = y0 = eta_init = omega_init = None
            if init is not None:
                x0, y0, eta_a, omega_a = _align_state(init, batch)
                x0, y0 = jnp.asarray(x0), jnp.asarray(y0)
                if eta_a is not None:
                    eta_init = jnp.asarray(eta_a, jnp.float32)
                if omega_a is not None:
                    omega_init = jnp.asarray(omega_a, jnp.float32)
            w_dt = jnp.float64 if precision == "f64" else jnp.float32
            args = (jnp.asarray(batch.weights(), w_dt),
                    jnp.asarray(batch.start), jnp.asarray(batch.end),
                    jnp.asarray(batch.feas),
                    jnp.asarray(batch.cost, w_dt),
                    jnp.float32(step_scale))
            _count_dispatch()
            (x, y, primal, dual, rel_gap, iters_b, restarts_b,
             conv, eta_out, omega_out) = _pdhg_run_many_tol(
                *args, jnp.float32(tol), max_iters=iters,
                check_every=check_every, Tp=batch.Tp, operator=operator,
                adaptive=adaptive, restart=restart, scaling=scaling,
                precision=precision, omega_on=omega, x0=x0, y0=y0,
                eta_init=eta_init, omega_init=omega_init)
            iters_b = np.asarray(iters_b, np.int64)
            restarts_b = np.asarray(restarts_b, np.int64)
            conv = np.asarray(conv)
            eta_np = np.asarray(eta_out, np.float32)
            omega_np = np.asarray(omega_out, np.float32) if omega else None
            x, y = np.asarray(x), np.asarray(y)
            primal, dual, rel_gap = (np.asarray(primal), np.asarray(dual),
                                     np.asarray(rel_gap))
    results = []
    for b, t in enumerate(batch.problems):
        x_b = x[b, : t.n, : t.m]
        feas_b = batch.feas[b, : t.n, : t.m]
        if tol is None:
            mapping = np.where(feas_b, x_b, -1.0).argmax(axis=1)
            mapping = mapping.astype(np.int64)
        else:
            mapping = _canonical_mapping(x_b, feas_b,
                                         np.asarray(t.node_types.cost))
        results.append(PDHGResult(
            x=x_b,
            objective=float(primal[b]),
            lower_bound=float(dual[b]),
            gap=float(primal[b] - dual[b]),
            iters=int(iters_b[b]),
            mapping=mapping,
            x_max=x_b.max(axis=1),
            restarts=int(restarts_b[b]),
            kkt=float(rel_gap[b]),
            converged=bool(conv[b]),
        ))
    if not full_output:
        return results
    stats = SolveStats(
        iterations=iters_b, restarts=restarts_b, kkt=rel_gap,
        converged=conv, tol=tol,
        state=PDHGState(x=np.asarray(x, np.float32),
                        y=np.asarray(y, np.float32), eta=eta_np,
                        omega=omega_np),
    )
    return results, stats


@functools.lru_cache(maxsize=None)
def _pipeline_fn(max_iters: int, check_every: int, Tp: int, operator: str,
                 adaptive: bool, restart: bool, scaling: str,
                 precision: str, omega_on: bool, n_devices):
    """Build (once per static config) the jitted whole-sweep stepper:
    one ``lax.scan`` over sweep groups whose body is the tol-mode core,
    warm-starting each group from its predecessor's final iterates —
    ONE compiled dispatch for the entire chain.  ``n_devices`` wraps
    the scan in a ``shard_map`` over the batch dim so a multi-chip host
    solves disjoint lanes data-parallel (each shard's early-exit
    ``while_loop`` stops independently)."""

    def run(W, S, E, F, C, step_scale, tol):
        G, B, n, m, D = W.shape
        it_dt = jnp.float64 if precision == "f64" else jnp.float32

        def body(carry, inp):
            x, y, eta, om, has = carry
            w, s, e, f, cst = inp
            (x_o, y_o, primal, dual, rel, it_b, rs_b, conv, eta_o,
             om_o) = _tol_core(
                w, s, e, f, cst, step_scale, tol, max_iters, check_every,
                Tp, operator, adaptive, restart, 12, scaling, precision,
                omega_on, x0=x, y0=y, eta_init=eta, omega_init=om,
                use_init=has)
            # states cross group boundaries in ORIGINAL coordinates —
            # each group re-scales by its own Ruiz factors on entry
            carry = (x_o.astype(it_dt), y_o.astype(it_dt),
                     eta_o.astype(it_dt), om_o.astype(it_dt),
                     jnp.bool_(True))
            outs = (x_o.astype(jnp.float32), primal, dual, rel, it_b,
                    rs_b, conv, eta_o.astype(jnp.float32),
                    om_o.astype(jnp.float32))
            return carry, outs

        carry0 = (jnp.zeros((B, n, m), it_dt),
                  jnp.zeros((B, Tp, m, D), it_dt),
                  jnp.zeros((B,), it_dt), jnp.ones((B,), it_dt),
                  jnp.bool_(False))
        carry, outs = jax.lax.scan(body, carry0, (W, S, E, F, C))
        return outs + (carry[1].astype(jnp.float32),)

    if n_devices is not None:
        from jax.sharding import Mesh
        from jax.sharding import PartitionSpec as P

        mesh = Mesh(np.array(jax.devices()[:n_devices]), ("lanes",))
        gb = P(None, "lanes")  # (G, B, ...) stacked group arrays
        run = jax.shard_map(run, mesh=mesh,
                            in_specs=(gb, gb, gb, gb, gb, P(), P()),
                            out_specs=(gb,) * 9 + (P("lanes"),),
                            check_vma=False)
    return jax.jit(run)


def _sweep_pipeline(groups, pad_to, tol, iters, step_scale, operator,
                    adaptive, restart, check_every, scaling, precision,
                    omega, devices):
    """The compiled sweep chain: pack every group to one common shape,
    stack them on a leading group axis, and run the whole warm-started
    chain as one device dispatch (``_pipeline_fn``)."""
    sizes = {len(g) for g in groups}
    if len(sizes) != 1:
        raise ValueError(
            f"pipeline=True needs equal group sizes (states warm-start "
            f"lane-for-lane), got sizes {sorted(sizes)}")
    batches = [pack_problems(g, pad_to=pad_to) for g in groups]
    b0 = batches[0]
    if operator == "auto":
        operator = ("dense" if b0.B * b0.n * b0.Tp <= _DENSE_ACT_BUDGET
                    else "cumsum")
    n_devices = None
    if devices is not None and devices > 1:
        if b0.B % devices != 0:
            raise ValueError(
                f"pipeline sharding needs devices to divide the group "
                f"size, got B={b0.B}, devices={devices}")
        if devices > len(jax.devices()):
            raise ValueError(
                f"devices={devices} exceeds the {len(jax.devices())} "
                f"local device(s)")
        n_devices = devices
    with jax.enable_x64(True):
        w_dt = jnp.float64 if precision == "f64" else jnp.float32
        W = jnp.asarray(np.stack([bt.weights() for bt in batches]), w_dt)
        S = jnp.asarray(np.stack([bt.start for bt in batches]))
        E = jnp.asarray(np.stack([bt.end for bt in batches]))
        F = jnp.asarray(np.stack([bt.feas for bt in batches]))
        C = jnp.asarray(np.stack([bt.cost for bt in batches]), w_dt)
        fn = _pipeline_fn(iters, check_every, b0.Tp, operator, adaptive,
                          restart, scaling, precision, omega, n_devices)
        _count_dispatch()
        out = fn(W, S, E, F, C, jnp.float32(step_scale), jnp.float32(tol))
        (xs, primals, duals, rels, iters_g, restarts_g, convs, etas,
         omegas, y_last) = [np.asarray(o) for o in out]
    results: list[PDHGResult] = []
    stats: list[SolveStats] = []
    for g, batch in enumerate(batches):
        for b, t in enumerate(batch.problems):
            x_b = xs[g][b, : t.n, : t.m]
            feas_b = batch.feas[b, : t.n, : t.m]
            results.append(PDHGResult(
                x=x_b,
                objective=float(primals[g][b]),
                lower_bound=float(duals[g][b]),
                gap=float(primals[g][b] - duals[g][b]),
                iters=int(iters_g[g][b]),
                mapping=_canonical_mapping(x_b, feas_b,
                                           np.asarray(t.node_types.cost)),
                x_max=x_b.max(axis=1),
                restarts=int(restarts_g[g][b]),
                kkt=float(rels[g][b]),
                converged=bool(convs[g][b]),
            ))
        # only the final group's state is materialized (it is the only
        # dual iterate the scan carries out); earlier groups' telemetry
        # is complete but their state is None
        state = None
        if g == len(batches) - 1:
            state = PDHGState(x=xs[g], y=y_last, eta=etas[g],
                              omega=omegas[g] if omega else None)
        stats.append(SolveStats(
            iterations=iters_g[g].astype(np.int64),
            restarts=restarts_g[g].astype(np.int64),
            kkt=rels[g], converged=convs[g], tol=tol, state=state))
    return results, stats


def _sweep_impl(groups, tol: float = DEFAULT_TOL, iters: int = 4000,
                step_scale: float = 0.9, operator: str = "auto",
                adaptive: bool = True, restart: bool = True,
                check_every: int = DEFAULT_CHECK_EVERY,
                align_shapes: bool = True, scaling: str = "ruiz",
                precision: str = "mixed", omega: bool = True,
                pipeline: bool = False, devices: int | None = None):
    """Warm-started fleet sweep: solve a grid-adjacent sequence of
    instance groups, seeding each group's primal/dual iterates from its
    predecessor's solution.

    ``groups[g]`` holds one sweep point's instances (e.g. the seed
    replicas of one grid cell), ordered so consecutive groups are
    neighbors on the sweep grid — exactly the row-major, seed-innermost
    order ``workload.sweep_specs`` emits.  Neighboring LP instances
    differ by one perturbed axis, so the previous optimum is deep inside
    the new problem's basin and the adaptive engine converges in a
    fraction of a cold start's iterations (the sweep analogue of Eva's
    incremental re-provisioning).

    With ``align_shapes`` every group is packed to one common padded
    shape, so the whole sweep reuses a single compiled solve and states
    carry over without re-alignment.  A group whose size differs from
    its predecessor's cold-starts (states match lane-for-lane only).

    ``pipeline=True`` compiles the whole chain into ONE ``lax.scan``
    dispatch (requires aligned shapes and equal group sizes; see
    ``_sweep_pipeline``); ``devices`` additionally shards the batch dim
    across that many local devices via ``shard_map``.

    Returns ``(results, stats)``: the flat per-instance ``PDHGResult``
    list (group order preserved) and one ``SolveStats`` per group.
    """
    groups = [list(g) for g in groups]
    if not groups or any(not g for g in groups):
        raise ValueError("solve_lp_sweep needs non-empty groups")
    pad_to = None
    if align_shapes:
        trimmed = [trim_timeline(p)[0] for g in groups for p in g]
        pad_to = (max(t.n for t in trimmed), max(t.m for t in trimmed),
                  max(t.D for t in trimmed), max(t.T for t in trimmed))
    if pipeline:
        if not align_shapes:
            raise ValueError(
                "pipeline=True requires align_shapes=True (every group "
                "must share one padded shape)")
        return _sweep_pipeline(
            groups, pad_to, tol=tol, iters=iters, step_scale=step_scale,
            operator=operator, adaptive=adaptive, restart=restart,
            check_every=check_every, scaling=scaling, precision=precision,
            omega=omega, devices=devices)
    results: list[PDHGResult] = []
    stats: list[SolveStats] = []
    state: PDHGState | None = None
    for g in groups:
        batch = pack_problems(g, pad_to=pad_to)
        if state is not None and state.B != batch.B:
            state = None
        res, st = solve_lp_many(
            batch, iters=iters, step_scale=step_scale, operator=operator,
            tol=tol, adaptive=adaptive, restart=restart,
            check_every=check_every, init=state, full_output=True,
            scaling=scaling, precision=precision, omega=omega)
        results.extend(res)
        stats.append(st)
        state = st.state
    return results, stats


def solve_lp_sweep(groups, tol: float = DEFAULT_TOL, iters: int = 4000,
                   step_scale: float = 0.9, operator: str = "auto",
                   adaptive: bool = True, restart: bool = True,
                   check_every: int = DEFAULT_CHECK_EVERY,
                   align_shapes: bool = True, scaling: str = "ruiz",
                   precision: str = "mixed", omega: bool = True,
                   pipeline: bool = False, devices: int | None = None):
    """Deprecated: drive sweeps through the typed configs instead —
    ``FleetEngine(solver=SolverConfig(tol=...), sweep=SweepConfig(
    warm_start=k, pipeline=...)).solve(...)``.  This shim forwards to
    the same implementation (``_sweep_impl``), so results are
    bit-identical; it only adds the warning."""
    warnings.warn(
        "solve_lp_sweep is deprecated; use FleetEngine(solver="
        "SolverConfig(tol=...), sweep=SweepConfig(warm_start=..., "
        "pipeline=...)).solve(...) — results are bit-identical",
        DeprecationWarning, stacklevel=2)
    return _sweep_impl(groups, tol=tol, iters=iters,
                       step_scale=step_scale, operator=operator,
                       adaptive=adaptive, restart=restart,
                       check_every=check_every, align_shapes=align_shapes,
                       scaling=scaling, precision=precision, omega=omega,
                       pipeline=pipeline, devices=devices)
