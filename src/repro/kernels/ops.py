"""Jit'd public wrappers around the Pallas kernels.

On TPU the kernels lower to Mosaic.  On the CPU backend (the test
suite, ``JAX_PLATFORMS=cpu``) they run under ``interpret=True``, which
executes the kernel body in Python for correctness.  Any other backend
is an error rather than a silent interpret-mode fallback.  ``ref.py``
holds the pure-jnp oracles used by the test sweeps.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from . import congestion as _congestion
from . import fit as _fit
from . import ref

__all__ = ["congestion", "congestion_many", "fit_scores",
           "fit_scores_many", "fit_scores_step"]

_EPS = 1e-7


@functools.lru_cache(maxsize=1)
def _interpret() -> bool:
    """Pallas interpret mode: off on TPU, on for the CPU backend."""
    backend = jax.default_backend()
    if backend not in ("tpu", "cpu"):
        raise RuntimeError(
            f"the Pallas kernels compile for TPU (or run interpreted on "
            f"the CPU backend for tests); the default backend is "
            f"{backend!r}")
    return backend == "cpu"


def congestion(start, end, w, T: int, use_ref: bool = False):
    """(T, K) interval congestion; Pallas kernel unless ``use_ref``."""
    start = jnp.asarray(start, jnp.int32)
    end = jnp.asarray(end, jnp.int32)
    w = jnp.asarray(w, jnp.float32)
    if use_ref:
        return ref.congestion_ref(start, end, w, T)
    return _congestion.congestion_pallas(
        start, end, w, T, interpret=_interpret()
    )


def congestion_many(start, end, w, T: int, use_ref: bool = False):
    """(G, T, K) batched interval congestion; Pallas kernel unless
    ``use_ref``.  start/end: (G, n); w: (G, n, K)."""
    start = jnp.asarray(start, jnp.int32)
    end = jnp.asarray(end, jnp.int32)
    w = jnp.asarray(w, jnp.float32)
    if use_ref:
        return ref.congestion_many_ref(start, end, w, T)
    return _congestion.congestion_many_pallas(
        start, end, w, T, interpret=_interpret()
    )


def fit_scores(rem, dem, s: int, e: int, cap, scored: bool = False,
               use_ref: bool = False):
    """Host-facing fit API for the placement engine.

    rem: (N, T, D) remaining capacities of the open nodes.
    dem: (D,) demand; [s, e] the task's span; cap: (D,) type capacity.

    Returns (feasible (N,) bool, score (N,) float) where score is the cosine
    similarity of capacity-normalized demand vs. remaining capacity over the
    span (only computed when ``scored``).
    """
    rem = np.asarray(rem)
    N, T, D = rem.shape
    dem_j = jnp.asarray(dem, jnp.float32)
    inv_cap = 1.0 / jnp.asarray(cap, jnp.float32)
    mask = jnp.zeros(T, jnp.float32).at[s : e + 1].set(1.0)
    if use_ref:
        feas_m, dot, norm2 = ref.fit_scores_ref(
            jnp.asarray(rem, jnp.float32), dem_j, mask, inv_cap
        )
    else:
        rem_tdn = jnp.asarray(np.ascontiguousarray(rem.transpose(1, 2, 0)),
                              jnp.float32)
        feas_m, dot, norm2 = _fit.fit_scores_pallas(
            rem_tdn, dem_j, mask, inv_cap, interpret=_interpret()
        )
    feas = np.asarray(feas_m) >= -_EPS
    if not scored:
        return feas, np.zeros(N, np.float32)
    span = e - s + 1
    dem_n = np.asarray(dem) / np.asarray(cap)
    dem_norm = float(np.linalg.norm(dem_n)) * np.sqrt(span)
    cos = np.asarray(dot) / (dem_norm * np.sqrt(np.asarray(norm2)) + 1e-30)
    return feas, cos


def fit_scores_many(rem, dem, s, e, inv_cap, scored: bool = False,
                    use_ref: bool = False):
    """Host-facing batched fit API for the lockstep placement engine.

    rem:     (B, N, T, D) open-node remaining capacities, all instances.
    dem:     (B, D) the pending task's demand per instance.
    s, e:    (B,) int inclusive span bounds per instance.
    inv_cap: (B, D) 1/cap of each instance's targeted node-type, with 0
             on padded dimensions (so they contribute nothing to the
             similarity reductions).

    Returns (feasible (B, N) bool, score (B, N) float) — the batched
    analogue of ``fit_scores``; padded/foreign nodes are masked by the
    caller at selection time.
    """
    rem = np.asarray(rem)
    B, N, T, D = rem.shape
    s = np.asarray(s, np.int64)
    e = np.asarray(e, np.int64)
    dem_j = jnp.asarray(dem, jnp.float32)
    inv_j = jnp.asarray(inv_cap, jnp.float32)
    t_ids = np.arange(T)
    mask = ((s[:, None] <= t_ids[None, :])
            & (t_ids[None, :] <= e[:, None])).astype(np.float32)
    if use_ref:
        feas_m, dot, norm2 = ref.fit_scores_many_ref(
            jnp.asarray(rem, jnp.float32), dem_j, jnp.asarray(mask), inv_j
        )
    else:
        rem_btdn = jnp.asarray(
            np.ascontiguousarray(rem.transpose(0, 2, 3, 1)), jnp.float32)
        feas_m, dot, norm2 = _fit.fit_scores_many_pallas(
            rem_btdn, dem_j, jnp.asarray(mask), inv_j,
            interpret=_interpret()
        )
    feas = np.asarray(feas_m) >= -_EPS
    if not scored:
        return feas, np.zeros((B, N), np.float32)
    span = (e - s + 1).astype(np.float64)
    dem_n = np.asarray(dem) * np.asarray(inv_cap)
    dem_norm = np.linalg.norm(dem_n, axis=1) * np.sqrt(span)  # (B,)
    cos = np.asarray(dot) / (
        dem_norm[:, None] * np.sqrt(np.asarray(norm2)) + 1e-30)
    return feas, cos


def fit_scores_step(rem, dem, span, capx, dem_norm, scored: bool = False,
                    quantum=None, eps: float = _EPS):
    """In-loop callable form of ``fit_scores_many`` for compiled steppers.

    Unlike the host-facing wrappers above, this is a pure-jnp function
    meant to be *traced* — it takes and returns ``jnp`` arrays, does no
    host conversion or padding, and is safe inside ``lax.while_loop`` /
    ``lax.scan`` bodies (the compiled lockstep placement stepper,
    ``repro.core.place_step``, calls it once per placement step).

    All slot-carrying operands arrive flattened to one contiguous
    reduction axis K = T*D (slot k = t*D + d), the same layout trick
    the numpy engine uses for its feasibility scan: the similarity dot
    then lowers to a batched mat-vec over a contiguous axis instead of
    a 4-D einsum with a tiny trailing dimension, which CPU/TPU backends
    vectorize an order of magnitude better.

    rem:      (B, N, K) open-node remaining capacity.
    dem:      (B, K) the pending task's demand, tiled over timeslots.
    span:     (B, K) bool, True inside each instance's task span.
    capx:     (B, K) node-type capacity tiled over slots, +inf on
              padded dims, so ``rem / capx`` is exact on real dims and
              0 on padded ones.
    dem_norm: (B,) the precomputed per-task demand norm of the
              similarity denominator.
    quantum:  similarity tie-break quantization as a *runtime* scalar
              (1e9 for the engines' shared 9-decimal rounding).  Passing
              it as an operand keeps XLA from folding the division into
              a multiply-by-reciprocal, which is not bit-equal to the
              host engines' ``np.round(score, 9)``.

    Returns ``(feas, score)``, both (B, N): feasibility is the same
    elementwise float comparison the host engines evaluate
    (``not any(rem < dem - eps)`` over the span), and ``score`` is the
    quantized cosine similarity (zeros when ``scored`` is False).  In a
    float64 trace (``jax.enable_x64(True)``) every elementwise
    term is bit-identical to the numpy engines; the reduction sums may
    differ in the last ulp, which the shared quantization collapses.
    """
    thr = dem - eps
    viol = ((rem < thr[:, None, :]) & span[:, None, :]).any(axis=2)
    feas = ~viol
    if not scored:
        return feas, jnp.zeros(feas.shape, rem.dtype)
    span_f = span.astype(rem.dtype)
    rem_n = rem / capx[:, None, :]
    q = (dem / capx) * span_f                 # exact: dem_n * {0, 1}
    dot = jnp.einsum("bnk,bk->bn", rem_n, q,  # batched mat-vec
                     precision=jax.lax.Precision.HIGHEST)
    rm = rem_n * span_f[:, None, :]
    norm2 = (rm * rm).sum(axis=2)
    score = dot / (dem_norm[:, None] * jnp.sqrt(norm2) + 1e-30)
    if quantum is not None:
        score = jnp.rint(score * quantum) / quantum
    return feas, score
