"""Pure-jnp oracles for the Pallas kernels.

These are the ground truth every kernel test compares against
(``assert_allclose`` over shape/dtype sweeps).

Every contraction runs at ``Precision.HIGHEST``, so an oracle is
exact f32 on every backend (the TPU's default is one bf16 pass).

Like the kernels, every oracle is generic over the trailing feature
dimensions (D/K): lowered virtual constraint columns from
``repro.core.constraints`` (exclusivity, anti-affinity) are ordinary
capacity dimensions here and need no special casing.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = ["congestion_ref", "congestion_many_ref", "fit_scores_ref",
           "fit_scores_many_ref"]

_HI = jax.lax.Precision.HIGHEST


def congestion_ref(start, end, w, T: int):
    """out[t, k] = sum_u [start_u <= t <= end_u] * w[u, k].

    start, end: (n,) int32 inclusive slots; w: (n, K) float; out: (T, K).
    The interval-congestion operator — used by the LP constraint evaluation,
    the Lemma-1 lower bound and the PDHG solver's linear operator.
    """
    t = jnp.arange(T, dtype=jnp.int32)
    mask = (start[None, :] <= t[:, None]) & (t[:, None] <= end[None, :])
    return jnp.matmul(mask.astype(w.dtype), w, precision=_HI)


def congestion_many_ref(start, end, w, T: int):
    """out[g, t, k] = sum_u [start_gu <= t <= end_gu] * w[g, u, k].

    start, end: (G, n) int32; w: (G, n, K); out: (G, T, K) — the batched
    interval-congestion operator behind the many-instance LP engine.
    """
    t = jnp.arange(T, dtype=jnp.int32)
    mask = (start[:, None, :] <= t[None, :, None]) \
        & (t[None, :, None] <= end[:, None, :])  # (G, T, n)
    return jnp.einsum("gtn,gnk->gtk", mask.astype(w.dtype), w,
                      precision=_HI)


def fit_scores_ref(rem, dem, mask, inv_cap):
    """Placement fit scoring over all open nodes at once.

    rem:     (N, T, D) remaining capacity per node.
    dem:     (D,)      task demand.
    mask:    (T,)      1.0 inside the task's span, 0.0 outside.
    inv_cap: (D,)      1 / cap of this node-type.

    Returns (feas_margin, dot, rem_norm2):
      feas_margin: (N,) min over span,d of rem - dem  (feasible iff >= -eps)
      dot:         (N,) sum over span,d of (rem/cap) * (dem/cap)
      rem_norm2:   (N,) sum over span,d of (rem/cap)^2
    """
    dtype = rem.dtype
    big = jnp.asarray(jnp.finfo(dtype).max, dtype)
    margin = rem - dem[None, None, :]
    masked_margin = jnp.where(mask[None, :, None] > 0, margin, big)
    feas_margin = masked_margin.min(axis=(1, 2))
    rem_n = rem * inv_cap[None, None, :]
    dem_n = dem * inv_cap
    dot = jnp.einsum("ntd,d,t->n", rem_n, dem_n, mask, precision=_HI)
    rem_norm2 = jnp.einsum("ntd,ntd,t->n", rem_n, rem_n, mask,
                           precision=_HI)
    return feas_margin, dot, rem_norm2


def fit_scores_many_ref(rem, dem, mask, inv_cap):
    """Batched placement fit scoring — one task per instance, all open
    nodes of all B instances at once (the lockstep ``place_many`` hot
    loop).

    rem:     (B, N, T, D) remaining capacity per (instance, node).
    dem:     (B, D)       the current task's demand, per instance.
    mask:    (B, T)       1.0 inside that instance's task span.
    inv_cap: (B, D)       1 / cap of the targeted node-type; 0 on padded
                          dims (which then contribute nothing to
                          dot / rem_norm2).

    Returns (feas_margin, dot, rem_norm2), each (B, N) — the batched
    analogue of ``fit_scores_ref``; padded nodes/slots are the caller's
    responsibility (mask slots via ``mask``, nodes at selection time).
    """
    dtype = rem.dtype
    big = jnp.asarray(jnp.finfo(dtype).max, dtype)
    margin = rem - dem[:, None, None, :]
    masked_margin = jnp.where(mask[:, None, :, None] > 0, margin, big)
    feas_margin = masked_margin.min(axis=(2, 3))
    rem_n = rem * inv_cap[:, None, None, :]
    dem_n = dem * inv_cap
    dot = jnp.einsum("bntd,bd,bt->bn", rem_n, dem_n, mask, precision=_HI)
    rem_norm2 = jnp.einsum("bntd,bntd,bt->bn", rem_n, rem_n, mask,
                           precision=_HI)
    return feas_margin, dot, rem_norm2
