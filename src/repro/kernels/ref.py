"""Pure-jnp oracles for the Pallas congestion kernel.

These are the ground truth the kernel tests compare against
(``assert_allclose`` over shape/dtype sweeps).

Every contraction runs at ``Precision.HIGHEST``, so an oracle is
exact f32 on every backend (the TPU's default is one bf16 pass).

Like the kernel, every oracle is generic over the trailing feature
dimensions (D/K): lowered virtual constraint columns from
``repro.core.constraints`` (exclusivity, anti-affinity) are ordinary
capacity dimensions here and need no special casing.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = ["congestion_ref", "congestion_many_ref"]

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
