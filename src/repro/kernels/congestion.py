"""Pallas TPU kernel: interval-congestion matmul.

Computes ``out[t, k] = sum_u [start_u <= t <= end_u] * w[u, k]`` — the core
operator behind the paper's congestion quantities (Lemma 1 lower bound, LP
congestion constraints, and the PDHG LP solver's A / A^T applications).

TPU adaptation (vs. the paper's per-slot Python loops): the task-active
interval mask ``A[t, u]`` is never materialized in HBM; each (Tt, nb) tile
is generated *inside VMEM* from the ``start``/``end`` vectors with
``broadcasted_iota``, then contracted against the demand tile on the MXU.
Block sizes keep the working set (Tt*nb mask + nb*Kb weights + Tt*Kb acc)
within VMEM and 128-aligned for the MXU.

Grid: (G, T/Tt, K/Kb, n/nb) with the instance axis outermost (one grid
group per batched instance; G=1 for the single-instance wrapper) and the
task axis innermost so each output tile stays resident while the task
dimension streams through.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

__all__ = ["congestion_pallas", "congestion_many_pallas"]

# 128-aligned MXU tiles; fp32 working set = (128*512 + 512*128 + 128*128)*4
# ~= 580 KiB << 16 MiB VMEM, leaving headroom for double buffering.
BLOCK_T = 128
BLOCK_N = 512
BLOCK_K = 128


def congestion_pallas(
    start: jax.Array,
    end: jax.Array,
    w: jax.Array,
    T: int,
    block_t: int = BLOCK_T,
    block_n: int = BLOCK_N,
    block_k: int = BLOCK_K,
    interpret: bool = False,
) -> jax.Array:
    """(T, K) congestion from (n,) int32 start/end and (n, K) weights —
    the G=1 case of ``congestion_many_pallas`` (one tiling/padding
    implementation to maintain)."""
    return congestion_many_pallas(
        start[None], end[None], w[None], T,
        block_t=block_t, block_n=block_n, block_k=block_k,
        interpret=interpret,
    )[0]


def _congestion_many_kernel(start_ref, end_ref, w_ref, out_ref, *, block_t):
    ti = pl.program_id(1)
    k = pl.program_id(3)

    @pl.when(k == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    # (Tt, nb) active mask generated in-register from the interval bounds
    t0 = ti * block_t
    t_ids = t0 + jax.lax.broadcasted_iota(jnp.int32, (block_t, 1), 0)
    start = start_ref[...].reshape(1, -1)  # (1, nb)
    end = end_ref[...].reshape(1, -1)
    mask = (start <= t_ids) & (t_ids <= end)
    acc = jnp.dot(
        mask.astype(w_ref.dtype), w_ref[0],
        preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.HIGHEST,  # f32 weights, not bf16
    )
    out_ref[...] += acc.astype(out_ref.dtype)[None]


@functools.partial(
    jax.jit, static_argnames=("T", "block_t", "block_n", "block_k", "interpret")
)
def congestion_many_pallas(
    start: jax.Array,
    end: jax.Array,
    w: jax.Array,
    T: int,
    block_t: int = BLOCK_T,
    block_n: int = BLOCK_N,
    block_k: int = BLOCK_K,
    interpret: bool = False,
) -> jax.Array:
    """(G, T, K) congestion for a batch of G independent instances.

    start, end: (G, n) int32; w: (G, n, K).  The instance axis becomes the
    outermost grid dimension, so each instance's output tile streams its own
    task dimension exactly like the single-instance kernel; padding follows
    the same never-active / zero-weight scheme and is exact.
    """
    G, n, K = w.shape
    dtype = w.dtype
    n_p = max(pl.cdiv(n, block_n) * block_n, block_n)
    K_p = max(pl.cdiv(K, block_k) * block_k, block_k)
    T_p = max(pl.cdiv(T, block_t) * block_t, block_t)
    # bounds ride as (G, 1, n_p) so each block's last two dims are
    # (1 = full, block_n), which the TPU's (8, 128) tiling rule accepts
    # for any G; a (1, block_n) block of a (G, n_p) array does not
    start_p = jnp.full((G, 1, n_p), 1, jnp.int32).at[:, 0, :n].set(
        start.astype(jnp.int32))
    end_p = jnp.full((G, 1, n_p), 0, jnp.int32).at[:, 0, :n].set(
        end.astype(jnp.int32))
    w_p = jnp.zeros((G, n_p, K_p), dtype).at[:, :n, :K].set(w)

    grid = (G, T_p // block_t, K_p // block_k, n_p // block_n)
    out = pl.pallas_call(
        functools.partial(_congestion_many_kernel, block_t=block_t),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, block_n), lambda g, i, j, k: (g, 0, k)),
            pl.BlockSpec((1, 1, block_n), lambda g, i, j, k: (g, 0, k)),
            pl.BlockSpec((1, block_n, block_k), lambda g, i, j, k: (g, k, j)),
        ],
        out_specs=pl.BlockSpec(
            (1, block_t, block_k), lambda g, i, j, k: (g, i, j)),
        out_shape=jax.ShapeDtypeStruct((G, T_p, K_p), dtype),
        interpret=interpret,
    )(start_p, end_p, w_p)
    return out[:, :T, :K]
