"""Pallas TPU kernel: placement fit-scoring.

The placement phase's hot loop (paper §III "Time Complexity":
O(n * |S| * D * T) dominates) asks, for one task against *all* open nodes of
a node-type: is the node feasible over the task's span, and how similar is
its remaining capacity to the demand (similarity-fit)?  This kernel fuses
the three reductions in one pass over the (N, T, D) remaining-capacity
tensor:

    feas_margin[n] = min_{t in span, d} rem[n,t,d] - dem[d]
    dot[n]         = sum_{t in span, d} (rem/cap)[n,t,d] * (dem/cap)[d]
    rem_norm2[n]   = sum_{t in span, d} (rem/cap)[n,t,d]^2

Layout: rem is passed transposed as (T, D, N) so nodes ride the 128-lane
axis and timeslots the 8-sublane axis; D is a small static inner loop.
Grid: (B, N/Nb, T/Tb) with the T axis innermost, accumulating into the
(Nb,) outputs while they stay VMEM-resident; the single-instance entry
point is the B=1 case.

The kernel is generic over D, which is the constraint contract: the
lowering in ``repro.core.constraints`` appends virtual unit-capacity
dimensions (a shared exclusivity column with a δ=1e-6 sliver demand for
non-exclusive rows, one column per anti-affinity group) and they ride
the same feasibility-margin reduction as real resources.  The margins
involved (0 vs δ−EPS ≈ 9e-7, accumulations of δ) sit far above f32
resolution at these O(1) magnitudes, so the f32 kernel path stays
bit-consistent with the f64 numpy path on the feasibility *decision*.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

__all__ = ["fit_scores_pallas", "fit_scores_many_pallas"]

BLOCK_N = 128
BLOCK_T = 256

_BIG = 3.0e38  # < fp32 max; neutral for the min-reduction


@functools.partial(
    jax.jit, static_argnames=("block_n", "block_t", "interpret")
)
def fit_scores_pallas(
    rem_tdn: jax.Array,   # (T, D, N) remaining capacity, node-minor
    dem: jax.Array,       # (D,)
    mask: jax.Array,      # (T,) float, 1 inside the span
    inv_cap: jax.Array,   # (D,)
    block_n: int = BLOCK_N,
    block_t: int = BLOCK_T,
    interpret: bool = False,
):
    """Returns (feas_margin, dot, rem_norm2), each (N,) float32 — the
    B=1 case of ``fit_scores_many_pallas`` (one tiling/padding
    implementation to maintain).

    Padding is exact: padded slots get mask=0 (neutral for all three
    reductions), padded nodes are sliced away.
    """
    feas, dot, norm = fit_scores_many_pallas(
        rem_tdn[None], dem[None], mask[None], inv_cap[None],
        block_n=block_n, block_t=block_t, interpret=interpret)
    return feas[0], dot[0], norm[0]


def _fit_many_kernel(rem_ref, dem_ref, mask_ref, invcap_ref, feas_ref,
                     dot_ref, norm_ref):
    t = pl.program_id(2)

    @pl.when(t == 0)
    def _init():
        feas_ref[...] = jnp.full_like(feas_ref, _BIG)
        dot_ref[...] = jnp.zeros_like(dot_ref)
        norm_ref[...] = jnp.zeros_like(norm_ref)

    mask = mask_ref[0, 0].reshape(-1, 1)  # (Tb, 1) in {0, 1}
    D = rem_ref.shape[2]
    feas = feas_ref[0, 0]
    dot = dot_ref[0, 0]
    norm = norm_ref[0, 0]
    for d in range(D):  # D is small and static: unrolled VPU loop
        rem_d = rem_ref[0, :, d, :]  # (Tb, Nb)
        dem_d = dem_ref[0, 0, d]
        inv_d = invcap_ref[0, 0, d]
        margin = jnp.where(mask > 0, rem_d - dem_d, _BIG)
        feas = jnp.minimum(feas, margin.min(axis=0))
        rem_n = rem_d * inv_d * mask
        dot = dot + (dem_d * inv_d) * rem_n.sum(axis=0)
        norm = norm + (rem_n * rem_n).sum(axis=0)
    feas_ref[0, 0] = feas
    dot_ref[0, 0] = dot
    norm_ref[0, 0] = norm


@functools.partial(
    jax.jit, static_argnames=("block_n", "block_t", "interpret")
)
def fit_scores_many_pallas(
    rem_btdn: jax.Array,  # (B, T, D, N) remaining capacity, node-minor
    dem: jax.Array,       # (B, D) per-instance task demand
    mask: jax.Array,      # (B, T) float, 1 inside each instance's span
    inv_cap: jax.Array,   # (B, D) per-instance 1/cap; 0 on padded dims
    block_n: int = BLOCK_N,
    block_t: int = BLOCK_T,
    interpret: bool = False,
):
    """Batched fit scoring: grid over B with the single-instance tiling.

    Returns (feas_margin, dot, rem_norm2), each (B, N) float32 — one
    lockstep ``place_many`` step scores the pending task of every
    instance against all its open nodes in this one call.  Padding is
    exact exactly as in ``fit_scores_pallas``: padded slots carry mask=0
    (neutral for all three reductions), padded nodes are sliced away by
    the host, padded dims carry ``inv_cap=0`` (and zero demand), so they
    only add a neutral ``rem - 0 >= 0`` term to the min-reduction.
    """
    B, T, D, N = rem_btdn.shape
    dtype = jnp.float32
    N_p = max(pl.cdiv(N, block_n) * block_n, block_n)
    T_p = max(pl.cdiv(T, block_t) * block_t, block_t)
    # every per-instance row rides as (B, 1, X), so each block's last
    # two dims are (1 = full, X-block) and the TPU's (8, 128) tiling
    # rule holds for any B (a (1, X) block of a (B, X) array breaks it
    # once B > 1, and a 1-D block breaks it once X outgrows one tile)
    rem_p = jnp.zeros((B, T_p, D, N_p), dtype).at[:, :T, :, :N].set(
        rem_btdn.astype(dtype))
    mask_p = jnp.zeros((B, 1, T_p), dtype).at[:, 0, :T].set(
        mask.astype(dtype))
    dem_3d = dem.astype(dtype).reshape(B, 1, D)
    inv_3d = inv_cap.astype(dtype).reshape(B, 1, D)

    grid = (B, N_p // block_n, T_p // block_t)
    out_shape = [jax.ShapeDtypeStruct((B, 1, N_p), dtype)] * 3
    out_spec = pl.BlockSpec((1, 1, block_n), lambda b, i, t: (b, 0, i))
    row_spec = pl.BlockSpec((1, 1, D), lambda b, i, t: (b, 0, 0))
    feas, dot, norm = pl.pallas_call(
        _fit_many_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_t, D, block_n),
                         lambda b, i, t: (b, t, 0, i)),
            row_spec,
            pl.BlockSpec((1, 1, block_t), lambda b, i, t: (b, 0, t)),
            row_spec,
        ],
        out_specs=[out_spec, out_spec, out_spec],
        out_shape=out_shape,
        interpret=interpret,
    )(rem_p, dem_3d, mask_p, inv_3d)
    return feas[:, 0, :N], dot[:, 0, :N], norm[:, 0, :N]
