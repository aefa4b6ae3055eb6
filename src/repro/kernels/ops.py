"""Jit'd public wrappers around the Pallas congestion kernel.

On TPU the kernel lowers to Mosaic.  On the CPU backend (the test
suite, ``JAX_PLATFORMS=cpu``) it runs under ``interpret=True``, which
executes the kernel body in Python for correctness.  Any other backend
is an error rather than a silent interpret-mode fallback.  ``ref.py``
holds the pure-jnp oracles used by the test sweeps.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from . import congestion as _congestion
from . import ref

__all__ = ["congestion", "congestion_many"]


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
