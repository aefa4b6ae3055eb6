"""Compiles at real size for a described TPU v5e (no chip needed).

The TPU compiler is installed with JAX and compiles for a chip that is
described, not attached.  These tests guard what interpret mode cannot
see: Pallas block shapes the chip's (8, 128) tiling rule refuses, and
programs the compiler rejects.  Nothing runs, so they say nothing about
results or times.

The topology is described inside a module-scoped fixture, never while
the module is imported: only one process at a time may load the TPU
library, and every pytest worker imports this file.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

# the paper's runtime instance: gct_like_instance(n=2000, m=13, seed=0)
RUNTIME_INSTANCE = dict(n=2000, m=13, seed=0)


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache off
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)


@pytest.fixture(scope="module")
def runtime_batch():
    from repro.core import pack_problems, trim_timeline
    from repro.workload import gct_like_instance

    t = trim_timeline(gct_like_instance(**RUNTIME_INSTANCE))[0]
    return pack_problems([t], assume_trimmed=True)


def _spec(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def test_tol_lp_dense_compiles_at_paper_scale(one_chip, runtime_batch):
    """The tolerance-stopped LP with the f64 certificate and polish
    (precision='mixed') at n=2000, m=13, T'=1973 fits one chip."""
    from repro.core.batch import _pdhg_run_many_tol

    b = runtime_batch
    B, n, m, D, Tp = b.B, b.n, b.m, b.D, b.Tp
    S = lambda shape, dt: _spec(one_chip, shape, dt)  # noqa: E731
    with jax.enable_x64(True):
        compiled = _pdhg_run_many_tol.lower(
            S((B, n, m, D), jnp.float32), S((B, n), jnp.int32),
            S((B, n), jnp.int32), S((B, n, m), jnp.bool_),
            S((B, m), jnp.float32), S((), jnp.float32),
            S((), jnp.float32), max_iters=4000, check_every=25, Tp=Tp,
            operator="dense", scaling="ruiz", precision="mixed",
            omega_on=True).compile()
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < 16e9  # one v5e holds 16 GB


def _congestion_many(S, n, Tp):
    from repro.kernels.congestion import congestion_many_pallas

    G = 4
    return jax.jit(congestion_many_pallas, static_argnames="T").lower(
        S((G, n), jnp.int32), S((G, n), jnp.int32),
        S((G, n, 2), jnp.float32), T=Tp)


@pytest.mark.parametrize("lower", [_congestion_many],
                         ids=["congestion_many_G4"])
def test_kernel_compiles_to_mosaic(one_chip, runtime_batch, lower):
    S = lambda shape, dt: _spec(one_chip, shape, dt)  # noqa: E731
    compiled = lower(S, runtime_batch.n, runtime_batch.Tp).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("purchase,similarity",
                         [(True, True), (True, False), (False, False)],
                         ids=["own_similarity", "own_first", "cross_fill"])
def test_compiled_placement_sub_phase_compiles(one_chip, runtime_batch,
                                               purchase, similarity):
    """One sub-phase of the compiled placement stepper, traced in f64,
    over the runtime instance's first 64 start-sorted tasks: own-pack
    under either fit policy, and cross-fill (first fit, no purchase) on
    a pool whose 64 nodes are all open."""
    from repro.core.place_step import _pad4, _plan_chunks, _sub_phase_fn

    b = runtime_batch
    L, n_cap, D = 64, 64, b.D
    Tpp = _pad4(b.Tp)
    order = np.argsort(b.start[0], kind="stable")[:L]
    lens = np.array([L], np.int32)
    s_seq = b.start[0][order][:, None].astype(np.int32)
    e_seq = b.end[0][order][:, None].astype(np.int32)
    chunks = _plan_chunks(lens, s_seq, e_seq, n_cap, Tpp,
                          0 if purchase else n_cap, grows=purchase)
    S = lambda shape, dt: _spec(one_chip, shape, dt)  # noqa: E731
    with jax.enable_x64(True):
        f64, i32 = jnp.float64, jnp.int32
        compiled = _sub_phase_fn().lower(
            S((1, n_cap, Tpp * D), f64), S((1,), i32), S((1,), i32),
            S((L, 1, D), f64), S((L, 1), i32), S((L, 1), i32),
            S((L, 1), f64), S((1, D), f64), S((1, D), f64), S((), f64),
            purchase=purchase, similarity=similarity,
            chunks=chunks).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < 16e9
