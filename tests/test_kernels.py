"""Pallas congestion kernel tests: shape/dtype sweeps vs. the pure-jnp
oracles, and the interpret-mode rule."""

import numpy as np
import pytest

from repro.kernels import ops, ref
from repro.kernels.congestion import congestion_pallas


RNG = np.random.default_rng(42)


class TestCongestionKernel:
    @pytest.mark.parametrize("n,K,T", [
        (1, 1, 1),
        (7, 3, 24),          # sub-block everything
        (128, 128, 128),     # exact block boundary
        (300, 10, 200),
        (1000, 26, 995),     # GCT-like trimmed timeline
        (513, 129, 130),     # off-by-one over block edges
    ])
    def test_matches_ref(self, n, K, T):
        start = RNG.integers(0, T, n)
        end = np.minimum(start + RNG.integers(0, max(T // 2, 1), n), T - 1)
        w = RNG.random((n, K)).astype(np.float32)
        out = np.asarray(ops.congestion(start, end, w, T))
        want = np.asarray(ops.congestion(start, end, w, T, use_ref=True))
        np.testing.assert_allclose(out, want, rtol=1e-5, atol=1e-5)

    @pytest.mark.parametrize("dtype", [np.float32, np.float64])
    def test_dtypes(self, dtype):
        n, K, T = 50, 4, 30
        start = RNG.integers(0, T, n)
        end = np.minimum(start + RNG.integers(0, 10, n), T - 1)
        w = RNG.random((n, K)).astype(dtype)
        out = np.asarray(ops.congestion(start, end, w, T))
        want = np.asarray(ref.congestion_ref(
            np.asarray(start, np.int32), np.asarray(end, np.int32),
            w.astype(np.float32), T))
        np.testing.assert_allclose(out, want, rtol=1e-5, atol=1e-5)

    def test_point_tasks(self):
        """start == end tasks contribute to exactly one slot."""
        start = np.array([3, 3, 5])
        end = np.array([3, 3, 5])
        w = np.ones((3, 1), np.float32)
        out = np.asarray(ops.congestion(start, end, w, 8))
        np.testing.assert_allclose(out[:, 0], [0, 0, 0, 2, 0, 1, 0, 0])

    def test_small_block_sizes(self):
        """Exercise multi-step grids with tiny blocks."""
        n, K, T = 40, 6, 50
        start = RNG.integers(0, T, n)
        end = np.minimum(start + RNG.integers(0, 20, n), T - 1)
        w = RNG.random((n, K)).astype(np.float32)
        out = np.asarray(congestion_pallas(
            np.asarray(start, np.int32), np.asarray(end, np.int32),
            np.asarray(w), T, block_t=8, block_n=16, block_k=8,
            interpret=True))
        want = np.asarray(ref.congestion_ref(
            np.asarray(start, np.int32), np.asarray(end, np.int32), w, T))
        np.testing.assert_allclose(out, want, rtol=1e-5, atol=1e-5)


class TestInterpretMode:
    """Interpret mode only on the CPU backend; no silent fallback on
    any other."""

    @pytest.fixture
    def backend(self, monkeypatch):
        def use(name):
            monkeypatch.setattr(ops.jax, "default_backend", lambda: name)
            ops._interpret.cache_clear()
        yield use
        ops._interpret.cache_clear()

    @pytest.mark.parametrize("name,interpret", [("cpu", True),
                                                ("tpu", False)])
    def test_cpu_interprets_tpu_compiles(self, backend, name, interpret):
        backend(name)
        assert ops._interpret() is interpret

    def test_other_backend_raises(self, backend):
        backend("gpu")
        with pytest.raises(RuntimeError, match="'gpu'"):
            ops.congestion(np.array([0]), np.array([0]),
                           np.ones((1, 1), np.float32), 1)
