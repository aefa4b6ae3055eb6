"""Pallas kernel tests: shape/dtype sweeps vs. the pure-jnp oracles, plus
placement-engine parity between the numpy and kernel backends."""

import numpy as np
import pytest

from repro.kernels import ops, ref
from repro.kernels.congestion import congestion_pallas
from repro.kernels.fit import fit_scores_pallas


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


class TestFitKernel:
    @pytest.mark.parametrize("N,T,D", [
        (1, 1, 1),
        (3, 24, 2),
        (128, 256, 5),       # exact blocks
        (130, 300, 7),       # padding on both axes
        (64, 1000, 2),
    ])
    def test_matches_ref(self, N, T, D):
        rem = RNG.random((N, T, D)).astype(np.float32)
        dem = (RNG.random(D) * 0.2).astype(np.float32)
        cap = (0.5 + RNG.random(D)).astype(np.float32)
        s = int(RNG.integers(0, T))
        e = int(RNG.integers(s, T))
        feas_k, cos_k = ops.fit_scores(rem, dem, s, e, cap, scored=True)
        feas_r, cos_r = ops.fit_scores(rem, dem, s, e, cap, scored=True,
                                       use_ref=True)
        np.testing.assert_array_equal(feas_k, feas_r)
        np.testing.assert_allclose(cos_k, cos_r, rtol=1e-4, atol=1e-5)

    def test_feasibility_boundary(self):
        """A node with exactly the demand remaining is feasible; one with
        epsilon less is not."""
        T, D = 10, 2
        dem = np.array([0.5, 0.5], np.float32)
        rem = np.stack([
            np.full((T, D), 0.5, np.float32),          # exact fit
            np.full((T, D), 0.5 - 1e-3, np.float32),   # just misses
        ])
        feas, _ = ops.fit_scores(rem, dem, 0, T - 1, np.ones(D, np.float32))
        assert feas[0] and not feas[1]

    def test_span_masking(self):
        """Capacity shortfalls outside the span must not matter."""
        T, D = 12, 1
        rem = np.full((1, T, D), 1.0, np.float32)
        rem[0, 8:, 0] = 0.0  # empty outside span
        dem = np.array([0.9], np.float32)
        feas, _ = ops.fit_scores(rem, dem, 0, 7, np.ones(1, np.float32))
        assert feas[0]
        feas, _ = ops.fit_scores(rem, dem, 0, 8, np.ones(1, np.float32))
        assert not feas[0]

    def test_small_blocks(self):
        N, T, D = 20, 40, 3
        rem = RNG.random((N, T, D)).astype(np.float32)
        dem = (RNG.random(D) * 0.1).astype(np.float32)
        cap = np.ones(D, np.float32)
        mask = np.zeros(T, np.float32)
        mask[5:30] = 1.0
        got = fit_scores_pallas(
            np.ascontiguousarray(rem.transpose(1, 2, 0)), dem, mask,
            1.0 / cap, block_n=8, block_t=8, interpret=True)
        want = ref.fit_scores_ref(rem, dem, mask, 1.0 / cap)
        for g, w in zip(got, want):
            np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                       rtol=1e-4, atol=1e-5)


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


class TestBackendParity:
    @pytest.mark.slow
    def test_placement_identical_across_backends(self):
        from repro.core import penalty_map, trim_timeline, two_phase, verify
        from repro.workload import SyntheticSpec, synthetic_instance

        p = synthetic_instance(SyntheticSpec(n=120, m=4, D=3, seed=7))
        t, _ = trim_timeline(p)
        mp = penalty_map(t, "avg")
        for fit in ("first", "similarity"):
            s_np = two_phase(t, mp, fit=fit, backend="numpy")
            s_k = two_phase(t, mp, fit=fit, backend="kernel")
            verify(t, s_np)
            verify(t, s_k)
            np.testing.assert_array_equal(s_np.assign, s_k.assign)
            np.testing.assert_array_equal(s_np.node_type, s_k.node_type)
