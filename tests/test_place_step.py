"""The compiled stepper's scorer, ``place_step.fit_scores_step``, against
the float64 expressions of ``TypePool.find_fit``.

Each lane holds one pending task and its node-type's open nodes.  The
scorer runs in an x64 trace with the engines' 9-decimal quantum; the
reference is ``find_fit``'s own feasibility expression and its chosen
node under ``first`` and ``similarity`` fit.  Feasibility must match
exactly, and so must the chosen node; quantized scores may differ by
one quantum where a reduction sum's last ulp falls on a rounding edge.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.place_step import _QUANTUM, fit_scores_step
from repro.core.placement import FIT_POLICIES, TypePool
from repro.core.solution import EPS


def _lanes(rng, B, N, T, D):
    """B lanes of N open nodes each: capacity left mostly well above the
    demand, a dip below it inside the span on some nodes, and no
    capacity at all outside the span on others."""
    cap = rng.uniform(0.5, 2.0, (B, D))
    rem = cap[:, None, None, :] * rng.uniform(0.35, 1.0, (B, N, T, D))
    dem = cap * rng.uniform(0.05, 0.3, (B, D))
    s = rng.integers(0, T, B)
    e = np.array([rng.integers(lo, T) for lo in s])
    for b in range(B):
        for i in np.flatnonzero(rng.random(N) < 0.3):
            t, d = rng.integers(s[b], e[b] + 1), rng.integers(D)
            rem[b, i, t, d] = dem[b, d] * 0.5
        outside = np.r_[0: s[b], e[b] + 1: T]
        if len(outside):
            for i in np.flatnonzero(rng.random(N) < 0.3):
                rem[b, i, rng.choice(outside)] = 0.0
    return rem, dem, cap, s, e


def _step(rem, dem, cap, s, e, scored=True):
    """``fit_scores_step`` on (B, N, T, D) lanes, flattened to K = T*D
    slots as the compiled stepper lays them out."""
    B, N, T, D = rem.shape
    t = np.arange(T)
    span = (s[:, None] <= t) & (t <= e[:, None])              # (B, T)
    dem_n = dem / cap
    dem_norm = np.linalg.norm(dem_n, axis=1) * np.sqrt(e - s + 1)
    with jax.enable_x64(True):
        feas, score = fit_scores_step(
            jnp.asarray(rem.reshape(B, N, T * D)),
            jnp.asarray(np.tile(dem, (1, T))),
            jnp.asarray(np.repeat(span, D, axis=1)),
            jnp.asarray(np.tile(cap, (1, T))),
            jnp.asarray(dem_norm), scored=scored,
            quantum=jnp.asarray(np.float64(_QUANTUM)), eps=EPS)
        return np.asarray(feas), np.asarray(score)


def _find_fit(rem_b, dem_b, cap_b, s_b, e_b, fit):
    """(find_fit's feasibility mask, its quantized similarity scores,
    its chosen node) for one lane."""
    N, T, _ = rem_b.shape
    pool = TypePool(cap_b, T)
    for i in range(N):
        pool.open_node(i)
        pool.rem[i] = rem_b[i]
    rem_slice = pool.rem[:, s_b: e_b + 1, :]
    feas = (rem_slice >= dem_b[None, None, :] - EPS).all(axis=(1, 2))
    dem_n = dem_b / cap_b
    rem_n = rem_slice / cap_b[None, None, :]
    dot = np.einsum("ntd,d->n", rem_n, dem_n)
    dem_norm = np.linalg.norm(dem_n) * np.sqrt(e_b - s_b + 1)
    rem_norm = np.sqrt(np.einsum("ntd,ntd->n", rem_n, rem_n))
    score = np.round(dot / (dem_norm * rem_norm + 1e-30), 9)
    return feas, score, pool.find_fit(dem_b, s_b, e_b, fit)


def _chosen(feas, score, fit):
    """The stepper's pick for one lane: first maximum, None if no node
    fits."""
    if not feas.any():
        return None
    if fit == "first":
        return int(np.argmax(feas))
    return int(np.argmax(np.where(feas, score, -np.inf)))


def _assert_matches_find_fit(rem, dem, cap, s, e, fit):
    feas, score = _step(rem, dem, cap, s, e)
    for b in range(rem.shape[0]):
        want_feas, want_score, want_node = _find_fit(
            rem[b], dem[b], cap[b], s[b], e[b], fit)
        np.testing.assert_array_equal(feas[b], want_feas)
        np.testing.assert_allclose(score[b], want_score, rtol=0,
                                   atol=1.5 / _QUANTUM)
        assert _chosen(feas[b], score[b], fit) == want_node


@pytest.mark.parametrize("fit", FIT_POLICIES)
@pytest.mark.parametrize("N,T,D", [
    (1, 1, 1),
    (3, 24, 2),
    (128, 256, 5),
    (130, 300, 7),
    (64, 1000, 2),
])
def test_one_lane_matches_find_fit(N, T, D, fit):
    rng = np.random.default_rng(N * 1000 + T + D)
    _assert_matches_find_fit(*_lanes(rng, 1, N, T, D), fit)


@pytest.mark.parametrize("fit", FIT_POLICIES)
@pytest.mark.parametrize("B,N,T,D", [
    (1, 1, 1, 1),
    (3, 7, 24, 2),
    (2, 16, 40, 5),
    (4, 30, 13, 3),
    (2, 130, 20, 2),
])
def test_many_lanes_match_find_fit(B, N, T, D, fit):
    rng = np.random.default_rng(B * 100000 + N * 1000 + T + D)
    _assert_matches_find_fit(*_lanes(rng, B, N, T, D), fit)


def test_eps_feasibility_boundary():
    """A node left with exactly ``dem - EPS`` fits; one a float step
    below it does not, as in find_fit."""
    T, D = 10, 2
    dem = np.array([[0.5, 0.5]])
    cap = np.ones((1, D))
    edge = 0.5 - EPS
    rem = np.stack([np.full((T, D), 0.5),
                    np.full((T, D), edge),
                    np.full((T, D), np.nextafter(edge, -np.inf))])[None]
    feas, _ = _step(rem, dem, cap, np.array([0]), np.array([T - 1]))
    np.testing.assert_array_equal(feas[0], [True, True, False])
    _assert_matches_find_fit(rem, dem, cap, np.array([0]),
                             np.array([T - 1]), "first")


def test_span_masks_the_timeline():
    """A shortfall outside the span does not count; one slot inside
    does."""
    T = 12
    rem = np.ones((1, 1, T, 1))
    rem[0, 0, 8:, 0] = 0.0
    dem = np.array([[0.9]])
    cap = np.ones((1, 1))
    feas_in, _ = _step(rem, dem, cap, np.array([0]), np.array([7]))
    feas_out, _ = _step(rem, dem, cap, np.array([0]), np.array([8]))
    assert feas_in[0, 0] and not feas_out[0, 0]


@pytest.mark.parametrize("fit", FIT_POLICIES)
def test_span_edges(fit):
    """Point spans at slots 0 and T-1, and a span of the whole
    timeline, lane by lane."""
    rng = np.random.default_rng(3)
    B, N, T, D = 3, 9, 12, 3
    rem, dem, cap, _, _ = _lanes(rng, B, N, T, D)
    for s, e in [(np.array([0, 5, T - 1]), np.array([0, 5, T - 1])),
                 (np.zeros(B, np.int64), np.full(B, T - 1))]:
        _assert_matches_find_fit(rem, dem, cap, s, e, fit)


def test_padded_dims_add_nothing():
    """A padded dimension (capacity +inf, no demand) leaves feasibility,
    the quantized scores and the chosen node as they were."""
    rng = np.random.default_rng(4)
    rem3, dem3, cap3, s, e = _lanes(rng, 2, 6, 10, 3)
    rem4 = np.concatenate([rem3, np.ones(rem3.shape[:3] + (1,))], axis=3)
    dem4 = np.concatenate([dem3, np.zeros((2, 1))], axis=1)
    cap4 = np.concatenate([cap3, np.full((2, 1), np.inf)], axis=1)
    f3, c3 = _step(rem3, dem3, cap3, s, e)
    f4, c4 = _step(rem4, dem4, cap4, s, e)
    np.testing.assert_array_equal(f3, f4)
    np.testing.assert_allclose(c3, c4, rtol=0, atol=1.5 / _QUANTUM)
    for b in range(2):
        assert _chosen(f3[b], c3[b], "similarity") \
            == _chosen(f4[b], c4[b], "similarity")


def test_lanes_are_independent():
    """A lane scores the same alone as beside two other lanes."""
    rng = np.random.default_rng(5)
    rem, dem, cap, s, e = _lanes(rng, 3, 8, 14, 2)
    f3, c3 = _step(rem, dem, cap, s, e)
    f1, c1 = _step(rem[1:2], dem[1:2], cap[1:2], s[1:2], e[1:2])
    np.testing.assert_array_equal(f3[1], f1[0])
    np.testing.assert_allclose(c3[1], c1[0], rtol=0, atol=1.5 / _QUANTUM)
    for fit in FIT_POLICIES:
        assert _chosen(f3[1], c3[1], fit) == _chosen(f1[0], c1[0], fit)


@pytest.mark.parametrize("fit", FIT_POLICIES)
def test_equal_nodes_tie_to_the_first(fit):
    """Two nodes with equal remaining capacity score exactly alike, and
    the earlier one is chosen, as find_fit chooses it."""
    rng = np.random.default_rng(6)
    rem, dem, cap, s, e = _lanes(rng, 1, 5, 16, 3)
    rem[0, 1] = rem[0, 3] = cap[0] * 0.95
    rem[0, [0, 2, 4]] = 0.0  # fit nothing
    feas, score = _step(rem, dem, cap, s, e)
    assert feas[0, 1] and feas[0, 3]
    assert score[0, 1] == score[0, 3]
    assert _chosen(feas[0], score[0], fit) == 1
    _assert_matches_find_fit(rem, dem, cap, s, e, fit)


def test_unscored_gives_the_same_mask_and_zero_scores():
    rng = np.random.default_rng(7)
    rem, dem, cap, s, e = _lanes(rng, 3, 10, 20, 2)
    feas, _ = _step(rem, dem, cap, s, e)
    feas_u, score_u = _step(rem, dem, cap, s, e, scored=False)
    np.testing.assert_array_equal(feas_u, feas)
    assert score_u.shape == feas.shape and not score_u.any()
