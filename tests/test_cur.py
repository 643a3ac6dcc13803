"""Properties of the CUR decomposition core (paper §3, Theorem 3.1)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core.cur import (
    compute_u, cur_from_indices, exact_svd, randomized_svd, rank_for)
from repro.core.deim import deim
from repro.core.wanda import wanda_scores

settings.register_profile("ci", deadline=None, max_examples=20)
settings.load_profile("ci")


def _lowrank(key, m, n, r, noise=1e-3):
    k1, k2, k3 = jax.random.split(key, 3)
    A = jax.random.normal(k1, (m, r))
    B = jax.random.normal(k2, (r, n))
    return A @ B + noise * jax.random.normal(k3, (m, n))


# ---------------------------------------------------------------------------
# DEIM
# ---------------------------------------------------------------------------

@given(m=st.integers(12, 80), r=st.integers(1, 10), seed=st.integers(0, 50))
def test_deim_indices_distinct_and_in_range(m, r, seed):
    r = min(r, m)
    V = jax.random.normal(jax.random.PRNGKey(seed), (m, r))
    Q, _ = jnp.linalg.qr(V)
    p = np.asarray(deim(Q))
    assert len(set(p.tolist())) == r
    assert p.min() >= 0 and p.max() < m


def test_deim_first_index_is_argmax():
    V = jax.random.normal(jax.random.PRNGKey(3), (40, 5))
    p = deim(V)
    assert int(p[0]) == int(jnp.argmax(jnp.abs(V[:, 0])))


def test_deim_interpolation_property():
    """After selecting j indices, the residual of vector j at the selected
    rows is (near) zero — the defining DEIM property."""
    V = jax.random.normal(jax.random.PRNGKey(4), (50, 6))
    Q, _ = jnp.linalg.qr(V)
    p = np.asarray(deim(Q))
    for j in range(1, 6):
        A = Q[p[:j], :j]
        c = np.linalg.solve(np.asarray(A), np.asarray(Q[p[:j], j]))
        res = np.asarray(Q[:, j]) - np.asarray(Q[:, :j]) @ c
        assert np.max(np.abs(res[p[:j]])) < 1e-4


def _orthonormal(m, r, seed):
    A = np.random.default_rng(seed).standard_normal((m, r))
    return np.linalg.qr(A)[0]


def _deim_oracle(V):
    """Sorensen & Embree 2016, Alg. 1 in float64 with a solve per step.
    Returns the indices and, per step, the gap between the two largest
    residual magnitudes over the largest."""
    m, r = V.shape
    res, p, gaps = V[:, 0].copy(), [], []
    for j in range(r):
        if j:
            c = np.linalg.solve(V[p, :j], V[p, j])
            res = V[:, j] - V[:, :j] @ c
            res[p] = 0.0
        top2 = np.sort(np.abs(res))[-2:]
        gaps.append((top2[1] - top2[0]) / top2[1])
        p.append(int(np.argmax(np.abs(res))))
    return np.array(p), np.array(gaps)


# two seeds per shape whose oracle keeps every step's top two residuals
# more than 1e-3 apart (most seeds at 512 x 64 do not)
@pytest.mark.parametrize("m,r,seed", [(64, 8, 0), (64, 8, 1), (256, 32, 1),
                                      (256, 32, 2), (512, 64, 2),
                                      (512, 64, 3)])
def test_deim_matches_solve_oracle(m, r, seed):
    """Elimination picks the indices of the solve-based Algorithm 1."""
    V = _orthonormal(m, r, seed)
    want, gaps = _deim_oracle(V)
    assert gaps.min() > 1e-3            # no near-tie the test could flip on
    np.testing.assert_array_equal(np.asarray(deim(jnp.asarray(V))), want)


@pytest.mark.parametrize("r", [16, 48])
def test_deim_is_prefix_consistent(r):
    V = jnp.asarray(_orthonormal(256, 64, 5), jnp.float32)
    np.testing.assert_array_equal(np.asarray(deim(V[:, :r])),
                                  np.asarray(deim(V))[:r])


def test_deim_rank_deficient_gives_distinct_indices():
    """Two equal columns and one zero column: r distinct indices in range."""
    V = np.random.default_rng(6).standard_normal((40, 6)).astype(np.float32)
    V[:, 3] = V[:, 1]
    V[:, 5] = 0.0
    p = np.asarray(deim(jnp.asarray(V)))
    assert len(set(p.tolist())) == 6
    assert p.min() >= 0 and p.max() < 40


# ---------------------------------------------------------------------------
# Theorem 3.1 error bound
# ---------------------------------------------------------------------------

@given(m=st.integers(20, 60), n=st.integers(20, 60), r=st.integers(2, 8),
       seed=st.integers(0, 20))
def test_spectral_error_bound_holds(m, n, r, seed):
    W = _lowrank(jax.random.PRNGKey(seed), m, n, r + 4, noise=0.05)
    P, sig, Q = exact_svd(W, min(m, n))
    p = deim(P[:, :r])
    q = deim(Q[:, :r])
    C, U, R = cur_from_indices(W, p, q)
    err = jnp.linalg.norm(W - C @ U @ R, 2)
    eta_p = 1.0 / jnp.linalg.svd(P[p, :r], compute_uv=False)[-1]
    eta_q = 1.0 / jnp.linalg.svd(Q[q, :r], compute_uv=False)[-1]
    bound = (eta_p + eta_q) * sig[r]
    assert float(err) <= float(bound) * (1 + 1e-3)


def test_u_is_frobenius_optimal():
    """U = C+ W R+ minimizes ||W - CUR||_F over U (Eq. 1 / Stewart)."""
    key = jax.random.PRNGKey(7)
    W = _lowrank(key, 30, 40, 6, noise=0.1)
    P, sig, Q = exact_svd(W, 10)
    p, q = deim(P[:, :5]), deim(Q[:, :5])
    C, U, R = cur_from_indices(W, p, q)
    base = float(jnp.linalg.norm(W - C @ U @ R))
    for s in range(5):
        dU = 0.1 * jax.random.normal(jax.random.fold_in(key, s), U.shape)
        perturbed = float(jnp.linalg.norm(W - C @ (U + dU) @ R))
        assert perturbed >= base - 1e-4


def test_exact_recovery_of_lowrank_matrix():
    """A rank-r matrix is reconstructed (near) exactly by rank-r CUR."""
    W = _lowrank(jax.random.PRNGKey(11), 40, 50, 4, noise=0.0)
    P, sig, Q = exact_svd(W, 6)
    p, q = deim(P[:, :4]), deim(Q[:, :4])
    C, U, R = cur_from_indices(W, p, q)
    rel = jnp.linalg.norm(W - C @ U @ R) / jnp.linalg.norm(W)
    assert float(rel) < 1e-4


# ---------------------------------------------------------------------------
# Eq. 2 rank selection
# ---------------------------------------------------------------------------

@given(m=st.integers(8, 4096), n=st.integers(8, 4096))
def test_rank_for_reduces_params(m, n):
    r = rank_for(m, n, r_max=256)
    assert r >= 1 and (r & (r - 1)) == 0          # power of two
    if r > 1:  # the parameter-reduction condition of §3.2
        assert m * r + r * r + r * n < m * n


def test_rank_for_paper_scale():
    # llama3.1-8B gate (4096 x 14336) -> capped at r_max
    assert rank_for(4096, 14336, 256) == 256
    assert rank_for(4096, 14336, 512) == 512
    # tiny matrix: rank collapses
    assert rank_for(8, 8, 256) <= 4


# ---------------------------------------------------------------------------
# randomized SVD (beyond-paper speed path)
# ---------------------------------------------------------------------------

def test_randomized_svd_matches_exact_on_lowrank():
    W = _lowrank(jax.random.PRNGKey(13), 120, 90, 8, noise=1e-4)
    P1, s1, Q1 = exact_svd(W, 8)
    P2, s2, Q2 = randomized_svd(W, 8, jax.random.PRNGKey(0))
    assert jnp.allclose(s1[:8], s2[:8], rtol=1e-2)
    # subspaces align (up to sign): |P1^T P2| ~ I
    M = jnp.abs(P1.T @ P2)
    assert float(jnp.min(jnp.diag(M))) > 0.98


def test_wanda_scores_orientation():
    """S_ij = |W_ij| * ||X_i|| — rows scale with input activations."""
    W = jnp.ones((4, 3))
    act_sq = jnp.asarray([0.0, 1.0, 4.0, 9.0])
    S = wanda_scores(W, act_sq)
    np.testing.assert_allclose(np.asarray(S[:, 0]), [0, 1, 2, 3], atol=1e-6)
