"""CUR decomposition: rank selection (Eq. 2), the Frobenius-optimal link
matrix U = C+ W R+ (Eq. 1), randomized range-finder SVD (beyond-paper speed
path), and the error-bound constants of Theorem 3.1.
"""
from __future__ import annotations

import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp


def rank_for(m: int, n: int, r_max: int = 256) -> int:
    """Paper Eq. 2: largest power-of-2 rank that still reduces parameters,
    capped at r_max. Solves mr + r^2 + rn < mn."""
    r_star = (math.sqrt(m * m + 6 * m * n + n * n) - (m + n)) / 2.0
    if r_star < 1:
        return 1
    r = 2 ** int(math.floor(math.log2(r_star)))
    # r* is the root of mr + r^2 + rn = mn: an exact power of two lands
    # on equality, which Eq. 2's strict inequality excludes
    while r > 1 and m * r + r * r + r * n >= m * n:
        r //= 2
    return min(r, r_max)


def compute_u(W: jnp.ndarray, C: jnp.ndarray, R: jnp.ndarray) -> jnp.ndarray:
    """U = pinv(C) @ W @ pinv(R) — optimal in Frobenius norm given C, R."""
    Cp = jnp.linalg.pinv(C.astype(jnp.float32))
    Rp = jnp.linalg.pinv(R.astype(jnp.float32))
    return Cp @ W.astype(jnp.float32) @ Rp


def exact_svd(S: jnp.ndarray, r: int):
    """Leading-r SVD via full LAPACK SVD (paper-faithful path)."""
    P, sig, Qt = jnp.linalg.svd(S.astype(jnp.float32), full_matrices=False)
    return P[:, :r], sig[:r], Qt[:r, :].T


def randomized_svd(S: jnp.ndarray, r: int, key,
                   oversample: int = 8, n_iter: int = 2):
    """Halko randomized range-finder SVD: two tall-skinny GEMM passes + QR +
    small SVD. MXU-friendly and O(mnr) instead of O(mn min(m,n)) — the
    beyond-paper compression-speed optimization (DESIGN.md §3)."""
    S = S.astype(jnp.float32)
    m, n = S.shape
    k = min(r + oversample, min(m, n))
    G = jax.random.normal(key, (n, k), jnp.float32)
    Y = S @ G
    Q, _ = jnp.linalg.qr(Y)
    for _ in range(n_iter):
        Z = S.T @ Q
        Q, _ = jnp.linalg.qr(S @ Z)
    B = Q.T @ S                                   # (k, n)
    Ub, sig, Qt = jnp.linalg.svd(B, full_matrices=False)
    P = Q @ Ub
    return P[:, :r], sig[:r], Qt[:r, :].T


def cur_from_indices(W: jnp.ndarray, p: jnp.ndarray, q: jnp.ndarray):
    """Extract C = W[:, q], R = W[p, :], U = C+ W R+."""
    C = W[:, q]
    R = W[p, :]
    U = compute_u(W, C, R)
    return C, U, R


def cur_error_constants(P: jnp.ndarray, Q: jnp.ndarray,
                        p: jnp.ndarray, q: jnp.ndarray):
    """eta_p = ||(P[p,:])^-1||_2, eta_q = ||(Q[q,:])^-1||_2 (Theorem 3.1)."""
    def inv_norm(M):
        s = jnp.linalg.svd(M, compute_uv=False)
        return 1.0 / jnp.maximum(s[-1], 1e-30)
    return inv_norm(P[p, :]), inv_norm(Q[q, :])


def spectral_error_bound(P, Q, sig, p, q):
    """(eta_p + eta_q) * sigma_{r+1} — the Theorem 3.1 upper bound on
    ||M - C U R||_2 for the matrix M whose leading singular vectors are
    (P, Q) and whose singular values are ``sig`` (at least r+1 of them).

    NB the bound is only valid for the matrix that was decomposed: under
    ``wanda_deim`` selection that is the WANDA importance matrix S, *not*
    the raw weight W (``WeightInfo.bound_on`` records which)."""
    eta_p, eta_q = cur_error_constants(P, Q, p, q)
    r = p.shape[0]
    return (eta_p + eta_q) * sig[r] if sig.shape[0] > r else jnp.inf
