"""Discrete Empirical Interpolation Method (DEIM) index selection.

Given the leading-r singular vectors V (m, r) of an importance matrix, DEIM
picks exactly r distinct row indices: index j is the position of the largest
interpolation residual of singular vector j against the previously selected
rows (Sorensen & Embree 2016, Alg. 1). After j steps of Gaussian elimination
with partial pivoting, column j of V's Schur complement is that residual
(Chaturantabut & Sorensen 2010; Sorensen & Embree 2016, §2), so the whole
selection is r elementwise rank-1 updates in float32: O(m r^2) work, no solve.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def deim(V: jnp.ndarray) -> jnp.ndarray:
    """V: (m, r) orthonormal-ish columns. Returns (r,) distinct indices.

    Step j reads only column j's residual, so ``deim(V[:, :r]) ==
    deim(V)[:r]``. A zero pivot eliminates nothing, so a rank-deficient V
    still yields r distinct indices."""
    R = V.astype(jnp.float32)                            # (m, r) residuals
    m, r = R.shape
    cols = jnp.arange(r)

    def body(j, state):
        R, res, p, visited = state                       # res = R[:, j]
        pj = jnp.argmax(jnp.where(visited, -1.0, jnp.abs(res)))
        piv = res[pj]
        l = jnp.where(piv == 0.0, 0.0, res / jnp.where(piv == 0.0, 1.0, piv))
        R = R - l[:, None] * R[pj][None, :]
        # column j + 1 as an exact masked sum, fused into the update: on
        # TPU a dynamic column slice of R copies all of R every step
        res = jnp.where(cols == j + 1, R, 0.0).sum(axis=1)
        return R, res, p.at[j].set(pj), visited.at[pj].set(True)

    state = (R, R[:, 0], jnp.zeros((r,), jnp.int32), jnp.zeros((m,), bool))
    return jax.lax.fori_loop(0, r, body, state)[2]


def deim_pair(P: jnp.ndarray, Q: jnp.ndarray):
    """Row indices from left singular vectors P (m,r) and column indices
    from right singular vectors Q (n,r): (p, q) as in Theorem 3.1."""
    return deim(P), deim(Q)
