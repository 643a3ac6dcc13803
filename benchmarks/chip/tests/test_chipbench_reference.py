"""The plain CURe reference's own arithmetic: the leading singular
subspaces, the DEIM growth factor that judges a selection, and the
paper's layer choice by angular distance."""
import numpy as np
import pytest

import chipbench_tiny  # noqa: F401  (puts the repo on the path)
from benchmarks.chip.reference import cure as ref


@pytest.mark.parametrize("shape", [(40, 90), (90, 40)])
def test_top_subspace_spans_the_leading_singular_vectors(shape):
    S = np.random.default_rng(0).standard_normal(shape)
    P, Q = ref.top_subspace(S, 8)
    U, _, Vt = np.linalg.svd(S, full_matrices=False)
    for got, want in ((P, U[:, :8]), (Q, Vt[:8].T)):
        assert got.shape == want.shape
        np.testing.assert_allclose(got.T @ got, np.eye(8), atol=1e-10)
        np.testing.assert_allclose(got @ got.T, want @ want.T, atol=1e-10)


def test_growth_tells_deim_from_the_first_indices():
    rng = np.random.default_rng(1)
    W = rng.standard_normal((256, 384))
    S = ref.scores(W, rng.uniform(0.5, 2.0, 256))
    P, Q = ref.top_subspace(S, 32)
    for V in (P, Q):
        best = ref.growth(V, ref.deim(V))
        assert 1.0 <= best < 50.0
        assert ref.growth(V, np.arange(32)) > 5 * best


def test_growth_of_a_repeated_or_short_selection_is_infinite():
    V = np.linalg.qr(np.random.default_rng(2).standard_normal((20, 4)))[0]
    assert ref.growth(V, [0, 1, 2, 2]) == float("inf")
    assert ref.growth(V, [0, 1, 2]) == float("inf")
    assert np.isfinite(ref.growth(V, [0, 1, 2, 3]))


def test_layer_choice_is_the_papers():
    from repro.core import angular
    h = np.random.default_rng(3).standard_normal((9, 5, 16))
    for i in range(1, 9):
        h[i] = h[i - 1] + (0.2 + 0.1 * i) * h[i]
    dist = ref.distances(h)
    np.testing.assert_allclose(dist, angular.layer_distances(h), rtol=1e-5)
    assert ref.select_layers(dist, 3) == angular.select_layers(dist, 3)
    assert 0 not in ref.select_layers(dist, 7)
    assert 7 not in ref.select_layers(dist, 7)
