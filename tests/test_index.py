"""ANN-index substrate tests: K-means invariants, capacity assignment,
in-cluster kNN exactness, cluster-component property (paper §3.2)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.configs.base import NomadConfig
from repro.data.synthetic import gaussian_mixture
from repro.index.ann import build_index, _np_dist2
from repro.index.kmeans import assign_jnp, capacity_assign, kmeans_fit, lsh_init_centroids
from repro.index.knn import _cluster_knn_jit, cluster_knn


def test_kmeans_objective_nonincreasing():
    x, _ = gaussian_mixture(2000, 16, n_components=6, seed=1)
    x = jnp.asarray(x)
    cents = lsh_init_centroids(jax.random.key(0), x, 6)
    prev = np.inf
    for _ in range(8):
        a, d2 = assign_jnp(x, cents)
        obj = float(jnp.sum(d2))
        assert obj <= prev + 1e-3 * abs(prev), "EM objective increased"
        prev = obj
        sums = jnp.zeros((6, 16)).at[a].add(x)
        cnt = jnp.zeros((6,)).at[a].add(1.0)
        cents = jnp.where((cnt > 0)[:, None], sums / jnp.maximum(cnt, 1)[:, None], cents)


def test_kmeans_assignment_is_nearest():
    x, _ = gaussian_mixture(500, 8, seed=2)
    cents, assign, counts = kmeans_fit(jax.random.key(1), jnp.asarray(x), 5, n_iters=10)
    d2 = _np_dist2(x, np.asarray(cents))
    np.testing.assert_array_equal(np.asarray(assign), d2.argmin(1))
    assert int(counts.sum()) == 500


@given(st.integers(0, 2**31 - 1), st.integers(2, 10))
@settings(max_examples=15, deadline=None)
def test_capacity_assign_invariants(seed, K):
    rng = np.random.default_rng(seed)
    n = 200
    x = rng.normal(0, 1, (n, 4)).astype(np.float32)
    cents = rng.normal(0, 1, (K, 4)).astype(np.float32)
    cap = int(np.ceil(1.3 * n / K))
    a = capacity_assign(_np_dist2, x, cents, cap)
    assert (a >= 0).all() and (a < K).all()
    counts = np.bincount(a, minlength=K)
    assert (counts <= cap).all(), "capacity violated"


def test_capacity_assign_prefers_nearest_when_room():
    rng = np.random.default_rng(0)
    x = rng.normal(0, 1, (50, 3)).astype(np.float32)
    cents = rng.normal(0, 1, (10, 3)).astype(np.float32)
    a = capacity_assign(_np_dist2, x, cents, capacity=50)  # no pressure
    np.testing.assert_array_equal(a, _np_dist2(x, cents).argmin(1))


def test_cluster_knn_exactness():
    rng = np.random.default_rng(3)
    C, D, k = 40, 8, 5
    xb = jnp.asarray(rng.normal(0, 1, (C, D)), jnp.float32)
    valid = jnp.ones((C,), bool)
    knn, w = cluster_knn(xb, valid, k)
    d2 = np.array(jnp.sum(jnp.square(xb[:, None] - xb[None, :]), -1))  # writable copy
    np.fill_diagonal(d2, np.inf)
    want = np.argsort(d2, axis=1)[:, :k]
    got_d = np.take_along_axis(d2, np.asarray(knn), 1)
    want_d = np.take_along_axis(d2, want, 1)
    np.testing.assert_allclose(np.sort(got_d, 1), np.sort(want_d, 1), rtol=1e-4)


def test_cluster_knn_respects_padding():
    rng = np.random.default_rng(4)
    C, D, k, real = 32, 4, 4, 20
    xb = jnp.asarray(rng.normal(0, 1, (C, D)), jnp.float32)
    valid = jnp.arange(C) < real
    knn, w = cluster_knn(xb, valid, k)
    w = np.asarray(w)
    knn = np.asarray(knn)
    # padded heads carry no edges; no edge points at a padded tail
    assert (w[real:] == 0).all()
    assert (knn[:real][w[:real] > 0] < real).all()


def test_cluster_knn_has_no_sort_or_scatter():
    """The in-cell kNN takes its neighbours and its weights' ranks from two
    top-ks; a sort or scatter in the program means a (C, C) rank pass."""
    C, D, k = 24, 4, 5
    text = _cluster_knn_jit.lower(
        jnp.zeros((C, D), jnp.float32), jnp.ones((C,), bool), k=k, impl="jnp"
    ).as_text()
    assert text.count("chlo.top_k") == 2
    assert "stablehlo.sort" not in text
    assert "stablehlo.scatter" not in text


def test_build_index_layout_and_component_property():
    cfg = NomadConfig(n_points=1500, dim=12, n_clusters=6, n_neighbors=5)
    x, _ = gaussian_mixture(1500, 12, n_components=6, seed=5)
    idx = build_index(x, cfg, impl="jnp")
    K, C = idx.n_clusters, idx.capacity
    # permutation is a bijection onto valid rows
    assert idx.perm.shape == (1500,)
    assert len(set(idx.perm.tolist())) == 1500
    valid = idx.valid_mask
    assert valid[idx.perm].all()
    assert int(valid.sum()) == 1500
    # x_rows really is the permuted input
    np.testing.assert_allclose(idx.x_rows[idx.perm], x, rtol=1e-6)
    # paper §3.2: every kNN edge stays inside its cluster block (component)
    cluster_of = np.arange(K * C) // C
    live = idx.knn_w > 0  # (K·C, k)
    head_cluster = np.broadcast_to(cluster_of[:, None], idx.knn_idx.shape)
    tail_cluster = cluster_of[idx.knn_idx]
    assert (head_cluster[live] == tail_cluster[live]).all()
    # counts consistent
    np.testing.assert_array_equal(
        idx.counts, valid.reshape(K, C).sum(1)
    )
