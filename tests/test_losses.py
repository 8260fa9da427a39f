"""Loss-layer tests, incl. the paper's two structural claims:

* **Reduction** (paper §3.3): with R̃ = ∅ the NOMAD loss *is* InfoNC-t-SNE.
* **Theorem 1** (paper §7): the mean-approximated loss upper-bounds the
  InfoNC-t-SNE loss — the Jensen step exactly, the Taylor step approximately
  (checked with tolerance on clustered data, and exactly in the tight-cluster
  limit where the Taylor remainder vanishes).

Property tests use hypothesis over positions/weights/partitions.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import losses
from repro.core.cauchy import cauchy, cauchy_pairwise
from repro.core.rank_model import edge_weights, normalizer


# ---------------------------------------------------------------------------
# Cauchy kernel properties
# ---------------------------------------------------------------------------


@given(st.integers(0, 2**31 - 1), st.integers(1, 5))
@settings(max_examples=30, deadline=None)
def test_cauchy_range_symmetry_identity(seed, d):
    k1, k2 = jax.random.split(jax.random.key(seed))
    a = jax.random.normal(k1, (7, d)) * 10
    b = jax.random.normal(k2, (7, d)) * 10
    q = cauchy(a, b)
    assert np.all(np.asarray(q) > 0) and np.all(np.asarray(q) <= 1.0)
    np.testing.assert_allclose(np.asarray(cauchy(b, a)), np.asarray(q), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(cauchy(a, a)), 1.0, rtol=1e-6)
    # pairwise form agrees with broadcast form
    qp = cauchy_pairwise(a, b)
    np.testing.assert_allclose(
        np.asarray(qp), np.asarray(cauchy(a[:, None, :], b[None, :, :])), rtol=1e-4, atol=1e-6
    )


# ---------------------------------------------------------------------------
# Inverse-rank model (Eq. 6)
# ---------------------------------------------------------------------------


def rank_matrix(dist2):
    """Oracle: R[i, j] = rank of i in j's ascending-distance order (0 = j
    itself), from a stable argsort of every column of ``dist2``."""
    order = jnp.argsort(dist2, axis=0)  # (C, C): order[r, j] = point at rank r w.r.t. j
    C = dist2.shape[0]
    ranks = jnp.zeros((C, C), jnp.int32)
    return ranks.at[order, jnp.arange(C)[None, :]].set(jnp.arange(C, dtype=jnp.int32)[:, None])


def oracle_edge_weights(dist2, knn_idx, k, valid):
    """Eq. 6 weights with every rank read from the full :func:`rank_matrix`."""
    R = rank_matrix(dist2)
    r_ji = R[jnp.arange(dist2.shape[0])[:, None], knn_idx]
    w = jnp.exp(1.0 / jnp.maximum(r_ji.astype(jnp.float32), 1.0)) / normalizer(k)
    w = jnp.where((r_ji >= 1) & (r_ji <= k), w, 0.0)
    return jnp.where(valid[:, None] & valid[knn_idx], w, 0.0)


def test_rank_matrix_definition():
    x = jnp.asarray([[0.0], [1.0], [3.0], [3.5]])
    d2 = jnp.square(x - x.T)
    # every row lists every tail (k = C), so w[i, j] is e^{1/R[i, j]}/Z, or 0 at rank 0
    k = 4
    w = np.asarray(edge_weights(d2, jnp.tile(jnp.arange(k), (k, 1)), k, jnp.ones((k,), bool)))
    R = np.where(w > 0, np.rint(1.0 / np.log(np.where(w > 0, w, 1.0) * normalizer(k))), 0)
    np.testing.assert_array_equal(R, np.asarray(rank_matrix(d2)))
    # rank of i w.r.t. column j; diagonal is 0 (j itself)
    assert (np.diag(R) == 0).all()
    # w.r.t. point 0 (x=0): order is [0, 1, 3, 3.5] → ranks 0,1,2,3
    np.testing.assert_array_equal(R[:, 0], [0, 1, 2, 3])
    # w.r.t. point 2 (x=3): nearest is 3.5 (rank1), then 1 (rank2), then 0
    np.testing.assert_array_equal(R[:, 2], [3, 2, 0, 1])


@given(st.integers(0, 2**31 - 1), st.integers(2, 8), st.integers(8, 24))
@settings(max_examples=20, deadline=None)
def test_edge_weights_properties(seed, k, c):
    if k >= c:
        k = c - 1
    x = jax.random.normal(jax.random.key(seed), (c, 3))
    d2 = jnp.sum(jnp.square(x[:, None] - x[None, :]), -1)
    big = jnp.eye(c) * 1e30
    _, knn = jax.lax.top_k(-(d2 + big), k)
    valid = jnp.ones((c,), bool)
    w = np.asarray(edge_weights(d2, knn, k, valid))
    assert (w >= 0).all()
    assert (w <= np.exp(1.0) / normalizer(k) + 1e-6).all()
    # weight 0 ⟺ the tail ranks the head beyond k
    R = np.asarray(rank_matrix(d2))
    r_ji = np.take_along_axis(R, np.asarray(knn), axis=1)
    assert ((w > 0) == ((r_ji >= 1) & (r_ji <= k))).all()
    np.testing.assert_array_equal(w, np.asarray(oracle_edge_weights(d2, knn, k, valid)))


def _cell_knn_inputs(x, k, real):
    """(dist2, knn_idx, valid) as ``index/knn.py:_cluster_knn_jit`` hands
    them to ``edge_weights``: padding at BIG, self left out of the search."""
    C = x.shape[0]
    big = jnp.float32(1e30)
    d2 = jnp.sum(jnp.square(x[:, None] - x[None, :]), -1)
    valid = jnp.arange(C) < real
    pad = ~(valid[:, None] & valid[None, :])
    _, knn = jax.lax.top_k(-(d2 + pad * big + jnp.eye(C) * big), k)
    return d2 + pad * big, knn, valid


@pytest.mark.parametrize(
    "case", ["duplicate_rows", "integer_coords", "padded", "c_is_k_plus_1", "c_is_k", "vmap"]
)
def test_edge_weights_equal_rank_matrix_oracle(case):
    """The k+1-nearest lookup gives the full rank matrix's weights bit for
    bit, ties (lowest index first) included."""
    rng = np.random.default_rng(17)
    C, k, real = 48, 7, 48
    x = rng.normal(0, 1, (C, 3))
    if case == "duplicate_rows":  # pairs at distance 0
        x[C // 2 :] = x[: C // 2]
    elif case == "integer_coords":  # many equal distances
        x = np.rint(2 * x)
    elif case == "padded":
        real = 31
    elif case == "c_is_k_plus_1":
        C, x = k + 1, x[: k + 1]
    elif case == "c_is_k":
        C, x = k, x[:k]
    if case == "vmap":  # as _knn_over_clusters maps the cells
        xs = jnp.asarray(rng.normal(0, 1, (3, C, 3)).round(1), jnp.float32)
        args = jax.vmap(lambda xb, r: _cell_knn_inputs(xb, k, r))(xs, jnp.asarray([C, 29, 40]))
        got = jax.vmap(lambda d, n, v: edge_weights(d, n, k, v))(*args)
        want = jax.vmap(lambda d, n, v: oracle_edge_weights(d, n, k, v))(*args)
    else:
        kk = min(k, C)  # a cell of k slots searches k of them (self at BIG)
        d2, knn, valid = _cell_knn_inputs(jnp.asarray(x, jnp.float32), kk, real)
        got = edge_weights(d2, knn, kk, valid)
        want = oracle_edge_weights(d2, knn, kk, valid)
    got, want = np.asarray(got), np.asarray(want)
    assert (want > 0).any()
    np.testing.assert_array_equal(got, want)


def test_normalizer_matches_eq6():
    # Z = Σ_{j=0}^{k} e^{1/(j+1)}, k+1 terms
    k = 15
    want = sum(np.exp(1.0 / (j + 1)) for j in range(k + 1))
    assert abs(normalizer(k) - want) < 1e-9


# ---------------------------------------------------------------------------
# Reduction property: R̃ = ∅ ⇒ Eq. 3 ≡ Eq. 2
# ---------------------------------------------------------------------------


@given(st.integers(0, 2**31 - 1))
@settings(max_examples=15, deadline=None)
def test_reduction_to_infonc(seed):
    key = jax.random.key(seed)
    ks = jax.random.split(key, 4)
    B, k, M, d = 6, 4, 8, 2
    ti = jax.random.normal(ks[0], (B, d))
    tp = jax.random.normal(ks[1], (B, k, d))
    pw = jax.random.uniform(ks[2], (B, k))
    tn = jax.random.normal(ks[3], (B, M, d))
    # NOMAD machinery with zero mean-mass and unit-weight exact samples
    l_nomad_form = losses.contrastive_loss(
        ti, tp, pw, jnp.zeros((B,)), tn, jnp.ones((B, M))
    )
    l_infonc = losses.infonc_tsne_loss(ti, tp, pw, tn)
    np.testing.assert_allclose(float(l_nomad_form), float(l_infonc), rtol=1e-6)


# ---------------------------------------------------------------------------
# Theorem 1
# ---------------------------------------------------------------------------


def _exact_losses(theta, clusters, heads, tp, pw, n_noise=1):
    """Exact-expectation InfoNC (|M|=1, uniform ξ over all points) vs the
    NOMAD mean-approximated loss on the same configuration."""
    N = theta.shape[0]
    K = int(clusters.max()) + 1
    q_pos = cauchy(theta[heads][:, None, :], tp)  # (B, k)
    q_all = cauchy_pairwise(theta[heads], theta)  # (B, N)
    # Eq. 2, |M| = 1, expectation exact: E_m[log(q_pos + q(im))]
    inner = jnp.log(q_pos[:, :, None] + q_all[:, None, :])  # (B, k, N)
    l2 = -jnp.mean(jnp.sum(pw[:, :, None] * (jnp.log(q_pos)[:, :, None] - inner) / N, axis=(1, 2)))
    # Eq. 3: all cells approximated by their means (R̃ = R)
    means = jnp.stack([theta[clusters == r].mean(0) for r in range(K)])
    p_r = jnp.asarray([(clusters == r).mean() for r in range(K)])
    q_mu = cauchy(theta[heads][:, None, :], means[None, :, :])  # (B, K)
    m_tilde = jnp.sum(p_r[None, :] * q_mu, axis=1)  # |M| = 1
    l3 = -jnp.mean(jnp.sum(pw * (jnp.log(q_pos) - jnp.log(q_pos + m_tilde[:, None])), axis=1))
    return float(l2), float(l3)


def _mk_config(seed, spread):
    rng = np.random.default_rng(seed)
    K, per, d = 4, 12, 2
    centers = rng.normal(0, 5, (K, d))
    pts = (centers[:, None, :] + rng.normal(0, spread, (K, per, d))).reshape(-1, d)
    clusters = np.repeat(np.arange(K), per)
    theta = jnp.asarray(pts, jnp.float32)
    heads = jnp.asarray(rng.integers(0, K * per, 8))
    nbrs = jnp.asarray(rng.integers(0, K * per, (8, 3)))
    tp = theta[nbrs]
    pw = jnp.asarray(rng.uniform(0.1, 1.0, (8, 3)), jnp.float32)
    return theta, jnp.asarray(clusters), heads, tp, pw


@pytest.mark.parametrize("seed", [0, 1, 2, 3, 4])
def test_theorem1_upper_bound_tight_clusters(seed):
    """Tight clusters ⇒ Taylor remainder →0 ⇒ the bound must hold cleanly."""
    theta, clusters, heads, tp, pw = _mk_config(seed, spread=1e-3)
    l2, l3 = _exact_losses(theta, clusters, heads, tp, pw)
    assert l3 >= l2 - 1e-5, (l2, l3)


@given(st.integers(0, 2**31 - 1), st.floats(0.01, 0.8))
@settings(max_examples=25, deadline=None)
def test_theorem1_approx_upper_bound(seed, spread):
    """Moderate spread: '≳' with the second-order Taylor slack (paper §7:
    the approximation is accurate to second order; slack scales with the
    within-cell variance)."""
    theta, clusters, heads, tp, pw = _mk_config(seed, spread)
    l2, l3 = _exact_losses(theta, clusters, heads, tp, pw)
    slack = 0.5 * spread**2 + 1e-5
    assert l3 >= l2 - slack, (l2, l3, spread)


def test_jensen_step_exact():
    """The Jensen inequality step of the proof, exactly (|M| = 1):
    E_m[log(q + q(im))] ≤ log(q + E_m[q(im)])."""
    rng = np.random.default_rng(0)
    for _ in range(20):
        theta = jnp.asarray(rng.normal(0, 3, (50, 2)), jnp.float32)
        i = int(rng.integers(0, 50))
        q = float(rng.uniform(0.01, 1.0))
        q_im = np.asarray(cauchy_pairwise(theta[i : i + 1], theta))[0]
        lhs = np.mean(np.log(q + q_im))
        rhs = np.log(q + np.mean(q_im))
        assert lhs <= rhs + 1e-7


def test_nomad_loss_gradient_structure():
    """Means are stop-gradded: ∂L/∂θ must not flow into the mean positions
    (the paper's design — means refresh only via the epoch all-gather)."""
    B, k, S, K, d = 4, 3, 5, 6, 2
    ks = jax.random.split(jax.random.key(0), 6)
    ti = jax.random.normal(ks[0], (B, d))
    tp = jax.random.normal(ks[1], (B, k, d))
    pw = jax.random.uniform(ks[2], (B, k))
    tn = jax.random.normal(ks[3], (B, S, d))
    means = jax.random.normal(ks[4], (K, d))
    counts = jnp.full((K,), 10.0)
    cells = jax.random.randint(ks[5], (B,), 0, K)

    def f(means):
        return losses.nomad_loss(ti, tp, pw, means, counts, cells, tn, 8, 60)

    g = jax.grad(f)(means)
    np.testing.assert_allclose(np.asarray(g), 0.0, atol=1e-9)

    # …but gradients DO flow to heads, positives and exact negatives
    g_i = jax.grad(
        lambda t: losses.nomad_loss(t, tp, pw, means, counts, cells, tn, 8, 60)
    )(ti)
    assert float(jnp.max(jnp.abs(g_i))) > 0
