"""The fit step's θ update: one row-sorted update of all its rows.

``core/nomad.py:sgd_update`` applies a step's updates of the heads, their
positives and their negatives as one ``"row_add"`` registry kernel: on a
TPU the pairs sorted by row and added in one sweep over θ, on the CPU one
scatter-add. These tests compare both implementations (the Pallas kernel
in interpret mode) with the three scatter-adds they replace, on planted
repeats and on the steps of both methods and of ``partial_fit``:

* every moved row is within a few float32 ulps of the three-scatter form
  (only the order in which a row's repeats are summed may differ);
* the set of moved rows is exactly the same;
* rows no pair names, the padded slots among them, are bit-identical.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import NomadConfig
from repro.core import nomad
from repro.kernels.row_add.ops import row_add

ULPS = 4
IMPLS = ["jnp", "pallas"]


def three_scatters(theta, loss_fn, rows, pos_rows, neg_rows, th_i, th_pos, th_neg, lr, impl=None):
    """The update as three serial scatter-adds of possibly repeated rows."""
    loss, (g_i, g_pos, g_neg) = jax.value_and_grad(loss_fn, argnums=(0, 1, 2))(
        th_i, th_pos, th_neg
    )
    d = theta.shape[1]
    theta = theta.at[rows].add(-lr * g_i)
    theta = theta.at[pos_rows.reshape(-1)].add(-lr * g_pos.reshape(-1, d))
    theta = theta.at[neg_rows.reshape(-1)].add(-lr * g_neg.reshape(-1, d))
    return theta, loss


def ulp_bound(theta0, rows, updates):
    """Per row, ULPS float32 ulps of |θ0| plus the |updates| it receives:
    the scale of any order's rounding of that row's sum."""
    scale = np.abs(np.asarray(theta0, np.float64))
    np.add.at(scale, np.asarray(rows), np.abs(np.asarray(updates, np.float64)))
    return ULPS * np.finfo(np.float32).eps * scale


def assert_same_update(new, ref, theta0, rows, updates):
    new, ref, theta0 = (np.asarray(a) for a in (new, ref, theta0))
    moved_new = np.any(new != theta0, axis=1)
    moved_ref = np.any(ref != theta0, axis=1)
    np.testing.assert_array_equal(moved_new, moved_ref)
    untouched = np.ones(theta0.shape[0], bool)
    untouched[np.asarray(rows)] = False
    np.testing.assert_array_equal(new[untouched], theta0[untouched])
    assert np.all(np.abs(new.astype(np.float64) - ref) <= ulp_bound(theta0, rows, updates))


def planted(case: str, n_rows: int, B: int, k: int, S: int, rng):
    """Heads (B,), positives (B, k) and negatives (B, S) with planted repeats."""
    perm = rng.permutation(n_rows)
    rows = perm[:B]
    pos = perm[B : B + B * k].reshape(B, k)
    neg = perm[B + B * k : B + B * k + B * S].reshape(B, S)
    if case == "head_is_negative_of_another_head":
        neg[1, 0] = rows[0]
        neg[2, 3] = rows[0]
    elif case == "repeated_negatives_of_one_head":
        neg[0, :] = neg[0, 0]
        neg[3, 1::2] = neg[3, 0]
    elif case == "heads_share_a_positive":
        pos[1, 2] = pos[0, 4]
        pos[5, 0] = pos[0, 4]
    elif case == "everything_on_one_row":
        rows[:] = rows[0]
        pos[:] = rows[0]
        neg[:] = rows[0]
    elif case != "distinct_rows":
        raise ValueError(case)
    return (jnp.asarray(a, jnp.int32) for a in (rows, pos, neg))


@pytest.mark.parametrize(
    "case",
    [
        "head_is_negative_of_another_head",
        "repeated_negatives_of_one_head",
        "heads_share_a_positive",
        "everything_on_one_row",
        "distinct_rows",
    ],
)
@pytest.mark.parametrize("method,n_neg", [("nomad", 4), ("infonc", 16)])
@pytest.mark.parametrize("impl", IMPLS)
def test_sgd_update_matches_three_scatters_on_planted_repeats(case, method, n_neg, impl):
    B, k, d, n_rows, lr = 8, 5, 2, 400, 0.37
    rng = np.random.default_rng(0)
    rows, pos, neg = planted(case, n_rows, B, k, n_neg, rng)
    theta0 = jnp.asarray(rng.normal(size=(n_rows, d)), jnp.float32)
    grads = [jnp.asarray(rng.normal(size=s), jnp.float32) for s in ((B, d), (B, k, d), (B, n_neg, d))]

    def loss_fn(ti, tp, tn):  # gradients are exactly ``grads``
        return sum(jnp.sum(t * g) for t, g in zip((ti, tp, tn), grads))

    args = (rows, pos, neg, theta0[rows], theta0[pos], theta0[neg], lr, impl)
    new, loss_new = jax.jit(nomad.sgd_update, static_argnums=(1, 9))(theta0, loss_fn, *args)
    ref, loss_ref = jax.jit(three_scatters, static_argnums=(1, 9))(theta0, loss_fn, *args)
    np.testing.assert_allclose(loss_new, loss_ref, rtol=1e-6)
    all_rows = np.concatenate([np.asarray(r).reshape(-1) for r in (rows, pos, neg)])
    updates = np.concatenate([-lr * np.asarray(g).reshape(-1, d) for g in grads])
    assert_same_update(new, ref, theta0, all_rows, updates)


@pytest.mark.parametrize(
    "n_rows,n,d,block,unroll",
    [
        (1, 6, 2, 128, 4),  # every pair on one row
        (50, 3000, 2, 128, 3),  # three windows of entries in one block
        (1000, 64, 3, 256, 4),  # a block past the table's end
        (5000, 3000, 2, 256, 1),  # windows that cross blocks
        (5000, 3000, 2, 256, 8),
        (4096, 2000, 2, 1024, 4),
    ],
)
def test_row_add_kernel_matches_float64_sum(n_rows, n, d, block, unroll):
    """Against a float64 sum: every pair lands, on the first and last rows
    of θ too, across blocks and windows; no other row changes a bit."""
    rng = np.random.default_rng(n)
    rows = rng.integers(0, n_rows, n)
    rows[:2] = [0, n_rows - 1]
    theta0 = rng.normal(size=(n_rows, d)).astype(np.float32)
    untouched = np.setdiff1d(np.arange(n_rows), rows)
    theta0[untouched[:1]] = -0.0  # a −0.0 row beside touched ones keeps its sign
    updates = rng.normal(size=(n, d)).astype(np.float32)
    got = np.asarray(row_add(
        jnp.asarray(theta0), jnp.asarray(rows, jnp.int32), jnp.asarray(updates),
        block=block, unroll=unroll, interpret=True,
    ))
    want = theta0.astype(np.float64)
    np.add.at(want, rows, updates.astype(np.float64))
    assert np.all(np.abs(got - want) <= ulp_bound(theta0, rows, updates))
    np.testing.assert_array_equal(got[untouched].view(np.int32), theta0[untouched].view(np.int32))
    np.testing.assert_array_equal(got, np.asarray(jnp.asarray(theta0).at[rows].add(updates)))


K, N_PER_CELL = 4, 30


def small_cfg(**kw) -> NomadConfig:
    base = dict(
        n_points=K * N_PER_CELL, dim=8, n_clusters=K, n_neighbors=5, n_noise=16,
        n_exact_negatives=4, batch_size=64, n_epochs=2, steps_per_epoch=2,
    )
    return NomadConfig(**{**base, **kw})


def step_state(cfg: NomadConfig, seed: int):
    """θ with padded slots left at 0, an in-cell kNN graph, the means."""
    C = cfg.cluster_capacity
    rng = np.random.default_rng(seed)
    counts = np.array([N_PER_CELL - 3, N_PER_CELL, N_PER_CELL + 2, N_PER_CELL + 1], np.int32)
    slot = np.arange(K * C) % C
    cell = np.arange(K * C) // C
    valid = slot < counts[cell]
    theta = np.where(valid[:, None], rng.normal(size=(K * C, cfg.out_dim)), 0.0)
    nbr = rng.integers(0, 1 << 20, size=(K * C, cfg.n_neighbors)) % counts[cell][:, None]
    idx = {
        "knn_idx": jnp.asarray(cell[:, None] * C + nbr, jnp.int32),
        "knn_w": jnp.asarray(rng.uniform(size=(K * C, cfg.n_neighbors)), jnp.float32),
        "counts": jnp.asarray(counts),
        "cum_counts": jnp.asarray(np.cumsum(counts), jnp.int32),
    }
    theta = jnp.asarray(theta, jnp.float32)
    means = nomad.local_means(theta, idx["counts"], C)
    return theta, idx, means, valid


def run_step(monkeypatch, make, cfg, theta, idx, means, update):
    """One step of ``make(cfg)`` with ``update`` as the θ update, plus every
    (row, update) pair the step produced."""
    pairs = []

    def recording(theta, loss_fn, rows, pos, neg, th_i, th_pos, th_neg, lr, impl):
        _, grads = jax.value_and_grad(loss_fn, argnums=(0, 1, 2))(th_i, th_pos, th_neg)
        d = theta.shape[1]
        pairs.append((
            jnp.concatenate([rows, pos.reshape(-1), neg.reshape(-1)]),
            jnp.concatenate([-lr * g.reshape(-1, d) for g in grads]),
        ))
        return update(theta, loss_fn, rows, pos, neg, th_i, th_pos, th_neg, lr, impl)

    monkeypatch.setattr(nomad, "sgd_update", recording)
    counts_f = idx["counts"].astype(jnp.float32)
    theta, _ = make(cfg)(theta, idx, (means, counts_f, 0), 0.8, jax.random.key(3))
    return theta, pairs[0]


@pytest.mark.parametrize("method", ["nomad", "infonc"])
@pytest.mark.parametrize("seed", [0, 1])
@pytest.mark.parametrize("impl", IMPLS)
def test_step_matches_three_scatters(monkeypatch, method, seed, impl):
    """Both methods' steps: small cells, so heads, positives and negatives
    repeat; padded slots are never touched."""
    monkeypatch.setenv("REPRO_KERNEL_ROW_ADD", impl)
    cfg = small_cfg()
    theta0, idx, means, valid = step_state(cfg, seed)
    make = lambda c: nomad.make_step_fn(c, method=method)  # noqa: E731
    new, (rows, updates) = run_step(monkeypatch, make, cfg, theta0, idx, means, nomad.sgd_update)
    ref, _ = run_step(monkeypatch, make, cfg, theta0, idx, means, three_scatters)
    assert len(np.unique(np.asarray(rows))) < rows.shape[0]  # repeats were there
    assert_same_update(new, ref, theta0, rows, updates)
    np.testing.assert_array_equal(np.asarray(new)[~valid], 0.0)


@pytest.mark.parametrize("method", ["nomad", "infonc"])
@pytest.mark.parametrize("impl", IMPLS)
def test_partial_step_leaves_unaffected_cells_bit_identical(monkeypatch, method, impl):
    monkeypatch.setenv("REPRO_KERNEL_ROW_ADD", impl)
    cfg = small_cfg()
    C = cfg.cluster_capacity
    theta0, idx, means, valid = step_state(cfg, 2)
    aff = np.array([1, 3], np.int32)
    counts = np.asarray(idx["counts"])
    idx = {**idx, "aff_cells": jnp.asarray(aff), "aff_cum_counts": jnp.asarray(np.cumsum(counts[aff]), jnp.int32)}
    make = lambda c: nomad.make_step_fn(c, method=method, heads=nomad.affected_heads(c))  # noqa: E731
    new, (rows, updates) = run_step(monkeypatch, make, cfg, theta0, idx, means, nomad.sgd_update)
    ref, _ = run_step(monkeypatch, make, cfg, theta0, idx, means, three_scatters)
    assert_same_update(new, ref, theta0, rows, updates)
    np.testing.assert_array_equal(np.asarray(new)[~valid], 0.0)
    if method == "nomad":  # in-cell negatives: only affected cells move
        outside = ~np.isin(np.arange(K * C) // C, aff)
        np.testing.assert_array_equal(np.asarray(new)[outside], np.asarray(theta0)[outside])
