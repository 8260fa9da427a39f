"""Plain reference of one sharded NOMAD fit dispatch (paper Fig. 2).

Straight ``jax.numpy``, float32 at full matmul precision, no kernels and
nothing imported from the program. The K cells are split contiguously
over n shards: shard s owns cells [s·K/n, (s+1)·K/n) and their K/n · C
rows of θ and of the kNN graph. One dispatch:

* the K cell means are computed from θ at the dispatch's start, over all
  shards, and held fixed;
* each shard runs ``steps`` SGD steps on its own rows alone. Its key is
  ``fold_in(key, s)`` when there is more than one shard, and step t draws
  from ``fold_in(key_s, t)`` split into (heads, negatives) with the same
  ``jax.random`` calls as ``ref_fit.py``: heads uniform over the shard's
  own valid rows, through its own cumulative counts; positives the
  head's kNN row (global row ids, less the shard's first row) and its
  Eq. 6 weights; S exact negatives uniform over the head's cell;
* loss (Eq. 3) as in ``ref_fit.py``, the mean term over all K means with
  the head's own cell left out by its global id s·K/n + c;
  cell_w = |M|·|r|/N over the global counts, neg_w = |M|·|c|/(N·S);
* sparse SGD: the gradient of every gathered row is scatter-added back,
  heads, then positives, then negatives;
* the dispatch's loss is the mean over shards of each shard's mean loss.

Given the means, the shards are independent within a dispatch, so each
is followed on the device that holds its rows, all at once.

``dtype`` runs the whole reference in another precision (the control);
``heads_kept`` takes the loss over the first heads only, and
``own_means_only`` the mean term over the shard's own cells only, as if
the means' all-gather were left out (planted faults).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


def _blocks(a: jax.Array, n: int) -> list:
    """The ``n`` row blocks of ``a``, each as the single-device array of
    the device that holds it; ``a`` must be split by rows over n devices."""
    rows = a.shape[0] // n
    shards = sorted(a.addressable_shards, key=lambda s: s.index[0].start or 0)
    spans = [(s.index[0].start or 0, s.index[0].stop or a.shape[0]) for s in shards]
    if spans != [(i * rows, (i + 1) * rows) for i in range(n)]:
        raise ValueError(f"expected {n} row blocks of {rows} rows, found {spans}")
    return [s.data for s in shards]


@functools.partial(jax.jit, static_argnames=("capacity", "dtype"))
def _means(theta, counts, *, capacity, dtype):
    with jax.default_matmul_precision("highest"):
        th = theta.astype(dtype).reshape(counts.shape[0], capacity, -1)
        valid = jnp.arange(capacity)[None, :] < counts[:, None]
        sums = jnp.sum(jnp.where(valid[:, :, None], th, 0), axis=1)
        return sums / jnp.maximum(counts, 1).astype(dtype)[:, None]


def _step(theta, knn_idx, knn_w, counts, cum, means, cell_w, own_off, row_off, lr,
          key, *, n, capacity, batch, n_neg, n_noise, heads_kept, dtype):
    C = capacity
    k_head, k_neg = jax.random.split(key)
    u = jax.random.randint(k_head, (batch,), 0, cum[-1])
    cell = jnp.searchsorted(cum, u, side="right").astype(jnp.int32)
    start = jnp.where(cell > 0, cum[cell - 1], 0)
    rows = cell * C + (u - start)
    pos_rows = knn_idx[rows] - row_off
    pos_w = knn_w[rows].astype(dtype)
    c = counts[cell]
    v = jax.random.uniform(k_neg, (batch, n_neg))
    slot = jnp.floor(v * c[:, None]).astype(jnp.int32)
    slot = jnp.minimum(slot, (c - 1)[:, None].astype(jnp.int32))
    neg_rows = cell[:, None] * C + slot

    not_own = jnp.arange(cell_w.shape[0])[None, :] != (cell + own_off)[:, None]
    p_own = (counts.astype(jnp.float32) / float(n)).astype(dtype)[cell]
    neg_w = jnp.asarray(n_noise, dtype) * p_own / jnp.asarray(n_neg, dtype)
    one = jnp.asarray(1, dtype)

    def loss_fn(th_i, th_pos, th_neg):
        q_mean = one / (one + jnp.sum(jnp.square(th_i[:, None, :] - means[None]), -1))
        m_tilde = jnp.sum(jnp.where(not_own, cell_w[None, :] * q_mean, 0), -1)
        q_neg = one / (one + jnp.sum(jnp.square(th_i[:, None, :] - th_neg), -1))
        m_exact = neg_w * jnp.sum(q_neg, -1)
        q_pos = one / (one + jnp.sum(jnp.square(th_i[:, None, :] - th_pos), -1))
        denom = q_pos + (m_tilde + m_exact)[:, None]
        per_head = -jnp.sum(pos_w * (jnp.log(q_pos) - jnp.log(denom)), -1)
        return jnp.mean(per_head[:heads_kept].astype(jnp.float32))

    loss, (g_i, g_pos, g_neg) = jax.value_and_grad(loss_fn, argnums=(0, 1, 2))(
        theta[rows], theta[pos_rows], theta[neg_rows]
    )
    d = theta.shape[1]
    lr = lr.astype(dtype)
    theta = theta.at[rows].add(-lr * g_i)
    theta = theta.at[pos_rows.reshape(-1)].add(-lr * g_pos.reshape(-1, d))
    theta = theta.at[neg_rows.reshape(-1)].add(-lr * g_neg.reshape(-1, d))
    return theta, loss


@functools.partial(
    jax.jit,
    static_argnames=("n", "capacity", "batch", "n_neg", "n_noise", "steps",
                     "n_shards", "heads_kept", "dtype", "own_means_only"),
)
def _shard_dispatch(theta, knn_idx, knn_w, counts, means, counts_all, lr0, lr1,
                    epoch_key, shard, *, n, capacity, batch, n_neg, n_noise, steps,
                    n_shards, heads_kept, dtype, own_means_only):
    """Shard ``shard``'s ``steps`` steps on its own rows."""
    Kl = counts.shape[0]
    with jax.default_matmul_precision("highest"):
        theta = theta.astype(dtype)
        cum = jnp.cumsum(counts).astype(jnp.int32)
        p_cell = (counts_all.astype(jnp.float32) / float(n)).astype(dtype)
        cell_w = jnp.asarray(n_noise, dtype) * p_cell
        if own_means_only:
            cell_w = jnp.where(jnp.arange(cell_w.shape[0]) // Kl == shard, cell_w, 0)
        key = jax.random.fold_in(epoch_key, shard) if n_shards > 1 else epoch_key
        step = functools.partial(
            _step, n=n, capacity=capacity, batch=batch, n_neg=n_neg,
            n_noise=n_noise, heads_kept=heads_kept or batch, dtype=dtype,
        )

        def body(theta, t):
            lr = lr0 + (lr1 - lr0) * (t / steps)
            return step(theta, knn_idx, knn_w, counts, cum, means, cell_w,
                        shard * Kl, shard * Kl * capacity, lr,
                        jax.random.fold_in(key, t))

        theta, losses = jax.lax.scan(body, theta, jnp.arange(steps))
    return theta, jnp.mean(losses)


def dispatch(theta, knn_idx, knn_w, counts, lr0, lr1, epoch_key, *, n, capacity,
             batch, n_neg, n_noise, steps, n_shards, heads_kept=None,
             dtype=jnp.float32, own_means_only=False):
    """One dispatch: ``(theta, graph, lr0, lr1, key) -> (theta, mean loss)``.

    ``theta``, ``knn_idx`` and ``knn_w`` are split by rows over
    ``n_shards`` devices, shard s on the s-th; ``counts`` holds all K cell
    sizes. ``steps`` is each shard's step count. Returns θ split as it
    came, and the loss as a float."""
    counts = np.asarray(counts)
    Kl = counts.shape[0] // n_shards
    th, ki, kw = (_blocks(a, n_shards) for a in (theta, knn_idx, knn_w))
    devs = [b.device for b in th]
    cnt = [jax.device_put(counts[s * Kl:(s + 1) * Kl], devs[s]) for s in range(n_shards)]
    means = np.concatenate([
        np.asarray(_means(th[s], cnt[s], capacity=capacity, dtype=dtype))
        for s in range(n_shards)
    ])
    out = [
        _shard_dispatch(
            th[s], ki[s], kw[s], cnt[s], jax.device_put(means, devs[s]),
            jax.device_put(counts, devs[s]), lr0, lr1, jax.device_put(epoch_key, devs[s]),
            s, n=n, capacity=capacity, batch=batch, n_neg=n_neg, n_noise=n_noise,
            steps=steps, n_shards=n_shards, heads_kept=heads_kept, dtype=dtype,
            own_means_only=own_means_only,
        )
        for s in range(n_shards)
    ]
    theta = jax.make_array_from_single_device_arrays(
        theta.shape, theta.sharding, [t for t, _ in out]
    )
    loss = float(np.mean(np.asarray([l for _, l in out], np.float32)))
    return theta, loss
