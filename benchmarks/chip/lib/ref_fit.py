"""Plain reference of one NOMAD fit dispatch (paper §3.3-3.4, Fig. 2).

Straight ``jax.numpy``, float32 at full matmul precision, no kernels and
nothing imported from the program. One dispatch is an epoch of ``steps``
SGD steps with the cell means computed once at its start:

* heads i uniform over the N valid points, through the cumulative cell
  counts; positives are the head's kNN row and its Eq. 6 weights;
* S exact negatives uniform over the head's own cell;
* loss (Eq. 3): -mean_b sum_j w_bj [log q_bj - log(q_bj + M~_b + M_b)],
  q = 1/(1 + |theta_b - y|^2),
  M~_b = |M| sum_{r != c(b)} (|r|/N) q(theta_b, mu_r)  (means held fixed),
  M_b  = |M| (|c(b)|/N) mean_s q(theta_b, theta_neg_bs);
* sparse SGD: the gradient of every gathered row is scatter-added back,
  heads, then positives, then negatives;
* the learning rate runs linearly from lr0 to lr1 over the dispatch, and
  step t draws from ``fold_in(epoch_key, t)`` split into (heads, negatives)
  with the same ``jax.random`` calls the method defines, so the reference
  and the program sample the same rows.

``dtype`` runs the whole reference in another precision (the control);
``heads_kept`` takes the loss over the first heads only (a planted fault).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


def _step(theta, knn_idx, knn_w, counts, cum, means, lr, key, *, n, capacity,
          batch, n_neg, n_noise, heads_kept, dtype):
    C = capacity
    k_head, k_neg = jax.random.split(key)
    u = jax.random.randint(k_head, (batch,), 0, cum[-1])
    cell = jnp.searchsorted(cum, u, side="right").astype(jnp.int32)
    start = jnp.where(cell > 0, cum[cell - 1], 0)
    rows = cell * C + (u - start)
    pos_rows = knn_idx[rows]
    pos_w = knn_w[rows].astype(dtype)
    c = counts[cell]
    v = jax.random.uniform(k_neg, (batch, n_neg))
    slot = jnp.floor(v * c[:, None]).astype(jnp.int32)
    slot = jnp.minimum(slot, (c - 1)[:, None].astype(jnp.int32))
    neg_rows = cell[:, None] * C + slot

    p_cell = (counts.astype(jnp.float32) / float(n)).astype(dtype)
    cell_w = jnp.asarray(n_noise, dtype) * p_cell
    not_own = jnp.arange(counts.shape[0])[None, :] != cell[:, None]
    neg_w = (jnp.asarray(n_noise, dtype) * p_cell[cell] / jnp.asarray(n_neg, dtype))
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
                     "heads_kept", "dtype"),
)
def dispatch(theta, knn_idx, knn_w, counts, lr0, lr1, epoch_key, *, n, capacity,
             batch, n_neg, n_noise, steps, heads_kept=None, dtype=jnp.float32):
    """One dispatch: ``(theta, graph, lr0, lr1, key) -> (theta, mean loss)``."""
    K = counts.shape[0]
    with jax.default_matmul_precision("highest"):
        theta = theta.astype(dtype)
        th = theta.reshape(K, capacity, -1)
        valid = jnp.arange(capacity)[None, :] < counts[:, None]
        sums = jnp.sum(jnp.where(valid[:, :, None], th, 0), axis=1)
        means = sums / jnp.maximum(counts, 1).astype(dtype)[:, None]
        cum = jnp.cumsum(counts).astype(jnp.int32)
        step = functools.partial(
            _step, n=n, capacity=capacity, batch=batch, n_neg=n_neg,
            n_noise=n_noise, heads_kept=heads_kept or batch, dtype=dtype,
        )

        def body(theta, t):
            lr = lr0 + (lr1 - lr0) * (t / steps)
            return step(theta, knn_idx, knn_w, counts, cum, means, lr,
                        jax.random.fold_in(epoch_key, t))

        theta, losses = jax.lax.scan(body, theta, jnp.arange(steps))
    return theta, jnp.mean(losses)
