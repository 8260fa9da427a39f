"""Seeded generators that make a cell's inputs on the device.

Every generator is one jitted call from the seed, so set-up moves no
array from the host and the same seed gives the same inputs bit for bit.
Seeds may exceed 32 bits: :func:`seed_key` folds the high bits into the
key instead of dropping them.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


def seed_key(seed: int) -> jax.Array:
    """A PRNG key for any non-negative whole number below 2**63."""
    if seed < 0:
        raise ValueError(f"seed must be >= 0, got {seed}")
    return jax.random.fold_in(
        jax.random.key(seed & 0x7FFFFFFF), (seed >> 31) & 0xFFFFFFFF
    )


@functools.partial(
    jax.jit,
    static_argnames=("n", "n_cells", "capacity", "k", "out_dim", "spread"),
)
def fit_inputs(key, init_scale, *, n, n_cells, capacity, k, out_dim, spread):
    """The state a fit dispatch runs on, laid out as the index build lays it.

    Returns ``(theta0, knn_idx, knn_w, counts)``:

    * ``counts`` (K,) int32: cell sizes around n/K, each perturbed by at
      most ``spread``·n/K, summing to n exactly and capped at ``capacity``;
    * ``knn_idx`` (K·C, k) int32: for every valid slot, k neighbours drawn
      from the other valid slots of its own cell (the build's in-cell
      graph); a padded slot points at itself;
    * ``knn_w`` (K·C, k) float32: inverse-rank weights exp(1/r)/Z for
      ranks r drawn from 1..k (the range of the build's edge weights);
      0 on padded slots;
    * ``theta0`` (K·C, out_dim) float32: N(0, init_scale) on valid slots,
      0 on padded ones.
    """
    k_cnt, k_theta, k_nbr, k_rank = jax.random.split(key, 4)
    base, extra = divmod(n, n_cells)
    half = int(spread * base / 2)
    e = jax.random.randint(k_cnt, (n_cells,), -half, half + 1)
    counts = (
        base
        + (jnp.arange(n_cells) < extra).astype(jnp.int32)
        + e
        - jnp.roll(e, 1)  # zero-sum perturbation: the total stays n
    )
    counts = jnp.minimum(counts, capacity)

    rows = jnp.arange(n_cells * capacity, dtype=jnp.int32)
    cell = rows // capacity
    slot = rows - cell * capacity
    cnt = counts[cell]
    valid = slot < cnt

    theta0 = jax.random.normal(k_theta, (rows.shape[0], out_dim), jnp.float32)
    theta0 = jnp.where(valid[:, None], theta0 * init_scale, 0.0)

    span = jnp.maximum(cnt - 1, 1)[:, None]
    offset = jax.random.randint(k_nbr, (rows.shape[0], k), 0, jnp.iinfo(jnp.int32).max)
    nslot = (slot[:, None] + 1 + offset % span) % jnp.maximum(cnt, 1)[:, None]
    knn_idx = jnp.where(valid[:, None], cell[:, None] * capacity + nslot, rows[:, None])

    z = float(np.exp(1.0 / np.arange(1, k + 2)).sum())
    rank = jax.random.randint(k_rank, (rows.shape[0], k), 1, k + 1)
    knn_w = jnp.where(valid[:, None], jnp.exp(1.0 / rank.astype(jnp.float32)) / z, 0.0)
    return theta0, knn_idx.astype(jnp.int32), knn_w.astype(jnp.float32), counts
