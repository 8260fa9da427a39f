"""Neighborhood preservation @ k (paper §4): mean overlap of k-neighborhoods
between the high- and low-dimensional spaces.

For large N the metric is evaluated on a uniform subsample of query points,
with neighbors searched over the full dataset in blocks (exact, not ANN —
the metric must not inherit the index's approximation).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def _topk_neighbors(queries: jnp.ndarray, data: jnp.ndarray, k: int, block: int = 8192):
    """Exact k nearest neighbors of each query (excluding identical index).

    queries: (Q, d) rows drawn from data at indices ``q_idx`` handled by the
    caller masking; here we exclude self-matches by distance==0 demotion.
    """
    q2 = jnp.sum(jnp.square(queries), -1)[:, None]

    best_d = jnp.full((queries.shape[0], k), jnp.inf, jnp.float32)
    best_i = jnp.full((queries.shape[0], k), -1, jnp.int32)
    n = data.shape[0]
    for start in range(0, n, block):
        db = data[start : start + block]
        cross = jnp.matmul(queries, db.T, precision=jax.lax.Precision.HIGHEST)
        d2 = q2 + jnp.sum(jnp.square(db), -1)[None, :] - 2.0 * cross
        d2 = jnp.maximum(d2, 0.0)
        cat_d = jnp.concatenate([best_d, d2], axis=1)
        cat_i = jnp.concatenate(
            [best_i, jnp.broadcast_to(jnp.arange(start, start + db.shape[0], dtype=jnp.int32)[None, :], d2.shape)],
            axis=1,
        )
        neg_d, idx = jax.lax.top_k(-cat_d, k)
        best_d = -neg_d
        best_i = jnp.take_along_axis(cat_i, idx, axis=1)
    return best_i


def neighborhood_preservation(
    x_high: np.ndarray,
    x_low: np.ndarray,
    k: int = 10,
    n_queries: int = 2000,
    seed: int = 0,
) -> float:
    """NP@k in [0, 1]. Self-neighbors are excluded (k+1 then drop self)."""
    n = x_high.shape[0]
    rng = np.random.default_rng(seed)
    q_idx = rng.choice(n, size=min(n_queries, n), replace=False)
    xh = jnp.asarray(x_high, jnp.float32)
    xl = jnp.asarray(x_low, jnp.float32)

    def knn_no_self(data, qi):
        nbrs = _topk_neighbors(data[qi], data, k + 1)
        out = np.asarray(nbrs)
        cleaned = np.empty((len(qi), k), np.int64)
        for r, (row, self_i) in enumerate(zip(out, qi)):
            row = row[row != self_i][:k]
            cleaned[r, : len(row)] = row
            if len(row) < k:  # duplicate points: pad with -2 (never matches)
                cleaned[r, len(row) :] = -2
        return cleaned

    hi = knn_no_self(xh, q_idx)
    lo = knn_no_self(xl, q_idx)
    overlap = [
        len(set(a.tolist()) & set(b.tolist())) / k for a, b in zip(hi, lo)
    ]
    return float(np.mean(overlap))


def map_stability(
    emb_prev: np.ndarray,
    emb_new: np.ndarray,
    k: int = 10,
    n_queries: int = 2000,
    seed: int = 0,
) -> float:
    """Map-stability score in [0, 1]: how much a map *moved* under an update.

    Both arguments are embeddings of the **same rows in the same order** —
    the previous map version and the new one restricted to the rows both
    contain (after ``partial_fit`` of M appended rows, pass
    ``new_embedding[:N_old]``). The score is the k-neighborhood overlap
    between the two low-dimensional spaces: 1.0 means every old row kept
    exactly its old neighbors (the map did not jump), 0.0 means no
    neighborhood survived. It is the same exact blocked kNN machinery as
    :func:`neighborhood_preservation` with the previous embedding standing
    in for the high-dimensional space.

    Applying one row permutation to *both* embeddings leaves the score
    unchanged whenever every row is queried (``n_queries >= n``); with a
    query subsample the sampled row *ids* differ under permutation, so
    exact invariance holds only at full coverage (tested that way).
    """
    emb_prev = np.asarray(emb_prev)
    emb_new = np.asarray(emb_new)
    if emb_prev.shape[0] != emb_new.shape[0]:
        raise ValueError(
            f"map_stability compares the same rows across versions: got "
            f"{emb_prev.shape[0]} previous vs {emb_new.shape[0]} new rows — "
            "slice the grown embedding to the shared prefix first"
        )
    return neighborhood_preservation(
        emb_prev, emb_new, k=k, n_queries=n_queries, seed=seed
    )
