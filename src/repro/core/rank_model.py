"""The inverse-rank edge model (paper Eq. 6).

    p(j|i) = e^{1/rank_j(i)} / Z   if rank_j(i) ≤ k, else 0
    Z      = Σ_{j=0}^{k} e^{1/(j+1)}

``rank_j(i)`` is the paper's (slightly unusual) definition: the index of the
*head* i in the list of points sorted by ascending distance **to the tail
j** — i.e. how close i looks from j's perspective. Index 0 is j itself, so
ranks of other points start at 1. The normaliser Z has k+1 terms exactly as
written in Eq. 6 (it includes the r = k+1 term); we keep it verbatim for
faithfulness — it is a constant, so it only scales the loss.

Only ranks 1..k carry weight, so each tail's list is cut to its k+1 nearest
(ties to the lower index, as a stable sort orders them): a rank is read by
finding the head in its tail's list, and a head absent from it ranks above k.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def normalizer(k: int) -> float:
    return float(np.exp(1.0 / np.arange(1, k + 2)).sum())


def edge_weights(dist2: jnp.ndarray, knn_idx: jnp.ndarray, k: int, valid: jnp.ndarray) -> jnp.ndarray:
    """Weights p(j|i) for each kNN edge i→j (Eq. 6).

    dist2:   (C, C) in-cluster squared distances (padding rows masked +inf)
    knn_idx: (C, k) neighbor slots per point
    valid:   (C,) real-point mask
    Returns (C, k) fp32 weights; invalid edges get 0.
    """
    C = dist2.shape[0]
    m = min(k + 1, C)
    # col_top[j, p] = the point at rank p from j (column j of dist2, ascending)
    _, col_top = jax.lax.top_k(-dist2.T, m)
    hit = col_top[knn_idx] == jnp.arange(C)[:, None, None]  # (C, k, m)
    r_ji = jnp.min(jnp.where(hit, jnp.arange(m), k + 1), axis=-1)  # rank of i from j
    w = jnp.exp(1.0 / jnp.maximum(r_ji.astype(jnp.float32), 1.0)) / normalizer(k)
    w = jnp.where((r_ji >= 1) & (r_ji <= k), w, 0.0)
    w = jnp.where(valid[:, None] & valid[knn_idx], w, 0.0)
    return w
