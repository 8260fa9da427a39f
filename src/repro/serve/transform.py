"""The jitted out-of-sample transform: frozen-neighbor NOMAD steps.

One batch of unseen rows is placed in four stages, all inside a single jit
(optionally wrapped in ``shard_map`` with the query rows sharded):

1. **assign** — nearest frozen k-means centroid per query, through the
   ``"kmeans_assign"`` registry kernel (the same fused distance+argmin the
   index build uses);
2. **kNN** — exact nearest neighbors inside the assigned frozen cluster
   block (:func:`repro.index.knn.query_cluster_knn`) — the §3.2 locality
   property, applied at query time. Edge weights follow Eq. 6 with the
   *query-side* rank (neighbor s gets e^{1/(s+1)}/Z): the tail-side rank
   of an unseen point would need the full (C, C) in-cell distance matrix
   per query cell, and both sides share the Z normaliser;
3. **init** — each query starts at the Cauchy-weighted mean of its frozen
   neighbors' positions, weights 1/(1+‖x_q − x_nb‖²) from the *high-dim*
   distances (NCVis-style: the noise-contrastive objective stays
   well-posed with one side frozen, so a good init is most of the work);
4. **optimize** — a ``lax.scan`` of ``transform_steps`` NOMAD steps in
   which only the query positions move: attraction through the fused
   ``"frozen_attract"`` kernel, repulsion through the same ``"cauchy_mean"``
   M̃ term training used (remote cells via frozen means, the own cell via
   frozen in-cell samples), lr linearly annealed.

**Every stage is per-row math against replicated frozen state, and the RNG
is folded per global query row** (``fold_in(key, row)``), so placements are
bit-identical across microbatch sizes and across local vs sharded serving
— the property tests/test_serve.py pins down.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import losses
from repro.core.cauchy import cauchy
from repro.core.rank_model import normalizer
from repro.index.knn import query_cluster_knn
from repro.serve.frozen import FrozenMap


def frozen_arrays(fz: FrozenMap) -> dict:
    """The FrozenMap as the flat dict pytree the jitted fn consumes."""
    K, C = fz.n_clusters, fz.capacity
    return {
        "theta": fz.theta_rows,
        "x_blocks": fz.x_rows.reshape(K, C, fz.dim),
        "centroids": fz.centroids,
        "counts": fz.counts,
        "means": fz.means,
        "inv_perm": fz.inv_perm,
    }


def make_transform_fn(
    fz: FrozenMap,
    *,
    steps: Optional[int] = None,
    lr: Optional[float] = None,
    mesh=None,
    axis: str = "serve",
    with_neighbors: bool = True,
):
    """Build the jitted batch-transform function for one FrozenMap.

    Returns ``fn(fz_arrays, qx (B, D), rows (B,) int32, seeds (B,) uint32,
    valid (B,) bool) -> (theta (B, d), own (B,), nb_ids (B, k),
    nb_dists (B, k), step_losses (steps,))``. With ``mesh`` given, the
    body runs under ``shard_map`` with queries row-sharded over ``axis``
    and the frozen state replicated; B must then divide by the mesh size.

    The RNG stream is ``fold_in(key(seeds[i]), rows[i])`` — folded per
    row from a *per-row* seed, so one batch may mix rows of several
    logical requests (each with its own seed and its own local row
    numbering) and every row still gets exactly the RNG a dedicated
    ``MapServer.transform(q, seed=...)`` call would have given it. This
    is what lets the service-layer batching engine coalesce concurrent
    requests into one device batch bit-identically.

    ``with_neighbors=False`` returns ``(theta, own, step_losses)`` only:
    jit dead-code-eliminates the neighbor-id unpermute + sqrt and skips
    two (B, k) host transfers — the placement-only service fast path.
    """
    cfg = fz.cfg
    C = fz.capacity
    k = cfg.n_neighbors
    S = cfg.n_exact_negatives
    T = cfg.transform_steps if steps is None else steps
    lr0 = cfg.resolved_transform_lr() if lr is None else lr
    impl = cfg.resolved_kernel_impl()
    knn_block = cfg.serve_knn_block
    n_noise = float(cfg.n_noise)
    n_total = float(fz.n_points)
    sharded = mesh is not None
    # Eq. 6 weight table, precomputed on HOST: as a traced jnp constant XLA
    # folds it differently under shard_map vs plain jit (one-ulp exp
    # differences), which would break the local ≡ sharded bit-equality
    w_rank = jnp.asarray(
        np.exp(1.0 / np.arange(1, k + 1, dtype=np.float32)) / normalizer(k),
        jnp.float32,
    )

    def body(fza, qx, rows, seeds, valid):
        from repro.kernels import registry

        # -- 1. assign to a frozen cell -------------------------------------
        own, _ = registry.dispatch(
            "kmeans_assign", qx.astype(jnp.float32), fza["centroids"], impl=impl
        )

        # -- 2. frozen in-cell kNN ------------------------------------------
        slot, nb_d2, nb_valid = query_cluster_knn(
            qx, own, fza["x_blocks"], fza["counts"], k, block=knn_block
        )
        nb_row = own[:, None] * C + slot  # (B, k) rows into theta/inv_perm
        nb_theta = jax.lax.stop_gradient(fza["theta"][nb_row])  # (B, k, d)
        nb_w = jnp.where(nb_valid, w_rank[None, :], 0.0)

        # -- 3. Cauchy-weighted init ----------------------------------------
        w_init = jnp.where(nb_valid, 1.0 / (1.0 + nb_d2), 0.0)
        theta0 = jnp.einsum(
            "bk,bkd->bd",
            w_init / jnp.maximum(jnp.sum(w_init, -1, keepdims=True), 1e-12),
            nb_theta,
        )

        # -- 4. frozen NOMAD steps ------------------------------------------
        counts_f = fza["counts"].astype(jnp.float32)
        p_cell = counts_f / n_total  # (K,)
        cell_w = n_noise * p_cell
        p_own = p_cell[own]  # (B,)
        cnt_own = jnp.maximum(fza["counts"][own], 1)
        n_valid = jnp.sum(valid)
        if sharded:
            n_valid = jax.lax.psum(n_valid, axis)
        # per-row RNG stream: batching/sharding-invariant by construction
        # (key(seed) then fold_in(row) — identical bits whether the key is
        # built host-side from one python int or traced from a seeds row)
        row_key = jax.vmap(
            lambda s, r: jax.random.fold_in(jax.random.key(s), r)
        )(seeds, rows)

        def step(theta, t):
            kt = jax.vmap(lambda kk: jax.random.fold_in(kk, t))(row_key)
            u = jax.vmap(lambda kk: jax.random.uniform(kk, (S,)))(kt)
            nslot = jnp.minimum(
                jnp.floor(u * cnt_own[:, None]).astype(jnp.int32),
                (cnt_own - 1)[:, None].astype(jnp.int32),
            )
            th_neg = jax.lax.stop_gradient(
                fza["theta"][own[:, None] * C + nslot]
            )  # (B, S, d)

            def loss_fn(th):
                m_tilde = losses.nomad_mean_term(
                    th, fza["means"], cell_w, own, impl
                )
                q_neg = cauchy(th[:, None, :], th_neg)  # (B, S)
                m_exact = (n_noise * p_own / S) * jnp.sum(q_neg, axis=-1)
                lb = registry.dispatch(
                    "frozen_attract", th, nb_theta, nb_w, m_tilde + m_exact,
                    impl=impl,
                )
                return jnp.sum(jnp.where(valid, lb, 0.0))

            loss_sum, g = jax.value_and_grad(loss_fn)(theta)
            if sharded:
                loss_sum = jax.lax.psum(loss_sum, axis)
            lr_t = lr0 * (1.0 - t.astype(jnp.float32) / max(T, 1))
            return theta - lr_t * g, loss_sum / jnp.maximum(n_valid, 1)

        theta, step_losses = jax.lax.scan(step, theta0, jnp.arange(T))

        if not with_neighbors:
            return theta, own, step_losses
        nb_ids = jnp.where(nb_valid, fza["inv_perm"][nb_row], -1)
        nb_dists = jnp.where(nb_valid, jnp.sqrt(nb_d2), jnp.inf)
        return theta, own, nb_ids, nb_dists, step_losses

    if not sharded:
        return jax.jit(body)

    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    fz_specs = jax.tree_util.tree_map(
        lambda a: P(*([None] * a.ndim)), frozen_arrays(fz)
    )
    if with_neighbors:
        out_specs = (P(axis, None), P(axis), P(axis, None), P(axis, None), P())
    else:
        out_specs = (P(axis, None), P(axis), P())
    sharded_body = shard_map(
        body,
        mesh=mesh,
        in_specs=(fz_specs, P(axis, None), P(axis), P(axis), P(axis)),
        out_specs=out_specs,
        check_vma=False,
    )
    return jax.jit(sharded_body)
