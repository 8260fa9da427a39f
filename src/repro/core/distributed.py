"""Distributed NOMAD Projection (paper Fig. 2, on a TPU mesh).

Clusters are sharded contiguously across devices: shard ``s`` of ``n``
owns clusters ``[s·K/n, (s+1)·K/n)`` — each cluster is a component of the
ANN graph (paper §3.2), so positive forces and exact in-cell negatives
never leave the device. The only collective in the optimisation loop is
the per-refresh all-gather of cluster means and (static) counts.

The epoch body is process-agnostic: built over a mesh that spans the
**global** device pool (``jax.devices()``), its all-gathers/psums cross
process boundaries under multi-process ``jax.distributed`` with no code
change — gather/sum over the same per-device shards in the same mesh
order makes a P-process fit bit-equal to a 1-process fit on the same
device count (asserted in tests/test_multiprocess.py).

Two exchange modes:

* ``flat``         — the paper: all-gather all K means over every device.
* ``hierarchical`` — our multi-pod extension (the paper's stated future
  work): full means circulate only within a pod; remote pods are
  summarised by one size-weighted *super-mean* each. The same
  Jensen+Taylor argument (paper §7) applied to the pod-level partition
  justifies the approximation; DCN bytes drop from K·d to pods·d.

The SGD step body is ``repro.core.nomad.make_step_fn`` — identical math to
the single-device reference, which is what the equivalence test checks.

Host-side orchestration lives in the unified estimator now
(:class:`repro.core.nomad.NomadProjection` + ``repro.core.strategy``); this
module provides the ``shard_map`` epoch function those strategies wrap, and
keeps :func:`fit_distributed` as a deprecation shim.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from repro.configs.base import NomadConfig
from repro.core import losses
from repro.core.nomad import local_means, sample_in_cluster, sample_points


def shard_index_and_count(mesh: Mesh, axes) -> tuple:
    """(flat shard index, total shards) for possibly-multiple mesh axes."""
    sizes = [mesh.shape[a] for a in axes]
    idx = jnp.zeros((), jnp.int32)
    for a, s in zip(axes, sizes):
        idx = idx * s + jax.lax.axis_index(a)
    total = int(np.prod(sizes))
    return idx, total


def make_sharded_epoch_fn(
    cfg: NomadConfig,
    mesh: Mesh,
    *,
    shard_axes=("data", "model"),
    pod_axis: Optional[str] = None,
    steps_per_epoch: int,
    n_shards: int,
):
    """Build ``epoch(theta, idx, lr0, lr1, key) -> (theta, mean_loss)``.

    ``theta``: (K·C, d) global view, rows sharded over ``shard_axes``
    (+ ``pod_axis`` outermost if given). ``idx`` dict likewise row-sharded
    except the replicated ``counts_global``.
    """
    C = cfg.cluster_capacity
    K = cfg.n_clusters
    Kl = K // n_shards
    B, S, Mn = cfg.batch_size, cfg.n_exact_negatives, cfg.n_noise
    # batch_size is PER SHARD (paper: per-GPU); one epoch still touches ~N
    # heads because steps_per_epoch is divided by n_shards in fit_distributed.
    B_local = B
    refresh = cfg.mean_refresh_steps or steps_per_epoch
    n_chunks = max(steps_per_epoch // refresh, 1)
    all_axes = ((pod_axis,) if pod_axis else ()) + tuple(shard_axes)
    hierarchical = cfg.hierarchical and pod_axis is not None
    n_total = cfg.n_points

    def gather_cells(theta_l, counts_l, counts_global, shard_off):
        """Per-refresh exchange → (cell_means, cell_w, own-exclusion base).

        Returns the means matrix the loss sees, its |M|·p weights, and the
        global id offset of this shard's own cells within that matrix.
        """
        means_l = local_means(theta_l, counts_l, C)  # (Kl, d)
        if not hierarchical:
            means_g = jax.lax.all_gather(means_l, all_axes, axis=0, tiled=True)
            cell_w = float(Mn) * counts_global.astype(jnp.float32) / n_total
            return means_g, cell_w, shard_off
        # ---- hierarchical: full means intra-pod, super-means inter-pod ----
        means_pod = jax.lax.all_gather(means_l, tuple(shard_axes), axis=0, tiled=True)
        n_pods = mesh.shape[pod_axis]
        Kp = K // n_pods  # clusters per pod
        pod_idx = jax.lax.axis_index(pod_axis)
        pod_counts = jax.lax.dynamic_slice_in_dim(
            counts_global.astype(jnp.float32), pod_idx * Kp, Kp
        )
        w_sum = jnp.maximum(jnp.sum(pod_counts), 1.0)
        super_mean = jnp.sum(means_pod * pod_counts[:, None], 0, keepdims=True) / w_sum
        super_means = jax.lax.all_gather(super_mean[0], pod_axis, axis=0, tiled=False)
        super_counts = jax.lax.all_gather(jnp.sum(pod_counts), pod_axis, tiled=False)
        # own pod's super-mean is excluded (its cells are already exact/full)
        own_pod = jax.lax.axis_index(pod_axis)
        super_w = float(Mn) * super_counts / n_total
        super_w = jnp.where(jnp.arange(n_pods) == own_pod, 0.0, super_w)
        cell_means = jnp.concatenate([means_pod, super_means], axis=0)  # (Kp+P, d)
        pod_cell_w = float(Mn) * pod_counts / n_total
        cell_w = jnp.concatenate([pod_cell_w, super_w])
        own_base = shard_off - pod_idx * Kp  # own cells indexed within the pod block
        return cell_means, cell_w, own_base

    def sgd_step(theta_l, idx_l, cell_means, cell_w, own_base, counts_l, lr, key):
        k_head, k_neg = jax.random.split(key)
        rows, cl_local = sample_points(k_head, B_local, idx_l["cum_counts"], C)
        pos_rows = idx_l["knn_idx"][rows]
        pos_w = idx_l["knn_w"][rows]
        th_i = theta_l[rows]
        th_pos = theta_l[pos_rows]
        neg_rows = sample_in_cluster(k_neg, cl_local, counts_l, C, S)
        th_neg = theta_l[neg_rows]
        own_cell = cl_local + own_base
        p_own = counts_l.astype(jnp.float32)[cl_local] / n_total
        neg_w = jnp.broadcast_to((float(Mn) * p_own / S)[:, None], (B_local, S))
        cell_means = jax.lax.stop_gradient(cell_means)

        def loss_fn(ti, tp, tn):
            # one fused registry kernel per step (jnp path ≡ the legacy
            # mean-term + contrastive composition, bit-for-bit)
            per_head = losses.nomad_step_term(
                ti, tp, pos_w, tn, neg_w, cell_means, cell_w, own_cell,
                cfg.resolved_kernel_impl(),
            )
            return jnp.mean(per_head)

        loss, (g_i, g_pos, g_neg) = jax.value_and_grad(loss_fn, argnums=(0, 1, 2))(
            th_i, th_pos, th_neg
        )
        d = theta_l.shape[1]
        theta_l = theta_l.at[rows].add(-lr * g_i)
        theta_l = theta_l.at[pos_rows.reshape(-1)].add(-lr * g_pos.reshape(-1, d))
        theta_l = theta_l.at[neg_rows.reshape(-1)].add(-lr * g_neg.reshape(-1, d))
        return theta_l, loss

    row_spec = P((pod_axis,) + tuple(shard_axes) if pod_axis else shard_axes)
    specs_in = (
        P(*row_spec, None),  # theta (K·C, d)
        {
            "knn_idx": P(*row_spec, None),
            "knn_w": P(*row_spec, None),
            "counts": P(*row_spec),
            "cum_counts": P(*row_spec),
        },
        P(),  # counts_global (K,) replicated
        P(),  # lr0
        P(),  # lr1
        P(),  # key
    )

    @functools.partial(
        shard_map,
        mesh=mesh,
        in_specs=specs_in,
        out_specs=(P(*row_spec, None), P()),
        check_vma=False,
    )
    def epoch(theta_l, idx_l, counts_global, lr0, lr1, key):
        shard_idx, _ = shard_index_and_count(mesh, all_axes)
        shard_off = shard_idx * Kl
        if n_shards > 1:  # decorrelate shards; 1 shard matches the local stream
            key = jax.random.fold_in(key, shard_idx)
        counts_l = idx_l["counts"]

        def chunk_body(carry, c):
            theta_l, t0 = carry
            cell_means, cell_w, own_base = gather_cells(
                theta_l, counts_l, counts_global, shard_off
            )

            def step_body(carry, t):
                theta_l = carry
                lr = lr0 + (lr1 - lr0) * (t / steps_per_epoch)
                theta_l, loss = sgd_step(
                    theta_l,
                    idx_l,
                    cell_means,
                    cell_w,
                    own_base,
                    counts_l,
                    lr,
                    jax.random.fold_in(key, t),
                )
                return theta_l, loss

            theta_l, losses_ = jax.lax.scan(
                step_body, theta_l, t0 + jnp.arange(refresh)
            )
            return (theta_l, t0 + refresh), jnp.mean(losses_)

        (theta_l, _), chunk_losses = jax.lax.scan(
            chunk_body, (theta_l, jnp.zeros((), jnp.int32)), jnp.arange(n_chunks)
        )
        loss = jax.lax.pmean(jnp.mean(chunk_losses), all_axes)
        return theta_l, loss

    return epoch


# ---------------------------------------------------------------------------
# Host-side orchestration
# ---------------------------------------------------------------------------


def shard_index_arrays(index, n_shards: int):
    """Split an AnnIndex into the global-view arrays the epoch fn expects.

    kNN row ids are rebased to be shard-local (subtracting the shard's row
    offset) — positives never cross shards by construction, this just
    asserts it numerically.
    """
    K, C = index.n_clusters, index.capacity
    if K % n_shards:
        raise ValueError(f"n_clusters={K} not divisible by n_shards={n_shards}")
    Kl = K // n_shards
    rows_per = Kl * C
    knn_local = index.knn_idx.copy()
    for s in range(n_shards):
        lo, hi = s * rows_per, (s + 1) * rows_per
        blk = knn_local[lo:hi]
        if blk.size and ((blk < lo) | (blk >= hi)).any():
            raise AssertionError("kNN edge crosses shard boundary")
        knn_local[lo:hi] = blk - lo
    cum = np.concatenate(
        [np.cumsum(index.counts[s * Kl : (s + 1) * Kl]) for s in range(n_shards)]
    )
    return {
        "knn_idx": jnp.asarray(knn_local, jnp.int32),
        "knn_w": jnp.asarray(index.knn_w, jnp.float32),
        "counts": jnp.asarray(index.counts, jnp.int32),
        "cum_counts": jnp.asarray(cum, jnp.int32),
    }


def fit_distributed(
    cfg: NomadConfig,
    x: np.ndarray,
    mesh: Mesh,
    *,
    shard_axes=("data", "model"),
    pod_axis: Optional[str] = None,
    index=None,
    theta0=None,
    callback=None,
):
    """DEPRECATED shim — use the unified estimator instead:

        NomadProjection(cfg, strategy="sharded", mesh=mesh).fit(x)

    Delegates to :class:`repro.core.nomad.NomadProjection` and returns the
    legacy ``(embedding, index, losses)`` tuple. Note the legacy ``callback``
    now receives the *unpermuted* ``(N, out_dim)`` embedding, not the raw
    sharded θ buffer.
    """
    import warnings

    warnings.warn(
        "fit_distributed is deprecated; use "
        "NomadProjection(cfg, strategy='sharded'|'hierarchical', mesh=mesh).fit(x)",
        DeprecationWarning,
        stacklevel=2,
    )
    from repro.core.nomad import NomadProjection

    strategy = "hierarchical" if (cfg.hierarchical and pod_axis) else "sharded"
    est = NomadProjection(
        cfg, strategy=strategy, mesh=mesh, shard_axes=shard_axes, pod_axis=pod_axis
    )
    res = est.fit(x, index=index, callback=callback, theta0=theta0)
    return res.embedding, res.index, res.losses
