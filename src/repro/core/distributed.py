"""Distributed NOMAD Projection (paper Fig. 2, on a TPU mesh).

Clusters are sharded contiguously across devices: shard ``s`` of ``n``
owns clusters ``[s·K/n, (s+1)·K/n)`` — each cluster is a component of the
ANN graph (paper §3.2), so positive forces and exact in-cell negatives
never leave the device. The only collective in the optimisation loop is
the all-gather of cluster means at each dispatch's start.

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

The SGD step and the epoch body are ``repro.core.nomad.make_step_fn`` and
``scan_steps``, the functions the single-device fit runs: each shard draws
its heads from its own cells, and the means are exchanged once, at the
dispatch's start. On a one-device mesh the sharded fit is bit-equal to the
local one (``tests/test_strategy.py``).

Host-side orchestration lives in the unified estimator
(:class:`repro.core.nomad.NomadProjection` + ``repro.core.strategy``); this
module provides the ``shard_map`` epoch function those strategies wrap,
and the placement of θ and the index on the mesh.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import NomadConfig
from repro.core.nomad import (
    SCOPE_LOSS,
    SCOPE_MEANS,
    SCOPE_SAMPLE,
    local_means,
    make_step_fn,
    scan_steps,
)

# ``jax.named_scope`` of the shards' collectives: the means' all-gather
# (inside ``nomad_means``) and the loss's ``pmean`` (inside ``nomad_loss``),
# so a profiler trace tells the exchange from the stage's own compute.
SCOPE_EXCHANGE = "nomad_exchange"


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
    """Build ``epoch(theta, idx, counts_global, lr0, lr1, key) -> (theta,
    mean_loss)``.

    ``theta``: (K·C, d) global view, rows sharded over ``shard_axes``
    (+ ``pod_axis`` outermost if given). ``idx`` dict likewise row-sharded;
    ``counts_global`` is the replicated float32 counts (:func:`place_index`).
    ``idx["knn_idx"]`` holds global row ids, made shard-local per step.
    ``cfg.batch_size`` is per shard (the paper's is per GPU), so
    ``ShardedStrategy`` gives each shard ``1/n_shards`` of the one-device
    step count and an epoch still draws ~N heads.
    """
    C = cfg.cluster_capacity
    K = cfg.n_clusters
    Kl = K // n_shards
    all_axes = ((pod_axis,) if pod_axis else ()) + tuple(shard_axes)
    hierarchical = cfg.hierarchical and pod_axis is not None
    step_fn = make_step_fn(cfg)

    @jax.named_scope(SCOPE_MEANS)
    def gather_cells(theta_l, counts_l, counts_global, shard_off):
        """The exchange at a dispatch's start → ``(means, counts, cell_off)``.

        The means matrix the loss sees, the cell sizes that weight it, and
        the offset of this shard's own cells within it (the step's
        ``cells``).
        """
        means_l = local_means(theta_l, counts_l, C)  # (Kl, d)
        if not hierarchical:
            with jax.named_scope(SCOPE_EXCHANGE):
                means_g = jax.lax.all_gather(means_l, all_axes, axis=0, tiled=True)
            return means_g, counts_global, shard_off
        # ---- hierarchical: full means intra-pod, super-means inter-pod ----
        with jax.named_scope(SCOPE_EXCHANGE):
            means_pod = jax.lax.all_gather(means_l, tuple(shard_axes), axis=0, tiled=True)
        n_pods = mesh.shape[pod_axis]
        Kp = K // n_pods  # clusters per pod
        pod_idx = jax.lax.axis_index(pod_axis)
        pod_counts = jax.lax.dynamic_slice_in_dim(
            counts_global.astype(jnp.float32), pod_idx * Kp, Kp
        )
        w_sum = jnp.maximum(jnp.sum(pod_counts), 1.0)
        super_mean = jnp.sum(means_pod * pod_counts[:, None], 0, keepdims=True) / w_sum
        with jax.named_scope(SCOPE_EXCHANGE):
            super_means = jax.lax.all_gather(super_mean[0], pod_axis, axis=0, tiled=False)
            super_counts = jax.lax.all_gather(jnp.sum(pod_counts), pod_axis, tiled=False)
        # own pod's super-mean is excluded (its cells are already exact/full)
        super_counts = jnp.where(jnp.arange(n_pods) == pod_idx, 0.0, super_counts)
        cell_means = jnp.concatenate([means_pod, super_means], axis=0)  # (Kp+P, d)
        cell_counts = jnp.concatenate([pod_counts, super_counts])
        own_base = shard_off - pod_idx * Kp  # own cells indexed within the pod block
        return cell_means, cell_counts, own_base

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
        with jax.named_scope(SCOPE_SAMPLE):
            shard_idx, _ = shard_index_and_count(mesh, all_axes)
            shard_off = shard_idx * Kl
            row_off = shard_off * C
            if n_shards > 1:  # decorrelate shards; 1 shard matches the local stream
                key = jax.random.fold_in(key, shard_idx)
        cells = gather_cells(theta_l, idx_l["counts"], counts_global, shard_off)
        theta_l, loss = scan_steps(
            step_fn, steps_per_epoch, theta_l, idx_l, cells, lr0, lr1, key, row_off
        )
        with jax.named_scope(SCOPE_LOSS), jax.named_scope(SCOPE_EXCHANGE):
            loss = jax.lax.pmean(loss, all_axes)
        return theta_l, loss

    return epoch


# ---------------------------------------------------------------------------
# Host-side orchestration
# ---------------------------------------------------------------------------


def place_rows(a, sharding: NamedSharding, dtype=None) -> jax.Array:
    """``a`` on ``sharding``, each device given only its own block.

    A host array is cut block by block (``jax.make_array_from_callback``),
    so no device receives more than its shard; a ``jax.Array``, sharded or
    not, is re-placed on the devices (a no-op where it already lies there).
    ``dtype`` defaults to JAX's canonical form of ``a``'s.
    """
    dtype = jnp.dtype(jax.dtypes.canonicalize_dtype(a.dtype) if dtype is None else dtype)
    if isinstance(a, jax.Array):
        return jax.device_put(a if a.dtype == dtype else a.astype(dtype), sharding)
    return jax.make_array_from_callback(
        a.shape, sharding, lambda blk: np.asarray(a[blk], dtype)
    )


@functools.partial(jax.jit, static_argnums=1)
def _edges_cross(knn_idx, rows_per: int):
    """Whether any kNN row id lies outside its own row's shard."""
    row = jax.lax.broadcasted_iota(jnp.int32, knn_idx.shape, 0)
    lo = row - row % rows_per
    return jnp.any((knn_idx < lo) | (knn_idx >= lo + rows_per))


def place_index(index, mesh: Mesh, axes) -> tuple:
    """Place an index's arrays on ``mesh``, rows split over ``axes``.

    Returns ``(idx, counts_global)``: the row-sharded ``knn_idx`` (global
    row ids; the epoch makes them shard-local), ``knn_w``, ``counts`` and
    each shard's own ``cum_counts``, and the replicated float32 counts.
    ``index`` needs ``knn_idx``, ``knn_w`` and ``counts``, as host arrays or
    ``jax.Array``s; each device receives only its shard, so neither the
    host nor any one device holds a copy of the whole graph. Positives
    never cross shards by construction (a shard owns whole cells); one
    device reduction asserts it.
    """
    n_shards = int(np.prod([mesh.shape[a] for a in axes]))
    K = index.counts.shape[0]
    if K % n_shards:
        raise ValueError(f"n_clusters={K} not divisible by n_shards={n_shards}")
    row_sh = NamedSharding(mesh, P(axes, None))
    vec_sh = NamedSharding(mesh, P(axes))
    knn_idx = place_rows(index.knn_idx, row_sh, jnp.int32)
    if _edges_cross(knn_idx, knn_idx.shape[0] // n_shards):
        raise AssertionError("kNN edge crosses shard boundary")
    counts = place_rows(index.counts, vec_sh, jnp.int32)
    idx = {
        "knn_idx": knn_idx,
        "knn_w": place_rows(index.knn_w, row_sh, jnp.float32),
        "counts": counts,
        "cum_counts": jax.jit(
            lambda c: jnp.cumsum(c.reshape(n_shards, -1), axis=1).reshape(-1),
            out_shardings=vec_sh,
        )(counts),
    }
    counts_global = jax.jit(
        lambda c: c.astype(jnp.float32), out_shardings=NamedSharding(mesh, P())
    )(counts)
    return idx, counts_global
