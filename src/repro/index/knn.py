"""Exact within-cluster kNN + the inverse-rank edge weights (paper §3.2/Eq 6).

Because neighbor candidates are confined to the point's own (padded) cluster
block, every cluster is a connected component of the ANN graph — the paper's
device-locality property for positive forces.

The pairwise-distance matrix dispatches through the kernel registry
(kernel ``"pairwise"``, MXU form ‖x‖²+‖y‖²−2x·yᵀ). The two top-ks, each
row's k nearest and each tail's k+1 nearest for the weights' ranks, stay in
jnp (sort-heavy, VPU-bound either way).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core.rank_model import edge_weights

BIG = jnp.float32(1e30)

# bytes of (C, C) f32 distance tiles one batch of clusters may hold at once;
# each cluster's kNN keeps several such tiles (distances, masks, transpose)
_KNN_TILE_BYTES = 256 << 20


def _pairwise_dist2_jnp(xb: jax.Array) -> jax.Array:
    x2 = jnp.sum(jnp.square(xb), -1)
    d2 = x2[:, None] + x2[None, :] - 2.0 * (xb @ xb.T)
    return jnp.maximum(d2, 0.0)


@functools.partial(jax.jit, static_argnames=("k", "impl"))
def _cluster_knn_jit(
    x_block: jax.Array,  # (C, D) one padded cluster
    valid: jax.Array,  # (C,) real-point mask
    k: int,
    impl: str,  # pre-resolved: "pallas" | "jnp"
):
    from repro.kernels import registry

    C = x_block.shape[0]
    xb = x_block.astype(jnp.float32)
    if impl == "pallas":
        d2 = registry.dispatch("pairwise", xb, xb, impl="pallas")
    else:
        d2 = _pairwise_dist2_jnp(xb)
    # mask padding and self for neighbor search
    pad_mask = ~(valid[:, None] & valid[None, :])
    search = d2 + pad_mask * BIG + jnp.eye(C, dtype=jnp.float32) * BIG
    _, knn_idx = jax.lax.top_k(-search, k)  # (C, k) ascending distance
    # ranks use the true distance matrix with padding pushed to the end
    d2_ranked = d2 + pad_mask * BIG
    w = edge_weights(d2_ranked, knn_idx, k, valid)
    return knn_idx.astype(jnp.int32), w


def cluster_knn(x_block, valid, k: int, impl=None, *, use_pallas=None):
    """Returns (knn_idx (C, k) in-cluster slots, weights (C, k) fp32).

    ``impl`` is a registry impl ("auto"|"pallas"|"jnp", legacy bools
    accepted; the ``use_pallas=`` keyword is a deprecated alias); it is
    resolved *outside* the jit so env overrides apply per call, never baked
    into a cached trace.
    """
    from repro.index.kmeans import deprecate_use_pallas
    from repro.kernels import registry

    impl = deprecate_use_pallas(impl, use_pallas, "cluster_knn")
    return _cluster_knn_jit(x_block, valid, k, registry.resolve("pairwise", impl))


def batched_cluster_knn(
    x_blocks: jax.Array, valid: jax.Array, k: int, impl=None, *, use_pallas=None
):
    """vmap over clusters: x_blocks (Kc, C, D), valid (Kc, C)."""
    from repro.index.kmeans import deprecate_use_pallas
    from repro.kernels import registry

    impl = deprecate_use_pallas(impl, use_pallas, "batched_cluster_knn")
    return _knn_over_clusters(x_blocks, valid, k, registry.resolve("pairwise", impl))


def _knn_over_clusters(x_blocks, valid, k: int, impl: str):
    """vmap of :func:`_cluster_knn_jit` over clusters, in batches small
    enough that their (C, C) tiles fit ``_KNN_TILE_BYTES``. Small clusters
    take one vmap; large ones (C in the thousands, where a whole vmap would
    need K·C² floats at once) run as a ``lax.map`` of vmapped batches."""
    Kc, C = valid.shape
    batch = max(1, _KNN_TILE_BYTES // (4 * C * C))
    one = lambda xb, vb: _cluster_knn_jit(xb, vb, k, impl)
    if batch >= Kc:
        return jax.vmap(one)(x_blocks, valid)
    return jax.lax.map(lambda a: one(*a), (x_blocks, valid), batch_size=batch)


def query_cluster_knn(
    q: jax.Array,  # (B, D) query vectors
    own: jax.Array,  # (B,) assigned cluster per query
    x_blocks: jax.Array,  # (K, C, D) frozen cluster-major vectors
    counts: jax.Array,  # (K,) real points per cluster
    k: int,
    *,
    block: int = 256,
):
    """Query-only kNN against a *frozen* index: each query searches its own
    assigned (padded) cluster block — the same §3.2 locality the training
    graph uses, so a served point attaches exactly where a refit would put
    its positives.

    Runs in ``block``-row chunks via ``lax.map`` so the gathered
    (block, C, D) tile bounds peak memory regardless of the query count.
    Returns (slot (B, k) in-cluster slots, d2 (B, k) ascending,
    valid (B, k) real-neighbor mask) — per-row math only, so results are
    independent of batching/sharding.
    """
    B, d = q.shape
    C = x_blocks.shape[1]
    block = max(1, min(block, B))
    nb = -(-B // block)
    pad = nb * block - B
    qp = jnp.concatenate([q, jnp.zeros((pad, d), q.dtype)]) if pad else q
    ownp = jnp.concatenate([own, jnp.zeros((pad,), own.dtype)]) if pad else own
    x2 = jnp.sum(jnp.square(x_blocks.astype(jnp.float32)), -1)  # (K, C)

    def one(args):
        qb, ob = args  # (block, D), (block,)
        xb = x_blocks[ob].astype(jnp.float32)  # (block, C, D)
        qf = qb.astype(jnp.float32)
        d2 = (
            jnp.sum(jnp.square(qf), -1)[:, None]
            + x2[ob]
            - 2.0 * jnp.einsum("bd,bcd->bc", qf, xb)
        )
        d2 = jnp.maximum(d2, 0.0)
        invalid = jnp.arange(C)[None, :] >= counts[ob][:, None]
        neg, slot = jax.lax.top_k(-(d2 + invalid * BIG), k)
        return slot.astype(jnp.int32), -neg

    slot, d2 = jax.lax.map(
        one, (qp.reshape(nb, block, d), ownp.reshape(nb, block))
    )
    slot = slot.reshape(nb * block, k)[:B]
    d2 = d2.reshape(nb * block, k)[:B]
    valid = slot < counts[own][:, None]
    valid &= d2 < BIG / 2  # padded-out candidates (cluster smaller than k)
    return slot, jnp.where(valid, d2, 0.0), valid


def cluster_knn_batch_sharded(mesh, axis: str, x_blocks, counts, k: int, impl=None):
    """``batched_cluster_knn`` with the cluster axis sharded over ``axis``.

    Each device runs the kNN of its own contiguous cluster blocks — the
    cluster-component property (§3.2) makes the stage embarrassingly
    parallel, so the only data movement is placing ``x_blocks`` row-sharded.
    On a 1-device mesh this is the local vmap, bit-for-bit.
    """
    from jax import shard_map
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.kernels import registry

    import numpy as np

    resolved = registry.resolve("pairwise", impl)
    Kc, C, _d = x_blocks.shape
    if Kc % mesh.shape[axis]:
        raise ValueError(
            f"n_clusters={Kc} not divisible by the {mesh.shape[axis]}-device "
            f"build mesh"
        )
    # valid stays a host array: device_put from host works under a
    # multi-process mesh (x_blocks may already be a global jax.Array with
    # this exact sharding — device_put is then the identity)
    valid = np.arange(C)[None, :] < np.asarray(counts)[:, None]
    xb = jax.device_put(x_blocks, NamedSharding(mesh, P(axis, None, None)))
    vb = jax.device_put(valid, NamedSharding(mesh, P(axis, None)))

    @jax.jit
    @functools.partial(
        shard_map,
        mesh=mesh,
        in_specs=(P(axis, None, None), P(axis, None)),
        out_specs=(P(axis, None, None), P(axis, None, None)),
        check_vma=False,
    )
    def run(xb_l, vb_l):
        return _knn_over_clusters(xb_l, vb_l, k, resolved)

    return run(xb, vb)
