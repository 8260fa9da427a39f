"""LSH-initialised K-means (paper §3.2).

The paper: "We initialize our K-Means clustering using a locally sensitive
hash, run expectation maximization until convergence, and compute exact
nearest neighbors for each point within its cluster."

Every E-step (local, sharded, and the capacity-bidding rounds in
:mod:`repro.index.build`) runs through one row-blocked helper,
:func:`blocked_assign`, which dispatches the distance+argmin inner loop
through the kernel registry (kernel ``"kmeans_assign"``): the fused Pallas
path when resolved, else the jnp oracle per block. Peak live memory is one
``(block, K)`` tile — never ``(N, K)``.

EM itself is a ``lax.scan`` with **on-device convergence**: a ``done`` flag
freezes the carry once the centroid shift drops under ``tol``, so a build
never host-syncs a ``float(shift)`` per iteration. The ``shard_map``
variant (:func:`kmeans_fit_sharded`) runs the same scan body with points
sharded across devices — per-iteration communication is one psum of
(K, D+1) partial statistics, the classic distributed-EM factorisation —
and on a 1-device mesh it is bit-identical to the local scan.
"""

from __future__ import annotations

import functools
import warnings
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np


def deprecate_use_pallas(impl, use_pallas, fn_name: str):
    """Shared shim: ``use_pallas=`` keyword → ``impl=`` with a warning."""
    if use_pallas is None:
        return impl
    warnings.warn(
        f"{fn_name}(use_pallas=...) is deprecated; pass "
        "impl='auto'|'pallas'|'jnp' instead",
        DeprecationWarning,
        stacklevel=3,
    )
    return use_pallas if impl is None else impl


def lsh_init_centroids(
    key, x: jax.Array, n_clusters: int, valid=None, n_valid: Optional[int] = None
) -> jax.Array:
    """Random-hyperplane LSH buckets → bucket means as initial centroids.

    b = ceil(log2 K) hyperplanes give 2^b ≥ K buckets; the K most populated
    buckets seed the centroids; empty seats fall back to random points.
    ``valid`` (N,) bool excludes padding rows from the bucket statistics and
    ``n_valid`` bounds the random fallback draw (the sharded build pads N up
    to the device count; padding must enter neither).
    """
    n, d = x.shape
    b = max(1, int(np.ceil(np.log2(n_clusters))))
    kh, kf = jax.random.split(key)
    planes = jax.random.normal(kh, (d, b), jnp.float32)
    bits = (x.astype(jnp.float32) @ planes) > 0  # (n, b)
    codes = jnp.sum(bits * (2 ** jnp.arange(b, dtype=jnp.int32))[None, :], axis=1)
    n_buckets = 2**b
    if valid is None:
        sums = jnp.zeros((n_buckets, d), jnp.float32).at[codes].add(x.astype(jnp.float32))
        cnts = jnp.zeros((n_buckets,), jnp.float32).at[codes].add(1.0)
    else:
        w = valid.astype(jnp.float32)
        sums = jnp.zeros((n_buckets, d), jnp.float32).at[codes].add(
            x.astype(jnp.float32) * w[:, None]
        )
        cnts = jnp.zeros((n_buckets,), jnp.float32).at[codes].add(w)
    order = jnp.argsort(-cnts)  # most populated first
    top = order[:n_clusters]
    cents = sums[top] / jnp.maximum(cnts[top], 1.0)[:, None]
    # empty buckets → random data points (never padding rows)
    fallback = x[
        jax.random.randint(kf, (n_clusters,), 0, n if n_valid is None else n_valid)
    ].astype(jnp.float32)
    return jnp.where((cnts[top] > 0)[:, None], cents, fallback)


def assign_jnp(x: jax.Array, cents: jax.Array, block: int = 16384):
    """Nearest-centroid assignment; returns (assign (n,), min_dist2 (n,))."""
    c2 = jnp.sum(jnp.square(cents), -1)

    def one_block(xb):
        d2 = (
            jnp.sum(jnp.square(xb), -1)[:, None]
            + c2[None, :]
            - 2.0 * xb @ cents.T
        )
        return jnp.argmin(d2, -1).astype(jnp.int32), jnp.min(d2, -1)

    n = x.shape[0]
    if n <= block:
        return one_block(x.astype(jnp.float32))
    outs = [one_block(x[s : s + block].astype(jnp.float32)) for s in range(0, n, block)]
    return jnp.concatenate([o[0] for o in outs]), jnp.concatenate([o[1] for o in outs])


def blocked_assign(x: jax.Array, cents: jax.Array, impl: str, block: int):
    """Row-blocked E-step through the kernel registry.

    ``impl`` must be pre-resolved ("pallas" | "jnp") so the choice is
    static inside any enclosing trace. ``lax.map`` keeps one block live at
    a time: peak memory is (block, K) on the jnp path, the kernel's own
    tiles on the Pallas path — never (N, K).
    """
    from repro.kernels import registry

    n, d = x.shape
    block = max(1, min(block, n))
    nb = -(-n // block)
    pad = nb * block - n
    xp = jnp.concatenate([x, jnp.zeros((pad, d), x.dtype)]) if pad else x

    def one(xb):
        return registry.dispatch("kmeans_assign", xb, cents, impl=impl)

    a, d2 = jax.lax.map(one, xp.reshape(nb, block, d))
    return a.reshape(-1)[:n], d2.reshape(-1)[:n]


def _m_step(x, assign, n_clusters, old_cents):
    """Unweighted M-step (the weighted/psum variant lives in ``_em_scan``,
    where the padding mask and the collective seam belong)."""
    sums = jnp.zeros((n_clusters, x.shape[1]), jnp.float32).at[assign].add(
        x.astype(jnp.float32)
    )
    cnts = jnp.zeros((n_clusters,), jnp.float32).at[assign].add(1.0)
    cents = sums / jnp.maximum(cnts, 1.0)[:, None]
    return jnp.where((cnts > 0)[:, None], cents, old_cents), cnts


def _em_scan(x, cents0, n_clusters, n_iters, tol, impl, block, w=None, psum_axis=None):
    """The one EM body: scan with a ``done``-frozen carry (no host syncs).

    On convergence the carry keeps the *pre-update* centroids, so the
    carried ``(assign, cnts)`` stay consistent with the returned centroids
    and no post-loop E-step is needed. ``psum_axis`` turns the M-step's
    (K, D+1) statistics into psums — the distributed-EM factorisation.

    Returns ``(cents, assign, cnts, done)``.
    """
    n = x.shape[0]

    def partial_stats(a):
        xf = x.astype(jnp.float32)
        ww = jnp.ones((n,), jnp.float32) if w is None else w
        sums = jnp.zeros((n_clusters, x.shape[1]), jnp.float32).at[a].add(
            xf * ww[:, None]
        )
        cnts = jnp.zeros((n_clusters,), jnp.float32).at[a].add(ww)
        return sums, cnts

    def e_then_m(cents):
        a, _ = blocked_assign(x, cents, impl, block)
        sums, cnts = partial_stats(a)
        if psum_axis is not None:
            sums = jax.lax.psum(sums, psum_axis)  # the one collective
            cnts = jax.lax.psum(cnts, psum_axis)
        new = sums / jnp.maximum(cnts, 1.0)[:, None]
        new = jnp.where((cnts > 0)[:, None], new, cents)
        return a, new, cnts

    def live(carry):
        cents, _assign, _cnts, done = carry
        a, new, cnts = e_then_m(cents)
        shift = jnp.max(jnp.sum(jnp.square(new - cents), -1))
        conv = shift < tol
        # freeze centroids on convergence: (cents, a, cnts) stay consistent
        return jnp.where(conv, cents, new), a, cnts, conv

    def body(carry, _):
        carry = jax.lax.cond(carry[3], lambda c: c, live, carry)
        return carry, None

    init = (
        cents0,
        jnp.zeros((n,), jnp.int32),
        jnp.zeros((n_clusters,), jnp.float32),
        jnp.zeros((), bool),
    )
    carry, _ = jax.lax.scan(body, init, None, length=n_iters)
    return carry


@functools.partial(
    jax.jit, static_argnames=("n_clusters", "n_iters", "impl", "block")
)
def _kmeans_fit_jit(x, cents0, tol, n_clusters, n_iters, impl, block):
    cents, assign, cnts, done = _em_scan(
        x, cents0, n_clusters, n_iters, tol, impl, block
    )

    def align(args):
        # ran out of iterations before converging: the carried assignment is
        # one E-step stale w.r.t. the final centroids — align once
        cents, _a, _c = args
        a, _ = blocked_assign(x, cents, impl, block)
        _, cnts = _m_step(x, a, n_clusters, cents)
        return cents, a, cnts

    return jax.lax.cond(
        done, lambda args: args, align, (cents, assign, cnts)
    )


@functools.partial(
    jax.jit, static_argnames=("n_clusters", "n_iters", "impl", "block")
)
def _kmeans_cents_jit(x, cents0, tol, n_clusters, n_iters, impl, block):
    cents, _a, _c, _done = _em_scan(
        x, cents0, n_clusters, n_iters, tol, impl, block
    )
    return cents


def kmeans_centroids(
    key,
    x: jax.Array,
    n_clusters: int,
    n_iters: int = 25,
    tol: float = 1e-4,
    impl=None,
    *,
    block: int = 16384,
):
    """Centroids-only EM — the index build's kmeans stage.

    Same scan body as :func:`kmeans_fit` minus the assignment outputs (the
    build derives its assignment from the capacity-bounded bidding rounds,
    not the unconstrained E-step), and the same body
    :func:`kmeans_fit_sharded` runs under ``shard_map`` — which is what
    makes a 1-device sharded build bit-identical to the local one.
    """
    from repro.kernels import registry

    x = jnp.asarray(x)
    cents0 = lsh_init_centroids(key, x, n_clusters)
    return _kmeans_cents_jit(
        x,
        cents0,
        jnp.float32(tol),
        n_clusters,
        n_iters,
        registry.resolve("kmeans_assign", impl),
        min(block, x.shape[0]),
    )


def kmeans_fit(
    key,
    x: jax.Array,
    n_clusters: int,
    n_iters: int = 25,
    tol: float = 1e-4,
    impl=None,
    *,
    block: int = 16384,
    use_pallas=None,
):
    """Lloyd's EM from LSH init. Returns (centroids, assignments, counts).

    ``impl`` is a registry impl: "auto" | "pallas" | "jnp" (legacy bools
    accepted; the ``use_pallas=`` keyword is a deprecated alias). The whole
    EM loop is one jitted ``lax.scan`` with on-device convergence — no
    per-iteration host sync — and the returned assignment is always the
    nearest-centroid assignment of the returned centroids: on convergence
    the loop's own final E-step already is (no recompute), otherwise one
    alignment E-step runs inside the same jit.
    """
    from repro.kernels import registry

    impl = deprecate_use_pallas(impl, use_pallas, "kmeans_fit")
    x = jnp.asarray(x)
    cents0 = lsh_init_centroids(key, x, n_clusters)
    return _kmeans_fit_jit(
        x,
        cents0,
        jnp.float32(tol),
        n_clusters,
        n_iters,
        registry.resolve("kmeans_assign", impl),
        min(block, x.shape[0]),
    )


def _pad_chunk(chunk: np.ndarray, chunk_rows: int):
    """Pad a (possibly ragged) host chunk to exactly ``chunk_rows`` rows and
    return its validity weights — every streamed chunk then hits one fixed
    (chunk_rows, D) jit trace, and padding never enters a statistic."""
    c = chunk.shape[0]
    w = np.zeros((chunk_rows,), np.float32)
    w[:c] = 1.0
    if c < chunk_rows:
        chunk = np.concatenate(
            [chunk, np.zeros((chunk_rows - c, chunk.shape[1]), chunk.dtype)]
        )
    return chunk, w


def kmeans_centroids_streamed(
    key,
    store,
    n_clusters: int,
    *,
    chunk_rows: int,
    n_iters: int = 25,
    tol: float = 1e-4,
    impl=None,
    block: int = 16384,
):
    """Centroids-only EM over a disk-backed :class:`repro.data.store.
    EmbeddingStore` — the streamed twin of :func:`kmeans_centroids`.

    Each pass streams the corpus in ``chunk_rows``-row chunks through a
    double-buffered :func:`repro.data.store.stream_chunks` feed; per chunk
    one jitted call runs the registry E-step and accumulates the (K, D+1)
    partial statistics on device, so peak host RSS is O(chunk_rows · D) and
    device state is O(chunk + K·D). Same LSH init key schedule as the
    resident scan, and convergence freezes the *pre-update* centroids
    (matching ``_em_scan``); the one host sync is a ``float(shift)`` per EM
    pass, amortised over a full pass of the data. Chunk boundaries depend
    only on (N, chunk_rows), so results are identical for any two stores
    holding the same rows.
    """
    import functools as _ft

    from repro.data.store import stream_chunks
    from repro.kernels import registry

    resolved = registry.resolve("kmeans_assign", impl)
    n, d = store.shape
    chunk_rows = max(1, min(chunk_rows, n))
    blk = max(1, min(block, chunk_rows))

    b = max(1, int(np.ceil(np.log2(n_clusters))))
    kh, kf = jax.random.split(key)
    planes = jax.random.normal(kh, (d, b), jnp.float32)
    pow2 = (2 ** jnp.arange(b, dtype=jnp.int32))[None, :]
    n_buckets = 2**b

    @_ft.partial(jax.jit, donate_argnums=(0, 1))
    def lsh_partial(sums, cnts, xb, w):
        bits = (xb @ planes) > 0
        codes = jnp.sum(bits * pow2, axis=1)
        sums = sums.at[codes].add(xb * w[:, None])
        cnts = cnts.at[codes].add(w)
        return sums, cnts

    sums = jnp.zeros((n_buckets, d), jnp.float32)
    cnts = jnp.zeros((n_buckets,), jnp.float32)
    for _s, chunk in stream_chunks(store, chunk_rows):
        xb, w = _pad_chunk(chunk, chunk_rows)
        sums, cnts = lsh_partial(sums, cnts, jnp.asarray(xb), jnp.asarray(w))
    order = jnp.argsort(-cnts)
    top = order[:n_clusters]
    bucket_cents = sums[top] / jnp.maximum(cnts[top], 1.0)[:, None]
    fb_rows = np.asarray(jax.random.randint(kf, (n_clusters,), 0, n))
    fallback = jnp.asarray(store.read_rows(fb_rows), jnp.float32)
    cents = jnp.where((cnts[top] > 0)[:, None], bucket_cents, fallback)

    @_ft.partial(jax.jit, donate_argnums=(0, 1))
    def em_partial(sums, cnts, xb, w, cents):
        a, _ = blocked_assign(xb, cents, resolved, blk)
        sums = sums.at[a].add(xb * w[:, None])
        cnts = cnts.at[a].add(w)
        return sums, cnts

    for _it in range(n_iters):
        sums = jnp.zeros((n_clusters, d), jnp.float32)
        cnts = jnp.zeros((n_clusters,), jnp.float32)
        for _s, chunk in stream_chunks(store, chunk_rows):
            xb, w = _pad_chunk(chunk, chunk_rows)
            sums, cnts = em_partial(
                sums, cnts, jnp.asarray(xb), jnp.asarray(w), cents
            )
        new = sums / jnp.maximum(cnts, 1.0)[:, None]
        new = jnp.where((cnts > 0)[:, None], new, cents)
        shift = float(jnp.max(jnp.sum(jnp.square(new - cents), -1)))
        if shift < tol:
            break  # freeze-on-converge: keep the pre-update centroids
        cents = new
    return cents


def kmeans_fit_sharded(
    key,
    x_sharded,
    n_clusters,
    mesh,
    axis: str,
    n_iters: int = 25,
    tol: float = 0.0,
    impl=None,
    *,
    block: int = 16384,
    n_real: Optional[int] = None,
):
    """Distributed EM: X rows sharded over ``axis``; psum of (K, D+1) stats.

    x_sharded: global-view array already placed with rows sharded. Returns
    replicated centroids. (Per-iteration collective: K×(D+1) fp32.)
    ``n_real`` masks trailing padding rows (rows padded so the row count
    divides the mesh axis). ``tol=0`` keeps the historical fixed-iteration
    behaviour; with the same ``tol``/``block``/``impl`` as
    :func:`kmeans_fit`, a 1-device mesh reproduces the local scan
    bit-for-bit.

    Under multi-process ``jax.distributed`` the same body runs unchanged:
    ``mesh`` spans the global device pool, ``x_sharded`` is a global view
    assembled from per-process pieces, and the one (K, D+1) psum per
    iteration crosses processes. A P-process run is bit-equal to a
    1-process run over the same P devices — the psum sums the same
    per-device partials in the same mesh order either way.
    """
    from jax.sharding import PartitionSpec as P
    from jax import shard_map

    from repro.kernels import registry

    resolved = registry.resolve("kmeans_assign", impl)
    n = x_sharded.shape[0]
    valid = None if n_real is None else (jnp.arange(n) < n_real)
    cents0 = lsh_init_centroids(
        key, x_sharded, n_clusters, valid=valid, n_valid=n_real
    )
    if valid is None:
        valid = jnp.ones((n,), jnp.float32)
    else:
        valid = valid.astype(jnp.float32)
    blk = min(block, n // mesh.shape[axis])

    @jax.jit
    @functools.partial(
        shard_map,
        mesh=mesh,
        in_specs=(P(axis, None), P(axis), P(None, None)),
        out_specs=P(None, None),
        check_vma=False,
    )
    def em_iters(x_local, w_local, cents):
        cents, _a, _c, _done = _em_scan(
            x_local,
            cents,
            n_clusters,
            n_iters,
            jnp.float32(tol),
            resolved,
            blk,
            w=w_local,
            psum_axis=axis,
        )
        return cents

    return em_iters(x_sharded, valid, cents0)


def capacity_assign(
    dist2_fn,
    x: np.ndarray,
    cents: np.ndarray,
    capacity: int,
    max_rounds: int = 12,
) -> np.ndarray:
    """Capacity-bounded nearest-centroid assignment (host-side reference).

    TPU adaptation (DESIGN.md §2): static shapes need bounded clusters.
    Greedy rounds: each unassigned point bids for its nearest centroid with
    free capacity; each centroid admits its ``capacity`` closest bidders.
    Terminates because every round either fills a centroid or assigns all.

    State is O(N + K): a rejected bidder's centroid is, by construction,
    full from that round on (rejection only happens when bidders exceed the
    remaining capacity), so the ``free <= 0`` mask already covers every
    cluster the seed implementation tracked in its (N, K) ``banned``
    matrix. The production build runs the device equivalent
    (:func:`repro.index.build.capacity_assign_device`); this NumPy loop is
    the oracle it is tested against and the benchmark baseline.
    """
    n = x.shape[0]
    K = cents.shape[0]
    assign = np.full(n, -1, np.int64)
    free = np.full(K, capacity, np.int64)

    for _ in range(max_rounds):
        todo = np.flatnonzero(assign < 0)
        if todo.size == 0:
            return assign
        d2 = dist2_fn(x[todo], cents)  # (T, K)
        d2 = np.where(free[None, :] <= 0, np.inf, d2)
        pick = np.argmin(d2, 1)
        for c in range(K):
            if free[c] <= 0:
                continue
            bidders = todo[pick == c]
            if bidders.size == 0:
                continue
            if bidders.size > free[c]:
                order = np.argsort(d2[pick == c, c], kind="stable")
                admitted = bidders[order[: free[c]]]
            else:
                admitted = bidders
            assign[admitted] = c
            free[c] -= admitted.size
    # force-place any stragglers into the nearest centroid with space
    todo = np.flatnonzero(assign < 0)
    if todo.size:
        d2 = dist2_fn(x[todo], cents)
        order = np.argsort(d2, axis=1)
        for t, row in zip(todo, order):
            for c in row:
                if free[c] > 0:
                    assign[t] = c
                    free[c] -= 1
                    break
    if (assign < 0).any():
        raise RuntimeError("capacity_assign: total capacity < N")
    return assign
