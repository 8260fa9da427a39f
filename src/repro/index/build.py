"""Device-resident, sharded index-build subsystem (paper §3.2 at scale).

PR 2 made *training* device-resident and sharded; this module does the same
for the **index build** — the LSH-init k-means → capacity-bounded clusters
→ in-cluster exact kNN pipeline that used to run through host NumPy with an
O(N·K) ``banned`` matrix (~40 GB at N=10M, K=4K) inside a Python bidding
loop.

:class:`IndexBuilder` mirrors the training strategy layer:
``build_strategy="auto"|"local"|"sharded"`` resolves from ``jax.devices()``
(or the mesh the estimator trains on), and every stage runs on device:

* **kmeans**  — the ``lax.scan`` EM of :mod:`repro.index.kmeans` with
  on-device convergence, its E-step the row-blocked ``"kmeans_assign"``
  registry kernel (``"sharded"`` routes through ``kmeans_fit_sharded``:
  rows sharded, one (K, D+1) psum per iteration);
* **assign**  — capacity-bounded assignment as a jitted ``while_loop`` of
  bidding rounds: ONE row-blocked pass through the ``"pairwise"`` registry
  kernel caches each row's top-R nearest centroids (R =
  ``cfg.build_candidates``), then every round is O(N·R): each unassigned
  row bids for its nearest centroid with free capacity, and the
  ``"capacity_admit"`` registry kernel (stable segmented rank) admits each
  centroid's ``free`` closest bidders — exactly the host reference's round
  semantics. Carried state is ``assign (N,) + free (K,)``; no (N, K)
  allocation exists on host or device;
* **permute** — the cluster-major permutation as one vectorised
  argsort/scatter jit (the seed looped ``for c in range(K)`` on host);
* **knn**     — ``batched_cluster_knn``; under ``"sharded"`` each device
  computes the kNN of its own contiguous cluster blocks via ``shard_map``.

``"sharded"`` never places the full (N, D) on one device, and on a
1-device mesh it reproduces ``"local"`` bit-for-bit (asserted in
tests/test_index_build.py). Stragglers — rows whose whole candidate list
filled up, a fraction of a percent at normal slack — are force-placed on
host from O(T·K) distances, T = number of stragglers.
"""

from __future__ import annotations

import dataclasses
import functools
import os
import time
from contextlib import contextmanager
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import NomadConfig
from repro.index import kmeans as km
from repro.index.ann import AnnIndex, _np_dist2, data_fingerprint
from repro.index.knn import batched_cluster_knn, cluster_knn_batch_sharded

BUILD_AXIS = "build"


# ---------------------------------------------------------------------------
# Capacity-bounded assignment: device bidding rounds over cached candidates
# ---------------------------------------------------------------------------


def _candidate_pass(x, cents, n_cand: int, impl: str, block: int):
    """One row-blocked pass: each row's ``R = min(n_cand, K)`` nearest
    centroids, distance-sorted. The (block, K) distance tile comes from the
    ``"pairwise"`` registry kernel; only the (N, R) top-k survives — the
    single O(N·K) *compute* pass of the whole assignment, with O(N·R)
    *memory*."""
    from repro.kernels import registry

    n, d = x.shape
    r = min(n_cand, cents.shape[0])
    block = max(1, min(block, n))
    nb = -(-n // block)
    pad = nb * block - n
    xp = jnp.concatenate([x, jnp.zeros((pad, d), x.dtype)]) if pad else x

    def one(xb):
        d2 = registry.dispatch("pairwise", xb, cents, impl=impl)
        neg, idx = jax.lax.top_k(-d2, r)
        return idx.astype(jnp.int32), -neg

    idx, d2 = jax.lax.map(one, xp.reshape(nb, block, d))
    return idx.reshape(nb * block, r)[:n], d2.reshape(nb * block, r)[:n]


def _bid_from_candidates(cand_idx, cand_d2, free):
    """Each row's nearest centroid with free capacity — candidates are
    distance-sorted, so that is the first free one. Rows whose whole
    candidate list is full (``has=False``) sit the round out (and fall to
    the host straggler pass if the loop ends)."""
    ok = free[cand_idx] > 0  # (N, R)
    has = jnp.any(ok, axis=1)
    j = jnp.argmax(ok, axis=1)  # first free candidate
    rows = jnp.arange(cand_idx.shape[0])
    return cand_idx[rows, j], cand_d2[rows, j], has


def _round_cond_body(estep_fn, n: int, n_real: int, K: int, max_rounds: int):
    """The shared bidding-round while_loop pieces (local and sharded).

    Every round with a non-empty bidder pool admits at least one point
    (``capacity_admit`` admits min(bidders, free) per centroid), so the
    loop provably progresses; ``progressed`` stops it early once the only
    unassigned rows are candidate-exhausted stragglers."""
    from repro.kernels import registry

    real = jnp.arange(n) < n_real

    def cond(carry):
        assign, _free, r, progressed = carry
        return (r < max_rounds) & progressed & jnp.any((assign < 0) & real)

    def body(carry):
        assign, free, r, _progressed = carry
        pick, d2, has = estep_fn(free)
        bidding = (assign < 0) & real & has
        admitted = registry.dispatch("capacity_admit", pick, d2, bidding, free)
        assign = jnp.where(admitted, pick, assign)
        taken = jnp.zeros_like(free).at[jnp.where(admitted, pick, K)].add(
            1, mode="drop"
        )
        return assign, free - taken, r + 1, jnp.any(bidding)

    init = (
        jnp.full((n,), -1, jnp.int32),
        None,  # free filled in by the caller
        jnp.zeros((), jnp.int32),
        jnp.ones((), bool),
    )
    return cond, body, init


@functools.partial(
    jax.jit, static_argnames=("capacity", "impl", "block", "max_rounds", "n_cand")
)
def _capacity_rounds_local(x, cents, capacity, impl, block, max_rounds, n_cand):
    n = x.shape[0]
    K = cents.shape[0]
    cand_idx, cand_d2 = _candidate_pass(x, cents, n_cand, impl, block)
    cond, body, init = _round_cond_body(
        lambda free: _bid_from_candidates(cand_idx, cand_d2, free),
        n,
        n,
        K,
        max_rounds,
    )
    init = (init[0], jnp.full((K,), capacity, jnp.int32), init[2], init[3])
    assign, free, _, _ = jax.lax.while_loop(cond, body, init)
    return assign, free


def _capacity_rounds_sharded(
    mesh, x_sharded, cents, capacity, impl, block, max_rounds, n_cand, n_real
):
    """Rows (and their candidate cache) sharded over the build axis; the
    per-round exchange is one all_gather of the (N,) bids (admission is
    replicated — O(N + K) state, never (N, K) nor (N, D) on one device)."""
    n = x_sharded.shape[0]
    K = cents.shape[0]
    blk = max(1, min(block, n // mesh.shape[BUILD_AXIS]))

    @jax.jit
    @functools.partial(
        shard_map,
        mesh=mesh,
        in_specs=(P(BUILD_AXIS, None), P(None, None)),
        out_specs=(P(None), P(None)),
        check_vma=False,
    )
    def run(x_local, cents):
        cand_idx, cand_d2 = _candidate_pass(x_local, cents, n_cand, impl, blk)

        def estep(free):
            p_l, d_l, h_l = _bid_from_candidates(cand_idx, cand_d2, free)
            return (
                jax.lax.all_gather(p_l, BUILD_AXIS, axis=0, tiled=True),
                jax.lax.all_gather(d_l, BUILD_AXIS, axis=0, tiled=True),
                jax.lax.all_gather(h_l, BUILD_AXIS, axis=0, tiled=True),
            )

        cond, body, init = _round_cond_body(estep, n, n_real, K, max_rounds)
        init = (init[0], jnp.full((K,), capacity, jnp.int32), init[2], init[3])
        assign, free, _, _ = jax.lax.while_loop(cond, body, init)
        return assign, free

    return run(x_sharded, cents)


def _row_gather(x, rows: np.ndarray) -> np.ndarray:
    """Rows of ``x`` whether it is an ndarray or an EmbeddingStore."""
    from repro.data.store import is_store

    return x.read_rows(rows) if is_store(x) else x[rows]


def _force_place_host(x, cents, assign, free, chunk: int = 8192):
    """Place stragglers (rows unassigned after ``max_rounds``) into their
    nearest centroid with space — O(T·K) host *compute*, chunked so the
    live distance block never exceeds (chunk, K) even if contention drives
    T toward N. ``x`` may be an array or a disk-backed store."""
    todo = np.flatnonzero(assign < 0)
    if todo.size == 0:
        return assign, 0
    for s in range(0, todo.size, chunk):
        block = todo[s : s + chunk]
        d2 = _np_dist2(_row_gather(x, block), cents)
        for t, row in zip(block, np.argsort(d2, axis=1)):
            for c in row:
                if free[c] > 0:
                    assign[t] = c
                    free[c] -= 1
                    break
    if (assign < 0).any():
        raise RuntimeError("capacity assignment: total capacity < N")
    return assign, int(todo.size)


def capacity_assign_device(
    x: np.ndarray,
    cents: np.ndarray,
    capacity: int,
    *,
    impl="auto",
    block: int = 16384,
    max_rounds: int = 16,
    n_cand: int = 32,
) -> np.ndarray:
    """Device-resident capacity-bounded assignment (single-device form).

    The round semantics match :func:`repro.index.kmeans.capacity_assign`
    (the host NumPy oracle): unassigned points bid for their nearest
    centroid with free capacity; each centroid admits its ``free`` closest
    bidders, ties broken by original index. (A point whose ``n_cand``
    nearest centroids all fill is force-placed by the straggler pass —
    the one place the two can differ, and only under extreme contention.)
    Returns ``assign`` (N,) int64.
    """
    from repro.kernels import registry

    resolved = registry.resolve("pairwise", impl)
    assign, free = _capacity_rounds_local(
        jnp.asarray(x),
        jnp.asarray(cents, jnp.float32),
        capacity,
        resolved,
        max(1, min(block, x.shape[0])),
        max_rounds,
        n_cand,
    )
    assign = np.asarray(assign).astype(np.int64)
    assign, _ = _force_place_host(
        np.asarray(x), np.asarray(cents), assign, np.asarray(free).copy()
    )
    return assign


# ---------------------------------------------------------------------------
# Cluster-major permutation: one argsort/scatter jit
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("n_clusters", "capacity"))
def _permutation_from_assign(assign, n_clusters, capacity):
    """assign (N,) → (perm (N,), counts (K,)) on device.

    row = cluster · capacity + slot, slots in stable original-index order —
    identical layout to the seed's per-cluster host loop, vectorised. Only
    O(N + K) integer state; the (K·C, D) row buffer itself is one host
    memcpy of the (host-resident) input, done per consumer: whole for the
    local kNN stage, shard-by-shard for the sharded one.
    """
    n = assign.shape[0]
    order = jnp.argsort(assign, stable=True)
    counts = jnp.zeros((n_clusters,), jnp.int32).at[assign].add(1)
    starts = jnp.concatenate(
        [jnp.zeros((1,), jnp.int32), jnp.cumsum(counts)[:-1].astype(jnp.int32)]
    )
    a_sorted = assign[order]
    slot = jnp.arange(n, dtype=jnp.int32) - starts[a_sorted]
    rows = a_sorted * capacity + slot
    perm = jnp.zeros((n,), jnp.int32).at[order].set(rows)
    return perm, counts


def _scatter_rows_host(x, perm, n_clusters, capacity):
    """x_rows (K·C, D) in the caller's dtype — one vectorised host scatter."""
    x_rows = np.zeros((n_clusters * capacity, x.shape[1]), x.dtype)
    x_rows[perm] = x
    return x_rows


def _finalize_knn(knn_local, knn_w, K: int, C: int):
    """(K, C, k) in-cluster slots → (K·C, k) global rows; dead edges → self."""
    knn_local = np.asarray(knn_local)
    knn_w = np.asarray(knn_w).reshape(K * C, -1)
    base = (np.arange(K) * C)[:, None, None]
    knn_idx = (knn_local + base).reshape(K * C, -1).astype(np.int64)
    self_rows = np.arange(K * C)[:, None]
    knn_idx = np.where(knn_w > 0, knn_idx, self_rows)
    return knn_idx, knn_w.astype(np.float32)


# ---------------------------------------------------------------------------
# Streamed (out-of-core) stages: disk-backed stores, O(chunk) host RSS
# ---------------------------------------------------------------------------


@functools.partial(
    jax.jit, donate_argnums=(0, 1), static_argnames=("n_cand", "impl", "block")
)
def _cand_write_chunk(cand_idx, cand_d2, xb, start, cents, n_cand, impl, block):
    """One streamed chunk of the candidate pass: top-R centroids of the
    chunk's rows written into the device-resident (N_pad, R) cache. The
    cache is donated, so the update is in-place where the backend allows."""
    idx, d2 = _candidate_pass(xb, cents, n_cand, impl, block)
    cand_idx = jax.lax.dynamic_update_slice(cand_idx, idx, (start, 0))
    cand_d2 = jax.lax.dynamic_update_slice(cand_d2, d2, (start, 0))
    return cand_idx, cand_d2


@functools.partial(
    jax.jit, static_argnames=("n_clusters", "capacity", "max_rounds", "n_real")
)
def _capacity_rounds_cached(
    cand_idx, cand_d2, n_clusters, capacity, max_rounds, n_real
):
    """The bidding rounds of ``_capacity_rounds_local`` over a candidate
    cache built elsewhere (the streamed pass) — same round semantics, same
    carried O(N + K) state. Rows beyond ``n_real`` are chunk padding and
    never bid."""
    n = cand_idx.shape[0]
    cond, body, init = _round_cond_body(
        lambda free: _bid_from_candidates(cand_idx, cand_d2, free),
        n,
        n_real,
        n_clusters,
        max_rounds,
    )
    init = (init[0], jnp.full((n_clusters,), capacity, jnp.int32), init[2], init[3])
    assign, free, _, _ = jax.lax.while_loop(cond, body, init)
    return assign, free


def _resolve_spill_dir(cfg: NomadConfig, store) -> str:
    """Where a streamed build spills the cluster-major ``x_rows`` store.

    Deterministic locations first: ``cfg.checkpoint_dir/x_rows_spill-<tag>``
    when the fit owns a checkpoint directory, else a sibling of the input
    store (``<path>.x_rows-<tag>``). The tag hashes the full config + the
    store path, so a refit with the *same* config overwrites its own spill
    (whose bytes it reproduces) while a different config — a sweep over
    seeds, cluster counts, dtypes — gets its own directory and can never
    corrupt the ``x_rows`` a still-live ``AnnIndex`` references. Only when
    neither location is writable does it fall back to a fresh system temp
    dir (beware: /tmp is often RAM-backed tmpfs — point checkpoint_dir at
    real disk for truly big corpora).
    """
    import hashlib
    import tempfile

    tag = hashlib.sha256(
        (repr(sorted(dataclasses.asdict(cfg).items())) + str(store.path)).encode()
    ).hexdigest()[:8]
    candidates = []
    if cfg.checkpoint_dir:
        candidates.append(
            os.path.join(cfg.checkpoint_dir, "x_rows_spill-" + tag)
        )
    if store.path:
        candidates.append(str(store.path).rstrip("/\\") + ".x_rows-" + tag)
    for cand in candidates:
        try:
            os.makedirs(cand, exist_ok=True)
            # per-process probe name: concurrent jax.distributed processes
            # probe the same candidate dir and must not race each other
            probe = os.path.join(cand, f".write-probe-{os.getpid()}")
            with open(probe, "w"):
                pass
            os.remove(probe)
            return cand
        except OSError:
            continue
    return tempfile.mkdtemp(prefix="repro-x-rows-")


def _spill_sharded_scatter(
    store, perm: np.ndarray, n_rows: int, dim: int, out_dir: str, dtype: str,
    chunk_rows: int, rows_per_shard: int = 65536, max_shards: int = 256,
):
    """Stream the input store once and scatter ``row i → perm[i]`` into a
    sharded on-disk store of ``n_rows`` rows — the cluster-major ``x_rows``
    layout without ever holding it (or the input) in host RAM. Shards are
    pre-created as writable memmaps; each chunk's rows are grouped by
    destination shard and written in one fancy-indexed slice per shard.
    The scatter touches every shard per chunk, so all shard memmaps stay
    open — ``max_shards`` caps the fd count (shards grow instead) to stay
    far under default ulimits at any N.
    """
    from repro.data.store import (
        SHARD_PATTERN,
        ShardedStore,
        _commit_meta,
        _disk_dtype,
        _encode,
        stream_chunks,
    )

    os.makedirs(out_dir, exist_ok=True)
    rows_per_shard = max(rows_per_shard, -(-n_rows // max_shards))
    rows_per_shard = max(1, min(rows_per_shard, n_rows))
    n_shards = -(-n_rows // rows_per_shard)
    shard_rows = [
        min(rows_per_shard, n_rows - j * rows_per_shard) for j in range(n_shards)
    ]
    starts = np.concatenate([[0], np.cumsum(shard_rows)])
    files, mms = [], []
    for j in range(n_shards):
        name = SHARD_PATTERN.format(j)
        files.append(name)
        mms.append(
            np.lib.format.open_memmap(
                os.path.join(out_dir, name),
                mode="w+",
                dtype=_disk_dtype(dtype),
                shape=(shard_rows[j], dim),
            )
        )
    for s, chunk in stream_chunks(store, chunk_rows):
        targets = perm[s : s + chunk.shape[0]]
        order = np.argsort(targets, kind="stable")
        t_sorted = targets[order]
        enc = _encode(chunk, dtype)[order]
        bounds = np.searchsorted(t_sorted, starts)
        for j in range(n_shards):
            lo, hi = bounds[j], bounds[j + 1]
            if lo == hi:
                continue
            mms[j][t_sorted[lo:hi] - starts[j]] = enc[lo:hi]
    for mm in mms:
        mm.flush()
    del mms
    _commit_meta(out_dir, n_rows, dim, dtype, files, shard_rows)
    return ShardedStore(out_dir)


# ---------------------------------------------------------------------------
# The builder
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class BuildReport:
    """Provenance of one index build (feeds FitResult + benchmarks)."""

    strategy: str
    n_shards: int
    total_s: float
    stage_s: dict  # {"kmeans" | "assign" | "permute" | "knn": seconds}
    stage_rss_mb: dict  # high-watermark host RSS at the end of each stage
    stragglers: int = 0


def _rss_mb() -> float:
    try:
        import resource
        import sys

        rss = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss
        # ru_maxrss is kilobytes on Linux, bytes on macOS
        return rss / (1024.0 * 1024.0) if sys.platform == "darwin" else rss / 1024.0
    except Exception:  # non-POSIX platform
        return 0.0


def resolve_build_strategy(
    spec: str, cfg: NomadConfig, mesh: Optional[Mesh] = None
):
    """``"auto"|"local"|"sharded"|"distributed"`` →
    ("local", None) | ("sharded"|"distributed", Mesh).

    The build mesh is one flat axis over the largest cluster-divisible
    prefix of the available devices (the training mesh's devices when the
    estimator passes one in, else ``jax.devices()`` — the **global** pool
    under ``jax.distributed``); ``"auto"`` picks sharded exactly when that
    mesh is wider than one device. ``"distributed"`` runs the same
    collective program but reads/places rows per process; under multiple
    processes every global device must participate, so ``n_clusters`` must
    divide evenly (a truncated mesh would orphan some process's devices).
    """
    from repro.core.strategy import flat_mesh, largest_divisor_leq

    spec = spec or "auto"
    if spec not in ("auto", "local", "sharded", "distributed"):
        raise ValueError(
            f"unknown build_strategy {spec!r} "
            "(want 'auto'|'local'|'sharded'|'distributed')"
        )
    if spec == "local":
        return "local", None
    devs = list(mesh.devices.reshape(-1)) if mesh is not None else jax.devices()
    width = largest_divisor_leq(cfg.n_clusters, len(devs))
    if spec == "distributed":
        if jax.process_count() > 1 and width != len(devs):
            raise ValueError(
                f"build_strategy='distributed': n_clusters={cfg.n_clusters} "
                f"must be divisible by the global device count {len(devs)} "
                f"({jax.process_count()} processes) — every process's "
                "devices must join the build mesh"
            )
        return "distributed", flat_mesh(devs[:width], BUILD_AXIS)
    if spec == "auto" and width == 1:
        return "local", None
    return "sharded", flat_mesh(devs[:width], BUILD_AXIS)


class IndexBuilder:
    """Builds the §3.2 :class:`AnnIndex` on device, locally or sharded.

    Mirrors the training strategy layer: ``strategy`` (default
    ``cfg.build_strategy``) is ``"auto"|"local"|"sharded"``; ``mesh`` (the
    estimator's training mesh, if any) supplies the device pool. After
    ``build`` the per-stage wall times and peak host RSS sit in
    :attr:`report` (a :class:`BuildReport`).
    """

    def __init__(
        self,
        cfg: NomadConfig,
        *,
        strategy: Optional[str] = None,
        mesh: Optional[Mesh] = None,
        impl=None,
    ):
        self.cfg = cfg
        self.spec = strategy if strategy is not None else cfg.build_strategy
        self.mesh = mesh
        self.impl = impl if impl is not None else cfg.resolved_kernel_impl()
        self.report: Optional[BuildReport] = None

    # -- the one build -------------------------------------------------------

    def build(self, x) -> AnnIndex:
        from repro.data.store import as_store, is_store

        cfg = self.cfg
        n, d = x.shape  # ndarray and EmbeddingStore both expose .shape
        K, C = cfg.n_clusters, cfg.cluster_capacity
        if K * C < n:
            raise ValueError(f"capacity {C}×{K} < N={n}; raise capacity_slack")
        # multi-process jax (or an explicit "distributed" spec) takes the
        # cross-process path first: the streamed pipeline's sequential
        # chunk accumulation cannot be split across processes bit-equally,
        # so distributed builds reuse the sharded collective program over
        # the global mesh with per-process row reads instead
        if self.spec == "distributed" or jax.process_count() > 1:
            name, mesh = resolve_build_strategy("distributed", cfg, self.mesh)
        else:
            # a store input — or an explicit cfg.chunk_rows — selects the
            # out-of-core streamed pipeline; chunking fixes the accumulation
            # order, so the two containers produce bit-identical indices
            streamed = is_store(x) or cfg.chunk_rows > 0
            name, mesh = (
                ("streamed", None)
                if streamed
                else resolve_build_strategy(self.spec, cfg, self.mesh)
            )

        stage_s: dict = {}
        stage_rss: dict = {}

        @contextmanager
        def stage(label):
            t0 = time.perf_counter()
            yield
            # accumulate: the straggler force-place re-enters "assign"
            stage_s[label] = stage_s.get(label, 0.0) + (time.perf_counter() - t0)
            stage_rss[label] = _rss_mb()

        t0 = time.perf_counter()
        if name == "streamed":
            index, stragglers = self._build_streamed(as_store(x), stage)
            n_shards = 1
        elif name == "local":
            index, stragglers = self._build_local(x, stage)
            n_shards = 1
        elif name == "distributed":
            index, stragglers = self._build_distributed(as_store(x), mesh, stage)
            n_shards = mesh.shape[BUILD_AXIS]
        else:
            index, stragglers = self._build_sharded(x, mesh, stage)
            n_shards = mesh.shape[BUILD_AXIS]
        self.report = BuildReport(
            strategy=name,
            n_shards=n_shards,
            total_s=time.perf_counter() - t0,
            stage_s=stage_s,
            stage_rss_mb=stage_rss,
            stragglers=stragglers,
        )
        return index

    # -- stages ----------------------------------------------------------------

    def _assemble(self, x, cents, x_rows, perm, counts, knn_local, knn_w):
        cfg = self.cfg
        K, C = cfg.n_clusters, cfg.cluster_capacity
        knn_idx, knn_w = _finalize_knn(knn_local, knn_w, K, C)
        return AnnIndex(
            x_rows=x_rows,
            knn_idx=knn_idx,
            knn_w=knn_w,
            counts=np.asarray(counts).astype(np.int64),
            centroids=np.asarray(cents),
            perm=perm,
            capacity=C,
            n_points=x.shape[0],
            fingerprint=data_fingerprint(x),
        )

    def _finish(self, x, cents, assign_d, free_d, stage, knn_fn):
        """The strategy-independent tail: straggler force-place → permute →
        kNN (``knn_fn`` is the one per-strategy piece) → assemble. One body
        for both paths keeps sharded ≡ local by construction."""
        cfg = self.cfg
        n, d = x.shape
        K, C = cfg.n_clusters, cfg.cluster_capacity

        with stage("assign"):  # stragglers are assign work (times accumulate)
            assign = np.asarray(assign_d)[:n].astype(np.int64)
            assign, stragglers = _force_place_host(
                x, np.asarray(cents), assign, np.asarray(free_d).copy()
            )

        with stage("permute"):
            perm_d, counts = _permutation_from_assign(
                jnp.asarray(assign, jnp.int32), K, C
            )
            perm = np.asarray(perm_d).astype(np.int64)
            x_rows = _scatter_rows_host(x, perm, K, C)

        with stage("knn"):
            knn_local, knn_w = knn_fn(
                np.asarray(x_rows, np.float32).reshape(K, C, d), counts
            )
            jax.block_until_ready(knn_w)

        return (
            self._assemble(x, cents, x_rows, perm, counts, knn_local, knn_w),
            stragglers,
        )

    def _build_local(self, x, stage):
        from repro.kernels import registry

        cfg = self.cfg
        n = x.shape[0]
        K, C, k = cfg.n_clusters, cfg.cluster_capacity, cfg.n_neighbors
        block = cfg.build_block_rows
        key = jax.random.key(cfg.seed)
        xd = jnp.asarray(x)

        with stage("kmeans"):
            cents = km.kmeans_centroids(
                key,
                xd,
                K,
                n_iters=cfg.kmeans_iters,
                tol=cfg.kmeans_tol,
                impl=self.impl,
                block=block,
            )
            jax.block_until_ready(cents)

        with stage("assign"):
            assign_d, free_d = _capacity_rounds_local(
                xd,
                cents,
                C,
                registry.resolve("pairwise", self.impl),
                max(1, min(block, n)),
                cfg.build_max_rounds,
                cfg.build_candidates,
            )

        def knn_fn(x_blocks_host, counts):
            valid = jnp.arange(C)[None, :] < counts[:, None]
            return batched_cluster_knn(
                jnp.asarray(x_blocks_host), valid, k, self.impl
            )

        return self._finish(x, cents, assign_d, free_d, stage, knn_fn)

    def _build_streamed(self, store, stage):
        """The out-of-core build: every §3.2 stage consumes the corpus as a
        double-buffered stream of ``cfg.resolved_chunk_rows()``-row chunks
        (``repro.data.store.stream_chunks`` → ``data/loader.py``'s
        ``Prefetcher``), so peak host RSS is O(chunk + K·D) — plus the
        O(N·k) kNN graph that *is* the product — instead of O(N·D).
        Device state adds the O(N·R) candidate cache of the capacity
        assignment (R = ``cfg.build_candidates``; the full (N, D) never
        lands anywhere). When the input store is disk-backed the permuted
        cluster-major ``x_rows`` is scattered straight into a disk-backed
        sharded store (dtype ``cfg.store_dtype``) as the stream passes.

        Chunk boundaries depend only on (N, chunk_rows), never on the
        store's native shard layout, so a sharded/memmap store and an
        in-memory array holding the same rows build bit-identical indices.
        """
        from repro.data.store import ArrayStore, stream_chunks
        from repro.index.kmeans import _pad_chunk
        from repro.kernels import registry

        cfg = self.cfg
        n, d = store.shape
        K, C, k = cfg.n_clusters, cfg.cluster_capacity, cfg.n_neighbors
        chunk = max(1, min(cfg.resolved_chunk_rows(), n))
        blk = max(1, min(cfg.build_block_rows, chunk))
        impl = registry.resolve("pairwise", self.impl)
        key = jax.random.key(cfg.seed)

        with stage("kmeans"):
            cents = km.kmeans_centroids_streamed(
                key,
                store,
                K,
                chunk_rows=chunk,
                n_iters=cfg.kmeans_iters,
                tol=cfg.kmeans_tol,
                impl=self.impl,
                block=cfg.build_block_rows,
            )
            jax.block_until_ready(cents)

        with stage("assign"):
            r = min(cfg.build_candidates, K)
            n_pad = -(-n // chunk) * chunk
            cand_idx = jnp.zeros((n_pad, r), jnp.int32)
            cand_d2 = jnp.full((n_pad, r), jnp.inf, jnp.float32)
            for s, ch in stream_chunks(store, chunk):
                xb, _w = _pad_chunk(ch, chunk)
                cand_idx, cand_d2 = _cand_write_chunk(
                    cand_idx,
                    cand_d2,
                    jnp.asarray(xb),
                    jnp.int32(s),
                    cents,
                    cfg.build_candidates,
                    impl,
                    blk,
                )
            assign_d, free_d = _capacity_rounds_cached(
                cand_idx, cand_d2, K, C, cfg.build_max_rounds, n
            )
            assign = np.asarray(assign_d)[:n].astype(np.int64)
            assign, stragglers = _force_place_host(
                store, np.asarray(cents), assign, np.asarray(free_d).copy()
            )

        with stage("permute"):
            perm_d, counts = _permutation_from_assign(
                jnp.asarray(assign, jnp.int32), K, C
            )
            perm = np.asarray(perm_d).astype(np.int64)
            if store.path is not None:  # disk in → disk out
                x_rows = _spill_sharded_scatter(
                    store, perm, K * C, d,
                    _resolve_spill_dir(cfg, store), cfg.store_dtype, chunk,
                    max_shards=cfg.store_max_shards,
                )
            else:  # in-memory store: scatter per chunk into one host buffer
                buf = np.zeros((K * C, d), np.float32)
                for s, ch in store.iter_chunks(chunk):
                    buf[perm[s : s + ch.shape[0]]] = ch
                x_rows = buf

        with stage("knn"):
            counts_h = np.asarray(counts)
            kc = max(1, chunk // C)
            knn_local = np.empty((K, C, k), np.int32)
            knn_w = np.empty((K, C, k), np.float32)
            x_rows_store = x_rows if store.path is not None else ArrayStore(x_rows)
            for s, blk_rows in stream_chunks(x_rows_store, kc * C):
                c0, nb = s // C, blk_rows.shape[0] // C
                valid = (
                    np.arange(C)[None, :] < counts_h[c0 : c0 + nb, None]
                )
                idxb, wb = batched_cluster_knn(
                    jnp.asarray(blk_rows.reshape(nb, C, d)),
                    jnp.asarray(valid),
                    k,
                    self.impl,
                )
                knn_local[c0 : c0 + nb] = np.asarray(idxb)
                knn_w[c0 : c0 + nb] = np.asarray(wb)

        return (
            self._assemble(store, cents, x_rows, perm, counts, knn_local, knn_w),
            stragglers,
        )

    def _build_sharded(self, x, mesh, stage):
        from repro.kernels import registry

        cfg = self.cfg
        n, d = x.shape
        K, C, k = cfg.n_clusters, cfg.cluster_capacity, cfg.n_neighbors
        block = cfg.build_block_rows
        n_dev = mesh.shape[BUILD_AXIS]
        key = jax.random.key(cfg.seed)

        # pad rows up to the device count; padding never enters any statistic
        n_pad = -(-n // n_dev) * n_dev
        xp = x if n_pad == n else np.concatenate(
            [x, np.zeros((n_pad - n, d), x.dtype)]
        )
        row_sh = NamedSharding(mesh, P(BUILD_AXIS, None))
        xd = jax.device_put(jnp.asarray(xp), row_sh)

        with stage("kmeans"):
            cents = km.kmeans_fit_sharded(
                key,
                xd,
                K,
                mesh,
                BUILD_AXIS,
                n_iters=cfg.kmeans_iters,
                tol=cfg.kmeans_tol,
                impl=self.impl,
                block=block,
                n_real=n if n_pad != n else None,
            )
            jax.block_until_ready(cents)

        with stage("assign"):
            assign_d, free_d = _capacity_rounds_sharded(
                mesh,
                xd,
                cents,
                C,
                registry.resolve("pairwise", self.impl),
                block,
                cfg.build_max_rounds,
                cfg.build_candidates,
                n,
            )

        def knn_fn(x_blocks_host, counts):
            # device_put from host inside cluster_knn_batch_sharded moves
            # each device only its own cluster blocks — the full (K·C, D)
            # never lands on one device
            return cluster_knn_batch_sharded(
                mesh, BUILD_AXIS, x_blocks_host, counts, k, self.impl
            )

        return self._finish(x, cents, assign_d, free_d, stage, knn_fn)

    def _build_distributed(self, store, mesh, stage):
        """``_build_sharded``'s collective program with per-process data
        movement: each process reads only the contiguous row ranges its own
        devices shard (never all N rows), assembles the global (N_pad, D)
        via ``jax.make_array_from_single_device_arrays``, and the kmeans /
        assign / kNN collectives (one psum, one all_gather per round) span
        the whole ``jax.distributed`` mesh. On a single process this is the
        sharded build bit-for-bit (same jitted programs, same shardings) —
        which is exactly what makes a P-process run verifiable against a
        1-process P-device run.

        The cluster-major ``x_rows`` is spilled cooperatively: every
        process writes the shard files of its own devices' cluster blocks
        (``write_sharded(..., commit=False)`` at its row offset), then
        process 0 commits the metadata after a barrier. Requires a spill
        location all processes resolve identically (``cfg.checkpoint_dir``
        or a disk-backed input store); the kNN stage reuses the in-RAM
        per-device blocks, so the spill is never read back during the
        build.
        """
        from repro.core.strategy import fetch_global, sync_processes
        from repro.data.store import (
            ShardedStore,
            commit_sharded_meta,
            write_sharded,
        )
        from repro.kernels import registry

        cfg = self.cfg
        n, d = store.shape
        K, C, k = cfg.n_clusters, cfg.cluster_capacity, cfg.n_neighbors
        block = cfg.build_block_rows
        n_dev = mesh.shape[BUILD_AXIS]
        devs = list(mesh.devices.reshape(-1))
        pid = jax.process_index()
        n_proc = jax.process_count()
        key = jax.random.key(cfg.seed)

        n_pad = -(-n // n_dev) * n_dev
        rows_per = n_pad // n_dev
        row_sh = NamedSharding(mesh, P(BUILD_AXIS, None))

        with stage("place"):
            pieces = []
            for di, dev in enumerate(devs):
                if dev.process_index != pid:
                    continue
                lo = min(di * rows_per, n)
                hi = min(lo + rows_per, n)
                blk_rows = store.read(lo, hi)
                if blk_rows.shape[0] < rows_per:  # tail padding, one device
                    blk_rows = np.concatenate(
                        [blk_rows,
                         np.zeros((rows_per - blk_rows.shape[0], d), np.float32)]
                    )
                pieces.append(jax.device_put(jnp.asarray(blk_rows), dev))
            xd = jax.make_array_from_single_device_arrays(
                (n_pad, d), row_sh, pieces
            )

        with stage("kmeans"):
            cents = km.kmeans_fit_sharded(
                key,
                xd,
                K,
                mesh,
                BUILD_AXIS,
                n_iters=cfg.kmeans_iters,
                tol=cfg.kmeans_tol,
                impl=self.impl,
                block=block,
                n_real=n if n_pad != n else None,
            )
            jax.block_until_ready(cents)
        cents_h = fetch_global(cents)  # replicated → local copy everywhere

        with stage("assign"):
            assign_d, free_d = _capacity_rounds_sharded(
                mesh,
                xd,
                cents,
                C,
                registry.resolve("pairwise", self.impl),
                block,
                cfg.build_max_rounds,
                cfg.build_candidates,
                n,
            )
            # replicated outputs; the straggler pass runs identically on
            # every process (deterministic host math over shared inputs)
            assign = fetch_global(assign_d)[:n].astype(np.int64)
            assign, stragglers = _force_place_host(
                store, cents_h, assign, fetch_global(free_d).copy()
            )

        with stage("permute"):
            perm_d, counts = _permutation_from_assign(
                jnp.asarray(assign, jnp.int32), K, C
            )
            perm = np.asarray(perm_d).astype(np.int64)
            counts_h = np.asarray(counts)
            # gather each local device's cluster blocks from the store:
            # device di owns clusters [di·K/n_dev, (di+1)·K/n_dev), i.e.
            # x_rows rows [di·rps, (di+1)·rps) with rps = (K/n_dev)·C
            Kl = K // n_dev
            rps = Kl * C
            local = [di for di, dev in enumerate(devs)
                     if dev.process_index == pid]
            blocks = []
            for di in local:
                lo = di * rps
                sel = (perm >= lo) & (perm < lo + rps)
                src = np.flatnonzero(sel)
                xloc = np.zeros((rps, d), np.float32)
                xloc[perm[src] - lo] = store.read_rows(src)
                blocks.append(xloc)
            if n_proc > 1:
                if not (cfg.checkpoint_dir or store.path):
                    raise ValueError(
                        "distributed build: the x_rows spill needs a "
                        "location every process resolves identically — "
                        "set cfg.checkpoint_dir or build from a "
                        "disk-backed store (the temp-dir fallback differs "
                        "per process)"
                    )
                spill_dir = _resolve_spill_dir(cfg, store)
                for di, xloc in zip(local, blocks):
                    write_sharded(
                        [xloc],
                        spill_dir,
                        rows_per_shard=rps,
                        dtype=cfg.store_dtype,
                        row_offset=di * rps,
                        total_rows=K * C,
                        commit=False,
                    )
                sync_processes("x-rows-spill")
                if pid == 0:
                    commit_sharded_meta(
                        spill_dir, K * C, d,
                        rows_per_shard=rps, dtype=cfg.store_dtype,
                    )
                sync_processes("x-rows-commit")
                x_rows = ShardedStore(spill_dir)
            else:
                x_rows = np.concatenate(blocks)  # (K·C, D) host, like sharded

        with stage("knn"):
            blk_sh = NamedSharding(mesh, P(BUILD_AXIS, None, None))
            xb = jax.make_array_from_single_device_arrays(
                (K, C, d),
                blk_sh,
                [jax.device_put(jnp.asarray(xloc.reshape(Kl, C, d)), devs[di])
                 for di, xloc in zip(local, blocks)],
            )
            knn_local_d, knn_w_d = cluster_knn_batch_sharded(
                mesh, BUILD_AXIS, xb, counts_h, k, self.impl
            )
            knn_local = fetch_global(knn_local_d)
            knn_w = fetch_global(knn_w_d)

        return (
            self._assemble(
                store, cents_h, x_rows, perm, counts_h, knn_local, knn_w
            ),
            stragglers,
        )
