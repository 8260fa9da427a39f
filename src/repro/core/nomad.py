"""NOMAD Projection driver (paper §3 end-to-end).

``make_step_fn`` builds the one SGD step over a cluster-major block of
positions and ``scan_steps`` the one epoch body: the single-device fit
(the block is everything), the partial refine (heads from a cell subset)
and the ``shard_map`` path (the block is this shard's cells, the means
exchanged) all run them. All index structures come from
:mod:`repro.index.ann`.

Method selection:
* ``"nomad"``  — Eq. 3: remote cells via means (M̃), own cell sampled (M).
* ``"infonc"`` — Eq. 2: the InfoNC-t-SNE baseline; all negatives drawn
  uniformly from the full support (single-device only — this is exactly the
  non-factorising loss the paper is working around).

Sampling conventions (paper §3.3): heads i uniform over points (uniform
marginal P_i); noise tails uniform over points (uniform ξ); |M| = n_noise.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from typing import TYPE_CHECKING

from repro.configs.base import NomadConfig
from repro.core import losses
from repro.core.pca import pca_init
from repro.kernels import registry

if TYPE_CHECKING:  # runtime import is lazy (repro.index imports repro.core)
    from repro.index.ann import AnnIndex


# ---------------------------------------------------------------------------
# Stage names of the fit step
# ---------------------------------------------------------------------------

# ``jax.named_scope`` names that split every op of an epoch's step into the
# fit's stages; a profiler trace carries them in each op's ``op_name``. They
# are metadata only: the compiled program is the same op for op without them.
SCOPE_SAMPLE = "nomad_sample"  # heads, negatives, kNN row lookups, step key and lr
SCOPE_GATHER = "nomad_gather"  # θ rows of heads, positives and negatives
SCOPE_LOSS = "nomad_loss"  # the loss and its gradient (the fused kernel)
SCOPE_SCATTER = "nomad_scatter"  # the sparse SGD update of θ
SCOPE_MEANS = "nomad_means"  # the per-cell means refresh (and its exchange)


# ---------------------------------------------------------------------------
# Sampling helpers (cluster-major layout)
# ---------------------------------------------------------------------------


def sample_points(key, n: int, cum_counts: jax.Array, capacity: int):
    """n uniform valid points. Returns (rows, cluster_ids) — both (n,)."""
    total = cum_counts[-1]
    u = jax.random.randint(key, (n,), 0, total)
    cluster = jnp.searchsorted(cum_counts, u, side="right").astype(jnp.int32)
    start = jnp.where(cluster > 0, cum_counts[cluster - 1], 0)
    slot = u - start
    return cluster * capacity + slot, cluster


def sample_in_cluster(key, cluster_ids: jax.Array, counts: jax.Array, capacity: int, s: int):
    """(B,) cluster ids → (B, s) uniform valid rows within each cluster."""
    B = cluster_ids.shape[0]
    c = counts[cluster_ids]  # (B,)
    u = jax.random.uniform(key, (B, s))
    slot = jnp.floor(u * c[:, None]).astype(jnp.int32)
    slot = jnp.minimum(slot, (c - 1)[:, None].astype(jnp.int32))
    return cluster_ids[:, None] * capacity + slot


def local_means(theta_rows: jax.Array, counts: jax.Array, capacity: int):
    """Masked per-cluster means of positions: (K·C, d) → (K, d)."""
    K = counts.shape[0]
    th = theta_rows.reshape(K, capacity, -1).astype(jnp.float32)
    valid = (jnp.arange(capacity)[None, :] < counts[:, None]).astype(jnp.float32)
    sums = jnp.sum(th * valid[:, :, None], axis=1)
    return sums / jnp.maximum(counts.astype(jnp.float32), 1.0)[:, None]


# ---------------------------------------------------------------------------
# The SGD step
# ---------------------------------------------------------------------------


def sgd_update(theta, loss_fn, rows, pos_rows, neg_rows, th_i, th_pos, th_neg, lr, impl):
    """The loss of a step and its sparse SGD update of θ: only the rows the
    step touched move (reaction forces included). The update of the heads,
    positives and negatives is one ``"row_add"`` registry kernel: on a TPU
    the pairs sorted by row and added in one sweep over θ, in place of a
    scatter-add that serialises on repeated rows. Returns (theta, loss)."""
    with jax.named_scope(SCOPE_LOSS):
        loss, (g_i, g_pos, g_neg) = jax.value_and_grad(loss_fn, argnums=(0, 1, 2))(
            th_i, th_pos, th_neg
        )
    with jax.named_scope(SCOPE_SCATTER):
        d = theta.shape[1]
        all_rows = jnp.concatenate([rows, pos_rows.reshape(-1), neg_rows.reshape(-1)])
        grads = jnp.concatenate([g_i, g_pos.reshape(-1, d), g_neg.reshape(-1, d)])
        theta = registry.dispatch("row_add", theta, all_rows, -lr * grads, impl=impl)
    return theta, loss


def affected_heads(cfg: NomadConfig):
    """Head sampler of a partial refine: heads uniform over the valid rows
    of the cells ``idx["aff_cells"]`` (cumulative counts
    ``idx["aff_cum_counts"]``), as global rows and cell ids."""
    B, C = cfg.batch_size, cfg.cluster_capacity

    def heads(key, idx):
        rows, a = sample_points(key, B, idx["aff_cum_counts"], C)
        cell = idx["aff_cells"][a]
        # the a-th affected cell's rows, moved to that cell's place in θ
        return rows + (cell - a) * C, cell

    return heads


def make_step_fn(
    cfg: NomadConfig,
    *,
    method: str = "nomad",
    heads: Optional[Callable] = None,
    n_total: Optional[int] = None,
):
    """Build the fit step ``step(theta, idx, cells, lr, key, row_off=0) ->
    (theta, loss)``, the one step of every strategy.

    ``idx`` is a dict of index arrays over ``theta``'s rows: the whole
    layout on one device, a shard's own cells on a mesh. ``heads(key, idx)
    -> (rows, cell)`` draws the step's heads and their cells in ``idx``'s
    numbering; the default draws them uniformly over every valid row
    (``idx["cum_counts"]``), :func:`affected_heads` over a partial refine's
    cells. ``cells`` is ``(means, counts, cell_off)``, held fixed over a
    dispatch: the means the loss repels from, the cell sizes that weight
    them, and the offset of ``idx``'s cell ids in that numbering (0 on one
    device; see ``core/distributed.py:gather_cells`` on a mesh).
    ``row_off`` is subtracted from the kNN ids, which ``place_index`` keeps
    global on a mesh.

    The NOMAD branch runs the whole per-step loss through the fused
    ``"nomad_step"`` registry kernel (via :func:`losses.nomad_loss`):
    distances, Cauchy weights, attraction and the online-accumulated
    repulsive mass are one tiled pass with a custom VJP on TPU/GPU, and
    the bit-equal legacy multi-pass composition on CPU (``impl="jnp"``).
    ``cfg.kernel_impl`` / ``REPRO_KERNELS`` select per run.
    """
    n_total = n_total or cfg.n_points
    B, S, Mn = cfg.batch_size, cfg.n_exact_negatives, cfg.n_noise
    C = cfg.cluster_capacity
    if heads is None:

        def heads(key, idx):
            return sample_points(key, B, idx["cum_counts"], C)

    def step(theta, idx, cells, lr, key, row_off=0):
        means, counts, cell_off = cells
        with jax.named_scope(SCOPE_SAMPLE):
            k_head, k_neg = jax.random.split(key)
            rows, cell = heads(k_head, idx)
            pos_rows = idx["knn_idx"][rows] - row_off  # (B, k)
            pos_w = idx["knn_w"][rows]  # (B, k)
            if method == "infonc":
                # Eq. 2 baseline: |M| noise tails uniform over the full support
                neg_rows, _ = sample_points(k_neg, B * Mn, idx["cum_counts"], C)
                neg_rows = neg_rows.reshape(B, Mn)
            else:
                neg_rows = sample_in_cluster(k_neg, cell, idx["counts"], C, S)
                own_cell = cell + cell_off
        with jax.named_scope(SCOPE_GATHER):
            th_i = theta[rows]
            th_pos = theta[pos_rows]
            th_neg = theta[neg_rows]

        if method == "infonc":

            def loss_fn(ti, tp, tn):
                return losses.infonc_tsne_loss(ti, tp, pos_w, tn)

        else:

            def loss_fn(ti, tp, tn):
                return losses.nomad_loss(
                    ti,
                    tp,
                    pos_w,
                    means,
                    counts,
                    own_cell,
                    tn,
                    n_noise=Mn,
                    n_total=n_total,
                    impl=cfg.resolved_kernel_impl(),
                )

        return sgd_update(
            theta, loss_fn, rows, pos_rows, neg_rows, th_i, th_pos, th_neg, lr,
            cfg.resolved_kernel_impl(),
        )

    return step


def scan_steps(step_fn, steps_per_epoch: int, theta, idx, cells, lr0, lr1, key, row_off=0):
    """The epoch body of every strategy: ``steps_per_epoch`` steps in one
    ``lax.scan``, against ``cells`` computed once at the dispatch's start
    and held fixed (stop-gradient) over it, as in Fig. 2. Step ``t`` runs
    at lr ``lr0 + (lr1 - lr0)·t/steps_per_epoch`` with key
    ``fold_in(key, t)``. Returns (θ, the mean step loss)."""

    def body(theta, t):
        with jax.named_scope(SCOPE_SAMPLE):
            lr = lr0 + (lr1 - lr0) * (t / steps_per_epoch)
            step_key = jax.random.fold_in(key, t)
        return step_fn(theta, idx, cells, lr, step_key, row_off)

    theta, losses_ = jax.lax.scan(body, theta, jnp.arange(steps_per_epoch))
    with jax.named_scope(SCOPE_LOSS):
        return theta, jnp.mean(losses_)


def make_epoch_fn(cfg: NomadConfig, step_fn, steps_per_epoch: int):
    """jit-compiled one-device epoch: the means of every cell once, then
    :func:`scan_steps`."""
    C = cfg.cluster_capacity

    @jax.jit
    def epoch(theta, idx, lr0, lr1, epoch_key):
        with jax.named_scope(SCOPE_MEANS):
            means = local_means(theta, idx["counts"], C)
            counts_f = idx["counts"].astype(jnp.float32)
        return scan_steps(
            step_fn, steps_per_epoch, theta, idx, (means, counts_f, 0), lr0, lr1, epoch_key
        )

    return epoch


# ---------------------------------------------------------------------------
# Fit driver — one estimator, every scale (execution lives in core/strategy)
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class FitResult:
    embedding: np.ndarray  # (N, out_dim) in the original point order
    index: "AnnIndex"
    losses: list
    wall_time_s: float
    epoch_times: list
    # execution provenance
    strategy: str = "local"
    n_shards: int = 1
    mesh_shape: Optional[tuple] = None
    mesh_axes: Optional[tuple] = None
    # index-build provenance: "local" | "sharded" (IndexBuilder ran),
    # "cache" (checkpoint_dir/index.npz reused), "provided" (index= argument)
    index_build_strategy: str = ""
    index_build_s: float = 0.0
    # checkpoint/resume provenance
    start_epoch: int = 0
    resumed: bool = False
    checkpoint_dir: str = ""
    checkpoint_epochs: list = dataclasses.field(default_factory=list)
    # multi-process provenance (jax.distributed; 1/0 single-process)
    process_count: int = 1
    process_index: int = 0


@dataclasses.dataclass
class PartialFitResult:
    """What one :meth:`NomadProjection.partial_fit` call produced."""

    embedding: np.ndarray  # (N_old + M, out_dim) in original ∥ appended order
    index: "AnnIndex"  # grown index (K' cells, capacity unchanged)
    n_new: int  # appended rows admitted this call
    n_points: int  # total rows after the append
    losses: list  # refinement epoch mean losses
    wall_time_s: float = 0.0
    epoch_times: list = dataclasses.field(default_factory=list)
    refine_epochs: int = 0
    # admission provenance
    affected_cells: np.ndarray = None  # (A,) cells placed into / re-seeded
    n_split_cells: int = 0  # cells that overflowed and were re-seeded
    n_new_cells: int = 0  # layout growth (K' - K)
    stage_s: dict = dataclasses.field(default_factory=dict)
    # lineage provenance (empty when cfg.checkpoint_dir is unset)
    version: str = ""
    parent_version: str = ""
    checkpoint_dir: str = ""  # the self-contained version directory


def _config_digest(cfg: NomadConfig) -> dict:
    """The config fields a checkpoint must agree on to resume bit-exactly."""
    d = dataclasses.asdict(cfg)
    for transient in (
        "checkpoint_dir",
        "checkpoint_every_epochs",
        "use_pallas",
        "kernel_impl",
        # serve-side knobs never change what a fit computes
        "serve_strategy",
        "serve_microbatch",
        "serve_knn_block",
        "transform_steps",
        "transform_lr",
        # incremental-growth knob: changing it never alters the base fit
        "partial_refine_epochs",
    ):
        d.pop(transient, None)
    return d


def prepare_inputs(
    x, dim: Optional[int] = None, caller: str = "fit", chunk_rows: int = 0
):
    """The one validation/dtype-coercion gate for ``fit`` AND ``transform``.

    Integer and half-precision inputs are upcast to float32 (the pipeline's
    native dtype); float64 is *rejected* rather than silently halved so the
    precision loss stays a caller decision; NaN/Inf fail with the same
    actionable error everywhere.

    Out-of-core inputs — an :class:`repro.data.store.EmbeddingStore`, an
    ``np.memmap``, or a path to a ``.npy``/sharded-store directory — are
    validated **per chunk** (``chunk_rows`` rows at a time, default 8192)
    and returned as a store the caller streams from: neither the float32
    cast nor the NaN scan ever allocates a full-size temporary. In-memory
    arrays keep the resident behaviour and return an ``np.ndarray``.
    """
    import os as _os

    from repro.data.store import DEFAULT_CHUNK_ROWS, as_store, is_store

    if (
        is_store(x)
        or isinstance(x, np.memmap)
        or isinstance(x, (str, _os.PathLike))
    ):
        st = as_store(x)
        if st.dtype_name == "float64":
            raise ValueError(
                f"{caller}: x is float64 — the whole pipeline (index build, "
                "kernels, serving) runs float32; pass x.astype(np.float32) "
                "explicitly so the precision cut is your call, not a silent one"
            )
        if dim is not None and st.dim != dim:
            raise ValueError(
                f"{caller}: x has dim {st.dim} but the fitted map expects "
                f"dim {dim} — queries must live in the training feature space"
            )
        n_bad = 0
        for _s, chunk in st.iter_chunks(
            chunk_rows if chunk_rows > 0 else DEFAULT_CHUNK_ROWS
        ):
            finite = np.isfinite(chunk)
            if not finite.all():
                n_bad += int(chunk.size - finite.sum())
        if n_bad:
            raise ValueError(
                f"{caller}: x contains {n_bad} non-finite values (NaN/Inf) — "
                "clean or impute before projecting; a single NaN poisons the "
                "k-means statistics and every distance downstream"
            )
        return st

    x = np.asarray(x)
    if x.ndim != 2:
        raise ValueError(
            f"{caller}: expected a 2-D (n_points, dim) array, got shape {x.shape}"
        )
    if x.dtype == np.float64:
        raise ValueError(
            f"{caller}: x is float64 — the whole pipeline (index build, "
            "kernels, serving) runs float32; pass x.astype(np.float32) "
            "explicitly so the precision cut is your call, not a silent one"
        )
    if x.dtype != np.float32:
        x = x.astype(np.float32)
    if not np.isfinite(x).all():
        n_bad = int(np.size(x) - np.isfinite(x).sum())
        raise ValueError(
            f"{caller}: x contains {n_bad} non-finite values (NaN/Inf) — "
            "clean or impute before projecting; a single NaN poisons the "
            "k-means statistics and every distance downstream"
        )
    if dim is not None and x.shape[1] != dim:
        raise ValueError(
            f"{caller}: x has dim {x.shape[1]} but the fitted map expects "
            f"dim {dim} — queries must live in the training feature space"
        )
    return x


class NomadProjection:
    """The unified scikit-style front end: ``NomadProjection(cfg).fit(x)``.

    One estimator covers every scale. ``strategy`` (ctor arg, default
    ``cfg.strategy``) selects how epochs execute — ``"auto"`` resolves from
    ``jax.devices()``; ``"local"`` / ``"sharded"`` / ``"hierarchical"`` force
    a mode; an :class:`repro.core.strategy.ExecutionStrategy` instance plugs
    in a custom one. All paths return the same enriched :class:`FitResult`.
    The ANN index is built the same way: ``cfg.build_strategy`` resolves an
    :class:`repro.index.build.IndexBuilder` over the training mesh's device
    pool, so the §3.2 pipeline is device-resident (and sharded) before the
    first epoch runs; ``FitResult.index_build_strategy`` /
    ``index_build_s`` record what happened.

    Progress streams through the structured event API
    (:class:`repro.core.strategy.FitCallbacks`): ``on_epoch_start``,
    ``on_epoch_end`` (with the *unpermuted* ``(N, out_dim)`` embedding),
    ``on_means_refresh``, ``on_checkpoint``.

    With ``cfg.checkpoint_dir`` set, θ is checkpointed every
    ``cfg.checkpoint_every_epochs`` epochs (atomic commit; the ANN index is
    cached beside it), and a killed run continues with
    ``NomadProjection.from_checkpoint(dir).fit(x)`` — same fold_in schedule,
    so the result matches an uninterrupted run.

    A fitted (or checkpoint-loaded) estimator also serves: ``transform(q)``
    places unseen rows on the frozen map (``repro.serve``) without touching
    a single fitted coordinate — ``from_checkpoint(dir).transform(q)``
    needs no access to the training array at all.
    """

    def __init__(
        self,
        cfg: NomadConfig,
        method: Optional[str] = None,
        *,
        strategy=None,
        mesh=None,
        shard_axes=None,
        pod_axis=None,
    ):
        self.cfg = cfg
        self.method = method or cfg.method
        self.strategy = strategy if strategy is not None else cfg.strategy
        self.mesh = mesh
        self.shard_axes = shard_axes
        self.pod_axis = pod_axis
        self._resume_default = False
        self._fit_result: Optional[FitResult] = None
        self._frozen = None
        self._server = None

    # -- constructors ----------------------------------------------------------

    @classmethod
    def from_checkpoint(
        cls, checkpoint_dir: str, cfg: Optional[NomadConfig] = None, **overrides
    ) -> "NomadProjection":
        """Rebuild the estimator a checkpoint directory was written by.

        The returned estimator resumes by default: ``.fit(x)`` restores the
        latest θ + epoch and continues to ``cfg.n_epochs``. Pass field
        ``overrides`` (or a full ``cfg``) to alter the continuation.
        """
        from repro.checkpoint.checkpointer import load_metadata

        meta = load_metadata(checkpoint_dir)
        if cfg is None:
            if "config" not in meta:
                raise ValueError(
                    f"checkpoint under {checkpoint_dir} has no stored config "
                    "(written by a pre-unified-API launcher?) — pass cfg= "
                    "explicitly to resume it"
                )
            stored = dict(meta["config"])
            stored.update(checkpoint_dir=checkpoint_dir, **overrides)
            cfg = NomadConfig(**stored)
        est = cls(cfg, method=meta.get("method"))
        est._resume_default = True
        return est

    # -- the one fit ----------------------------------------------------------

    def fit(
        self,
        x: np.ndarray,
        index: "Optional[AnnIndex]" = None,
        *,
        callbacks=None,
        resume: Optional[bool] = None,
        theta0=None,
    ) -> FitResult:
        """Fit the map. ``resume=True`` continues from ``cfg.checkpoint_dir``.

        ``x`` may be an in-memory array **or** a disk-backed corpus — an
        :class:`repro.data.store.EmbeddingStore`, an ``np.memmap``, or a
        path to a ``.npy`` / sharded-store directory. Store inputs stream
        through the whole pipeline (per-chunk validation, streamed §3.2
        index build, streamed PCA init); the epoch loop itself touches only
        θ and the O(N·k) index arrays, never the corpus, so a fit from disk
        keeps host RSS at O(chunk + K·D + N·k). With the same
        ``cfg.chunk_rows`` set, fit(store) and fit(ndarray) of identical
        rows are bit-equal (chunking pins the f32 accumulation order).

        ``callbacks`` is a :class:`repro.core.strategy.FitCallbacks` (or a
        sequence of them) that receives the fit's events.
        """
        import os
        import warnings

        from repro.core.strategy import (
            CheckpointEvent,
            EpochEndEvent,
            EpochStartEvent,
            MeansRefreshEvent,
            as_callbacks,
            resolve_strategy,
            sync_processes,
        )
        from repro.index.ann import (
            data_fingerprint,
            index_cache_path,
            load_index,
            save_index,
        )
        from repro.index.build import IndexBuilder

        cfg = self.cfg
        x = prepare_inputs(x, caller="fit", chunk_rows=cfg.chunk_rows)
        t0 = time.time()
        events = as_callbacks(callbacks)
        resume = self._resume_default if resume is None else resume
        ckdir = cfg.checkpoint_dir
        if resume and not ckdir:
            raise ValueError("resume=True needs cfg.checkpoint_dir to be set")

        # ---- index: argument > on-disk cache > fresh build --------------------
        index_cache = index_cache_path(ckdir) if ckdir else ""
        cache_stale = False
        build_strategy, build_s = "provided", 0.0
        if index is None and index_cache and os.path.exists(index_cache):
            cached = load_index(index_cache)
            # a stale cache (checkpoint_dir reused across datasets) must not
            # silently replace the data the caller passed in — neither by
            # shape nor, for same-shape datasets, by content (fingerprint of
            # a deterministic row sample)
            if cached.n_points != x.shape[0] or cached.x_rows.shape[1] != x.shape[1]:
                cache_stale = True
                warnings.warn(
                    f"ignoring index cache {index_cache}: built for "
                    f"({cached.n_points}, {cached.x_rows.shape[1]}) data, "
                    f"got {x.shape} — rebuilding"
                )
            elif cached.fingerprint and cached.fingerprint != data_fingerprint(x):
                cache_stale = True
                warnings.warn(
                    f"ignoring index cache {index_cache}: same shape but "
                    f"different data content (fingerprint mismatch) — rebuilding"
                )
            else:
                index = cached
                build_strategy = "cache"
        if index is None:
            builder = IndexBuilder(cfg, mesh=self.mesh)
            index = builder.build(x)
            build_strategy = builder.report.strategy
            build_s = builder.report.total_s
        if index_cache and (cache_stale or not os.path.exists(index_cache)):
            # multi-process: every process built the identical index via the
            # cross-process collectives — one writer, everyone waits for it
            if jax.process_index() == 0:
                os.makedirs(ckdir, exist_ok=True)
                save_index(index, index_cache)
            sync_processes("index-cache")

        # ---- θ: resume from checkpoint > warm start > fresh init --------------
        start_epoch, resumed = 0, False
        if resume:
            from repro.checkpoint import Checkpointer, latest_step

            if latest_step(ckdir) is not None:
                shape = (index.n_clusters * index.capacity, cfg.out_dim)
                skeleton = {"theta": np.zeros(shape, np.float32)}
                tree, meta = Checkpointer(ckdir).restore(skeleton)
                theta0 = tree["theta"]
                start_epoch = int(meta["epoch"]) + 1
                resumed = True
                stored = meta.get("config")
                if stored is not None and {
                    k: v for k, v in stored.items()
                    if k in _config_digest(cfg)
                } != _config_digest(cfg):
                    warnings.warn(
                        "resuming with a config that differs from the one the "
                        "checkpoint was written with — the continued run will "
                        "not match an uninterrupted one"
                    )
        if theta0 is None:
            theta0 = self._init_theta(x, index)

        # ---- strategy ------------------------------------------------------------
        strategy = resolve_strategy(
            self.strategy,
            cfg,
            method=self.method,
            mesh=self.mesh,
            shard_axes=self.shard_axes,
            pod_axis=self.pod_axis,
        )
        theta = strategy.prepare(cfg, self.method, index, theta0)

        ckpt = None
        multiprocess = jax.process_count() > 1
        if ckdir:
            from repro.checkpoint import Checkpointer

            # multi-process: process 0 writes synchronously and everyone
            # barriers on the commit — the async writer thread would race
            # the barrier's collectives
            ckpt = Checkpointer(
                ckdir,
                n_shards=strategy.n_shards,
                keep=3,
                async_save=not multiprocess,
                primary=jax.process_index() == 0,
            )
        every = max(1, cfg.checkpoint_every_epochs)

        # ---- the one epoch loop ---------------------------------------------------
        lr0 = cfg.resolved_lr0()
        key = jax.random.key(cfg.seed + 1)
        losses_, epoch_times, checkpoint_epochs = [], [], []
        try:
            for e in range(start_epoch, cfg.n_epochs):
                te = time.time()
                f0 = 1.0 - e / cfg.n_epochs
                f1 = 1.0 - (e + 1) / cfg.n_epochs
                if events is not None:
                    events.on_epoch_start(
                        EpochStartEvent(e, cfg.n_epochs, lr0 * f0, lr0 * f1, strategy.name)
                    )
                theta, mloss = strategy.run_epoch(
                    theta, e, lr0 * f0, lr0 * f1, jax.random.fold_in(key, e)
                )
                losses_.append(mloss)
                epoch_times.append(time.time() - te)

                if ckpt is not None and ((e + 1) % every == 0 or e == cfg.n_epochs - 1):
                    # strategy.fetch is collective: every process gathers the
                    # full θ even though only the primary writes it
                    ckpt.save(
                        e,
                        {"theta": strategy.fetch(theta)},
                        sharded_keys=("theta",),
                        metadata={
                            "epoch": e,
                            "config": dataclasses.asdict(cfg),
                            "method": self.method,
                            "strategy": strategy.name,
                            # snapshot: the async writer must not see later appends
                            "losses": list(losses_),
                        },
                    )
                    if multiprocess:
                        # no process races past a commit its peers rely on
                        sync_processes(f"ckpt-{e}")
                    checkpoint_epochs.append(e)
                    if events is not None:
                        events.on_checkpoint(
                            CheckpointEvent(e, e, ckdir, strategy.n_shards)
                        )
                if events is not None:
                    events.on_means_refresh(
                        MeansRefreshEvent(e, strategy.refreshes_per_epoch(), strategy.name)
                    )
                    emb_e = (
                        index.unpermute(strategy.fetch(theta))
                        if events.wants_embedding
                        else None
                    )
                    events.on_epoch_end(
                        EpochEndEvent(
                            e, cfg.n_epochs, mloss, epoch_times[-1], strategy.name, emb_e
                        )
                    )
        finally:
            if ckpt is not None:
                ckpt.wait()  # commit the in-flight save even on interruption

        emb = index.unpermute(strategy.fetch(theta))
        meta = strategy.describe()
        result = FitResult(
            embedding=emb,
            index=index,
            losses=losses_,
            wall_time_s=time.time() - t0,
            epoch_times=epoch_times,
            strategy=meta["strategy"],
            n_shards=meta["n_shards"],
            mesh_shape=meta["mesh_shape"],
            mesh_axes=meta["mesh_axes"],
            index_build_strategy=build_strategy,
            index_build_s=build_s,
            start_epoch=start_epoch,
            resumed=resumed,
            checkpoint_dir=ckdir,
            checkpoint_epochs=checkpoint_epochs,
            process_count=meta["process_count"],
            process_index=meta["process_index"],
        )
        self._fit_result = result
        self._frozen = None  # a refit invalidates any cached frozen state
        self._server = None
        return result

    # -- incremental growth (append-only corpora) ------------------------------

    def _previous_state(self):
        """(index, theta_rows, parent_dir) of the map being grown.

        In-process fit state wins; otherwise the newest lineage version
        under ``cfg.checkpoint_dir`` (falling back to the root itself for
        pre-lineage checkpoints) — so ``from_checkpoint(root).partial_fit``
        needs **no access to the original corpus**: the previous rows come
        from the cached index's ``x_rows``.
        """
        from repro.checkpoint import MapLineage, latest_step, load_theta
        from repro.index.ann import index_cache_path, load_index

        cfg = self.cfg
        if self._fit_result is not None:
            index = self._fit_result.index
            theta_rows = np.zeros(
                (index.n_clusters * index.capacity, cfg.out_dim), np.float32
            )
            theta_rows[index.perm] = self._fit_result.embedding
            return index, theta_rows, ""
        if not cfg.checkpoint_dir:
            raise RuntimeError(
                "partial_fit needs a fitted map: call fit(x) first, or load "
                "one with NomadProjection.from_checkpoint(dir)"
            )
        lineage = MapLineage(cfg.checkpoint_dir)
        base = lineage.latest()
        base_dir = base.path if base is not None else cfg.checkpoint_dir
        import os

        cache = index_cache_path(base_dir)
        if not os.path.exists(cache) or latest_step(base_dir) is None:
            raise RuntimeError(
                f"partial_fit: {base_dir} holds no fitted map (need both "
                "index.npz and a step_*/ checkpoint) — run fit(x) with "
                "cfg.checkpoint_dir set first"
            )
        index = load_index(cache)
        theta_rows, _meta = load_theta(base_dir)
        return index, theta_rows, base_dir

    def partial_fit(
        self,
        new_x,
        *,
        callbacks=None,
        refine_epochs: Optional[int] = None,
        seed: Optional[int] = None,
    ) -> PartialFitResult:
        """Grow the fitted map in place with appended rows (no refit).

        Pipeline: **place** ``new_x`` on the frozen map via the serve path
        (initial positions + nearest-centroid target cells) → **admit**
        into capacity-bounded cells, re-seeding only cells that overflow
        (:mod:`repro.index.incremental`) → **patch** the in-cluster kNN
        graph and ``x_rows`` for affected cells only → **refine** with a
        few cheap epochs whose heads are restricted to the affected cells
        (:class:`repro.core.strategy.PartialRefineStrategy`) → **version**
        the artifacts: with ``cfg.checkpoint_dir`` set, a self-contained
        ``vN/`` directory (θ checkpoint + index cache) is written and
        recorded in the ``versions.json`` lineage, ready for
        ``MapRegistry.swap`` / ``FrozenMap.from_checkpoint``.

        Rows in cells the append never touches keep **bit-identical**
        positions; appending 0 rows is a true no-op (no artifact changes,
        no version written). Multi-process runs are not supported — grow
        on one process, serve the version anywhere.
        """
        import os

        from repro.core.strategy import (
            EpochEndEvent,
            EpochStartEvent,
            PartialRefineStrategy,
            as_callbacks,
        )

        if jax.process_count() > 1:
            raise NotImplementedError(
                "partial_fit is single-process: grow the map on one process "
                "and point peers/servers at the new lineage version"
            )
        cfg = self.cfg
        t0 = time.time()
        events = as_callbacks(callbacks)
        index, theta_rows, _base_dir = self._previous_state()
        if index.capacity != cfg.cluster_capacity:
            raise ValueError(
                f"partial_fit: index capacity {index.capacity} != "
                f"cfg.cluster_capacity {cfg.cluster_capacity} — partial_fit "
                "must run with the config the map was fitted with (capacity "
                "is a static layout property; it never changes on append)"
            )

        from repro.data.store import is_store

        new_x = prepare_inputs(
            new_x, dim=int(index.x_rows.shape[1]), caller="partial_fit"
        )
        if is_store(new_x):
            new_x = new_x.materialize()  # appends are batch-sized, not corpus-sized
        M = int(new_x.shape[0])
        n_old = index.n_points

        ckdir = cfg.checkpoint_dir
        lineage = None
        if ckdir:
            from repro.checkpoint import MapLineage

            lineage = MapLineage(ckdir)

        if M == 0:  # the no-op invariant: nothing changes, nothing is written
            latest = lineage.latest() if lineage is not None else None
            return PartialFitResult(
                embedding=index.unpermute(np.asarray(theta_rows)),
                index=index,
                n_new=0,
                n_points=n_old,
                losses=[],
                wall_time_s=time.time() - t0,
                refine_epochs=0,
                affected_cells=np.zeros((0,), np.int64),
                stage_s={},
                version=latest.name if latest is not None else "",
                parent_version=latest.name if latest is not None else "",
                checkpoint_dir="",
            )

        # ---- place: the frozen-transform serve path ---------------------------
        from repro.serve import FrozenMap, MapServer

        t_place = time.time()
        frozen = FrozenMap.from_index_theta(index, theta_rows, cfg)
        placed = MapServer(frozen).transform(
            np.asarray(new_x), seed=cfg.seed if seed is None else seed,
            return_neighbors=False,
        )
        stage_s = {"place": time.time() - t_place}

        # ---- version bookkeeping (dir must exist before a store spill) --------
        version_name, parent_name, version_dir = "", "", ""
        if lineage is not None:
            if not lineage.exists():
                # upgrade a pre-lineage checkpoint in place: the base fit
                # becomes v0 at the root
                lineage.record(
                    name="v0",
                    dirname=".",
                    parent="",
                    fingerprint=index.fingerprint,
                    n_points=n_old,
                    kind="fit",
                )
            parent_name = lineage.latest().name
            version_name = lineage.next_name()
            version_dir = os.path.join(ckdir, version_name)
            os.makedirs(version_dir, exist_ok=True)

        # ---- admit + patch (repro.index.incremental) --------------------------
        from repro.index.incremental import admit_and_patch

        spill_dir = None
        if is_store(index.x_rows):
            if version_dir:
                spill_dir = os.path.join(version_dir, "x_rows_store")
            else:
                import tempfile

                spill_dir = tempfile.mkdtemp(prefix="nomad-partial-spill-")
        upd = admit_and_patch(
            index,
            theta_rows,
            np.asarray(new_x),
            np.asarray(placed.cells),
            np.asarray(placed.embedding, np.float32),
            cfg,
            impl=cfg.resolved_kernel_impl(),
            spill_dir=spill_dir,
        )
        stage_s.update(upd.stage_s)

        # ---- refine: cheap epochs over affected cells only --------------------
        t_refine = time.time()
        refine_epochs = (
            cfg.partial_refine_epochs if refine_epochs is None else refine_epochs
        )
        losses_, epoch_times = [], []
        if refine_epochs > 0 and upd.affected_cells.size:
            strategy = PartialRefineStrategy(upd.affected_cells)
            theta = strategy.prepare(cfg, self.method, upd.index, upd.theta_rows)
            # start from the final fit epoch's lr scale — the equilibrium
            # regime the frozen rows were left in — annealed to 0 again
            lr_r = cfg.resolved_lr0() / max(cfg.n_epochs, 1)
            key = jax.random.fold_in(
                jax.random.key(cfg.seed + 11), upd.index.n_points
            )
            for e in range(refine_epochs):
                te = time.time()
                f0 = 1.0 - e / refine_epochs
                f1 = 1.0 - (e + 1) / refine_epochs
                if events is not None:
                    events.on_epoch_start(
                        EpochStartEvent(
                            e, refine_epochs, lr_r * f0, lr_r * f1, strategy.name
                        )
                    )
                theta, mloss = strategy.run_epoch(
                    theta, e, lr_r * f0, lr_r * f1, jax.random.fold_in(key, e)
                )
                losses_.append(mloss)
                epoch_times.append(time.time() - te)
                if events is not None:
                    emb_e = (
                        upd.index.unpermute(strategy.fetch(theta))
                        if events.wants_embedding
                        else None
                    )
                    events.on_epoch_end(
                        EpochEndEvent(
                            e, refine_epochs, mloss, epoch_times[-1],
                            strategy.name, emb_e,
                        )
                    )
            theta_new = strategy.fetch(theta)
        else:
            theta_new = np.asarray(upd.theta_rows)
        stage_s["refine"] = time.time() - t_refine

        # ---- version: self-contained dir + lineage entry ----------------------
        t_version = time.time()
        if lineage is not None:
            from repro.checkpoint import Checkpointer
            from repro.index.ann import index_cache_path, save_index

            ckpt = Checkpointer(version_dir, keep=2, async_save=False)
            ckpt.save(
                max(refine_epochs - 1, 0),
                {"theta": theta_new},
                metadata={
                    "epoch": max(refine_epochs - 1, 0),
                    "config": dataclasses.asdict(cfg),
                    "method": self.method,
                    "strategy": "partial",
                    "losses": list(losses_),
                    "parent_version": parent_name,
                },
            )
            ckpt.wait()
            save_index(upd.index, index_cache_path(version_dir))
            lineage.record(
                name=version_name,
                dirname=version_name,
                parent=parent_name,
                fingerprint=upd.index.fingerprint,
                n_points=upd.index.n_points,
                kind="partial_fit",
            )
            stage_s["version"] = time.time() - t_version

        emb = upd.index.unpermute(theta_new)
        result = PartialFitResult(
            embedding=emb,
            index=upd.index,
            n_new=M,
            n_points=upd.index.n_points,
            losses=losses_,
            wall_time_s=time.time() - t0,
            epoch_times=epoch_times,
            refine_epochs=refine_epochs,
            affected_cells=upd.affected_cells,
            n_split_cells=upd.n_split_cells,
            n_new_cells=upd.n_new_cells,
            stage_s=stage_s,
            version=version_name,
            parent_version=parent_name,
            checkpoint_dir=version_dir,
        )
        # the estimator now serves (and grows) the new version
        self._fit_result = FitResult(
            embedding=emb,
            index=upd.index,
            losses=losses_,
            wall_time_s=result.wall_time_s,
            epoch_times=epoch_times,
            strategy="partial",
            index_build_strategy="incremental",
            checkpoint_dir=version_dir,
        )
        self._frozen = None
        self._server = None
        return result

    def fit_transform(self, x: np.ndarray, **kwargs) -> np.ndarray:
        """``fit(...)`` and return just the ``(N, out_dim)`` embedding.

        Forwards through ``fit`` and therefore through the same
        :func:`prepare_inputs` validation gate ``transform`` uses —
        float64/NaN inputs fail with the same actionable error everywhere.
        """
        return self.fit(x, **kwargs).embedding

    # -- out-of-sample serving (repro.serve) -----------------------------------

    def map_server(self, **overrides):
        """The :class:`repro.serve.MapServer` this estimator serves from.

        Frozen state comes from the last ``fit`` when one ran in this
        process, else straight from ``cfg.checkpoint_dir`` (θ + cached
        index — **no training data needed**, the ``from_checkpoint``
        serving path). The config-default server is cached; passing
        ``overrides`` (``strategy=``, ``microbatch=``, ``mesh=``,
        ``steps=``, ``lr=``) returns a fresh *uncached* server, so a
        one-off override can never change what ``transform()`` later does.
        """
        from repro.checkpoint import latest_step
        from repro.serve import FrozenMap, MapServer

        if self._server is not None and not overrides:
            return self._server
        if self._frozen is None:
            if self._fit_result is not None:
                self._frozen = FrozenMap.from_fit(self._fit_result, self.cfg)
            elif self.cfg.checkpoint_dir and latest_step(self.cfg.checkpoint_dir) is not None:
                self._frozen = FrozenMap.from_checkpoint(self.cfg.checkpoint_dir, self.cfg)
            else:
                raise RuntimeError(
                    "transform needs a fitted map: call fit(x) first, or load "
                    "one with NomadProjection.from_checkpoint(dir)"
                )
        if overrides:
            return MapServer(self._frozen, **overrides)
        self._server = MapServer(self._frozen)
        return self._server

    def transform(self, x: np.ndarray, *, seed: int = 0) -> np.ndarray:
        """Place unseen rows on the frozen fitted map (out-of-sample
        extension). Returns the ``(n_queries, out_dim)`` placements;
        ``map_server().transform(x)`` returns the full
        :class:`repro.serve.TransformResult` (cells, neighbor ids/distances,
        per-batch latency). Never moves fitted positions — the serve
        kernels' gradients stop at the query rows.
        """
        return self.map_server().transform(x, seed=seed).embedding

    def _init_theta(self, x, index: "AnnIndex") -> jax.Array:
        from repro.data.store import as_store, is_store

        cfg = self.cfg
        if cfg.init == "pca":
            if is_store(x) or cfg.chunk_rows > 0:
                # the streamed init: same chunk schedule as the streamed
                # build, so fit(store) ≡ fit(ndarray) stays bit-exact
                from repro.core.pca import pca_init_streamed

                th0 = pca_init_streamed(
                    as_store(x),
                    cfg.out_dim,
                    cfg.init_scale,
                    chunk_rows=cfg.resolved_chunk_rows(),
                )
            else:
                th0 = np.asarray(
                    pca_init(jnp.asarray(x), cfg.out_dim, cfg.init_scale)
                )
        else:
            rng = np.random.default_rng(cfg.seed)
            th0 = rng.normal(0, cfg.init_scale, (x.shape[0], cfg.out_dim)).astype(
                np.float32
            )
        rows = np.zeros((index.n_clusters * index.capacity, cfg.out_dim), np.float32)
        rows[index.perm] = th0
        return jnp.asarray(rows)
