"""Pluggable execution strategies for the unified ``NomadProjection`` front end.

One estimator, every scale: the estimator owns the epoch loop, callbacks and
checkpointing; a strategy owns *where and how one epoch runs*:

* :class:`LocalStrategy`        — single device, ``make_epoch_fn`` (the
  paper's single-GPU reference; the only strategy that supports the
  non-factorising ``"infonc"`` baseline).
* :class:`ShardedStrategy`      — the paper's Fig. 2 multi-device mode:
  cluster-sharded ``shard_map`` epochs with a flat per-refresh all-gather of
  cell means (``core/distributed.py:make_sharded_epoch_fn``).
* :class:`HierarchicalStrategy` — the multi-pod extension: full means
  circulate intra-pod, remote pods are summarised by one super-mean each.

``resolve_strategy("auto", cfg, ...)`` picks for you from ``jax.devices()``
and the config: one device → local; several devices → sharded over the
largest cluster-divisible device count (hierarchical when
``cfg.hierarchical`` and a 2-pod mesh fits). Every strategy consumes the
same global cluster-major ``theta`` view and returns per-epoch
``(theta, loss)``, so checkpoints written under one strategy restore under
any other (elastic resume).

The *index build* has a twin of this layer —
:class:`repro.index.build.IndexBuilder`, resolved from
``cfg.build_strategy`` over the same device pool — so ``fit`` is
device-resident end to end: build strategies produce the index the
execution strategies then train on.
"""

from __future__ import annotations

import dataclasses
import warnings
from typing import Callable, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import NomadConfig


# ---------------------------------------------------------------------------
# Event API
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class EpochStartEvent:
    epoch: int
    n_epochs: int
    lr0: float  # lr at the first step of this epoch
    lr1: float  # lr at the last step of this epoch
    strategy: str


@dataclasses.dataclass
class EpochEndEvent:
    epoch: int
    n_epochs: int
    loss: float
    time_s: float
    strategy: str
    # (N, out_dim) in the ORIGINAL point order — never the raw cluster-major
    # capacity-padded buffer. None when no consumer asked for embeddings.
    embedding: Optional[np.ndarray] = None


@dataclasses.dataclass
class MeansRefreshEvent:
    epoch: int
    n_refreshes: int  # mean refreshes performed inside this epoch
    strategy: str


@dataclasses.dataclass
class CheckpointEvent:
    epoch: int
    step: int  # checkpoint step id (== epoch)
    directory: str
    n_shards: int


class FitCallbacks:
    """Structured fit events. Subclass and override what you need.

    ``wants_embedding`` controls whether :attr:`EpochEndEvent.embedding` is
    materialised (an O(N·d) device→host copy + unpermute per epoch); set it
    to False for cheap loss/time-only observers on big runs.
    """

    wants_embedding: bool = True

    def on_epoch_start(self, event: EpochStartEvent) -> None: ...

    def on_epoch_end(self, event: EpochEndEvent) -> None: ...

    def on_means_refresh(self, event: MeansRefreshEvent) -> None: ...

    def on_checkpoint(self, event: CheckpointEvent) -> None: ...


class CallbackList(FitCallbacks):
    """Fan one event stream out to several callback objects."""

    def __init__(self, callbacks: Sequence[FitCallbacks]):
        self.callbacks = list(callbacks)

    @property
    def wants_embedding(self) -> bool:  # type: ignore[override]
        return any(cb.wants_embedding for cb in self.callbacks)

    def on_epoch_start(self, event):
        for cb in self.callbacks:
            cb.on_epoch_start(event)

    def on_epoch_end(self, event):
        for cb in self.callbacks:
            cb.on_epoch_end(event)

    def on_means_refresh(self, event):
        for cb in self.callbacks:
            cb.on_means_refresh(event)

    def on_checkpoint(self, event):
        for cb in self.callbacks:
            cb.on_checkpoint(event)


class LegacyCallback(FitCallbacks):
    """Adapter for the old bare ``callback(epoch, embedding, loss)``.

    Unlike the pre-redesign behaviour (which leaked the raw cluster-major,
    capacity-padded ``theta`` buffer), the adapter hands the *unpermuted*
    ``(N, out_dim)`` embedding — the same array ``FitResult.embedding`` ends
    up with.
    """

    def __init__(self, fn: Callable):
        self.fn = fn

    def on_epoch_end(self, event: EpochEndEvent) -> None:
        self.fn(event.epoch, event.embedding, event.loss)


def as_callbacks(
    callbacks=None, legacy_callback: Optional[Callable] = None
) -> Optional[FitCallbacks]:
    """Normalise fit()'s callback arguments into one FitCallbacks (or None)."""
    out = []
    if callbacks is not None:
        if isinstance(callbacks, FitCallbacks):
            out.append(callbacks)
        else:  # sequence of FitCallbacks
            out.extend(callbacks)
    if legacy_callback is not None:
        warnings.warn(
            "fit(callback=...) is deprecated; pass callbacks=FitCallbacks() "
            "(see repro.core.strategy.FitCallbacks). The legacy callback now "
            "receives the unpermuted (N, out_dim) embedding.",
            DeprecationWarning,
            stacklevel=3,
        )
        out.append(LegacyCallback(legacy_callback))
    if not out:
        return None
    return out[0] if len(out) == 1 else CallbackList(out)


# ---------------------------------------------------------------------------
# Multi-process helpers
# ---------------------------------------------------------------------------


def fetch_global(arr) -> np.ndarray:
    """Device array → host np.ndarray, multi-process safe.

    Single-process (and anything fully addressable) is a plain
    ``np.asarray``. Under ``jax.distributed`` a sharded array is *not*
    fully addressable — ``np.asarray`` raises — so the missing shards are
    gathered from peer processes first (every process gets the full
    array). Collective: every process must call this together.
    """
    if getattr(arr, "is_fully_addressable", True) or getattr(
        arr, "is_fully_replicated", False
    ):
        # fully replicated arrays (e.g. psum outputs) have a complete local
        # copy on every process — np.asarray reads it without communication
        return np.asarray(arr)
    from jax.experimental import multihost_utils

    return np.asarray(multihost_utils.process_allgather(arr, tiled=True))


def sync_processes(tag: str = "sync") -> None:
    """Cross-process barrier; no-op in a single-process runtime."""
    if jax.process_count() > 1:
        from jax.experimental import multihost_utils

        multihost_utils.sync_global_devices(tag)


# ---------------------------------------------------------------------------
# Strategies
# ---------------------------------------------------------------------------

# host spans around each epoch's dispatch (see ExecutionStrategy._dispatch)
SPAN_DISPATCH = "nomad.fit.dispatch"
SPAN_SYNC = "nomad.fit.sync"
# host span around the sharded strategy's placement of θ and the index
SPAN_PLACE = "nomad.fit.place"


class ExecutionStrategy:
    """Where/how one NOMAD epoch runs. Stateful: ``prepare`` then ``run_epoch``."""

    name: str = "?"

    def __init__(self) -> None:
        self.n_shards: int = 1
        self.mesh: Optional[Mesh] = None

    # -- lifecycle -----------------------------------------------------------

    def prepare(self, cfg: NomadConfig, method: str, index, theta0) -> jax.Array:
        """Place ``theta0``/index on device(s), build the epoch fn; return theta."""
        raise NotImplementedError

    def run_epoch(self, theta, epoch: int, lr0: float, lr1: float, key):
        """One epoch: ``(theta, lr schedule, rng) -> (theta, mean_loss)``."""
        return self._dispatch(theta, self._idx, lr0, lr1, key)

    def _dispatch(self, *args):
        """Run the jitted epoch on ``args`` and wait for its loss, under the
        host spans ``nomad.fit.dispatch`` (the enqueue) and
        ``nomad.fit.sync`` (the wait): profiler events on the device trace's
        clock, so an idle gap of the device falls in one or the other or
        outside both."""
        with jax.profiler.TraceAnnotation(SPAN_DISPATCH):
            theta, loss = self._epoch_fn(*args)
        with jax.profiler.TraceAnnotation(SPAN_SYNC):
            return theta, float(loss)

    # -- introspection ---------------------------------------------------------

    def refreshes_per_epoch(self) -> int:
        steps = self._steps
        refresh = self._refresh
        return max(1, -(-steps // refresh))

    def fetch(self, theta) -> np.ndarray:
        """θ → host array; gathers remote shards under multi-process jax."""
        return fetch_global(theta)

    def describe(self) -> dict:
        return {
            "strategy": self.name,
            "n_shards": self.n_shards,
            "mesh_shape": tuple(self.mesh.shape.values()) if self.mesh else None,
            "mesh_axes": tuple(self.mesh.axis_names) if self.mesh else None,
            "process_count": jax.process_count(),
            "process_index": jax.process_index(),
        }


class LocalStrategy(ExecutionStrategy):
    """Single-device reference loop (``core/nomad.py:make_epoch_fn``)."""

    name = "local"

    def prepare(self, cfg, method, index, theta0):
        from repro.core.nomad import make_epoch_fn, make_step_fn

        self._steps = cfg.resolved_steps_per_epoch()
        self._refresh = cfg.mean_refresh_steps or self._steps
        self._idx = {
            "knn_idx": jnp.asarray(index.knn_idx, jnp.int32),
            "knn_w": jnp.asarray(index.knn_w, jnp.float32),
            "counts": jnp.asarray(index.counts, jnp.int32),
            "cum_counts": jnp.asarray(np.cumsum(index.counts), jnp.int32),
        }
        step_fn = make_step_fn(cfg, method=method)
        self._epoch_fn = make_epoch_fn(cfg, step_fn, self._steps)
        return jnp.asarray(theta0)


class PartialRefineStrategy(ExecutionStrategy):
    """Refinement epochs restricted to the cells a ``partial_fit`` touched.

    Same epoch contract as :class:`LocalStrategy` — means refreshed over
    the **full** layout (repulsion still sees every cell), the usual
    ``make_epoch_fn`` scan — but heads are sampled only from
    ``affected_cells`` (:func:`repro.core.nomad.make_partial_step_fn`).
    Positives come from the patched in-cluster kNN and negatives from the
    head's own cell, so gradients never reach a row outside the affected
    cells: everything the append didn't touch stays bit-identical, which
    is the property the map-stability gate leans on.

    Steps per epoch scale with the *affected* point count, not N — the
    "cheap" in cheap refinement.
    """

    name = "partial"

    def __init__(self, affected_cells):
        super().__init__()
        self.affected_cells = np.asarray(affected_cells, np.int32)

    def prepare(self, cfg, method, index, theta0):
        from repro.core.nomad import make_epoch_fn, make_partial_step_fn

        if self.affected_cells.size == 0:
            raise ValueError("PartialRefineStrategy needs >=1 affected cell")
        counts = np.asarray(index.counts)
        aff = self.affected_cells
        n_aff = int(counts[aff].sum())
        self._steps = max(1, -(-n_aff // cfg.batch_size))
        self._refresh = cfg.mean_refresh_steps or self._steps
        self._idx = {
            "knn_idx": jnp.asarray(index.knn_idx, jnp.int32),
            "knn_w": jnp.asarray(index.knn_w, jnp.float32),
            "counts": jnp.asarray(counts, jnp.int32),
            "cum_counts": jnp.asarray(np.cumsum(counts), jnp.int32),
            "aff_cells": jnp.asarray(aff, jnp.int32),
            "aff_cum_counts": jnp.asarray(np.cumsum(counts[aff]), jnp.int32),
        }
        step_fn = make_partial_step_fn(cfg, method=method, n_total=index.n_points)
        self._epoch_fn = make_epoch_fn(cfg, step_fn, self._steps)
        return jnp.asarray(theta0)


class ShardedStrategy(ExecutionStrategy):
    """Fig. 2 cluster-sharded ``shard_map`` epochs, flat mean exchange.

    ``mesh=None`` builds a default 1-axis mesh over the largest device count
    that divides ``cfg.n_clusters``. With a mesh given, ``shard_axes``
    defaults to every axis except ``pod_axis``.
    """

    name = "sharded"
    _hierarchical = False

    def __init__(
        self,
        mesh: Optional[Mesh] = None,
        shard_axes: Optional[Sequence[str]] = None,
        pod_axis: Optional[str] = None,
    ):
        super().__init__()
        self.mesh = mesh
        self.shard_axes = tuple(shard_axes) if shard_axes is not None else None
        self.pod_axis = pod_axis

    def _resolve_mesh(self, cfg: NomadConfig) -> None:
        if self.mesh is None:
            self.mesh = default_mesh(cfg, hierarchical=self._hierarchical)
            self.shard_axes = ("data",)
            self.pod_axis = "pod" if "pod" in self.mesh.axis_names else None
        if self.pod_axis is None and "pod" in self.mesh.axis_names and (
            self.shard_axes is None or "pod" not in self.shard_axes
        ):
            self.pod_axis = "pod"
        if self.shard_axes is None:
            self.shard_axes = tuple(
                a for a in self.mesh.axis_names if a != self.pod_axis
            )
        uncovered = [
            a
            for a in self.mesh.axis_names
            if a not in self.shard_axes and a != self.pod_axis
            and self.mesh.shape[a] > 1
        ]
        if uncovered:
            raise ValueError(
                f"mesh axes {uncovered} are covered by neither shard_axes="
                f"{self.shard_axes} nor pod_axis={self.pod_axis!r}; θ would be "
                "silently replicated across them"
            )
        n_shards = int(np.prod([self.mesh.shape[a] for a in self.shard_axes]))
        if self.pod_axis:
            n_shards *= self.mesh.shape[self.pod_axis]
        if cfg.n_clusters % n_shards:
            raise ValueError(
                f"strategy={self.name!r}: n_clusters={cfg.n_clusters} is not "
                f"divisible by the {n_shards}-shard mesh "
                f"{dict(self.mesh.shape)}; pick a compatible mesh or "
                "strategy='local'"
            )
        self.n_shards = n_shards

    def prepare(self, cfg, method, index, theta0):
        from repro.core.distributed import make_sharded_epoch_fn, place_index, place_rows

        if method != "nomad":
            raise ValueError(
                f"method={method!r} only runs with strategy='local' — its loss "
                "does not factorise over the cluster partition (paper Eq. 2)"
            )
        if self._hierarchical:
            cfg = cfg.replace(hierarchical=True)
        self._resolve_mesh(cfg)
        if self._hierarchical and self.pod_axis is None:
            raise ValueError(
                "strategy='hierarchical' needs a mesh with a pod axis "
                "(e.g. axes ('pod', 'data'))"
            )

        # shards work in parallel, so each runs 1/n_shards of the
        # single-device step count — per-epoch sample volume stays ≈ N.
        self._steps = max(1, -(-cfg.resolved_steps_per_epoch() // self.n_shards))
        self._refresh = cfg.mean_refresh_steps or self._steps

        axes = ((self.pod_axis,) if self.pod_axis else ()) + self.shard_axes
        with jax.profiler.TraceAnnotation(SPAN_PLACE):
            self._idx, self._counts_global = place_index(index, self.mesh, axes)
            theta = place_rows(theta0, NamedSharding(self.mesh, P(axes, None)))
        self._epoch_fn = jax.jit(
            make_sharded_epoch_fn(
                cfg,
                self.mesh,
                shard_axes=self.shard_axes,
                pod_axis=self.pod_axis,
                steps_per_epoch=self._steps,
                n_shards=self.n_shards,
            )
        )
        return theta

    def run_epoch(self, theta, epoch, lr0, lr1, key):
        return self._dispatch(theta, self._idx, self._counts_global, lr0, lr1, key)


class HierarchicalStrategy(ShardedStrategy):
    """Multi-pod mode: intra-pod full means, inter-pod super-means."""

    name = "hierarchical"
    _hierarchical = True


# ---------------------------------------------------------------------------
# Resolution
# ---------------------------------------------------------------------------


def largest_divisor_leq(k: int, n: int) -> int:
    """Largest divisor of ``k`` that is ≤ ``n`` — the widest device count a
    K-cluster workload can shard over. Shared by training-strategy and
    index-build (:func:`repro.index.build.resolve_build_strategy`)
    resolution so ``"auto"`` picks the same device pool for both."""
    for d in range(min(k, n), 0, -1):
        if k % d == 0:
            return d
    return 1


_largest_divisor_leq = largest_divisor_leq  # pre-PR-3 private name


def flat_mesh(devs, axis: str) -> Mesh:
    """One flat mesh axis over ``devs`` — the shape shared by the training
    default mesh, the index-build mesh
    (:func:`repro.index.build.resolve_build_strategy`) and the serve mesh
    (:func:`repro.serve.server.resolve_serve_strategy`).

    ``devs`` must come from the GLOBAL pool (``jax.devices()``), never
    ``jax.local_devices()`` — under ``jax.distributed`` a mesh built from
    local devices would silently compute a per-process answer with no
    cross-process collectives. ``launch/mesh.py:flat_mesh`` wraps this
    with the global pool filled in."""
    return Mesh(np.asarray(devs).reshape(-1), (axis,))


def default_mesh(cfg: NomadConfig, *, hierarchical: bool = False) -> Mesh:
    """A mesh over (a prefix of) ``jax.devices()`` compatible with K clusters.

    ``jax.devices()`` is the global pool: under ``jax.distributed`` it
    spans every process, so the default mesh (and the shard_map
    collectives over it) crosses process boundaries automatically.
    """
    devs = jax.devices()
    K = cfg.n_clusters
    if hierarchical:
        # 2 pods × the largest per-pod width that keeps K divisible
        pods = 2
        per_pod = _largest_divisor_leq(K // pods if K % pods == 0 else 1, len(devs) // pods)
        if K % pods == 0 and per_pod >= 1 and pods * per_pod <= len(devs):
            arr = np.asarray(devs[: pods * per_pod]).reshape(pods, per_pod)
            return Mesh(arr, ("pod", "data"))
        # fall through to a flat mesh when a 2-pod layout doesn't fit
    d = _largest_divisor_leq(K, len(devs))
    return flat_mesh(devs[:d], "data")


def resolve_strategy(
    spec,
    cfg: NomadConfig,
    *,
    method: Optional[str] = None,
    mesh: Optional[Mesh] = None,
    shard_axes: Optional[Sequence[str]] = None,
    pod_axis: Optional[str] = None,
) -> ExecutionStrategy:
    """Turn ``"auto"|"local"|"sharded"|"hierarchical"`` (or an instance) into
    a ready-to-prepare strategy."""
    if isinstance(spec, ExecutionStrategy):
        return spec
    spec = spec or "auto"
    method = method or cfg.method

    if spec == "auto":
        # GLOBAL device count — under jax.distributed this spans every
        # process (jax.local_device_count() would wedge each process into
        # its own single-host strategy with no cross-process collectives)
        n_dev = jax.device_count()
        if mesh is not None:
            if cfg.hierarchical and "pod" in mesh.axis_names:
                spec = "hierarchical"
            else:
                spec = "sharded"
        elif method == "infonc" or n_dev == 1:
            spec = "local"
        elif _largest_divisor_leq(cfg.n_clusters, n_dev) == 1:
            warnings.warn(
                f"strategy='auto': {n_dev} devices share no divisor with "
                f"n_clusters={cfg.n_clusters}; falling back to strategy='local'"
            )
            spec = "local"
        elif cfg.hierarchical and n_dev >= 4 and cfg.n_clusters % 2 == 0:
            spec = "hierarchical"
        else:
            spec = "sharded"

    if spec == "local":
        if jax.process_count() > 1:
            raise ValueError(
                f"strategy='local' (method={method!r}) cannot run under "
                f"multi-process jax.distributed ({jax.process_count()} "
                "processes): the local loop would compute one independent "
                "answer per process. Use strategy='sharded' with "
                "n_clusters divisible by the global device count."
            )
        return LocalStrategy()
    if spec == "sharded":
        return ShardedStrategy(mesh=mesh, shard_axes=shard_axes, pod_axis=pod_axis)
    if spec == "hierarchical":
        return HierarchicalStrategy(
            mesh=mesh, shard_axes=shard_axes, pod_axis=pod_axis
        )
    raise ValueError(
        f"unknown strategy {spec!r} (want 'auto'|'local'|'sharded'|'hierarchical')"
    )
