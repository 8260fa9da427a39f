"""Unified NomadProjection front end: strategy selection, event callbacks,
checkpoint/resume equivalence, and the strategies' shared fit step.

Everything here runs on the single in-process CPU device — the sharded
strategy is exercised on a 1-device mesh, where it must agree with the
local strategy bit-for-bit (same RNG stream, same loss composition). The
full multi-device paths are covered by the `slow` subprocess selftests.
"""

import os

import jax
import numpy as np
import pytest
from jax.sharding import Mesh

from repro.configs.base import NomadConfig
from repro.core.nomad import FitResult, NomadProjection
from repro.core.strategy import (
    EpochEndEvent,
    FitCallbacks,
    HierarchicalStrategy,
    LocalStrategy,
    PartialRefineStrategy,
    ShardedStrategy,
    resolve_strategy,
)
from repro.data.synthetic import gaussian_mixture

N, DIM = 1500, 16

CFG = NomadConfig(
    n_points=N,
    dim=DIM,
    n_clusters=4,
    n_neighbors=10,
    n_noise=16,
    n_exact_negatives=4,
    batch_size=256,
    n_epochs=4,
)


@pytest.fixture(scope="module")
def data():
    x, labels = gaussian_mixture(N, DIM, n_components=4, seed=0)
    return x, labels


@pytest.fixture(scope="module")
def one_device_mesh():
    return Mesh(np.asarray(jax.devices()[:1]).reshape(1), ("data",))


# ---------------------------------------------------------------------------
# Strategy selection
# ---------------------------------------------------------------------------


def test_auto_resolves_local_on_one_device():
    # the in-process test runner has a single CPU device
    assert isinstance(resolve_strategy("auto", CFG), LocalStrategy)
    assert isinstance(resolve_strategy("local", CFG), LocalStrategy)
    assert isinstance(resolve_strategy("sharded", CFG), ShardedStrategy)
    assert isinstance(resolve_strategy("hierarchical", CFG), HierarchicalStrategy)


def test_auto_with_mesh_resolves_sharded(one_device_mesh):
    s = resolve_strategy("auto", CFG, mesh=one_device_mesh)
    assert isinstance(s, ShardedStrategy) and not isinstance(s, HierarchicalStrategy)


def test_strategy_instance_passthrough():
    s = LocalStrategy()
    assert resolve_strategy(s, CFG) is s


def test_unknown_strategy_rejected():
    with pytest.raises(ValueError, match="unknown strategy"):
        resolve_strategy("pmap", CFG)
    with pytest.raises(ValueError, match="unknown strategy"):
        NomadConfig(strategy="pmap")


def test_infonc_requires_local(data, one_device_mesh):
    x, _ = data
    proj = NomadProjection(CFG.replace(method="infonc"), strategy="sharded",
                           mesh=one_device_mesh)
    with pytest.raises(ValueError, match="strategy='local'"):
        proj.fit(x)


def test_default_mesh_divides_clusters():
    from repro.core.strategy import default_mesh

    mesh = default_mesh(CFG)
    n_shards = int(np.prod(list(mesh.shape.values())))
    assert CFG.n_clusters % n_shards == 0
    assert n_shards <= len(jax.devices())


# ---------------------------------------------------------------------------
# Local ≡ sharded on a 1-device mesh (strategy equivalence)
# ---------------------------------------------------------------------------


def test_local_and_sharded_agree_on_one_device(data, one_device_mesh):
    x, _ = data
    loc = NomadProjection(CFG, strategy="local").fit(x)
    sh = NomadProjection(CFG, strategy="sharded", mesh=one_device_mesh).fit(
        x, index=loc.index
    )
    assert sh.strategy == "sharded" and sh.n_shards == 1
    assert sh.mesh_shape == (1,) and sh.mesh_axes == ("data",)
    np.testing.assert_array_equal(loc.embedding, sh.embedding)
    np.testing.assert_allclose(loc.losses, sh.losses, rtol=1e-6)


@pytest.mark.parametrize("method", ["nomad", "infonc"])
def test_partial_refine_over_every_cell_agrees_with_local(data, method):
    """With every cell affected, the partial refine draws the local fit's
    heads: one dispatch from the same θ and key gives the same θ and
    loss, bit for bit."""
    from repro.index.build import IndexBuilder

    x, _ = data
    index = IndexBuilder(CFG).build(x)
    rows = index.n_clusters * index.capacity
    theta0 = jax.random.normal(jax.random.key(0), (rows, CFG.out_dim)) * 1e-2
    local, partial = LocalStrategy(), PartialRefineStrategy(np.arange(index.n_clusters))
    th_l = local.prepare(CFG, method, index, theta0)
    th_p = partial.prepare(CFG, method, index, theta0)
    assert partial._steps == local._steps
    lr0 = CFG.resolved_lr0()
    th_l, loss_l = local.run_epoch(th_l, 0, lr0, lr0 / 2, jax.random.key(7))
    th_p, loss_p = partial.run_epoch(th_p, 0, lr0, lr0 / 2, jax.random.key(7))
    assert not np.array_equal(np.asarray(th_l), np.asarray(theta0))
    np.testing.assert_array_equal(np.asarray(th_l), np.asarray(th_p))
    assert loss_l == loss_p


# ---------------------------------------------------------------------------
# Event API
# ---------------------------------------------------------------------------


class Recorder(FitCallbacks):
    def __init__(self):
        self.starts, self.ends, self.refreshes, self.checkpoints = [], [], [], []

    def on_epoch_start(self, ev):
        self.starts.append(ev)

    def on_epoch_end(self, ev):
        self.ends.append(ev)

    def on_means_refresh(self, ev):
        self.refreshes.append(ev)

    def on_checkpoint(self, ev):
        self.checkpoints.append(ev)


def test_callbacks_receive_unpermuted_embedding(data):
    x, _ = data
    rec = Recorder()
    res = NomadProjection(CFG).fit(x, callbacks=rec)
    assert [e.epoch for e in rec.ends] == list(range(CFG.n_epochs))
    for ev in rec.ends:
        assert isinstance(ev, EpochEndEvent)
        # the unpermuted (N, out_dim) view — NOT the (K·C, d) padded buffer
        assert ev.embedding.shape == (N, CFG.out_dim)
        assert ev.strategy == "local"
    np.testing.assert_array_equal(rec.ends[-1].embedding, res.embedding)
    assert [e.epoch for e in rec.starts] == list(range(CFG.n_epochs))
    assert rec.starts[0].lr0 == pytest.approx(CFG.resolved_lr0())
    assert all(ev.n_refreshes == 1 for ev in rec.refreshes)  # default: 1/epoch


def test_wants_embedding_false_skips_materialisation(data):
    x, _ = data

    class Cheap(FitCallbacks):
        wants_embedding = False

        def __init__(self):
            self.embs = []

        def on_epoch_end(self, ev):
            self.embs.append(ev.embedding)

    cb = Cheap()
    NomadProjection(CFG.replace(n_epochs=2)).fit(x, callbacks=cb)
    assert cb.embs == [None, None]


# ---------------------------------------------------------------------------
# Checkpointing + resume
# ---------------------------------------------------------------------------


class _Kill(Exception):
    pass


class _KillAfter(FitCallbacks):
    wants_embedding = False

    def __init__(self, epoch):
        self.epoch = epoch

    def on_epoch_end(self, ev):
        if ev.epoch == self.epoch:
            raise _Kill(f"killed after epoch {ev.epoch}")


def test_kill_resume_matches_uninterrupted(data, tmp_path):
    """Kill a fit after epoch 3, resume via from_checkpoint, and get the
    exact embedding of an uninterrupted run (same seed/fold_in schedule)."""
    x, _ = data
    base = CFG.replace(n_epochs=6, checkpoint_every_epochs=2)

    full = NomadProjection(base.replace(checkpoint_dir=str(tmp_path / "a"))).fit(x)
    assert full.checkpoint_epochs == [1, 3, 5]

    ckdir = str(tmp_path / "b")
    cfg = base.replace(checkpoint_dir=ckdir)
    with pytest.raises(_Kill):
        NomadProjection(cfg).fit(x, callbacks=_KillAfter(3))
    assert os.path.exists(os.path.join(ckdir, "index.npz"))

    est = NomadProjection.from_checkpoint(ckdir)
    assert est.cfg.n_epochs == 6 and est.cfg.checkpoint_dir == ckdir
    res = est.fit(x)  # from_checkpoint ⇒ resume by default
    assert res.resumed and res.start_epoch == 4
    assert len(res.losses) == 2  # epochs 4, 5
    np.testing.assert_array_equal(full.embedding, res.embedding)


def test_resume_false_restarts_from_scratch(data, tmp_path):
    x, _ = data
    cfg = CFG.replace(n_epochs=3, checkpoint_dir=str(tmp_path), checkpoint_every_epochs=1)
    r1 = NomadProjection(cfg).fit(x)
    r2 = NomadProjection(cfg).fit(x, resume=False)
    assert not r2.resumed and r2.start_epoch == 0
    np.testing.assert_array_equal(r1.embedding, r2.embedding)


def test_resume_without_checkpoint_dir_raises(data):
    x, _ = data
    with pytest.raises(ValueError, match="checkpoint_dir"):
        NomadProjection(CFG).fit(x, resume=True)


def test_checkpoint_events_and_provenance(data, tmp_path):
    x, _ = data
    rec = Recorder()
    cfg = CFG.replace(checkpoint_dir=str(tmp_path), checkpoint_every_epochs=2)
    res = NomadProjection(cfg).fit(x, callbacks=rec)
    assert res.checkpoint_dir == str(tmp_path)
    assert res.checkpoint_epochs == [1, 3]  # every 2, + final epoch (3)
    assert [e.epoch for e in rec.checkpoints] == [1, 3]
    assert all(e.directory == str(tmp_path) for e in rec.checkpoints)


def test_stale_index_cache_rebuilt_not_reused(data, tmp_path):
    """Reusing a checkpoint_dir with different data must not silently fit
    against the cached index of the old dataset."""
    x, _ = data
    cfg = CFG.replace(n_epochs=2, checkpoint_dir=str(tmp_path))
    NomadProjection(cfg).fit(x)  # writes index.npz for (N, DIM)
    x2, _ = gaussian_mixture(800, DIM, n_components=4, seed=1)
    cfg2 = cfg.replace(n_points=800)
    with pytest.warns(UserWarning, match="index cache"):
        res = NomadProjection(cfg2).fit(x2, resume=False)
    assert res.embedding.shape == (800, CFG.out_dim)
    assert res.index.n_points == 800  # cache was rebuilt, not reused


def test_from_checkpoint_without_config_metadata(tmp_path):
    from repro.checkpoint import Checkpointer

    ck = Checkpointer(str(tmp_path))
    ck.save(0, {"theta": np.zeros((8, 2), np.float32)}, metadata={"epoch": 0})
    with pytest.raises(ValueError, match="no stored config"):
        NomadProjection.from_checkpoint(str(tmp_path))


def test_pod_axis_autodetected_with_explicit_shard_axes(data):
    x, _ = data
    mesh = Mesh(np.asarray(jax.devices()[:1]).reshape(1, 1), ("pod", "data"))
    strat = ShardedStrategy(mesh=mesh, shard_axes=("data",))
    res = NomadProjection(CFG.replace(n_epochs=1), strategy=strat).fit(x)
    assert strat.pod_axis == "pod"  # not silently dropped from the sharding
    assert res.n_shards == 1


# ---------------------------------------------------------------------------
# Out-of-core: fit(store) ≡ fit(ndarray), every strategy
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def data_store(data, tmp_path_factory):
    from repro.data.store import write_sharded

    x, _ = data
    d = tmp_path_factory.mktemp("fit_store")
    return write_sharded(x, str(d / "corpus"), rows_per_shard=400)


def test_fit_from_store_equals_fit_from_ndarray_local(data, data_store):
    """The acceptance criterion: NomadProjection.fit on a sharded on-disk
    store returns a FitResult bit-equal to the in-memory fit. The shared
    cfg.chunk_rows pins the f32 accumulation order of the streamed build +
    PCA init, so only the byte source differs."""
    x, _ = data
    cfg = CFG.replace(chunk_rows=512)
    ra = NomadProjection(cfg).fit(x)
    rb = NomadProjection(cfg).fit(data_store)
    assert ra.index_build_strategy == rb.index_build_strategy == "streamed"
    np.testing.assert_array_equal(ra.embedding, rb.embedding)
    np.testing.assert_allclose(ra.losses, rb.losses, rtol=0)
    for f in ("knn_idx", "knn_w", "counts", "centroids", "perm"):
        np.testing.assert_array_equal(
            getattr(ra.index, f), getattr(rb.index, f), err_msg=f
        )


@pytest.mark.parametrize("build_strategy", ["local", "sharded"])
def test_fit_from_store_equals_ndarray_every_build_strategy(
    data, data_store, build_strategy
):
    x, _ = data
    cfg = CFG.replace(
        n_epochs=2, chunk_rows=512, build_strategy=build_strategy
    )
    ra = NomadProjection(cfg).fit(x)
    rb = NomadProjection(cfg).fit(data_store)
    np.testing.assert_array_equal(ra.embedding, rb.embedding)


def test_fit_from_store_equals_ndarray_sharded_strategy(
    data, data_store, one_device_mesh
):
    x, _ = data
    cfg = CFG.replace(n_epochs=2, chunk_rows=512)
    ra = NomadProjection(cfg, strategy="sharded", mesh=one_device_mesh).fit(x)
    rb = NomadProjection(cfg, strategy="sharded", mesh=one_device_mesh).fit(
        data_store
    )
    assert ra.strategy == rb.strategy == "sharded"
    np.testing.assert_array_equal(ra.embedding, rb.embedding)


def test_fit_from_memmap_streams(data, tmp_path):
    """An np.memmap input is auto-wrapped into a store: the fit streams it
    (and matches the same-chunking in-memory fit bit-for-bit)."""
    x, _ = data
    path = str(tmp_path / "x.npy")
    np.save(path, x)
    mm = np.load(path, mmap_mode="r")
    cfg = CFG.replace(n_epochs=2, chunk_rows=512)
    ra = NomadProjection(cfg).fit(mm)
    rb = NomadProjection(cfg).fit(x)
    assert ra.index_build_strategy == "streamed"
    np.testing.assert_array_equal(ra.embedding, rb.embedding)


def test_fit_store_checkpoint_resume_and_cache(data_store, tmp_path, data):
    """The checkpoint/resume path works from a disk-backed corpus: the
    second fit reuses the store-backed index cache (fingerprint-checked)
    and reproduces the run bit-for-bit."""
    cfg = CFG.replace(
        n_epochs=2, chunk_rows=512, checkpoint_dir=str(tmp_path / "ck")
    )
    r1 = NomadProjection(cfg).fit(data_store)
    assert r1.index_build_strategy == "streamed"
    r2 = NomadProjection(cfg).fit(data_store, resume=False)
    assert r2.index_build_strategy == "cache"
    np.testing.assert_array_equal(r1.embedding, r2.embedding)


# ---------------------------------------------------------------------------
# The unified front end's surface
# ---------------------------------------------------------------------------


def test_fit_transform(data):
    x, _ = data
    cfg = CFG.replace(n_epochs=2)
    emb = NomadProjection(cfg).fit_transform(x)
    res = NomadProjection(cfg).fit(x)
    np.testing.assert_array_equal(emb, res.embedding)


def test_fit_result_metadata(data):
    x, _ = data
    res = NomadProjection(CFG.replace(n_epochs=2)).fit(x)
    assert isinstance(res, FitResult)
    assert res.strategy == "local" and res.n_shards == 1
    assert res.mesh_shape is None and res.start_epoch == 0 and not res.resumed
    assert res.checkpoint_epochs == [] and res.checkpoint_dir == ""


def test_method_from_config(data):
    x, _ = data
    cfg = CFG.replace(n_epochs=2, method="infonc")
    res = NomadProjection(cfg).fit(x)
    assert np.isfinite(res.embedding).all()
    with pytest.raises(ValueError, match="unknown method"):
        NomadConfig(method="umap")
