"""The build cell's comparison and readers: a sound build agrees with the
plain reference on every check; the controls and each fault planted in the
build's path come out not correct; the stage readers and the kernel's
roofline read a build trace recorded on a TPU v5e.

The harness is driven on the CPU at N = 2,000, D = 64, K = 4 (the look for
a chip is skipped), with the build's path broken underneath where a fault
is planted.
"""

import glob
import os

import numpy as np
import pytest

from conftest import BENCH, HERE

TINY_BUILD = dict(n_points=2000, dim=64)
SEEDS = [11, 2**31 + 5]


@pytest.fixture
def tiny_build_cell(run_mod):
    cell = run_mod.find_cell("build.pubmed")
    cell.config = dict(cell.config, **TINY_BUILD)
    return cell


@pytest.fixture(scope="module")
def driver():
    import run

    return run.load_module(os.path.join(BENCH, "drivers", "build.py"), "build_driver")


@pytest.fixture(scope="module")
def calib():
    import run

    return run.load_module(os.path.join(BENCH, "calibrate_build.py"), "calibrate_build")


def test_sound_run_is_correct(tiny_build_cell, drive, capsys):
    rc, res = drive(tiny_build_cell, capsys)
    assert rc == 0 and res["correct"] is True, res["checks"]
    assert res["attempted"] >= 1 and res["failed"] == 0
    assert list(res)[-1] == "checks"
    assert set(res["metrics"]) == {"build_rows_per_s", "setup_s"}
    assert set(res["checks"]) == set(tiny_build_cell.limits)
    assert res["checks"]["builds_apart"]["value"] == 0


@pytest.mark.parametrize("fault", ["kmeans_unchanged", "four_rows", "knn_one_rank_far", "ranks_off_by_one"])
def test_fault_is_not_correct(fault, tiny_build_cell, drive, capsys, monkeypatch, calib):
    calib.plant(fault, monkeypatch.setattr)
    rc, res = drive(tiny_build_cell, capsys)
    assert rc == 0
    assert res["correct"] is False, res["checks"]
    caught = [n for n, c in res["checks"].items() if c["value"] > c["limit"]]
    assert set(caught) >= set(calib.CAUGHT_BY[fault]), res["checks"]


@pytest.mark.parametrize("seed", SEEDS)
def test_sound_build_agrees_on_every_check(tiny_build_cell, driver, seed):
    import contextlib

    from lib import ref_build

    cfg = driver.nomad_config(tiny_build_cell.config)
    _, x, got, _ = driver.start(cfg, tiny_build_cell.traffic, seed, lambda n: contextlib.nullcontext())
    ref = ref_build.Reference(x, cfg)
    numbers = ref.compare(got)
    for name, value in numbers.items():
        assert value <= tiny_build_cell.limits[name], (name, value)
    # the reference's own build reads 0 on every number
    assert all(v == 0 for v in ref.compare(ref_build.build(x, cfg)).values())


@pytest.mark.parametrize("seed", SEEDS)
def test_bf16_control_is_not_correct(tiny_build_cell, driver, seed):
    """The reference in bfloat16, put in the program's place. (On the CPU
    ``Precision.HIGH`` is float32, so the ``high`` control is the chip's
    to read.)"""
    from lib import ref_build

    cfg = driver.nomad_config(tiny_build_cell.config)
    x = driver.make_corpus(cfg, tiny_build_cell.traffic, seed)
    numbers = ref_build.Reference(x, cfg).compare(ref_build.build(x, cfg, "bf16"))
    assert any(v > tiny_build_cell.limits[n] for n, v in numbers.items()), numbers


def test_placement_is_checked_exactly(tiny_build_cell, driver):
    import contextlib

    from lib import ref_build

    cfg = driver.nomad_config(tiny_build_cell.config)
    _, x, got, _ = driver.start(cfg, tiny_build_cell.traffic, 3, lambda n: contextlib.nullcontext())
    K, C = cfg.n_clusters, cfg.cluster_capacity
    assert ref_build.placement_bad(x, got, K, C) == 0
    flipped = dict(got, x_rows=got["x_rows"].copy())
    flipped["x_rows"][got["perm"][5], 0] = np.nextafter(flipped["x_rows"][got["perm"][5], 0], np.inf)
    assert ref_build.placement_bad(x, flipped, K, C) == 1
    twice = dict(got, perm=got["perm"].copy())
    twice["perm"][1] = twice["perm"][0]
    assert ref_build.placement_bad(x, twice, K, C) >= 2


def test_layout_and_placement_rounds():
    from types import SimpleNamespace

    from lib import ref_build

    # two centroids of room 2: rows 0-2 all prefer centroid 0, row 2 the
    # farthest, so it goes to centroid 1 in the second round
    x = np.array([[0.0], [0.1], [0.5], [3.0]], np.float32)
    cents = np.array([[0.0], [3.0]], np.float32)
    cfg = SimpleNamespace(build_candidates=32, build_max_rounds=16, cluster_capacity=2)
    cell = ref_build.place(x, cents, cfg)
    assert cell.tolist() == [0, 0, 1, 1]
    perm, counts = ref_build.layout(cell, 2, 2)
    assert perm.tolist() == [0, 1, 2, 3] and counts.tolist() == [2, 2]


@pytest.mark.parametrize("n, K", [(23437, 4), (2000, 4), (10, 3)])
def test_corpus_sizes_are_the_shares_of_n(n, K):
    from lib import corpus

    shares = [1.4, 1.1, 0.8, 0.7][:K]
    got = corpus.sizes(n, K, shares)
    assert sum(got) == n and len(got) == K
    assert all(abs(g - n * s / sum(shares)) < 2 for g, s in zip(got, shares))


def test_every_seed_draws_the_same_sizes(tiny_build_cell, driver):
    from lib import corpus

    cfg = driver.nomad_config(tiny_build_cell.config)
    want = sorted(corpus.sizes(cfg.n_points, cfg.n_clusters, tiny_build_cell.traffic["corpus"]["size_shares"]))
    for seed in SEEDS:
        _, comp = corpus.mixture(
            driver.gen.seed_key(seed), n=cfg.n_points, dim=cfg.dim,
            n_components=cfg.n_clusters, **tiny_build_cell.traffic["corpus"],
        )
        assert sorted(np.bincount(np.asarray(comp), minlength=cfg.n_clusters).tolist()) == want


@pytest.mark.parametrize("seed", SEEDS)
def test_the_corpus_overflows_a_cell_and_placement_moves_rows(tiny_build_cell, driver, seed):
    """The largest component holds more rows than a cell's capacity, so
    the bidding rounds move rows away from their nearest centroid."""
    import contextlib

    from lib import ref_build

    cfg = driver.nomad_config(tiny_build_cell.config)
    _, x, got, _ = driver.start(cfg, tiny_build_cell.traffic, seed, lambda n: contextlib.nullcontext())
    nearest, _ = ref_build.nearest(x, got["centroids"])
    cell = got["perm"] // cfg.cluster_capacity
    assert np.bincount(np.asarray(nearest), minlength=cfg.n_clusters).max() > cfg.cluster_capacity
    assert np.sum(np.asarray(nearest) != cell) > 0


# ---- the readers on a recorded build trace ----------------------------------

BUILD_TINY = os.path.join(HERE, "data", "build_tiny")
STAGE_METRICS = ("build.kmeans_share", "build.assign_share", "build.permute_share", "build.knn_share")


TRACE_CELL = (5000, 64)  # C, D of the recorded build


def _ctx(monkeypatch):
    """What the harness hands a reader after the traced run recorded in
    ``build_tiny``: ``build.pubmed`` at N = 16,000, D = 64, K = 4 (C =
    5,000: the kNN runs as a ``lax.map`` over batches of two cells, as at
    the cell's size it runs over batches of one), ``--trace 1``, on one v5e
    chip."""
    from lib import counts, scopes, trace

    monkeypatch.setattr(scopes, "TRACE_ROOT", BUILD_TINY)
    red = trace.reduce_dir(BUILD_TINY, window_span="bench.window")
    return {
        "trace": red,
        "device_kind": "TPU v5 lite",
        "window_s": red.window_s,
        "pairwise_cell": TRACE_CELL,
        "pairwise_cells": 4 * _builds_in(BUILD_TINY),
        "pairwise": counts.pairwise(TRACE_CELL[0], TRACE_CELL[0], TRACE_CELL[1]),
        "flops": 1e9,
    }


def _builds_in(path):
    from lib import trace

    (xplane,) = glob.glob(os.path.join(path, "**", "*.xplane.pb"), recursive=True)
    _, spans = trace.read_xplane(xplane)
    return sum(n == "bench.build.run" for n, _, _ in spans)


def _read(run_mod, metric, ctx):
    mod = run_mod.load_module(os.path.join(BENCH, "metrics", metric + ".py"), "m_" + metric.replace(".", "_"))
    return mod.read(ctx)


def test_stage_shares_sum_to_the_busy_time(run_mod, monkeypatch):
    from lib import build_stages

    ctx = _ctx(monkeypatch)
    st = build_stages.stages(ctx)
    assert st is not None
    assert set(st.modules.values()) == set(build_stages.STAGES)
    shares = [_read(run_mod, m, ctx) for m in STAGE_METRICS]
    assert all(s is not None and s > 0 for s in shares), shares
    other = 100.0 * st.stage_s.get(build_stages.OTHER, 0.0) / ctx["trace"].busy_s
    assert sum(shares) + other == pytest.approx(100.0, rel=1e-9)
    # the kNN does most of a build's device work
    assert shares[3] == max(shares)


def test_idle_roofline_and_mfu_on_the_recorded_trace(run_mod, monkeypatch):
    from lib.build_stages import is_cell_pairwise

    ctx = _ctx(monkeypatch)
    assert ctx["pairwise_cells"] >= 4
    idle = _read(run_mod, "device_idle.build", ctx)
    assert idle == pytest.approx(100.0 * (1 - ctx["trace"].busy_s / ctx["trace"].window_s))
    t = ctx["trace"].self_time(lambda text: is_cell_pairwise(text, *TRACE_CELL))
    assert t > 0
    roof = _read(run_mod, "pairwise_roofline", ctx)
    assert 0 < roof < 100
    # a cell of another size is not this kernel's call
    assert _read(run_mod, "pairwise_roofline", dict(ctx, pairwise_cell=(2400, 64))) is None
    mfu = _read(run_mod, "build_mfu", ctx)
    assert mfu == pytest.approx(100.0 * 1e9 / ctx["window_s"] / 197e12)


def test_stage_readers_are_silent_on_a_fit_trace(run_mod, monkeypatch):
    from lib import scopes, trace

    fit = os.path.join(HERE, "data", "fit_tiny_scoped")
    monkeypatch.setattr(scopes, "TRACE_ROOT", fit)
    ctx = {"trace": trace.reduce_dir(fit, window_span="bench.window")}
    assert all(_read(run_mod, m, ctx) is None for m in STAGE_METRICS)


def test_cell_pairwise_call_is_told_apart():
    from lib.build_stages import is_cell_pairwise

    call = ('%custom-call.3 = f32[7424,7424]{1,0:T(8,128)} custom-call(f32[7424,1024]{1,0:T(8,128)} %a, '
            'f32[7424,1024]{1,0:T(8,128)} %b), custom_call_target="tpu_custom_call"')
    cand = ('%custom-call.1 = f32[16384,256]{1,0} custom-call(f32[16384,1024]{1,0} %a, '
            'f32[256,1024]{1,0} %b), custom_call_target="tpu_custom_call"')
    batch = call.replace("f32[7424,", "f32[2,7424,")
    assert is_cell_pairwise(call, 7324, 768) and is_cell_pairwise(batch, 7324, 768)
    assert not is_cell_pairwise(call, 3000, 768)
    assert not is_cell_pairwise(cand, 7324, 768)
    assert not is_cell_pairwise(call.replace("tpu_custom_call", "other"), 7324, 768)
