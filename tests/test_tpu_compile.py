"""The Pallas kernels of the map path, compiled for a TPU v5e chip.

Interpret mode runs the kernel bodies on the CPU, but it accepts shapes and
slices that Mosaic, the TPU's kernel compiler, refuses. These tests compile
each kernel with ``interpret=False`` for one chip of a described ``v5e:2x2``
topology, at the widths the map path runs (one chip's share of a
PubMed-width map: B=8192 heads, k=15, S=16, K=256 cells, 768-d rows and
clusters of 5,120), and check that the program holds a Mosaic kernel
(``tpu_custom_call``). Nothing runs: a compile says nothing of results or
times.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and pytest-xdist workers all
import this file. Every test of the file is in this one file so that one
worker loads it.
"""

from __future__ import annotations

import os
import re

import jax
import jax.numpy as jnp
import pytest

from repro.kernels import registry


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler in this install
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    # a program compiled for a described chip is written to the persistent
    # cache but cannot be read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _shapes(sig, sharding, dtype=None):
    return [
        jax.ShapeDtypeStruct(
            shape, dtype if dtype and dt.startswith("float") else dt, sharding=sharding
        )
        for shape, dt in sig
    ]


def _compiled_text(fn, shapes) -> str:
    return jax.jit(fn).lower(*shapes).compile().as_text()


def _kernel(name):
    spec = registry.get(name)
    tiles = spec.tiles_for_backend("tpu")
    return spec, lambda *a: spec.pallas(*a, tiles=tiles, interpret=False)


def _loss_and_grad(kernel, argnums):
    """The fit's use: the loss value and its gradient, so the program holds
    the forward kernel and the backward kernel."""
    return jax.value_and_grad(lambda *a: jnp.sum(kernel(*a)), argnums=argnums)


def test_nomad_step_compiles_forward_and_backward(one_chip):
    from repro.kernels.nomad_step.ops import _sig

    _spec, kernel = _kernel("nomad_step")
    shapes = _shapes(_sig(8192, 15, 16, 256, 2), one_chip)
    assert "tpu_custom_call" in _compiled_text(kernel, shapes)
    bwd = _compiled_text(_loss_and_grad(kernel, (0, 1, 3)), shapes)
    assert bwd.count("tpu_custom_call") >= 2


def test_cauchy_mean_compiles_forward_and_backward(one_chip):
    from repro.kernels.cauchy_mean.ops import _sig

    _spec, kernel = _kernel("cauchy_mean")
    shapes = _shapes(_sig(8192, 256, 2), one_chip)
    assert "tpu_custom_call" in _compiled_text(kernel, shapes)
    assert _compiled_text(_loss_and_grad(kernel, 0), shapes).count("tpu_custom_call") >= 2


def test_frozen_attract_compiles_forward_and_backward(one_chip):
    from repro.kernels.frozen_attract.ops import _sig

    _spec, kernel = _kernel("frozen_attract")
    shapes = _shapes(_sig(4096, 15, 2), one_chip)
    assert "tpu_custom_call" in _compiled_text(kernel, shapes)
    bwd = _compiled_text(_loss_and_grad(kernel, (0, 3)), shapes)
    assert bwd.count("tpu_custom_call") >= 2


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_kmeans_assign_compiles(one_chip, dtype):
    from repro.kernels.kmeans_assign.ops import _sig

    _spec, kernel = _kernel("kmeans_assign")
    shapes = _shapes(_sig(8192, 256, 768), one_chip, dtype)
    assert "tpu_custom_call" in _compiled_text(kernel, shapes)


def test_pairwise_compiles_at_cluster_width(one_chip):
    from repro.kernels.pairwise.ops import _sig

    _spec, kernel = _kernel("pairwise")
    shapes = _shapes(_sig(5120, 5120, 768), one_chip)
    assert "tpu_custom_call" in _compiled_text(kernel, shapes)


def _computation(hlo: str, name: str) -> str:
    """The text of the computation ``name`` of an HLO module."""
    start = hlo.index(f"\n%{name} (") + 1
    return hlo[start : hlo.index("\n}\n", start)]


def test_fit_epoch_at_wiki60m_theta_updates_theta_in_place(one_chip, monkeypatch):
    """The fit epoch compiles at wiki60m's θ (K = 8,192 cells of C = 9,155
    rows). Its scan body writes θ once a step, by the ``row_add`` kernel,
    which takes θ transposed as a bitcast: no copy of the whole θ (a stray
    one costs ~1.5 ms a step), and no scatter of θ."""
    from repro.configs.base import NomadConfig
    from repro.core import nomad

    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "0")
    cfg = NomadConfig(
        n_points=60_000_000, dim=1024, n_clusters=8192, n_neighbors=15, n_noise=128,
        n_exact_negatives=16, batch_size=8192, n_epochs=80, steps_per_epoch=2,
        kernel_impl="pallas",
    )
    K, k = cfg.n_clusters, cfg.n_neighbors
    R = K * cfg.cluster_capacity
    assert R == 74_997_760

    def shape(s, dtype):
        return jax.ShapeDtypeStruct(s, dtype, sharding=one_chip)

    idx = {
        "knn_idx": shape((R, k), jnp.int32),
        "knn_w": shape((R, k), jnp.float32),
        "counts": shape((K,), jnp.int32),
        "cum_counts": shape((K,), jnp.int32),
    }
    epoch = nomad.make_epoch_fn(cfg, nomad.make_step_fn(cfg), cfg.steps_per_epoch)
    scalar = shape((), jnp.float32)
    hlo = epoch.lower(
        shape((R, 2), jnp.float32), idx, scalar, scalar, jax.random.key(0)
    ).compile().as_text()

    theta = re.escape(f"f32[{R},2]")
    scan = re.findall(rf"\n[^\n]*{theta}[^\n]* while\([^\n]*body=%([\w.\-]+)", hlo)
    assert len(scan) == 1, scan
    body = _computation(hlo, scan[0])
    whole = rf"(?:{theta}|{re.escape(f'f32[2,{R}]')})"
    writes = re.findall(
        rf"\n\s*(?:ROOT )?%\S+ = {whole}\{{[^}}]*\}} ([\w\-]+)\(([^\n]*)", body
    )
    ops = sorted(op for op, _ in writes if op != "get-tuple-element")
    assert ops == ["bitcast", "bitcast", "custom-call"], writes
    (call,) = [rest for op, rest in writes if op == "custom-call"]
    assert 'custom_call_target="tpu_custom_call"' in call and "row_add" in call
    assert nomad.SCOPE_SCATTER in call
    assert " scatter(" not in body
