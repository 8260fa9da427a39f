"""The fit's stage scopes and host spans.

The epoch programs name their stages with ``jax.named_scope``
(``core/nomad.py``: ``SCOPE_*``), so that a profiler trace can split the
device time of a step by stage. These tests compile the epochs on the CPU
and check that

* every scatter, gather, sort and kernel op of the scan body carries
  exactly one stage scope in its ``op_name``;
* the scopes are metadata only: the compiled instruction lines, with
  their ``metadata={...}`` stripped, are the same as those of the program
  built with ``jax.named_scope`` replaced by a no-op;

for the local epoch (each method; the NOMAD loss on both kernel paths),
the partial-refine epoch and the sharded epoch on four host devices (in a
child process, so that this one keeps its single device). And that
``run_epoch`` opens the ``nomad.fit.dispatch`` and ``nomad.fit.sync`` host
spans in a trace.
"""

from __future__ import annotations

import contextlib
import glob
import json
import os
import re
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import NomadConfig
from repro.core import nomad

SCOPES = (
    nomad.SCOPE_SAMPLE,
    nomad.SCOPE_GATHER,
    nomad.SCOPE_LOSS,
    nomad.SCOPE_SCATTER,
    nomad.SCOPE_MEANS,
)
CHECKED = ("scatter", "gather", "sort", "custom-call")
KERNELS = ("nomad_step_fwd", "nomad_step_bwd")
K, N_PER_CELL, STEPS = 4, 100, 3


def tiny_cfg(**kw) -> NomadConfig:
    base = dict(
        n_points=K * N_PER_CELL, dim=8, n_clusters=K, n_neighbors=5, n_noise=16,
        n_exact_negatives=4, batch_size=32, n_epochs=2, steps_per_epoch=STEPS,
    )
    return NomadConfig(**{**base, **kw})


def index_arrays(cfg: NomadConfig, **extra) -> dict:
    C = cfg.cluster_capacity
    counts = jnp.full((K,), N_PER_CELL, jnp.int32)
    rows = jnp.arange(K * C, dtype=jnp.int32)
    return {
        "knn_idx": jnp.tile(rows[:, None], (1, cfg.n_neighbors)),
        "knn_w": jnp.full((K * C, cfg.n_neighbors), 1.0 / cfg.n_neighbors, jnp.float32),
        "counts": counts,
        "cum_counts": jnp.cumsum(counts),
        **extra,
    }


def compiled_text(lower) -> str:
    return lower().compile().as_text()


def local_epoch(cfg, step_fn, idx):
    C = cfg.cluster_capacity
    epoch = nomad.make_epoch_fn(cfg, step_fn, STEPS)
    theta = jnp.zeros((K * C, cfg.out_dim), jnp.float32)
    return epoch.lower(theta, idx, 0.1, 0.05, jax.random.key(0))


@contextlib.contextmanager
def no_scope(name):
    yield


def unscoped(build) -> str:
    """``build()`` with ``jax.named_scope`` a no-op while it traces."""
    real = jax.named_scope
    jax.named_scope = no_scope
    try:
        return build()
    finally:
        jax.named_scope = real


def instructions(text: str) -> list:
    """``(name, opcode, op_name, line without metadata)`` of every HLO
    instruction of a compiled module's text."""
    out = []
    for line in text.splitlines():
        m = re.match(r"\s*(?:ROOT )?%(\S+) = (.*)$", line)
        if not m:
            continue
        rest = m.group(2)
        if rest.startswith("("):  # a tuple type
            depth = 0
            for i, ch in enumerate(rest):
                depth += (ch == "(") - (ch == ")")
                if depth == 0:
                    break
            rest = rest[i + 1:].lstrip()
        else:
            rest = rest.split(" ", 1)[1] if " " in rest else rest
        opcode = rest.split("(", 1)[0]
        op_name = re.search(r'op_name="([^"]*)"', line)
        out.append((
            m.group(1), opcode, op_name.group(1) if op_name else "",
            re.sub(r", metadata=\{[^}]*\}", "", line),
        ))
    return out


def scopes_of(op_name: str) -> set:
    return {part for part in op_name.split("/") if part in SCOPES}


def unscoped_in_body(text: str) -> list:
    """The scatter, gather, sort and kernel ops of the scan body that
    carry no stage scope or more than one; the kernels' ops must be in
    ``nomad_loss``."""
    bad = []
    for name, opcode, op_name, _ in instructions(text):
        if "/while/body/" not in op_name:
            continue
        kernel = any(k in op_name for k in KERNELS)
        if opcode in CHECKED or kernel:
            got = scopes_of(op_name)
            if len(got) != 1 or (kernel and got != {nomad.SCOPE_LOSS}):
                bad.append((name, opcode, op_name))
    return bad


def body_ops(text: str) -> list:
    return [i for i in instructions(text) if "/while/body/" in i[2] and i[1] in CHECKED]


def lines(text: str) -> list:
    return [i[3] for i in instructions(text)]


# (method, kernel path) of the local epoch; the InfoNC loss runs no kernel
LOCAL_BUILDS = [("nomad", "jnp"), ("nomad", "pallas"), ("infonc", "jnp")]


@pytest.mark.parametrize("method,impl", LOCAL_BUILDS)
def test_local_epoch_ops_carry_one_scope(method, impl):
    cfg = tiny_cfg(kernel_impl=impl)
    text = compiled_text(lambda: local_epoch(cfg, nomad.make_step_fn(cfg, method=method), index_arrays(cfg)))
    assert body_ops(text), "the scan body holds gathers and scatters"
    assert unscoped_in_body(text) == []
    if impl == "pallas":
        assert any(k in op for _, _, op, _ in instructions(text) for k in KERNELS)


@pytest.mark.parametrize("method,impl", LOCAL_BUILDS)
def test_local_epoch_scopes_are_metadata_only(method, impl):
    cfg = tiny_cfg(kernel_impl=impl)

    def build():
        return compiled_text(
            lambda: local_epoch(cfg, nomad.make_step_fn(cfg, method=method), index_arrays(cfg))
        )

    scoped, plain = build(), unscoped(build)
    assert any(scopes_of(i[2]) for i in instructions(scoped))
    assert not any(scopes_of(i[2]) for i in instructions(plain))
    assert lines(scoped) == lines(plain)


def _partial_build():
    cfg = tiny_cfg()
    aff = jnp.asarray([1, 3], jnp.int32)
    idx = index_arrays(
        cfg, aff_cells=aff, aff_cum_counts=jnp.cumsum(jnp.full((2,), N_PER_CELL, jnp.int32))
    )
    return compiled_text(lambda: local_epoch(cfg, nomad.make_partial_step_fn(cfg), idx))


def test_partial_epoch_ops_carry_one_scope():
    text = _partial_build()
    assert body_ops(text)
    assert unscoped_in_body(text) == []


def test_partial_epoch_scopes_are_metadata_only():
    assert lines(_partial_build()) == lines(unscoped(_partial_build))


def test_stage_scopes_are_single_path_components():
    assert len(set(SCOPES)) == 5
    assert all(re.fullmatch(r"nomad_[a-z]+", s) for s in SCOPES)


# ---- the sharded epoch, in a child process with four host devices -----------


def _sharded_report() -> str:
    from repro.core.distributed import SCOPE_EXCHANGE, make_sharded_epoch_fn, place_index
    from repro.launch.mesh import make_mesh

    cfg = tiny_cfg(n_clusters=K)
    C = cfg.cluster_capacity
    mesh = make_mesh((4,), ("data",))

    class Index:
        n_clusters, capacity = K, C
        knn_idx = np.tile(np.arange(K * C, dtype=np.int32)[:, None], (1, cfg.n_neighbors))
        knn_w = np.full((K * C, cfg.n_neighbors), 1.0 / cfg.n_neighbors, np.float32)
        counts = np.full((K,), N_PER_CELL, np.int32)

    idx, counts = place_index(Index, mesh, ("data",))

    def build():
        epoch = jax.jit(
            make_sharded_epoch_fn(
                cfg, mesh, shard_axes=("data",), steps_per_epoch=STEPS, n_shards=4
            )
        )
        theta = jnp.zeros((K * C, cfg.out_dim), jnp.float32)
        return epoch.lower(theta, idx, counts, 0.1, 0.05, jax.random.key(0)).compile().as_text()

    scoped, plain = build(), unscoped(build)
    return json.dumps({
        "devices": jax.device_count(),
        "body_ops": len(body_ops(scoped)),
        "unscoped": unscoped_in_body(scoped),
        "all_gather": any(i[1].startswith("all-gather") for i in instructions(scoped)),
        "collectives": sorted(
            (i[1], SCOPE_EXCHANGE in i[2].split("/"))
            for i in instructions(scoped)
            if i[1].startswith(("all-gather", "all-reduce"))
        ),
        "same": lines(scoped) == lines(plain),
    })


@pytest.fixture(scope="module")
def sharded():
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["JAX_PLATFORMS"] = "cpu"
    src = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "src")
    env["PYTHONPATH"] = src + os.pathsep + env.get("PYTHONPATH", "")
    r = subprocess.run(
        [sys.executable, os.path.abspath(__file__)],
        capture_output=True, text=True, timeout=600, env=env,
    )
    assert r.returncode == 0, r.stderr[-3000:]
    return json.loads(r.stdout.strip().splitlines()[-1])


def test_sharded_epoch_ops_carry_one_scope(sharded):
    assert sharded["devices"] == 4
    assert sharded["body_ops"] > 0 and sharded["all_gather"]
    assert sharded["unscoped"] == []


def test_sharded_epoch_scopes_are_metadata_only(sharded):
    assert sharded["same"]


def test_sharded_exchange_ops_carry_the_exchange_scope(sharded):
    """The means' all-gather and the loss's all-reduce are named
    ``nomad_exchange`` (nested in ``nomad_means`` and ``nomad_loss``)."""
    ops = [tuple(c) for c in sharded["collectives"]]
    assert {op for op, _ in ops} >= {"all-gather", "all-reduce"}, ops
    assert all(scoped for _, scoped in ops), ops


# ---- host spans ---------------------------------------------------------------


def test_run_epoch_opens_dispatch_and_sync_spans(tmp_path):
    import types

    from jax.profiler import ProfileData

    from repro.core.strategy import SPAN_DISPATCH, SPAN_SYNC, LocalStrategy

    cfg = tiny_cfg()
    idx = index_arrays(cfg)
    strategy = LocalStrategy()
    theta = strategy.prepare(
        cfg, "nomad",
        types.SimpleNamespace(knn_idx=idx["knn_idx"], knn_w=idx["knn_w"], counts=np.asarray(idx["counts"])),
        jnp.zeros((K * cfg.cluster_capacity, cfg.out_dim), jnp.float32),
    )
    strategy.run_epoch(theta, 0, 0.1, 0.05, jax.random.key(0))  # compiles
    with jax.profiler.trace(str(tmp_path)):
        _, loss = strategy.run_epoch(theta, 1, 0.1, 0.05, jax.random.key(1))
    assert isinstance(loss, float) and np.isfinite(loss)
    (path,) = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"), recursive=True)
    spans = {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith("nomad."):
                        spans[ev.name] = (ev.start_ns, ev.start_ns + ev.duration_ns)
    assert set(spans) == {SPAN_DISPATCH, SPAN_SYNC}
    # the enqueue comes first, the wait for the loss after it
    assert spans[SPAN_DISPATCH][1] <= spans[SPAN_SYNC][0]


if __name__ == "__main__":
    print(_sharded_report())
