"""Sharded fit driver: whole dispatches of ``ShardedStrategy.run_epoch``
over the cell's chips (paper Fig. 2).

Set-up makes the state a fit runs on from the seed, on the chips and
already split by rows as the strategy splits it (``lib/gen.py:fit_inputs``
under ``jax.jit`` with the row sharding as its ``out_shardings``; the
values do not depend on the sharding, so for the same sizes and seed they
are those of ``drivers/fit.py``). It hands the state to
``ShardedStrategy.prepare`` over a flat mesh of the cell's chips, the
layer ``NomadProjection(cfg, strategy="sharded")`` uses, and drives the
first ``check_dispatches`` dispatches through ``run_epoch``; the first
compiles. The window runs whole dispatches back to back until
``--seconds`` have passed; none starts after that. Each shard runs
ceil(steps_per_epoch / shards) steps of ``batch`` heads a dispatch:

    fit_points_per_s = shards · batch · shard steps · whole dispatches in
                       the window / (end of the last one's loss sync - start)

After the window the program's state is freed, the inputs are made again
from the seed, and the plain reference (``lib/ref_fit_sharded.py``)
follows the same dispatches, each shard on its own chip. Compared as
``drivers/fit.py`` compares: each dispatch's mean loss, the norms of θ's
change over the first dispatch and over all, and exactly the rows the
first dispatch moved on one side only. ``flops`` and ``kernel_calls`` are
per chip: the trace's self times are means over the chips, and
``fit_mfu`` divides by one chip's peak.
"""

from __future__ import annotations

import functools
import gc
import sys
import time
import types

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

from drivers.fit import change_norm, compare, moved, nomad_config, schedule
from lib import counts, gen, ref_fit_sharded
from lib.result import Outcome


def sharded_config(conf: dict):
    return nomad_config(conf).replace(strategy="sharded")


def mesh_of(conf: dict, devices: list):
    """The configuration's flat mesh over ``devices``, one shard a chip."""
    from repro.core.strategy import flat_mesh

    (axis,) = conf["mesh"]["axes"]
    if len(devices) != conf["shards"]:
        raise ValueError(f"{conf['shards']} shards need as many chips, got {len(devices)}")
    return flat_mesh(devices, axis)


def shard_steps(cfg, n_shards: int) -> int:
    return -(-cfg.resolved_steps_per_epoch() // n_shards)


@functools.lru_cache(maxsize=None)
def _inputs_fn(cfg, spread: float, mesh):
    rows = NamedSharding(mesh, P(mesh.axis_names, None))
    return jax.jit(
        lambda key, scale: gen.fit_inputs(
            key, scale, n=cfg.n_points, n_cells=cfg.n_clusters,
            capacity=cfg.cluster_capacity, k=cfg.n_neighbors, out_dim=cfg.out_dim,
            spread=spread,
        ),
        out_shardings=(rows, rows, rows, NamedSharding(mesh, P())),
    )


def make_inputs(cfg, traffic: dict, seed: int, mesh):
    """``(theta0, knn_idx, knn_w, counts)`` from the seed, θ and the graph
    split by rows over ``mesh``, the counts on every chip."""
    make = _inputs_fn(cfg, float(traffic["count_spread"]), mesh)
    return make(jax.random.fold_in(gen.seed_key(seed), 0), cfg.init_scale)


def reference_fn(cfg, seed: int, n_shards: int, **kw):
    """``epoch_fn`` of the plain reference (or of a control or fault of it:
    ``dtype``, ``heads_kept``, ``own_means_only``)."""

    def epoch_fn(theta, graph, e):
        knn_idx, knn_w, cnt = graph
        lr0, lr1, key = schedule(cfg, seed, e)
        return ref_fit_sharded.dispatch(
            theta, knn_idx, knn_w, cnt, lr0, lr1, key,
            n=cfg.n_points, capacity=cfg.cluster_capacity, batch=cfg.batch_size,
            n_neg=cfg.n_exact_negatives, n_noise=cfg.n_noise,
            steps=shard_steps(cfg, n_shards), n_shards=n_shards, **kw,
        )

    return epoch_fn


def record(got: dict, theta, theta0, loss, e: int) -> None:
    got["losses"].append(float(loss))
    got["norms"].append(float(change_norm(theta, theta0)))
    if e == 0:
        got["moved1"] = jax.device_get(moved(theta, theta0))


def readings(cfg, traffic: dict, seed: int, mesh, epoch_fn) -> dict:
    """As ``drivers/fit.py:readings``, on the state split over ``mesh``."""
    theta0, knn_idx, knn_w, cnt = make_inputs(cfg, traffic, seed, mesh)
    theta, out = theta0, {"losses": [], "norms": []}
    for e in range(int(traffic["check_dispatches"])):
        theta, loss = epoch_fn(theta, (knn_idx, knn_w, cnt), e)
        record(out, theta, theta0, loss, e)
    return out


def start(cfg, traffic: dict, seed: int, mesh, span, phase=lambda name: None):
    """Set-up: the state from the seed, ``ShardedStrategy.prepare`` on it
    and the first ``check_dispatches`` dispatches through ``run_epoch``.
    Returns ``(dispatch, theta, got)``; the window goes on with
    ``dispatch(theta, e)``."""
    from repro.core.strategy import ShardedStrategy

    theta0, knn_idx, knn_w, cnt = jax.block_until_ready(make_inputs(cfg, traffic, seed, mesh))
    phase("inputs")
    strategy = ShardedStrategy(mesh=mesh)
    theta = strategy.prepare(
        cfg, "nomad", types.SimpleNamespace(knn_idx=knn_idx, knn_w=knn_w, counts=cnt), theta0
    )
    del knn_idx, knn_w, cnt
    jax.block_until_ready(theta)
    phase("prepare")

    def dispatch(theta, e):
        lr0, lr1, key = schedule(cfg, seed, e)
        with span("bench.fit.run_epoch"):
            return strategy.run_epoch(theta, e, lr0, lr1, key)

    got = {"losses": [], "norms": []}
    for e in range(int(traffic["check_dispatches"])):
        theta, loss = dispatch(theta, e)
        record(got, theta, theta0, loss, e)
        phase(f"dispatch{e}")
    return dispatch, theta, got


def run(h):
    conf = h.cell.config
    cfg = sharded_config(conf)
    traffic = h.cell.traffic
    mesh = mesh_of(conf, h.devices)
    n_check = int(traffic["check_dispatches"])
    dispatch, theta, got = start(cfg, traffic, h.seed, mesh, h.span, h.phase)
    n_shards = mesh.size
    steps = shard_steps(cfg, n_shards)

    # ---- the window ------------------------------------------------------
    t0 = time.perf_counter()
    setup_s = t0 - h.t_start
    done, e = 0, n_check
    with h.window():
        while time.perf_counter() - t0 < h.seconds:
            theta, _ = dispatch(theta, e)
            done += 1
            e += 1
        t1 = time.perf_counter()
    window_s = t1 - t0
    peak = h.memory_peak_bytes()
    heads = n_shards * cfg.batch_size * steps

    # ---- the reference, on the program's state freed --------------------
    del theta, dispatch
    gc.collect()
    t_ref = time.perf_counter()
    want = readings(cfg, traffic, h.seed, mesh, reference_fn(cfg, h.seed, n_shards))
    checks = compare(got, want, h.cell.limits)
    print(f"reference {time.perf_counter() - t_ref:.3f} s", file=sys.stderr)

    K, d = cfg.n_clusters, cfg.out_dim
    shape = (cfg.batch_size, cfg.n_neighbors, cfg.n_exact_negatives, K, d)
    step_flops = counts.fit_step_flops(*shape)  # every chip's heads meet all K means
    refresh_flops = counts.means_refresh_flops(K * cfg.cluster_capacity // n_shards, d, K // n_shards)
    return Outcome(
        metrics={"fit_points_per_s": done * heads / window_s, "setup_s": setup_s},
        attempted=done,
        failed=0,
        checks=checks,
        memory_peak_bytes=peak,
        window_s=window_s,
        layer={
            "window_s": window_s,
            "flops": done * (steps * step_flops + refresh_flops),
            "kernel_calls": done * steps,
            "n_shards": n_shards,
            # the K means gathered to each chip, and the loss's all-reduce
            "exchange_bytes": K * d * 4 + 4,
            "nomad_step_blocks": (
                f"f32[{cfg.n_neighbors * d},{cfg.batch_size}]",
                f"f32[{cfg.n_exact_negatives * d},{cfg.batch_size}]",
            ),
            "nomad_step_fwd": counts.nomad_step_fwd(*shape),
            "nomad_step_bwd": counts.nomad_step_bwd(*shape),
        },
    )

