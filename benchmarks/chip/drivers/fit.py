"""Fit driver: whole dispatches of ``LocalStrategy.run_epoch`` on one chip.

Set-up makes the state a fit runs on from the seed, on the device
(``lib/gen.py:fit_inputs``: θ, the in-cell kNN graph with its weights and
the cell counts, laid out as the index build lays them out), hands it to
``LocalStrategy.prepare``, and drives the first ``check_dispatches``
dispatches through ``run_epoch``, the same call and state the window then
runs on. The first of them compiles. The window runs whole dispatches
back to back until ``--seconds`` have passed; none starts after that.

    fit_points_per_s = batch · steps_per_epoch · whole dispatches in the
                       window / (end of the last one's loss sync - start)

After the window the program's state is freed, the inputs are made again
from the seed, and the plain reference (``lib/ref_fit.py``) follows the
same ``check_dispatches`` dispatches. Compared, each as a relative gap
against the reference: the mean loss of each of those dispatches, the
norm of θ's change over the first, and over all of them; and, exactly,
the rows that moved in the first dispatch on one side only
(``moved_apart``): which rows a dispatch moves is fixed by the sampling,
not by rounding, so a fault confined to a few rows, one cell or the
padded slots shows there where no norm or loss moves.
"""

from __future__ import annotations

import gc
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from lib import counts, gen, ref_fit
from lib.result import Check, Outcome


def nomad_config(conf: dict):
    from repro.configs.base import NomadConfig

    keys = (
        "n_points", "dim", "out_dim", "n_clusters", "n_neighbors", "n_noise",
        "n_exact_negatives", "batch_size", "n_epochs", "steps_per_epoch",
        "capacity_slack", "init_scale", "kmeans_iters",
    )
    return NomadConfig(
        name=conf["name"], strategy="local", **{k: conf[k] for k in keys}
    )


@jax.jit
def change_norm(a, b):
    return jnp.sqrt(jnp.sum(jnp.square(a.astype(jnp.float32) - b.astype(jnp.float32))))


@jax.jit
def moved(theta, theta0):
    """Which rows of ``theta`` differ from ``theta0`` at all, packed eight
    rows to a byte."""
    return jnp.packbits(jnp.any(theta != theta0, axis=1))


def make_inputs(cfg, traffic: dict, seed: int):
    return gen.fit_inputs(
        jax.random.fold_in(gen.seed_key(seed), 0),
        cfg.init_scale,
        n=cfg.n_points,
        n_cells=cfg.n_clusters,
        capacity=cfg.cluster_capacity,
        k=cfg.n_neighbors,
        out_dim=cfg.out_dim,
        spread=float(traffic["count_spread"]),
    )


def schedule(cfg, seed: int, e: int):
    """(lr0, lr1, key) of dispatch ``e``: the paper's linear anneal over
    ``n_epochs`` epochs, one key per dispatch."""
    lr = cfg.resolved_lr0()
    f0, f1 = 1.0 - e / cfg.n_epochs, 1.0 - (e + 1) / cfg.n_epochs
    key = jax.random.fold_in(jax.random.fold_in(gen.seed_key(seed), 1), e)
    return lr * f0, lr * f1, key


def rel_gap(got: float, want: float) -> float:
    return abs(got - want) / max(abs(want), 1e-30)


def readings(cfg, traffic: dict, seed: int, epoch_fn) -> dict:
    """Mean losses and θ-change norms of the first ``check_dispatches``
    dispatches of ``epoch_fn(theta, graph, e) -> (theta, loss)``, and the
    rows the first moved (``moved1``, packed)."""
    theta0, knn_idx, knn_w, cnt = make_inputs(cfg, traffic, seed)
    theta, out = theta0, {"losses": [], "norms": []}
    for e in range(int(traffic["check_dispatches"])):
        theta, loss = epoch_fn(theta, (knn_idx, knn_w, cnt), e)
        out["losses"].append(float(loss))
        out["norms"].append(float(change_norm(theta, theta0)))
        if e == 0:
            out["moved1"] = jax.device_get(moved(theta, theta0))
    return out


def reference_fn(cfg, seed: int, *, dtype=jnp.float32, heads_kept=None):
    """``epoch_fn`` of the plain reference (or of a control of it)."""

    def epoch_fn(theta, graph, e):
        knn_idx, knn_w, cnt = graph
        lr0, lr1, key = schedule(cfg, seed, e)
        return ref_fit.dispatch(
            theta, knn_idx, knn_w, cnt, lr0, lr1, key,
            n=cfg.n_points, capacity=cfg.cluster_capacity, batch=cfg.batch_size,
            n_neg=cfg.n_exact_negatives, n_noise=cfg.n_noise,
            steps=cfg.resolved_steps_per_epoch(), heads_kept=heads_kept, dtype=dtype,
        )

    return epoch_fn


def gaps(got: dict, want: dict) -> dict:
    """The compared numbers: relative gaps of the program's readings (or a
    control's) to the reference's, and the count of rows that the first
    dispatch moved on one side only."""
    return {
        "loss_gap": max(rel_gap(g, w) for g, w in zip(got["losses"], want["losses"])),
        "update1_gap": rel_gap(got["norms"][0], want["norms"][0]),
        "change_gap": rel_gap(got["norms"][-1], want["norms"][-1]),
        "moved_apart": int(np.unpackbits(got["moved1"] ^ want["moved1"]).sum()),
    }


def compare(got: dict, want: dict, limits: dict) -> list:
    return [Check(name, value, limits[name]) for name, value in gaps(got, want).items()]


def start(cfg, traffic: dict, seed: int, span, phase=lambda name: None):
    """Set-up: the state from the seed, ``LocalStrategy.prepare`` on it, and
    the first ``check_dispatches`` dispatches through ``run_epoch``
    (recorded as :func:`readings` records them).
    Returns ``(dispatch, theta, got)``: the window goes on with the same
    strategy object through ``dispatch(theta, e)``. ``phase(name)`` marks
    the end of each part of set-up."""
    import types

    from repro.core.strategy import LocalStrategy

    theta0, knn_idx, knn_w, cnt = jax.block_until_ready(make_inputs(cfg, traffic, seed))
    phase("inputs")
    strategy = LocalStrategy()
    theta = strategy.prepare(
        cfg, "nomad", types.SimpleNamespace(knn_idx=knn_idx, knn_w=knn_w, counts=cnt), theta0
    )
    del knn_idx, knn_w, cnt
    phase("prepare")

    def dispatch(theta, e):
        lr0, lr1, key = schedule(cfg, seed, e)
        with span("bench.fit.run_epoch"):
            return strategy.run_epoch(theta, e, lr0, lr1, key)

    got = {"losses": [], "norms": []}
    for e in range(int(traffic["check_dispatches"])):
        theta, loss = dispatch(theta, e)
        got["losses"].append(loss)
        got["norms"].append(float(change_norm(theta, theta0)))
        if e == 0:
            got["moved1"] = jax.device_get(moved(theta, theta0))
        phase(f"dispatch{e}")
    return dispatch, theta, got


def run(h):
    cfg = nomad_config(h.cell.config)
    traffic = h.cell.traffic
    n_check = int(traffic["check_dispatches"])
    steps = cfg.resolved_steps_per_epoch()
    dispatch, theta, got = start(cfg, traffic, h.seed, h.span, h.phase)

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
    heads = cfg.batch_size * steps  # LocalStrategy: one shard

    # ---- the reference, on the program's state freed --------------------
    del theta, dispatch
    gc.collect()
    t_ref = time.perf_counter()
    want = readings(cfg, traffic, h.seed, reference_fn(cfg, h.seed))
    checks = compare(got, want, h.cell.limits)
    print(f"reference {time.perf_counter() - t_ref:.3f} s", file=sys.stderr)

    shape = (cfg.batch_size, cfg.n_neighbors, cfg.n_exact_negatives, cfg.n_clusters, cfg.out_dim)
    step_flops = counts.fit_step_flops(*shape)
    refresh_flops = counts.means_refresh_flops(
        cfg.n_clusters * cfg.cluster_capacity, cfg.out_dim, cfg.n_clusters
    )
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
            "nomad_step_blocks": (
                f"f32[{cfg.n_neighbors * cfg.out_dim},{cfg.batch_size}]",
                f"f32[{cfg.n_exact_negatives * cfg.out_dim},{cfg.batch_size}]",
            ),
            "nomad_step_fwd": counts.nomad_step_fwd(*shape),
            "nomad_step_bwd": counts.nomad_step_bwd(*shape),
        },
    )
