"""Build driver: whole index builds, ``IndexBuilder(cfg).build(x)``, back to back.

Set-up makes the corpus from the seed on the device (``lib/corpus.py``,
one mixture component per cell) and holds it as a host ``np.ndarray``,
the container users pass. It then runs ``check_builds`` whole builds
through the path the window runs; the first compiles, and the output of
the first is what the reference checks. The window runs whole builds back
to back, each a new ``IndexBuilder`` on the same array, until ``--seconds``
have passed; none starts after that.

    build_rows_per_s = N · whole builds in the window
                       / (return of the last build - start)

``build()`` returns after its kNN weights have come back to the host.
After the window, the plain reference (``lib/ref_build.py``) checks the
first build: the placement exactly (``placement_bad``), the k-means
objective (``kmeans_gap``), the rows placed apart from the reference's
placement under the build's centroids (``assign_apart``), and on the
build's own cells the rows whose neighbours differ (``knn_apart``), the
widest relative gap of a neighbour's distance (``knn_d_gap``) and the
edges whose Eq. 6 weight differs (``weight_apart``). Every other build of
the run has to equal the first bit for bit (``builds_apart``).
"""

from __future__ import annotations

import gc
import sys
import time

import jax
import numpy as np

from lib import corpus, counts, gen, ref_build
from lib.result import Check, Outcome

FIELDS = ("perm", "counts", "x_rows", "centroids", "knn_idx", "knn_w")


def nomad_config(conf: dict):
    from repro.configs.base import NomadConfig

    keys = (
        "n_points", "dim", "n_clusters", "n_neighbors", "kmeans_iters", "kmeans_tol",
        "capacity_slack", "build_candidates", "build_block_rows", "build_max_rounds", "seed",
    )
    return NomadConfig(name=conf["name"], **{k: conf[k] for k in keys})


def make_corpus(cfg, traffic: dict, seed: int) -> np.ndarray:
    x, _ = corpus.mixture(
        jax.random.fold_in(gen.seed_key(seed), 0),
        n=cfg.n_points,
        dim=cfg.dim,
        n_components=cfg.n_clusters,
        **traffic["corpus"],
    )
    return np.asarray(x)


def as_dict(index) -> dict:
    return {f: np.asarray(getattr(index, f)) for f in FIELDS}


def same(a: dict, b: dict) -> bool:
    """Bit for bit, in every field of the index."""
    return all(
        a[f].shape == b[f].shape and a[f].dtype == b[f].dtype and a[f].tobytes() == b[f].tobytes()
        for f in FIELDS
    )


def build_flops(cfg, kmeans_steps: int) -> float:
    """FLOPs one build needs: ``kmeans_steps`` k-means E-steps (those that
    run: the program's Lloyd loop skips its body once it has converged),
    the candidate pass, and every cell's distance matrix."""
    n, K, C, d = cfg.n_points, cfg.n_clusters, cfg.cluster_capacity, cfg.dim
    return (
        kmeans_steps * counts.kmeans_assign(n, K, d)["flops"]
        + counts.pairwise(n, K, d)["flops"]
        + K * counts.pairwise(C, C, d)["flops"]
    )


def start(cfg, traffic: dict, seed: int, span, phase=lambda name: None, log=None):
    """Set-up: the corpus, and ``check_builds`` builds through
    :func:`build`'s path. Returns ``(build, x, got, others)``: the build
    call the window runs, the corpus, the first build's output and those
    of the rest. With ``log``, each build's ``BuildReport`` stage walls
    and straggler count are appended to it as a line."""
    from repro.index.build import IndexBuilder

    x = make_corpus(cfg, traffic, seed)
    phase("inputs")

    def build():
        with span("bench.build.run"):
            builder = IndexBuilder(cfg)
            out = as_dict(builder.build(x))
        if log is not None:
            r = builder.report
            stages = ", ".join(f"{k} {v:.3f}" for k, v in r.stage_s.items())
            log.append(f"{r.total_s:.3f} s ({stages}; stragglers {r.stragglers})")
        return out

    outs = []
    for b in range(int(traffic["check_builds"])):
        outs.append(build())
        phase(f"build{b}")
    return build, x, outs[0], outs[1:]


def compare(ref, got: dict, limits: dict, others=()) -> list:
    numbers = ref.compare(got)
    numbers["builds_apart"] = sum(not same(got, o) for o in others)
    return [Check(name, value, limits[name]) for name, value in numbers.items()]


def run(h):
    cfg = nomad_config(h.cell.config)
    log: list = []
    build, x, got, others = start(cfg, h.cell.traffic, h.seed, h.span, h.phase, log)

    # ---- the window ------------------------------------------------------
    t0 = time.perf_counter()
    setup_s = t0 - h.t_start
    done = 0
    with h.window():
        while time.perf_counter() - t0 < h.seconds:
            others.append(build())
            done += 1
        t1 = time.perf_counter()
    window_s = t1 - t0
    peak = h.memory_peak_bytes()
    for i, line in enumerate(log):  # set-up's first
        print(f"build {i}: {line}", file=sys.stderr)

    # ---- the reference ---------------------------------------------------
    gc.collect()
    t_ref = time.perf_counter()
    ref = ref_build.Reference(x, cfg)
    checks = compare(ref, got, h.cell.limits, others)
    print(f"reference {time.perf_counter() - t_ref:.3f} s", file=sys.stderr)

    K, C, d = cfg.n_clusters, cfg.cluster_capacity, cfg.dim
    return Outcome(
        metrics={"build_rows_per_s": done * cfg.n_points / window_s, "setup_s": setup_s},
        attempted=done,
        failed=0,
        checks=checks,
        memory_peak_bytes=peak,
        window_s=window_s,
        layer={
            "window_s": window_s,
            # every build of the run equals the checked one (builds_apart),
            # whose Lloyd loop stops where the reference's does from the
            # same start, but for a step on a few seeds (PERF.md)
            "flops": done * build_flops(cfg, ref.kmeans_steps),
            "pairwise_cells": done * K,
            "pairwise_cell": (C, d),
            "pairwise": counts.pairwise(C, C, d),
        },
    )
