"""Incremental-maps benchmark: grow a fitted map and prove it didn't jump.

Exercises the whole ``partial_fit`` pipeline at benchmark size — base fit
→ place/admit/patch/refine/version — and emits the two things CI gates:

* **stage walls** (``stages.*.wall_s``): the incremental path must stay
  incremental — a regression to refit-scale cost gates like any other
  wall via ``benchmarks/check_regression.py``.
* **map-quality scores** (``scores.*_score``): gated as *floors* —
  ``stability_score`` (k-neighborhood overlap of the old rows between the
  previous and grown map, :func:`repro.metrics.map_stability`) and
  ``np_old_score`` (neighborhood preservation of the old rows' original
  vectors in the grown map). ``np_joint_score`` — the same metric for a
  full refit of X ∥ Y — is reported beside them so the committed baseline
  records how close incremental comes to the refit yardstick.

  PYTHONPATH=src python benchmarks/partial_fit.py --quick --json BENCH_partial_fit.json
"""

from __future__ import annotations

import argparse
import json
import shutil
import sys
import tempfile
import time

import numpy as np


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--n", type=int, default=20_000, help="base corpus rows")
    ap.add_argument("--append", type=int, default=2_000, help="rows to grow by")
    ap.add_argument("--dim", type=int, default=32)
    ap.add_argument("--clusters", type=int, default=32)
    ap.add_argument("--neighbors", type=int, default=10)
    ap.add_argument("--epochs", type=int, default=12, help="base-fit epochs")
    ap.add_argument("--refine-epochs", type=int, default=3)
    ap.add_argument("--components", type=int, default=16, help="mixture modes")
    ap.add_argument("--k", type=int, default=10, help="metric neighborhood size")
    ap.add_argument("--queries", type=int, default=1_000, help="metric queries")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--quick", action="store_true", help="CI size")
    ap.add_argument("--json", default="", help="write BENCH_partial_fit.json here")
    return ap.parse_args(argv)


def _mixture(n, dim, components, seed):
    rng = np.random.default_rng(seed)
    centers = np.random.default_rng(9).normal(0, 5, (components, dim))
    labels = rng.integers(0, components, n)
    return (centers[labels] + rng.normal(0, 1, (n, dim))).astype(np.float32)


def build_report(args) -> dict:
    from repro.configs.base import NomadConfig
    from repro.core.nomad import NomadProjection
    from repro.metrics import map_stability, neighborhood_preservation

    if args.quick:
        args.n, args.append = min(args.n, 2_000), min(args.append, 300)
        args.dim, args.clusters = min(args.dim, 16), min(args.clusters, 16)
        args.neighbors, args.epochs = min(args.neighbors, 8), min(args.epochs, 8)
        args.queries = min(args.queries, 800)

    x = _mixture(args.n, args.dim, args.components, args.seed + 1)
    y = _mixture(args.append, args.dim, args.components, args.seed + 2)

    def cfg_for(n, ckdir=""):
        return NomadConfig(
            n_points=n,
            dim=args.dim,
            n_clusters=args.clusters,
            n_neighbors=args.neighbors,
            n_epochs=args.epochs,
            partial_refine_epochs=args.refine_epochs,
            strategy="local",
            build_strategy="local",
            seed=args.seed,
            checkpoint_dir=ckdir,
        )

    ckdir = tempfile.mkdtemp(prefix="bench-partial-fit-")
    try:
        t0 = time.time()
        est = NomadProjection(cfg_for(args.n, ckdir))
        base = est.fit(x)
        fit_base_s = time.time() - t0

        pf = est.partial_fit(y)

        t1 = time.time()
        joint = NomadProjection(cfg_for(args.n + args.append)).fit(
            np.concatenate([x, y])
        )
        fit_joint_s = time.time() - t1

        mk = dict(k=args.k, n_queries=args.queries, seed=args.seed)
        stability = map_stability(base.embedding, pf.embedding[: args.n], **mk)
        np_old = neighborhood_preservation(x, pf.embedding[: args.n], **mk)
        np_joint = neighborhood_preservation(x, joint.embedding[: args.n], **mk)
    finally:
        shutil.rmtree(ckdir, ignore_errors=True)

    stages = {"fit_base": {"wall_s": round(fit_base_s, 3)}}
    for name in ("place", "admit", "patch_knn", "patch_rows", "refine", "version"):
        if name in pf.stage_s:
            stages[name] = {"wall_s": round(pf.stage_s[name], 3)}
    stages["partial_fit_total"] = {"wall_s": round(pf.wall_time_s, 3)}
    stages["fit_joint"] = {"wall_s": round(fit_joint_s, 3)}

    return {
        "benchmark": "partial_fit",
        "config": {
            "n": args.n,
            "append": args.append,
            "dim": args.dim,
            "clusters": args.clusters,
            "neighbors": args.neighbors,
            "epochs": args.epochs,
            "refine_epochs": args.refine_epochs,
            "metric_k": args.k,
            "metric_queries": args.queries,
        },
        "admission": {
            "n_split_cells": pf.n_split_cells,
            "n_new_cells": pf.n_new_cells,
            "n_affected_cells": int(pf.affected_cells.size),
            "version": pf.version,
        },
        "stages": stages,
        # *_score leaves are FLOOR-gated by check_regression.py: a fresh
        # score below baseline - slack fails, a faster wall never does
        "scores": {
            "stability_score": round(stability, 4),
            "np_old_score": round(np_old, 4),
            "np_joint_score": round(np_joint, 4),
        },
    }


def run(quick: bool = False):
    """benchmarks.run entry: [(name, us_per_call, derived), …]."""
    args = parse_args(["--quick"] if quick else [])
    report = build_report(args)
    rows = [
        (f"partial_fit.{name}", d["wall_s"] * 1e6, "")
        for name, d in report["stages"].items()
    ]
    sc = report["scores"]
    rows.append(
        (
            "partial_fit.scores",
            0.0,
            f"stability={sc['stability_score']:.3f} "
            f"np_old={sc['np_old_score']:.3f} "
            f"np_joint={sc['np_joint_score']:.3f}",
        )
    )
    return rows


def main(argv=None) -> int:
    args = parse_args(argv)
    report = build_report(args)
    print(f"{'stage':>18}  wall_s")
    for name, d in report["stages"].items():
        print(f"{name:>18}  {d['wall_s']:.3f}")
    for name, v in report["scores"].items():
        print(f"{name:>18}  {v:.4f}")
    a = report["admission"]
    print(
        f"admission: {a['n_split_cells']} split(s), {a['n_new_cells']} new "
        f"cell(s), {a['n_affected_cells']} affected"
    )
    if args.json:
        with open(args.json, "w") as f:
            json.dump(report, f, indent=1)
        print("report →", args.json)
    return 0


if __name__ == "__main__":
    from repro.runtime import enable_compile_cache

    enable_compile_cache()
    sys.exit(main())
