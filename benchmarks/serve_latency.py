"""Serve-path latency benchmark: batch size vs p50/p99 placement latency.

The first inference-side hot path: fits a small map once, freezes it, then
times ``MapServer.transform`` across microbatch sizes — per-batch wall
clocks give p50/p99 placement latency and throughput (points/s).

  PYTHONPATH=src python benchmarks/serve_latency.py --json BENCH_serve_latency.json
  PYTHONPATH=src python benchmarks/serve_latency.py --n-fit 1500 --clusters 8 \
      --epochs 3 --batches 64,256 --repeat 3

CI smoke-runs this at tiny N on every push and gates the recorded walls
against ``benchmarks/baselines/serve_latency.json`` via
``benchmarks/check_regression.py`` (>25% regression fails the job).
"""

from __future__ import annotations

import argparse
import json
import time

import numpy as np


def bench(
    n_fit=20_000,
    dim=64,
    clusters=16,
    neighbors=15,
    epochs=10,
    batch_sizes=(64, 256, 1024),
    repeat=5,
    steps=24,
    strategy="auto",
    seed=0,
):
    from repro.configs.base import NomadConfig
    from repro.core.nomad import NomadProjection
    from repro.data.synthetic import gaussian_mixture

    cfg = NomadConfig(
        n_points=n_fit,
        dim=dim,
        n_clusters=clusters,
        n_neighbors=neighbors,
        n_epochs=epochs,
        batch_size=min(1024, n_fit),
        transform_steps=steps,
        serve_strategy=strategy,
        seed=seed,
    )
    x, _ = gaussian_mixture(n_fit, dim, n_components=min(12, clusters), seed=seed)
    est = NomadProjection(cfg)
    t0 = time.time()
    est.fit(x)
    fit_s = time.time() - t0

    out = {
        "n_fit": n_fit,
        "dim": dim,
        "clusters": clusters,
        "neighbors": neighbors,
        "transform_steps": steps,
        "fit_s": fit_s,
        "batch": {},
    }
    for bs in batch_sizes:
        server = est.map_server(microbatch=bs)
        q, _ = gaussian_mixture(
            bs * server.n_shards, dim, n_components=min(12, clusters), seed=seed + 1
        )
        from repro.serve import TransformResult

        server.transform(q, seed=seed)  # warm-up: pays the jit compile
        lats = []
        for r in range(max(1, repeat)):
            res = server.transform(q, seed=seed + r)
            lats.extend(res.batch_latency_s)
        # pooled across repeats through the shared TransformResult helper —
        # the same percentile math res.p50_latency_s uses per call
        p50 = TransformResult.percentile(lats, 50)
        p99 = TransformResult.percentile(lats, 99)
        out["batch"][str(bs)] = {
            # "wall_s" is the stage-wall key check_regression.py gates on
            "wall_s": p50,
            "p50_s": p50,
            "p99_s": p99,
            "points_per_s": float(len(q) / p50),
            "n_runs": len(lats),
            "strategy": server.strategy,
            "n_shards": server.n_shards,
        }
    return out


def run(quick: bool = False):
    """benchmarks/run.py contract: [(name, us_per_call, derived), …]."""
    res = bench(
        n_fit=1500 if quick else 20_000,
        dim=16 if quick else 64,
        clusters=8 if quick else 16,
        neighbors=5 if quick else 15,
        epochs=3 if quick else 10,
        batch_sizes=(64, 256) if quick else (64, 256, 1024),
        repeat=3 if quick else 5,
        steps=8 if quick else 24,
    )
    return [
        (
            f"serve/transform_b{bs}",
            r["p50_s"] * 1e6,
            f"p99={r['p99_s'] * 1e3:.1f}ms tput={r['points_per_s']:.0f}pts/s "
            f"({r['strategy']})",
        )
        for bs, r in res["batch"].items()
    ]


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--n-fit", type=int, default=20_000)
    ap.add_argument("--dim", type=int, default=64)
    ap.add_argument("--clusters", type=int, default=16)
    ap.add_argument("--neighbors", type=int, default=15)
    ap.add_argument("--epochs", type=int, default=10)
    ap.add_argument("--batches", default="64,256,1024", help="comma-separated")
    ap.add_argument("--repeat", type=int, default=5, help="timed transforms per batch size")
    ap.add_argument("--steps", type=int, default=24, help="frozen NOMAD steps per query")
    ap.add_argument("--strategy", default="auto", choices=["auto", "local", "sharded"])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--json", default="", help="write the report to this path")
    args = ap.parse_args()

    res = bench(
        n_fit=args.n_fit,
        dim=args.dim,
        clusters=args.clusters,
        neighbors=args.neighbors,
        epochs=args.epochs,
        batch_sizes=tuple(int(b) for b in args.batches.split(",")),
        repeat=args.repeat,
        steps=args.steps,
        strategy=args.strategy,
        seed=args.seed,
    )
    print(json.dumps(res, indent=1))
    if args.json:
        with open(args.json, "w") as f:
            json.dump(res, f, indent=1)
        print(f"# wrote {args.json}")
    return 0


if __name__ == "__main__":
    from repro.runtime import enable_compile_cache

    enable_compile_cache()
    import sys

    sys.exit(main())
