"""Service load test: N concurrent clients vs the in-process service.

The serving-layer acceptance benchmark: fits a small map once, stands up
the full service stack (registry → cache → batching engine → MapServer),
then drives it with concurrent client threads issuing ragged ``/project``
requests. Per client-count scenario it reports request p50/p99 wall,
throughput (rows/s), the batching engine's batch-fill ratio, and cache
hits:

  PYTHONPATH=src python benchmarks/service_load.py --json BENCH_service_load.json
  PYTHONPATH=src python benchmarks/service_load.py --n-fit 1500 --clusters 8 \
      --epochs 3 --clients 1,8 --requests 20 --rows 24

Two transports:

* ``core`` (default) — clients call ``MapService.project`` directly; the
  dependency-free path every install can run, and the one the committed
  baseline (``benchmarks/baselines/service_load.json``) gates via
  ``benchmarks/check_regression.py``;
* ``http`` — the same requests through the FastAPI app over httpx's
  in-process ASGI transport (needs the ``[service]`` extra); measures the
  marshalling overhead on top of the core numbers.

CI's ``service`` job smoke-runs both at tiny N on every push and gates
the core walls against the baseline (>25% AND ≥0.25s regression fails).
"""

from __future__ import annotations

import argparse
import json
import threading
import time


def _client_requests(n_requests, rows, dim, seed, cache_frac):
    """One client's request schedule: mostly unique queries, a
    ``cache_frac`` fraction repeating the first one (cache exercise)."""
    from repro.data.synthetic import gaussian_mixture

    reqs = []
    for i in range(n_requests):
        if i > 0 and cache_frac > 0 and (i % max(1, round(1 / cache_frac))) == 0:
            reqs.append(reqs[0])  # identical (query, seed) → service cache hit
        else:
            q, _ = gaussian_mixture(
                max(1, rows + (i % 5) - 2), dim, n_components=4, seed=seed + i
            )
            reqs.append((q, seed + i))
    return reqs


def _drive(project, clients, n_requests, rows, dim, cache_frac, timeout=120.0):
    """Run the client storm; returns (per-request walls, total wall)."""
    walls = [[] for _ in range(clients)]
    errs = []
    start = threading.Barrier(clients + 1)

    def run(c):
        try:
            reqs = _client_requests(n_requests, rows, dim, 10_000 * (c + 1), cache_frac)
            start.wait()
            for q, seed in reqs:
                t0 = time.time()
                project(q, seed)
                walls[c].append(time.time() - t0)
        except Exception as e:  # noqa: BLE001
            errs.append(e)

    threads = [threading.Thread(target=run, args=(c,)) for c in range(clients)]
    for t in threads:
        t.start()
    start.wait()
    t0 = time.time()
    for t in threads:
        t.join(timeout)
    total = time.time() - t0
    if errs:
        raise errs[0]
    flat = [w for ws in walls for w in ws]
    if len(flat) != clients * n_requests:
        raise RuntimeError(f"dropped requests: {len(flat)}/{clients * n_requests}")
    return flat, total


def bench(
    n_fit=20_000,
    dim=64,
    clusters=16,
    neighbors=15,
    epochs=10,
    steps=24,
    microbatch=256,
    max_delay_s=0.002,
    clients_list=(1, 8, 32),
    n_requests=30,
    rows=64,
    cache_frac=0.25,
    transport="core",
    seed=0,
):
    from repro.configs.base import NomadConfig
    from repro.core.nomad import NomadProjection
    from repro.data.synthetic import gaussian_mixture
    from repro.serve import FrozenMap, TransformResult
    from repro.service import MapService

    cfg = NomadConfig(
        n_points=n_fit,
        dim=dim,
        n_clusters=clusters,
        n_neighbors=neighbors,
        n_epochs=epochs,
        batch_size=min(1024, n_fit),
        transform_steps=steps,
        serve_microbatch=microbatch,
        service_max_delay_s=max_delay_s,
        seed=seed,
    )
    x, _ = gaussian_mixture(n_fit, dim, n_components=min(12, clusters), seed=seed)
    est = NomadProjection(cfg)
    t0 = time.time()
    est.fit(x)
    fit_s = time.time() - t0
    frozen = FrozenMap.from_fit(est._fit_result, cfg)

    out = {
        "n_fit": n_fit,
        "dim": dim,
        "clusters": clusters,
        "transform_steps": steps,
        "microbatch": microbatch,
        "max_delay_s": max_delay_s,
        "requests_per_client": n_requests,
        "rows_per_request": rows,
        "cache_frac": cache_frac,
        "transport": transport,
        "fit_s": fit_s,
        "clients": {},
    }
    for clients in clients_list:
        # a fresh stack per scenario: counters and cache start cold
        svc = MapService()
        handle = svc.registry.add(frozen)  # warm: compile paid before timing

        if transport == "core":
            def project(q, s, _svc=svc):
                _svc.project(q, seed=s)
        elif transport == "http":
            from fastapi.testclient import TestClient

            from repro.service.app import create_app

            client = TestClient(create_app(svc))

            def project(q, s, _c=client):
                r = _c.post("/project", json={"rows": q.tolist(), "seed": int(s)})
                r.raise_for_status()
        else:
            raise ValueError(f"unknown transport {transport!r}")

        walls, total = _drive(project, clients, n_requests, rows, dim, cache_frac)
        stats = handle.batcher.stats
        p50 = TransformResult.percentile(walls, 50)
        p99 = TransformResult.percentile(walls, 99)
        out["clients"][f"c{clients}"] = {
            # "wall_s" is the stage-wall key check_regression.py gates on
            "wall_s": p50,
            "p50_s": p50,
            "p99_s": p99,
            "requests_per_s": float(len(walls) / total),
            "device_rows_per_s": float(stats.n_rows / total),
            "batch_fill": stats.batch_fill,
            "n_batches": stats.n_batches,
            "n_requests": stats.n_requests,
            "cache_hits": svc.cache.stats()["hits"],
            "scenario_wall_s": total,
        }
        svc.close()
    return out


def run(quick: bool = False):
    """benchmarks/run.py contract: [(name, us_per_call, derived), …]."""
    res = bench(
        n_fit=1500 if quick else 20_000,
        dim=16 if quick else 64,
        clusters=8 if quick else 16,
        neighbors=5 if quick else 15,
        epochs=3 if quick else 10,
        steps=8 if quick else 24,
        microbatch=64 if quick else 256,
        clients_list=(1, 8) if quick else (1, 8, 32),
        n_requests=10 if quick else 30,
        rows=24 if quick else 64,
    )
    return [
        (
            f"service/load_{name}",
            r["p50_s"] * 1e6,
            f"p99={r['p99_s'] * 1e3:.1f}ms {r['requests_per_s']:.0f}req/s "
            f"fill={r['batch_fill']:.2f} hits={r['cache_hits']}",
        )
        for name, r in res["clients"].items()
    ]


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--n-fit", type=int, default=20_000)
    ap.add_argument("--dim", type=int, default=64)
    ap.add_argument("--clusters", type=int, default=16)
    ap.add_argument("--neighbors", type=int, default=15)
    ap.add_argument("--epochs", type=int, default=10)
    ap.add_argument("--steps", type=int, default=24)
    ap.add_argument("--microbatch", type=int, default=256)
    ap.add_argument("--max-delay-ms", type=float, default=2.0,
                    help="batching engine coalescing deadline")
    ap.add_argument("--clients", default="1,8,32", help="comma-separated client counts")
    ap.add_argument("--requests", type=int, default=30, help="requests per client")
    ap.add_argument("--rows", type=int, default=64, help="rows per request (±2 jitter)")
    ap.add_argument("--cache-frac", type=float, default=0.25,
                    help="fraction of repeated (cache-hitting) requests")
    ap.add_argument("--transport", default="core", choices=["core", "http"])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--json", default="", help="write the report to this path")
    args = ap.parse_args()

    res = bench(
        n_fit=args.n_fit,
        dim=args.dim,
        clusters=args.clusters,
        neighbors=args.neighbors,
        epochs=args.epochs,
        steps=args.steps,
        microbatch=args.microbatch,
        max_delay_s=args.max_delay_ms / 1e3,
        clients_list=tuple(int(c) for c in args.clients.split(",")),
        n_requests=args.requests,
        rows=args.rows,
        cache_frac=args.cache_frac,
        transport=args.transport,
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
