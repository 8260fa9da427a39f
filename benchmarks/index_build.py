"""Index-build benchmark: wall time + peak RSS per stage, device vs seed host.

("peak RSS" = the process ru_maxrss high-watermark sampled at the end of
each stage — monotone across stages, so attribute a jump to the stage where
it first appears.)

Times the device-resident :class:`repro.index.build.IndexBuilder` pipeline
(kmeans / assign / permute / knn) and, with ``--compare-host``, the seed's
host pipeline — the NumPy bidding loop with its O(N·K) ``banned`` matrix,
the host-synced ``float(shift)`` EM loop, and the ``for c in range(K)``
permutation — then reports the speedup and the neighborhood-edge agreement
between the two indices (the PR-3 acceptance metric).

  PYTHONPATH=src python benchmarks/index_build.py --n 100000 --json BENCH_index_build.json
  PYTHONPATH=src python benchmarks/index_build.py --n 2000 --clusters 8 --compare-host

``--store-dir PATH`` additionally writes the corpus chunk-by-chunk into a
sharded on-disk store at PATH and times the *streamed* out-of-core build
(repro.data.store → IndexBuilder) against the monolithic in-RAM path. The
streamed phase runs first — ``ru_maxrss`` is process-monotone — so
``rss_compare`` cleanly attributes the watermark delta to the monolithic
path's full-size (N, D) copies. tests/test_store.py pins the N=50k bound.

CI smoke-runs this at tiny N on every push (see .github/workflows/ci.yml);
``BENCH_index_build.json`` is the machine-readable artifact.
"""

from __future__ import annotations

import argparse
import json
import time

import numpy as np


# ---------------------------------------------------------------------------
# The seed host pipeline (pre-PR-3), reproduced verbatim as the baseline
# ---------------------------------------------------------------------------


def _seed_capacity_assign(dist2_fn, x, cents, capacity, max_rounds=12):
    """The seed's bidding loop, O(N·K) ``banned`` matrix included."""
    n, K = x.shape[0], cents.shape[0]
    assign = np.full(n, -1, np.int64)
    free = np.full(K, capacity, np.int64)
    banned = np.zeros((n, K), bool)  # the O(N·K) host wall
    for _ in range(max_rounds):
        todo = np.flatnonzero(assign < 0)
        if todo.size == 0:
            return assign
        d2 = dist2_fn(x[todo], cents)
        d2 = np.where(banned[todo] | (free[None, :] <= 0), np.inf, d2)
        pick = np.argmin(d2, 1)
        for c in range(K):
            if free[c] <= 0:
                continue
            bidders = todo[pick == c]
            if bidders.size == 0:
                continue
            if bidders.size > free[c]:
                order = np.argsort(d2[pick == c, c])
                admitted = bidders[order[: free[c]]]
                banned[bidders[order[free[c] :]], c] = True
            else:
                admitted = bidders
            assign[admitted] = c
            free[c] -= admitted.size
    todo = np.flatnonzero(assign < 0)
    if todo.size:
        d2 = dist2_fn(x[todo], cents)
        for t, row in zip(todo, np.argsort(d2, axis=1)):
            for c in row:
                if free[c] > 0:
                    assign[t] = c
                    free[c] -= 1
                    break
    return assign


def seed_host_build(x, cfg):
    """The seed build_index: host kmeans loop (per-iter float(shift) sync),
    host bidding with ``banned``, per-cluster permutation loop, device kNN.
    Returns (AnnIndex, {stage: {"wall_s", "rss_high_watermark_mb"}})."""
    import jax
    import jax.numpy as jnp

    from repro.index.ann import AnnIndex, _np_dist2, data_fingerprint
    from repro.index.build import _rss_mb
    from repro.index.kmeans import assign_jnp, lsh_init_centroids, _m_step
    from repro.index.knn import batched_cluster_knn

    n, d = x.shape
    K, C, k = cfg.n_clusters, cfg.cluster_capacity, cfg.n_neighbors
    stages = {}

    t0 = time.time()
    key = jax.random.key(cfg.seed)
    xd = jnp.asarray(x)
    cents = lsh_init_centroids(key, xd, K)
    for _ in range(cfg.kmeans_iters):
        a, _ = assign_jnp(xd, cents)
        new_cents, _ = _m_step(xd, a, K, cents)
        shift = float(jnp.max(jnp.sum(jnp.square(new_cents - cents), -1)))
        cents = new_cents
        if shift < cfg.kmeans_tol:
            break
    cents = np.asarray(cents)
    stages["kmeans"] = {"wall_s": time.time() - t0, "rss_high_watermark_mb": _rss_mb()}

    t0 = time.time()
    assign = _seed_capacity_assign(_np_dist2, x, cents, C)
    stages["assign"] = {"wall_s": time.time() - t0, "rss_high_watermark_mb": _rss_mb()}

    t0 = time.time()
    order = np.argsort(assign, kind="stable")
    counts = np.bincount(assign, minlength=K).astype(np.int64)
    starts = np.zeros(K, np.int64)
    starts[1:] = np.cumsum(counts)[:-1]
    perm = np.zeros(n, np.int64)
    x_rows = np.zeros((K * C, d), x.dtype)
    for c in range(K):
        members = order[starts[c] : starts[c] + counts[c]]
        rows = c * C + np.arange(counts[c])
        perm[members] = rows
        x_rows[rows] = x[members]
    stages["permute"] = {"wall_s": time.time() - t0, "rss_high_watermark_mb": _rss_mb()}

    t0 = time.time()
    valid = (np.arange(C)[None, :] < counts[:, None]).astype(bool)
    knn_local, knn_w = batched_cluster_knn(
        jnp.asarray(x_rows).reshape(K, C, d), jnp.asarray(valid), k, "jnp"
    )
    knn_local = np.asarray(knn_local)
    knn_w = np.asarray(knn_w).reshape(K * C, k)
    base = (np.arange(K) * C)[:, None, None]
    knn_idx = (knn_local + base).reshape(K * C, k).astype(np.int64)
    self_rows = np.arange(K * C)[:, None]
    knn_idx = np.where(knn_w > 0, knn_idx, self_rows)
    stages["knn"] = {"wall_s": time.time() - t0, "rss_high_watermark_mb": _rss_mb()}

    index = AnnIndex(
        x_rows=x_rows,
        knn_idx=knn_idx,
        knn_w=knn_w.astype(np.float32),
        counts=counts,
        centroids=cents,
        perm=perm,
        capacity=C,
        n_points=n,
        fingerprint=data_fingerprint(x),
    )
    return index, stages


# ---------------------------------------------------------------------------
# Comparison metric
# ---------------------------------------------------------------------------


def edge_agreement(a, b) -> float:
    """Neighborhood-edge IoU between two indices, in original point ids."""

    def edges(idx):
        rows = idx.n_clusters * idx.capacity
        inv = np.full(rows, -1, np.int64)
        inv[idx.perm] = np.arange(idx.n_points)
        k = idx.knn_idx.shape[1]
        heads = inv[np.repeat(np.arange(rows), k)]
        tails = inv[idx.knn_idx.reshape(-1)]
        live = idx.knn_w.reshape(-1) > 0
        return np.unique(heads[live] * np.int64(rows) + tails[live])

    ea, eb = edges(a), edges(b)
    inter = np.intersect1d(ea, eb, assume_unique=True).size
    union = ea.size + eb.size - inter
    return float(inter) / max(1, union)


# ---------------------------------------------------------------------------
# Driver
# ---------------------------------------------------------------------------


def bench(
    n=100_000,
    dim=64,
    clusters=256,
    neighbors=15,
    strategy="auto",
    seed=0,
    compare_host=False,
    repeat=1,
    store_dir="",
):
    from repro.configs.base import NomadConfig
    from repro.data.synthetic import gaussian_mixture
    from repro.index.build import IndexBuilder, _rss_mb

    cfg = NomadConfig(
        n_points=n,
        dim=dim,
        n_clusters=clusters,
        n_neighbors=neighbors,
        seed=seed,
        build_strategy=strategy,
    )

    # ---- streamed (out-of-core) build, FIRST: ru_maxrss is a process-
    # monotone high watermark, so the low-RSS path must run before the
    # monolithic path allocates its full-size copies ---------------------------
    streamed = None
    if store_dir:
        from repro.data.synthetic import gaussian_mixture_store

        # the corpus is generated chunk-by-chunk straight onto disk (same
        # rows gaussian_mixture() would produce) — no O(N·D) host buffer
        store, _ = gaussian_mixture_store(
            store_dir, n, dim, n_components=min(32, clusters), seed=seed
        )
        sb = IndexBuilder(cfg)
        sruns = []
        for _ in range(max(1, repeat)):
            streamed_index = sb.build(store)
            sruns.append(sb.report)
        srep = min(sruns, key=lambda r: r.total_s)
        streamed = {
            "total_s_per_run": [r.total_s for r in sruns],
            "total_s": srep.total_s,
            "stages": {
                s: {
                    "wall_s": srep.stage_s[s],
                    "rss_high_watermark_mb": srep.stage_rss_mb[s],
                }
                for s in srep.stage_s
            },
        }
        streamed_peak_mb = _rss_mb()

    x, _ = gaussian_mixture(n, dim, n_components=min(32, clusters), seed=seed)

    # repeat > 1 reports the best (jit-warm) run — one deployment compiles
    # once and builds many indices, so steady-state is the honest number;
    # run 0's times include compilation
    builder = IndexBuilder(cfg)
    runs = []
    for _ in range(max(1, repeat)):
        index = builder.build(x)
        runs.append(builder.report)
    rep = min(runs, key=lambda r: r.total_s)
    out = {
        "n": n,
        "dim": dim,
        "clusters": clusters,
        "neighbors": neighbors,
        "capacity": cfg.cluster_capacity,
        "strategy": rep.strategy,
        "n_shards": rep.n_shards,
        "stragglers": rep.stragglers,
        "device": {
            "total_s_per_run": [r.total_s for r in runs],
            "total_s": rep.total_s,
            "stages": {
                s: {
                    "wall_s": rep.stage_s[s],
                    "rss_high_watermark_mb": rep.stage_rss_mb[s],
                }
                for s in rep.stage_s
            },
        },
    }
    if streamed is not None:
        out["streamed"] = streamed
        out["rss_compare"] = {
            "streamed_peak_mb": streamed_peak_mb,
            "monolithic_peak_mb": _rss_mb(),
            # both watermarks include the interpreter/jax baseline; the
            # streamed phase ran first, so a monolithic peak above the
            # streamed one is attributable to the monolithic allocations
            "note": (
                "process-monotone ru_maxrss: streamed build sampled before "
                "the monolithic path ran; monolithic includes everything "
                "resident up to its own peak"
            ),
        }
        # the two pipelines accumulate f32 in different orders (chunked vs
        # resident), so centroids differ at fp level — report the graph IoU
        out["streamed"]["edge_agreement_vs_monolithic"] = edge_agreement(
            streamed_index, index
        )
    if compare_host:
        from repro.index.ann import _np_dist2

        t0 = time.time()
        host_index, host_stages = seed_host_build(x, cfg)
        out["host_seed"] = {"total_s": time.time() - t0, "stages": host_stages}
        # end-to-end agreement: includes the (tol-sized) kmeans difference —
        # the scan EM freezes pre-update centroids on convergence where the
        # seed loop kept the post-update ones
        out["edge_agreement"] = edge_agreement(index, host_index)
        out["edge_agreement_note"] = (
            "end-to-end IoU; both builds are converged k-means solutions but "
            "the scan EM returns pre-update centroids at the tol stop where "
            "the seed loop returned post-update ones, so cell boundaries "
            "differ by O(sqrt(tol)) — assign_agreement_same_centroids "
            "isolates the refactored capacity assignment itself"
        )
        # isolated capacity-assignment agreement: host bidding rounds on the
        # *device* centroids vs the device rounds — same round semantics,
        # so this is 1.0 up to fp argmin ties
        a_host = _seed_capacity_assign(
            _np_dist2, x, index.centroids, cfg.cluster_capacity
        )
        a_dev = index.perm // cfg.cluster_capacity
        out["assign_agreement_same_centroids"] = float(np.mean(a_host == a_dev))
        out["speedup_vs_host"] = out["host_seed"]["total_s"] / max(
            rep.total_s, 1e-9
        )
    return out


def run(quick: bool = False):
    """benchmarks/run.py contract: [(name, us_per_call, derived), …]."""
    res = bench(
        n=4000 if quick else 50_000,
        dim=16 if quick else 64,
        clusters=8 if quick else 128,
        neighbors=5 if quick else 15,
        compare_host=True,
        repeat=2,  # best-of-2: run 0 pays the jit compiles
    )
    rows = [
        (
            f"index_build/{s}_n{res['n']}",
            res["device"]["stages"][s]["wall_s"] * 1e6,
            f"rss={res['device']['stages'][s]['rss_high_watermark_mb']:.0f}MB",
        )
        for s in ("kmeans", "assign", "permute", "knn")
    ]
    rows.append(
        (
            f"index_build/total_n{res['n']}",
            res["device"]["total_s"] * 1e6,
            f"speedup_vs_host={res['speedup_vs_host']:.2f}x "
            f"edge_agreement={res['edge_agreement']:.4f}",
        )
    )
    return rows


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--n", type=int, default=100_000)
    ap.add_argument("--dim", type=int, default=64)
    ap.add_argument("--clusters", type=int, default=256)
    ap.add_argument("--neighbors", type=int, default=15)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--strategy", default="auto", choices=["auto", "local", "sharded"])
    ap.add_argument("--compare-host", action="store_true")
    ap.add_argument("--repeat", type=int, default=2, help="build runs; best wins")
    ap.add_argument(
        "--store-dir",
        default="",
        help="also run the streamed out-of-core build from a sharded store "
        "written (chunk-by-chunk) at this path; reports peak-RSS + wall for "
        "monolithic vs streamed",
    )
    ap.add_argument("--json", default="", help="write the report to this path")
    args = ap.parse_args()

    res = bench(
        n=args.n,
        dim=args.dim,
        clusters=args.clusters,
        neighbors=args.neighbors,
        strategy=args.strategy,
        seed=args.seed,
        compare_host=args.compare_host,
        repeat=args.repeat,
        store_dir=args.store_dir,
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
