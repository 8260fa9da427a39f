"""Flagship-scale benchmark: an end-to-end synthetic map across processes.

The paper's flagship artifact (a map of Multilingual Wikipedia) is the
scale this repo has been growing toward: a corpus too big for one host's
RAM, indexed and fit across processes. This driver reproduces that shape
synthetically, end to end:

1. **generate** — ``gaussian_mixture_store`` streams an (N, D) corpus
   chunk-by-chunk into a sharded on-disk store; no (N, D) array ever
   exists in any process.
2. **distributed map** — spawns P worker processes of
   ``python -m repro.launch.distributed`` against a local coordinator.
   Each worker reads only its own devices' row ranges of the store (the
   ``"distributed"`` index build), and the fit's collectives cross
   process boundaries on one global mesh.
3. **collect** — merges every worker's ``--stats`` JSON (per-stage walls
   + peak RSS per process) into one machine-readable report.

  # CI smoke (2 processes, N=200k):
  PYTHONPATH=src python benchmarks/flagship.py --n 200000 --processes 2 \
      --epochs 3 --json BENCH_flagship.json

  # flagship runbook (N >= 10M): see README "Scaling across hosts".
  PYTHONPATH=src python benchmarks/flagship.py --n 10000000 --dim 64 \
      --processes 4 --clusters 512 --epochs 20 \
      --store-dir /data/flagship-store --keep-store --json BENCH_flagship.json

Report layout: gated stage walls (max over processes — the straggler
defines the wall) live under ``stages.*.wall_s`` so
``benchmarks/check_regression.py`` picks them up; the per-process detail
(``peak_rss_mb``, ``stage_seconds``) deliberately avoids the ``wall_s``
key so per-process jitter never trips the gate.
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

# stages a worker reports, in pipeline order (fit/total appended last)
BUILD_STAGES = ("place", "kmeans", "assign", "permute", "knn")


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--n", type=int, default=200_000)
    ap.add_argument("--dim", type=int, default=32)
    ap.add_argument("--processes", type=int, default=2)
    ap.add_argument(
        "--host-devices", type=int, default=1,
        help="CPU devices per process (XLA host-platform simulation)",
    )
    ap.add_argument("--epochs", type=int, default=3)
    ap.add_argument("--clusters", type=int, default=0, help="0 = workload default")
    ap.add_argument("--neighbors", type=int, default=0)
    ap.add_argument("--workload", default="nomad_quickstart")
    ap.add_argument("--components", type=int, default=32, help="mixture modes")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--gen-chunk-rows", type=int, default=65_536)
    ap.add_argument(
        "--store-dir", default="",
        help="corpus store location (default: a fresh temp dir)",
    )
    ap.add_argument(
        "--keep-store", action="store_true",
        help="leave the generated store on disk (reuse across runs)",
    )
    ap.add_argument("--work-dir", default="", help="stats/scratch dir")
    ap.add_argument("--json", default="", help="write BENCH_flagship.json here")
    ap.add_argument("--timeout", type=int, default=3600, help="worker wall cap (s)")
    return ap.parse_args(argv)


def _generate(args) -> tuple:
    """Chunk-streamed corpus → sharded store; returns (store_dir, wall_s)."""
    from repro.data.store import ShardedStore
    from repro.data.synthetic import gaussian_mixture_store

    store_dir = args.store_dir or os.path.join(args.work_dir, "corpus")
    meta = os.path.join(store_dir, "meta.json")
    t0 = time.time()
    if os.path.exists(meta):
        st = ShardedStore(store_dir)
        if st.shape == (args.n, args.dim):
            print(f"generate: reusing {store_dir} {st.shape}", flush=True)
            return store_dir, 0.0
        raise SystemExit(
            f"--store-dir {store_dir} holds a {st.shape} store, "
            f"want ({args.n}, {args.dim}) — point at a fresh dir"
        )
    gaussian_mixture_store(
        store_dir,
        args.n,
        args.dim,
        n_components=args.components,
        seed=args.seed,
        chunk_rows=args.gen_chunk_rows,
    )
    wall = time.time() - t0
    print(f"generate: ({args.n}, {args.dim}) → {store_dir} in {wall:.1f}s", flush=True)
    return store_dir, wall


def _spawn(args, store_dir: str) -> tuple:
    """Run the P-process map; returns (per-process stats list, wall_s)."""
    stats_base = os.path.join(args.work_dir, "stats.json")
    cmd = [
        sys.executable, "-m", "repro.launch.distributed",
        "--spawn", str(args.processes),
        "--host-devices", str(args.host_devices),
        "--store", store_dir,
        "--epochs", str(args.epochs),
        "--stats", stats_base,
    ]
    if args.clusters:
        cmd += ["--clusters", str(args.clusters)]
    if args.neighbors:
        cmd += ["--neighbors", str(args.neighbors)]
    if args.workload != "nomad_quickstart":
        cmd += ["--workload", args.workload]
    env = dict(os.environ)
    src = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "src")
    env["PYTHONPATH"] = src + os.pathsep + env.get("PYTHONPATH", "")
    print("spawn:", " ".join(cmd), flush=True)
    t0 = time.time()
    proc = subprocess.run(cmd, env=env, timeout=args.timeout)
    wall = time.time() - t0
    if proc.returncode != 0:
        raise SystemExit(f"distributed map failed (rc {proc.returncode})")
    root, ext = os.path.splitext(stats_base)
    paths = (
        [stats_base]
        if args.processes == 1
        else [f"{root}.p{i}{ext}" for i in range(args.processes)]
    )
    stats = []
    for p in paths:
        with open(p) as f:
            stats.append(json.load(f))
    return stats, wall


def build_report(args, gen_wall: float, map_wall: float, stats: list) -> dict:
    """Gated ``stages.*.wall_s`` (max over processes) + per-process detail."""
    stages = {"generate": {"wall_s": round(gen_wall, 3)}}
    for name in (*BUILD_STAGES, "fit", "total"):
        walls = [s["stage_seconds"].get(name) for s in stats]
        walls = [w for w in walls if w is not None]
        if walls:
            stages[name] = {"wall_s": round(max(walls), 3)}
    stages["map_end_to_end"] = {"wall_s": round(map_wall, 3)}
    return {
        "benchmark": "flagship",
        "config": {
            "n": args.n,
            "dim": args.dim,
            "processes": args.processes,
            "host_devices": args.host_devices,
            "epochs": args.epochs,
            "workload": args.workload,
        },
        "stages": stages,
        "per_process": [
            {
                "process": s["process"],
                "local_devices": s["local_devices"],
                "peak_rss_mb": round(float(s["peak_rss_mb"]), 1),
                "stage_seconds": {
                    k: round(float(v), 3) for k, v in s["stage_seconds"].items()
                },
            }
            for s in sorted(stats, key=lambda s: s["process"])
        ],
    }


def _run_report(args) -> dict:
    """generate → spawn → collect, returning the report dict."""
    if not args.work_dir:
        import tempfile

        args.work_dir = tempfile.mkdtemp(prefix="flagship-")
    os.makedirs(args.work_dir, exist_ok=True)

    store_dir, gen_wall = _generate(args)
    try:
        stats, map_wall = _spawn(args, store_dir)
    finally:
        if not (args.keep_store or args.store_dir):
            import shutil

            shutil.rmtree(store_dir, ignore_errors=True)
    return build_report(args, gen_wall, map_wall, stats)


def run(quick: bool = False):
    """benchmarks.run entry: [(name, us_per_call, derived), …].

    Spawns worker subprocesses; on the CPU, where that is not possible
    (no free ports, sandboxed exec), the failure surfaces as
    :class:`benchmarks.run.SuiteSkipped` so the harness reports *why*
    the suite produced no rows instead of failing the whole run. On an
    accelerator the spawn is not a simulation any more, and a worker
    failure fails the suite.
    """
    from benchmarks.run import SuiteSkipped

    argv = ["--processes", "2", "--timeout", "1200"]
    argv += (
        ["--n", "20000", "--dim", "16", "--clusters", "16", "--epochs", "2"]
        if quick
        else ["--n", "200000", "--epochs", "3"]
    )
    try:
        report = _run_report(parse_args(argv))
    except (SystemExit, OSError, subprocess.SubprocessError) as e:
        import jax

        if jax.default_backend() != "cpu":
            raise RuntimeError(
                f"flagship workers failed on {jax.default_backend()}: {e}"
            ) from e
        raise SuiteSkipped(f"multi-process spawn unavailable: {e}") from e
    rows = [
        (f"flagship.{name}", d["wall_s"] * 1e6, "")
        for name, d in report["stages"].items()
    ]
    for p in report["per_process"]:
        rows.append(
            (
                f"flagship.p{p['process']}",
                0.0,
                f"peak_rss_mb={p['peak_rss_mb']:.0f}",
            )
        )
    return rows


def main(argv=None) -> int:
    args = parse_args(argv)
    report = _run_report(args)
    print(f"{'stage':>14}  wall_s")
    for name, d in report["stages"].items():
        print(f"{name:>14}  {d['wall_s']:.3f}")
    for p in report["per_process"]:
        print(
            f"process {p['process']}: peak RSS {p['peak_rss_mb']:.0f} MB, "
            f"{p['local_devices']} local device(s)"
        )
    if args.json:
        with open(args.json, "w") as f:
            json.dump(report, f, indent=1)
        print("report →", args.json)
    return 0


if __name__ == "__main__":
    sys.exit(main())
