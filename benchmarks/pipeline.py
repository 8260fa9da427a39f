"""End-to-end pipeline benchmark: embed→store→fit→inverse→explore per family.

Runs every registered :data:`repro.configs.PIPELINE_WORKLOADS` entry —
one per architecture family (dense attention / SSM / MoE) — through
``repro.pipeline.run_pipeline`` plus an ``/explore`` round trip on a
checkpoint-loaded :class:`MapService`, and emits the two things CI gates:

* **stage walls** (``stages.<family>.<stage>.wall_s``): embed (streaming
  model forward → sharded store), fit (store-backed NOMAD fit),
  inverse_train (the jitted 2D→embedding head), explore (decode + frozen
  kNN through the service) — a regression in any stage of any family
  gates via ``benchmarks/check_regression.py``.
* **round-trip scores** (``scores.<family>_roundtrip_score``): the
  inverse head's R² over the map's own rows, gated as a *floor* — the
  2D→embedding direction must keep recovering the corpus.

  PYTHONPATH=src python benchmarks/pipeline.py --quick --json BENCH_pipeline.json
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import shutil
import sys
import tempfile
import time


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--docs", type=int, default=2_048, help="corpus documents")
    ap.add_argument("--seq-len", type=int, default=64)
    ap.add_argument("--epochs", type=int, default=15, help="map fit epochs")
    ap.add_argument("--inverse-steps", type=int, default=600)
    ap.add_argument("--explore-queries", type=int, default=64)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--quick", action="store_true", help="CI size")
    ap.add_argument("--json", default="", help="write BENCH_pipeline.json here")
    return ap.parse_args(argv)


def _short(name: str) -> str:
    return name.removeprefix("pipeline_")


def build_report(args) -> dict:
    from repro.configs import PIPELINE_WORKLOADS
    from repro.pipeline import run_pipeline
    from repro.service import MapService

    if args.quick:
        args.docs = min(args.docs, 768)
        args.seq_len = min(args.seq_len, 32)
        args.epochs = min(args.epochs, 6)
        args.inverse_steps = min(args.inverse_steps, 300)

    stages, scores, families = {}, {}, {}
    for name in sorted(PIPELINE_WORKLOADS):
        w = dataclasses.replace(
            PIPELINE_WORKLOADS[name],
            n_docs=args.docs,
            seq_len=args.seq_len,
            n_epochs=args.epochs,
        )
        workdir = tempfile.mkdtemp(prefix=f"bench-{name}-")
        try:
            r = run_pipeline(
                w, workdir, seed=args.seed, inverse_steps=args.inverse_steps
            )
            # explore round trip: checkpoint-loaded service, decode + kNN
            svc = MapService()
            try:
                svc.registry.load(r.checkpoint_dir)
                coords = r.fit.embedding[: args.explore_queries]
                svc.explore(coords[:1])  # pay the jit compile outside the wall
                t0 = time.perf_counter()
                out = svc.explore(coords)
                explore_s = time.perf_counter() - t0
            finally:
                svc.close()
            short = _short(name)
            st = {k: {"wall_s": round(v, 3)} for k, v in r.stage_s.items()}
            st["explore"] = {"wall_s": round(explore_s, 3)}
            stages[short] = st
            scores[f"{short}_roundtrip_score"] = round(r.roundtrip_score, 4)
            families[short] = {
                "arch": w.arch,
                "family": r.workload.arch_config().family,
                "dim": int(r.store.shape[1]),
                "n_explore_hits": int((out.neighbor_ids >= 0).sum()),
            }
        finally:
            shutil.rmtree(workdir, ignore_errors=True)

    return {
        "benchmark": "pipeline",
        "config": {
            "docs": args.docs,
            "seq_len": args.seq_len,
            "epochs": args.epochs,
            "inverse_steps": args.inverse_steps,
            "explore_queries": args.explore_queries,
        },
        "families": families,
        "stages": stages,
        # *_score leaves are FLOOR-gated by check_regression.py: an inverse
        # head that stops recovering the corpus fails, a faster wall never does
        "scores": scores,
    }


def run(quick: bool = False):
    """benchmarks.run entry: [(name, us_per_call, derived), …]."""
    args = parse_args(["--quick"] if quick else [])
    report = build_report(args)
    rows = []
    for fam, st in report["stages"].items():
        for stage, d in st.items():
            rows.append((f"pipeline.{fam}.{stage}", d["wall_s"] * 1e6, ""))
    for name, v in report["scores"].items():
        rows.append((f"pipeline.{name}", 0.0, f"r2={v:.3f}"))
    return rows


def main(argv=None) -> int:
    args = parse_args(argv)
    report = build_report(args)
    print(f"{'family.stage':>32}  wall_s")
    for fam, st in report["stages"].items():
        for stage, d in st.items():
            print(f"{fam + '.' + stage:>32}  {d['wall_s']:.3f}")
    for name, v in report["scores"].items():
        print(f"{name:>32}  {v:.4f}")
    if args.json:
        with open(args.json, "w") as f:
            json.dump(report, f, indent=1)
        print("report →", args.json)
    return 0


if __name__ == "__main__":
    from repro.runtime import enable_compile_cache

    enable_compile_cache()
    sys.exit(main())
