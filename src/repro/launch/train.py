"""NOMAD Projection end-to-end training launcher (deliverable b's driver).

A thin CLI over the unified estimator — everything fault-tolerant lives in
``NomadProjection.fit`` now:

* index build (K-means + in-cluster kNN) is cached on disk next to the
  checkpoint dir — on restart the index is reloaded, not rebuilt;
* one checkpoint per ``--checkpoint-every`` epochs (atomic commit, async);
* ``--resume`` restores θ + epoch and continues bit-exactly (same
  ``fold_in`` schedule as the uninterrupted run);
* **elastic**: the checkpoint stores the global θ row-block, so a run
  started on N devices restores onto any other divisor count (node loss →
  restart smaller; scale-up → restart bigger). Cluster blocks re-shard
  because the layout is cluster-major (checkpoint/checkpointer.py).

Host-device simulation: ``--host-devices N`` forces N CPU devices (set
before jax imports — this is why main() parses argv first).

Examples:
  PYTHONPATH=src python -m repro.launch.train --workload nomad_quickstart \
      --host-devices 8 --mesh 2x4 --epochs 10 --checkpoint-dir /tmp/nomad_ckpt
  … kill it mid-run, then:
  PYTHONPATH=src python -m repro.launch.train --workload nomad_quickstart \
      --host-devices 4 --mesh 4 --resume --checkpoint-dir /tmp/nomad_ckpt
"""

from __future__ import annotations

import argparse
import os
import sys
import time


def parse_args(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", default="nomad_quickstart")
    ap.add_argument("--n-points", type=int, default=0, help="override workload size")
    ap.add_argument("--epochs", type=int, default=0, help="override epoch count")
    ap.add_argument("--mesh", default="", help="e.g. '2x4' (axes data,model) or '4'")
    ap.add_argument("--host-devices", type=int, default=0)
    ap.add_argument("--checkpoint-dir", default="")
    ap.add_argument("--checkpoint-every", type=int, default=0)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--hierarchical", action="store_true")
    ap.add_argument("--fail-at-epoch", type=int, default=-1, help="crash injection (tests)")
    ap.add_argument("--out", default="", help="write final embedding .npy here")
    ap.add_argument("--metrics", action="store_true", help="NP@10/triplet at the end")
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = parse_args(argv)
    if args.host_devices:
        os.environ["XLA_FLAGS"] = (
            f"--xla_force_host_platform_device_count={args.host_devices}"
        )

    import jax
    import numpy as np

    from repro.checkpoint import latest_step, load_metadata
    from repro.configs import get_nomad
    from repro.core.nomad import NomadProjection
    from repro.core.strategy import FitCallbacks
    from repro.data.synthetic import hierarchical_mixture
    from repro.launch.mesh import make_mesh
    from repro.runtime import enable_compile_cache

    enable_compile_cache()

    cfg = get_nomad(args.workload)
    if args.n_points:
        cfg = cfg.replace(n_points=args.n_points)
    if args.epochs:
        cfg = cfg.replace(n_epochs=args.epochs)
    if args.hierarchical:
        cfg = cfg.replace(hierarchical=True)
    if args.checkpoint_dir:
        cfg = cfg.replace(checkpoint_dir=args.checkpoint_dir)
    if args.checkpoint_every:
        cfg = cfg.replace(checkpoint_every_epochs=args.checkpoint_every)

    # ---- mesh ------------------------------------------------------------------
    if args.mesh:
        dims = tuple(int(d) for d in args.mesh.split("x"))
    else:
        dims = (len(jax.devices()),)
    axis_names = {1: ("data",), 2: ("data", "model"), 3: ("pod", "data", "model")}[len(dims)]
    mesh = make_mesh(dims, axis_names)
    pod_axis = "pod" if "pod" in axis_names else None
    shard_axes = tuple(a for a in axis_names if a != "pod")
    n_shards = 1
    for d in dims:
        n_shards *= d
    print(f"mesh {dims} axes {axis_names}; {n_shards} shards")

    # ---- data ------------------------------------------------------------------
    # the index is owned by fit: argument > fingerprint-checked
    # checkpoint_dir/index.npz cache > IndexBuilder on the training mesh
    x, sup, sub = hierarchical_mixture(cfg.n_points, cfg.dim, seed=cfg.seed)
    ckdir = cfg.checkpoint_dir

    resume = bool(args.resume and ckdir and latest_step(ckdir) is not None)
    if resume:
        meta = load_metadata(ckdir)
        print(f"resume: epoch {int(meta['epoch']) + 1} (ckpt step {meta['epoch']})")

    class Progress(FitCallbacks):
        wants_embedding = False

        def on_epoch_start(self, ev):
            if ev.epoch == args.fail_at_epoch:
                print(f"CRASH INJECTION at epoch {ev.epoch}", flush=True)
                os._exit(17)

        def on_epoch_end(self, ev):
            print(
                f"epoch {ev.epoch:4d} loss {ev.loss:.5f} ({ev.time_s:.2f}s)",
                flush=True,
            )

        def on_checkpoint(self, ev):
            print(f"checkpoint: epoch {ev.epoch} → {ev.directory}", flush=True)

    strategy = "hierarchical" if (cfg.hierarchical and pod_axis) else "sharded"
    proj = NomadProjection(
        cfg, strategy=strategy, mesh=mesh, shard_axes=shard_axes, pod_axis=pod_axis
    )
    res = proj.fit(x, callbacks=Progress(), resume=resume)
    print(
        f"index: {res.index_build_strategy}"
        + (f" build in {res.index_build_s:.1f}s" if res.index_build_s else "")
    )

    emb = res.embedding
    if args.out:
        np.save(args.out, emb)
        print("embedding →", args.out)
    if args.metrics:
        from repro.metrics import neighborhood_preservation, random_triplet_accuracy

        np10 = neighborhood_preservation(x, emb, k=10, n_queries=min(1000, cfg.n_points))
        rta = random_triplet_accuracy(x, emb, 10_000)
        print(f"NP@10={np10:.4f} triplet={rta:.4f}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
