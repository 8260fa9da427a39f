"""Multi-process distributed fit: coordinator bootstrap + per-process CLI.

The paper's flagship result (a map of Multilingual Wikipedia) exists
because NOMAD Projection runs across accelerators *and hosts*: clusters
shard over one global mesh, and the only optimisation-loop collective —
the per-refresh all-gather of cluster means — crosses process boundaries
exactly like it crosses devices. This module is the host-side glue:

* :func:`initialize_distributed` — ``jax.distributed.initialize`` against a
  coordinator address, with the CPU backend switched to its ``gloo``
  collectives implementation first (without it, XLA:CPU rejects any
  multi-process computation outright). After it returns,
  ``jax.devices()`` spans every process while ``jax.local_devices()``
  stays process-local — every mesh built from the global pool
  (``core/strategy.py:default_mesh``, ``launch/mesh.py:flat_mesh``,
  ``index/build.py:resolve_build_strategy``) then shards across hosts
  with no further changes: ``shard_map`` collectives reduce over mesh
  axes, not processes.

* ``python -m repro.launch.distributed`` — the per-process entrypoint.
  One invocation per process (``--process-id i``), all pointing at the
  same ``--coordinator host:port``; or ``--spawn K`` to launch K local
  worker processes against an automatically chosen local coordinator
  port (the CI/test harness, and the quickest way to try 2 processes on
  one machine). The spawn is a CPU simulation: on an accelerator host it
  refuses more workers than the host has chips. Every process must see
  the same data — pass ``--store``
  (a shared-filesystem embedding store) for anything big; each process
  then reads only its own row range of it (the ``"distributed"`` index
  build), so no process ever holds all N rows.

Determinism contract (pinned by tests/test_multiprocess.py): a K-process
fit is bit-for-bit equal to the 1-process sharded fit over the same
global device count — process layout changes *where* shards live, never
what they compute.
"""

from __future__ import annotations

import argparse
import json
import os
import socket
import subprocess
import sys
import time


def pick_free_port(host: str = "127.0.0.1") -> int:
    """An OS-assigned free TCP port (closed again — a tiny race the
    coordinator bind reports loudly if ever lost)."""
    with socket.socket(socket.AF_INET, socket.SOCK_STREAM) as s:
        s.bind((host, 0))
        return int(s.getsockname()[1])


def initialize_distributed(
    coordinator: str,
    num_processes: int,
    process_id: int,
    *,
    timeout_s: int = 60,
) -> None:
    """``jax.distributed.initialize`` with the CPU collectives prerequisite.

    Must run before any jax computation touches the backend. On CPU the
    collectives implementation is switched to ``gloo`` first — XLA:CPU's
    default implementation refuses cross-process computations with
    "Multiprocess computations aren't implemented on the CPU backend".
    GPU/TPU backends keep their native (NCCL/ICI) collectives.
    """
    import jax

    if num_processes < 2:
        return  # single process: nothing to coordinate
    if not coordinator:
        raise ValueError(
            "multi-process init needs a coordinator address "
            "(host:port of process 0)"
        )
    platforms = os.environ.get("JAX_PLATFORMS", "")
    if platforms in ("", "cpu"):
        # harmless when another backend wins; required when CPU does
        jax.config.update("jax_cpu_collectives_implementation", "gloo")
    jax.distributed.initialize(
        coordinator_address=coordinator,
        num_processes=num_processes,
        process_id=process_id,
        initialization_timeout=timeout_s,
    )


def barrier(tag: str = "barrier") -> None:
    """Block until every process reaches this point (no-op single-process)."""
    import jax

    if jax.process_count() > 1:
        from jax.experimental import multihost_utils

        multihost_utils.sync_global_devices(tag)


# ---------------------------------------------------------------------------
# The per-process entrypoint
# ---------------------------------------------------------------------------


def parse_args(argv=None):
    ap = argparse.ArgumentParser(
        prog="python -m repro.launch.distributed",
        description="Per-process NOMAD fit worker (jax.distributed).",
    )
    ap.add_argument("--coordinator", default="", help="host:port of process 0")
    ap.add_argument("--num-processes", type=int, default=1)
    ap.add_argument("--process-id", type=int, default=0)
    ap.add_argument(
        "--spawn", type=int, default=0,
        help="launch K local worker processes against a local coordinator",
    )
    ap.add_argument(
        "--host-devices", type=int, default=0,
        help="force N CPU devices per process (XLA host-platform simulation)",
    )
    ap.add_argument("--init-timeout", type=int, default=60)
    # workload
    ap.add_argument("--workload", default="nomad_quickstart")
    ap.add_argument("--store", default="", help="shared embedding store (dir or .npy)")
    ap.add_argument("--n-points", type=int, default=0)
    ap.add_argument("--dim", type=int, default=0)
    ap.add_argument("--epochs", type=int, default=0)
    ap.add_argument("--clusters", type=int, default=0)
    ap.add_argument("--neighbors", type=int, default=0)
    ap.add_argument("--chunk-rows", type=int, default=0)
    # fault tolerance
    ap.add_argument("--checkpoint-dir", default="")
    ap.add_argument("--checkpoint-every", type=int, default=0)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--fail-at-epoch", type=int, default=-1, help="crash injection (tests)")
    # outputs (process 0 writes; --stats is per-process)
    ap.add_argument("--out", default="", help="final embedding .npy (process 0)")
    ap.add_argument("--dump-index", default="", help="index arrays .npz (process 0)")
    ap.add_argument("--stats", default="", help="per-process stage walls + RSS JSON")
    return ap.parse_args(argv)


def local_platform() -> tuple:
    """(platform, local device count) of this host.

    Asked of a short-lived child process: a process that has touched JAX
    holds the chip, and the workers spawned after it would find none.
    ``JAX_PLATFORMS=cpu`` needs no probe.
    """
    if os.environ.get("JAX_PLATFORMS", "") == "cpu":
        return "cpu", 0
    probe = "import jax; print(jax.default_backend(), jax.local_device_count())"
    out = subprocess.run(
        [sys.executable, "-c", probe], capture_output=True, text=True, check=True
    )
    platform, count = out.stdout.split()[-2:]
    return platform, int(count)


def check_spawn(n_workers: int, platform: str, n_chips: int) -> None:
    """The K-process spawn simulates a cluster on CPU devices. On an
    accelerator host a chip serves one process, so more workers than chips
    cannot all start: refuse before starting any."""
    if platform != "cpu" and n_workers > n_chips:
        raise ValueError(
            f"--spawn {n_workers}: this {platform} host has {n_chips} chip(s) "
            "and a chip serves one process; run one process, which drives "
            "every local chip"
        )


def _spawn_workers(args, argv) -> int:
    """``--spawn K``: run K local workers against a local coordinator."""
    try:
        check_spawn(args.spawn, *local_platform())
    except ValueError as e:
        print(e, file=sys.stderr, flush=True)
        return 2
    port = pick_free_port()
    strip = {"--spawn": 1}
    child_common: list = []
    it = iter(argv)
    for a in it:
        if a in strip:
            next(it, None)
            continue
        child_common.append(a)
    procs = []
    for i in range(args.spawn):
        cmd = [
            sys.executable, "-m", "repro.launch.distributed",
            *child_common,
            "--coordinator", f"127.0.0.1:{port}",
            "--num-processes", str(args.spawn),
            "--process-id", str(i),
        ]
        procs.append(subprocess.Popen(cmd))
    rcs = [p.wait() for p in procs]
    bad = [rc for rc in rcs if rc != 0]
    return bad[0] if bad else 0


def main(argv=None) -> int:
    argv = list(sys.argv[1:] if argv is None else argv)
    args = parse_args(argv)
    if args.spawn > 0:
        return _spawn_workers(args, argv)
    if args.host_devices:
        os.environ["XLA_FLAGS"] = (
            f"--xla_force_host_platform_device_count={args.host_devices}"
        )

    from repro.runtime import enable_compile_cache

    enable_compile_cache()
    t_start = time.time()
    try:
        initialize_distributed(
            args.coordinator,
            args.num_processes,
            args.process_id,
            timeout_s=args.init_timeout,
        )
    except Exception as e:  # noqa: BLE001 — fail loud, fast and actionable
        print(
            f"distributed init failed (coordinator {args.coordinator!r}, "
            f"process {args.process_id}/{args.num_processes}): {e}",
            file=sys.stderr,
            flush=True,
        )
        return 3

    import jax
    import numpy as np

    from repro.checkpoint import latest_step, load_metadata
    from repro.configs import get_nomad
    from repro.core.nomad import NomadProjection
    from repro.core.strategy import FitCallbacks
    from repro.data.store import as_store
    from repro.index.build import IndexBuilder, _rss_mb

    pid, nproc = jax.process_index(), jax.process_count()
    print(
        f"process {pid}/{nproc}: {jax.local_device_count()} local / "
        f"{jax.device_count()} global devices",
        flush=True,
    )

    cfg = get_nomad(args.workload)
    # every process must run the cross-process collective build — the
    # "distributed" IndexBuilder path (per-process row ranges of the store)
    cfg = cfg.replace(build_strategy="distributed")
    if args.store:
        store = as_store(args.store)
        x = store
        cfg = cfg.replace(n_points=store.n_rows, dim=store.dim)
    else:
        if args.n_points:
            cfg = cfg.replace(n_points=args.n_points)
        if args.dim:
            cfg = cfg.replace(dim=args.dim)
        from repro.data.synthetic import hierarchical_mixture

        x, _sup, _sub = hierarchical_mixture(cfg.n_points, cfg.dim, seed=cfg.seed)
    if args.epochs:
        cfg = cfg.replace(n_epochs=args.epochs)
    if args.clusters:
        cfg = cfg.replace(n_clusters=args.clusters)
    if args.neighbors:
        cfg = cfg.replace(n_neighbors=args.neighbors)
    if args.chunk_rows:
        cfg = cfg.replace(chunk_rows=args.chunk_rows)
    if args.checkpoint_dir:
        cfg = cfg.replace(checkpoint_dir=args.checkpoint_dir)
    if args.checkpoint_every:
        cfg = cfg.replace(checkpoint_every_epochs=args.checkpoint_every)

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
            if pid == 0:
                print(
                    f"epoch {ev.epoch:4d} loss {ev.loss:.5f} ({ev.time_s:.2f}s)",
                    flush=True,
                )

        def on_checkpoint(self, ev):
            if pid == 0:
                print(f"checkpoint: epoch {ev.epoch} → {ev.directory}", flush=True)

    index = None
    build_stage_s: dict = {}
    if args.stats:
        # explicit build so per-stage walls land in the stats JSON
        builder = IndexBuilder(cfg)
        index = builder.build(x)
        build_stage_s = dict(builder.report.stage_s)
        print(
            f"index: {builder.report.strategy} "
            f"({builder.report.n_shards} shards, {builder.report.total_s:.1f}s)",
            flush=True,
        )

    proj = NomadProjection(cfg, strategy="auto")
    res = proj.fit(x, index=index, callbacks=Progress(), resume=resume)
    if pid == 0:
        print(
            f"index: {res.index_build_strategy}"
            + (f" build in {res.index_build_s:.1f}s" if res.index_build_s else "")
        )
        print(
            f"fit: strategy={res.strategy} shards={res.n_shards} "
            f"processes={res.process_count}",
            flush=True,
        )

    if args.out and pid == 0:
        np.save(args.out, res.embedding)
        print("embedding →", args.out)
    if args.dump_index and pid == 0:
        idx = res.index
        np.savez(
            args.dump_index,
            knn_idx=idx.knn_idx,
            knn_w=idx.knn_w,
            counts=idx.counts,
            centroids=idx.centroids,
            perm=idx.perm,
        )
        print("index arrays →", args.dump_index)
    if args.stats:
        # spawned workers share one argv — derive a per-process filename
        stats_path = args.stats
        if nproc > 1:
            root, ext = os.path.splitext(stats_path)
            stats_path = f"{root}.p{pid}{ext}"
        stats = {
            "process": pid,
            "n_processes": nproc,
            "local_devices": jax.local_device_count(),
            "global_devices": jax.device_count(),
            "peak_rss_mb": _rss_mb(),
            "stage_seconds": {
                **build_stage_s,
                "fit": float(sum(res.epoch_times)),
                "total": float(time.time() - t_start),
            },
            "epoch_seconds": [float(t) for t in res.epoch_times],
            "losses": [float(v) for v in res.losses],
        }
        with open(stats_path, "w") as f:
            json.dump(stats, f, indent=1)
        print("stats →", stats_path, flush=True)

    barrier("fit-done")  # no process exits while peers still need collectives
    print(f"process {pid}: DONE", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
