"""Smoke run of the NOMAD map path on TPU chips, through the public entry points.

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # the sharded path on a four-chip host

One chip: each Pallas kernel is compiled and checked against its jnp oracle
at its bench shapes (and the fused fit step's gradient against AD through
the oracle). Then a PubMed-width corpus, 1,048,576 rows × 768 from a seeded
Gaussian mixture (one chip's share of the paper's 24M-row PubMed map, at
PubMed's map settings with 256 cells of ~4,096 rows), goes through
``IndexBuilder`` → ``NomadProjection.fit`` (2 epochs, strategy and kernels
left at "auto") → ``FrozenMap`` → ``MapServer.transform`` on 4,096
held-out rows of the same mixture. Every phase is checked and fails loud:
finite results of the expected shapes, falling losses, NP@10 above 3×
chance, the "local" strategy, and a Mosaic kernel (``tpu_custom_call``) in
the compiled fit step.

Four chips (``--chips 4``): the same kind of corpus is indexed by the
sharded build and fit with ``strategy="sharded"`` over a 4-device mesh,
then compared with a one-device ``strategy="local"`` fit on the same
index, within the multi-device selftest's bounds.

The walls printed are set-up times that include compilation; they are not
measurements. The last line of standard output is one JSON object,
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
Without a TPU, or outside a checkout of the repository, the script exits
non-zero before any phase. It starts no other process.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
import traceback

import numpy as np

CHECKOUT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(CHECKOUT, "src"))
os.environ.setdefault("TPU_LOG_DIR", "disabled")  # libtpu logs to /tmp otherwise

N_ROWS = 1 << 20  # one chip's share of a PubMed-width map
EPOCHS = 2  # 2 × 128 steps of 8,192 heads
HELD_OUT = 4096  # rows transformed onto the frozen map
N_CLUSTERS = 256  # ~4,096 rows per cell, near PubMed's 24M / 4,096 ≈ 5,859
N_COMPONENTS = 64  # topics of the synthetic corpus


def log(msg: str) -> None:
    print(msg, flush=True)


def check(ok: bool, what: str) -> None:
    if not ok:
        raise AssertionError(what)


def map_config(n_rows: int, epochs: int, seed: int):
    """PubMed's published map settings (configs/nomad_workloads.py), cut to
    one chip's rows and ``N_CLUSTERS`` cells."""
    from repro.configs.nomad_workloads import PUBMED

    return PUBMED.replace(
        name="chip_smoke",
        n_points=n_rows,
        n_clusters=N_CLUSTERS,
        n_epochs=epochs,
        seed=seed,
        # the frozen kNN gathers (block, capacity, dim) f32 per tile:
        # 64 × 5,120 × 768 is 1 GB, the default 256 would be 4 GB
        serve_knn_block=64,
    )


def make_corpus(n_rows: int, dim: int, seed: int):
    """(train rows, held-out rows) of one seeded Gaussian mixture."""
    from repro.data.synthetic import gaussian_mixture

    x, _labels = gaussian_mixture(n_rows + HELD_OUT, dim, n_components=N_COMPONENTS, seed=seed)
    return x[:n_rows], x[n_rows:]


def wall(label: str, t0: float, extra: str = "") -> None:
    log(f"set-up wall, includes compilation: {label} {time.perf_counter() - t0:.3f} s{extra}")


# ---------------------------------------------------------------------------
# One chip
# ---------------------------------------------------------------------------


def phase_kernels(seed: int) -> None:
    """Every Pallas kernel, compiled, against its jnp oracle; and the fused
    fit step's custom VJP against AD through its oracle."""
    import jax
    import jax.numpy as jnp

    from repro.kernels import registry
    from repro.kernels.nomad_step.ref import nomad_step_ref

    key = jax.random.key(seed)
    for name in registry.names():
        if not registry.has_pallas(name):
            continue
        spec = registry.get(name)
        registry.validate(name, spec.make_inputs(key, spec.bench_shapes), interpret=False)
        shapes = " ".join("x".join(map(str, s)) for s, _ in spec.bench_shapes)
        log(f"kernel {name}: compiled Pallas matches the jnp oracle at {shapes}")

    spec = registry.get("nomad_step")
    args = spec.make_inputs(key, spec.bench_shapes)
    tiles = spec.tiles_for_backend("tpu")
    got = jax.grad(
        lambda *a: jnp.mean(spec.pallas(*a, tiles=tiles, interpret=False)), (0, 1, 3)
    )(*args)
    want = jax.grad(lambda *a: jnp.mean(nomad_step_ref(*a)), (0, 1, 3))(*args)
    for g, w, part in zip(got, want, ("theta_i", "theta_pos", "theta_neg")):
        np.testing.assert_allclose(
            np.asarray(g), np.asarray(w), rtol=1e-4, atol=1e-6, err_msg=part
        )
    log("kernel nomad_step: gradient matches AD through the oracle")


def fit_step_hlo(cfg, index) -> str:
    """The compiled program of one local fit epoch, as the fit builds it."""
    import jax
    import jax.numpy as jnp

    from repro.core.nomad import make_epoch_fn, make_step_fn

    rows, k, K = index.n_clusters * index.capacity, cfg.n_neighbors, index.n_clusters
    sds = jax.ShapeDtypeStruct
    idx = {
        "knn_idx": sds((rows, k), jnp.int32),
        "knn_w": sds((rows, k), jnp.float32),
        "counts": sds((K,), jnp.int32),
        "cum_counts": sds((K,), jnp.int32),
    }
    epoch = make_epoch_fn(cfg, make_step_fn(cfg), cfg.resolved_steps_per_epoch())
    theta = sds((rows, cfg.out_dim), jnp.float32)
    return epoch.lower(theta, idx, 1.0, 0.5, jax.random.key(0)).compile().as_text()


def phase_map(cfg, x, held, seed: int) -> None:
    import jax

    from repro.core.nomad import NomadProjection
    from repro.index.build import IndexBuilder
    from repro.metrics import neighborhood_preservation
    from repro.serve import FrozenMap, MapServer

    n = x.shape[0]
    t0 = time.perf_counter()
    builder = IndexBuilder(cfg)
    index = builder.build(x)
    rep = builder.report
    stages = " ".join(f"{k}={v:.3f}" for k, v in rep.stage_s.items())
    wall("index build", t0, f" (strategy={rep.strategy}, stragglers={rep.stragglers}; {stages})")
    check(index.counts.sum() == n, "index lost rows")
    check(np.isfinite(index.knn_w).all(), "non-finite kNN weights")

    t0 = time.perf_counter()
    res = NomadProjection(cfg).fit(x, index=index)
    wall("fit", t0, f" ({cfg.n_epochs} epochs × {cfg.resolved_steps_per_epoch()} steps)")
    losses = ", ".join(f"{v:.6f}" for v in res.losses)
    log(f"fit: strategy={res.strategy} shards={res.n_shards} losses=[{losses}]")
    check(res.strategy == "local", f"one chip must fit locally, got {res.strategy!r}")
    check(res.embedding.shape == (n, cfg.out_dim), f"embedding shape {res.embedding.shape}")
    check(np.isfinite(res.embedding).all(), "non-finite embedding")
    check(res.losses[-1] < res.losses[0], f"losses did not fall: {res.losses}")

    hlo = fit_step_hlo(cfg, index)
    check("tpu_custom_call" in hlo, "the compiled fit step holds no Pallas kernel")
    log("fit step: compiled program holds the nomad_step Pallas kernel (tpu_custom_call)")

    np10 = neighborhood_preservation(x, res.embedding, k=10, n_queries=2000, seed=seed)
    chance = 10 / n
    log(f"NP@10 = {np10:.6f} on 2000 queries (chance {chance:.2e})")
    check(np10 > 3 * chance, f"map no better than chance: NP@10 {np10}")

    t0 = time.perf_counter()
    frozen = FrozenMap.from_fit(res, cfg)
    jax.block_until_ready(frozen.x_rows)
    wall("freeze", t0)

    t0 = time.perf_counter()
    out = MapServer(frozen).transform(held, seed=seed, return_neighbors=True)
    wall("transform", t0, f" ({held.shape[0]} rows, {cfg.transform_steps} steps)")
    check(out.embedding.shape == (held.shape[0], cfg.out_dim), "placement shape")
    check(np.isfinite(out.embedding).all(), "non-finite placements")
    check(((out.cells >= 0) & (out.cells < cfg.n_clusters)).all(), "cell out of range")
    ids = out.neighbor_ids
    check(ids.shape == (held.shape[0], cfg.n_neighbors), "neighbor shape")
    check(((ids >= -1) & (ids < n)).all() and (ids >= 0).mean() > 0.99, "neighbor ids")


# ---------------------------------------------------------------------------
# Four chips
# ---------------------------------------------------------------------------


def phase_sharded(cfg, x, seed: int) -> None:
    """Sharded build + sharded fit over every chip against a one-device fit
    on the same index."""
    import jax

    from repro.core.nomad import NomadProjection
    from repro.core.strategy import ShardedStrategy
    from repro.index.build import IndexBuilder
    from repro.metrics import neighborhood_preservation, random_triplet_accuracy

    n_dev = jax.device_count()

    class WatchTheta(ShardedStrategy):
        """The sharded strategy, noting where θ's shards live after an epoch."""

        shards = ()

        def run_epoch(self, theta, *args):
            theta, loss = super().run_epoch(theta, *args)
            self.shards = [(s.device, s.data.shape[0]) for s in theta.addressable_shards]
            return theta, loss

    t0 = time.perf_counter()
    builder = IndexBuilder(cfg, strategy="sharded")
    index = builder.build(x)
    rep = builder.report
    stages = " ".join(f"{k}={v:.3f}" for k, v in rep.stage_s.items())
    wall("sharded index build", t0, f" (shards={rep.n_shards}; {stages})")
    check(rep.strategy == "sharded" and rep.n_shards == n_dev, f"build {rep}")

    t0 = time.perf_counter()
    strategy = WatchTheta()
    sharded = NomadProjection(cfg, strategy=strategy).fit(x, index=index)
    wall("sharded fit", t0)
    rows = index.n_clusters * index.capacity
    devices = {d for d, _ in strategy.shards}
    log(
        f"sharded fit: strategy={sharded.strategy} n_shards={sharded.n_shards} "
        f"theta on {len(devices)} devices, rows per shard "
        f"{sorted(r for _, r in strategy.shards)}"
    )
    check(sharded.strategy == "sharded", f"strategy {sharded.strategy!r}")
    check(sharded.n_shards == n_dev, f"n_shards {sharded.n_shards}")
    check(len(devices) == n_dev, f"theta spans {len(devices)} devices")
    check(all(r == rows // n_dev for _, r in strategy.shards), "uneven theta shards")
    check(np.isfinite(sharded.embedding).all(), "non-finite sharded embedding")

    t0 = time.perf_counter()
    local = NomadProjection(cfg, strategy="local").fit(x, index=index)
    wall("local fit", t0)
    check(local.strategy == "local", f"strategy {local.strategy!r}")

    np_s = neighborhood_preservation(x, sharded.embedding, k=10, n_queries=2000, seed=seed)
    np_l = neighborhood_preservation(x, local.embedding, k=10, n_queries=2000, seed=seed)
    rta_s = random_triplet_accuracy(x, sharded.embedding, 20_000, seed=seed)
    rta_l = random_triplet_accuracy(x, local.embedding, 20_000, seed=seed)
    log(f"NP@10 sharded={np_s:.6f} local={np_l:.6f}; triplet sharded={rta_s:.6f} local={rta_l:.6f}")
    check(np_s > 0.5 * np_l - 0.01, f"sharded NP@10 {np_s} vs local {np_l}")
    check(rta_s > 0.8 * rta_l, f"sharded triplet accuracy {rta_s} vs local {rta_l}")


# ---------------------------------------------------------------------------


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    if not os.path.isdir(os.path.join(CHECKOUT, "src", "repro")):
        print("chip_smoke.py runs from a checkout of the repository", file=sys.stderr)
        return 2
    from repro.runtime import enable_compile_cache

    cache = enable_compile_cache()

    import jax

    devices = jax.devices()
    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind, "count": len(devices)}
    if dev.platform != "tpu":
        print(f"no TPU: JAX runs on {dev.platform}", file=sys.stderr)
        return 2
    if len(devices) != args.chips:
        print(f"--chips {args.chips} but JAX sees {len(devices)} chips", file=sys.stderr)
        return 2
    log(f"platform={dev.platform} device_kind={dev.device_kind} count={len(devices)}")
    log(f"compile cache: {cache}")

    cfg = map_config(N_ROWS, EPOCHS, args.seed)
    phase = "corpus"
    try:
        t0 = time.perf_counter()
        x, held = make_corpus(N_ROWS, cfg.dim, args.seed)
        wall("corpus", t0, f" ({x.shape[0]} × {x.shape[1]} + {held.shape[0]} held out)")
        if args.chips == 1:
            phase = "kernels"
            phase_kernels(args.seed)
            phase = "map"
            phase_map(cfg, x, held, args.seed)
        else:
            phase = "sharded"
            phase_sharded(cfg, x, args.seed)
    except Exception:  # noqa: BLE001 — every phase fails the run, loudly
        traceback.print_exc()
        print(f"FAILED in phase {phase}", file=sys.stderr)
        return 1
    stats = dev.memory_stats() or {}
    log(f"peak_bytes_in_use={stats.get('peak_bytes_in_use', 'not reported')}")
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
