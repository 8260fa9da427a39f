"""The sharded fit (paper Fig. 2) on four host devices, against plain code.

In a child process with four host devices (this one keeps its single
device):

* two ``ShardedStrategy`` dispatches at a small size, on seeded random
  state laid out as the index build lays it, against a plain reference of
  one sharded dispatch (``sharded_reference_dispatch`` below: a copy of
  the mathematics of ``benchmarks/chip/lib/ref_fit_sharded.py``, plain
  ``jax.numpy`` at ``highest``, nothing of the program): each dispatch's
  loss, the norms of θ's change, and exactly the rows the first moved;
* ``place_index`` from host arrays and from ``jax.Array``s (on one device,
  and already split by rows) against the host pass it replaced
  (``host_shard_pass`` below): the same weights, counts, per-shard
  cumulative counts and float counts bit for bit, and the same neighbour
  ids, kept global (the epoch subtracts each shard's first row); every
  array split by rows over the four devices; an edge that crosses a shard
  still raises ``AssertionError``;
* ``ShardedStrategy.prepare`` opens the host span ``nomad.fit.place``.
"""

from __future__ import annotations

import functools
import glob
import json
import os
import subprocess
import sys
from types import SimpleNamespace

import numpy as np
import pytest

N, K, K_SHARDS, B, SHARD_STEPS = 4000, 8, 4, 256, 16
SEEDS = (3, 2**31 + 5)
# f32 rounding of the same sums in another order: the sound gaps read
# 0 to 1e-7 over 32 steps; a lost head, row or mean moves them by 1e-3 and more
REL_TOL = 1e-5


def _cfg():
    from repro.configs.base import NomadConfig

    return NomadConfig(
        n_points=N, dim=8, n_clusters=K, n_neighbors=15, n_noise=128,
        n_exact_negatives=16, batch_size=B, n_epochs=4,
        steps_per_epoch=SHARD_STEPS * K_SHARDS, init_scale=1e-4, strategy="sharded",
    )


def seeded_state(seed: int, n: int, K: int, C: int, k: int, d: int):
    """``(theta0, knn_idx, knn_w, counts)``: cells of about n/K valid rows,
    each valid row's k neighbours drawn from its own cell with inverse-rank
    weights, padded rows pointing at themselves with weight 0."""
    rng = np.random.default_rng(seed)
    counts = np.full(K, n // K, np.int64)
    shift = rng.integers(-(n // K) // 10, (n // K) // 10 + 1, K)
    counts = np.minimum(counts + shift - np.roll(shift, 1), C).astype(np.int32)
    rows = np.arange(K * C)
    cell, slot = rows // C, rows % C
    valid = slot < counts[cell]
    theta0 = np.where(valid[:, None], rng.normal(0, 1e-4, (K * C, d)), 0).astype(np.float32)
    span = np.maximum(counts[cell] - 1, 1)[:, None]
    nslot = (slot[:, None] + 1 + rng.integers(0, 1 << 30, (K * C, k)) % span) % np.maximum(
        counts[cell], 1
    )[:, None]
    knn_idx = np.where(valid[:, None], cell[:, None] * C + nslot, rows[:, None]).astype(np.int32)
    z = np.exp(1.0 / np.arange(1, k + 2)).sum()  # Eq. 6's inverse-rank weights
    rank = rng.integers(1, k + 1, (K * C, k))
    knn_w = np.where(valid[:, None], np.exp(1.0 / rank) / z, 0).astype(np.float32)
    return theta0, knn_idx, knn_w, counts


def host_shard_pass(knn_idx, knn_w, counts, n_shards: int):
    """The host pass ``place_index`` replaced: neighbour ids made
    shard-local block by block, cumulative counts per shard."""
    K = counts.shape[0]
    Kl, rows_per = K // n_shards, knn_idx.shape[0] // n_shards
    local = knn_idx.copy()
    for s in range(n_shards):
        lo, hi = s * rows_per, (s + 1) * rows_per
        blk = local[lo:hi]
        if ((blk < lo) | (blk >= hi)).any():
            raise AssertionError("kNN edge crosses shard boundary")
        local[lo:hi] = blk - lo
    cum = np.concatenate([np.cumsum(counts[s * Kl:(s + 1) * Kl]) for s in range(n_shards)])
    return local, knn_w, counts, cum.astype(np.int32), counts.astype(np.float32)


# ---- the plain reference of one sharded dispatch ------------------------------


def _ref_step(theta, knn_idx, knn_w, counts, cum, means, cell_w, own_off, row_off, lr, key,
              *, n, C, batch, n_neg, n_noise):
    import jax
    import jax.numpy as jnp

    k_head, k_neg = jax.random.split(key)
    u = jax.random.randint(k_head, (batch,), 0, cum[-1])
    cell = jnp.searchsorted(cum, u, side="right").astype(jnp.int32)
    start = jnp.where(cell > 0, cum[cell - 1], 0)
    rows = cell * C + (u - start)
    pos_rows = knn_idx[rows] - row_off
    pos_w = knn_w[rows]
    c = counts[cell]
    v = jax.random.uniform(k_neg, (batch, n_neg))
    slot = jnp.minimum(jnp.floor(v * c[:, None]).astype(jnp.int32), (c - 1)[:, None])
    neg_rows = cell[:, None] * C + slot
    not_own = jnp.arange(cell_w.shape[0])[None, :] != (cell + own_off)[:, None]
    neg_w = n_noise * (counts.astype(jnp.float32) / n)[cell] / n_neg

    def loss_fn(th_i, th_pos, th_neg):
        q_mean = 1 / (1 + jnp.sum(jnp.square(th_i[:, None, :] - means[None]), -1))
        m_tilde = jnp.sum(jnp.where(not_own, cell_w[None, :] * q_mean, 0), -1)
        m_exact = neg_w * jnp.sum(1 / (1 + jnp.sum(jnp.square(th_i[:, None, :] - th_neg), -1)), -1)
        q_pos = 1 / (1 + jnp.sum(jnp.square(th_i[:, None, :] - th_pos), -1))
        denom = q_pos + (m_tilde + m_exact)[:, None]
        return jnp.mean(-jnp.sum(pos_w * (jnp.log(q_pos) - jnp.log(denom)), -1))

    loss, (g_i, g_pos, g_neg) = jax.value_and_grad(loss_fn, argnums=(0, 1, 2))(
        theta[rows], theta[pos_rows], theta[neg_rows]
    )
    d = theta.shape[1]
    theta = theta.at[rows].add(-lr * g_i)
    theta = theta.at[pos_rows.reshape(-1)].add(-lr * g_pos.reshape(-1, d))
    theta = theta.at[neg_rows.reshape(-1)].add(-lr * g_neg.reshape(-1, d))
    return theta, loss


def sharded_reference_dispatch(theta, knn_idx, knn_w, counts, lr0, lr1, key, *, n, C,
                               batch, n_neg, n_noise, steps, n_shards):
    """One dispatch: the K means from θ at its start over all shards, then
    each shard's ``steps`` steps on its own rows, its key folded with its
    index; the loss is the mean over shards of each shard's mean loss."""
    import jax
    import jax.numpy as jnp

    theta = jnp.asarray(theta)
    K = counts.shape[0]
    Kl, rows_per = K // n_shards, K * C // n_shards
    with jax.default_matmul_precision("highest"):
        th = theta.reshape(K, C, -1)
        valid = jnp.arange(C)[None, :] < jnp.asarray(counts)[:, None]
        means = jnp.sum(jnp.where(valid[:, :, None], th, 0), 1) / jnp.maximum(
            jnp.asarray(counts), 1
        ).astype(jnp.float32)[:, None]
        cell_w = n_noise * (jnp.asarray(counts, jnp.float32) / n)
        step = jax.jit(functools.partial(_ref_step, n=n, C=C, batch=batch, n_neg=n_neg,
                                         n_noise=n_noise))
        blocks, losses = [], []
        for s in range(n_shards):
            lo, hi = s * rows_per, (s + 1) * rows_per
            cnt = jnp.asarray(counts[s * Kl:(s + 1) * Kl])
            th_s, key_s = theta[lo:hi], jax.random.fold_in(key, s)
            shard_losses = []
            for t in range(steps):
                lr = jnp.float32(lr0) + (jnp.float32(lr1) - jnp.float32(lr0)) * (t / steps)
                th_s, loss = step(th_s, jnp.asarray(knn_idx[lo:hi]), jnp.asarray(knn_w[lo:hi]),
                                  cnt, jnp.cumsum(cnt), means, cell_w, s * Kl, lo, lr,
                                  jax.random.fold_in(key_s, t))
                shard_losses.append(loss)
            blocks.append(th_s)
            losses.append(jnp.mean(jnp.stack(shard_losses)))
    return jnp.concatenate(blocks), float(jnp.mean(jnp.stack(losses)))


# ---- the child process --------------------------------------------------------


def _dispatches(seed: int) -> dict:
    import jax

    from repro.core.strategy import ShardedStrategy
    from repro.launch.mesh import make_mesh

    cfg = _cfg()
    C = cfg.cluster_capacity
    theta0, knn_idx, knn_w, counts = seeded_state(seed, N, K, C, cfg.n_neighbors, cfg.out_dim)
    strategy = ShardedStrategy(mesh=make_mesh((K_SHARDS,), ("data",)))
    theta = strategy.prepare(
        cfg, "nomad", SimpleNamespace(knn_idx=knn_idx, knn_w=knn_w, counts=counts), theta0
    )
    lr = cfg.resolved_lr0()
    ref = theta0
    out = {"program": [], "reference": []}
    for e in range(2):
        lr0, lr1 = lr * (1 - e / cfg.n_epochs), lr * (1 - (e + 1) / cfg.n_epochs)
        key = jax.random.fold_in(jax.random.key(seed & 0x7FFFFFFF), e)
        theta, loss = strategy.run_epoch(theta, e, lr0, lr1, key)
        ref, ref_loss = sharded_reference_dispatch(
            ref, knn_idx, knn_w, counts, lr0, lr1, key, n=N, C=C, batch=B,
            n_neg=cfg.n_exact_negatives, n_noise=cfg.n_noise, steps=SHARD_STEPS,
            n_shards=K_SHARDS,
        )
        for side, th, ls in (("program", np.asarray(theta), loss), ("reference", np.asarray(ref), ref_loss)):
            out[side].append({
                "loss": float(ls),
                "norm": float(np.linalg.norm(th - theta0)),
                "moved": np.flatnonzero((th != theta0).any(1)).tolist() if e == 0 else None,
            })
    out["steps"] = strategy._steps
    return out


def _placement() -> dict:
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.core.distributed import place_index
    from repro.launch.mesh import make_mesh

    mesh = make_mesh((K_SHARDS,), ("data",))
    C = _cfg().cluster_capacity
    _, knn_idx, knn_w, counts = seeded_state(7, N, K, C, 15, 2)
    want = host_shard_pass(knn_idx, knn_w, counts, K_SHARDS)
    row_off = (np.arange(K * C, dtype=np.int32) // (K * C // K_SHARDS) * (K * C // K_SHARDS))[:, None]
    split = NamedSharding(mesh, P("data", None))
    inputs = {
        "host": (knn_idx, knn_w, counts),
        "device": tuple(jnp.asarray(a) for a in (knn_idx, knn_w, counts)),
        "device_split": (jax.device_put(knn_idx, split), jax.device_put(knn_w, split),
                         jnp.asarray(counts)),
    }
    out = {}
    for name, (ki, kw, cnt) in inputs.items():
        idx, cg = place_index(SimpleNamespace(knn_idx=ki, knn_w=kw, counts=cnt), mesh, ("data",))
        got = (np.asarray(idx["knn_idx"]) - row_off, np.asarray(idx["knn_w"]),
               np.asarray(idx["counts"]), np.asarray(idx["cum_counts"]), np.asarray(cg))
        out[name] = {
            "equal": [g.dtype == w.dtype and np.array_equal(g, w) for g, w in zip(got, want)],
            "global_ids": bool(np.array_equal(np.asarray(idx["knn_idx"]), knn_idx)),
            "row_blocks": {
                k: sorted((s.index[0].start or 0, s.data.shape[0], s.device.id)
                          for s in v.addressable_shards)
                for k, v in idx.items()
            },
            "counts_global_replicated": cg.sharding.is_fully_replicated,
        }
    bad = knn_idx.copy()
    bad[K * C // K_SHARDS - 1, 0] = K * C // K_SHARDS  # the last row of shard 0 → shard 1
    out["crossing"] = {}
    for name, arr in (("host", bad), ("device", jnp.asarray(bad))):
        try:
            place_index(SimpleNamespace(knn_idx=arr, knn_w=knn_w, counts=counts), mesh, ("data",))
            out["crossing"][name] = "placed"
        except AssertionError as e:
            out["crossing"][name] = str(e)
    return out


def _place_span() -> list:
    import tempfile

    import jax
    from jax.profiler import ProfileData

    from repro.core.strategy import ShardedStrategy
    from repro.launch.mesh import make_mesh

    cfg = _cfg()
    C = cfg.cluster_capacity
    theta0, knn_idx, knn_w, counts = seeded_state(1, N, K, C, cfg.n_neighbors, cfg.out_dim)
    index = SimpleNamespace(knn_idx=knn_idx, knn_w=knn_w, counts=counts)
    with tempfile.TemporaryDirectory() as d:
        with jax.profiler.trace(d):
            ShardedStrategy(mesh=make_mesh((K_SHARDS,), ("data",))).prepare(cfg, "nomad", index, theta0)
        (path,) = glob.glob(os.path.join(d, "**", "*.xplane.pb"), recursive=True)
        return sorted({
            ev.name
            for plane in ProfileData.from_file(path).planes if plane.name.startswith("/host:")
            for line in plane.lines for ev in line.events if ev.name.startswith("nomad.")
        })


@pytest.fixture(scope="module")
def child():
    env = dict(os.environ, XLA_FLAGS="--xla_force_host_platform_device_count=4", JAX_PLATFORMS="cpu")
    src = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "src")
    env["PYTHONPATH"] = src + os.pathsep + env.get("PYTHONPATH", "")
    r = subprocess.run(
        [sys.executable, os.path.abspath(__file__)],
        capture_output=True, text=True, timeout=600, env=env,
    )
    assert r.returncode == 0, r.stderr[-3000:]
    return json.loads(r.stdout.strip().splitlines()[-1])


def _rel(a: float, b: float) -> float:
    return abs(a - b) / max(abs(b), 1e-30)


@pytest.mark.parametrize("seed", SEEDS)
def test_sharded_dispatches_match_the_plain_reference(child, seed):
    run = child["dispatches"][str(seed)]
    assert run["steps"] == SHARD_STEPS
    for got, want in zip(run["program"], run["reference"]):
        assert _rel(got["loss"], want["loss"]) < REL_TOL, (got["loss"], want["loss"])
        assert _rel(got["norm"], want["norm"]) < REL_TOL, (got["norm"], want["norm"])
        assert got["norm"] > 0
    got, want = run["program"][0]["moved"], run["reference"][0]["moved"]
    assert got == want and len(got) > B


@pytest.mark.parametrize("source", ["host", "device", "device_split"])
def test_placement_equals_the_host_pass(child, source):
    out = child["placement"][source]
    assert out["equal"] == [True] * 5, out["equal"]
    assert out["global_ids"]
    assert out["counts_global_replicated"]


@pytest.mark.parametrize("source", ["host", "device", "device_split"])
def test_placement_splits_every_array_by_rows_over_the_devices(child, source):
    C = _cfg().cluster_capacity
    rows = {"knn_idx": K * C, "knn_w": K * C, "counts": K, "cum_counts": K}
    for name, blocks in child["placement"][source]["row_blocks"].items():
        per = rows[name] // K_SHARDS
        assert [b[:2] for b in blocks] == [[s * per, per] for s in range(K_SHARDS)], name
        assert sorted(b[2] for b in blocks) == list(range(K_SHARDS)), name


@pytest.mark.parametrize("source", ["host", "device"])
def test_an_edge_across_shards_still_raises(child, source):
    assert child["placement"]["crossing"][source] == "kNN edge crosses shard boundary"


def test_prepare_opens_the_place_span(child):
    from repro.core.strategy import SPAN_PLACE

    assert child["place_span"] == [SPAN_PLACE]


if __name__ == "__main__":
    import jax

    assert jax.device_count() == K_SHARDS
    print(json.dumps({
        "dispatches": {str(s): _dispatches(s) for s in SEEDS},
        "placement": _placement(),
        "place_span": _place_span(),
    }))
