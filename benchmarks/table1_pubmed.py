"""Table-1 analogue: the PubMed-scale single-vs-multi-device comparison.

Table 1 claims NOMAD on 8 GPUs matches OpenTSNE's NP@10 (6.2% → 6.1±0.3%)
at 5.4× the speed, while single-GPU methods OOM. Offline we scale the axes
that matter — same index, same per-shard batch — and report:

* wall-time per epoch: 1 shard vs 8 simulated shards (speedup column),
* NP@10 parity between the two (quality column),
* peak *per-shard* working set of θ+index (the vRAM-cap story: it falls
  ~n_shards×, which is why the 8-GPU run completes where 1-GPU OOMs).

Runs the 8-shard fit in a subprocess with 8 host devices, as elsewhere.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import time

import numpy as np

from repro.configs.base import NomadConfig
from repro.core.nomad import NomadProjection
from repro.data.synthetic import gaussian_mixture
from repro.metrics import neighborhood_preservation

N, DIM = 12_000, 96
REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_WORKER = r"""
import sys, time, json
import numpy as np, jax
from repro.configs.base import NomadConfig
from repro.core.nomad import NomadProjection
from repro.data.synthetic import gaussian_mixture
from repro.metrics import neighborhood_preservation
from repro.index.ann import build_index

cfg = NomadConfig(**json.loads(sys.argv[1]))
x, _ = gaussian_mixture(cfg.n_points, cfg.dim, n_components=16, seed=0)
index = build_index(x, cfg)
from repro.launch.mesh import make_mesh
mesh = make_mesh((2, 4), ("data", "model"))
proj = NomadProjection(cfg, strategy="sharded", mesh=mesh,
                       shard_axes=("data", "model"))
t0 = time.time()
emb = proj.fit_transform(x, index=index)
wall = time.time() - t0
np10 = neighborhood_preservation(x, emb, k=10, n_queries=600)
print("RESULT", json.dumps({"wall": wall, "np10": np10}))
"""


def run(quick: bool = False):
    epochs = 6 if quick else 20
    cfg = NomadConfig(
        n_points=N, dim=DIM, n_clusters=32, n_neighbors=15, n_noise=32,
        n_exact_negatives=8, batch_size=1024, n_epochs=epochs,
    )
    rows = []
    x, _ = gaussian_mixture(N, DIM, n_components=16, seed=0)

    from repro.index.ann import build_index

    index = build_index(x, cfg)
    t0 = time.time()
    res = NomadProjection(cfg, strategy="local").fit(x, index=index)
    wall1 = time.time() - t0
    np10_1 = neighborhood_preservation(x, res.embedding, k=10, n_queries=600)

    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    import dataclasses

    payload = json.dumps(dataclasses.asdict(cfg))
    r = subprocess.run(
        [sys.executable, "-c", _WORKER, payload],
        capture_output=True, text=True, env=env, cwd=REPO, timeout=1800,
    )
    assert r.returncode == 0, r.stderr[-2000:]
    out = json.loads(r.stdout.strip().splitlines()[-1].split("RESULT ")[1])

    theta_bytes = cfg.n_clusters * cfg.cluster_capacity * 2 * 4
    knn_bytes = cfg.n_clusters * cfg.cluster_capacity * cfg.n_neighbors * 8
    shard_bytes_1 = theta_bytes + knn_bytes
    rows.append(
        ("table1/nomad-1shard", wall1 / epochs * 1e6,
         f"np10={np10_1:.4f};shard_mb={shard_bytes_1/2**20:.1f}")
    )
    rows.append(
        ("table1/nomad-8shard", out["wall"] / epochs * 1e6,
         f"np10={out['np10']:.4f};speedup={wall1/out['wall']:.2f}x;"
         f"shard_mb={shard_bytes_1/8/2**20:.1f}")
    )
    return rows


if __name__ == "__main__":
    from repro.runtime import enable_compile_cache

    enable_compile_cache()
    for r in run():
        print(",".join(str(c) for c in r))
