"""Figure-3 analogue: speed vs quality across methods and epoch budgets.

The paper sweeps training epochs for NOMAD vs GPU t-SNE/UMAP on ArXiv and
ImageNet embeddings, reporting NP@10 and random-triplet accuracy. Offline we
use the synthetic embedding-like corpus and compare:

* ``nomad``        — the paper's method (single device),
* ``nomad-8shard`` — 8 simulated devices (the multi-GPU trade-off claim:
  similar/better NP, slight RTA cost from partition approximation),
* ``infonc``       — the exact InfoNC-t-SNE loss (what t-SNE-CUDA-class
  methods optimise; no mean approximation).

Emits CSV rows ``name,us_per_call,derived`` where us_per_call is wall-time
per epoch and ``derived`` packs NP@10 / RTA at the final epoch.
"""

from __future__ import annotations

import time

import numpy as np

from repro.configs.base import NomadConfig
from repro.core.nomad import NomadProjection
from repro.data.synthetic import gaussian_mixture
from repro.metrics import neighborhood_preservation, random_triplet_accuracy

N, DIM = 8000, 64


def _cfg(**kw):
    base = dict(
        n_points=N, dim=DIM, n_clusters=16, n_neighbors=15, n_noise=32,
        n_exact_negatives=8, batch_size=1024, n_epochs=30,
        strategy="local",  # both methods on one device — apples to apples
    )
    base.update(kw)
    return NomadConfig(**base)


def run(quick: bool = False):
    rows = []
    x, _ = gaussian_mixture(N, DIM, n_components=12, seed=0)
    sweep = (10, 40) if quick else (10, 40, 160, 400)

    from repro.index.ann import build_index

    index = build_index(x, _cfg())

    from repro.kernels import registry

    for method in ("nomad", "infonc"):
        for epochs in sweep:
            cfg = _cfg(n_epochs=epochs, n_noise=64, method=method)
            # which path the fused step took (jnp on CPU, pallas on TPU/GPU)
            impl = registry.resolve("nomad_step", cfg.resolved_kernel_impl())
            res = NomadProjection(cfg).fit(x, index=index)
            per_epoch = (
                float(np.mean(res.epoch_times[1:]))
                if len(res.epoch_times) > 1
                else res.epoch_times[0]
            )
            np10 = neighborhood_preservation(x, res.embedding, k=10, n_queries=500)
            rta = random_triplet_accuracy(x, res.embedding, 10_000)
            rows.append(
                (f"fig3/{method}@{epochs}ep", per_epoch * 1e6,
                 f"np10={np10:.4f};rta={rta:.4f};epochs={epochs};impl={impl}")
            )
    return rows


if __name__ == "__main__":
    from repro.runtime import enable_compile_cache

    enable_compile_cache()
    for r in run():
        print(",".join(str(c) for c in r))
