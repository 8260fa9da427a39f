"""Figure-1/4 analogue: multiscale structure of the map.

The paper's qualitative claim: the Wikipedia map is coherent at global,
mid, and extremely local zoom. Quantified here on a two-level hierarchical
mixture: neighbor label purity at the super-cluster level (global zoom)
and the sub-cluster level (local zoom), plus super-cluster centroid
separation in the 2-D map.
"""

from __future__ import annotations

import numpy as np

from repro.configs.base import NomadConfig
from repro.core.nomad import NomadProjection
from repro.data.synthetic import hierarchical_mixture
from repro.metrics.neighborhood import _topk_neighbors

import jax.numpy as jnp


def run(quick: bool = False):
    n = 6000
    x, sup, sub = hierarchical_mixture(n, 48, n_super=5, n_sub=4, seed=0)
    cfg = NomadConfig(
        n_points=n, dim=48, n_clusters=20, n_neighbors=15, n_noise=32,
        n_exact_negatives=8, batch_size=1024,
        n_epochs=10 if quick else 30,
    )
    res = NomadProjection(cfg).fit(x)
    emb = res.embedding
    q = 600
    nb = np.asarray(_topk_neighbors(jnp.asarray(emb[:q]), jnp.asarray(emb), 10))
    sup_purity = float(np.mean(sup[nb] == sup[:q, None]))
    sub_purity = float(np.mean(sub[nb] == sub[:q, None]))
    # global separation: between/within scatter of super-cluster centroids
    cents = np.stack([emb[sup == s].mean(0) for s in range(5)])
    within = np.mean([emb[sup == s].std(0).mean() for s in range(5)])
    between = np.std(cents, axis=0).mean()
    per_epoch = float(np.mean(res.epoch_times[1:])) * 1e6
    return [(
        "fig4/multiscale", per_epoch,
        f"super_purity={sup_purity:.3f};sub_purity={sub_purity:.3f};"
        f"separation={between/max(within,1e-9):.2f}",
    )]


if __name__ == "__main__":
    from repro.runtime import enable_compile_cache

    enable_compile_cache()
    for r in run():
        print(",".join(str(c) for c in r))
