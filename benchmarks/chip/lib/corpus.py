"""A seeded corpus of embedding rows for the index build, made on the device.

    x = centre[c] + z @ basis[c] + noise        row of component c

Each of ``n_components`` components has a centre of norm about
``centre_norm``, a ``latent_dim``-dimensional basis of near-orthonormal
rows, latent coordinates ``z ~ N(0, latent_std²)`` and isotropic noise of
total norm about ``noise_norm``. The low-rank latent keeps the in-cell
distances spread out (they do not all concentrate at one value, as in pure
high-dimensional noise), so each row's nearest neighbours are well
separated from the next ones; the noise keeps every row full rank.

Component sizes are fixed by ``size_shares``, cycled over the components:
component j holds about ``share_j / mean share`` times n / n_components
rows, summing to n exactly. Every seed gives the same set of sizes, drawn
to the components in another order, and rows come in a random order, as
users pass them. One jitted call makes the corpus from the seed.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


def sizes(n: int, n_components: int, size_shares) -> list:
    """Rows of each component: ``size_shares`` cycled over the components,
    in proportion, rounded down, the rows left over one each to the first."""
    shares = [float(size_shares[c % len(size_shares)]) for c in range(n_components)]
    out = [int(n * s / sum(shares)) for s in shares]
    for c in range(n - sum(out)):
        out[c] += 1
    return out


def mixture(key, *, n, dim, n_components, size_shares, **shape):
    """Returns ``(x (n, dim) float32, component (n,) int32)``."""
    return _mixture(
        key, n=n, dim=dim, sizes=tuple(sizes(n, n_components, size_shares)), **shape
    )


@functools.partial(
    jax.jit,
    static_argnames=("n", "dim", "sizes", "latent_dim", "centre_norm", "latent_std", "noise_norm"),
)
def _mixture(key, *, n, dim, sizes, latent_dim, centre_norm, latent_std, noise_norm):
    n_components = len(sizes)
    k_cnt, k_perm, k_centre, k_basis, k_z, k_noise = jax.random.split(key, 6)
    order = jax.random.permutation(k_cnt, n_components).astype(jnp.int32)
    comp = jnp.repeat(order, jnp.array(sizes), total_repeat_length=n)
    comp = jax.random.permutation(k_perm, comp)

    scale = 1.0 / jnp.sqrt(jnp.float32(dim))
    centre = jax.random.normal(k_centre, (n_components, dim), jnp.float32) * (centre_norm * scale)
    basis = jax.random.normal(k_basis, (n_components, latent_dim, dim), jnp.float32) * scale
    z = jax.random.normal(k_z, (n, latent_dim), jnp.float32) * latent_std
    lifted = jnp.zeros((n, dim), jnp.float32)
    for c in range(n_components):
        zc = jnp.dot(z, basis[c], precision=jax.lax.Precision.HIGHEST)
        lifted = jnp.where((comp == c)[:, None], zc, lifted)
    noise = jax.random.normal(k_noise, (n, dim), jnp.float32) * (noise_norm * scale)
    return centre[comp] + lifted + noise, comp
