"""Blocked pairwise squared-distance Pallas TPU kernel.

``dist²(x, y) = ‖x‖² + ‖y‖² − 2·x·yᵀ`` — the cross term is a matmul, so the
kernel rides the MXU; the norms are cheap VPU epilogues. This is the
hot inner loop of both the within-cluster exact kNN (paper §3.2) and the
K-means E-step.

Grid: (N/bn, M/bm, D/bd) with accumulation over the D axis; the norm
epilogue fires on the last D step. Block sizes default to MXU-aligned
(128×…) tiles; the D tile keeps x/y slabs within a VMEM budget.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _kernel(x_ref, y_ref, out_ref, *, n_d_steps: int):
    d_step = pl.program_id(2)

    @pl.when(d_step == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    x = x_ref[...]  # (bn, bd)
    y = y_ref[...]  # (bm, bd)
    # accumulate ‖x‖² + ‖y‖² − 2 x yᵀ piecewise over D
    cross = jax.lax.dot_general(
        x, y, (((1,), (1,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,  # f32 operands, f32 distances
        preferred_element_type=jnp.float32,
    )
    x2 = jnp.sum(jnp.square(x), axis=1, keepdims=True)  # (bn, 1)
    y2 = jnp.sum(jnp.square(y), axis=1, keepdims=True).T  # (1, bm)
    out_ref[...] += x2 + y2 - 2.0 * cross

    @pl.when(d_step == n_d_steps - 1)
    def _clamp():
        out_ref[...] = jnp.maximum(out_ref[...], 0.0)


def pairwise_dist2_pallas(
    x: jax.Array,
    y: jax.Array,
    *,
    block_n: int = 256,
    block_m: int = 256,
    block_d: int = 512,
    interpret: bool = True,
) -> jax.Array:
    """x (N, D), y (M, D) fp32 → (N, M) fp32. Caller pads to block multiples."""
    n, d = x.shape
    m, _ = y.shape
    bn, bm, bd = min(block_n, n), min(block_m, m), min(block_d, d)
    assert n % bn == 0 and m % bm == 0 and d % bd == 0, (x.shape, y.shape, (bn, bm, bd))
    grid = (n // bn, m // bm, d // bd)
    return pl.pallas_call(
        functools.partial(_kernel, n_d_steps=grid[2]),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bn, bd), lambda i, j, kd: (i, kd)),
            pl.BlockSpec((bm, bd), lambda i, j, kd: (j, kd)),
        ],
        out_specs=pl.BlockSpec((bn, bm), lambda i, j, kd: (i, j)),
        out_shape=jax.ShapeDtypeStruct((n, m), jnp.float32),
        interpret=interpret,
    )(x.astype(jnp.float32), y.astype(jnp.float32))
