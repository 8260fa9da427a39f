"""Fused K-means E-step Pallas kernel: distance tile (MXU) + running argmin.

Grid (N/bn, K/bk); the running (min, argmin) lives in the output blocks
(VMEM-resident, re-read each K step) — no (N, K) distance matrix ever
reaches HBM. Epilogue clamps distances at 0.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

BIG = 3.0e38  # python float: jnp scalars would be captured as consts


def _kernel(x_ref, c_ref, arg_ref, min_ref, *, bk):
    kstep = pl.program_id(1)

    @pl.when(kstep == 0)
    def _init():
        min_ref[...] = jnp.full_like(min_ref, BIG)
        arg_ref[...] = jnp.zeros_like(arg_ref)

    x = x_ref[...]  # (bn, D)
    c = c_ref[...]  # (bk, D)
    cross = jax.lax.dot_general(
        x, c, (((1,), (1,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,  # f32 operands, f32 distances
        preferred_element_type=jnp.float32,
    )
    d2 = (
        jnp.sum(jnp.square(x), axis=1, keepdims=True)
        + jnp.sum(jnp.square(c), axis=1, keepdims=True).T
        - 2.0 * cross
    )
    d2 = jnp.maximum(d2, 0.0)  # (bn, bk)
    tile_min = jnp.min(d2, axis=1)  # (bn,)
    tile_arg = (kstep * bk + jnp.argmin(d2, axis=1)).astype(jnp.int32)
    cur = min_ref[0, :]
    better = tile_min < cur
    min_ref[0, :] = jnp.where(better, tile_min, cur)
    arg_ref[0, :] = jnp.where(better, tile_arg, arg_ref[0, :])


def assign_nearest_pallas(x, cents, *, block_n=512, block_k=256, interpret=True):
    """x (N, D), cents (K, D), D block-resident → ((1,N) int32, (1,N) fp32)."""
    n, d = x.shape
    k = cents.shape[0]
    bn, bk = min(block_n, n), min(block_k, k)
    assert n % bn == 0 and k % bk == 0, (n, k, bn, bk)
    grid = (n // bn, k // bk)
    return pl.pallas_call(
        functools.partial(_kernel, bk=bk),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bn, d), lambda i, kk: (i, 0)),
            pl.BlockSpec((bk, d), lambda i, kk: (kk, 0)),
        ],
        out_specs=(
            pl.BlockSpec((1, bn), lambda i, kk: (0, i)),
            pl.BlockSpec((1, bn), lambda i, kk: (0, i)),
        ),
        out_shape=(
            jax.ShapeDtypeStruct((1, n), jnp.int32),
            jax.ShapeDtypeStruct((1, n), jnp.float32),
        ),
        interpret=interpret,
    )(x.astype(jnp.float32), cents.astype(jnp.float32))
