"""Fused Cauchy-vs-means Pallas TPU kernels (forward + backward).

This is NOMAD's negative-force hot spot: every sampled head is repelled by
all K cluster means (Eq. 4), a B×K Cauchy contraction executed every step.
Fusing the weight construction (`|M|·p(m∈r)·[r ≠ own]`), the affinity and
the reduction means the (B, K) intermediate never touches HBM — only
θ (d×B), μ (d×K), w (K) stream in and s (B) streams out; arithmetic
intensity is ~K/2 flops/byte, comfortably compute-bound on the VPU.

Layout note (TPU adaptation): head positions are passed transposed, (d, B)
with d = 2, so the minor (lane) axis is the large one; the tiny d axis sits
on sublanes. The means stay (K, d) and the weights (K, 1), so the (bk, bb)
working tile — cells on sublanes, heads on lanes — is built from a θ row
broadcast down sublanes and a μ column broadcast across lanes, and its K
reduction lands lane-major like the output. The tile lives in VMEM/VREGs
only.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _dist2_tile(th, mu, d):
    """th (d, bb), mu (bk, d) → (bk, bb) squared distances (d unrolled)."""
    acc = None
    for dd in range(d):
        diff = th[dd : dd + 1, :] - mu[:, dd : dd + 1]
        acc = diff * diff if acc is None else acc + diff * diff
    return acc


def _not_own(own_ref, kstep, bk, bb):
    """(bk, bb) mask: 1 where cell kstep·bk + row is not the head's own."""
    r_ids = kstep * bk + jax.lax.broadcasted_iota(jnp.int32, (bk, bb), 0)
    return (own_ref[...] != r_ids).astype(jnp.float32)


def _fwd_kernel(theta_ref, means_ref, w_ref, own_ref, out_ref, *, d, bk):
    kstep = pl.program_id(1)

    @pl.when(kstep == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    th = theta_ref[...]  # (d, bb)
    q = 1.0 / (1.0 + _dist2_tile(th, means_ref[...], d))  # (bk, bb)
    mask = _not_own(own_ref, kstep, bk, th.shape[1])
    w = w_ref[...]  # (bk, 1)
    out_ref[...] += jnp.sum(q * w * mask, axis=0, keepdims=True)


def _bwd_kernel(theta_ref, means_ref, w_ref, own_ref, gbar_ref, gout_ref, *, d, bk):
    kstep = pl.program_id(1)

    @pl.when(kstep == 0)
    def _init():
        gout_ref[...] = jnp.zeros_like(gout_ref)

    th = theta_ref[...]  # (d, bb)
    mu = means_ref[...]  # (bk, d)
    q = 1.0 / (1.0 + _dist2_tile(th, mu, d))  # (bk, bb)
    mask = _not_own(own_ref, kstep, bk, th.shape[1])
    factor = w_ref[...] * mask * q * q  # (bk, bb)
    gbar = gbar_ref[...]  # (1, bb)
    for dd in range(d):
        diff = th[dd : dd + 1, :] - mu[:, dd : dd + 1]
        gsum = jnp.sum(factor * diff, axis=0, keepdims=True)
        gout_ref[dd : dd + 1, :] += -2.0 * gbar * gsum


def _grids(B, K, bb, bk):
    assert B % bb == 0 and K % bk == 0, (B, K, bb, bk)
    return (B // bb, K // bk)


def cauchy_mean_fwd_pallas(theta_t, means, w, own, *, bb=512, bk=1024, interpret=True):
    """theta_t (d, B), means (K, d), w (K, 1), own (1, B) → s (1, B)."""
    d, B = theta_t.shape
    K = means.shape[0]
    bb, bk = min(bb, B), min(bk, K)
    grid = _grids(B, K, bb, bk)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, d=d, bk=bk),
        grid=grid,
        in_specs=[
            pl.BlockSpec((d, bb), lambda i, kk: (0, i)),
            pl.BlockSpec((bk, d), lambda i, kk: (kk, 0)),
            pl.BlockSpec((bk, 1), lambda i, kk: (kk, 0)),
            pl.BlockSpec((1, bb), lambda i, kk: (0, i)),
        ],
        out_specs=pl.BlockSpec((1, bb), lambda i, kk: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, B), jnp.float32),
        interpret=interpret,
    )(theta_t, means, w, own)


def cauchy_mean_bwd_pallas(theta_t, means, w, own, gbar, *, bb=512, bk=1024, interpret=True):
    """Adds gbar: returns gθ (d, B)."""
    d, B = theta_t.shape
    K = means.shape[0]
    bb, bk = min(bb, B), min(bk, K)
    grid = _grids(B, K, bb, bk)
    return pl.pallas_call(
        functools.partial(_bwd_kernel, d=d, bk=bk),
        grid=grid,
        in_specs=[
            pl.BlockSpec((d, bb), lambda i, kk: (0, i)),
            pl.BlockSpec((bk, d), lambda i, kk: (kk, 0)),
            pl.BlockSpec((bk, 1), lambda i, kk: (kk, 0)),
            pl.BlockSpec((1, bb), lambda i, kk: (0, i)),
            pl.BlockSpec((1, bb), lambda i, kk: (0, i)),
        ],
        out_specs=pl.BlockSpec((d, bb), lambda i, kk: (0, i)),
        out_shape=jax.ShapeDtypeStruct((d, B), jnp.float32),
        interpret=interpret,
    )(theta_t, means, w, own, gbar)
