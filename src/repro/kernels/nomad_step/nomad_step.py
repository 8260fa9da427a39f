"""Fused NOMAD SGD-step Pallas TPU kernels (forward + backward).

One tiled pass per step computes everything the θ update needs: pairwise
distances to the k positives and S exact negatives, Cauchy weights, the
B×K mean-repulsion term, and the per-head loss — the flash-attention
trick applied to Eq. 3: the repulsive mass m_b = M̃_b + M_b is accumulated
*online* across K-tiles (grid dim 1), so the (B, k+S) affinity block and
the (B, K) mean-term block never materialise in HBM. Only θ (d×B), the
positive/negative blocks (k·d×B / S·d×B), their weights, μ (d×K) and the
cell weights stream in; loss (1×B) and m (1×B, the backward's residual)
stream out.

Layout (same TPU adaptation as ``cauchy_mean``/``frozen_attract``):
per-head tensors cross the kernel transposed with the large B axis on
lanes; the tiny static k, S and d axes are flattened as (k·d, B) rows
s·d + dd and fully unrolled. The means arrive untransposed, μ (K, d) and
cell weights (K, 1), so the (bk, bb) mean-term tile puts cells on
sublanes and heads on lanes: θ rows broadcast down sublanes, μ columns
across lanes, and the K reduction lands lane-major like m itself.

Schedule (grid = (B//bb, K//bk), kstep = program_id(1) iterates fastest):

  kstep 0        zero-init m; (+ backward: write attraction & exact-neg
                 gradient parts, which don't depend on the K tile)
  every kstep    m += Σ_r cell_w·[r≠own]·q(θ, μ_r) over this bk tile
                 (+ backward: g_i += mean-term gradient of this tile)
  last kstep     m += Σ_s neg_w·q(θ, θ_neg)  (exact in-cell negatives),
                 then loss = Σ_s pos_w·(log(q_pos + m) + log1p(d2_pos))

The backward takes m as a residual (saved by the forward), so the online
accumulation never has to be replayed before the gradient tiles.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.cauchy_mean.cauchy_mean import _dist2_tile, _not_own


def _flat_dist2(th, flat_ref, j, d):
    """th (d, bb) vs row-block j of a (n·d, bb) flattened tensor → (diffs, d2)."""
    diffs, d2 = [], None
    for dd in range(d):
        diff = th[dd, :] - flat_ref[j * d + dd, :]
        diffs.append(diff)
        d2 = diff * diff if d2 is None else d2 + diff * diff
    return diffs, d2


def _fwd_kernel(
    th_ref, pos_ref, pw_ref, neg_ref, nw_ref, mu_ref, cw_ref, own_ref,
    loss_ref, m_ref, *, d, k, s, bk, nk,
):
    kstep = pl.program_id(1)

    @pl.when(kstep == 0)
    def _init():
        m_ref[...] = jnp.zeros_like(m_ref)
        loss_ref[...] = jnp.zeros_like(loss_ref)

    th = th_ref[...]  # (d, bb)
    q = 1.0 / (1.0 + _dist2_tile(th, mu_ref[...], d))  # (bk, bb)
    mask = _not_own(own_ref, kstep, bk, th.shape[1])
    w = cw_ref[...]  # (bk, 1)
    m_ref[...] += jnp.sum(q * w * mask, axis=0, keepdims=True)  # online M̃

    @pl.when(kstep == nk - 1)
    def _finish():
        m = m_ref[0, :]
        for j in range(s):  # exact in-cell negatives: M
            _, d2 = _flat_dist2(th, neg_ref, j, d)
            m += nw_ref[...][j, :] * (1.0 / (1.0 + d2))
        m_ref[0, :] = m
        acc = jnp.zeros_like(m)
        for j in range(k):  # attraction + shared log-denominator
            _, d2 = _flat_dist2(th, pos_ref, j, d)
            qp = 1.0 / (1.0 + d2)
            acc += pw_ref[...][j, :] * (jnp.log(qp + m) + jnp.log1p(d2))
        loss_ref[0, :] = acc


def _bwd_kernel(
    th_ref, pos_ref, pw_ref, neg_ref, nw_ref, mu_ref, cw_ref, own_ref,
    m_ref, gbar_ref, gi_ref, gpos_ref, gneg_ref, *, d, k, s, bk,
):
    kstep = pl.program_id(1)
    th = th_ref[...]  # (d, bb)
    m = m_ref[...][0, :]  # (bb,) — the forward's residual (full M̃ + M)
    gbar = gbar_ref[...][0, :]

    # G_b = ∂loss_b/∂m_b = Σ_j pw_j/(q_pj + m) — k is tiny and unrolled, so
    # recomputing it per K-tile is cheaper than a cross-tile carry.
    pw = pw_ref[...]
    pos_terms = []
    G = None
    for j in range(k):
        diffs, d2 = _flat_dist2(th, pos_ref, j, d)
        qp = 1.0 / (1.0 + d2)
        qpm = qp + m
        pos_terms.append((diffs, qp, qpm))
        contrib = pw[j, :] / qpm
        G = contrib if G is None else G + contrib

    @pl.when(kstep == 0)
    def _first():
        # attraction (∂ via q_pos) + exact negatives (∂ via m): K-independent
        gi = [jnp.zeros_like(m) for _ in range(d)]
        for j in range(k):
            diffs, qp, qpm = pos_terms[j]
            factor = pw[j, :] * (qp - qp * qp / qpm)
            for dd in range(d):
                gi[dd] += factor * diffs[dd]
                gpos_ref[j * d + dd, :] = -2.0 * gbar * factor * diffs[dd]
        nw = nw_ref[...]
        for j in range(s):
            diffs, d2 = _flat_dist2(th, neg_ref, j, d)
            qn = 1.0 / (1.0 + d2)
            coef = G * nw[j, :] * qn * qn
            for dd in range(d):
                gneg_ref[j * d + dd, :] = 2.0 * gbar * coef * diffs[dd]
                gi[dd] -= coef * diffs[dd]
        for dd in range(d):
            gi_ref[dd, :] = 2.0 * gbar * gi[dd]

    # mean-term gradient of this K tile, accumulated online into g_i
    mu = mu_ref[...]  # (bk, d)
    q = 1.0 / (1.0 + _dist2_tile(th, mu, d))  # (bk, bb)
    mask = _not_own(own_ref, kstep, bk, th.shape[1])
    factor = cw_ref[...] * mask * q * q  # (bk, bb)
    for dd in range(d):
        diff = th[dd : dd + 1, :] - mu[:, dd : dd + 1]
        gi_ref[dd, :] += -2.0 * gbar * G * jnp.sum(factor * diff, axis=0)


def _grids(B, K, bb, bk):
    assert B % bb == 0 and K % bk == 0, (B, K, bb, bk)
    return (B // bb, K // bk)


def nomad_step_fwd_pallas(
    th, pos, pw, neg, nw, mu, cw, own, *, bb=512, bk=1024, interpret=True
):
    """th (d,B), pos (k·d,B), pw (k,B), neg (S·d,B), nw (S,B), mu (K,d),
    cw (K,1), own (1,B) → (loss (1,B), m (1,B))."""
    d, B = th.shape
    k, s = pw.shape[0], nw.shape[0]
    K = mu.shape[0]
    bb, bk = min(bb, B), min(bk, K)
    grid = _grids(B, K, bb, bk)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, d=d, k=k, s=s, bk=bk, nk=grid[1]),
        grid=grid,
        in_specs=[
            pl.BlockSpec((d, bb), lambda i, kk: (0, i)),
            pl.BlockSpec((k * d, bb), lambda i, kk: (0, i)),
            pl.BlockSpec((k, bb), lambda i, kk: (0, i)),
            pl.BlockSpec((s * d, bb), lambda i, kk: (0, i)),
            pl.BlockSpec((s, bb), lambda i, kk: (0, i)),
            pl.BlockSpec((bk, d), lambda i, kk: (kk, 0)),
            pl.BlockSpec((bk, 1), lambda i, kk: (kk, 0)),
            pl.BlockSpec((1, bb), lambda i, kk: (0, i)),
        ],
        out_specs=[
            pl.BlockSpec((1, bb), lambda i, kk: (0, i)),
            pl.BlockSpec((1, bb), lambda i, kk: (0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((1, B), jnp.float32),
            jax.ShapeDtypeStruct((1, B), jnp.float32),
        ],
        interpret=interpret,
    )(th, pos, pw, neg, nw, mu, cw, own)


def nomad_step_bwd_pallas(
    th, pos, pw, neg, nw, mu, cw, own, m, gbar, *, bb=512, bk=1024, interpret=True
):
    """Adds m (1,B) residual + gbar (1,B): returns (g_i (d,B),
    g_pos (k·d,B), g_neg (S·d,B))."""
    d, B = th.shape
    k, s = pw.shape[0], nw.shape[0]
    K = mu.shape[0]
    bb, bk = min(bb, B), min(bk, K)
    grid = _grids(B, K, bb, bk)
    return pl.pallas_call(
        functools.partial(_bwd_kernel, d=d, k=k, s=s, bk=bk),
        grid=grid,
        in_specs=[
            pl.BlockSpec((d, bb), lambda i, kk: (0, i)),
            pl.BlockSpec((k * d, bb), lambda i, kk: (0, i)),
            pl.BlockSpec((k, bb), lambda i, kk: (0, i)),
            pl.BlockSpec((s * d, bb), lambda i, kk: (0, i)),
            pl.BlockSpec((s, bb), lambda i, kk: (0, i)),
            pl.BlockSpec((bk, d), lambda i, kk: (kk, 0)),
            pl.BlockSpec((bk, 1), lambda i, kk: (kk, 0)),
            pl.BlockSpec((1, bb), lambda i, kk: (0, i)),
            pl.BlockSpec((1, bb), lambda i, kk: (0, i)),
            pl.BlockSpec((1, bb), lambda i, kk: (0, i)),
        ],
        out_specs=[
            pl.BlockSpec((d, bb), lambda i, kk: (0, i)),
            pl.BlockSpec((k * d, bb), lambda i, kk: (0, i)),
            pl.BlockSpec((s * d, bb), lambda i, kk: (0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((d, B), jnp.float32),
            jax.ShapeDtypeStruct((k * d, B), jnp.float32),
            jax.ShapeDtypeStruct((s * d, B), jnp.float32),
        ],
        interpret=interpret,
    )(th, pos, pw, neg, nw, mu, cw, own, m, gbar)
