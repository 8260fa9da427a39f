"""Public op + registry spec: ``nomad_step_fused`` with a custom VJP.

The whole per-step NOMAD loss (attraction + mean repulsion + exact in-cell
negatives) as ONE registry kernel. Differentiable in (θ_i, θ_pos, θ_neg)
only — by the paper's design the edge weights are data, the cell weights
are statistics, and the means refresh by all-gather, never by gradient
flow; the VJP returns ``None`` for all of them.

The forward saves the online-accumulated repulsive mass m (1, B') as a
residual so the backward never replays the K sweep before its gradient
tiles; both directions are Pallas kernels over the same (bb, bk) tiling
(one cached ``custom_vjp`` instance per static (bb, bk, interpret) triple,
so the pair stays consistent under autodiff).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import registry
from repro.kernels.nomad_step.nomad_step import (
    nomad_step_bwd_pallas,
    nomad_step_fwd_pallas,
)
from repro.kernels.nomad_step.ref import nomad_step_ref
from repro.kernels.padding import pad_minor as _pad_minor

DEFAULT_BB, DEFAULT_BK = 512, 1024


@functools.lru_cache(maxsize=None)
def _build_op(bb_max: int, bk_max: int, interpret: bool):
    """One custom-vjp op per static (bb, bk, interpret) configuration."""

    def _prep(theta_i, theta_pos, pos_w, theta_neg, neg_w, means, cell_w, own_cell):
        B, d = theta_i.shape
        k, S = pos_w.shape[1], neg_w.shape[1]
        bb = min(bb_max, max(B, 8))
        bk = min(bk_max, max(means.shape[0], 128))
        th = _pad_minor(theta_i.astype(jnp.float32).T, bb)  # (d, B')
        # (B, k, d) → (k, d, B) → (k·d, B'): row j·d + dd = component dd of tail j
        pos = _pad_minor(
            jnp.transpose(theta_pos.astype(jnp.float32), (1, 2, 0)).reshape(k * d, B), bb
        )
        pw = _pad_minor(pos_w.astype(jnp.float32).T, bb)  # (k, B') pad w=0
        neg = _pad_minor(
            jnp.transpose(theta_neg.astype(jnp.float32), (1, 2, 0)).reshape(S * d, B), bb
        )
        nw = _pad_minor(neg_w.astype(jnp.float32).T, bb)  # (S, B') pad w=0
        mu = _pad_minor(means.astype(jnp.float32).T, bk).T  # (K', d)
        cw = _pad_minor(cell_w.astype(jnp.float32)[None, :], bk).T  # (K', 1) pad w=0
        own = _pad_minor(own_cell.astype(jnp.int32)[None, :], bb, fill=-1)
        return th, pos, pw, neg, nw, mu, cw, own, bb, bk, B

    @jax.custom_vjp
    def op(theta_i, theta_pos, pos_w, theta_neg, neg_w, means, cell_w, own_cell):
        loss, _ = _fwd(theta_i, theta_pos, pos_w, theta_neg, neg_w, means, cell_w, own_cell)
        return loss

    def _fwd(theta_i, theta_pos, pos_w, theta_neg, neg_w, means, cell_w, own_cell):
        th, pos, pw, neg, nw, mu, cw, own, bb, bk, B = _prep(
            theta_i, theta_pos, pos_w, theta_neg, neg_w, means, cell_w, own_cell
        )
        loss, m = nomad_step_fwd_pallas(
            th, pos, pw, neg, nw, mu, cw, own, bb=bb, bk=bk, interpret=interpret
        )
        res = (theta_i, theta_pos, pos_w, theta_neg, neg_w, means, cell_w, own_cell, m)
        return loss[0, :B], res

    def _bwd(res, gbar):
        theta_i, theta_pos, pos_w, theta_neg, neg_w, means, cell_w, own_cell, m = res
        th, pos, pw, neg, nw, mu, cw, own, bb, bk, B = _prep(
            theta_i, theta_pos, pos_w, theta_neg, neg_w, means, cell_w, own_cell
        )
        gb = _pad_minor(gbar.astype(jnp.float32)[None, :], bb)
        gi, gpos, gneg = nomad_step_bwd_pallas(
            th, pos, pw, neg, nw, mu, cw, own, m, gb, bb=bb, bk=bk, interpret=interpret
        )
        d, k, S = theta_i.shape[1], pos_w.shape[1], neg_w.shape[1]
        g_i = gi[:, :B].T.astype(theta_i.dtype)  # (B, d)
        g_pos = gpos[:, :B].reshape(k, d, B).transpose(2, 0, 1).astype(theta_pos.dtype)
        g_neg = gneg[:, :B].reshape(S, d, B).transpose(2, 0, 1).astype(theta_neg.dtype)
        return (g_i, g_pos, None, g_neg, None, None, None, None)

    op.defvjp(_fwd, _bwd)
    return op


def nomad_step_fused(
    theta_i,
    theta_pos,
    pos_w,
    theta_neg,
    neg_w,
    means,
    cell_w,
    own_cell,
    *,
    bb: int = DEFAULT_BB,
    bk: int = DEFAULT_BK,
    interpret: bool | None = None,
):
    """Per-head NOMAD step loss (B,), one tiled pass. Differentiable in
    (θ_i, θ_pos, θ_neg) only (custom VJP); online accumulation over
    (bb, bk) tiles — no (B, k+S) or (B, K) HBM intermediate."""
    if interpret is None:
        interpret = registry.interpret_default()
    return _build_op(bb, bk, interpret)(
        theta_i, theta_pos, pos_w, theta_neg, neg_w, means, cell_w, own_cell
    )


# ---------------------------------------------------------------------------
# Registry spec
# ---------------------------------------------------------------------------


def _pallas_adapter(*args, tiles, interpret):
    return nomad_step_fused(
        *args,
        bb=tiles.get("bb", DEFAULT_BB),
        bk=tiles.get("bk", DEFAULT_BK),
        interpret=interpret,
    )


def _make_inputs(key, sig):
    (ts, tdt), (ps, pdt), (ws, wdt), (ns, ndt), (nws, nwdt), (ms, mdt), (cs, cdt), (os_, odt) = sig
    K = ms[0]
    ks = jax.random.split(key, 8)
    theta = jax.random.normal(ks[0], ts, tdt) * 3.0
    pos = jax.random.normal(ks[1], ps, pdt) * 3.0
    pw = jax.random.uniform(ks[2], ws, wdt)
    neg = jax.random.normal(ks[3], ns, ndt) * 3.0
    nw = jax.random.uniform(ks[4], nws, nwdt)
    means = jax.random.normal(ks[5], ms, mdt) * 3.0
    cw = jax.random.uniform(ks[6], cs, cdt)
    own = jax.random.randint(ks[7], os_, 0, K, odt)
    return theta, pos, pw, neg, nw, means, cw, own


def _sig(B, k, S, K, d, dt="float32"):
    return (
        ((B, d), dt),
        ((B, k, d), dt),
        ((B, k), dt),
        ((B, S, d), dt),
        ((B, S), dt),
        ((K, d), dt),
        ((K,), dt),
        ((B,), "int32"),
    )


def _cost_model(sig):
    """Forward-pass cost: FLOPs of the three affinity families + streamed
    bytes (loss + m out; everything else in once)."""
    (B, d) = sig[0][0]
    k = sig[2][0][1]
    S = sig[4][0][1]
    K = sig[5][0][0]
    flops = float(B) * (K * (3 * d + 4) + (k + S) * (3 * d + 12))
    bytes_ = 4.0 * (
        B * d + B * k * d + B * k + B * S * d + B * S + K * d + K + B + 2 * B
    )
    return {"flops": flops, "bytes": bytes_}


SPEC = registry.register(
    registry.KernelSpec(
        name="nomad_step",
        ref=nomad_step_ref,
        pallas=_pallas_adapter,
        tile_candidates=(
            {"bb": 256, "bk": 512},
            {"bb": 512, "bk": 512},
            {"bb": 512, "bk": 1024},
            {"bb": 1024, "bk": 512},
        ),
        default_tiles={
            "": {"bb": DEFAULT_BB, "bk": DEFAULT_BK},
            "tpu": {"bb": DEFAULT_BB, "bk": DEFAULT_BK},
        },
        make_inputs=_make_inputs,
        check_shapes=(
            _sig(512, 15, 16, 64, 2),
            _sig(100, 5, 4, 33, 2),  # ragged B and K exercise pad_minor
            _sig(64, 3, 8, 100, 3),
            _sig(777, 15, 16, 130, 2),
        ),
        bench_shapes=_sig(2048, 15, 16, 1024, 2),
        tol=(2e-5, 2e-5),
        cost_model=_cost_model,
    )
)
