"""Public op + registry spec: ``cauchy_weighted_sum`` with a custom VJP
(both directions are Pallas kernels; means and weights are
non-differentiable by the paper's design — means are refreshed by
all-gather, not by gradient flow).

Tile sizes (``bb`` over the batch, ``bk`` over the cells) are arguments
now: each distinct (bb, bk, interpret) triple gets its own cached
``custom_vjp`` instance so the pair stays consistent between forward and
backward under autodiff.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import registry
from repro.kernels.cauchy_mean.cauchy_mean import (
    cauchy_mean_bwd_pallas,
    cauchy_mean_fwd_pallas,
)
from repro.kernels.cauchy_mean.ref import cauchy_weighted_sum_ref
from repro.kernels.padding import pad_minor as _pad_minor

DEFAULT_BB, DEFAULT_BK = 512, 1024


@functools.lru_cache(maxsize=None)
def _build_op(bb_max: int, bk_max: int, interpret: bool):
    """One custom-vjp op per static (bb, bk, interpret) configuration."""

    def _prep(theta_i, means, cell_w, own_cell):
        B = theta_i.shape[0]
        bb, bk = min(bb_max, max(B, 8)), min(bk_max, max(means.shape[0], 128))
        th = _pad_minor(theta_i.astype(jnp.float32).T, bb)  # (d, B')
        mu = _pad_minor(means.astype(jnp.float32).T, bk).T  # (K', d)
        w = _pad_minor(cell_w.astype(jnp.float32)[None, :], bk).T  # (K', 1) pad w=0
        own = _pad_minor(own_cell.astype(jnp.int32)[None, :], bb, fill=-1)
        return th, mu, w, own, bb, bk, B

    @jax.custom_vjp
    def op(theta_i, means, cell_w, own_cell):
        s, _ = _fwd(theta_i, means, cell_w, own_cell)
        return s

    def _fwd(theta_i, means, cell_w, own_cell):
        th, mu, w, own, bb, bk, B = _prep(theta_i, means, cell_w, own_cell)
        s = cauchy_mean_fwd_pallas(th, mu, w, own, bb=bb, bk=bk, interpret=interpret)
        return s[0, :B], (theta_i, means, cell_w, own_cell)

    def _bwd(res, gbar):
        theta_i, means, cell_w, own_cell = res
        th, mu, w, own, bb, bk, B = _prep(theta_i, means, cell_w, own_cell)
        gb = _pad_minor(gbar.astype(jnp.float32)[None, :], bb)
        g = cauchy_mean_bwd_pallas(th, mu, w, own, gb, bb=bb, bk=bk, interpret=interpret)
        g_theta = g[:, :B].T.astype(theta_i.dtype)  # (B, d)
        return (g_theta, None, None, None)

    op.defvjp(_fwd, _bwd)
    return op


def cauchy_weighted_sum(
    theta_i,
    means,
    cell_w,
    own_cell,
    *,
    bb: int = DEFAULT_BB,
    bk: int = DEFAULT_BK,
    interpret: bool | None = None,
):
    """s_b = Σ_r cell_w[r] · [own_cell[b] ≠ r] · q(θ_b, μ_r). Differentiable
    in ``theta_i`` only (custom VJP); fused over (B, K) tiles of (bb, bk)."""
    if interpret is None:
        interpret = registry.interpret_default()
    return _build_op(bb, bk, interpret)(theta_i, means, cell_w, own_cell)


# ---------------------------------------------------------------------------
# Registry spec
# ---------------------------------------------------------------------------


def _pallas_adapter(theta_i, means, cell_w, own_cell, *, tiles, interpret):
    return cauchy_weighted_sum(
        theta_i,
        means,
        cell_w,
        own_cell,
        bb=tiles.get("bb", DEFAULT_BB),
        bk=tiles.get("bk", DEFAULT_BK),
        interpret=interpret,
    )


def _make_inputs(key, sig):
    (ts, tdt), (ms, mdt), (ws, wdt), (os_, odt) = sig
    K = ms[0]
    k1, k2, k3, k4 = jax.random.split(key, 4)
    theta = jax.random.normal(k1, ts, tdt) * 3.0
    means = jax.random.normal(k2, ms, mdt) * 3.0
    w = jax.random.uniform(k3, ws, wdt)
    own = jax.random.randint(k4, os_, 0, K, odt)
    return theta, means, w, own


def _sig(B, K, d, dt="float32"):
    return (((B, d), dt), ((K, d), dt), ((K,), dt), ((B,), "int32"))


def _cost_model(sig):
    (B, d) = sig[0][0]
    K = sig[1][0][0]
    flops = float(B) * K * (3 * d + 4)  # dist² + Cauchy + weighted sum
    bytes_ = 4.0 * (B * d + K * d + K + 2 * B)
    return {"flops": flops, "bytes": bytes_}


SPEC = registry.register(
    registry.KernelSpec(
        name="cauchy_mean",
        ref=cauchy_weighted_sum_ref,
        pallas=_pallas_adapter,
        tile_candidates=(
            {"bb": 256, "bk": 512},
            {"bb": 512, "bk": 1024},
            {"bb": 512, "bk": 2048},
            {"bb": 1024, "bk": 1024},
        ),
        default_tiles={
            "": {"bb": DEFAULT_BB, "bk": DEFAULT_BK},
            "tpu": {"bb": DEFAULT_BB, "bk": DEFAULT_BK},
        },
        make_inputs=_make_inputs,
        check_shapes=(
            _sig(512, 1024, 2),
            _sig(100, 64, 2),
            _sig(64, 100, 3),
            _sig(777, 333, 2),
        ),
        bench_shapes=_sig(2048, 2048, 2),
        tol=(1e-5, 1e-6),
        cost_model=_cost_model,
    )
)
