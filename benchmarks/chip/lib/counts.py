"""Operations and bytes the benchmark's kernels and steps need, from shapes.

Each count is written from the equations of the work and the shapes of
its operands, not from an implementation's tiling: a kernel that re-reads
an operand or recomputes a term does more than is counted here, and its
roofline share says so. FLOPs count additions, multiplications, divisions
and transcendentals as one each; bytes are float32/int32 operands read
from and results written to HBM once.

NOMAD step (paper Eq. 3-5), per head b with d output dimensions, k
positives, S exact in-cell negatives and K cell means:

    d2   = sum_d (theta_b - y)^2          d subs, d squares, d-1 adds
    q    = 1 / (1 + d2)                   2
    mean pair:  m_b += cw_r [r != own] q  3 (mask, weight, accumulate)
    tail:       the attraction/negative terms of the loss, 12 per tail
                (q, log(q + m), log1p(d2), weight, accumulate)

The backward needs, per mean pair, q^2 and the scaled difference added
into the head's gradient (2d + 3), and per tail the gradient of the tail
term and its difference written into both the head's and the tail's
gradient (4d + 8).
"""

from __future__ import annotations

F32 = 4.0


def nomad_step_fwd(B: int, k: int, S: int, K: int, d: int) -> dict:
    """One forward call of the fused step loss over B heads."""
    flops = float(B) * (K * (3 * d + 4) + (k + S) * (3 * d + 12))
    bytes_ = F32 * (
        B * d  # heads
        + B * k * d + B * k  # positives and their weights
        + B * S * d + B * S  # exact negatives and their weights
        + K * d + K  # means and cell weights
        + B  # own cell ids
        + 2 * B  # loss and repulsive mass out
    )
    return {"flops": flops, "bytes": bytes_}


def nomad_step_bwd(B: int, k: int, S: int, K: int, d: int) -> dict:
    """One backward call: gradients to heads, positives and negatives."""
    flops = float(B) * (K * (2 * d + 3) + (k + S) * (4 * d + 8))
    bytes_ = F32 * (
        B * d + B * k * d + B * k + B * S * d + B * S + K * d + K + B
        + 2 * B  # repulsive mass residual and the incoming cotangent
        + B * d + B * k * d + B * S * d  # the three gradients out
    )
    return {"flops": flops, "bytes": bytes_}


def fit_step_flops(B: int, k: int, S: int, K: int, d: int) -> float:
    """FLOPs one fit step needs: the loss and its gradient. Sampling,
    gathers and the scatter-add move bytes, not FLOPs; the means refresh
    is counted per dispatch by :func:`means_refresh_flops`."""
    return nomad_step_fwd(B, k, S, K, d)["flops"] + nomad_step_bwd(B, k, S, K, d)["flops"]


def means_refresh_flops(rows: int, d: int, K: int) -> float:
    """Masked cell means over the padded layout: one add per row and
    dimension, one division per cell and dimension."""
    return float(rows * d + K * d)


def pairwise(n: int, m: int, d: int) -> dict:
    """Squared distances of n rows against m rows, expanded as
    |x|^2 + |y|^2 - 2 x.y (2d per pair for the dot product, 4 more for the
    norms, the combination and the clamp at 0)."""
    flops = 2.0 * n * m * d + 4.0 * n * m
    bytes_ = F32 * (n * d + m * d + n * m)
    return {"flops": flops, "bytes": bytes_}


def kmeans_assign(n: int, K: int, d: int) -> dict:
    """Nearest of K centroids for n rows: 2d per pair for the distance,
    2 for the running minimum and its index; rows and centroids in, index
    and distance out."""
    flops = 2.0 * n * K * d + 2.0 * n * K
    bytes_ = F32 * (n * d + K * d + 2 * n)
    return {"flops": flops, "bytes": bytes_}
