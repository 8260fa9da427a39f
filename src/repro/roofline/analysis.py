"""Roofline-term computation (assignment §Roofline).

Hardware peaks come from :data:`PEAKS`, keyed by the ``device_kind`` JAX
reports; every caller names the device it means, and a device that is not
in the table is an error, never a silent default.

The HLO module analysed is the per-partition program, so the parser's
numbers are *per device*; the three terms are per-device times directly:

  compute    = flops_dev / peak            (≡ HLO_FLOPs·chips / (chips·peak))
  memory     = bytes_dev / hbm_bw
  collective = coll_bytes_dev / link_bw

MODEL_FLOPS (the "useful work" yardstick) is 6·N·D for training and 2·N·D
for single forward passes (N = active params, D = tokens), plus the
attention KV term for decode. ``MODEL_FLOPS / (HLO_FLOPs·chips)`` exposes
remat/padding/capacity waste.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

from repro.configs.base import ArchConfig, ShapeConfig
from repro.roofline.hlo_cost import CostReport

# Published per-chip peaks, keyed by ``jax.devices()[0].device_kind``.
PEAKS = {
    "TPU v5 lite": {
        "peak_flops": 197e12,  # bf16 FLOP/s per chip
        "hbm_bw": 819e9,  # B/s per chip
        "ici_bw": 50e9,  # B/s per link (1,600 Gbit/s per chip over 4 links)
        "name": "tpu-v5e",
        "source": 'Google Cloud documentation, "TPU v5e"',
    },
}


def peaks_for(device_kind: str) -> dict:
    """The :data:`PEAKS` row of ``device_kind``; raises for an unknown one."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(
            f"no published peaks for device_kind {device_kind!r} "
            f"(known: {sorted(PEAKS)}); add a sourced row to PEAKS"
        ) from None


@dataclasses.dataclass
class RooflineTerms:
    compute_s: float
    memory_s: float
    collective_s: float
    model_flops: float
    hlo_flops_global: float
    useful_ratio: float

    @property
    def dominant(self) -> str:
        terms = {
            "compute": self.compute_s,
            "memory": self.memory_s,
            "collective": self.collective_s,
        }
        return max(terms, key=terms.get)

    @property
    def bound_s(self) -> float:
        """Roofline lower-bound step time (terms overlap perfectly)."""
        return max(self.compute_s, self.memory_s, self.collective_s)

    @property
    def roofline_fraction(self) -> float:
        """Useful-compute time / bound time: 1.0 = perfectly compute-bound
        with zero waste. The score §Perf pushes up."""
        if self.bound_s <= 0:
            return 0.0
        ideal = self.model_flops / self.hlo_flops_global * self.compute_s if self.hlo_flops_global else 0.0
        return ideal / self.bound_s


def roofline_terms(
    report: CostReport,
    n_chips: int,
    model_fl: float,
    hw: dict,
) -> RooflineTerms:
    compute_s = report.flops / hw["peak_flops"]
    memory_s = report.bytes / hw["hbm_bw"]
    collective_s = report.collective_bytes / hw["ici_bw"]
    hlo_global = report.flops * n_chips
    return RooflineTerms(
        compute_s=compute_s,
        memory_s=memory_s,
        collective_s=collective_s,
        model_flops=model_fl,
        hlo_flops_global=hlo_global,
        useful_ratio=(model_fl / hlo_global) if hlo_global else 0.0,
    )


def kernel_roofline(flops: float, bytes_: float, us: float, hw: dict) -> dict:
    """Achieved-vs-roofline summary for ONE kernel call.

    Takes an analytic cost (``spec.cost_model(sig)``) and a measured wall
    time (µs) and returns achieved GFLOP/s and GB/s, their fractions of
    the hardware peaks, the roofline-bound wall time at those peaks, the
    achieved fraction of that bound, and which resource bounds the kernel.
    Feeds the roofline columns of ``benchmarks/kernel_micro.py`` and the
    autotuner's per-candidate ``--report``.
    """
    s = us * 1e-6
    compute_s = flops / hw["peak_flops"]
    memory_s = bytes_ / hw["hbm_bw"]
    roofline_s = max(compute_s, memory_s)
    return {
        "gflops": flops / s / 1e9 if s > 0 else 0.0,
        "gbs": bytes_ / s / 1e9 if s > 0 else 0.0,
        "frac_peak_flops": (flops / s) / hw["peak_flops"] if s > 0 else 0.0,
        "frac_peak_bw": (bytes_ / s) / hw["hbm_bw"] if s > 0 else 0.0,
        "roofline_us": roofline_s * 1e6,
        "roofline_frac": roofline_s / s if s > 0 else 0.0,
        "bound": "compute" if compute_s >= memory_s else "memory",
    }


def model_flops(cfg: ArchConfig, shape: ShapeConfig) -> float:
    """Analytic useful FLOPs for one step of this cell."""
    counts = cfg.param_counts()
    n_active = counts["active"]
    B, S = shape.global_batch, shape.seq_len
    if shape.kind == "train":
        tokens = B * S
        flops = 6.0 * n_active * tokens
        flops += 2.0 * _attention_flops(cfg, B, S) * 3  # fwd + 2×bwd
        return flops
    if shape.kind == "prefill":
        tokens = B * S
        return 2.0 * n_active * tokens + 2.0 * _attention_flops(cfg, B, S)
    # decode: one token per sequence + full-cache attention reads
    flops = 2.0 * n_active * B
    Sc = min(S, cfg.sliding_window) if cfg.sliding_window else S
    if cfg.n_heads:
        n_attn_layers = sum(
            1 for l in range(cfg.n_layers) if cfg.layer_is_attention(l)
        )
        flops += 4.0 * B * cfg.n_heads * cfg.head_dim * Sc * n_attn_layers
    if cfg.ssm_state:
        n_ssm = cfg.n_layers - sum(
            1 for l in range(cfg.n_layers) if cfg.layer_is_attention(l)
        )
        flops += 6.0 * B * cfg.ssm_heads * cfg.ssm_head_dim * cfg.ssm_state * n_ssm
    return flops


def _attention_flops(cfg: ArchConfig, B: int, S: int) -> float:
    """Score+PV matmul FLOPs for one forward pass (causal halving applied)."""
    if not cfg.n_heads:
        return 0.0
    n_attn = sum(1 for l in range(cfg.n_layers) if cfg.layer_is_attention(l))
    eff = min(S, cfg.sliding_window) if cfg.sliding_window else S
    per_query = eff if cfg.sliding_window else S / 2  # causal triangle
    return 4.0 * B * S * per_query * cfg.n_heads * cfg.head_dim * n_attn


def nomad_model_flops(n_points, batch, k_nn, n_exact, n_clusters, steps) -> float:
    """Useful FLOPs of one NOMAD epoch: Cauchy affinities of positives,
    exact negatives, and the B×K mean term, fwd+bwd (×3)."""
    per_step = batch * (k_nn + n_exact + n_clusters) * 8.0  # ~8 flops/affinity
    return 3.0 * per_step * steps


def nomad_analytic_terms(cfg, n_chips: int, steps: int, hw: dict) -> dict:
    """Kernel-true per-device roofline terms for one NOMAD epoch.

    The HLO-parsed memory term is inflated on CPU: the Pallas cauchy_mean
    kernel runs in interpret mode, so its (bb × bk) tiles appear as HLO
    fusion boundaries and get billed as HBM traffic; the Mosaic kernel
    keeps them in VMEM. This computes what the TPU actually streams:
    per step, the gathered/scattered θ rows (heads + kNN tails + exact
    negatives, read+write) plus the kernel's true I/O (θ_i, μ, w in; s,
    dθ out), plus one full pass over local θ for the means of a dispatch.
    """
    d = cfg.out_dim
    B = cfg.batch_size  # per shard
    touched = B * (1 + cfg.n_neighbors + cfg.n_exact_negatives)
    per_step = (
        2 * touched * d * 4  # gather + scatter of positions
        + touched * 4 * 2  # index reads
        + 2 * B * d * 4  # kernel θ_i in, dθ out
        + 2 * cfg.n_clusters * (d + 1) * 4  # μ, w (+ recompute in bwd)
        + 2 * B * 4  # s out / ḡ in
    )
    rows_local = (cfg.n_clusters // n_chips) * cfg.cluster_capacity
    mem_bytes = per_step * steps + rows_local * d * 4
    # the paper's point: the only wire traffic is the means exchange
    coll_bytes = cfg.n_clusters * (d + 1) * 4
    flops = 3.0 * B * (cfg.n_neighbors + cfg.n_exact_negatives + cfg.n_clusters) * 8.0 * steps
    return {
        "compute_s": flops / hw["peak_flops"],
        "memory_s": mem_bytes / hw["hbm_bw"],
        "collective_s": coll_bytes / hw["ici_bw"],
    }
