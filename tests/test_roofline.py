"""Roofline infrastructure tests: the HLO cost parser against XLA's own
numbers (loop-free), against analytic FLOPs (looped), against a handwritten
HLO fixture (collectives + trip counts), and the term computation."""

import jax
import jax.numpy as jnp
import numpy as np

import pytest

from repro.roofline.analysis import PEAKS, model_flops, peaks_for, roofline_terms
from repro.roofline.hlo_cost import CostReport, analyze_hlo


def test_parser_matches_xla_loop_free():
    D = 256
    f = jax.jit(lambda a, b, c: jax.nn.relu(a @ b) @ c)
    sds = jax.ShapeDtypeStruct((D, D), jnp.float32)
    comp = f.lower(sds, sds, sds).compile()
    rep = analyze_hlo(comp.as_text())
    ca = comp.cost_analysis()
    assert abs(rep.flops - ca["flops"]) / ca["flops"] < 0.02
    assert abs(rep.bytes - ca["bytes accessed"]) / ca["bytes accessed"] < 0.1
    assert abs(rep.dot_flops - 2 * 2 * D**3) / (4 * D**3) < 0.01


def test_parser_multiplies_scan_trip_count():
    """THE reason this parser exists: XLA counts while bodies once."""
    D, L = 128, 12
    sds = jax.ShapeDtypeStruct((D, D), jnp.float32)

    def g(x, ws):
        return jax.lax.scan(lambda x, w: (jnp.tanh(x @ w), None), x, ws)[0]

    comp = jax.jit(g).lower(sds, jax.ShapeDtypeStruct((L, D, D), jnp.float32)).compile()
    rep = analyze_hlo(comp.as_text())
    want = L * 2 * D**3
    assert abs(rep.dot_flops - want) / want < 0.02
    xla = comp.cost_analysis()["flops"]
    assert xla < rep.flops / 3  # demonstrates XLA's undercount


FIXTURE = """
HloModule fixture

%body (p: (s32[], f32[64,64])) -> (s32[], f32[64,64]) {
  %p = (s32[], f32[64,64]) parameter(0)
  %i = s32[] get-tuple-element(%p), index=0
  %x = f32[64,64]{1,0} get-tuple-element(%p), index=1
  %d = f32[64,64]{1,0} dot(%x, %x), lhs_contracting_dims={1}, rhs_contracting_dims={0}
  %ar = f32[64,64]{1,0} all-reduce(%d), replica_groups={}, to_apply=%sum
  %one = s32[] constant(1)
  %i2 = s32[] add(%i, %one)
  ROOT %t = (s32[], f32[64,64]) tuple(%i2, %ar)
}

%cond (p2: (s32[], f32[64,64])) -> pred[] {
  %p2 = (s32[], f32[64,64]) parameter(0)
  %i3 = s32[] get-tuple-element(%p2), index=0
  %n = s32[] constant(5)
  ROOT %lt = pred[] compare(%i3, %n), direction=LT
}

%sum (a: f32[], b: f32[]) -> f32[] {
  %a = f32[] parameter(0)
  %b = f32[] parameter(1)
  ROOT %s = f32[] add(%a, %b)
}

ENTRY %main (x0: f32[64,64]) -> (s32[], f32[64,64]) {
  %x0 = f32[64,64]{1,0} parameter(0)
  %zero = s32[] constant(0)
  %init = (s32[], f32[64,64]) tuple(%zero, %x0)
  %ag = f32[128,64]{1,0} all-gather(%x0), replica_groups={}, dimensions={0}
  ROOT %w = (s32[], f32[64,64]) while(%init), condition=%cond, body=%body, backend_config={"known_trip_count":{"n":"5"}}
}
"""


def test_fixture_trip_counts_and_collectives():
    rep = analyze_hlo(FIXTURE)
    # dot: 2*64^3 per trip × 5 trips
    assert abs(rep.dot_flops - 5 * 2 * 64**3) < 1e-3
    # all-reduce inside the loop: input bytes 64*64*4 × 5; all-gather: output
    # bytes 128*64*4 once
    want_ar = 5 * 64 * 64 * 4
    want_ag = 128 * 64 * 4
    assert abs(rep.coll_by_type["all-reduce"] - want_ar) < 1e-3
    assert abs(rep.coll_by_type["all-gather"] - want_ag) < 1e-3
    assert rep.unknown_trip_whiles == 0


def test_roofline_terms_and_dominance():
    rep = CostReport(flops=197e12 * 0.01, bytes=819e9 * 0.05, collective_bytes=50e9 * 0.002)
    t = roofline_terms(
        rep, n_chips=256, model_fl=197e12 * 0.01 * 256 * 0.5, hw=peaks_for("TPU v5 lite")
    )
    assert abs(t.compute_s - 0.01) < 1e-9
    assert abs(t.memory_s - 0.05) < 1e-9
    assert abs(t.collective_s - 0.002) < 1e-9
    assert t.dominant == "memory"
    assert abs(t.useful_ratio - 0.5) < 1e-9
    # roofline fraction: useful-compute time / bound = (0.5·0.01)/0.05
    assert abs(t.roofline_fraction - 0.1) < 1e-9


def test_peak_table_is_keyed_by_device_kind():
    v5e = peaks_for("TPU v5 lite")
    assert v5e is PEAKS["TPU v5 lite"]
    assert (v5e["peak_flops"], v5e["hbm_bw"]) == (197e12, 819e9)
    assert "TPU v5e" in v5e["source"]
    with pytest.raises(ValueError, match="no published peaks"):
        peaks_for("cpu")


def test_model_flops_sanity():
    from repro.configs import ARCHS, SHAPES

    cfg = ARCHS["phi4-mini-3.8b"]
    mf_train = model_flops(cfg, SHAPES["train_4k"])
    # ≈ 6 · N_active · tokens; phi4 ≈ 3.8B params, 1M tokens → ~2.6e16
    assert 1e16 < mf_train < 6e16, mf_train
    mf_dec = model_flops(cfg, SHAPES["decode_32k"])
    assert mf_dec < mf_train / 1000
    # MoE: active ≪ total
    moe = ARCHS["mixtral-8x7b"].param_counts()
    assert moe["active"] < 0.4 * moe["total"]
    # jamba: 398B-class total
    jam = ARCHS["jamba-1.5-large-398b"].param_counts()
    assert 3.0e11 < jam["total"] < 5.5e11, jam
