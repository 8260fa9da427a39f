"""The reduction from a profiler trace to busy time, self time and gaps."""

import glob
import os

import pytest

from conftest import HERE
from lib import trace
from lib.trace import Op

# the fit windows recorded on the chip (``data/build_tiny`` is the build's,
# read in test_chip_build_check.py)
RECORDED = sorted(glob.glob(os.path.join(HERE, "data", "fit_*", "**", "*.xplane.pb"), recursive=True))

WHILE = "%while.3 = (s32[], f32[800,2]{0,1:T(2,128)}) while((s32[], f32[800,2]) %tuple.1), condition=%c, body=%b"
GATHER = "%fusion.7 = f32[96,2]{0,1:T(2,128)} fusion(f32[800,2]{0,1:T(2,128)} %p, s32[96]{0} %i), kind=kCustom"
SCATTER = "%fusion.9 = f32[800,2]{0,1:T(2,128)} fusion(f32[800,2]{0,1} %t, s32[96]{0} %i, f32[96,2]{0,1} %u), kind=kCustom"
KERNEL = ('%jvp__.4 = (f32[1,64]{1,0}, f32[1,64]{1,0}) custom-call(f32[2,64]{1,0} %a, f32[30,64]{1,0} %b, '
          'f32[32,64]{1,0} %c), custom_call_target="tpu_custom_call"')
COPY = "%copy.2 = f32[96,2]{1,0} copy(f32[96,2]{0,1} %x)"


def _synthetic():
    # window [100, 1100) ns. A while loop [150, 700) nests a gather
    # [200, 300), a scatter [300, 480) and the kernel [500, 650); a copy
    # [50, 120) starts before the window; a copy [1000, 1200) runs past it.
    ops = {0: [
        Op(COPY, 50, 120),
        Op(WHILE, 150, 700),
        Op(GATHER, 200, 300),
        Op(SCATTER, 300, 480),
        Op(KERNEL, 500, 650),
        Op(COPY, 1000, 1200),
    ]}
    spans = [
        ("bench.window", 100, 1100),
        ("bench.fit.run_epoch", 110, 760),
        ("bench.fit.run_epoch", 800, 1100),
        ("bench.host_sync", 740, 790),
    ]
    return ops, spans


def test_busy_is_the_union_inside_the_window():
    red = trace.reduce(*_synthetic(), window_span="bench.window")
    assert red.window_s == pytest.approx(1000e-9)
    # [100, 120) + [150, 700) + [1000, 1100)
    assert red.busy_s == pytest.approx(670e-9)
    assert red.idle_share == pytest.approx(0.33)


def test_self_time_takes_out_nested_ops():
    red = trace.reduce(*_synthetic(), window_span="bench.window")
    assert red.op_s[WHILE] == pytest.approx((550 - 100 - 180 - 150) * 1e-9)
    assert red.op_s[COPY] == pytest.approx((20 + 100) * 1e-9)
    assert sum(red.op_s.values()) == pytest.approx(red.busy_s)
    assert red.self_time(lambda t: "tpu_custom_call" in t) == pytest.approx(150e-9)


def test_labels_kinds_and_shapes():
    assert trace.label(SCATTER) == "fusion.9 f32[800,2] fusion"
    assert trace.label(KERNEL) == "jvp__.4 (tuple) custom-call tpu_custom_call"
    assert trace.kind(WHILE) == "while"
    assert trace.is_kernel(KERNEL, ("f32[30,64]", "f32[32,64]"))
    assert not trace.is_kernel(KERNEL, ("f32[30,128]", "f32[32,128]"))
    assert not trace.is_kernel(SCATTER, ())
    red = trace.reduce(*_synthetic(), window_span="bench.window")
    assert red.top_ops(1) == [("fusion.9 f32[800,2] fusion", pytest.approx(180e-9))]


def test_gaps_are_named_by_the_innermost_span():
    red = trace.reduce(*_synthetic(), window_span="bench.window")
    # gaps [700, 1000) mid 850 and [120, 150) mid 135
    assert red.gaps == [
        ("bench.fit.run_epoch", pytest.approx(300e-9)),
        ("bench.fit.run_epoch", pytest.approx(30e-9)),
    ]


def test_no_window_or_no_device_op_is_an_error():
    ops, spans = _synthetic()
    with pytest.raises(ValueError):
        trace.reduce(ops, spans, window_span="bench.missing")
    with pytest.raises(ValueError):
        trace.reduce({0: []}, spans, window_span="bench.window")


def test_several_devices_average():
    ops, spans = _synthetic()
    ops[1] = [Op(COPY, 100, 1100)]
    red = trace.reduce(ops, spans, window_span="bench.window", n_devices=2)
    assert red.busy_s == pytest.approx((670e-9 + 1000e-9) / 2)


def test_fit_readers_on_the_synthetic_trace(run_mod):
    from conftest import BENCH

    red = trace.reduce(*_synthetic(), window_span="bench.window")
    blocks = ("f32[30,64]", "f32[32,64]")
    gs = run_mod.load_module(os.path.join(BENCH, "metrics", "fit.gather_scatter_share.py"), "gs")
    # busy 670 less the kernel 150 and the while's own 120: the gather, the
    # scatter and the copies
    assert gs.read({"trace": red, "nomad_step_blocks": blocks}) == pytest.approx(100 * 400 / 670)
    # a kernel of another interface is not found: its time is data path
    other = ("f32[30,128]", "f32[32,128]")
    assert gs.read({"trace": red, "nomad_step_blocks": other}) == pytest.approx(100 * 550 / 670)
    rf = run_mod.load_module(os.path.join(BENCH, "metrics", "nomad_step_roofline.py"), "rf")
    ctx = {"trace": red, "nomad_step_blocks": other, "kernel_calls": 1}
    assert rf.read(ctx) is None


@pytest.mark.parametrize("path", RECORDED, ids=os.path.basename)
def test_recorded_trace(path):
    """A trace recorded on a TPU v5e of a short fit window."""
    ops, spans = trace.read_xplane(path)
    assert ops and all(ops[d] for d in ops)
    assert all(n.startswith(trace.SPAN_PREFIX) for n, _, _ in spans)
    red = trace.reduce(ops, spans, window_span="bench.window")
    assert 0 < red.busy_s <= red.window_s
    # self times of nested ops add up to the busy time
    assert sum(red.op_s.values()) == pytest.approx(red.busy_s, rel=1e-6)
    assert red.self_time(lambda t: "tpu_custom_call" in t) > 0
    assert any(trace.kind(op.name) == "while" for op in red.ops)
    assert red.gaps and all(g[1] > 0 for g in red.gaps)
    # its kernel (B 1,024, k 15, S 16, d 2) is found, and the data path is
    # most of the rest
    from conftest import BENCH
    from run import load_module

    blocks = ("f32[30,1024]", "f32[32,1024]")
    assert red.self_time(lambda t: trace.is_kernel(t, blocks)) > 0
    gs = load_module(os.path.join(BENCH, "metrics", "fit.gather_scatter_share.py"), "gs")
    assert 50 < gs.read({"trace": red, "nomad_step_blocks": blocks}) < 100
