"""The fit's stages read from a trace (``lib/scopes.py`` and its readers).

Two traces recorded on a TPU v5e, each of a short window of the fit at
B = 1,024, k = 15, S = 16, d = 2: ``fit_tiny`` of the program before it
named its stages (every op is unscoped there), and ``fit_tiny_scoped`` of
the program with its ``nomad_*`` scopes and ``nomad.fit.*`` host spans
(``run.py``'s harness on ``fit.wiki60m`` cut to 20,000 points in 8 cells,
4 steps a dispatch, ``--trace 1``).
"""

import glob
import os

import pytest

from conftest import BENCH, HERE
from lib import scopes, trace

DATA = os.path.join(HERE, "data")
STAGES = ("fit.sample_share", "fit.gather_share", "fit.loss_share", "fit.scatter_share", "fit.means_share")
BLOCKS = ("f32[30,1024]", "f32[32,1024]")


def _xplane(name):
    (path,) = glob.glob(os.path.join(DATA, name, "**", "*.xplane.pb"), recursive=True)
    return path


def _ctx(name, monkeypatch):
    """What the harness hands a reader after a traced run whose trace is
    the fixture ``name``."""
    monkeypatch.setattr(scopes, "TRACE_ROOT", os.path.join(DATA, name))
    red = trace.reduce_dir(os.path.join(DATA, name), window_span="bench.window")
    return {"trace": red, "nomad_step_blocks": BLOCKS}


def _read(run_mod, metric, ctx):
    mod = run_mod.load_module(os.path.join(BENCH, "metrics", metric + ".py"), "m_" + metric.replace(".", "_"))
    return mod.read(ctx)


def _control_share(ctx):
    red = ctx["trace"]
    return 100.0 * red.self_time(lambda t: trace.kind(t) in trace.CONTROL) / red.busy_s


def test_wire_reader_on_hand_made_bytes():
    # field 1 varint 300, field 2 bytes "ab", field 3 fixed64, field 4 fixed32, field 5 varint 1
    buf = bytes([0x08, 0xAC, 0x02, 0x12, 2]) + b"ab" + bytes([0x19]) + bytes(8) + bytes([0x25]) + bytes(4) + bytes([0x28, 1])
    got = list(scopes._fields(buf))
    assert got == [(1, 300), (2, (5, 7)), (5, 1)]
    assert scopes._str(buf, got[1][1]) == "ab"
    assert scopes._ints(bytes([0x96, 0x01, 0x05]), (0, 3)) == [150, 5]


def test_scope_of_takes_whole_components():
    assert scopes.scope_of("jit(epoch)/while/body/closed_call/nomad_scatter/mul") == "nomad_scatter"
    assert scopes.scope_of("jit(epoch)/while/body/nomad_loss/transpose(nomad_loss)/jvp(nomad_step_bwd)") == "nomad_loss"
    assert scopes.scope_of("jit(epoch)/nomad_means/cond/nomad_sample/x") == "nomad_sample"
    assert scopes.scope_of("jit(epoch)/while/body/transpose(nomad_loss)") is None
    assert scopes.scope_of("") is None


def test_interval_overlap_and_module_lookup():
    assert scopes._overlap([(0, 10), (20, 30)], [(5, 25)]) == 10
    assert scopes._overlap([(0, 10)], [(10, 20)]) == 0
    events = [(0, 10, "a"), (10, 15, "b"), (20, 30, "c")]
    starts = [s for s, _, _ in events]
    assert [scopes._module_at(events, starts, t) for t in (0, 12, 17, 29, 30, -1)] == ["a", "b", None, "c", None, None]


def _wire(*fields) -> bytes:
    """A protobuf message of ``(field number, value)``: an int is a varint,
    ``str``/``bytes`` length-delimited, a list of ints packed."""

    def varint(x):
        out = b""
        while True:
            out += bytes([(x & 0x7F) | (0x80 if x > 0x7F else 0)])
            x >>= 7
            if not x:
                return out

    out = b""
    for num, v in fields:
        if isinstance(v, int):
            out += varint(num << 3) + varint(v)
        else:
            v = b"".join(varint(x) for x in v) if isinstance(v, list) else v
            v = v.encode() if isinstance(v, str) else v
            out += varint(num << 3 | 2) + varint(len(v)) + v
    return out


def _instr(iid, name, opcode, op_name="", operands=(), called=()):
    fields = [(1, name), (2, opcode), (35, iid)]
    if op_name:
        fields.append((7, _wire((2, op_name))))
    if operands:
        fields.append((36, list(operands)))
    if called:
        fields.append((38, list(called)))
    return _wire(*fields)


def test_an_op_with_no_op_name_takes_its_users_or_callers_scope():
    """XLA's own loops and copies carry no ``op_name``: a loop whose result
    only a ``nomad_means`` op reads is that stage's, and so is its body; a
    copy whose users disagree, or that nothing uses, is in no scope."""
    body = _wire((5, 10), (2, _instr(1, "dynamic-update-slice.1", "dynamic-update-slice")))
    entry = _wire(
        (5, 20),
        (2, _instr(1, "while.1", "while", called=[10])),
        (2, _instr(2, "fusion.1", "fusion", "jit(epoch)/nomad_means/reduce_sum", operands=[1])),
        (2, _instr(3, "copy.1", "copy")),
        (2, _instr(4, "fusion.2", "fusion", "jit(epoch)/while/body/nomad_scatter/scatter-add", operands=[3])),
        (2, _instr(5, "while.2", "while", "jit(epoch)/while", operands=[3])),
        (2, _instr(6, "copy.2", "copy")),
    )
    buf = _wire((1, _wire((3, body), (3, entry))))
    mod = scopes.parse_module(buf, (0, len(buf)))
    assert mod.scope == {
        "dynamic-update-slice.1": "nomad_means",
        "while.1": "nomad_means",
        "fusion.1": "nomad_means",
        "copy.1": None,
        "fusion.2": "nomad_scatter",
        "while.2": None,
        "copy.2": None,
    }
    assert mod.op_name["while.1"] == "" and "copy.2" in mod.op_name


def test_unscoped_fixture_joins_the_epoch():
    st = scopes.stages_of(_xplane("fit_tiny"))
    assert any(m.startswith("jit_epoch(") for m in st.modules)
    non_control = st.busy_s - st.control_s
    assert st.joined_s >= 0.999 * non_control
    assert not st.scoped and st.program_idle_s is None


def test_unscoped_fixture_reads_all_as_unscoped(run_mod, monkeypatch):
    ctx = _ctx("fit_tiny", monkeypatch)
    assert _read(run_mod, "fit.unscoped_share", ctx) + _control_share(ctx) == pytest.approx(100.0, abs=1e-6)
    # the program had no scopes and no spans: those readers are silent
    for metric in STAGES + ("device_idle.fit.program",):
        assert _read(run_mod, metric, ctx) is None, metric


def test_scoped_fixture_stages_sum_to_the_busy_time(run_mod, monkeypatch):
    ctx = _ctx("fit_tiny_scoped", monkeypatch)
    shares = {m: _read(run_mod, m, ctx) for m in STAGES + ("fit.unscoped_share",)}
    assert sum(shares.values()) + _control_share(ctx) == pytest.approx(100.0, abs=1e-6)
    for metric in STAGES:
        assert shares[metric] > 0, metric
    st = scopes.stages(ctx)
    assert st.joined_s >= 0.999 * (st.busy_s - st.control_s)


def test_scoped_fixture_agrees_with_the_complement(run_mod, monkeypatch):
    """The data path (every op but the kernel and control flow) holds all
    stages but the loss, and the loss holds the kernel."""
    ctx = _ctx("fit_tiny_scoped", monkeypatch)
    complement = _read(run_mod, "fit.gather_scatter_share", ctx)
    rest = sum(
        _read(run_mod, m, ctx)
        for m in ("fit.sample_share", "fit.gather_share", "fit.scatter_share", "fit.means_share", "fit.unscoped_share")
    )
    assert rest <= complement + 1e-9
    assert rest + _read(run_mod, "fit.loss_share", ctx) >= complement - 1e-9


def test_scoped_fixture_program_idle_is_part_of_the_idle(run_mod, monkeypatch):
    ctx = _ctx("fit_tiny_scoped", monkeypatch)
    program = _read(run_mod, "device_idle.fit.program", ctx)
    assert program is not None and 0 <= program <= _read(run_mod, "device_idle.fit", ctx)


def test_a_file_is_parsed_once(monkeypatch):
    ctx = _ctx("fit_tiny_scoped", monkeypatch)
    assert scopes.stages(ctx) is scopes.stages(ctx)


def test_another_window_or_no_trace_reads_nothing(run_mod, monkeypatch, tmp_path):
    ctx = _ctx("fit_tiny_scoped", monkeypatch)
    red = ctx["trace"]
    stale = dict(ctx, trace=trace.Reduced(red.window_s + 2e-6, red.busy_s, red.op_s, red.gaps, red.n_devices, red.ops))
    monkeypatch.setattr(scopes, "TRACE_ROOT", str(tmp_path))
    for metric in STAGES + ("fit.unscoped_share", "device_idle.fit.program"):
        assert _read(run_mod, metric, ctx) is None, metric
    monkeypatch.setattr(scopes, "TRACE_ROOT", os.path.join(DATA, "fit_tiny_scoped"))
    for metric in STAGES + ("fit.unscoped_share", "device_idle.fit.program"):
        assert _read(run_mod, metric, stale) is None, metric
