"""The fit's stages in one profiler trace: device self time per
``nomad_*`` scope, and the device's idle time inside the program's own
host spans (``nomad.*``).

The program names its stages with ``jax.named_scope``; the name reaches
each compiled HLO instruction as a component of its ``op_name``
(``jit(epoch)/while/body/closed_call/nomad_scatter/mul``). The device's
``XLA Ops`` events carry only the instruction's text, so the op names come
from the ``/host:metadata`` plane of the same ``.xplane.pb``: one
``XEventMetadata`` per module run, named as the device's ``XLA Modules``
events name it (``jit_epoch(1484...)``), holding the module's optimised
``HloProto``. JAX ships no Python class for either proto, so a small
reader of the protobuf wire format takes out the few fields needed.

    join    an op of the window belongs to the module whose ``XLA
            Modules`` event on its device holds its start; its instruction
            is found by name in that module
    scope   the ``nomad_*`` component of the instruction's ``op_name``
            (innermost, if several); a fusion is its own instruction, so
            it takes the scope of its root, and the fusions whose fused
            instructions carry more than one scope are recorded (``mixed``).
            An instruction with no ``op_name`` (XLA writes none on the
            loops and copies it makes) takes the one scope that all its
            users carry, else the one scope of the instructions that call
            its computation, else none: the means refresh's relayout of θ
            runs in ``while`` loops with no ``op_name`` whose results only
            ``nomad_means`` ops read, and their bodies' ops take the loops'
            scope
    stage   self time (``lib/trace.py``) of the window's ops of one scope;
            ops of control flow (``lib.trace.CONTROL``) are in no stage,
            ops in no scope (or not found in their module) are
            ``unscoped``, so the stages, control flow and ``unscoped`` sum
            to the busy time

The program's host spans (``nomad.fit.dispatch``, ``nomad.fit.sync``)
split the device's idle time: idle inside them is the program's own
enqueue and sync, the rest is its caller's. ``lib/trace.py`` keeps only
the harness's ``bench.*`` spans, so they are read here.

A file is parsed once; every reader of a run shares the result.
"""

from __future__ import annotations

import bisect
import functools
import glob
import os
import re
from dataclasses import dataclass, field
from typing import Optional

from lib import trace

TRACE_ROOT = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), ".traces")
WINDOW_SPAN = "bench.window"
SCOPES = ("nomad_sample", "nomad_gather", "nomad_loss", "nomad_scatter", "nomad_means")
PROGRAM_SPAN_PREFIX = "nomad.fit."
METADATA_PLANE = "/host:metadata"
MODULES_LINE = "XLA Modules"
UNSCOPED = "unscoped"
_INSTR_NAME = re.compile(r"^%?([^\s=]+) = ")
_SKIP_IN_FUSION = ("parameter", "constant")


# ---- protobuf wire format --------------------------------------------------


def _varint(buf: bytes, i: int) -> tuple:
    x = shift = 0
    while True:
        c = buf[i]
        i += 1
        x |= (c & 0x7F) << shift
        shift += 7
        if c < 0x80:
            return x, i


def _fields(buf: bytes, lo: int = 0, hi: Optional[int] = None):
    """``(field number, value)`` of a message in ``buf[lo:hi]``: an int for
    a varint, ``(start, end)`` of the bytes for a length-delimited field."""
    hi = len(buf) if hi is None else hi
    i = lo
    while i < hi:
        key, i = _varint(buf, i)
        num, wire = key >> 3, key & 7
        if wire == 0:
            v, i = _varint(buf, i)
            yield num, v
        elif wire == 2:
            n, i = _varint(buf, i)
            yield num, (i, i + n)
            i += n
        elif wire in (1, 5):
            i += 8 if wire == 1 else 4
        else:
            raise ValueError(f"wire type {wire} at byte {i}")


def _str(buf: bytes, span: tuple) -> str:
    return buf[span[0]:span[1]].decode("utf-8", "replace")


def _ints(buf: bytes, value) -> list:
    """A repeated int64 field's entry: packed bytes or one varint."""
    if isinstance(value, int):
        return [value]
    out, i = [], value[0]
    while i < value[1]:
        x, i = _varint(buf, i)
        out.append(x)
    return out


# XSpace.planes 1; XPlane.name 2, .event_metadata 4 (map entry: value 2),
# .stat_metadata 5 (map entry: value 2); XEventMetadata.name 2, .stats 5;
# XStatMetadata.id 1, .name 2; XStat.metadata_id 1, .bytes_value 6.
# HloProto.hlo_module 1; HloModuleProto.computations 3;
# HloComputationProto.instructions 2, .id 5; HloInstructionProto.name 1,
# .opcode 2, .metadata 7, .id 35, .operand_ids 36, .called_computation_ids
# 38; OpMetadata.op_name 2.


def hlo_protos(buf: bytes) -> dict:
    """``{module event name: (start, end) of its HloProto}`` from the
    metadata plane of a serialised ``XSpace``."""
    out = {}
    for num, plane in _fields(buf):
        if num != 1:
            continue
        parts = list(_fields(buf, *plane))
        if not any(n == 2 and _str(buf, v) == METADATA_PLANE for n, v in parts):
            continue
        stat_names = {}
        for n, entry in parts:
            if n == 5:
                meta = dict(_fields(buf, *dict(_fields(buf, *entry))[2]))
                stat_names[meta.get(1, 0)] = _str(buf, meta[2]) if 2 in meta else ""
        for n, entry in parts:
            if n != 4:
                continue
            name, proto = None, None
            for m, v in _fields(buf, *dict(_fields(buf, *entry))[2]):
                if m == 2:
                    name = _str(buf, v)
                elif m == 5:
                    stat = dict(_fields(buf, *v))
                    if stat_names.get(stat.get(1, 0)) == "Hlo Proto" and 6 in stat:
                        proto = stat[6]
            if name and proto:
                out[name] = proto
    return out


def scope_of(op_name: str) -> Optional[str]:
    """The innermost ``nomad_*`` scope among the components of an op name."""
    hit = None
    for part in op_name.split("/"):
        if part in SCOPES:
            hit = part
    return hit


@dataclass
class Module:
    """The instructions of one compiled module."""

    op_name: dict  # instruction name -> op_name
    scope: dict  # instruction name -> its nomad_* scope, or None
    mixed: dict  # fusion name -> the scopes its fused instructions carry


def parse_module(buf: bytes, proto: tuple) -> Module:
    comps: dict = {}  # computation id -> [(id, name, opcode, op_name, operand ids, called)]
    for num, mod in _fields(buf, *proto):
        if num != 1:
            continue
        for n, comp in _fields(buf, *mod):
            if n != 3:
                continue
            cid, instrs = 0, []
            for m, v in _fields(buf, *comp):
                if m == 5:
                    cid = v
                elif m == 2:
                    iid, name, opcode, op_name = 0, "", "", ""
                    operands: list = []
                    called: list = []
                    for k, w in _fields(buf, *v):
                        if k == 1:
                            name = _str(buf, w)
                        elif k == 2:
                            opcode = _str(buf, w)
                        elif k == 7:
                            for kk, ww in _fields(buf, *w):
                                if kk == 2:
                                    op_name = _str(buf, ww)
                        elif k == 35:
                            iid = w
                        elif k == 36:
                            operands += _ints(buf, w)
                        elif k == 38:
                            called += _ints(buf, w)
                    instrs.append((iid, name, opcode, op_name, operands, called))
            comps[cid] = instrs
    op_name_of: dict = {}  # (computation id, instruction id) -> op_name
    users: dict = {}  # (computation id, instruction id) -> its users there
    callers: dict = {}  # computation id -> the instructions that call it
    for cid, instrs in comps.items():
        for iid, _, _, op_name, operands, called in instrs:
            op_name_of[(cid, iid)] = op_name
            for o in operands:
                users.setdefault((cid, o), []).append((cid, iid))
            for c in called:
                callers.setdefault(c, []).append((cid, iid))

    def agreed(keys: list) -> Optional[str]:
        found = {scope_at(k) for k in keys}
        return found.pop() if len(found) == 1 else None

    @functools.lru_cache(maxsize=None)
    def scope_at(key: tuple) -> Optional[str]:
        op_name = op_name_of[key]
        if op_name:
            return scope_of(op_name)
        return agreed(users.get(key, [])) or agreed(callers.get(key[0], []))

    op_names, scope, mixed = {}, {}, {}
    for cid, instrs in comps.items():
        for iid, name, opcode, op_name, _, called in instrs:
            op_names[name] = op_name
            scope[name] = scope_at((cid, iid))
            if opcode != "fusion":
                continue
            inner = {
                scope_of(o) or UNSCOPED
                for c in called
                for _, _, code, o, _, _ in comps.get(c, ())
                if code not in _SKIP_IN_FUSION and o
            }
            if len(inner) > 1:
                mixed[name] = tuple(sorted(inner))
    return Module(op_names, scope, mixed)


# ---- the trace ---------------------------------------------------------------


@dataclass
class Stages:
    """One window's device time by stage (seconds, mean over devices)."""

    window_s: float
    busy_s: float
    scope_s: dict  # scope -> self seconds; UNSCOPED for ops in no scope
    control_s: float
    joined_s: float  # self seconds of non-control ops found in their module
    program_idle_s: Optional[float]  # idle inside nomad.fit.* spans; None without them
    modules: set = field(default_factory=set)  # modules the window ran
    mixed: dict = field(default_factory=dict)  # (module, fusion) -> (scopes, self seconds)

    @property
    def scoped(self) -> bool:
        """Whether any op of the window carries a ``nomad_*`` scope."""
        return any(self.scope_s.get(s, 0.0) > 0 for s in SCOPES)


def _read(path: str):
    """Device ops and module events per device, and every host span."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    ops: dict = {}
    modules: dict = {}
    spans = []
    for plane in pd.planes:
        m = trace.DEVICE_PLANE.match(plane.name)
        if m:
            dev = int(m.group(1))
            for line in plane.lines:
                if line.name == trace.OPS_LINE:
                    ops.setdefault(dev, []).extend(
                        trace.Op(ev.name, int(ev.start_ns), int(ev.start_ns) + int(ev.duration_ns))
                        for ev in line.events
                    )
                elif line.name == MODULES_LINE:
                    modules.setdefault(dev, []).extend(
                        (int(ev.start_ns), int(ev.start_ns) + int(ev.duration_ns), ev.name)
                        for ev in line.events
                    )
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith((trace.SPAN_PREFIX, PROGRAM_SPAN_PREFIX)):
                        start = int(ev.start_ns)
                        spans.append((ev.name, start, start + int(ev.duration_ns)))
    return ops, modules, spans


def _module_at(events: list, starts: list, t: int) -> Optional[str]:
    """The module whose event (sorted, one after another) holds ``t``."""
    i = bisect.bisect_right(starts, t) - 1
    return events[i][2] if i >= 0 and t < events[i][1] else None


def _overlap(a: list, b: list) -> int:
    """Total length of the intersection of two sets of merged intervals."""
    total, j = 0, 0
    for s, e in a:
        while j < len(b) and b[j][1] <= s:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            total += max(0, min(e, b[k][1]) - max(s, b[k][0]))
            k += 1
    return total


def stages_of(path: str, n_devices: int = 1) -> Stages:
    """Reduce the window of the trace at ``path`` by stage."""
    ops, module_events, spans = _read(path)
    with open(path, "rb") as f:
        buf = f.read()
    protos = hlo_protos(buf)
    parsed: dict = {}

    def module(name: Optional[str]) -> Optional[Module]:
        if name not in protos:
            return None
        if name not in parsed:
            parsed[name] = parse_module(buf, protos[name])
        return parsed[name]

    bench = [s for s in spans if s[0].startswith(trace.SPAN_PREFIX)]
    program = [(s, e) for n, s, e in spans if n.startswith(PROGRAM_SPAN_PREFIX)]
    devices = sorted(ops)[:n_devices]
    out = Stages(window_s=0.0, busy_s=0.0, scope_s={}, control_s=0.0, joined_s=0.0, program_idle_s=None)
    idle = 0
    for d in devices:
        red = trace.reduce({d: ops[d]}, bench, window_span=WINDOW_SPAN)
        lo, hi = next((s, e) for n, s, e in bench if n == WINDOW_SPAN)
        out.window_s = red.window_s
        out.busy_s += red.busy_s / len(devices)
        events = sorted(module_events.get(d, []))
        starts = [s for s, _, _ in events]
        for op in red.ops:
            sec = op.self_ns * 1e-9 / len(devices)
            if trace.kind(op.name) in trace.CONTROL:
                out.control_s += sec
                continue
            # an op that began before the window is clipped to its start,
            # which the module that ran the op holds too
            name = _module_at(events, starts, op.start)
            mod = module(name)
            m = _INSTR_NAME.match(op.name)
            instr = m.group(1) if m else ""
            found = mod is not None and instr in mod.op_name
            if mod:
                out.modules.add(name)
            if found:
                out.joined_s += sec
            scope = (mod.scope[instr] if found else None) or UNSCOPED
            out.scope_s[scope] = out.scope_s.get(scope, 0.0) + sec
            if mod and instr in mod.mixed:
                inner, before = out.mixed.get((name, instr), (mod.mixed[instr], 0.0))
                out.mixed[(name, instr)] = (inner, before + sec)
        if program:
            busy = trace.merged([(op.start, op.end) for op in red.ops])
            inside = trace.merged([(max(s, lo), min(e, hi)) for s, e in program if min(e, hi) > max(s, lo)])
            idle += _overlap(trace.merged(trace.idle_gaps(busy, lo, hi)), inside)
    if program:
        out.program_idle_s = idle * 1e-9 / len(devices)
    return out


def newest_trace() -> Optional[str]:
    paths = glob.glob(os.path.join(TRACE_ROOT, "**", "*.xplane.pb"), recursive=True)
    return max(paths, key=os.path.getmtime) if paths else None


@functools.lru_cache(maxsize=1)
def _stages_once(path: str, mtime_ns: int, size: int, n_devices: int) -> Optional[Stages]:
    """:func:`stages_of`, once per version of a file; ``None`` where the
    trace holds no window or no device op, or is not an ``XSpace``."""
    try:
        return stages_of(path, n_devices)
    except ValueError:
        return None


def stages(ctx: dict) -> Optional[Stages]:
    """The stages of the run's window: the newest trace under
    :data:`TRACE_ROOT`, if its window is the one the harness reduced
    (``ctx["trace"]``, within 1 µs); else ``None``."""
    path = newest_trace()
    if path is None:
        return None
    red = ctx["trace"]
    st = os.stat(path)
    out = _stages_once(path, st.st_mtime_ns, st.st_size, red.n_devices)
    if out is None or abs(out.window_s - red.window_s) > 1e-6:
        return None
    return out


def share(ctx: dict, scope: str) -> Optional[float]:
    """A stage's self time over the device's busy time, in %; ``None``
    where the window holds no scoped op (a program without scopes)."""
    st = stages(ctx)
    if st is None or (scope in SCOPES and not st.scoped):
        return None
    return 100.0 * st.scope_s.get(scope, 0.0) / ctx["trace"].busy_s
