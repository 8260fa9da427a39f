"""Reduce one profiler trace to device busy time, time per op, idle gaps.

The JAX profiler writes ``<dir>/plugins/profile/<time>/<host>.xplane.pb``.
Planes named ``/device:TPU:<n>`` hold the device's timeline; their
``XLA Ops`` line has one event per operation run, named by the full text
of its HLO instruction (``%fusion.131 = f32[74997760,2]{...} fusion(...)``).
Control flow nests there: a ``while`` event spans every op of its body.
The host plane's lines hold the harness's spans
(``jax.profiler.TraceAnnotation``, named ``bench.*``). All start times
share one clock, in nanoseconds from the trace's start.

    busy       union of the op intervals of a device inside the window
    idle       window - busy, as a share of the window
    self time  an op's time inside the window less that of the ops
               nested in it; the self times of a device sum to its busy
    gap        a stretch of the window in which the device ran nothing,
               named by the innermost harness span around its midpoint

With several devices, busy and op times are the mean over devices.
"""

from __future__ import annotations

import glob
import os
import re
from dataclasses import dataclass, field

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
SPAN_PREFIX = "bench."
_INSTR = re.compile(r"^%?(?P<name>[^\s=]+) = ")
_TARGET = re.compile(r'custom_call_target="([^"]+)"')
CONTROL = ("while", "conditional", "call")


def label(text: str) -> str:
    """A short name for an HLO instruction's text: its name, result type
    and kind (``fusion.131 f32[74997760,2] fusion``)."""
    m = _INSTR.match(text)
    if not m:
        return text[:80]
    rest = text[m.end():]
    if rest.startswith("("):  # a tuple result
        depth = 0
        for i, ch in enumerate(rest):
            depth += (ch == "(") - (ch == ")")
            if depth == 0:
                break
        typ, rest = "(tuple)", rest[i + 1:].lstrip()
    else:
        typ, _, rest = rest.partition(" ")
        typ = typ.split("{", 1)[0]
    parts = [m.group("name"), typ, rest.split("(", 1)[0]]
    target = _TARGET.search(text)
    return " ".join(parts + ([target.group(1)] if target else []))


def kind(text: str) -> str:
    """The HLO opcode of an instruction's text (``fusion``, ``while``)."""
    return label(text).split(" ")[2] if _INSTR.match(text) else ""


def is_kernel(text: str, blocks: tuple) -> bool:
    """A Mosaic (Pallas) kernel call whose text names every array type of
    ``blocks`` (``f32[30,8192]``)."""
    return 'custom_call_target="tpu_custom_call"' in text and all(b in text for b in blocks)


@dataclass
class Op:
    name: str  # the HLO instruction's text
    start: int  # ns
    end: int  # ns
    self_ns: int = 0


@dataclass
class Reduced:
    window_s: float
    busy_s: float
    op_s: dict  # op text -> self seconds (mean over devices)
    gaps: list  # the longest idle gaps [(span name, seconds)], longest first
    n_devices: int = 1
    ops: list = field(default_factory=list, repr=False)  # [Op] of every device

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def self_time(self, keep) -> float:
        """Self seconds (mean over devices) of the ops ``keep(text)`` selects."""
        return sum(op.self_ns for op in self.ops if keep(op.name)) * 1e-9 / self.n_devices

    def top_ops(self, n: int) -> list:
        """The ``n`` ops with the most self time, by :func:`label`."""
        by: dict = {}
        for name, s in self.op_s.items():
            by[label(name)] = by.get(label(name), 0.0) + s
        return sorted(by.items(), key=lambda kv: -kv[1])[:n]


def merged(intervals: list) -> list:
    """``[(start, end), ...]`` merged into disjoint, sorted intervals."""
    out: list = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def idle_gaps(intervals: list, lo: int, hi: int) -> list:
    """The stretches of ``[lo, hi)`` that no interval covers."""
    gaps, t = [], lo
    for s, e in merged(intervals):
        if s > t:
            gaps.append((t, min(s, hi)))
        t = max(t, e)
    if t < hi:
        gaps.append((t, hi))
    return [(a, b) for a, b in gaps if b > a]


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def read_xplane(path: str):
    """``(device ops {device: [Op]}, host spans [(name, start, end)])``."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    ops: dict = {}
    spans = []
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                dev = ops.setdefault(int(m.group(1)), [])
                for ev in line.events:
                    start = int(ev.start_ns)
                    dev.append(Op(ev.name, start, start + int(ev.duration_ns)))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(SPAN_PREFIX):
                        start = int(ev.start_ns)
                        spans.append((ev.name, start, start + int(ev.duration_ns)))
    return ops, spans


def _self_times(ops: list) -> None:
    """Set each op's ``self_ns``: its length less that of the ops nested
    directly in it (events of one line nest or follow each other)."""
    stack: list = []
    for op in sorted(ops, key=lambda o: (o.start, -o.end)):
        op.self_ns = op.end - op.start
        while stack and stack[-1].end <= op.start:
            stack.pop()
        if stack:
            stack[-1].self_ns -= op.end - op.start
        stack.append(op)


def _span_at(t: int, spans: list) -> str:
    around = [(e - s, n) for n, s, e in spans if s <= t < e]
    return min(around)[1] if around else "outside spans"


def reduce(
    ops: dict, spans: list, *, window_span: str, n_devices: int = 1, top_gaps: int = 10
) -> Reduced:
    """Reduce device ops and host spans over the window named ``window_span``."""
    windows = [(s, e) for n, s, e in spans if n == window_span]
    if not windows:
        raise ValueError(f"no host span {window_span!r} in the trace")
    lo, hi = windows[0]
    devices = sorted(ops)[:n_devices]
    if not devices or not any(ops[d] for d in devices):
        raise ValueError("the trace holds no device operation")
    busy, op_s, gaps, kept = 0, {}, [], []
    for d in devices:
        clipped = [
            Op(op.name, max(op.start, lo), min(op.end, hi))
            for op in ops[d]
            if min(op.end, hi) > max(op.start, lo)
        ]
        _self_times(clipped)
        for op in clipped:
            kept.append(op)
            op_s[op.name] = op_s.get(op.name, 0.0) + op.self_ns * 1e-9 / len(devices)
        clipped = [(op.start, op.end) for op in clipped]
        busy += sum(e - s for s, e in merged(clipped))
        gaps.extend(idle_gaps(clipped, lo, hi))
    gaps.sort(key=lambda g: g[0] - g[1])
    named = [(n, s, e) for n, s, e in spans if n != window_span and s < hi and e > lo]
    gaps = [(_span_at((a + b) // 2, named), (b - a) * 1e-9) for a, b in gaps[:top_gaps]]
    return Reduced(
        window_s=(hi - lo) * 1e-9,
        busy_s=busy * 1e-9 / len(devices),
        op_s=op_s,
        gaps=gaps,
        n_devices=len(devices),
        ops=kept,
    )


def reduce_dir(trace_dir: str, *, window_span: str, n_devices: int = 1) -> Reduced:
    ops, spans = read_xplane(find_xplane(trace_dir))
    return reduce(ops, spans, window_span=window_span, n_devices=n_devices)

