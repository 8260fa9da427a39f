"""The index build's stages in one profiler trace: device self time per
stage, from the compiled programs each stage runs.

The program's build (``IndexBuilder.build``, local path) runs one named
jitted program per stage, and eager operations around them:

    kmeans    ``_kmeans_cents_jit`` (Lloyd's iterations), after the eager
              operations of its LSH initialisation
    assign    ``_capacity_rounds_local`` (the candidate pass and the
              bidding rounds)
    permute   ``_permutation_from_assign``, after the eager conversion of
              its input
    knn       the programs that call ``_cluster_knn_jit`` (an eager
              ``lax.map`` over the cells compiles as ``jit_scan``), after
              the eager mask of valid slots

A program belongs to a stage where its module is named for the stage's
function (``jit__capacity_rounds_local(...)``) or where an instruction's
``op_name`` names it (``.../jit(_cluster_knn_jit)/...``); the op names
come from the trace's ``/host:metadata`` plane, read by ``lib/scopes.py``.
A program that names none (an eager ``jit_argsort``, ``jit_reshape``)
belongs to the stage of the next named program its device runs, or of the
last one where none follows: eager operations prepare the named program
that follows them. Each op of the window takes its program's stage, and
its self time (``lib/trace.py``) counts there; ops of a program that
belongs to no stage (no named program in the window at all) are ``other``.
The stages and ``other`` sum to the busy time.

The program has no host span per stage yet; these shares need none.
"""

from __future__ import annotations

import bisect
import functools
import os
import re
from dataclasses import dataclass, field
from typing import Optional

from lib import scopes, trace

STAGES = {
    "kmeans": "_kmeans_cents_jit",
    "assign": "_capacity_rounds_local",
    "permute": "_permutation_from_assign",
    "knn": "_cluster_knn_jit",
}
OTHER = "other"
_MODULE = re.compile(r"^jit_(.+?)\(")
_PAIRWISE = re.compile(
    r"^%?\S+ = f32\[(?:(\d+),)?(\d+),(\d+)\]\{[^}]*\} custom-call\(f32\[(?:(\d+),)?(\d+),(\d+)\]"
)


@dataclass
class BuildStages:
    """One window's device time by stage (seconds, mean over devices)."""

    window_s: float
    busy_s: float
    stage_s: dict  # stage -> self seconds; OTHER for programs of no stage
    modules: dict = field(default_factory=dict)  # module name -> its stage


def _named_stage(name: str, op_names) -> Optional[str]:
    m = _MODULE.match(name)
    for stage, fn in STAGES.items():
        if m and m.group(1) == fn:
            return stage
    for stage, fn in STAGES.items():
        tag = f"jit({fn})"
        if any(tag in o for o in op_names):
            return stage
    return None


def stages_of(path: str, n_devices: int = 1) -> BuildStages:
    """Reduce the window of the trace at ``path`` by build stage."""
    ops, module_events, spans = scopes._read(path)
    with open(path, "rb") as f:
        buf = f.read()
    protos = scopes.hlo_protos(buf)

    @functools.lru_cache(maxsize=None)
    def named(name: str) -> Optional[str]:
        op_names = scopes.parse_module(buf, protos[name]).op_name.values() if name in protos else ()
        return _named_stage(name, op_names)

    bench = [s for s in spans if s[0].startswith(trace.SPAN_PREFIX)]
    out = BuildStages(window_s=0.0, busy_s=0.0, stage_s={})
    for d in sorted(ops)[:n_devices]:
        red = trace.reduce({d: ops[d]}, bench, window_span=scopes.WINDOW_SPAN)
        out.window_s = red.window_s
        out.busy_s += red.busy_s / n_devices
        events = sorted(module_events.get(d, []))
        starts = [s for s, _, _ in events]
        # each module run takes its own stage, else the next named one's,
        # else the last named one's
        stage_of, nxt = [None] * len(events), None
        for i in range(len(events) - 1, -1, -1):
            nxt = named(events[i][2]) or nxt
            stage_of[i] = nxt
        last = None
        for i, (_, _, name) in enumerate(events):
            last = named(name) or last
            stage_of[i] = stage_of[i] or last
            out.modules[name] = stage_of[i]
        for op in red.ops:
            i = bisect.bisect_right(starts, op.start) - 1
            inside = i >= 0 and op.start < events[i][1]
            stage = (stage_of[i] if inside else None) or OTHER
            out.stage_s[stage] = out.stage_s.get(stage, 0.0) + op.self_ns * 1e-9 / n_devices
    return out


@functools.lru_cache(maxsize=1)
def _stages_once(path: str, mtime_ns: int, size: int, n_devices: int) -> Optional[BuildStages]:
    try:
        return stages_of(path, n_devices)
    except ValueError:
        return None


def stages(ctx: dict) -> Optional[BuildStages]:
    """The stages of the run's window: the newest trace under
    ``lib.scopes.TRACE_ROOT``, if its window is the one the harness
    reduced (``ctx["trace"]``, within 1 µs); else ``None``."""
    path = scopes.newest_trace()
    if path is None:
        return None
    red = ctx["trace"]
    st = os.stat(path)
    out = _stages_once(path, st.st_mtime_ns, st.st_size, red.n_devices)
    if out is None or abs(out.window_s - red.window_s) > 1e-6:
        return None
    return out


def share(ctx: dict, stage: str) -> Optional[float]:
    """A stage's self time over the device's busy time, in %; ``None``
    where no program of the window is named for the stage."""
    st = stages(ctx)
    if st is None or stage not in st.modules.values():
        return None
    return 100.0 * st.stage_s.get(stage, 0.0) / ctx["trace"].busy_s


def is_cell_pairwise(text: str, C: int, d: int) -> bool:
    """Whether an op is a Mosaic call of the ``pairwise`` kernel on cells:
    a ``tpu_custom_call`` that takes two (c, e) float32 blocks, or two
    batches (b, c, e) of them, c the cell capacity C or its padding (under
    2C) and e the width d or its padding, and returns their (c, c) or
    (b, c, c) float32 distances. The candidate pass's call (rows against
    centroids) returns no square matrix."""
    m = _PAIRWISE.match(text)
    if not m or 'custom_call_target="tpu_custom_call"' not in text:
        return False
    b, r0, r1, ab, a0, a1 = m.groups()
    operand = f"f32[{ab + ',' if ab else ''}{a0},{a1}]"
    return (
        b == ab and r0 == r1 == a0 and C <= int(r0) < 2 * C and int(a1) >= d
        and text.count(operand) >= 2
    )
