"""fit.collective_share: self time of the window's collective ops over the
device's busy time, both means over the chips, from the trace
(``lib/trace.py``). The collectives are the ops whose HLO opcode
(``lib.trace.kind``) is ``all-gather``, ``all-reduce``,
``reduce-scatter``, ``collective-permute`` or ``all-to-all``, or the
``-start``/``-done`` halves of one: on the sharded fit, the cell means'
all-gather and the loss's all-reduce once a dispatch. On a TPU a
collective's time holds its wait for the slowest chip, so the share also
shows shards out of step. Silent where the window holds no collective."""

from lib.trace import kind

COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "collective-permute", "all-to-all")


def is_collective(text: str) -> bool:
    op = kind(text)
    return any(op == c or op in (c + "-start", c + "-done") for c in COLLECTIVES)


def read(ctx):
    red = ctx["trace"]
    t = red.self_time(is_collective)
    if not t:
        return None
    return 100.0 * t / red.busy_s
