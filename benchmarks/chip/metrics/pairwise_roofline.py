"""pairwise_roofline: the least time the chip could take for the window's
calls of the ``pairwise`` kernel on cells, the larger of FLOPs / peak and
bytes / HBM bandwidth for each cell's (C, D) x (C, D) -> (C, C) float32
distance matrix (``lib/counts.py:pairwise``, ``lib/peaks.py``), over the
self time of those calls in the trace.

A call is a Mosaic call that takes two (C, D) float32 blocks (or a batch
of them), padded or not, and returns their square matrices
(``lib/build_stages.py:is_cell_pairwise``); the window needs one matrix
per cell per build (``pairwise_cells``, from the driver). Where no call
matches, the kernel is off the kNN's path or its interface changed: the
metric is left out, and the harness says so on standard error."""

from lib.build_stages import is_cell_pairwise
from lib.peaks import peaks_for


def read(ctx):
    C, d = ctx["pairwise_cell"]
    t = ctx["trace"].self_time(lambda text: is_cell_pairwise(text, C, d))
    if not t or not ctx.get("pairwise_cells"):
        return None
    hw = peaks_for(ctx["device_kind"])
    c = ctx["pairwise"]
    least = max(c["flops"] / hw["peak_flops"], c["bytes"] / hw["hbm_bw"])
    return 100.0 * ctx["pairwise_cells"] * least / t
