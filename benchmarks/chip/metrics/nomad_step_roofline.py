"""nomad_step_roofline: the least time the chip could take for the window's
calls of the fused step kernel, forward and backward, each bound by the
larger of FLOPs / peak and bytes / HBM bandwidth (``lib/counts.py``,
``lib/peaks.py``), over the kernels' self time in the trace.

A call of the kernel is a Mosaic call (``tpu_custom_call``) that takes the
positives and the negatives as (k·d, B) and (S·d, B) float32 blocks
(``nomad_step_blocks``, from the driver); no other kernel of the step
takes them. Where no call matches, the kernel is off the path or its
interface changed: the metric is left out, and the harness says so on
standard error."""

from lib.peaks import peaks_for
from lib.trace import is_kernel


def read(ctx):
    t = ctx["trace"].self_time(lambda text: is_kernel(text, ctx["nomad_step_blocks"]))
    if not t or not ctx.get("kernel_calls"):
        return None
    hw = peaks_for(ctx["device_kind"])
    least = sum(
        max(c["flops"] / hw["peak_flops"], c["bytes"] / hw["hbm_bw"])
        for c in (ctx["nomad_step_fwd"], ctx["nomad_step_bwd"])
    )
    return 100.0 * ctx["kernel_calls"] * least / t
