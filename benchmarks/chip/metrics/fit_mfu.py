"""fit_mfu: the FLOPs the window's fit steps need (``lib/counts.py``: the
loss and its gradient per step, the means refresh per dispatch), over the
window's seconds and the chip's bf16 peak (``lib/peaks.py``)."""

from lib.peaks import peaks_for


def read(ctx):
    if not ctx.get("flops"):
        return None
    return 100.0 * ctx["flops"] / ctx["window_s"] / peaks_for(ctx["device_kind"])["peak_flops"]
