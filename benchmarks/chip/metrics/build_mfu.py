"""build_mfu: the FLOPs the window's whole builds need (``drivers/build.py:
build_flops``, from ``lib/counts.py``: the k-means E-steps that run, the
candidate pass, every cell's distance matrix), over the window's seconds
and the chip's bf16 peak (``lib/peaks.py``). The whole build's share of
the peak, beside the kernel's roofline: a change that takes the kernel off
the path leaves this one to bound it."""

from lib.peaks import peaks_for


def read(ctx):
    if not ctx.get("flops"):
        return None
    return 100.0 * ctx["flops"] / ctx["window_s"] / peaks_for(ctx["device_kind"])["peak_flops"]
