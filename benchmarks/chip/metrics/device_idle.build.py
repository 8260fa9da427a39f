"""device_idle.build: the share of the window of whole builds in which the
chip ran no operation, from the device trace (``lib/trace.py``: 1 - busy /
window). The build's host work shows here: the straggler pass, the row
scatter into the cell-major layout, the transfers and the index's
assembly."""


def read(ctx):
    return 100.0 * ctx["trace"].idle_share
