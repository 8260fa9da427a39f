"""device_idle.fit: the share of the fit window in which the chip ran no
operation, from the device trace (``lib/trace.py``: 1 - busy / window)."""


def read(ctx):
    return 100.0 * ctx["trace"].idle_share
