"""device_idle.fit.program: the share of the fit window in which the chip
ran no operation while the host was inside the program's own spans
(``nomad.fit.dispatch``, the enqueue of a dispatch, and ``nomad.fit.sync``,
the wait for its loss), from the trace (``lib/scopes.py``).
``device_idle.fit`` less this is idle time the caller's code holds. Silent
where the window holds no such span."""

from lib import scopes


def read(ctx):
    st = scopes.stages(ctx)
    if st is None or st.program_idle_s is None:
        return None
    return 100.0 * st.program_idle_s / ctx["trace"].window_s
