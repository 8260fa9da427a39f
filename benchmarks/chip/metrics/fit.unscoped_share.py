"""fit.unscoped_share: self time of the window's ops in no ``nomad_*``
scope and not control flow (the scan's own slicing and stacking, copies
XLA made whose users are in no one scope, the harness's own small
programs, ops not found in their module), over the device's busy time,
from the trace (``lib/scopes.py``). What the program's stage scopes miss:
the five stage shares, control flow and this sum to 100."""

from lib import scopes


def read(ctx):
    return scopes.share(ctx, scopes.UNSCOPED)
