"""fit.sample_share: self time of the fit step's sampling (heads and their
cell search, in-cell negatives, the kNN row lookups, the step's key and
learning rate: the ops whose ``op_name`` carries the program's
``nomad_sample`` scope) over the device's busy time in the window, from
the trace (``lib/scopes.py``). Silent where no op of the window carries a
``nomad_*`` scope."""

from lib import scopes


def read(ctx):
    return scopes.share(ctx, "nomad_sample")
