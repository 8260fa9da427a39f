"""fit.scatter_share: self time of the fit step's sparse SGD update of θ
(the sort of the step's (row, update) pairs, the blocks' bounds, the
``row_add`` kernel and the ``-lr * g`` products: the ops whose ``op_name``
carries the program's ``nomad_scatter`` scope) over the device's busy time
in the window, from the trace (``lib/scopes.py``). Silent where no op of
the window carries a ``nomad_*`` scope."""

from lib import scopes


def read(ctx):
    return scopes.share(ctx, "nomad_scatter")
