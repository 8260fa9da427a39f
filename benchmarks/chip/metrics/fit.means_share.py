"""fit.means_share: self time of the per-cell means refresh (the ops whose
``op_name`` carries the program's ``nomad_means`` scope, and the ops with
no ``op_name`` that XLA made for it: the relayout of θ in its own loops)
over the device's busy time in the window, from the trace
(``lib/scopes.py``). Silent where no op of the window carries a
``nomad_*`` scope."""

from lib import scopes


def read(ctx):
    return scopes.share(ctx, "nomad_means")
