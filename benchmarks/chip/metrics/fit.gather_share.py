"""fit.gather_share: self time of the fit step's gathers of θ rows (heads,
positives, negatives: the ops whose ``op_name`` carries the program's
``nomad_gather`` scope) over the device's busy time in the window, from
the trace (``lib/scopes.py``). A gather XLA fuses into another stage's op
counts there. Silent where no op of the window carries a ``nomad_*``
scope."""

from lib import scopes


def read(ctx):
    return scopes.share(ctx, "nomad_gather")
