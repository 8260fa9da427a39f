"""fit.loss_share: self time of the fit step's loss and its gradient (the
``nomad_step`` kernel, forward and backward, and their layout ops: the ops
whose ``op_name`` carries the program's ``nomad_loss`` scope) over the
device's busy time in the window, from the trace (``lib/scopes.py``).
Silent where no op of the window carries a ``nomad_*`` scope."""

from lib import scopes


def read(ctx):
    return scopes.share(ctx, "nomad_loss")
