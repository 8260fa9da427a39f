"""build.permute_share: device self time of the cell-major permutation (`_permutation_from_assign` and the conversion of its input), over the device's
busy time in the window of whole builds, from the trace
(``lib/build_stages.py``). Silent where no program of the window is
named for the stage."""

from lib import build_stages


def read(ctx):
    return build_stages.share(ctx, "permute")
