"""build.assign_share: device self time of the placement under capacity: the candidate pass and the bidding rounds (`_capacity_rounds_local`), over the device's
busy time in the window of whole builds, from the trace
(``lib/build_stages.py``). Silent where no program of the window is
named for the stage."""

from lib import build_stages


def read(ctx):
    return build_stages.share(ctx, "assign")
