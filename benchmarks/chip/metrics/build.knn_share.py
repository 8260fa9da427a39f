"""build.knn_share: device self time of the in-cell kNN: each cell's distance matrix, top-k, rank matrix and Eq. 6 weights (the programs that call `_cluster_knn_jit`) and the mask of valid slots, over the device's
busy time in the window of whole builds, from the trace
(``lib/build_stages.py``). Silent where no program of the window is
named for the stage."""

from lib import build_stages


def read(ctx):
    return build_stages.share(ctx, "knn")
