"""build.kmeans_share: device self time of the k-means stage: the LSH initialisation's eager operations and Lloyd's iterations (`_kmeans_cents_jit`), over the device's
busy time in the window of whole builds, from the trace
(``lib/build_stages.py``). Silent where no program of the window is
named for the stage."""

from lib import build_stages


def read(ctx):
    return build_stages.share(ctx, "kmeans")
