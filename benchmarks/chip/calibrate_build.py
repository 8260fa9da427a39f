"""Readings that a build cell's correctness limits are set from, on the chip.

    python3 benchmarks/chip/calibrate_build.py --workload build.pubmed \\
        --seeds 1,2,...,12 --control-seeds 1,2,3 > readings.jsonl

For every seed of ``--seeds``: the program's first build (the same
``drivers/build.py:start`` a run makes) against the plain reference, as
the numbers a run compares; then one build each with a fault planted in
the build's path (:func:`plant`): k-means that leaves its initial
centroids unchanged; four rows placed in another cell; one cell's
neighbours taken one rank too far; the weights' ranks off by one.
For every seed of ``--control-seeds``, put in the program's place: the
reference with its distances at ``Precision.HIGH`` (the control: the step
below the program's float32 at ``HIGHEST``) and in bfloat16. One JSON
line per reading, written as it comes. No window is measured. Needs the
chip, like a run.
"""

from __future__ import annotations

import argparse
import contextlib
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
import run  # noqa: E402  (sets the compile cache before JAX loads)

# the numbers each planted fault has to fail
CAUGHT_BY = {
    "kmeans_unchanged": ("kmeans_gap",),
    "four_rows": ("assign_apart",),
    "knn_one_rank_far": ("knn_apart", "knn_d_gap"),
    "ranks_off_by_one": ("weight_apart",),
}


def plant(fault: str, setattr_) -> None:
    """Break the program's build path with ``fault``, through
    ``setattr_(module, name, value)`` (pytest's ``monkeypatch.setattr``,
    or :func:`patched`'s)."""
    import numpy as np

    from lib.ref_build import normalizer
    from repro.index import build as b

    if fault == "kmeans_unchanged":
        from repro.index import kmeans as km

        orig_km = km.kmeans_centroids

        def unchanged(*a, **kw):
            return orig_km(*a, **dict(kw, n_iters=0))

        setattr_(km, "kmeans_centroids", unchanged)
    elif fault == "four_rows":
        orig_place = b._force_place_host

        def place(x, cents, assign, free, *a, **kw):
            assign, stragglers = orig_place(x, cents, assign, free, *a, **kw)
            held = np.bincount(assign, minlength=len(free))
            src, dst = int(np.argmax(held)), int(np.argmin(held))
            assign[np.flatnonzero(assign == src)[:4]] = dst
            return assign, stragglers

        setattr_(b, "_force_place_host", place)
    elif fault == "knn_one_rank_far":
        orig_knn = b.batched_cluster_knn

        def knn(x_blocks, valid, k, impl=None):
            idx, w = orig_knn(x_blocks, valid, k, impl)
            far_idx, far_w = orig_knn(x_blocks[:1], valid[:1], k + 1, impl)
            return idx.at[0].set(far_idx[0, :, 1:]), w.at[0].set(far_w[0, :, 1:])

        setattr_(b, "batched_cluster_knn", knn)
    elif fault == "ranks_off_by_one":
        orig_fin = b._finalize_knn

        def finalize(knn_local, knn_w, K, C):
            w = np.asarray(knn_w, np.float64)
            k = w.shape[-1]
            z = normalizer(k)
            r = np.rint(1.0 / np.log(np.where(w > 0, w * z, np.e)))
            off = np.where((w > 0) & (r + 1 <= k), np.exp(1.0 / (r + 1)) / z, 0.0)
            return orig_fin(knn_local, off.astype(np.float32), K, C)

        setattr_(b, "_finalize_knn", finalize)
    else:
        raise ValueError(f"no fault {fault!r}")


@contextlib.contextmanager
def patched(fault: str):
    undo = []

    def setattr_(mod, name, value):
        undo.append((mod, name, getattr(mod, name)))
        setattr(mod, name, value)

    plant(fault, setattr_)
    try:
        yield
    finally:
        for mod, name, value in reversed(undo):
            setattr(mod, name, value)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    args = ap.parse_args(argv)
    sys.path.insert(0, os.path.join(run.ROOT, "src"))
    cell = run.find_cell(args.workload)
    try:
        run.require_chips(cell.chips)
    except run.NoChip as e:
        print(f"calibrate_build.py: {e}", file=sys.stderr)
        return 2
    from lib import ref_build

    drv = run.load_module(os.path.join(HERE, "drivers", cell.driver + ".py"), "driver")
    cfg = drv.nomad_config(cell.config)
    tr = cell.traffic
    quiet = lambda name: contextlib.nullcontext()  # noqa: E731

    def emit(**kw):
        print(json.dumps(dict(cell=cell.name, **kw)), flush=True)

    def seeds(text):
        return [int(s) for s in text.split(",") if s]

    program, control = seeds(args.seeds), seeds(args.control_seeds)
    for seed in dict.fromkeys(program + control):
        t = time.perf_counter()
        build, x, got, _ = drv.start(cfg, tr, seed, quiet)
        ref = ref_build.Reference(x, cfg)
        if seed in program:
            emit(kind="program", seed=seed, numbers=ref.compare(got), s=time.perf_counter() - t)
            again = build()
            emit(kind="program_again", seed=seed, same=drv.same(got, again))
            for fault in CAUGHT_BY:
                with patched(fault):
                    bad = build()
                emit(kind="fault_" + fault, seed=seed, numbers=ref.compare(bad))
        if seed in control:
            for how in ("high", "bf16"):
                t = time.perf_counter()
                other = ref_build.build(x, cfg, how)
                emit(kind="control_" + how, seed=seed, numbers=ref.compare(other),
                     s=time.perf_counter() - t)
    return 0


if __name__ == "__main__":
    sys.exit(main())
