"""Readings that a sharded fit cell's correctness limits are set from, on
the chip.

    python3 benchmarks/chip/calibrate_sharded.py --workload fit.wiki60m.4chip \\
        --seeds 1,2,3 --control-seeds 1,2 > readings.jsonl

For every seed of ``--seeds``: the program's set-up dispatches (the same
``drivers/fit_sharded.py:start`` a run makes) against the plain reference
(``lib/ref_fit_sharded.py``), as the gaps a run compares; and the same
readings with a fault planted in the program's record of the rows the
first dispatch moved (four moved rows of the last cell left where they
started, as a wrong scatter target would leave them). For every seed of
``--control-seeds``, put in the program's place: the reference in
bfloat16 (the control); the reference with half of each batch left out
and the loss's mean taken over the rest; and the reference with each
shard's mean term over its own cells' means only, as if the means'
all-gather were left out (the fault of the sharding). One JSON line per
reading, written as it comes. No window is measured. Needs the cell's
chips, like a run.
"""

from __future__ import annotations

import argparse
import contextlib
import gc
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
import run  # noqa: E402  (sets the compile cache before JAX loads)


def main(argv=None, *, chips=run.require_chips, find=run.find_cell) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    args = ap.parse_args(argv)
    sys.path.insert(0, os.path.join(run.ROOT, "src"))
    cell = find(args.workload)
    try:
        devices = chips(cell.chips)
    except run.NoChip as e:
        print(f"calibrate_sharded.py: {e}", file=sys.stderr)
        return 2
    import jax.numpy as jnp
    import numpy as np

    fs = run.load_module(os.path.join(HERE, "drivers", cell.driver + ".py"), "driver")
    from drivers.fit import gaps

    cfg = fs.sharded_config(cell.config)
    mesh = fs.mesh_of(cell.config, devices)
    n_shards = mesh.size
    tr = cell.traffic
    C = cfg.cluster_capacity

    def emit(**kw):
        print(json.dumps(dict(cell=cell.name, **kw)), flush=True)

    def seeds(text):
        return [int(s) for s in text.split(",") if s]

    def reference(seed, **kw):
        return fs.readings(cfg, tr, seed, mesh, fs.reference_fn(cfg, seed, n_shards, **kw))

    program, control = seeds(args.seeds), seeds(args.control_seeds)
    for seed in dict.fromkeys(program + control):
        got = None
        if seed in program:
            dispatch, theta, got = fs.start(cfg, tr, seed, mesh, lambda name: contextlib.nullcontext())
            del dispatch, theta
            gc.collect()
        want = reference(seed)
        if got is not None:
            emit(kind="program", seed=seed, gaps=gaps(got, want),
                 got={k: got[k] for k in ("losses", "norms")})
            bits = np.unpackbits(got["moved1"])
            last_cell = np.flatnonzero(bits[(cfg.n_clusters - 1) * C:]) + (cfg.n_clusters - 1) * C
            bits[last_cell[:4]] = 0
            emit(kind="fault_few_rows", seed=seed, gaps=gaps(dict(got, moved1=np.packbits(bits)), want))
            del got, bits
        if seed in control:
            for kind, kw in (("control_bf16", dict(dtype=jnp.bfloat16)),
                             ("fault_half_batch", dict(heads_kept=cfg.batch_size // 2)),
                             ("fault_own_means_only", dict(own_means_only=True))):
                emit(kind=kind, seed=seed, gaps=gaps(reference(seed, **kw), want))
        del want
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
