"""Readings that a cell's correctness limits are set from, on the chip.

    python3 benchmarks/chip/calibrate.py --workload fit.wiki60m \\
        --seeds 1,2,...,12 --control-seeds 1,2,3 > readings.jsonl

For every seed of ``--seeds``: the program's set-up dispatches (the same
``drivers/fit.py:start`` a run makes) against the plain reference, as the
gaps a run compares; and the same readings with a fault planted in the
program's record of the rows the first dispatch moved (four moved rows of
the last cell left where they started, as a wrong scatter target would
leave them). For every seed of ``--control-seeds``, put in the
program's place: the reference in bfloat16 (the control) and the
reference with half of each batch left out and the loss's mean taken over
the rest (a planted fault). One JSON line per reading, written as it
comes. No window is measured. Needs the chip, like a run.
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
        print(f"calibrate.py: {e}", file=sys.stderr)
        return 2
    import jax.numpy as jnp
    import numpy as np

    fit = run.load_module(os.path.join(HERE, "drivers", cell.driver + ".py"), "driver")
    cfg = fit.nomad_config(cell.config)
    tr = cell.traffic
    C = cfg.cluster_capacity

    def emit(**kw):
        print(json.dumps(dict(cell=cell.name, **kw)), flush=True)

    def seeds(text):
        return [int(s) for s in text.split(",") if s]

    program, control = seeds(args.seeds), seeds(args.control_seeds)
    for seed in dict.fromkeys(program + control):
        got = None
        if seed in program:
            dispatch, theta, got = fit.start(cfg, tr, seed, lambda name: contextlib.nullcontext())
            del dispatch, theta
            gc.collect()
        want = fit.readings(cfg, tr, seed, fit.reference_fn(cfg, seed))
        if got is not None:
            emit(kind="program", seed=seed, gaps=fit.gaps(got, want),
                 got={k: got[k] for k in ("losses", "norms")})
            bits = np.unpackbits(got["moved1"])
            last_cell = np.flatnonzero(bits[(cfg.n_clusters - 1) * C:]) + (cfg.n_clusters - 1) * C
            bits[last_cell[:4]] = 0
            bad = dict(got, moved1=np.packbits(bits))
            emit(kind="fault_few_rows", seed=seed, gaps=fit.gaps(bad, want))
            del got, bad, bits
        if seed in control:
            for kind, kw in (("control_bf16", dict(dtype=jnp.bfloat16)),
                             ("fault_half_batch", dict(heads_kept=cfg.batch_size // 2))):
                other = fit.readings(cfg, tr, seed, fit.reference_fn(cfg, seed, **kw))
                emit(kind=kind, seed=seed, gaps=fit.gaps(other, want))
                del other
        del want
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
