"""Roofline table (assignment §Roofline): reads the dry-run result JSONs and
emits one row per (arch × shape × mesh) with the three terms, the dominant
bottleneck, MODEL_FLOPS/HLO_FLOPs, and per-device memory.

Run ``python -m repro.launch.dryrun`` first (results/dryrun). If a frozen
baseline exists (results/dryrun_baseline), a before/after delta column is
added for cells whose terms changed — the §Perf audit trail.
"""

from __future__ import annotations

import glob
import json
import os

RESULTS = os.environ.get("REPRO_DRYRUN_DIR", "results/dryrun")
BASELINE = "results/dryrun_baseline"


def _load(d):
    out = {}
    for f in glob.glob(os.path.join(d, "*", "*.json")):
        r = json.load(open(f))
        if r.get("ok"):
            out[(r["mesh"], r["arch"], r["shape"])] = r
    return out


def run(quick: bool = False):
    cur = _load(RESULTS)
    base = _load(BASELINE) if os.path.isdir(BASELINE) else {}
    rows = []
    for key in sorted(cur):
        r = cur[key]
        t = r["terms"]
        bound_ms = t["bound_s"] * 1e3
        derived = (
            f"compute_ms={t['compute_s']*1e3:.2f};memory_ms={t['memory_s']*1e3:.2f};"
            f"collective_ms={t['collective_s']*1e3:.2f};dominant={t['dominant']};"
            f"useful={t['useful_ratio']:.3f};roofline={t['roofline_fraction']:.4f};"
            f"gib_per_dev={r['memory']['per_device_total']/2**30:.2f}"
        )
        b = base.get(key)
        if b and abs(b["terms"]["bound_s"] - t["bound_s"]) / max(b["terms"]["bound_s"], 1e-12) > 0.02:
            derived += f";baseline_bound_ms={b['terms']['bound_s']*1e3:.2f}"
            derived += f";speedup={b['terms']['bound_s']/max(t['bound_s'],1e-12):.2f}x"
        rows.append((f"roofline/{key[0]}/{key[1]}/{key[2]}", bound_ms * 1e3, derived))
    return rows


if __name__ == "__main__":
    from repro.runtime import enable_compile_cache

    enable_compile_cache()
    for r in run():
        print(",".join(str(c) for c in r))
