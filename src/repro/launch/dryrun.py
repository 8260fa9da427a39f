import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run (assignment deliverable e).

For every (architecture × supported input shape × mesh) cell this driver
builds the step function of the cell's kind (train / prefill / decode),
``jit(...).lower(*ShapeDtypeStructs).compile()`` — nothing is allocated —
and records:

* ``compiled.memory_analysis()``  → per-device bytes (proves it fits),
* ``compiled.cost_analysis()``    → XLA's (loop-body-once) numbers,
* our trip-count-aware HLO cost   → FLOPs / HBM bytes / collective bytes,
* the three roofline terms + dominant bottleneck (§Roofline).

The NOMAD workloads (the paper's own contribution) run through the same
gate: ``--arch nomad_pubmed`` / ``nomad_wiki60m`` lower the *distributed
epoch step* (shard_map over the full mesh, means all-gather included).

Results land in ``results/dryrun/<mesh>/<arch>__<shape>.json`` (one file
per cell, written incrementally — safe to re-run with --skip-existing).

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch all --mesh both
  PYTHONPATH=src python -m repro.launch.dryrun --arch yi-34b --shape train_4k
"""

import argparse
import json
import time
import traceback

import numpy as np


# the production meshes are TPU v5e pods; roofline terms use its peaks
TARGET_DEVICE_KIND = "TPU v5 lite"


def _mesh_tag(multi_pod: bool) -> str:
    return "pod2x16x16" if multi_pod else "pod16x16"


def run_lm_cell(arch_name: str, shape_name: str, multi_pod: bool, save_hlo: str | None):
    import jax
    import jax.numpy as jnp

    from repro.configs import SHAPES, get_arch
    from repro.launch.mesh import make_production_mesh
    from repro.launch.sharding import step_shardings
    from repro.models import steps as steps_lib
    from repro.optim import AdamW, warmup_cosine
    from repro.roofline.analysis import model_flops, peaks_for, roofline_terms
    from repro.roofline.hlo_cost import analyze_hlo

    cfg = get_arch(arch_name)
    shape = SHAPES[shape_name]
    mesh = make_production_mesh(multi_pod=multi_pod)
    n_chips = int(np.prod(list(mesh.shape.values())))

    # pin activation batch sharding (see models/lm.py set_activation_sharding)
    from repro.models import lm as lm_lib
    from repro.models import moe as moe_lib

    dp_axes = ("pod", "data") if multi_pod else ("data",)
    dp_size = int(np.prod([mesh.shape[a] for a in dp_axes]))
    micro = shape.global_batch // (cfg.accum_steps if shape.kind == "train" else 1)
    if micro % dp_size == 0:
        token_axes = dp_axes
    elif micro % mesh.shape["data"] == 0:
        token_axes = ("data",)
    else:
        token_axes = None
    lm_lib.set_activation_sharding(token_axes)
    # expert-parallel shard_map MoE (§Perf iteration 4); for decode, expert
    # weights go TP-resident when they fit (§Perf iteration 6)
    if cfg.n_experts:
        from repro.launch.sharding import serving_weights_resident

        fsdp = ("data",)
        stationary = False
        if shape.kind == "decode":
            if cfg.n_experts % mesh.shape["model"] == 0:
                stationary = True  # move tokens, not weights (any batch)
            elif serving_weights_resident(cfg, mesh):
                fsdp = ()  # expert weights fully TP-resident
        moe_lib.set_ep_mesh(mesh, fsdp, token_axes, stationary=stationary)
    else:
        moe_lib.set_ep_mesh(None, None)

    optimizer = AdamW(
        schedule=warmup_cosine(3e-4, 2000, 100_000),
        moment_dtype=cfg.opt_moment_dtype,
    )
    from repro.models import attention as attn_lib
    from repro.launch.sharding import cache_pspecs as _cp

    attn_lib.set_decode_context(None, None, ())
    if shape.kind == "train":
        step = steps_lib.make_train_step(cfg, optimizer, microbatched=True)
        donate = (0, 1)
    elif shape.kind == "prefill":
        step = steps_lib.make_prefill_step(cfg)
        donate = ()
    else:
        step = steps_lib.make_decode_step(cfg)
        donate = (1,)
        if cfg.n_heads:  # sharded flash-decode (§Perf iteration 7)
            b = shape.global_batch
            if b % dp_size == 0:
                baxes, saxes = dp_axes, ("model",)
            else:
                baxes, saxes = None, dp_axes + ("model",)
            attn_lib.set_decode_context(mesh, baxes, saxes)

    specs = steps_lib.input_specs(cfg, shape, optimizer)
    in_sh, out_sh = step_shardings(cfg, shape, mesh, specs)

    t0 = time.time()
    jitted = jax.jit(step, in_shardings=in_sh, out_shardings=out_sh, donate_argnums=donate)
    with mesh:
        lowered = jitted.lower(*specs)
    lower_s = time.time() - t0
    t0 = time.time()
    compiled = lowered.compile()
    compile_s = time.time() - t0

    ma = compiled.memory_analysis()
    ca = compiled.cost_analysis() or {}
    hlo = compiled.as_text()
    rep = analyze_hlo(hlo)
    mf = model_flops(cfg, shape)
    # per-device useful flops → terms; model_flops is global
    terms = roofline_terms(rep, n_chips, mf, peaks_for(TARGET_DEVICE_KIND))
    if save_hlo:
        os.makedirs(save_hlo, exist_ok=True)
        with open(os.path.join(save_hlo, f"{arch_name}__{shape_name}__{_mesh_tag(multi_pod)}.hlo"), "w") as f:
            f.write(hlo)

    return {
        "arch": arch_name,
        "shape": shape_name,
        "mesh": _mesh_tag(multi_pod),
        "n_chips": n_chips,
        "kind": shape.kind,
        "ok": True,
        "lower_s": round(lower_s, 2),
        "compile_s": round(compile_s, 2),
        "memory": {
            "argument_bytes": ma.argument_size_in_bytes,
            "output_bytes": ma.output_size_in_bytes,
            "temp_bytes": ma.temp_size_in_bytes,
            "alias_bytes": ma.alias_size_in_bytes,
            "per_device_total": ma.argument_size_in_bytes
            + ma.output_size_in_bytes
            + ma.temp_size_in_bytes
            - ma.alias_size_in_bytes,
        },
        "xla_cost": {"flops": ca.get("flops", 0.0), "bytes": ca.get("bytes accessed", 0.0)},
        "hlo_cost": {
            "flops": rep.flops,
            "bytes": rep.bytes,
            "collective_bytes": rep.collective_bytes,
            "coll_by_type": rep.coll_by_type,
            "coll_ops": rep.coll_ops,
            "dot_flops": rep.dot_flops,
            "unknown_trip_whiles": rep.unknown_trip_whiles,
        },
        "model_flops": mf,
        "terms": {
            "compute_s": terms.compute_s,
            "memory_s": terms.memory_s,
            "collective_s": terms.collective_s,
            "dominant": terms.dominant,
            "useful_ratio": terms.useful_ratio,
            "roofline_fraction": terms.roofline_fraction,
            "bound_s": terms.bound_s,
        },
    }


def run_nomad_cell(workload: str, multi_pod: bool, save_hlo: str | None):
    """Lower + compile the distributed NOMAD epoch step on the mesh."""
    import jax
    import jax.numpy as jnp

    from repro.configs import get_nomad
    from repro.core.distributed import make_sharded_epoch_fn
    from repro.launch.mesh import make_production_mesh
    from repro.roofline.analysis import (
        nomad_analytic_terms,
        nomad_model_flops,
        peaks_for,
        roofline_terms,
    )
    from repro.roofline.hlo_cost import analyze_hlo
    from jax.sharding import NamedSharding, PartitionSpec as P

    cfg = get_nomad(workload)
    mesh = make_production_mesh(multi_pod=multi_pod)
    n_chips = int(np.prod(list(mesh.shape.values())))
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    pod_axis = "pod" if multi_pod else None

    K, C = cfg.n_clusters, cfg.cluster_capacity
    steps = max(1, -(-cfg.resolved_steps_per_epoch() // n_chips))
    epoch_fn = make_sharded_epoch_fn(
        cfg,
        mesh,
        shard_axes=("data", "model"),
        pod_axis=pod_axis,
        steps_per_epoch=steps,
        n_shards=n_chips,
    )

    rows = K * C
    sds = jax.ShapeDtypeStruct
    theta = sds((rows, cfg.out_dim), jnp.float32)
    idx = {
        "knn_idx": sds((rows, cfg.n_neighbors), jnp.int32),
        "knn_w": sds((rows, cfg.n_neighbors), jnp.float32),
        "counts": sds((K,), jnp.int32),
        "cum_counts": sds((K,), jnp.int32),
    }
    counts_global = sds((K,), jnp.float32)
    lr = sds((), jnp.float32)
    key = jax.eval_shape(lambda: jax.random.key(0))

    row_sh = NamedSharding(mesh, P(axes, None))
    vec_sh = NamedSharding(mesh, P(axes))
    rep_sh = NamedSharding(mesh, P())
    in_sh = (
        row_sh,
        {"knn_idx": row_sh, "knn_w": row_sh, "counts": vec_sh, "cum_counts": vec_sh},
        rep_sh,
        rep_sh,
        rep_sh,
        rep_sh,
    )
    t0 = time.time()
    jitted = jax.jit(epoch_fn, in_shardings=in_sh, out_shardings=(row_sh, rep_sh), donate_argnums=(0,))
    with mesh:
        lowered = jitted.lower(theta, idx, counts_global, lr, lr, key)
    lower_s = time.time() - t0
    t0 = time.time()
    compiled = lowered.compile()
    compile_s = time.time() - t0

    ma = compiled.memory_analysis()
    ca = compiled.cost_analysis() or {}
    hlo = compiled.as_text()
    rep = analyze_hlo(hlo)
    mf = nomad_model_flops(
        cfg.n_points, cfg.batch_size * n_chips, cfg.n_neighbors,
        cfg.n_exact_negatives, cfg.n_clusters, steps,
    )
    terms = roofline_terms(rep, n_chips, mf, peaks_for(TARGET_DEVICE_KIND))
    if save_hlo:
        os.makedirs(save_hlo, exist_ok=True)
        with open(os.path.join(save_hlo, f"{workload}__epoch__{_mesh_tag(multi_pod)}.hlo"), "w") as f:
            f.write(hlo)
    return {
        "arch": workload,
        "shape": "epoch",
        "mesh": _mesh_tag(multi_pod),
        "n_chips": n_chips,
        "kind": "nomad-epoch",
        "ok": True,
        "lower_s": round(lower_s, 2),
        "compile_s": round(compile_s, 2),
        "steps_per_epoch": steps,
        "hierarchical": bool(cfg.hierarchical and multi_pod),
        # kernel-true terms: the HLO memory term is inflated by the Pallas
        # interpret-mode tile boundaries (VMEM-resident on a real TPU)
        "analytic_terms": nomad_analytic_terms(cfg, n_chips, steps, peaks_for(TARGET_DEVICE_KIND)),
        "memory": {
            "argument_bytes": ma.argument_size_in_bytes,
            "output_bytes": ma.output_size_in_bytes,
            "temp_bytes": ma.temp_size_in_bytes,
            "alias_bytes": ma.alias_size_in_bytes,
            "per_device_total": ma.argument_size_in_bytes
            + ma.output_size_in_bytes
            + ma.temp_size_in_bytes
            - ma.alias_size_in_bytes,
        },
        "xla_cost": {"flops": ca.get("flops", 0.0), "bytes": ca.get("bytes accessed", 0.0)},
        "hlo_cost": {
            "flops": rep.flops,
            "bytes": rep.bytes,
            "collective_bytes": rep.collective_bytes,
            "coll_by_type": rep.coll_by_type,
            "coll_ops": rep.coll_ops,
            "dot_flops": rep.dot_flops,
            "unknown_trip_whiles": rep.unknown_trip_whiles,
        },
        "model_flops": mf,
        "terms": {
            "compute_s": terms.compute_s,
            "memory_s": terms.memory_s,
            "collective_s": terms.collective_s,
            "dominant": terms.dominant,
            "useful_ratio": terms.useful_ratio,
            "roofline_fraction": terms.roofline_fraction,
            "bound_s": terms.bound_s,
        },
    }


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="all", help="arch id | nomad workload | 'all'")
    ap.add_argument("--shape", default="all")
    ap.add_argument("--mesh", default="both", choices=["single", "multi", "both"])
    ap.add_argument("--out", default="results/dryrun")
    ap.add_argument("--skip-existing", action="store_true")
    ap.add_argument("--save-hlo", default="", help="dir to dump compiled HLO text")
    args = ap.parse_args()

    from repro.configs import ARCHS, NOMAD_WORKLOADS, SHAPES

    meshes = {"single": [False], "multi": [True], "both": [False, True]}[args.mesh]

    cells = []
    arch_list = (
        list(ARCHS) + ["nomad_pubmed", "nomad_wiki60m"]
        if args.arch == "all"
        else [args.arch]
    )
    for a in arch_list:
        if a in NOMAD_WORKLOADS:
            for mp in meshes:
                cells.append((a, "epoch", mp))
            continue
        cfg = ARCHS[a]
        shapes = cfg.supported_shapes() if args.shape == "all" else [args.shape]
        for s in shapes:
            if s not in cfg.supported_shapes():
                print(f"SKIP {a} × {s}: unsupported (see DESIGN.md skip table)")
                continue
            for mp in meshes:
                cells.append((a, s, mp))

    results = []
    for arch, shape, mp in cells:
        tag = _mesh_tag(mp)
        out_dir = os.path.join(args.out, tag)
        os.makedirs(out_dir, exist_ok=True)
        out_path = os.path.join(out_dir, f"{arch}__{shape}.json")
        if args.skip_existing and os.path.exists(out_path):
            print(f"SKIP (exists) {arch} × {shape} × {tag}")
            continue
        print(f"=== {arch} × {shape} × {tag} ===", flush=True)
        try:
            if arch in NOMAD_WORKLOADS:
                rec = run_nomad_cell(arch, mp, args.save_hlo or None)
            else:
                rec = run_lm_cell(arch, shape, mp, args.save_hlo or None)
            t = rec["terms"]
            print(
                f"  ok: compile {rec['compile_s']}s | mem/dev "
                f"{rec['memory']['per_device_total']/2**30:.2f} GiB | "
                f"compute {t['compute_s']*1e3:.2f} ms, memory {t['memory_s']*1e3:.2f} ms, "
                f"collective {t['collective_s']*1e3:.2f} ms → {t['dominant']}-bound; "
                f"useful {t['useful_ratio']:.2f}, roofline {t['roofline_fraction']:.2f}",
                flush=True,
            )
        except Exception as e:  # noqa: BLE001 — record and continue the sweep
            rec = {
                "arch": arch,
                "shape": shape,
                "mesh": tag,
                "ok": False,
                "error": f"{type(e).__name__}: {e}",
                "traceback": traceback.format_exc()[-4000:],
            }
            print(f"  FAIL: {rec['error']}", flush=True)
        with open(out_path, "w") as f:
            json.dump(rec, f, indent=1)
        results.append(rec)

    n_ok = sum(1 for r in results if r.get("ok"))
    print(f"\n{n_ok}/{len(results)} cells compiled OK")
    return 0 if n_ok == len(results) else 1


if __name__ == "__main__":
    raise SystemExit(main())
