"""Registry-driven Pallas kernel micro-benchmarks + fused-step comparison.

Enumerates :mod:`repro.kernels.registry` — every registered kernel is timed
on its declared ``bench_shapes`` working point, Pallas path vs jnp oracle
at equal shapes. On a device with a row in
:data:`repro.roofline.analysis.PEAKS`, kernels with a ``cost_model`` get
achieved-vs-roofline columns (GFLOP/s, GB/s, fraction of the roofline
bound); elsewhere (the CPU) those columns are left out, since a CPU time
says nothing about the chip.

``step_compare`` times the production question behind the fusion: ONE
jitted SGD step through the fused ``nomad_step`` dispatch vs the same
mathematics as SEPARATE jitted registry passes (gather | mean term |
contrastive grad | mean-term VJP | scatter) with a host sync — an HBM
round-trip on device — between each. That layout is what a non-fused
registry forces, and the fused step must beat it on any backend.

  PYTHONPATH=src python benchmarks/kernel_micro.py             # run + CSV
  PYTHONPATH=src python benchmarks/kernel_micro.py --list      # enumerate
  PYTHONPATH=src python benchmarks/kernel_micro.py --autotune  # sweep grids
  PYTHONPATH=src python benchmarks/kernel_micro.py --report    # per-candidate roofline
  PYTHONPATH=src python benchmarks/kernel_micro.py --json out.json  # regression gate
"""

from __future__ import annotations

import argparse
import json
import sys
import time

import jax
import jax.numpy as jnp

from repro.core import losses
from repro.kernels import autotune, registry
from repro.roofline.analysis import PEAKS, kernel_roofline


def _time(fn, *args, reps=3):
    jax.block_until_ready(fn(*args))  # compile
    t0 = time.time()
    for _ in range(reps):
        jax.block_until_ready(fn(*args))
    return (time.time() - t0) / reps * 1e6


def _shape_label(sig) -> str:
    """Lossless: one dims-group per argument — "1024x256-1024x256"."""
    return "-".join("x".join(str(d) for d in shape) for shape, _dt in sig)


def _device_peaks():
    """The PEAKS row of this device, or None where it has none (the CPU)."""
    return PEAKS.get(jax.devices()[0].device_kind)


def _roofline_cols(spec, us):
    """" gflops=… gbs=… bound=… roofline_frac=…", or "" without a cost
    model or without published peaks for this device."""
    hw = _device_peaks()
    if spec.cost_model is None or us is None or hw is None:
        return "", None
    cost = spec.cost_model(spec.bench_shapes)
    rl = kernel_roofline(cost["flops"], cost["bytes"], us, hw)
    txt = (
        f" gflops={rl['gflops']:.2f} gbs={rl['gbs']:.2f}"
        f" bound={rl['bound']} roofline_frac={rl['roofline_frac']:.2e}"
    )
    return txt, rl


def run(quick: bool = False):
    """[(name, us_per_call, derived), …] — one pallas + one oracle row per
    registered kernel (benchmarks/run.py contract), then the step compare."""
    rows = []
    for name in registry.names():
        spec = registry.get(name)
        args = spec.make_inputs(jax.random.key(0), spec.bench_shapes)
        label = _shape_label(spec.bench_shapes)
        if spec.pallas is not None:
            tiles = spec.tiles_for_backend(registry.backend())
            mode = "interpret" if registry.interpret_default() else "compiled"
            pallas_fn = lambda *a: spec.pallas(*a, tiles=tiles, interpret=registry.interpret_default())
            us = _time(pallas_fn, *args)
            cols, _ = _roofline_cols(spec, us)
            rows.append((f"kernel/{name}_{label}", us, mode + cols))
        rows.append((f"kernel/{name}_ref", _time(jax.jit(spec.ref), *args), "oracle"))
    rows.extend(step_compare(quick=quick))
    return rows


# ---------------------------------------------------------------------------
# Fused step vs multi-pass step
# ---------------------------------------------------------------------------


def step_compare(
    n_points: int = 50_000,
    batch: int = 4096,
    k: int = 15,
    s_neg: int = 16,
    n_cells: int = 64,
    d: int = 2,
    reps: int = 5,
    quick: bool = False,
):
    """Time one NOMAD SGD step, fused vs staged, at N ≥ 50k.

    Both variants run the backend's production implementation (registry
    ``impl=None`` → auto), so the measured gap is pure *structure*: one
    compiled computation vs five dispatches with a host sync (device: an
    HBM round-trip) between every pair.
    """
    if quick:
        n_points, batch = 50_000, 2048
    keys = jax.random.split(jax.random.key(0), 8)
    theta = jax.random.normal(keys[0], (n_points, d), jnp.float32)
    rows_i = jax.random.randint(keys[1], (batch,), 0, n_points)
    pos_rows = jax.random.randint(keys[2], (batch, k), 0, n_points)
    neg_rows = jax.random.randint(keys[3], (batch, s_neg), 0, n_points)
    pos_w = jax.random.uniform(keys[4], (batch, k), jnp.float32)
    means = jax.random.normal(keys[5], (n_cells, d), jnp.float32)
    cell_w = jax.random.uniform(keys[6], (n_cells,), jnp.float32)
    own = jax.random.randint(keys[7], (batch,), 0, n_cells)
    neg_w = jnp.full((batch, s_neg), 1.0 / s_neg, jnp.float32)
    lr = 0.05
    impl = None  # auto: jnp on CPU, pallas on TPU/GPU — same for both variants

    @jax.jit
    def fused_step(theta):
        th_i, th_pos, th_neg = theta[rows_i], theta[pos_rows], theta[neg_rows]

        def loss_fn(ti, tp, tn):
            return jnp.mean(
                losses.nomad_step_term(ti, tp, pos_w, tn, neg_w, means, cell_w, own, impl)
            )

        loss, (g_i, g_pos, g_neg) = jax.value_and_grad(loss_fn, (0, 1, 2))(
            th_i, th_pos, th_neg
        )
        theta = theta.at[rows_i].add(-lr * g_i)
        theta = theta.at[pos_rows.reshape(-1)].add(-lr * g_pos.reshape(-1, d))
        theta = theta.at[neg_rows.reshape(-1)].add(-lr * g_neg.reshape(-1, d))
        return theta, loss

    # --- the same math as separate jitted registry passes -----------------
    gather = jax.jit(lambda th: (th[rows_i], th[pos_rows], th[neg_rows]))
    mean_fwd = jax.jit(lambda ti: losses.nomad_mean_term(ti, means, cell_w, own, impl))

    def _contrastive(ti, tp, tn, mt):
        return losses.contrastive_loss(ti, tp, pos_w, mt, tn, neg_w)

    contrastive_vg = jax.jit(jax.value_and_grad(_contrastive, (0, 1, 2, 3)))

    def _mean_vjp(ti, g_mt):
        _, vjp = jax.vjp(lambda t: losses.nomad_mean_term(t, means, cell_w, own, impl), ti)
        return vjp(g_mt)[0]

    mean_vjp = jax.jit(_mean_vjp)

    @jax.jit
    def scatter(theta, g_i, g_pos, g_neg):
        theta = theta.at[rows_i].add(-lr * g_i)
        theta = theta.at[pos_rows.reshape(-1)].add(-lr * g_pos.reshape(-1, d))
        theta = theta.at[neg_rows.reshape(-1)].add(-lr * g_neg.reshape(-1, d))
        return theta

    def multipass_step(theta):
        th_i, th_pos, th_neg = jax.block_until_ready(gather(theta))
        m_tilde = jax.block_until_ready(mean_fwd(th_i))
        loss, (g_i, g_pos, g_neg, g_mt) = jax.block_until_ready(
            contrastive_vg(th_i, th_pos, th_neg, m_tilde)
        )
        g_i = g_i + jax.block_until_ready(mean_vjp(th_i, g_mt))
        theta = jax.block_until_ready(scatter(theta, g_i, g_pos, g_neg))
        return theta, loss

    us_fused = _time(fused_step, theta, reps=reps)
    us_multi = _time(lambda th: multipass_step(th), theta, reps=reps)
    speedup = us_multi / us_fused if us_fused > 0 else float("inf")
    label = f"N{n_points}_B{batch}"
    return [
        (f"step/nomad_fused_{label}", us_fused, "one jitted step (fused dispatch)"),
        (f"step/nomad_multipass_{label}", us_multi, "5 jitted stages + host sync"),
        (f"step/nomad_fused_speedup_{label}", speedup, "multipass_us / fused_us (x)"),
    ]


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------


def _report():
    """Per-candidate sweep with achieved-vs-roofline columns."""
    for name in registry.names():
        spec = registry.get(name)
        if spec.pallas is None:
            print(f"{name}: jnp-only (no tile grid)")
            continue
        entry = autotune.sweep(spec, spec.bench_shapes, report=True)
        hw = _device_peaks()
        cost = spec.cost_model(spec.bench_shapes) if spec.cost_model and hw else None
        print(f"{name} @ {_shape_label(spec.bench_shapes)} (winner {entry['tiles']}):")
        for cand in entry.get("candidates", []):
            line = f"  tiles={cand['tiles']} us={cand['us']:.1f}"
            if cost:
                rl = kernel_roofline(cost["flops"], cost["bytes"], cand["us"], hw)
                line += (
                    f" gflops={rl['gflops']:.2f} gbs={rl['gbs']:.2f}"
                    f" bound={rl['bound']} roofline_us={rl['roofline_us']:.3f}"
                    f" roofline_frac={rl['roofline_frac']:.2e}"
                )
            print(line)


def _json_report(rows) -> dict:
    """wall_s-leaved layout for benchmarks/check_regression.py."""
    out = {"kernels": {}, "step": {}}
    for name, us, derived in rows:
        group, _, leaf = name.partition("/")
        if "speedup" in leaf:
            out["step"][leaf] = {"x": us, "note": derived}
            continue
        bucket = out["kernels"] if group == "kernel" else out["step"]
        bucket[leaf] = {"wall_s": us * 1e-6, "us": us, "note": derived}
    return out


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--list", action="store_true", help="enumerate registry kernels")
    ap.add_argument("--autotune", action="store_true", help="sweep each kernel's tile grid")
    ap.add_argument(
        "--report", action="store_true", help="sweep + achieved-vs-roofline per candidate"
    )
    ap.add_argument("--json", metavar="PATH", help="write wall_s report for the CI gate")
    ap.add_argument("--quick", action="store_true")
    args = ap.parse_args()

    if args.list:
        for name in registry.names():
            spec = registry.get(name)
            print(
                f"{name}: bench={_shape_label(spec.bench_shapes)} "
                f"candidates={len(spec.tile_candidates)} "
                f"default_tiles={dict(spec.tiles_for_backend(registry.backend()))} "
                f"cost_model={'yes' if spec.cost_model else 'no'}"
            )
        return 0

    if args.report:
        _report()
        return 0

    if args.autotune:
        # same policy as autotune.tiles_for: interpret-mode wall-times say
        # nothing about Mosaic, so don't poison the shippable cache with
        # them unless the user forces REPRO_AUTOTUNE=1.
        cache = autotune.autotune_enabled()
        for name in registry.names():
            spec = registry.get(name)
            if spec.pallas is None:
                continue
            entry = autotune.sweep(spec, spec.bench_shapes)
            if cache:
                autotune.record(spec, spec.bench_shapes, entry)
            print(f"{name}: winner={entry['tiles']} us={entry.get('us')}")
        if cache:
            print(f"# winners cached at {autotune.cache_path()}")
        else:
            print("# interpret mode: winners NOT cached (REPRO_AUTOTUNE=1 forces)")
        return 0

    rows = run(quick=args.quick)
    for r in rows:
        print(",".join(str(c) for c in r))
    if args.json:
        with open(args.json, "w") as f:
            json.dump(_json_report(rows), f, indent=1, sort_keys=True)
        print(f"# wrote {args.json}")
    return 0


if __name__ == "__main__":
    from repro.runtime import enable_compile_cache

    enable_compile_cache()
    sys.exit(main())
