"""The sharded fit cell's comparison: a sound run is correct; the control
and each fault that a sharded fit can have come out not correct.

The harness drives ``fit.wiki60m.4chip`` cut to a test size on four host
devices, in a child process (this one keeps its single device), with the
timed path broken underneath where a fault is planted: θ left unchanged,
the loss over half of each batch, four rows of the last cell kept where
they started, and the means' all-gather left out (each shard sees its own
cells' means in every slot). The reference's control (bfloat16) and its
planted faults, as ``calibrate_sharded.py`` puts them in the program's
place, are compared with the reference too.
"""

from __future__ import annotations

import contextlib
import io
import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(os.path.dirname(BENCH))
CELL = "fit.wiki60m.4chip"
# the cell cut for the CPU, widths kept: 16 steps a shard, 2 cells a shard
TINY = dict(n_points=4000, n_clusters=8, batch_size=256, steps_per_epoch=64)


def _state_unchanged():
    from repro.core.strategy import ShardedStrategy

    orig = ShardedStrategy.run_epoch
    ShardedStrategy.run_epoch = lambda self, theta, *a: (theta, orig(self, theta, *a)[1])
    return lambda: setattr(ShardedStrategy, "run_epoch", orig)


def _half_batch():
    from repro.core import losses

    orig = losses.nomad_step_term

    def first_half(ti, tp, pw, tn, nw, means, cell_w, own, impl=None):
        h = ti.shape[0] // 2
        return orig(ti[:h], tp[:h], pw[:h], tn[:h], nw[:h], means, cell_w, own[:h], impl)

    losses.nomad_step_term = first_half
    return lambda: setattr(losses, "nomad_step_term", orig)


def _few_rows():
    from repro.core.strategy import ShardedStrategy

    orig = ShardedStrategy.run_epoch

    def run_epoch(self, theta, *a):
        out, loss = orig(self, theta, *a)
        first = theta.shape[0] - theta.shape[0] // self._idx["counts"].shape[0]
        return out.at[first:first + 4].set(theta[first:first + 4]), loss

    ShardedStrategy.run_epoch = run_epoch
    return lambda: setattr(ShardedStrategy, "run_epoch", orig)


def _gather_skipped():
    import jax
    import jax.numpy as jnp

    orig = jax.lax.all_gather

    def own_only(x, axis_name, *, axis=0, tiled=False, **kw):
        n = jax.lax.psum(1, axis_name)
        return jnp.concatenate([x] * n, axis=axis) if tiled else jnp.stack([x] * n, axis)

    jax.lax.all_gather = own_only
    return lambda: setattr(jax.lax, "all_gather", orig)


FAULTS = {
    "state_unchanged": _state_unchanged,
    "half_batch": _half_batch,
    "few_rows": _few_rows,
    "gather_skipped": _gather_skipped,
}


def _report() -> dict:
    for p in (BENCH, os.path.join(ROOT, "src")):
        sys.path.insert(0, p)
    import jax
    import jax.numpy as jnp

    import calibrate_sharded
    import run
    from drivers import fit_sharded as fs
    from drivers.fit import compare

    cell = run.find_cell(CELL)
    cell.config = dict(cell.config, **TINY)
    chips = lambda n: jax.devices()[:n]  # noqa: E731

    def drive(seed=4_000_000_007):
        out = io.StringIO()
        with contextlib.redirect_stdout(out):
            rc = run.main(
                ["--workload", CELL, "--seed", str(seed), "--seconds", "0.3", "--trace", "0"],
                chips=chips, find=lambda name: cell,
            )
        return dict(json.loads(out.getvalue().strip().splitlines()[-1]), rc=rc)

    report = {"devices": jax.device_count(), "sound": drive(), "sound_large_seed": drive(2**31 + 5)}
    for name, plant in FAULTS.items():
        undo = plant()
        try:
            report[name] = drive()
        finally:
            undo()

    cfg = fs.sharded_config(cell.config)
    mesh = fs.mesh_of(cell.config, chips(4))
    seed = 11
    want = fs.readings(cfg, cell.traffic, seed, mesh, fs.reference_fn(cfg, seed, 4))
    report["reference"] = {}
    for kind, kw in (("control_bf16", dict(dtype=jnp.bfloat16)),
                     ("fault_half_batch", dict(heads_kept=cfg.batch_size // 2)),
                     ("fault_own_means_only", dict(own_means_only=True))):
        got = fs.readings(cfg, cell.traffic, seed, mesh, fs.reference_fn(cfg, seed, 4, **kw))
        report["reference"][kind] = {c.name: [c.value, c.limit] for c in compare(got, want, cell.limits)}

    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        calibrate_sharded.main(
            ["--workload", CELL, "--seeds", "3", "--control-seeds", "3"],
            chips=chips, find=lambda name: cell,
        )
    report["calibrate"] = [json.loads(line) for line in out.getvalue().splitlines()]
    return report


@pytest.fixture(scope="module")
def report():
    env = dict(os.environ, XLA_FLAGS="--xla_force_host_platform_device_count=4", JAX_PLATFORMS="cpu")
    r = subprocess.run(
        [sys.executable, os.path.abspath(__file__)],
        capture_output=True, text=True, timeout=900, env=env, cwd=ROOT,
    )
    assert r.returncode == 0, r.stderr[-3000:]
    return json.loads(r.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("run_name", ["sound", "sound_large_seed"])
def test_sound_run_is_correct(report, run_name):
    assert report["devices"] == 4
    res = report[run_name]
    assert res["rc"] == 0 and res["correct"] is True, res["checks"]
    assert res["device"]["count"] == 4
    assert res["attempted"] >= 1 and res["failed"] == 0
    assert set(res["metrics"]) == {"fit_points_per_s", "setup_s"}
    assert list(res)[-2:] == ["checks", "rc"]  # the harness printed checks last


@pytest.mark.parametrize(
    "fault, caught_by",
    [("state_unchanged", "update1_gap"), ("half_batch", "loss_gap"),
     ("few_rows", "moved_apart"), ("gather_skipped", "loss_gap")],
)
def test_fault_is_not_correct(report, fault, caught_by):
    res = report[fault]
    assert res["rc"] == 0 and res["correct"] is False, res["checks"]
    c = res["checks"][caught_by]
    assert c["value"] > c["limit"], res["checks"]


@pytest.mark.parametrize("kind", ["control_bf16", "fault_half_batch", "fault_own_means_only"])
def test_reference_control_and_faults_are_not_correct(report, kind):
    checks = report["reference"][kind]
    assert any(value > limit for value, limit in checks.values()), checks


def test_calibration_reads_every_kind(report):
    kinds = [line["kind"] for line in report["calibrate"]]
    assert kinds == ["program", "fault_few_rows", "control_bf16", "fault_half_batch",
                     "fault_own_means_only"]
    program = report["calibrate"][0]["gaps"]
    assert program["moved_apart"] == 0 and program["loss_gap"] < 2e-5
    assert report["calibrate"][1]["gaps"]["moved_apart"] == 4


GATHER = ("%all-gather-start.1 = (f32[2048,2]{0,1}, f32[8192,2]{0,1}) all-gather-start("
          "f32[2048,2]{0,1} %m), channel_id=1, replica_groups={{0,1,2,3}}, dimensions={0}")
GATHER_DONE = "%all-gather-done.1 = f32[8192,2]{0,1} all-gather-done((f32[2048,2]{0,1}, f32[8192,2]{0,1}) %s)"
REDUCE = "%all-reduce.2 = f32[] all-reduce(f32[] %l), channel_id=2, replica_groups={{0,1,2,3}}, to_apply=%add"
FUSION = "%fusion.9 = f32[800,2]{0,1} fusion(f32[800,2]{0,1} %t, s32[96]{0} %i), kind=kCustom"


def test_collective_share_reads_the_collectives_over_busy_time():
    for p in (BENCH, os.path.join(ROOT, "src")):
        if p not in sys.path:
            sys.path.insert(0, p)
    import run
    from lib import trace
    from lib.trace import Op

    reader = run.load_module(os.path.join(BENCH, "metrics", "fit.collective_share.py"), "cs")
    spans = [("bench.window", 0, 1000)]
    # two chips: one waits longer in the gather (a shard out of step)
    ops = {0: [Op(FUSION, 0, 800), Op(GATHER, 800, 820), Op(GATHER_DONE, 820, 850),
               Op(REDUCE, 850, 860)],
           1: [Op(FUSION, 0, 600), Op(GATHER, 600, 620), Op(GATHER_DONE, 620, 850),
               Op(REDUCE, 850, 860)]}
    red = trace.reduce(ops, spans, window_span="bench.window", n_devices=2)
    assert reader.read({"trace": red}) == pytest.approx(100 * (60 + 260) / 2 / 860)
    alone = trace.reduce({0: [Op(FUSION, 0, 800)]}, spans, window_span="bench.window")
    assert reader.read({"trace": alone}) is None


if __name__ == "__main__":
    print(json.dumps(_report()))
