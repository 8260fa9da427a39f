"""The fit cells' comparison: a sound run is correct; the control and each
fault that a fit cell can have come out not correct.

The harness is driven on the CPU at a test size (the look for a chip is
skipped), with the timed path broken underneath where a fault is planted.
"""

import os
import subprocess
import sys

import jax.numpy as jnp
import pytest

from conftest import ROOT, TINY_FIT


def test_sound_run_is_correct(tiny_fit_cell, drive, capsys):
    rc, res = drive(tiny_fit_cell(), capsys)
    assert rc == 0 and res["correct"] is True
    assert res["attempted"] >= 1 and res["failed"] == 0
    assert list(res)[-1] == "checks"
    assert set(res["metrics"]) == {"fit_points_per_s", "setup_s"}


def _state_unchanged(monkeypatch):
    from repro.core.strategy import LocalStrategy

    orig = LocalStrategy.run_epoch
    monkeypatch.setattr(
        LocalStrategy, "run_epoch", lambda self, theta, *a: (theta, orig(self, theta, *a)[1])
    )


def _half_batch(monkeypatch):
    from repro.core import losses

    orig = losses.nomad_loss

    def first_half(theta_i, theta_pos, pos_w, means, counts, cell_of_i, theta_neg, **kw):
        h = theta_i.shape[0] // 2
        return orig(theta_i[:h], theta_pos[:h], pos_w[:h], means, counts, cell_of_i[:h],
                    theta_neg[:h], **kw)

    monkeypatch.setattr(losses, "nomad_loss", first_half)


def _few_rows(monkeypatch):
    """A wrong scatter target for a few rows: four rows of the last cell
    keep the θ the dispatch started from."""
    from repro.core.strategy import LocalStrategy

    orig = LocalStrategy.run_epoch

    def run_epoch(self, theta, *a):
        out, loss = orig(self, theta, *a)
        first = theta.shape[0] - theta.shape[0] // self._idx["counts"].shape[0]
        return out.at[first:first + 4].set(theta[first:first + 4]), loss

    monkeypatch.setattr(LocalStrategy, "run_epoch", run_epoch)


@pytest.mark.parametrize(
    "fault, caught_by",
    [(_state_unchanged, "update1_gap"), (_half_batch, "loss_gap"), (_few_rows, "moved_apart")],
    ids=["state_unchanged", "half_batch", "few_rows"],
)
def test_fault_is_not_correct(fault, caught_by, tiny_fit_cell, drive, capsys, monkeypatch):
    fault(monkeypatch)
    rc, res = drive(tiny_fit_cell(), capsys)
    assert rc == 0
    assert res["correct"] is False, res["checks"]
    c = res["checks"][caught_by]
    assert c["value"] > c["limit"], res["checks"]


@pytest.mark.parametrize("cell", ["fit.wiki60m", "fit.pubmed"])
@pytest.mark.parametrize("seed", [11, 2**31 + 5])
def test_bf16_control_is_not_correct(run_mod, tiny_fit_cell, cell, seed):
    """The reference computed in bfloat16, put in the program's place."""
    fit = run_mod.load_module(os.path.join(ROOT, "benchmarks/chip/drivers/fit.py"), "fit_driver")
    c = tiny_fit_cell(cell)
    cfg = fit.nomad_config(c.config)
    want = fit.readings(cfg, c.traffic, seed, fit.reference_fn(cfg, seed))
    got = fit.readings(cfg, c.traffic, seed, fit.reference_fn(cfg, seed, dtype=jnp.bfloat16))
    checks = fit.compare(got, want, c.limits)
    assert not all(ch.ok for ch in checks), [(ch.name, ch.value, ch.limit) for ch in checks]


def test_no_tpu_exits_nonzero_without_a_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "benchmarks/chip/run.py", "--workload", "fit.wiki60m",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300,
    )
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "TPU" in p.stderr


def test_tiny_size_keeps_the_widths():
    assert set(TINY_FIT) == {"n_points", "n_clusters", "batch_size", "steps_per_epoch"}
