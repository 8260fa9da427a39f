"""Multi-device integration tests — run in subprocesses with
``--xla_force_host_platform_device_count=8`` so the main pytest process
keeps its single CPU device (assignment: only the dry-run gets 512).

Covers: distributed-vs-single-device quality parity, determinism,
hierarchical multi-pod, distributed K-means, pipeline parallelism, and the
checkpoint/restart + elastic-resharding path of the NOMAD launcher.
"""

import os
import subprocess
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(mod_args, devices=8, timeout=900):
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    return subprocess.run(
        [sys.executable, "-m", *mod_args],
        capture_output=True,
        text=True,
        timeout=timeout,
        env=env,
        cwd=REPO,
    )


@pytest.mark.slow
def test_distributed_selftest():
    r = _run(["repro.launch.selftest"])
    assert r.returncode == 0, r.stdout[-3000:] + r.stderr[-3000:]
    assert "SELFTEST PASS" in r.stdout


@pytest.mark.slow
def test_pipeline_selftest():
    r = _run(["repro.launch.selftest_pipeline"])
    assert r.returncode == 0, r.stdout[-3000:] + r.stderr[-3000:]
    assert "PIPELINE SELFTEST PASS" in r.stdout


@pytest.mark.slow
def test_train_crash_restart_elastic(tmp_path):
    """Kill the launcher mid-run, restart on FEWER devices from the
    checkpoint, and verify it resumes at the right epoch and finishes."""
    ck = str(tmp_path / "ckpt")
    common = [
        "repro.launch.train",
        "--workload", "nomad_quickstart",
        "--n-points", "4000",
        "--epochs", "6",
        "--checkpoint-dir", ck,
        "--checkpoint-every", "2",
        "--out", str(tmp_path / "emb.npy"),
    ]
    r1 = _run(common + ["--mesh", "2x4", "--fail-at-epoch", "4"])
    assert r1.returncode == 17, r1.stdout[-2000:] + r1.stderr[-2000:]
    assert "CRASH INJECTION" in r1.stdout
    assert "epoch    3" in r1.stdout

    # elastic restart: 8 shards → 4 shards. Async-save durability semantics:
    # a hard crash may lose the single in-flight checkpoint (atomic commit
    # means never a corrupt one), so the resume point is epoch 4 (ckpt 3
    # committed) or epoch 2 (ckpt 3 was still in flight when we _exit'd).
    r2 = _run(common + ["--mesh", "4", "--resume", "--metrics"], devices=4)
    assert r2.returncode == 0, r2.stdout[-2000:] + r2.stderr[-2000:]
    assert "resume: epoch 4" in r2.stdout or "resume: epoch 2" in r2.stdout, r2.stdout
    # fit owns the index now: the resumed run must hit the on-disk cache
    # (fingerprint-checked) rather than rebuild
    assert "index: cache" in r2.stdout, r2.stdout
    emb = np.load(tmp_path / "emb.npy")
    assert emb.shape == (4000, 2) and np.isfinite(emb).all()


def test_spawn_refuses_more_workers_than_chips(monkeypatch):
    """--spawn K simulates a cluster on CPU devices; on an accelerator host
    a chip serves one process, so K above the chip count is refused before
    any worker starts. The platform is steered here, in the test."""
    from repro.launch import distributed as dist

    dist.check_spawn(8, "cpu", 0)  # CPU simulation: any worker count
    dist.check_spawn(1, "tpu", 1)
    with pytest.raises(ValueError, match="1 chip"):
        dist.check_spawn(2, "tpu", 1)

    started = []
    monkeypatch.setattr(dist, "local_platform", lambda: ("tpu", 4))
    monkeypatch.setattr(dist.subprocess, "Popen", lambda *a, **k: started.append(a))
    assert dist.main(["--spawn", "8"]) == 2
    assert started == []
