"""CPU tests of the chip benchmark's yardstick.

    python -m pytest benchmarks/chip/tests

They never look for a chip: a run is driven with ``chips=`` handing over
the CPU device, at sizes a test run can hold.
"""

from __future__ import annotations

import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(os.path.dirname(BENCH))
for p in (BENCH, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

# a fit small enough for the CPU, with the configuration's widths
TINY_FIT = dict(n_points=4000, n_clusters=8, batch_size=256, steps_per_epoch=16)


@pytest.fixture
def run_mod():
    import run

    return run


@pytest.fixture
def tiny_fit_cell(run_mod):
    """``fit.wiki60m`` cut to :data:`TINY_FIT`, with its committed limits."""

    def make(name: str = "fit.wiki60m"):
        cell = run_mod.find_cell(name)
        cell.config = dict(cell.config, **TINY_FIT)
        return cell

    return make


@pytest.fixture
def drive(run_mod):
    """Run the harness on a given cell on the CPU; returns (rc, result)."""
    import json

    import jax

    def go(cell, capsys, seed: int = 4_000_000_007, seconds: float = 0.5):
        rc = run_mod.main(
            ["--workload", cell.name, "--seed", str(seed), "--seconds", str(seconds), "--trace", "0"],
            chips=lambda n: jax.devices()[:n],
            find=lambda name: cell,
        )
        out = capsys.readouterr().out.strip().splitlines()
        return rc, json.loads(out[-1])

    return go
