"""Production mesh construction.

Defined as functions (never module-level constants) so importing this module
never touches jax device state — the dry-run sets
``--xla_force_host_platform_device_count`` *before* any jax initialisation.
"""

from __future__ import annotations

import jax


def _auto_axes(n_axes: int) -> tuple:
    """Every axis ``Auto``: sharding propagates through jit as before 0.9."""
    return (jax.sharding.AxisType.Auto,) * n_axes


def make_production_mesh(*, multi_pod: bool = False):
    """16×16 = 256 chips per pod; 2 pods = 512 chips when ``multi_pod``."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=_auto_axes(len(axes)))


def make_mesh(shape, axes):
    """Arbitrary mesh with every axis ``Auto``."""
    return jax.make_mesh(tuple(shape), tuple(axes), axis_types=_auto_axes(len(axes)))


def flat_mesh(axis: str = "data", devs=None):
    """One flat axis over ``devs`` — defaulting to the **global** device
    pool (``jax.devices()``), which under ``jax.distributed`` spans every
    process, never just the local one. Prefer this over hand-rolling
    ``Mesh(jax.local_devices(), ...)``: a process-local mesh silently
    excludes the rest of the fleet and breaks cross-process collectives.
    """
    from repro.core.strategy import flat_mesh as _flat

    return _flat(list(devs) if devs is not None else jax.devices(), axis)
