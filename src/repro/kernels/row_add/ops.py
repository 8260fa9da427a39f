"""Public op + registry spec: ``row_add``, a row-sorted scatter-add.

``row_add(table, rows, updates)`` is ``table.at[rows].add(updates)`` for
rows that may repeat: the fit step's sparse SGD update of θ. The op sorts
the (row, update) pairs by row (a stable sort, so a row's repeats keep
their order), finds each block's range of them, and hands them to the
sweep kernel of :mod:`repro.kernels.row_add.row_add`, which adds them to
the table in place in that order.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels import registry
from repro.kernels.padding import pad_minor as _pad_minor
from repro.kernels.row_add.ref import row_add_ref
from repro.kernels.row_add.row_add import LANES, row_add_sorted_pallas

DEFAULT_BLOCK = 262144  # table rows per grid step: a (2, 262144) f32 tile is 2 MiB
DEFAULT_UNROLL = 4  # entries whose lane groups are read before any is added
WINDOW = 1024  # entries per SMEM copy: the tiling of a 1-D int32 array on the chip


def row_add(table, rows, updates, *, block: int = DEFAULT_BLOCK,
            unroll: int = DEFAULT_UNROLL, interpret: bool | None = None):
    """``table`` (R, d) float32 with ``updates[i]`` (n, d) added to row
    ``rows[i]`` (n,), for rows in [0, R) that may repeat."""
    if interpret is None:
        interpret = registry.interpret_default()
    R, d = table.shape
    block = min(block, -(-R // LANES) * LANES)
    sorted_ = jax.lax.sort(
        (rows.astype(jnp.int32), *jnp.unstack(updates.astype(jnp.float32), axis=1)),
        num_keys=1,
    )
    rows, cols = sorted_[0], sorted_[1:]
    n_blocks = -(-R // block)
    bounds = jnp.arange(n_blocks + 1, dtype=jnp.int32) * block
    starts = jnp.searchsorted(rows, bounds, side="left").astype(jnp.int32)
    out_t = row_add_sorted_pallas(
        starts,
        _pad_minor(rows, WINDOW),
        tuple(_pad_minor(c, WINDOW) for c in cols),
        table.T,
        block=block,
        window=WINDOW,
        unroll=unroll,
        interpret=interpret,
    )
    return out_t.T


# ---------------------------------------------------------------------------
# Registry spec
# ---------------------------------------------------------------------------


def _pallas_adapter(table, rows, updates, *, tiles, interpret):
    return row_add(
        table, rows, updates, block=tiles.get("block", DEFAULT_BLOCK),
        unroll=tiles.get("unroll", DEFAULT_UNROLL), interpret=interpret,
    )


def _make_inputs(key, sig):
    (ts, tdt), (rs, rdt), (us, udt) = sig
    kt, kr, ku = jax.random.split(key, 3)
    table = jax.random.normal(kt, ts, tdt)
    rows = jax.random.randint(kr, rs, 0, ts[0], rdt)  # repeats, in any order
    updates = jax.random.normal(ku, us, udt)
    return table, rows, updates


def _sig(R, n, d):
    return (((R, d), "float32"), ((n,), "int32"), ((n, d), "float32"))


def _cost_model(sig):
    """The sweep reads and writes the whole table; the entries come in once."""
    (R, d), (n,) = sig[0][0], sig[1][0]
    return {"flops": float(n * d), "bytes": 4.0 * (2 * R * d + n * (1 + d))}


SPEC = registry.register(
    registry.KernelSpec(
        name="row_add",
        ref=row_add_ref,
        pallas=_pallas_adapter,
        tile_candidates=(
            {"block": 131072, "unroll": 4},
            {"block": 262144, "unroll": 4},
            {"block": 262144, "unroll": 8},
        ),
        default_tiles={
            "": {"block": DEFAULT_BLOCK, "unroll": DEFAULT_UNROLL},
            "tpu": {"block": DEFAULT_BLOCK, "unroll": DEFAULT_UNROLL},
        },
        make_inputs=_make_inputs,
        check_shapes=(
            _sig(1000, 3000, 2),  # ragged R: one block past the table's end
            _sig(4096, 64, 2),
            _sig(777, 500, 3),
        ),
        bench_shapes=_sig(1 << 22, 1 << 18, 2),
        tol=(1e-6, 1e-6),
        cost_model=_cost_model,
        dtype_grid=("float32",),
    )
)
