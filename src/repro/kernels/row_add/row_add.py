"""Row-sorted scatter-add Pallas TPU kernel.

A scatter-add whose rows may repeat has to apply its read-modify-writes
one at a time, and on a TPU v5e each costs about 80 ns when XLA emits it
(PERF.md, §6). This kernel applies the same updates, sorted by row, in a
sweep over the table:

* the table crosses the kernel transposed, (d, R), so its rows lie on
  lanes and a block of ``block`` rows is one (d, block) VMEM tile; XLA
  keeps a (R, 2) float32 table in exactly those bytes, so the transpose
  is free;
* the grid walks the blocks; ``starts`` (scalar-prefetched) gives each
  block's range of the sorted entries, which are copied to SMEM a window
  of ``window`` entries at a time;
* the entries are added in order to the (d, 128) lane groups that hold
  their rows, the current group kept in registers. ``unroll`` entries read
  their groups before any is added: sorted entries never come back to a
  group they left, so a group other than the one in registers is
  untouched in memory.

Every element is written back as it was read but the ones an entry adds
to: rows no entry names keep their bits (a masked select, never an add of
0, so −0.0 stays −0.0).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128


def _kernel(starts_ref, rows_hbm, *refs, d, block, window, unroll):
    upd_hbm = refs[:d]
    t_in, t_out, rows_s = refs[d : d + 3]
    upd_s = refs[d + 3 : 2 * d + 3]
    sem = refs[2 * d + 3]

    b = pl.program_id(0)
    t_out[...] = t_in[...]
    s, e = starts_ref[b], starts_ref[b + 1]
    base = b * block
    col = jax.lax.broadcasted_iota(jnp.int32, (d, LANES), 0)
    lane = jax.lax.broadcasted_iota(jnp.int32, (d, LANES), 1)

    def add(carry, js):
        """Add entries ``js`` of the window, in order."""
        cur, cur_off = carry
        todo = []
        for j in js:
            local = rows_s[j] - base
            off = pl.multiple_of((local // LANES) * LANES, LANES)
            delta = jnp.full((d, LANES), upd_s[d - 1][j], jnp.float32)
            for c in range(d - 1):
                delta = jnp.where(col == c, upd_s[c][j], delta)
            todo.append((off, local - off, delta, t_out[:, pl.ds(off, LANES)]))
        for off, at, delta, fresh in todo:
            t_out[:, pl.ds(pl.multiple_of(cur_off, LANES), LANES)] = cur
            group = jnp.where(off == cur_off, cur, fresh)
            cur, cur_off = jnp.where(lane == at, group + delta, group), off
        return cur, cur_off

    def window_(w, carry):
        ws = pl.multiple_of(w * window, window)
        copies = [
            pltpu.make_async_copy(src.at[pl.ds(ws, window)], dst, sem.at[i])
            for i, (src, dst) in enumerate(zip((rows_hbm, *upd_hbm), (rows_s, *upd_s)))
        ]
        for cp in copies:
            cp.start()
        for cp in copies:
            cp.wait()
        lo, hi = jnp.maximum(s - ws, 0), jnp.minimum(e - ws, window)
        full = lo + (hi - lo) // unroll * unroll
        carry = jax.lax.fori_loop(
            0, (hi - lo) // unroll,
            lambda i, c: add(c, [lo + i * unroll + u for u in range(unroll)]),
            carry,
        )
        return jax.lax.fori_loop(full, hi, lambda j, c: add(c, [j]), carry)

    first = (t_out[:, pl.ds(0, LANES)], jnp.int32(0))
    cur, cur_off = jax.lax.fori_loop(s // window, pl.cdiv(e, window), window_, first)
    t_out[:, pl.ds(pl.multiple_of(cur_off, LANES), LANES)] = cur


@functools.partial(jax.jit, static_argnames=("block", "window", "unroll", "interpret"))
def row_add_sorted_pallas(
    starts, rows, cols, table_t, *, block, window, unroll, interpret=False
):
    """table_t (d, R) float32 with the sorted entries added, in place.

    ``rows`` (n,) sorted and ``cols`` (d arrays of (n,)) hold the entries,
    n a multiple of ``window``; ``starts`` (R/block + 1,) the first entry of
    each block.
    """
    d, R = table_t.shape
    any_ = pl.BlockSpec(memory_space=pl.ANY)
    block_spec = pl.BlockSpec((d, block), lambda b, st: (0, b))
    return pl.pallas_call(
        functools.partial(_kernel, d=d, block=block, window=window, unroll=unroll),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(starts.shape[0] - 1,),
            in_specs=[any_] * (1 + d) + [block_spec],
            out_specs=block_spec,
            scratch_shapes=[pltpu.SMEM((window,), jnp.int32)]
            + [pltpu.SMEM((window,), jnp.float32)] * d
            + [pltpu.SemaphoreType.DMA((1 + d,))],
        ),
        out_shape=jax.ShapeDtypeStruct((d, R), jnp.float32),
        input_output_aliases={2 + d: 0},
        interpret=interpret,
        name="row_add",
    )(starts, rows, *cols, table_t)
