"""Pure-jnp oracle for ``row_add``: one scatter-add of possibly repeated rows."""

from __future__ import annotations


def row_add_ref(table, rows, updates):
    """table (R, d), rows (n,) int32, updates (n, d) → table with
    ``updates[i]`` added to row ``rows[i]`` for every i."""
    return table.at[rows].add(updates.astype(table.dtype))
