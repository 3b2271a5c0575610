"""MAP TREG's device table: one row a FIELD, its whole product state.

A field of a `MAP TREG` record is the product lattice (ver, tomb, register)
of ops/compose.py. On the device it is a row of two states this module
only puts side by side, with no join of its own:

* ``cells``: ``ver`` and ``tomb``, per-replica counters joined by pointwise
  max: PNCOUNT's plane (ops/pncount.py: ``[P | N]`` becomes
  ``[ver | tomb]``, (K, 4R) u32) and its join (ops/planes.py
  ``scatter_join`` / ``join_cells``);
* ``reg``: the inner register, TREG's five planes (ops/treg.py) and its
  ``converge_batch`` / ``converge_dense``, the vid plane holding the host
  table's per-row generation and prefix ties settled by the host as
  TREG's are.

So whatever becomes of the counters' or TREG's device mirror (ROADMAP
D1b) becomes of this one: there is no kernel here to keep or to drop.
A batch carries UNIQUE field rows (the host table's pending list).
"""

from __future__ import annotations

from typing import NamedTuple

import jax

from . import planes, pncount, treg


class MapFieldState(NamedTuple):
    cells: jax.Array  # (K, 4R) u32: ver hi | tomb hi | ver lo | tomb lo
    reg: treg.TRegState  # (K,) x 5


def init(num_rows: int, num_replicas: int) -> MapFieldState:
    return MapFieldState(
        pncount.init(num_rows, num_replicas), treg.init(num_rows)
    )


def converge_batch(state: MapFieldState, key_idx, d_cells, *d_reg):
    """Join a batch of field rows at UNIQUE ``key_idx``: the counters'
    join over the cells, TREG's over the register. Returns
    (state, tie mask (B,)) as ``treg.converge_batch`` does."""
    cells, _rows = planes.scatter_join(state.cells, key_idx, d_cells)
    reg, tie = treg.converge_batch(state.reg, key_idx, *d_reg)
    return MapFieldState(cells, reg), tie


def converge_dense(state: MapFieldState, d_cells, *d_reg):
    """The whole table joined elementwise with a batch in row order (a
    restore): each plane streamed once. Returns (state, tie mask (K,))."""
    reg, tie = treg.converge_dense(state.reg, *d_reg)
    return MapFieldState(planes.join_cells(state.cells, d_cells), reg), tie


def read(state: MapFieldState, key_idx):
    """Rows gathered back: (cells (B, 4R), ts_hi, ts_lo, rank_hi,
    rank_lo, vid), what a test compares with the host table."""
    return (state.cells[key_idx],) + tuple(p[key_idx] for p in state.reg)


def grow(state: MapFieldState, num_rows: int, num_replicas: int):
    return MapFieldState(
        pncount.grow(state.cells, num_rows, num_replicas),
        treg.grow(state.reg, num_rows),
    )
