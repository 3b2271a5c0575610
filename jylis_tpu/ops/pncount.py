"""PNCOUNT: positive/negative counter lattice as batched TPU kernels.

Semantics (docs/_docs/types/pncount.md:49-55): two grow-only per-replica
maps, P and N, converged independently by per-replica max; the value is
sum(P) - sum(N) as a signed 64-bit integer. Reference repo:
jylis/repo_pncount.pony:26-67 (INC grows P, DEC grows N, GET nets them).

Layout mirrors gcount with the polarities side by side: the keyspace is
the (K, 2R) u64 matrix ``[P | N]``, stored as one u32 plane of (K, 4R)
cells (ops/planes.py): per row P's high words, N's high words, P's low
words, N's low words. Joining it is gcount's join over 2R columns, so a
batched converge is ONE gather -> joint max -> scatter composite for both
polarities. This type is the north-star benchmark target (BASELINE.json:
1M-key, 64-replica anti-entropy: a row is 256 lanes, two whole tiles).
Batches must carry UNIQUE key rows (serving repos guarantee it via their
pending dicts).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from . import gcount, planes

I64 = jnp.int64


def init(num_keys: int, num_replicas: int) -> jax.Array:
    """Empty keyspace: (K, 4R) u32 cells."""
    return gcount.init(num_keys, 2 * num_replicas)


def from_counts(p, n) -> jax.Array:
    """Build from (K, R) u64 ndarrays of each polarity (tests / interop)."""
    return gcount.from_counts(np.concatenate([np.asarray(p), np.asarray(n)], axis=1))


join = gcount.join
converge_batch = gcount.converge_batch
increment = gcount.increment  # INC grows a P column


def value(cells: jax.Array) -> jax.Array:
    """Signed net values of (..., 4R) rows.

    Computed with u64 wraparound then bitcast to int64, matching the
    reference's Pony (p_sum - n_sum).i64() modular behavior
    (repo_pncount.pony:55-57).
    """
    hi, lo = planes.halves(cells)
    r = hi.shape[-1] // 2
    p = planes.rowsum64(hi[..., :r], lo[..., :r])
    n = planes.rowsum64(hi[..., r:], lo[..., r:])
    return jax.lax.bitcast_convert_type(p - n, I64)


def drain_batch(state: jax.Array, key_idx: jax.Array, d: jax.Array):
    """Join a (B, 4R) delta batch at UNIQUE (B,) key rows and return
    (state, the batch rows' net values), summed from the joined rows."""
    state, rows = planes.scatter_join(state, key_idx, d)
    return state, value(rows)


def decrement(
    state: jax.Array, key_idx: jax.Array, replica_idx: jax.Array, amount: jax.Array
) -> jax.Array:
    """DEC at UNIQUE (key, replica) coordinates: grows the N column."""
    return gcount.increment(
        state, key_idx, replica_idx + state.shape[1] // 4, amount
    )


def read(state: jax.Array, key_idx: jax.Array) -> jax.Array:
    """GET for a batch of keys: signed net value."""
    return value(state[key_idx])


def read_all(state: jax.Array) -> jax.Array:
    return value(state)


def grow(state: jax.Array, num_keys: int, num_replicas: int) -> jax.Array:
    if state.shape == (num_keys, 4 * num_replicas):
        return state
    return planes.grow_cells(state, num_keys, 4, num_replicas)
