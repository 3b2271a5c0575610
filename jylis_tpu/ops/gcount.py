"""GCOUNT: grow-only counter lattice as batched TPU kernels.

Semantics (docs/_docs/types/gcount.md:43-47): state is a map
replica-id -> u64; join takes the per-replica max; the counter's value is the
sum over replicas. Driven by the reference repo at
jylis/repo_gcount.pony:25-60 (INC adds to this node's entry, GET sums).

TPU-native layout: the whole keyspace for the type is ONE dense tensor
``counts[key, replica]``, stored as one u32 plane of ``(K, 2R)`` cells, a
row's R high words and then its R low words (ops/planes.py: XLA's u64
emulation is 4-25x slower on exactly the scatter/reduce ops this path
lives on, and a row of whole 128-lane tiles is what keeps a sparse drain
off the rest of the plane). The per-key sequential converge loop of the
reference (repo_manager.pony:92-93) becomes a single gather -> joint-max
-> scatter composite over the batch — one fused XLA launch regardless of
batch size, which is the BASELINE.json north star.

Batches must carry UNIQUE key rows (the serving repos' pending dicts
guarantee it; `planes.coalesce` is the host helper otherwise).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from . import planes

U32 = jnp.uint32


def init(num_keys: int, num_replicas: int) -> jax.Array:
    """Empty keyspace: (K, 2R) u32 cells."""
    return jnp.zeros((num_keys, 2 * num_replicas), U32)


def from_counts(counts) -> jax.Array:
    """Build from a (K, R) u64 ndarray (tests / interop)."""
    return jnp.asarray(planes.pack64_np(counts))


def to_counts(state: jax.Array) -> np.ndarray:
    return planes.unpack64_np(state)


def join(a: jax.Array, b: jax.Array) -> jax.Array:
    """Full-state lattice join: elementwise per-replica u64 max."""
    return planes.join_cells(a, b)


def value(cells: jax.Array) -> jax.Array:
    """Counter values of (..., 2R) rows: row sums, u64 with wraparound."""
    return planes.rowsum_cells(cells)


def drain_batch(state: jax.Array, key_idx: jax.Array, d: jax.Array):
    """Join a batch of per-key deltas in one fused composite and return
    (state, the batch rows' values): the sums come from the joined rows the
    composite already holds, so the plane is read once and written once.

    key_idx: (B,) int32 UNIQUE rows; d: (B, 2R) u32 delta cells (absolute
    per-replica values, delta-CRDT style). Out-of-range rows are dropped,
    matching fire-and-forget delivery (SURVEY.md section 2.5)."""
    state, rows = planes.scatter_join(state, key_idx, d)
    return state, value(rows)


def converge_batch(state: jax.Array, key_idx: jax.Array, d: jax.Array) -> jax.Array:
    """`drain_batch` without the read-back."""
    return planes.scatter_join(state, key_idx, d)[0]


def increment(
    state: jax.Array,
    key_idx: jax.Array,
    replica_idx: jax.Array,
    amount: jax.Array,
) -> jax.Array:
    """Local INC at UNIQUE (key, replica) coordinates: carry-propagating
    u64 add with wraparound (the reference's Pony u64 overflow posture).
    amount: (B,) uint64 (small host batches — split on device is cheap)."""
    lo_idx = replica_idx + state.shape[1] // 2
    a_hi = (amount >> jnp.uint64(32)).astype(U32)
    a_lo = amount.astype(U32)
    new_hi, new_lo = planes.add_carry(
        state[key_idx, replica_idx], state[key_idx, lo_idx], a_hi, a_lo
    )
    state = state.at[key_idx, replica_idx].set(
        new_hi, mode="drop", unique_indices=True
    )
    return state.at[key_idx, lo_idx].set(new_lo, mode="drop", unique_indices=True)


def read(state: jax.Array, key_idx: jax.Array) -> jax.Array:
    """GET for a batch of keys."""
    return value(state[key_idx])


def read_all(state: jax.Array) -> jax.Array:
    return value(state)


def grow(state: jax.Array, num_keys: int, num_replicas: int) -> jax.Array:
    """Host-side capacity growth (zeros are the lattice identity)."""
    if state.shape == (num_keys, 2 * num_replicas):
        return state
    return planes.grow_cells(state, num_keys, 2, num_replicas)
