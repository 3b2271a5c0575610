"""Two-plane u32 emulation of u64 counter tensors.

TPUs have no native 64-bit integer datapath: XLA emulates u64, and the
emulation is catastrophic exactly on the ops this framework is hottest on
(an early round recorded, on (1M,64) tensors, a u64 scatter at ~4x the
u32 scatter and a u64 row-sum reduce ~25x; not re-measured on a local
chip). So the counter keyspaces
store ``hi``/``lo`` u32 planes and do every heavy op in u32:

* **join (per-entry u64 max):** joint lexicographic compare of (hi, lo) —
  a handful of u32 compare/selects.
* **converge (scatter-merge):** gather current planes at the batch rows,
  join on the batch, scatter-SET both planes back with
  ``unique_indices=True``. A u64 scatter-max never happens. Requires
  unique rows per batch — which the serving repos guarantee (per-key
  pending dicts coalesce first); `coalesce` is the host-side helper for
  any caller that can't.
* **read (row sums):** each u32 plane splits into u16 halves summed in
  u32 (exact for up to 2^16 replica columns), recombined into u64 only on
  the tiny (K,) result.

All functions are pure and jittable.
"""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np

U32 = jnp.uint32
U64 = jnp.uint64


# ---- host-side helpers -----------------------------------------------------


def split64_np(x: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    """u64 ndarray -> (hi, lo) u32 ndarrays."""
    x = np.asarray(x, dtype=np.uint64)
    return (x >> np.uint64(32)).astype(np.uint32), x.astype(np.uint32)


def combine64_np(hi: np.ndarray, lo: np.ndarray) -> np.ndarray:
    return (np.asarray(hi, np.uint64) << np.uint64(32)) | np.asarray(lo, np.uint64)


def coalesce(key_idx: np.ndarray, deltas: np.ndarray):
    """Max-combine duplicate rows of a (B,) x (B, R) u64 delta batch on the
    host, returning unique rows + combined deltas (what converge requires)."""
    key_idx = np.asarray(key_idx)
    uniq, inv = np.unique(key_idx, return_inverse=True)
    out = np.zeros((len(uniq),) + deltas.shape[1:], np.uint64)
    np.maximum.at(out, inv, np.asarray(deltas, np.uint64))
    return uniq.astype(key_idx.dtype), out


# ---- device-side primitives ------------------------------------------------


def join_max(a_hi, a_lo, b_hi, b_lo):
    """Elementwise u64 max over plane pairs (joint lexicographic compare)."""
    take_b = (b_hi > a_hi) | ((b_hi == a_hi) & (b_lo > a_lo))
    return jnp.where(take_b, b_hi, a_hi), jnp.where(take_b, b_lo, a_lo)


def add_carry(a_hi, a_lo, b_hi, b_lo):
    """Elementwise u64 add with wraparound (Pony U64 overflow posture)."""
    lo = a_lo + b_lo
    carry = (lo < b_lo).astype(U32)
    return a_hi + b_hi + carry, lo


def scatter_join(hi, lo, key_idx, d_hi, d_lo):
    """Join a delta batch into (K, ...) planes at UNIQUE rows: gather ->
    joint max -> two u32 scatter-sets (mode="drop" for pad rows)."""
    cur_hi = hi[key_idx]
    cur_lo = lo[key_idx]
    new_hi, new_lo = join_max(cur_hi, cur_lo, d_hi, d_lo)
    return (
        hi.at[key_idx].set(new_hi, mode="drop", unique_indices=True),
        lo.at[key_idx].set(new_lo, mode="drop", unique_indices=True),
    )


def rowsum64(hi, lo) -> jnp.ndarray:
    """Sum of u64 values along the last axis, without u64 reductions:
    u16-split each plane, sum in u32, recombine on the small result.
    Exact for up to 2^16 summands (replica columns)."""
    mask = jnp.uint32(0xFFFF)

    def _split_sum(x):
        lo16 = jnp.sum(x & mask, axis=-1, dtype=U32).astype(U64)
        hi16 = jnp.sum(x >> jnp.uint32(16), axis=-1, dtype=U32).astype(U64)
        return lo16 + (hi16 << jnp.uint64(16))

    return _split_sum(lo) + (_split_sum(hi) << jnp.uint64(32))
