"""u32 emulation of u64 counter tensors: one lane-dense plane a keyspace.

TPUs have no native 64-bit integer datapath: XLA emulates u64, and the
emulation is catastrophic exactly on the ops this framework is hottest on
(an early round recorded, on (1M,64) tensors, a u64 scatter at ~4x the
u32 scatter and a u64 row-sum reduce ~25x; not re-measured on a local
chip). So a counter keyspace, a u64 matrix ``x[key, column]`` of C
columns, is stored as ONE u32 plane of ``cells[key, 2C]``: the high words
of the row's C columns, then their low words (``pack64_np``). One plane
and not a hi and a lo plane, because of what the TPU does with a narrow
minor dimension: an array whose rows are not whole 128-lane tiles is kept
column-major on the device, and a row gather out of a column-major plane
of 64 columns makes the compiler transpose the WHOLE plane first (PERF.md,
PR 29: eight 256 MiB copies a drain). At the north star's 64 replica ids
a GCOUNT row is 128 lanes and a PNCOUNT row (P columns, then N) 256, so a
key's row is whole tiles, row-major, and a sparse drain touches its rows
and nothing else. Every heavy op stays in u32:

* **join (per-entry u64 max):** joint lexicographic compare of (hi, lo) —
  a handful of u32 compare/selects.
* **converge (scatter-merge):** gather the batch rows' cells, join on the
  batch, scatter-SET the joined rows back with ``unique_indices=True``. A
  u64 scatter-max never happens. Requires unique rows per batch — which
  the serving repos guarantee (per-key pending dicts coalesce first);
  `coalesce` is the host-side helper for any caller that can't. The
  joined rows are returned too: a drain sums THEM, not a second gather.
* **read (row sums):** each u32 half splits into u16 halves summed in
  u32 (exact for up to 2^16 replica columns), recombined into u64 only on
  the tiny (K,) result.

All functions are pure and jittable.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

U32 = jnp.uint32
U64 = jnp.uint64


def halves(cells):
    """(..., 2C) cells -> their (hi, lo) word halves, (..., C) each."""
    c = cells.shape[-1] // 2
    return cells[..., :c], cells[..., c:]


# ---- host-side helpers -----------------------------------------------------


def split64_np(x: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    """u64 ndarray -> (hi, lo) u32 ndarrays."""
    x = np.asarray(x, dtype=np.uint64)
    return (x >> np.uint64(32)).astype(np.uint32), x.astype(np.uint32)


def combine64_np(hi: np.ndarray, lo: np.ndarray) -> np.ndarray:
    return (np.asarray(hi, np.uint64) << np.uint64(32)) | np.asarray(lo, np.uint64)


def pack64_np(x: np.ndarray) -> np.ndarray:
    """u64 (..., C) ndarray -> u32 (..., 2C) cells: high words, then low.
    Through a u32 view of the words and never a shifted u64 temporary: a
    boot's dense batch is a gibibyte, and a fresh gibibyte costs the host
    seconds."""
    x = np.ascontiguousarray(x, dtype="<u8")  # little-endian: (lo, hi) pairs
    c = x.shape[-1]
    words = x.view("<u4").reshape(x.shape[:-1] + (c, 2))
    cells = np.empty(x.shape[:-1] + (2 * c,), np.uint32)
    cells[..., :c] = words[..., 1]
    cells[..., c:] = words[..., 0]
    return cells


def unpack64_np(cells: np.ndarray) -> np.ndarray:
    """u32 (..., 2C) cells -> u64 (..., C) ndarray (`pack64_np` undone)."""
    hi, lo = halves(np.asarray(cells))
    x = np.empty(hi.shape, "<u8")
    words = x.view("<u4").reshape(hi.shape + (2,))
    words[..., 1] = hi
    words[..., 0] = lo
    return x.astype(np.uint64, copy=False)


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


def join_cells(a, b):
    """Elementwise u64 max of two cell arrays of one shape: one verdict a
    cell (joint lexicographic compare of its words), applied to both."""
    (a_hi, a_lo), (b_hi, b_lo) = halves(a), halves(b)
    take_b = (b_hi > a_hi) | ((b_hi == a_hi) & (b_lo > a_lo))
    return jnp.where(jnp.concatenate([take_b, take_b], axis=-1), b, a)


def scatter_join(cells, key_idx, d):
    """Join a (B, 2C) delta batch into the (K, 2C) plane at UNIQUE rows:
    gather -> joint max -> one u32 scatter-set (mode="drop" for pad rows).
    Returns the plane and the joined (B, 2C) rows."""
    new = join_cells(cells[key_idx], d)
    return cells.at[key_idx].set(new, mode="drop", unique_indices=True), new


@partial(jax.jit, static_argnames=("num_keys", "segments", "width"))
def grow_cells(cells, num_keys: int, segments: int, width: int):
    """Capacity growth (zeros are the lattice identity): the plane's rows
    are ``segments`` runs of equal width (hi and lo words; for PNCOUNT of
    each polarity), and every run widens to ``width`` columns. One program,
    so the grown plane is written once and no second one is ever live."""
    k, w = cells.shape
    old = w // segments
    out = jnp.zeros((num_keys, segments * width), U32)
    for s in range(segments):
        out = out.at[:k, s * width : s * width + old].set(
            cells[:, s * old : (s + 1) * old]
        )
    return out


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


def rowsum_cells(cells) -> jnp.ndarray:
    """`rowsum64` over all C columns of (..., 2C) cells."""
    return rowsum64(*halves(cells))
