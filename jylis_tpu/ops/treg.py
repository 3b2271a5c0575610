"""TREG: last-writer-wins timestamped register as batched TPU kernels.

Semantics (docs/_docs/types/treg.md:56-63): a register keeps one
(value, timestamp) pair; pair A beats pair B iff ts_A > ts_B, or the
timestamps are equal and value_A > value_B by string sorting rules.
Reference repo: jylis/repo_treg.pony:24-68.

TPU-native layout: the keyspace is parallel vectors — the u64 timestamp
and the u64 order-preserving value-prefix rank (ops/interner.py) each
stored as hi/lo u32 planes (XLA's u64 scatter emulation costs ~150 ms per
1M indices regardless of row width — measured; u32 scatters are ~15x
cheaper), plus ``vid[key] : int32`` (interned value id, -1 = unset). The
value tie-break runs on-device via the rank; batches where ts and rank are
equal but vids differ (a prefix collision) are flagged and resolved on
host with full strings — correctness is exact, the device just fast-paths
the overwhelmingly common case.

Contract: one batch must contain at most one delta per key (the reference
coalesces per-key deltas per flush window, repo_gcount.pony:43-48 pattern);
use ``converge_many`` to fold several replica batches.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

U32 = jnp.uint32
I32 = jnp.int32


class TRegState(NamedTuple):
    ts_hi: jax.Array  # (K,) uint32; 0 when unset
    ts_lo: jax.Array
    rank_hi: jax.Array  # (K,) uint32 value-prefix rank planes; 0 when unset
    rank_lo: jax.Array
    vid: jax.Array  # (K,) int32 interned value id; -1 when unset


def init(num_keys: int) -> TRegState:
    # distinct buffers: drains donate the state
    return TRegState(
        jnp.zeros((num_keys,), U32),
        jnp.zeros((num_keys,), U32),
        jnp.zeros((num_keys,), U32),
        jnp.zeros((num_keys,), U32),
        jnp.full((num_keys,), -1, I32),
    )


def _gt64(a_hi, a_lo, b_hi, b_lo):
    """a > b over hi/lo u32 planes."""
    return (a_hi > b_hi) | ((a_hi == b_hi) & (a_lo > b_lo))


def _eq64(a_hi, a_lo, b_hi, b_lo):
    return (a_hi == b_hi) & (a_lo == b_lo)


def _b_wins(a, b):
    """Where pair B strictly beats pair A, plus an on-host-tie flag.

    a/b: tuples (ts_hi, ts_lo, rank_hi, rank_lo, vid). An unset register
    (vid -1, zeros) loses to any set pair: a set pair has either ts > 0 or
    a real value whose presence beats absence — encoded by treating
    vid >= 0 as a final presence tie-break.
    """
    a_th, a_tl, a_rh, a_rl, a_v = a
    b_th, b_tl, b_rh, b_rl, b_v = b
    ts_eq = _eq64(a_th, a_tl, b_th, b_tl)
    rank_eq = _eq64(a_rh, a_rl, b_rh, b_rl)
    wins = _gt64(b_th, b_tl, a_th, a_tl) | (
        ts_eq
        & (_gt64(b_rh, b_rl, a_rh, a_rl) | (rank_eq & (a_v < 0) & (b_v >= 0)))
    )
    tie = ts_eq & rank_eq & (a_v >= 0) & (b_v >= 0) & (a_v != b_v)
    return wins, tie


def converge_batch(
    state: TRegState,
    key_idx: jax.Array,
    d_ts_hi: jax.Array,
    d_ts_lo: jax.Array,
    d_rank_hi: jax.Array,
    d_rank_lo: jax.Array,
    d_vid: jax.Array,
) -> tuple[TRegState, jax.Array]:
    """Join one delta batch (unique keys): gather rows, compare, scatter.

    Returns (new_state, tie_mask); tie_mask (B,) bool marks rows whose
    winner must be decided on host by full string comparison.
    """
    cur = tuple(plane[key_idx] for plane in state)
    d = (d_ts_hi, d_ts_lo, d_rank_hi, d_rank_lo, d_vid)
    wins, tie = _b_wins(cur, d)
    new = [jnp.where(wins, dv, cv) for dv, cv in zip(d, cur)]
    return (
        TRegState(
            *(
                plane.at[key_idx].set(nv, mode="drop", unique_indices=True)
                for plane, nv in zip(state, new)
            )
        ),
        tie,
    )


def converge_dense(
    state: TRegState,
    d_ts_hi: jax.Array,
    d_ts_lo: jax.Array,
    d_rank_hi: jax.Array,
    d_rank_lo: jax.Array,
    d_vid: jax.Array,
) -> tuple[TRegState, jax.Array]:
    """Full-keyspace elementwise LWW join — the dense fast path.

    The delta arrays are in dense key order ((K,) each, same length as the
    state); rows with no delta carry the lattice identity (0, 0, 0, 0, -1),
    which never wins and never ties. No gather, no scatter: when a batch
    covers most of the keyspace (a full anti-entropy sweep — the
    BASELINE.json north-star shape), this streams each plane exactly once
    instead of paying random-access gathers and scatters twice per plane.

    Returns (new_state, tie_mask (K,)).
    """
    d = (d_ts_hi, d_ts_lo, d_rank_hi, d_rank_lo, d_vid)
    wins, tie = _b_wins(tuple(state), d)
    return (
        TRegState(*(jnp.where(wins, dv, cv) for dv, cv in zip(d, state))),
        tie,
    )


def converge_many(
    state: TRegState,
    key_idx: jax.Array,
    d_ts_hi: jax.Array,
    d_ts_lo: jax.Array,
    d_rank_hi: jax.Array,
    d_rank_lo: jax.Array,
    d_vid: jax.Array,
) -> tuple[TRegState, jax.Array]:
    """Fold several replica batches: inputs are (N, B)-shaped; scans over N.

    Returns (state, tie_mask (N, B)). One compiled program for a whole
    multi-batch anti-entropy round. NOT on the serving path: the repo
    coalesces concurrent deltas per key host-side with the exact LWW rule
    (full strings, no rank-collision ambiguity — repo_treg.py:_write), so
    a drain always carries one winner per key; this kernel exists for
    offline folds where batches arrive pre-formed.
    """

    def step(st, batch):
        ki, th, tl, rh, rl, vd = batch
        st, tie = converge_batch(st, ki, th, tl, rh, rl, vd)
        return st, tie

    return jax.lax.scan(
        step, state, (key_idx, d_ts_hi, d_ts_lo, d_rank_hi, d_rank_lo, d_vid)
    )


def set_batch(state, key_idx, ts_hi, ts_lo, rank_hi, rank_lo, vid):
    """Local SET is lattice-identical to converging a delta (LWW join)."""
    return converge_batch(state, key_idx, ts_hi, ts_lo, rank_hi, rank_lo, vid)


def read(state: TRegState, key_idx: jax.Array):
    """GET for a batch of keys -> (ts_hi, ts_lo, vid); vid -1 = nil reply."""
    return state.ts_hi[key_idx], state.ts_lo[key_idx], state.vid[key_idx]


def grow(state: TRegState, num_keys: int) -> TRegState:
    k = state.vid.shape[0]
    if num_keys == k:
        return state
    return TRegState(
        jnp.zeros((num_keys,), U32).at[:k].set(state.ts_hi),
        jnp.zeros((num_keys,), U32).at[:k].set(state.ts_lo),
        jnp.zeros((num_keys,), U32).at[:k].set(state.rank_hi),
        jnp.zeros((num_keys,), U32).at[:k].set(state.rank_lo),
        jnp.full((num_keys,), -1, I32).at[:k].set(state.vid),
    )
