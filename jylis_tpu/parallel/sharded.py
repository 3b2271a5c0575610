"""Key-sharded counter keyspaces: shard_map merge kernels + join collective.

The north-star path (BASELINE.json): PNCOUNT/GCOUNT anti-entropy over a
(keys × replicas) u64 tensor — stored as one u32 plane of hi|lo cells
(ops/planes.py; XLA's u64 emulation is 4-25x slower on scatters/reduces) —
scaled over a device mesh:

* **State layout:** the plane sharded ``P("keys", None)`` — a device owns
  a contiguous block of key rows with all replica columns resident, so
  both the join composite and the row-sum read are LOCAL.
* **Routing:** the host assigns key rows blockwise to shards
  (``row // rows_per_shard``); `route_batch` coalesces duplicate keys
  (max-combine — the join composite needs unique rows), buckets per shard,
  and pads to a common width, producing arrays whose leading axis is
  sharded over ``keys``. This is the host-side analog of the reference's
  per-type actor mailbox (repo_manager.pony:92-93) — batching is where the
  reference's per-key loop became one device launch.
* **Merge:** inside `shard_map`, each device runs the same gather ->
  joint-max -> scatter-set composite as the single-chip kernel on its
  block — ZERO collectives on the serving path; the mesh scales
  merges/sec linearly with chips.
* **Join collective:** when full per-replica states arrive sharded over a
  ``rep`` mesh axis (synthetic replicas spread over chips), the lattice
  join across that axis is a local fold + a two-phase u32 pmax (hi plane
  first, then the lo plane masked to hi-winners) — a max-all-reduce over
  ICI, the CRDT analog of data-parallel gradient psum.

All functions are pure and jit/shard_map-composable; dynamic work arrives
pre-padded (static shapes keep XLA's tiling friendly and the jit cache
small).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from ..utils.batching import bucket, pad_rows
from ..ops import gcount, planes, pncount, treg

U32 = jnp.uint32


def shard_plane(mesh, arr):
    """Place one (K, ...) plane keys-sharded on the mesh. K must divide
    evenly by the keys axis (pad capacity with zeros — the lattice
    identity — before calling)."""
    return jax.device_put(arr, NamedSharding(mesh, P("keys", None)))


def shard_vec(mesh, arr):
    """Place one (K,) vector keys-sharded on the mesh."""
    return jax.device_put(arr, NamedSharding(mesh, P("keys")))


def shard_docbatch(mesh, batch):
    """Place a (K, D, W)-planed UJSON DocBatch keys-sharded on the mesh.

    The segmented fold (ops/ujson_device.fold_segments) is embarrassingly
    parallel over its key axis, so with the leading axis sharded the same
    jitted program runs SPMD across the mesh with ZERO collectives —
    UJSON's drain scales with chips exactly like the plane-backed types.
    K must divide evenly by the keys axis (pad with identity groups)."""
    return type(batch)(
        *(
            jax.device_put(
                p, NamedSharding(mesh, P("keys", *([None] * (p.ndim - 1))))
            )
            for p in batch
        )
    )


def _route(key_idx, deltas, n_shards: int, rows_per_shard: int, bucket_width=False):
    """Shared routing core: coalesce, bucket per shard, pad to a common
    width. Returns (local_rows, d_hi, d_lo, slot_rows) where slot_rows maps
    each flattened slot back to its GLOBAL key row (-1 for pad slots).
    With bucket_width the width is padded to a power of two (bounds the jit
    cache over drain sizes)."""
    key_idx, deltas = planes.coalesce(key_idx, deltas)
    shard_of = key_idx // rows_per_shard
    order = np.argsort(shard_of, kind="stable")
    counts = np.bincount(shard_of, minlength=n_shards)
    width = max(int(counts.max()) if len(key_idx) else 0, 1)
    if bucket_width:
        width = bucket(width, 8)
    # distinct out-of-range pads per shard: each device's scatter keeps an
    # honestly-unique index vector
    local_rows = np.broadcast_to(pad_rows(width), (n_shards, width)).copy()
    local_deltas = np.zeros((n_shards, width, deltas.shape[-1]), np.uint64)
    slot_rows = np.full((n_shards, width), -1, np.int64)
    start = 0
    for s in range(n_shards):
        c = int(counts[s])
        sel = order[start : start + c]
        local_rows[s, :c] = key_idx[sel] % rows_per_shard
        local_deltas[s, :c] = deltas[sel]
        slot_rows[s, :c] = key_idx[sel]
        start += c
    return (
        local_rows.reshape(n_shards * width),
        local_deltas.reshape(n_shards * width, deltas.shape[-1]),
        slot_rows.reshape(n_shards * width),
    )


def route_batch(key_idx, deltas, n_shards: int, rows_per_shard: int):
    """Host-side shard routing: global (B,) rows + (B, C) u64 deltas become
    ((n_shards * W,) local rows, (n_shards * W, 2C) u32 delta cells) with
    the leading axis blockwise-sharded; W is the padded per-shard width.
    Duplicate keys are max-combined here (the device composite requires
    unique rows); padded slots carry PAD_ROW, which the scatter drops.
    """
    local_rows, payload, _ = _route(key_idx, deltas, n_shards, rows_per_shard)
    return local_rows, planes.pack64_np(payload)


def route_drain(key_idx, deltas, n_shards: int, rows_per_shard: int):
    """Serving-path routing: like `route_batch`, but the per-shard width is
    bucketed to a power of two (bounds the jit cache over drain sizes), the
    payload comes back as hi/lo u32 planes (TREG's columns), and the slot ->
    global-row map is returned so the host value cache can be refreshed
    from the per-slot results the sharded drain kernels emit."""
    local_rows, payload, slot_rows = _route(
        key_idx, deltas, n_shards, rows_per_shard, bucket_width=True
    )
    d_hi, d_lo = planes.split64_np(payload)
    return local_rows, d_hi, d_lo, slot_rows


def route_drain64(key_idx, deltas, n_shards: int, rows_per_shard: int):
    """`route_drain` with the u64 payload columns as they are: TLOG's
    segment tensors take them directly, the counters pack them into cells
    (`planes.pack64_np`)."""
    return _route(key_idx, deltas, n_shards, rows_per_shard, bucket_width=True)


_CELLS = P("keys", None)  # a (K, 2C) plane or a routed (n * W, 2C) batch


# jit hoisted to module level with the mesh static: rebuilding the
# jit(shard_map) wrapper per call would retrace and recompile every merge
@partial(jax.jit, static_argnames=("mesh",), donate_argnums=(1,))
def _converge_sharded(mesh, cells, local_rows, d):
    # per-shard join composite: the single-chip kernel on this device's block
    return jax.shard_map(
        gcount.converge_batch,
        mesh=mesh,
        in_specs=(_CELLS, P("keys"), _CELLS),
        out_specs=_CELLS,
    )(cells, local_rows, d)


def converge_sharded(mesh, cells, local_rows, d):
    """One anti-entropy merge step over the mesh: every device joins its
    routed slice into its key block. No communication."""
    return _converge_sharded(mesh, cells, local_rows, d)


@partial(jax.jit, static_argnames=("mesh",))
def _read_all_sharded(mesh, cells):
    return jax.shard_map(
        planes.rowsum_cells, mesh=mesh, in_specs=(_CELLS,), out_specs=P("keys")
    )(cells)


def read_all_sharded(mesh, cells):
    """Row sums (counter values, u64 wrapping) for the whole keyspace;
    output stays keys-sharded — only materialise on host what you need."""
    return _read_all_sharded(mesh, cells)


# ---- serving drains: converge + read-back in ONE sharded launch ------------
#
# The counter repos' drain needs the post-join row sums for its host value
# cache. Each device runs the single-chip drain (`drain_batch`: join, then
# sum the joined rows it holds) on its key block, so the whole drain is one
# device launch (one dispatch, one read-back) and its work is proportional
# to the BATCH, not the keyspace. Pad slots join clamped garbage, which the
# host drops via the slot_rows map.


def _drain_sharded(drain_batch, mesh, cells, local_rows, d):
    return jax.shard_map(
        drain_batch,
        mesh=mesh,
        in_specs=(_CELLS, P("keys"), _CELLS),
        out_specs=(_CELLS, P("keys")),
    )(cells, local_rows, d)


@partial(jax.jit, static_argnames=("mesh",), donate_argnums=(1,))
def drain_sharded_g(mesh, cells, local_rows, d):
    """GCOUNT sharded drain: join the routed batch into each device's key
    block and return (cells, per-slot u64 row sums)."""
    return _drain_sharded(gcount.drain_batch, mesh, cells, local_rows, d)


@partial(jax.jit, static_argnames=("mesh",), donate_argnums=(1,))
def drain_sharded_pn(mesh, cells, local_rows, d):
    """PNCOUNT sharded drain: both polarities join in one launch; returns
    (cells, per-slot i64 net values)."""
    return _drain_sharded(pncount.drain_batch, mesh, cells, local_rows, d)


# ---- TREG sharded drain ----------------------------------------------------
#
# TREG's keyspace is five (K,) planes (ops/treg.py). Deltas route through
# the same `route_drain` machinery by packing each row's payload as u64
# columns [ts, rank, vid]: rows from the repo's pending dict are UNIQUE,
# so the router's max-coalesce is the identity and the payload columns
# pass through untouched. On device the columns unpack into the plane
# quintuple, the LWW compare-and-scatter runs per key block, and the
# touched rows' (ts, vid) plus the prefix-rank tie flags come back
# per-slot for the host cache / host tie resolution.


def _local_drain_treg(ts_hi, ts_lo, rk_hi, rk_lo, vid, rows_blk, d_hi, d_lo):
    state = treg.TRegState(ts_hi, ts_lo, rk_hi, rk_lo, vid)
    d_vid = d_lo[:, 2].astype(jnp.int32)
    state, tie = treg.converge_batch(
        state, rows_blk, d_hi[:, 0], d_lo[:, 0], d_hi[:, 1], d_lo[:, 1], d_vid
    )
    out_ts_hi = state.ts_hi[rows_blk]
    out_ts_lo = state.ts_lo[rows_blk]
    out_vid = state.vid[rows_blk]
    return (*state, tie, out_ts_hi, out_ts_lo, out_vid)


@partial(jax.jit, static_argnames=("mesh",), donate_argnums=(1, 2, 3, 4, 5))
def drain_sharded_treg(mesh, ts_hi, ts_lo, rk_hi, rk_lo, vid, local_rows, d_hi, d_lo):
    """TREG sharded drain: LWW-join the routed batch into each device's
    key block; returns (5 state planes, per-slot tie flags, per-slot
    ts_hi/ts_lo/vid read-back)."""
    return jax.shard_map(
        _local_drain_treg,
        mesh=mesh,
        in_specs=(
            P("keys"),
            P("keys"),
            P("keys"),
            P("keys"),
            P("keys"),
            P("keys"),
            P("keys", None),
            P("keys", None),
        ),
        out_specs=(
            P("keys"),
            P("keys"),
            P("keys"),
            P("keys"),
            P("keys"),
            P("keys"),
            P("keys"),
            P("keys"),
            P("keys"),
        ),
    )(ts_hi, ts_lo, rk_hi, rk_lo, vid, local_rows, d_hi, d_lo)


def _local_patch_treg(vid, rows_blk, patch_vid):
    return vid.at[rows_blk].set(patch_vid, mode="drop", unique_indices=True)


@partial(jax.jit, static_argnames=("mesh",), donate_argnums=(1,))
def patch_sharded_treg(mesh, vid, local_rows, patch_vid):
    """Host-resolved prefix-rank ties scatter their winning vids back."""
    return jax.shard_map(
        _local_patch_treg,
        mesh=mesh,
        in_specs=(P("keys"), P("keys"), P("keys")),
        out_specs=P("keys"),
    )(vid, local_rows, patch_vid)


# ---- TLOG sharded drain ----------------------------------------------------
#
# TLOG's keyspace is (K, L) ts/vid segment tensors + (K,) length/cutoff
# vectors (ops/tlog.py). Deltas route as u64 payload columns
# [ts(ld) | vid(ld) | cutoff | count], unpacked per device block; the
# batched sort-dedup-mask merge runs shard-local, then the fused trim
# applies where count < TRIM_NOOP — so drains, trims, and drain+trim are
# all ONE dispatch. NOT donated: the caller retries from the pre-merge
# state when a row overflows its slot budget.


def _local_drain_tlog(nth, ntl, nv, length, cutoff, rows_blk, payload, ld):
    from ..ops import tlog as tlog_ops

    state = tlog_ops.TLogState(nth, ntl, nv, length, cutoff)
    d_ts = payload[:, :ld]
    d_vid = payload[:, ld : 2 * ld].astype(jnp.int64)
    d_cut = payload[:, 2 * ld]
    counts = payload[:, 2 * ld + 1].astype(jnp.int64)
    st, ovf = tlog_ops.converge_then_trim(
        state, rows_blk, d_ts, d_vid, d_cut, rows_blk, counts
    )
    return (*st, ovf, st.length[rows_blk], st.cutoff[rows_blk])


@partial(jax.jit, static_argnames=("mesh", "ld"))
def drain_sharded_tlog(mesh, nth, ntl, nv, length, cutoff, local_rows, payload, ld):
    """TLOG sharded drain (+ fused optional per-row trim) over the wide
    3-plane layout; returns (5 state tensors, per-slot overflow flags,
    per-slot lengths, per-slot cutoffs)."""
    return jax.shard_map(
        partial(_local_drain_tlog, ld=ld),
        mesh=mesh,
        in_specs=(
            P("keys", None),
            P("keys", None),
            P("keys", None),
            P("keys"),
            P("keys"),
            P("keys"),
            P("keys", None),
        ),
        out_specs=(
            P("keys", None),
            P("keys", None),
            P("keys", None),
            P("keys"),
            P("keys"),
            P("keys"),
            P("keys"),
            P("keys"),
        ),
    )(nth, ntl, nv, length, cutoff, local_rows, payload)


def _tree_join(hi_blk, lo_blk):
    """Log-depth joint fold over the leading axis."""
    while hi_blk.shape[0] > 1:
        s = hi_blk.shape[0]
        half = s // 2
        fhi, flo = planes.join_max(
            hi_blk[:half], lo_blk[:half], hi_blk[half : 2 * half], lo_blk[half : 2 * half]
        )
        if s % 2:  # odd leftover rides along
            fhi = jnp.concatenate([fhi, hi_blk[-1:]])
            flo = jnp.concatenate([flo, lo_blk[-1:]])
        hi_blk, lo_blk = fhi, flo
    return hi_blk, lo_blk


def _local_then_pmax(hi_blk, lo_blk):
    # fold the shard's own replica rows jointly first (pmax alone only
    # joins row-for-row across devices), then two-phase u32 all-reduce:
    # hi decides; lo competes only where hi is the winner
    fhi, flo = _tree_join(hi_blk, lo_blk)
    jhi = jax.lax.pmax(fhi, "rep")
    lo_cand = jnp.where(fhi == jhi, flo, jnp.uint32(0))
    jlo = jax.lax.pmax(lo_cand, "rep")
    return (
        jnp.broadcast_to(jhi, hi_blk.shape),
        jnp.broadcast_to(jlo, lo_blk.shape),
    )


@partial(jax.jit, static_argnames=("mesh",))
def _pmax_join(mesh, hi, lo):
    return jax.shard_map(
        _local_then_pmax,
        mesh=mesh,
        in_specs=(P("rep", "keys"), P("rep", "keys")),
        out_specs=(P("rep", "keys"), P("rep", "keys")),
    )(hi, lo)


def join_replica_axis(mesh, hi_stacked, lo_stacked):
    """Lattice-join full states sharded over the ``rep`` mesh axis.

    hi/lo_stacked: (S, K) u32 planes sharded P("rep", "keys") — S
    per-replica full u64 states. Afterwards every row of every rep-shard
    holds the converged state.
    """
    return _pmax_join(mesh, hi_stacked, lo_stacked)
