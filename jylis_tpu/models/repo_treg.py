"""TREG repo: device-resident last-writer-wins register keyspace.

Reference analog: repo_treg.pony:11-68 (Map[key -> TRegString], per-key
converge loop). Here the keyspace is the ops/treg struct-of-arrays; local
SETs and incoming deltas coalesce host-side per key (exact LWW compare with
full strings — the host has them), then drain in one fused
compare-and-scatter call whose gathered results feed the host serving
cache. Rank-prefix ties that the device cannot settle (flagged rows) are
resolved here with full strings and patched with a tiny follow-up scatter.

Host bookkeeping (keys, winner, pending window, delta accumulator) lives
behind the table backends in treg_table.py: pure-Python dicts as the
oracle, or the native C++ engine — the SAME state the server's native
batch applier (native/serve_engine.cpp) mutates, so SETs applied natively
and Python-side drains/flushes share one source of truth. GET never pays
a device round-trip: the winner is an O(1) host compare.

Delta wire shape: (value: bytes, ts: u64).
"""

from __future__ import annotations

from functools import partial

import jax
import numpy as np

from ..native.engine import resolve_engine
from ..ops import planes, treg
from ..ops.interner import Interner, prefix_rank
from ..parallel import (
    drain_sharded_treg,
    patch_sharded_treg,
    route_drain,
    serving_mesh,
    shard_vec,
)
from .base import ParseError, bucket, need, pad_rows, parse_u64
from .treg_table import NativeTregTable, PyTregTable
from ..utils.metrics import DEVICE, FINISH, drain_phase, timed_drain
from .help import RepoHelp

TREG_HELP = RepoHelp("TREG", {"GET": "key", "SET": "key value timestamp"})

# pending writes/deltas flush to the device once they pile this high:
# reads never need the drain (GET computes the winner host-side), so this
# bounds host memory while keeping device batches large.
# native/serve_engine.cpp TREG_PENDING_DRAIN must match.
PENDING_DRAIN_THRESHOLD = 4096

# interner compaction: once the table holds this many more ids than live
# registers, rebuild it from the live set (ops/interner.compact) so value
# churn can't grow host memory without bound
COMPACT_SLACK = 4096


@partial(jax.jit, donate_argnums=0)
def _drain(state, ki, ts_hi, ts_lo, rank_hi, rank_lo, vid):
    st, tie = treg.converge_batch(state, ki, ts_hi, ts_lo, rank_hi, rank_lo, vid)
    return st, tie, st.ts_hi[ki], st.ts_lo[ki], st.vid[ki]


@partial(jax.jit, donate_argnums=0)
def _drain_dense(state, ts_hi, ts_lo, rank_hi, rank_lo, vid):
    st, tie = treg.converge_dense(state, ts_hi, ts_lo, rank_hi, rank_lo, vid)
    return st, tie, st.ts_hi, st.ts_lo, st.vid


@partial(jax.jit, donate_argnums=0)
def _patch_vids(state, ki, vids):
    return state._replace(vid=state.vid.at[ki].set(vids, mode="drop"))


# a batch covering >= 1/DENSE_FRACTION of the keyspace drains through the
# elementwise dense join (each plane streamed once, no random access)
DENSE_FRACTION = 4


class RepoTREG:
    name = "TREG"
    help = TREG_HELP

    def __init__(
        self, identity: int, key_cap: int = 1024, mesh="auto", engine="auto"
    ):
        # identity is ignored: LWW needs no replica identity (repo_treg.pony:15)
        # mesh mode mirrors the counter repos (repo_counters.py): with >1
        # visible device the five planes live keys-sharded and drains
        # route through parallel/sharded.drain_sharded_treg
        self._mesh = serving_mesh() if mesh == "auto" else mesh
        self._n_shards = self._mesh.devices.size if self._mesh is not None else 1
        self._key_cap = self._round_cap(key_cap)
        self._state = self._place(treg.init(self._key_cap))
        self._interner = Interner()
        self._cache: dict[int, tuple[int, int]] = {}  # row -> (ts, vid)
        self.engine = engine = resolve_engine(engine)
        self._tbl = (
            NativeTregTable(engine) if engine is not None else PyTregTable()
        )

    def _round_cap(self, k: int) -> int:
        ns = self._n_shards
        return -(-k // ns) * ns

    def _place(self, state):
        if self._mesh is None:
            return state
        return type(state)(*(shard_vec(self._mesh, p) for p in state))

    # -- commands (repo_treg.pony:24-68) -----------------------------------

    def apply(self, resp, args: list[bytes]) -> bool:
        op = need(args, 0)
        if op == b"GET":
            # LWW winner = join(drained cache, un-drained pending) by the
            # exact (ts, value) rule — an O(1) host compare, so a GET
            # NEVER pays a device round-trip (the counters' host-shadow
            # posture; drains happen on write thresholds and snapshots)
            row = self._tbl.find(need(args, 1))
            cand = self._tbl.winner(row) if row >= 0 else None
            if cand is None:
                resp.null()
            else:
                ts, value = cand
                resp.array_start(2)
                resp.string(value)
                resp.u64(ts)
            return False
        if op == b"SET":
            key = need(args, 1)
            value = need(args, 2)
            ts = parse_u64(need(args, 3))
            row = self._tbl.upsert(key)
            self._tbl.write(row, ts, value)
            # local delta coalesces by the same LWW rule (exact, host-side)
            self._tbl.note_delta(row, ts, value)
            if self._tbl.pend_count() >= PENDING_DRAIN_THRESHOLD:
                self.drain()
            resp.ok()
            return True
        raise ParseError()

    def converge(self, key: bytes, delta: tuple) -> None:
        # buffer only: the serving path drains via drain_overdue in a
        # worker thread; sync callers (snapshot restore) drain explicitly
        value, ts = delta
        self._tbl.write(self._tbl.upsert(key), ts, value)

    def deltas_size(self) -> int:
        return self._tbl.deltas_size()

    def may_drain(self, args: list[bytes]) -> bool:
        """GET never drains (host winner compare); a SET may trigger the
        threshold drain, which the server offloads to a thread. +1: the
        SET about to run adds a row, so the threshold it will see inside
        apply is one higher than what is pending now."""
        return (
            bool(args)
            and args[0] == b"SET"
            and self._tbl.pend_count() + 1 >= PENDING_DRAIN_THRESHOLD
        )

    def drain_overdue(self) -> bool:
        """Cluster converge path: after buffering a batch, the manager
        offloads the drain to a worker thread when this trips."""
        return self._tbl.pend_count() >= PENDING_DRAIN_THRESHOLD

    def flush_deltas(self):
        return self._tbl.flush_deltas()

    # -- sync digest (cluster/syncdigest.py) ---------------------------------

    def sync_dirty_keys(self) -> list[bytes]:
        return [self._tbl.key_of(r) for r in self._tbl.export_sync_dirty()]

    def sync_canon(self, key: bytes) -> bytes | None:
        """Canonical per-key state: the LWW winner — an O(1) host read
        (every converged replica agrees on it by the exact
        (ts, value) rule)."""
        row = self._tbl.find(key)
        w = self._tbl.winner(row) if row >= 0 else None
        return None if w is None else repr(w).encode()

    # -- snapshot (persist.py): full state in the wire-delta shape ----------

    def dump_state(self):
        # the host winner IS the join the device converges to, so the
        # dump needs no device read; the drain keeps the device mirror
        # caught up for the sharded/mesh serving path
        self.drain()
        return self._tbl.dump()

    def load_state(self, batch) -> None:
        for key, delta in batch:
            self.converge(key, delta)

    # -- device drain -------------------------------------------------------

    def warm_drain_shapes(self) -> None:
        """Compile the sparse drain at the two batch shapes a serving
        node meets at its present capacity: the threshold batch (a local
        SET trips it at exactly PENDING_DRAIN_THRESHOLD rows) and the
        next bucket up (a foreign batch landing on a nearly full pending
        window drains threshold + batch rows). Run at boot, after
        recovery has settled the capacity: otherwise the first such
        drain compiles while it holds the repo lock, and every client of
        the type waits out an XLA compile (seconds when the persistent
        cache is cold). Every row is an out-of-range pad, so the scatter
        drops them all and the state is what it was. A shape the
        capacity would send down the dense path is skipped (a small or
        empty keyspace compiles nothing here); the mesh path has its own
        programs and is left to its first drain."""
        if self._mesh is not None:
            return
        self.drain()  # what recovery buffered: settles the capacity
        for b in (PENDING_DRAIN_THRESHOLD, 2 * PENDING_DRAIN_THRESHOLD):
            if b * DENSE_FRACTION >= self._key_cap:
                continue
            zeros = np.zeros(b, np.uint32)
            self._state, *_ = _drain(
                self._state, pad_rows(b), zeros, zeros, zeros, zeros,
                np.full(b, -1, np.int32),
            )

    @timed_drain("TREG", lambda self: self._tbl.pend_count())
    def drain(self) -> None:
        pend = self._tbl.export_pend()  # [(row, ts, value)], not yet cleared
        if not pend:
            return
        cap = self._round_cap(bucket(max(self._tbl.rows(), 1), self._key_cap))
        if cap != self._key_cap:
            self._key_cap = cap
            self._state = self._place(treg.grow(self._state, cap))
        self._maybe_compact_interner()
        if self._mesh is not None:
            self._drain_sharded(pend)
            self._tbl.fold_pend()
            return
        rows = [row for row, _ts, _v in pend]
        dense = len(rows) * DENSE_FRACTION >= self._key_cap
        b = self._key_cap if dense else bucket(len(rows))
        ki = pad_rows(b)
        d_ts = np.zeros(b, np.uint64)
        d_rank = np.zeros(b, np.uint64)
        d_vid = np.full(b, -1, np.int32)
        values: dict[int, bytes] = {}  # batch slot -> full delta string
        for i, (row, ts, value) in enumerate(pend):
            slot = row if dense else i
            ki[i] = row
            d_ts[slot] = ts
            d_rank[slot] = prefix_rank(value)
            d_vid[slot] = self._interner.intern(value)
            values[slot] = value
        ts_hi, ts_lo = planes.split64_np(d_ts)
        rank_hi, rank_lo = planes.split64_np(d_rank)
        drain_phase(self, DEVICE)
        if dense:
            self._state, tie, out_ts_hi, out_ts_lo, out_vid = _drain_dense(
                self._state, ts_hi, ts_lo, rank_hi, rank_lo, d_vid
            )
            slots = rows  # outputs are in dense key order
        else:
            self._state, tie, out_ts_hi, out_ts_lo, out_vid = _drain(
                self._state, ki, ts_hi, ts_lo, rank_hi, rank_lo, d_vid
            )
            slots = list(range(len(rows)))
        tie = np.asarray(tie)
        out_ts = planes.combine64_np(np.asarray(out_ts_hi), np.asarray(out_ts_lo))
        out_vid = np.asarray(out_vid).copy()
        drain_phase(self, FINISH)
        if tie[slots].any():
            # prefix collision: full-string compare decides; patch losers
            patch_ki, patch_vid = [], []
            for row, slot in zip(rows, slots):
                if not tie[slot]:
                    continue
                cur_val = self._interner.lookup(int(out_vid[slot]))
                if values[slot] > cur_val:
                    patch_ki.append(row)
                    patch_vid.append(int(d_vid[slot]))
                    out_vid[slot] = d_vid[slot]
            if patch_ki:
                pb = bucket(len(patch_ki))
                pk = pad_rows(pb)  # distinct out-of-range pads drop
                pv = np.full(pb, -1, np.int32)
                pk[: len(patch_ki)] = patch_ki
                pv[: len(patch_vid)] = patch_vid
                self._state = _patch_vids(self._state, pk, pv)
        for row, slot in zip(rows, slots):
            self._cache[row] = (int(out_ts[slot]), int(out_vid[slot]))
        self._tbl.fold_pend()

    def _maybe_compact_interner(self) -> None:
        """Epoch compaction (weak-spot fix, VERDICT round 2): every value
        ever SET kept its interner slot forever. The host cache mirrors
        the device vid plane exactly (drain writes both), so when the
        table outgrows the live registers, rebuild it from the cache and
        REPLACE the device vid plane with the host-built remapped mirror
        — one transfer, no kernel. Runs under the repo lock at drain
        time, before any new pending values intern."""
        if len(self._interner) <= 2 * len(self._cache) + COMPACT_SLACK:
            return
        remap = self._interner.compact(
            vid for _ts, vid in self._cache.values() if vid >= 0
        )
        self._cache = {
            row: (ts, int(remap[vid]) if vid >= 0 else -1)
            for row, (ts, vid) in self._cache.items()
        }
        vids_by_row = np.full(self._key_cap, -1, np.int32)
        for row, (_ts, vid) in self._cache.items():
            vids_by_row[row] = vid
        new_vid = (
            shard_vec(self._mesh, vids_by_row)
            if self._mesh is not None
            else jax.numpy.asarray(vids_by_row)
        )
        self._state = self._state._replace(vid=new_vid)

    def _drain_sharded(self, pend) -> None:
        """Mesh-mode drain: payload columns [ts, rank, vid] route to the
        key blocks; ties come back per slot and resolve on host exactly
        like the single-chip path, patched with a routed vid scatter."""
        rows = [row for row, _ts, _v in pend]
        payload = np.zeros((len(rows), 3), np.uint64)
        values: dict[int, bytes] = {}
        for i, (row, ts, value) in enumerate(pend):
            payload[i, 0] = ts
            payload[i, 1] = prefix_rank(value)
            payload[i, 2] = self._interner.intern(value)  # vids are >= 0
            values[row] = value
        rps = self._key_cap // self._n_shards
        lr, d_hi, d_lo, slots = route_drain(
            np.asarray(rows, np.int64), payload, self._n_shards, rps
        )
        drain_phase(self, DEVICE)
        out = drain_sharded_treg(self._mesh, *self._state, lr, d_hi, d_lo)
        self._state = treg.TRegState(*out[:5])
        tie = np.asarray(out[5])
        out_ts = planes.combine64_np(np.asarray(out[6]), np.asarray(out[7]))
        out_vid = np.asarray(out[8]).copy()
        drain_phase(self, FINISH)
        patch_rows: list[int] = []
        patch_vids: list[int] = []
        for j, g in enumerate(slots):
            if g < 0:
                continue
            row = int(g)
            if tie[j]:
                cur_val = self._interner.lookup(int(out_vid[j]))
                if values[row] > cur_val:
                    my_vid = self._interner.intern(values[row])
                    patch_rows.append(row)
                    patch_vids.append(my_vid)
                    out_vid[j] = my_vid
            self._cache[row] = (int(out_ts[j]), int(out_vid[j]))
        if patch_rows:
            pp = np.asarray(patch_vids, np.uint64).reshape(-1, 1)
            lr2, _p_hi, p_lo, _slots = route_drain(
                np.asarray(patch_rows, np.int64), pp, self._n_shards, rps
            )
            vid_new = patch_sharded_treg(
                self._mesh, self._state.vid, lr2, p_lo[:, 0].astype(np.int32)
            )
            self._state = self._state._replace(vid=vid_new)
