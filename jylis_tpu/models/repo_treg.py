"""TREG repo: device-resident last-writer-wins register keyspace.

Reference analog: repo_treg.pony:11-68 (Map[key -> TRegString], per-key
converge loop). Here the keyspace is the ops/treg struct-of-arrays; local
SETs and incoming deltas coalesce host-side per key (exact LWW compare with
full strings — the host has them), then drain in one fused
compare-and-scatter call. The pending window reaches that call as ready
planes (the table's `export_planes`: one pass inside the engine, no
per-row Python, no per-row bytes object); rank-prefix ties that the device
cannot settle (flagged rows) go back to the table, which decides them with
the full strings in one call (`settle_ties`), and are patched with a tiny
follow-up scatter. The mirror's vid plane holds a per-row generation the
table keeps beside its drained winner (treg_table.py), not an id into a
table of values: the kernel only ever compares a row's id with that row's
delta's.

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
from ..parallel import (
    drain_sharded_treg,
    patch_sharded_treg,
    route_drain,
    serving_mesh,
    shard_vec,
)
from .base import ParseError, bucket, need, pad_rows, parse_u64
from .treg_table import NativeTregTable, PyTregTable
from ..utils.metrics import (
    DEVICE,
    FINISH,
    drain_phase,
    resolve_registry,
    timed_drain,
)
from .help import RepoHelp

TREG_HELP = RepoHelp("TREG", {"GET": "key", "SET": "key value timestamp"})

# pending writes/deltas flush to the device once they pile this high:
# reads never need the drain (GET computes the winner host-side), so this
# bounds host memory while keeping device batches large.
# native/serve_engine.cpp TREG_PENDING_DRAIN must match.
PENDING_DRAIN_THRESHOLD = 4096


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


def patch_tie_vids(state, rows, vids):
    """The mirror's ids at ``rows`` set to ``vids`` (what a table's
    `settle_ties` decided), padded to a bucket so the patch compiles
    once a size; distinct out-of-range pads drop."""
    pb = bucket(len(rows))
    pk = pad_rows(pb)
    pv = np.full(pb, -1, np.int32)
    pk[: len(rows)] = rows
    pv[: len(rows)] = vids
    return _patch_vids(state, pk, pv)


# a batch covering >= 1/DENSE_FRACTION of the keyspace drains through the
# elementwise dense join (each plane streamed once, no random access)
DENSE_FRACTION = 4


def batch_planes(b: int) -> list:
    """The lattice identity (0, 0, 0, 0, -1) at every slot of a b-row
    drain batch: [ts_hi, ts_lo, rank_hi, rank_lo, vid]."""
    return [np.zeros(b, np.uint32) for _ in range(4)] + [
        np.full(b, -1, np.int32)
    ]


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
            self._state, *_ = _drain(
                self._state, pad_rows(b), *batch_planes(b)
            )

    @timed_drain("TREG", lambda self: self._tbl.pend_count())
    def drain(self) -> None:
        n = self._tbl.pend_count()
        if not n:
            return
        cap = self._round_cap(bucket(max(self._tbl.rows(), 1), self._key_cap))
        if cap != self._key_cap:
            self._key_cap = cap
            self._state = self._place(treg.grow(self._state, cap))
        if self._mesh is not None:
            ties = self._drain_sharded(n)
        else:
            ties = self._drain_single(n)
        self._tbl.fold_pend()
        reg = resolve_registry(self)
        reg.tally("drain.TREG.bulk_rows", n if self.engine is not None else 0)
        reg.tally("drain.TREG.tie_rows", ties)

    def _drain_single(self, n: int) -> int:
        dense = n * DENSE_FRACTION >= self._key_cap
        b = self._key_cap if dense else bucket(n)
        ki = np.empty(n, np.int32) if dense else pad_rows(b)
        d = batch_planes(b)
        self._tbl.export_planes(ki, *d, dense)
        drain_phase(self, DEVICE)
        if dense:
            self._state, tie, *_ = _drain_dense(self._state, *d)
        else:
            self._state, tie, *_ = _drain(self._state, ki, *d)
        hit = np.flatnonzero(np.asarray(tie))
        drain_phase(self, FINISH)
        if hit.size:
            # prefix collision: full-string compare decides; patch losers
            # (dense outputs are in key order: the slot IS the row)
            rows, vids = self._tbl.settle_ties(hit if dense else ki[hit])
            if len(rows):
                self._state = patch_tie_vids(self._state, rows, vids)
        return int(hit.size)

    def _drain_sharded(self, n: int) -> int:
        """Mesh-mode drain: payload columns [ts, rank, vid] route to the
        key blocks; ties come back per slot and resolve on host exactly
        like the single-chip path, patched with a routed vid scatter."""
        ki = np.empty(n, np.int32)
        ts_hi, ts_lo, rank_hi, rank_lo, vid = d = batch_planes(n)
        self._tbl.export_planes(ki, *d, False)
        payload = np.stack(
            [
                planes.combine64_np(ts_hi, ts_lo),
                planes.combine64_np(rank_hi, rank_lo),
                vid.astype(np.uint64),  # ids are >= 0
            ],
            axis=1,
        )
        rps = self._key_cap // self._n_shards
        lr, d_hi, d_lo, slots = route_drain(
            ki.astype(np.int64), payload, self._n_shards, rps
        )
        drain_phase(self, DEVICE)
        out = drain_sharded_treg(self._mesh, *self._state, lr, d_hi, d_lo)
        self._state = treg.TRegState(*out[:5])
        # a pad slot carries id 0, not -1: only real slots can tie
        hit = np.flatnonzero(np.asarray(out[5]) & (slots >= 0))
        drain_phase(self, FINISH)
        if hit.size:
            rows, vids = self._tbl.settle_ties(slots[hit])
            if len(rows):
                lr2, _p_hi, p_lo, _slots = route_drain(
                    rows.astype(np.int64),
                    vids.astype(np.uint64).reshape(-1, 1),
                    self._n_shards,
                    rps,
                )
                vid_new = patch_sharded_treg(
                    self._mesh, self._state.vid, lr2, p_lo[:, 0].astype(np.int32)
                )
                self._state = self._state._replace(vid=vid_new)
        return int(hit.size)
