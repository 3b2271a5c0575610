"""GCOUNT / PNCOUNT repos: device-resident counter keyspaces.

Reference analog: repo_gcount.pony:11-60 and repo_pncount.pony:12-67, where
each repo is a Map[key -> counter] and converge is a per-key loop. Here the
whole keyspace is ONE u32 plane of (keys x replicas) u64 cells, PNCOUNT's
two polarities side by side in it (ops/gcount, ops/pncount, ops/planes),
and all mutations — local INCs and incoming anti-entropy
deltas alike — funnel into a coalesced pending batch that drains as a
single fused scatter-max + row-sum XLA call. The drain's row sums feed a
host value cache, so GET is a table lookup and the device only ever sees
large batches (the BASELINE.json north-star structure).

Host bookkeeping (keys, own contributions, value cache, dirty/pending/
foreign flags) lives behind the table backends in counter_table.py:
pure-Python dicts as the oracle, or the native C++ engine — the SAME
state the server's native batch applier (native/counter_engine.cpp)
mutates, so commands applied natively and Python-side drains/flushes
share one source of truth. Foreign delta columns (sparse per-replica
maps from the cluster) stay in Python dicts; they merge with the
exported pending-own values at drain time.

Delta wire shape: GCOUNT -> dict {replica_id: u64}; PNCOUNT -> a
(p_dict, n_dict) pair. Outbound deltas carry only this node's own column
(absolute values — joinable delta-state), which the table tracks exactly,
so flushes never need a device read.
"""

from __future__ import annotations

from functools import partial

import jax
import numpy as np

from ..native.engine import G as ENG_G, PN as ENG_PN, resolve_engine
from ..ops import gcount, planes, pncount
from ..parallel import (
    drain_sharded_g,
    drain_sharded_pn,
    route_drain64,
    serving_mesh,
    shard_plane,
)
from .base import ParseError, bucket, need, pad_rows, parse_u64, U64_MAX
from .counter_table import NativeTable, PyTable
from ..utils.metrics import DEVICE, FINISH, drain_phase, timed_drain
from .help import RepoHelp

GCOUNT_HELP = RepoHelp("GCOUNT", {"GET": "key", "INC": "key value"})
PNCOUNT_HELP = RepoHelp(
    "PNCOUNT", {"GET": "key", "INC": "key value", "DEC": "key value"}
)


# sparse drains: `drain_batch` gathers the batch's rows, joins them and sums
# the JOINED rows, so the donated plane is touched at those rows alone
# (the programs' names are the trace's: `jit__drain_g`, `jit__drain_pn`)
@partial(jax.jit, donate_argnums=0)
def _drain_g(state, ki, d):
    return gcount.drain_batch(state, ki, d)


@partial(jax.jit, donate_argnums=0)
def _drain_pn(state, ki, d):
    return pncount.drain_batch(state, ki, d)


# dense drains: when a batch covers most of the keyspace (a full
# anti-entropy sweep), an elementwise join streams the plane once instead
# of paying random-access gathers + scatters per row
@partial(jax.jit, donate_argnums=0)
def _drain_g_dense(state, d):
    st = gcount.join(state, d)
    return st, gcount.read_all(st)


@partial(jax.jit, donate_argnums=0)
def _drain_pn_dense(state, d):
    st = pncount.join(state, d)
    return st, pncount.read_all(st)


# a batch covering >= 1/DENSE_FRACTION of the keyspace drains dense: the
# sparse composite's random accesses cost far more per row than streaming
DENSE_FRACTION = 4


def _wrap_i64(v: int) -> int:
    """Wrap into signed-64 range (the reference's modular (p-n).i64())."""
    return ((v + (1 << 63)) & U64_MAX) - (1 << 63)


class _CounterRepo:
    """Shared machinery; subclasses bind the ops module and command set."""

    _which: int  # native engine table id

    def __init__(
        self,
        identity: int,
        key_cap: int = 1024,
        rep_cap: int = 8,
        mesh="auto",
        engine="auto",
    ):
        self._identity = identity
        self._rids: dict[int, int] = {}  # replica id -> column
        # mesh mode (SURVEY.md §5.8): with >1 visible device the keyspace
        # planes live keys-sharded over the serving mesh and drains route
        # through parallel/sharded — the per-type actor keyspace of
        # repo_manager.pony:92-93 become per-device key blocks. With one
        # device this resolves to None and the single-chip fast path
        # below is untouched.
        self._mesh = serving_mesh() if mesh == "auto" else mesh
        self._n_shards = self._mesh.devices.size if self._mesh is not None else 1
        self._key_cap = self._round_cap(key_cap)
        self._rep_cap = rep_cap
        self.engine = engine = resolve_engine(engine)  # shared when set
        self._tbl = (
            NativeTable(engine, self._which) if engine is not None else PyTable()
        )
        # foreign delta columns buffered per row per polarity (sparse
        # {col: max-value} maps from cluster converges)
        self._pending_f: tuple[dict[int, dict[int, int]], ...] = ({}, {})
        # sync-digest bookkeeping (cluster/syncdigest): a CUMULATIVE join
        # of every foreign column ever converged, keyed by replica id —
        # unlike _pending_f it never clears, so the per-key canonical
        # state (own ⊔ foreign) reads host-side with no device pull
        self._sync_f: tuple[dict[int, dict[int, int]], ...] = ({}, {})
        self._sync_dirty_extra: set[int] = set()  # converge-path rows

    def _get_raw(self, key: bytes) -> int:
        """Serving value bits for a key (drains first when foreign deltas
        make the cache stale; local writes keep it exact)."""
        row = self._tbl.find(key)
        if row < 0:
            return 0
        if self._tbl.is_foreign(row):
            self.drain()
        return self._tbl.value(row)

    def _col_for(self, rid: int) -> int:
        col = self._rids.get(rid)
        if col is None:
            col = len(self._rids)
            self._rids[rid] = col
        return col

    def _round_cap(self, k: int) -> int:
        """Key capacity must split evenly over the mesh's keys axis."""
        ns = self._n_shards
        return -(-k // ns) * ns

    def _place(self, state):
        """(Re-)place the state plane keys-sharded when a mesh is active."""
        if self._mesh is None:
            return state
        return shard_plane(self._mesh, state)

    def _grow_to_fit(self) -> None:
        k = self._round_cap(bucket(max(self._tbl.rows(), 1), self._key_cap))
        r = bucket(max(len(self._rids), 1), self._rep_cap)
        if k != self._key_cap or r != self._rep_cap:
            self._key_cap, self._rep_cap = k, r
            self._state = self._place(self._ops.grow(self._state, k, r))

    def deltas_size(self) -> int:
        return self._tbl.dirty_count()

    def may_drain(self, args: list[bytes]) -> bool:
        """Will this command hit the device? Only a GET over a row holding
        un-drained FOREIGN deltas does (local writes keep the host value
        cache exact); the server offloads such commands to a thread."""
        if len(args) < 2 or args[0] != b"GET":
            return False
        row = self._tbl.find(args[1])
        return row >= 0 and self._tbl.is_foreign(row)

    def _pend_size(self) -> int:
        """Exact drain batch size: own-pending rows unioned with the
        buffered foreign rows (metrics, read before the drain runs)."""
        own_rows, _vp, _vn = self._tbl.export_pending(clear=False)
        rows = set(own_rows)
        rows.update(self._pending_f[0])
        rows.update(self._pending_f[1])
        return len(rows)

    def converge_polarity(self, key: bytes, polarity: int, delta: dict) -> None:
        row = self._tbl.upsert(key)
        p = self._pending_f[polarity].setdefault(row, {})
        sf = self._sync_f[polarity].setdefault(row, {})
        for rid, v in delta.items():
            col = self._col_for(rid)
            if v > p.get(col, 0):
                p[col] = v
            if v > sf.get(rid, 0):
                sf[rid] = v
        self._sync_dirty_extra.add(row)
        self._tbl.set_foreign(row)

    def _collect_rows(self):
        """The drain batch: pending-own values merged with the buffered
        foreign columns -> (rows, per-row {col: val} per polarity).
        Reads WITHOUT clearing: the window clears in `_finish_drain`, so
        a device failure mid-drain keeps every contribution for the
        retry (the old dict path's exception-safety contract)."""
        own_rows, vp, vn = self._tbl.export_pending(clear=False)
        own_col = self._col_for(self._identity)
        per_pol: tuple[dict[int, dict[int, int]], ...] = ({}, {})
        for pol, own_vals in ((0, vp), (1, vn)):
            fdict = self._pending_f[pol]
            for row, v in zip(own_rows, own_vals):
                if v:
                    per_pol[pol][row] = {own_col: v}
            for row, cols in fdict.items():
                d = per_pol[pol].setdefault(row, {})
                for col, v in cols.items():
                    if v > d.get(col, 0):
                        d[col] = v
        rows = list(dict.fromkeys(list(per_pol[0]) + list(per_pol[1])))
        return rows, per_pol

    def _finish_drain(self, rows, values_bits) -> None:
        self._tbl.apply_drain(rows, values_bits)
        self._tbl.export_pending(clear=True)  # drain succeeded: clear window
        self._pending_f[0].clear()
        self._pending_f[1].clear()

    # -- sync digest (cluster/syncdigest.py) ---------------------------------

    def sync_dirty_keys(self) -> list[bytes]:
        """Keys whose canonical state may have changed since the last
        digest pass (native INC/DEC fast path ∪ converge/load); clears."""
        rows = set(self._tbl.export_sync_dirty())
        rows.update(self._sync_dirty_extra)
        self._sync_dirty_extra.clear()
        return [self._tbl.key_of(r) for r in rows]

    def _sync_cols(self, row: int, polarity: int) -> list[tuple[int, int]]:
        """{rid: max} for one polarity: own contribution ⊔ the cumulative
        foreign mirror — exactly the column state the device converges
        to, with no device read."""
        d = dict(self._sync_f[polarity].get(row, ()))
        if self._tbl.own_set(row) & (1 << polarity):
            own = self._tbl.own(row, polarity)
            if own > d.get(self._identity, 0):
                d[self._identity] = own
        return sorted((rid, v) for rid, v in d.items() if v)

    # -- snapshot plumbing shared by both types ------------------------------

    def _sorted_keys(self):
        return sorted(
            (self._tbl.key_of(r), r) for r in range(self._tbl.rows())
        )


class RepoGCOUNT(_CounterRepo):
    name = "GCOUNT"
    help = GCOUNT_HELP
    _ops = gcount
    _which = ENG_G

    def __init__(self, identity: int, **kw):
        super().__init__(identity, **kw)
        self._state = self._place(gcount.init(self._key_cap, self._rep_cap))

    def _get_value(self, key: bytes) -> int:
        return self._get_raw(key)

    def sync_canon(self, key: bytes) -> bytes | None:
        row = self._tbl.find(key)
        if row < 0:
            return None
        cols = self._sync_cols(row, 0)
        return repr(cols).encode() if cols else None

    # -- commands (repo_gcount.pony:25-60) ---------------------------------

    def apply(self, resp, args: list[bytes]) -> bool:
        op = need(args, 0)
        if op == b"GET":
            resp.u64(self._get_value(need(args, 1)))
            return False
        if op == b"INC":
            key = need(args, 1)
            amount = parse_u64(need(args, 2))
            self._tbl.inc(self._tbl.upsert(key), 0, amount)
            resp.ok()
            return True
        raise ParseError()

    # -- lattice plumbing ---------------------------------------------------

    def converge(self, key: bytes, delta: dict) -> None:
        self.converge_polarity(key, 0, delta)

    @timed_drain("GCOUNT", _CounterRepo._pend_size)
    def drain(self) -> None:
        rows, per_pol = self._collect_rows()
        if not rows:
            return
        self._grow_to_fit()
        pending = per_pol[0]
        if self._mesh is not None:
            deltas = np.zeros((len(rows), self._rep_cap), np.uint64)
            for i, row in enumerate(rows):
                for col, v in pending.get(row, {}).items():
                    deltas[i, col] = v
            lr, payload, slots = route_drain64(
                np.asarray(rows, np.int64),
                deltas,
                self._n_shards,
                self._key_cap // self._n_shards,
            )
            d = planes.pack64_np(payload)
            drain_phase(self, DEVICE)
            self._state, sums = drain_sharded_g(self._mesh, self._state, lr, d)
            sums = np.asarray(sums)
            drain_phase(self, FINISH)
            live = [(int(g), sums[j]) for j, g in enumerate(slots) if g >= 0]
            self._finish_drain([r for r, _ in live], [v for _, v in live])
        elif len(rows) * DENSE_FRACTION >= self._key_cap:
            dense = np.zeros((self._key_cap, self._rep_cap), np.uint64)
            for row in rows:
                for col, v in pending.get(row, {}).items():
                    dense[row, col] = v
            d = planes.pack64_np(dense)
            drain_phase(self, DEVICE)
            self._state, sums = _drain_g_dense(self._state, d)
            sums = np.asarray(sums)
            drain_phase(self, FINISH)
            self._finish_drain(rows, [sums[row] for row in rows])
        else:
            b = bucket(len(rows))
            ki = pad_rows(b)
            ki[: len(rows)] = rows
            deltas = np.zeros((b, self._rep_cap), np.uint64)
            for i, row in enumerate(rows):
                for col, v in pending.get(row, {}).items():
                    deltas[i, col] = v
            d = planes.pack64_np(deltas)
            drain_phase(self, DEVICE)
            self._state, sums = _drain_g(self._state, ki, d)
            sums = np.asarray(sums)
            drain_phase(self, FINISH)
            self._finish_drain(rows, [sums[i] for i in range(len(rows))])

    def flush_deltas(self):
        rows, op, _on, _sb = self._tbl.export_dirty()
        out = sorted(
            (self._tbl.key_of(r), {self._identity: int(v)})
            for r, v in zip(rows, op)
        )
        return out

    # -- snapshot (persist.py): full state in the wire-delta shape ----------

    def dump_state(self):
        self.drain()
        counts = gcount.to_counts(self._state)
        # jlint: order-ok — builds a col->rid LOOKUP map (order unused);
        # the wire encoder sorts every span by rid before any byte ships
        cols = {col: rid for rid, col in self._rids.items()}
        out = []
        for key, row in self._sorted_keys():
            d = {
                cols[c]: int(v)
                for c, v in enumerate(counts[row, : len(cols)])
                if v
            }
            if d:
                out.append((key, d))
        return out

    def load_state(self, batch) -> None:
        for key, delta in batch:
            self.converge(key, delta)
            # my own column is my private monotonic state: losing it would
            # make future INCs disappear under the pending max
            # jlint: ridbranch-ok — boot-only own-column repair; the
            # lattice value converged above is identity-independent
            if self._identity in delta:
                self._tbl.own_max(
                    self._tbl.upsert(key), 0, delta[self._identity]
                )


class RepoPNCOUNT(_CounterRepo):
    name = "PNCOUNT"
    help = PNCOUNT_HELP
    _ops = pncount
    _which = ENG_PN

    def __init__(self, identity: int, **kw):
        super().__init__(identity, **kw)
        self._state = self._place(pncount.init(self._key_cap, self._rep_cap))

    def _get_value(self, key: bytes) -> int:
        return _wrap_i64(self._get_raw(key))

    def sync_canon(self, key: bytes) -> bytes | None:
        row = self._tbl.find(key)
        if row < 0:
            return None
        p = self._sync_cols(row, 0)
        n = self._sync_cols(row, 1)
        return repr((p, n)).encode() if p or n else None

    # -- commands (repo_pncount.pony:26-67) --------------------------------

    def apply(self, resp, args: list[bytes]) -> bool:
        op = need(args, 0)
        if op == b"GET":
            resp.i64(self._get_value(need(args, 1)))
            return False
        if op in (b"INC", b"DEC"):
            key = need(args, 1)
            amount = parse_u64(need(args, 2))
            self._tbl.inc(
                self._tbl.upsert(key), 0 if op == b"INC" else 1, amount
            )
            resp.ok()
            return True
        raise ParseError()

    def converge(self, key: bytes, delta: tuple) -> None:
        dp, dn = delta
        self.converge_polarity(key, 0, dp)
        self.converge_polarity(key, 1, dn)

    @timed_drain("PNCOUNT", _CounterRepo._pend_size)
    def drain(self) -> None:
        rows, per_pol = self._collect_rows()
        if not rows:
            return
        self._grow_to_fit()
        pend_p, pend_n = per_pol
        # every batch is the (rows, 2R) u64 matrix [P | N], as the plane is
        r = self._rep_cap
        if self._mesh is not None:
            deltas = np.zeros((len(rows), 2 * r), np.uint64)
            for i, row in enumerate(rows):
                for col, v in pend_p.get(row, {}).items():
                    deltas[i, col] = v
                for col, v in pend_n.get(row, {}).items():
                    deltas[i, r + col] = v
            lr, payload, slots = route_drain64(
                np.asarray(rows, np.int64),
                deltas,
                self._n_shards,
                self._key_cap // self._n_shards,
            )
            d = planes.pack64_np(payload)
            drain_phase(self, DEVICE)
            self._state, sums = drain_sharded_pn(self._mesh, self._state, lr, d)
            sums = np.asarray(sums).view(np.uint64)
            drain_phase(self, FINISH)
            live = [(int(g), sums[j]) for j, g in enumerate(slots) if g >= 0]
            self._finish_drain([r for r, _ in live], [v for _, v in live])
        elif len(rows) * DENSE_FRACTION >= self._key_cap:
            dense = np.zeros((self._key_cap, 2 * r), np.uint64)
            for row in rows:
                for col, v in pend_p.get(row, {}).items():
                    dense[row, col] = v
                for col, v in pend_n.get(row, {}).items():
                    dense[row, r + col] = v
            d = planes.pack64_np(dense)
            drain_phase(self, DEVICE)
            self._state, sums = _drain_pn_dense(self._state, d)
            sums = np.asarray(sums).view(np.uint64)
            drain_phase(self, FINISH)
            self._finish_drain(rows, [sums[row] for row in rows])
        else:
            b = bucket(len(rows))
            ki = pad_rows(b)
            ki[: len(rows)] = rows
            deltas = np.zeros((b, 2 * r), np.uint64)
            for i, row in enumerate(rows):
                for col, v in pend_p.get(row, {}).items():
                    deltas[i, col] = v
                for col, v in pend_n.get(row, {}).items():
                    deltas[i, r + col] = v
            d = planes.pack64_np(deltas)
            drain_phase(self, DEVICE)
            self._state, sums = _drain_pn(self._state, ki, d)
            sums = np.asarray(sums).view(np.uint64)
            drain_phase(self, FINISH)
            self._finish_drain(rows, [sums[i] for i in range(len(rows))])

    def flush_deltas(self):
        rows, op, on, sb = self._tbl.export_dirty()
        out = []
        for r, p, n, bits in zip(rows, op, on, sb):
            dp = {self._identity: int(p)} if bits & 1 else {}
            dn = {self._identity: int(n)} if bits & 2 else {}
            out.append((self._tbl.key_of(r), (dp, dn)))
        out.sort()
        return out

    # -- snapshot (persist.py): full state in the wire-delta shape ----------

    def dump_state(self):
        self.drain()
        # jlint: order-ok — builds a col->rid LOOKUP map (order unused);
        # the wire encoder sorts every span by rid before any byte ships
        cols = {col: rid for rid, col in self._rids.items()}
        counts = planes.unpack64_np(self._state)  # [P | N]
        p, n = counts[:, : self._rep_cap], counts[:, self._rep_cap :]
        out = []
        for key, row in self._sorted_keys():
            dp = {cols[c]: int(v) for c, v in enumerate(p[row, : len(cols)]) if v}
            dn = {cols[c]: int(v) for c, v in enumerate(n[row, : len(cols)]) if v}
            if dp or dn:
                out.append((key, (dp, dn)))
        return out

    def load_state(self, batch) -> None:
        for key, (dp, dn) in batch:
            self.converge(key, (dp, dn))
            row = self._tbl.upsert(key)
            # jlint: ridbranch-ok — boot-only own-column repair (above)
            if self._identity in dp:
                self._tbl.own_max(row, 0, dp[self._identity])
            # jlint: ridbranch-ok — boot-only own-column repair (above)
            if self._identity in dn:
                self._tbl.own_max(row, 1, dn[self._identity])
