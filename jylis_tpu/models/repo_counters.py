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
share one source of truth. The foreign window lives there too, held
ONCE: the columns peers converged into a row, cumulative, by column
(this module owns replica id -> column). A slice of foreign deltas is
flattened into arrays in one pass and folded in by one table call
(`converge_batch`; `converge` is its one-key form, `load_state` its
boot form); the sync digest reads the same columns; and a drain takes
its batch from one table call, ready: the rows and the u64 matrix
`[P | N]`, pending own values joined with the foreign rows' columns.

Delta wire shape: GCOUNT -> dict {replica_id: u64}; PNCOUNT -> a
(p_dict, n_dict) pair. Outbound deltas carry only this node's own column
(absolute values — joinable delta-state), which the table tracks exactly,
so flushes never need a device read.
"""

from __future__ import annotations

from functools import partial
from itertools import chain, islice
from operator import methodcaller

import jax
import numpy as np

from ..native.codec import flatten_lazy
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
from ..utils.metrics import (
    DEVICE,
    FINISH,
    drain_phase,
    resolve_registry,
    timed_drain,
)
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

# keys a restore folds per table call: bounds the flattened arrays
LOAD_SLICE = 1 << 16

_VALUES = methodcaller("values")  # of a dict or of the codec's lazy map


def _wrap_i64(v: int) -> int:
    """Wrap into signed-64 range (the reference's modular (p-n).i64())."""
    return ((v + (1 << 63)) & U64_MAX) - (1 << 63)


class _CounterRepo:
    """Shared machinery; subclasses bind the ops module, the drain
    programs and the command set."""

    _which: int  # native engine table id
    _npol: int  # polarities: the batch matrix is (rows, _npol * rep_cap)

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
        self._rid_of: list[int] = []  # column -> replica id
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
        self._state = self._place(self._ops.init(self._key_cap, self._rep_cap))

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
            col = self._rids[rid] = len(self._rid_of)
            self._rid_of.append(rid)
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
        foreign rows (metrics, read before the drain runs)."""
        return self._tbl.drain_count()

    # -- lattice plumbing ---------------------------------------------------

    def _fold(self, batch, batched: bool, adopt: bool = False) -> None:
        """Join [(key, delta), ...] into the table's foreign window: the
        deltas flattened into arrays (cells per (key, polarity), and the
        cells' columns and values in that order) with no interpreted
        step a key, one column lookup per distinct replica id, one table
        call. ``adopt`` (a restore) also takes this node's own column as
        its own contribution: it is private monotonic state, and losing
        it would make future INCs vanish under the max."""
        keys, deltas = zip(*batch)
        flat = flatten_lazy(deltas)  # the native decode's: no dict a key
        if flat is None:
            # one {rid: value} per (key, polarity): PNCOUNT's delta is (P, N)
            dicts = deltas if self._npol == 1 else list(chain.from_iterable(deltas))
            flat = (
                list(map(len, dicts)),
                list(chain.from_iterable(dicts)),
                list(chain.from_iterable(map(_VALUES, dicts))),
            )
        counts, rids, vals = flat
        for rid in dict.fromkeys(rids):
            self._col_for(rid)
        cells = self._tbl.fold_foreign(
            self._tbl.upsert_many(keys),
            self._npol,
            np.array(counts, np.int32),
            np.fromiter(map(self._rids.__getitem__, rids), np.int32, len(rids)),
            np.array(vals, np.uint64),
            self._col_for(self._identity) if adopt else -1,
        )
        self._tally(len(keys), len(keys) if batched else 0, cells)

    def converge_batch(self, batch) -> None:
        """A slice of a peer's push, [(key, delta), ...], in one fold."""
        if batch:
            self._fold(batch, batched=True)

    def converge(self, key: bytes, delta) -> None:
        self._fold(((key, delta),), batched=False)

    def load_state(self, batch) -> None:
        batch = iter(batch)
        while chunk := list(islice(batch, LOAD_SLICE)):
            self._fold(chunk, batched=True, adopt=True)

    def _drain(self) -> None:
        """THE drain: the table's ready batch, placed as the mesh, the
        dense or the sparse program takes it, and the joined rows' sums
        back into the table. The window clears in `finish_drain` alone,
        so a device failure mid-drain keeps every contribution for the
        retry."""
        n = self._tbl.drain_count()
        if not n:
            return
        own_col = self._col_for(self._identity)
        self._grow_to_fit()
        mesh = self._mesh
        dense = mesh is None and n * DENSE_FRACTION >= self._key_cap
        # every batch is the (rows, npol * R) u64 matrix [P | N], as the
        # plane is: a row of the batch each (the sparse program's padded
        # with zero rows), or the dense program's whole plane
        b = n if mesh is not None else self._key_cap if dense else bucket(n)
        rows, mat = self._tbl.export_drain(
            own_col, self._rep_cap, self._npol, b, by_row=dense
        )
        if mesh is not None:
            ki, mat, slots = route_drain64(
                rows, mat, self._n_shards, self._key_cap // self._n_shards
            )
        elif not dense:
            ki = pad_rows(b)
            ki[:n] = rows
        d = planes.pack64_np(mat)
        drain_phase(self, DEVICE)
        if mesh is not None:
            self._state, sums = self._drain_mesh(mesh, self._state, ki, d)
        elif dense:
            self._state, sums = self._drain_dense(self._state, d)
        else:
            self._state, sums = self._drain_sparse(self._state, ki, d)
        sums = np.asarray(sums).view(np.uint64)
        drain_phase(self, FINISH)
        if mesh is not None:
            live = slots >= 0
            rows, sums = slots[live], sums[live]
        else:
            sums = sums[rows] if dense else sums[:n]
        self._tbl.finish_drain(rows, sums)

    # -- sync digest (cluster/syncdigest.py) ---------------------------------

    def sync_dirty_keys(self) -> list[bytes]:
        """Keys whose canonical state may have changed since the last
        digest pass (native INC/DEC fast path ∪ converge/load); clears."""
        return [self._tbl.key_of(r) for r in self._tbl.export_sync_dirty()]

    def _sync_cols(self, row: int):
        """Per polarity, the sorted [(rid, max)] of a row: own
        contribution ⊔ the foreign columns — exactly the column state
        the device converges to, with no device read."""
        cols, vp, vn = self._tbl.sync_cols(row, self._col_for(self._identity))
        rids = [self._rid_of[c] for c in cols]
        return (
            sorted((rid, v) for rid, v in zip(rids, vp) if v),
            sorted((rid, v) for rid, v in zip(rids, vn) if v),
        )

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
    _npol = 1
    _drain_sparse = staticmethod(_drain_g)
    _drain_dense = staticmethod(_drain_g_dense)
    _drain_mesh = staticmethod(drain_sharded_g)

    def _get_value(self, key: bytes) -> int:
        return self._get_raw(key)

    def sync_canon(self, key: bytes) -> bytes | None:
        row = self._tbl.find(key)
        if row < 0:
            return None
        cols, _n = self._sync_cols(row)
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

    def _tally(self, keys: int, batched: int, cells: int) -> None:
        reg = resolve_registry(self)
        reg.tally("drain.GCOUNT.converged_keys", keys)
        reg.tally("drain.GCOUNT.batched_keys", batched)
        reg.tally("drain.GCOUNT.foreign_cells", cells)

    @timed_drain("GCOUNT", _CounterRepo._pend_size)
    def drain(self) -> None:
        self._drain()

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
        rid_of = self._rid_of
        out = []
        for key, row in self._sorted_keys():
            d = {
                rid_of[c]: int(v)
                for c, v in enumerate(counts[row, : len(rid_of)])
                if v
            }
            if d:
                out.append((key, d))
        return out


class RepoPNCOUNT(_CounterRepo):
    name = "PNCOUNT"
    help = PNCOUNT_HELP
    _ops = pncount
    _which = ENG_PN
    _npol = 2
    _drain_sparse = staticmethod(_drain_pn)
    _drain_dense = staticmethod(_drain_pn_dense)
    _drain_mesh = staticmethod(drain_sharded_pn)

    def _get_value(self, key: bytes) -> int:
        return _wrap_i64(self._get_raw(key))

    def sync_canon(self, key: bytes) -> bytes | None:
        row = self._tbl.find(key)
        if row < 0:
            return None
        p, n = self._sync_cols(row)
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

    # -- lattice plumbing ---------------------------------------------------

    def _tally(self, keys: int, batched: int, cells: int) -> None:
        reg = resolve_registry(self)
        reg.tally("drain.PNCOUNT.converged_keys", keys)
        reg.tally("drain.PNCOUNT.batched_keys", batched)
        reg.tally("drain.PNCOUNT.foreign_cells", cells)

    @timed_drain("PNCOUNT", _CounterRepo._pend_size)
    def drain(self) -> None:
        self._drain()

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
        rid_of = self._rid_of
        counts = planes.unpack64_np(self._state)  # [P | N]
        p, n = counts[:, : self._rep_cap], counts[:, self._rep_cap :]
        out = []
        for key, row in self._sorted_keys():
            dp = {rid_of[c]: int(v) for c, v in enumerate(p[row, : len(rid_of)]) if v}
            dn = {rid_of[c]: int(v) for c, v in enumerate(n[row, : len(rid_of)]) if v}
            if dp or dn:
                out.append((key, (dp, dn)))
        return out
