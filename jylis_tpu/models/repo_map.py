"""MAP repo: a generic key -> (field -> registered lattice) keyspace.

ROADMAP item 4's first half. No reference analog — jylis has no
composite type; the design frame is arXiv:2004.04303 (lattice
composition) + arXiv:1605.06424 (decomposed deltas). The value
semantics live in ops/compose.py; this repo is the vertical-slice
glue: RESP surface, decomposed per-field delta flushes, converge,
(key, field)-granular digest entries, snapshot dump/load, and the
drain of TREG fields to their device table.

RESP surface (``MAP <TYPE> <OP> …``, TYPE = any registered inner
lattice — TREG, TLOG, GCOUNT, PNCOUNT):

    MAP <TYPE> SET key field <inner write args…>
    MAP <TYPE> GET key field
    MAP <TYPE> GETALL key
    MAP <TYPE> DEL key field
    MAP <TYPE> KEYS key

``GETALL`` (Redis's HGETALL) answers one array: the key's live fields of
that type in ascending byte order of their names, each as the field name
followed by what ``GET`` of that field answers; an unknown key answers
the empty array.

Two tables (models/map_table.py). On a native node the fields whose
inner type is TREG live in the engine's field table, which settles
``MAP TREG SET`` / ``GET`` / ``GETALL`` inside the server's burst; every
other command comes here, and fields of the other inner types stay in
the Python table (`PyMapTable`, also the oracle that holds every field
when there is no engine). A native row is the field's whole state and
takes every write and every foreign unit at once, so a read never waits
for a drain; a drain ships the rows changed since the last one to the
device table (ops/map_fields.py: the counters' join for ``ver`` /
``tomb``, TREG's for the register, ties settled by the table as TREG's
are), at TREG's threshold.

Delta wire shape: ``(packed(key, field), (itype, ver, tomb, val))`` —
one FIELD's full product state per entry (self-justifying under join;
the inner val uses the inner type's own delta encoding, recursively —
schema v9). One field edit ships one field, never the map; a DEL ships
a tombstone-only unit (ver empty, val = inner bottom). The digest tree
hashes packed (key, field) leaves, so Merkle-range repair pulls
divergent FIELDS.
"""

from __future__ import annotations

from functools import partial

import jax
import numpy as np

from ..cluster.codec import WireBatch
from ..native.engine import resolve_engine
from ..ops import map_fields
from ..ops.compose import REGISTRY, pack_field, unpack_field
from ..utils.metrics import (
    DEVICE,
    FINISH,
    drain_phase,
    resolve_registry,
    timed_drain,
)
from .base import ParseError, bucket, need, pad_rows
from .help import RepoHelp
from .map_table import NativeMapTable, PyMapTable
from .repo_treg import DENSE_FRACTION, batch_planes, patch_tie_vids

MAP_HELP = RepoHelp(
    "MAP",
    {
        "SET": "type key field ...  (inner write args, e.g. TREG: value ts)",
        "GET": "type key field",
        "GETALL": "type key",
        "DEL": "type key field",
        "KEYS": "type key",
    },
)

# field rows changed since the last drain go to the device table once they
# pile this high: TREG's threshold (reads never need the drain), and
# native/serve_engine.cpp MAP_PENDING_DRAIN must match
PENDING_DRAIN_THRESHOLD = 4096
# foreign units of the Python table buffered past this fold in a worker
# thread off the serving loop
HOST_PENDING_THRESHOLD = 512


# the device table's two programs (a trace shows `jit__drain_map` and
# `jit__drain_map_dense`): the planes' join and TREG's over one row index
@partial(jax.jit, donate_argnums=0)
def _drain_map(state, ki, cells, ts_hi, ts_lo, rank_hi, rank_lo, vid):
    return map_fields.converge_batch(
        state, ki, cells, ts_hi, ts_lo, rank_hi, rank_lo, vid
    )


@partial(jax.jit, donate_argnums=0)
def _drain_map_dense(state, cells, ts_hi, ts_lo, rank_hi, rank_lo, vid):
    return map_fields.converge_dense(
        state, cells, ts_hi, ts_lo, rank_hi, rank_lo, vid
    )


class RepoMAP:
    name = "MAP"
    help = MAP_HELP

    def __init__(
        self, identity: int, engine="auto", field_cap: int = 1024,
        rep_cap: int = 8, **_kw
    ):
        self._identity = identity
        self.engine = engine = resolve_engine(engine)
        self._tbl = PyMapTable()
        # the TREG fields' table and its device mirror: a native node's
        self._nat = None
        self._state = None
        if engine is not None:
            # the split into two tables rests on this: a field's type
            # is settled by the greater type NAME, so a row of the
            # native table (TREG's) is never displaced by another type
            if max(REGISTRY) != "TREG":
                raise RuntimeError(
                    f"an inner type named above TREG ({max(REGISTRY)}): "
                    "the native field table's rows would win contests "
                    "they must lose"
                )
            self._nat = NativeMapTable(engine, identity)
            # eight replica columns from the start, the counters' floor
            # (repo_counters.py): a cluster of up to eight gathers with
            # no plane re-laid and no drain recompiled under the lock;
            # at one replica seven of the eight stay zero
            self._field_cap, self._rep_cap = field_cap, rep_cap
            self._state = map_fields.init(field_cap, rep_cap)
        # wire units dropped at the converge boundary (malformed
        # composite key from a peer): nothing joinable to keep, but the
        # count stays visible to tests/debugging
        self._dropped_units = 0

    # -- commands ------------------------------------------------------------

    def apply(self, resp, args: list[bytes]) -> bool:
        itype_b = need(args, 0)
        op = need(args, 1)
        itype = itype_b.decode("ascii", "replace")
        inner = REGISTRY.get(itype)
        if inner is None:
            raise ParseError()
        nat = self._nat if itype == "TREG" else None
        if op == b"GET":
            val = self.get_value(need(args, 2), need(args, 3), itype)
            if val is None:
                resp.null()
            else:
                inner.render(resp, val)
            return False
        if op in (b"KEYS", b"GETALL"):
            key = need(args, 2)
            whole = op == b"GETALL"
            if nat is not None:
                pairs = [
                    (nat.field_name(int(r)), nat.get(int(r)))
                    for r in nat.record(key, count=whole)
                ]
            else:
                if self._tbl.pending:
                    self.drain()
                m = self._tbl.find(key)
                pairs = [
                    (f, m.fields[f].val)
                    for f in (m.live_fields(itype) if m is not None else [])
                ]
                if whole:
                    reg = resolve_registry(self)
                    reg.tally("drain.MAP.getalls", 1)
                    reg.tally("drain.MAP.getall_fields", len(pairs))
            resp.array_start(len(pairs) * (2 if whole else 1))
            for f, val in pairs:
                resp.string(f)
                if whole:
                    inner.render(resp, val)
            return False
        if op == b"SET":
            key, field = need(args, 2), need(args, 3)
            if nat is not None:
                self._set_native(key, field, args[4:])
                resp.ok()
                return True
            if self._tbl.pending:
                # local edit counters must advance past everything this
                # replica has OBSERVED, including buffered foreign units
                self.drain()
            row = self._nat.find(key, field) if self._nat is not None else -1
            try:
                if row >= 0:
                    # the field is a TREG register and TREG outranks
                    # every other type name: parsed, and dominated
                    inner.write(None, self._identity, args[4:])
                    self._nat.note_edit(row)
                else:
                    self._tbl.map_for(key).set_field(
                        field, self._identity, itype, args[4:]
                    )
                    self._tbl.note_edit(key, field)
                    if self._nat is not None:
                        self._nat.mark_mixed(key)
            except ValueError:
                raise ParseError() from None
            resolve_registry(self).tally("drain.MAP.sets", 1)
            resp.ok()
            return True
        if op == b"DEL":
            key, field = need(args, 2), need(args, 3)
            resp.ok()
            # the field's type is not the command's to match: a DEL
            # removes the field whatever it holds (MapCRDT.del_field)
            row = self._nat.find(key, field) if self._nat is not None else -1
            if row >= 0:
                if not self._nat.delete(row):
                    return False
                self._after_native_write()
                return True
            if self._tbl.pending:
                # observed-remove: the tombstone must cover the edits
                # this replica has seen — fold them in first
                self.drain()
            m = self._tbl.find(key)
            unit = m.del_field(field, self._identity) if m is not None else None
            if unit is None:
                return False  # unknown/dead field: nothing to remove
            self._tbl.note_edit(key, field)
            return True
        raise ParseError()

    def _py_view(self, key: bytes, field: bytes, itype: str):
        if self._tbl.pending:
            self.drain()
        m = self._tbl.find(key)
        return m.get_field(field, itype) if m is not None else None

    def _set_native(self, key: bytes, field: bytes, tail: list) -> None:
        try:
            value, ts = REGISTRY["TREG"].write(None, self._identity, tail)
        except ValueError:
            raise ParseError() from None
        self._displace(key, field)
        self._nat.set(key, field, self._identity, ts, value)
        self._after_native_write()

    def _displace(self, key: bytes, field: bytes) -> bool:
        """A TREG unit is landing on (key, field): a field of a lesser
        type the Python table holds under that name loses wholesale
        (type-name dominance), here as on every replica. True when that
        field had an edit still to flush: the native row inherits it
        (`note_edit`), as the oracle's one dirty set would carry it."""
        if not self._tbl.maps:
            return False
        if self._tbl.pending:
            self._fold_host()
        m = self._tbl.find(key)
        if m is None or m.fields.pop(field, None) is None:
            return False
        packed = pack_field(key, field)
        self._tbl.sync_dirty.discard(packed)
        if packed in self._tbl.dirty:
            self._tbl.dirty.discard(packed)
            return True
        return False

    def _after_native_write(self) -> None:
        if self._nat.pend_count() >= PENDING_DRAIN_THRESHOLD:
            self.drain()

    # -- lattice plumbing ----------------------------------------------------

    def converge(self, key: bytes, delta: tuple) -> None:
        # key is the PACKED (key, field) composite. A TREG unit joins
        # the native row at once (the device catches up at the next
        # drain); any other is buffered for the Python table's fold —
        # the serving path drains via drain_overdue in a worker thread.
        # Validate the composite SHAPE eagerly: the codec treats batch
        # keys as opaque bytes, so a buggy peer can ship a key no
        # unpack can parse — buffered unvalidated, it would blow up the
        # fold mid-drain and take every other buffered unit with it.
        # A key that names no (key, field) carries nothing joinable:
        # drop it here, alone.
        try:
            k, f = unpack_field(key)
        except ValueError:
            self._dropped_units += 1
            return
        if self._nat is None:
            self._tbl.buffer_unit(key, delta)
        elif delta[0] == "TREG":
            edited = self._displace(k, f)
            row = self._nat.join_unit(key, delta)
            if edited:
                self._nat.note_edit(row)
        else:
            self._nat.mark_mixed(k)
            self._tbl.buffer_unit(key, delta)

    def may_drain(self, args: list[bytes]) -> bool:
        """A write that lands on a native row may trip the threshold
        drain, which the server offloads to a thread (+1 as
        RepoTREG.may_drain); reads never drain the device table."""
        return (
            self._nat is not None
            and len(args) > 1
            and args[1] in (b"SET", b"DEL")
            and self._nat.pend_count() + 1 >= PENDING_DRAIN_THRESHOLD
        )

    def drain_overdue(self) -> bool:
        return len(self._tbl.pending) >= HOST_PENDING_THRESHOLD or (
            self._nat is not None
            and self._nat.pend_count() >= PENDING_DRAIN_THRESHOLD
        )

    def _pend_size(self) -> int:
        n = len(self._tbl.pending)
        return n + self._nat.pend_count() if self._nat is not None else n

    def _fold_host(self) -> None:
        """The Python table's buffered units; one that names a field
        the native table holds is a lesser type's, and loses."""
        if self._nat is not None:
            self._tbl.pending = [
                (packed, unit)
                for packed, unit in self._tbl.pending
                if self._nat.find(*unpack_field(packed)) < 0
            ]
        self._tbl.fold_pending()

    def warm_drain_shapes(self) -> None:
        """Compile the sparse drain at the two batch shapes a serving
        node meets at its present capacity (RepoTREG.warm_drain_shapes:
        the threshold batch and the next bucket up), after recovery has
        settled the capacity. Every row is an out-of-range pad."""
        if self._nat is None:
            return
        self.drain()
        for b in (PENDING_DRAIN_THRESHOLD, 2 * PENDING_DRAIN_THRESHOLD):
            if b * DENSE_FRACTION >= self._field_cap:
                continue
            self._state, _tie = _drain_map(
                self._state, pad_rows(b),
                np.zeros((b, 4 * self._rep_cap), np.uint32), *batch_planes(b)
            )

    @timed_drain("MAP", _pend_size)
    def drain(self) -> None:
        nat = self._nat
        n = nat.pend_count() if nat is not None else 0
        if not n:
            # a host fold: nothing to assemble for a device, the whole
            # drain is results going into the host table
            drain_phase(self, FINISH)
            self._fold_host()
            return
        cap = bucket(max(nat.rows(), 1), self._field_cap)
        rep = bucket(max(nat.replicas(), 1), self._rep_cap)
        if (cap, rep) != (self._field_cap, self._rep_cap):
            self._field_cap, self._rep_cap = cap, rep
            self._state = map_fields.grow(self._state, cap, rep)
        dense = n * DENSE_FRACTION >= cap
        b = cap if dense else bucket(n)
        ki = np.empty(n, np.int32) if dense else pad_rows(b)
        cells = np.zeros((b, 4 * rep), np.uint32)
        reg = batch_planes(b)
        nat.export_planes(ki, cells, *reg, dense=dense)
        drain_phase(self, DEVICE)
        if dense:
            self._state, tie = _drain_map_dense(self._state, cells, *reg)
        else:
            self._state, tie = _drain_map(self._state, ki, cells, *reg)
        hit = np.flatnonzero(np.asarray(tie))
        drain_phase(self, FINISH)
        if hit.size:
            # prefix collision: the host row is the winner; patch the
            # mirror's id (dense outputs are in row order)
            rows, vids = nat.settle_ties(hit if dense else ki[hit])
            self._state = self._state._replace(
                reg=patch_tie_vids(self._state.reg, rows, vids)
            )
        nat.clear_pend()
        resolve_registry(self).tally("drain.MAP.tie_rows", int(hit.size))
        if self._tbl.pending:
            self._fold_host()

    def device_rows(self, rows):
        """Field rows gathered back from the device table (tests)."""
        return map_fields.read(self._state, np.asarray(rows, np.int32))

    def deltas_size(self) -> int:
        n = len(self._tbl.dirty)
        return n + self._nat.dirty_count() if self._nat is not None else n

    def flush_deltas(self):
        """The fields edited since the last flush, each as its full
        unit. A native node's leave as ONE buffer of wire bytes
        (`WireBatch`) that the journal and the broadcast copy into
        their frames; the Python table's units join it encoded."""
        if self._tbl.pending:
            self.drain()
        out = []
        for packed in self._tbl.export_dirty():
            unit = self._tbl.field_unit(packed)
            if unit is not None:
                out.append((packed, unit))
        if self._nat is None:
            return out
        batch = self._nat.wire(self._nat.take_dirty())
        return batch + WireBatch.of_units(out) if out else batch

    # -- sync digest (models/database.py incremental tree) -------------------

    def sync_prepare(self) -> None:
        if self._tbl.pending:
            self.drain()

    def sync_dirty_keys(self) -> list[bytes]:
        out = self._tbl.export_sync_dirty()
        if self._nat is not None:
            out += self._nat.wire(self._nat.take_sync()).keys()
        return out

    def sync_canon(self, key: bytes) -> bytes | None:
        canon = self._tbl.field_canon(key)
        if canon is None and self._nat is not None:
            row = self._nat.find(*unpack_field(key))
            if row >= 0:
                ((_k, (itype, ver, tomb, (value, ts))),) = self._nat.wire([row])
                canon = (
                    itype, tuple(sorted(ver.items())),
                    tuple(sorted(tomb.items())), (ts, value),
                )
        return None if canon is None else repr(canon).encode()

    # -- snapshot (persist.py): full state in the wire-delta shape ----------

    def dump_state(self):
        """Every field's unit, sorted by packed key within each table.
        The native table's come as ONE buffer of wire bytes
        (`WireBatch`), which the snapshot writer copies and anything
        else iterates as (key, unit) tuples."""
        self.drain()
        out = []
        for packed in self._tbl.all_packed():
            unit = self._tbl.field_unit(packed)
            if unit is not None:
                out.append((packed, unit))
        if self._nat is None or not self._nat.rows():
            return out
        native = self._nat.wire()
        if not out:
            return native
        return sorted(out + list(native))

    def load_state(self, batch) -> None:
        if self._nat is not None and isinstance(batch, WireBatch):
            # boot recovery: the checked wire bytes join the native
            # table in one call, no object a unit; the host arrays are
            # made for the batch's own count first, and the drain below
            # grows the device table to it once
            self._nat.reserve(batch.count // 8, batch.count)
            self._nat.load_wire(batch.payload, batch.count)
            if self._tbl.pending:
                self._fold_host()
            for key, m in list(self._tbl.maps.items()):
                for field in list(m.fields):
                    row = self._nat.find(key, field)
                    if row >= 0 and self._displace(key, field):
                        self._nat.note_edit(row)
        else:
            for packed, unit in batch:
                self.converge(packed, unit)
        self.drain()

    # -- direct host views (tests) --------------------------------------------

    def get_value(self, key: bytes, field: bytes, itype: str):
        if self._nat is not None and itype == "TREG":
            row = self._nat.find(key, field)
            return self._nat.get(row) if row >= 0 else None
        return self._py_view(key, field, itype)


def unpack_wire_key(packed: bytes) -> tuple[bytes, bytes]:
    """Re-exported for operators/tests reading journal or range frames."""
    return unpack_field(packed)


__all__ = ["RepoMAP", "MAP_HELP", "pack_field", "unpack_wire_key"]
