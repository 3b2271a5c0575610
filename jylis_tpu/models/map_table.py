"""Host table backends for the MAP repo.

The flat types split host bookkeeping into table backends with a
pure-Python oracle and a native C++ twin (counter_table.py,
treg_table.py). MAP has both too, split by INNER TYPE:

* `PyMapTable`: the oracle, and on every node the table of fields whose
  inner type is not TREG (TLOG, GCOUNT, PNCOUNT). With no native engine
  (``Database(engine="python")``) it holds TREG fields as well.
* `NativeMapTable`: a view over the native serving engine's field table
  (native/engine.h MapTable via native/engine.py): the TREG fields of a
  native node, one row a field with its whole product state. The same
  state the server's burst mutates (`MAP TREG SET` / `GET` / `GETALL`
  settle there), and the source of the device table's drains
  (models/repo_map.py). A (key, field) lives in ONE of the two tables:
  TREG is the greatest type name, so a TREG unit displaces the oracle's
  field of that name and a lesser type's unit never displaces a native
  row (ops/compose.py, type-name dominance).

PyMapTable's state model: ``key -> ops.compose.MapCRDT`` (field -> product-lattice
Field). Three kinds of dirtiness are tracked at FIELD granularity,
keyed by the packed composite wire key (compose.pack_field):

* ``dirty``       — fields edited locally since the last delta flush
                    (what flush_deltas exports: decomposed per-field
                    units, never the map).
* ``sync_dirty``  — fields changed since the last digest fold (what
                    the incremental Merkle tree consumes: leaves hash
                    (key, field) pairs, so range repair pulls fields).
* ``pending``     — foreign units buffered by converge until the next
                    drain (the host analog of the device repos'
                    coalesced delta window; drain is the timed seam).
"""

from __future__ import annotations

from ..cluster.codec import WireBatch
from ..ops.compose import MapCRDT, pack_field, unpack_field


class PyMapTable:
    def __init__(self):
        self.maps: dict[bytes, MapCRDT] = {}
        self.dirty: set[bytes] = set()
        self.sync_dirty: set[bytes] = set()
        self.pending: list[tuple[bytes, tuple]] = []

    def map_for(self, key: bytes) -> MapCRDT:
        m = self.maps.get(key)
        if m is None:
            m = MapCRDT()
            self.maps[key] = m
        return m

    def find(self, key: bytes) -> MapCRDT | None:
        return self.maps.get(key)

    def note_edit(self, key: bytes, field: bytes) -> None:
        packed = pack_field(key, field)
        self.dirty.add(packed)
        self.sync_dirty.add(packed)

    def buffer_unit(self, packed: bytes, unit: tuple) -> None:
        self.pending.append((packed, unit))

    def fold_pending(self) -> None:
        """Apply the buffered foreign units (the drain body). Per-unit
        tolerance: the repo validates composite keys at the converge
        boundary, but a malformed unit reaching here anyway (a direct
        load path, a future regression) must drop ALONE — the swap
        above already emptied the buffer, so one raise would discard
        every unit buffered behind it."""
        pending, self.pending = self.pending, []
        for packed, unit in pending:
            try:
                key, field = unpack_field(packed)
                self.map_for(key).converge_field(field, unit)
            except (ValueError, KeyError):
                continue
            self.sync_dirty.add(packed)

    def export_dirty(self) -> list[bytes]:
        out = sorted(self.dirty)
        self.dirty.clear()
        return out

    def export_sync_dirty(self) -> list[bytes]:
        out = sorted(self.sync_dirty)
        self.sync_dirty.clear()
        return out

    def field_unit(self, packed: bytes) -> tuple | None:
        """The FULL current unit of one field (a fresh copy — callers
        alias it into journal/broadcast sinks), or None if unknown."""
        key, field = unpack_field(packed)
        m = self.maps.get(key)
        if m is None:
            return None
        f = m.fields.get(field)
        return None if f is None else f.unit()

    def field_canon(self, packed: bytes) -> tuple | None:
        """Canonical state of one field — tombstoned fields INCLUDED
        (a replica that saw a DEL and one that did not must digest
        apart until the tombstone syncs)."""
        key, field = unpack_field(packed)
        m = self.maps.get(key)
        if m is None:
            return None
        f = m.fields.get(field)
        return None if f is None else f.canon()

    def all_packed(self) -> list[bytes]:
        return sorted(
            pack_field(key, field)
            for key, m in self.maps.items()
            for field in m.fields
        )


class NativeMapTable:
    """The TREG fields of MAP, in the shared native serving engine."""

    __slots__ = ("_eng",)

    def __init__(self, engine, identity: int):
        self._eng = engine
        engine.map_set_rid(identity)

    def rows(self) -> int:
        return self._eng.map_rows()

    def replicas(self) -> int:
        return self._eng.map_rid_count()

    def reserve(self, keys: int, fields: int) -> None:
        self._eng.map_reserve(keys, fields)

    def find(self, key: bytes, field: bytes) -> int:
        return self._eng.map_find(key, field)

    def set(self, key: bytes, field: bytes, rid: int, ts: int,
            value: bytes) -> int:
        return self._eng.map_set(key, field, rid, ts, value)

    def delete(self, row: int) -> bool:
        return self._eng.map_del(row)

    def note_edit(self, row: int) -> None:
        self._eng.map_note_edit(row)

    def get(self, row: int):
        return self._eng.map_get(row)

    def field_name(self, row: int) -> bytes:
        return self._eng.map_field_name(row)

    def mark_mixed(self, key: bytes) -> None:
        self._eng.map_mark_mixed(key)

    def record(self, key: bytes, count: bool = False):
        return self._eng.map_record(key, count)

    def join_unit(self, packed: bytes, unit: tuple) -> int:
        _itype, ver, tomb, (value, ts) = unit
        return self._eng.map_join_unit(packed, ver, tomb, ts, value)

    def load_wire(self, payload, count: int) -> None:
        self._eng.map_load_wire(payload, count)

    def wire(self, rows=None) -> WireBatch:
        """Rows (every row, sorted by packed key, with None) as the
        batch a push message, the journal or a snapshot carries."""
        return WireBatch(*self._eng.map_wire(rows))

    def pend_count(self) -> int:
        return self._eng.map_pend_count()

    def dirty_count(self) -> int:
        return self._eng.map_dirty_count()

    def export_planes(self, ki, cells, *reg, dense: bool) -> int:
        return self._eng.map_export_planes(ki, cells, *reg, dense)

    def settle_ties(self, rows):
        return self._eng.map_settle_ties(rows)

    def clear_pend(self) -> None:
        self._eng.map_clear_pend()

    def take_dirty(self) -> list[int]:
        return self._eng.map_take_dirty()

    def take_sync(self) -> list[int]:
        return self._eng.map_take_sync()
