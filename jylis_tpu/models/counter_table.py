"""Host-state backends for the counter repos.

The counters' host bookkeeping — key interning, own contributions,
serving-value cache, dirty/pending-own/foreign flags — lives behind one
small table interface with two implementations:

* `PyTable` — pure-Python dicts, the semantic oracle and the fallback
  when no C++ toolchain is available.
* `NativeTable` — a view over one table of the native counter engine
  (native/counter_engine.cpp via native/engine.py). The same state the
  server's native batch applier mutates, so commands applied natively
  and repo calls from Python see one source of truth.

Values are stored as u64 bit patterns; PNCOUNT decodes them as the
wrapped two's-complement i64 the reference's (p-n).i64() defines.
Polarity 0 is GCOUNT's only / PNCOUNT's P plane; polarity 1 is N.

The foreign window lives here too, once: every column a peer converged
into a row, cumulative, by COLUMN (the repo owns replica id -> column).
`fold_foreign` joins a slice of deltas into it in one call, the sync
digest reads it (`sync_cols`), and `export_drain` hands the drain its
batch ready: rows and the u64 matrix `[P | N]`, pending own values
joined with the foreign rows' columns, a row of the batch a row of the
matrix (padded with zero rows to ``pad_to``), or with ``by_row`` each at
its own row number (a dense drain's whole plane). Nothing clears before
`finish_drain`, so a device failure mid-drain leaves every contribution
for the retry.
"""

from __future__ import annotations

import numpy as np

U64_MASK = (1 << 64) - 1


class PyTable:
    __slots__ = (
        "_keys", "_rkeys", "_value", "_own", "_ownset", "_pend", "_pendset",
        "_pend_rows", "_dirty", "_foreign", "_sync_dirty", "_fcols",
    )

    def __init__(self):
        self._keys: dict[bytes, int] = {}
        self._rkeys: list[bytes] = []
        self._value: list[int] = []  # u64 bits
        self._own = ([], [])  # per polarity, per row
        self._ownset = ([], [])
        self._pend = ([], [])
        self._pendset = ([], [])
        self._pend_rows: dict[int, None] = {}
        self._dirty: dict[int, None] = {}
        self._foreign: dict[int, None] = {}  # rows a drain owes a join
        self._sync_dirty: dict[int, None] = {}  # since last digest pass
        # per polarity: row -> {col: max value a peer converged}
        self._fcols: tuple[dict[int, dict[int, int]], ...] = ({}, {})

    def rows(self) -> int:
        return len(self._rkeys)

    def upsert(self, key: bytes) -> int:
        row = self._keys.get(key)
        if row is None:
            row = len(self._rkeys)
            self._keys[key] = row
            self._rkeys.append(key)
            self._value.append(0)
            for pol in (0, 1):
                self._own[pol].append(0)
                self._ownset[pol].append(False)
                self._pend[pol].append(0)
                self._pendset[pol].append(False)
        return row

    def find(self, key: bytes) -> int:
        return self._keys.get(key, -1)

    def key_of(self, row: int) -> bytes:
        return self._rkeys[row]

    def inc(self, row: int, polarity: int, amount: int) -> None:
        own = (self._own[polarity][row] + amount) & U64_MASK
        self._own[polarity][row] = own
        self._ownset[polarity][row] = True
        if own > self._pend[polarity][row]:
            self._pend[polarity][row] = own
        if not (self._pendset[0][row] or self._pendset[1][row]):
            self._pend_rows[row] = None
        self._pendset[polarity][row] = True
        self._dirty[row] = None
        self._sync_dirty[row] = None
        delta = amount if polarity == 0 else -amount
        self._value[row] = (self._value[row] + delta) & U64_MASK

    def is_foreign(self, row: int) -> bool:
        return row in self._foreign

    def value(self, row: int) -> int:
        return self._value[row]

    def own_set(self, row: int) -> int:
        return (1 if self._ownset[0][row] else 0) | (
            2 if self._ownset[1][row] else 0
        )

    def upsert_many(self, keys: list[bytes]) -> np.ndarray:
        return np.fromiter(map(self.upsert, keys), np.int64, len(keys))

    def fold_foreign(
        self, key_rows, npol: int, counts, cols, vals, adopt_col: int = -1
    ) -> int:
        """The oracle of `jy_eng_fold_foreign` (counter_engine.cpp)."""
        counts, cols, vals = counts.tolist(), cols.tolist(), vals.tolist()
        at = 0
        for k, row in enumerate(key_rows.tolist()):
            self._foreign[row] = None
            self._sync_dirty[row] = None
            for pol in range(npol):
                if not counts[k * npol + pol]:
                    continue
                cells = self._fcols[pol].setdefault(row, {})
                for _ in range(counts[k * npol + pol]):
                    col, v = cols[at], vals[at]
                    at += 1
                    if v > cells.get(col, 0):
                        cells[col] = v
                    if col == adopt_col:
                        if v > self._own[pol][row]:
                            self._own[pol][row] = v
                        self._ownset[pol][row] = True
        return at

    def pend_count(self) -> int:
        return len(self._pend_rows)

    def _drain_rows(self) -> list[int]:
        return list(dict.fromkeys([*self._pend_rows, *self._foreign]))

    def drain_count(self) -> int:
        return len(self._drain_rows())

    def export_drain(
        self, own_col: int, rep_cap: int, npol: int, pad_to: int, by_row: bool
    ):
        rows = self._drain_rows()
        mat = np.zeros((pad_to, npol * rep_cap), np.uint64)
        for i, row in enumerate(rows):
            if by_row:
                i = row
            for pol in range(npol):
                cells = {}
                if row in self._foreign:
                    cells.update(self._fcols[pol].get(row, ()))
                if self._pendset[pol][row]:
                    cells[own_col] = max(
                        self._pend[pol][row], cells.get(own_col, 0)
                    )
                for col, v in cells.items():
                    if col >= rep_cap:
                        raise RuntimeError("a column beyond the drain's width")
                    mat[i, pol * rep_cap + col] = v
        return np.asarray(rows, np.int64), mat

    def finish_drain(self, rows, values) -> None:
        for row, v in zip(rows, values):
            self._value[row] = int(v) & U64_MASK
        self._foreign.clear()
        for r in self._pend_rows:
            self._pend[0][r] = self._pend[1][r] = 0
            self._pendset[0][r] = self._pendset[1][r] = False
        self._pend_rows.clear()

    def sync_cols(self, row: int, own_col: int):
        """(cols, P values, N values): the row's foreign columns joined
        with its own contribution at ``own_col``."""
        per_pol = []
        for pol in (0, 1):
            d = dict(self._fcols[pol].get(row, ()))
            if self._ownset[pol][row] and self._own[pol][row] > d.get(own_col, 0):
                d[own_col] = self._own[pol][row]
            per_pol.append(d)
        cols = list(dict.fromkeys([*per_pol[0], *per_pol[1]]))
        return (
            cols,
            [per_pol[0].get(c, 0) for c in cols],
            [per_pol[1].get(c, 0) for c in cols],
        )

    def dirty_count(self) -> int:
        return len(self._dirty)

    def export_dirty(self):
        rows = list(self._dirty)
        op = [self._own[0][r] for r in rows]
        on = [self._own[1][r] for r in rows]
        sb = [self.own_set(r) for r in rows]
        self._dirty.clear()
        return rows, op, on, sb

    def export_sync_dirty(self) -> list[int]:
        rows = list(self._sync_dirty)
        self._sync_dirty.clear()
        return rows


def _sync_buffers(cap: int):
    """`NativeTable.sync_cols`' out-buffers and their addresses (taking
    an array's address costs more than the call that fills it): kept by
    the table, whose repo lock makes the digest pass their one user."""
    cols, vals = np.empty(cap, np.int32), np.empty((2, cap), np.uint64)
    return cols, vals, cols.ctypes.data, vals[0].ctypes.data, vals[1].ctypes.data, cap


class NativeTable:
    """One counter type's view over a shared native engine."""

    __slots__ = ("_eng", "_which", "_sync")

    def __init__(self, engine, which: int):
        self._eng = engine
        self._which = which
        self._sync = _sync_buffers(64)

    def rows(self) -> int:
        return self._eng.rows(self._which)

    def upsert(self, key: bytes) -> int:
        return self._eng.upsert(self._which, key)

    def find(self, key: bytes) -> int:
        return self._eng.find(self._which, key)

    def key_of(self, row: int) -> bytes:
        return self._eng.key_of(self._which, row)

    def inc(self, row: int, polarity: int, amount: int) -> None:
        self._eng.inc(self._which, row, polarity, amount)

    def is_foreign(self, row: int) -> bool:
        return self._eng.is_foreign(self._which, row)

    def value(self, row: int) -> int:
        return self._eng.value(self._which, row)

    def upsert_many(self, keys: list[bytes]) -> np.ndarray:
        return self._eng.upsert_many(self._which, keys)

    def fold_foreign(
        self, key_rows, npol: int, counts, cols, vals, adopt_col: int = -1
    ) -> int:
        return self._eng.fold_foreign(
            self._which, key_rows, npol, counts, cols, vals, adopt_col
        )

    def pend_count(self) -> int:
        return self._eng.pend_count(self._which)

    def drain_count(self) -> int:
        return self._eng.drain_count(self._which)

    def export_drain(
        self, own_col: int, rep_cap: int, npol: int, pad_to: int, by_row: bool
    ):
        return self._eng.export_drain(
            self._which, own_col, rep_cap, npol, pad_to, by_row
        )

    def finish_drain(self, rows, values) -> None:
        self._eng.finish_drain(self._which, rows, values)

    def sync_cols(self, row: int, own_col: int):
        while True:
            cols, vals, *where = self._sync
            n = self._eng.sync_cols(self._which, row, own_col, *where)
            if n >= 0:
                vp, vn = vals[:, :n].tolist()
                return cols[:n].tolist(), vp, vn
            self._sync = _sync_buffers(-n)

    def dirty_count(self) -> int:
        return self._eng.dirty_count(self._which)

    def export_dirty(self):
        rows, op, on, sb = self._eng.export_dirty(self._which)
        return rows.tolist(), op.tolist(), on.tolist(), sb.tolist()

    def export_sync_dirty(self) -> list[int]:
        return self._eng.export_sync_dirty(self._which)
