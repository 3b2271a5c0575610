"""ctypes wrapper for the native serving engine (native/engine.h,
counter_engine.cpp + serve_engine.cpp).

`ServeEngine` owns the host state every command touches — the
GCOUNT/PNCOUNT counter tables, the TREG winner/pending/delta registers,
the TLOG pending/merged-view/delta logs, the validated UJSON write
queue and the UJSON per-(key, path) render memo — and applies whole
pipelined command bursts per FFI call. The Python dict
backends (models/counter_table.py, models/treg_table.py,
models/tlog_table.py) remain the semantic oracles and the fallback when
no toolchain is available; differential tests pin the equivalence.
"""

from __future__ import annotations

import ctypes
import struct
import weakref
from itertools import chain

import numpy as np

from ..utils.metrics import resolve_registry
from . import lib

G = 0
PN = 1

# the reply buffer a burst's replies are written into: it starts at
# _OUT_CAP, is replaced by a larger one when ONE reply outgrows it, and
# never passes _OUT_CEIL (a whole thread of 16,000 x 1 KB posts); a reply
# beyond that is the Python path's, which renders it in bounded flushes
_OUT_CAP = 1 << 16
_OUT_CEIL = 1 << 24
_MAX_ARGS = 1024
# `scan_apply`'s ``held`` with every engine type in it
ALL_TYPES = 0b111111

# jy_tlog_export_merged's "view unavailable" sentinel (serve_engine.cpp)
_TLOG_UNAVAILABLE = -1 - (1 << 40)


def _declare(c: ctypes.CDLL) -> None:
    ct = ctypes
    vp, i32, i64, u64, u8p = (
        ct.c_void_p, ct.c_int32, ct.c_int64, ct.c_uint64, ct.c_char_p,
    )
    pi64 = ct.POINTER(ct.c_int64)
    pi32 = ct.POINTER(ct.c_int32)
    pvp = ct.POINTER(ct.c_void_p)
    pu64 = ct.POINTER(ct.c_uint64)
    sigs = {
        "jy_eng_new": (vp, []),
        "jy_eng_free": (None, [vp]),
        "jy_eng_rows": (i64, [vp, i32]),
        "jy_eng_upsert": (i64, [vp, i32, u8p, i64]),
        "jy_eng_find": (i64, [vp, i32, u8p, i64]),
        "jy_eng_key": (None, [vp, i32, i64, pvp, pi64]),
        "jy_eng_inc": (None, [vp, i32, i64, i32, u64]),
        "jy_eng_is_foreign": (i32, [vp, i32, i64]),
        "jy_eng_value": (u64, [vp, i32, i64]),
        "jy_eng_own": (u64, [vp, i32, i64, i32]),
        "jy_eng_upsert_many": (None, [vp, i32, u8p, vp, i64, vp]),
        "jy_eng_fold_foreign": (i64, [vp, i32, vp, i64, i32, vp, vp, vp, i32]),
        "jy_eng_drain_count": (i64, [vp, i32]),
        "jy_eng_export_drain": (i64, [vp, i32, i32, i32, i32, i32, vp, vp, i64]),
        "jy_eng_finish_drain": (None, [vp, i32, vp, vp, i64]),
        "jy_eng_sync_cols": (i64, [vp, i32, i64, i32, vp, vp, vp, i64]),
        "jy_eng_dirty_count": (i64, [vp, i32]),
        "jy_eng_pend_count": (i64, [vp, i32]),
        "jy_eng_export_dirty": (i64, [vp, i32, vp, vp, vp, vp, i64]),
        "jy_eng_export_sync_dirty": (i64, [vp, i32, vp, i64]),
        "jy_treg_export_sync_dirty": (i64, [vp, vp, i64]),
        "jy_tlog_export_sync_dirty": (i64, [vp, vp, i64]),
        "jy_treg_deltas_info": (None, [vp, pi64, pi64, pi64]),
        "jy_treg_export_deltas_bulk": (
            None, [vp, vp, vp, vp, vp, vp, vp, vp],
        ),
        "jy_tlog_deltas_info": (None, [vp, pi64, pi64, pi64]),
        "jy_tlog_export_deltas_bulk": (
            None, [vp, vp, vp, vp, vp, vp, vp, vp],
        ),
        "jy_tlog_export_pend_bulk": (i64, [vp, vp, i64, vp, vp, vp, i64]),
        "jy_tlog_vals_info": (None, [vp, i32, pi64, pi64]),
        "jy_tlog_export_vals": (None, [vp, i32, vp, vp, vp]),
        # TREG
        "jy_treg_rows": (i64, [vp]),
        "jy_treg_upsert": (i64, [vp, u8p, i64]),
        "jy_treg_find": (i64, [vp, u8p, i64]),
        "jy_treg_key": (None, [vp, i64, pvp, pi64]),
        "jy_treg_write": (None, [vp, i64, u64, u8p, i64]),
        "jy_treg_note_delta": (None, [vp, i64, u64, u8p, i64]),
        "jy_treg_winner": (i32, [vp, i64, pu64, pvp, pi64]),
        "jy_treg_pend_count": (i64, [vp]),
        "jy_treg_export_planes": (
            i64, [vp, vp, vp, vp, vp, vp, vp, i64, i32],
        ),
        "jy_treg_settle_ties": (i64, [vp, vp, i64, vp]),
        "jy_treg_fold_pend": (None, [vp]),
        "jy_treg_delta_count": (i64, [vp]),
        "jy_treg_export_deltas": (i64, [vp, vp, vp, i64]),
        "jy_treg_delta_val": (None, [vp, i64, pvp, pi64]),
        "jy_treg_clear_deltas": (None, [vp]),
        # TLOG
        "jy_tlog_rows": (i64, [vp]),
        "jy_tlog_upsert": (i64, [vp, u8p, i64]),
        "jy_tlog_find": (i64, [vp, u8p, i64]),
        "jy_tlog_key": (None, [vp, i64, pvp, pi64]),
        "jy_tlog_ins": (None, [vp, i64, u64, u8p, i64]),
        "jy_tlog_conv_entry": (None, [vp, i64, u64, u8p, i64]),
        "jy_tlog_conv_cutoff": (None, [vp, i64, u64]),
        "jy_tlog_size": (i64, [vp, i64]),
        "jy_tlog_len_cache": (i64, [vp, i64]),
        "jy_tlog_cut_cache": (u64, [vp, i64]),
        "jy_tlog_cutoff_view": (u64, [vp, i64]),
        "jy_tlog_pend_cutoff": (u64, [vp, i64]),
        "jy_tlog_quiescent": (i32, [vp, i64]),
        "jy_tlog_gen": (u64, [vp, i64]),
        "jy_tlog_pend_len": (i64, [vp, i64]),
        "jy_tlog_overdue": (i32, [vp]),
        "jy_tlog_ins_tips": (i32, [vp, i64]),
        "jy_tlog_pend_total": (i64, [vp]),
        "jy_tlog_set_entries_bound": (None, [vp, i64]),
        "jy_tlog_touched_rows": (i64, [vp, vp, i64]),
        "jy_tlog_touched_count": (i64, [vp]),
        "jy_tlog_export_base": (i64, [vp, i64, vp, vp, i64]),
        "jy_tlog_compact": (i32, [vp]),
        "jy_tlog_base_valid": (i32, [vp, i64]),
        "jy_tlog_live_total": (i64, [vp]),
        "jy_tlog_export_pend": (i64, [vp, i64, vp, vp, i64]),
        "jy_tlog_val": (None, [vp, i32, pvp, pi64]),
        "jy_tlog_intern": (i32, [vp, u8p, i64]),
        "jy_tlog_finish_row": (i32, [vp, i64, i64, u64]),
        "jy_tlog_finish_end": (None, [vp]),
        "jy_tlog_set_base": (None, [vp, i64, i64, vp, vp]),
        "jy_tlog_export_merged": (i64, [vp, i64, vp, vp, i64]),
        "jy_tlog_delta_rows_count": (i64, [vp]),
        "jy_tlog_export_delta_rows": (i64, [vp, vp, i64]),
        "jy_tlog_export_delta": (i64, [vp, i64, vp, vp, i64]),
        "jy_tlog_delta_cutoff": (u64, [vp, i64]),
        "jy_tlog_delta_raise_cutoff": (None, [vp, i64, u64]),
        "jy_tlog_clear_deltas": (None, [vp]),
        "jy_eng_served": (None, [vp, vp]),
        # MAP field table
        "jy_map_rows": (i64, [vp]),
        "jy_map_rid_count": (i64, [vp]),
        "jy_map_rids": (None, [vp, vp]),
        "jy_map_reserve": (None, [vp, i64, i64]),
        "jy_map_set_rid": (None, [vp, u64]),
        "jy_map_find": (i64, [vp, u8p, i64, u8p, i64]),
        "jy_map_set": (i64, [vp, u8p, i64, u8p, i64, u64, u64, u8p, i64]),
        "jy_map_del": (i32, [vp, i64]),
        "jy_map_note_edit": (None, [vp, i64]),
        "jy_map_get": (i32, [vp, i64, pu64, pvp, pi64]),
        "jy_map_field_name": (None, [vp, i64, pvp, pi64]),
        "jy_map_mark_mixed": (None, [vp, u8p, i64]),
        "jy_map_is_mixed": (i32, [vp, u8p, i64]),
        "jy_map_record": (i64, [vp, u8p, i64, vp, i64, i32]),
        "jy_map_join_unit": (
            i64, [vp, u8p, i64, vp, i64, vp, i64, u64, u8p, i64],
        ),
        "jy_map_check_wire": (i32, [vp, i64, i64]),
        "jy_map_load_wire": (None, [vp, vp, i64, i64]),
        "jy_map_wire_build": (i64, [vp, vp, i64]),
        "jy_map_wire_take": (None, [vp, vp, vp]),
        "jy_map_pend_count": (i64, [vp]),
        "jy_map_dirty_count": (i64, [vp]),
        "jy_map_export_planes": (
            i64, [vp, vp, vp, i64, vp, vp, vp, vp, vp, i64, i32],
        ),
        "jy_map_settle_ties": (i64, [vp, vp, i64, vp]),
        "jy_map_clear_pend": (None, [vp]),
        "jy_map_take": (i64, [vp, i32, vp, i64]),
        "jy_map_tallies": (None, [vp, vp]),
        # UJSON queue + render memo
        "jy_uq_count": (i64, [vp]),
        "jy_uq_bytes": (i64, [vp]),
        "jy_uq_data": (i64, [vp, vp, i64]),
        "jy_uq_clear": (None, [vp]),
        "jy_uj_upsert": (i64, [vp, u8p, i64]),
        "jy_uj_memo_put": (None, [vp, i64, u8p, i64, u8p, i64]),
        "jy_uj_invalidate": (None, [vp, u8p, i64, u8p, i64, i32]),
        "jy_uj_memo_len": (i64, [vp, u8p, i64]),
        # batch applier
        "jy_eng_scan_apply2": (
            i32,
            [vp, vp, i64, i32, vp, i64, i64, pi64, pi64, vp, vp, i32, pi32, vp],
        ),
        "jy_eng_types_ahead": (i32, [vp, i64, vp, vp, i32]),
        **_sender_sigs(),
    }
    _apply_sigs(c, sigs)


def _apply_sigs(c, sigs) -> None:
    for fn_name, (restype, argtypes) in sigs.items():
        fn = getattr(c, fn_name)
        fn.restype = restype
        fn.argtypes = argtypes


def _sender_sigs() -> dict:
    """The reply sender's entry points (reply_sender.cpp): declared on
    the plain handle and on the one that keeps the GIL."""
    vp, i32, i64 = ctypes.c_void_p, ctypes.c_int32, ctypes.c_int64
    return {
        "jy_snd_open": (i64, [vp, i32, i64, i64]),
        "jy_snd_send": (i64, [vp, i64, vp, i64]),
        "jy_snd_behind": (i64, [vp, i64]),
        "jy_snd_wait": (i32, [vp, i64]),
        "jy_snd_notify_fd": (i32, [vp]),
        "jy_snd_close": (i64, [vp, i64]),
        "jy_snd_pending": (i64, [vp]),
        "jy_snd_stats": (None, [vp, vp]),
        "jy_snd_stop": (None, [vp]),
    }


_declared = False
# the same library through a handle that KEEPS the GIL: a hand-off to the
# sender is microseconds long and blocks on nothing, and releasing the
# interpreter around it would let a drain thread's Python phase take it
# between a burst and its hand-off
_gil_lib: ctypes.PyDLL | None = None


def _ensure_declared(cdll) -> None:
    global _declared, _gil_lib
    if not _declared:
        _declare(cdll)
        _gil_lib = ctypes.PyDLL(cdll._name)
        _apply_sigs(_gil_lib, _sender_sigs())
        _declared = True


def map_wire_ok(payload, count: int) -> bool:
    """Can the native MAP field table read these ``count`` units (a MAP
    batch's wire bytes, any buffer) in place: well-formed, every inner
    type TREG. False without the library."""
    cdll = lib()
    if cdll is None:
        return False
    _ensure_declared(cdll)
    buf = np.frombuffer(payload, np.uint8)
    return bool(cdll.jy_map_check_wire(buf.ctypes.data, len(buf), count))


class ServeEngine:
    """One native engine instance = all six data-type tables of one node."""

    def __init__(self, cdll):
        _ensure_declared(cdll)
        self._lib = cdll
        self._gil = _gil_lib
        self._h = cdll.jy_eng_new()
        self._out = (ctypes.c_uint8 * _OUT_CAP)()
        self._offs = (ctypes.c_int64 * _MAX_ARGS)()
        self._lens = (ctypes.c_int64 * _MAX_ARGS)()
        self._changed = (ctypes.c_int32 * len(self.TYPE_ORDER))()
        self._tlog_vals: list[bytes] = []  # native vid -> bytes mirror
        self._sender_seen = [0] * 8  # what sender_tally last counted
        self._map_seen = [0] * 3  # what map_tally last counted

    def __del__(self):
        if getattr(self, "_h", None):
            self._lib.jy_eng_free(self._h)
            self._h = None

    # ---- counter table ops -------------------------------------------------

    def rows(self, which: int) -> int:
        return self._lib.jy_eng_rows(self._h, which)

    def upsert(self, which: int, key: bytes) -> int:
        return self._lib.jy_eng_upsert(self._h, which, key, len(key))

    def find(self, which: int, key: bytes) -> int:
        return self._lib.jy_eng_find(self._h, which, key, len(key))

    def key_of(self, which: int, row: int) -> bytes:
        ptr = ctypes.c_void_p()
        n = ctypes.c_int64()
        self._lib.jy_eng_key(self._h, which, row, ctypes.byref(ptr), ctypes.byref(n))
        return ctypes.string_at(ptr, n.value)

    def inc(self, which: int, row: int, polarity: int, amount: int) -> None:
        self._lib.jy_eng_inc(self._h, which, row, polarity, amount)

    def is_foreign(self, which: int, row: int) -> bool:
        return bool(self._lib.jy_eng_is_foreign(self._h, which, row))

    def value(self, which: int, row: int) -> int:
        return self._lib.jy_eng_value(self._h, which, row)

    def own(self, which: int, row: int, polarity: int) -> int:
        return self._lib.jy_eng_own(self._h, which, row, polarity)

    def upsert_many(self, which: int, keys: list[bytes]) -> np.ndarray:
        """Rows of ``keys`` (made where new), one call for all of them."""
        lens = np.fromiter(map(len, keys), np.int64, len(keys))
        rows = np.empty(len(keys), np.int64)
        self._lib.jy_eng_upsert_many(
            self._h, which, b"".join(keys), lens.ctypes.data, len(keys),
            rows.ctypes.data,
        )
        return rows

    def fold_foreign(
        self, which: int, key_rows, npol: int, counts, cols, vals,
        adopt_col: int = -1,
    ) -> int:
        """A slice of foreign deltas joins the table's foreign window
        (`jy_eng_fold_foreign`): int64 ``key_rows``, int32 ``counts`` per
        (key, polarity), and the cells' int32 ``cols`` and u64 ``vals``
        in that order. Returns the cells folded."""
        return self._lib.jy_eng_fold_foreign(
            self._h, which, key_rows.ctypes.data, len(key_rows), npol,
            counts.ctypes.data, cols.ctypes.data, vals.ctypes.data, adopt_col,
        )

    def drain_count(self, which: int) -> int:
        return self._lib.jy_eng_drain_count(self._h, which)

    def export_drain(
        self, which: int, own_col: int, rep_cap: int, npol: int, pad_to: int,
        by_row: bool,
    ):
        """The drain batch: (n,) int64 rows and the (pad_to, npol *
        rep_cap) u64 matrix whose first n rows are theirs, or with
        ``by_row`` those at the rows' own numbers; the rest 0."""
        rows = np.empty(pad_to, np.int64)
        mat = np.zeros((pad_to, npol * rep_cap), np.uint64)
        n = self._lib.jy_eng_export_drain(
            self._h, which, own_col, rep_cap, npol, by_row,
            rows.ctypes.data, mat.ctypes.data, pad_to,
        )
        if n < 0:
            raise RuntimeError(
                f"counter drain batch does not fit ({pad_to} rows x "
                f"{rep_cap} columns)"
            )
        return rows[:n], mat

    def finish_drain(self, which: int, rows, values) -> None:
        rows = np.ascontiguousarray(rows, np.int64)
        values = np.ascontiguousarray(values, np.uint64)
        self._lib.jy_eng_finish_drain(
            self._h, which,
            rows.ctypes.data, values.ctypes.data, len(rows),
        )

    def sync_cols(self, which: int, row: int, own_col: int, cols, vp, vn, cap):
        """One row's canonical columns (`jy_eng_sync_cols`) into the
        caller's buffers, given by address: the cells written, or -n
        when the row's n do not fit ``cap``."""
        return self._lib.jy_eng_sync_cols(
            self._h, which, row, own_col, cols, vp, vn, cap
        )

    def dirty_count(self, which: int) -> int:
        return self._lib.jy_eng_dirty_count(self._h, which)

    def pend_count(self, which: int) -> int:
        return self._lib.jy_eng_pend_count(self._h, which)

    def export_dirty(self, which: int):
        cap = 256
        while True:
            rows = np.empty(cap, np.int64)
            op = np.empty(cap, np.uint64)
            on = np.empty(cap, np.uint64)
            sb = np.empty(cap, np.uint8)
            n = self._lib.jy_eng_export_dirty(
                self._h, which,
                rows.ctypes.data, op.ctypes.data, on.ctypes.data,
                sb.ctypes.data, cap,
            )
            if n >= 0:
                return rows[:n], op[:n], on[:n], sb[:n]
            cap = -n

    def _export_sync_dirty(self, fn, *head) -> list[int]:
        cap = 256
        while True:
            rows = np.empty(cap, np.int64)
            n = fn(self._h, *head, rows.ctypes.data, cap)
            if n >= 0:
                return rows[:n].tolist()
            cap = -n

    def export_sync_dirty(self, which: int) -> list[int]:
        """Counter rows changed since the last digest pass; clears."""
        return self._export_sync_dirty(
            self._lib.jy_eng_export_sync_dirty, which
        )

    def treg_export_sync_dirty(self) -> list[int]:
        return self._export_sync_dirty(self._lib.jy_treg_export_sync_dirty)

    def tlog_export_sync_dirty(self) -> list[int]:
        return self._export_sync_dirty(self._lib.jy_tlog_export_sync_dirty)

    # ---- TREG table ops ----------------------------------------------------

    def treg_rows(self) -> int:
        return self._lib.jy_treg_rows(self._h)

    def treg_upsert(self, key: bytes) -> int:
        return self._lib.jy_treg_upsert(self._h, key, len(key))

    def treg_find(self, key: bytes) -> int:
        return self._lib.jy_treg_find(self._h, key, len(key))

    def treg_key_of(self, row: int) -> bytes:
        ptr = ctypes.c_void_p()
        n = ctypes.c_int64()
        self._lib.jy_treg_key(self._h, row, ctypes.byref(ptr), ctypes.byref(n))
        return ctypes.string_at(ptr, n.value)

    def treg_write(self, row: int, ts: int, value: bytes) -> None:
        self._lib.jy_treg_write(self._h, row, ts, value, len(value))

    def treg_note_delta(self, row: int, ts: int, value: bytes) -> None:
        self._lib.jy_treg_note_delta(self._h, row, ts, value, len(value))

    def treg_winner(self, row: int):
        ts = ctypes.c_uint64()
        ptr = ctypes.c_void_p()
        n = ctypes.c_int64()
        if not self._lib.jy_treg_winner(
            self._h, row, ctypes.byref(ts), ctypes.byref(ptr), ctypes.byref(n)
        ):
            return None
        return ts.value, ctypes.string_at(ptr, n.value)

    def treg_pend_count(self) -> int:
        return self._lib.jy_treg_pend_count(self._h)

    def treg_export_planes(
        self, ki, ts_hi, ts_lo, rank_hi, rank_lo, vid, dense: bool
    ) -> int:
        """The pending window as the drain kernel's batch planes, written
        into the caller's arrays in ONE pass (not cleared: clear =
        treg_fold_pend). ``ki`` (int32, at least the window long) takes
        the rows in window order; the u32 planes and ``vid`` (int32) are
        the padded batch arrays the jitted call takes: sparse fills slot
        i, dense fills slot row. Returns the rows written."""
        cap = len(vid)
        n = self._lib.jy_treg_pend_count(self._h)
        planes = (ts_hi, ts_lo, rank_hi, rank_lo)
        if (
            len(ki) < n
            or ki.dtype != np.int32
            or vid.dtype != np.int32
            or any(len(p) != cap or p.dtype != np.uint32 for p in planes)
            or not all(a.flags.c_contiguous for a in (ki, vid, *planes))
        ):
            raise ValueError("treg_export_planes: batch arrays do not fit")
        n = self._lib.jy_treg_export_planes(
            self._h, ki.ctypes.data, ts_hi.ctypes.data, ts_lo.ctypes.data,
            rank_hi.ctypes.data, rank_lo.ctypes.data, vid.ctypes.data, cap,
            int(dense),
        )
        if n < 0:
            raise ValueError("treg_export_planes: a slot outside the batch")
        return n

    def treg_settle_ties(self, rows):
        """Rows the device flagged as prefix ties -> (rows whose pending
        write wins by the full (ts, value) rule, the ids the mirror must
        be patched to): one call, int32 arrays."""
        rows = np.array(rows, np.int32)  # a copy: compacted in place
        vids = np.empty(len(rows), np.int32)
        m = self._lib.jy_treg_settle_ties(
            self._h, rows.ctypes.data, len(rows), vids.ctypes.data
        )
        return rows[:m], vids[:m]

    def treg_fold_pend(self) -> None:
        self._lib.jy_treg_fold_pend(self._h)

    def treg_delta_count(self) -> int:
        return self._lib.jy_treg_delta_count(self._h)

    def treg_flush_deltas(self):
        """Sorted [(key, (value, ts))]; clears the delta window. ONE bulk
        FFI pass — per-row round-trips made a 20k-key flush ~12x slower
        than the dict oracle."""
        n = ctypes.c_int64()
        vb = ctypes.c_int64()
        kb = ctypes.c_int64()
        self._lib.jy_treg_deltas_info(
            self._h, ctypes.byref(n), ctypes.byref(vb), ctypes.byref(kb)
        )
        n = n.value
        if n == 0:
            return []
        ts = np.empty(n, np.uint64)
        vo = np.empty(n, np.int64)
        vl = np.empty(n, np.int64)
        ko = np.empty(n, np.int64)
        kl = np.empty(n, np.int64)
        vblob = np.empty(max(vb.value, 1), np.uint8)
        kblob = np.empty(max(kb.value, 1), np.uint8)
        self._lib.jy_treg_export_deltas_bulk(
            self._h, ts.ctypes.data, vo.ctypes.data, vl.ctypes.data,
            vblob.ctypes.data, ko.ctypes.data, kl.ctypes.data,
            kblob.ctypes.data,
        )
        self._lib.jy_treg_clear_deltas(self._h)
        vbytes = vblob.tobytes()
        kbytes = kblob.tobytes()
        out = [
            (kbytes[o : o + ln], (vbytes[vo_ : vo_ + vl_], t))
            for o, ln, vo_, vl_, t in zip(
                ko.tolist(), kl.tolist(), vo.tolist(), vl.tolist(),
                ts.tolist(),
            )
        ]
        out.sort()
        return out

    # ---- TLOG table ops ----------------------------------------------------

    def _tlog_val(self, vid: int) -> bytes:
        vals = self._tlog_vals
        if vid >= len(vals):
            self._tlog_refill_vals()
        return vals[vid]

    def _tlog_refill_vals(self) -> None:
        """Mirror every native-interned value from the current mirror
        length up, in ONE bulk export."""
        lo = len(self._tlog_vals)
        n = ctypes.c_int64()
        nb = ctypes.c_int64()
        self._lib.jy_tlog_vals_info(
            self._h, lo, ctypes.byref(n), ctypes.byref(nb)
        )
        if n.value <= 0:
            return
        off = np.empty(n.value, np.int64)
        ln = np.empty(n.value, np.int64)
        blob = np.empty(max(nb.value, 1), np.uint8)
        self._lib.jy_tlog_export_vals(
            self._h, lo, off.ctypes.data, ln.ctypes.data, blob.ctypes.data
        )
        data = blob.tobytes()
        self._tlog_vals.extend(
            data[o : o + l] for o, l in zip(off.tolist(), ln.tolist())
        )

    def tlog_rows(self) -> int:
        return self._lib.jy_tlog_rows(self._h)

    def tlog_upsert(self, key: bytes) -> int:
        return self._lib.jy_tlog_upsert(self._h, key, len(key))

    def tlog_find(self, key: bytes) -> int:
        return self._lib.jy_tlog_find(self._h, key, len(key))

    def tlog_key_of(self, row: int) -> bytes:
        ptr = ctypes.c_void_p()
        n = ctypes.c_int64()
        self._lib.jy_tlog_key(self._h, row, ctypes.byref(ptr), ctypes.byref(n))
        return ctypes.string_at(ptr, n.value)

    def tlog_ins(self, row: int, ts: int, value: bytes) -> None:
        self._lib.jy_tlog_ins(self._h, row, ts, value, len(value))

    def tlog_conv_entry(self, row: int, ts: int, value: bytes) -> None:
        self._lib.jy_tlog_conv_entry(self._h, row, ts, value, len(value))

    def tlog_conv_cutoff(self, row: int, c: int) -> None:
        self._lib.jy_tlog_conv_cutoff(self._h, row, c)

    def tlog_size(self, row: int) -> int:
        return self._lib.jy_tlog_size(self._h, row)

    def tlog_len_cache(self, row: int) -> int:
        return self._lib.jy_tlog_len_cache(self._h, row)

    def tlog_cut_cache(self, row: int) -> int:
        return self._lib.jy_tlog_cut_cache(self._h, row)

    def tlog_cutoff_view(self, row: int) -> int:
        return self._lib.jy_tlog_cutoff_view(self._h, row)

    def tlog_pend_cutoff(self, row: int) -> int:
        return self._lib.jy_tlog_pend_cutoff(self._h, row)

    def tlog_quiescent(self, row: int) -> bool:
        return bool(self._lib.jy_tlog_quiescent(self._h, row))

    def tlog_gen(self, row: int) -> int:
        return self._lib.jy_tlog_gen(self._h, row)

    def tlog_pend_len(self, row: int) -> int:
        return self._lib.jy_tlog_pend_len(self._h, row)

    def tlog_overdue(self) -> bool:
        return bool(self._lib.jy_tlog_overdue(self._h))

    def tlog_ins_tips(self, in_row: int) -> bool:
        return bool(self._lib.jy_tlog_ins_tips(self._h, in_row))

    def tlog_pend_total(self) -> int:
        return self._lib.jy_tlog_pend_total(self._h)

    def tlog_set_entries_bound(self, n: int) -> None:
        self._lib.jy_tlog_set_entries_bound(self._h, n)

    def tlog_touched_rows(self) -> list[int]:
        cap = 256
        while True:
            rows = np.empty(cap, np.int64)
            n = self._lib.jy_tlog_touched_rows(self._h, rows.ctypes.data, cap)
            if n >= 0:
                return rows[:n].tolist()
            cap = -n

    def tlog_touched_count(self) -> int:
        return self._lib.jy_tlog_touched_count(self._h)

    def tlog_base_entries(self, row: int):
        """[(ts, value)] of the drained row content when the carried base
        is valid; None when the repo must gather it from the device."""
        cap = 64
        while True:
            ts = np.empty(cap, np.uint64)
            vid = np.empty(cap, np.int32)
            n = self._lib.jy_tlog_export_base(
                self._h, row, ts.ctypes.data, vid.ctypes.data, cap
            )
            if n == _TLOG_UNAVAILABLE:
                return None
            if n >= 0:
                return [
                    (int(ts[i]), self._tlog_val(int(vid[i]))) for i in range(n)
                ]
            cap = -n

    def tlog_compact(self) -> bool:
        """Native value-interner compaction; resets the vid mirror when a
        remap happened."""
        if self._lib.jy_tlog_compact(self._h):
            self._tlog_vals.clear()
            return True
        return False

    def tlog_base_valid(self, row: int) -> bool:
        return bool(self._lib.jy_tlog_base_valid(self._h, row))

    def tlog_live_total(self) -> int:
        return self._lib.jy_tlog_live_total(self._h)

    def tlog_export_pend(self, row: int) -> list[tuple[int, bytes]]:
        cap = max(self.tlog_pend_len(row), 1)
        ts = np.empty(cap, np.uint64)
        vid = np.empty(cap, np.int32)
        n = self._lib.jy_tlog_export_pend(
            self._h, row, ts.ctypes.data, vid.ctypes.data, cap
        )
        assert n >= 0
        return [(int(ts[i]), self._tlog_val(int(vid[i]))) for i in range(n)]

    def tlog_export_pend_bulk(self, rows: list[int]):
        """{row: [(ts, value)]} for the drain's row set in one call."""
        nrows = len(rows)
        if nrows == 0:
            return {}
        rows_a = np.asarray(rows, np.int64)
        counts = np.empty(nrows, np.int64)
        cap = 256
        while True:
            ts = np.empty(cap, np.uint64)
            vid = np.empty(cap, np.int32)
            total = self._lib.jy_tlog_export_pend_bulk(
                self._h, rows_a.ctypes.data, nrows, counts.ctypes.data,
                ts.ctypes.data, vid.ctypes.data, cap,
            )
            if total >= 0:
                break
            cap = -total
        if int(vid[:total].max(initial=-1)) >= len(self._tlog_vals):
            self._tlog_refill_vals()
        vals = self._tlog_vals
        ts_l = ts[:total].tolist()
        vid_l = vid[:total].tolist()
        out = {}
        e = 0
        for row, c in zip(rows, counts.tolist()):
            out[row] = [(ts_l[j], vals[vid_l[j]]) for j in range(e, e + c)]
            e += c
        return out

    def tlog_intern(self, value: bytes) -> int:
        return self._lib.jy_tlog_intern(self._h, value, len(value))

    def tlog_finish_row(self, row: int, length: int, cut: int) -> bool:
        """True when the host still holds the row's drained base."""
        return bool(self._lib.jy_tlog_finish_row(self._h, row, length, cut))

    def tlog_finish_end(self) -> None:
        self._lib.jy_tlog_finish_end(self._h)

    def tlog_set_base(self, row: int, entries) -> None:
        """entries: [(ts, value bytes)] — the drained row content."""
        n = len(entries)
        ts = np.empty(max(n, 1), np.uint64)
        vid = np.empty(max(n, 1), np.int32)
        for i, (t, v) in enumerate(entries):
            ts[i] = t
            vid[i] = self.tlog_intern(v)
        self._lib.jy_tlog_set_base(
            self._h, row, n, ts.ctypes.data, vid.ctypes.data
        )

    def tlog_merged_entries(self, row: int):
        """[(ts, value)] of the merged view, unsorted; None when the
        drained base is unknown (call tlog_size / tlog_set_base first)."""
        cap = 64
        while True:
            ts = np.empty(cap, np.uint64)
            vid = np.empty(cap, np.int32)
            n = self._lib.jy_tlog_export_merged(
                self._h, row, ts.ctypes.data, vid.ctypes.data, cap
            )
            if n == _TLOG_UNAVAILABLE:
                return None
            if n >= 0:
                return [
                    (int(ts[i]), self._tlog_val(int(vid[i]))) for i in range(n)
                ]
            cap = -n

    def tlog_deltas_size(self) -> int:
        return self._lib.jy_tlog_delta_rows_count(self._h)

    def tlog_delta_raise_cutoff(self, row: int, c: int) -> None:
        self._lib.jy_tlog_delta_raise_cutoff(self._h, row, c)

    def tlog_flush_deltas(self):
        """Sorted [(key, (entries latest-first, cutoff))]; clears. ONE
        bulk FFI pass (see treg_flush_deltas)."""
        n = ctypes.c_int64()
        te = ctypes.c_int64()
        kb = ctypes.c_int64()
        self._lib.jy_tlog_deltas_info(
            self._h, ctypes.byref(n), ctypes.byref(te), ctypes.byref(kb)
        )
        n = n.value
        if n == 0:
            return []
        counts = np.empty(n, np.int64)
        cutoffs = np.empty(n, np.uint64)
        ts_flat = np.empty(max(te.value, 1), np.uint64)
        vid_flat = np.empty(max(te.value, 1), np.int32)
        ko = np.empty(n, np.int64)
        kl = np.empty(n, np.int64)
        kblob = np.empty(max(kb.value, 1), np.uint8)
        self._lib.jy_tlog_export_deltas_bulk(
            self._h, counts.ctypes.data, cutoffs.ctypes.data,
            ts_flat.ctypes.data, vid_flat.ctypes.data,
            ko.ctypes.data, kl.ctypes.data, kblob.ctypes.data,
        )
        self._lib.jy_tlog_clear_deltas(self._h)
        if int(vid_flat[: te.value].max(initial=-1)) >= len(self._tlog_vals):
            self._tlog_refill_vals()
        vals = self._tlog_vals
        kbytes = kblob.tobytes()
        ts_l = ts_flat.tolist()
        vid_l = vid_flat.tolist()
        out = []
        e = 0
        for i, (c, cut, o, ln) in enumerate(
            zip(counts.tolist(), cutoffs.tolist(), ko.tolist(), kl.tolist())
        ):
            ents = sorted(
                ((ts_l[j], vals[vid_l[j]]) for j in range(e, e + c)),
                reverse=True,
            )
            e += c
            out.append((kbytes[o : o + ln], ([(v, t) for t, v in ents], cut)))
        out.sort()
        return out


    # ---- MAP field table ops (native/engine.h MapTable) --------------------

    def map_rows(self) -> int:
        return self._lib.jy_map_rows(self._h)

    def map_rid_count(self) -> int:
        return self._lib.jy_map_rid_count(self._h)

    def map_rids(self) -> list[int]:
        """The replica ids the table knows: a device row's columns."""
        out = np.zeros(self.map_rid_count(), np.uint64)
        self._lib.jy_map_rids(self._h, out.ctypes.data)
        return out.tolist()

    def map_reserve(self, keys: int, fields: int) -> None:
        self._lib.jy_map_reserve(self._h, keys, fields)

    def map_set_rid(self, rid: int) -> None:
        """The replica id a natively settled ``MAP TREG SET`` edits as."""
        self._lib.jy_map_set_rid(self._h, rid)

    def map_find(self, key: bytes, field: bytes) -> int:
        return self._lib.jy_map_find(self._h, key, len(key), field, len(field))

    def map_set(self, key: bytes, field: bytes, rid: int, ts: int,
                value: bytes) -> int:
        return self._lib.jy_map_set(
            self._h, key, len(key), field, len(field), rid, ts, value,
            len(value),
        )

    def map_del(self, row: int) -> bool:
        return bool(self._lib.jy_map_del(self._h, row))

    def map_note_edit(self, row: int) -> None:
        self._lib.jy_map_note_edit(self._h, row)

    def map_get(self, row: int):
        """(value, ts) of a LIVE field row, else None."""
        ts = ctypes.c_uint64()
        ptr = ctypes.c_void_p()
        n = ctypes.c_int64()
        if not self._lib.jy_map_get(
            self._h, row, ctypes.byref(ts), ctypes.byref(ptr), ctypes.byref(n)
        ):
            return None
        return ctypes.string_at(ptr, n.value), ts.value

    def map_field_name(self, row: int) -> bytes:
        ptr = ctypes.c_void_p()
        n = ctypes.c_int64()
        self._lib.jy_map_field_name(
            self._h, row, ctypes.byref(ptr), ctypes.byref(n)
        )
        return ctypes.string_at(ptr, n.value)

    def map_mark_mixed(self, key: bytes) -> None:
        self._lib.jy_map_mark_mixed(self._h, key, len(key))

    def map_is_mixed(self, key: bytes) -> bool:
        return bool(self._lib.jy_map_is_mixed(self._h, key, len(key)))

    def map_record(self, key: bytes, count: bool = False):
        """A record's live field rows, in name order, as an int64 array
        (``count``: tallied as one GETALL)."""
        cap = 16
        while True:
            rows = np.empty(cap, np.int64)
            n = self._lib.jy_map_record(
                self._h, key, len(key), rows.ctypes.data, cap, int(count)
            )
            if n >= 0:
                return rows[:n]
            cap = -n

    def map_join_unit(self, packed: bytes, ver: dict, tomb: dict, ts: int,
                      value: bytes) -> int:
        """Join one foreign TREG unit under its packed key; -1 when the
        key names no (key, field)."""
        pairs = [
            np.fromiter(chain.from_iterable(d.items()), np.uint64, 2 * len(d))
            for d in (ver, tomb)
        ]
        return self._lib.jy_map_join_unit(
            self._h, packed, len(packed), pairs[0].ctypes.data, len(ver),
            pairs[1].ctypes.data, len(tomb), ts, value, len(value),
        )

    def map_load_wire(self, payload, count: int) -> None:
        buf = np.frombuffer(payload, np.uint8)
        self._lib.jy_map_load_wire(self._h, buf.ctypes.data, len(buf), count)

    def map_wire(self, rows=None):
        """``rows`` (or, with None, every row sorted by packed key) as
        their wire units: (the units' count, their bytes concatenated,
        where each unit starts in them)."""
        if rows is None:
            n = self.map_rows()
            need = self._lib.jy_map_wire_build(self._h, None, 0)
        else:
            rows = np.ascontiguousarray(rows, np.int64)
            n = len(rows)
            need = self._lib.jy_map_wire_build(self._h, rows.ctypes.data, n)
        out = np.empty(need, np.uint8)
        starts = np.empty(n, np.int64)
        self._lib.jy_map_wire_take(self._h, out.ctypes.data, starts.ctypes.data)
        return n, out.tobytes(), starts.tolist()

    def map_pend_count(self) -> int:
        return self._lib.jy_map_pend_count(self._h)

    def map_dirty_count(self) -> int:
        return self._lib.jy_map_dirty_count(self._h)

    def map_export_planes(
        self, ki, cells, ts_hi, ts_lo, rank_hi, rank_lo, vid, dense: bool
    ) -> int:
        """The rows changed since the last drain as the drain's batch
        planes (`treg_export_planes`' contract, plus ``cells``: the
        (batch, 4 * replicas) u32 plane of ver | tomb columns)."""
        cap = len(vid)
        arrs = (ts_hi, ts_lo, rank_hi, rank_lo)
        if (
            len(ki) < self.map_pend_count()
            or ki.dtype != np.int32
            or vid.dtype != np.int32
            or cells.dtype != np.uint32
            or cells.ndim != 2
            or cells.shape[0] != cap
            or cells.shape[1] % 4
            or any(len(p) != cap or p.dtype != np.uint32 for p in arrs)
            or not all(a.flags.c_contiguous for a in (ki, vid, cells, *arrs))
        ):
            raise ValueError("map_export_planes: batch arrays do not fit")
        n = self._lib.jy_map_export_planes(
            self._h, ki.ctypes.data, cells.ctypes.data, cells.shape[1] // 4,
            ts_hi.ctypes.data, ts_lo.ctypes.data, rank_hi.ctypes.data,
            rank_lo.ctypes.data, vid.ctypes.data, cap, int(dense),
        )
        if n < 0:
            raise ValueError("map_export_planes: a slot outside the batch")
        return n

    def map_settle_ties(self, rows):
        rows = np.array(rows, np.int32)  # a copy: compacted in place
        vids = np.empty(len(rows), np.int32)
        m = self._lib.jy_map_settle_ties(
            self._h, rows.ctypes.data, len(rows), vids.ctypes.data
        )
        return rows[:m], vids[:m]

    def map_clear_pend(self) -> None:
        self._lib.jy_map_clear_pend(self._h)

    def map_take_dirty(self):
        """Field rows edited locally since the last flush; clears."""
        return self._export_sync_dirty(self._lib.jy_map_take, 0)

    def map_take_sync(self):
        """Field rows changed since the last digest pass; clears."""
        return self._export_sync_dirty(self._lib.jy_map_take, 1)

    def map_tally(self) -> None:
        """Bring the registry's `drain.MAP.*` command tallies up to the
        table's counters (`pull_tallies`)."""
        now = (ctypes.c_uint64 * 3)()
        self._lib.jy_map_tallies(self._h, now)
        seen, self._map_seen = self._map_seen, list(now)
        reg = resolve_registry(self)
        reg.tally("drain.MAP.sets", now[0] - seen[0])
        reg.tally("drain.MAP.getalls", now[1] - seen[1])
        reg.tally("drain.MAP.getall_fields", now[2] - seen[2])

    def pull_tallies(self) -> None:
        """What the registry calls before it reports its tallies: the
        counters that live in the library are read now."""
        self.sender_tally()
        self.map_tally()

    # the engine's changed/served-counter type order (serve_engine.cpp)
    TYPE_ORDER = ("GCOUNT", "PNCOUNT", "TREG", "TLOG", "UJSON", "MAP")

    def served_counts(self) -> dict[str, int]:
        """Commands settled natively since startup, per data type."""
        out = np.zeros(len(self.TYPE_ORDER), np.uint64)
        self._lib.jy_eng_served(self._h, out.ctypes.data)
        return dict(zip(self.TYPE_ORDER, out.tolist()))

    # ---- UJSON render memo -------------------------------------------------

    @staticmethod
    def _uj_path_blob(path_args) -> bytes:
        """Path argument vector as the memo's length-prefixed blob key
        (binary-safe, and component-prefix == byte-prefix — engine.h).
        Components are CANONICALISED to the UTF-8 encoding of the
        errors="replace" decode the oracle applies (repo_ujson
        _decode_path): byte-distinct spellings that alias in the
        document alias in the memo too, so invalidation through one
        spelling can never leave another's render stale. The engine's
        bank-time invalidation uses raw bytes, which equal this
        canonical form exactly for valid UTF-8 — and it defers any
        write whose path is not valid UTF-8 (engine.h utf8_valid)."""
        return b"".join(
            struct.pack("<I", len(c)) + c
            for c in (
                bytes(p).decode("utf-8", "replace").encode()
                for p in path_args
            )
        )

    def uj_memo_put(self, key: bytes, path_args, reply: bytes) -> None:
        """Install the oracle-rendered GET reply for (key, path)."""
        row = self._lib.jy_uj_upsert(self._h, key, len(key))
        blob = self._uj_path_blob(path_args)
        self._lib.jy_uj_memo_put(
            self._h, row, blob, len(blob), reply, len(reply)
        )

    def uj_invalidate(self, key: bytes, path_args, subtree: bool) -> None:
        """Drop the renders a write at path can change: INS/RM
        (subtree=False) touch only renders at prefix paths; SET/CLR
        (subtree=True) rewrite the subtree, so both prefix directions."""
        blob = self._uj_path_blob(path_args)
        self._lib.jy_uj_invalidate(
            self._h, key, len(key), blob, len(blob), 1 if subtree else 0
        )

    def uj_memo_len(self, key: bytes) -> int:
        return self._lib.jy_uj_memo_len(self._h, key, len(key))

    # ---- UJSON queue -------------------------------------------------------

    def uq_count(self) -> int:
        return self._lib.jy_uq_count(self._h)

    def uq_drain(self) -> list[list[bytes]]:
        """Pop every banked UJSON write (INS/SET/RM/CLR) as its raw
        argument list (without the leading type word), in arrival
        order."""
        nbytes = self._lib.jy_uq_bytes(self._h)
        if nbytes == 0:
            return []
        blob = (ctypes.c_uint8 * nbytes)()
        got = self._lib.jy_uq_data(self._h, blob, nbytes)
        assert got == nbytes
        self._lib.jy_uq_clear(self._h)
        data = bytes(blob)
        out = []
        pos = 0
        while pos < len(data):
            (argc,) = struct.unpack_from("<I", data, pos)
            pos += 4
            args = []
            for _ in range(argc):
                (ln,) = struct.unpack_from("<I", data, pos)
                pos += 4
                args.append(data[pos : pos + ln])
                pos += ln
            out.append(args)
        return out

    # ---- the batch applier -------------------------------------------------

    def bind_metrics(self, registry) -> None:
        """Count this engine's reply buffer in ``registry`` (its
        Database's): the bytes it holds now, then every grow. The
        sender's counters live in the library and are read when the
        registry reports."""
        self.metrics = registry
        registry.tally("serving.ENGINE.reply_buffer_bytes", len(self._out))
        me = weakref.ref(self)
        registry.sender_fn = lambda: (e := me()) and e.pull_tallies()

    def _grow_out(self, need: int) -> None:
        """Replace the reply buffer by one of the next power of two that
        holds ``need`` bytes. The loop is one thread and a burst's
        replies are copied out (`reply_bytes`, or into the sender's job)
        before the next burst runs, so nothing still reads the old
        array."""
        cap = 1 << (need - 1).bit_length()
        reg = resolve_registry(self)
        reg.tally("serving.ENGINE.reply_grows", 1)
        reg.tally("serving.ENGINE.reply_buffer_bytes", cap - len(self._out))
        self._out = (ctypes.c_uint8 * cap)()

    def reply_bytes(self, n: int) -> bytes:
        """A copy of the first ``n`` bytes of the reply array."""
        return ctypes.string_at(self._out, n)

    def scan_apply(self, buf, held: int = ALL_TYPES):
        """Apply a pipelined burst under the repo locks of the types in
        ``held`` (bit i: type i of `TYPE_ORDER`;
        the default is a caller that owns the engine alone): the run of
        commands ahead up to the first that names another of the six
        types, which is left where it is (rc 5). Returns
        (rc, consumed, n: the replies' length, unhandled: list[bytes] |
        None, changed: tuple of 6 per-type counts in that order); the
        replies are the first ``n`` bytes of the reply
        array, which the next burst reuses: `reply_bytes` copies them
        out, `sender_send` hands them to the sender. rc as
        documented in serve_engine.cpp, but for its 3 (answered here:
        the reply buffer grows to the reply and the burst runs again)
        and its 4 (counted here, handed on as 1)."""
        if not buf:
            return 0, 0, 0, None, (0,) * len(self.TYPE_ORDER)
        base = ctypes.addressof(ctypes.c_char.from_buffer(buf))
        out_len = ctypes.c_int64()
        consumed = ctypes.c_int64()
        n_args = ctypes.c_int32()
        while True:
            rc = self._lib.jy_eng_scan_apply2(
                self._h, ctypes.c_void_p(base), len(buf), held,
                self._out, len(self._out), _OUT_CEIL, ctypes.byref(out_len),
                ctypes.byref(consumed),
                self._offs, self._lens, _MAX_ARGS, ctypes.byref(n_args),
                self._changed,
            )
            if rc != 3:
                break
            self._grow_out(out_len.value)  # the bytes the reply needs
        unhandled = None
        if rc == 4:
            resolve_registry(self).tally("serving.ENGINE.oversize_defers", 1)
            rc = 1
        if rc == 1:
            view = memoryview(buf)
            unhandled = [
                bytes(view[self._offs[i] : self._offs[i] + self._lens[i]])
                for i in range(n_args.value)
            ]
            del view
        return (
            rc, consumed.value, out_len.value, unhandled, tuple(self._changed)
        )

    # ---- the reply sender (native/reply_sender.cpp) ------------------------

    def sender_open(self, fd: int, low: int, high: int) -> int:
        """A door for the socket ``fd``: the connection's id (> 0, never
        reused; the sender works on its own duplicate of the
        descriptor), or -1 when it could not be had. ``low`` / ``high``
        are the loop's water marks for a writer's buffer."""
        return self._lib.jy_snd_open(self._h, fd, low, high)

    def sender_send(self, conn: int, n: int, data: bytes | None = None) -> int:
        """Hand the first ``n`` bytes of ``data`` to the sender (copied
        there), or with no ``data`` of the reply array: the only copy a
        burst's replies make on their way out. The bytes the
        connection's consumer is behind by: its pending bytes once the
        socket has refused some (or jobs pile up past the high-water
        mark behind one not yet through), else 0; -1 for a connection
        that is closed or dead."""
        return self._gil.jy_snd_send(
            self._h, conn, self._out if data is None else data, n
        )

    def sender_behind(self, conn: int) -> int:
        """What `sender_send` answers, now (0: closed or dead)."""
        return self._gil.jy_snd_behind(self._h, conn)

    def sender_wait(self, conn: int) -> bool:
        """Arm the sender's signal (`sender_notify_fd`) for the moment
        ``conn`` is written down to its low-water mark; True while it
        is armed and has not fired, False when there is nothing to wait
        for (not behind by more than the high-water mark, closed or
        dead)."""
        return bool(self._gil.jy_snd_wait(self._h, conn))

    def sender_notify_fd(self) -> int:
        return self._lib.jy_snd_notify_fd(self._h)

    def sender_close(self, conn: int) -> int:
        """Close ``conn`` BEFORE its socket is closed: the sender takes
        no more for it, writes out what it holds (the bytes still to go
        are returned) and then closes its descriptor, so the peer reads
        every reply and then the end of the stream. Idempotent."""
        return self._lib.jy_snd_close(self._h, conn)

    def sender_pending(self) -> int:
        """The bytes the sender holds now, over all connections, the
        closing ones too."""
        return self._gil.jy_snd_pending(self._h)

    def sender_stop(self) -> None:
        """Join the sender's thread, if one runs; what closing
        connections still hold is written as far as their sockets take
        it at once and dropped beyond."""
        self._lib.jy_snd_stop(self._h)

    def sender_stats(self) -> list[int]:
        """The sender's atomics, now: reply writes handed over, sends a
        socket did not take whole, hand-offs that woke the thread, bytes
        dropped at a close or reset, the most bytes ever held, busy
        microseconds; then the bytes held now and 1 while the thread
        runs."""
        out = (ctypes.c_uint64 * 8)()
        if getattr(self, "_h", None):
            self._lib.jy_snd_stats(self._h, out)
        stats = list(out)
        stats[5] //= 1000
        return stats

    def sender_tally(self) -> None:
        """Bring the registry's `serving.ENGINE.sender_*` tallies up to
        the sender's atomics (the registry calls this before it reports
        its tallies)."""
        now = self.sender_stats()
        seen, self._sender_seen = self._sender_seen, now
        reg = resolve_registry(self)
        reg.tally("serving.ENGINE.sender_sends", now[0] - seen[0])
        reg.tally("serving.ENGINE.sender_partial", now[1] - seen[1])
        reg.tally("serving.ENGINE.sender_wakes", now[2] - seen[2])
        reg.tally("serving.ENGINE.sender_dropped_bytes", now[3] - seen[3])
        reg.tally("serving.ENGINE.sender_pending_max_bytes", now[4] - seen[4])
        reg.tally("serving.ENGINE.sender_busy_us", now[5] - seen[5])

    def types_ahead(self, buf) -> int:
        """What the run of commands at the head of ``buf`` names, as
        `scan_apply` would see it: the low six bits are the set of
        engine types (the ``held`` order) named by the complete commands
        up to the first that names none of them or cannot be read (64
        commands ahead at most);
        ``>> 8`` is the FIRST command's type, 6 for another first word
        (SYSTEM, TENSOR, ...), 7 where no complete command names one.
        Reads only."""
        if not buf:
            return 7 << 8
        base = ctypes.addressof(ctypes.c_char.from_buffer(buf))
        return self._lib.jy_eng_types_ahead(
            base, len(buf), self._offs, self._lens, _MAX_ARGS
        )


# the counter-only name the round-3 engine shipped under; kept for callers
CounterEngine = ServeEngine


def make_engine() -> ServeEngine | None:
    cdll = lib()
    return ServeEngine(cdll) if cdll is not None else None


def resolve_engine(engine):
    """The repos'/Database's shared engine-argument convention:
    "auto" -> a fresh native engine (None without a toolchain),
    "python" -> None (pure-Python table backends), anything else is
    passed through (a shared ServeEngine instance or None)."""
    if engine == "auto":
        return make_engine()
    if engine == "python":
        return None
    return engine
