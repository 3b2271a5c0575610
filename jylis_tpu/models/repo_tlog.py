"""TLOG repo: device-resident timestamped-log keyspace.

Reference analog: repo_tlog.pony:16-111 (Map[key -> TLog], per-key list
insertion). Here the keyspace is the padded ops/tlog plane block (narrow
2-plane layout until the first 64-bit timestamp widens it); local INS and
incoming delta logs buffer host-side per key and drain as ONE batched
merge dispatch at write thresholds and snapshots — TRIM/TRIMAT/CLR fuse
into that same dispatch (the kernel's per-row count column), and their
returned (length, cutoff) pairs maintain the host caches. Reads never
drain: GET/SIZE/CUTOFF serve the exact merged view (union + dedup +
cutoff filter over the drained base and the pending buffer, memoised per
row). A drain keeps the row it drained: its epilogue folds the pending
window into the drained base the host holds (tlog_table.py
``finish_row``), so no read goes back to the device for a row, a
restored row's first read included. The only device touch a read can
make is the repair path: the one-row gather that rebuilds the base after
a drain whose folded base failed the length guard
(``drain.TLOG.bases_lost`` counts those, ``drain.TLOG.row_gathers`` the
gathers).

Host bookkeeping (keys, pending windows, length/cutoff caches, the
merged-view memo, delta accumulators) lives behind the table backends in
tlog_table.py: pure-Python as the oracle, or the native C++ engine — the
SAME state the server's native batch applier (native/serve_engine.cpp)
mutates, so INS/SIZE/GET/CUTOFF settled natively and Python-side
drains/flushes share one source of truth.

Delta wire shape: (entries: list[(value: bytes, ts: u64)], cutoff: u64).
"""

from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor
from functools import partial

import jax
import numpy as np

from ..native.engine import resolve_engine
from ..ops import tlog
from ..ops.interner import Interner
from ..parallel import (
    drain_sharded_tlog,
    route_drain64,
    serving_mesh,
    shard_plane,
    shard_vec,
)
from .base import PAD_ROW, ParseError, bucket, need, parse_opt_count, parse_u64
from .tlog_table import NativeTlogTable, PyTlogTable
from ..utils.metrics import (
    ASSEMBLE,
    DEVICE,
    FINISH,
    drain_phase,
    resolve_registry,
    timed_drain,
)
from .help import RepoHelp

# interner compaction: once the table holds this many more ids than live
# log entries, rebuild it from the live set (ops/interner.compact) so
# INS/TRIM churn can't grow host memory without bound
COMPACT_SLACK = 8192

# The drain's shape lattice. The jitted drain is specialised on the row
# bucket, the pending-width bucket and the plane's len_cap at once, and a
# TLOG program (two multi-key sorts, the fused trim) compiles in seconds
# (~20 s on the chip host) under the repo lock. So each batch dimension
# starts at a floor that every drain of a serving window falls into (a
# TRIM-forced drain carries the few rows written since the last one, a
# few entries each) and goes up in powers of FOUR: at most four times
# the work of the exact size, a quarter of the programs of a power-of-two
# lattice that started at 1. Only the floor's program is ever compiled
# ahead (`warm_drain_shapes`, `_warm_ahead`), and a node that has it runs
# nothing else: a drain that outgrows the floor's batch runs as passes of
# its shape (`_passes`), and the table calls a drain overdue once as many
# entries are pending, over all rows, as ONE floor batch holds
# (`set_entries_bound`: DRAIN_ROWS_FLOOR x DRAIN_WIDTH_FLOOR), so that a
# replica whose cutoffs all come from peers drains every few seconds in a
# dozen passes, not after a row has gathered a thousand entries in a
# hundred. The lattice above the floor is for a node with nothing compiled
# ahead: the boot's restore, a young keyspace, the mesh.
DRAIN_ROWS_FLOOR = 64
DRAIN_WIDTH_FLOOR = 16
# passes dispatched before the host waits for one: each holds a copy of
# the planes on the device until the next has read it (the drain is not
# donated), so a rejoin's hundreds of passes must not all be in flight
PASSES_IN_FLIGHT = 4
# Compiling ahead: the boot compiles the programs of its len_cap and the
# next two; from then on, once the longest row passes WARM_FILL of
# len_cap the programs of the NEXT len_cap are compiled (if they are not
# yet), off the lock, so that the `grow` that row will force meets them
# ready. Planes narrower than
# WARM_MIN_LEN are left alone: rows that short double sooner than a
# program compiles.
WARM_FILL = 0.75
WARM_MIN_LEN = 256
# one worker: a level's programs compile one after another, and the
# interpreter waits for the one in flight at exit
_WARM_POOL = ThreadPoolExecutor(max_workers=1, thread_name_prefix="tlog-warm")


def drain_bucket(n: int, floor: int) -> int:
    """A drain batch dimension padded to the lattice above."""
    b = floor
    while b < n:
        b <<= 2
    return b

def sparse_batch(b: int, ld: int):
    """An empty sparse drain batch (ki, d_ts, d_vid, d_cut, counts): every
    row an out-of-range pad the scatter drops, every trim a no-op."""
    return (
        np.full(b, PAD_ROW, np.int32),
        np.zeros((b, ld), np.uint64),
        np.full((b, ld), -1, np.int64),
        np.zeros(b, np.uint64),
        np.full(b, tlog.TRIM_NOOP, np.int64),
    )


TLOG_HELP = RepoHelp(
    "TLOG",
    {
        "GET": "key [count]",
        "INS": "key value timestamp",
        "SIZE": "key",
        "CUTOFF": "key",
        "TRIMAT": "key timestamp",
        "TRIM": "key count",
        "CLR": "key",
    },
)


@jax.jit
def _drain_tlog(state, ki, d_ts, d_vid, d_cut, counts):
    # fused merge + optional per-row trim (counts >= TRIM_NOOP are no-ops):
    # TRIM/CLR ride the same single dispatch as the drain they need first.
    # NOT donated: on overflow the caller retries from the pre-merge state
    st, ovf = tlog.converge_then_trim(state, ki, d_ts, d_vid, d_cut, ki, counts)
    return st, ovf, st.length[ki], st.cutoff[ki]


@jax.jit
def _drain_tlog_dense(state, d_ts, d_vid, d_cut, trim_ki, counts):
    # dense drain: delta rows aligned 1:1 with the keyspace — no gather or
    # scatter (ops/tlog converge_batch key_idx=None); full length/cutoff
    # vectors read back in the same launch
    st, ovf = tlog.converge_then_trim(
        state, None, d_ts, d_vid, d_cut, trim_ki, counts
    )
    return st, ovf, st.length, st.cutoff


@jax.jit
def _get_row_tlog(state, k):
    ts, vid, _length = tlog.read_row(state, k)
    return ts, vid


# one program a (shape, target) pair: eager, `tlog.grow` would trace and
# compile its pad, slices and scatters one by one while the lock is held
@partial(jax.jit, static_argnums=(1, 2))
def _grow_tlog(state, num_keys, max_len):
    return tlog.grow(state, num_keys, max_len)


class RepoTLOG:
    name = "TLOG"
    help = TLOG_HELP

    def __init__(
        self,
        identity: int,
        key_cap: int = 1024,
        len_cap: int = 16,
        mesh="auto",
        engine="auto",
    ):
        # identity unused: log entries carry no replica identity
        # mesh mode mirrors the counter/TREG repos: with >1 visible device
        # the segment tensors live keys-sharded and drains/trims route
        # through parallel/sharded
        self._mesh = serving_mesh() if mesh == "auto" else mesh
        self._n_shards = self._mesh.devices.size if self._mesh is not None else 1
        self._key_cap = self._round_cap(key_cap)
        self._len_cap = len_cap
        # mesh mode always uses the wide (3-plane) layout: the shard_map
        # drains have one fixed plane structure; single-chip serving keeps
        # the narrow 2-plane layout until a 64-bit timestamp arrives
        self._state = self._place(
            tlog.init(self._key_cap, len_cap, wide=self._mesh is not None)
        )
        self._interner = Interner()
        self.engine = engine = resolve_engine(engine)
        self._tbl = (
            NativeTlogTable(engine) if engine is not None else PyTlogTable()
        )
        # row -> desc-sorted [(ts, value)], the rendered drained part; built
        # on first read, dropped whenever a drain or trim touches the row —
        # so quiescent GETs never dispatch to the device
        self._render: dict[int, list[tuple[int, bytes]]] = {}
        # row -> (table gen, desc-sorted merged list): the GET-order memo
        # over the table's merged view
        self._sorted: dict[int, tuple[int, list[tuple[int, bytes]]]] = {}
        # the longest row a drain has reported (never lowered by a trim:
        # an overestimate only compiles early), the plane shapes whose
        # serving programs are compiled, the level compiling now, and
        # whether the boot's own warming is over (`warm_drain_shapes`:
        # until then nothing is compiled ahead beside it)
        self._longest = 0
        self._warmed: set[tuple[int, int]] = set()
        self._warming = None
        self._warm_on = False

    def _round_cap(self, k: int) -> int:
        """Key capacity must split evenly over the mesh's keys axis."""
        ns = self._n_shards
        return -(-k // ns) * ns

    def _place(self, state):
        """(Re-)place state tensors keys-sharded when a mesh is active."""
        if self._mesh is None:
            return state
        return tlog.TLogState(
            shard_plane(self._mesh, state.nth),
            shard_plane(self._mesh, state.ntl),
            shard_plane(self._mesh, state.nv),
            shard_vec(self._mesh, state.length),
            shard_vec(self._mesh, state.cutoff),
        )

    # -- commands (repo_tlog.pony:29-111) ----------------------------------

    def apply(self, resp, args: list[bytes]) -> bool:
        op = need(args, 0)
        if op == b"GET":
            self._cmd_get(resp, need(args, 1), parse_opt_count(args, 2))
            return False
        if op == b"INS":
            key = need(args, 1)
            value = need(args, 2)
            ts = parse_u64(need(args, 3))
            row = self._tbl.upsert(key)
            self._tbl.ins(row, ts, value)
            if self._tbl.overdue():
                self.drain()
            resp.ok()
            return True
        if op == b"SIZE":
            row = self._tbl.find(need(args, 1))
            if row < 0:
                resp.u64(0)
            elif self._tbl.quiescent(row):
                resp.u64(self._tbl.len_cache(row))  # O(1), no gather
            else:
                resp.u64(self._size_nonquiescent(row))
            return False
        if op == b"CUTOFF":
            row = self._tbl.find(need(args, 1))
            resp.u64(self._tbl.cutoff_view(row) if row >= 0 else 0)
            return False
        if op == b"TRIMAT":
            key = need(args, 1)
            ts = parse_u64(need(args, 2))
            self._device_trimat(key, ts)
            resp.ok()
            return True
        if op == b"TRIM":
            key = need(args, 1)
            count = parse_u64(need(args, 2))
            self._device_trim(key, count)
            resp.ok()
            return True
        if op == b"CLR":
            self._device_trim(need(args, 1), 0)
            resp.ok()
            return True
        raise ParseError()

    def _drained_entries(self, row: int) -> list[tuple[int, bytes]]:
        """The drained part of a row, (ts, value) desc — the render cache.
        A miss serves from the table's carried base when it is valid (the
        common case: the drain kept the exact row content host-side); only
        a base-invalid row pays the ONE device row gather — and then
        REPAIRS the table's base from it (ADVICE round 5): without the
        repair a quiescent row whose drain lost its base (the length
        guard of ``finish_row``) would serve correctly but never settle
        natively again, paying the FFI stop + Python dispatch on every
        later GET."""
        ents = self._render.get(row)
        if ents is None:
            length = self._tbl.len_cache(row)
            if length == 0:
                ents = []
            else:
                base = self._tbl.base_entries(row)
                if base is not None:
                    ents = sorted(base, reverse=True)
                else:
                    resolve_registry(self).tally("drain.TLOG.row_gathers", 1)
                    ts_row, vid_row = _get_row_tlog(self._state, row)
                    ts_row = np.asarray(ts_row)
                    vid_row = np.asarray(vid_row)
                    ents = [
                        (int(ts_row[i]), self._interner.lookup(int(vid_row[i])))
                        for i in range(length)
                    ]
                    ents.sort(reverse=True)
                resolve_registry(self).tally("drain.TLOG.view_sorts", 1)
            self._render[row] = ents
        if not self._tbl.base_valid(row):
            self._tbl.set_base(row, ents)
        return ents

    def _size_nonquiescent(self, row: int) -> int:
        """Merged-view size with the drained-base handshake: the table
        serves it host-side unless its base is unknown (a drain lost
        it: `drain.TLOG.bases_lost`), in which case ONE device row
        gather rebuilds it (_drained_entries also writes it back as the
        table's base)."""
        n = self._tbl.size(row)
        if n < 0:
            self._drained_entries(row)
            n = self._tbl.size(row)
        return n

    def _merged_view(self, row: int) -> tuple[list[tuple[int, bytes]], int]:
        """The exact log as a drain would leave it, (ts, value) desc —
        computed on the host: reads NEVER pay a device drain (at most one
        row gather for the drained base)."""
        cut = self._tbl.cutoff_view(row)
        if self._tbl.quiescent(row):
            return self._drained_entries(row), cut
        self._size_nonquiescent(row)  # ensure the merged memo is current
        gen = self._tbl.gen(row)
        hit = self._sorted.get(row)
        if hit is not None and hit[0] == gen:
            return hit[1], cut
        ents = sorted(self._tbl.merged_entries(row), reverse=True)
        resolve_registry(self).tally("drain.TLOG.view_sorts", 1)
        self._sorted[row] = (gen, ents)
        return ents, cut

    def _cmd_get(self, resp, key: bytes, count: int) -> None:
        row = self._tbl.find(key)
        if row < 0:
            resp.array_start(0)
            return
        ents, _cut = self._merged_view(row)
        n = min(count, len(ents))
        resp.array_start(n)
        for ts, value in ents[:n]:
            resp.array_start(2)
            resp.string(value)
            resp.u64(ts)

    def _device_trimat(self, key: bytes, ts: int) -> None:
        """TRIMAT == TRIM with a direct cutoff target: raise the pending
        cutoff and drain ONCE — the merge joins pending entries and the new
        cutoff in the same lattice op ((S ⊔ P) ⊔ C == S ⊔ (P ⊔ C)), so the
        old drain-set-drain double dispatch was pure overhead (VERDICT r2
        weak item 6)."""
        row = self._tbl.upsert(key)
        self._tbl.converge_cutoff(row, ts)
        resolve_registry(self).tally("drain.TLOG.trims", 1)
        self.drain()
        self._tbl.delta_raise_cutoff(row, self._tbl.cut_cache(row))

    def _device_trim(self, key: bytes, count: int) -> None:
        """TRIM/CLR: the trim needs the row's pending entries merged
        first, so it rides the drain dispatch as the fused per-row count
        column — ONE launch total (was drain-then-trim, two)."""
        row = self._tbl.upsert(key)
        resolve_registry(self).tally("drain.TLOG.trims", 1)
        # counts above any possible length are no-ops (tlog.md:58); clamping
        # to the kernel sentinel keeps huge client counts out of int64 range
        self.drain(trim=(row, min(count, tlog.TRIM_NOOP)))
        self._tbl.delta_raise_cutoff(row, self._tbl.cut_cache(row))

    # -- lattice plumbing ---------------------------------------------------

    def converge(self, key: bytes, delta: tuple) -> None:
        # buffer only: the serving path drains via drain_overdue in a
        # worker thread; sync callers (snapshot restore) drain explicitly
        entries, cutoff = delta
        row = self._tbl.upsert(key)
        for value, ts in entries:
            self._tbl.converge_entry(row, ts, value)
        reg = resolve_registry(self)
        reg.tally("drain.TLOG.foreign_entries", len(entries))
        if cutoff and cutoff > self._tbl.cutoff_view(row):
            reg.tally("drain.TLOG.foreign_cutoffs", 1)
            self._tbl.converge_cutoff(row, cutoff)

    def deltas_size(self) -> int:
        return self._tbl.deltas_size()

    def may_drain(self, args: list[bytes]) -> bool:
        """Device-bound commands the server offloads to a thread: trims
        always dispatch; an INS that will tip a drain threshold does.
        Reads NEVER drain — GET/SIZE/CUTOFF serve the exact merged view
        host-side — but a read that must rebuild the drained base pays
        one device row gather — a dispatch plus a blocking read-back:
        offload it too so it never stalls the event loop
        (the counter repos' foreign-GET pattern)."""
        if not args:
            return False
        op = args[0]
        if op in (b"TRIM", b"TRIMAT", b"CLR"):
            return True
        if op == b"INS" and len(args) >= 2:
            row = self._tbl.find(args[1])
            in_row = self._tbl.pend_len(row) if row >= 0 else 0
            return self._tbl.ins_tips(in_row)
        if op in (b"GET", b"SIZE") and len(args) >= 2:
            row = self._tbl.find(args[1])
            if row < 0:
                return False
            if self._tbl.quiescent(row):
                if op == b"SIZE":
                    return False  # O(1) length-cache answer, no gather
                return (
                    row not in self._render
                    and self._tbl.len_cache(row) > 0
                    and not self._tbl.base_valid(row)  # a real device gather
                )
            return self._tbl.size(row) < 0  # gather only when base unknown
        return False

    def drain_overdue(self) -> bool:
        """Cluster converge path: after buffering a batch, the manager
        offloads the drain to a worker thread when a bound of the table
        trips. O(1): the table keeps the counts as it appends."""
        return self._tbl.overdue()

    def flush_deltas(self):
        return self._tbl.flush_deltas()

    # -- sync digest (cluster/syncdigest.py) ---------------------------------

    def sync_dirty_keys(self) -> list[bytes]:
        return [self._tbl.key_of(r) for r in self._tbl.export_sync_dirty()]

    def sync_canon(self, key: bytes) -> bytes | None:
        """Canonical per-key state: the merged view (the exact post-drain
        lattice content, pending included) plus the grow-only cutoff —
        host-side except for the rare base-invalid row's one-row gather."""
        row = self._tbl.find(key)
        if row < 0:
            return None
        ents, cut = self._merged_view(row)
        if not ents and not cut:
            return None
        return repr((ents, cut)).encode()

    # -- snapshot (persist.py): full state in the wire-delta shape ----------

    def dump_state(self):
        self.drain()
        # one bulk device->host pull, then slice rows locally (a per-key
        # jitted gather would be O(keys) dispatches inside shutdown)
        st = self._state
        all_ts = tlog.decode_ts_np(
            None if st.nth is None else np.asarray(st.nth), np.asarray(st.ntl)
        )
        all_vid = tlog.decode_vid_np(np.asarray(st.nv))
        out = []
        for key, row in sorted(
            (self._tbl.key_of(r), r) for r in range(self._tbl.rows())
        ):
            length = self._tbl.len_cache(row)
            cutoff = self._tbl.cut_cache(row)
            entries = [
                (self._interner.lookup(int(all_vid[row, i])), int(all_ts[row, i]))
                for i in range(length)
            ]
            if entries or cutoff:
                out.append((key, (entries, cutoff)))
        return out

    def load_state(self, batch) -> None:
        for key, delta in batch:
            self.converge(key, delta)

    def _maybe_compact_interner(self) -> None:
        """Epoch compaction (weak-spot fix, VERDICT round 2): every value
        ever INSerted kept its interner slot after being trimmed away.
        Live ids are exactly the device rows' first `length` slots
        (canonical order scrubs the rest to -1), so pull the vid plane
        once, rebuild the table from the live set, and push the remapped
        plane back. Runs under the repo lock at drain time, before any
        new pending values intern."""
        # the native value interner compacts itself on the same cadence
        # (cheap floor check per drain; full walk only when it has grown)
        self._tbl.compact_values()
        live = self._tbl.live_total()  # O(1): maintained at finish_row
        if len(self._interner) <= 2 * live + COMPACT_SLACK:
            return
        lengths = {
            r: self._tbl.len_cache(r) for r in range(self._tbl.rows())
        }
        all_vid = tlog.decode_vid_np(np.asarray(self._state.nv))  # one pull
        rows = [
            all_vid[row, :length]
            for row, length in lengths.items()
            if length > 0
        ]
        flat = np.concatenate(rows) if rows else np.empty(0, np.int64)
        remap = self._interner.compact(flat[flat >= 0])
        new_vid = np.full(all_vid.shape, -1, np.int64)
        for row, length in lengths.items():
            if length > 0:
                src = all_vid[row, :length]
                # mask negatives on application exactly as on collection:
                # remap[-1] would silently alias the last live id
                new_vid[row, :length] = np.where(
                    src >= 0, remap[np.clip(src, 0, None)], -1
                )
        new_nv = tlog.encode_vid_np(new_vid)
        self._state = self._state._replace(
            nv=shard_plane(self._mesh, new_nv)
            if self._mesh is not None
            else jax.numpy.asarray(new_nv)
        )

    def _grow(self, key_cap: int, len_cap: int) -> None:
        """Regrow the planes, single-chip as ONE jitted program (compiled
        ahead by `_warm_levels` where the row that forces it was seen
        coming), so that the lock is not held for a compile."""
        grow = _grow_tlog if self._mesh is None else tlog.grow
        self._key_cap, self._len_cap = key_cap, len_cap
        self._state = self._place(grow(self._state, key_cap, len_cap))
        resolve_registry(self).tally("drain.TLOG.grows", 1)
        self._bound_drains()

    def _bound_drains(self) -> None:
        """Once the floor's program is compiled for the planes as they
        are, a drain is overdue at the entries one floor batch holds."""
        if (self._key_cap, self._len_cap) in self._warmed:
            self._tbl.set_entries_bound(DRAIN_ROWS_FLOOR * DRAIN_WIDTH_FLOOR)

    def _warm_levels(self, state: tlog.TLogState, first: int, last: int) -> None:
        """Compile what a serving drain runs at the plane widths
        ``first``..``last`` doublings above ``state``'s: the sparse drain
        at the lattice's floor, the one-row gather of a read, and the
        `grow` to the next width, which also carries the walk there.
        The batch is all pads and the drain not donated: ``state`` stays
        what it was."""
        for level in range(last + 1):
            if level >= first:
                _drain_tlog(state, *sparse_batch(DRAIN_ROWS_FLOOR, DRAIN_WIDTH_FLOOR))
                _get_row_tlog(state, 0)
                self._warmed.add(tuple(state.shape))
            k, l = state.shape
            state = _grow_tlog(state, k, 2 * l)

    def warm_drain_shapes(self) -> None:
        """Boot, after recovery has settled the capacity (single-threaded
        caller): compile the serving programs for the recovered len_cap
        AND the next two, so that neither the first drains nor the first
        `grow` compile with clients waiting, and the warm thread's
        compiles (`_warm_ahead`, which take host cores beside the
        serving loop for tens of seconds) start only once a row has
        grown to three times the boot's width. A longest row already past
        WARM_FILL regrows the planes first: it would overflow within the
        first seconds of serving, and nobody waits now. Planes under
        WARM_MIN_LEN (an empty or young keyspace: `Database.warmup`
        compiled the default shape) and the mesh path, which has its
        own programs, are left to their first drain."""
        if self._mesh is not None:
            return
        self.drain()  # what recovery buffered (a restore has drained already)
        self._warm_on = True
        if self._len_cap < WARM_MIN_LEN:
            return
        if self._longest > WARM_FILL * self._len_cap:
            self._grow(self._key_cap, 2 * self._len_cap)
        self._warm_levels(self._state, 0, 2)
        self._bound_drains()

    def _warm_ahead(self) -> None:
        """After a drain of a booted single-chip node: once the longest
        row is past WARM_FILL of len_cap, compile the next len_cap's
        programs on the warm thread, from the state as it is now (device
        arrays are immutable: the thread shares it safely, and drops the
        grown copies it makes)."""
        k, l = self._key_cap, self._len_cap
        if (
            not self._warm_on
            or l < WARM_MIN_LEN
            or self._longest <= WARM_FILL * l
            or (k, 2 * l) in self._warmed
            or (self._warming is not None and not self._warming.done())
        ):
            return
        self._warming = _WARM_POOL.submit(self._warm_levels, self._state, 1, 1)

    def _finish_rows(self, updates) -> None:
        """A drain's epilogue for the rows it is done with: refresh the
        per-row host caches from the kernel's (row, length, cutoff)
        read-backs and clear their pending windows. A row the table
        could not keep a host base for is a later read's device gather."""
        lost = 0
        for row, ln, ct in updates:
            self._render.pop(row, None)
            self._sorted.pop(row, None)
            lost += not self._tbl.finish_row(row, int(ln), int(ct))
            self._longest = max(self._longest, int(ln))
        resolve_registry(self).tally("drain.TLOG.bases_lost", lost)

    @timed_drain("TLOG", lambda self: self._tbl.touched_count())
    def drain(self, trim: tuple[int, int] | None = None) -> None:
        """Flush pending entries/cutoffs; with ``trim`` = (row, count),
        the TRIM/CLR of that row fuses into the dispatch that carries
        the last of its entries, via the kernel's per-row count column
        (counts of TRIM_NOOP leave other rows untouched). One dispatch,
        or the passes of `_passes` on a node whose floor program is
        compiled ahead."""
        row_set = set(self._tbl.touched_rows())
        if not row_set and trim is None:
            return
        reg = resolve_registry(self)
        if self._tbl.overdue():
            # a bound tripped (the INS that crossed it, the batch a peer
            # sent, a restore): every trim drains at once, under the
            # lock that buffered its cutoff, so none finds this set
            reg.tally("drain.TLOG.overdue", 1)
        self._maybe_compact_interner()
        if trim is not None:
            row_set.add(trim[0])
        rows = sorted(row_set)
        pend = self._tbl.export_pend_bulk(rows)
        cuts_in = {r: self._tbl.pend_cutoff(r) for r in rows}
        # adaptive layout: the narrow (2-plane) state holds every ts below
        # TS32_MAX; the first wider timestamp or cutoff upgrades it
        # losslessly before this drain ships (mesh states start wide)
        if not self._state.wide and (
            any(ts > tlog.TS32_MAX for lst in pend.values() for ts, _ in lst)
            or any(c > tlog.TS32_MAX for c in cuts_in.values())
        ):
            self._state = tlog.widen(self._state)
        # capacity: keys, then entry slots (worst case current + pending)
        kcap = self._round_cap(bucket(max(self._tbl.rows(), 1), self._key_cap))
        need_len = max(
            self._tbl.len_cache(r) + len(pend.get(r, ())) for r in rows
        )
        lcap = bucket(max(need_len, 1), self._len_cap)
        if kcap != self._key_cap or lcap != self._len_cap:
            self._grow(kcap, lcap)
        reg.tally(
            "drain.TLOG.entries", sum(len(pend.get(r, ())) for r in rows)
        )
        if self._mesh is not None:
            self._drain_sharded(rows, pend, cuts_in, trim)
            return
        batches = [
            self._batch(chunk, lo, hi, pend, cuts_in, trim)
            for chunk, lo, hi in self._passes(rows, pend)
        ]
        reg.tally("drain.TLOG.passes", len(batches))
        drain_phase(self, DEVICE)
        while True:
            # every pass is dispatched before any result is read: the
            # device runs them back to back, the host waits once
            state, outs = self._state, []
            for n, (program, args, _done) in enumerate(batches, 1):
                state, *out = program(state, *args)
                outs.append(out)
                if n % PASSES_IN_FLIGHT == 0:
                    out[0].block_until_ready()
            outs = [[np.asarray(a) for a in out] for out in outs]
            if not any(out[0].any() for out in outs):
                break
            # a row outgrew its slots (the dense kernel flags every row
            # whose entries reach the tail columns the delta writes
            # through, pending or not): again from the retained
            # pre-merge state, with the slots doubled
            self._grow(self._key_cap, 2 * self._len_cap)
        self._state = state
        drain_phase(self, FINISH)
        for (_program, _args, done), (_ovf, lens, cuts) in zip(batches, outs):
            self._finish_rows((r, lens[i], cuts[i]) for r, i in done.items())
        self._tbl.finish_drain_end()
        self._warm_ahead()

    def _passes(self, rows, pend) -> list[tuple[list[int], int, int]]:
        """What a single-chip drain dispatches, as (rows, lo, hi): the
        entries ``pend[row][lo:hi]`` of each row of the chunk. ONE pass
        takes everything, padded to the lattice, unless the floor's
        program is compiled for these planes and the batch outgrows it:
        then the rows go DRAIN_ROWS_FLOOR at a time, DRAIN_WIDTH_FLOOR
        entries of each, level by level while any row has entries left.
        A log is a set and a cutoff a maximum, so the passes leave the
        state one dispatch would."""
        widest = max((len(pend.get(r, ())) for r in rows), default=0)
        if (self._key_cap, self._len_cap) not in self._warmed or (
            len(rows) <= DRAIN_ROWS_FLOOR and widest <= DRAIN_WIDTH_FLOOR
        ):
            return [(rows, 0, max(widest, 1))]
        out, lo, live = [], 0, rows
        while live:
            out += [
                (live[i : i + DRAIN_ROWS_FLOOR], lo, lo + DRAIN_WIDTH_FLOOR)
                for i in range(0, len(live), DRAIN_ROWS_FLOOR)
            ]
            lo += DRAIN_WIDTH_FLOOR
            live = [r for r in live if len(pend.get(r, ())) > lo]
        return out

    def _batch(self, rows, lo, hi, pend, cuts_in, trim):
        """One pass as (program, its arguments after the state, {row:
        index of the row in the program's length and cutoff outputs} of
        the rows whose entries END in this pass): ``pend[row][lo:hi]`` and
        the cutoff of every row of ``rows``. A row that ends here takes
        its trim here and is finished from this pass's outputs (the host
        folds its WHOLE pending window into the base it holds, which is
        what the device row then is); one that goes on is left alone."""
        ld = drain_bucket(hi - lo, DRAIN_WIDTH_FLOOR)
        # dense path (repo_counters precedent): when the batch covers a
        # quarter of the keyspace and rows are narrow, aligned delta
        # rows skip the gather/scatter entirely
        dense = len(rows) * 4 >= self._key_cap and ld <= 64
        if dense:
            kc = self._key_cap
            d_ts = np.zeros((kc, ld), np.uint64)
            d_vid = np.full((kc, ld), -1, np.int64)
            d_cut = np.zeros(kc, np.uint64)
            slot = {row: row for row in rows}
        else:
            b = drain_bucket(len(rows), DRAIN_ROWS_FLOOR)
            ki, d_ts, d_vid, d_cut, counts = sparse_batch(b, ld)
            ki[: len(rows)] = rows
            slot = {row: i for i, row in enumerate(rows)}
        for row, i in slot.items():
            for j, (ts, value) in enumerate(pend.get(row, ())[lo:hi]):
                d_ts[i, j] = ts
                d_vid[i, j] = self._interner.intern(value)
            d_cut[i] = cuts_in.get(row, 0)
        done = {r: slot[r] for r in rows if len(pend.get(r, ())) <= hi}
        fused = trim is not None and trim[0] in done
        if dense:
            tb = bucket(1)
            trim_ki = np.full(tb, PAD_ROW, np.int32)
            counts = np.full(tb, tlog.TRIM_NOOP, np.int64)
            if fused:
                trim_ki[0], counts[0] = trim
            return _drain_tlog_dense, (d_ts, d_vid, d_cut, trim_ki, counts), done
        if fused:
            counts[slot[trim[0]]] = trim[1]
        return _drain_tlog, (ki, d_ts, d_vid, d_cut, counts), done

    def _drain_sharded(self, rows, pend, cuts_in, trim=None) -> None:
        """Mesh-mode drain: per-row deltas route as u64 payload columns
        [ts(ld) | vid(ld) | cutoff | count]; the batched merge + fused
        trim runs per key block with per-slot lengths/cutoffs read back in
        the same launch. Same overflow-retry contract as the single-chip
        path."""
        import jax.numpy as jnp

        while True:
            ld = bucket(max((len(pend.get(r, ())) for r in rows), default=1), 1)
            payload = np.zeros((len(rows), 2 * ld + 2), np.uint64)
            # empty vid slots must read back as -1, not id 0
            payload[:, ld : 2 * ld] = np.uint64(0xFFFFFFFFFFFFFFFF)
            payload[:, 2 * ld + 1] = np.uint64(tlog.TRIM_NOOP)
            for i, row in enumerate(rows):
                for j, (ts, value) in enumerate(pend.get(row, ())):
                    payload[i, j] = ts
                    payload[i, ld + j] = self._interner.intern(value)
                payload[i, 2 * ld] = cuts_in.get(row, 0)
                if trim is not None and row == trim[0]:
                    payload[i, 2 * ld + 1] = trim[1]
            lr, pay, slots = route_drain64(
                np.asarray(rows, np.int64),
                payload,
                self._n_shards,
                self._key_cap // self._n_shards,
            )
            drain_phase(self, DEVICE)
            out = drain_sharded_tlog(
                self._mesh, *self._state, lr, jnp.asarray(pay), ld
            )
            ovf = np.asarray(out[5])
            if bool(ovf[slots >= 0].any()):
                # retry from the retained pre-merge state with doubled slots
                drain_phase(self, ASSEMBLE)
                self._grow(self._key_cap, 2 * self._len_cap)
                continue
            self._state = tlog.TLogState(*out[:5])
            lens, cuts = np.asarray(out[6]), np.asarray(out[7])
            drain_phase(self, FINISH)
            self._finish_rows(
                (int(g), lens[j], cuts[j])
                for j, g in enumerate(slots)
                if g >= 0
            )
            self._tbl.finish_drain_end()
            return
