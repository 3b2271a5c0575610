"""UJSON repo: causal-document keyspace with device-RESIDENT hot keys.

Reference analog: repo_ujson.pony:14-110. Variadic argument shape: the
first arg is the database key, the LAST arg is the value/document (for
SET/INS/RM), and everything between is a path of nested-map keys
(repo_ujson.pony:45-49). GET/CLR take key + optional path only.

Every key is in exactly ONE of two modes:

* host mode (``_data``): the authoritative doc is a host ``UJSON``
  (ops/ujson_host.py). Keys are born here; local writes always happen
  here. This is the reference's shape.
* device mode (``_res``): the doc lives as a packed row in the
  device-resident store (ops/ujson_resident.ResidentStore). A key is
  promoted the first time its anti-entropy fan-in earns device work, and
  from then on drains encode ONLY the new deltas and fold them into the
  resident row on device — the full document is never re-encoded or
  host-walked again (the round-3 bottleneck, and the reference's
  per-delta full-doc converge loop, repo_ujson.pony:96-110).

Reads on device-mode keys decode lazily and cache; the cache invalidates
per key when a fold touches the key. Under the default rule (promotion by
fan-in, ``resident_min_leaves`` 0) local writes demote the key back to
host mode first (observed-remove mutators need the current doc anyway),
so write-hot keys simply stay in the reference's host shape while
anti-entropy-hot keys stay resident.

Residency by SIZE (``--ujson-resident-min-leaves N``, N > 0): a document
of N or more leaves is admitted when a snapshot or the journal restores
it or when a write grows it to N, and from then on a local write does NOT
demote: the mutator runs on the decoded view (so observed-remove sees
exactly what this node has observed), its delta joins the flush delta for
the peers AND queues as a ROW DELTA for the resident row, which folds on
the device with the key's foreign deltas. A 1,000-leaf document is never
re-encoded around a write. The ROW (with what is pending for it) is the
document; a decoded view is a read cache of it: the boot leaves none
(a document's first read or write gathers and decodes its row) and every
fold drops the view of each key it folds, so the next read or write of
the key gathers and decodes what the device folded. The store's planes
are sized from what was restored and its fold programs compiled at boot
(`warm_drain_shapes`).

Seqs past u32 exceed every device layout; those keys fall back to host
mode permanently (same contract as round 3).

Delta wire shape: the UJSON object itself (entries + causal context).
"""

from __future__ import annotations

from ..ops.ujson_host import UJSON
from ..utils.metrics import DEVICE, FINISH, drain_phase, resolve_registry, timed_drain
from .base import ParseError, need
from .help import RepoHelp

# pending deltas per key at which a SINGLE non-resident key's drain moves
# to the device (and the key becomes resident): below this the host loop
# wins against an unshared dispatch round-trip
DEVICE_FANIN_MIN = 256
# per-key fan-in worth joining a SEGMENTED drain: when many keys drain
# together the dispatch is shared, so smaller fan-ins than
# DEVICE_FANIN_MIN pay for their slice of the launch: the host fold is
# O(D^2) per key, the delta encode is O(D). The one UJSON cell,
# ycsb-ujson-1kx1k-r3.b, sits BELOW it on every key (a 95% read mix gives
# its hottest document a few foreign deltas a second) and reaches the
# store by --ujson-resident-min-leaves instead; no cell sits above it
# (ROADMAP D7)
SEG_FANIN_MIN = 64
# buffered remote deltas across all keys before the converge path forces
# a drain: bounds host memory for write-hot, never-read keys the same way
# TLOG's PENDING_DRAIN_THRESHOLD does (repo_tlog.py:41)
PENDING_TOTAL_MAX = 4096
# a GET-path drain on a RESIDENT key with fewer pending deltas than this
# serves them host-side into the read cache instead of dispatching a
# device fold: the lattice join is idempotent, so the deltas stay pending
# and fold for real at the next full drain — a read-heavy key with a
# delta trickle never pays a device round trip per GET
TRICKLE_MAX = 16
# residency by size: row deltas (local writes the decoded view has
# already absorbed) a resident key queues before it folds them into its
# row on its own — one small dispatch of a few rows, and one decode of the
# row at the key's next read or write — so that a write-hot document's
# list stays short and a FULL drain (every resident key with anything
# pending, seconds of lock on a CPU peer) is left to the total bound,
# twice a minute and not six times. Not 64, though the deepest pinned
# program would hold it: longer lists reach the total bound sooner, and
# a full drain (and the ~600 rows decoded again after it) costs more
# than the folds saved (my chip runs, PR 39: 3,100 -> 2,925 ops/s)
ROW_FOLD_MIN = 32

UJSON_HELP = RepoHelp(
    "UJSON",
    {
        "GET": "key [key...]",
        "SET": "key [key...] ujson",
        "CLR": "key [key...]",
        "INS": "key [key...] value",
        "RM": "key [key...] value",
    },
)


def _decode_path(parts: list[bytes]) -> tuple[str, ...]:
    return tuple(p.decode("utf-8", "replace") for p in parts)


class RepoUJSON:
    name = "UJSON"
    help = UJSON_HELP

    def __init__(self, identity: int, mesh="auto", engine=None):
        from ..parallel import serving_mesh

        self._identity = identity
        # native serving engine (native/serve_engine.cpp): validated
        # INS/SET/RM/CLR commands bank in its write queue (_flush_queue
        # applies them, in arrival order, before any other UJSON work
        # reads or writes), and GET replies this repo rendered are
        # memoised per (key, path) so repeat reads settle natively —
        # every write here invalidates the overlapping memos
        self.engine = engine
        # mesh mode: the resident store's row axis shards over the
        # serving mesh and drains use the row-aligned fold — SPMD with
        # zero collectives, like every plane-backed type
        self._mesh = serving_mesh() if mesh == "auto" else mesh
        self._data: dict[bytes, UJSON] = {}
        self._deltas: dict[bytes, UJSON] = {}
        self._pend: dict[bytes, list[UJSON]] = {}  # buffered remote deltas
        self._pend_total = 0  # deltas across keys, O(1) overdue check
        self._overdue = False  # some key's fan-in reached DEVICE_FANIN_MIN
        self._res = None  # ResidentStore, created on first promotion
        self._res_cache: dict[bytes, UJSON] = {}  # decoded device-mode docs
        # pending deltas already host-converged into the cached view
        # (the GET-path trickle), so repeat reads don't re-walk the doc
        self._res_applied: dict[bytes, int] = {}
        self._host_only: set[bytes] = set()  # seqs past u32: never promote
        self._sync_dirty: set[bytes] = set()  # since last digest pass
        # --ujson-resident-min-leaves (Database.set_ujson_resident_min):
        # 0 = promotion by fan-in only, local writes demote
        self.resident_min_leaves = 0
        self._grown: set[bytes] = set()  # host docs to size up at a drain
        self._demoted: set[bytes] = set()  # once resident: a readmit
        self._fold_keys = 0  # timed_drain's batch size of the fold under way

    # -- mode plumbing -------------------------------------------------------

    def _store(self):
        if self._res is None:
            from ..ops.ujson_resident import ResidentStore

            shard_fn = None
            if self._mesh is not None:
                from ..parallel import shard_docbatch

                mesh = self._mesh
                shard_fn = lambda b: shard_docbatch(mesh, b)  # noqa: E731
            self._res = ResidentStore(mesh=self._mesh, shard_fn=shard_fn)
        return self._res

    @property
    def _state(self):
        """The resident planes, for `Database.device_layout` (the
        shutdown log's `device state` line)."""
        return None if self._res is None else self._res._batch

    def _is_resident(self, key: bytes) -> bool:
        return self._res is not None and key in self._res

    def _view(self, key: bytes) -> UJSON | None:
        """The current doc for reading: host doc, or the resident row
        decoded through the per-key cache."""
        doc = self._data.get(key)
        if doc is not None:
            return doc
        if self._is_resident(key):
            doc = self._res_cache.get(key)
            if doc is None:
                doc = self._res_cache[key] = self._res.read(key)
                resolve_registry(self).tally("drain.UJSON.row_reads", 1)
            return doc
        return None

    def _demote(self, key: bytes, overflow: bool = False) -> None:
        """Move a device-mode key back to host mode: before a local
        write under the fan-in rule (observed-remove mutators walk the
        doc, and host mode is where local delta accumulation lives), or
        for good when a delta overflows every device layout."""
        if not self._is_resident(key):
            return
        doc = self._res_cache.pop(key, None)
        self._res_applied.pop(key, None)
        if doc is not None:
            self._res.discard(key)
        else:
            doc = self._res.evict(key)
        self._data[key] = doc
        self._demoted.add(key)
        reg = resolve_registry(self)
        if overflow:
            reg.tally("drain.UJSON.demote_overflow", 1)
        else:
            reg.tally("drain.UJSON.demote_write", 1)
        reg.tally("drain.UJSON.resident_rows", -1)

    def _host_fold(self, doc: UJSON, deltas) -> None:
        """Converge pending deltas into a host document or a resident
        row's decoded view: counted, with the entries each join examined
        for removal (`UJSON.walked`: the delta's own size, not the
        document's), and each join a ujson.host_fold span."""
        reg = resolve_registry(self)
        if not reg.enabled:
            for d in deltas:
                doc.converge(d)
            return
        seam = reg.seam("ujson.host_fold")
        walked, n = doc.walked, 0
        for d in deltas:
            n += 1
            t0 = seam.begin()
            doc.converge(d)
            seam.end(t0)
        reg.tally("drain.UJSON.host_deltas", n)
        reg.tally("drain.UJSON.host_walked", doc.walked - walked)

    def _mutate(self, doc: UJSON, op: bytes, path, value, delta: UJSON) -> None:
        if op == b"INS":
            doc.ins(self._identity, path, value, delta)
        elif op == b"RM":
            doc.rm(self._identity, path, value, delta)
        elif op == b"SET":
            doc.set_doc(self._identity, path, value, delta)
        else:
            doc.clr(self._identity, path, delta)

    def _write(self, op: bytes, key: bytes, path, value) -> None:
        """One local SET / CLR / INS / RM, the one sequence both the
        direct apply and the banked queue run. Raises ValueError on a
        value the JSON grammar refuses.

        Host-mode keys, and every key under the fan-in rule: observe
        (drain) first where the op removes, demote, mutate the host doc,
        accumulate into the flush delta — the reference's shape. A
        RESIDENT key under residency by size stays resident
        (`_write_resident`)."""
        resolve_registry(self).tally("drain.UJSON.local_writes", 1)
        if op != b"INS" or self.resident_min_leaves:
            # observed-remove (and SET clears OBSERVED dots): observe
            # first; under residency by size an INS too, so that a
            # resident key's view has absorbed everything pending
            self._drain_key(key)
        if self.resident_min_leaves and self._is_resident(key):
            self._write_resident(key, op, path, value)
            return
        self._demote(key)
        if op in (b"SET", b"INS"):
            doc = self._data_for(key)
        else:
            doc = self._data.get(key)
        if doc is not None:
            self._mutate(doc, op, path, value, self._delta_for(key))
            self._note_size(key, doc)
        elif op == b"RM":
            # still validates the value like the reference (:107)
            from ..ops.ujson_host import parse_value

            parse_value(value)

    def _write_resident(self, key: bytes, op: bytes, path, value) -> None:
        """A local write on a resident key that stays resident: the
        mutator runs on the decoded view (what this node has observed:
        the row joined with every pending delta), with a delta of its
        own. That delta joins the flush delta — the peers get the same
        join the host shape would have accumulated — and queues for the
        row as a delta the view has already absorbed, to fold on the
        device with the key's foreign deltas. Nothing is re-encoded
        around the write, and the row is decoded only where the key has
        no view (its first touch, its next after a fold)."""
        doc = self._view(key)
        one = UJSON()
        self._mutate(doc, op, path, value, one)
        if not (one.entries or one.ctx.vv or one.ctx.cloud):
            return  # e.g. an RM of a value that is not there
        self._delta_for(key).converge(one)
        lst = self._pend.setdefault(key, [])
        lst.append(one)
        self._pend_total += 1
        self._res_applied[key] = len(lst)
        resolve_registry(self).tally("drain.UJSON.row_deltas", 1)
        if len(lst) >= ROW_FOLD_MIN:
            self._drain_key(key, fold=True)

    def _note_size(self, key: bytes, doc: UJSON) -> None:
        """Residency by size: a host-mode document that has reached
        ``resident_min_leaves`` is sized up at the next drain."""
        if 0 < self.resident_min_leaves <= len(doc.entries):
            self._grown.add(key)

    def _data_for(self, key: bytes) -> UJSON:
        d = self._data.get(key)
        if d is None:
            d = self._data[key] = UJSON()
        return d

    def _delta_for(self, key: bytes) -> UJSON:
        d = self._deltas.get(key)
        if d is None:
            d = self._deltas[key] = UJSON()
        return d

    def _path_and_value(self, args: list[bytes]):
        """key [path...] value — at least key and value (repo_ujson.pony:45-49)."""
        if len(args) < 3:
            raise ParseError()
        return args[1], _decode_path(args[2:-1]), args[-1].decode("utf-8", "replace")

    def _flush_queue(self) -> None:
        """Apply every write the native engine banked (in arrival order):
        INS, SET, RM and CLR, exactly the sequences their apply() branches
        run — observed-remove ops observe (drain) first. Runs before any
        other UJSON work so the queue is invisible to reads, flushes,
        drains and snapshots; the engine pre-validated each value token
        (engine.h ujson_prim_ok / ujson_doc_ok), so the applies cannot
        fail (the +OK replies are already on the wire)."""
        if self.engine is None or not self.engine.uq_count():
            return
        for args in self.engine.uq_drain():
            op = args[0]
            if op == b"CLR":
                key, path, value = args[1], _decode_path(args[2:]), None
            else:
                key, path, value = self._path_and_value(args)
            self._write(op, key, path, value)
            self._sync_dirty.add(key)

    def prepare_flush(self) -> None:
        """Manager hook (flush_async): drain the write queue in a worker
        thread before the loop-side delta flush — a queued write on a
        resident key demotes, which can decode (a blocking device pull)."""
        self._flush_queue()

    def apply(self, resp, args: list[bytes]) -> bool:
        self._flush_queue()
        op = need(args, 0)
        if op in (b"SET", b"CLR", b"INS", b"RM") and len(args) >= 2:
            self._sync_dirty.add(args[1])
            if self.engine is not None:
                # a write applied on THIS path (deferred by the engine, or
                # a direct apply) must drop the overlapping render memos,
                # exactly as a natively banked one does at bank time
                self.engine.uj_invalidate(
                    args[1],
                    args[2:] if op == b"CLR" else args[2:-1],
                    subtree=op in (b"SET", b"CLR"),
                )
        if op == b"GET":
            key = need(args, 1)
            self._drain_key(key)
            path = _decode_path(args[2:])
            doc = self._view(key)
            text = ""
            if doc is not None:
                reg = resolve_registry(self)
                seam = reg.seam("ujson.render")
                sorts = doc.sorts
                t0 = seam.begin()
                text = doc.render(path)
                seam.end(t0)
                if doc.sorts != sorts:
                    # the render read no kept token order: it sorted one
                    # (a view's first render of the path)
                    reg.tally("drain.UJSON.render_sorts", 1)
            resp.string(text)
            if self.engine is not None and doc is not None:
                body = text.encode()
                # memo repair (the TLOG base-repair shape): the next GET
                # of this (key, path) settles natively on these bytes.
                # Keys with no document never memoise — a read-only scan
                # over absent keys must not grow engine rows without
                # bound (rows are bounded by the written keyspace)
                self.engine.uj_memo_put(
                    key, args[2:], b"$%d\r\n%s\r\n" % (len(body), body)
                )
            return False
        if op == b"SET":
            key, path, value = self._path_and_value(args)
            try:
                self._write(op, key, path, value)
            except ValueError:
                raise ParseError() from None
            resp.ok()
            return True
        if op == b"CLR":
            key = need(args, 1)
            self._write(op, key, _decode_path(args[2:]), None)
            resp.ok()
            return True
        if op == b"INS":
            key, path, value = self._path_and_value(args)
            try:
                self._write(op, key, path, value)
            except ValueError:
                raise ParseError() from None
            resp.ok()
            return True
        if op == b"RM":
            key, path, value = self._path_and_value(args)
            try:
                self._write(op, key, path, value)
            except ValueError:
                raise ParseError() from None
            resp.ok()
            return True
        raise ParseError()

    def converge(self, key: bytes, delta: UJSON) -> None:
        lst = self._pend.setdefault(key, [])
        lst.append(delta)
        self._pend_total += 1
        self._sync_dirty.add(key)
        resolve_registry(self).tally("drain.UJSON.foreign_deltas", 1)
        if self.engine is not None:
            # a remote delta can change any subtree: drop every render
            # memo for the key (path () with subtree=True covers all)
            self.engine.uj_invalidate(key, (), subtree=True)
        if len(lst) >= DEVICE_FANIN_MIN:
            self._overdue = True

    def drain_overdue(self) -> bool:
        """Cluster converge path: the manager offloads a full drain to a
        worker thread when a key's fan-in reaches device size or the
        total buffered deltas hit the cap — a write-hot, never-read key
        stays bounded like every other type."""
        return self._overdue or self._pend_total >= PENDING_TOTAL_MAX

    # INS included: it never drains, but on a resident key it demotes —
    # which can decode (a blocking device pull) and must not run on the
    # event loop
    may_drain_OPS = (b"GET", b"SET", b"CLR", b"RM", b"INS")

    # banked native-queue commands above which even a host-only flush
    # offloads to a thread (a bounded event-loop stall beats none)
    UQ_INLINE_MAX = 1024

    def may_drain(self, args: list[bytes]) -> bool:
        """Commands that will touch the device get offloaded to a thread
        (manager.apply_async): a device-sized pending fan-in, a resident
        key whose pending exceeds the trickle budget (the drain folds on
        device), or a resident read/demotion that must decode (cache
        miss). A trickle on a warm cache stays on the loop — the drain
        serves it host-side in microseconds. A non-empty native write
        queue offloads only when its flush can actually touch the device
        (a resident store exists, a fan-in reached device size, or the
        queue is large): a small host-only flush runs inline, so the one
        deferred command that flushes it never opens a lock window that
        routes every OTHER connection's burst off the native path
        (server/server.py read-loop busy check — the round-5 shape
        threaded every flush and turned each UJSON defer into a
        whole-node demotion storm under concurrency)."""
        if self.engine is not None and self.engine.uq_count():
            if (
                self._may_wait()
                or self._overdue
                or self._pend_total >= PENDING_TOTAL_MAX
                or self.engine.uq_count() > self.UQ_INLINE_MAX
            ):
                return True
            # host-only flush: fall through to this command's own checks
        if len(args) < 2 or args[0] not in self.may_drain_OPS:
            return False
        key = args[1]
        if len(self._pend.get(key, ())) >= DEVICE_FANIN_MIN:
            return True
        if self._is_resident(key):
            return self._unabsorbed(key) > TRICKLE_MAX or (
                key not in self._res_cache and self._may_wait()
            )
        return False

    def _may_wait(self) -> bool:
        """Can work on a resident key with no decoded view WAIT for the
        device? Under the fan-in rule any such touch may (a decode, a
        demotion): a thread's business, as ever. Under residency by size
        a missing view is a common case (the boot leaves none, a fold
        drops its keys') and costs one gather of one row, which waits
        only while a fold is in flight: then it goes to a thread, and
        else it runs on the loop — a thread hop for every banked flush
        makes the UJSON lock a convoy (my chip run, PR 39: held 47% of
        the wall, 96% of bursts slept, `ops_per_s` halved)."""
        if self._res is None:
            return False
        return not self.resident_min_leaves or self._res.busy()

    def _unabsorbed(self, key: bytes) -> int:
        """Pending deltas of a resident key that a drain would have to
        deal with now. Under the fan-in rule every pending delta (the
        trickle budget is on the list's length, as before); under
        residency by size only those the decoded view has not absorbed:
        a local write's row delta is absorbed as it is made, and a list
        of absorbed deltas can wait for the next full drain's fold."""
        n = len(self._pend.get(key, ()))
        if self.resident_min_leaves:
            n -= self._res_applied.get(key, 0)
        return n

    def _drain_key(self, key: bytes, fold: bool = False) -> None:
        """Deal with one key's pending deltas; ``fold`` sends a resident
        key's list to the device whatever its length."""
        deltas = self._pend.get(key)
        if not deltas:
            return
        if self._is_resident(key):
            if not fold and self._unabsorbed(key) <= TRICKLE_MAX:
                # read-path trickle: converge into the cached view on the
                # host (idempotent join — the deltas stay pending for the
                # next full drain's device fold); _res_applied tracks how
                # many this cache already absorbed, so repeat reads don't
                # re-walk the doc per pending delta
                doc = self._view(key)
                self._host_fold(doc, deltas[self._res_applied.get(key, 0):])
                self._res_applied[key] = len(deltas)
                return
            self._pend.pop(key)
            self._pend_total -= len(deltas)
            rest = self._resident_fold({key: deltas})
            if not rest:
                return
            deltas = rest[key]
        elif len(deltas) >= DEVICE_FANIN_MIN and key not in self._host_only:
            self._pend.pop(key)
            self._pend_total -= len(deltas)
            rest = self._resident_fold({key: deltas})
            if not rest:
                return
            deltas = rest[key]
        else:
            self._pend.pop(key)
            self._pend_total -= len(deltas)
        doc = self._data_for(key)
        self._host_fold(doc, deltas)
        self._note_size(key, doc)

    def _resident_fold(self, groups: dict[bytes, list[UJSON]]):
        """Promote keys as needed and fold their pending deltas into the
        resident rows — ONE device dispatch for every key in the drain.
        Returns the groups that must fall back to the host loop (seqs
        beyond the u64/32 device layouts). The one UJSON path that
        dispatches to the device, so it is what `UJSON drains` counts
        (keys = keys folded; host-loop folds are not drains) and what
        drain.UJSON and its three phases time: assemble is admission and
        the delta encode, device the dispatch (nothing waits for its
        result), finish the views' bookkeeping."""
        self._fold_keys = len(groups)
        return self._fold_resident(groups)

    def _admit(self, store, items: list[tuple[bytes, UJSON]]) -> list[bytes]:
        """Make host docs resident as they are, each kept as its row's
        decoded view (row state == this doc). Returns the keys no device
        layout can hold (seqs past u32): host-only from here on."""
        refused: list[bytes] = []
        try:
            store.admit(items)
        except OverflowError:
            # isolate the un-encodable docs; the rest still promote
            items, bulk = [], items
            for k, d in bulk:
                try:
                    store.admit([(k, d)])
                except OverflowError:
                    self._host_only.add(k)
                    refused.append(k)
                    continue
                items.append((k, d))
        for k, d in items:
            self._data.pop(k, None)
            self._res_cache[k] = d  # row state == this doc, cache it
        reg = resolve_registry(self)
        reg.tally("drain.UJSON.admits", len(items))
        reg.tally("drain.UJSON.resident_rows", len(items))
        reg.tally(
            "drain.UJSON.readmits", sum(k in self._demoted for k, _ in items)
        )
        reg.tally("drain.UJSON.demote_overflow", len(refused))
        return refused

    def _admit_sized(self) -> None:
        """Residency by size: host-mode documents that have reached
        ``resident_min_leaves`` (restored at that size, or grown to it)
        move to the store in one admission."""
        grown, self._grown = self._grown, set()
        items = [
            (k, d)
            for k in sorted(grown)
            if (d := self._data.get(k)) is not None
            and len(d.entries) >= self.resident_min_leaves
            and k not in self._host_only
            and k not in self._pend
        ]
        if not items:
            return
        store = self._store()
        if store.full():
            # HBM admission gate (ResidentStore.BYTE_BUDGET)
            resolve_registry(self).tally("drain.UJSON.demote_budget", len(items))
            return
        self._admit(store, items)

    @timed_drain("UJSON", lambda self: self._fold_keys)
    def _fold_resident(self, groups: dict[bytes, list[UJSON]]):
        store = self._store()
        reg = resolve_registry(self)
        fallback: dict[bytes, list[UJSON]] = {}

        to_admit = [k for k in groups if k not in store]
        if to_admit and store.full():
            # HBM admission gate (ResidentStore.BYTE_BUDGET): further
            # keys serve from the host lattice; resident keys keep their
            # rows
            for k in to_admit:
                fallback[k] = groups[k]
            reg.tally("drain.UJSON.demote_budget", len(to_admit))
            to_admit = []
        if to_admit:
            items = [(k, self._data.get(k) or UJSON()) for k in to_admit]
            for k in self._admit(store, items):
                fallback[k] = groups[k]

        fold = {k: v for k, v in groups.items() if k not in fallback}
        wide = [k for k, v in fold.items() if not all(map(store.fits, v))]
        if wide:
            # a delta too wide for the store's pinned grid (a peer's
            # flush that coalesced many writes, a SET of a document): its
            # row is rewritten from the decoded view, which absorbs the
            # key's whole list first — no fold in a shape nobody compiled
            items = []
            for k in wide:
                lst = fold.pop(k)
                doc = self._view(k)
                done = self._res_applied.pop(k, 0)
                self._host_fold(doc, lst[done:])
                items.append((k, doc))
            try:
                store.rewrite(items)
            except OverflowError:
                for k, doc in items:
                    self._demote(k, overflow=True)
                    self._host_only.add(k)
            reg.tally("drain.UJSON.row_rewrites", len(items))
        mark = lambda: drain_phase(self, DEVICE)  # noqa: E731
        try:
            store.fold_in(fold, mark)
        except OverflowError:
            for k, v in fold.items():
                try:
                    store.fold_in({k: v}, mark)
                except OverflowError:
                    self._demote(k, overflow=True)
                    self._host_only.add(k)
                    fallback[k] = v
                else:
                    self._folded(k, v)
        else:
            drain_phase(self, FINISH)
            for k, v in fold.items():
                self._folded(k, v)
        return fallback

    def _folded(self, key: bytes, deltas: list[UJSON]) -> None:
        """A key's pending list has folded into its row, and its decoded
        view drops, whatever it had absorbed: the next read or write of
        the key gathers and decodes what the device folded, so the row is
        what every answer after a fold depends on. Deltas the view had
        not absorbed reach the document by this fold alone:
        `device_deltas`."""
        applied = self._res_applied.pop(key, 0)
        self._res_cache.pop(key, None)
        resolve_registry(self).tally(
            "drain.UJSON.device_deltas", len(deltas) - applied
        )

    # -- sync digest (cluster/syncdigest.py) ---------------------------------

    def sync_prepare(self) -> None:
        """Fold all pending deltas in ONE device/host pass before the
        canon reads (a per-key fold would dispatch per dirty key)."""
        self._flush_queue()
        self.drain()

    def sync_dirty_keys(self) -> list[bytes]:
        out = list(self._sync_dirty)
        self._sync_dirty.clear()
        return out

    def sync_canon(self, key: bytes) -> bytes | None:
        """Canonical per-key state: the doc's dot-store + causal context
        with every unordered container sorted, so converged replicas
        (whose dict/set iteration orders differ) hash identically."""
        doc = self._view(key)
        if doc is None or not (doc.entries or doc.ctx.vv or doc.ctx.cloud):
            return None
        ents = sorted(
            (dot, path, token) for dot, (path, token) in doc.entries.items()
        )
        return repr(
            (ents, sorted(doc.ctx.vv.items()), sorted(doc.ctx.cloud))
        ).encode()

    # -- snapshot (persist.py): full state in the wire-delta shape ----------

    def dump_state(self):
        self._flush_queue()
        self.drain()
        docs = dict(self._data)
        if self._res is not None:
            docs.update(self._res.dump())
        # keep docs whose causal context is non-trivial even when empty of
        # entries: the tombstone knowledge is what makes removals stick
        return [
            (key, doc)
            for key, doc in sorted(docs.items())
            if doc.entries or doc.ctx.vv or doc.ctx.cloud
        ]

    def load_state(self, batch) -> None:
        for key, delta in batch:
            self.converge(key, delta)

    def deltas_size(self) -> int:
        # the banked queue is NOT drained here: this runs on the event
        # loop (proactive flush), and a queued write on a resident key
        # demotes with a blocking device decode. prepare_flush (threaded,
        # manager.flush_async / clean_shutdown) drains it; deltas from
        # still-banked writes simply ship on the next heartbeat flush.
        return len(self._deltas)

    def flush_deltas(self):
        out = sorted(self._deltas.items())
        self._deltas.clear()
        return out

    def drain(self) -> None:
        self._flush_queue()
        # device pass first: every resident key with pending, plus every
        # key whose fan-in earns a slice of a shared launch, folds in ONE
        # dispatch; what remains (small fan-ins on host-mode keys, or
        # everything on layout overflow) host-loops
        groups = {
            k: lst
            for k, lst in self._pend.items()
            if k not in self._host_only
            and (self._is_resident(k) or len(lst) >= SEG_FANIN_MIN)
        }
        # SEG_FANIN_MIN only pays when the dispatch is SHARED: a lone
        # non-resident key below the single-dispatch crossover stays on
        # the host loop
        if len(groups) == 1:
            k = next(iter(groups))
            if not self._is_resident(k) and len(groups[k]) < DEVICE_FANIN_MIN:
                groups = {}
        if groups:
            for k in groups:
                self._pend.pop(k)
            self._pend_total -= sum(len(v) for v in groups.values())
            fallback = self._resident_fold(groups)
            for k, lst in fallback.items():
                self._host_fold(self._data_for(k), lst)
        for key in list(self._pend):
            self._drain_key(key)
        self._overdue = False
        if self._grown:
            self._admit_sized()

    def warm_drain_shapes(self) -> None:
        """Boot, after recovery (Database.warm_drain_shapes). Residency
        by size only: fold what was restored into host documents, admit
        those at the size in ONE admission, size the planes from them
        with room and compile the fold and gather programs at the pinned
        shapes (`ResidentStore.pin_shapes`), so a serving window that
        stays inside the room compiles nothing. A write that grows
        another document to the size later admits it with one row's
        `place_rows` (and, past the row capacity, a `grow_capacity` and
        the fold programs again): compiles a window would see."""
        if not self.resident_min_leaves:
            return
        self.drain()
        if self._res is not None:
            self._res.pin_shapes()
            self._res.warm_pinned()
            # the admission's views go: a document's first read or write
            # gathers and decodes its row, so every answer this node
            # gives stands on what the device holds, and the host does
            # not start out with the whole keyspace decoded beside it
            self._res_cache.clear()
