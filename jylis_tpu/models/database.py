"""Database: keyspace router over the per-type repos.

Reference analog: database.pony:5-65 — routes cmd[0] to the matching repo
manager (case sensitive), renders the data-type help for unknown first
words, fans flush/converge to the repos, and joins shutdown.
"""

from __future__ import annotations

import asyncio
import hashlib
from contextlib import AsyncExitStack, asynccontextmanager

import jax

from .. import sessions as sessions_mod
from .help import DATATYPE_HELP, respond_help

SESSION_HELP = """\
The following are valid SESSION commands (docs/sessions.md):
  SESSION TOKEN                 - mint this node's session token
  SESSION WRAP <command...>     - apply a command, reply [reply, token]
  SESSION READ <token> <command...> - serve once the token is covered
                                  (bounded wait, then a STALE error),
                                  reply [token', reply]"""

# keyspace-range fanout for the anti-entropy digest tree (schema v8):
# every key lands in one of 256 stable buckets by the first byte of
# sha256(key) — a function of the KEY alone, so converged replicas
# bucket identically regardless of write order or backend. 256 leaves
# of 32 bytes each keep a whole-tree frame ~8 KB sparse-encoded, small
# enough to ship instead of a keyspace dump whenever root digests
# mismatch.
SYNC_FANOUT = 256


def sync_bucket(key: bytes) -> int:
    """The digest-tree leaf a key belongs to (stable across replicas)."""
    return hashlib.sha256(key).digest()[0]
from . import repo_system
from .base import ParseError
from .manager import RepoManager
from .repo_bcount import RepoBCOUNT
from .repo_counters import RepoGCOUNT, RepoPNCOUNT
from .repo_map import RepoMAP
from .repo_system import RepoSYSTEM
from .repo_tensor import RepoTENSOR
from .repo_treg import RepoTREG
from .repo_tlog import RepoTLOG
from .repo_ujson import RepoUJSON

# THE data-type registry: every serving repo class, in the one fixed
# order every replica shares (it is the SyncRequest digest-vector order
# and the snapshot frame order). SYSTEM rides separately. Everything
# that enumerates types — DATA_TYPES, the digest trees, SYSTEM DIGEST
# TYPES, smoke3's per-type gate — derives from THIS tuple, so a new
# type cannot silently fall out of a digest-match gate. New entries
# append (the digest vector is positional across the wire).
DATA_REPO_CLASSES = (
    RepoTREG,
    RepoTLOG,
    RepoGCOUNT,
    RepoPNCOUNT,
    RepoUJSON,
    RepoTENSOR,
    RepoMAP,
    RepoBCOUNT,
)

DATA_TYPE_NAMES = tuple(cls.name for cls in DATA_REPO_CLASSES)


class Database:
    def __init__(
        self,
        identity: int,
        system_repo: RepoSYSTEM | None = None,
        engine: str = "auto",
    ):
        from ..native.engine import resolve_engine
        from ..obs.registry import MetricsRegistry

        # THIS instance's whole observability surface (obs/registry.py):
        # drain/journal/serving counters, latency histograms, gauges,
        # trace ring. Passed down explicitly to every component that
        # times or traces (repos, Server, Journal, Cluster) — the old
        # process-global dicts in utils/metrics.py cross-talked between
        # Databases in one process, which this retires.
        self.metrics = MetricsRegistry()
        # session guarantees (sessions.py): the node's applied-interval
        # vector + waiter queue, fed by the cluster engine and served by
        # the SESSION command family below. session_wait_ms is the
        # bounded-wait knob (--session-wait-ms); admission_cap the
        # per-command-class inflight cap (--admission-cap, 0 = off),
        # pushed onto every manager by set_admission_cap.
        self.sessions = sessions_mod.SessionIndex()
        self.session_wait_ms = sessions_mod.SESSION_WAIT_MS_DEFAULT
        self.system = system_repo if system_repo is not None else RepoSYSTEM(identity)
        # ONE native engine shared by every data repo AND the server's
        # batch applier (server/server.py): single source of host truth.
        # engine="python" pins the pure-Python table backends everywhere
        # (differential tests compare the two whole stacks).
        self.native_engine = resolve_engine(engine)
        if self.native_engine is not None:
            self.native_engine.bind_metrics(self.metrics)
        self._map: dict[bytes, RepoManager] = {}
        # SYSTEM METRICS' "cmds" lines: THIS instance's Python-path
        # tally merged with THIS instance's engine counters — wired
        # per-Database (a global registry would cross-talk between
        # Database instances in tests)
        self._served_py: dict[str, int] = {}
        self.system.served_fn = self._served_totals
        self.system.serving_fn = self.serving_totals
        for repo in tuple(
            cls(identity, engine=self.native_engine)
            for cls in DATA_REPO_CLASSES
        ) + (self.system,):
            # timed_drain resolves the registry through this attribute,
            # so drain counters/histograms land per-Database
            repo.metrics = self.metrics
            mgr = RepoManager(
                repo.name, repo, repo.help, served=self._served_py,
                registry=self.metrics,  # BUSY refusals, the lock/flush spans
            )
            self._map[repo.name.encode()] = mgr

        # incremental sync digest (round-5 verdict item 2): per data type,
        # a map of key -> sha256(canonical per-key state) and the running
        # XOR of those hashes. Updating costs O(keys dirty since the last
        # pass) — a reconnect never dumps the keyspace to compute 32 bytes.
        # Derived from the registry, never hand-listed: a new repo class
        # lands in every digest surface automatically.
        self.DATA_TYPES = DATA_TYPE_NAMES
        self._sync_hash: dict[str, dict[bytes, bytes]] = {
            n: {} for n in self.DATA_TYPES
        }
        self._sync_xor: dict[str, bytes] = {
            n: bytes(32) for n in self.DATA_TYPES
        }
        # the keyspace-range digest tree (schema v8 Merkle-range repair):
        # per type, SYNC_FANOUT leaf accumulators — leaf b is the XOR of
        # the per-key hashes of every key whose sync_bucket is b, so the
        # XOR of all leaves IS _sync_xor and both update in the same
        # O(dirty) incremental fold. A sync responder whose root
        # mismatches ships these 256 x 32 bytes instead of the keyspace,
        # and the requester pulls only divergent buckets.
        self._sync_leaf: dict[str, list[int]] = {
            n: [0] * SYNC_FANOUT for n in self.DATA_TYPES
        }
        # bucket -> live keys, maintained by the same O(dirty) fold: the
        # range-serve path (dump_range_async) filters by membership here
        # instead of re-hashing every key in the keyspace per round — a
        # multi-round heal costs one sha256 per DIRTY key, not one per
        # key per round. References only (the keys already live in
        # _sync_hash), so the memory cost is pointer-sized.
        self._sync_bkeys: dict[str, list[set]] = {
            n: [set() for _ in range(SYNC_FANOUT)] for n in self.DATA_TYPES
        }
        # SYSTEM DIGEST (the drill matrix's convergence probe, exposed
        # to any Redis client): the async serving path computes it
        # under the repo locks (apply_async intercept below); the sync
        # single-threaded path goes through this hook on RepoSYSTEM
        self.system.digest_fn = self._sync_digest_blocking
        # SYSTEM DIGEST TYPES (the operator's divergence localizer):
        # per-type digest lines so an operator can name the diverged
        # TYPE before walking its ranges; same two-path wiring as the
        # combined digest
        self.system.digest_types_fn = self._sync_digest_types_blocking
        # SYSTEM METRICS' SESSION section (token/read/refusal counters)
        self.system.session_fn = self.sessions.metrics_totals
        # overload armor (admission.py): node-wide per-class admission,
        # consulted by the Server at every Python-path dispatch. The
        # default controller is unarmed (no policy, no byte bound) —
        # set_admission replaces it with the configured one and keeps
        # the OVERLOAD section of SYSTEM METRICS pointed at it.
        self.set_admission("", 0)

    def _served_totals(self) -> dict[str, int]:
        """Commands served per type on BOTH paths (SYSTEM METRICS)."""
        totals = dict(self._served_py)
        if self.native_engine is not None:
            for name, n in self.native_engine.served_counts().items():
                if n:
                    totals[name] = totals.get(name, 0) + n
        return totals

    def serving_totals(self) -> dict[str, int]:
        """The native-vs-demoted serving split (SYSTEM METRICS SERVING
        lines): commands the
        engine settled in C++ vs commands that went through the Python
        dispatch path (engine defers, demoted connections, and direct
        applies), plus the registry's exact serving counters
        (obs.SERVING: demotion events, refusals, the Python path's
        commands by cause, the engine's reply bytes)."""
        native = 0
        if self.native_engine is not None:
            native = sum(self.native_engine.served_counts().values())
        return {
            "native_cmds": native,
            "demoted_cmds": sum(self._served_py.values()),
            **self.metrics.serving_counters,
        }

    def _sync_update_repo(self, name: str, repo) -> None:
        """Fold the repo's dirty keys into its digest accumulator (worker
        thread, repo lock held by the caller)."""
        prep = getattr(repo, "sync_prepare", None)
        if prep is not None:
            prep()
        dirty = repo.sync_dirty_keys()
        if not dirty:
            return
        hmap = self._sync_hash[name]
        leaves = self._sync_leaf[name]
        bkeys = self._sync_bkeys[name]
        x = int.from_bytes(self._sync_xor[name], "big")
        tag = name.encode()
        for key in dirty:
            bucket = sync_bucket(key)
            old = hmap.pop(key, None)
            if old is not None:
                o = int.from_bytes(old, "big")
                x ^= o
                leaves[bucket] ^= o
            canon = repo.sync_canon(key)
            if canon is not None:
                h = hashlib.sha256(
                    tag + b"\x00" + len(key).to_bytes(4, "big") + key + canon
                ).digest()
                hmap[key] = h
                hi = int.from_bytes(h, "big")
                x ^= hi
                leaves[bucket] ^= hi
                bkeys[bucket].add(key)
            else:
                bkeys[bucket].discard(key)
        self._sync_xor[name] = x.to_bytes(32, "big")

    async def sync_type_digests_async(self) -> tuple[bytes, ...]:
        """One 32-byte digest PER data type (DATA_TYPES order) — converged
        peers (any op order, any backend) produce equal bytes per type, so
        a sync responder streams only the types that actually differ.
        Cost is O(keys written since the last call): each repo folds only
        its dirty keys, under its own lock, in a worker thread."""
        for name in self.DATA_TYPES:
            mgr = self._map[name.encode()]
            async with mgr.hold_sync():
                await asyncio.to_thread(self._sync_update_repo, name, mgr.repo)
        return tuple(self._sync_xor[n] for n in self.DATA_TYPES)

    async def sync_digest_async(self) -> bytes:
        """The combined 32-byte digest over every data type."""
        return hashlib.sha256(
            b"".join(await self.sync_type_digests_async())
        ).digest()

    async def sync_tree_async(self, name: str) -> tuple:
        """One type's keyspace-range digest tree as SPARSE leaves:
        ((bucket, 32-byte digest), ...) for the non-empty buckets only —
        the MsgDigestTree payload. Folds the type's dirty keys first
        (same O(dirty) incremental cost as the root digest)."""
        mgr = self._map[name.encode()]
        async with mgr.hold_sync():
            await asyncio.to_thread(self._sync_update_repo, name, mgr.repo)
        return tuple(
            (i, v.to_bytes(32, "big"))
            for i, v in enumerate(self._sync_leaf[name])
            if v
        )

    async def dump_range_async(self, name: str, buckets) -> list:
        """One type's state RESTRICTED to the given digest-tree buckets,
        in the wire-delta shape: the MsgRangeRequest serve path. Dump +
        filter run in a worker thread under the repo lock, so a range
        serve stalls only its own type and only briefly — and the bytes
        it produces scale with the requested buckets, not the keyspace.
        Key selection goes through the bucket index (folded current
        first, O(dirty)), so a multi-round heal never re-hashes the
        keyspace per round."""
        mgr = self._map[name.encode()]

        def dump_filtered():
            self._sync_update_repo(name, mgr.repo)
            bkeys = self._sync_bkeys[name]
            wanted = set()
            for b in buckets:
                if 0 <= b < len(bkeys):
                    wanted |= bkeys[b]
            return [
                (key, delta)
                for key, delta in mgr.repo.dump_state()
                if key in wanted
            ]

        async with mgr.hold_sync():
            return await asyncio.to_thread(dump_filtered)

    def _sync_digest_blocking(self) -> bytes:
        """The combined digest for SINGLE-THREADED callers (warmup,
        direct drives, tests): same bytes as sync_digest_async, no
        locks — the serving path never reaches this (apply_async
        intercepts SYSTEM DIGEST before repo dispatch)."""
        for name in self.DATA_TYPES:
            self._sync_update_repo(name, self._map[name.encode()].repo)
        return hashlib.sha256(
            b"".join(self._sync_xor[n] for n in self.DATA_TYPES)
        ).digest()

    def _sync_digest_types_blocking(self) -> list[tuple[str, bytes]]:
        """Per-type digests for SINGLE-THREADED callers — the sync-path
        SYSTEM DIGEST TYPES (the serving path intercepts in apply_async,
        which awaits the repo locks)."""
        for name in self.DATA_TYPES:
            self._sync_update_repo(name, self._map[name.encode()].repo)
        return [(n, self._sync_xor[n]) for n in self.DATA_TYPES]

    def set_admission(self, policy: str, queue_bytes: int) -> None:
        """Arm the node-wide overload armor (--admission-policy /
        --admission-queue-bytes, admission.py): per-class priority
        shedding under the declared OVERLOAD state plus the hard
        queued-bytes bound. Replaces the unarmed default controller."""
        from ..admission import AdmissionController

        self.admission = AdmissionController(
            policy, queue_bytes, registry=self.metrics
        )
        if self.native_engine is not None:
            # the byte bound counts what the reply sender holds too
            self.admission.held_elsewhere = self.native_engine.sender_pending
        self.system.overload_fn = self.admission.metrics_totals

    def set_admission_cap(self, cap: int) -> None:
        """Per-command-class admission control (--admission-cap): each
        data-type manager refuses lock-queued commands past ``cap``
        in flight with a typed BUSY error, so one hot key's drain
        backlog degrades ITS command class, never the node. 0 = off."""
        for mgr in self._map.values():
            mgr.admission_cap = cap

    def set_ujson_resident_min(self, leaves: int) -> None:
        """UJSON residency by size (--ujson-resident-min-leaves): a
        document of ``leaves`` or more leaves lives in the device-
        resident store from restore on and takes local writes as row
        deltas (models/repo_ujson.py). 0 = promotion by fan-in only."""
        self._map[b"UJSON"].repo.resident_min_leaves = leaves

    # ---- session guarantees (sessions.py, docs/sessions.md) ---------------

    async def _mint_token(self) -> bytes:
        """Force the pending local deltas through the cluster flush
        path (so every prior write on this connection is sequenced and
        the vector's own entry covers it), then encode the vector."""
        if self.sessions.flush_fn is not None:
            await self.sessions.flush_fn()
        self.sessions.stats["tokens_minted"] += 1
        return self.sessions.token_bytes()

    async def _apply_session(self, resp, cmd: list[bytes]) -> None:
        sess = self.sessions
        op = cmd[1] if len(cmd) > 1 else b""
        if op == b"TOKEN" and len(cmd) == 2:
            resp.string(await self._mint_token())
            return
        if op == b"WRAP" and len(cmd) > 2 and cmd[2] != b"SESSION":
            # the write reply carries the session token: one reply
            # array of [inner reply, token], the token minted AFTER the
            # inner command applied and flushed — read-your-writes
            # portable from this ack onward
            resp.array_start(2)
            await self.apply_async(resp, cmd[2:])
            resp.string(await self._mint_token())
            return
        if op == b"READ" and len(cmd) > 3 and cmd[3] != b"SESSION":
            try:
                token = sessions_mod.decode_token_memo(bytes(cmd[2]))
            except sessions_mod.SessionError as e:
                sess.stats["badtoken_refusals"] += 1
                resp.err(f"BADTOKEN (unusable session token: {e})")
                return
            if not await sess.wait_dominated(token, self.session_wait_ms):
                sess.stats["stale_refusals"] += 1
                resp.err(
                    "STALE (session token not covered within "
                    f"{self.session_wait_ms}ms; retry here later or "
                    "read where you wrote)"
                )
                return
            sess.stats["reads_served"] += 1
            # monotonic reads: the reply token is the join of what the
            # client presented and what this replica has verified — and
            # a SERVED read's vector dominates the token, so the join
            # IS the vector (memoised bytes, not a fresh encode)
            resp.array_start(2)
            resp.string(sess.token_bytes())
            await self.apply_async(resp, cmd[3:])
            return
        respond_help(resp, SESSION_HELP)

    def set_journal(self, journal) -> None:
        """Attach the delta write-ahead journal (journal/): every repo's
        flushed delta batches append to it before reaching the network
        sink (manager._emit). Pass None to detach. Attaching also arms
        the JOURNAL section of SYSTEM METRICS (explicit zeros from
        boot); the journal's own registry is whatever it was constructed
        with — main.py passes this Database's."""
        for mgr in self._map.values():
            mgr.journal = journal
        self.metrics.journal_enabled = journal is not None

    def manager(self, name: str) -> RepoManager:
        return self._map[name.encode()]

    def managers(self):
        return self._map.values()

    def apply(self, resp, cmd: list[bytes]) -> None:
        mgr = self._map.get(cmd[0]) if cmd else None
        if mgr is None:
            respond_help(resp, DATATYPE_HELP)
            return
        mgr.apply(resp, cmd)

    async def apply_async(self, resp, cmd: list[bytes]) -> None:
        """Serving path: per-repo locking + threaded drains (manager.py)."""
        if cmd and cmd[0] == b"SESSION":
            # session-guarantee surface (sessions.py): python-path only
            # — the native engine defers unknown first words, so a
            # session command rides the same per-repo async machinery
            # its inner command needs anyway
            await self._apply_session(resp, cmd)
            return
        if (
            len(cmd) == 3
            and cmd[0] == b"SYSTEM"
            and cmd[1] == b"DIGEST"
            and cmd[2] == b"TYPES"
        ):
            # the per-type breakdown of the digest below: one
            # "<TYPE> <hex>" line per data type, so an operator (or
            # scripts/smoke3.py's gate) can localize a divergence to a
            # type before walking its ranges
            digests = await self.sync_type_digests_async()
            resp.array_start(len(self.DATA_TYPES))
            for name, digest in zip(self.DATA_TYPES, digests):
                resp.string(f"{name} {digest.hex()}".encode())
            return
        if len(cmd) == 2 and cmd[0] == b"SYSTEM" and cmd[1] == b"DIGEST":
            # served here (not in RepoSYSTEM.apply, which is sync):
            # the digest takes every DATA repo's lock in turn, which
            # only the async path can await. The hex of the combined
            # per-type digest — equal bytes on converged replicas, so
            # "are these nodes converged?" is answerable
            # from any Redis client.
            digest = await self.sync_digest_async()
            resp.string(digest.hex().encode())
            return
        if len(cmd) > 1 and cmd[0] == b"SYSTEM" and cmd[1] == b"PROFILE":
            # the device-trace window (obs/span.py): starting and, more
            # so, stopping the profiler blocks for as long as the trace
            # takes to collect and write — a worker thread's business
            try:
                seconds = repo_system.parse_profile(cmd[1:])
            except ParseError:
                respond_help(resp, self.system.help.render(cmd[1:]))
                return
            repo_system.reply_profile(
                resp, await asyncio.to_thread(repo_system.run_profile, seconds)
            )
            return
        mgr = self._map.get(cmd[0]) if cmd else None
        if mgr is None:
            respond_help(resp, DATATYPE_HELP)
            return
        await mgr.apply_async(resp, cmd)

    async def converge_async(self, deltas) -> None:
        name, batch = deltas
        mgr = self._map.get(name.encode() if isinstance(name, str) else name)
        if mgr is not None:
            await mgr.converge_async(batch)

    async def flush_deltas_async(self, fn) -> None:
        for mgr in self._map.values():
            await mgr.flush_async(fn)

    def flush_deltas(self, fn) -> None:
        # jlint: order-ok — _map is built in the fixed constructor order,
        # identical on every replica; flush order is deterministic
        for mgr in self._map.values():
            mgr.flush_deltas(fn)

    def converge_deltas(self, deltas) -> None:
        """deltas: (type-name: str, [(key: bytes, delta), ...])."""
        name, batch = deltas
        mgr = self._map.get(name.encode() if isinstance(name, str) else name)
        if mgr is not None:
            mgr.converge_deltas(batch)

    def drain_all(self) -> None:
        for mgr in self._map.values():
            mgr.repo.drain()

    def warm_drain_shapes(self) -> None:
        """Boot, after recovery: repos whose serving-time drain shapes
        depend on the recovered capacity compile them now, not under
        the repo lock with clients waiting (single-threaded caller)."""
        for mgr in self._map.values():
            warm = getattr(mgr.repo, "warm_drain_shapes", None)
            if warm is not None:
                warm()

    async def dump_state_async(self, names=None):
        """Full state per type for the cluster sync path: [(name, batch)].
        Each repo dumps under its own lock with device touches in a
        worker thread, so serving stalls only per-type and briefly —
        unlike the shutdown snapshot, no cross-repo atomicity is needed
        (the receiver's lattice join absorbs any in-between writes).
        ``names`` restricts the dump (the sync digest covers data types
        only; SYSTEM streams separately)."""
        out = []
        for mgr in self._map.values():
            if names is not None and mgr.name not in names:
                continue
            async with mgr.hold_sync():
                batch = await asyncio.to_thread(mgr.repo.dump_state)
            out.append((mgr.name, batch))
        return out

    def device_layout(self) -> list[tuple[str, tuple, int]]:
        """(type, widest plane's shape, devices every plane is split
        over) per repo that keeps plane state on the device — the
        shutdown log's `device state` line (main.py)."""
        out = []
        for mgr in self._map.values():
            # a counter keyspace is one plane, the others a tuple of them
            planes = jax.tree_util.tree_leaves(getattr(mgr.repo, "_state", None))
            if planes:
                widest = max(planes, key=lambda p: p.size)
                spread = min(len(p.sharding.device_set) for p in planes)
                out.append((mgr.name, tuple(widest.shape), spread))
        return out

    def clean_shutdown(self) -> None:
        """Single-threaded shutdown (tests / direct drivers); the serving
        stack uses clean_shutdown_async, which serialises with in-flight
        threaded drains."""
        for mgr in self._map.values():
            mgr.clean_shutdown()

    def stop_intake(self) -> None:
        """Reject new commands immediately (safe from a signal callback)."""
        for mgr in self._map.values():
            mgr._shutdown = True

    async def clean_shutdown_async(self) -> None:
        for mgr in self._map.values():
            await mgr.clean_shutdown_async()

    @asynccontextmanager
    async def all_locks(self):
        """Async context holding every repo lock (fixed order): the
        shutdown snapshot dumps under it so nothing mutates mid-dump.
        A native burst takes only the locks its commands name, and is
        shut out all the same: its set is a subset of these (RepoLock,
        "Why a holder of EVERY lock still excludes every burst")."""
        async with AsyncExitStack() as stack:
            for mgr in self._map.values():
                await stack.enter_async_context(mgr.hold_sync())
            yield


class _NullRespond:
    """Discards replies; lets warmup drive the real command paths."""

    def __getattr__(self, name):
        return lambda *a, **k: None


def warmup() -> None:
    """Pre-compile every serving-path device kernel at the default bucket
    shapes by driving a throwaway Database through one command of each
    kind. Without this, the FIRST client read after a write blocks the
    event loop for the XLA compile (seconds per kernel when the persistent
    compile cache, jylis_tpu/__init__.py, is cold) — long enough
    for peers to hit the 10-tick idle eviction and drop our connections,
    opening fire-and-forget delta-loss windows. jit caches are per-process,
    so the throwaway instance warms the real repos' kernels."""
    db = Database(identity=0)
    resp = _NullRespond()
    for line in (
        b"GCOUNT INC k 1",
        b"GCOUNT GET k",
        b"PNCOUNT INC k 1",
        b"PNCOUNT DEC k 1",
        b"PNCOUNT GET k",
        b"TREG SET k v 1",
        b"TREG GET k",
        b"TLOG INS k v 2",
        b"TLOG GET k",
        b"TLOG SIZE k",
        b"TLOG TRIM k 1",
        b"TLOG GET k",
        b"UJSON SET k a 1",
        b"UJSON GET k a",
        # the f32 payload (1.0f LE) is space-free, so the split survives
        b"TENSOR SET k MAX 1 \x00\x00\x80?",
        b"TENSOR GET k",
        b"MAP TREG SET k f v 1",
        b"MAP TREG GETALL k",
    ):
        db.apply(resp, line.split(b" "))
    # counter GETs after purely-local INCs serve from the host cache and
    # never touch the device; a foreign delta forces the drain kernels
    # (_drain_g/_drain_pn) through their XLA compile here, not mid-serving
    db.manager("GCOUNT").repo.converge(b"k", {7: 1})
    db.apply(resp, [b"GCOUNT", b"GET", b"k"])
    db.manager("PNCOUNT").repo.converge(b"k", ({7: 1}, {7: 1}))
    db.apply(resp, [b"PNCOUNT", b"GET", b"k"])
    # TENSOR GETs never touch the device; the threshold/converge drain
    # kernel compiles here at its default bucket shape, not mid-serving
    db.manager("TENSOR").repo.drain()
    # MAP TREG reads never touch the device; the field table's sparse
    # drain compiles here at its default bucket shape
    db.manager("MAP").repo.drain()
