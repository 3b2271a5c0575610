"""The explorable world: real Clusters over the model network.

A ``World`` is one configuration (2-node, 3-node, or 3-node-2-region) of
REAL ``Cluster`` instances wired to ``net.py``'s in-memory transport
and virtual clock, each over a ``ModelDatabase`` — a minimal host-side
GCOUNT lattice (pointwise-max join, the paper's canonical delta CRDT)
that speaks the real wire codec, so every frame the explorer reorders
is a genuine schema-v6 frame through the genuine framing/CRC/codec
path.

The explorer talks to the world through three methods:

* ``enabled_actions()`` — the deterministic, stably-ordered action
  frontier: deliveries per link, heartbeat ticks per instance, bounded
  duplicates / connection kills / partitions / crash-reboots / extra
  client writes;
* ``apply(action)`` — fire one action, then settle the event loop to
  idle (every task parked on a model-network future);
* ``state_hash()`` — canonical digest of ALL protocol-relevant state
  (lattices, membership, conn/dial/sync machine fields, link contents,
  remaining budgets), timestamps rank-normalised so the virtual clock's
  absolute values never defeat deduplication.

Invariants: ``check_invariants()`` runs the cheap per-state laws
(lattice monotonicity, held-queue FIFO + bound, dial-backoff
boundedness/monotonicity) after every action; ``quiesce()`` heals
everything, drives the system to a fixpoint and asserts the global
laws (digest match everywhere, no stranded rtt stamps, nothing in
flight). A failure raises :class:`Violation` carrying the invariant
name — the explorer turns that plus its action trace into a minimized
schedule file.
"""

from __future__ import annotations

import asyncio
import hashlib
import selectors
import struct
from concurrent.futures import ThreadPoolExecutor

from jylis_tpu import sessions as sessions_mod
from jylis_tpu.cluster import cluster as cluster_mod
from jylis_tpu.cluster.cluster import Cluster
from jylis_tpu.obs.registry import MetricsRegistry
from jylis_tpu.ops import compose
from jylis_tpu.ops.bcount import BCount
from jylis_tpu.ops.tensor_host import Tensor, okey_u32
from jylis_tpu.utils.address import Address
from jylis_tpu.utils.config import Config
from jylis_tpu.utils.log import Log

from .net import Network, VirtualClock

CONFIG_NAMES = ("nodes2", "nodes3", "regions3")

TICK_MS = 100  # virtual ms per heartbeat action

# per-trace budgets for the expensive/structural actions: unbounded,
# each would multiply the frontier at every depth for little new
# coverage (state-hash dedup already collapses the repeats)
DEFAULT_BUDGETS = {
    "writes": 1,  # extra client writes per group (on top of the seed write)
    "dups": 1,
    "kills": 1,
    "crashes": 1,
    "partitions": 1,
    # BCOUNT contention (schema v9): escrow-checked decrements per group
    # and escrow transfers OUT of the seed-escrow group (global) — the
    # schedules the `0 <= value <= bound` invariant must survive
    "bdecs": 1,
    "bxfers": 1,
    # session tokens (schema v10): SESSION TOKEN mints per group — each
    # snapshots the group's vector + its own-column floor, and the
    # read-your-writes invariant then holds at EVERY later state: any
    # replica whose vector dominates the token must show the floor
    "mints": 1,
    # bridge failover (PR 15, regions3 only): bkill takes a group DOWN
    # and LEAVES it down (unlike crash's immediate reboot) so the
    # schedules between the kill and the matching breboot — exactly
    # where liveness demotion, succession, and the dual-bridge overlap
    # live — are explorable; quiesce reboots any still-down group
    # before asserting convergence
    "bkills": 1,
}

# the demotion threshold every model Cluster runs with (small enough
# that directed schedules reach a handover within a few tick actions);
# the bridge_demotion invariant checks observers against THIS value
# even when bridge_unsafe arms the broken never-demote rule
BRIDGE_DEMOTE_MODEL = 6

# the modelled bounded counter: one key, bound granted (and matching
# dec-escrow minted via incs) by the rid-1 replica's row — a CONVERGED
# initial state every replica boots with, so the contended resource
# exists before any schedule runs. Other replicas can decrement only
# after an escrow transfer reaches them: exactly the interplay the
# exploration must cover.
BCOUNT_KEY = b"q"
BCOUNT_SEED_RID = 1
BCOUNT_BOUND = 2


def _seed_bcount() -> BCount:
    bc = BCount()
    bc.grants[BCOUNT_SEED_RID] = BCOUNT_BOUND
    bc.incs[BCOUNT_SEED_RID] = BCOUNT_BOUND
    return bc


class Violation(Exception):
    """One invariant broke. ``name`` is the invariant's stable id."""

    def __init__(self, name: str, detail: str):
        super().__init__(f"{name}: {detail}")
        self.name = name
        self.detail = detail


class ModelDatabase:
    """Host-side GCOUNT + TENSOR lattices with the exact Database
    surface the Cluster consumes, producing real codec-shaped deltas.
    GCOUNT is the scalar delta payload; TENSOR (element-wise-max mode,
    dim-2 vectors — ops/tensor_host.Tensor, the REAL wire object) makes
    every explored schedule also carry a non-scalar binary payload
    over the wire and the region bridge. One ``write`` action mutates
    both lattices (the
    tensor cell is a deterministic function of the counter write), so
    the frontier does not grow a second write axis. ``journal`` is the
    WAL analog: local writes survive a crash-reboot (the tensor write
    re-derives from the journaled counter), converged remote state does
    not (it heals back over the rejoin sync — the exact path worth
    exploring)."""

    DATA_TYPES = ("GCOUNT", "TENSOR", "MAP", "BCOUNT")

    def __init__(self, name: str, rid: int, journal=None,
                 escrow_unsafe: bool = False, session_unsafe: bool = False):
        self.name = name
        self.rid = rid
        self.escrow_unsafe = escrow_unsafe
        # the node's applied-interval vector (jylis_tpu/sessions.py —
        # the REAL object, bound by the real Cluster exactly like the
        # product's Database). session_unsafe arms the deliberately
        # broken watermark rule (first-observed jump) the explorer must
        # refute with a minimized counterexample.
        self.sessions = sessions_mod.SessionIndex(unsafe=session_unsafe)
        self.state: dict[bytes, dict[int, int]] = {}
        self.state_t: dict[bytes, Tensor] = {}
        # MAP (schema v9): real compose.MapCRDT objects, keyed per map
        # key; wire batches carry packed (key, field) composites exactly
        # like the product. One write action edits a per-rid field (a
        # deterministic function of the counter write, so the frontier
        # grows no new axis and the WAL replay re-derives it).
        self.state_m: dict[bytes, compose.MapCRDT] = {}
        # BCOUNT (schema v9): real ops/bcount.BCount states; every
        # replica boots with the SAME converged seed (the bound + the
        # rid-1 escrow), so `0 <= value <= bound` is at stake from the
        # first action
        self.state_b: dict[bytes, BCount] = {BCOUNT_KEY: _seed_bcount()}
        self.pending: list[tuple[bytes, dict[int, int]]] = []
        self.write_seq = 0  # own-write ordinal (drives WRITE_KEYS)
        # own counter columns that have been FLUSHED under THIS
        # incarnation (absolute values — state-based columns subsume
        # earlier writes): exactly the cells a token minted now covers.
        # Journal-replayed state is deliberately NOT here — a reboot
        # forgets its shipped history, and a fresh token must not claim
        # writes only the OLD incarnation's (possibly lost) stream or a
        # digest sync can deliver (the product contract: clients retain
        # their token across writes; docs/sessions.md).
        self.own_shipped: dict[bytes, int] = {}
        self.pending_t: list[tuple[bytes, Tensor]] = []
        self.pending_m: list[tuple[bytes, tuple]] = []
        self.pending_b: list[tuple[bytes, tuple]] = []
        self.refused_decs = 0  # OUTOFBOUND analog: local-rights refusals
        # WAL entries are tagged ops now that two kinds exist:
        # ("w", key, n) counter writes (tensor + MAP edits re-derive),
        # and ("bstate", wire) — the POST-MUTATION full per-key BCOUNT
        # view, replayed by unconditional converge. This mirrors the
        # product exactly: its journal stores the flushed full-view
        # delta and its replay converges it back — replay NEVER re-runs
        # a rights check (a journaled spend whose funding had arrived
        # over the network before the crash must not vanish because the
        # seed state alone cannot fund it; review fix).
        self.journal: list[tuple] = list(journal or ())
        self.metrics = MetricsRegistry()
        for entry in self.journal:  # boot replay (all lattices)
            if entry[0] == "w":
                _, key, n = entry
                rows = self.state.setdefault(key, {})
                rows[self.rid] = max(rows.get(self.rid, 0), n)
                self._tensor_join(key, self._tensor_delta(n))
                self._map_edit(key, n)
            elif entry[0] == "bstate":
                self.state_b[BCOUNT_KEY].converge(
                    BCount.from_wire(entry[1])
                )

    def _tensor_delta(self, n: int) -> Tensor:
        # a function of (rid, counter value): replayable from the WAL
        return Tensor.max_value(struct.pack("<2f", float(self.rid), float(n)))

    def _tensor_join(self, key: bytes, delta: Tensor) -> None:
        cur = self.state_t.get(key)
        if cur is None:
            cur = Tensor()
            self.state_t[key] = cur
        cur.converge(delta)

    def _map_edit(self, key: bytes, n: int) -> tuple[bytes, tuple]:
        """The MAP face of a counter write: bump a GCOUNT-valued field
        owned by this rid in map key ``m``. Returns the decomposed
        (packed composite, full field unit) delta entry."""
        m = self.state_m.setdefault(b"m", compose.MapCRDT())
        field = b"f%d" % self.rid
        m.set_field(field, self.rid, "GCOUNT", [b"1"])
        packed = compose.pack_field(b"m", field)
        return (packed, m.fields[field].unit())

    def _bcount_transfer(self, to_rid: int) -> bool:
        """Move one unit of dec-escrow to another replica."""
        return self.state_b[BCOUNT_KEY].transfer(self.rid, to_rid, 1, "DEC")

    # write keys cycle per own-write ordinal: distinct keys are what
    # makes a lost-frame gap OBSERVABLE (absolute counter columns
    # subsume earlier writes to the SAME key, so a one-key model could
    # never exhibit the session hole the unsafe watermark rule hides)
    WRITE_KEYS = (b"x", b"y", b"z", b"w", b"v")

    def local_write(self, key: bytes | None = None) -> None:
        if key is None:
            key = self.WRITE_KEYS[min(self.write_seq, 4)]
        self.write_seq += 1
        rows = self.state.setdefault(key, {})
        n = rows.get(self.rid, 0) + 1
        rows[self.rid] = n
        self.journal.append(("w", key, n))  # WAL before the network sees it
        self.pending.append((key, {self.rid: n}))
        t = self._tensor_delta(n)
        self._tensor_join(key, t)
        self.pending_t.append((key, t))
        self.pending_m.append(self._map_edit(key, n))

    def local_bdec(self) -> bool:
        """One escrow-checked decrement; a refusal (insufficient local
        dec-escrow — the RESP surface's OUTOFBOUND) changes no lattice
        state and is counted. In escrow_unsafe mode the DELIBERATELY
        BROKEN rule ships: the local rights check is skipped (the
        canonical escrow bug — spending without owning the right), and
        the explorer must surface it as a minimized `value < 0`
        counterexample schedule."""
        bc = self.state_b[BCOUNT_KEY]
        if self.escrow_unsafe:
            bc.decs[self.rid] = bc.decs.get(self.rid, 0) + 1
        elif not bc.dec(self.rid, 1):
            self.refused_decs += 1
            return False
        self.journal.append(("bstate", bc.to_wire()))
        self.pending_b.append((BCOUNT_KEY, bc.to_wire()))
        return True

    def local_bxfer(self, to_rid: int) -> bool:
        if not self._bcount_transfer(to_rid):
            self.refused_decs += 1
            return False
        wire = self.state_b[BCOUNT_KEY].to_wire()
        self.journal.append(("bstate", wire))
        self.pending_b.append((BCOUNT_KEY, wire))
        return True

    def _join(self, batch) -> None:
        for key, delta in batch:
            rows = self.state.setdefault(bytes(key), {})
            for rid, v in delta.items():
                if v > rows.get(rid, 0):
                    rows[rid] = v

    async def converge_async(self, deltas) -> None:
        name, batch = deltas
        if name == "GCOUNT":
            self._join(batch)
        elif name == "TENSOR":
            for key, delta in batch:
                self._tensor_join(bytes(key), delta)
        elif name == "MAP":
            for packed, unit in batch:
                key, field = compose.unpack_field(bytes(packed))
                self.state_m.setdefault(
                    key, compose.MapCRDT()
                ).converge_field(field, unit)
        elif name == "BCOUNT":
            for key, wire in batch:
                bc = self.state_b.setdefault(bytes(key), BCount())
                bc.converge(BCount.from_wire(wire))

    async def flush_deltas_async(self, fn) -> None:
        if self.pending:
            batch, self.pending = self.pending, []
            for key, delta in batch:
                n = delta.get(self.rid, 0)
                if n > self.own_shipped.get(key, 0):
                    self.own_shipped[key] = n
            fn(("GCOUNT", tuple(batch)))
        if self.pending_t:
            batch_t, self.pending_t = self.pending_t, []
            fn(("TENSOR", tuple(batch_t)))
        if self.pending_m:
            batch_m, self.pending_m = self.pending_m, []
            fn(("MAP", tuple(batch_m)))
        if self.pending_b:
            batch_b, self.pending_b = self.pending_b, []
            fn(("BCOUNT", tuple(batch_b)))

    async def sync_type_digests_async(self) -> tuple[bytes, ...]:
        return (self._digest_g(), self._digest_t(), self._digest_m(),
                self._digest_b())

    # ---- schema-v8 range tier (the real Database's digest-tree API) ----

    @staticmethod
    def _bucket(key: bytes) -> int:
        # the product's sync_bucket (models/database.py): sha256(key)[0]
        return hashlib.sha256(key).digest()[0]

    def _key_hashes(self, name: str):
        """(key, canonical per-key hash) pairs — converged replicas
        produce identical pairs, so leaf digests compare across nodes
        exactly like the real incremental tree."""
        if name == "GCOUNT":
            for k, rows in self.state.items():
                if rows:
                    yield k, hashlib.sha256(
                        b"G\x00" + k + repr(sorted(rows.items())).encode()
                    ).digest()
        elif name == "TENSOR":
            for k, t in self.state_t.items():
                if t.mode != 0:
                    yield k, hashlib.sha256(
                        b"T\x00" + k + repr(t.canon()).encode()
                    ).digest()
        elif name == "MAP":
            # composite (key, field) leaves, exactly like the product's
            # digest tree: range repair pulls divergent FIELDS
            for k, m in self.state_m.items():
                for field, f in m.fields.items():
                    packed = compose.pack_field(k, field)
                    yield packed, hashlib.sha256(
                        b"M\x00" + packed + repr(f.canon()).encode()
                    ).digest()
        elif name == "BCOUNT":
            for k, bc in self.state_b.items():
                if not bc.is_bottom():
                    yield k, hashlib.sha256(
                        b"B\x00" + k + repr(bc.canon()).encode()
                    ).digest()

    async def sync_tree_async(self, name: str) -> tuple:
        leaves: dict[int, int] = {}
        for key, h in self._key_hashes(name):
            b = self._bucket(key)
            leaves[b] = leaves.get(b, 0) ^ int.from_bytes(h, "big")
        return tuple(
            (b, v.to_bytes(32, "big"))
            for b, v in sorted(leaves.items())
            if v
        )

    async def dump_range_async(self, name: str, buckets) -> list:
        bset = set(buckets)
        dump = await self.dump_state_async(names=(name,))
        batch = dump[0][1] if dump else []
        return [(k, d) for k, d in batch if self._bucket(k) in bset]

    def _tensor_copy(self, t: Tensor) -> Tensor:
        out = Tensor()
        out.converge(t)
        return out

    async def dump_state_async(self, names=None):
        names = tuple(names) if names is not None else self.DATA_TYPES
        out = []
        for n in names:
            if n == "GCOUNT":
                out.append(
                    (
                        "GCOUNT",
                        [(k, dict(v)) for k, v in sorted(self.state.items())],
                    )
                )
            elif n == "TENSOR":
                # copies: the dump is encoded in a worker thread while
                # actions keep mutating the live lattice objects
                out.append(
                    (
                        "TENSOR",
                        [
                            (k, self._tensor_copy(t))
                            for k, t in sorted(self.state_t.items())
                            if t.mode != 0
                        ],
                    )
                )
            elif n == "MAP":
                out.append(
                    (
                        "MAP",
                        [
                            (compose.pack_field(k, field),
                             m.fields[field].unit())
                            for k, m in sorted(self.state_m.items())
                            for field in sorted(m.fields)
                        ],
                    )
                )
            elif n == "BCOUNT":
                out.append(
                    (
                        "BCOUNT",
                        [
                            (k, bc.to_wire())
                            for k, bc in sorted(self.state_b.items())
                            if not bc.is_bottom()
                        ],
                    )
                )
            elif n == "SYSTEM":
                out.append(("SYSTEM", []))
        return out

    def _digest_g(self) -> bytes:
        canon = sorted(
            (k.hex(), sorted(v.items()))
            for k, v in self.state.items()
            if v
        )
        return hashlib.sha256(repr(canon).encode()).digest()

    def _digest_t(self) -> bytes:
        canon = sorted(
            (k.hex(), t.canon())
            for k, t in self.state_t.items()
            if t.mode != 0
        )
        return hashlib.sha256(repr(canon).encode()).digest()

    def _digest_m(self) -> bytes:
        canon = sorted(
            (k.hex(), m.canon()) for k, m in self.state_m.items()
        )
        return hashlib.sha256(repr(canon).encode()).digest()

    def _digest_b(self) -> bytes:
        canon = sorted(
            (k.hex(), bc.canon())
            for k, bc in self.state_b.items()
            if not bc.is_bottom()
        )
        return hashlib.sha256(repr(canon).encode()).digest()

    def digest(self) -> bytes:
        return hashlib.sha256(
            self._digest_g() + self._digest_t() + self._digest_m()
            + self._digest_b()
        ).digest()

    def cells(self) -> dict[tuple, int]:
        """Per-cell monotonicity floor: counter cells AND tensor
        coordinates (as okey ints — per-coordinate max must never
        regress)."""
        out: dict[tuple, int] = {
            (k, rid): v
            for k, rows in self.state.items()
            for rid, v in rows.items()
        }
        import numpy as np

        for k, t in self.state_t.items():
            if t.mode == 0:
                continue
            # the REAL lattice order (tensor_host.okey_u32), not a copy:
            # the floor must track the product's definition exactly
            keys = okey_u32(np.frombuffer(t.val, "<u4"))
            for i, okey in enumerate(keys.tolist()):
                out[("T", k, i)] = okey
        # MAP: per-field edit counters, tombstone cells, and the inner
        # GCOUNT columns are all monotone
        for k, m in self.state_m.items():
            for field, f in m.fields.items():
                for rid, seq in f.ver.items():
                    out[("Mv", k, field, rid)] = seq
                for rid, seq in f.tomb.items():
                    out[("Mt", k, field, rid)] = seq
                if f.itype == "GCOUNT":
                    for rid, v in f.val.items():
                        out[("Mg", k, field, rid)] = v
        # BCOUNT: every component cell is monotone (the join is
        # pointwise max over all five)
        for k, bc in self.state_b.items():
            for tag, span in (
                ("Bg", bc.grants), ("Bi", bc.incs), ("Bd", bc.decs),
            ):
                for rid, v in span.items():
                    out[(tag, k, rid)] = v
            for tag, mat in (("Bxi", bc.xi), ("Bxd", bc.xd)):
                for (f_, t_), v in mat.items():
                    out[(tag, k, f_, t_)] = v
        return out


class Instance:
    """One Cluster's place in the world. ``group`` is the
    crash/partition granularity (one instance a group in every
    configuration left)."""

    def __init__(self, key: str, group: str, addr: Address):
        self.key = key
        self.group = group
        self.addr = addr
        self.alive = True
        self.cluster: Cluster | None = None
        self.database: ModelDatabase | None = None


class _TrackedExecutor(ThreadPoolExecutor):
    """Single worker + a future ledger: settle() can WAIT on in-flight
    ``to_thread`` work (the sync-dump encodes) instead of racing it —
    one worker keeps completion order = submission order, so the drain
    is deterministic."""

    def __init__(self):
        super().__init__(max_workers=1, thread_name_prefix="jmodel")
        self.futures = []

    def submit(self, fn, /, *args, **kwargs):
        f = super().submit(fn, *args, **kwargs)
        self.futures.append(f)
        return f


class _NullSelector(selectors.BaseSelector):
    """The model loop has no real file descriptors — every wake-up is a
    call_soon from the model network or the executor — so the epoll
    syscall per loop iteration (hundreds of thousands per exploration)
    is pure overhead. `select` parks briefly only when the loop is
    genuinely idle waiting on the executor thread."""

    def __init__(self):
        self._map = {}

    def register(self, fileobj, events, data=None):  # pragma: no cover
        key = selectors.SelectorKey(fileobj, 0, events, data)
        self._map[fileobj] = key
        return key

    def unregister(self, fileobj):  # pragma: no cover
        return self._map.pop(fileobj)

    def select(self, timeout=None):
        if timeout is None or timeout > 0:
            # genuinely idle (waiting on the executor thread): yield the
            # GIL briefly instead of busy-spinning the loop
            import time as _time

            _time.sleep(5e-5)
        return []

    def get_map(self):
        return self._map

    def close(self):
        self._map.clear()


class Runtime:
    """One event loop + tracked executor shared across the thousands of
    short-lived Worlds a replay-based search creates — loop construction
    and teardown would otherwise dominate the whole exploration.

    ``task_events`` counts every task creation AND completion (via a
    task factory): together with the network's progress counter it is
    the O(1) settle fingerprint — ``asyncio.all_tasks()`` walks a
    weakset of every live task and measurably dominated the search."""

    def __init__(self):
        self.loop = asyncio.SelectorEventLoop(_NullSelector())
        self.executor = _TrackedExecutor()
        self.loop.set_default_executor(self.executor)
        self.task_events = 0

        def factory(loop, coro):
            self.task_events += 1
            task = asyncio.Task(coro, loop=loop)
            task.add_done_callback(self._task_done)
            return task

        self.loop.set_task_factory(factory)

    def _task_done(self, _task) -> None:
        self.task_events += 1

    def close(self) -> None:
        self.executor.shutdown(wait=False, cancel_futures=True)
        self.loop.close()


def _mk_config(
    addr: Address, seeds, region: str = "", bridge_unsafe: bool = False
) -> Config:
    cfg = Config()
    cfg.addr = addr
    cfg.seed_addrs = list(seeds)
    cfg.heartbeat_time = 999.0  # never started: the explorer IS the heart
    cfg.region = region
    # bridge_unsafe arms the DELIBERATELY broken demotion rule — the
    # v10 status quo: a threshold no schedule can reach, so a dead
    # bridge stays elected forever. The bridge_demotion invariant
    # (checked against BRIDGE_DEMOTE_MODEL regardless) must then yield
    # a minimized counterexample.
    cfg.bridge_demote_ticks = (1 << 30) if bridge_unsafe else (
        BRIDGE_DEMOTE_MODEL
    )
    # provenance tracing (schema v11) stays off under the explorer: a
    # 1-in-N sampling counter in broadcast_deltas would otherwise make
    # frame bytes depend on global write ordering, multiplying the
    # explored state space without adding any modeled behavior
    cfg.trace_sample = 0
    cfg.log = Log.create_none()
    return cfg


class World:
    def __init__(
        self,
        config_name: str,
        budgets: dict | None = None,
        runtime: Runtime | None = None,
        escrow_unsafe: bool = False,
        session_unsafe: bool = False,
        bridge_unsafe: bool = False,
    ):
        if config_name not in CONFIG_NAMES:
            raise ValueError(f"unknown config {config_name!r}")
        self.config_name = config_name
        self.budgets = dict(DEFAULT_BUDGETS)
        if budgets:
            self.budgets.update(budgets)
        # escrow_unsafe arms ModelDatabase's deliberately broken
        # transfer rule (no rights check, full-bound amount): the
        # exploration MUST then find a schedule violating the bcount
        # invariant — the counterexample demonstration in test_model.py
        self.escrow_unsafe = escrow_unsafe
        # session_unsafe arms the broken session-watermark rule
        # (sessions.SessionIndex unsafe mode): the exploration MUST
        # then find a token-satisfied read observing a missing write —
        # the session_ryw counterexample demonstration
        self.session_unsafe = session_unsafe
        # bridge_unsafe arms the broken bridge-demotion rule (an
        # unreachable threshold — the pre-failover v10 behavior): the
        # bridge_demotion invariant must then yield a minimized
        # stale-bridge counterexample (PR 15)
        self.bridge_unsafe = bridge_unsafe
        self._owns_runtime = runtime is None
        self._runtime = runtime or Runtime()
        self.loop = self._runtime.loop
        self._executor = self._runtime.executor
        self.clock = VirtualClock()
        self.net = Network()
        self.instances: dict[str, Instance] = {}
        self.dbs: dict[str, ModelDatabase] = {}
        self._group_builders: dict[str, callable] = {}
        self.used = {
            "dups": 0, "kills": 0, "crashes": 0, "partitions": 0,
            "bxfers": 0, "bkills": 0,
        }
        # groups taken down by bkill and not yet rebooted: no ticks, no
        # writes, no deliveries land there; quiesce reboots them first
        self.down_groups: set[str] = set()
        self._down_journals: dict[str, list] = {}
        self.writes_left: dict[str, int] = {}
        self.bdecs_left: dict[str, int] = {}
        self.mints_left: dict[str, int] = {}
        # minted session tokens: (group, vector, own-column floor,
        # minting boot) — the read-your-writes invariant checks every
        # one at every state; the quiescence LIVENESS law additionally
        # requires universal domination, but only for tokens whose
        # minting group never crashed afterward (a crash can destroy
        # the only copy of the sequenced frames a token references —
        # the data heals via anti-entropy, the token honestly stays
        # STALE forever; docs/sessions.md documents the contract)
        self.tokens: list[tuple[str, dict, dict, int]] = []
        self.boot_count: dict[str, int] = {}
        self.group_rids: dict[str, int] = {}
        # invariant shadows: per-db lattice floor, per-(instance, addr)
        # last observed dial-backoff state
        self._floor: dict[str, dict] = {}
        self._backoff_seen: dict[tuple[str, str], tuple[int, int]] = {}
        self._build()
        # seed divergence: every group starts with one local write on
        # the shared key, so convergence is never vacuous
        for group in sorted(self.dbs):
            self.dbs[group].local_write()
        self._run(lambda: None)

    def close(self) -> None:
        def down():
            for inst in self.instances.values():
                if inst.alive:
                    inst.cluster.dispose()
            for conn in self.net.conns.values():
                conn.kill()  # EOF every parked read task

        try:
            self._run(down)
        finally:
            # reap anything still parked on a model future, so a shared
            # runtime starts the next World with a clean task table
            pending = asyncio.all_tasks(self.loop)
            for task in pending:
                task.cancel()
            if pending:
                try:
                    self.loop.run_until_complete(
                        asyncio.gather(*pending, return_exceptions=True)
                    )
                # jlint: broad-ok — best-effort reap of cancelled tasks
                # at teardown; gather(return_exceptions=True) only
                # raises loop-state errors, and a failed reap must not
                # mask the exploration's own result
                except Exception:
                    pass
            self._executor.futures.clear()
            if self._owns_runtime:
                self._runtime.close()

    # ---- construction ------------------------------------------------------

    def _spawn(self, key, group, addr, seeds, db, region="") -> Instance:
        inst = Instance(key, group, addr)
        inst.database = db
        inst.cluster = Cluster(
            _mk_config(addr, seeds, region, self.bridge_unsafe),
            db,
            clock=self.clock,
            connect=self.net.connect_fn(inst),
        )
        self.instances[key] = inst
        self.net.register(str(addr), inst)
        return inst

    def _build(self) -> None:
        if self.config_name == "nodes2":
            addrs = {
                "A": Address("10.0.0.1", "7001", "A"),
                "B": Address("10.0.0.2", "7001", "B"),
            }
            for i, name in enumerate(sorted(addrs)):
                self._node_group(name, addrs[name], [
                    a for n, a in sorted(addrs.items()) if n != name
                ], rid=i + 1)
        elif self.config_name == "nodes3":
            addrs = {
                "foo": Address("10.0.0.1", "7001", "foo"),
                "bar": Address("10.0.0.2", "7001", "bar"),
                "baz": Address("10.0.0.3", "7001", "baz"),
            }
            # bar/baz know only the seed: mesh discovery through gossip
            # is part of the explored state space (the reference test's
            # topology)
            self._node_group("foo", addrs["foo"], [], rid=1)
            self._node_group("bar", addrs["bar"], [addrs["foo"]], rid=2)
            self._node_group("baz", addrs["baz"], [addrs["foo"]], rid=3)
        else:  # regions3: two regions, one deterministic bridge each.
            # foo+bar form region ra's intra mesh (foo, the smaller
            # address, is its bridge); baz alone is region rb (its own
            # bridge). The explored topology is therefore foo<->bar and
            # the foo<->baz WAN link, with bar<->baz REACHABLE ONLY
            # through foo's origin-preserving relays — exactly the path
            # a session token minted on bar must survive to verify on
            # baz (and the path the region-prune policy must carve out
            # of the bootstrap full mesh without partitioning anyone).
            addrs = {
                "foo": Address("10.0.0.1", "7001", "foo"),
                "bar": Address("10.0.0.2", "7001", "bar"),
                "baz": Address("10.0.0.3", "7001", "baz"),
            }
            self._node_group("foo", addrs["foo"], [], rid=1, region="ra")
            self._node_group(
                "bar", addrs["bar"], [addrs["foo"]], rid=2, region="ra"
            )
            self._node_group(
                "baz", addrs["baz"], [addrs["foo"]], rid=3, region="rb"
            )

    def _node_group(self, name, addr, seeds, rid, region: str = "") -> None:
        def build(journal=None):
            db = ModelDatabase(name, rid, journal,
                               escrow_unsafe=self.escrow_unsafe,
                               session_unsafe=self.session_unsafe)
            self.dbs[name] = db
            self._spawn(name, name, addr, seeds, db, region=region)

        self._group_builders[name] = build
        self.writes_left[name] = self.budgets["writes"]
        self.bdecs_left[name] = self.budgets["bdecs"]
        self.mints_left[name] = self.budgets["mints"]
        self.group_rids[name] = rid
        build()

    # ---- event-loop stepping ----------------------------------------------

    def _run(self, fn) -> None:
        async def step():
            res = fn()
            if asyncio.iscoroutine(res):
                await res
            await self._settle()

        self.loop.run_until_complete(step())

    async def _settle(self) -> None:
        """Run the loop until every task is parked on a model-network
        future (or done) and no executor work is in flight. The
        fingerprint is (net progress, live task count); 8 stable
        spin rounds covers any pure-compute continuation chain."""
        stable, last = 0, None
        for _ in range(2000):
            await asyncio.sleep(0)
            pending = [f for f in self._executor.futures if not f.done()]
            if pending:
                await asyncio.wrap_future(pending[0])
                stable, last = 0, None
                continue
            self._executor.futures.clear()
            fp = (self.net.progress, self._runtime.task_events)
            if fp == last:
                stable += 1
                if stable >= 3:
                    return
            else:
                stable, last = 0, fp
        raise Violation("settle", "event loop failed to quiesce")

    # ---- actions -----------------------------------------------------------

    def _groups(self) -> list[str]:
        return sorted(self._group_builders)

    def enabled_actions(self) -> list[tuple]:
        acts: list[tuple] = []
        for cid in sorted(self.net.conns):
            conn = self.net.conns[cid]
            for direction in ("fwd", "rev"):
                link = conn.link(direction)
                recv = conn.target if direction == "fwd" else conn.dialer
                inst = self.instances.get(recv)
                if link.outbox and inst is not None and inst.alive:
                    acts.append(("deliver", cid, direction))
                    if self.used["dups"] < self.budgets["dups"]:
                        acts.append(("dup", cid, direction))
            if not conn.closed and self.used["kills"] < self.budgets["kills"]:
                acts.append(("kill", cid))
        for key in sorted(self.instances):
            if self.instances[key].alive:
                acts.append(("tick", key))
        for group in self._groups():
            if self.writes_left.get(group, 0) > 0 and self._group_alive(group):
                acts.append(("write", group))
            if self.bdecs_left.get(group, 0) > 0 and self._group_alive(group):
                acts.append(("bdec", group))
            if self.mints_left.get(group, 0) > 0 and self._group_alive(group):
                acts.append(("mint", group))
            if (
                self.used["crashes"] < self.budgets["crashes"]
                and self._group_alive(group)
            ):
                acts.append(("crash", group))
            # bridge-kill/reboot axis (PR 15, regions3): unlike crash's
            # immediate reboot, bkill leaves the group DOWN so the
            # demotion/succession window is itself explorable
            if self.config_name == "regions3":
                if (
                    self.used["bkills"] < self.budgets["bkills"]
                    and self._group_alive(group)
                ):
                    acts.append(("bkill", group))
                if group in self.down_groups:
                    acts.append(("breboot", group))
        # escrow transfers OUT of the seed-escrow group (the only group
        # holding dec-rights before any transfer): the interplay the
        # bcount invariant must survive — a transfer racing the sender's
        # own decrements, delivered or lost against each receiver
        if self.used["bxfers"] < self.budgets["bxfers"]:
            for gfrom in self._groups():
                if self.group_rids.get(gfrom) != BCOUNT_SEED_RID:
                    continue
                if not self._group_alive(gfrom):
                    continue
                for gto in self._groups():
                    if gto != gfrom and self._group_alive(gto):
                        acts.append(("bxfer", gfrom, gto))
        groups = self._groups()
        for i, a in enumerate(groups):
            for b in groups[i + 1:]:
                pair = frozenset((a, b))
                if pair in self.net.partitions:
                    acts.append(("heal", a, b))
                elif self.used["partitions"] < self.budgets["partitions"]:
                    acts.append(("part", a, b))
        return acts

    def _group_alive(self, group: str) -> bool:
        if group in self.down_groups:
            return False
        return all(
            i.alive for i in self.instances.values() if i.group == group
        )

    def action_enabled(self, action: tuple) -> bool:
        """Targeted membership test, equivalent to `action in
        enabled_actions()` without rebuilding the whole frontier —
        apply() runs this once per REPLAYED action, which is the
        exploration hot path."""
        kind = action[0]
        if kind == "tick":
            inst = self.instances.get(action[1])
            return inst is not None and inst.alive
        if kind in ("deliver", "dup"):
            if kind == "dup" and self.used["dups"] >= self.budgets["dups"]:
                return False
            conn = self.net.conns.get(action[1])
            if conn is None or action[2] not in ("fwd", "rev"):
                return False
            recv = conn.target if action[2] == "fwd" else conn.dialer
            inst = self.instances.get(recv)
            return bool(
                conn.link(action[2]).outbox
                and inst is not None
                and inst.alive
            )
        if kind == "kill":
            conn = self.net.conns.get(action[1])
            return (
                conn is not None
                and not conn.closed
                and self.used["kills"] < self.budgets["kills"]
            )
        if kind == "write":
            return (
                self.writes_left.get(action[1], 0) > 0
                and action[1] in self._group_builders
                and self._group_alive(action[1])
            )
        if kind == "bdec":
            return (
                self.bdecs_left.get(action[1], 0) > 0
                and action[1] in self._group_builders
                and self._group_alive(action[1])
            )
        if kind == "mint":
            return (
                self.mints_left.get(action[1], 0) > 0
                and action[1] in self._group_builders
                and self._group_alive(action[1])
            )
        if kind == "bxfer":
            return (
                self.used["bxfers"] < self.budgets["bxfers"]
                and self.group_rids.get(action[1]) == BCOUNT_SEED_RID
                and action[2] in self._group_builders
                and action[1] != action[2]
                and self._group_alive(action[1])
                and self._group_alive(action[2])
            )
        if kind == "crash":
            return (
                action[1] in self._group_builders
                and self.used["crashes"] < self.budgets["crashes"]
                and self._group_alive(action[1])
            )
        if kind == "bkill":
            return (
                self.config_name == "regions3"
                and action[1] in self._group_builders
                and self.used["bkills"] < self.budgets["bkills"]
                and self._group_alive(action[1])
            )
        if kind == "breboot":
            return (
                self.config_name == "regions3"
                and action[1] in self.down_groups
            )
        if kind == "part":
            return (
                action[1] in self._group_builders
                and action[2] in self._group_builders
                and action[1] != action[2]
                and frozenset((action[1], action[2]))
                not in self.net.partitions
                and self.used["partitions"] < self.budgets["partitions"]
            )
        if kind == "heal":
            return frozenset((action[1], action[2])) in self.net.partitions
        return False

    def apply(self, action: tuple) -> bool:
        """Fire one action then settle; False if it is not currently
        enabled (replay after a code change skips, never crashes)."""
        action = tuple(action)
        if not self.action_enabled(action):
            return False
        kind = action[0]
        if kind == "tick":
            inst = self.instances[action[1]]
            self.clock.advance(TICK_MS)
            self._run(inst.cluster._heartbeat)
        elif kind == "deliver":
            link = self.net.conns[action[1]].link(action[2])
            self._run(link.deliver_one)
        elif kind == "dup":
            self.used["dups"] += 1
            link = self.net.conns[action[1]].link(action[2])
            self._run(link.duplicate_one)
        elif kind == "kill":
            self.used["kills"] += 1
            self._run(self.net.conns[action[1]].kill)
        elif kind == "write":
            self.writes_left[action[1]] -= 1
            self._run(self.dbs[action[1]].local_write)
        elif kind == "bdec":
            self.bdecs_left[action[1]] -= 1
            self._run(self.dbs[action[1]].local_bdec)
        elif kind == "mint":
            self.mints_left[action[1]] -= 1
            self._mint(action[1])
        elif kind == "bxfer":
            self.used["bxfers"] += 1
            to_rid = self.group_rids[action[2]]
            self._run(
                lambda: self.dbs[action[1]].local_bxfer(to_rid)
            )
        elif kind == "crash":
            self.used["crashes"] += 1
            self._crash_reboot(action[1])
        elif kind == "bkill":
            self.used["bkills"] += 1
            self._kill_group(action[1])
        elif kind == "breboot":
            self._reboot_group(action[1])
        elif kind == "part":
            self.used["partitions"] += 1
            pair = frozenset((action[1], action[2]))
            self.net.partitions.add(pair)
            self._run(lambda: self.net.kill_between(action[1], action[2]))
        elif kind == "heal":
            self.net.partitions.discard(frozenset((action[1], action[2])))
            self._run(lambda: None)
        else:
            raise ValueError(f"unknown action {action!r}")
        self.net.gc_conns()
        return True

    def _mint(self, group: str) -> None:
        """SESSION TOKEN at ``group``: force its pending local deltas
        through the driving cluster's flush path (the product's
        Database._mint_token barrier), snapshot the vector, and record
        the group's OWN counter columns as the token's floor — exactly
        the writes the token's self entry covers. The session_ryw
        invariant then holds the floor against every replica whose
        vector ever dominates the token."""
        self._run(self.instances[group].cluster.flush_now)
        db = self.dbs[group]
        vec = dict(db.sessions.vector())
        rid = self.group_rids[group]
        # floor = own columns SHIPPED under this incarnation: what the
        # vector's self entry provably covers. (Journal-replayed state
        # a reboot never re-shipped is NOT claimable by a fresh token —
        # the explorer found exactly that over-claim in an earlier cut.)
        floor = {
            (key.hex(), rid): n for key, n in db.own_shipped.items()
        }
        self.tokens.append(
            (group, vec, floor, self.boot_count.get(group, 0))
        )

    def _crash_reboot(self, group: str) -> None:
        self._kill_group(group)
        self._reboot_group(group)

    def _kill_group(self, group: str) -> None:
        """Take a group down and LEAVE it down (the bkill half): its
        journal is snapshotted for the eventual reboot, its instances
        dispose, its conns die abortively. The explorable window
        between this and the matching breboot is where bridge
        demotion, deterministic succession and the dual-bridge overlap
        live."""
        self._down_journals[group] = list(self.dbs[group].journal)
        self.down_groups.add(group)

        def down():
            for key in [
                k for k, i in self.instances.items() if i.group == group
            ]:
                inst = self.instances.pop(key)
                inst.alive = False
                inst.cluster.dispose()
            self.net.kill_of_group(group)

        self._run(down)
        self.net.gc_conns()

    def _reboot_group(self, group: str) -> None:
        # a reboot is a new incarnation: advance the virtual clock so
        # the rebuilt Cluster mints a fresh boot epoch (production wall
        # time guarantees this; the model must too, or the new seq
        # stream would alias the old one in every peer's session vector)
        self.clock.advance(TICK_MS)
        self.boot_count[group] = self.boot_count.get(group, 0) + 1
        journal = self._down_journals.pop(group)
        self.down_groups.discard(group)
        # reboot from "disk": the journaled local writes survive,
        # converged remote state heals back over the rejoin sync
        self._group_builders[group](journal)
        # floor resets with the reboot: losing REMOTE state at a crash
        # is the documented durability model, not a join regression
        self._floor.pop(group, None)
        for k in [k for k in self._backoff_seen if k[0].startswith(group)]:
            del self._backoff_seen[k]
        self._run(lambda: None)

    # ---- invariants --------------------------------------------------------

    def check_invariants(self) -> None:
        # lattice monotonicity: no (key, replica) cell ever regresses
        for group, db in self.dbs.items():
            cells = db.cells()
            floor = self._floor.get(group, {})
            for cell, v in floor.items():
                if cells.get(cell, 0) < v:
                    raise Violation(
                        "monotonicity",
                        f"{group}: cell {cell} regressed {v} -> "
                        f"{cells.get(cell, 0)}",
                    )
            self._floor[group] = cells
            # BCOUNT escrow safety (schema v9): 0 <= value <= bound on
            # EVERY replica's local view in EVERY reachable state — the
            # invariant the escrow construction exists to enforce
            # without coordination (ops/bcount.py). A deliberately
            # broken escrow rule (World(escrow_unsafe=True)) must
            # surface here as a minimized counterexample schedule.
            for key, bc in db.state_b.items():
                value, bound = bc.value(), bc.bound()
                if value < 0:
                    raise Violation(
                        "bcount_negative",
                        f"{group}: {key!r} value {value} < 0 "
                        f"(decs outran the escrow that funded them)",
                    )
                if value > bound:
                    raise Violation(
                        "bcount_bound",
                        f"{group}: {key!r} value {value} > bound {bound}",
                    )
        # session guarantee (schema v10): a token-satisfied read never
        # observes a regression — any replica whose applied vector
        # dominates a minted token must show the token's floor (the
        # minting group's own counter columns at mint time). This is
        # THE read-your-writes invariant, checked at every state; the
        # deliberately broken watermark rule (session_unsafe) must
        # surface here as a minimized counterexample schedule.
        for g0, vec, floor, _boot in self.tokens:
            for group, db in self.dbs.items():
                if not self._group_alive(group):
                    continue
                svec = db.sessions.vector()
                if not all(svec.get(r, 0) >= s for r, s in vec.items()):
                    continue  # not dominated: STALE territory, no claim
                for (key_hex, rid), v in floor.items():
                    got = db.state.get(bytes.fromhex(key_hex), {}).get(
                        rid, 0
                    )
                    if got < v:
                        raise Violation(
                            "session_ryw",
                            f"{group}: dominates {g0}'s token but cell "
                            f"({key_hex}, {rid}) shows {got} < floor {v}",
                        )
        for key, inst in self.instances.items():
            if not inst.alive:
                continue
            c = inst.cluster
            # bounded handover (PR 15): a node never keeps electing a
            # bridge its OWN evidence says has been silent past the
            # demotion bound while a live successor exists. Checked
            # against BRIDGE_DEMOTE_MODEL — NOT the instance's armed
            # threshold — so the deliberately broken never-demote rule
            # (bridge_unsafe) surfaces here as a minimized stale-bridge
            # counterexample while the safe rule survives the identical
            # schedule by construction.
            if c._region:
                b = c._bridge_of(c._region)
                me = str(inst.addr)
                seen = c._seen_tick.get(b) if b is not None else None
                if (
                    b is not None
                    and b != me
                    and seen is not None
                    and c._tick - seen > BRIDGE_DEMOTE_MODEL
                ):
                    def _fresh(a) -> bool:
                        if str(a) == me:
                            return True
                        t = c._seen_tick.get(str(a))
                        return (
                            t is not None
                            and c._tick - t <= BRIDGE_DEMOTE_MODEL
                        )

                    alt = any(
                        _fresh(a)
                        for a in c._known_addrs
                        if str(a) != b
                        and c._regions.get(str(a), ("", 0))[0]
                        == c._region
                    )
                    if alt:
                        raise Violation(
                            "bridge_demotion",
                            f"{key}: elected bridge {b} silent "
                            f"{c._tick - seen} ticks (bound "
                            f"{BRIDGE_DEMOTE_MODEL}) with a live "
                            "successor available",
                        )
            # held queue: bounded and FIFO by hold time
            if len(c._held) > c._held_cap:
                raise Violation(
                    "held_bound",
                    f"{key}: {len(c._held)} held > cap {c._held_cap}",
                )
            stamps = [ts for ts, _data, _keys in c._held]
            if stamps != sorted(stamps):
                raise Violation("held_fifo", f"{key}: held stamps {stamps}")
            # delta-interval sender state (schema v8): the retransmit
            # window is bounded and strictly seq-ordered, and no peer's
            # acked watermark outruns the sender's own seq counter
            if len(c._delta_log) > c._delta_log_cap:
                raise Violation(
                    "delta_log_bound",
                    f"{key}: {len(c._delta_log)} logged > cap "
                    f"{c._delta_log_cap}",
                )
            seqs = [s for s, _ in c._delta_log]
            if seqs != sorted(seqs) or len(set(seqs)) != len(seqs):
                raise Violation(
                    "delta_log_order", f"{key}: window seqs {seqs}"
                )
            if seqs and seqs[-1] > c._delta_seq:
                raise Violation(
                    "delta_log_order",
                    f"{key}: window head {seqs[-1]} > seq {c._delta_seq}",
                )
            for addr, st in c._peers.items():
                if st.acked is not None and st.acked > c._delta_seq:
                    raise Violation(
                        "ack_bound",
                        f"{key}->{addr}: acked {st.acked} > delta_seq "
                        f"{c._delta_seq}",
                    )
            # receiver interval state: the out-of-order park is bounded
            # and strictly above the contiguity cursor
            for skey, ooo in c._recv_ooo.items():
                if len(ooo) > cluster_mod.RECV_OOO_CAP:
                    raise Violation(
                        "ooo_bound", f"{key}<-{skey}: {len(ooo)} parked"
                    )
                cum = c._recv_cum.get(skey, 0)
                if ooo and min(ooo) <= cum + 1:
                    raise Violation(
                        "ooo_order",
                        f"{key}<-{skey}: parked {sorted(ooo)[:4]} at cum "
                        f"{cum} (contiguous seqs must collapse)",
                    )
            # dial backoff: bounded above by cap(+jitter), monotone
            # while failures accumulate (reset only by contact)
            for addr, st in c._peers.items():
                wait = st.next_dial_tick - c._tick
                bound = c._backoff_cap + c._backoff_cap // 2 + 1
                if st.fails > 0 and wait > bound:
                    raise Violation(
                        "backoff_bound",
                        f"{key}->{addr}: wait {wait} ticks > bound {bound}",
                    )
                seen = self._backoff_seen.get((key, str(addr)))
                if (
                    seen is not None
                    and st.fails > seen[0]
                    and st.next_dial_tick < seen[1]
                ):
                    raise Violation(
                        "backoff_monotone",
                        f"{key}->{addr}: fails {seen[0]}->{st.fails} but "
                        f"next_dial {seen[1]}->{st.next_dial_tick}",
                    )
                self._backoff_seen[(key, str(addr))] = (
                    st.fails, st.next_dial_tick,
                )

    # ---- quiescence + global laws -----------------------------------------

    def _deliver_all(self, cap: int = 200) -> None:
        for _ in range(cap):
            moved = 0

            def burst():
                nonlocal moved
                for cid in sorted(self.net.conns):
                    conn = self.net.conns[cid]
                    for direction in ("fwd", "rev"):
                        link = conn.link(direction)
                        recv = (
                            conn.target if direction == "fwd"
                            else conn.dialer
                        )
                        inst = self.instances.get(recv)
                        while (
                            link.outbox and inst is not None and inst.alive
                        ):
                            link.deliver_one()
                            moved += 1

            # quiescence needs no per-frame interleaving control: one
            # settle per burst, not per frame
            self._run(burst)
            self.net.gc_conns()
            if not moved:
                return
        raise Violation("quiesce", "deliveries never drained")

    def _digests(self) -> dict[str, str]:
        return {g: db.digest().hex() for g, db in sorted(self.dbs.items())}

    def quiesce(self) -> None:
        """Heal everything, run to a fixpoint, assert the global laws:
        digest match on every replica, no in-flight or held frames, no
        stranded rtt stamps."""
        self.net.partitions.clear()
        # groups still down from a bkill reboot first: quiescence is
        # about the HEALED system, and a down group can neither
        # converge nor serve its half of any invariant
        for group in sorted(self.down_groups):
            self._reboot_group(group)
        period = cluster_mod.SYNC_PERIOD_TICKS
        stable = 0
        for _ in range(40 * period):
            self._deliver_all()
            if len(set(self._digests().values())) == 1:
                stable += 1
                # a full extra sync period after digests agree lets the
                # in-flight sync conversations and pong traffic finish
                if stable > period + 2:
                    break
            else:
                stable = 0
            for key in sorted(self.instances):
                if self.instances[key].alive:
                    self.clock.advance(TICK_MS)
                    self._run(self.instances[key].cluster._heartbeat)
        self._deliver_all()
        digests = self._digests()
        if len(set(digests.values())) != 1:
            raise Violation("convergence", f"digest mismatch: {digests}")
        for key, inst in sorted(self.instances.items()):
            if not inst.alive:
                continue
            c = inst.cluster
            if c._held:
                raise Violation(
                    "held_drained", f"{key}: {len(c._held)} frames held "
                    "after quiescence",
                )
            for addr, conn in sorted(
                c._actives.items(), key=lambda kv: str(kv[0])
            ):
                if conn.established and conn.pong_sent:
                    raise Violation(
                        "rtt_stamps",
                        f"{key}->{addr}: {len(conn.pong_sent)} stranded "
                        "rtt stamps after quiescence",
                    )
                if conn.range_pending:
                    raise Violation(
                        "range_walk_done",
                        f"{key}->{addr}: range walk stalled with "
                        f"{sorted(conn.range_pending)} pending",
                    )
            # the v8 repair machinery fully drains at quiescence: no
            # parked out-of-order seqs, no queued range serves, and no
            # peer still owed a range repair (interval-dirty)
            if any(c._recv_ooo.values()):
                raise Violation(
                    "ooo_drained",
                    f"{key}: out-of-order seqs parked after quiescence",
                )
            if c._range_queue:
                raise Violation(
                    "range_queue_drained",
                    f"{key}: {len(c._range_queue)} range serves queued",
                )
            if c._relay_queue:
                raise Violation(
                    "relay_queue_drained",
                    f"{key}: {len(c._relay_queue)} repair relays queued "
                    "after quiescence",
                )
            for addr, st in sorted(
                c._peers.items(), key=lambda kv: str(kv[0])
            ):
                if st.interval_dirty and str(addr) in {
                    str(i.addr) for i in self.instances.values() if i.alive
                }:
                    raise Violation(
                        "dirty_cleared",
                        f"{key}->{addr}: still interval-dirty after "
                        "quiescence (range repair never completed)",
                    )
        for cid, conn in sorted(self.net.conns.items()):
            for direction in ("fwd", "rev"):
                link = conn.link(direction)
                if link.outbox or link.inbox:
                    raise Violation(
                        "in_flight", f"{cid}/{direction} still carries "
                        "bytes after quiescence",
                    )
        self._quiesce_sessions()

    def _quiesce_sessions(self) -> None:
        """Session liveness at quiescence: once everything healed and
        every digest matches, every minted token must become dominated
        on every alive replica — live contiguity covers the direct
        paths, digest-match adoption covers reboots and region hops.
        Adoption can need a couple more sync periods after the digests
        first agree (it rides the periodic MsgSyncRequest exchange, and
        a vector entry may have to hop bridge-wise), so tick a bounded
        extra window before asserting."""
        if not self.tokens:
            return
        period = cluster_mod.SYNC_PERIOD_TICKS

        def all_dominated() -> bool:
            for g0, vec, _floor, boot in self.tokens:
                if self.boot_count.get(g0, 0) != boot:
                    # the minting group crashed after the mint: the
                    # token's frames may be unrecoverable — it honestly
                    # stays STALE (safety still checked every state)
                    continue
                for group, db in self.dbs.items():
                    if not self._group_alive(group):
                        continue
                    svec = db.sessions.vector()
                    if not all(
                        svec.get(r, 0) >= s for r, s in vec.items()
                    ):
                        return False
            return True

        for _ in range(8 * period):
            if all_dominated():
                return
            for key in sorted(self.instances):
                if self.instances[key].alive:
                    self.clock.advance(TICK_MS)
                    self._run(self.instances[key].cluster._heartbeat)
            self._deliver_all()
        if not all_dominated():
            raise Violation(
                "session_liveness",
                "a minted token is still not dominated everywhere "
                "after quiescence + adoption window",
            )

    # ---- state hashing -----------------------------------------------------

    @staticmethod
    def _sha(data: bytes) -> str:
        return hashlib.sha256(data).hexdigest()[:16]

    def _rel(self, tick: int, t) -> int | None:
        if t is None:
            return None
        period = cluster_mod.SYNC_PERIOD_TICKS
        return min(tick - t, 8 * period)

    def canonical(self):
        period = cluster_mod.SYNC_PERIOD_TICKS
        mod = cluster_mod.ANNOUNCE_EVERY * period
        # rank-normalise every wall-ms the state carries: absolute
        # virtual-clock values would make every state unique
        times = set()
        for inst in self.instances.values():
            if inst.alive:
                c = inst.cluster
                times.update(ts for ts, _data, _keys in c._held)
                if c._defer_since_ms is not None:
                    times.add(c._defer_since_ms)
        rank = {t: i for i, t in enumerate(sorted(times))}
        dbs = {
            g: {
                "digest": db.digest().hex()[:16],
                "pending": [
                    (k.hex(), sorted(d.items())) for k, d in db.pending
                ],
                "pending_t": [
                    (k.hex(), self._sha(repr(t.canon()).encode()))
                    for k, t in db.pending_t
                ],
                "pending_m": [
                    (k.hex(), self._sha(repr(u).encode()))
                    for k, u in db.pending_m
                ],
                "pending_b": [
                    (k.hex(), self._sha(repr(w).encode()))
                    for k, w in db.pending_b
                ],
                "refused": db.refused_decs,
                "journal_len": len(db.journal),
                # the applied-interval vector + parked seqs (v10): two
                # states differing only here answer a SESSION READ
                # differently, so they must not dedup-merge — and the
                # shipped-floor feeds future mints' claims
                "svec": db.sessions.canonical(),
                "shipped": sorted(
                    (k.hex(), n) for k, n in db.own_shipped.items()
                ),
            }
            for g, db in sorted(self.dbs.items())
        }
        insts = {}
        for key in sorted(self.instances):
            inst = self.instances[key]
            if not inst.alive:
                insts[key] = "down"
                continue
            c = inst.cluster
            tick = c._tick
            insts[key] = {
                "tick_mod": tick % mod,
                "known": [
                    sorted(str(a) for a in c._known_addrs.adds),
                    sorted(str(a) for a in c._known_addrs.removes),
                ],
                "actives": {
                    str(a): [
                        conn.established,
                        len(conn.pong_sent),
                        self._rel(tick, conn.sync_served_tick),
                        conn.sync_defer_streak,
                        self._rel(tick, conn.sync_defer_last_tick),
                        conn.last_write_dropped,
                        # idle age drives the eviction machine: without
                        # it a 6-ticks-idle conn (evicts next tick)
                        # dedup-merges with a fresh one and the
                        # eviction subtree is never explored
                        self._rel(tick, c._last_activity.get(conn)),
                        # the requester's range-walk cursor (v8)
                        sorted(
                            (n, tuple(b)) for n, b in
                            conn.range_pending.items()
                        ),
                    ]
                    for a, conn in sorted(
                        c._actives.items(), key=lambda kv: str(kv[0])
                    )
                },
                "passives": sorted(
                    [str(conn.peer_addr), conn.established,
                     len(conn.pong_sent),
                     self._rel(tick, c._last_activity.get(conn))]
                    for conn in c._passives
                ),
                "peers": {
                    str(a): [st.fails, max(st.next_dial_tick - tick, 0)]
                    for a, st in sorted(
                        c._peers.items(), key=lambda kv: str(kv[0])
                    )
                    if st.fails or st.next_dial_tick > tick
                },
                # delta-interval state (v8): the sender's seq counter +
                # retransmit window, per-peer ack watermarks and dirty
                # flags, and the receiver's per-sender cursors/parks —
                # all protocol-relevant (a state differing only here
                # behaves differently on the next reconnect)
                "interval": [
                    c._delta_seq,
                    [[seq, self._sha(data)] for seq, data in c._delta_log],
                    sorted(
                        (str(a), st.acked, st.interval_dirty, st.reset_seq)
                        for a, st in c._peers.items()
                        if st.acked is not None or st.interval_dirty
                    ),
                    sorted(c._recv_cum.items()),
                    sorted(
                        (skey, tuple(sorted(ooo)))
                        for skey, ooo in c._recv_ooo.items()
                        if ooo
                    ),
                    len(c._range_queue),
                ],
                "held": [
                    [rank[ts], self._sha(data)] for ts, data, _keys in c._held
                ],
                # region topology state (v10): the gossiped region map
                # drives dial policy and relay roles
                "regions": sorted(c._regions.items()),
                # bridge failover (PR 15): per-address liveness ages
                # (capped at the demote bound + 1 — election only asks
                # "over or under", so finer ages would defeat dedup for
                # nothing), the elected bridge, and the repair relay
                # queue. Region-less instances skip all three (the
                # state exists but drives no behavior there).
                "bridge": [
                    sorted(
                        (a, min(tick - t, c._bridge_demote + 1))
                        for a, t in c._seen_tick.items()
                    ),
                    c._bridge_seen if c._bridge_seen != () else None,
                    [len(c._relay_queue), c._relay_queue_bytes],
                ] if c._region else None,
                "stats": sorted(c._stats.items()),
                "drops": sorted(c._drop_counts.items()),
                "msg_drops": sorted(c._msg_drops.items()),
                "sync": [
                    self._rel(tick, c._sync_rx_tick),
                    sorted(
                        (str(a), self._rel(tick, t))
                        for a, t in c._sync_req_tick.items()
                    ),
                    sorted(str(a) for a in c._sync_req_inflight),
                    len(c._sync_waiters),
                    c._sync_dump_inflight,
                    c._sync_defer_streak,
                    c._sync_serve_defer_total,
                    self._rel(tick, c._sync_defer_total_tick),
                    c._local_writes_seen,
                    None if c._defer_since_ms is None
                    else rank[c._defer_since_ms],
                ],
            }
        conns = {
            cid: {
                "closed": conn.closed,
                "links": {
                    d: [
                        [self._sha(f) for f in conn.link(d).outbox],
                        self._sha(bytes(conn.link(d).inbox)),
                        conn.link(d).closed,
                    ]
                    for d in ("fwd", "rev")
                },
            }
            for cid, conn in sorted(self.net.conns.items())
        }
        return {
            "config": self.config_name,
            "dbs": dbs,
            "instances": insts,
            "conns": conns,
            "partitions": sorted(sorted(p) for p in self.net.partitions),
            "down": sorted(self.down_groups),
            "used": sorted(self.used.items()),
            "writes_left": sorted(self.writes_left.items()),
            "bdecs_left": sorted(self.bdecs_left.items()),
            "mints_left": sorted(self.mints_left.items()),
            "boots": sorted(self.boot_count.items()),
            "tokens": [
                (g, sorted(vec.items()), sorted(floor.items()), boot)
                for g, vec, floor, boot in self.tokens
            ],
        }

    def state_hash(self) -> str:
        # repr, not json.dumps: canonical() builds every dict in sorted
        # insertion order, so repr is deterministic — and measurably
        # cheaper than the json encoder at tens of thousands of states
        return hashlib.sha256(repr(self.canonical()).encode()).hexdigest()
