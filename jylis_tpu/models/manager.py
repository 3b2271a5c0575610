"""Per-type repo manager: dispatch, help-on-failure, proactive flush.

Reference analog: RepoManagerCore (repo_manager.pony:36-108). The actor
boundary becomes the asyncio event loop plus a per-repo lock (RepoLock);
what RepoManager keeps is the behavioral contract:

* shutdown flag rejects new commands with the SHUTDOWN error (:49-55),
* parse failure renders the repo's help text (:62-66),
* a mutating command triggers a proactive delta flush, throttled to at
  most once per 500 ms per repo (:68-84),
* flush_deltas registers the delta sink and drains if non-empty (:86-90),
* clean_shutdown stops intake and performs a final flush (:95-108).

Concurrency (SURVEY.md §7(c) host↔device pipelining): commands that will
hit the device (the repo's ``may_drain`` predicate) run in a worker
thread via ``asyncio.to_thread`` so a multi-millisecond drain never
stalls the event loop — other repos' commands, other client connections,
and the cluster heartbeat all proceed. The per-repo lock (``RepoLock``,
below) is what the one-actor-per-type boundary becomes: every repo
access (apply, cluster converge, heartbeat flush) serialises through it,
so repo state is never touched concurrently with an offloaded drain.

The lock's rule: a take that cannot yield while it holds — the server's
native burst (``RepoLock.acquire_all``), and the inline fast path of
``apply_async``, which needs no take at all — goes ahead whenever nobody
HOLDS the lock, sleepers or not; a release wakes EVERY sleeper, in
arrival order. So only a holder that keeps the lock across a yield (a
threaded drain, a cluster apply, a flush, a digest, a snapshot) can make
anyone sleep, and everyone who slept behind it is settled in the loop
iteration after it lets go. Among ``async with`` takers (the ones that
may hold across a yield) the lock is first come, first served. This
preserves per-connection order (the reference's guarantee: a connection
awaits its own commands one by one) while cross-connection interleaving
stays unordered as it always was (lattice operations commute).

Replies from offloaded commands are buffered and replayed on the loop
thread (transports are not thread-safe). The sync ``apply`` path remains
for single-threaded callers (warmup, persistence restore, direct-drive
tests and benchmarks).
"""

from __future__ import annotations

import asyncio
import time
from time import perf_counter

from ..obs import span
from ..utils.metrics import DEFAULT as _DEFAULT_REGISTRY
from .base import ParseError
from .help import respond_help


class _ReplayResp:
    """Records resp-protocol calls in a worker thread; replays them on the
    event-loop thread afterwards."""

    __slots__ = ("calls",)

    def __init__(self):
        self.calls: list = []

    def __getattr__(self, name):
        def record(*args):
            self.calls.append((name, args))

        return record

    def replay(self, resp) -> None:
        for name, args in self.calls:
            getattr(resp, name)(*args)


class RepoLock:
    """The repo lock: ``asyncio.Lock``'s surface (``async with``,
    ``locked()``) with a hand-off rule made for one event loop whose
    short holders never yield.

    Two kinds of take:

    * ``async with lock`` / ``acquire()`` — a holder that MAY keep the
      lock across a yield (threaded drain, cluster apply, flush, digest,
      dump, snapshot, shutdown). First come, first served: it goes
      straight in only when nobody holds the lock and nobody is in line;
      otherwise it sleeps at the end of the line.
    * ``RepoLock.take_all(locks)`` / ``acquire_all(locks)`` — a holder
      that releases before it yields (the server's native burst, over
      the locks of the types its commands name: any SUBSET of a
      database's locks, most often one). It takes every lock of its set
      at once whenever nobody HOLDS one of them, whoever is in line, and
      holds nothing while it sleeps: on the first held lock of the set
      it waits its turn in that lock's line and starts over.
      (``RepoManager.apply_async``'s inline fast path is the same kind
      with no take at all: it runs whenever ``locked()`` is false.)

    ``release()`` wakes EVERY sleeper, in arrival order; each retries
    when it runs, and one that finds the lock held again (a sleeper
    ahead of it took it and kept it) sleeps again IN ITS PLACE, before
    later arrivals. ``asyncio.Lock`` instead queued any taker behind a
    sleeper and woke one sleeper per release, one loop iteration later:
    once a drain had made the connections queue, every later burst
    queued behind them and the loop settled one command per iteration
    for good (PERF.md section 6, PR 25).

    Why nobody starves. Task steps run in the order their wake-ups were
    scheduled, and wake-ups are scheduled in line order, so a sleeper
    runs before everyone behind it. When it runs, the lock can be held
    only by (a) an ``acquire()`` taker that was AHEAD of it in line — a
    later one sleeps behind it, because the line is not empty — or (b)
    an ``acquire_all`` taker, which cannot be observed holding: it let
    go within the task step it took in. So a sleeper's wait is at most
    the holds of those ahead of it when it arrived, whatever stream of
    bursts and later long takers follows; a burst sleeps only behind
    holders that yield, and retries on each of their releases. (The one
    burst that yields while holding is a drill's: an armed
    ``native.scan_apply`` sleep. It adds its injected sleep to the
    bound, nothing else.) The argument is made lock by lock and never
    speaks of the size of a burst's set: two bursts whose sets overlap
    cannot both be inside a take, a burst asleep on one lock holds none
    of the others of its set, and a lock outside a burst's set is
    neither read nor written by it.

    Why a holder of EVERY lock still excludes every burst
    (``Database.all_locks``: the shutdown snapshot, a joining peer's
    first digest). It takes the locks one by one, each the long way,
    and keeps them across yields; a burst takes its set only when all
    of the set is free and holds nothing while it waits, so it can
    neither sit on a lock the snapshot waits for while waiting for one
    the snapshot has (no deadlock, whatever locks the bursts asleep at
    that moment sleep on), nor run on a type whose lock the snapshot
    holds; and once all are held no burst's set, being a subset, is
    free.

    A sleeper that leaves the line without taking (cancelled) wakes the
    rest if the lock is free: a taker that lined up behind it after the
    last release has nobody else to wake it.
    """

    __slots__ = ("_held", "_line", "_tickets")

    def __init__(self):
        self._held = False
        # ticket -> the future its sleeper awaits; a dict keeps arrival
        # order, and a sleeper that sleeps again re-uses its ticket
        self._line: dict[int, asyncio.Future] = {}
        self._tickets = 0

    def locked(self) -> bool:
        """True exactly while somebody holds the lock."""
        return self._held

    async def acquire(self) -> None:
        if not self._held and not self._line:
            self._held = True
            return
        self._tickets = ticket = self._tickets + 1
        new_future = asyncio.get_running_loop().create_future
        try:
            while True:
                self._line[ticket] = fut = new_future()
                await fut
                if not self._held:
                    self._held = True
                    return
        finally:
            self._line.pop(ticket, None)
            if not self._held:  # cancelled: left the line without taking
                self._wake()

    def release(self) -> None:
        if not self._held:
            raise RuntimeError("RepoLock is not held")
        self._held = False
        if self._line:
            self._wake()

    def _wake(self) -> None:
        for fut in self._line.values():
            if not fut.done():
                fut.set_result(None)

    async def __aenter__(self) -> None:
        await self.acquire()

    async def __aexit__(self, exc_type, exc, tb) -> None:
        self.release()

    @staticmethod
    def take_all(locks) -> bool:
        """`acquire_all` for the caller that wants to know BEFORE it
        sleeps (the server times the sleep and nothing else): takes
        every lock when nobody holds one and says True, else takes
        nothing. Never yields."""
        for lock in locks:
            if lock._held:
                return False
        for lock in locks:
            lock._held = True
        return True

    @staticmethod
    async def acquire_all(locks) -> bool:
        """Take every lock of ``locks`` or none — for a holder that
        releases (``release_all``) before it yields. No yield when all
        are free; True when it had to sleep (what the caller checked
        before the call may no longer hold)."""
        slept = False
        while not RepoLock.take_all(locks):
            # wait our turn in the first held lock's line, holding
            # nothing; letting go again wakes whoever lined up behind us
            lock = next(lock for lock in locks if lock._held)
            await lock.acquire()
            lock.release()
            slept = True
        return slept

    @staticmethod
    def release_all(locks) -> None:
        for lock in locks:
            lock.release()


class _Hold:
    """``async with _Hold(mgr, wait, held)``: the repo lock taken the
    long way, by a holder that may keep it across a yield and so can be
    SEEN holding it (``busy()`` true in somebody else's task step). Its
    wait and its hold are a span each: ``wait`` (a lock.wait_* seam, or
    None) runs wanting -> holding; ``held`` (a lock.hold_* seam) runs
    holding -> the body is left, and the release follows at once. With
    ``seen=False`` the hold's span starts only where the body calls
    `seen`: a client command that found the lock held takes it this way
    too and most often applies inline, lets go within the task step it
    took in and was never seen. While profiling is armed the hold's
    annotation carries the repo's type and, for a client's command, its
    verb."""

    __slots__ = ("_mgr", "_wait", "_held", "_cmd", "_eager", "_tok")

    def __init__(self, mgr, wait, held, cmd=None, seen=True):
        self._mgr, self._wait, self._held, self._cmd = mgr, wait, held, cmd
        self._eager = seen
        self._tok = None

    async def __aenter__(self) -> _Hold:
        lock, wait = self._mgr._lock, self._wait
        if wait is None:
            await lock.acquire()
        else:
            t_wait = wait.begin()
            await lock.acquire()
            wait.end(t_wait)
        if self._eager:
            self.seen()
        return self

    def seen(self) -> None:
        meta = None
        if span.armed():
            meta = {"type": self._mgr.name}
            if self._cmd is not None and len(self._cmd) > 1:
                meta["verb"] = self._cmd[1].decode("ascii", "replace")
        self._tok = self._held.begin(None, meta)

    async def __aexit__(self, exc_type, exc, tb) -> None:
        if self._tok is not None:
            self._held.end(self._tok)
        self._mgr._lock.release()


PROACTIVE_FLUSH_INTERVAL = 0.5  # seconds; repo_manager.pony:80

SHUTDOWN_ERR = "SHUTDOWN (server is shutting down, rejecting all requests)"


class RepoManager:
    def __init__(
        self,
        name: str,
        repo,
        help_obj,
        clock=time.monotonic,
        served=None,
        registry=None,
    ):
        self.name = name
        self.repo = repo
        # a repo that folds a whole slice of foreign deltas in one call
        # (the counters) is handed the slice; the others take it a key
        self._converge_batch = getattr(repo, "converge_batch", None)
        self.help = help_obj
        self._clock = clock
        # per-Database commands-served tally (SYSTEM METRICS "cmds");
        # the native engine counts its own settles in its own tables
        self._served = served if served is not None else {}
        self._deltas_fn = None
        self._last_proactive = None
        self._shutdown = False
        self._lock = RepoLock()
        # admission control (Database.set_admission_cap): commands of
        # THIS class queued behind the repo lock past the cap are
        # refused with a typed BUSY instead of queuing without bound —
        # a hot key whose drains back the lock up degrades its own
        # command class, never the node. 0 = off (default). The
        # registry counts refusals (SERVING busy_refusals).
        self.admission_cap = 0
        # the owning Database's registry; a standalone manager records
        # into the process DEFAULT (utils/metrics.resolve_registry's
        # policy). The host-time spans of this repo's lock and flush
        # (obs/span.py; exact starts and ends in docs/observability.md):
        self.registry = reg = registry or _DEFAULT_REGISTRY
        self._s_wait_serve = reg.seam("lock.wait_serve")
        self._s_wait_cluster = reg.seam("lock.wait_cluster")
        self._s_hold_serve = reg.seam("lock.hold_serve")
        self._s_hold_converge = reg.seam("lock.hold_converge")
        self._s_hold_flush = reg.seam("lock.hold_flush")
        self._s_hold_sync = reg.seam("lock.hold_sync")
        self._s_apply = reg.seam("cluster.apply")
        self._s_flush = reg.seam("repo.flush")
        # serve.py_apply: _apply_core ON THE LOOP THREAD, what a
        # Python-path command's apply and reply render cost the loop
        # (lock wait, thread hops and the proactive flush excluded)
        self._h_py_apply = reg.hist("serve.py_apply")
        self._inflight = 0
        # delta write-ahead journal (journal/journal.py), attached via
        # Database.set_journal: every flushed batch is handed to the
        # journal's writer thread before it reaches the network sink —
        # the hand-off itself runs under the same per-repo serialisation
        # the flush runs under (flush paths execute on the event loop
        # even when the apply was threaded), so journal order per repo
        # matches flush order
        self.journal = None

    def apply(self, resp, cmd: list[bytes]) -> None:
        """cmd includes the routing word (cmd[0] == data type name).
        Single-threaded path — see module docstring."""
        if self._shutdown:
            resp.err(SHUTDOWN_ERR)
            return
        if self._apply_core(resp, cmd):
            self._maybe_proactive_flush()

    def _apply_core(self, resp, cmd: list[bytes]) -> bool:
        self._served[self.name] = self._served.get(self.name, 0) + 1
        try:
            return self.repo.apply(resp, cmd[1:])
        except ParseError:
            respond_help(resp, self.help.render(cmd[1:]))
            return False

    def _apply_on_loop(self, resp, cmd: list[bytes]) -> bool:
        """`_apply_core` from a coroutine, timed as serve.py_apply."""
        if not self.registry.enabled:
            return self._apply_core(resp, cmd)
        t0 = perf_counter()
        changed = self._apply_core(resp, cmd)
        self._h_py_apply.record(perf_counter() - t0)
        return changed

    def hold_sync(self) -> _Hold:
        """The lock for a digest, a tree, a range or state dump, the
        shutdown snapshot (models/database.py): lock.hold_sync."""
        return _Hold(self, None, self._s_hold_sync)

    async def apply_async(self, resp, cmd: list[bytes]) -> None:
        """Serving path: device-bound commands offload to a thread under
        the repo lock; host-only commands run inline.

        Fast path: when the lock is free (a threaded drain ALWAYS holds
        it, and releases only on the loop thread) and the command needs
        no device offload, apply synchronously with no await at all —
        the event loop is single-threaded, so the inline apply is atomic.
        This goes ahead of sleepers in the lock's line (RepoLock: a
        holder that cannot yield never waits for a lock nobody holds);
        cross-connection interleaving is unordered anyway (lattice ops
        commute) and per-connection order is preserved by the server's
        sequential awaits."""
        if self._shutdown:
            resp.err(SHUTDOWN_ERR)
            return
        if not self._lock.locked():
            may = getattr(self.repo, "may_drain", None)
            if may is None or not may(cmd[1:]):
                if self._apply_on_loop(resp, cmd):
                    self._maybe_proactive_flush()
                return
        if self.admission_cap and self._inflight >= self.admission_cap:
            # only lock-queued commands count as inflight (the inline
            # fast path above never queues), so the cap binds exactly
            # when this class is backed up behind its own drains
            self.registry.note_serving("busy_refusals")
            self.registry.trace_event("serving", "busy", "", self.name)
            resp.err(
                f"BUSY ({self.name} admission cap {self.admission_cap} "
                "reached; this command class is backed up — retry)"
            )
            return
        self._inflight += 1
        try:
            # lock.wait_serve: wanting the repo lock to holding it —
            # queueing behind a drain or a cluster apply, not service
            async with _Hold(
                self, self._s_wait_serve, self._s_hold_serve, cmd, seen=False
            ) as hold:
                if self._shutdown:
                    # shutdown won the lock race while we queued behind a
                    # drain: the final flush already ran — accepting now
                    # would acknowledge a write that never replicates
                    resp.err(SHUTDOWN_ERR)
                    return
                may = getattr(self.repo, "may_drain", None)
                if may is not None and may(cmd[1:]):
                    # lock.hold_serve: the hold that others see, of
                    # which drain.<TYPE> is a part — the hop to the
                    # worker thread, its wait for the GIL, the drain, the
                    # completion's wait for the loop to come round, the
                    # replay, the proactive flush
                    hold.seen()
                    replay = _ReplayResp()
                    changed = await asyncio.to_thread(
                        self._apply_core, replay, cmd
                    )
                    replay.replay(resp)
                else:
                    changed = self._apply_on_loop(resp, cmd)
                if changed:
                    self._maybe_proactive_flush()
        finally:
            self._inflight -= 1

    # keys converged per slice, and how long the fold may run before it
    # yields: a multi-thousand-key batch (a sync dump chunk, a post-load
    # flush) converged in one go blocks the loop long enough to slip
    # heartbeats and Pongs past peers' idle-eviction windows — the
    # connection churn then LOSES deltas (fire-and-forget). Yielding
    # under the same lock keeps liveness traffic flowing with identical
    # lattice results. The yield is by TIME, checked after each slice: a
    # lock held across a yield is one every client command of the type
    # then meets held (its native burst sleeps in the lock's line; a
    # burst of another type runs beside it), so
    # a fold that is over in a couple of milliseconds — a peer's 500 ms
    # flush of 1 KB registers — runs through, and only a fold that
    # would hold the loop longer yields.
    CONVERGE_SLICE = 256
    CONVERGE_RUN_S = 0.002

    async def converge_async(self, batch) -> None:
        # lock.hold_converge: the sliced fold, its yields, the overdue
        # drain's thread — all of a cluster apply's hold
        async with _Hold(self, self._s_wait_cluster, self._s_hold_converge):
            if self._shutdown:
                return  # fire-and-forget: late deltas re-deliver elsewhere
            batch = list(batch)
            meta = {"keys": len(batch)} if span.armed() else None
            t_run = self._clock()
            for i in range(0, len(batch), self.CONVERGE_SLICE):
                # cluster.apply: the fold of one slice into the pending
                # dicts — no lock wait, no yield, not the drain below
                t_fold = self._s_apply.begin(None, meta)
                self.converge_deltas(batch[i : i + self.CONVERGE_SLICE])
                self._s_apply.end(t_fold)
                if (
                    i + self.CONVERGE_SLICE < len(batch)
                    and self._clock() - t_run >= self.CONVERGE_RUN_S
                ):
                    await asyncio.sleep(0)  # let pings/pongs interleave
                    t_run = self._clock()
            # threshold drains run AFTER buffering, in a worker thread —
            # never inline on the event loop; the post-state check is
            # exact where any pre-batch prediction can miss per-row sizes
            overdue = getattr(self.repo, "drain_overdue", None)
            if overdue is not None and overdue():
                await asyncio.to_thread(self.repo.drain)

    async def flush_async(self, fn) -> None:
        async with _Hold(self, self._s_wait_cluster, self._s_hold_flush):
            # repos with banked native-queue work drain it in a worker
            # thread first (it can touch the device); the loop-side delta
            # flush then sees fully-applied state
            prep = getattr(self.repo, "prepare_flush", None)
            if prep is not None:
                await asyncio.to_thread(prep)
            self.flush_deltas(fn)

    def busy(self) -> bool:
        """True while a (possibly threaded) repo access holds the lock.
        The server's route (server.py `_capped_busy`) reads it of the
        engine's managers only under `admission_cap`: a chunk that
        arrives while a capped lock is held takes the per-repo Python
        path, so that its wait counts in `_inflight`. Without a cap a
        native burst reads the locks themselves (`RepoLock.take_all`):
        a round whose first command names THIS type sleeps for the lock,
        a round of another type never meets it."""
        return self._lock.locked()

    async def clean_shutdown_async(self) -> None:
        """Lock-holding shutdown: waits out any in-flight threaded drain,
        then stops intake and performs the final flush atomically."""
        self._shutdown = True  # reject commands queued behind the lock
        async with _Hold(self, None, self._s_hold_flush):
            prep = getattr(self.repo, "prepare_flush", None)
            if prep is not None:  # banked native-queue writes must ship
                await asyncio.to_thread(prep)
            if self._deltas_fn is not None:
                self.flush_deltas(self._deltas_fn)

    def _maybe_proactive_flush(self) -> None:
        if self._deltas_fn is None:
            return
        now = self._clock()
        if (
            self._last_proactive is None
            or now - self._last_proactive >= PROACTIVE_FLUSH_INTERVAL
        ):
            self._flush()
            self._last_proactive = now

    def _flush(self) -> None:
        """One delta flush, unconditional like the reference's proactive
        path (:81). repo.flush: the export of the dirty rows and `_emit`
        — journal hand-off, write heat, the sink's broadcast encode —
        as ONE span of the caller's (the loop's) time."""
        meta = {"keys": self.repo.deltas_size()} if span.armed() else None
        t_flush = self._s_flush.begin(None, meta)
        self._emit(self.repo.flush_deltas())
        self._s_flush.end(t_flush)

    def flush_deltas(self, fn) -> None:
        """Heartbeat entry point: registers the sink, drains if non-empty."""
        self._deltas_fn = fn
        if self.repo.deltas_size() > 0:
            self._flush()

    def _emit(self, batch) -> None:
        """Every flushed batch leaves through here: journal first (a
        batch that reached peers' lattices but not our disk is exactly
        the crash-loss gap the journal closes), then the network sink.
        The journal append only enqueues — encode/write/fsync happen on
        the journal's writer thread, off the serving path."""
        if self.journal is not None:
            self.journal.append(self.name, batch)
        if self.registry.enabled and batch:
            # per-digest-tree-bucket write heat: count each flushed key
            # against its sync_bucket (the SAME sha256(key)[0] the
            # anti-entropy digest tree shards by, database.py), so
            # SYSTEM OBSERVE can show where writes concentrate in the
            # tree — the placement telemetry ROADMAP item 3 needs.
            # Lazy import: database.py imports this module at load.
            from .database import sync_bucket

            from ..cluster.codec import keys_of

            note = self.registry.note_write_heat
            for key in keys_of(batch):
                note(
                    self.name,
                    sync_bucket(
                        key if isinstance(key, bytes) else key.encode()
                    ),
                )
        self._deltas_fn((self.name, batch))

    def converge_deltas(self, batch) -> None:
        if self._converge_batch is not None:
            self._converge_batch(batch)
            return
        for key, delta in batch:
            self.repo.converge(key, delta)

    def clean_shutdown(self) -> None:
        self._shutdown = True
        prep = getattr(self.repo, "prepare_flush", None)
        if prep is not None:
            prep()
        if self._deltas_fn is not None:
            self.flush_deltas(self._deltas_fn)
