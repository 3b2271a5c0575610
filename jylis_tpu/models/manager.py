"""Per-type repo manager: dispatch, help-on-failure, proactive flush.

Reference analog: RepoManagerCore (repo_manager.pony:36-108). The actor
boundary becomes the asyncio event loop plus a per-repo asyncio.Lock;
what this class keeps is the behavioral contract:

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
and the cluster heartbeat all proceed. The per-repo lock is what the
one-actor-per-type boundary becomes: every repo access (apply, cluster
converge, heartbeat flush) serialises through it, so repo state is
never touched concurrently with an offloaded drain. FIFO holds among
lock-taking paths only — host-only commands take a lock-free inline
fast path when no drain is active (see apply_async), which preserves
per-connection order (the reference's guarantee) while
cross-connection interleaving stays unordered as it always was.
Replies from
offloaded commands are buffered and replayed on the loop thread
(transports are not thread-safe). The sync ``apply`` path remains for
single-threaded callers (warmup, persistence restore, direct-drive
tests and benchmarks).
"""

from __future__ import annotations

import asyncio
import time

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
        self.help = help_obj
        self._clock = clock
        # per-Database commands-served tally (SYSTEM METRICS "cmds");
        # the native engine counts its own settles in its own tables
        self._served = served if served is not None else {}
        self._deltas_fn = None
        self._last_proactive = None
        self._shutdown = False
        self._lock = asyncio.Lock()
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
        self._s_apply = reg.seam("cluster.apply")
        self._s_flush = reg.seam("repo.flush")
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

    async def apply_async(self, resp, cmd: list[bytes]) -> None:
        """Serving path: device-bound commands offload to a thread under
        the repo lock; host-only commands run inline.

        Fast path: when the lock is free (a threaded drain ALWAYS holds
        it, and releases only on the loop thread) and the command needs
        no device offload, apply synchronously with no await at all —
        the event loop is single-threaded, so the inline apply is atomic.
        This can barge ahead of waiters queued on the lock, so per-repo
        FIFO holds only among lock-taking paths; cross-connection
        interleaving is unordered anyway (lattice ops commute) and
        per-connection order is preserved by the server's sequential
        awaits."""
        if self._shutdown:
            resp.err(SHUTDOWN_ERR)
            return
        if not self._lock.locked():
            may = getattr(self.repo, "may_drain", None)
            if may is None or not may(cmd[1:]):
                if self._apply_core(resp, cmd):
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
            t_wait = self._s_wait_serve.begin()
            async with self._lock:
                self._s_wait_serve.end(t_wait)
                if self._shutdown:
                    # shutdown won the lock race while we queued behind a
                    # drain: the final flush already ran — accepting now
                    # would acknowledge a write that never replicates
                    resp.err(SHUTDOWN_ERR)
                    return
                may = getattr(self.repo, "may_drain", None)
                if may is not None and may(cmd[1:]):
                    replay = _ReplayResp()
                    changed = await asyncio.to_thread(
                        self._apply_core, replay, cmd
                    )
                    replay.replay(resp)
                else:
                    changed = self._apply_core(resp, cmd)
                if changed:
                    self._maybe_proactive_flush()
        finally:
            self._inflight -= 1

    # keys converged per event-loop slice: a multi-thousand-key batch (a
    # sync dump chunk, a post-load flush) converged in one go blocks the
    # loop long enough to slip heartbeats and Pongs past peers'
    # idle-eviction windows — the connection churn then LOSES deltas
    # (fire-and-forget). Slicing under the same lock keeps liveness
    # traffic flowing between slices with identical lattice results.
    CONVERGE_SLICE = 256

    async def converge_async(self, batch) -> None:
        t_wait = self._s_wait_cluster.begin()
        async with self._lock:
            self._s_wait_cluster.end(t_wait)
            if self._shutdown:
                return  # fire-and-forget: late deltas re-deliver elsewhere
            batch = list(batch)
            meta = {"keys": len(batch)} if span.armed() else None
            for i in range(0, len(batch), self.CONVERGE_SLICE):
                # cluster.apply: the fold of one slice into the pending
                # dicts — no lock wait, no yield, not the drain below
                t_fold = self._s_apply.begin(None, meta)
                self.converge_deltas(batch[i : i + self.CONVERGE_SLICE])
                self._s_apply.end(t_fold)
                if i + self.CONVERGE_SLICE < len(batch):
                    await asyncio.sleep(0)  # let pings/pongs interleave
            # threshold drains run AFTER buffering, in a worker thread —
            # never inline on the event loop; the post-state check is
            # exact where any pre-batch prediction can miss per-row sizes
            overdue = getattr(self.repo, "drain_overdue", None)
            if overdue is not None and overdue():
                await asyncio.to_thread(self.repo.drain)

    async def flush_async(self, fn) -> None:
        t_wait = self._s_wait_cluster.begin()
        async with self._lock:
            self._s_wait_cluster.end(t_wait)
            # repos with banked native-queue work drain it in a worker
            # thread first (it can touch the device); the loop-side delta
            # flush then sees fully-applied state
            prep = getattr(self.repo, "prepare_flush", None)
            if prep is not None:
                await asyncio.to_thread(prep)
            self.flush_deltas(fn)

    def busy(self) -> bool:
        """True while a (possibly threaded) repo access holds the lock —
        the server's native fast path defers to Python while true."""
        return self._lock.locked()

    async def clean_shutdown_async(self) -> None:
        """Lock-holding shutdown: waits out any in-flight threaded drain,
        then stops intake and performs the final flush atomically."""
        self._shutdown = True  # reject commands queued behind the lock
        async with self._lock:
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

            note = self.registry.note_write_heat
            for key, _delta in batch:
                note(
                    self.name,
                    sync_bucket(
                        key if isinstance(key, bytes) else key.encode()
                    ),
                )
        self._deltas_fn((self.name, batch))

    def converge_deltas(self, batch) -> None:
        for key, delta in batch:
            self.repo.converge(key, delta)

    def clean_shutdown(self) -> None:
        self._shutdown = True
        prep = getattr(self.repo, "prepare_flush", None)
        if prep is not None:
            prep()
        if self._deltas_fn is not None:
            self.flush_deltas(self._deltas_fn)
