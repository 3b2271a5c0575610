"""Process entry point and signal-driven clean shutdown.

Reference analog: main.pony:1-15 (wire Config -> System -> Database ->
Server -> Cluster -> Dispose in that order, print the logo and listen
addresses) and dispose.pony:3-33 (SIGINT/SIGTERM -> drain deltas to peers
-> stop server and cluster -> exit). Run as ``python -m jylis_tpu``.
"""

from __future__ import annotations

import asyncio
import os
import signal
import sys
import time

import jax

from . import faults
from . import persist
from . import journal as journal_mod
from .cluster import Cluster
from .models import database as database_mod
from .models.database import Database
from .obs import loop as loop_mod
from .obs import span
from .server.server import Server
from .system import System
from .utils.config import config_from_cli
from .utils.logo import LOGO


class Dispose:
    """Idempotent clean-shutdown driver (dispose.pony:12-19): first drain
    every repo's remaining deltas to peers, snapshot if configured, then
    stop the listeners."""

    def __init__(
        self,
        database: Database,
        server: Server,
        cluster: Cluster,
        snapshot_path: str = "",
        log=None,
        journal=None,
    ):
        self._database = database
        self._server = server
        self._cluster = cluster
        self._snapshot_path = snapshot_path
        self._log = log
        self._journal = journal
        self._disposing = False
        self._shutdown_task: asyncio.Task | None = None
        self.snapshot_task: asyncio.Task | None = None  # online snapshot loop
        # the loop's in-flight write future: cancelling the task does NOT
        # stop a to_thread worker, so shutdown must await this too
        self.snapshot_inflight: dict = {"write": None}
        self.done = asyncio.Event()

    def on_signal(self) -> None:
        loop = asyncio.get_running_loop()
        for sig in (signal.SIGINT, signal.SIGTERM):
            loop.add_signal_handler(sig, self.dispose)

    def dispose(self) -> None:
        if self._disposing:
            return
        self._disposing = True
        # signal callback: stop intake NOW (sync-safe), then run the
        # lock-holding shutdown sequence as a task — the final flush and
        # snapshot must serialise with any in-flight threaded drain.
        # The loop holds only a weak ref to tasks; keep a strong one so
        # the shutdown (final flush + snapshot) can't be collected mid-run
        self._database.stop_intake()
        self._shutdown_task = asyncio.get_running_loop().create_task(
            self._shutdown()
        )

    async def _shutdown(self) -> None:
        # device drains can raise at shutdown; the listeners must still stop
        # and `done` must still be set, or a second SIGINT would no-op
        # (_disposing already True) and the process would only die to SIGKILL
        try:
            # the online snapshot loop must be fully stopped before the
            # shutdown snapshot runs: both write path.tmp, and a
            # concurrent writer would corrupt the rename source. Two
            # steps: cancel the loop task, then await any write worker
            # it had in flight (task cancellation cannot stop a thread)
            if self.snapshot_task is not None:
                self.snapshot_task.cancel()
                try:
                    await self.snapshot_task
                except asyncio.CancelledError:
                    pass
                inflight = self.snapshot_inflight.get("write")
                if inflight is not None:
                    await asyncio.wait([inflight])
            # final flush rides broadcast_deltas; per-repo locks wait out
            # threaded drains and fence off late-queued commands
            await self._database.clean_shutdown_async()
            if self._snapshot_path:
                try:
                    async with self._database.all_locks():
                        await asyncio.to_thread(
                            persist.save_snapshot,
                            self._database,
                            self._snapshot_path,
                        )
                    if self._journal is not None:
                        # the shutdown snapshot (final flush included)
                        # supersedes the whole journal: retire it so the
                        # next boot replays nothing. On snapshot failure
                        # we skip this and the journal stays — it is then
                        # the only copy of the unsnapshotted deltas.
                        await asyncio.to_thread(self._journal.rotate_begin)
                        await asyncio.to_thread(self._journal.rotate_commit)
                except Exception as e:  # jlint: broad-ok — the shutdown
                    # snapshot dumps every repo through device drains,
                    # which can raise anything from OSError to XLA
                    # runtime errors; whatever it was, it is logged and
                    # the listeners below must still stop (a second
                    # SIGINT no-ops, so failing here would hang the node)
                    if self._log is not None:
                        self._log.err() and self._log.e(f"snapshot failed: {e}")
            # after the final drains (snapshot dump included) so the report
            # covers them and no profiler trace restarts behind our back
            if self._log is not None:
                self._log.info() and self._log.i(
                    f"merge metrics: {self._database.metrics.report()}"
                )
                busy = self._database.metrics.hist("loop.busy")
                self._log.info() and self._log.i(
                    f"event loop: {busy.count} iterations, busy "
                    f"{busy.total:.1f}s, cpu "
                    f"{self._database.metrics.loop_cpu_s():.1f}s"
                )
                self._log.info() and self._log.i(
                    f"device state: {device_state_summary(self._database)}"
                )
                self._log.info() and self._log.i(
                    f"device memory: {device_memory_summary()}"
                )
            # a trace window left open (SYSTEM PROFILE START) is written
            # now; stop_trace blocks on the file, so off the loop
            await asyncio.to_thread(span.stop_window)
        finally:
            if self._journal is not None:
                # close() joins the writer thread and fsyncs — blocking
                # work (jlint JL101): run it off the loop so the server/
                # cluster dispose below (and any last client goodbyes)
                # are not held behind the disk. Its final flush/fsync can
                # raise (full disk at shutdown); the listeners below must
                # still stop and `done` must still be set, or the node
                # hangs until SIGKILL.
                try:
                    await asyncio.to_thread(self._journal.close)
                except OSError as e:
                    if self._log is not None:
                        self._log.err() and self._log.e(
                            f"journal close failed: {e}"
                        )
            self._cluster.dispose()
            await self._server.dispose()
            self.done.set()


async def run(argv: list[str] | None = None) -> None:
    config = config_from_cli(argv)
    if config.failpoints:
        # flag arming lands on top of any JYLIS_FAILPOINTS env arming
        # (faults.py parses the env at import); same spec syntax
        faults.arm_spec(config.failpoints)
    system = System(config)
    t_boot = time.perf_counter()
    jax.devices()  # backend init (seconds on a TPU), timed apart from ...
    t_warm = time.perf_counter()
    database_mod.warmup()  # compile serving kernels before going live
    # ... compile (or compile-cache load) seconds: the part of boot the
    # persistent cache (jylis_tpu/__init__.py) exists to remove
    config.log.info() and config.log.i(
        f"warmup: backend up in {t_warm - t_boot:.2f}s, "
        f"serving kernels ready in {time.perf_counter() - t_warm:.2f}s"
    )
    # (warmup's throwaway Database records its compile-time drains into
    # its OWN registry, so the serving registry starts clean by
    # construction — the old process-global clear() is gone with the
    # globals it cleared)
    # jlint: blocking-ok — pre-serving boot; warmup above already built
    # and memoised the native lib, so this resolves from cache
    database = Database(
        identity=config.addr.hash64(), system_repo=system.repo
    )
    # the loop's own time (loop.busy, jylis_loop_cpu_seconds_total)
    # records into this node's registry from here on (obs/loop.py)
    loop_mod.attach(database.metrics)
    # session-guarantee + admission-control knobs (docs/sessions.md)
    database.session_wait_ms = config.session_wait_ms
    database.set_admission_cap(config.admission_cap)
    # overload armor (admission.py, docs/operations.md "Overload"):
    # node-wide per-class shedding + the queued-bytes hard bound
    database.set_admission(
        config.admission_policy, config.admission_queue_bytes
    )
    # UJSON residency by size (--ujson-resident-min-leaves, 0 = by
    # fan-in only): set before recovery, which admits what it restores
    database.set_ujson_resident_min(config.ujson_resident_min_leaves)
    # fleet-convergence SLO thresholds for the provenance-span folds
    # (obs/jtrace.py; validated by config_from_cli, defensive here for
    # direct Config() drives in tests)
    database.metrics.spans.set_slo_ms(
        int(s)
        for s in getattr(config, "converge_slo_ms", "").split(",")
        if s.strip()
    )
    log = config.log

    snapshot_path = ""
    journal = None
    # boot-path disk I/O below (makedirs / snapshot restore / journal
    # open) runs before the server or cluster listeners exist: the loop
    # has no clients to stall, and sequencing recovery before serving is
    # the point — each site carries its own suppression
    if config.data_dir:
        # jlint: blocking-ok — pre-serving boot, no clients on the loop
        os.makedirs(config.data_dir, exist_ok=True)
        snapshot_path = os.path.join(config.data_dir, persist.SNAPSHOT_NAME)
        # restore EVERY snapshot present (the node's own plus any
        # `snapshot.lane<k>.jylis` an older multi-lane node left):
        # restore is lattice convergence, so overlap is a no-op. Only
        # the OWN file is ever written or moved aside.
        for spath in persist.list_snapshots(config.data_dir):
            try:
                n = persist.load_snapshot(database, spath)
                log.info() and log.i(
                    f"snapshot restored ({n} type batches, {spath})"
                )
            except persist.SnapshotError as e:
                log.err() and log.e(f"snapshot not restored: {e}")
                if spath != snapshot_path:
                    continue
                # preserve the unreadable file: the next clean shutdown will
                # write snapshot_path fresh, and overwriting the only copy
                # of un-restored data would destroy it
                aside = spath + ".unreadable"
                try:
                    # jlint: blocking-ok — pre-serving boot recovery
                    os.replace(spath, aside)
                    log.err() and log.e(f"moved aside to {aside}")
                except OSError:
                    pass
        if config.journal:
            # recovery ordering: snapshot first, then the journal tail —
            # though lattice join makes the order a formality (overlap
            # between snapshot and journal converges to the same state).
            # Merge replay: the own segment with truncation/move-aside,
            # any lane-named segment an older node left read-only.
            journal_path = os.path.join(
                config.data_dir, journal_mod.SEGMENT_NAME
            )
            n = journal_mod.recover_all(
                database, config.data_dir, journal_path, log
            )
            if n:
                log.info() and log.i(f"journal replayed ({n} delta batches)")
            journal = journal_mod.Journal(
                journal_path,
                fsync=config.journal_fsync,
                fsync_interval=config.journal_fsync_interval,
                max_bytes=config.journal_max_bytes,
                registry=database.metrics,
            )
            journal.open()  # jlint: blocking-ok (pre-serving boot)
            database.set_journal(journal)

    # the drain programs whose shapes depend on the recovered capacity
    # (database.warmup above compiled them at the default one)
    t_warm = time.perf_counter()
    database.warm_drain_shapes()
    log.info() and log.i(
        "warmup: threshold drains at the recovered capacity ready in "
        f"{time.perf_counter() - t_warm:.2f}s"
    )

    server = Server(config, database)
    # jlint: blocking-ok — Cluster construction reads/writes the tiny
    # boot-epoch sidecar (pre-serving boot, no clients on the loop yet;
    # cluster.py Cluster._boot_epoch)
    cluster = Cluster(config, database)
    await server.start()
    # SYSTEM TOPOLOGY advertises the node's RESP port (cluster-aware
    # client discovery, client.py) — known only after listen
    cluster.resp_port = int(server.port)
    await cluster.start()
    metrics_http = None
    if config.metrics_port:
        # opt-in Prometheus endpoint (obs/prom.py): the SYSTEM METRICS
        # surface as text exposition, scrapeable without a Redis client
        from .obs.prom import MetricsHTTP

        metrics_http = MetricsHTTP(
            database, max(config.metrics_port, 0), log
        )
        await metrics_http.start()
    dispose = Dispose(database, server, cluster, snapshot_path, log, journal)
    dispose.on_signal()

    if snapshot_path and (config.snapshot_interval > 0 or journal is not None):
        dispose.snapshot_task = asyncio.create_task(
            _snapshot_loop(
                database, snapshot_path, config.snapshot_interval, log,
                dispose.snapshot_inflight, journal,
            )
        )

    print(LOGO)
    from . import __version__

    log.info() and log.i(f"jylis-tpu version: {__version__}")
    log.info() and log.i(f"device: {device_summary()}")
    if database.native_engine is not None:
        log.info() and log.i("serving engine: native")
    else:
        # lib() returned None: no toolchain, or the g++ build failed (its
        # stderr is above). Correct but several times slower — say so.
        log.warn() and log.w(
            "serving engine: python tables (native library unavailable)"
        )
    log.info() and log.i(f"cluster address: {config.addr}")
    log.info() and log.i(f"serving clients on port: {server.port}")
    if metrics_http is not None:
        log.info() and log.i(f"metrics endpoint on port: {metrics_http.port}")
    try:
        await dispose.done.wait()
    except BaseException:  # jlint: broad-ok — re-raised immediately;
        # unclean shutdown: dump the structured trace ring to stderr —
        # the node's own account of its final seconds, which the
        # now-dead SYSTEM TRACE command can no longer serve
        _dump_trace(database, log)
        raise
    finally:
        if metrics_http is not None:
            await metrics_http.dispose()


def device_summary() -> str:
    """What the drains run on, as jax reports it: the boot log's device
    line (chip_smoke.py reads it back — nothing else tells an operator
    that a node came up on the CPU backend instead of the chip)."""
    from .parallel import serving_mesh

    devs = jax.devices()
    mesh = serving_mesh()
    shape = "none" if mesh is None else "x".join(
        f"{k}={v}" for k, v in mesh.shape.items()
    )
    return (
        f"platform={devs[0].platform} kind={devs[0].device_kind!r} "
        f"count={len(devs)} mesh={shape}"
    )


def device_state_summary(database) -> str:
    """Every device-backed keyspace's widest plane and how many devices
    EVERY plane of it is split over — on a multi-chip host the proof
    that the mesh path shards (not: everything on the first device)."""
    return "; ".join(
        f"{name} {'x'.join(map(str, shape))} over {n} device(s)"
        for name, shape, n in database.device_layout()
    ) or "none"


def device_memory_summary() -> str:
    """Per-device live and peak HBM bytes where the backend reports them
    (XLA:CPU reports nothing)."""
    parts = []
    for d in jax.local_devices():
        stats = d.memory_stats() or {}
        parts.append(
            f"dev{d.id} in_use={stats.get('bytes_in_use', 'unreported')} "
            f"peak={stats.get('peak_bytes_in_use', 'unreported')}"
        )
    return "; ".join(parts)


def _dump_trace(database, log) -> None:
    try:
        entries = database.metrics.trace.dump()
        if entries:
            from .obs.trace import TraceRing

            print(f"--- trace ring ({len(entries)} events) ---", file=sys.stderr)
            for entry in entries:
                print(TraceRing.format(entry), file=sys.stderr)
    except Exception as e:  # jlint: broad-ok — the trace dump is
        # best-effort post-mortem output; failing to render it must not
        # mask the exception that killed the node
        log.err() and log.e(f"trace dump failed: {e!r}")


async def _snapshot_loop(
    database, path: str, interval: float, log, inflight: dict, journal=None
) -> None:
    """Online snapshots while serving (extension over shutdown-only
    persistence — a crash otherwise loses everything since boot). Each
    type dumps under its own repo lock with device touches in a worker
    thread (Database.dump_state_async, the bootstrap-sync dump), so
    serving never pauses globally; cross-type skew is CRDT-safe because
    restore is lattice convergence. The write is atomic, so a crash
    mid-snapshot keeps the previous file.

    With a journal attached, this loop is also the compaction driver:
    it wakes EARLY when the journal crosses its size threshold (the
    rotate_notify hook), rotates the active segment aside FIRST — so
    every delta flushed after the cut lands in the fresh segment and the
    snapshot dumped below covers everything before it — and retires the
    old segment only after the snapshot write succeeds. A failure or
    crash anywhere in between leaves the ``.retiring`` segment for boot
    recovery; the next rotation folds the segments together. With
    ``--snapshot-interval 0`` (and a journal), snapshots happen ONLY on
    size-triggered compaction.

    The write future is published through ``inflight["write"]`` until it
    completes: if this task is cancelled mid-write, the worker thread
    runs on, and Dispose awaits the future before the shutdown snapshot
    touches the same tmp file."""
    rotate_event = asyncio.Event()
    if journal is not None:
        loop = asyncio.get_running_loop()
        # appends can come from the loop or (in direct drives) elsewhere;
        # call_soon_threadsafe is correct from both
        journal.rotate_notify = lambda: loop.call_soon_threadsafe(
            rotate_event.set
        )
        # a segment already oversized at boot (a crash beat the previous
        # compaction) — or one that crossed the threshold before this
        # hook existed — never re-asks: check once at install time
        if journal.needs_rotation():
            rotate_event.set()
    while True:
        if journal is None:
            await asyncio.sleep(interval)
        else:
            try:
                await asyncio.wait_for(
                    rotate_event.wait(),
                    timeout=interval if interval > 0 else None,
                )
            except asyncio.TimeoutError:
                pass
            rotate_event.clear()
        try:
            if journal is not None:
                await asyncio.to_thread(journal.rotate_begin)
            batches = await database.dump_state_async()
            fut = asyncio.ensure_future(
                asyncio.to_thread(persist.write_snapshot, batches, path)
            )
            inflight["write"] = fut
            fut.add_done_callback(
                lambda f: inflight.__setitem__("write", None)
                if inflight.get("write") is f
                else None
            )
            await asyncio.shield(fut)
            if journal is not None:
                await asyncio.to_thread(journal.rotate_commit)
            log.debug() and log.d(f"online snapshot written: {path}")
        except asyncio.CancelledError:
            raise
        except Exception as e:  # jlint: broad-ok — one failed online
            # snapshot (full disk, a device drain raising mid-dump) must
            # not kill the loop that would take the NEXT one; logged, and
            # the journal keeps the unsnapshotted deltas either way
            log.err() and log.e(f"online snapshot failed: {e}")


def main(argv: list[str] | None = None) -> None:
    try:
        # the node's loop is a selector loop with a timing selector
        # (obs/loop.py)
        asyncio.run(run(argv), loop_factory=loop_mod.new_event_loop)
    except KeyboardInterrupt:
        pass


if __name__ == "__main__":
    main(sys.argv[1:])
