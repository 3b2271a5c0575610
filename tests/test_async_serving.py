"""Event-loop liveness under device drains (SURVEY.md §7(c)).

A slow drain on one repo must not stall the loop: unrelated repos keep
serving, the heartbeat keeps ticking, and per-repo ordering holds.
Drains are made artificially slow by wrapping the repo's drain with a
blocking sleep — the worker thread eats it, the loop must not.
"""

import asyncio
import time

import jylis_tpu  # noqa: F401
from jylis_tpu.models.database import Database
from jylis_tpu.server.server import Server
from jylis_tpu.utils.config import Config
from jylis_tpu.utils.log import Log

from test_server import send_recv
from test_tlog_tallies import lose_base

SLOW = 0.6  # seconds a slowed drain blocks its worker thread


def make_server(engine="auto"):
    cfg = Config()
    cfg.port = "0"
    cfg.log = Log.create_none()
    db = Database(identity=1, engine=engine)
    return Server(cfg, db), db


def slow_down_drain(db, name: str) -> None:
    repo = db.manager(name).repo
    orig = repo.drain

    def slow_drain():
        time.sleep(SLOW)
        orig()

    repo.drain = slow_drain


def test_slow_drain_does_not_stall_unrelated_repo_or_loop():
    async def main():
        server, db = make_server()
        await server.start()
        try:
            slow_down_drain(db, "GCOUNT")
            # foreign delta: the next GCOUNT GET must drain (slowly)
            db.manager("GCOUNT").repo.converge(b"k", {99: 5})

            slow_task = asyncio.create_task(
                send_recv(server.port, b"GCOUNT GET k\r\n")
            )
            await asyncio.sleep(0.05)  # let the slow GET enter its drain

            # 1) an unrelated repo's command completes while the drain runs
            t0 = time.monotonic()
            out = await send_recv(server.port, b"PNCOUNT INC x 7\r\n")
            fast_latency = time.monotonic() - t0
            assert out == b"+OK\r\n"
            assert fast_latency < SLOW / 2, fast_latency

            # 2) the loop itself stays responsive (heartbeat-tick proxy)
            t0 = time.monotonic()
            await asyncio.sleep(0.05)
            assert time.monotonic() - t0 < SLOW / 2

            # 3) the slow GET still returns the converged value
            assert await slow_task == b":5\r\n"
        finally:
            await server.dispose()

    asyncio.run(main())


def test_heartbeat_ticks_during_slow_drain():
    """A real Heart attached to a flushing target keeps firing while a
    drain occupies the worker thread (the tick only schedules the flush
    task; the flush for the busy repo waits on its own lock)."""
    from jylis_tpu.cluster.heart import Heart

    async def main():
        server, db = make_server()
        await server.start()
        ticks = []

        class Target:
            _log = None

            def _heartbeat(self):
                ticks.append(time.monotonic())
                asyncio.get_running_loop().create_task(
                    db.flush_deltas_async(lambda d: None)
                )

        try:
            slow_down_drain(db, "GCOUNT")
            db.manager("GCOUNT").repo.converge(b"k", {99: 5})
            heart = Heart(Target(), 0.05)
            heart.start()
            slow_task = asyncio.create_task(
                send_recv(server.port, b"GCOUNT GET k\r\n")
            )
            await asyncio.sleep(SLOW * 0.8)  # drain still in flight
            heart.dispose()
            # ≥ 0.48s of 50ms ticks: a blocked loop would produce ~1-2
            assert len(ticks) >= 5, ticks
            gaps = [b - a for a, b in zip(ticks, ticks[1:])]
            assert max(gaps) < SLOW / 2, gaps
            assert await slow_task == b":5\r\n"
        finally:
            await server.dispose()

    asyncio.run(main())


def test_same_repo_ordering_across_connections():
    """FIFO repo lock: a write queued behind a slow foreign-delta GET
    lands after it; the final read sees both."""

    async def main():
        server, db = make_server()
        await server.start()
        try:
            slow_down_drain(db, "GCOUNT")
            db.manager("GCOUNT").repo.converge(b"k", {99: 5})
            slow_task = asyncio.create_task(
                send_recv(server.port, b"GCOUNT GET k\r\n")
            )
            await asyncio.sleep(0.05)
            out = await send_recv(server.port, b"GCOUNT INC k 2\r\n")
            assert out == b"+OK\r\n"
            assert await slow_task == b":5\r\n"  # GET ordered before INC
            out = await send_recv(server.port, b"GCOUNT GET k\r\n")
            assert out == b":7\r\n"
        finally:
            await server.dispose()

    asyncio.run(main())


def test_shutdown_serializes_with_inflight_drain_and_fences_queued_writes():
    """clean_shutdown_async must wait out a threaded drain before the
    final flush, and a write queued BEHIND that drain must be rejected
    (not silently lost after the final flush)."""

    async def main():
        server, db = make_server()
        await server.start()
        flushed = []
        db.flush_deltas(flushed.append)  # register the sink
        flushed.clear()
        try:
            slow_down_drain(db, "GCOUNT")
            db.manager("GCOUNT").repo.converge(b"k", {99: 5})
            # a write that lands BEFORE shutdown: must be in the final flush
            await send_recv(server.port, b"GCOUNT INC k 2\r\n")
            slow_task = asyncio.create_task(
                send_recv(server.port, b"GCOUNT GET k\r\n")
            )
            await asyncio.sleep(0.05)  # the slow drain now holds the lock
            late_task = asyncio.create_task(
                send_recv(server.port, b"GCOUNT INC k 100\r\n")
            )
            await asyncio.sleep(0.05)
            await db.clean_shutdown_async()
            assert await slow_task == b":7\r\n"
            late = await late_task
            assert late.startswith(b"-SHUTDOWN"), late
            if db.native_engine is not None:
                # it slept as a native burst (a chunk of the held type
                # stays native) and was demoted when it woke; the slow
                # GET's connection has nothing more to apply: no round,
                # no demotion
                serving = db.metrics.serving_counters
                assert serving["slept_bursts"] == 1 and serving["demotions"] == 1
            # the pre-shutdown INC flushed; the fenced one did not
            gcount = [b for name, b in flushed if name == "GCOUNT"]
            assert any(
                k == b"k" and d == {db.manager("GCOUNT").repo._identity: 2}
                for batch in gcount
                for k, d in batch
            )
            assert not any(
                d.get(db.manager("GCOUNT").repo._identity, 0) >= 100
                for batch in gcount
                for _k, d in batch
            )
        finally:
            await server.dispose()

    asyncio.run(main())


def test_treg_threshold_offload_predicate():
    """may_drain must predict the drain the SET is about to trigger
    (+1 for the row it adds), so threshold drains go to a worker thread."""
    from jylis_tpu.models import repo_treg

    repo = repo_treg.RepoTREG(identity=1)
    for i in range(repo_treg.PENDING_DRAIN_THRESHOLD - 1):
        repo.converge(b"t%d" % i, (b"v", 1))
    assert repo.may_drain([b"SET", b"tX", b"v", b"1"])
    assert not repo.may_drain([b"GET", b"tX"])
    repo.converge(b"tX", (b"v", 1))  # tips the threshold: buffered only
    assert repo.drain_overdue()


def test_pipelined_connection_replies_stay_in_order():
    """One connection pipelines a device-bound GET and host-only commands;
    RESP replies must come back in request order."""

    async def main():
        server, db = make_server()
        await server.start()
        try:
            slow_down_drain(db, "GCOUNT")
            db.manager("GCOUNT").repo.converge(b"k", {99: 5})
            payload = b"GCOUNT GET k\r\nPNCOUNT INC y 1\r\nPNCOUNT GET y\r\n"
            out = await send_recv(server.port, payload, expect_len=14)
            assert out == b":5\r\n+OK\r\n:1\r\n"
        finally:
            await server.dispose()

    asyncio.run(main())


def test_ujson_converge_path_is_bounded():
    """A write-hot, never-read UJSON key must not buffer deltas without
    bound: the converge path reports overdue at device-fold size (or the
    total cap) and a drain converges + empties the buffer."""
    from jylis_tpu.models import repo_ujson
    from jylis_tpu.ops.ujson_host import UJSON

    repo = repo_ujson.RepoUJSON(identity=1)
    src = repo_ujson.RepoUJSON(identity=2)

    class _Null:
        def __getattr__(self, name):
            return lambda *a: None

    for i in range(repo_ujson.DEVICE_FANIN_MIN):
        src.apply(_Null(), [b"SET", b"doc", b"n", b"%d" % i])
        for key, delta in src.flush_deltas():
            repo.converge(key, delta)
    assert repo.drain_overdue()
    repo.drain()
    assert not repo.drain_overdue()
    assert not repo._pend and repo._pend_total == 0
    got = []

    class _R:
        def string(self, s):
            got.append(s)

    repo.apply(_R(), [b"GET", b"doc", b"n"])
    assert got == ["%d" % (repo_ujson.DEVICE_FANIN_MIN - 1)]

    # the total-cap path: many keys, small fan-ins each
    repo2 = repo_ujson.RepoUJSON(identity=1)
    doc = UJSON()
    delta = UJSON()
    doc.set_doc(7, ("a",), "1", delta)
    for i in range(repo_ujson.PENDING_TOTAL_MAX):
        repo2.converge(b"k%d" % i, delta)
    assert repo2.drain_overdue()
    repo2.drain()
    assert repo2._pend_total == 0 and not repo2.drain_overdue()


def test_tlog_read_gather_offload_predicate():
    """A drain keeps the row it drained, so a read after it stays inline;
    only a row whose base a drain LOST (the length guard) rebuilds it with
    a device row gather, and may_drain must route that read to the worker
    thread."""
    from jylis_tpu.models.repo_tlog import RepoTLOG

    repo = RepoTLOG(identity=1, mesh=None)

    class _Null:
        def __getattr__(self, name):
            return lambda *a: None

    repo.apply(_Null(), [b"INS", b"k", b"v1", b"5"])
    repo.drain()  # the render cache is dropped, the base folded and kept
    assert not repo.may_drain([b"GET", b"k"])
    assert not repo.may_drain([b"SIZE", b"k"])
    assert not repo.may_drain([b"GET", b"missing"])
    repo.converge(b"k", ([(b"v2", 6)], 0))  # pending: merged on the host
    assert not repo.may_drain([b"SIZE", b"k"])
    repo.drain()
    assert not repo.may_drain([b"GET", b"k"])
    lose_base(repo, b"k")  # the guard fails
    assert repo.may_drain([b"GET", b"k"])
    assert not repo.may_drain([b"SIZE", b"k"])  # quiescent: O(1) len cache
    repo.converge(b"k", ([(b"v3", 7)], 0))  # pending: SIZE must merge now
    assert repo.may_drain([b"SIZE", b"k"])
    repo.apply(_Null(), [b"GET", b"k"])  # gathers once, repairs the base
    assert not repo.may_drain([b"GET", b"k"])
    assert not repo.may_drain([b"SIZE", b"k"])
