"""The repo lock's hand-off rule (models/manager.py, RepoLock), alone and
under a served node.

Alone: a holder that cannot yield takes a lock nobody holds whoever is
in line; a release wakes every sleeper, in arrival order; a sleeper that
is beaten keeps its place; `async with` takers are first come, first
served, so none starves; a cancelled sleeper strands nobody.

Served: after a drain lets go, the connections that slept behind it are
settled together, and the loop goes back to many commands per iteration
— pinned by COUNTING loop iterations, not by timing them. A native burst
takes the locks of the types its commands name and no other: beside a
held lock a client of another type is served in the engine, a client of
the held type sleeps for it, and the holder of every lock (the shutdown
snapshot) still shuts every burst out.
"""

import asyncio
import time

import pytest

import jylis_tpu  # noqa: F401
from jylis_tpu.models.database import DATA_TYPE_NAMES
from jylis_tpu.models.manager import RepoLock
from jylis_tpu.models.repo_treg import PENDING_DRAIN_THRESHOLD
from jylis_tpu.obs import loop as loop_mod
from jylis_tpu.server.resp import Respond

from test_async_serving import SLOW, make_server, slow_down_drain
from test_server import send_recv


def run(coro):
    return asyncio.run(asyncio.wait_for(coro, 10))


async def steps(n=3):
    """Let every runnable task take its step (and what that wakes)."""
    for _ in range(n):
        await asyncio.sleep(0)


async def hold(lock, log, name, release=None):
    """An `async with` taker; a `release` event makes it a LONG holder."""
    async with lock:
        log.append(name)
        if release is not None:
            await release.wait()


def no_yield(coro) -> bool:
    """Drive `coro` by hand: True when it finished without yielding."""
    try:
        coro.send(None)
    except StopIteration:
        return True
    coro.close()
    return False


def test_locked_is_true_exactly_while_held():
    async def main():
        lock = RepoLock()
        assert not lock.locked()
        async with lock:
            assert lock.locked()
        assert not lock.locked()
        assert await RepoLock.acquire_all([lock]) is False  # never slept
        assert lock.locked()
        RepoLock.release_all([lock])
        assert not lock.locked()
        with pytest.raises(RuntimeError):
            lock.release()

    run(main())


def test_a_free_lock_is_taken_without_a_yield():
    async def main():
        a, b = RepoLock(), RepoLock()
        assert no_yield(a.acquire())
        a.release()
        assert no_yield(RepoLock.acquire_all([a, b]))
        assert a.locked() and b.locked()
        RepoLock.release_all([a, b])

    run(main())


def test_a_free_lock_with_sleepers_is_taken_without_a_yield():
    """The convoy's cure: between a release and the sleepers' steps the
    lock is free, and a burst takes it there and then. An
    ``asyncio.Lock`` queued the burst behind the sleepers."""

    async def main():
        lock, other, log = RepoLock(), RepoLock(), []
        await lock.acquire()  # the drain
        sleepers = [asyncio.create_task(hold(lock, log, i)) for i in range(3)]
        await steps()
        assert log == [] and len(lock._line) == 3
        lock.release()  # three sleepers woken, none has run yet
        assert not lock.locked() and len(lock._line) == 3
        assert no_yield(RepoLock.acquire_all([other, lock]))
        RepoLock.release_all([other, lock])
        await asyncio.gather(*sleepers)
        assert log == [0, 1, 2]

    run(main())


def test_release_wakes_every_sleeper_and_they_run_in_arrival_order():
    async def main():
        lock, log = RepoLock(), []
        gate = asyncio.Event()
        first = asyncio.create_task(hold(lock, log, "drain", gate))
        await steps()
        sleepers = [asyncio.create_task(hold(lock, log, i)) for i in range(8)]
        await steps()
        gate.set()
        await first
        # ONE pass of the loop settles all eight (each takes and lets go
        # within its step); asyncio.Lock woke one per release, each a
        # loop iteration later
        await asyncio.sleep(0)
        assert log == ["drain", *range(8)]
        assert all(s.done() for s in sleepers) and not lock._line

    run(main())


def test_a_sleeper_beaten_by_a_long_holder_keeps_its_place():
    async def main():
        lock, log = RepoLock(), []
        gates = [asyncio.Event() for _ in range(2)]
        first = asyncio.create_task(hold(lock, log, "drain", gates[0]))
        await steps()
        long = asyncio.create_task(hold(lock, log, "long", gates[1]))
        early = asyncio.create_task(hold(lock, log, "early"))
        await steps()
        gates[0].set()
        await first
        await steps()
        # `long` got there first and keeps it: `early` sleeps again...
        assert log == ["drain", "long"] and lock.locked()
        late = asyncio.create_task(hold(lock, log, "late"))
        await steps()
        gates[1].set()
        await asyncio.gather(long, early, late)
        # ...in its place, before the later arrival
        assert log == ["drain", "long", "early", "late"]

    run(main())


def test_a_long_taker_among_a_stream_of_bursts_gets_the_lock_in_one_release():
    """No starvation: bursts take the free lock whoever is in line, but
    never hold it across a yield, so the sleeper finds it free when it
    runs; and a LATER long taker cannot slip in between the release and
    the sleeper's step."""

    async def main():
        lock, log = RepoLock(), []
        gates = [asyncio.Event() for _ in range(2)]
        bursts = stop = 0

        async def burst_stream():
            nonlocal bursts
            while not stop:
                await RepoLock.acquire_all([lock])
                bursts += 1
                RepoLock.release_all([lock])
                await asyncio.sleep(0)

        await lock.acquire()  # the drain
        streams = [asyncio.create_task(burst_stream()) for _ in range(4)]
        sleeper = asyncio.create_task(hold(lock, log, "cluster", gates[0]))
        await steps()
        assert bursts == 0  # the drain holds: everybody sleeps
        lock.release()
        # released, sleepers woken, none has run: a later long taker
        # arrives in that gap, finds the lock free, and still waits
        assert not lock.locked() and not no_yield(lock.acquire())
        later = asyncio.create_task(hold(lock, log, "snapshot", gates[1]))
        await steps()
        assert log == ["cluster"]  # within the ONE release
        held_at = bursts
        await steps(5)
        assert bursts == held_at  # and it keeps the bursts out
        gates[0].set()
        await sleeper
        await steps(5)
        assert log == ["cluster", "snapshot"]
        gates[1].set()
        await later
        await steps(5)
        assert bursts > held_at  # the stream goes on
        stop = 1
        await asyncio.gather(*streams)

    run(main())


def test_a_burst_sleeps_holding_nothing_and_says_that_it_slept():
    async def main():
        a, b, log = RepoLock(), RepoLock(), []
        gate = asyncio.Event()
        drain = asyncio.create_task(hold(b, log, "drain", gate))
        await steps()
        burst = asyncio.create_task(RepoLock.acquire_all([a, b]))
        await steps()
        assert not burst.done() and not a.locked()  # all or none
        gate.set()
        assert await burst is True
        assert a.locked() and b.locked()
        RepoLock.release_all([a, b])
        await drain

    run(main())


def test_a_burst_reads_and_writes_the_locks_of_its_set_alone():
    """Subsets: a burst over [a] runs while b is held and leaves b as it
    was; a burst over [a, b] sleeps in b's line holding neither; two
    bursts asleep on DIFFERENT locks each wake at their own release."""

    async def main():
        a, b, c, log = RepoLock(), RepoLock(), RepoLock(), []
        gates = {"b": asyncio.Event(), "c": asyncio.Event()}
        drains = [asyncio.create_task(hold(lock, log, name, gates[name]))
                  for lock, name in ((b, "b"), (c, "c"))]
        await steps()
        assert RepoLock.take_all([a])  # beside two holds
        assert a.locked() and b.locked() and not b._line
        RepoLock.release_all([a])
        assert not RepoLock.take_all([a, b]) and not a.locked()  # all or none
        on_b = asyncio.create_task(RepoLock.acquire_all([a, b]))
        on_c = asyncio.create_task(RepoLock.acquire_all([c]))
        await steps()
        assert len(b._line) == 1 and len(c._line) == 1 and not a.locked()
        gates["c"].set()
        assert await on_c is True and not on_b.done()
        RepoLock.release_all([c])
        gates["b"].set()
        assert await on_b is True and a.locked() and b.locked()
        RepoLock.release_all([a, b])
        await asyncio.gather(*drains)

    run(main())


def test_the_holder_of_every_lock_shuts_out_bursts_asleep_on_different_locks():
    """`Database.all_locks` beside subsets: bursts sleep on two different
    locks when the snapshot lines up for all of them, one by one. No
    deadlock (a sleeping burst holds nothing the snapshot waits for), the
    snapshot gets every lock in ONE release each, and while it holds
    them no burst runs, whatever its set."""

    async def main():
        locks = [RepoLock() for _ in range(5)]
        log, ran = [], []
        gates = [asyncio.Event() for _ in range(2)]
        drains = [asyncio.create_task(hold(locks[i], log, f"drain{i}", gates[n]))
                  for n, i in enumerate((1, 4))]
        await steps()

        async def burst(name, mine):
            await RepoLock.acquire_all(mine)
            ran.append(name)
            RepoLock.release_all(mine)

        bursts = [asyncio.create_task(burst("on1", [locks[1]])),
                  asyncio.create_task(burst("on4", [locks[0], locks[4]])),
                  asyncio.create_task(burst("free", [locks[2]]))]
        await steps()
        assert ran == ["free"]  # a set with no held lock runs beside both
        inside = asyncio.Event()
        leave = asyncio.Event()

        async def snapshot():
            for lock in locks:  # the fixed order, each the long way
                await lock.acquire()
            inside.set()
            await leave.wait()
            for lock in locks:
                lock.release()

        snap = asyncio.create_task(snapshot())
        await steps()
        assert not inside.is_set()
        late = asyncio.create_task(burst("late", [locks[0]]))  # lock 0 is the snapshot's
        await steps()
        gates[0].set()
        gates[1].set()
        await asyncio.wait_for(inside.wait(), 5)
        # the bursts that slept behind the drains woke first and ran (they
        # were ahead in line); nobody runs while the snapshot holds
        before = list(ran)
        assert set(before) >= {"free", "on1"}
        await steps(5)
        assert ran == before and all(lock.locked() for lock in locks)
        leave.set()
        await asyncio.wait_for(asyncio.gather(snap, late, *bursts, *drains), 5)
        assert sorted(ran) == ["free", "late", "on1", "on4"]
        assert not any(lock.locked() or lock._line for lock in locks)

    run(main())


def test_cancelling_a_sleeper_leaves_the_lock_usable():
    async def main():
        lock, log = RepoLock(), []
        gate = asyncio.Event()
        first = asyncio.create_task(hold(lock, log, "drain", gate))
        await steps()
        doomed = asyncio.create_task(hold(lock, log, "doomed"))
        await steps()
        doomed.cancel()  # while the drain still holds
        await steps()
        assert not lock._line
        woken = asyncio.create_task(hold(lock, log, "woken"))
        await steps()
        gate.set()
        await first  # let go: `woken` took its turn
        assert log == ["drain", "woken"]
        await lock.acquire()
        woken = asyncio.create_task(hold(lock, log, "woken again"))
        await steps()
        lock.release()
        # `woken` is woken and has not run; `behind` lines up behind it
        # with nobody else to wake it; `woken` is cancelled in that gap
        behind = asyncio.create_task(hold(lock, log, "behind"))
        woken.cancel()
        await asyncio.gather(woken, return_exceptions=True)
        await behind
        assert log == ["drain", "woken", "behind"] and not lock.locked()
        assert not lock._line
        async with lock:
            pass

    run(main())


def test_after_a_drain_the_loop_settles_many_commands_per_iteration():
    """32 closed-loop connections sleep behind one slow TREG drain (the
    engine's FIRST lock: a burst that queues there holds nothing that
    would send the others down the Python path). Once it lets go, the
    next 300 commands must cost far fewer than 300 loop iterations
    (C{loop.busy}): with ``asyncio.Lock`` every burst queued behind the
    sleepers and was woken one per iteration. And no command waited for
    the lock longer than the drain held it."""
    conns, after = 32, 300
    get = b"TREG GET x\r\n"

    async def main():
        server, db = make_server()
        await server.start()
        assert loop_mod.attach(db.metrics)
        iters = db.metrics.hist("loop.busy")
        wait = db.metrics.hist("lock.wait_serve")
        seen = {"cmds": 0, "base": None, "iters": None}

        async def client(reply):
            reader, writer = await asyncio.open_connection("127.0.0.1", server.port)
            try:
                while seen["iters"] is None:
                    writer.write(get)
                    assert await reader.readexactly(len(reply)) == reply
                    seen["cmds"] += 1
                    base = seen["base"]
                    if base is not None and seen["cmds"] - base[0] >= after:
                        seen["iters"] = iters.count - base[1]
            finally:
                writer.close()

        try:
            assert await send_recv(server.port, b"TREG SET x v 1\r\n") == b"+OK\r\n"
            reply = await send_recv(server.port, get)
            # one row short of the drain threshold: the next SET drains
            repo = db.manager("TREG").repo
            slow_down_drain(db, "TREG")
            for i in range(PENDING_DRAIN_THRESHOLD - 1 - repo._tbl.pend_count()):
                repo.converge(b"p%d" % i, (b"v", i + 1))
            clients = [asyncio.create_task(client(reply)) for _ in range(conns)]
            while seen["cmds"] < 4 * conns:  # the loop serves them all
                await asyncio.sleep(0.005)
            t_drain = time.perf_counter()
            slow = asyncio.create_task(send_recv(server.port, b"TREG SET y v 5\r\n"))
            while not db.manager("TREG").busy():
                await asyncio.sleep(0.005)
            await asyncio.sleep(SLOW / 2)
            stalled = seen["cmds"]
            await asyncio.sleep(0.05)
            assert seen["cmds"] == stalled  # every connection sleeps
            while db.manager("TREG").busy():  # (the drain is a thread's)
                await asyncio.sleep(0.005)
            seen["base"] = (seen["cmds"], iters.count)
            held = time.perf_counter() - t_drain
            assert await slow == b"+OK\r\n"
            await asyncio.wait_for(asyncio.gather(*clients), 20)
        finally:
            await server.dispose()
        assert seen["iters"] < after / 3, seen
        assert SLOW / 2 < wait.max < held + 0.05, (wait.max, held)

    asyncio.run(asyncio.wait_for(main(), 60), loop_factory=loop_mod.new_event_loop)


# ---- a chunk that arrives while a repo lock is held ---------------------------


def array(line: bytes) -> bytes:
    """An inline command as a RESP array of bulk strings."""
    words = line.split()
    return b"*%d\r\n" % len(words) + b"".join(
        b"$%d\r\n%s\r\n" % (len(w), w) for w in words)


async def python_path(lines) -> list[bytes]:
    """Each command's reply from a node that has no engine: the oracle."""
    server, _db = make_server(engine="python")
    await server.start()
    try:
        reader, writer = await asyncio.open_connection("127.0.0.1", server.port)
        out = []
        for line in lines:
            writer.write(line + b"\r\n")
            out.append(await asyncio.wait_for(reader.read(1 << 16), 5))
        writer.close()
        return out
    finally:
        await server.dispose()


async def native_server():
    server, db = make_server()
    if db.native_engine is None:
        pytest.skip("no native engine on this host")
    await server.start()
    return server, db


async def asleep(lock, n):
    """Until `n` takers sleep in the lock's line."""
    while len(lock._line) < n:
        await asyncio.sleep(0.001)


_OF_THE_HELD_TYPE = {
    "TLOG": [b"TLOG INS k a 1", b"TLOG INS k b 2", b"TLOG GET k",
             b"TLOG INS k c 3", b"TLOG GET k 2", b"TLOG GET other"],
    "GCOUNT": [b"GCOUNT INC k 2", b"GCOUNT GET k", b"GCOUNT INC k 3",
               b"GCOUNT GET k", b"GCOUNT GET other"],
}


@pytest.mark.parametrize("form", ["inline", "array"])
@pytest.mark.parametrize("held", sorted(_OF_THE_HELD_TYPE))
def test_a_chunk_of_the_held_type_sleeps_for_the_lock_and_stays_native(held, form):
    """Somebody holds a type's lock across a yield; n connections send
    one command of THAT type each. The Python path would sleep in that
    very line, so the chunks stay native: each burst sleeps holding
    nothing (one lock.wait_serve sample, one slept_bursts), wakes at the
    release in arrival order and runs in the engine. Replies are the
    Python path's byte for byte; nothing was routed or demoted."""
    lines = _OF_THE_HELD_TYPE[held]

    async def main():
        want = await python_path(lines)
        server, db = await native_server()
        reg = db.metrics
        lock = db.manager(held)._lock
        conns = []
        try:
            await lock.acquire()
            for i, line in enumerate(lines):
                reader, writer = await asyncio.open_connection("127.0.0.1", server.port)
                writer.write(line + b"\r\n" if form == "inline" else array(line))
                conns.append((reader, writer))
                await asyncio.wait_for(asleep(lock, i + 1), 5)  # in arrival order
            await asyncio.sleep(0.05)
            assert all(not r._buffer for r, _w in conns)  # nobody was answered
            assert reg.serving_counters["slept_bursts"] == 0  # counted on waking
            lock.release()
            got = [await asyncio.wait_for(r.read(1 << 16), 5) for r, _w in conns]
        finally:
            for _r, w in conns:
                w.close()
            await server.dispose()
        assert got == want
        serving = db.serving_totals()
        assert serving["slept_bursts"] == len(lines)
        assert reg.slept_by_type == {held: len(lines)}  # slept_bursts{type}
        assert serving["busy_routed_cmds"] == 0 and serving["demoted_cmds"] == 0
        assert serving["native_cmds"] == len(lines)
        assert serving["native_bursts"] == serving["burst_locks"] == len(lines)
        assert serving["bursts_beside_hold"] == 0  # the held lock was their own
        assert reg.hist("lock.wait_serve").count == len(lines)
        assert reg.hist("serve.py_apply").count == 0

    run(main())


TREG_LINES = [b"TREG SET t v 1", b"TREG GET t"]
MAP_LINES = [b"MAP TREG SET t field3 v 1", b"MAP TREG GET t field3", b"MAP TREG GETALL t"]


@pytest.mark.parametrize("form", ["inline", "array"])
@pytest.mark.parametrize("held,lines", [
    ("TLOG", TREG_LINES), ("UJSON", TREG_LINES), ("MAP", TREG_LINES),
    ("TREG", MAP_LINES), ("TLOG", MAP_LINES)])
def test_a_chunk_of_another_type_is_served_in_the_engine_beside_the_hold(held, lines, form):
    """A round holds what it names: beside a long hold of the TLOG (the
    UJSON, the MAP) lock a TREG client is served at once AND natively,
    under the TREG lock alone, and a MAP client beside a hold of TREG's
    under MAP's alone (the engine's sixth type, whatever inner type its
    fields hold); nothing is routed to the Python path, and
    `bursts_beside_hold` counts the round."""

    async def main():
        want = await python_path(lines)
        server, db = await native_server()
        mgr = db.manager(held)
        try:
            async with mgr.hold_sync():  # a `_Hold`: a digest's, a dump's
                wire = b"".join(l + b"\r\n" if form == "inline" else array(l) for l in lines)
                got = await send_recv(server.port, wire, len(b"".join(want)))
                assert mgr.busy()  # answered while it was held
        finally:
            await server.dispose()
        assert got == b"".join(want)
        serving = db.serving_totals()
        assert serving["native_cmds"] == len(lines) and serving["demoted_cmds"] == 0
        assert serving["busy_routed_cmds"] == 0 and serving["slept_bursts"] == 0
        assert serving["native_bursts"] == serving["burst_locks"] == 1
        assert serving["bursts_beside_hold"] == 1
        assert db.metrics.hist("lock.wait_serve").count == 0
        assert db.metrics.hist("serve.py_apply").count == 0

    run(main())


@pytest.mark.parametrize("case", ["split", "python_only", "malformed"])
def test_a_chunk_whose_type_cannot_be_told_needs_no_lock_and_no_route(case):
    """Under a held (TLOG) lock, a chunk that names none of the engine's
    types stays with the engine all the same, which takes no lock for
    it: the head of a command split across two reads waits for its rest
    (and the whole command then sleeps for its own type's lock), a type
    only Python serves is handed back (deferred) and answered while the
    lock is held, a protocol error demotes the connection to the parser
    that words the error."""

    async def main():
        server, db = await native_server()
        lock = db.manager("TLOG")._lock
        try:
            assert await send_recv(server.port, b"TLOG INS k a 1\r\n") == b"+OK\r\n"
            await lock.acquire()
            reader, writer = await asyncio.open_connection("127.0.0.1", server.port)
            if case == "split":
                writer.write(b"TLOG GE")
                await asyncio.sleep(0.05)
                assert not lock._line  # an unfinished command waits for nobody
                writer.write(b"T k\r\n")
                await asyncio.wait_for(asleep(lock, 1), 5)
                assert not reader._buffer
                lock.release()
                want = b"*1\r\n*2\r\n$1\r\na\r\n:1\r\n"
            elif case == "python_only":
                writer.write(b"SYSTEM VERSION\r\n")
                want = None
            else:
                writer.write(b"*x\r\n")
                want = b"-"
            got = await asyncio.wait_for(reader.read(1 << 16), 5)
            if case != "split":
                assert lock.locked()  # answered while it was held
                lock.release()
            writer.close()
        finally:
            await server.dispose()
        assert got == want if case == "split" else got.startswith(want or b"$")
        serving = db.serving_totals()
        assert serving["busy_routed_cmds"] == 0
        assert serving["slept_bursts"] == (1 if case == "split" else 0)
        assert serving["deferred_cmds"] == (1 if case == "python_only" else 0)
        assert serving["demotions"] == (1 if case == "malformed" else 0)

    run(main())


def test_a_pipelined_chunk_behind_its_first_commands_hold_answers_in_order():
    """[TLOG INS, TREG GET, TLOG GET] in one chunk behind a TLOG hold:
    the first command names the held type, so the burst sleeps for it
    and wakes holding that lock alone: a round of the one TLOG command,
    then the run ahead names two types, both free: one round under two
    locks. The replies in command order."""
    lines = [b"TLOG INS k a 1", b"TREG GET t", b"TLOG GET k"]

    async def main():
        want = b"".join(await python_path(lines))
        server, db = await native_server()
        lock = db.manager("TLOG")._lock
        try:
            await lock.acquire()
            wire = b"".join(l + b"\r\n" for l in lines)
            burst = asyncio.create_task(send_recv(server.port, wire, len(want)))
            await asyncio.wait_for(asleep(lock, 1), 5)
            lock.release()
            assert await burst == want
        finally:
            await server.dispose()
        serving = db.serving_totals()
        assert serving["slept_bursts"] == 1 and serving["native_cmds"] == 3
        assert serving["busy_routed_cmds"] == 0 and serving["demoted_cmds"] == 0
        assert serving["native_bursts"] == 2 and serving["burst_locks"] == 1 + 2

    run(main())


def test_a_pipelined_chunk_is_served_up_to_the_command_that_names_the_held_type():
    """[TREG SET, TREG GET, TLOG INS, TREG GET, UJSON GET] in one chunk
    beside a TLOG hold: the run ahead names a held type, so the first
    round is the first command's type alone and stops before the TLOG
    command: its two replies arrive WHILE the lock is held. The rest
    sleeps for TLOG, then runs as one round over what it names; replies
    in command order, byte for byte the Python path's."""
    lines = [b"TREG SET t v 1", b"TREG GET t", b"TLOG INS k a 1", b"TREG GET t",
             b"UJSON GET d"]

    async def main():
        want = await python_path(lines)
        server, db = await native_server()
        lock = db.manager("TLOG")._lock
        try:
            await lock.acquire()
            reader, writer = await asyncio.open_connection("127.0.0.1", server.port)
            writer.write(b"".join(l + b"\r\n" for l in lines))
            await asyncio.wait_for(asleep(lock, 1), 5)
            head = await asyncio.wait_for(reader.read(1 << 16), 5)
            assert head == b"".join(want[:2]) and lock.locked()
            lock.release()
            rest = b""
            while len(rest) < len(b"".join(want[2:])):
                rest += await asyncio.wait_for(reader.read(1 << 16), 5)
            writer.close()
        finally:
            await server.dispose()
        assert rest == b"".join(want[2:])
        serving = db.serving_totals()
        assert serving["slept_bursts"] == 1 and db.metrics.slept_by_type == {"TLOG": 1}
        assert serving["busy_routed_cmds"] == 0 and serving["demotions"] == 0
        # UJSON GET of a document never rendered is the engine's hand-back
        assert serving["native_cmds"] == 4 and serving["deferred_cmds"] == 1
        assert serving["bursts_beside_hold"] == 1  # the first round, beside TLOG's

    run(main())


@pytest.mark.parametrize("own", [True, False])
def test_under_an_admission_cap_a_chunk_that_meets_a_held_lock_is_routed_and_counted(own):
    """`--admission-cap`: the wait for a repo lock must count in its
    manager's `_inflight`, so while a capped lock is held a chunk takes
    the per-repo Python path whatever it names: of the held type, one
    command queues and the next is refused with the typed BUSY; of
    another type, it is served at once (its own lock is free)."""

    async def main():
        server, db = await native_server()
        db.set_admission_cap(1)
        lock = db.manager("TLOG")._lock
        try:
            await lock.acquire()
            if own:
                first = asyncio.create_task(send_recv(server.port, b"TLOG INS k a 1\r\n"))
                await asyncio.wait_for(asleep(lock, 1), 5)
                refused = await send_recv(server.port, b"TLOG INS k b 2\r\n")
                assert refused.startswith(b"-BUSY (TLOG admission cap 1")
                assert lock.locked()
                lock.release()
                assert await first == b"+OK\r\n"
            else:
                assert await send_recv(server.port, b"TREG SET t v 1\r\n") == b"+OK\r\n"
                assert lock.locked()
                lock.release()
        finally:
            await server.dispose()
        serving = db.serving_totals()
        assert serving["busy_refusals"] == (1 if own else 0)
        assert serving["busy_routed_cmds"] == (2 if own else 1)
        assert serving["slept_bursts"] == 0 and serving["native_cmds"] == 0

    run(main())


def test_a_burst_asleep_when_the_shutdown_takes_the_lock_is_refused():
    """A write sleeps natively behind a hold; `clean_shutdown_async`
    lines up for the same lock. When the burst wakes the repo's final
    flush is spoken for: it is demoted and refused, never acknowledged."""
    from jylis_tpu.models.manager import SHUTDOWN_ERR

    async def main():
        server, db = await native_server()
        mgr = db.manager("TLOG")
        try:
            await mgr._lock.acquire()
            write = asyncio.create_task(send_recv(server.port, b"TLOG INS k a 1\r\n"))
            await asyncio.wait_for(asleep(mgr._lock, 1), 5)
            down = asyncio.create_task(mgr.clean_shutdown_async())
            await asyncio.wait_for(asleep(mgr._lock, 2), 5)
            mgr._lock.release()
            assert await write == b"-" + SHUTDOWN_ERR.encode() + b"\r\n"
            await down
        finally:
            await server.dispose()
        serving = db.serving_totals()
        assert serving["slept_bursts"] == 1 and serving["demotions"] == 1
        assert serving["native_cmds"] == 0
        assert mgr.repo._tbl.rows() == 0  # the write was never applied

    run(main())


# ---- holds that can be seen held are spans -----------------------------------


@pytest.mark.parametrize("path", ["served", "python"])
def test_a_threaded_drains_hold_contains_its_drain_and_later_takers_record_none(path):
    """lock.hold_serve runs from holding to released around the hop to
    the worker thread, the drain and the hop back, so it is at least the
    drain.<TYPE> sample inside it. Commands of the type that arrive
    meanwhile sleep in the lock's line and record no hold, whichever
    way they came: over a socket they stay native (the busy() rule keeps
    a chunk of the HELD type in the engine: three slept bursts, no
    Python apply); handed to `apply_async` they take the lock the long
    way, apply inline and let go within their task step, where nobody
    could see them hold it."""

    async def main():
        server, db = make_server()
        await server.start()
        reg = db.metrics
        try:
            slow_down_drain(db, "GCOUNT")
            db.manager("GCOUNT").repo.converge(b"k", {99: 5})
            slow = asyncio.create_task(send_recv(server.port, b"GCOUNT GET k\r\n"))
            while not db.manager("GCOUNT").busy():
                await asyncio.sleep(0.005)
            if path == "served":
                waiters = [asyncio.create_task(send_recv(server.port, b"GCOUNT INC x 1\r\n"))
                           for _ in range(3)]
                assert [await w for w in waiters] == [b"+OK\r\n"] * 3
            else:
                got = [bytearray() for _ in range(3)]
                await asyncio.gather(*(
                    db.apply_async(Respond(out.extend), [b"GCOUNT", b"INC", b"x", b"1"])
                    for out in got))
                assert got == [b"+OK\r\n"] * 3
            assert await slow == b":5\r\n"
        finally:
            await server.dispose()
        hold, drain = reg.hist("lock.hold_serve"), reg.hist("drain.GCOUNT")
        assert hold.count == 1 and drain.count == 1
        assert hold.total >= SLOW and hold.total >= drain.total
        assert reg.hist("lock.wait_serve").count == 4  # the GET's own take too
        # (the drain ran in the thread: the GET's apply is in neither count)
        served = path == "served"
        assert reg.hist("serve.py_apply").count == (0 if served else 3)
        assert reg.serving_counters["slept_bursts"] == (3 if served else 0)
        assert reg.serving_counters["busy_routed_cmds"] == 0
        for other in ("lock.hold_converge", "lock.hold_flush", "lock.hold_sync"):
            assert reg.hist(other).count == 0

    run(main())


@pytest.mark.parametrize("who,seam,holds", [
    ("converge", "lock.hold_converge", 1),
    ("flush", "lock.hold_flush", 1),
    ("shutdown", "lock.hold_flush", 1),
    ("digest", "lock.hold_sync", len(DATA_TYPE_NAMES)),
    ("dump", "lock.hold_sync", 1),
    ("snapshot", "lock.hold_sync", None),
])
def test_every_long_holder_records_its_hold(who, seam, holds):
    """Cluster apply, heartbeat flush, final flush, digest, state dump
    and the shutdown snapshot each record holding -> released under
    their own seam, once per repo lock taken, and the lock is seen held
    inside."""

    async def main():
        _server, db = make_server()
        mgr = db.manager("GCOUNT")
        h = db.metrics.hist(seam)
        if who == "converge":
            await db.converge_async(("GCOUNT", [(b"k", {98: 1})]))
        elif who == "flush":
            await mgr.flush_async(lambda deltas: None)
        elif who == "shutdown":
            await mgr.clean_shutdown_async()
        elif who == "digest":
            await db.sync_digest_async()
        elif who == "dump":
            await db.dump_state_async(names={"GCOUNT"})
        else:
            async with db.all_locks():
                assert all(m.busy() for m in db.managers())
                assert h.count == 0  # recorded when it lets go
        assert h.count == (holds if holds is not None else len(list(db.managers())))
        assert h.total > 0 and not mgr.busy()
        others = {"lock.hold_serve", "lock.hold_converge", "lock.hold_flush",
                  "lock.hold_sync"} - {seam}
        assert all(db.metrics.hist(o).count == 0 for o in others)

    run(main())


def test_an_armed_hold_is_an_annotation_with_the_type_and_the_verb(monkeypatch):
    from jylis_tpu.obs import span

    made = []

    class Annotation:
        def __init__(self, name, **kwargs):
            made.append((name, kwargs))

        def __enter__(self):
            pass

        def __exit__(self, *exc):
            pass

    class Profiler:
        TraceAnnotation = Annotation

    monkeypatch.setattr(span, "_armed", True)
    monkeypatch.setattr(span, "_profiler", Profiler)

    async def main():
        _server, db = make_server()
        db.manager("GCOUNT").repo.converge(b"k", {99: 5})
        resp = type("R", (), {"__getattr__": lambda self, name: lambda *a: None})()
        await db.apply_async(resp, [b"GCOUNT", b"GET", b"k"])  # drains: threaded
        await db.converge_async(("GCOUNT", [(b"k", {98: 1})]))

    run(main())
    holds = [(n, kw) for n, kw in made if n.startswith("lock.hold_")]
    assert holds == [("lock.hold_serve", {"type": "GCOUNT", "verb": "GET"}),
                     ("lock.hold_converge", {"type": "GCOUNT"})]
