"""A served chunk is settled with no task (server.py `_Conn`: the read
callback notes the arrival, `Server._serve_ready` takes up every chunk
of the iteration): the invariants that path has to keep, each against
the Python-engine path (``engine="python"``, asyncio's streams: the
oracle) where a reply stream is compared. Replies leave in command order
across a round settled without a task, one that slept for a lock and a
command handed back to Python; a connection whose slow-path task is in
flight appends what arrives and stops reading; a command split across
chunks waits in the connection's buffer for its rest; a demotion
mid-stream loses nothing; an armed failpoint stalls its connection and
not the loop; `dispose` finds a task asleep; and every round is counted
once, inline or not.
"""

import asyncio
import socket

import pytest

import jylis_tpu  # noqa: F401
from jylis_tpu import faults
from jylis_tpu.server import server as server_mod

from test_async_serving import make_server
from test_mixed_burst import reply_length


def native_server():
    server, db = make_server()
    if db.native_engine is None:
        pytest.skip("no native engine on this host")
    return server, db


async def connect(port: int) -> socket.socket:
    sock = socket.socket()
    sock.setblocking(False)
    await asyncio.get_running_loop().sock_connect(sock, ("127.0.0.1", port))
    sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
    return sock


async def send(sock, data: bytes) -> None:
    await asyncio.get_running_loop().sock_sendall(sock, data)


async def recv_replies(sock, count: int, timeout: float = 10) -> bytes:
    """The bytes of the next ``count`` whole replies."""
    loop = asyncio.get_running_loop()
    data, at = b"", 0
    for _ in range(count):
        while (n := reply_length(data, at)) is None:
            got = await asyncio.wait_for(loop.sock_recv(sock, 1 << 20), timeout)
            assert got, "the server closed the connection"
            data += got
        at += n
    assert at == len(data), data[at:at + 40]
    return data


async def recv_to_eof(sock, timeout: float = 10) -> bytes:
    loop = asyncio.get_running_loop()
    data = b""
    while got := await asyncio.wait_for(loop.sock_recv(sock, 1 << 20), timeout):
        data += got
    return data


async def oracle(stream: bytes) -> bytes:
    """What the Python-engine path answers to ``stream`` sent whole and
    half-closed: every reply, then the end of the stream."""
    server, _db = make_server(engine="python")
    await server.start()
    try:
        sock = await connect(server.port)
        await send(sock, stream)
        sock.shutdown(socket.SHUT_WR)
        got = await recv_to_eof(sock)
        sock.close()
        return got
    finally:
        await server.dispose()


def conn_of(server):
    (conn,) = server._conns
    assert isinstance(conn, server_mod._Conn)
    return conn


FAST = b"GCOUNT INC a 1\r\n"
SLEPT = (b"TLOG INS l post 7\r\n", b"SYSTEM VERSION\r\n", b"GCOUNT GET a\r\n",
         b"TLOG GET l\r\n")
LATE = (b"GCOUNT INC a 2\r\n", b"GCOUNT GET a\r\n")


@pytest.mark.parametrize("late", [False, True])
def test_replies_leave_in_command_order_across_fast_slept_and_deferred(late):
    """One pipelined connection: a round settled with no task, then a
    chunk whose first round sleeps for TLOG's lock and whose second
    command is handed back to Python, then (``late``) bytes that arrive
    while that task is asleep: the oracle's reply stream, and every
    round counted once: inline + the slow path's = native_bursts."""

    async def main():
        server, db = native_server()
        await server.start()
        lock = db.manager("TLOG")._lock
        try:
            sock = await connect(server.port)
            await send(sock, FAST)
            got = await recv_replies(sock, 1)
            serving = db.metrics.serving_counters
            assert serving["inline_bursts"] == serving["native_bursts"] == 1
            await lock.acquire()
            await send(sock, b"".join(SLEPT))
            await asyncio.sleep(0.1)
            conn = conn_of(server)
            assert conn.task is not None and not conn.task.done()
            if late:
                await send(sock, b"".join(LATE))
                await asyncio.sleep(0.1)
                assert not conn.transport.is_reading()  # paused, in flight
            lock.release()
            got += await recv_replies(sock, len(SLEPT) + 2 * late)
            await asyncio.sleep(0.05)
            assert conn.task is None and conn.transport.is_reading()
            sock.close()
        finally:
            await server.dispose()
        assert got == await oracle(FAST + b"".join(SLEPT) + b"".join(LATE) * late)
        assert serving["slept_bursts"] == serving["deferred_cmds"] == 1
        # the slow path's rounds: the slept one (it ends at SYSTEM, rc 1),
        # the one after the deferred command, and with `late` at most one
        # more for what arrived meanwhile
        slow = serving["native_bursts"] - serving["inline_bursts"]
        assert serving["inline_bursts"] == 1 and 2 <= slow <= 2 + late
        assert db.serving_totals()["demoted_cmds"] == 1  # SYSTEM VERSION alone

    asyncio.run(main())


@pytest.mark.parametrize("freed", [False, True])
def test_a_round_that_found_its_lock_held_asks_the_engine_once(freed):
    """The round that met a held lock hands the task the lock it found
    (`_Conn.want`): the engine is asked for the types ahead ONCE, and a
    lock that came free before the task's first step (``freed``) is
    taken without a sleep and not counted as one."""

    async def main():
        server, db = native_server()
        await server.start()
        lock = db.manager("TLOG")._lock
        engine, asked = server._engine, []
        types_ahead, round_asleep = engine.types_ahead, server._round_asleep

        def counting(view):
            asked.append(bytes(view))
            return types_ahead(view)

        async def asleep(conn):
            assert conn.want == 1 << server._ENGINE_TYPES.index("TLOG")
            if freed:
                lock.release()
            return await round_asleep(conn)

        engine.types_ahead, server._round_asleep = counting, asleep
        try:
            await lock.acquire()
            sock = await connect(server.port)
            await send(sock, SLEPT[0])
            await asyncio.sleep(0.1)
            if not freed:
                assert conn_of(server).task is not None
                lock.release()
            assert await recv_replies(sock, 1) == b"+OK\r\n"
            assert conn_of(server).want == 0
            sock.close()
        finally:
            engine.types_ahead = types_ahead
            await server.dispose()
        assert asked == [SLEPT[0]]
        serving = db.metrics.serving_counters
        assert serving["slept_bursts"] == (0 if freed else 1)
        assert serving["native_bursts"] == 1 and serving["inline_bursts"] == 0

    asyncio.run(main())


def test_a_connection_asleep_behind_a_held_lock_stops_reading_its_socket():
    """A client pipelines 10 MB behind a held TLOG lock: the node takes
    one more chunk, pauses the transport, and its buffer stays bounded
    while the client's send blocks; the lock's release resumes it and
    every command is answered, in order."""
    key = b"k" * 180
    cmd = b"TLOG GET " + key + b" 1\r\n"
    count = (10 << 20) // len(cmd)

    async def main():
        server, db = native_server()
        await server.start()
        lock = db.manager("TLOG")._lock
        try:
            await lock.acquire()
            sock = await connect(server.port)
            await send(sock, cmd)
            await asyncio.sleep(0.05)
            conn = conn_of(server)
            assert conn.task is not None
            flood = asyncio.create_task(send(sock, cmd * (count - 1)))
            await asyncio.sleep(0.5)
            assert not flood.done()  # the client blocks
            assert not conn.transport.is_reading()
            held = len(conn._buf)
            assert held <= 4 * server_mod._RECV, held
            await asyncio.sleep(0.2)
            assert len(conn._buf) == held and not conn.task.done()
            lock.release()
            got = await recv_replies(sock, count, 60)
            await flood
            assert got == b"*0\r\n" * count
            await asyncio.sleep(0.05)
            assert conn.task is None and conn.transport.is_reading()
            assert len(conn._buf) == server_mod._RECV  # back to its size
            sock.close()
        finally:
            await server.dispose()
        assert db.metrics.serving_counters["slept_bursts"] == 1

    asyncio.run(main())


SPLIT = (b"*5\r\n$4\r\nTREG\r\n$3\r\nSET\r\n$2\r\nk1\r\n$5\r\nhello\r\n$1\r\n7\r\n"
         b"TREG GET k1\r\n")


def test_a_command_split_at_every_byte_waits_in_the_buffer_for_its_rest():
    """Two commands cut in two chunks at every byte: the head stays in
    the connection's buffer (`scan_apply`'s `consumed`), no round takes
    the slow path, and the buffer starts again at 0 once it is empty."""

    async def main():
        server, db = native_server()
        await server.start()
        want = await oracle(SPLIT)
        try:
            sock = await connect(server.port)
            for cut in range(1, len(SPLIT)):
                await send(sock, SPLIT[:cut])
                await asyncio.sleep(0.002)
                await send(sock, SPLIT[cut:])
                assert await recv_replies(sock, 2) == want, cut
            conn = conn_of(server)
            assert conn._start == conn._end == 0 and conn.task is None
            sock.close()
        finally:
            await server.dispose()
        serving = db.metrics.serving_counters
        assert serving["inline_bursts"] == serving["native_bursts"]
        assert serving["native_bursts"] >= len(SPLIT) - 1
        assert db.serving_totals()["demoted_cmds"] == 0

    asyncio.run(main())


def test_a_large_command_grows_the_buffer_and_gives_it_back():
    value = b"v" * (1 << 20)
    stream = (b"*5\r\n$4\r\nTREG\r\n$3\r\nSET\r\n$1\r\nk\r\n$%d\r\n%s\r\n$1\r\n1\r\n"
              % (len(value), value)) + b"TREG GET k\r\n"

    async def main():
        server, db = native_server()
        await server.start()
        try:
            sock = await connect(server.port)
            await send(sock, stream)
            assert await recv_replies(sock, 2) == await oracle(stream)
            conn = conn_of(server)
            assert len(conn._buf) == server_mod._RECV and conn._end == 0
            sock.close()
        finally:
            await server.dispose()
        assert db.serving_totals()["demoted_cmds"] == 0

    asyncio.run(main())


@pytest.mark.parametrize("cause", ["fault", "malformed"])
def test_a_demotion_mid_stream_loses_no_reply(cause):
    """An injected failure at the engine's boundary demotes the
    connection: the chunk it met and every later one are the Python
    parser's, and the reply stream is still the oracle's. Malformed
    input demotes too, and the parser's own error ends the connection."""
    first = b"GCOUNT INC a 1\r\nTREG SET k v 1\r\n"
    if cause == "fault":
        second = b"GCOUNT INC a 2\r\nTREG GET k\r\n"
        third = b"GCOUNT GET a\r\nTLOG INS l x 1\r\nTLOG GET l\r\n"
    else:
        second = b"GCOUNT GET a\r\n*1\r\n$-5\r\nGCOUNT GET a\r\n"
        third = b""

    async def main():
        server, db = native_server()
        await server.start()
        faults.reset()
        try:
            sock = await connect(server.port)
            await send(sock, first)
            got = await recv_replies(sock, 2)
            if cause == "fault":
                faults.arm("native.scan_apply", "error", budget=1)
                await send(sock, second)
                got += await recv_replies(sock, 2)
                await send(sock, third)
                got += await recv_replies(sock, 3)
                assert not conn_of(server).native
                sock.shutdown(socket.SHUT_WR)
            else:
                await send(sock, second)
            got += await recv_to_eof(sock)
            sock.close()
        finally:
            faults.reset()
            await server.dispose()
        assert got == await oracle(first + second + third)
        serving = db.metrics.serving_counters
        assert serving["demotions"] == 1
        if cause == "fault":
            assert serving["demoted_conn_cmds"] == 5

    asyncio.run(main())


def test_an_armed_sleep_stalls_one_connection_and_not_the_loop():
    async def main():
        server, db = native_server()
        await server.start()
        loop = asyncio.get_running_loop()
        faults.reset()
        try:
            a, b = await connect(server.port), await connect(server.port)
            faults.arm("native.scan_apply", "sleep", arg=0.4, budget=1)
            t0 = loop.time()
            await send(a, b"GCOUNT INC x 1\r\n")
            await asyncio.sleep(0.05)
            await send(b, b"SYSTEM VERSION\r\n")
            assert (await recv_replies(b, 1, 2)).startswith(b"$")
            assert loop.time() - t0 < 0.3  # answered while A sleeps
            assert await recv_replies(a, 1, 5) == b"+OK\r\n"
            assert loop.time() - t0 >= 0.35
            a.close()
            b.close()
        finally:
            faults.reset()
            await server.dispose()
        # A's round was the slow path's; B's first (SYSTEM: handed back)
        # was settled with no task, its second followed the dispatch
        serving = db.metrics.serving_counters
        assert serving["inline_bursts"] == 1 and serving["native_bursts"] == 3

    asyncio.run(main())


def test_dispose_finds_a_slow_path_task_asleep_and_it_applies_nothing_more():
    async def main():
        server, db = native_server()
        await server.start()
        lock = db.manager("GCOUNT")._lock
        await lock.acquire()
        sock = await connect(server.port)
        await send(sock, b"GCOUNT INC x 5\r\n")
        await asyncio.sleep(0.05)
        task = conn_of(server).task
        assert task is not None and not task.done()
        await asyncio.wait_for(server.dispose(), 5)
        assert task.done() and not server._conns
        lock.release()
        await asyncio.sleep(0.05)
        assert await recv_to_eof(sock, 2) == b""  # never acknowledged
        sock.close()
        assert not lock.locked()
        assert db.metrics.serving_counters["native_bursts"] == 0
        assert db.serving_totals()["native_cmds"] == 0

    asyncio.run(main())


def test_pipelined_commands_and_a_half_close_are_all_answered_then_eof():
    stream = b"GCOUNT INC a 1\r\nSYSTEM VERSION\r\nGCOUNT GET a\r\nGCOUNT GE"

    async def main():
        server, _db = native_server()
        await server.start()
        try:
            sock = await connect(server.port)
            await send(sock, stream)
            sock.shutdown(socket.SHUT_WR)
            got = await recv_to_eof(sock)
            sock.close()
            await asyncio.sleep(0.05)
            assert not server._conns
        finally:
            await server.dispose()
        assert got == await oracle(stream)
        assert got.startswith(b"+OK\r\n$") and got.endswith(b":1\r\n")

    asyncio.run(main())


def test_a_socket_the_sender_cannot_take_is_served_on_the_streams_path():
    """`sender_open` failing (no descriptor to be had) leaves the
    connection with `writer.write`, the oracle door: the streams path
    from its first byte."""

    async def main():
        server, db = native_server()
        db.native_engine.sender_open = lambda fd, low, high: -1
        await server.start()
        try:
            sock = await connect(server.port)
            await send(sock, b"GCOUNT INC a 3\r\nGCOUNT GET a\r\n")
            assert await recv_replies(sock, 2) == b"+OK\r\n:3\r\n"
            (writer,) = server._conns
            assert isinstance(writer, asyncio.StreamWriter)
            sock.close()
        finally:
            await server.dispose()
        serving = db.metrics.serving_counters
        assert serving["loop_sends"] >= 1 and serving["native_bursts"] == 0
        assert serving["demoted_conn_cmds"] == 2

    asyncio.run(main())


def test_the_byte_bound_every_node_arms_costs_no_task():
    """`--admission-queue-bytes` defaults to 256 MiB, so every real
    node's admission controller is armed: a native round was never
    gated by it (the gate is the Python path's), `_settle` looks at
    the bound after a round as the coroutine did, and needs no task."""

    async def main():
        server, db = native_server()
        db.admission.queue_bytes_cap = 256 << 20
        assert db.admission.armed
        await server.start()
        try:
            sock = await connect(server.port)
            for n in range(1, 4):
                await send(sock, b"GCOUNT INC a 1\r\nGCOUNT GET a\r\n")
                assert await recv_replies(sock, 2) == b"+OK\r\n:%d\r\n" % n
            assert conn_of(server).adm_armed
            sock.close()
        finally:
            await server.dispose()
        serving = db.metrics.serving_counters
        assert serving["inline_bursts"] == serving["native_bursts"] == 3

    asyncio.run(main())


def test_the_python_engine_listener_stays_on_streams():
    async def main():
        server, db = make_server(engine="python")
        await server.start()
        try:
            sock = await connect(server.port)
            await send(sock, b"GCOUNT INC a 3\r\n")
            assert await recv_replies(sock, 1) == b"+OK\r\n"
            (writer,) = server._conns
            assert isinstance(writer, asyncio.StreamWriter)
            sock.close()
        finally:
            await server.dispose()
        assert db.metrics.serving_counters["inline_bursts"] == 0

    asyncio.run(main())
