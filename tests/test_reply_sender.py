"""The reply's `send` leaves the loop thread (PR 40): every reply byte of
a served connection is handed to the native sender
(native/reply_sender.cpp), over real sockets — one door a connection, a
slow consumer that delays nobody else, backpressure that parks only the
connection that is behind, a closed connection that still gets every
reply, reset / descriptor reuse, `dispose`, and the oracle door (`writer.write`) of a node on the Python tables.
The jax-free half (two producers against the thread, under TSAN) is
tests/test_native_tsan.py."""

from __future__ import annotations

import asyncio
import socket
import struct
import threading
import time

import pytest

import jylis_tpu  # noqa: F401
from jylis_tpu.models.database import Database
from jylis_tpu.native.engine import make_engine
from jylis_tpu.obs import prom
from jylis_tpu.server.server import Server
from jylis_tpu.utils.config import Config
from jylis_tpu.utils.log import Log
from jylis_tpu.utils.metrics import metric_lines
from test_native_drive import TS0, post, resp

pytestmark = pytest.mark.skipif(
    make_engine() is None, reason="native engine unavailable (no toolchain)"
)

# jy_snd_stats order (ServeEngine.sender_stats)
SENDS, PARTIAL, WAKES, DROPPED, PENDING_MAX, BUSY_US, PENDING, RUNNING = range(8)


class Node:
    """A Server on its own loop in a thread, so that a test's blocking
    sockets are real clients of it."""

    def __init__(self, db: Database):
        self.db = db
        self.loop = asyncio.new_event_loop()
        cfg = Config()
        cfg.port = "0"
        cfg.log = Log.create_none()
        self.server = Server(cfg, db)
        self._thread = threading.Thread(target=self.loop.run_forever, daemon=True)
        self._thread.start()
        self.call(self.server.start())
        self.port = self.server.port

    def call(self, coro, timeout: float = 30.0):
        return asyncio.run_coroutine_threadsafe(coro, self.loop).result(timeout)

    def connect(self, rcvbuf: int = 0) -> socket.socket:
        s = socket.socket()
        if rcvbuf:  # before connect: it bounds the window that is offered
            s.setsockopt(socket.SOL_SOCKET, socket.SO_RCVBUF, rcvbuf)
        s.settimeout(30)
        s.connect(("127.0.0.1", self.port))
        s.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
        return s

    def stats(self) -> list[int]:
        return self.db.native_engine.sender_stats()

    def close(self) -> None:
        try:
            self.call(self.server.dispose())
        finally:
            self.loop.call_soon_threadsafe(self.loop.stop)
            self._thread.join(10)
            assert not self._thread.is_alive()
            self.loop.close()


@pytest.fixture
def node():
    n = Node(Database(identity=1))
    yield n
    n.close()


def read_exactly(s: socket.socket, n: int) -> bytes:
    got = bytearray()
    while len(got) < n:
        chunk = s.recv(min(1 << 20, n - len(got)))
        assert chunk, f"closed after {len(got)} of {n} bytes"
        got += chunk
    return bytes(got)


def read_to_eof(s: socket.socket) -> bytes:
    got = bytearray()
    while True:
        try:
            chunk = s.recv(1 << 20)
        except ConnectionResetError:
            break
        if not chunk:
            break
        got += chunk
    return bytes(got)


def wait_for(cond, what: str, seconds: float = 20.0) -> None:
    deadline = time.monotonic() + seconds
    while not cond():
        assert time.monotonic() < deadline, f"timed out waiting for {what}"
        time.sleep(0.005)


# one connection's stream across every path a reply can take: the
# engine's bursts, commands the engine hands back (a TLOG TRIM, a UJSON
# GET that misses the render memo), a command too wide for the engine's
# scanner (the connection is demoted for good), the demoted tail, and an
# error reply before the close
CROSS_PATH = (
    resp(b"GCOUNT", b"INC", b"g", b"5")
    + resp(b"GCOUNT", b"GET", b"g")
    + resp(b"TREG", b"SET", b"r", b"v1", b"7")
    + resp(b"TREG", b"GET", b"r")
    + b"".join(
        resp(b"TLOG", b"INS", b"t", post(i), b"%d" % (TS0 + i)) for i in range(40)
    )
    + resp(b"TLOG", b"TRIM", b"t", b"30")
    + resp(b"TLOG", b"GET", b"t")
    + resp(b"TLOG", b"SIZE", b"t")
    + resp(b"UJSON", b"SET", b"d", b"a", b'"x"')
    + resp(b"UJSON", b"GET", b"d")
    + resp(b"UJSON", b"GET", b"d")
    + resp(b"PNCOUNT", b"DEC", b"p", b"3")
    + resp(b"PNCOUNT", b"GET", b"p")
    + resp(b"GCOUNT", b"GET", *[b"x"] * 1100)
    + resp(b"TREG", b"GET", b"r")
    + resp(b"GCOUNT", b"INC", b"g", b"1")
    + resp(b"GCOUNT", b"GET", b"g")
    + resp(b"TLOG", b"GET", b"t", b"5")
    + b"*1\r\n$-5\r\n"
)


def stream_of(db: Database) -> bytes:
    n = Node(db)
    try:
        s = n.connect()
        s.sendall(CROSS_PATH)
        return read_to_eof(s)
    finally:
        n.close()


def test_cross_path_order_is_the_python_paths_stream_byte_for_byte():
    native, oracle = Database(identity=1), Database(identity=1, engine="python")
    got, want = stream_of(native), stream_of(oracle)
    assert got == want
    assert want.count(b"\r\n") > 100 and want.endswith(b"\r\n")
    serving = native.serving_totals()
    # all three paths answered on the one connection, through one door
    assert serving["native_cmds"] > 40
    assert serving["deferred_cmds"] >= 2 and serving["demoted_conn_cmds"] >= 4
    assert serving["demotions"] == 1 and serving["loop_sends"] == 0
    assert native.native_engine.sender_stats()[SENDS] >= 6


def test_a_node_on_the_python_tables_starts_no_thread_and_counts_loop_sends():
    oracle = Database(identity=1, engine="python")
    assert stream_of(oracle)
    assert oracle.serving_totals()["loop_sends"] >= 1  # one flush a parsed batch
    lines = metric_lines(serving=oracle.serving_totals(), registry=oracle.metrics)
    assert "ENGINE sender_sends 0" in lines
    assert f"SERVING loop_sends {oracle.serving_totals()['loop_sends']}" in lines
    text = prom.render(oracle)
    assert "jylis_sender_busy_seconds_total 0.000000" in text
    # and a native Database that serves no connection starts none either
    idle = Database(identity=1)
    assert idle.native_engine.sender_stats()[RUNNING] == 0


def test_the_senders_counters_are_on_the_three_surfaces(node):
    s = node.connect()
    for i in range(20):
        if i == 10:
            # well past the thread's idle spin (200 us): it is asleep at
            # the next hand-off, whatever a round trip takes
            time.sleep(0.02)
        s.sendall(resp(b"GCOUNT", b"INC", b"k", b"1"))
        assert read_exactly(s, 5) == b"+OK\r\n"
    # the client can hold the last reply before either thread has counted
    # it: the hand-off adds to `sends` after it has queued the job (the
    # sender may send it first), and the sender takes the job's bytes off
    # `pending` and adds its `busy` only after `send` has returned
    wait_for(
        lambda: node.stats()[SENDS] == 20 and node.stats()[PENDING] == 0,
        "the sender's counts to settle",
    )
    st = node.stats()
    assert st[SENDS] == 20 and st[RUNNING] == 1 and st[PARTIAL] == 0
    assert 1 <= st[WAKES] <= 20 and st[BUSY_US] > 0
    # a reply is 5 bytes; the next hand-off can come before the thread
    # has taken the last send's count off
    assert st[PENDING_MAX] in (5, 10)
    db = node.db
    text = prom.render(db)
    assert 'jylis_drain_total{type="ENGINE",kind="sender_sends"} 20' in text
    assert 'jylis_serving_total{kind="loop_sends"} 0' in text
    busy = float(text.split("\njylis_sender_busy_seconds_total ")[1].split()[0])
    assert busy > 0.0
    lines = metric_lines(serving=db.serving_totals(), registry=db.metrics)
    assert "ENGINE sender_sends 20" in lines
    assert f"ENGINE sender_pending_max_bytes {st[PENDING_MAX]}" in lines
    assert ", 20 sender_sends, 0 sender_partial, " in db.metrics.report()
    s.close()


BIG = 16 * 1024 * 1024 - 4096  # a reply of 16 MiB, under the buffer's ceiling


def test_a_16_mib_reply_to_a_slow_consumer_arrives_whole_and_delays_nobody(node):
    value = bytes(range(256)) * (BIG // 256)
    setup = node.connect()
    setup.sendall(resp(b"TREG", b"SET", b"big", value, b"9"))
    assert read_exactly(setup, 5) == b"+OK\r\n"
    setup.close()

    slow = node.connect(rcvbuf=4096)
    slow.sendall(resp(b"TREG", b"GET", b"big"))
    want = b"*2\r\n$%d\r\n%s\r\n:9\r\n:0\r\n" % (len(value), value)
    wait_for(lambda: node.stats()[PARTIAL] >= 1, "the socket to refuse bytes")
    # the next command's reply queues behind the refused one, and ITS
    # hand-off tells the handler that its consumer is behind
    slow.sendall(resp(b"GCOUNT", b"GET", b"none"))

    # while the slow consumer has read NOTHING, a second connection's
    # replies are not behind it: 200 round trips, each answered at once
    fast = node.connect()
    worst = 0.0
    for i in range(200):
        t0 = time.monotonic()
        fast.sendall(resp(b"GCOUNT", b"INC", b"f", b"1") + resp(b"GCOUNT", b"GET", b"f"))
        assert read_exactly(fast, 5 + len(b":%d\r\n" % (i + 1))).endswith(
            b":%d\r\n" % (i + 1)
        )
        worst = max(worst, time.monotonic() - t0)
    assert worst < 1.0
    assert node.stats()[PENDING] > BIG // 2  # the reply still waits, whole
    # its handler sleeps for the consumer (serve.write_wait), the loop
    # does not: what the fast connection just showed

    got = bytearray()
    while len(got) < len(want):  # a consumer that reads slowly
        chunk = slow.recv(256 * 1024)
        assert chunk
        got += chunk
        if len(got) < 2 * 1024 * 1024:
            time.sleep(0.002)
    assert bytes(got) == want  # whole, in order, the next reply behind it
    wait_for(lambda: node.stats()[PENDING] == 0, "the queue to empty")
    assert node.db.metrics.hist("serve.write_wait").count >= 1
    assert node.stats()[DROPPED] == 0
    slow.close()
    fast.close()


def test_admission_queue_bytes_parks_only_the_slow_connection(node):
    db = node.db
    db.set_admission("", 1 << 20)  # --admission-queue-bytes 1 MiB
    value = b"v" * (1 << 20)
    setup = node.connect()
    setup.sendall(resp(b"TREG", b"SET", b"mb", value, b"3"))
    assert read_exactly(setup, 5) == b"+OK\r\n"
    setup.close()
    one = b"*2\r\n$%d\r\n%s\r\n:3\r\n" % (len(value), value)

    slow = node.connect(rcvbuf=4096)
    for _ in range(6):  # one GET a read, so one burst each
        slow.sendall(resp(b"TREG", b"GET", b"mb"))
        time.sleep(0.02)
    # the slow connection's handler parks once the node-wide total is
    # past the cap: it stops reading, and what it has queued stays bounded
    wait_for(lambda: db.admission.queued_bytes > 1 << 20, "the cap to be passed")
    time.sleep(0.2)
    assert node.stats()[PENDING] < 6 * len(one)

    fast = node.connect()  # served as before: a native burst a round trip
    native0 = db.serving_totals()["native_cmds"]
    for i in range(100):
        fast.sendall(resp(b"GCOUNT", b"INC", b"f", b"1"))
        assert read_exactly(fast, 5) == b"+OK\r\n"
    assert db.serving_totals()["native_cmds"] - native0 == 100
    assert db.admission.queued_bytes > 1 << 20  # still past it, still served

    assert read_exactly(slow, 6 * len(one)) == one * 6
    wait_for(lambda: db.admission.queued_bytes == 0, "the total to fall")
    slow.close()
    fast.close()


def test_admission_counts_a_reply_no_later_command_follows(node):
    """Clients that ask for ONE large reply each, never read and never
    send again: the bound counts what the sender holds for them (read
    from the sender when the total is compared, not noted at a
    connection's next hand-off, which never comes)."""
    db = node.db
    db.set_admission("", 1 << 20)
    value = b"v" * (8 << 20)
    setup = node.connect()
    setup.sendall(resp(b"TREG", b"SET", b"mb", value, b"3"))
    assert read_exactly(setup, 5) == b"+OK\r\n"
    setup.close()
    idle = [node.connect(rcvbuf=4096) for _ in range(3)]
    for c in idle:
        c.sendall(resp(b"TREG", b"GET", b"mb"))
    wait_for(lambda: node.stats()[PARTIAL] >= 3, "the sockets to refuse bytes")
    held = db.admission.queued_bytes
    assert held == node.stats()[PENDING] and held > 3 << 20
    assert db.admission.metrics_totals()["queued_bytes"] == held
    assert db.admission.admit("read") is not None  # past the cap: refused
    for c in idle:
        c.setsockopt(socket.SOL_SOCKET, socket.SO_LINGER, struct.pack("ii", 1, 0))
        c.close()
    wait_for(lambda: db.admission.queued_bytes == 0, "the total to fall")
    assert db.admission.admit("read") is None
    db.admission.done("read", 0.0)


CLOSING_VALUE = bytes(range(256)) * (8 << 12)  # 8 MiB


def held_for_clients(n: Node) -> int:
    """Reply bytes the node holds: the sender's, and its transports'."""
    eng = n.db.native_engine
    return (eng.sender_pending() if eng is not None else 0) + sum(
        w.transport.get_write_buffer_size() for w in list(n.server._conns)
    )


def closing_stream(db: Database, how: str) -> bytes:
    """What a client that reads slowly gets of pipelined 8 MiB replies
    when the connection is closed behind them: by its own half-close,
    by an error reply (two replies before either), by the node's
    `dispose` (one: the oracle door's handler waits for its consumer
    after the first and never applies a second)."""
    n = Node(db)
    disposer = None
    try:
        s = n.connect(rcvbuf=4096)
        wire = resp(b"TREG", b"SET", b"k", CLOSING_VALUE, b"4") + (
            1 if how == "dispose" else 2
        ) * resp(b"TREG", b"GET", b"k")
        if how == "resp_error":
            wire += b"*1\r\n$-5\r\n"
        s.sendall(wire)
        if how == "half_close":
            s.shutdown(socket.SHUT_WR)
        if how == "dispose":
            wait_for(
                lambda: held_for_clients(n) > len(CLOSING_VALUE) // 4,
                "the reply to be held",
            )
            disposer = threading.Thread(target=n.close)
            disposer.start()
        got = bytearray()
        while chunk := s.recv(1 << 20):  # to the end of the stream
            got += chunk
            if len(got) < 1 << 20:
                time.sleep(0.002)
        s.close()
        return bytes(got)
    finally:
        if disposer is None:
            n.close()
        else:
            disposer.join(30)
            assert not disposer.is_alive()


@pytest.mark.parametrize("how", ["half_close", "resp_error", "dispose"])
def test_a_closed_connection_still_gets_every_reply(how):
    """A closing transport flushes its buffer before it closes the
    socket, and so does the sender: replies that were applied are
    delivered, then the end of the stream, byte for byte the oracle
    door's stream."""
    native = Database(identity=1)
    got = closing_stream(native, how)
    want = closing_stream(Database(identity=1, engine="python"), how)
    one = b"*2\r\n$%d\r\n%s\r\n:4\r\n" % (len(CLOSING_VALUE), CLOSING_VALUE)
    n_replies = 1 if how == "dispose" else 2
    assert want.startswith(b"+OK\r\n" + one * n_replies)
    assert (len(want) > 5 + n_replies * len(one)) == (how == "resp_error")
    assert got == want
    st = native.native_engine.sender_stats()
    assert st[DROPPED] == 0 and st[PENDING] == 0 and st[PARTIAL] >= 1
    assert native.serving_totals()["loop_sends"] == 0


def test_bytes_of_a_closed_connection_never_reach_the_next_one_on_its_descriptor(node):
    value = b"old-" * (2 << 20)  # 8 MiB the first client will not read
    eng = node.db.native_engine
    opened: list[int] = []
    open0 = eng.sender_open
    eng.sender_open = lambda fd, low, high: opened.append(fd) or open0(fd, low, high)
    first = node.connect(rcvbuf=4096)
    first.sendall(resp(b"TREG", b"SET", b"o", value, b"1") + resp(b"TREG", b"GET", b"o"))
    wait_for(lambda: node.stats()[PARTIAL] >= 1, "the socket to refuse bytes")
    assert node.stats()[PENDING] > 0
    # the client RESETS with its reply pending: the handler ends, the
    # sender drops the jobs and closes its own descriptor
    first.setsockopt(socket.SOL_SOCKET, socket.SO_LINGER, struct.pack("ii", 1, 0))
    first.close()
    wait_for(lambda: node.stats()[PENDING] == 0, "the old jobs to be dropped")
    assert node.stats()[DROPPED] > 0
    # (both of its descriptors are free before the next connection draws
    # one: else the sender's duplicate for a NEW connection could take the
    # old number, which then names no connection's socket)
    wait_for(lambda: not node.server._conns, "the handler to end")
    # new connections until one is given the old descriptor's number (the
    # lowest free one: another test's leftovers may free lower ones first)
    later = []
    for i in range(64):
        c = node.connect()
        later.append(c)
        c.sendall(resp(b"GCOUNT", b"INC", b"n%d" % i, b"2") + resp(b"GCOUNT", b"GET", b"n%d" % i))
        assert read_exactly(c, 9) == b"+OK\r\n:2\r\n"
        if opened[-1] == opened[0]:
            break
    assert opened[-1] == opened[0] and len(opened) == len(later) + 1
    for c in later:
        c.settimeout(0.2)
        with pytest.raises(socket.timeout):
            c.recv(1)  # and not one byte of the old reply behind its own
        c.close()


def test_a_peer_reset_with_jobs_queued_ends_the_handler_and_counts_the_drop(node):
    value = b"z" * (8 << 20)
    s = node.connect(rcvbuf=4096)
    s.sendall(resp(b"TREG", b"SET", b"z", value, b"1") + resp(b"TREG", b"GET", b"z"))
    wait_for(lambda: node.stats()[PARTIAL] >= 1, "the socket to refuse bytes")
    assert len(node.server._conns) == 1
    s.setsockopt(socket.SOL_SOCKET, socket.SO_LINGER, struct.pack("ii", 1, 0))
    s.close()  # RST
    wait_for(lambda: not node.server._conns, "the handler to end")
    st = node.stats()
    assert st[DROPPED] > 0 and st[PENDING] == 0
    # the node serves on
    again = node.connect()
    again.sendall(resp(b"TREG", b"GET", b"z"))
    assert read_exactly(again, 20).startswith(b"*2\r\n$%d\r\n" % len(value))
    again.close()


def test_dispose_joins_the_thread_with_connections_open():
    n = Node(Database(identity=1))
    conns = [n.connect() for _ in range(4)]
    for i, s in enumerate(conns):
        s.sendall(resp(b"GCOUNT", b"INC", b"k", b"1"))
        assert read_exactly(s, 5) == b"+OK\r\n"
    blocked = n.connect(rcvbuf=4096)  # one whose client never reads
    blocked.sendall(
        resp(b"TREG", b"SET", b"b", b"b" * (8 << 20), b"1") + resp(b"TREG", b"GET", b"b")
    )
    wait_for(lambda: n.stats()[PARTIAL] >= 1, "the socket to refuse bytes")
    assert n.stats()[RUNNING] == 1
    t0 = time.monotonic()
    n.close()
    # its bytes are waited for while any move, and given up on after a
    # second in which none did
    assert 0.9 < time.monotonic() - t0 < 10.0
    st = n.db.native_engine.sender_stats()
    assert st[RUNNING] == 0 and st[PENDING] == 0 and st[DROPPED] > 0
    for s in conns:
        assert s.recv(1) == b""  # closed by the node
        s.close()
    blocked.close()
