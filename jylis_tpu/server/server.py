"""RESP TCP server: the client API endpoint.

Reference analog: server.pony + server_listen_notify.pony +
server_notify.pony — accept clients on config.port (default 6379, same as
Redis), feed their bytes through the incremental command parser, route
complete commands into Database.apply, and on protocol errors reply with an
error and drop the connection (server_notify.pony:19-22).

Concurrency model: the asyncio loop replaces the per-connection Pony
actors. Commands apply through Database.apply_async — device-bound work
runs in a worker thread under a per-repo lock (models/manager.py), so a
slow drain stalls neither other connections nor the heartbeat. Within one
connection commands complete strictly in order (RESP replies must match
request order): a chunk is settled before the next is looked at.

Two listeners, chosen once from what the database is. With a native
engine a connection is a `_Conn`, an `asyncio.BufferedProtocol`: the
socket is read into the connection's own buffer by the read callback,
which only notes the arrival; every chunk of a loop iteration is then
parsed, applied and handed to the engine's sender in ONE callback at the
head of the next (`Server._serve_ready` -> `_run`), with no stream,
future, wake-up or task step a command; only a round that has to sleep
(a held repo lock, a command for the Python path, a consumer that is
behind, the byte bound) gets a coroutine (`Server._slow`), at most one
a connection, and both call the same round (`Server._settle`). A database with engine="python" (no toolchain, the
oracle) keeps asyncio's streams and `_handle_client`; the two share the
Python path's dispatch (`_drain_parser`, `_dispatch_py`) and nothing of
the read side.

A native burst takes the repo locks of the types its commands name,
all at once (RepoLock.take_all) and no other, and lets go before it
yields, so it never waits for a lock that nobody holds and never makes
anyone queue: one loop iteration settles every connection whose bytes
are ready. Only a holder that keeps a lock across a yield (a threaded
drain, a cluster apply, a flush) makes bursts sleep, and only the
bursts whose next command names ITS type: its release wakes them all at
once, and a client of another type is served in the engine beside it.
"""

from __future__ import annotations

import asyncio
import os
import socket
from time import perf_counter

from .. import admission as admission_mod
from .. import faults
from ..models.database import Database
from ..models.manager import RepoLock
from ..native.resp import make_parser
from ..utils.net import ipv4_port
from .resp import Respond, RespError


# dispose: how long the sender's closed connections may go without a
# byte moving before what they still hold is given up on
_LINGER_STALL_S = 1.0
_LINGER_TICK_S = 0.01


class _Door:
    """The ONE way a connection's reply bytes leave, decided once from
    what the connection is: a plain TCP socket on a database with a
    native engine hands every reply to the engine's sender thread
    (native/reply_sender.cpp), so the loop's part of a reply is a copy
    and a queue push; anything else (a Database built with
    engine="python" or on a host with no toolchain, a writer with no
    socket) keeps `writer.write`, the oracle path. A connection that has
    a sender NEVER writes its asyncio transport: engine replies, the
    Python path's `out`, an error before a close all take this door, so
    no second queue can overtake the first and the reply stream stays
    in command order."""

    __slots__ = ("writer", "_server", "_engine", "_conn", "_behind")

    def __init__(self, server: "Server", writer, engine):
        self.writer = writer
        self._server = server
        self._engine = engine
        self._conn = -1  # the sender's id of this connection
        self._behind = 0  # what the sender's last answer said
        transport = writer.transport
        sock = transport.get_extra_info("socket")
        if (
            engine is not None
            and sock is not None
            and sock.type == socket.SOCK_STREAM
            and sock.family in (socket.AF_INET, socket.AF_INET6)
            and transport.get_extra_info("sslcontext") is None
        ):
            low, high = transport.get_write_buffer_limits()
            self._conn = engine.sender_open(sock.fileno(), low, high)

    def write(self, data: bytes) -> None:
        if self._conn >= 0:
            self._behind = self._engine.sender_send(
                self._conn, len(data), data
            )
        else:
            self.writer.write(data)
            self._server._reg.note_serving("loop_sends")

    def write_held(self, n: int) -> None:
        """`write` of the first ``n`` bytes of the engine's reply array
        (a burst's replies, still where `scan_apply` left them)."""
        if self._conn >= 0:
            self._behind = self._engine.sender_send(self._conn, n)
        else:
            self.writer.write(self._engine.reply_bytes(n))
            self._server._reg.note_serving("loop_sends")

    def unsent(self) -> int:
        """Bytes this connection's consumer is behind by. A transport's
        are what a write left in its buffer (the socket did not take
        the reply whole); the sender's are its pending bytes once the
        socket has refused some (or replies pile up past the high-water
        mark behind one the thread has not got through), as its last
        answer said: a handler learns of a refusal at its NEXT hand-off,
        and the answer is looked up again only while it was not 0 (so a
        burst whose consumer keeps up pays no call here)."""
        if self._conn < 0:
            return self.writer.transport.get_write_buffer_size()
        if self._behind > 0:
            self._behind = self._engine.sender_behind(self._conn)
        return max(self._behind, 0)

    def buffered(self) -> int:
        """What `--admission-queue-bytes` notes for this connection: the
        bytes in its transport's buffer. A sender's door notes none: the
        admission controller reads what the sender holds, for all of
        its connections, from the sender itself when it compares
        (`AdmissionController.held_elsewhere`), so a reply that was
        handed over a microsecond ago and one a consumer has refused
        for a minute both count, as long as they are held."""
        if self._conn < 0:
            return self.writer.transport.get_write_buffer_size()
        return 0

    async def drain(self) -> None:
        """`writer.drain()` for either door: past the high-water mark the
        handler sleeps until the connection is written down to the
        low-water mark (the sender signals the loop; a writer's drain
        also raises a connection error its reader saw)."""
        if self._conn < 0:
            await self.writer.drain()
        elif self._engine.sender_wait(self._conn):
            await self._server._sender_wait(self._conn)

    def close(self) -> None:
        """Before the socket is closed: the sender takes no more for the
        connection, writes out what it holds and THEN lets go of its
        descriptor, so a client that pipelined commands and half-closed,
        or that earned an error reply, reads every reply and then the
        end of the stream, as it does behind a closing transport (which
        flushes its buffer before it closes the socket). Only a peer's
        reset, or the sender's stop, drops bytes. Later writes are
        dropped and counted."""
        if self._conn >= 0:
            self._engine.sender_close(self._conn)
            self._server._sender_woken(self._conn)


class _PyPath:
    """What the per-command Python path needs of a connection: the
    parser, and the reply buffer `out` behind `resp`. Python-path
    replies buffer in `out` and flush once per parsed batch (bounded):
    a reply per write() was one tiny TCP segment per COMMAND, and a
    demoted connection's pipelined burst became a per-segment wakeup
    storm — measured 30-40x under the native path's batched writes on
    the same burst. The engine's replies bypass this buffer (they
    arrive pre-batched); it is written out before every direct engine
    write, so cross-path reply order is exactly command order."""

    __slots__ = ("parser", "out", "resp", "door", "_server")

    def __init__(self, server: "Server", door: _Door):
        # lib() is memoised at boot (warmup builds an auto-engine
        # Database before serving starts), so this never reaches the
        # loader's listdir/compile path on the loop
        self.parser = make_parser()  # native scanner when built
        self.out = bytearray()
        self.resp = Respond(self.out.extend)
        self.door = door
        self._server = server

    def flush(self, bound: int = 0) -> float:
        """Hand `out` to the door once it is past ``bound``; the
        seconds this put into pipeline.reply_write (0.0: nothing
        written, or observation off), which a caller with a stage
        open moves that stage's start forward by."""
        out = self.out
        if len(out) <= bound:
            return 0.0
        server = self._server
        t_w = perf_counter() if server._reg.enabled else 0.0
        self.door.write(bytes(out))
        out.clear()
        if not t_w:
            return 0.0
        seconds = perf_counter() - t_w
        server._h_reply_write.record(seconds)
        return seconds


# what a round says of itself (`Server._settle`, `Server._run`)
_DONE = 0  # every complete command is settled: the chunk's tail is open
_AGAIN = 1  # another round at once (engine rc 2, rc 5)
_DEFER = 2  # one command for the Python path first (`_Conn.unhandled`)
_SLEEP = 3  # nothing taken: a lock the round names is held, or the
#             native.scan_apply failpoint is armed (its await is a
#             coroutine's)
_DEMOTE = 4  # this connection is the Python parser's from here on

# a connection's receive buffer: what one `recv_into` may take, and the
# free tail under which room is made before the next
_RECV = 1 << 16
_RECV_MIN = _RECV >> 2


class _Conn(asyncio.BufferedProtocol):
    """One client connection of a database with a native engine: the
    transport reads the socket INTO this object's buffer (`get_buffer`
    hands out its free tail, `buffer_updated` is the chunk's arrival,
    which queues the connection with the server) and `serve` settles
    the chunk, with no task wherever no round of it has to sleep. The
    pending bytes are ``buf[start:end]``; a command split across chunks
    stays there until its rest arrives, and a buffer that is empty
    after a round starts again at 0, so at depth 1 nothing is ever
    moved.

    At most ONE slow-path task a connection (`Server._slow`). While it
    is in flight an arrival only appends (replies leave in command
    order: the task serves what arrived when it ends) and pauses the
    transport, which is resumed when the task ends: a handler asleep
    behind a held lock reads at most one more chunk, the kernel's
    buffer fills and a pipelining client blocks, as behind a
    StreamReader at its limit. The object is its door's `writer`
    (`transport`, `close`): `Server._conns` and `dispose` find it like
    a stream's writer."""

    __slots__ = (
        "transport", "door", "py", "unhandled", "_server", "_buf", "_view",
        "_start", "_end", "task", "arrived", "_paused", "_eof", "lost",
        "native", "routed_tail", "over_cap", "adm_armed", "t_arr",
        "t_route", "t_tail", "_t_rd", "queued", "want", "t_wait",
    )

    def __init__(self, server: "Server"):
        self._server = server
        self.transport = self.door = self.py = self.task = None
        self._buf = bytearray(_RECV)
        self._view = memoryview(self._buf)
        self._start = self._end = 0
        self.arrived = self._paused = self._eof = self.lost = False
        self.queued = False  # in the server's list of chunks to take up
        self.native = True  # False: demoted, the Python parser's for good
        self.routed_tail = False  # the parser may hold a routed chunk's tail
        self.over_cap = False  # --admission-queue-bytes passed after a round
        self.unhandled = None  # the command a round handed back (_DEFER)
        self.want = 0  # the one lock a round found held (_SLEEP), as a set
        self.t_wait = 0.0  # lock.wait_serve's token, begun where it was found
        self.adm_armed = False
        # the stage chain's open stamps (0.0: none, or observation off)
        self.t_arr = self.t_route = self.t_tail = self._t_rd = 0.0

    # ---- the transport's callbacks -----------------------------------------

    def connection_made(self, transport) -> None:
        server = self._server
        self.transport = transport
        if server._closing:
            # accepted just before dispose: the close loop could not see
            # this connection yet, and wait_closed would wait on it
            transport.close()
            return
        # pipeline.accept: one sample per connection, entry to ready for
        # the first read — the setup cost a new client pays before its
        # first command can even be parsed
        t_acc = perf_counter() if server._reg.enabled else 0.0
        engine = server._engine
        door = _Door(server, self, engine)
        if door._conn < 0:
            # no sender could be had for this socket: the connection is
            # the streams path's from birth (its own door: writer.write).
            # Not a `_Conn` with native=False: a door without a sender
            # needs a writer whose `drain()` sleeps on the transport's
            # pause_writing / resume_writing, which is the streams'
            # protocol's to keep and would be a second copy here
            loop = asyncio.get_running_loop()
            proto = asyncio.StreamReaderProtocol(
                asyncio.StreamReader(loop=loop), server._handle_client,
                loop=loop,
            )
            transport.set_protocol(proto)
            proto.connection_made(transport)
            return
        self.door = server._conns[self] = door
        self.py = _PyPath(server, door)
        self.adm_armed = server._database.admission.armed
        if t_acc:
            self._t_rd = perf_counter()
            server._h_accept.record(self._t_rd - t_acc)

    def get_buffer(self, sizehint: int):
        end = self._end
        if not end:
            return self._view
        if len(self._buf) - end < _RECV_MIN:
            self.make_room()
            end = self._end
        return self._view[end:]

    def buffer_updated(self, n: int) -> None:
        self._end += n
        if self.task is not None:
            # a slow-path task of this connection is in flight: append,
            # and read no more until it ends
            self.arrived = True
            if not self._paused:
                self._paused = True
                self.transport.pause_reading()
        elif not self.queued:
            self.queued = True
            self._server.chunk_ready(self)

    def serve(self) -> None:
        """Settle what has arrived (called by `Server._serve_ready`,
        once an iteration for every connection whose chunk came in),
        without a task wherever no round of it has to sleep."""
        self.queued = False
        if self.lost:
            return
        server = self._server
        # where the chunk is taken up, pipeline.read ends and
        # serve.route begins
        t_rt = perf_counter() if server._reg.enabled else 0.0
        if t_rt and self._t_rd:
            server._h_read.record(t_rt - self._t_rd)
        self.t_route = t_rt
        if self.adm_armed:
            # the overload signal's arrival stamp: queue time for every
            # command in this chunk runs from its arrival
            self.t_arr = t_rt or perf_counter()
        if (
            self.native
            and not self.routed_tail
            and not server._capped_busy()
        ):
            st = server._run(self, True)
            if st == _DONE and not self.over_cap:
                door = self.door
                if not (door._behind > 0 and door.unsent()):
                    self.close_tail(perf_counter() if self.t_tail else 0.0)
                    if self._eof:
                        self.finish()
                    return
        else:
            st = None
        self.task = asyncio.get_running_loop().create_task(
            server._slow(self, st)
        )

    def eof_received(self) -> bool:
        if self.task is not None or self.queued:
            self._eof = True  # closed once what arrived is served
        else:
            self.finish()
        return True  # the transport is closed by `finish`, door first

    def connection_lost(self, exc) -> None:
        self.lost = True
        if self.task is None:
            self._forget()

    # ---- the buffer --------------------------------------------------------

    def pending(self):
        return self._view[self._start:self._end]

    def consume(self, n: int) -> None:
        start = self._start = self._start + n
        if start == self._end:
            self._start = self._end = 0
            if len(self._buf) > _RECV:  # grown for one large command
                self._buf = bytearray(_RECV)
                self._view = memoryview(self._buf)

    def take_pending(self) -> bytes:
        """The pending bytes, which leave the buffer (for the parser)."""
        data = bytes(self._view[self._start:self._end])
        self.consume(len(data))
        return data

    def make_room(self, head: bytes = b"") -> None:
        """Pending bytes to the front, behind ``head`` (a split
        command's first bytes that the Python parser gives back), in a
        buffer doubled until a quarter of it is free."""
        data = head + bytes(self._view[self._start:self._end])
        size = len(self._buf)
        while size - len(data) < size >> 2:
            size <<= 1
        if size != len(self._buf):
            self._buf = bytearray(size)
            self._view = memoryview(self._buf)
        self._buf[:len(data)] = data
        self._start, self._end = 0, len(data)

    # ---- the connection's end ----------------------------------------------

    def close_tail(self, now: float) -> None:
        """``now`` (0.0: observation off) is where serve.tail ends and
        pipeline.read, the wait for the connection's next arrival,
        begins: where `serve` returns, or the slow path is done with a
        chunk."""
        if now and self.t_tail:
            self._server._h_tail.record(now - self.t_tail)
        self.t_tail = 0.0
        self._t_rd = now

    def slow_done(self, alive: bool) -> None:
        """The slow-path task has ended; not ``alive``: with the
        connection (an error reply, a reset, a cancel)."""
        self.task = None
        if self.lost:
            self._forget()
        elif self._eof or not alive:
            self.finish()
        elif self._paused:
            self._paused = False
            self.transport.resume_reading()

    def finish(self) -> None:
        """This side ends the connection: the door BEFORE the socket,
        so the sender writes out what it holds and then lets go."""
        self._eof = True
        self.door.close()
        self.transport.close()

    def close(self) -> None:  # what `dispose` asks of a writer
        self.transport.close()

    def _forget(self) -> None:
        door = self.door
        if door is not None:
            server = self._server
            server._database.admission.drop_conn(id(self))
            server._conns.pop(self, None)
            door.close()


class Server:
    def __init__(self, config, database: Database):
        self._config = config
        self._database = database
        self._log = config.log
        self._server: asyncio.base_events.Server | None = None
        # every client connection's door, by its writer (a stream's
        # StreamWriter, or the `_Conn` itself)
        self._conns: dict[object, _Door] = {}
        self._closing = False
        # handlers asleep until the sender has written their connection
        # down (serve.write_wait), by the sender's connection id; the
        # sender's eventfd is read by the loop from the first such sleep
        self._write_waiters: dict[int, asyncio.Future] = {}
        self._notify_fd = -1
        # connections whose chunk has arrived and is not taken up yet
        self._ready: list[_Conn] = []
        # dispatch-latency seams (obs/): one histogram per serving path —
        # a native burst (one engine scan_apply call settling many
        # commands) vs one Python-path dispatch (deferred, demoted, or
        # busy-routed command). Resolved once; the registry's `enabled`
        # flag is checked per record so an obs-off run skips the clock
        # reads too.
        self._reg = database.metrics
        self._h_burst = self._reg.hist("server.native_burst")
        self._h_py = self._reg.hist("server.py_dispatch")
        # serving-pipeline profiler (obs/): per-stage timers across the
        # whole RESP path, so the socket tax a client can only see as
        # one number is attributable stage by stage.
        # Each record is gated on the registry's `enabled` flag at the
        # seam, and the dispatch stage REUSES the burst/py elapsed above
        # rather than reading the clock again — the native hot path pays
        # zero additional perf_counter calls for the profiler.
        self._h_accept = self._reg.hist("pipeline.accept")
        self._h_read = self._reg.hist("pipeline.read")
        self._h_parse = self._reg.hist("pipeline.parse")
        self._h_classify = self._reg.hist("pipeline.classify")
        self._h_dispatch = self._reg.hist("pipeline.dispatch")
        self._h_reply_write = self._reg.hist("pipeline.reply_write")
        # a served burst's stages (docs/observability.md): ONE chain of
        # perf_counter stamps from where the chunk is taken up
        # (`_Conn.serve` entered; a stream's read returning) to where
        # `serve` returns (the next read's call), each boundary read once and
        # shared by the stage it ends and the stage it begins — route,
        # server.native_burst, pipeline.reply_write, tail. Histograms
        # only, no profiler annotation per burst. With pipeline.parse
        # and serve.py_apply (models/manager.py) they tile the handler's
        # share of loop.busy; what is left of it is asyncio's own (the
        # selector's callback, its recv_into and the arrival's note).
        # write_wait is a
        # drain() called with bytes the consumer has not taken: NOT loop
        # work, the open stage stops before it and goes on after it.
        self._h_route = self._reg.hist("serve.route")
        self._h_tail = self._reg.hist("serve.tail")
        self._h_write_wait = self._reg.hist("serve.write_wait")
        # lock.wait_serve (obs/span.py): wanting the repo lock a round's
        # first command names to holding it — what pipeline.dispatch and
        # server.py_dispatch INCLUDE for every command that queues behind
        # a drain, summed over connections; server.native_burst does not.
        # A burst records it only when it slept: a take of free locks
        # is the route's, and costs neither clock read nor annotation
        self._s_lock_wait = self._reg.seam("lock.wait_serve")
        # what a native round takes, by the set of types it names (the
        # engine's `held` bits): (locks, their managers each with its
        # type's bit number, every OTHER engine lock). Locks in the
        # DATABASE's map's order (TREG, TLOG, G, PN, UJSON, MAP), the
        # order database.all_locks takes them in
        self._mgrs = self._rounds = ()
        self._engine = database.native_engine
        if self._engine is not None:
            self._mgrs = mgrs = tuple(
                database.manager(n) for n in self._ENGINE_TYPES
            )
            order = (2, 3, 0, 1, 4, 5)
            self._rounds = tuple(
                (
                    tuple(mgrs[i]._lock for i in order if held >> i & 1),
                    tuple((mgrs[i], i) for i in order if held >> i & 1),
                    tuple(mgrs[i]._lock for i in order if not held >> i & 1),
                )
                for held in range(1 << len(mgrs))
            )

    async def start(self) -> None:
        try:
            port = int(self._config.port)
            # which listener is decided once, from what the database is
            if self._engine is not None:
                self._server = await asyncio.get_running_loop().create_server(
                    lambda: _Conn(self), host=None, port=port
                )
            else:
                self._server = await asyncio.start_server(
                    self._handle_client, host=None, port=port
                )
        except OSError as e:
            self._log.err() and self._log.e(f"server listen failed: {e}")
            raise
        self._log.info() and self._log.i("server listen ready")

    @property
    def port(self) -> int:
        assert self._server is not None
        return ipv4_port(self._server)

    async def _handle_client(
        self, reader: asyncio.StreamReader, writer: asyncio.StreamWriter
    ) -> None:
        """A connection on asyncio's streams: every connection of a
        database with no native engine (the oracle), and one of a native
        node whose socket the sender could not take. Every command is
        the Python parser's and `_dispatch_py`'s."""
        if self._closing:
            # accepted just before dispose: the close loop could not see
            # this writer yet, and wait_closed would wait on it forever
            writer.close()
            return
        reg = self._reg
        # pipeline.accept: one sample per connection, handler entry to
        # first read — the setup cost a new client pays before its first
        # command can even be parsed
        t_acc = perf_counter() if reg.enabled else 0.0
        door = self._conns[writer] = _Door(self, writer, None)
        py = _PyPath(self, door)
        try:
            adm_armed = self._database.admission.armed
            if t_acc:
                self._h_accept.record(perf_counter() - t_acc)
            while True:
                # pipeline.read: one socket read await. Deliberately
                # includes client idle time — under saturation this IS
                # the kernel-queue wait, and an idle connection's long
                # reads land in the top buckets where windowed quantiles
                # (SYSTEM LATENCY WINDOW) can separate them from load.
                t_rd = perf_counter() if reg.enabled else 0.0
                data = await reader.read(1 << 16)
                t_rt = 0.0
                if t_rd:
                    t_rt = perf_counter()
                    self._h_read.record(t_rt - t_rd)
                if not data:
                    break
                # the overload signal's arrival stamp: queue time for
                # every command in this chunk runs from this read
                t_arr = (t_rt or perf_counter()) if adm_armed else 0.0
                py.parser.append(data)
                if not await self._drain_parser(py, t_arr, False):
                    break
        except (ConnectionResetError, BrokenPipeError, asyncio.CancelledError):
            pass
        finally:
            self._database.admission.drop_conn(id(writer))
            del self._conns[writer]
            door.close()
            writer.close()
            try:
                await writer.wait_closed()
            except (ConnectionResetError, BrokenPipeError):
                pass

    async def _drain_parser(self, py: _PyPath, t_arr: float, routed: bool) -> bool:
        """Dispatch every command the Python parser holds, in order, and
        write their replies out. The Python path's commands count by
        cause, ``routed`` (the chunk took the Python path for busy()) or
        a connection with no engine or demoted (the third cause, the
        engine's hand-back, is `_apply_native`'s), once dispatched AND
        applied: a command asleep in a lock's line is in neither count
        nor demoted_cmds, so the two agree at any edge. False: the
        stream is malformed, the error reply is written and the
        connection ends."""
        reg = self._reg
        door = py.door
        try:
            # pipeline.parse: manual next() so each Python-path command
            # parse is timed individually; RespError still propagates
            # to the handler below exactly as the for-loop form raised it
            it = iter(py.parser)
            while True:
                t_ps = perf_counter() if reg.enabled else 0.0
                cmd = next(it, None)
                if t_ps:
                    self._h_parse.record(perf_counter() - t_ps)
                if cmd is None:
                    break
                await self._dispatch_py(py.resp, cmd, door, py.out, t_arr)
                if routed:
                    reg.note_serving("busy_routed_cmds")
                else:
                    reg.note_serving("demoted_conn_cmds")
                py.flush(1 << 16)  # bound the reply buffer mid-burst
        except RespError as e:
            py.resp.err(str(e))
            py.flush()
            return False
        py.flush()
        await door.drain()
        return True

    async def _dispatch_py(self, resp, cmd, door, out, t_arr=0.0) -> None:
        """ONE Python-path dispatch (demoted loop and the native path's
        deferred commands share it): the overload-armor admission gate
        (admission.py) in front of Database.apply_async. When armed it
        classifies the command (SESSION WRAP/READ inherit their inner
        command's class), refreshes this connection's queued-reply-bytes
        accounting, and either refuses up front with a typed BUSY
        (retry-after hint included, before any session flush / repo
        lock / drain is paid for) or dispatches and feeds the overload
        state machine. Unarmed costs two attribute reads.

        ``t_arr`` is the perf_counter stamp of the socket read that
        delivered this command's chunk. The latency fed to the state
        machine runs from THERE, not from dispatch start: under an open
        loop the queueing delay lives in the connection's parsed-burst
        backlog (a 64 KiB chunk is thousands of commands drained
        sequentially), and a service-time-only EWMA sits flat at
        sub-millisecond while clients wait seconds — the signal must
        see time-in-our-own-queue or the node never declares overload."""
        adm = self._database.admission
        if adm.armed:
            adm.note_conn_queued(id(door.writer), door.buffered() + len(out))
            # pipeline.classify: the admission toll per command on an
            # armed node — classify plus the gate's token walk, timed
            # for refusals and admissions alike
            t_cl = perf_counter() if self._reg.enabled else 0.0
            cls = admission_mod.classify(cmd)
            hint = await admission_mod.gate(adm, cls)
            if t_cl:
                self._h_classify.record(perf_counter() - t_cl)
            if hint is not None:
                resp.err(
                    admission_mod.busy_reply(
                        cls, hint, "node is shedding this class"
                    )
                )
                # the refusal path's ONLY await: without it a
                # backlogged chunk of thousands of shed commands runs
                # as one synchronous slab, and every OTHER connection's
                # (protected, admitted) commands stall behind it —
                # measured as ~300ms protected-read tails at 4x offered
                # load while the shed itself took microseconds
                await asyncio.sleep(0)
                return
            t0 = perf_counter()
            await self._database.apply_async(resp, cmd)
            t1 = perf_counter()
            adm.done(cls, t1 - (t_arr or t0))
            if self._reg.enabled:
                self._h_py.record(t1 - t0)
                self._h_dispatch.record(t1 - t0)
            return
        t0 = perf_counter() if self._reg.enabled else 0.0
        await self._database.apply_async(resp, cmd)
        if t0:
            el = perf_counter() - t0
            self._h_py.record(el)
            self._h_dispatch.record(el)

    # the engine's type order (serve_engine.cpp scan_apply2: `held`'s
    # bits, `changed`'s cells)
    _ENGINE_TYPES = ("GCOUNT", "PNCOUNT", "TREG", "TLOG", "UJSON", "MAP")

    async def _write_wait(self, door, t_stage: float) -> float:
        """``await door.drain()`` with bytes the consumer has not taken:
        the socket did not take the reply whole. serve.write_wait, NOT
        loop work — the caller's open stage (started at ``t_stage``)
        stops before it and goes on after it: its start stamp comes back
        moved forward by the wait. Below asyncio's high-water mark
        (64 KiB) the drain returns at once and the sample is the call's
        own cost; past it the handler sleeps until the transport, or the
        sender, has written down to the low-water mark."""
        t0 = perf_counter() if t_stage else 0.0
        await door.drain()
        if not t0:
            return 0.0
        seconds = perf_counter() - t0
        self._h_write_wait.record(seconds)
        return t_stage + seconds

    def _sender_wait(self, conn: int) -> asyncio.Future:
        """The future a handler sleeps on while the sender writes its
        connection down: set from `_on_sender_notify` (the sender cannot
        call the loop; it signals one eventfd the loop reads), or by the
        door's close."""
        loop = asyncio.get_running_loop()
        if self._notify_fd < 0:
            self._notify_fd = self._engine.sender_notify_fd()
            loop.add_reader(self._notify_fd, self._on_sender_notify)
        fut = self._write_waiters[conn] = loop.create_future()
        return fut

    def _on_sender_notify(self) -> None:
        try:
            os.read(self._notify_fd, 8)
        except BlockingIOError:
            pass
        engine = self._engine
        for conn in list(self._write_waiters):
            if not engine.sender_wait(conn):  # fired, closed or dead
                self._sender_woken(conn)

    def _sender_woken(self, conn: int) -> None:
        fut = self._write_waiters.pop(conn, None)
        if fut is not None and not fut.done():
            fut.set_result(None)

    def chunk_ready(self, conn: _Conn) -> None:
        """A connection's chunk has arrived (`_Conn.buffer_updated`): it
        is taken up with every other chunk of this loop iteration, in
        ONE callback at the head of the next (which starts at once: the
        ready queue is not empty, so `select()` does not wait). The
        iteration's `recv_into` calls stay back to back and the Python
        of the rounds runs in one stretch behind them, as the streams'
        task steps did; a round run INSIDE each read callback, between
        two system calls, cost ~17 us a command more on the chip host
        than the streams it replaced (PERF.md section 6, PR 44)."""
        ready = self._ready
        if not ready:
            asyncio.get_running_loop().call_soon(self._serve_ready)
        ready.append(conn)

    def _serve_ready(self) -> None:
        ready, self._ready = self._ready, []
        for conn in ready:
            try:
                conn.serve()
            except Exception as e:  # jlint: broad-ok — one connection's
                # failure must not strand the chunks taken up behind it
                self._log.err() and self._log.e(f"client connection dropped: {e!r}")
                conn.transport.abort()

    def _capped_busy(self) -> bool:
        """Under --admission-cap a wait for a repo lock must count in
        its manager's _inflight (the typed BUSY): a chunk that arrives
        while a capped lock is held, whatever it names, takes the
        per-repo Python path."""
        for mgr in self._mgrs:
            if mgr.admission_cap and mgr.busy():
                return True
        return False

    def _take(self, view) -> int:
        """A round holds what it names: the locks of the types the run
        of commands ahead addresses, taken together when all of them are
        free — exactly the boundary apply_async enforces per repo, so a
        threaded drain of ANOTHER type runs beside the round and one of
        a type it names keeps it out. All of them or none: it can never
        deadlock against the shutdown snapshot (all_locks), and it takes
        free locks whoever is in line (RepoLock). A round that names no
        engine type (SYSTEM, an unfinished command) takes none.

        When a lock the run names is held, its FIRST command's type
        alone: served now if that lock is free (the engine stops before
        the next command of another type). Returns the set taken
        (`scan_apply`'s ``held``), or with that one lock held too its
        complement (negative), nothing taken: the round sleeps in that
        lock's line, where the Python path would sleep. Never yields."""
        ahead = self._engine.types_ahead(view)
        held = ahead & 63
        if RepoLock.take_all(self._rounds[held][0]):
            return held
        held = 1 << (ahead >> 8)
        if RepoLock.take_all(self._rounds[held][0]):
            return held
        return ~held

    def _demote(self, conn: _Conn) -> int:
        """The whole connection moves to the Python dispatch path for
        its remaining lifetime (its pending bytes into the parser: on
        malformed input the Python parser then renders its specific
        error and the connection drops) — counted so the live
        fallback_frac (SYSTEM METRICS SERVING lines) reflects demotion
        events, and traced so SYSTEM TRACE shows when/why serving
        slowed."""
        self._reg.note_serving("demotions")
        self._reg.trace_event("server", "demote")
        conn.py.parser.append(conn.take_pending())
        conn.native = False
        conn.t_route = conn.t_tail = 0.0
        return _DEMOTE

    def _settle(self, conn: _Conn, view, held: int, inline: bool) -> int:
        """ONE round of the native serving engine over ``view`` (the
        connection's pending bytes), under the locks of ``held``, which
        the caller took and this lets go of: the ONE body of the
        task-less rounds and the slow path's. Never yields. A reply of
        any size is the engine's: its reply buffer grows to the reply
        inside `scan_apply`, and only one past that buffer's ceiling
        comes back as a command for the Python path.

        The stage chain: ``conn.t_route`` is where the round's route
        began (the chunk's arrival, the last round's end; 0.0:
        observation off); a round is route -> burst -> reply write ->
        tail, and ``conn.t_tail`` is left open for the caller unless
        another round follows (`_AGAIN`, `_DEFER`: the tail ends here
        and the next route begins; a deferred command's `_dispatch_py`
        lies between two rounds and belongs to neither)."""
        reg = self._reg
        locks, mgrs, others = self._rounds[held]
        try:
            for mgr, _i in mgrs:
                if mgr._shutdown:
                    # its final flush is spoken for (a burst that slept
                    # behind a drain may wake after it): applying now
                    # would acknowledge a write that never replicates
                    # (apply_async looks again under its lock for the
                    # same reason)
                    return self._demote(conn)
            reg.note_serving("native_bursts")
            if inline:
                reg.note_serving("inline_bursts")
            reg.note_serving("burst_locks", len(locks))
            for lock in others:
                if lock._held:  # and the round runs beside it
                    reg.note_serving("bursts_beside_hold")
                    break
            # serve.route ends, server.native_burst begins
            t_held = 0.0
            if conn.t_route:
                t_held = perf_counter()
                self._h_route.record(t_held - conn.t_route)
            rc, consumed, n_replies, unhandled, changed = (
                self._engine.scan_apply(view, held)
            )
            # the burst ends; the reply write, or with nothing to write
            # the tail, begins
            t_tail = 0.0
            if t_held:
                # pipeline.dispatch reuses the burst elapsed — one
                # engine call settles the whole burst
                t_tail = perf_counter()
                el = t_tail - t_held
                self._h_burst.record(el)
                self._h_dispatch.record(el)
            if n_replies:
                door, out = conn.door, conn.py.out
                if out:  # deferred-command replies precede these
                    door.write(bytes(out))
                    out.clear()
                # a call of its own AFTER the burst: the one copy the
                # replies make, and a queue push
                door.write_held(n_replies)
                reg.note_serving("reply_bytes", n_replies)
                if t_tail:
                    t_scan, t_tail = t_tail, perf_counter()
                    self._h_reply_write.record(t_tail - t_scan)
            # only a type in `held` can have changed: its lock is ours
            for mgr, i in mgrs:
                if changed[i]:
                    mgr._maybe_proactive_flush()
        finally:
            RepoLock.release_all(locks)
        conn.consume(consumed)
        conn.t_tail = t_tail
        # slow-consumer hard bound (--admission-queue-bytes): engine
        # replies go straight through the door; once the node-wide
        # queued total is past the cap the connection's slow path parks
        # it (`_apply_native`: real per-connection backpressure, outside
        # the repo locks)
        adm = self._database.admission
        if adm.queue_bytes_cap:
            adm.note_conn_queued(id(conn), conn.door.buffered())
            if adm.queued_bytes > adm.queue_bytes_cap:
                conn.over_cap = True
        if rc == 0:  # consumed all complete commands
            return _DONE
        if rc < 0:
            # rc -1: malformed input — the Python parser (the oracle)
            # renders its specific error message so both serving paths
            # stay byte-identical on protocol errors, then drops the
            # connection. rc -2: oversized command — Python handles
            # this connection from here on.
            return self._demote(conn)
        # another round: this one's tail ends here. rc 2: the reply
        # buffer was flushed; rc 5: the next command names a type this
        # round did not hold: the next round's route begins at once.
        # rc 1: one command for the Python path first, in order
        conn.t_tail = 0.0
        if t_tail:
            conn.t_route = perf_counter()
            self._h_tail.record(conn.t_route - t_tail)
        if rc == 1:
            conn.unhandled = unhandled
            return _DEFER
        return _AGAIN

    def _run(self, conn: _Conn, inline: bool) -> int:
        """A connection's rounds, one after another, until one is the
        last (`_DONE`) or cannot be settled without sleeping; what that
        round said. Never yields: `_Conn.serve` calls it with no task
        (``inline``) and the slow path calls it between its sleeps. An ARMED
        native.scan_apply failpoint is the slow path's to await, so that
        an injected sleep stalls one connection and not the loop."""
        while True:
            if faults.armed("native.scan_apply"):
                return _SLEEP
            view = conn.pending()
            held = self._take(view)
            if held < 0:
                # the wait begins HERE, not at the task's first step:
                # the loop iteration between the two is not the route's
                conn.want = ~held
                conn.t_wait = self._s_lock_wait.begin()
                return _SLEEP
            st = self._settle(conn, view, held, inline)
            if st != _AGAIN or conn.over_cap:
                return st

    async def _round_asleep(self, conn: _Conn) -> int:
        """One round that may sleep before it runs: for the lock its
        first command names (lock.wait_serve's, not the route's: the
        burst holds nothing meanwhile), and at the armed failpoint."""
        # the lock `_run` found held is still the first command's,
        # whatever arrived behind it: not asked of the engine again
        held, conn.want = conn.want, 0
        t_wait, conn.t_wait = conn.t_wait, 0.0
        if not held:  # the armed failpoint sent the round here
            held = self._take(conn.pending())
        elif not RepoLock.take_all(self._rounds[held][0]):
            held = ~held
        if held < 0:
            held = ~held
            if not t_wait:
                t_wait = self._s_lock_wait.begin()
            await RepoLock.acquire_all(self._rounds[held][0])
            self._reg.note_serving("slept_bursts")
            self._reg.note_slept(self._ENGINE_TYPES[held.bit_length() - 1])
        if t_wait:
            waited_s = self._s_lock_wait.end(t_wait)
            if conn.t_route:
                conn.t_route += waited_s
        settle = False
        try:
            # native.scan_apply: a failure AT the FFI burst boundary
            # must demote this connection to the Python oracle path
            # (replies stay correct, at the measured demotion cliff),
            # never kill the connection. The ASYNC point: an injected
            # sleep must simulate a slow burst for THIS connection —
            # the sync point's time.sleep stalled the whole loop
            # (heartbeats and Pongs included), turning the drill into a
            # node-wide freeze that idle-evicts our peer connections
            # (caught by jlint's interprocedural JL101)
            await faults.async_point("native.scan_apply")
            settle = True
        except faults.FaultError:
            return self._demote(conn)
        finally:
            if not settle:  # a fault, or cancelled in the injected sleep
                RepoLock.release_all(self._rounds[held][0])
        # the bytes may have moved while this slept (an arrival)
        return self._settle(conn, conn.pending(), held, False)

    async def _apply_native(self, conn: _Conn, st: int) -> int:
        """The slow path's rounds, from where `_Conn.serve`'s stopped
        (``st``): everything a round cannot do without
        sleeping. Commands the engine can't settle route through the
        normal per-repo async path in order (`resp` buffers those
        replies in `out`, which is written out before the engine's next
        direct write so the reply stream stays in command order).
        `_DONE` (stay native, the last round's tail open) or `_DEMOTE`."""
        py, door = conn.py, conn.door
        while True:
            if conn.over_cap:
                # parks only THIS connection until its consumer catches
                # up, so the node's memory stays bounded without slowing
                # healthy consumers; the open stage stops for the wait
                conn.over_cap = False
                if st == _DONE:
                    conn.t_tail = await self._write_wait(door, conn.t_tail)
                else:
                    conn.t_route = await self._write_wait(door, conn.t_route)
                self._database.admission.note_conn_queued(
                    id(conn), door.buffered()
                )
            if st == _AGAIN:
                st = self._run(conn, False)
            elif st == _SLEEP:
                st = await self._round_asleep(conn)
            elif st == _DEFER:
                await self._dispatch_py(
                    py.resp, conn.unhandled, door, py.out, conn.t_arr
                )
                self._reg.note_serving("deferred_cmds")
                # a burst of repeatedly deferring reads (e.g. rows whose
                # drained base the host lacks, or replies past the
                # ceiling of the engine's reply buffer) produces no
                # engine write to piggyback on: bound the buffer here
                # exactly like the parser's loop does
                py.flush(1 << 16)
                if conn.t_route:
                    conn.t_route = perf_counter()
                st = _AGAIN
            else:
                return st

    async def _serve(self, conn: _Conn, st: int | None) -> bool:
        """One chunk of a connection on its slow path: ``st`` is what
        `_Conn.serve`'s last round said, None when it tried none (a
        capped lock held, the parser holding a routed chunk's tail, a
        demoted connection). False: the
        connection ends."""
        py, door = conn.py, conn.door
        routed = False  # this chunk took the Python path for busy()
        if conn.native:
            if st is None:
                st = _AGAIN
                if self._capped_busy():
                    st = None
                elif conn.routed_tail:
                    # a previous chunk was routed through the Python
                    # parser and may have left a split command's head
                    # behind: reclaim it so the stream returns to the
                    # engine
                    tail = py.parser.take_tail()
                    if tail is None:
                        st = None  # malformed/unserved: stay
                    else:
                        conn.routed_tail = False
                        if tail:
                            conn.make_room(tail)
                if st is None:
                    routed = conn.routed_tail = True
            if st is not None:
                st = await self._apply_native(conn, st)
                if st == _DONE:
                    # still the tail, but for a deferred command's reply
                    # (pipeline.reply_write's) and a wait for the socket
                    # (not loop work)
                    wrote_s = py.flush()
                    if conn.t_tail and wrote_s:
                        conn.t_tail += wrote_s
                    if door.unsent():
                        conn.t_tail = await self._write_wait(door, conn.t_tail)
                    return True
                # demoted: the pending bytes are the parser's already
        py.parser.append(conn.take_pending())
        return await self._drain_parser(py, conn.t_arr, routed)

    async def _slow(self, conn: _Conn, st: int | None) -> None:
        """A connection's slow-path task, at most one in flight: the
        chunk `_Conn.serve` could not settle, then whatever arrived
        meanwhile (the read callback only appended it), chunk by chunk."""
        alive = False
        try:
            while await self._serve(conn, st) and not conn.lost:
                now = perf_counter() if self._reg.enabled else 0.0
                conn.close_tail(now)
                if not conn.arrived:
                    alive = True
                    break
                conn.arrived = False
                st, conn.t_route = None, now
                if conn.adm_armed:
                    conn.t_arr = now or perf_counter()
        except (ConnectionResetError, BrokenPipeError, asyncio.CancelledError):
            pass
        finally:
            conn.slow_done(alive)

    async def dispose(self) -> None:
        """Stop listening and close client connections (the reference
        stops its listener and lets process exit end connections,
        server.pony:16-20; Python 3.12's wait_closed would otherwise
        block shutdown until every idle client hung up on its own)."""
        self._closing = True  # handlers not yet in _conns self-close
        if self._server is not None:
            self._server.close()
            conns = list(self._conns.items())
            for w, door in conns:
                door.close()
                w.close()
            await self._server.wait_closed()
            # a slow-path task in flight (a burst asleep behind a repo
            # lock, a command in a worker thread) is waited out while
            # tasks still end, then cancelled: it applies nothing more
            tasks = [w.task for w, _ in conns if getattr(w, "task", None)]
            if tasks:
                _, left = await asyncio.wait(tasks, timeout=_LINGER_STALL_S)
                for task in left:
                    task.cancel()
                if left:
                    await asyncio.wait(left)
        engine = self._engine
        if engine is not None:
            if self._notify_fd >= 0:
                asyncio.get_running_loop().remove_reader(self._notify_fd)
                self._notify_fd = -1
            # the closed connections' last replies: `wait_closed` above
            # waited for the transports to flush theirs, and the sender
            # writes its own out the same; it is given up on once no
            # byte has moved for _LINGER_STALL_S (a consumer that reads
            # slowly gets everything, one that does not read is cut)
            left, stalled = engine.sender_pending(), 0.0
            while left and stalled < _LINGER_STALL_S:
                await asyncio.sleep(_LINGER_TICK_S)
                now = engine.sender_pending()
                stalled = 0.0 if now < left else stalled + _LINGER_TICK_S
                left = now
            # a blocking join, microseconds long: the thread is awake or
            # in a poll() the stop wakes
            engine.sender_stop()
