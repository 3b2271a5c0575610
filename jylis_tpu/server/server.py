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
request order), which each connection's sequential await provides.

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
        low-water mark (the sender signals the loop), and a connection
        error the reader saw is raised."""
        if self._conn >= 0 and self._engine.sender_wait(self._conn):
            await self._server._sender_wait(self._conn)
        await self.writer.drain()

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


class Server:
    def __init__(self, config, database: Database):
        self._config = config
        self._database = database
        self._log = config.log
        self._server: asyncio.base_events.Server | None = None
        self._conns: dict[asyncio.StreamWriter, _Door] = {}
        self._closing = False
        # handlers asleep until the sender has written their connection
        # down (serve.write_wait), by the sender's connection id; the
        # sender's eventfd is read by the loop from the first such sleep
        self._write_waiters: dict[int, asyncio.Future] = {}
        self._notify_fd = -1
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
        # perf_counter stamps from the read's return to the next read's
        # call, each boundary read once and shared by the stage it ends
        # and the stage it begins — route, server.native_burst,
        # pipeline.reply_write, tail. Histograms only, no profiler
        # annotation per burst. With pipeline.parse and serve.py_apply
        # (models/manager.py) they tile the handler's share of loop.busy;
        # what is left of it is asyncio's own. write_wait is a drain()
        # called with bytes still in the transport: NOT loop work, the
        # tail stops before it and goes on after it.
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
        # type's bit number, every OTHER engine lock). Locks in DATABASE
        # MAP order (TREG, TLOG, G, PN, UJSON), the order
        # database.all_locks takes them in
        self._mgrs = self._rounds = ()
        if database.native_engine is not None:
            self._mgrs = mgrs = tuple(
                database.manager(n) for n in self._ENGINE_TYPES
            )
            order = (2, 3, 0, 1, 4)
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
            if getattr(self._config, "lanes", 1) > 1:
                # multi-lane serving: every lane binds the SAME port
                # with SO_REUSEPORT and the kernel shards accepted
                # connections across the lane processes — no userspace
                # acceptor, no fd passing. IPv4-only in this mode (each
                # family would otherwise need its own shared socket).
                import socket as _socket

                sock = _socket.socket(_socket.AF_INET, _socket.SOCK_STREAM)
                sock.setsockopt(
                    _socket.SOL_SOCKET, _socket.SO_REUSEPORT, 1
                )
                sock.bind(("0.0.0.0", int(self._config.port)))
                self._server = await asyncio.start_server(
                    self._handle_client, sock=sock
                )
            else:
                self._server = await asyncio.start_server(
                    self._handle_client, host=None, port=int(self._config.port)
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
        # jlint: blocking-ok — lib() is memoised at boot (warmup builds
        # an auto-engine Database before serving starts), so this never
        # reaches the loader's listdir/compile path on the loop
        parser = make_parser()  # native scanner when built, Python fallback
        # Python-path replies buffer here and flush once per parsed batch
        # (bounded below): a reply per write() was one tiny TCP segment
        # per COMMAND, and a demoted connection's pipelined burst became
        # a per-segment wakeup storm — measured 30-40x under the native
        # path's batched writes on the same burst. The engine's replies
        # bypass this buffer (they arrive pre-batched); it is written
        # out before every direct engine write, so cross-path reply
        # order is exactly command order.
        out = bytearray()
        resp = Respond(out.extend)

        def flush(bound: int = 0) -> float:
            """Hand `out` to the writer once it is past ``bound``; the
            seconds this put into pipeline.reply_write (0.0: nothing
            written, or observation off), which a caller with a stage
            open moves that stage's start forward by."""
            if len(out) <= bound:
                return 0.0
            t_w = perf_counter() if reg.enabled else 0.0
            door.write(bytes(out))
            out.clear()
            if not t_w:
                return 0.0
            seconds = perf_counter() - t_w
            self._h_reply_write.record(seconds)
            return seconds

        engine = getattr(self._database, "native_engine", None)
        use_native = engine is not None
        buf = bytearray()
        door = self._conns[writer] = _Door(self, writer, engine)
        unsent = door.unsent  # read once per burst
        try:
            adm_armed = self._database.admission.armed
            if t_acc:
                self._h_accept.record(perf_counter() - t_acc)
            t_tail = 0.0  # start of the open serve.tail stage; 0.0: none
            while True:
                # pipeline.read: one socket read await. Deliberately
                # includes client idle time — under saturation this IS
                # the kernel-queue wait, and an idle connection's long
                # reads land in the top buckets where windowed quantiles
                # (SYSTEM LATENCY WINDOW) can separate them from load.
                # Where it is called, a served burst's tail ends.
                t_rd = perf_counter() if reg.enabled else 0.0
                if t_tail:
                    if t_rd:
                        self._h_tail.record(t_rd - t_tail)
                    t_tail = 0.0
                data = await reader.read(1 << 16)
                t_rt = 0.0  # where it returns, serve.route begins
                if t_rd:
                    t_rt = perf_counter()
                    self._h_read.record(t_rt - t_rd)
                if not data:
                    break
                # the overload signal's arrival stamp: queue time for
                # every command in this chunk runs from this read
                t_arr = (t_rt or perf_counter()) if adm_armed else 0.0
                routed = False  # this chunk took the Python path for busy()
                if use_native:
                    # under --admission-cap a wait for a repo lock must
                    # count in its manager's _inflight (the typed BUSY):
                    # a chunk that arrives while a capped lock is held,
                    # whatever it names, takes the per-repo Python path
                    go_native = not any(
                        m.admission_cap and m.busy() for m in self._mgrs
                    )
                    if go_native and parser.has_pending():
                        # a previous chunk was routed through the Python
                        # parser and left a split command's head behind:
                        # reclaim it so the stream returns to the engine
                        tail = parser.take_tail()
                        if tail is None:
                            go_native = False  # malformed/unserved: stay
                        else:
                            buf += tail
                    if not go_native:
                        routed = True
                        parser.append(bytes(buf))
                        buf.clear()
                    else:
                        buf += data
                        t_tail = await self._apply_native(
                            engine, buf, parser, resp, flush, door, out,
                            t_arr, t_rt,
                        )
                        if t_tail is not None:
                            # still the tail, but for a deferred
                            # command's reply (pipeline.reply_write's)
                            # and a wait for the socket (not loop work)
                            wrote_s = flush()
                            if t_tail and wrote_s:
                                t_tail += wrote_s
                            if unsent():
                                t_tail = await self._write_wait(door, t_tail)
                            else:
                                await writer.drain()
                            continue
                        use_native = False
                        t_tail = 0.0
                        data = b""  # demoted: tail already moved into parser
                parser.append(data)
                try:
                    # pipeline.parse: manual next() so each Python-path
                    # command parse is timed individually; RespError
                    # still propagates to the handler below exactly as
                    # the for-loop form raised it
                    it = iter(parser)
                    while True:
                        t_ps = perf_counter() if reg.enabled else 0.0
                        cmd = next(it, None)
                        if t_ps:
                            self._h_parse.record(perf_counter() - t_ps)
                        if cmd is None:
                            break
                        await self._dispatch_py(resp, cmd, door, out, t_arr)
                        # the Python path's commands by cause (the third
                        # is the engine's hand-back, in _apply_native),
                        # counted once dispatched AND applied: a command
                        # asleep in a lock's line is in neither count
                        # nor demoted_cmds, so the two agree at any edge
                        if routed:
                            reg.note_serving("busy_routed_cmds")
                        else:
                            reg.note_serving("demoted_conn_cmds")
                        flush(1 << 16)  # bound the reply buffer mid-burst
                except RespError as e:
                    resp.err(str(e))
                    flush()
                    break
                flush()
                await door.drain()
        except (ConnectionResetError, BrokenPipeError, asyncio.CancelledError):
            pass
        finally:
            self._database.admission.drop_conn(id(writer))
            del self._conns[writer]
            door.close()  # BEFORE the socket: the sender finishes it
            writer.close()
            try:
                await writer.wait_closed()
            except (ConnectionResetError, BrokenPipeError):
                pass

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
    _ENGINE_TYPES = ("GCOUNT", "PNCOUNT", "TREG", "TLOG", "UJSON")

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
            self._notify_fd = self._database.native_engine.sender_notify_fd()
            loop.add_reader(self._notify_fd, self._on_sender_notify)
        fut = self._write_waiters[conn] = loop.create_future()
        return fut

    def _on_sender_notify(self) -> None:
        try:
            os.read(self._notify_fd, 8)
        except BlockingIOError:
            pass
        engine = self._database.native_engine
        for conn in list(self._write_waiters):
            if not engine.sender_wait(conn):  # fired, closed or dead
                self._sender_woken(conn)

    def _sender_woken(self, conn: int) -> None:
        fut = self._write_waiters.pop(conn, None)
        if fut is not None and not fut.done():
            fut.set_result(None)

    async def _apply_native(
        self, engine, buf, parser, resp, flush, door, out, t_arr=0.0,
        t_route=0.0,
    ):
        """Drain `buf` through the native serving engine; commands it
        can't settle route through the normal per-repo async path in
        order (`resp` buffers those replies in `out`, which is written
        out before the engine's next direct write so the reply stream
        stays in command order). A reply of any size is the engine's:
        its reply buffer grows to the reply inside `scan_apply`, and
        only one past that buffer's ceiling comes back as a command for
        the Python path.

        ``t_route`` is the stamp of the read that delivered these bytes
        (0.0: observation off), where the burst's stage chain starts.
        Each round of the loop below is route -> burst -> reply write ->
        tail; a deferred command's `_dispatch_py` lies between two rounds
        and belongs to neither. Returns the start stamp of the last
        round's tail, still open (stay native; 0.0 with observation
        off), or None (demote this connection to the Python path; tail
        moved into `parser` — on malformed input the Python parser then
        renders its specific error and the connection drops)."""
        reg = self._reg
        rounds = self._rounds

        def demote() -> None:
            # the whole connection moves to the Python dispatch path for
            # its remaining lifetime — counted so the live fallback_frac
            # (SYSTEM METRICS SERVING lines) reflects demotion events,
            # and traced so SYSTEM TRACE shows when/why serving slowed
            reg.note_serving("demotions")
            reg.trace_event("server", "demote")
            parser.append(bytes(buf))
            buf.clear()

        while True:
            # a round holds what it names: the locks of the types the run
            # of commands ahead addresses, taken together when all of
            # them are free — exactly the boundary apply_async enforces
            # per repo, so a threaded drain of ANOTHER type runs beside
            # the round and one of a type it names keeps it out. All of
            # them or none, and nothing while it sleeps: it can never
            # deadlock against the shutdown snapshot (all_locks), and it
            # takes free locks whoever is in line (RepoLock). A round
            # that names no engine type (SYSTEM, an unfinished command)
            # takes none.
            ahead = engine.types_ahead(buf)
            held = ahead & 31
            locks, mgrs, others = rounds[held]
            if not RepoLock.take_all(locks):
                # a lock the run names is held. Its FIRST command's type
                # alone, then: served now if that lock is free (the
                # engine stops before the next command of another type),
                # else the burst sleeps in that lock's line, holding
                # nothing — the Python path would sleep in the same line
                which = ahead >> 8
                held = 1 << which
                locks, mgrs, others = rounds[held]
                if not RepoLock.take_all(locks):
                    # the sleep is lock.wait_serve's, not the route's
                    t_wait = self._s_lock_wait.begin()
                    await RepoLock.acquire_all(locks)
                    waited_s = self._s_lock_wait.end(t_wait)
                    reg.note_serving("slept_bursts")
                    reg.note_slept(self._ENGINE_TYPES[which])
                    if t_route:
                        t_route += waited_s
            try:
                for mgr, _i in mgrs:
                    if mgr._shutdown:
                        # its final flush is spoken for (a burst that
                        # slept behind a drain may wake after it):
                        # applying now would acknowledge a write that
                        # never replicates (apply_async looks again
                        # under its lock for the same reason)
                        return demote()
                try:
                    # native.scan_apply: a failure AT the FFI burst
                    # boundary must demote this connection to the Python
                    # oracle path (replies stay correct, at the measured
                    # demotion cliff), never kill the connection. The
                    # ASYNC point: an injected sleep must simulate a slow
                    # burst for THIS connection — the sync point's
                    # time.sleep stalled the whole loop (heartbeats and
                    # Pongs included), turning the drill into a node-wide
                    # freeze that idle-evicts our peer connections
                    # (caught by jlint's interprocedural JL101)
                    await faults.async_point("native.scan_apply")
                    reg.note_serving("native_bursts")
                    reg.note_serving("burst_locks", len(locks))
                    for lock in others:
                        if lock._held:  # and the round runs beside it
                            reg.note_serving("bursts_beside_hold")
                            break
                    # serve.route ends, server.native_burst begins
                    t_held = 0.0
                    if t_route:
                        t_held = perf_counter()
                        self._h_route.record(t_held - t_route)
                    rc, consumed, n_replies, unhandled, changed = (
                        engine.scan_apply(buf, held)
                    )
                    # the burst ends; the reply write, or with nothing
                    # to write the tail, begins
                    t_tail = 0.0
                    if t_held:
                        # pipeline.dispatch reuses the burst elapsed —
                        # one engine call settles the whole burst
                        t_tail = perf_counter()
                        el = t_tail - t_held
                        self._h_burst.record(el)
                        self._h_dispatch.record(el)
                except faults.FaultError:
                    return demote()
                if n_replies:
                    if out:  # deferred-command replies precede these
                        door.write(bytes(out))
                        out.clear()
                    # a call of its own AFTER the burst: with a sender
                    # the one copy the replies make, and a queue push
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
            del buf[:consumed]
            # slow-consumer hard bound (--admission-queue-bytes): engine
            # replies land straight in the door's buffer; once the
            # node-wide queued total is past the cap, drain() here is
            # real per-connection backpressure — it parks only THIS
            # connection until its consumer catches up, outside the
            # repo locks, so the loop's memory stays bounded without
            # slowing healthy consumers
            adm = self._database.admission
            if adm.queue_bytes_cap:
                adm.note_conn_queued(id(door.writer), door.buffered())
                if adm.queued_bytes > adm.queue_bytes_cap:
                    t_tail = await self._write_wait(door, t_tail)
                    adm.note_conn_queued(id(door.writer), door.buffered())
            if rc == 0:  # consumed all complete commands
                return t_tail
            if rc < 0:
                # rc -1: malformed input — the Python parser (the oracle)
                # renders its specific error message so both serving paths
                # stay byte-identical on protocol errors, then drops the
                # connection. rc -2: oversized command — Python handles
                # this connection from here on.
                return demote()
            # another round: this one's tail ends here. rc 2: the reply
            # buffer was flushed; rc 5: the next command names a type
            # this round did not hold: the next round's route begins at
            # once. rc 1: one command for the Python path first, in order
            t_route = 0.0
            if t_tail:
                t_route = perf_counter()
                self._h_tail.record(t_route - t_tail)
            if rc == 1:
                await self._dispatch_py(resp, unhandled, door, out, t_arr)
                reg.note_serving("deferred_cmds")
                # a burst of repeatedly deferring reads (e.g. rows whose
                # drained base the host lacks, or replies past the
                # ceiling of the engine's reply buffer) produces no
                # engine write to piggyback on: bound the buffer here
                # exactly like the demoted loop does
                flush(1 << 16)
                if t_route:
                    t_route = perf_counter()

    async def dispose(self) -> None:
        """Stop listening and close client connections (the reference
        stops its listener and lets process exit end connections,
        server.pony:16-20; Python 3.12's wait_closed would otherwise
        block shutdown until every idle client hung up on its own)."""
        self._closing = True  # handlers not yet in _conns self-close
        if self._server is not None:
            self._server.close()
            for w, door in list(self._conns.items()):
                door.close()
                w.close()
            await self._server.wait_closed()
        engine = self._database.native_engine
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
