"""In-process multi-node cluster integration tests.

Reference analog: test/test_cluster.pony:67-130 — three complete node
stacks (System, Database, Server, Cluster) in one process on loopback, with
the heartbeat dialed down to 50 ms; `bar` and `baz` know only seed `foo`,
so full-mesh discovery through gossip is itself under test; each node INCs
the same GCOUNT key with a different amount and the test asserts `foo`
reads the converged total through the real wire path (codec -> framing ->
TCP -> converge).
"""

import asyncio
import os

import pytest

import jylis_tpu  # noqa: F401
from jylis_tpu.cluster import Cluster
from jylis_tpu.models.database import Database
from jylis_tpu.server.server import Server
from jylis_tpu.system import System
from jylis_tpu.utils.address import Address
from jylis_tpu.utils.config import Config
from jylis_tpu.utils.log import Log
from procutil import free_port

TICK = 0.05  # the reference test's 50 ms heartbeat (test_cluster.pony:70)

_DEVNULL = None


def _devnull():
    """One shared discard sink for info-logging Nodes (a handle per Node
    would leak until GC finalization)."""
    global _DEVNULL
    if _DEVNULL is None:
        _DEVNULL = open(os.devnull, "w")
    return _DEVNULL


class Node:
    """One full node stack on ephemeral loopback ports.

    ``log_level="info"`` discards stream output but keeps the dual sink
    into the replicated SYSTEM log — failure diagnostics can then read
    each node's own account of its sync/cluster decisions."""

    def __init__(self, name: str, cluster_port: int, seeds=(), log_level=None,
                 region: str = ""):
        self.config = Config()
        self.config.port = "0"
        self.config.addr = Address("127.0.0.1", str(cluster_port), name)
        self.config.seed_addrs = list(seeds)
        self.config.heartbeat_time = TICK
        self.config.region = region  # v10 region-aware peering tests
        if log_level is None:
            self.config.log = Log.create_none()
        else:
            self.config.log = Log(log_level, out=_devnull())
        self.system = System(self.config)
        self.database = Database(
            identity=self.config.addr.hash64(), system_repo=self.system.repo
        )
        self.server = Server(self.config, self.database)
        self.cluster = Cluster(self.config, self.database)

    async def start(self):
        await self.server.start()
        await self.cluster.start()

    async def stop(self):
        self.cluster.dispose()
        await self.server.dispose()


class _CollectResp:
    """Records reply-writer calls for failure diagnostics."""

    def __init__(self):
        self.vals = []

    def __getattr__(self, name):
        return lambda *a: self.vals.extend((name, *a))


async def resp_call(port: int, payload: bytes) -> bytes:
    reader, writer = await asyncio.open_connection("127.0.0.1", port)
    writer.write(payload)
    await writer.drain()
    out = await asyncio.wait_for(reader.read(1 << 16), timeout=2.0)
    writer.close()
    return out


def grab_ports(n: int) -> list[int]:
    """n distinct loopback ports from this worker's own range (the
    reference test uses fixed ports 9999/9998/9997)."""
    return [free_port() for _ in range(n)]


async def make_three_nodes():
    """bar and baz are seeded only with foo (test_cluster.pony:94-95)."""
    p_foo, p_bar, p_baz = grab_ports(3)
    foo_addr = Address("127.0.0.1", str(p_foo), "foo")
    foo = Node("foo", p_foo)
    bar = Node("bar", p_bar, seeds=[foo_addr])
    baz = Node("baz", p_baz, seeds=[foo_addr])
    await foo.start()
    await bar.start()
    await baz.start()
    assert foo.cluster.listen_port == p_foo  # bound the advertised port
    return foo, bar, baz


@pytest.fixture()
def three_nodes():
    """Builds the cluster inside the test's own loop via a factory."""
    return make_three_nodes


def meshed(*nodes) -> bool:
    """Full mesh with all active conns through handshake."""
    return all(
        len(n.cluster._actives) == len(nodes) - 1
        and all(c.established for c in n.cluster._actives.values())
        for n in nodes
    )


async def converge_wait(check, ticks: int = 40):
    """Poll `check()` for up to `ticks` heartbeats (the reference uses a
    fixed tick count; we poll to keep the test fast when convergence is
    quicker)."""
    for _ in range(ticks):
        if check():
            return True
        await asyncio.sleep(TICK)
    return check()


def test_three_node_gcount_convergence(three_nodes):
    async def main():
        foo, bar, baz = await three_nodes()
        try:
            assert await converge_wait(lambda: meshed(foo, bar, baz))
            # INC the same key on each node with a different amount
            # (test_cluster.pony:122-130: 2 + 3 + 4 -> :9)
            for node, amount in ((foo, b"2"), (bar, b"3"), (baz, b"4")):
                got = await resp_call(
                    node.server.port,
                    b"*4\r\n$6\r\nGCOUNT\r\n$3\r\nINC\r\n$4\r\ntest\r\n$1\r\n"
                    + amount
                    + b"\r\n",
                )
                assert got == b"+OK\r\n"

            async def converged():
                out = await resp_call(
                    foo.server.port, b"*3\r\n$6\r\nGCOUNT\r\n$3\r\nGET\r\n$4\r\ntest\r\n"
                )
                return out

            deadline = asyncio.get_event_loop().time() + 40 * TICK
            out = b""
            while asyncio.get_event_loop().time() < deadline:
                out = await converged()
                if out == b":9\r\n":
                    break
                await asyncio.sleep(TICK)
            assert out == b":9\r\n"  # the reference test's exact pinned bytes
        finally:
            for n in (foo, bar, baz):
                await n.stop()

    asyncio.run(main())


def test_gossip_discovers_full_membership(three_nodes):
    async def main():
        foo, bar, baz = await three_nodes()
        try:
            # bar and baz never heard of each other directly; gossip via foo
            # must produce a full mesh (cluster.pony:51-71,215-239)
            def full_mesh():
                return all(
                    len(n.cluster._known_addrs) == 3 for n in (foo, bar, baz)
                ) and all(
                    len(n.cluster._actives) == 2 for n in (foo, bar, baz)
                )

            ok = await converge_wait(full_mesh)
            assert ok, {
                n.config.addr.name: (
                    sorted(str(a) for a in n.cluster._known_addrs),
                    len(n.cluster._actives),
                )
                for n in (foo, bar, baz)
            }
        finally:
            for n in (foo, bar, baz):
                await n.stop()

    asyncio.run(main())


def test_all_types_replicate(three_nodes):
    """Every data type's deltas ride the anti-entropy path end to end."""

    async def main():
        foo, bar, baz = await three_nodes()
        try:
            # the reference test waits 3 ticks before writing
            # (test_cluster.pony:122): deltas flushed before any active
            # connection is established are fire-and-forget gone
            assert await converge_wait(lambda: meshed(foo, bar, baz))
            writes = [
                b"*5\r\n$4\r\nTREG\r\n$3\r\nSET\r\n$1\r\nr\r\n$2\r\nhi\r\n$1\r\n5\r\n",
                b"*5\r\n$4\r\nTLOG\r\n$3\r\nINS\r\n$1\r\nl\r\n$1\r\nx\r\n$1\r\n3\r\n",
                b"*4\r\n$7\r\nPNCOUNT\r\n$3\r\nINC\r\n$1\r\np\r\n$1\r\n7\r\n",
                b"*5\r\n$5\r\nUJSON\r\n$3\r\nSET\r\n$1\r\nu\r\n$1\r\na\r\n$2\r\n42\r\n",
            ]
            for w in writes:
                got = await resp_call(bar.server.port, w)
                assert got == b"+OK\r\n", (w, got)

            reads = {
                b"*3\r\n$4\r\nTREG\r\n$3\r\nGET\r\n$1\r\nr\r\n": b"*2\r\n$2\r\nhi\r\n:5\r\n",
                b"*3\r\n$4\r\nTLOG\r\n$3\r\nGET\r\n$1\r\nl\r\n": b"*1\r\n*2\r\n$1\r\nx\r\n:3\r\n",
                b"*3\r\n$7\r\nPNCOUNT\r\n$3\r\nGET\r\n$1\r\np\r\n": b":7\r\n",
                b"*4\r\n$5\r\nUJSON\r\n$3\r\nGET\r\n$1\r\nu\r\n$1\r\na\r\n": b"$2\r\n42\r\n",
            }

            async def all_seen():
                for req, want in reads.items():
                    if await resp_call(baz.server.port, req) != want:
                        return False
                return True

            deadline = asyncio.get_event_loop().time() + 60 * TICK
            ok = False
            while asyncio.get_event_loop().time() < deadline:
                if await all_seen():
                    ok = True
                    break
                await asyncio.sleep(TICK)
            assert ok
        finally:
            for n in (foo, bar, baz):
                await n.stop()

    asyncio.run(main())


def test_system_log_replicates(three_nodes):
    """The SYSTEM log is itself a CRDT: lines logged on one node appear in
    SYSTEM GETLOG on another (SURVEY.md §2.6)."""

    async def main():
        foo, bar, baz = await three_nodes()
        try:
            assert await converge_wait(lambda: meshed(foo, bar, baz))
            foo.config.log._level = 1  # enable info on foo only
            foo.config.log._out = None
            foo.config.log.i("hello-from-foo")

            async def seen():
                out = await resp_call(
                    baz.server.port, b"*2\r\n$6\r\nSYSTEM\r\n$6\r\nGETLOG\r\n"
                )
                return b"hello-from-foo" in out

            deadline = asyncio.get_event_loop().time() + 60 * TICK
            ok = False
            while asyncio.get_event_loop().time() < deadline:
                if await seen():
                    ok = True
                    break
                await asyncio.sleep(TICK)
            assert ok
        finally:
            for n in (foo, bar, baz):
                await n.stop()

    asyncio.run(main())


def test_worth_holding_filters_empty_system_keepalives():
    """Empty SYSTEM keepalive frames (the deltas_size()==1 quirk) must not
    enter the held-delta buffer, or a long-solo node FIFO-evicts real
    pre-join writes with empty frames."""
    wh = Cluster._worth_holding
    assert not wh("SYSTEM", [])
    assert not wh("SYSTEM", [(b"_log", ([], 0))])
    assert wh("SYSTEM", [(b"_log", ([(b"line", 5)], 0))])
    assert wh("SYSTEM", [(b"_log", ([], 7))])  # a cutoff is joinable state
    assert wh("GCOUNT", [(b"k", object())])


def test_solo_node_holds_real_deltas_not_keepalives():
    async def main():
        (port,) = grab_ports(1)
        foo = Node("foo", port)
        await foo.start()
        try:
            # no peers: an empty SYSTEM frame is dropped, a real one is held
            foo.cluster.broadcast_deltas(("SYSTEM", [(b"_log", ([], 0))]))
            assert foo.cluster._held == []
            foo.cluster.broadcast_deltas(
                ("SYSTEM", [(b"_log", ([(b"pre-join line", 5)], 0))])
            )
            assert len(foo.cluster._held) == 1
        finally:
            await foo.stop()

    asyncio.run(main())


def test_idle_eviction_boundary():
    """Eviction fires after MORE than IDLE_TICKS_LIMIT idle ticks, matching
    the reference's `(last_tick + 10) < _tick` (cluster.pony:118-121)."""
    from jylis_tpu.cluster.cluster import IDLE_TICKS_LIMIT, _Conn

    node = Node("solo", grab_ports(1)[0])
    cl = node.cluster
    conn = _Conn(writer=None, active_addr=None)
    cl._passives.add(conn)
    cl._last_activity[conn] = cl._tick
    cl._tick += IDLE_TICKS_LIMIT  # idle exactly the limit: keep
    cl._evict_idle()
    assert conn in cl._passives
    cl._tick += 1  # one past the limit: evict
    cl._evict_idle()
    assert conn not in cl._passives
    assert conn not in cl._last_activity


def test_active_redialed_after_drop(three_nodes):
    """A dropped active connection's address stays known, so the next
    heartbeat's sync re-dials it (cluster.pony:92-99)."""

    async def main():
        foo, bar, baz = await three_nodes()
        try:
            assert await converge_wait(lambda: meshed(foo, bar, baz))
            bar_addr = bar.config.addr
            dropped = foo.cluster._actives[bar_addr]
            foo.cluster._drop(dropped)
            assert bar_addr not in foo.cluster._actives

            def redialed():
                conn = foo.cluster._actives.get(bar_addr)
                return (
                    conn is not None
                    and conn is not dropped
                    and conn.established
                )

            assert await converge_wait(redialed)
        finally:
            for n in (foo, bar, baz):
                await n.stop()

    asyncio.run(main())


def test_wire_frame_crc_detects_any_single_byte_flip():
    """Schema v5/v6 transport integrity: every cluster frame carries a
    CRC32 over the origin stamp + body, so a bit flip past the TCP
    checksum — in the payload OR the timestamp — is a detected drop,
    never a decodable forged message or a forged convergence-lag sample
    (the drill matrix demonstrated a flipped counter value converging
    cluster-wide without this)."""
    from jylis_tpu.cluster.cluster import check_frame, wire_frame
    from jylis_tpu.cluster.framing import FrameReader, HEADER_SIZE

    body = b"some message body"
    framed = wire_frame(body, origin_ms=1234)
    frames = FrameReader()
    frames.append(framed)
    raw = next(iter(frames))
    assert check_frame(raw) == (1234, body)
    for i in range(len(raw)):  # flip every byte of crc+stamp+payload
        bad = bytearray(raw)
        bad[i] ^= 0x01
        assert check_frame(bytes(bad)) is None, i
    assert check_frame(b"") is None  # shorter than the CRC itself
    # default stamp is "now": a real wall-clock millisecond count
    frames2 = FrameReader()
    frames2.append(wire_frame(body))
    origin, payload = check_frame(next(iter(frames2)))
    assert payload == body and origin > 1_600_000_000_000
    assert len(framed) == HEADER_SIZE + 4 + 8 + len(body)


def test_handshake_signature_mismatch_drops_connection():
    """A peer presenting the wrong schema signature is dropped before any
    message exchange (cluster_notify.pony:37-61: auth failure)."""

    async def main():
        from jylis_tpu.cluster.framing import frame

        (port,) = grab_ports(1)
        foo = Node("foo", port)
        await foo.start()
        try:
            reader, writer = await asyncio.open_connection("127.0.0.1", port)
            writer.write(frame(b"x" * 32))  # wrong signature, right shape
            await writer.drain()
            got = await asyncio.wait_for(reader.read(1 << 16), timeout=2.0)
            assert got == b""  # peer closed without establishing
            writer.close()
            assert await converge_wait(lambda: not foo.cluster._passives)
        finally:
            await foo.stop()

    asyncio.run(main())


def test_held_deltas_reach_late_joiner():
    """Writes made while a node is ALONE are held (bounded) and delivered
    once the first peer joins — strictly better than the reference, which
    loses them (SURVEY.md §2.5 'known gap')."""

    async def main():
        p_foo, p_bar = grab_ports(2)
        foo = Node("foo", p_foo)
        await foo.start()
        try:
            # write while solo: the proactive flush finds zero peers
            got = await resp_call(
                foo.server.port,
                b"*4\r\n$6\r\nGCOUNT\r\n$3\r\nINC\r\n$4\r\npre1\r\n$1\r\n7\r\n",
            )
            assert got == b"+OK\r\n"
            # let heartbeats flush the repo into the held buffer
            assert await converge_wait(lambda: len(foo.cluster._held) > 0)

            bar = Node("bar", p_bar, seeds=[foo.config.addr])
            await bar.start()
            try:
                async def bar_sees_pre_join_write():
                    out = await resp_call(
                        bar.server.port,
                        b"*3\r\n$6\r\nGCOUNT\r\n$3\r\nGET\r\n$4\r\npre1\r\n",
                    )
                    return out == b":7\r\n"

                deadline = asyncio.get_event_loop().time() + 60 * TICK
                ok = False
                while asyncio.get_event_loop().time() < deadline:
                    if await bar_sees_pre_join_write():
                        ok = True
                        break
                    await asyncio.sleep(TICK)
                assert ok
                assert foo.cluster._held == []  # buffer fully flushed
            finally:
                await bar.stop()
        finally:
            await foo.stop()

    asyncio.run(main())


def test_backpressured_connection_dropped_on_broadcast():
    """A peer whose transport write buffer exceeds the cap is treated as
    dead: the broadcast drops it instead of buffering without bound."""

    from jylis_tpu.cluster.cluster import _Conn

    class FakeTransport:
        def __init__(self, buffered: int):
            self.buffered = buffered

        def is_closing(self):
            return False

        def get_write_buffer_size(self):
            return self.buffered

    class FakeWriter:
        def __init__(self, buffered: int):
            self.transport = FakeTransport(buffered)
            self.wrote = b""
            self.closed = False

        def write(self, data):
            self.wrote += data

        def close(self):
            self.closed = True

    node = Node("solo", grab_ports(1)[0])
    cl = node.cluster
    slow_addr = Address("127.0.0.1", "1", "slow")
    ok_addr = Address("127.0.0.1", "2", "ok")
    slow = _Conn(FakeWriter(_Conn.WRITE_BUFFER_LIMIT + 1), slow_addr)
    ok = _Conn(FakeWriter(0), ok_addr)
    slow.established = ok.established = True
    cl._actives[slow_addr] = slow
    cl._actives[ok_addr] = ok
    cl.broadcast_deltas(("GCOUNT", [(b"k", {1: 5})]))
    assert slow_addr not in cl._actives  # backpressured conn dropped
    assert slow.writer.closed
    assert ok_addr in cl._actives  # healthy conn delivered
    assert ok.writer.wrote != b""
    assert cl._held == []  # delivery succeeded, nothing held


def test_mid_heal_serve_defer_streak_is_per_peer():
    """ADVICE round 5: three concurrently-rejoining peers request sync in
    a stable order through a SUSTAINED mid-heal window (the aligned-
    heartbeat phase-lock regime the defer cap exists for). The cap must
    bind per requester — with a single global streak the serve slot
    (streak==2, reset to 0) lands on the same peer every period and the
    others' refusal chains grow without bound."""
    from jylis_tpu.cluster.cluster import SYNC_PERIOD_TICKS, _Conn
    from jylis_tpu.cluster.msg import MsgSyncRequest

    class FakeTransport:
        def is_closing(self):
            return False

        def get_write_buffer_size(self):
            return 0

    class FakeWriter:
        def __init__(self):
            self.transport = FakeTransport()

        def write(self, data):
            pass

        async def drain(self):
            pass

        def close(self):
            pass

    async def main():
        node = Node("server", grab_ports(1)[0])
        cl = node.cluster
        conns = [_Conn(FakeWriter(), None) for _ in range(3)]
        for conn in conns:
            conn.established = True
            cl._passives.add(conn)
        # a digest that can never match: the server must stream real dumps
        req = MsgSyncRequest((b"x" * 32,) * 5)
        first_serve: dict[int, int] = {}
        for period in range(4):
            cl._tick += SYNC_PERIOD_TICKS
            cl._sync_rx_tick = cl._tick  # the heal stream keeps flowing
            for i, conn in enumerate(conns):  # stable arrival order
                before = conn.sync_served_tick
                await cl._passive_msg(conn, req)
                if conn.sync_served_tick != before:
                    first_serve.setdefault(i, period)
            if cl._flush_tasks:  # let the dump task drain the waiters
                await asyncio.gather(*list(cl._flush_tasks))
        # EVERY peer's refusal chain is finite: served by its 3rd request
        # (two capped defers), not just whichever peer the slot lands on
        assert first_serve == {0: 2, 1: 2, 2: 2}, first_serve

        # and a requester whose CONNECTION churns every period (fresh
        # _Conn, fresh per-conn allowance) is still served in bounded
        # time: the aggregate consecutive-defer cap binds instead
        served_after = None
        for attempt in range(10):
            cl._tick += SYNC_PERIOD_TICKS
            cl._sync_rx_tick = cl._tick
            fresh = _Conn(FakeWriter(), None)
            fresh.established = True
            cl._passives.add(fresh)
            await cl._passive_msg(fresh, req)
            if fresh.sync_served_tick is not None:
                served_after = attempt
                break
            if cl._flush_tasks:
                await asyncio.gather(*list(cl._flush_tasks))
        assert served_after is not None and served_after <= 7, served_after

    asyncio.run(main())


def test_node_restart_from_snapshot_rejoins_and_converges(tmp_path):
    """Failure recovery end to end (SURVEY §5.3/§5.4): a node snapshots,
    dies, restarts from disk on the SAME advertised identity, rejoins the
    mesh, and both its restored state and writes it missed while down
    converge — through the real wire path."""
    from jylis_tpu import persist

    snap = str(tmp_path / "bar.snapshot")

    async def main():
        p_foo, p_bar = grab_ports(2)
        foo_addr = Address("127.0.0.1", str(p_foo), "foo")
        foo = Node("foo", p_foo)
        bar = Node("bar", p_bar, seeds=[foo_addr])
        await foo.start()
        await bar.start()
        assert await converge_wait(lambda: meshed(foo, bar))

        # writes on both sides, all five types on bar's side of the fence
        assert await resp_call(bar.server.port, b"GCOUNT INC hits 7\r\n")
        assert await resp_call(bar.server.port, b"PNCOUNT DEC bal 3\r\n")
        assert await resp_call(bar.server.port, b"TREG SET m keep 9\r\n")
        assert await resp_call(bar.server.port, b"TLOG INS lg x 4\r\n")
        assert await resp_call(bar.server.port, b"UJSON SET cfg on true\r\n")

        # bar snapshots and dies (clean shutdown path)
        bar.database.clean_shutdown()
        persist.save_snapshot(bar.database, snap)
        await bar.stop()

        # foo takes a write while bar is down
        assert await resp_call(foo.server.port, b"GCOUNT INC hits 5\r\n")

        # bar restarts from disk with the same identity and seeds
        bar2 = Node("bar", p_bar, seeds=[foo_addr])
        restored = persist.load_snapshot(bar2.database, snap)
        assert restored > 0
        await bar2.start()
        assert await converge_wait(lambda: meshed(foo, bar2))

        # restored state survived locally...
        assert await resp_call(bar2.server.port, b"TREG GET m\r\n") == (
            b"*2\r\n$4\r\nkeep\r\n:9\r\n"
        )
        assert await resp_call(bar2.server.port, b"UJSON GET cfg on\r\n") == (
            b"$4\r\ntrue\r\n"
        )
        # ...replicates to foo, and the missed write reaches bar2
        async def both_converged():
            got_foo = await resp_call(foo.server.port, b"PNCOUNT GET bal\r\n")
            got_bar = await resp_call(bar2.server.port, b"GCOUNT GET hits\r\n")
            return got_foo == b":-3\r\n" and got_bar == b":12\r\n"

        for _ in range(60):
            if await both_converged():
                break
            await asyncio.sleep(TICK)
        assert await both_converged()

        # bar2's own-column identity survived: further INCs don't regress
        assert await resp_call(bar2.server.port, b"GCOUNT INC hits 1\r\n")
        await converge_wait(lambda: True, 4)  # let it flush

        async def final():
            a = await resp_call(foo.server.port, b"GCOUNT GET hits\r\n")
            b = await resp_call(bar2.server.port, b"GCOUNT GET hits\r\n")
            return a == b == b":13\r\n"

        for _ in range(60):
            if await final():
                break
            await asyncio.sleep(TICK)
        assert await final()

        await bar2.stop()
        await foo.stop()

    asyncio.run(main())


def test_stale_name_blacklisted():
    """An address gossiped with my host:port but another name is permanently
    removed (cluster.pony:215-230)."""

    async def main():
        (port,) = grab_ports(1)
        foo = Node("foo", port)
        await foo.start()
        addr = foo.config.addr
        try:
            from jylis_tpu.ops.p2set import P2Set

            stale = Address(addr.host, addr.port, "old-name")
            incoming = P2Set([stale, addr])
            foo.cluster._converge_addrs(incoming)
            assert stale not in foo.cluster._known_addrs
            assert stale in foo.cluster._known_addrs.removes
            # and it can never come back
            again = P2Set([stale])
            foo.cluster._converge_addrs(again)
            assert stale not in foo.cluster._known_addrs
        finally:
            await foo.stop()

    asyncio.run(main())


def test_bootstrap_sync_recovers_writes_dropped_past_held_cap():
    """A solo node's held buffer is bounded: writes beyond the cap fall
    off and fire-and-forget would lose them forever. The bootstrap sync
    (MsgSyncRequest on establishment) delivers the FULL state, so a
    late joiner converges even the dropped windows."""

    async def main():
        p_foo, p_bar = grab_ports(2)
        foo = Node("foo", p_foo)
        await foo.start()
        foo.cluster._held_cap = 4  # make the cap reachable in-test
        try:
            for i in range(8):  # one flush window (held frame) per write
                got = await resp_call(
                    foo.server.port,
                    b"*4\r\n$6\r\nGCOUNT\r\n$3\r\nINC\r\n$4\r\nkey%d\r\n$1\r\n%d\r\n"
                    % (i, i + 1),
                )
                assert got == b"+OK\r\n"
                before = len(foo.cluster._held)
                await converge_wait(
                    lambda b=before: len(foo.cluster._held) != b, ticks=10
                )
            assert len(foo.cluster._held) <= 4  # early windows dropped

            bar = Node("bar", p_bar, seeds=[foo.config.addr])
            await bar.start()
            try:
                async def bar_converged():
                    for i, want in ((0, b":1\r\n"), (7, b":8\r\n")):
                        out = await resp_call(
                            bar.server.port,
                            b"*3\r\n$6\r\nGCOUNT\r\n$3\r\nGET\r\n$4\r\nkey%d\r\n" % i,
                        )
                        if out != want:
                            return False
                    return True

                deadline = asyncio.get_event_loop().time() + 100 * TICK
                ok = False
                while asyncio.get_event_loop().time() < deadline:
                    if await bar_converged():
                        ok = True
                        break
                    await asyncio.sleep(TICK)
                assert ok, "late joiner missing writes dropped from held buffer"
            finally:
                await bar.stop()
        finally:
            await foo.stop()

    asyncio.run(main())


def test_partition_heal_syncs_missed_writes():
    """A node partitioned while its peers keep writing misses those
    deltas permanently under pure fire-and-forget (the reference's known
    gap, cluster.pony:250-252). On heal, the re-established connection
    requests a full-state sync and the rejoiner converges — across ALL
    data types."""

    async def main():
        p_foo, p_bar = grab_ports(2)
        foo = Node("foo", p_foo)
        bar = Node("bar", p_bar, seeds=[foo.config.addr])
        await foo.start()
        await bar.start()
        try:
            # healthy cluster first: one write replicates
            await resp_call(
                foo.server.port, b"*4\r\n$6\r\nGCOUNT\r\n$3\r\nINC\r\n$1\r\na\r\n$1\r\n5\r\n"
            )

            async def bar_reads(payload, want):
                return (await resp_call(bar.server.port, payload)) == want

            deadline = asyncio.get_event_loop().time() + 60 * TICK
            replicated = False
            while asyncio.get_event_loop().time() < deadline:
                if await bar_reads(b"*3\r\n$6\r\nGCOUNT\r\n$3\r\nGET\r\n$1\r\na\r\n", b":5\r\n"):
                    replicated = True
                    break
                await asyncio.sleep(TICK)
            assert replicated, "healthy-phase replication failed"

            # partition bar: its cluster stack goes away entirely
            bar.cluster.dispose()
            await asyncio.sleep(2 * TICK)

            # foo keeps serving writes during the partition (every type)
            for payload in (
                b"*4\r\n$6\r\nGCOUNT\r\n$3\r\nINC\r\n$1\r\ng\r\n$1\r\n3\r\n",
                b"*4\r\n$7\r\nPNCOUNT\r\n$3\r\nDEC\r\n$1\r\np\r\n$1\r\n2\r\n",
                b"*5\r\n$4\r\nTREG\r\n$3\r\nSET\r\n$1\r\nt\r\n$5\r\nhello\r\n$1\r\n9\r\n",
                b"*5\r\n$4\r\nTLOG\r\n$3\r\nINS\r\n$1\r\nl\r\n$4\r\nitem\r\n$1\r\n4\r\n",
                b"*5\r\n$5\r\nUJSON\r\n$3\r\nSET\r\n$1\r\nu\r\n$1\r\nf\r\n$2\r\n42\r\n",
            ):
                got = await resp_call(foo.server.port, payload)
                assert got == b"+OK\r\n", (payload, got)
            # several flush windows pass; bar is gone, deltas unrecoverable
            # by push alone (foo had an established conn? no - with bar
            # down, frames go to held; make the loss real by overflowing)
            foo.cluster._held_cap = 1
            await asyncio.sleep(6 * TICK)

            # heal: bar's cluster stack comes back at the same address
            bar.cluster = Cluster(bar.config, bar.database)
            await bar.cluster.start()

            checks = (
                (b"*3\r\n$6\r\nGCOUNT\r\n$3\r\nGET\r\n$1\r\ng\r\n", b":3\r\n"),
                (b"*3\r\n$7\r\nPNCOUNT\r\n$3\r\nGET\r\n$1\r\np\r\n", b":-2\r\n"),
                (
                    b"*3\r\n$4\r\nTREG\r\n$3\r\nGET\r\n$1\r\nt\r\n",
                    b"*2\r\n$5\r\nhello\r\n:9\r\n",
                ),
                (b"*3\r\n$4\r\nTLOG\r\n$4\r\nSIZE\r\n$1\r\nl\r\n", b":1\r\n"),
                (
                    b"*4\r\n$5\r\nUJSON\r\n$3\r\nGET\r\n$1\r\nu\r\n$1\r\nf\r\n",
                    b"$2\r\n42\r\n",
                ),
            )

            async def all_converged():
                for payload, want in checks:
                    if (await resp_call(bar.server.port, payload)) != want:
                        return False
                return True

            deadline = asyncio.get_event_loop().time() + 120 * TICK
            ok = False
            while asyncio.get_event_loop().time() < deadline:
                if await all_converged():
                    ok = True
                    break
                await asyncio.sleep(TICK)
            assert ok, "partitioned node failed to sync missed writes on heal"
        finally:
            await bar.stop()
            await foo.stop()

    asyncio.run(main())


def test_eight_node_churn_convergence():
    """Scale past the reference's 3-node pattern (VERDICT r2 weak item 7):
    an 8-node full mesh under join/leave/rejoin churn with concurrent
    writes must converge every alive node, keep connection counts at
    O(alive), and keep P2Set membership tombstones bounded by the actual
    churn (full-mesh + permanent blacklisting both have failure modes
    that only appear past toy scale)."""

    async def main():
        ports = grab_ports(9)
        seed = None
        nodes = []
        for i in range(8):
            seeds = [seed.config.addr] if seed else []
            n = Node("churn-%d" % i, ports[i], seeds, log_level="info")
            await n.start()
            nodes.append(n)
            if seed is None:
                seed = n
        alive = list(nodes)
        total = 0

        def mesh_alive():
            # meshed() is too strict under churn: dead addresses linger in
            # membership (the reference keeps re-dialing them), so every
            # heartbeat transiently parks a placeholder conn in _actives.
            # The churn-phase invariant is: an ESTABLISHED active to every
            # ALIVE peer, and no unbounded leak beyond the re-dial
            # placeholders for the (bounded) dead addresses.
            addrs = {n.config.addr for n in alive}
            return all(
                sum(
                    1
                    for a, c in n.cluster._actives.items()
                    if a in addrs and c.established
                )
                == len(alive) - 1
                and len(n.cluster._actives) <= len(alive) + 1
                for n in alive
            )

        try:
            assert await converge_wait(lambda: meshed(*alive), ticks=120), (
                "8-node full mesh never formed"
            )

            async def inc(node, amount):
                out = await resp_call(
                    node.server.port,
                    b"*4\r\n$6\r\nGCOUNT\r\n$3\r\nINC\r\n$5\r\nchurn\r\n$%d\r\n%d\r\n"
                    % (len(b"%d" % amount), amount),
                )
                assert out == b"+OK\r\n"
                return amount

            async def read_total(node):
                return await resp_call(
                    node.server.port,
                    b"*3\r\n$6\r\nGCOUNT\r\n$3\r\nGET\r\n$5\r\nchurn\r\n",
                )

            async def all_converged(want):
                for n in alive:
                    if await read_total(n) != b":%d\r\n" % want:
                        return False
                return True

            async def converge_total(want, ticks=600):
                # generous: under full-suite load the event loop and the
                # 28-connection gossip mesh share one contended CPU
                for _ in range(ticks):
                    if await all_converged(want):
                        return True
                    await asyncio.sleep(TICK)
                return await all_converged(want)

            async def totals_detail():
                return [
                    (n.config.addr.name, await read_total(n)) for n in alive
                ]

            # phase 1: concurrent writes on all 8 nodes
            for round_ in range(3):
                for i, n in enumerate(alive):
                    total += await inc(n, i + 1)
            assert await converge_total(total), (
                "phase-1 totals diverged", total, await totals_detail())

            # phase 2: two nodes leave mid-traffic; writes continue
            for dying in (nodes[6], nodes[7]):
                alive.remove(dying)
                await dying.stop()
            for round_ in range(2):
                for i, n in enumerate(alive):
                    total += await inc(n, 1)
            assert await converge_wait(mesh_alive, ticks=400), (
                "survivors never settled to a 6-node mesh"
            )
            assert await converge_total(total), (
                "phase-2 totals diverged", total, await totals_detail())

            # phase 3: node 6 REJOINS as a restart would — same host:port,
            # fresh generated name — which must blacklist its stale name
            # cluster-wide; plus a brand-new ninth node joins. Both must
            # bootstrap the full count, then contribute writes.
            reborn = Node(
                "churn-6-reborn", ports[6], [seed.config.addr],
                log_level="info",
            )
            await reborn.start()
            alive.append(reborn)
            fresh = Node(
                "churn-8-late", ports[8], [seed.config.addr],
                log_level="info",
            )
            await fresh.start()
            alive.append(fresh)
            assert await converge_wait(mesh_alive, ticks=400), (
                "rejoined mesh never formed"
            )
            total += await inc(reborn, 5)
            total += await inc(fresh, 7)
            ok = await converge_total(total)
            if not ok:
                # full diagnostics to a file (pytest truncates long
                # assert reprs, which hid exactly the two bootstrapping
                # nodes): per node — socket total vs repo-direct total
                # vs native-engine row state (distinguishes
                # never-converged from converged-but-served-stale),
                # per-type digests, sync bookkeeping, and the node's own
                # SYSTEM log (sync decisions log at info)
                with open("/tmp/churn_diag.txt", "w") as f:
                    f.write(f"DIVERGED total={total}\n")
                    for n in alive:
                        # per-node probes are best-effort: the nodes are
                        # still serving, and a probe racing a threaded
                        # drain must not mask the divergence assert below
                        try:
                            t = await read_total(n)
                            r = _CollectResp()
                            async with n.database.manager("GCOUNT")._lock:
                                n.database.manager("GCOUNT").repo.apply(
                                    r, [b"GET", b"churn"]
                                )
                            eng = n.database.native_engine
                            row_state = None
                            if eng is not None:
                                row = eng.find(0, b"churn")
                                if row >= 0:
                                    row_state = dict(
                                        value=eng.value(0, row),
                                        foreign=eng.is_foreign(0, row),
                                        own_p=eng.own(0, row, 0),
                                    )
                            digs = [
                                d.hex()[:12]
                                for d in
                                await n.database.sync_type_digests_async()
                            ]
                            c = n.cluster
                            f.write(
                                f"NODE {n.config.addr.name} socket={t!r} "
                                f"repo={r.vals!r} native={row_state!r} "
                                f"digests={digs} tick={c._tick} "
                                f"req_tick={ {a.name: v for a, v in c._sync_req_tick.items()} } "
                                f"rx_tick={c._sync_rx_tick} "
                                f"dump_inflight={c._sync_dump_inflight} "
                                f"waiters={len(c._sync_waiters)} "
                                f"known={len(list(c._known_addrs))}\n"
                            )
                        except Exception as e:  # noqa: BLE001
                            f.write(
                                f"NODE {n.config.addr.name} probe failed: "
                                f"{e!r}\n"
                            )
                    for n in alive:
                        try:
                            f.write(f"==== SYSTEM log {n.config.addr.name}\n")
                            for value, ts in n.system.repo._log.latest():
                                f.write(
                                    f"  {ts} {value.decode(errors='replace')}\n"
                                )
                        except Exception as e:  # noqa: BLE001
                            f.write(f"  log probe failed: {e!r}\n")
                print("diagnostics written to /tmp/churn_diag.txt", flush=True)
            assert ok, ("post-rejoin totals diverged", total)

            # O(conn) sanity: established actives == alive-1 on every
            # node, and total actives bounded by alive+1 (the one re-dial
            # placeholder for a lingering dead address) — checked inside
            # mesh_alive; assert it holds now that churn is over
            assert await converge_wait(mesh_alive, ticks=120), (
                "active connection counts never settled"
            )
            # blacklisted addresses leave the sync-request bookkeeping
            # too (membership convergence prunes them): every tracked
            # cooldown entry belongs to a currently-known address
            for n in alive:
                assert all(
                    a in n.cluster._known_addrs
                    for a in n.cluster._sync_req_tick
                ), (n.config.addr.name, dict(n.cluster._sync_req_tick))


            # tombstones bounded by actual churn: the only PERMANENT
            # removal is node 6's stale name (same host:port, new name);
            # node 7's clean leave must NOT tombstone it, and membership
            # is the 8 alive addresses (7's address lingers as a live
            # entry — the reference keeps re-dialing it; bounded, not
            # growing)
            for n in alive:
                assert len(n.cluster._known_addrs.removes) <= 2, (
                    n.config.addr.name,
                    n.cluster._known_addrs.removes,
                )
                assert len(n.cluster._known_addrs.adds) <= 10
        finally:
            for n in alive:
                await n.stop()

    asyncio.run(main())


def test_out_of_envelope_messages_are_declared_drops_not_silence():
    """Satellite of the protocol-atlas round: a message outside the
    (role, state, msg) envelope is DISCARDED with the conn kept, but
    counted per reason (msg_drop_* in CLUSTER metrics) and traced —
    jlint pass 10 (JL1002) forbids re-introducing a silent ignore."""
    from jylis_tpu.cluster.cluster import Cluster, MsgDrop, _Conn
    from jylis_tpu.cluster.msg import MsgSyncDone

    cfg = Config()
    cfg.addr = Address("127.0.0.1", "7001", "solo")
    cfg.log = Log.create_none()

    class _Db:  # registry-less direct drive: resolve_registry -> DEFAULT
        pass

    cluster = Cluster(cfg, _Db())

    async def main():
        from jylis_tpu.cluster.msg import MsgPong

        passive = _Conn(writer=None, active_addr=None)
        passive.established = True
        await cluster._passive_msg(passive, MsgPong())
        await cluster._passive_msg(passive, MsgSyncDone())
        await cluster._passive_msg(passive, MsgSyncDone())
        active = _Conn(
            writer=None, active_addr=Address("127.0.0.1", "7002", "peer")
        )
        active.established = True
        await cluster._active_msg(active, MsgPong())  # nothing outstanding
        # an EXPECTED SyncDone on the active side is a counted close of
        # our sync request, never a drop
        await cluster._active_msg(active, MsgSyncDone())

    asyncio.run(main())
    totals = cluster.metrics_totals()
    assert totals[f"msg_drop_{MsgDrop.PONG_UNSOLICITED}"] == 1
    assert totals[f"msg_drop_{MsgDrop.SYNC_DONE_UNSOLICITED}"] == 2
    assert totals[f"msg_drop_{MsgDrop.PONG_UNMATCHED}"] == 1
    assert totals["sync_done_recv"] == 1


def test_matched_pong_is_not_a_drop():
    """The declared-drop path must not fire when a Pong answers a
    stamped send: pop + rtt record, zero msg_drop counters."""
    from jylis_tpu.cluster.cluster import Cluster, _Conn
    from jylis_tpu.cluster.msg import MsgPong

    cfg = Config()
    cfg.addr = Address("127.0.0.1", "7001", "solo")
    cfg.log = Log.create_none()

    class _Db:
        pass

    cluster = Cluster(cfg, _Db())

    async def main():
        active = _Conn(
            writer=None, active_addr=Address("127.0.0.1", "7002", "peer")
        )
        active.established = True
        active.pong_sent.append(0.0)
        await cluster._active_msg(active, MsgPong())
        assert not active.pong_sent

    asyncio.run(main())
    assert not any(
        k.startswith("msg_drop_") for k in cluster.metrics_totals()
    )
