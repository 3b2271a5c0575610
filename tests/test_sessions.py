"""Session guarantees (jylis_tpu/sessions.py + the SESSION surface).

Three layers: the token codec's robustness (truncation at every byte,
CRC, u64 bounds, empty vector — a client-held artifact must fail typed,
never misread), the SessionIndex contiguity/adoption rules (the
watermark discipline read-your-writes rests on), and the end-to-end
guarantee over real sockets: tokens minted on one replica
verify on another (bounded wait), go typed-STALE when uncovered, and
reply tokens stay monotone across a replica bounce and a node failover.
"""

import asyncio

import pytest

import jylis_tpu  # noqa: F401
from jylis_tpu import sessions
from jylis_tpu.cluster import Cluster
from jylis_tpu.models.database import Database
from jylis_tpu.utils.address import Address
from jylis_tpu.utils.config import Config
from jylis_tpu.utils.log import Log

from test_cluster import Node, converge_wait, grab_ports, meshed, resp_call

TICK = 0.05


# ---- token codec robustness -------------------------------------------------


def test_token_roundtrip_shapes():
    for vec in (
        {},
        {"127.0.0.1:9999:a!0": 0},
        {"h:1:n!1700000000000": (1 << 64) - 1},
        {f"10.0.0.{i}:7001:n{i}!{i}": i * 7 for i in range(40)},
    ):
        assert sessions.decode_token(sessions.encode_token(vec)) == vec


def test_token_truncation_at_every_byte_is_typed():
    tok = sessions.encode_token(
        {"10.0.0.1:7001:foo!1700000000123": 300, "h:2:b!7": 1}
    )
    for i in range(len(tok)):
        with pytest.raises(sessions.SessionError):
            sessions.decode_token(tok[:i])


def test_token_corruption_and_trailing_are_typed():
    tok = sessions.encode_token({"h:1:n!7": 5})
    for i in range(len(tok)):
        flipped = bytearray(tok)
        flipped[i] ^= 0x40
        with pytest.raises(sessions.SessionError):
            sessions.decode_token(bytes(flipped))
    with pytest.raises(sessions.SessionError):
        sessions.decode_token(tok + b"x")  # CRC no longer matches
    with pytest.raises(sessions.SessionError):
        sessions.decode_token(b"")


def test_token_u64_bound_and_duplicate_rid_refused():
    import struct
    import zlib

    # hand-build a token whose seq varint exceeds u64
    body = bytearray((sessions.TOKEN_VERSION,))
    sessions._w_varint(body, 1)
    rid = b"h:1:n!1"
    sessions._w_varint(body, len(rid))
    body += rid
    sessions._w_varint(body, 1 << 64)
    tok = bytes(body) + struct.pack(">I", zlib.crc32(bytes(body)))
    with pytest.raises(sessions.SessionError):
        sessions.decode_token(tok)
    # ... and one with the same rid twice
    body = bytearray((sessions.TOKEN_VERSION,))
    sessions._w_varint(body, 2)
    for _ in range(2):
        sessions._w_varint(body, len(rid))
        body += rid
        sessions._w_varint(body, 3)
    tok = bytes(body) + struct.pack(">I", zlib.crc32(bytes(body)))
    with pytest.raises(sessions.SessionError):
        sessions.decode_token(tok)


def test_empty_token_dominates_trivially():
    tok = sessions.encode_token({})
    assert sessions.decode_token(tok) == {}
    assert sessions.dominates({}, {})
    assert sessions.dominates({"a": 1}, {})
    assert not sessions.dominates({}, {"a": 1})


# ---- SessionIndex watermark discipline -------------------------------------


def test_contiguity_advances_and_parks():
    idx = sessions.SessionIndex()
    assert idx.note_applied("o", 1) is True
    assert idx.vector() == {"o": 1}
    # a gap parks; the watermark NEVER jumps (the read-your-writes rule)
    assert idx.note_applied("o", 3) is True
    assert idx.vector() == {"o": 1}
    # the gap filler collapses the park
    assert idx.note_applied("o", 2) is True
    assert idx.vector() == {"o": 3}
    # duplicates are not first-sight (the bridge relay predicate)
    assert idx.note_applied("o", 2) is False


def test_unsafe_mode_jumps_the_gap():
    idx = sessions.SessionIndex(unsafe=True)
    idx.note_applied("o", 5)
    assert idx.vector() == {"o": 5}  # the deliberately broken rule


def test_adoption_folds_and_collapses_parked():
    idx = sessions.SessionIndex()
    idx.note_applied("o", 4)  # parked (gap 1-3)
    assert idx.vector() == {"o": 0} or "o" not in idx.vector()
    idx.adopt({"o": 3, "p": 9})
    assert idx.vector() == {"o": 4, "p": 9}  # adoption subsumed the gap


def test_park_cap_drops_lowest_not_the_watermark():
    idx = sessions.SessionIndex()
    for seq in range(2, sessions.PARK_CAP + 4):
        idx.note_applied("o", seq)
    assert idx.vector().get("o", 0) == 0  # never jumped
    assert idx.stats["parked_dropped"] > 0


def test_epoch_pruning_keeps_newest_incarnations():
    idx = sessions.SessionIndex()
    for epoch in range(10):
        idx.adopt({sessions.make_rid("h:1:n", epoch): 5})
    rids = set(idx.vector())
    assert len(rids) == sessions.EPOCHS_PER_ADDR
    assert sessions.make_rid("h:1:n", 9) in rids
    assert sessions.make_rid("h:1:n", 0) not in rids


def test_wait_dominated_bounded():
    async def go():
        idx = sessions.SessionIndex()
        assert await idx.wait_dominated({}, 50) is True
        t0 = asyncio.get_running_loop().time()
        assert await idx.wait_dominated({"o": 1}, 80) is False
        waited = asyncio.get_running_loop().time() - t0
        assert 0.05 <= waited < 2.0
        # a late advance wakes a waiter before the deadline
        task = asyncio.ensure_future(idx.wait_dominated({"o": 1}, 5000))
        await asyncio.sleep(0.01)
        idx.note_applied("o", 1)
        assert await asyncio.wait_for(task, 2.0) is True

    asyncio.run(go())


# ---- end-to-end over real sockets ------------------------------------------


async def _wrap_write(port: int, *words: bytes) -> bytes:
    """SESSION WRAP <write>: returns the minted token from the [reply,
    token] array."""
    payload = b"SESSION WRAP " + b" ".join(words) + b"\r\n"
    out = await resp_call(port, payload)
    assert out.startswith(b"*2\r\n+OK\r\n$"), out
    _, _, rest = out.partition(b"+OK\r\n$")
    n, _, tail = rest.partition(b"\r\n")
    return tail[: int(n)]


async def _session_read(port: int, token: bytes, *words: bytes) -> bytes:
    import struct

    cmd = [b"SESSION", b"READ", token, *words]
    payload = b"*%d\r\n" % len(cmd) + b"".join(
        b"$%d\r\n%s\r\n" % (len(w), w) for w in cmd
    )
    return await resp_call(port, payload)


def test_session_read_your_writes_across_nodes():
    """Write + WRAP on foo; SESSION READ with the token on bar serves
    the write (bounded wait covers the propagation window) and returns
    a monotone reply token."""
    asyncio.run(_ryw_across_nodes())


async def _ryw_across_nodes():
    p_foo, p_bar = grab_ports(2)
    foo = Node("foo", p_foo)
    bar = Node("bar", p_bar, seeds=[Address("127.0.0.1", str(p_foo), "foo")])
    await foo.start()
    await bar.start()
    try:
        await converge_wait(lambda: meshed(foo, bar))
        tok = await _wrap_write(
            foo.server.port, b"GCOUNT", b"INC", b"sess", b"7"
        )
        vec = sessions.decode_token(tok)
        assert any(v >= 1 for v in vec.values()), vec
        # the read waits out the propagation if needed, then serves
        out = b""
        for _ in range(80):
            out = await _session_read(
                bar.server.port, tok, b"GCOUNT", b"GET", b"sess"
            )
            if out.startswith(b"*2\r\n$"):
                break
            assert out.startswith(b"-STALE"), out
            await asyncio.sleep(TICK)
        assert out.startswith(b"*2\r\n$"), out
        assert out.endswith(b":7\r\n"), out
        # monotonic reads: the reply token dominates the presented one
        _, _, rest = out.partition(b"$")
        n, _, tail = rest.partition(b"\r\n")
        reply_vec = sessions.decode_token(tail[: int(n)])
        assert sessions.dominates(reply_vec, vec), (reply_vec, vec)
    finally:
        await foo.stop()
        await bar.stop()


def test_session_stale_and_badtoken_are_typed():
    asyncio.run(_stale_badtoken())


async def _stale_badtoken():
    p_foo, = grab_ports(1)
    foo = Node("foo", p_foo)
    foo.database.session_wait_ms = 120
    await foo.start()
    try:
        # a token naming a stream this node never saw: typed STALE
        # after the bounded wait
        tok = sessions.encode_token({"10.9.9.9:7001:ghost!1": 5})
        out = await _session_read(
            foo.server.port, tok, b"GCOUNT", b"GET", b"k"
        )
        assert out.startswith(b"-STALE"), out
        # garbage bytes: typed BADTOKEN, no wait
        out = await _session_read(
            foo.server.port, b"not-a-token", b"GCOUNT", b"GET", b"k"
        )
        assert out.startswith(b"-BADTOKEN"), out
        totals = foo.database.sessions.metrics_totals()
        assert totals["stale_refusals"] == 1
        assert totals["badtoken_refusals"] == 1
    finally:
        await foo.stop()


def test_session_token_survives_node_failover():
    """Mint on foo, let bar converge, KILL foo: the token still
    verifies on bar (the applied vector tracked foo's stream), so the
    client fails over with its guarantee intact."""
    asyncio.run(_failover())


async def _failover():
    p_foo, p_bar = grab_ports(2)
    foo = Node("foo", p_foo)
    bar = Node("bar", p_bar, seeds=[Address("127.0.0.1", str(p_foo), "foo")])
    await foo.start()
    await bar.start()
    try:
        await converge_wait(lambda: meshed(foo, bar))
        tok = await _wrap_write(
            foo.server.port, b"TREG", b"SET", b"fk", b"v1", b"9"
        )
        vec = sessions.decode_token(tok)

        # wait until bar's vector covers the token, then fail foo over
        await converge_wait(
            lambda: bar.database.sessions.dominated(vec), ticks=100
        )
        await foo.stop()
        out = await _session_read(
            bar.server.port, tok, b"TREG", b"GET", b"fk"
        )
        assert out.startswith(b"*2\r\n$"), out
        assert b"v1" in out, out
    finally:
        await bar.stop()


def test_session_token_bounces_across_two_databases():
    """Two plain Databases converging over a real loopback cluster: a
    token minted on one verifies on the other once the cluster
    delivers — the same vector, no replica-specific state in the
    token."""
    asyncio.run(_replica_bounce())


async def _replica_bounce():
    p0, p1 = grab_ports(2)
    a0 = Address("127.0.0.1", str(p0), "n0")
    a1 = Address("127.0.0.1", str(p1), "n1")

    def replica(addr, seeds, ident):
        cfg = Config()
        cfg.port = "0"
        cfg.addr = addr
        cfg.seed_addrs = list(seeds)
        cfg.heartbeat_time = TICK
        cfg.log = Log.create_none()
        db = Database(identity=ident)
        cl = Cluster(cfg, db)
        return cfg, db, cl

    _, db0, cl0 = replica(a0, [a1], 1)
    _, db1, cl1 = replica(a1, [a0], 2)
    await cl0.start()
    await cl1.start()
    try:

        class _Resp:
            def __init__(self):
                self.parts = []

            def __getattr__(self, name):
                return lambda *a: self.parts.append((name, a))

        r = _Resp()
        await db0.apply_async(r, [b"GCOUNT", b"INC", b"lk", b"3"])
        tok = await db0._mint_token()
        vec = sessions.decode_token(tok)
        assert any(v >= 1 for v in vec.values())

        async def dominated() -> bool:
            return db1.sessions.dominated(vec)

        for _ in range(200):
            if db1.sessions.dominated(vec):
                break
            await asyncio.sleep(TICK / 2)
        assert db1.sessions.dominated(vec)
        # the bounce: SESSION READ on the OTHER replica serves immediately
        r2 = _Resp()
        await db1.apply_async(
            r2, [b"SESSION", b"READ", tok, b"GCOUNT", b"GET", b"lk"]
        )
        kinds = [k for k, _ in r2.parts]
        assert "err" not in kinds, r2.parts
        assert ("u64", (3,)) in r2.parts or ("i64", (3,)) in r2.parts, r2.parts
    finally:
        cl0.dispose()
        cl1.dispose()


# ---- admission control ------------------------------------------------------


@pytest.mark.parametrize("path", ["apply_async", "served"])
def test_admission_cap_refuses_busy_class_only(path):
    """With the cap armed and the repo lock held (a stalled drain), the
    class's queued commands get typed BUSY; other classes still serve;
    releasing the lock restores service and the refusals are counted.
    Over a served connection too: under a cap a chunk of the held type
    is NOT kept native to sleep for the lock (server.py's route), so
    its wait counts in the manager's inflight and the next is refused."""
    asyncio.run(_admission_cap(path == "served"))


async def _admission_cap(served: bool):
    from jylis_tpu.server.resp import Respond
    from jylis_tpu.server.server import Server

    db = Database(identity=1)
    db.set_admission_cap(1)
    cfg = Config()
    cfg.port = "0"
    cfg.log = Log.create_none()
    server = Server(cfg, db)
    await server.start()

    async def ask(line: bytes) -> bytes:
        """One command's reply bytes, over a socket or handed to
        `apply_async` (rendered by the real `Respond`)."""
        if not served:
            out = bytearray()
            await db.apply_async(Respond(out.extend), line.split())
            return bytes(out)
        reader, writer = await asyncio.open_connection("127.0.0.1", server.port)
        writer.write(line + b"\r\n")
        got = await asyncio.wait_for(reader.read(1 << 16), 5)
        writer.close()
        return got

    try:
        mgr = db.manager("GCOUNT")
        async with mgr._lock:  # a drain wedging this class
            waiter = asyncio.ensure_future(ask(b"GCOUNT INC h 1"))
            while mgr._inflight < 1:  # the first queued command
                await asyncio.sleep(0.005)
            busy = await ask(b"GCOUNT INC h 1")
            assert busy.startswith(b"-BUSY (GCOUNT admission cap 1"), busy
            # the node is NOT degraded: another class serves inline
            assert await ask(b"PNCOUNT GET ok") == b":0\r\n"
        assert await waiter == b"+OK\r\n"
        assert db.metrics.serving_counters["busy_refusals"] == 1
        assert db.metrics.serving_counters["slept_bursts"] == 0
        assert await ask(b"GCOUNT GET h") == b":1\r\n"
    finally:
        await server.dispose()
    db.clean_shutdown()


def test_session_token_through_dead_bridge_stale_then_satisfied():
    """Bridge failover x sessions (PR 15): a token minted on a region
    member whose only WAN path was the now-dead bridge goes typed
    STALE on the remote region within --session-wait-ms — never a
    stale serve — and SATISFIES after the deterministic handover,
    once the successor's digest sync carries the adoption proof
    across."""
    asyncio.run(_token_through_dead_bridge())


async def _token_through_dead_bridge():
    from jylis_tpu import faults

    p_a, p_b, p_c = sorted(grab_ports(3))
    a = Node("aye", p_a, region="r1")
    b = Node("bee", p_b, seeds=[a.config.addr], region="r1")
    c = Node("sea", p_c, seeds=[a.config.addr], region="r2")
    c.database.session_wait_ms = 150
    for n in (a, b, c):
        n.cluster._bridge_demote = 8
        await n.start()
    a_stopped = False
    try:
        def sparse() -> bool:
            return (
                len(a.cluster._actives) == 2
                and a.cluster._is_bridge()
                and c.cluster._is_bridge()
                and all(
                    cn.established
                    for n in (a, b, c)
                    for cn in n.cluster._actives.values()
                )
            )

        assert await converge_wait(sparse, ticks=200)

        # the WAN relay is severed BEFORE the write: the token's
        # frames reach the bridge and die there — exactly the gap a
        # dead bridge leaves
        faults.arm("cluster.relay", "drop", budget=10_000)
        try:
            tok = await _wrap_write(
                b.server.port, b"GCOUNT", b"INC", b"fk", b"3"
            )
            vec = sessions.decode_token(tok)
            # sea must not have been healed through a periodic sync
            # before the kill — the STALE assertion below needs the
            # gap to be real
            assert not c.database.sessions.dominated(vec)
            await a.stop()  # the bridge dies with the relay unflushed
            a_stopped = True
        finally:
            faults.disarm("cluster.relay")

        # pre-handover: typed STALE within the bounded wait
        loop = asyncio.get_event_loop()
        t0 = loop.time()
        out = await _session_read(
            c.server.port, tok, b"GCOUNT", b"GET", b"fk"
        )
        waited = loop.time() - t0
        assert out.startswith(b"-STALE"), out
        assert waited < 2.0, waited  # 150 ms bound + socket slack

        # handover: bee succeeds, dials sea, range repair + the
        # digest-match adoption carry the watermark across
        assert await converge_wait(
            lambda: b.cluster._is_bridge(), ticks=600
        )
        out = b""
        for _ in range(400):
            out = await _session_read(
                c.server.port, tok, b"GCOUNT", b"GET", b"fk"
            )
            if out.startswith(b"*2\r\n$"):
                break
            assert out.startswith(b"-STALE"), out
            await asyncio.sleep(TICK)
        assert out.startswith(b"*2\r\n$"), out
        assert out.endswith(b":3\r\n"), out
        # monotonic reads survive the failover: reply token dominates
        _, _, rest = out.partition(b"$")
        n_, _, tail = rest.partition(b"\r\n")
        reply_vec = sessions.decode_token(tail[: int(n_)])
        assert sessions.dominates(reply_vec, vec), (reply_vec, vec)
        assert b.cluster._stats["sync_full_dumps"] == 0
        assert c.cluster._stats["sync_full_dumps"] == 0
    finally:
        for n in ((b, c) if a_stopped else (a, b, c)):
            await n.stop()
