"""Digest-gated bootstrap sync (round-4 verdict item 3): an in-sync peer
re-establishing a connection must trigger ZERO dump frames (its digest
matches, the server answers Pong), and a large keyspace must stream as
bounded chunked frames, converging fully on the requester."""

import asyncio

import pytest

import jylis_tpu  # noqa: F401
from jylis_tpu.cluster import cluster as cluster_mod

from procutil import free_port
from test_cluster import TICK, Node, _CollectResp, converge_wait, resp_call


def test_in_sync_peer_reconnect_ships_zero_frames():
    async def main():
        pa, pb = free_port(), free_port()
        a = Node("syna", pa)
        b = Node("synb", pb, seeds=[a.config.addr])
        streamed = []
        orig = cluster_mod.Cluster._stream_sync

        async def counting_stream(self, conn, frames):
            streamed.append(len(frames))
            return await orig(self, conn, frames)

        cluster_mod.Cluster._stream_sync = counting_stream
        try:
            await a.start()
            await b.start()
            # write on A, converge to B (the initial bootstrap sync WILL
            # stream frames — B starts empty)
            got = await resp_call(
                a.server.port,
                b"*4\r\n$6\r\nGCOUNT\r\n$3\r\nINC\r\n$1\r\nk\r\n$1\r\n7\r\n",
            )
            assert got == b"+OK\r\n"

            async def b_sees():
                out = await resp_call(
                    b.server.port,
                    b"*3\r\n$6\r\nGCOUNT\r\n$3\r\nGET\r\n$1\r\nk\r\n",
                )
                return out == b":7\r\n"

            ok = False
            deadline = asyncio.get_event_loop().time() + 60 * TICK
            while asyncio.get_event_loop().time() < deadline:
                if await b_sees():
                    ok = True
                    break
                await asyncio.sleep(TICK)
            assert ok, "initial convergence failed"
            # let delta traffic quiesce so both digests settle
            await asyncio.sleep(6 * TICK)
            baseline = list(streamed)

            # force a re-establishment: drop B's active conn to A and let
            # the heartbeat re-dial; clear the request cooldown so the
            # re-established conn sends a fresh MsgSyncRequest
            b.cluster._sync_req_tick.clear()
            for conn in list(b.cluster._actives.values()):
                b.cluster._drop(conn)

            def reconnected():
                return any(
                    c.established for c in b.cluster._actives.values()
                )

            assert await converge_wait(reconnected, ticks=60)
            # wait for the sync round-trip to settle
            await asyncio.sleep(10 * TICK)
            # the reconnect sync streams ONLY the (single) SYSTEM frame —
            # zero data frames for an in-sync peer
            new = streamed[len(baseline):]
            assert all(n == 1 for n in new), (
                f"in-sync reconnect streamed data frames: {streamed} "
                f"(baseline {baseline})"
            )
            # and the peer remains converged
            assert await b_sees()
        finally:
            cluster_mod.Cluster._stream_sync = orig
            await a.stop()
            await b.stop()

    asyncio.run(main())


def test_large_keyspace_sync_is_chunked_and_converges():
    async def main():
        pa, pb = free_port(), free_port()
        a = Node("biga", pa)
        n_keys = 3 * cluster_mod.SYNC_CHUNK_KEYS + 17
        # seed A's GCOUNT directly (the wire path would be the slow part
        # of the test, not the subject)
        repo = a.database.manager("GCOUNT").repo
        for i in range(n_keys):
            repo.converge(b"key%06d" % i, {9: i + 1})

        sizes = []
        orig = cluster_mod.Cluster._send_frame

        async def counting_send(self, conn, data):
            sizes.append(len(data))
            return await orig(self, conn, data)

        cluster_mod.Cluster._send_frame = counting_send
        try:
            await a.start()
            b = Node("bigb", pb, seeds=[a.config.addr])
            await b.start()

            async def b_has_all():
                out = await resp_call(
                    b.server.port,
                    b"*3\r\n$6\r\nGCOUNT\r\n$3\r\nGET\r\n$9\r\nkey%06d\r\n"
                    % (n_keys - 1),
                )
                return out == b":%d\r\n" % n_keys

            ok = False
            deadline = asyncio.get_event_loop().time() + 120 * TICK
            while asyncio.get_event_loop().time() < deadline:
                if await b_has_all():
                    ok = True
                    break
                await asyncio.sleep(TICK)
            assert ok, "large sync never converged"
            # the GCOUNT type must arrive as >= ceil(n_keys/chunk) frames,
            # each bounded (chunking, not one monolithic frame)
            assert len(sizes) >= n_keys // cluster_mod.SYNC_CHUNK_KEYS + 1
            cap = max(
                cluster_mod.SYNC_CHUNK_KEYS * 64,  # ~bytes/key bound
                cluster_mod.SYNC_CHUNK_BYTES,
            )
            assert max(sizes) < cap, f"frame too large: {max(sizes)}"
        finally:
            cluster_mod.Cluster._send_frame = orig
            await a.stop()
            await b.stop()

    asyncio.run(main())


def test_incremental_digest_never_dumps(monkeypatch):
    """Round-5 verdict item 2: the digest-only path must not dump the
    keyspace — digests compute incrementally from dirty keys."""

    async def main():
        pa = free_port()
        a = Node("incra", pa)
        await a.start()
        try:
            # seed some state through the real serving path
            got = await resp_call(
                a.server.port,
                b"*4\r\n$6\r\nGCOUNT\r\n$3\r\nINC\r\n$1\r\nk\r\n$1\r\n7\r\n",
            )
            assert got == b"+OK\r\n"
            for mgr in a.database.managers():

                def boom(_mgr=mgr):
                    raise AssertionError(
                        f"digest path dumped {_mgr.name}"
                    )

                monkeypatch.setattr(mgr.repo, "dump_state", boom)
            d1 = await a.database.sync_digest_async()
            d2 = await a.database.sync_digest_async()
            assert d1 == d2 and len(d1) == 32
            # a write changes the digest; an identical second write does not
            got = await resp_call(
                a.server.port,
                b"*4\r\n$4\r\nTREG\r\n$3\r\nSET\r\n$1\r\nt\r\n$1\r\nv\r\n",
            )  # malformed arity: help reply, no state change
            d3 = await a.database.sync_digest_async()
            assert d3 == d1
            got = await resp_call(
                a.server.port,
                b"TREG SET t v 5\r\n",
            )
            assert got == b"+OK\r\n"
            d4 = await a.database.sync_digest_async()
            assert d4 != d1
        finally:
            await a.stop()

    asyncio.run(main())


def test_digest_equal_across_nodes_and_backends():
    """Converged peers must digest-match regardless of op order, replica
    identity of the writes they saw first, or table backend."""
    from jylis_tpu.models.database import Database

    def drive(db: Database, order: int):
        class R:
            def __getattr__(self, name):
                return lambda *a: None

        r = R()
        gc = db.manager("GCOUNT").repo
        pn = db.manager("PNCOUNT").repo
        tr = db.manager("TREG").repo
        tl = db.manager("TLOG").repo
        uj = db.manager("UJSON").repo
        ops = [
            lambda: gc.apply(r, [b"INC", b"g", b"5"]),
            lambda: gc.converge(b"g", {7: 9}),
            lambda: gc.converge(b"g", {8: 2}),
            lambda: pn.apply(r, [b"INC", b"p", b"3"]),
            lambda: pn.converge(b"p", ({9: 4}, {9: 1})),
            lambda: tr.apply(r, [b"SET", b"t", b"v1", b"5"]),
            lambda: tr.converge(b"t", (b"v2", 9)),
            lambda: tl.apply(r, [b"INS", b"l", b"x", b"3"]),
            lambda: tl.converge(b"l", ([(b"y", 4), (b"x", 3)], 0)),
            lambda: uj.apply(r, [b"INS", b"u", b"tags", b"1"]),
        ]
        if order:
            ops = ops[::-1]
        for op in ops:
            op()

    async def digest(db):
        return await db.sync_digest_async()

    async def main():
        # identity differs per node; write the OTHER node's own column via
        # converge so the joined state matches
        a = Database(identity=1)
        b = Database(identity=1, engine="python")
        drive(a, 0)
        drive(b, 1)
        da = await digest(a)
        db_ = await digest(b)
        assert da == db_, "converged nodes (different order/backends) diverge"
        # and a genuinely different state mismatches
        a.manager("GCOUNT").repo.converge(b"g", {12: 1})
        assert (await digest(a)) != db_

    asyncio.run(main())


def test_system_digest_types_localizes_divergence():
    """SYSTEM DIGEST TYPES (the operator's divergence localizer): one
    '<TYPE> <hex>' line per data type through the real serving path;
    converged nodes agree line-for-line, and a single-type divergence
    moves exactly that type's line."""

    async def main():
        pa = free_port()
        a = Node("dgta", pa)
        await a.start()
        try:
            out = await resp_call(a.server.port, b"SYSTEM DIGEST TYPES\r\n")
            lines = [l for l in out.split(b"\r\n") if l and l[:1] not in b"*$"]
            types = [l.split()[0] for l in lines]
            # derived from the registry, not a hand list: a new repo
            # class must land in the DIGEST TYPES surface automatically
            from jylis_tpu.models.database import DATA_TYPE_NAMES

            assert types == [n.encode() for n in DATA_TYPE_NAMES], lines
            assert all(len(l.split()[1]) == 64 for l in lines), lines
            before = dict(l.split() for l in lines)
            got = await resp_call(a.server.port, b"GCOUNT INC k 7\r\n")
            assert got == b"+OK\r\n"
            out = await resp_call(a.server.port, b"SYSTEM DIGEST TYPES\r\n")
            after = dict(
                l.split()
                for l in out.split(b"\r\n")
                if l and l[:1] not in b"*$"
            )
            changed = [t for t in before if before[t] != after[t]]
            assert changed == [b"GCOUNT"], changed
            # the combined digest is the same fold the TYPES lines show
            combined = await resp_call(a.server.port, b"SYSTEM DIGEST\r\n")
            assert len(combined.strip().split(b"\r\n")[-1]) == 64
        finally:
            await a.stop()

    asyncio.run(main())


def test_periodic_digest_exchange_heals_silent_loss():
    """Round-5: deltas lost on the SENDER's churned outbound connection
    are invisible to the receiver — only the periodic digest exchange
    can heal them. Simulate the loss by converging state directly into
    A (converge buffers never re-flush, so broadcast will NEVER carry
    it); B must still converge within ~one SYNC_PERIOD."""

    async def main():
        pa, pb = free_port(), free_port()
        a = Node("pera", pa)
        b = Node("perb", pb, seeds=[a.config.addr])
        await a.start()
        await b.start()
        try:
            def meshed():
                return any(
                    c.established for c in b.cluster._actives.values()
                ) and any(c.established for c in a.cluster._actives.values())

            assert await converge_wait(meshed, ticks=60)
            await asyncio.sleep(4 * TICK)  # initial sync settles
            # silent loss: state exists on A that no broadcast will carry
            a.database.manager("GCOUNT").repo.converge(b"ghost", {44: 7})

            async def b_sees():
                out = await resp_call(
                    b.server.port,
                    b"*3\r\n$6\r\nGCOUNT\r\n$3\r\nGET\r\n$5\r\nghost\r\n",
                )
                return out == b":7\r\n"

            deadline = (
                asyncio.get_event_loop().time()
                + (3 * cluster_mod.SYNC_PERIOD_TICKS) * TICK
                + 5.0
            )
            ok = False
            while asyncio.get_event_loop().time() < deadline:
                if await b_sees():
                    ok = True
                    break
                await asyncio.sleep(TICK)
            assert ok, "periodic digest exchange never healed the loss"
        finally:
            await a.stop()
            await b.stop()

    asyncio.run(main())


def test_mid_heal_serve_defer_is_capped():
    """A responder constantly receiving sync data ("mid-heal") must
    still serve a behind requester after a bounded number of deferrals.
    With cluster-wide aligned heartbeat periods, an UNCAPPED defer
    starves a rejoiner forever: the ahead node's own periodic pull makes
    the behind peer stream its stale dump right before the behind
    peer's request arrives, re-arming the defer window every period —
    the eight-node churn test's rejoin phase hit exactly this (nodes
    stuck at their post-join writes while every request got a silent
    Pong)."""

    async def main():
        pa, pb = free_port(), free_port()
        a = Node("capa", pa)
        b = Node("capb", pb, seeds=[a.config.addr])
        try:
            await a.start()
            await b.start()

            def meshed():
                return any(
                    c.established for c in b.cluster._actives.values()
                ) and any(c.established for c in a.cluster._actives.values())

            assert await converge_wait(meshed, ticks=60)
            await asyncio.sleep(4 * TICK)  # initial sync settles

            # pin the responder permanently "mid-heal": every tick looks
            # like fresh inbound sync data just arrived
            async def pin():
                while True:
                    a.cluster._sync_rx_tick = a.cluster._tick
                    await asyncio.sleep(TICK / 2)

            pin_task = asyncio.get_event_loop().create_task(pin())
            # silent-loss state on A: converge buffers never re-flush, so
            # broadcast (and the held-delta path) will NEVER carry it —
            # ONLY a served sync dump can deliver it to B
            a.database.manager("GCOUNT").repo.converge(b"ghost", {44: 9})

            async def b_sees():
                out = await resp_call(
                    b.server.port,
                    b"*3\r\n$6\r\nGCOUNT\r\n$3\r\nGET\r\n$5\r\nghost\r\n",
                )
                return out == b":9\r\n"

            # establishment request defers (streak 1); the next periodic
            # pulse defers (streak 2); the one after that MUST serve —
            # allow a couple of periods of slack on a loaded box
            deadline = asyncio.get_event_loop().time() + (
                5 * cluster_mod.SYNC_PERIOD_TICKS * TICK + 3.0
            )
            ok = False
            while asyncio.get_event_loop().time() < deadline:
                if await b_sees():
                    ok = True
                    break
                await asyncio.sleep(TICK)
            pin_task.cancel()
            assert ok, "capped mid-heal defer never served the rejoiner"
        finally:
            await a.stop()
            await b.stop()

    asyncio.run(main())


def test_dispose_mid_sync_stream_completes_promptly(monkeypatch):
    """Clean shutdown while a sync dump is streaming: dispose drops the
    waiter's connection under the serve task's feet — the task must
    drain out via its send-failure path (no hang, no unhandled error)
    and dispose must not wait on the stream. Streaming is made slow and
    many-framed deterministically (tiny chunks + a per-frame delay)."""
    monkeypatch.setattr(cluster_mod, "SYNC_CHUNK_KEYS", 4)
    orig_send = cluster_mod.Cluster._send_frame

    async def slow_send(self, conn, data):
        await asyncio.sleep(0.05)
        return await orig_send(self, conn, data)

    monkeypatch.setattr(cluster_mod.Cluster, "_send_frame", slow_send)

    async def main():
        pa, pb = free_port(), free_port()
        a = Node("dispa", pa)
        b = Node("dispb", pb, seeds=[a.config.addr])
        try:
            await a.start()
            r = _CollectResp()
            # 100 frames at 4 keys/chunk x 50 ms/frame = ~5 s of stream:
            # a dispose that joined the stream would blow the 2 s bound
            for i in range(400):
                a.database.manager("GCOUNT").repo.apply(
                    r, [b"INC", b"d%d" % i, b"5"]
                )
            await b.start()  # establishment sync request starts the dump

            def streaming():
                return a.cluster._sync_dump_inflight

            assert await converge_wait(streaming, ticks=120), (
                "sync dump never started"
            )
            await asyncio.sleep(4 * TICK)  # stream is mid-flight
            t0 = asyncio.get_event_loop().time()
            await a.stop()
            assert asyncio.get_event_loop().time() - t0 < 2.0, (
                "dispose blocked on the in-flight sync stream"
            )
            # the serve task unwinds via its send-failure path
            assert await converge_wait(
                lambda: not a.cluster._sync_dump_inflight, ticks=120
            ), "serve task never unwound after dispose"
        finally:
            await a.stop()  # idempotent; covers pre-stop assertion exits
            await b.stop()

    asyncio.run(main())

def test_write_hot_request_defer_is_capped():
    """The requester-side twin of the mid-heal cap: a node whose local
    writes never stop defers its periodic digest pull, but the defer
    streak caps at 3 — a steadily write-hot node must still pull (and
    heal a loss IT suffered) every few periods, not never."""

    async def main():
        pa, pb = free_port(), free_port()
        a = Node("hota", pa)
        b = Node("hotb", pb, seeds=[a.config.addr])
        try:
            await a.start()
            await b.start()

            def meshed():
                return any(
                    c.established for c in b.cluster._actives.values()
                ) and any(c.established for c in a.cluster._actives.values())

            assert await converge_wait(meshed, ticks=60)
            await asyncio.sleep(4 * TICK)  # initial sync settles

            # pin B permanently "write-hot": every tick re-arms the
            # periodic-pull deferral the heartbeat keeps clearing
            async def pin():
                while True:
                    b.cluster._local_writes_seen = True
                    await asyncio.sleep(TICK / 2)

            pin_task = asyncio.get_event_loop().create_task(pin())
            # silent loss on A that only B's own pull can heal (converge
            # buffers never re-flush; A defers serving nothing here)
            a.database.manager("GCOUNT").repo.converge(b"ghost", {44: 5})

            async def b_sees():
                out = await resp_call(
                    b.server.port,
                    b"*3\r\n$6\r\nGCOUNT\r\n$3\r\nGET\r\n$5\r\nghost\r\n",
                )
                return out == b":5\r\n"

            # the cap admits a pull at worst every 4th period; the
            # invariant is EVENTUALLY-pulls-despite-cap, so budget
            # generously — on a loaded box each tick's wall time
            # stretches well past TICK and the old two-window budget
            # (9 periods + 3 s) flaked roughly one run in four
            deadline = asyncio.get_event_loop().time() + (
                20 * cluster_mod.SYNC_PERIOD_TICKS * TICK + 15.0
            )
            ok = False
            while asyncio.get_event_loop().time() < deadline:
                if await b_sees():
                    ok = True
                    break
                await asyncio.sleep(TICK)
            pin_task.cancel()
            assert ok, "capped write-hot defer never pulled the heal"
        finally:
            await a.stop()
            await b.stop()

    asyncio.run(main())


def test_write_hot_behind_node_heals_from_mid_heal_responder(monkeypatch):
    """The two caps COMBINED: a behind node that is steadily write-hot
    pulls only every 4th period (requester cap), while the responder is
    kept perpetually mid-heal — the serve-defer streak must survive
    between those widely-spaced requests (decay window > requester
    spacing) or the responder's cap never binds and the behind node is
    starved forever. Shrinks SYNC_PERIOD_TICKS so three pull cycles fit
    a fast test."""
    monkeypatch.setattr(cluster_mod, "SYNC_PERIOD_TICKS", 10)

    async def main():
        pa, pb = free_port(), free_port()
        a = Node("comba", pa)
        b = Node("combb", pb, seeds=[a.config.addr])
        try:
            await a.start()
            await b.start()

            def meshed():
                return any(
                    c.established for c in b.cluster._actives.values()
                ) and any(c.established for c in a.cluster._actives.values())

            assert await converge_wait(meshed, ticks=60)
            await asyncio.sleep(4 * TICK)  # initial sync settles

            async def pin():
                while True:
                    a.cluster._sync_rx_tick = a.cluster._tick  # mid-heal
                    b.cluster._local_writes_seen = True  # write-hot
                    await asyncio.sleep(TICK / 2)

            pin_task = asyncio.get_event_loop().create_task(pin())
            a.database.manager("GCOUNT").repo.converge(b"ghost", {44: 3})

            async def b_sees():
                out = await resp_call(
                    b.server.port,
                    b"*3\r\n$6\r\nGCOUNT\r\n$3\r\nGET\r\n$5\r\nghost\r\n",
                )
                return out == b":3\r\n"

            # B pulls every 4th (shrunk) period; A serves its 3rd pull
            # at the latest — allow double that for a loaded box
            deadline = asyncio.get_event_loop().time() + (
                24 * cluster_mod.SYNC_PERIOD_TICKS * TICK + 3.0
            )
            ok = False
            while asyncio.get_event_loop().time() < deadline:
                if await b_sees():
                    ok = True
                    break
                await asyncio.sleep(TICK)
            pin_task.cancel()
            assert ok, (
                "write-hot behind node never healed from the mid-heal "
                "responder (combined defer caps starved it)"
            )
        finally:
            await a.stop()
            await b.stop()

    asyncio.run(main())


def test_sync_streams_only_mismatched_types():
    """Per-type digests (schema v4; range-served since v8): a heal
    range-repairs ONLY the data types whose digests differ — and never
    takes the legacy whole-state dump path at all."""

    async def main():
        pa, pb = free_port(), free_port()
        a = Node("sela", pa)
        b = Node("selb", pb, seeds=[a.config.addr])
        streamed_types = []
        orig = cluster_mod.Cluster._range_frames

        def recording_frames(self, name, buckets):
            streamed_types.append(name)
            return orig(self, name, buckets)

        cluster_mod.Cluster._range_frames = recording_frames
        try:
            await a.start()
            await b.start()
            # converge both on some TREG+TLOG state via the real wire
            got = await resp_call(a.server.port, b"TREG SET t v 5\r\n")
            assert got == b"+OK\r\n"
            got = await resp_call(a.server.port, b"TLOG INS l x 3\r\n")
            assert got == b"+OK\r\n"

            async def b_has_both():
                out = await resp_call(b.server.port, b"TREG GET t\r\n")
                if not out.startswith(b"*2"):
                    return False
                out = await resp_call(b.server.port, b"TLOG SIZE l\r\n")
                return out == b":1\r\n"

            deadline = asyncio.get_event_loop().time() + 60 * TICK
            while asyncio.get_event_loop().time() < deadline:
                if await b_has_both():
                    break
                await asyncio.sleep(TICK)
            assert await b_has_both()

            # deterministic quiesce barrier: proceed only once BOTH
            # nodes' digests agree (delta traffic fully settled)
            async def digests_match():
                da = await a.database.sync_digest_async()
                db_ = await b.database.sync_digest_async()
                return da == db_

            deadline = asyncio.get_event_loop().time() + 60 * TICK
            while asyncio.get_event_loop().time() < deadline:
                if await digests_match():
                    break
                await asyncio.sleep(TICK)
            assert await digests_match(), "nodes never quiesced"
            streamed_types.clear()
            # silent GCOUNT-only divergence + forced re-establishment
            a.database.manager("GCOUNT").repo.converge(b"only", {9: 3})
            b.cluster._sync_req_tick.clear()
            for conn in list(b.cluster._actives.values()):
                b.cluster._drop(conn)

            async def healed():
                out = await resp_call(
                    b.server.port, b"GCOUNT GET only\r\n"
                )
                return out == b":3\r\n"

            deadline = asyncio.get_event_loop().time() + 120 * TICK
            while asyncio.get_event_loop().time() < deadline:
                if await healed():
                    break
                await asyncio.sleep(TICK)
            assert await healed(), "GCOUNT divergence never healed"
            assert streamed_types, "no range stream served at all"
            assert set(streamed_types) == {"GCOUNT"}, streamed_types
            # v8 acceptance: a known-shape requester NEVER takes the
            # legacy whole-state dump path
            assert a.cluster._stats["sync_full_dumps"] == 0
            assert b.cluster._stats["sync_full_dumps"] == 0
        finally:
            cluster_mod.Cluster._range_frames = orig
            await a.stop()
            await b.stop()

    asyncio.run(main())


# ---- SYSTEM DIGEST ----------------------------------------------------------


def test_system_digest_async_path_and_convergence():
    """SYSTEM DIGEST over a real RESP connection: equal on converged
    replicas, different when they diverge."""

    async def main():
        p_a, p_b = free_port(), free_port()
        a = Node("aye", p_a)
        b = Node("bee", p_b, seeds=[a.config.addr])
        await a.start()
        await b.start()
        try:
            digest_cmd = b"*2\r\n$6\r\nSYSTEM\r\n$6\r\nDIGEST\r\n"
            empty_a = await resp_call(a.server.port, digest_cmd)
            empty_b = await resp_call(b.server.port, digest_cmd)
            assert empty_a.startswith(b"$64\r\n"), empty_a
            assert empty_a == empty_b  # both empty: equal digests
            out = await resp_call(
                a.server.port,
                b"*4\r\n$6\r\nGCOUNT\r\n$3\r\nINC\r\n$1\r\nk\r\n$1\r\n2\r\n",
            )
            assert out == b"+OK\r\n"

            async def matched():
                da = await resp_call(a.server.port, digest_cmd)
                db = await resp_call(b.server.port, digest_cmd)
                return da == db and da != empty_a

            deadline = asyncio.get_event_loop().time() + 300 * TICK
            while asyncio.get_event_loop().time() < deadline:
                if await matched():
                    break
                await asyncio.sleep(TICK)
            assert await matched()
        finally:
            await b.stop()
            await a.stop()

    asyncio.run(main())


def test_system_digest_sync_path_matches_async():
    from jylis_tpu.models.database import Database

    db = Database(identity=9)
    resp = _CollectResp()
    db.apply(resp, [b"GCOUNT", b"INC", b"k", b"4"])
    resp.vals.clear()
    db.apply(resp, [b"SYSTEM", b"DIGEST"])
    assert resp.vals[0] == "string"
    sync_hex = resp.vals[1]

    async def async_digest():
        return (await db.sync_digest_async()).hex().encode()

    assert asyncio.run(async_digest()) == sync_hex
