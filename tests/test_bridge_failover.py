"""Bridge failover (PR 15): liveness-aware deterministic succession,
cross-bridge repair relay, and the single-node-region reboot fix.

Election units drive one Cluster object's evidence directly (no
sockets); the integration tests run REAL in-process regioned nodes over
loopback TCP — the same stacks the chaos drill SIGKILLs as spawned
processes (test_drill_matrix.py) and jmodel explores exhaustively
(scripts/jmodel regions3 with the bkill/breboot axis).
"""

import asyncio

import pytest

from test_cluster import TICK, Node, converge_wait, grab_ports, resp_call
from jylis_tpu.cluster.cluster import (
    BRIDGE_DEMOTE_FAILS,
    Cluster,
    _PeerState,
)
from jylis_tpu.utils.address import Address
from jylis_tpu.utils.config import Config


def _mk_cluster(region="ra", demote=4) -> Cluster:
    cfg = Config()
    cfg.addr = Address("10.0.0.2", "7001", "bee")
    cfg.region = region
    cfg.bridge_demote_ticks = demote

    class _Db:
        pass

    return Cluster(cfg, _Db())


def _know(cluster: Cluster, addr: Address, region: str) -> None:
    cluster._known_addrs.add(addr)
    cluster._fold_regions(((str(addr), region, 1),))


AYE = Address("10.0.0.1", "7001", "aye")
SEA = Address("10.0.0.3", "7001", "sea")


def test_silent_bridge_is_demoted_and_next_smallest_succeeds():
    """The tentpole rule: an address with no received frame for more
    than --bridge-demote-ticks leaves the electorate, and the
    next-smallest live address (here: self) is the bridge — no
    election traffic, just each observer's own evidence."""
    c = _mk_cluster(demote=4)
    _know(c, AYE, "ra")
    c._tick = 10
    c._seen_tick[str(AYE)] = 10
    assert c._bridge_of("ra") == str(AYE)
    assert not c._is_bridge()
    c._tick = 14  # silence exactly at the bound: still live
    assert c._bridge_of("ra") == str(AYE)
    c._tick = 15  # one past the bound: demoted
    assert c._bridge_of("ra") == str(c._addr)
    assert c._is_bridge()


def test_handover_is_counted_and_gauged():
    c = _mk_cluster(demote=4)
    _know(c, AYE, "ra")
    c._tick = 1
    c._seen_tick[str(AYE)] = 1
    c._refresh_bridge_role()  # first election: not a handover
    assert c._stats["bridge_handovers"] == 0
    assert c.metrics_totals()["bridge_is_self"] == 0
    c._tick = 6
    c._refresh_bridge_role()
    assert c._stats["bridge_handovers"] == 1
    assert c.metrics_totals()["bridge_is_self"] == 1
    assert c._reg.gauges["cluster.bridge_is_self"] == 1.0
    # the incumbent returns (fresh frame): re-elected, counted again
    c._seen_tick[str(AYE)] = 6
    c._refresh_bridge_role()
    assert c._stats["bridge_handovers"] == 2
    assert c.metrics_totals()["bridge_is_self"] == 0


def test_never_seen_candidate_is_optimistic_until_dials_fail():
    """Bootstrap: gossip teaches addresses before any contact, so a
    never-seen candidate must stay electable (v9-style optimism) —
    until the dial machine's consecutive connect failures say the
    address is dead, the only evidence available without a conn."""
    c = _mk_cluster(demote=4)
    _know(c, AYE, "ra")
    c._tick = 100  # no _seen_tick entry for aye at all
    assert c._bridge_of("ra") == str(AYE)
    st = c._peers[AYE] = _PeerState()
    st.fails = BRIDGE_DEMOTE_FAILS - 1
    assert c._bridge_of("ra") == str(AYE)
    st.fails = BRIDGE_DEMOTE_FAILS
    assert c._bridge_of("ra") == str(c._addr)


def test_all_dead_region_falls_back_to_deterministic_smallest():
    """A region whose every member looks dead keeps the v10
    deterministic answer (smallest address): the topology must stay
    computable, and a wrong-but-stable election beats none."""
    c = _mk_cluster(region="", demote=4)  # observer outside the region
    _know(c, AYE, "rb")
    _know(c, SEA, "rb")
    c._tick = 50
    c._seen_tick[str(AYE)] = 1
    c._seen_tick[str(SEA)] = 1
    assert c._bridge_of("rb") == str(AYE)


def test_relay_queue_byte_cap_drops_and_counts():
    """The cross-bridge repair queue is byte-capped: frames past the
    cap DROP (counted + traced), never buffer without bound — the
    members' periodic syncs stay the correctness backstop."""
    from jylis_tpu.cluster import cluster as cluster_mod

    c = _mk_cluster(demote=4)

    async def main():
        cap = cluster_mod.RELAY_QUEUE_BYTES_CAP
        c._queue_repair_relay("GCOUNT", (), cap - 1)
        assert c._relay_queue_bytes == cap - 1
        assert c._reg.gauges["cluster.relay_queue_bytes"] == float(cap - 1)
        c._queue_repair_relay("GCOUNT", (), 2)  # would cross the cap
        assert c._stats["relay_dropped"] == 1
        # the drain task (no established conns) empties the queue; the
        # encode hops through a worker thread, so give it wall time
        for _ in range(100):
            await asyncio.sleep(0.01)
            if c._stats["repair_relays"]:
                break
        assert c._relay_queue_bytes == 0
        assert c._reg.gauges["cluster.relay_queue_bytes"] == 0.0
        assert c._stats["repair_relays"] == 1

    asyncio.run(main())


# ---- in-process integration -------------------------------------------------


def _sparse(a: Node, b: Node, c: Node) -> bool:
    """The policy topology settled: aye holds both conns, bee and sea
    never hold one to each other, everything established."""
    return (
        len(a.cluster._actives) == 2
        and str(b.config.addr) not in {str(x) for x in c.cluster._actives}
        and str(c.config.addr) not in {str(x) for x in b.cluster._actives}
        and all(
            cn.established
            for n in (a, b, c)
            for cn in n.cluster._actives.values()
        )
    )


async def _regioned_trio(demote: int = 8):
    """r1 = {aye (bridge), bee}, r2 = {sea}; aye gets the smallest
    cluster port so it IS r1's deterministic bridge (5-digit ephemeral
    ports sort as strings)."""
    p_a, p_b, p_c = sorted(grab_ports(3))
    a = Node("aye", p_a, region="r1")
    b = Node("bee", p_b, seeds=[a.config.addr], region="r1")
    c = Node("sea", p_c, seeds=[a.config.addr], region="r2")
    for n in (a, b, c):
        n.config.bridge_demote_ticks = demote
        n.cluster._bridge_demote = demote
        await n.start()
    assert await converge_wait(lambda: _sparse(a, b, c), ticks=200)
    assert a.cluster._is_bridge() and c.cluster._is_bridge()
    assert not b.cluster._is_bridge()
    return a, b, c


async def _write_inc(node: Node, key: bytes, n: int) -> None:
    got = await resp_call(
        node.server.port,
        b"*4\r\n$6\r\nGCOUNT\r\n$3\r\nINC\r\n$%d\r\n%s\r\n$%d\r\n%d\r\n"
        % (len(key), key, len(str(n)), n),
    )
    assert got == b"+OK\r\n", got


async def _read_count(node: Node, key: bytes) -> int:
    out = await resp_call(
        node.server.port,
        b"*3\r\n$6\r\nGCOUNT\r\n$3\r\nGET\r\n$%d\r\n%s\r\n" % (len(key), key),
    )
    assert out.startswith(b":"), out
    return int(out[1:].strip())


def test_dead_bridge_fails_over_and_cross_region_converges():
    """Kill r1's bridge mid-mesh: every r1/r2 observer demotes it
    within the bound, bee succeeds deterministically, sea accepts the
    successor, and a post-failover write on bee reaches sea — with
    zero whole-state dumps anywhere (the in-process twin of the
    SIGKILL chaos cell)."""

    async def main():
        a, b, c = await _regioned_trio(demote=8)
        try:
            await _write_inc(b, b"warm", 1)

            # the relay path works before the kill
            async def seen_on_c(key, want):
                return await _read_count(c, key) == want

            ok = False
            for _ in range(400):
                if await seen_on_c(b"warm", 1):
                    ok = True
                    break
                await asyncio.sleep(TICK)
            assert ok, "relay path never converged before the kill"

            # baseline: bootstrap already counted the self -> aye
            # reclassification, so only an increase proves this kill
            h0 = b.cluster._stats["bridge_handovers"]
            await a.stop()  # the bridge dies
            kill_tick_b = b.cluster._tick

            def successor() -> bool:
                return b.cluster._is_bridge() and (
                    c.cluster._bridge_of("r1") == str(b.config.addr)
                )

            assert await converge_wait(successor, ticks=600)
            # bounded handover: bee demoted aye within the demotion
            # bound plus the announce/dial slack (ticks are cheap in
            # process)
            assert b.cluster._tick - kill_tick_b <= 8 + 30
            assert b.cluster._stats["bridge_handovers"] > h0
            # the successor carries cross-region traffic
            await _write_inc(b, b"post", 2)
            ok = False
            for _ in range(800):
                if await seen_on_c(b"post", 2):
                    ok = True
                    break
                await asyncio.sleep(TICK)
            assert ok, "post-failover write never reached the remote region"
            assert b.cluster._stats["sync_full_dumps"] == 0
            assert c.cluster._stats["sync_full_dumps"] == 0
        finally:
            for n in (b, c):
                await n.stop()

    asyncio.run(main())


def test_returning_bridge_is_reelected_and_successor_steps_down():
    """The incumbent reboots: its frames refresh everyone's evidence,
    the smallest address wins again, and the interim successor's WAN
    conns are pruned back to policy — handover is symmetric."""

    async def main():
        a, b, c = await _regioned_trio(demote=6)
        stopped = [a]
        try:
            await a.stop()
            assert await converge_wait(
                lambda: b.cluster._is_bridge(), ticks=600
            )
            # reboot aye on the same address (fresh epoch)
            a2 = Node("aye", int(a.config.addr.port), region="r1")
            a2.config.bridge_demote_ticks = 6
            a2.cluster._bridge_demote = 6
            # it re-learns the mesh from bee (bee keeps dialing its
            # intra-region peer)
            a2.config.seed_addrs = [b.config.addr]
            a2.cluster._known_addrs.add(b.config.addr)
            await a2.start()
            stopped.append(a2)

            def incumbent_back() -> bool:
                return (
                    a2.cluster._is_bridge()
                    and not b.cluster._is_bridge()
                    and c.cluster._bridge_of("r1") == str(a2.config.addr)
                )

            assert await converge_wait(incumbent_back, ticks=600)
            # the interim successor sheds its WAN conn to sea on the
            # policy pass (counted, never a peer-fault backoff)
            assert await converge_wait(
                lambda: str(c.config.addr)
                not in {str(x) for x in b.cluster._actives},
                ticks=200,
            )
        finally:
            for n in (b, c, *stopped[1:]):
                await n.stop()

    asyncio.run(main())


def test_bridge_relays_wan_repair_into_its_region():
    """Cross-bridge repair: state that reaches the bridge over the WAN
    sync ladder (digest trees + range pulls — NOT live pushes) is
    re-exported into the intra mesh through the byte-capped relay
    queue, so members converge through their bridge instead of waiting
    for their own periodic sync toward it."""

    async def main():
        a, b, c = await _regioned_trio(demote=8)
        try:
            # inject a foreign delta into sea as CONVERGED state (as if
            # from a departed node): converge never re-exports, so the
            # only way this crosses the WAN is aye's periodic digest
            # sync pulling it as range repair
            await c.database.converge_async(
                ("GCOUNT", [(b"orphan", {999: 7})])
            )
            ok = False
            for _ in range(1600):
                if await _read_count(b, b"orphan") == 7:
                    ok = True
                    break
                await asyncio.sleep(TICK)
            assert ok, "repair never reached the member through the bridge"
            assert a.cluster._stats["repair_relays"] > 0
            assert a.cluster._stats["relay_dropped"] == 0
        finally:
            for n in (a, b, c):
                await n.stop()

    asyncio.run(main())


def test_single_node_region_reboot_has_no_dial_storm():
    """The satellite fix: a region whose only member is its bridge
    used to re-enter the unknown-region dial path on reboot (region
    gossip rode only the announce cadence, so the establishment-time
    MsgExchangeAddrs taught it every address BEFORE any
    classification). Gossip now precedes the address exchange at
    establishment, so the rebooted node classifies first and dials
    only policy peers — no storm, no prunes."""

    async def main():
        p_a, p_b, p_s = sorted(grab_ports(3))
        a = Node("aye", p_a, region="r1")
        b = Node("bee", p_b, seeds=[a.config.addr], region="r1")
        s = Node("solo", p_s, seeds=[a.config.addr], region="rs")
        for n in (a, b, s):
            await n.start()
        s2 = None
        try:
            def settled() -> bool:
                return (
                    s.cluster._is_bridge()
                    and a.cluster._is_bridge()
                    and str(s.config.addr) in {
                        str(x) for x in a.cluster._actives
                    }
                )

            assert await converge_wait(settled, ticks=400)

            # reboot the single-member region's bridge
            await s.stop()
            s2 = Node("solo", p_s, seeds=[a.config.addr], region="rs")
            await s2.start()
            assert await converge_wait(
                lambda: str(a.config.addr) in {
                    str(x) for x in s2.cluster._actives
                }
                and all(
                    cn.established
                    for cn in s2.cluster._actives.values()
                ),
                ticks=400,
            )
            # let a few announce rounds pass: any storm would have fired
            for _ in range(10):
                await asyncio.sleep(TICK)
            # the rebooted node never dialed the out-of-policy member:
            # bee was classified r1 non-bridge BEFORE the policy pass
            # could dial it
            st = s2.cluster._peers.get(b.config.addr)
            assert st is None or st.dials == 0, (
                f"dial storm: rebooted solo bridge dialed bee "
                f"{st.dials} time(s)"
            )
            assert s2.cluster._stats["region_prunes"] == 0
            assert b.config.addr not in s2.cluster._actives
        finally:
            for n in (a, b, s2 or s):
                await n.stop()

    asyncio.run(main())
