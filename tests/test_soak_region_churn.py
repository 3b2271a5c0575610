"""Region-churn soak (nightly `make soak`, PR 15): bridge crash/reboot
loops over a 3-region in-process WAN topology.

Each round SIGKILL-equivalently removes the CURRENT elected bridge of
a rotating region (abrupt `dispose`, no flush — what peers see when
the process dies), lets the liveness demotion hand the role to the
next-smallest live address, pushes cross-region traffic through the
successor, then reboots the incumbent on the same address (fresh boot
epoch) and watches it re-elected. After every round the surviving mesh
must be digest-matched ACROSS regions, `sync_full_dumps` must stay
pinned at zero on every node (the heal rides the interval/range
ladder, relayed across bridges — never a whole-state dump), and after
the final round `bridge_is_self` must sum to exactly one per region.

This is the soak tier of the failover proof; the tick-exact bound is
jmodel's `bridge_demotion` invariant, and the single-kill smoke is
`test_chaos_bridge_sigkill_fails_over_within_bound`.
"""

from __future__ import annotations

import asyncio

import pytest

import jylis_tpu  # noqa: F401

from test_cluster import TICK, Node, converge_wait, grab_ports, resp_call

ROUNDS = 6
DEMOTE_TICKS = 8

# 3 regions x 2 members: every region has a live successor on tap
REGIONS = {
    "r1": ("aa", "ab"),
    "r2": ("ba", "bb"),
    "r3": ("ca", "cb"),
}


async def _inc(node: Node, key: bytes, n: int) -> None:
    got = await resp_call(
        node.server.port,
        b"*4\r\n$6\r\nGCOUNT\r\n$3\r\nINC\r\n$%d\r\n%s\r\n$%d\r\n%d\r\n"
        % (len(key), key, len(str(n)), n),
    )
    assert got == b"+OK\r\n", got


async def _get(node: Node, key: bytes) -> int:
    out = await resp_call(
        node.server.port,
        b"*3\r\n$6\r\nGCOUNT\r\n$3\r\nGET\r\n$%d\r\n%s\r\n" % (len(key), key),
    )
    assert out.startswith(b":"), out
    return int(out[1:].strip())


async def _wait_counts(nodes, key: bytes, want: int, ticks: int = 1200):
    for _ in range(ticks):
        vals = [await _get(n, key) for n in nodes]
        if all(v == want for v in vals):
            return
        await asyncio.sleep(TICK)
    raise AssertionError(f"{key!r}: {vals} != {want}")


async def _wait_digest_match(nodes, ticks: int = 2400):
    async def digest(n: Node) -> bytes:
        return await resp_call(n.server.port, b"SYSTEM DIGEST\r\n")

    for _ in range(ticks):
        ds = [await digest(n) for n in nodes]
        if len(set(ds)) == 1:
            return
        await asyncio.sleep(TICK)
    raise AssertionError(f"digest mismatch after churn: {ds}")


@pytest.mark.soak
@pytest.mark.slow  # nightly (`make soak`), not per-commit
def test_soak_region_churn_bridge_crash_reboot_loops():
    asyncio.run(_churn())


async def _churn():
    ports = sorted(grab_ports(6))
    nodes: dict[str, Node] = {}
    port_of: dict[str, int] = {}
    # region seeds: the first (smallest-port) node of each region plus
    # the global smallest — every node can bootstrap the whole map
    order = [name for members in REGIONS.values() for name in members]
    for i, name in enumerate(order):
        port_of[name] = ports[i]

    def mk(name: str, region: str) -> Node:
        seeds = []
        for r, members in REGIONS.items():
            if name not in members:
                from jylis_tpu.utils.address import Address

                seeds.append(
                    Address("127.0.0.1", str(port_of[members[0]]), members[0])
                )
            elif name != members[0]:
                from jylis_tpu.utils.address import Address

                seeds.append(
                    Address("127.0.0.1", str(port_of[members[0]]), members[0])
                )
        n = Node(name, port_of[name], seeds=seeds, region=region)
        n.cluster._bridge_demote = DEMOTE_TICKS
        return n

    region_of = {
        name: r for r, members in REGIONS.items() for name in members
    }
    for name in order:
        nodes[name] = mk(name, region_of[name])
        await nodes[name].start()
    try:
        def bridges_settled() -> bool:
            per_region = {
                r: sum(
                    1
                    for m in members
                    if m in nodes and nodes[m].cluster._is_bridge()
                )
                for r, members in REGIONS.items()
            }
            return all(v == 1 for v in per_region.values())

        assert await converge_wait(bridges_settled, ticks=600)
        total = 0
        regions_cycle = list(REGIONS)
        for rnd in range(ROUNDS):
            region = regions_cycle[rnd % len(regions_cycle)]
            members = REGIONS[region]
            victim_name = next(
                m for m in members if nodes[m].cluster._is_bridge()
            )
            survivor_name = next(m for m in members if m != victim_name)
            victim = nodes.pop(victim_name)
            vport = int(victim.config.addr.port)
            await victim.stop()  # abrupt: no flush, conns just die

            # succession within the region
            assert await converge_wait(
                lambda: nodes[survivor_name].cluster._is_bridge(),
                ticks=900,
            ), f"round {rnd}: no successor in {region}"

            # traffic through the successor reaches every region
            total += 1
            writer = nodes[survivor_name]
            await _inc(writer, b"churn", 1)
            others = [
                n for name, n in nodes.items()
                if region_of[name] != region
            ]
            await _wait_counts(others, b"churn", total)

            # reboot the incumbent on the same address (fresh epoch);
            # smallest address wins again
            reborn = mk(victim_name, region)
            await reborn.start()
            nodes[victim_name] = reborn
            assert await converge_wait(
                lambda: reborn.cluster._is_bridge()
                and not nodes[survivor_name].cluster._is_bridge(),
                ticks=900,
            ), f"round {rnd}: incumbent never re-elected in {region}"
            await _wait_counts([reborn], b"churn", total)

        # steady state: one bridge per region, cross-region digest
        # match, and not one whole-state dump anywhere
        await _wait_digest_match(list(nodes.values()))
        assert bridges_settled()
        for name, n in nodes.items():
            assert n.cluster._stats["sync_full_dumps"] == 0, name
    finally:
        for n in nodes.values():
            await n.stop()
