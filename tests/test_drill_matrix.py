"""The crash/partition drill matrix: {fault class} x {injection site}
over a real 3-node cluster.

Every registered failpoint (the committed
scripts/jlint/failpoints_manifest.json — the matrix reads it, so a seam
added to the code can never be silently missing here) is exercised
under every fault class {error, sleep, corrupt, drop, crash}, and every
cell must end in a CONVERGED, DIGEST-MATCHED 3-node cluster:

* the drill asserts the site actually FIRED (faults.hits), so a cell
  can never pass vacuously;
* post-heal writes on every node must reach every node, and the
  per-type sync digests of all three databases must be equal;
* an injected FFI fault must serve correct replies via demotion;
* reconnect attempts to a downed peer must be bounded by the dial
  backoff, not one per heartbeat tick.

The fast subset (`@pytest.mark.chaos`, seconds) runs per commit via
`make chaos` (inside `make ci`); the full matrix is nightly
(`@pytest.mark.soak`, `make soak`). In-process cells model `crash` with
a handler that fails the in-flight operation and abruptly tears the
node down (no final flush, no shutdown snapshot) before rebooting it
from disk; one spawned-process cell exercises the real
JYLIS_FAILPOINTS env arming and os._exit path end to end.
"""

import asyncio
import json
import os
import struct
import subprocess
import sys
import time

import pytest

import test_cluster
from test_cluster import TICK, Node, converge_wait, grab_ports, meshed, resp_call
from jylis_tpu import faults, persist
from jylis_tpu import journal as journal_mod

MANIFEST = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    "scripts", "jlint", "failpoints_manifest.json",
)

with open(MANIFEST, encoding="utf-8") as _f:
    SITES = sorted(json.load(_f)["failpoints"])

CLASSES = ("error", "sleep", "corrupt", "drop", "crash")

# (arg, budget) per class: budgets bound every drill so the fault heals
# by exhaustion even if the drill's explicit disarm is late; sleeps are
# short because some sync seams fire on the shared event loop
FAULT_ARGS = {
    "error": (None, 5),
    "sleep": (0.05, 5),
    "corrupt": (None, 5),
    "drop": (None, 5),
    "crash": (None, 1),
}

BOOT_SITES = {"journal.replay", "snapshot.load"}
DISK_SITES = {
    "journal.append", "journal.fsync", "journal.rotate",
    "journal.replay", "snapshot.write", "snapshot.load",
}


@pytest.fixture(autouse=True)
def _clean_faults():
    faults.reset()
    yield
    faults.reset()
    faults.set_crash_handler(None)


class DiskNode(Node):
    """A test Node with main.py's persistence wiring: snapshot restore,
    journal recover/open/attach. fsync=always for deterministic drills."""

    def __init__(self, name, cluster_port, seeds=(), data_dir=None):
        super().__init__(name, cluster_port, seeds)
        self.data_dir = data_dir
        self.journal = None
        self.snapshot_path = None
        if data_dir:
            os.makedirs(data_dir, exist_ok=True)
            self.snapshot_path = os.path.join(data_dir, "snapshot.jylis")
            if os.path.exists(self.snapshot_path):
                try:
                    persist.load_snapshot(self.database, self.snapshot_path)
                except persist.SnapshotError:
                    os.replace(
                        self.snapshot_path, self.snapshot_path + ".unreadable"
                    )
            jpath = os.path.join(data_dir, "journal.jylis")
            journal_mod.recover(self.database, jpath)
            self.journal = journal_mod.Journal(jpath, fsync="always")
            self.journal.open()
            self.database.set_journal(self.journal)

    async def stop(self):
        await super().stop()
        if self.journal is not None:
            await asyncio.to_thread(self.journal.close)

    async def crash_stop(self):
        """Abrupt teardown: no final flush, no shutdown snapshot — what
        peers and the disk see when the process dies. (The journal
        writer is joined so the file is stable for the reboot; batches
        still queued at 'death' are the documented loss window.)"""
        self.cluster.dispose()
        await self.server.dispose()
        if self.journal is not None:
            await asyncio.to_thread(self.journal.close)


async def write_inc(node, key: bytes, amount: int) -> None:
    got = await resp_call(
        node.server.port,
        b"*4\r\n$6\r\nGCOUNT\r\n$3\r\nINC\r\n$%d\r\n%s\r\n$%d\r\n%d\r\n"
        % (len(key), key, len(str(amount)), amount),
    )
    assert got == b"+OK\r\n", got


async def read_count(node, key: bytes) -> bytes:
    return await resp_call(
        node.server.port,
        b"*3\r\n$6\r\nGCOUNT\r\n$3\r\nGET\r\n$%d\r\n%s\r\n" % (len(key), key),
    )


async def wait_counts(nodes, key: bytes, total: int, ticks: int = 300) -> None:
    want = b":%d\r\n" % total
    got = {}

    async def check():
        for n in nodes:
            got[n.config.addr.name] = await read_count(n, key)
        return all(v == want for v in got.values())

    deadline = asyncio.get_event_loop().time() + ticks * TICK
    while asyncio.get_event_loop().time() < deadline:
        if await check():
            return
        await asyncio.sleep(TICK)
    assert await check(), (key, total, got)


async def wait_digests_match(nodes, ticks: int = 300) -> None:
    """The acceptance bar: every node's per-type sync digests equal."""
    last = None
    deadline = asyncio.get_event_loop().time() + ticks * TICK
    while asyncio.get_event_loop().time() < deadline:
        last = [await n.database.sync_type_digests_async() for n in nodes]
        if all(d == last[0] for d in last):
            return
        await asyncio.sleep(TICK)
    assert all(d == last[0] for d in last), last


async def wait_pred(pred, ticks: int = 200):
    deadline = asyncio.get_event_loop().time() + ticks * TICK
    while asyncio.get_event_loop().time() < deadline:
        if pred():
            return True
        await asyncio.sleep(TICK)
    return pred()


def meshed_real(nodes) -> bool:
    """Every node holds an ESTABLISHED active conn to every other REAL
    node. Deliberately not `meshed()`'s exact-count check, which is
    racy against in-flight dial placeholders while a cell is still
    healing. (Historical note: before the transport CRC, a corrupt
    injected at a cluster seam could flip a byte inside a membership
    message that still decoded, gossiping a phantom address into the
    P2Set permanently — and worse, forge counter values that converged
    digest-matched. The schema-v5 per-frame CRC, added because THIS
    matrix caught that, turns every such corruption into a detected
    drop + reconnect heal.)"""
    addrs = {n.config.addr for n in nodes}
    for n in nodes:
        for other in addrs - {n.config.addr}:
            conn = n.cluster._actives.get(other)
            if conn is None or not conn.established:
                return False
    return True


# ---- the generic drill -----------------------------------------------------


async def drill(site: str, action: str, tmp_path) -> None:
    arg, budget = FAULT_ARGS[action]
    data_dir = str(tmp_path / "bee") if site in DISK_SITES else None
    p_a, p_b, p_c = grab_ports(3)
    a = Node("aye", p_a)
    b = DiskNode("bee", p_b, seeds=[a.config.addr], data_dir=data_dir)
    c = Node("sea", p_c, seeds=[a.config.addr])
    crashed: list[str] = []

    def crash_handler(name):
        # in-process 'crash': the in-flight operation fails like the
        # real process death would make it, and the driver below tears
        # the flagged node down abruptly before rebooting it from disk
        crashed.append(name)
        raise faults.FaultError(f"failpoint {name}: injected crash")

    await a.start()
    await b.start()
    await c.start()
    nodes = [a, b, c]
    total = 0
    try:
        assert await converge_wait(lambda: meshed(a, b, c), ticks=200)
        for i, n in enumerate(nodes):
            await write_inc(n, b"drill", i + 1)
            total += i + 1
        await wait_counts(nodes, b"drill", total)

        if action == "crash":
            faults.set_crash_handler(crash_handler)
        base_hits = faults.hits(site)

        # ---- inject + trigger the seam -------------------------------------
        if site in BOOT_SITES:
            if site == "snapshot.load":
                # a valid snapshot must exist for the loader to refuse
                await asyncio.to_thread(
                    persist.save_snapshot, b.database, b.snapshot_path
                )
            # journaled state present for replay
            await asyncio.to_thread(b.journal.flush)
            await b.crash_stop()
            faults.arm(site, action, arg, budget)
            b = DiskNode("bee", p_b, seeds=[a.config.addr], data_dir=data_dir)
            await b.start()
            nodes[1] = b
        else:
            faults.arm(site, action, arg, budget)
            if site == "cluster.dial":
                # force redials on every node
                for n in nodes:
                    for conn in list(n.cluster._actives.values()):
                        n.cluster._drop(conn)
            elif site in ("cluster.sync_dump", "sync.digest", "sync.range"):
                # a fresh rejoiner's digest mismatch drives the v8 sync
                # ladder: digest trees (sync.digest), budgeted range
                # streams (sync.range), and the SYSTEM/SyncDone frames
                # that still ride the dump seam (cluster.sync_dump)
                await c.stop()
                c = Node("sea", p_c, seeds=[a.config.addr])
                await c.start()
                nodes[2] = c
            elif site == "journal.rotate":
                try:
                    await asyncio.to_thread(b.journal.rotate_begin)
                    batches = await b.database.dump_state_async()
                    await asyncio.to_thread(
                        persist.write_snapshot, batches, b.snapshot_path
                    )
                    await asyncio.to_thread(b.journal.rotate_commit)
                except OSError:
                    pass  # the injected rotation failure path
            elif site == "snapshot.write":
                try:
                    batches = await b.database.dump_state_async()
                    await asyncio.to_thread(
                        persist.write_snapshot, batches, b.snapshot_path
                    )
                except OSError:
                    pass
            elif site == "native.scan_apply":
                if b.database.native_engine is None:
                    pytest.skip("no native toolchain: FFI seam absent")
                # a pipelined burst through the native path; replies must
                # stay correct even while the fault demotes connections
                burst = (
                    b"*4\r\n$6\r\nGCOUNT\r\n$3\r\nINC\r\n$3\r\nffi\r\n$1\r\n1\r\n"
                    b"*3\r\n$6\r\nGCOUNT\r\n$3\r\nGET\r\n$3\r\nffi\r\n"
                )
                out = await resp_call(b.server.port, burst)
                assert out == b"+OK\r\n:1\r\n", out
            # cluster.read / cluster.write / cluster.decode /
            # journal.append / journal.fsync: ordinary traffic fires them
            for n in nodes:
                await write_inc(n, b"during", 2)

        # the cell is only meaningful if the seam actually fired
        fired = await wait_pred(lambda: faults.hits(site) > base_hits)
        assert fired, f"failpoint {site} never fired under {action}"

        # ---- crash: the flagged node dies abruptly, then reboots -----------
        if action == "crash":
            await wait_pred(lambda: bool(crashed), ticks=100)
            assert crashed, f"crash at {site} never flagged"
            faults.disarm(site)
            await b.crash_stop()
            b = DiskNode("bee", p_b, seeds=[a.config.addr], data_dir=data_dir)
            await b.start()
            nodes[1] = b

        # ---- heal ----------------------------------------------------------
        faults.disarm(site)
        assert await converge_wait(
            lambda: meshed_real(nodes), ticks=300
        ), {n.config.addr.name: len(n.cluster._actives) for n in nodes}
        for i, n in enumerate(nodes):
            await write_inc(n, b"heal", 10 + i)
        await wait_counts(nodes, b"heal", 10 + 11 + 12)
        await wait_counts(nodes, b"drill", total)
        await wait_digests_match(nodes)
    finally:
        faults.reset()
        faults.set_crash_handler(None)
        for n in nodes:
            try:
                await n.stop()
            except Exception:
                pass


# ---- the TENSOR drill ------------------------------------------------------


async def write_tensor(node, key: bytes, vec) -> None:
    payload = struct.pack("<%df" % len(vec), *vec)
    cmd = (
        b"*6\r\n$6\r\nTENSOR\r\n$3\r\nSET\r\n$%d\r\n%s\r\n$3\r\nMAX\r\n"
        b"$1\r\n0\r\n$%d\r\n%s\r\n" % (len(key), key, len(payload), payload)
    )
    got = await resp_call(node.server.port, cmd)
    assert got == b"+OK\r\n", got


async def read_tensor(node, key: bytes) -> bytes:
    return await resp_call(
        node.server.port,
        b"*3\r\n$6\r\nTENSOR\r\n$3\r\nGET\r\n$%d\r\n%s\r\n" % (len(key), key),
    )


async def wait_tensor(nodes, key: bytes, vec, ticks: int = 300) -> None:
    payload = struct.pack("<%df" % len(vec), *vec)
    want = (
        b"*3\r\n$3\r\nMAX\r\n$%d\r\n%s\r\n:0\r\n" % (len(payload), payload)
    )
    got = {}

    async def check():
        for n in nodes:
            got[n.config.addr.name] = await read_tensor(n, key)
        return all(v == want for v in got.values())

    deadline = asyncio.get_event_loop().time() + ticks * TICK
    while asyncio.get_event_loop().time() < deadline:
        if await check():
            return
        await asyncio.sleep(TICK)
    assert await check(), (key, vec, want, got)


async def drill_tensor(site: str, action: str, tmp_path) -> None:
    """The generic drill with TENSOR traffic: binary vector payloads
    journaled/gossiped THROUGH the injected fault, every cell ending in
    element-wise-converged reads and matched per-type digests."""
    arg, budget = FAULT_ARGS[action]
    data_dir = str(tmp_path / "bee") if site in DISK_SITES else None
    p_a, p_b, p_c = grab_ports(3)
    a = Node("aye", p_a)
    b = DiskNode("bee", p_b, seeds=[a.config.addr], data_dir=data_dir)
    c = Node("sea", p_c, seeds=[a.config.addr])
    crashed: list[str] = []

    def crash_handler(name):
        crashed.append(name)
        raise faults.FaultError(f"failpoint {name}: injected crash")

    await a.start()
    await b.start()
    await c.start()
    nodes = [a, b, c]
    try:
        assert await converge_wait(lambda: meshed(a, b, c), ticks=200)
        # seed divergence: each node contributes one coordinate's max
        for i, n in enumerate(nodes):
            vec = [0.0, 0.0, 0.0]
            vec[i] = float(10 + i)
            await write_tensor(n, b"drill", vec)
        await wait_tensor(nodes, b"drill", [10.0, 11.0, 12.0])

        if action == "crash":
            faults.set_crash_handler(crash_handler)
        base_hits = faults.hits(site)
        faults.arm(site, action, arg, budget)
        # tensor traffic riding THROUGH the armed seam
        for i, n in enumerate(nodes):
            await write_tensor(n, b"during", [float(i + 1), 0.5])
        fired = await wait_pred(lambda: faults.hits(site) > base_hits)
        assert fired, f"failpoint {site} never fired under {action}"

        if action == "crash":
            await wait_pred(lambda: bool(crashed), ticks=100)
            assert crashed, f"crash at {site} never flagged"
            faults.disarm(site)
            await b.crash_stop()
            b = DiskNode("bee", p_b, seeds=[a.config.addr], data_dir=data_dir)
            await b.start()
            nodes[1] = b

        faults.disarm(site)
        assert await converge_wait(
            lambda: meshed_real(nodes), ticks=300
        ), {n.config.addr.name: len(n.cluster._actives) for n in nodes}
        await wait_tensor(nodes, b"during", [3.0, 0.5])
        for i, n in enumerate(nodes):
            await write_tensor(n, b"heal", [float(20 + i)])
        await wait_tensor(nodes, b"heal", [22.0])
        await wait_tensor(nodes, b"drill", [10.0, 11.0, 12.0])
        await wait_digests_match(nodes)
    finally:
        faults.reset()
        faults.set_crash_handler(None)
        for n in nodes:
            try:
                await n.stop()
            except Exception:
                pass


# ---- the composed-types drill (MAP + BCOUNT, schema v9) --------------------


def _resp_array(*args: bytes) -> bytes:
    out = b"*%d\r\n" % len(args)
    for a in args:
        out += b"$%d\r\n%s\r\n" % (len(a), a)
    return out


async def compose_cmd(node, *args: bytes) -> bytes:
    return await resp_call(node.server.port, _resp_array(*args))


async def wait_reply(nodes, args: tuple, want: bytes, ticks: int = 300):
    got = {}

    async def check():
        for n in nodes:
            got[n.config.addr.name] = await compose_cmd(n, *args)
        return all(v == want for v in got.values())

    deadline = asyncio.get_event_loop().time() + ticks * TICK
    while asyncio.get_event_loop().time() < deadline:
        if await check():
            return
        await asyncio.sleep(TICK)
    assert await check(), (args, want, got)


async def drill_compose(site: str, action: str, tmp_path) -> None:
    """The generic drill with MAP + BCOUNT traffic: recursive field
    units and full-escrow views journaled/gossiped THROUGH the injected
    fault, every cell ending with converged composed reads, the escrow
    invariant intact, and matched per-type digests (which now include
    MAP and BCOUNT via the registry)."""
    arg, budget = FAULT_ARGS[action]
    data_dir = str(tmp_path / "bee") if site in DISK_SITES else None
    p_a, p_b, p_c = grab_ports(3)
    a = Node("aye", p_a)
    b = DiskNode("bee", p_b, seeds=[a.config.addr], data_dir=data_dir)
    c = Node("sea", p_c, seeds=[a.config.addr])
    crashed: list[str] = []

    def crash_handler(name):
        crashed.append(name)
        raise faults.FaultError(f"failpoint {name}: injected crash")

    await a.start()
    await b.start()
    await c.start()
    nodes = [a, b, c]
    try:
        assert await converge_wait(lambda: meshed(a, b, c), ticks=200)
        # seed: every node owns one MAP field; a grants + fills escrow
        for i, n in enumerate(nodes):
            got = await compose_cmd(
                n, b"MAP", b"GCOUNT", b"SET", b"drill", b"f%d" % i,
                b"%d" % (i + 1),
            )
            assert got == b"+OK\r\n", got
        assert await compose_cmd(
            a, b"BCOUNT", b"GRANT", b"inv", b"10") == b"+OK\r\n"
        assert await compose_cmd(
            a, b"BCOUNT", b"INC", b"inv", b"10") == b"+OK\r\n"
        for i in range(3):
            await wait_reply(
                nodes, (b"MAP", b"GCOUNT", b"GET", b"drill", b"f%d" % i),
                b":%d\r\n" % (i + 1),
            )
        await wait_reply(nodes, (b"BCOUNT", b"GET", b"inv"),
                         b"*2\r\n:10\r\n:10\r\n")

        if action == "crash":
            faults.set_crash_handler(crash_handler)
        base_hits = faults.hits(site)
        faults.arm(site, action, arg, budget)
        # composed traffic riding THROUGH the armed seam: field edits, a
        # field removal, and escrow spends (a's own rights fund them)
        for i, n in enumerate(nodes):
            await compose_cmd(n, b"MAP", b"GCOUNT", b"SET", b"drill",
                              b"f%d" % i, b"10")
        await compose_cmd(a, b"MAP", b"GCOUNT", b"SET", b"drill", b"gone",
                          b"1")
        await compose_cmd(a, b"MAP", b"GCOUNT", b"DEL", b"drill", b"gone")
        await compose_cmd(a, b"BCOUNT", b"DEC", b"inv", b"4")
        fired = await wait_pred(lambda: faults.hits(site) > base_hits)
        assert fired, f"failpoint {site} never fired under {action}"

        if action == "crash":
            await wait_pred(lambda: bool(crashed), ticks=100)
            assert crashed, f"crash at {site} never flagged"
            faults.disarm(site)
            await b.crash_stop()
            b = DiskNode("bee", p_b, seeds=[a.config.addr], data_dir=data_dir)
            await b.start()
            nodes[1] = b

        faults.disarm(site)
        assert await converge_wait(
            lambda: meshed_real(nodes), ticks=300
        ), {n.config.addr.name: len(n.cluster._actives) for n in nodes}
        for i in range(3):
            await wait_reply(
                nodes, (b"MAP", b"GCOUNT", b"GET", b"drill", b"f%d" % i),
                b":%d\r\n" % (i + 11),
            )
        # the tombstoned field stays dead everywhere; escrow arithmetic
        # survived the fault with the invariant intact
        await wait_reply(nodes, (b"MAP", b"GCOUNT", b"GET", b"drill",
                                 b"gone"), b"$-1\r\n")
        await wait_reply(nodes, (b"BCOUNT", b"GET", b"inv"),
                         b"*2\r\n:6\r\n:10\r\n")
        await wait_digests_match(nodes)
    finally:
        faults.reset()
        faults.set_crash_handler(None)
        for n in nodes:
            try:
                await n.stop()
            except Exception:
                pass


# ---- per-commit chaos smoke (make chaos: seconds, not minutes) -------------

SMOKE_CELLS = [
    ("cluster.dial", "error"),
    ("cluster.write", "drop"),
    ("cluster.decode", "corrupt"),
    ("journal.fsync", "error"),
]

# partition-heal cells over the v8 sync seams (anti-entropy v2): each
# cell kills/rejoins a node so the heal walks the range ladder THROUGH
# the armed seam, asserts the seam FIRED, that the heal was RANGE
# repair and not a whole-state dump, and (via the generic drill's
# tail) that every node ends digest-matched
SYNC_CELLS = [
    ("sync.digest", "drop"),
    ("sync.digest", "error"),
    ("sync.range", "drop"),
    ("sync.range", "error"),
]

# TENSOR action cells: {error, corrupt, crash} x one journal + one
# cluster seam each — non-scalar binary payloads through the fault
# classes most likely to mangle them (a corrupt cluster.write exercises
# the CRC drop; a corrupt journal.append exercises boot-replay refusal;
# crash reboots the disk node mid-tensor-traffic)
TENSOR_CELLS = [
    ("journal.append", "error"),
    ("cluster.write", "error"),
    ("journal.append", "corrupt"),
    ("cluster.write", "corrupt"),
    ("journal.append", "crash"),
    ("cluster.write", "crash"),
]


@pytest.mark.chaos
@pytest.mark.parametrize("site,action", SMOKE_CELLS)
def test_chaos_smoke_cell(site, action, tmp_path):
    asyncio.run(drill(site, action, tmp_path))


async def _drill_sync_cell(site, action, tmp_path):
    """The generic drill plus the v8 partition-heal assertions: the
    rejoin that fired the seam must have healed through the range tier
    (ranges served, digest trees exchanged) with ZERO legacy whole-state
    dumps anywhere."""
    await drill(site, action, tmp_path)
    # drill() tears its nodes down; the ladder assertions ride a fresh
    # 3-node rejoin with the seam disarmed (post-heal behaviour)
    p_a, p_b, p_c = grab_ports(3)
    a = Node("aye", p_a)
    b = Node("bee", p_b, seeds=[a.config.addr])
    c = Node("sea", p_c, seeds=[a.config.addr])
    await a.start()
    await b.start()
    await c.start()
    nodes = [a, b, c]
    try:
        assert await converge_wait(lambda: meshed(a, b, c), ticks=200)
        for i, n in enumerate(nodes):
            await write_inc(n, b"cell", i + 1)
        await wait_counts(nodes, b"cell", 6)
        await c.stop()
        c = Node("sea", p_c, seeds=[a.config.addr])
        await c.start()
        nodes[2] = c
        await wait_counts(nodes, b"cell", 6)
        await wait_digests_match(nodes)
        served = sum(n.cluster._stats["ranges_served"] for n in nodes)
        trees = sum(n.cluster._stats["sync_trees_sent"] for n in nodes)
        dumps = sum(n.cluster._stats["sync_full_dumps"] for n in nodes)
        assert trees > 0, "rejoin never exchanged a digest tree"
        assert served > 0, "rejoin never range-repaired"
        assert dumps == 0, f"legacy whole-state dump fired {dumps}x"
    finally:
        for n in nodes:
            try:
                await n.stop()
            except Exception:
                pass


@pytest.mark.chaos
@pytest.mark.parametrize("site,action", SYNC_CELLS)
def test_chaos_sync_cell(site, action, tmp_path):
    asyncio.run(_drill_sync_cell(site, action, tmp_path))


@pytest.mark.chaos
@pytest.mark.parametrize("site,action", TENSOR_CELLS)
def test_chaos_tensor_cell(site, action, tmp_path):
    asyncio.run(drill_tensor(site, action, tmp_path))


# composed-type action cells (schema v9): the same {error, corrupt,
# crash} x {journal.append, cluster.write} grid TENSOR rides, but with
# recursive MAP field units (tombstones included) and BCOUNT escrow
# views through the fault — a corrupt cluster.write exercises the CRC
# drop on a nested unit, a corrupt journal.append the boot-replay
# refusal, crash the disk node's mid-traffic reboot with escrow replay
COMPOSE_CELLS = [
    ("journal.append", "error"),
    ("cluster.write", "error"),
    ("journal.append", "corrupt"),
    ("cluster.write", "corrupt"),
    ("journal.append", "crash"),
    ("cluster.write", "crash"),
]


@pytest.mark.chaos
@pytest.mark.parametrize("site,action", COMPOSE_CELLS)
def test_chaos_compose_cell(site, action, tmp_path):
    asyncio.run(drill_compose(site, action, tmp_path))


@pytest.mark.chaos
def test_chaos_ffi_fault_demotes_and_serves_correctly():
    """An injected failure at the FFI burst boundary must demote the
    connection to the Python oracle path — correct replies, counted
    demotion — never kill the connection."""

    async def main():
        (port,) = grab_ports(1)
        node = Node("solo", port)
        await node.start()
        try:
            if node.database.native_engine is None:
                pytest.skip("no native toolchain: FFI seam absent")
            # demotions count in the serving Database's own registry
            before = node.database.metrics.serving_counters["demotions"]
            h0 = faults.hits("native.scan_apply")
            expected_total = 0
            # a transiently-busy engine (a threaded drain holding a repo
            # lock at burst time) routes commands down the Python path
            # WITHOUT touching the FFI seam — replies stay correct, the
            # failpoint just isn't reached; retry on a fresh connection
            # until the burst actually met the seam
            for attempt in range(10):
                faults.arm("native.scan_apply", "error", budget=1)
                burst = b"".join(
                    b"*4\r\n$6\r\nGCOUNT\r\n$3\r\nINC\r\n$1\r\nk\r\n$1\r\n2\r\n"
                    for _ in range(3)
                ) + b"*3\r\n$6\r\nGCOUNT\r\n$3\r\nGET\r\n$1\r\nk\r\n"
                reader, writer = await asyncio.open_connection(
                    "127.0.0.1", node.server.port
                )
                writer.write(burst)
                await writer.drain()
                got = b""
                while got.count(b"\r\n") < 4:
                    chunk = await asyncio.wait_for(
                        reader.read(1 << 16), timeout=5.0
                    )
                    if not chunk:
                        break
                    got += chunk
                expected_total += 6
                assert got == b"+OK\r\n+OK\r\n+OK\r\n:%d\r\n" % expected_total, got
                if faults.hits("native.scan_apply") > h0:
                    break
                writer.close()
                await asyncio.sleep(0.1)
            assert faults.hits("native.scan_apply") == h0 + 1
            assert (
                node.database.metrics.serving_counters["demotions"]
                == before + 1
            )
            # the demoted connection keeps serving correctly
            writer.write(b"*3\r\n$6\r\nGCOUNT\r\n$3\r\nGET\r\n$1\r\nk\r\n")
            await writer.drain()
            assert await asyncio.wait_for(
                reader.read(1 << 10), timeout=5.0
            ) == b":%d\r\n" % expected_total
            writer.close()
        finally:
            await node.stop()

    asyncio.run(main())


@pytest.mark.chaos
def test_chaos_ffi_sleep_delays_one_connection_not_the_loop():
    """Regression (jlint v2 interprocedural JL101): the FFI burst
    failpoint used the SYNC `faults.point`, so an armed
    `native.scan_apply=sleep:X` parked the whole event loop —
    heartbeats, Pongs, and every other connection — turning a
    slow-burst drill into a node-wide freeze that idle-evicts our
    peer connections. It is now the async point: the injected sleep
    delays THIS connection's burst while the loop keeps running."""

    async def main():
        (port,) = grab_ports(1)
        node = Node("solo", port)
        await node.start()
        try:
            if node.database.native_engine is None:
                pytest.skip("no native toolchain: FFI seam absent")
            h0 = faults.hits("native.scan_apply")
            gaps: list[float] = []

            async def ticker():
                loop = asyncio.get_running_loop()
                last = loop.time()
                while True:
                    await asyncio.sleep(0.01)
                    now = loop.time()
                    gaps.append(now - last)
                    last = now

            t = asyncio.ensure_future(ticker())
            # retry past transient engine busy-ness (a threaded drain at
            # burst time routes down the Python path without reaching
            # the FFI seam) — same discipline as the demotion drill
            took = 0.0
            for attempt in range(10):
                faults.arm("native.scan_apply", "sleep", arg=0.4, budget=1)
                reader, writer = await asyncio.open_connection(
                    "127.0.0.1", node.server.port
                )
                t0 = asyncio.get_running_loop().time()
                writer.write(
                    b"*4\r\n$6\r\nGCOUNT\r\n$3\r\nINC\r\n$1\r\nk\r\n$1\r\n2\r\n"
                )
                await writer.drain()
                got = await asyncio.wait_for(reader.read(1 << 10), timeout=5.0)
                took = asyncio.get_running_loop().time() - t0
                assert got == b"+OK\r\n", got
                if faults.hits("native.scan_apply") > h0:
                    break
                writer.close()
                await asyncio.sleep(0.1)
            t.cancel()
            assert faults.hits("native.scan_apply") == h0 + 1
            # the injected sleep DID delay this burst...
            assert took >= 0.35, took
            # ...but the loop kept ticking through it (the sync point
            # produced one >=0.4 s gap here)
            assert gaps and max(gaps) < 0.2, max(gaps)
            writer.close()
        finally:
            faults.disarm("native.scan_apply")
            await node.stop()

    asyncio.run(main())


@pytest.mark.chaos
def test_chaos_reconnect_rate_bounded_by_backoff():
    """A downed peer is re-dialed at the backoff schedule, not once per
    heartbeat: over N ticks the attempt count must be O(log N + N/cap),
    where the old redial-every-tick loop produced ~N."""

    async def main():
        p_a, p_dead = grab_ports(2)
        from jylis_tpu.utils.address import Address

        dead_addr = Address("127.0.0.1", str(p_dead), "dead")
        a = Node("aye", p_a, seeds=[dead_addr])
        await a.start()
        try:
            # wait for 40 HEARTBEATS, not 40*TICK of wall time: on a
            # loaded host ticks stretch past TICK and a fixed sleep
            # observes too few of them for the lower dial bound
            n_ticks = 40
            assert await wait_pred(
                lambda: a.cluster._tick >= n_ticks, ticks=20 * n_ticks
            ), a.cluster._tick
            st = a.cluster._peers.get(dead_addr)
            assert st is not None
            # backoff 1,2,4,8,16,32(+jitter): ~6-8 attempts in 40 ticks
            assert 2 <= st.dials <= 12, st.dials
            m = a.cluster.metrics_totals()
            assert m["dial_fails"] >= st.dials - 1
            assert m["peers_backoff"] == 1
        finally:
            await a.stop()

    asyncio.run(main())


@pytest.mark.chaos
def test_chaos_incompatible_peer_backs_off_like_dial_failure():
    """A peer that ACCEPTS the TCP connect but then misbehaves (wrong
    schema signature — e.g. the other side of a rolling upgrade across
    a schema bump) must engage the dial backoff, not be re-dialed with
    a fresh connect + handshake + teardown every single heartbeat."""

    async def main():
        from jylis_tpu.cluster.cluster import wire_frame
        from jylis_tpu.cluster.framing import frame
        from jylis_tpu.utils.address import Address

        async def bad_peer(reader, writer):
            # answers the dial with a wrong-signature handshake
            writer.write(wire_frame(b"x" * 32))
            try:
                await writer.drain()
                await reader.read(1 << 16)
            except (ConnectionError, asyncio.CancelledError):
                pass
            finally:
                writer.close()

        server = await asyncio.start_server(bad_peer, "127.0.0.1", 0)
        bad_port = server.sockets[0].getsockname()[1]
        bad_addr = Address("127.0.0.1", str(bad_port), "oldversion")
        (p_a,) = grab_ports(1)
        a = Node("aye", p_a, seeds=[bad_addr])
        await a.start()
        try:
            n_ticks = 40
            await asyncio.sleep(n_ticks * TICK)
            st = a.cluster._peers.get(bad_addr)
            assert st is not None and st.dials >= 1
            # per-tick redial would reach ~40 attempts; backoff bounds it
            assert st.dials <= 12, st.dials
            assert a.cluster._drop_counts.get("handshake_mismatch", 0) >= 1
        finally:
            await a.stop()
            server.close()
            await server.wait_closed()

    asyncio.run(main())


@pytest.mark.chaos
def test_chaos_inbound_contact_resets_backoff():
    """A peer deep in backoff is re-dialed immediately once IT dials us
    (the v5 handshake identifies the dialer), so a rebooted node
    re-meshes in ~one tick instead of waiting out the cap."""

    async def main():
        p_a, p_b = grab_ports(2)
        from jylis_tpu.utils.address import Address

        b_addr = Address("127.0.0.1", str(p_b), "bee")
        a = Node("aye", p_a, seeds=[b_addr])
        await a.start()
        try:
            # let dials fail, then pin the peer deep into backoff
            assert await wait_pred(
                lambda: (a.cluster._peers.get(b_addr) or None) is not None
                and a.cluster._peers[b_addr].fails >= 2
            )
            st = a.cluster._peers[b_addr]
            st.next_dial_tick = a.cluster._tick + 10_000  # deep backoff
            b = Node("bee", p_b, seeds=[a.config.addr])
            await b.start()
            try:
                # b dials a; the handshake identity resets a's backoff
                assert await wait_pred(lambda: st.next_dial_tick <= a.cluster._tick)
                assert await converge_wait(lambda: meshed(a, b), ticks=100)
            finally:
                await b.stop()
        finally:
            await a.stop()

    asyncio.run(main())


@pytest.mark.chaos
def test_chaos_dial_timeout_bounds_blackholed_connect():
    """A blackholed connect (the OS would let it hang for minutes) is
    abandoned at --dial-timeout and enters backoff like any failure."""

    async def main():
        p_a, p_dead = grab_ports(2)
        from jylis_tpu.utils.address import Address

        dead_addr = Address("127.0.0.1", str(p_dead), "dead")
        a = Node("aye", p_a, seeds=[dead_addr])
        a.cluster._dial_timeout = 0.2
        faults.arm("cluster.dial", "sleep", 30.0, budget=1)
        await a.start()
        try:
            t0 = time.monotonic()
            assert await wait_pred(lambda: faults.hits("cluster.dial") >= 1)
            assert await wait_pred(
                lambda: a.cluster.metrics_totals()["dial_fails"] >= 1
            )
            # the 30 s injected hang was cut off by the 0.2 s timeout
            assert time.monotonic() - t0 < 10.0
        finally:
            await a.stop()

    asyncio.run(main())


@pytest.mark.chaos
def test_chaos_cluster_metrics_surface():
    """SYSTEM METRICS emits the CLUSTER section with the documented
    keys, queryable over a real RESP connection."""

    async def main():
        p_a, p_b = grab_ports(2)
        a = Node("aye", p_a)
        b = Node("bee", p_b, seeds=[a.config.addr])
        await a.start()
        await b.start()
        try:
            assert await converge_wait(lambda: meshed(a, b), ticks=200)
            out = await resp_call(
                a.server.port, b"*2\r\n$6\r\nSYSTEM\r\n$7\r\nMETRICS\r\n"
            )
            for key in (
                b"CLUSTER peers_known", b"CLUSTER peers_established",
                b"CLUSTER peers_backoff", b"CLUSTER dials",
                b"CLUSTER dial_fails", b"CLUSTER evictions",
                b"CLUSTER sync_served", b"CLUSTER sync_deferred",
                b"CLUSTER held_now", b"CLUSTER held_drops",
            ):
                assert key in out, (key, out)
            assert b"CLUSTER peers_established 1" in out
        finally:
            await b.stop()
            await a.stop()

    asyncio.run(main())


# ---- the full matrix (nightly) ---------------------------------------------


@pytest.mark.soak
@pytest.mark.slow  # nightly (`make soak`), not per-commit
@pytest.mark.parametrize("action", CLASSES)
@pytest.mark.parametrize("site", SITES)
def test_drill_matrix_cell(site, action, tmp_path):
    if (site, action) in SMOKE_CELLS:
        pytest.skip("covered per-commit by the chaos smoke")
    asyncio.run(drill(site, action, tmp_path))


@pytest.mark.soak
@pytest.mark.slow  # nightly (`make soak`), not per-commit
def test_spawned_env_crash_drill(tmp_path):
    """The real thing, end to end: a spawned node armed via the
    JYLIS_FAILPOINTS env var dies by os._exit at the injected site, and
    a clean respawn recovers from its journal and keeps serving."""
    from procutil import SPAWN_CPU, connect_client, free_port, spawn_node, stop_node

    data_dir = str(tmp_path / "crashnode")
    port, cport = free_port(), free_port()
    env = dict(os.environ, JYLIS_FAILPOINTS="journal.fsync=crash:1")
    args = [
        sys.executable, "-c", SPAWN_CPU,
        "--port", str(port), "--addr", f"127.0.0.1:{cport}:crashy",
        "--log-level", "warn", "--data-dir", data_dir,
        "--journal-fsync", "always",
    ]
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    proc = subprocess.Popen(args, cwd=repo, env=env)
    acked = 0
    try:
        client = connect_client(port, proc=proc)
        # the first journaled append fsyncs (always) and the armed
        # failpoint kills the process mid-serving
        deadline = time.time() + 120
        while proc.poll() is None and time.time() < deadline:
            try:
                client.execute_command("GCOUNT", "INC", "k", "1")
                acked += 1
            except (OSError, EOFError, RuntimeError, ValueError):
                break
            time.sleep(0.02)
        proc.wait(timeout=120)
        assert proc.returncode == faults.CRASH_EXIT_CODE, proc.returncode
        assert acked > 0
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.wait(timeout=10)

    # clean respawn: journal replay restores what the writer persisted
    proc2 = spawn_node(port, cport, "crashy", "--data-dir", data_dir)
    try:
        client = connect_client(port, proc=proc2)
        got = int(client.execute_command("GCOUNT", "GET", "k"))
        # no phantom data, and the node serves post-crash writes
        assert 0 <= got <= acked
        client.execute_command("GCOUNT", "INC", "k", "5")
        assert int(client.execute_command("GCOUNT", "GET", "k")) == got + 5
    finally:
        stop_node(proc2)


# ---- sessions & regions drills (schema v10) ---------------------------------


@pytest.mark.chaos
def test_chaos_inter_region_partition_then_heal_digest_matched():
    """Region topology under an injected WAN partition: the cluster
    prunes to the sparse policy mesh (intra full, one bridge pair),
    writes made while the relay seam is dropping frames diverge the
    remote region, and the heal (budget exhausted) ends with all three
    nodes digest-matched — the region machinery degrades to the
    periodic digest sync, never to silence."""

    async def main():
        ports = sorted(grab_ports(3))
        # the smallest address string is the deterministic bridge;
        # ephemeral ports are all 5 digits, so sorted ports sort as
        # strings too — aye gets the smallest and IS region r1's bridge
        p_a, p_b, p_c = ports
        a = Node("aye", p_a, region="r1")
        b = Node("bee", p_b, seeds=[a.config.addr], region="r1")
        c = Node("sea", p_c, seeds=[a.config.addr], region="r2")
        await a.start()
        await b.start()
        await c.start()
        nodes = [a, b, c]
        try:
            # the policy topology: bee and sea never hold a direct conn
            def sparse() -> bool:
                return (
                    len(a.cluster._actives) == 2
                    and str(b.config.addr) not in {
                        str(x) for x in c.cluster._actives
                    }
                    and str(c.config.addr) not in {
                        str(x) for x in b.cluster._actives
                    }
                    and all(
                        cn.established
                        for n in nodes
                        for cn in n.cluster._actives.values()
                    )
                )

            assert await converge_wait(sparse, ticks=200)
            assert a.cluster._is_bridge() and c.cluster._is_bridge()
            assert not b.cluster._is_bridge()

            # baseline: a bee write transits aye's relay into r2
            await write_inc(b, b"wan", 2)
            await wait_counts(nodes, b"wan", 2)
            assert a.cluster._stats["relays_sent"] > 0

            # inter-region partition: the relay seam drops every frame
            # for a bounded window; writes made under it diverge sea
            h0 = faults.hits("cluster.relay")
            faults.arm("cluster.relay", "drop", budget=4)
            try:
                await write_inc(b, b"wan", 3)
                await wait_counts([a, b], b"wan", 5)
            finally:
                faults.disarm("cluster.relay")
            assert faults.hits("cluster.relay") > h0, "fault never fired"

            # heal: the periodic digest sync (range tier) repairs r2 —
            # every node digest-matched, zero legacy dumps anywhere
            await wait_counts(nodes, b"wan", 5)
            await wait_digests_match(nodes)
            assert sum(
                n.cluster._stats["sync_full_dumps"] for n in nodes
            ) == 0
        finally:
            for n in nodes:
                await n.stop()

    asyncio.run(main())


@pytest.mark.chaos
def test_chaos_admission_cap_degrades_one_class_not_the_node():
    """Admission control under a wedged drain: with --admission-cap
    armed, commands of the backed-up class get the typed BUSY refusal
    (counted in SYSTEM METRICS), other classes keep serving, and the
    class recovers the moment the drain releases."""

    async def main():
        (port,) = grab_ports(1)
        node = Node("solo", port)
        node.database.set_admission_cap(1)
        await node.start()
        try:
            mgr = node.database.manager("GCOUNT")
            async with mgr._lock:  # the wedged-drain stand-in
                q1 = asyncio.ensure_future(
                    resp_call(node.server.port, b"GCOUNT INC h 1\r\n")
                )
                await asyncio.sleep(0.1)  # q1 queues: inflight = 1
                out = await resp_call(node.server.port, b"GCOUNT INC h 1\r\n")
                assert out.startswith(b"-BUSY"), out
                # one hot class never takes the node down with it
                ok = await resp_call(node.server.port, b"PNCOUNT GET ok\r\n")
                assert ok.startswith(b":"), ok
            assert (await q1).startswith(b"+OK"), "queued write must serve"
            out = await resp_call(node.server.port, b"GCOUNT GET h\r\n")
            assert out == b":1\r\n", out
            metrics = await resp_call(node.server.port, b"SYSTEM METRICS\r\n")
            assert b"SERVING busy_refusals 1" in metrics, metrics
        finally:
            await node.stop()

    asyncio.run(main())


def _metric(client, section: bytes, key: bytes) -> int | None:
    """One `SECTION key value` line from SYSTEM METRICS, or None."""
    want = section + b" " + key + b" "
    for line in client.execute_command("SYSTEM", "METRICS"):
        if line.startswith(want):
            return int(line[len(want):])
    return None


@pytest.mark.chaos
def test_chaos_bridge_sigkill_fails_over_within_bound():
    """Bridge failover, the real thing (PR 15): SIGKILL the elected
    bridge of a 2-region/3-process cluster MID-TRAFFIC. The successor
    (the region's next-smallest address) must observe the demotion and
    take over within the demotion bound, post-failover writes must
    cross regions through it, the survivors' SYSTEM DIGESTs must
    match, and sync_full_dumps stays pinned at zero — the heal rides
    the interval/range ladder, never a whole-state dump."""
    import signal as _signal

    from procutil import connect_client, free_port, spawn_node, stop_node

    hb = 0.2
    demote = 8
    ports = [free_port() for _ in range(3)]
    cports = sorted(free_port() for _ in range(3))
    # smallest cluster address = deterministic bridge: give it to aye
    seed = f"127.0.0.1:{cports[0]}:aye"
    extra = [
        "--heartbeat-time", str(hb), "--bridge-demote-ticks", str(demote),
    ]
    pa = spawn_node(ports[0], cports[0], "aye", "--region", "r1", *extra)
    pb = spawn_node(
        ports[1], cports[1], "bee", "--region", "r1",
        "--seed-addrs", seed, *extra,
    )
    pc = spawn_node(
        ports[2], cports[2], "sea", "--region", "r2",
        "--seed-addrs", seed, *extra,
    )
    procs = [pa, pb, pc]
    try:
        ca = connect_client(ports[0], proc=pa)
        cb = connect_client(ports[1], proc=pb)
        cc = connect_client(ports[2], proc=pc)

        # topology settled: aye and sea are bridges, bee is not, and
        # the member -> bridge -> relay -> remote path works
        deadline = time.time() + 120
        while time.time() < deadline:
            if (
                _metric(ca, b"CLUSTER", b"bridge_is_self") == 1
                and _metric(cc, b"CLUSTER", b"bridge_is_self") == 1
                and _metric(cb, b"CLUSTER", b"bridge_is_self") == 0
            ):
                break
            time.sleep(0.1)
        else:
            raise AssertionError("regions never settled to sparse policy")
        cb.execute_command("GCOUNT", "INC", "warm", "1")
        while cc.execute_command("GCOUNT", "GET", "warm") != 1:
            assert time.time() < deadline, "relay path never converged"
            time.sleep(0.05)

        # mid-traffic kill: writes in flight on the member while the
        # bridge dies. Baseline the handover counter FIRST: bootstrap
        # already counted one reclassification (self -> real bridge,
        # before the region map converged), so only an INCREASE proves
        # the failover
        h0 = _metric(cb, b"CLUSTER", b"bridge_handovers")
        for i in range(5):
            cb.execute_command("GCOUNT", "INC", "traffic", "1")
        t_kill = time.time()
        os.kill(pa.pid, _signal.SIGKILL)
        pa.wait(timeout=30)
        for i in range(5):
            cb.execute_command("GCOUNT", "INC", "traffic", "1")

        # successor observed within the demotion bound (plus generous
        # scheduling slack: heartbeat ticks stretch on loaded hosts —
        # the tight tick-level bound is the in-process test's and the
        # model's)
        bound_s = demote * hb + 10.0
        while _metric(cb, b"CLUSTER", b"bridge_is_self") != 1:
            assert time.time() - t_kill < bound_s, (
                f"no successor within {bound_s:.1f}s of SIGKILL"
            )
            time.sleep(0.1)
        assert _metric(cb, b"CLUSTER", b"bridge_handovers") > h0

        # cross-region convergence resumes through the successor
        cb.execute_command("GCOUNT", "INC", "post", "2")
        while cc.execute_command("GCOUNT", "GET", "post") != 2:
            assert time.time() < deadline, "post-failover write stranded"
            time.sleep(0.05)
        while cc.execute_command("GCOUNT", "GET", "traffic") != 10:
            assert time.time() < deadline, "mid-kill traffic never healed"
            time.sleep(0.05)

        # survivors digest-match, and the heal never fell back to a
        # whole-state dump
        while True:
            da = cb.execute_command("SYSTEM", "DIGEST")
            dc = cc.execute_command("SYSTEM", "DIGEST")
            if da == dc:
                break
            assert time.time() < deadline, (da, dc)
            time.sleep(0.1)
        assert _metric(cb, b"CLUSTER", b"sync_full_dumps") == 0
        assert _metric(cc, b"CLUSTER", b"sync_full_dumps") == 0
    finally:
        for p in procs:
            if p.poll() is None:
                stop_node(p)


@pytest.mark.chaos
def test_chaos_overload_plus_bridge_sigkill_protected_class_serves():
    """This PR's drill cell: a member under FORCED full shedding
    (``admission.shed=error`` failpoint, unbounded — every sheddable
    class refused, the sustained-overload regime without needing to
    saturate the box) while the region's bridge is SIGKILLed
    mid-traffic. The armor contract under compound failure: wrapped
    writes get typed BUSY refusals the whole time (never an accept the
    node can't honor), the protected control plane answers SYSTEM
    METRICS throughout — including during the failover window — raw
    native-path writes (which bypass the Python dispatch gate by
    design) keep serving and heal cross-region through the successor,
    the survivors digest-match, and sync_full_dumps stays zero."""
    import signal as _signal

    from procutil import connect_client, free_port, spawn_node, stop_node

    hb = 0.2
    demote = 8
    ports = [free_port() for _ in range(3)]
    cports = sorted(free_port() for _ in range(3))
    seed = f"127.0.0.1:{cports[0]}:aye"
    extra = [
        "--heartbeat-time", str(hb), "--bridge-demote-ticks", str(demote),
    ]
    pa = spawn_node(ports[0], cports[0], "aye", "--region", "r1", *extra)
    pb = spawn_node(
        ports[1], cports[1], "bee", "--region", "r1",
        "--seed-addrs", seed,
        "--admission-policy", "control>read>write>bulk",
        "--failpoints", "admission.shed=error",
        *extra,
    )
    pc = spawn_node(
        ports[2], cports[2], "sea", "--region", "r2",
        "--seed-addrs", seed, *extra,
    )
    procs = [pa, pb, pc]
    try:
        ca = connect_client(ports[0], proc=pa)
        cb = connect_client(ports[1], proc=pb)
        cc = connect_client(ports[2], proc=pc)

        deadline = time.time() + 120
        while time.time() < deadline:
            if (
                _metric(ca, b"CLUSTER", b"bridge_is_self") == 1
                and _metric(cc, b"CLUSTER", b"bridge_is_self") == 1
                and _metric(cb, b"CLUSTER", b"bridge_is_self") == 0
            ):
                break
            time.sleep(0.1)
        else:
            raise AssertionError("regions never settled to sparse policy")

        # the forced-shed member refuses wrapped writes with the TYPED
        # reply (class + machine-readable retry hint), and the shed
        # counter in the OVERLOAD section records each refusal
        from jylis_tpu.client import ResponseError

        def raw_inc(key, n):
            # a raw INC serves natively UNLESS its burst lands while
            # ANOTHER type's lock is held (a flush, a cluster apply) —
            # busy() then routes the burst through the per-command
            # Python path, where the forced admission.shed failpoint
            # refuses it (behind its own type's drain it sleeps for
            # the lock and stays native). A refusal
            # mutates nothing (never an accept the node can't honor),
            # so retrying until a burst goes native keeps the exact
            # convergence counts below sound; the contract drilled here
            # is that the native path keeps serving under forced shed,
            # not that no individual burst ever reroutes.
            while True:
                try:
                    cb.execute_command("GCOUNT", "INC", key, str(n))
                    return
                except ResponseError as e:
                    assert str(e).startswith("BUSY"), e
                    assert time.time() < deadline, "raw write never served"
                    time.sleep(0.02)

        shed0 = _metric(cb, b"OVERLOAD", b"shed_write") or 0
        for _ in range(10):
            try:
                cb.execute_command(
                    "SESSION", "WRAP", "GCOUNT", "INC", "wrapped", "1"
                )
            except ResponseError as e:
                msg = str(e)
                assert msg.startswith("BUSY"), msg
                assert "class=write" in msg, msg
                assert "retry-after-ms=" in msg, msg
            else:
                raise AssertionError("forced shed admitted a wrapped write")
        assert (_metric(cb, b"OVERLOAD", b"shed_write") or 0) >= shed0 + 10

        # raw native-path writes bypass the gate by design: traffic
        # keeps flowing and converging while the node refuses the rest
        raw_inc("warm", 1)
        while cc.execute_command("GCOUNT", "GET", "warm") != 1:
            assert time.time() < deadline, "relay path never converged"
            time.sleep(0.05)

        # SIGKILL the bridge mid-traffic, with the member still under
        # forced shedding the whole time
        h0 = _metric(cb, b"CLUSTER", b"bridge_handovers")
        for _ in range(5):
            raw_inc("traffic", 1)
        t_kill = time.time()
        os.kill(pa.pid, _signal.SIGKILL)
        pa.wait(timeout=30)
        for _ in range(5):
            raw_inc("traffic", 1)

        # the protected control plane serves DURING the failover
        # window: SYSTEM METRICS is the probe itself — every _metric
        # poll below is a control-class command answered by a node
        # that is refusing its write class
        bound_s = demote * hb + 10.0
        while _metric(cb, b"CLUSTER", b"bridge_is_self") != 1:
            assert time.time() - t_kill < bound_s, (
                f"no successor within {bound_s:.1f}s of SIGKILL"
            )
            time.sleep(0.1)
        assert _metric(cb, b"CLUSTER", b"bridge_handovers") > h0

        # shedding persists through the failover (the failpoint is
        # process-local state, untouched by the bridge handover)
        with pytest.raises(ResponseError, match="^BUSY"):
            cb.execute_command(
                "SESSION", "WRAP", "GCOUNT", "INC", "wrapped", "1"
            )

        # cross-region convergence resumes through the successor
        raw_inc("post", 2)
        while cc.execute_command("GCOUNT", "GET", "post") != 2:
            assert time.time() < deadline, "post-failover write stranded"
            time.sleep(0.05)
        while cc.execute_command("GCOUNT", "GET", "traffic") != 10:
            assert time.time() < deadline, "mid-kill traffic never healed"
            time.sleep(0.05)

        # survivors digest-match and the heal never fell back to a
        # whole-state dump
        while True:
            db = cb.execute_command("SYSTEM", "DIGEST")
            dc = cc.execute_command("SYSTEM", "DIGEST")
            if db == dc:
                break
            assert time.time() < deadline, (db, dc)
            time.sleep(0.1)
        assert _metric(cb, b"CLUSTER", b"sync_full_dumps") == 0
        assert _metric(cc, b"CLUSTER", b"sync_full_dumps") == 0
    finally:
        for p in procs:
            if p.poll() is None:
                stop_node(p)
