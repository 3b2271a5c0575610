"""Multi-lane serving: keyspace slicing, the lane bus/bridge, per-lane
journal segments with merge replay, SO_REUSEPORT sharing, SYSTEM
DIGEST, and the supervisor's metrics aggregation.

The bridge topology is exercised IN-PROCESS (the bus is literally the
existing Cluster engine on loopback, so two Databases + three Cluster
instances in one loop model lane 0 + lane 1 + an external peer
exactly); the spawned end-to-end path (supervisor, SO_REUSEPORT
sharding, lanes.json, cross-process convergence) lives in the chaos
lane-crash cell in test_drill_matrix.py.
"""

import asyncio
import json
import os

import pytest

import jylis_tpu  # noqa: F401
from test_cluster import TICK, Node, converge_wait, grab_ports, resp_call
from jylis_tpu import lanes as lanes_mod
from jylis_tpu import journal as journal_mod
from jylis_tpu.cluster import Cluster
from jylis_tpu.models.database import Database
from jylis_tpu.server.server import Server
from jylis_tpu.system import System
from jylis_tpu.utils.address import Address
from jylis_tpu.utils.config import Config, resolve_auto_lanes
from jylis_tpu.utils.log import Log
from jylis_tpu.utils.metrics import metric_lines


# ---- slicing / config ------------------------------------------------------


def test_lane_of_stable_and_in_range():
    keys = [b"k%d" % i for i in range(500)]
    for n in (1, 2, 4, 7):
        owners = [lanes_mod.lane_of(k, n) for k in keys]
        assert all(0 <= o < n for o in owners)
        assert owners == [lanes_mod.lane_of(k, n) for k in keys]
    # a non-degenerate spread: every lane owns something at 500 keys
    assert len(set(lanes_mod.lane_of(k, 4) for k in keys)) == 4


def test_auto_lanes_resolution():
    assert resolve_auto_lanes(1) == 1
    assert resolve_auto_lanes(2) == 1  # a lane split would just contend
    assert resolve_auto_lanes(4) == 4
    assert resolve_auto_lanes(64) == 8  # capped


def test_lane_identities_distinct_and_restart_stable():
    cfg = Config()
    cfg.addr = Address("10.0.0.1", "9999", "prod-node")
    cfg.lanes = 4
    cfg.lane_bus = [7001, 7002, 7003, 7004]
    ids = {lanes_mod.lane_identity(cfg, k) for k in range(4)}
    assert len(ids) == 4  # distinct CRDT replica identities per lane
    assert cfg.addr.hash64() not in ids
    # restart-stable: a reboot picks fresh ephemeral bus ports, and the
    # identity must NOT change with them (a port-derived identity would
    # mint N new replica ids per restart, growing counter columns
    # forever)
    cfg2 = Config()
    cfg2.addr = cfg.addr
    cfg2.lanes = 4
    cfg2.lane_bus = [8101, 8102, 8103, 8104]
    assert ids == {lanes_mod.lane_identity(cfg2, k) for k in range(4)}


def test_bus_config_seeds_exclude_self():
    cfg = Config()
    cfg.addr = Address("10.0.0.1", "9999", "n")
    cfg.lanes = 3
    cfg.lane_bus = [7001, 7002, 7003]
    bc = lanes_mod.bus_config(cfg, 1)
    assert bc.addr == lanes_mod.bus_address(cfg, 1)
    assert bc.addr not in bc.seed_addrs
    assert len(bc.seed_addrs) == 2
    assert bc.heartbeat_time == cfg.lane_bus_heartbeat


# ---- per-lane journal segments ---------------------------------------------


def test_segment_names():
    assert journal_mod.segment_name(None) == "journal.jylis"
    assert journal_mod.segment_name(2) == "journal.lane2.jylis"
    assert lanes_mod.snapshot_name(None) == "snapshot.jylis"
    assert lanes_mod.snapshot_name(3) == "snapshot.lane3.jylis"


def _journal_write(path: str, name: str, batch) -> None:
    j = journal_mod.Journal(path, fsync="off")
    j.open()
    j.append(name, batch)
    j.flush()
    j.close()


def test_recover_all_merges_every_lane_segment(tmp_path):
    d = str(tmp_path)
    _journal_write(
        os.path.join(d, "journal.lane0.jylis"), "GCOUNT", [(b"a", {1: 5})]
    )
    _journal_write(
        os.path.join(d, "journal.lane1.jylis"), "GCOUNT", [(b"b", {2: 7})]
    )
    # the classic single-lane segment merges too (a node that moved
    # from --lanes 1 to --lanes N keeps its history)
    _journal_write(
        os.path.join(d, "journal.jylis"), "GCOUNT", [(b"c", {3: 9})]
    )
    db = Database(identity=42)
    n = journal_mod.recover_all(
        db, d, os.path.join(d, "journal.lane0.jylis")
    )
    assert n == 3
    resp = _Collect()
    for key, want in ((b"a", b":5"), (b"b", b":7"), (b"c", b":9")):
        resp.vals.clear()
        db.apply(resp, [b"GCOUNT", b"GET", key])
        assert resp.vals == ["u64", int(want[1:])], (key, resp.vals)


def test_recover_all_never_mutates_foreign_torn_tail(tmp_path):
    d = str(tmp_path)
    own = os.path.join(d, "journal.lane0.jylis")
    foreign = os.path.join(d, "journal.lane1.jylis")
    _journal_write(own, "GCOUNT", [(b"a", {1: 5})])
    _journal_write(foreign, "GCOUNT", [(b"b", {2: 7})])
    # a live sibling mid-append: torn trailing bytes on the FOREIGN file
    with open(foreign, "ab") as f:
        f.write(b"\x00\x01\x02")
    size_before = os.path.getsize(foreign)
    db = Database(identity=42)
    n = journal_mod.recover_all(db, d, own)
    assert n == 2  # both complete batches converged
    # the foreign file was not truncated and not moved aside
    assert os.path.getsize(foreign) == size_before
    assert not os.path.exists(foreign + ".unreadable")


def test_recover_all_skips_corrupt_foreign_segment(tmp_path):
    d = str(tmp_path)
    own = os.path.join(d, "journal.lane0.jylis")
    foreign = os.path.join(d, "journal.lane1.jylis")
    _journal_write(own, "GCOUNT", [(b"a", {1: 5})])
    with open(foreign, "wb") as f:
        f.write(b"not a journal at all")
    db = Database(identity=42)
    n = journal_mod.recover_all(db, d, own)
    assert n == 1
    # never mutate another lane's file, even an unreadable one
    assert os.path.exists(foreign)
    assert not os.path.exists(foreign + ".unreadable")


# ---- SO_REUSEPORT ----------------------------------------------------------


def test_reuseport_two_servers_share_one_port():
    async def main():
        (port,) = grab_ports(1)
        cfgs, servers = [], []
        for _ in range(2):
            cfg = Config()
            cfg.port = str(port)
            cfg.lanes = 2  # arms the SO_REUSEPORT listener path
            cfg.log = Log.create_none()
            cfgs.append(cfg)
            servers.append(Server(cfg, Database(identity=1)))
        for s in servers:
            await s.start()  # the second bind would raise without SO_REUSEPORT
        try:
            for _ in range(8):
                out = await resp_call(
                    port, b"*4\r\n$6\r\nGCOUNT\r\n$3\r\nINC\r\n$1\r\nk\r\n$1\r\n1\r\n"
                )
                assert out == b"+OK\r\n", out
        finally:
            for s in servers:
                await s.dispose()

    asyncio.run(main())


# ---- the lane bus + lane-0 bridge, in-process ------------------------------


class LaneStack:
    """One in-process lane: Database + bus Cluster (+ external Cluster
    and bridge on lane 0), the exact wiring main.py does for a worker."""

    def __init__(self, config, lane_id: int, ext_seeds=()):
        self.config = config
        self.lane_id = lane_id
        bus_cfg = lanes_mod.bus_config(config, lane_id)
        self.system = System(bus_cfg)
        self.database = Database(
            identity=lanes_mod.lane_identity(config, lane_id),
            system_repo=self.system.repo,
        )
        self.system.repo.lane_fn = lambda: {
            "id": lane_id, "count": config.lanes
        }
        self.bus = Cluster(
            bus_cfg, self.database, register_system=(lane_id != 0)
        )
        self.external = None
        if lane_id == 0:
            ext_cfg = Config()
            ext_cfg.port = "0"
            ext_cfg.addr = config.addr
            ext_cfg.seed_addrs = list(ext_seeds)
            ext_cfg.heartbeat_time = TICK
            ext_cfg.log = config.log
            self.external = Cluster(ext_cfg, self.database, drive_flush=False)
            lanes_mod.wire_bridge(self.bus, self.external)
        srv_cfg = Config()
        srv_cfg.port = "0"
        srv_cfg.log = config.log
        self.server = Server(srv_cfg, self.database)

    async def start(self):
        await self.server.start()
        await self.bus.start()
        if self.external is not None:
            await self.external.start()

    async def stop(self):
        self.bus.dispose()
        if self.external is not None:
            self.external.dispose()
        await self.server.dispose()


async def _make_lane_pair(ext_seeds=()):
    b0, b1, ext_port = grab_ports(3)
    cfg = Config()
    cfg.addr = Address("127.0.0.1", str(ext_port), "lanenode")
    cfg.lanes = 2
    cfg.lane_bus = [b0, b1]
    cfg.lane_bus_heartbeat = TICK
    cfg.log = Log.create_none()
    lane0 = LaneStack(cfg, 0, ext_seeds=ext_seeds)
    lane1 = LaneStack(cfg, 1)
    await lane0.start()
    await lane1.start()
    return cfg, lane0, lane1


async def _gcount(port: int, key: bytes):
    out = await resp_call(
        port, b"*3\r\n$6\r\nGCOUNT\r\n$3\r\nGET\r\n$%d\r\n%s\r\n" % (len(key), key)
    )
    return out


def test_lanes_converge_over_bus():
    """A write accepted by one lane becomes readable on the other —
    serve-after-converge across the loopback bus."""

    async def main():
        cfg, lane0, lane1 = await _make_lane_pair()
        try:
            out = await resp_call(
                lane1.server.port,
                b"*4\r\n$6\r\nGCOUNT\r\n$3\r\nINC\r\n$1\r\nk\r\n$1\r\n7\r\n",
            )
            assert out == b"+OK\r\n", out

            async def converged():
                return await _gcount(lane0.server.port, b"k") == b":7\r\n"

            deadline = asyncio.get_event_loop().time() + 200 * TICK
            while asyncio.get_event_loop().time() < deadline:
                if await converged():
                    break
                await asyncio.sleep(TICK)
            assert await converged()
        finally:
            await lane0.stop()
            await lane1.stop()

    asyncio.run(main())


def test_bridge_relays_between_lanes_and_external_peer():
    """Lane 1's writes reach an external peer through lane 0's bridge,
    and the peer's writes reach lane 1 — one cluster identity outside,
    full fan-in inside."""

    async def main():
        (peer_port,) = grab_ports(1)
        peer = Node("peer", peer_port)
        await peer.start()
        try:
            cfg, lane0, lane1 = await _make_lane_pair(
                ext_seeds=[peer.config.addr]
            )
            try:
                assert await converge_wait(
                    lambda: any(
                        c.established
                        for c in lane0.external._actives.values()
                    ),
                    ticks=200,
                )
                # lane 1 -> bus -> lane 0 bridge -> external peer
                out = await resp_call(
                    lane1.server.port,
                    b"*4\r\n$6\r\nGCOUNT\r\n$3\r\nINC\r\n$1\r\nx\r\n$1\r\n5\r\n",
                )
                assert out == b"+OK\r\n", out
                # peer -> lane 0 external -> bridge -> bus -> lane 1
                peer.database.apply(_Collect(), [b"GCOUNT", b"INC", b"y", b"3"])

                async def both():
                    a = await _gcount(peer.server.port, b"x")
                    b = await _gcount(lane1.server.port, b"y")
                    return a == b":5\r\n" and b == b":3\r\n"

                deadline = asyncio.get_event_loop().time() + 400 * TICK
                while asyncio.get_event_loop().time() < deadline:
                    if await both():
                        break
                    await asyncio.sleep(TICK)
                assert await both()
            finally:
                await lane0.stop()
                await lane1.stop()
        finally:
            await peer.stop()

    asyncio.run(main())


# ---- SYSTEM DIGEST / LANE metrics ------------------------------------------


class _Collect:
    def __init__(self):
        self.vals = []

    def __getattr__(self, name):
        return lambda *a: self.vals.extend((name, *a))


def test_system_digest_async_path_and_convergence():
    """SYSTEM DIGEST over a real RESP connection: equal on converged
    replicas, different when they diverge."""

    async def main():
        p_a, p_b = grab_ports(2)
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
    db = Database(identity=9)
    resp = _Collect()
    db.apply(resp, [b"GCOUNT", b"INC", b"k", b"4"])
    resp.vals.clear()
    db.apply(resp, [b"SYSTEM", b"DIGEST"])
    assert resp.vals[0] == "string"
    sync_hex = resp.vals[1]

    async def async_digest():
        return (await db.sync_digest_async()).hex().encode()

    assert asyncio.run(async_digest()) == sync_hex


def test_metric_lines_lane_section():
    lines = metric_lines(lane={"id": 2, "count": 4})
    assert lines[0] == "LANE id 2"
    assert lines[1] == "LANE count 4"
    # single-lane nodes: no section at all (byte-stable legacy surface)
    assert not any(
        line.startswith("LANE") for line in metric_lines()
    )


# ---- metrics aggregation ---------------------------------------------------


def test_aggregate_expositions_relabels_and_sums():
    body0 = (
        "# HELP jylis_cmds_total Commands served per data type.\n"
        "# TYPE jylis_cmds_total counter\n"
        'jylis_cmds_total{type="GCOUNT"} 10\n'
        'jylis_gauge{name="cluster.backlog_ms"} 1.5\n'
        'jylis_seam_latency_seconds_count{seam="server.py_dispatch"} 4\n'
        "jylis_trace_events 2\n"
    )
    body1 = (
        "# HELP jylis_cmds_total Commands served per data type.\n"
        "# TYPE jylis_cmds_total counter\n"
        'jylis_cmds_total{type="GCOUNT"} 32\n'
        'jylis_gauge{name="cluster.backlog_ms"} 0.5\n'
        'jylis_seam_latency_seconds_count{seam="server.py_dispatch"} 6\n'
        "jylis_trace_events 1\n"
    )
    out = lanes_mod.aggregate_expositions({0: body0, 1: body1, 2: None})
    # per-lane relabeled samples
    assert 'jylis_cmds_total{lane="0",type="GCOUNT"} 10' in out
    assert 'jylis_cmds_total{lane="1",type="GCOUNT"} 32' in out
    # counters sum into the aggregate (lane-less) series
    assert 'jylis_cmds_total{type="GCOUNT"} 42' in out
    assert (
        'jylis_seam_latency_seconds_count{seam="server.py_dispatch"} 10'
        in out
    )
    assert "jylis_trace_events 3" in out
    # gauges stay per-lane only (summing a backlog is meaningless)
    assert 'jylis_gauge{name="cluster.backlog_ms"} 2' not in out
    assert 'jylis_gauge{lane="0",name="cluster.backlog_ms"} 1.5' in out
    # a dead lane is visible, not an error
    assert 'jylis_lane_up{lane="2"} 0' in out
    assert 'jylis_lane_up{lane="0"} 1' in out
    # HELP/TYPE emitted once
    assert out.count("# TYPE jylis_cmds_total counter") == 1


def test_aggregate_output_is_valid_exposition():
    import re

    sample_re = re.compile(
        r"^[a-zA-Z_:][a-zA-Z0-9_:]*"
        r"(\{[a-zA-Z_][a-zA-Z0-9_]*=\"(?:[^\"\\]|\\.)*\""
        r"(,[a-zA-Z_][a-zA-Z0-9_]*=\"(?:[^\"\\]|\\.)*\")*\})?"
        r" -?[0-9.eE+-]+( [0-9]+)?$"
    )
    out = lanes_mod.aggregate_expositions(
        {0: 'jylis_cmds_total{type="GCOUNT"} 10\njylis_trace_events 2\n'}
    )
    for line in out.splitlines():
        if line and not line.startswith("#"):
            assert sample_re.match(line), line


# ---- supervisor plumbing (no processes) ------------------------------------


def test_parse_lane_failpoints():
    got = lanes_mod._parse_lane_failpoints("1:lane.tick=crash:1;0:x=error")
    assert got == {1: "lane.tick=crash:1", 0: "x=error"}
    assert lanes_mod._parse_lane_failpoints("") == {}
    assert lanes_mod._parse_lane_failpoints("junk") == {}


def test_supervisor_child_argv_overrides(tmp_path):
    async def main():
        cfg = Config()
        cfg.port = "0"
        cfg.addr = Address("127.0.0.1", "9999", "supnode")
        cfg.lanes = 2
        cfg.data_dir = str(tmp_path)
        cfg.log = Log.create_none()
        sup = lanes_mod.Supervisor(
            cfg, ["--port", "0", "--lanes", "2", "--addr", "127.0.0.1:9999:"]
        )
        argv = sup._child_argv(1)
        assert argv[:3] == [__import__("sys").executable, "-m", "jylis_tpu"]
        # the appended overrides win under argparse (last occurrence)
        assert argv[argv.index("--lane-id") + 1] == "1"
        assert str(sup.resp_port) == argv[len(argv) - argv[::-1].index("--port")]
        assert argv[-2] == "--metrics-port"
        # lanes.json round-trips through write_manifest
        sup.write_manifest()
        manifest = json.load(open(os.path.join(str(tmp_path), "lanes.json")))
        assert manifest["port"] == sup.resp_port
        assert [lane["id"] for lane in manifest["lanes"]] == [0, 1]

    asyncio.run(main())


def test_supervisor_manifest_write_runs_off_the_event_loop(tmp_path, monkeypatch):
    """Regression (jlint v2 interprocedural JL101): `run()` and
    `_lane_died()` called `write_manifest` — open/json.dump/os.replace —
    directly on the supervisor event loop, which also carries every
    lane's death-watcher, signal handling, and the aggregated metrics
    endpoint. A contended disk during a crash-respawn storm stalled all
    three. The write now dispatches through write_manifest_async: a
    slow manifest write must not freeze the loop."""

    async def main():
        import threading
        import time as _time

        cfg = Config()
        cfg.port = "0"
        cfg.addr = Address("127.0.0.1", "9999", "supnode")
        cfg.lanes = 2
        cfg.data_dir = str(tmp_path)
        cfg.log = Log.create_none()
        sup = lanes_mod.Supervisor(cfg, ["--port", "0", "--lanes", "2"])

        wrote_on: list = []
        real = lanes_mod.Supervisor.write_manifest

        def slow_write(self):
            wrote_on.append(threading.current_thread())
            _time.sleep(0.3)  # the contended-disk shape
            real(self)

        monkeypatch.setattr(lanes_mod.Supervisor, "write_manifest", slow_write)

        # a loop heartbeat: the largest gap between ticks is the stall
        gaps: list[float] = []

        async def ticker():
            last = asyncio.get_running_loop().time()
            while True:
                await asyncio.sleep(0.01)
                now = asyncio.get_running_loop().time()
                gaps.append(now - last)
                last = now

        t = asyncio.ensure_future(ticker())
        try:
            await sup.write_manifest_async()
        finally:
            t.cancel()
        assert wrote_on and wrote_on[0] is not threading.main_thread()
        # the loop kept ticking THROUGH the 0.3 s write (pre-fix the
        # direct call would produce one >=0.3 s gap)
        assert gaps and max(gaps) < 0.15, max(gaps)
        # and the manifest really landed
        manifest = json.load(open(os.path.join(str(tmp_path), "lanes.json")))
        assert [lane["id"] for lane in manifest["lanes"]] == [0, 1]

    asyncio.run(main())


def test_lane_died_writes_manifest_off_loop(tmp_path, monkeypatch):
    """The crash-respawn path itself (`_lane_died`) must use the
    threaded manifest write — pinned by driving it with a stubbed spawn
    and asserting the write thread."""

    async def main():
        import threading

        cfg = Config()
        cfg.port = "0"
        cfg.addr = Address("127.0.0.1", "9999", "supnode")
        cfg.lanes = 2
        cfg.data_dir = str(tmp_path)
        cfg.log = Log.create_none()
        sup = lanes_mod.Supervisor(cfg, ["--port", "0", "--lanes", "2"])
        monkeypatch.setattr(lanes_mod, "RESTART_BACKOFF_S", 0.0)
        monkeypatch.setattr(
            lanes_mod.Supervisor, "_spawn", lambda self, k: None
        )
        wrote_on: list = []
        real = lanes_mod.Supervisor.write_manifest

        def recording_write(self):
            wrote_on.append(threading.current_thread())
            real(self)

        monkeypatch.setattr(
            lanes_mod.Supervisor, "write_manifest", recording_write
        )
        await sup._lane_died(1)
        assert wrote_on and wrote_on[0] is not threading.main_thread()

    asyncio.run(main())


def test_concurrent_manifest_writes_serialise(tmp_path, monkeypatch):
    """Two lanes dying near-simultaneously drive write_manifest_async
    concurrently; the writes share ONE fixed lanes.json.tmp path, so
    they must serialise (the on-loop call was implicitly serial; the
    off-loop fix carries an explicit lock) — interleaved writers would
    publish corrupt JSON."""

    async def main():
        import time as _time

        cfg = Config()
        cfg.port = "0"
        cfg.addr = Address("127.0.0.1", "9999", "supnode")
        cfg.lanes = 2
        cfg.data_dir = str(tmp_path)
        cfg.log = Log.create_none()
        sup = lanes_mod.Supervisor(cfg, ["--port", "0", "--lanes", "2"])
        spans: list = []
        real = lanes_mod.Supervisor.write_manifest

        def slow_write(self):
            t0 = _time.monotonic()
            _time.sleep(0.15)
            real(self)
            spans.append((t0, _time.monotonic()))

        monkeypatch.setattr(lanes_mod.Supervisor, "write_manifest", slow_write)
        await asyncio.gather(
            sup.write_manifest_async(), sup.write_manifest_async()
        )
        assert len(spans) == 2
        (a0, a1), (b0, b1) = sorted(spans)
        assert b0 >= a1, "concurrent manifest writes overlapped"
        # and the published file is valid JSON
        manifest = json.load(open(os.path.join(str(tmp_path), "lanes.json")))
        assert [lane["id"] for lane in manifest["lanes"]] == [0, 1]

    asyncio.run(main())


def test_supervisor_treats_a_lane_fatal_exit_as_fatal(tmp_path, monkeypatch):
    """One process per chip: a lane that finds an accelerator under
    --lanes N>1 exits LANE_FATAL_EXIT (main.py), and the supervisor must
    stop the node with that code at once — not respawn the lane into the
    same wall forever while staying up and exiting 0 on SIGTERM."""

    async def main():
        import sys
        import time as _time

        cfg = Config()
        cfg.port = "0"
        cfg.addr = Address("127.0.0.1", "9999", "supnode")
        cfg.lanes = 2
        cfg.data_dir = str(tmp_path)
        cfg.log = Log.create_none()
        sup = lanes_mod.Supervisor(cfg, ["--port", "0", "--lanes", "2"])
        spawned: list[int] = []

        def child_argv(self, lane_id):
            spawned.append(lane_id)
            # lane 0 refuses the way main.py does; lane 1 would serve on
            code = (
                f"import sys; sys.exit({lanes_mod.LANE_FATAL_EXIT})"
                if lane_id == 0
                else "import time; time.sleep(60)"
            )
            return [sys.executable, "-c", code]

        monkeypatch.setattr(lanes_mod.Supervisor, "_child_argv", child_argv)
        t0 = _time.monotonic()
        await asyncio.wait_for(sup.run(), 30)
        assert _time.monotonic() - t0 < 15
        assert sup.fatal_rc == lanes_mod.LANE_FATAL_EXIT
        assert spawned == [0, 1]  # no respawn of the refusing lane
        assert all(p.poll() is not None for p in sup.procs)  # sibling stopped

    asyncio.run(main())

