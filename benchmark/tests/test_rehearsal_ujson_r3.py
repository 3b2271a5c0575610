"""`ycsb-ujson-1kx1k-r3.b`: its entries in the manifest and the files they
name, and its rehearsal (three nodes on the CPU, tiny sizes): `correct` is
asked of all three replicas, every document is a resident row and no write
demotes one, and the five per-layer metrics this cell brought are in the
traced line beside the ones it shares."""

import json
import os
import re

from benchmark.harness import gen, manifest
from benchmark.tests.test_rehearsal import run_py

CELL = "ycsb-ujson-1kx1k-r3.b"
NEW = ("models.ujson_device_fold_frac", "models.ujson_host_walk_per_delta",
       "models.ujson_fold_us_per_delta", "models.ujson_render_us_per_get",
       "models.ujson_demotes_per_kwrite")
MOVES = dict(zip(NEW, ("read_p95_ms", "read_p95_ms", "read_p95_ms", "ops_per_s", "write_p95_ms")))
SHARED = ("server.dispatch_us_per_cmd", "server.deferred_frac", "server.reply_bytes_per_cmd",
          "models.drain_busy_share", "models.drain_ms_per_kkeys", "models.flush_busy_share",
          "models.lock_hold_serve_share", "journal.writer_busy_share", "device.idle_share",
          "cluster.apply_us_per_key", "cluster.apply_loop_share", "cluster.push_bytes_per_key",
          "cluster.reship_frac", "cluster.lock_hold_share")


def test_the_manifest_lists_the_cell_its_configuration_and_its_metrics():
    cell = manifest.Cell(CELL)
    assert cell.chips == 1 and cell.entry["traffic"] == "ycsb-b-sets-r3"
    assert {m["name"] for m in cell.end_to_end} == {"ops_per_s", "read_p95_ms", "write_p95_ms",
                                                    "setup_s"}
    listed = {m["name"]: m for m in cell.per_layer}
    assert set(NEW) | set(SHARED) <= set(listed)
    assert not any(n.startswith(("kernel.", "models.tlog_", "cluster.tlog_")) for n in listed)
    for name in NEW:
        entry = listed[name]
        assert entry["workloads"] == [CELL] and entry["moves"] == MOVES[name]
        assert entry["layer"] == "repos and drains"
        spec = cell.layer_spec(name)
        assert spec["reader"] == "counter_ratio" and spec["name"] == name
    entry, = (c for c in cell.manifest["configs"] if c["name"] == "ycsb-ujson-1kx1k-r3")
    assert entry["reduced"] == ["replicas", "peer_load", "journal_max_bytes"]
    assert entry["source"] == cell.config["source"] and len(entry["source"]) <= 200
    # appended: the sixth cell and the sixth configuration, the five before them where they
    # were (a later PR appends behind them: PR 40's metrics already follow this cell's five)
    assert [w["name"] for w in cell.manifest["workloads"]][:6] == [
        "pncount-1m-r64.fanin", "ycsb-treg-1m.a", "ycsb-treg-1m-r3.a", "ycsb-tlog-1kx1k.e",
        "ycsb-tlog-1kx1k-r3.e", CELL]
    assert cell.manifest["configs"][5] is entry
    assert [c["name"] for c in cell.manifest["configs"]][:5] == [
        "pncount-1m-r64", "ycsb-treg-1m", "ycsb-treg-1m-r3", "ycsb-tlog-1kx1k", "ycsb-tlog-1kx1k-r3"]
    names = [m["name"] for m in cell.manifest["per_layer"]]
    first = names.index(NEW[0])
    assert names[first:first + len(NEW)] == list(NEW)


def test_the_configuration_states_its_source_guarantees_cuts_and_the_residency_flag():
    config = manifest.Cell(CELL).config
    assert config["type"] == "UJSON" and config["peers"] == 2
    assert set(config["reduced"]) == {"replicas", "peer_load", "journal_max_bytes"}
    assert {"acknowledgement", "durability", "read_your_writes", "merge", "read",
            "convergence"} <= set(config["guarantees"])
    assert {"mapping", "ids", "device_state"} <= set(config["assumed"])
    state = config["state"]
    assert (state["keys"], state["members"]) == (1000, 1000)  # YCSB's 1M records
    assert len(str(state["id_base"])) == 19
    assert state["id_base"] + state["members"] < gen.TS_EPOCH_MS << gen.TS_SHIFT
    # the deployment's own setting, and what ends a parent without it at spawn
    for block in (config, config["rehearse"]):
        flags = block["node_flags"]
        leaves = int(flags[flags.index("--ujson-resident-min-leaves") + 1])
        assert 0 < leaves <= manifest.sized(config, block is not config)["state"]["members"]
    tlog = manifest.Cell("ycsb-tlog-1kx1k-r3.e").config["node_flags"]
    assert config["node_flags"][: len(tlog)] == tlog, "ycsb-tlog-1kx1k-r3's flags plus one"


def test_the_traffic_is_ycsb_b_on_sets_at_all_three_nodes_with_nothing_to_warm():
    cell = manifest.Cell(CELL)
    traffic = cell.traffic
    assert "warm_bursts" not in traffic and "probes" not in traffic
    # 32, not 20: the window opens clear of the first fold stall (the mix's `why`)
    assert traffic["warm_seconds"] == 32
    state = cell.config["state"]
    streams = {s["name"]: s for s in traffic["streams"]}
    assert set(streams) == {"clients", "peer_clients"}
    node, peers = streams["clients"], streams["peer_clients"]
    assert (node["loop"], node["target"], node["connections"], node["depth"], node["workers"],
            node["counted"]) == ("closed", "node", 64, 1, 4, True)
    assert (peers["loop"], peers["target"], peers["workers"], peers["counted"]) == (
        "open", "peers", 2, False)
    assert node["ops"] == peers["ops"] and node["keys"] == peers["keys"] == {
        "dist": "zipfian", "theta": 0.99}
    shares = {op["cmd"].split()[1]: (op["share"], op["class"]) for op in node["ops"]}
    assert shares == {"GET": (0.95, "read"), "INS": (0.025, "write"), "RM": (0.025, "write")}
    assert node["amount"] == peers["amount"] == [state["id_base"],
                                                 state["id_base"] + state["members"] - 1]
    rehearse = manifest.sized(cell.config, True)["state"]
    for s in manifest.sized(traffic, True)["streams"]:
        assert s["amount"] == [rehearse["id_base"], rehearse["id_base"] + rehearse["members"] - 1]
    assert "knee" in traffic["why"] and str(int(peers["rate_per_s"])) in traffic["why"].replace(",", "")


def test_the_three_node_ujson_cell_rehearses_with_its_new_metrics():
    p = run_py("--workload", CELL, "--seed", str(2**31 + 3939), "--seconds", "3", "--trace", "1",
               "--rehearse")
    assert p.returncode == 0, p.stderr[-3000:]
    result = json.loads(p.stdout.strip().splitlines()[-1])
    assert result["correct"] is True and result["failed"] == 0 and result["rehearsal"] is True
    assert result["compiles_in_window"] == 0
    for node in ("bench-node", "bench-peer1", "bench-peer2"):
        m = re.search(rf"correct\[{node} UJSON\]: mismatched reads (\d+) of (\d+)", p.stdout)
        assert m and m.group(1) == "0" and int(m.group(2)) >= 60, node
    metrics = {k: v["value"] for k, v in result["metrics"].items()}
    assert set(NEW) <= set(metrics), sorted(metrics)
    assert metrics["models.ujson_demotes_per_kwrite"] == 0, "a write on a resident row is a row delta"
    rehearse = manifest.sized(manifest.Cell(CELL).config, True)["state"]
    assert metrics["models.ujson_host_walk_per_delta"] >= 0.9 * rehearse["members"], \
        "a host fold walks the whole set"
    assert 0 <= metrics["models.ujson_device_fold_frac"] <= 1
    assert metrics["models.ujson_fold_us_per_delta"] > 0 and metrics["models.ujson_render_us_per_get"] > 0
    assert 0 < metrics["server.deferred_frac"] < 0.2 and metrics["cluster.reship_frac"] == 0
    assert metrics["server.reply_bytes_per_cmd"] > 10 * rehearse["members"]
    assert set(metrics) <= {m["name"] for m in manifest.Cell(CELL).per_layer}
    # every document of the snapshot is a resident row, and stayed one
    log = open(os.path.join(manifest.ROOT, "benchmark", "out", "logs", CELL, "bench-node.log")).read()
    shape = re.search(r"device state: .*UJSON (\d+)x(\d+) over \d+ device", log)
    assert shape and int(shape.group(1)) >= rehearse["keys"] and int(shape.group(2)) >= 2 * rehearse["members"]
    merge = re.search(r"merge metrics: .*UJSON: [^;]*", log).group(0)
    assert f"{rehearse['keys']} admits" in merge and " 0 demote_write" in merge
    assert f"{rehearse['keys']} resident_rows" in merge
