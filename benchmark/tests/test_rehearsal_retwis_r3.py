"""`retwis-1kx1k-r3.mix`: its entries in the manifest and the files they
name, and its rehearsal (three nodes on the CPU, tiny sizes): the first
cell whose clients speak several types on one connection. `correct` is
asked of all three replicas for each of the three types (nine verdicts),
the two per-layer metrics this cell brought are in the traced line,
nothing is routed to the Python path for a held lock, and the control
fails on each of the three types in turn."""

import json
import re

from benchmark import control
from benchmark.harness import manifest
from benchmark.tests.test_rehearsal import run_py

CELL, CONFIG, TRAFFIC = "retwis-1kx1k-r3.mix", "retwis-1kx1k-r3", "retwis-mix-r3"
NEW = ("server.locks_per_burst", "server.beside_hold_frac")
R3 = ["ycsb-treg-1m-r3.a", "ycsb-tlog-1kx1k-r3.e", "ycsb-ujson-1kx1k-r3.b"]
# per 100 operations of TAPIR's Table 2, 505 commands (the configuration's `assumed.mapping`)
COMMANDS = {"TLOG GET {key} 10": (275, "read"), "TREG GET {key}": (95, "read"),
            "TREG SET {key} {value:140} {ts}": (45, "write"),
            "UJSON GET {key} members": (30, "read"),
            "TLOG INS {key} {value:140} {ts}": (30, "write"),
            "UJSON INS {key} members {ts}": (15, "write"),
            "UJSON RM {key} members {amount}": (15, "write")}


def test_the_manifest_lists_the_cell_its_configuration_and_its_metrics():
    cell = manifest.Cell(CELL)
    assert cell.chips == 1 and cell.entry["traffic"] == TRAFFIC
    assert {m["name"] for m in cell.end_to_end} == {"ops_per_s", "read_p95_ms", "write_p95_ms",
                                                    "setup_s"}
    listed = {m["name"]: m for m in cell.per_layer}
    # everything the three one-type -r3 cells read has something to read here; the five
    # `models.ujson_*` are not asked for: test_rehearsal_ujson_r3.py holds their `workloads` to
    # its one cell until a `benchmark` PR loosens it and appends this cell (PERF.md section 7)
    for sibling in R3:
        theirs = {m["name"] for m in manifest.Cell(sibling).per_layer
                  if not m["name"].startswith("models.ujson_")}
        assert theirs <= set(listed), sorted(theirs - set(listed))
    assert not any(n.startswith("kernel.pncount") or n == "cluster.apply_lag_ms" for n in listed)
    # position and containment, never the tail: a later PR appends cells, configurations and
    # metrics (and this cell's name to their `workloads`) as new entries, without an edit here
    cells = [w["name"] for w in cell.manifest["workloads"]]
    assert cells.index(CELL) == 6  # appended: the six before it where they were
    assert listed[NEW[0]]["workloads"][:7] == cells[:7]
    assert listed[NEW[1]]["workloads"][:4] == R3 + [CELL]
    for name, better in zip(NEW, ("lower", "higher")):
        entry = listed[name]
        assert (entry["layer"], entry["moves"], entry["unit"], entry["better"],
                entry["source"]) == ("server", "ops_per_s", "ratio", better, "program_counter")
        spec = cell.layer_spec(name)
        assert spec["reader"] == "counter_ratio" and spec["name"] == name
        assert spec["den"] == ['jylis_serving_total{kind="native_bursts"}']
    names = [m["name"] for m in cell.manifest["per_layer"]]
    assert names.index(NEW[1]) == names.index(NEW[0]) + 1  # appended together, after PR 40's
    assert names[names.index(NEW[0]) - 1] == "server.sender_busy_share"
    entry = cell.manifest["configs"][6]
    assert entry["name"] == CONFIG
    assert entry["reduced"] == ["fan_out", "peer_load", "journal_max_bytes"]
    assert entry["source"] == cell.config["source"] and len(entry["source"]) <= 200
    assert "Retwis" in entry["source"] and "TAPIR" in entry["source"]


def test_the_configuration_states_three_types_and_no_weaker_guarantee_than_its_siblings():
    config = manifest.Cell(CELL).config
    siblings = {t: manifest.Cell(c).config for t, c in zip(("TREG", "TLOG", "UJSON"), R3)}
    # `type` names the first of `types` for `Cell.reference_module()` asked for none
    # (test_manifest.py does); the harness reads `types` (manifest.types_of)
    assert config["peers"] == 2 and config.get("type", "TREG") == "TREG" and "state" not in config
    assert config["node_flags"] == siblings["UJSON"]["node_flags"]
    assert list(config["reduced"]) == ["fan_out", "peer_load", "journal_max_bytes"]
    assert {"table_2", "mapping", "sizes", "record", "key_distribution", "follow_unfollow",
            "page", "counters"} <= set(config["assumed"])
    blocks = {b["type"]: b for b in config["types"]}
    assert list(blocks) == ["TREG", "TLOG", "UJSON"]
    assert blocks["TREG"]["state"] == {**siblings["TREG"]["state"], "value_bytes": 140}
    assert blocks["TLOG"]["state"] == {**siblings["TLOG"]["state"], "value_bytes": 140}
    assert blocks["UJSON"]["state"] == siblings["UJSON"]["state"]
    assert blocks["TREG"]["state"]["keys"] == 1_000_000
    # user 17's list and user 17's followers: one drawn index
    assert blocks["TLOG"]["state"]["keys"] == blocks["UJSON"]["state"]["keys"] == 1000
    assert blocks["TLOG"]["state"]["entries"] == blocks["UJSON"]["state"]["members"] == 1000
    for name, block in blocks.items():
        assert block["check"] == siblings[name]["check"]
        tiny = manifest.sized(block, True)
        assert tiny["state"]["keys"] < block["state"]["keys"] and tiny["check"]["sample"] >= 100
    tiny = {b["type"]: b["state"] for b in manifest.types_of(config, True)}
    assert tiny["TLOG"]["keys"] == tiny["UJSON"]["keys"]
    # the -r3 configurations' guarantees, letter for letter where one type states them
    g = config["guarantees"]
    assert g["durability"] == siblings["TREG"]["guarantees"]["durability"]
    assert g["peer_stalls"] == siblings["UJSON"]["guarantees"]["peer_stalls"]
    assert g["read_source"] == siblings["UJSON"]["guarantees"]["read_source"]
    for name, sib in siblings.items():
        assert sib["guarantees"]["merge"] in g["merge"], name
    assert "readable at once at the node that took it" in g["read_your_writes"]
    assert "after the local apply" in g["acknowledgement"]
    assert "all three after convergence" in g["convergence"] and "limit 0" in g["convergence"]


def test_the_traffic_is_the_mix_at_all_three_nodes_with_nothing_to_warm():
    cell = manifest.Cell(CELL)
    traffic = cell.traffic
    assert "probes" not in traffic and not traffic.get("warm_bursts")
    streams = {s["name"]: s for s in traffic["streams"]}
    assert list(streams) == ["clients", "peer_clients"]  # no sweeper
    node, peers = streams["clients"], streams["peer_clients"]
    assert (node["loop"], node["target"], node["connections"], node["depth"], node["workers"],
            node["counted"]) == ("closed", "node", 64, 1, 4, True)
    assert (peers["loop"], peers["target"], peers["counted"]) == ("open", "peers", False)
    assert node["ops"] == peers["ops"] and node["keys"] == peers["keys"] == {
        "dist": "zipfian", "theta": 0.99}
    total = sum(n for n, _c in COMMANDS.values())
    assert total == 505 and len(node["ops"]) == 7
    for op in node["ops"]:
        n, cls = COMMANDS[op["cmd"]]
        assert op["class"] == cls and op["share"] == round(n / total, 4), op
    assert abs(sum(op["share"] for op in node["ops"]) - 1) < 1e-9
    ids = next(b["state"] for b in cell.config["types"] if b["type"] == "UJSON")
    assert node["amount"] == peers["amount"] == [ids["id_base"], ids["id_base"] + ids["members"] - 1]
    tiny = next(b["state"] for b in manifest.types_of(cell.config, True) if b["type"] == "UJSON")
    for s in manifest.sized(traffic, True)["streams"]:
        assert s["amount"] == [tiny["id_base"], tiny["id_base"] + tiny["members"] - 1]
        assert s["ops"] == node["ops"]
    # 0.8 of the knee, rounded down to a multiple of 200/s, the sweep written beside it
    rate = peers["rate_per_s"]
    assert rate % 200 == 0 and "knee" in traffic["why"]
    assert str(int(rate)) in traffic["why"].replace(",", "")
    assert str(int(rate)) in cell.config["reduced"]["peer_load"].replace(",", "")
    assert "warm_seconds" in traffic["why"]


def test_the_control_fails_on_each_of_the_three_types_in_turn():
    out = control.control(CELL, 2**31 + 4242, rehearse=True, writes=4000)
    assert set(out["by_type"]) == {"TREG", "TLOG", "UJSON"} and not out["control_correct"]
    for name, r in out["by_type"].items():
        assert r["compared"] >= 100 and r["control_mismatched"] > 3 * max(1, out["limit"]), name


def test_the_three_node_three_type_cell_rehearses_with_nine_verdicts_and_its_new_metrics():
    p = run_py("--workload", CELL, "--seed", str(2**31 + 4242), "--seconds", "3", "--trace", "1",
               "--rehearse")
    assert p.returncode == 0, p.stderr[-3000:]
    result = json.loads(p.stdout.strip().splitlines()[-1])
    assert result["correct"] is True and result["failed"] == 0 and result["rehearsal"] is True
    assert result["compiles_in_window"] == 0
    verdicts = re.findall(r"correct\[(bench-\w+) (\w+)\]: mismatched reads (\d+) of (\d+)", p.stdout)
    assert len(verdicts) == 9 and {v[2] for v in verdicts} == {"0"}
    assert {(v[0], v[1]) for v in verdicts} == {
        (n, t) for n in ("bench-node", "bench-peer1", "bench-peer2")
        for t in ("TREG", "TLOG", "UJSON")}
    assert all(int(v[3]) >= 60 for v in verdicts)
    assert len(result["compared"]) == 9
    assert all(c == {"mismatched_reads": 0, "limit": 0} for c in result["compared"].values())
    metrics = {k: v["value"] for k, v in result["metrics"].items()}
    assert set(NEW) <= set(metrics), sorted(metrics)
    # depth 1: a round is one command under its own type's lock, or none
    assert 0.5 < metrics["server.locks_per_burst"] <= 1.0
    assert 0 <= metrics["server.beside_hold_frac"] < 1
    # no held lock sends a chunk to the Python path: the other-type half of busy() is gone
    assert metrics["server.busy_routed_frac"] == 0
    assert metrics["server.slept_burst_frac"] >= 0
    assert {"models.tlog_entries_per_drain", "cluster.reship_frac",
            "server.deferred_frac"} <= set(metrics)
    assert set(metrics) <= {m["name"] for m in manifest.Cell(CELL).per_layer}
    # the traced window's device plane holds programs of all three types
    ops = json.dumps(result["breakdown"])
    assert "drain_TLOG" in ops and "drain_UJSON" in ops
