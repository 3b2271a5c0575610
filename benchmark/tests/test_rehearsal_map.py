"""`ycsb-map-1mx10.a`: its entries in the manifest and the files they name,
and its rehearsal (one node on the CPU, 3,000 records x 10 fields): the
first cell on a composed type. `correct` with no failed operation, the two
per-layer metrics the cell brought in the traced line, the commands settled
by the engine (no more on the Python path than the one-value YCSB cell
sends), the MAP drain's programs in the traced window, and the control
failing as it must."""

import json
import re

from benchmark import control
from benchmark.harness import manifest
from benchmark.tests.test_rehearsal import run_py

CELL, CONFIG, TRAFFIC = "ycsb-map-1mx10.a", "ycsb-map-1mx10", "ycsb-a-fields"
SIBLING = "ycsb-treg-1m.a"
NEW = ("models.map_fields_per_drain", "journal.bytes_per_map_set")


def test_the_manifest_lists_the_cell_its_configuration_and_its_metrics():
    cell = manifest.Cell(CELL)
    assert cell.chips == 1 and cell.entry["traffic"] == TRAFFIC
    assert {m["name"] for m in cell.end_to_end} == {"ops_per_s", "read_p95_ms", "write_p95_ms",
                                                    "setup_s"}
    listed = {m["name"]: m for m in cell.per_layer}
    # everything the one-value YCSB cell reads has something to read here, but TREG's roofline
    theirs = {m["name"] for m in manifest.Cell(SIBLING).per_layer}
    assert theirs - set(listed) == {"kernel.treg_drain_roofline"}
    assert set(listed) - theirs == set(NEW)
    # position and containment, never the tail: a later PR appends as new entries
    cells = [w["name"] for w in cell.manifest["workloads"]]
    assert cells.index(CELL) == 7  # appended: the seven before it where they were
    configs = [c["name"] for c in cell.manifest["configs"]]
    assert configs.index(CONFIG) == 7
    entry = cell.manifest["configs"][7]
    assert entry["reduced"] == ["replicas", "journal_max_bytes"]
    assert entry["source"] == cell.config["source"] and len(entry["source"]) <= 200
    for word in ("YCSB core workload A", "fieldcount 10", "fieldlength 100",
                 "writeallfields=false", "HMSET one field", "HGETALL"):
        assert word in entry["source"]
    for name in ("read_p95_ms", "write_p95_ms"):
        e2e = next(m for m in cell.manifest["end_to_end"] if m["name"] == name)
        assert CELL in e2e["workloads"]
    names = [m["name"] for m in cell.manifest["per_layer"]]
    assert names.index(NEW[1]) == names.index(NEW[0]) + 1  # appended together, after PR 44's
    assert names[names.index(NEW[0]) - 1] == "server.inline_burst_frac"
    for name, layer, unit in zip(NEW, ("repos and drains", "journal"), ("ratio", "B")):
        m = listed[name]
        assert (m["layer"], m["moves"], m["unit"], m["source"], m["workloads"][0]) == (
            layer, "write_p95_ms", unit, "program_counter", CELL)
        spec = cell.layer_spec(name)
        assert spec["reader"] == "counter_ratio" and spec["name"] == name
    assert cell.layer_spec(NEW[1])["den"] == ['jylis_drain_total{type="MAP",kind="sets"}']
    for m in cell.manifest["per_layer"]:  # the cell is appended, wherever it is listed
        if CELL in m.get("workloads", []) and m["name"] not in NEW:
            assert m["workloads"].index(CELL) == len([w for w in m["workloads"] if w in cells[:7]])


def test_the_configuration_is_ycsbs_record_with_the_one_value_cells_guarantees_and_the_fields():
    config = manifest.Cell(CELL).config
    sibling = manifest.Cell(SIBLING).config
    assert config["type"] == "MAP" and config["peers"] == 0 and config["architecture"] is None
    assert config["state"] == {"keys": 1_000_000, "fields": 10, "value_bytes": 100,
                               "key_format": "user%07d",
                               "ts_ceiling": sibling["state"]["ts_ceiling"]}
    # the sibling's flags and no other: the field table sizes itself from what it restores
    assert config["node_flags"] == sibling["node_flags"]
    assert list(config["reduced"]) == ["replicas", "journal_max_bytes"]
    assert {"mapping", "timestamps", "snapshot", "device_state"} <= set(config["assumed"])
    for k, v in sibling["guarantees"].items():
        if k != "merge":
            assert config["guarantees"][k] == v, k
    g = config["guarantees"]
    assert "never displace each other" in g["field_isolation"]
    assert "last writer wins on exact u64 timestamps" in g["merge"] and "greater value" in g["merge"]
    assert "every live field" in g["read"]
    assert config["check"] == sibling["check"]
    tiny = manifest.sized(config, True)
    assert tiny["state"]["keys"] == 3000 and tiny["state"]["fields"] == 10
    assert tiny["check"]["sample"] >= 200  # test_control.py's floor
    assert tiny["node_flags"] == config["node_flags"]


def test_the_traffic_is_workload_a_with_one_field_written_an_update():
    traffic = manifest.Cell(CELL).traffic
    ycsb_a = manifest.Cell(SIBLING).traffic
    assert "probes" not in traffic and not traffic.get("warm_bursts")
    assert traffic["warm_seconds"] == ycsb_a["warm_seconds"]
    stream, = traffic["streams"]
    theirs, = ycsb_a["streams"]
    for k in ("loop", "target", "workers", "connections", "depth", "counted", "keys"):
        assert stream[k] == theirs[k], k
    assert (stream["connections"], stream["depth"], stream["keys"]) == (
        64, 1, {"dist": "zipfian", "theta": 0.99})
    ops = stream["ops"]
    assert ops[0] == {"cmd": "MAP TREG GETALL {key}", "share": 0.5, "class": "read"}
    assert [op["cmd"] for op in ops[1:]] == [
        "MAP TREG SET {key} field%d {value:100} {ts}" % j for j in range(10)]
    assert all(op["share"] == 0.05 and op["class"] == "write" for op in ops[1:])
    assert abs(sum(op["share"] for op in ops) - 1) < 1e-9
    assert manifest.sized(traffic, True)["streams"][0]["ops"] == ops


def test_the_control_fails_on_the_cell():
    out = control.control(CELL, 2**31 + 4646, rehearse=True, writes=4000)
    assert set(out["by_type"]) == {"MAP"} and not out["control_correct"]
    assert out["compared"] >= 200 and out["control_mismatched"] > 3 * max(1, out["limit"])


def test_the_cell_rehearses_correct_with_its_new_metrics_and_the_engine_settling_the_commands():
    p = run_py("--workload", CELL, "--seed", str(2**31 + 4646), "--seconds", "3", "--trace", "1",
               "--rehearse")
    assert p.returncode == 0, p.stderr[-3000:]
    result = json.loads(p.stdout.strip().splitlines()[-1])
    assert result["correct"] is True and result["failed"] == 0 and result["rehearsal"] is True
    assert result["compiles_in_window"] == 0 and result["attempted"] > 1000
    verdicts = re.findall(r"correct\[(bench-\w+) (\w+)\]: mismatched reads (\d+) of (\d+)", p.stdout)
    assert [(v[0], v[1], v[2]) for v in verdicts] == [("bench-node", "MAP", "0")]
    assert int(verdicts[0][3]) >= 200
    assert result["compared"] == {"bench-node.MAP": {"mismatched_reads": 0, "limit": 0}}
    metrics = {k: v["value"] for k, v in result["metrics"].items()}
    assert set(NEW) <= set(metrics), sorted(metrics)
    assert set(metrics) <= {m["name"] for m in manifest.Cell(CELL).per_layer}
    # every drain in the window is a threshold's: 4,096 changed field rows
    assert metrics["models.map_fields_per_drain"] == 4096
    # one field's unit, not the record's 1 KB (and less where a hot field coalesces in a flush)
    assert 20 < metrics["journal.bytes_per_map_set"] < 200
    # the engine settles the three forms: what reaches the Python path is the write that
    # meets the drain threshold, nothing else
    assert metrics["server.fallback_frac"] < 0.01 and metrics["server.busy_routed_frac"] == 0
    assert metrics["server.deferred_frac"] <= metrics["server.fallback_frac"] + 0.001
    assert metrics["server.locks_per_burst"] <= 1.0
    # a read reply is the record: ten names, ten values of 100 B, ten timestamps
    assert 500 < metrics["server.reply_bytes_per_cmd"] < 900
    # the traced window holds the MAP drain with its device phase
    assert "drain_MAP.device" in json.dumps(result["breakdown"])
