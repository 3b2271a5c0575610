"""The rehearsal of `ycsb-tlog-1kx1k-r3.e` (three nodes on the CPU, tiny
sizes): `correct` is asked of all three replicas, the node drains at its
bounds and not at a trim (the sweeper is at the peers), and the three
per-layer metrics this cell brought are in the traced line beside the ones
it shares."""

import json
import os
import re

from benchmark.harness import manifest
from benchmark.tests.test_rehearsal import ROOT, run_py

CELL = "ycsb-tlog-1kx1k-r3.e"
NEW = ("cluster.tlog_apply_us_per_entry", "models.tlog_overdue_drain_frac",
       "models.tlog_foreign_entry_frac")


def test_the_three_node_tlog_cell_rehearses_with_its_new_metrics():
    p = run_py("--workload", CELL, "--seed", str(2**31 + 3232), "--seconds", "3", "--trace", "1",
               "--rehearse")
    assert p.returncode == 0, p.stderr[-3000:]
    result = json.loads(p.stdout.strip().splitlines()[-1])
    assert result["correct"] is True and result["failed"] == 0 and result["rehearsal"] is True
    assert result["compiles_in_window"] == 0
    for node in ("bench-node", "bench-peer1", "bench-peer2"):
        m = re.search(rf"correct\[{node} TLOG\]: mismatched reads (\d+) of (\d+)", p.stdout)
        assert m and m.group(1) == "0" and int(m.group(2)) > 100, node
    metrics = {k: v["value"] for k, v in result["metrics"].items()}
    assert set(NEW) <= set(metrics), sorted(metrics)
    # no trim is taken at the node: EXACT over the whole boot, from the tallies of the node's
    # shutdown line (a drain that a TRIM / TRIMAT / CLR forced counts there, in or out of the
    # window), and at least one drain was started by a bound
    with open(os.path.join(ROOT, "benchmark", "out", "logs", CELL, "bench-node.log")) as f:
        tally = re.search(r"merge metrics: TLOG: (\d+) drains, .*? (\d+) trims, .*? (\d+) overdue",
                          f.read())
    drains, trims, overdue = map(int, tally.groups())
    assert trims == 0 and 1 <= overdue <= drains, tally.group(0)
    # the window's reading is overdue begun / batches ended between two scrapes 3 s apart: 1.0,
    # or a/b one apart when a drain lies astride an edge (seen: 2 begun, 1 ended = 2.0). Nothing
    # else: a and b are bounded by the boot's own counts
    assert metrics["models.tlog_overdue_drain_frac"] in {
        a / b for b in range(1, drains + 1) for a in range(overdue + 1) if abs(a - b) <= 1}
    assert 0.0 < metrics["models.tlog_foreign_entry_frac"] < 1.0
    assert metrics["cluster.tlog_apply_us_per_entry"] > 0 and metrics["cluster.reship_frac"] == 0
    assert metrics["models.tlog_entries_per_drain"] > 1 and metrics["models.tlog_row_gathers_per_kcmd"] == 0
    cell = manifest.Cell(CELL)
    assert set(metrics) <= {m["name"] for m in cell.per_layer}
    assert {m["name"] for m in cell.end_to_end} == {"ops_per_s", "read_p95_ms", "write_p95_ms", "setup_s"}
    streams = {s["name"]: s for s in cell.traffic["streams"]}
    assert streams["sweeper"]["target"] == "peers" and streams["peer_clients"]["target"] == "peers"
    one_node = manifest.Cell("ycsb-tlog-1kx1k.e")
    assert streams["clients"] == one_node.traffic["streams"][0], "the pair differs only in what the peers do"
    assert cell.config["state"] == one_node.config["state"]
