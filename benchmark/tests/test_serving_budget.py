"""A served burst's stages, the Python path's commands by cause and the
repo-lock holds as the benchmark reads them: the manifest lists the twelve
metrics for their cells, each through the ``counter_ratio`` reader, and a
traced rehearsal prints each with a value. The stages tile the handlers'
share of the loop's busy time, so their sum cannot pass it; the causes
partition ``server.fallback_frac``, so two of the three cannot pass it."""

import json

import pytest

from benchmark.harness import manifest, readers
from benchmark.tests.test_rehearsal import CELLS, run_py

EVERYWHERE = {
    "server.route_us_per_cmd", "server.engine_us_per_cmd", "server.reply_write_us_per_cmd",
    "server.tail_us_per_cmd", "server.py_apply_us_per_cmd", "server.handler_share",
    "server.busy_routed_frac", "server.deferred_frac", "server.reply_bytes_per_cmd",
    "server.write_wait_us_per_cmd", "models.lock_hold_serve_share"}
# the cells whose configuration has live peers: only there is a cluster hold to read
CLUSTERED = {w for w in CELLS if manifest.Cell(w).config["peers"] > 0}
STAGES = ["server.route_us_per_cmd", "server.engine_us_per_cmd",
          "server.reply_write_us_per_cmd", "server.tail_us_per_cmd"]


def new_metrics(workload: str) -> set[str]:
    return EVERYWHERE | ({"cluster.lock_hold_share"} if workload in CLUSTERED else set())


@pytest.mark.parametrize("workload", CELLS)
def test_the_manifest_lists_the_twelve_metrics_for_their_cells(workload):
    cell = manifest.Cell(workload)
    listed = {m["name"] for m in cell.per_layer}
    assert new_metrics(workload) <= listed
    assert ("cluster.lock_hold_share" in listed) == (workload in CLUSTERED)
    for name in new_metrics(workload):
        spec = cell.layer_spec(name)
        assert spec["reader"] == "counter_ratio" and spec["name"] == name
    assert len(EVERYWHERE) == 11


@pytest.mark.parametrize("workload", ["ycsb-treg-1m.a", "ycsb-tlog-1kx1k.e"])
def test_traced_rehearsal_prints_the_serving_budget(workload):
    p = run_py("--workload", workload, "--seed", str(2**31 + 36036), "--seconds", "3",
               "--trace", "1", "--rehearse")
    assert p.returncode == 0, p.stderr[-3000:]
    result = json.loads(p.stdout.strip().splitlines()[-1])
    assert result["correct"] is True and result["failed"] == 0
    got = {k: v["value"] for k, v in result["metrics"].items()}
    for name in new_metrics(workload):
        assert name in got and got[name] >= 0, (name, sorted(got))
    # the six seams lie inside the loop's busy intervals and do not overlap
    assert 0 < got["server.handler_share"] <= got["server.loop_busy_share"] + 0.5
    # ... so a native command's four stages cannot cost more loop time than
    # a command has (the traced window's own rate: the line carries it)
    per_cmd_us = 1e4 * got["server.loop_busy_share"] / result["end_to_end_while_traced"]["ops_per_s"]
    assert 0 < sum(got[n] for n in STAGES) <= per_cmd_us * 1.05
    for name in STAGES:
        assert got[name] > 0, name
    # two of the three causes that add up to the fallback share
    assert (got["server.busy_routed_frac"] + got["server.deferred_frac"]
            <= got["server.fallback_frac"] + 0.001)
    assert got["server.reply_bytes_per_cmd"] > 0


def test_a_program_without_the_seams_leaves_the_metrics_out():
    """A parent that has none of the new seams and counters: every reader
    that reads only new names finds no sample, returns None, raises
    nothing. The three that also read a seam the parent has give what that
    seam alone gives."""
    old = {'jylis_seam_latency_seconds_sum{seam="server.native_burst"}': 0.5,
           'jylis_seam_latency_seconds_sum{seam="pipeline.reply_write"}': 0.25,
           'jylis_seam_latency_seconds_sum{seam="pipeline.parse"}': 0.25,
           'jylis_serving_total{kind="native_cmds"}': 9.0,
           'jylis_serving_total{kind="demoted_cmds"}': 1.0}
    cell = manifest.Cell("ycsb-treg-1m-r3.a")
    ctx = readers.Context(cell, {}, old, 0, 10 * 10**9, None, "", True, "")
    has_a_seam = {"server.engine_us_per_cmd", "server.reply_write_us_per_cmd",
                  "server.handler_share"}
    for name in new_metrics(cell.name) - has_a_seam:
        assert readers.read(ctx, cell.layer_spec(name)) is None, name
    assert readers.read(ctx, cell.layer_spec("server.reply_write_us_per_cmd")) == 25000.0
    assert readers.read(ctx, cell.layer_spec("server.handler_share")) == 10.0
