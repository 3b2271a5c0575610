"""The host time budget as the benchmark reads it: a traced rehearsal of
each cell prints the loop, lock, drain-phase, cluster, flush and journal
metrics listed for that cell, with a value, beside the eight older ones.
Every one of them goes through the ``counter_ratio`` reader; on a program
without these seams (the parent of the PR that added them) the reader
finds no sample and the line leaves the metric out."""

import json
import os

import pytest

from benchmark.harness import manifest, readers
from benchmark.tests.test_rehearsal import run_py

ROOT = manifest.ROOT
NEW = {
    "pncount-1m-r64.fanin": {
        "server.loop_busy_share", "server.loop_cpu_share", "server.lock_wait_us_per_cmd",
        "models.drain_assemble_ms_per_kkeys", "models.drain_device_ms_per_kkeys",
        "models.drain_finish_ms_per_kkeys", "cluster.apply_busy_share",
        "cluster.lock_wait_ms_per_batch"},
    "ycsb-treg-1m.a": {
        "server.loop_busy_share", "server.loop_cpu_share", "server.lock_wait_us_per_cmd",
        "models.drain_assemble_ms_per_kkeys", "models.drain_device_ms_per_kkeys",
        "models.drain_finish_ms_per_kkeys", "models.flush_busy_share",
        "journal.writer_busy_share"},
}
PHASES = ["models.drain_assemble_ms_per_kkeys", "models.drain_device_ms_per_kkeys",
          "models.drain_finish_ms_per_kkeys"]


def test_the_manifest_lists_the_new_metrics_for_their_cells():
    for workload, names in NEW.items():
        listed = {m["name"] for m in manifest.Cell(workload).per_layer}
        assert names <= listed
        for name in names:
            spec = manifest.Cell(workload).layer_spec(name)
            assert spec["reader"] == "counter_ratio" and spec["name"] == name
    every = {n for names in NEW.values() for n in names}
    assert len(every) == 10


@pytest.mark.parametrize("workload", sorted(NEW))
def test_traced_rehearsal_prints_the_host_budget(workload):
    p = run_py("--workload", workload, "--seed", str(2**31 + 24024), "--seconds", "3",
               "--trace", "1", "--rehearse")
    assert p.returncode == 0, p.stderr[-3000:]
    result = json.loads(p.stdout.strip().splitlines()[-1])
    assert result["correct"] is True and result["failed"] == 0
    got = {k: v["value"] for k, v in result["metrics"].items()}
    drains = "models.drain_ms_per_kkeys" in got  # fanin's tiny window is sure to hold one
    for name in NEW[workload]:
        if name in PHASES and not drains:
            continue
        assert name in got and got[name] >= 0, (name, sorted(got))
    # a single loop cannot be busy, or on the CPU, for more than the window;
    # the thread's CPU clock also counts its select() calls, so on a
    # saturated loop it may read a little over the busy wall clock
    assert 0 < got["server.loop_busy_share"] <= 100.5
    assert 0 < got["server.loop_cpu_share"] <= min(100.5, got["server.loop_busy_share"] * 1.1)
    if workload.endswith(".fanin"):
        assert drains
        total = got["models.drain_ms_per_kkeys"]
        assert abs(sum(got[n] for n in PHASES) - total) < 0.05 * total
        gaps = dict(result["breakdown"]["idle_gaps"])
        assert {"drain_PNCOUNT", "drain_PNCOUNT.assemble", "drain_PNCOUNT.device",
                "drain_PNCOUNT.finish"} <= set(gaps)
        assert got["cluster.apply_busy_share"] > 0
    else:
        assert got["models.flush_busy_share"] > 0
        assert got["journal.writer_busy_share"] > 0
    assert "server.dispatch_us_per_cmd" in got and "device.idle_share" in got


def test_a_program_without_the_seams_leaves_the_metrics_out():
    """What the parent prints: no sample of a new seam, so every new
    reader returns None and nothing raises."""
    old = {'jylis_seam_latency_seconds_sum{seam="drain.PNCOUNT"}': 1.0,
           'jylis_seam_latency_seconds_sum{seam="pipeline.dispatch"}': 2.0,
           'jylis_serving_total{kind="native_cmds"}': 10.0,
           'jylis_serving_total{kind="demoted_cmds"}': 1.0,
           'jylis_drain_total{type="PNCOUNT",kind="keys"}': 5.0}
    cell = manifest.Cell("pncount-1m-r64.fanin")
    ctx = readers.Context(cell, {}, old, 0, 3 * 10**9, None, "", True, "")
    for names in NEW.values():
        for name in names:
            assert readers.read(ctx, cell.layer_spec(name)) is None
    assert readers.read(ctx, cell.layer_spec("models.drain_ms_per_kkeys")) == 200000.0
