"""`server.slept_burst_frac` as the benchmark reads it: the manifest lists
it for every cell through the ``counter_ratio`` reader; a traced
rehearsal of the cell whose clients meet their own type's drains prints it
above 0 with ``server.busy_routed_frac`` at 0 (a chunk of the held type
stays native and sleeps for the lock); a program without the counter gives
nothing and raises nothing."""

import json

import pytest

from benchmark.harness import manifest, readers
from benchmark.tests.test_rehearsal import CELLS, run_py

NAME = "server.slept_burst_frac"


@pytest.mark.parametrize("workload", CELLS)
def test_the_manifest_lists_the_metric_for_every_cell(workload):
    cell = manifest.Cell(workload)
    entry, = (m for m in cell.per_layer if m["name"] == NAME)
    assert (entry["layer"], entry["moves"], entry["better"]) == ("server", "ops_per_s", "lower")
    spec = cell.layer_spec(NAME)
    assert spec["reader"] == "counter_ratio" and spec["name"] == NAME
    assert spec["num"] == ['jylis_serving_total{kind="slept_bursts"}']


def test_traced_rehearsal_of_the_log_cell_sleeps_and_routes_nothing():
    p = run_py("--workload", "ycsb-tlog-1kx1k.e", "--seed", str(2**31 + 37037),
               "--seconds", "3", "--trace", "1", "--rehearse")
    assert p.returncode == 0, p.stderr[-3000:]
    result = json.loads(p.stdout.strip().splitlines()[-1])
    assert result["correct"] is True and result["failed"] == 0
    got = {k: v["value"] for k, v in result["metrics"].items()}
    assert 0 < got[NAME] < 1
    # every client chunk names TLOG and TLOG's is the lock the drains hold
    assert got["server.busy_routed_frac"] == 0
    assert got["server.deferred_frac"] <= got["server.fallback_frac"] + 0.001


def test_a_program_without_the_counter_leaves_the_metric_out():
    """The parent's scrape has no `slept_bursts` sample: the reader finds
    nothing to read and says None, so the driver's run of the parent with
    this PR's benchmark files stands."""
    old = {'jylis_serving_total{kind="native_cmds"}': 9.0,
           'jylis_serving_total{kind="demoted_cmds"}': 1.0,
           'jylis_serving_total{kind="busy_routed_cmds"}': 1.0}
    cell = manifest.Cell("ycsb-tlog-1kx1k.e")
    ctx = readers.Context(cell, {}, old, 0, 10 * 10**9, None, "", True, "")
    assert readers.read(ctx, cell.layer_spec(NAME)) is None
    assert readers.read(ctx, cell.layer_spec("server.busy_routed_frac")) == 0.1
    new = dict(old, **{'jylis_serving_total{kind="slept_bursts"}': 4.0})
    ctx = readers.Context(cell, {}, new, 0, 10 * 10**9, None, "", True, "")
    assert readers.read(ctx, cell.layer_spec(NAME)) == 0.4
