"""The dry run the README promises: a later PR adds a type reference, a
configuration, a traffic mix, a per-layer metric and a cell as NEW files
plus NEW manifest entries, edits no file that is there, and the harness
runs the new cell. Done here in a temp copy, on a type (GCOUNT) the
benchmark does not know, whose reference tells two writes of one verb
apart by a literal word (``apply_op``); and again for a deployment of TWO types
(``mixed_fixture.py``: TREG records and PNCOUNT counters on one user
index), whose every type is seeded, followed by its own reference, read
back at every node and named in the verdict."""

import argparse
import filecmp
import json
import os
import shutil
import subprocess
import sys

import pytest

from benchmark import control
from benchmark.harness import manifest
from benchmark.tests import mixed_fixture
from benchmark.tests.test_rehearsal import TamperingProxy

ROOT = manifest.ROOT

GCOUNT_REFERENCE = '''
import numpy as np

NAME = "GCOUNT"


class Reference:
    def __init__(self, recipe, seed, own_rid, peer_rids, hot_keys, values=None):
        self.recipe, self.own = recipe, own_rid
        rng = np.random.default_rng([seed, 0x47])
        self.total = rng.integers(1, 1 << 62, recipe["keys"], dtype=np.uint64)
        self.base = self.total.copy()
        self.fmt = recipe["key_format"].encode()

    def key(self, i):
        return self.fmt % i

    def snapshot_batch(self):
        return [(self.fmt % i, {self.own: int(v)}) for i, v in enumerate(self.base)]

    def apply(self, verb, keys, a, b):
        raise AssertionError("a reference that defines apply_op is never handed the verb alone")

    def apply_op(self, text, keys, a, b):
        # two INCs of one stream that differ in a literal word: the drawn amount, or 7000
        word = text.split(" ")[3]
        assert text.startswith("GCOUNT INC {key} ")
        by = a.astype(np.uint64) if word == "{amount}" else np.uint64(int(word))
        np.add.at(self.total, keys, by)

    def read_command(self, i):
        return (b"GCOUNT", b"GET", self.key(i))

    def expected(self, keys):
        return [int(self.total[i]) for i in keys]

    def expected_lower_precision(self, keys):
        return [int(np.float64(self.total[i])) for i in keys]
'''


def test_a_new_cell_is_new_files_and_new_entries_only(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    bench = tmp_path / "benchmark"
    shutil.copytree(os.path.join(ROOT, "benchmark"), bench,
                    ignore=shutil.ignore_patterns("out", "__pycache__", ".pytest_cache"))
    before = {os.path.relpath(os.path.join(b, f), bench)
              for b, _d, fs in os.walk(bench) for f in fs}

    (bench / "reference" / "GCOUNT.py").write_text(GCOUNT_REFERENCE)
    state = {"keys": 3000, "key_format": "gc:%05d"}
    (bench / "configs" / "gcount-tiny.json").write_text(json.dumps({
        "name": "gcount-tiny", "source": "a throwaway deployment for the dry run",
        "type": "GCOUNT", "peers": 1, "node_flags": ["--heartbeat-time", "0.5"],
        "guarantees": {}, "reduced": {}, "assumed": {}, "state": state,
        "check": {"sample": 100, "settle_seconds": 20}}))
    (bench / "traffic" / "inc-heavy.json").write_text(json.dumps({
        "name": "inc-heavy", "warm_seconds": 1, "streams": [
            {"name": "clients", "loop": "closed", "target": "node", "workers": 1,
             "connections": 4, "depth": 2, "counted": True, "keys": {"dist": "uniform"},
             "amount": [1, 9],
             "ops": [{"cmd": "GCOUNT INC {key} {amount}", "share": 2, "class": "write"},
                     {"cmd": "GCOUNT INC {key} 7000", "share": 1, "class": "write"},
                     {"cmd": "GCOUNT GET {key}", "share": 1, "class": "read"}]}]}))
    (bench / "layer_metrics" / "journal.appends_per_kcmd.json").write_text(json.dumps({
        "name": "journal.appends_per_kcmd", "reader": "counter_ratio", "scale": 1000.0,
        "num": ['jylis_journal_total{kind="appends"}'],
        "den": ['jylis_serving_total{kind="native_cmds"}',
                'jylis_serving_total{kind="demoted_cmds"}']}))
    m = json.loads((tmp_path / "BENCHMARK.json").read_text())
    m["configs"].append({"name": "gcount-tiny", "source": "a throwaway deployment for the dry run",
                         "file": "benchmark/configs/gcount-tiny.json", "reduced": [],
                         "why": "dry run"})
    m["workloads"].append({"name": "gcount-tiny.inc-heavy", "config": "gcount-tiny",
                           "traffic": "inc-heavy", "chips": 1, "why": "dry run"})
    for e in m["end_to_end"]:
        if "workloads" in e and e["name"] in ("read_p95_ms", "write_p95_ms"):
            e["workloads"].append("gcount-tiny.inc-heavy")
    m["per_layer"].append({"name": "journal.appends_per_kcmd", "unit": "1/kcmd",
                           "better": "lower", "source": "program_counter", "layer": "journal",
                           "moves": "write_p95_ms", "workloads": ["gcount-tiny.inc-heavy"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(m))

    env = dict(os.environ, PYTHONPATH=ROOT, JAX_PLATFORMS="cpu")
    for trace in ("0", "1"):
        p = subprocess.run(
            [sys.executable, str(bench / "run.py"), "--workload", "gcount-tiny.inc-heavy",
             "--seed", "31", "--seconds", "2", "--trace", trace, "--rehearse"],
            cwd=tmp_path, env=env, capture_output=True, text=True, timeout=600)
        assert p.returncode == 0, p.stderr[-3000:]
        result = json.loads(p.stdout.strip().splitlines()[-1])
        assert result["correct"] is True and result["failed"] == 0
        if trace == "0":
            assert set(result["metrics"]) == {"ops_per_s", "read_p95_ms", "write_p95_ms", "setup_s"}
        else:
            assert result["metrics"]["journal.appends_per_kcmd"]["value"] > 0
            assert result["metrics"]["journal.appends_per_kcmd"]["unit"] == "1/kcmd"

    # nothing that was there was edited
    for rel in before:
        assert filecmp.cmp(os.path.join(ROOT, "benchmark", rel), bench / rel, shallow=False), rel


@pytest.fixture(scope="module")
def mixed(tmp_path_factory):
    """A copy of the benchmark with the two-type fixture laid over."""
    root = tmp_path_factory.mktemp("mixed")
    assert mixed_fixture.lay_over(str(root)) == mixed_fixture.CELL
    return str(root)


NODES = ("bench-node", "bench-peer1", "bench-peer2")


@pytest.mark.parametrize("trace", [0, 1])
def test_a_two_type_cell_rehearses_correct_at_every_node_for_both_types(mixed, trace):
    env = dict(os.environ, PYTHONPATH=ROOT, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(mixed, "benchmark", "run.py"), "--workload",
         mixed_fixture.CELL, "--seed", str(2**31 + 4100 + trace), "--seconds", "3",
         "--trace", str(trace), "--rehearse"],
        cwd=mixed, env=env, capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    assert "state: 5000 TREG keys + 5000 PNCOUNT keys from the seed" in p.stdout
    result = json.loads(p.stdout.strip().splitlines()[-1])
    assert result["correct"] is True and result["failed"] == 0 and result["attempted"] > 1000
    assert result["compiles_in_window"] == 0
    # every (node, type) was compared, beside its limit, last in the line and on stderr
    assert list(result)[-1] == "compared"
    assert set(result["compared"]) == {f"{n}.{t}" for n in NODES for t in ("TREG", "PNCOUNT")}
    assert all(c == {"mismatched_reads": 0, "limit": 0} for c in result["compared"].values())
    assert p.stderr.strip().splitlines()[-1].startswith("compared bench-peer2.PNCOUNT:")
    for node in NODES:
        for type_name in ("TREG", "PNCOUNT"):
            assert f"correct[{node} {type_name}]: mismatched reads 0 of" in p.stdout
    if trace:
        assert {"server.busy_routed_frac", "server.fallback_frac",
                "models.drain_busy_share"} <= set(result["metrics"])
    else:
        assert set(result["metrics"]) == {"ops_per_s", "read_p95_ms", "write_p95_ms", "setup_s"}


@pytest.mark.parametrize("victim,other", [("TREG", "PNCOUNT"), ("PNCOUNT", "TREG")])
def test_a_tampered_type_is_caught_and_named_and_the_other_is_not(mixed, victim, other):
    """One type's writes altered under the timed path, on their way to the
    node: `correct` is false, the verdicts name THAT type at every replica
    (the node ships what it took) and clear the other."""
    from benchmark import run as bench

    args = argparse.Namespace(workload=mixed_fixture.CELL, seed=4177, seconds=3.0, trace=0,
                              rehearse=True)
    run = bench.Run(args, root=mixed)
    for block in run.types:
        block["check"]["settle_seconds"] = 3
    try:
        run.boot()
        proxy = TamperingProxy(run.node.port, only_type=victim.encode())
        run.load_ports[run.node.name] = proxy.port
        run.drive(run.traffic, args.seconds)
        assert proxy.tampered >= 5
        assert run.verify() is False
        proxy.listener.close()
        for node in NODES:
            assert run.compared[f"{node}.{victim}"][0] > 0, (node, run.compared)
            assert run.compared[f"{node}.{other}"] == [0, 0], (node, run.compared)
    finally:
        run.close(False)


@pytest.mark.parametrize("seed", [1, 2**31 + 7, 99])
def test_the_control_fails_for_each_type_of_a_two_type_cell(mixed, seed):
    out = control.control(mixed_fixture.CELL, seed, rehearse=True, writes=4000, root=mixed)
    assert set(out["by_type"]) == {"TREG", "PNCOUNT"} and not out["control_correct"]
    for type_name, r in out["by_type"].items():
        assert r["compared"] >= 200 and r["control_mismatched"] > 3, (type_name, r)


def test_the_fixture_is_new_files_and_new_entries_only(mixed):
    bench = os.path.join(mixed, "benchmark")
    there = {os.path.relpath(os.path.join(b, f), bench) for b, _d, fs in os.walk(bench)
             for f in fs if "/out" not in b and "__pycache__" not in b}
    new = {f"configs/{mixed_fixture.CONFIG}.json", f"traffic/{mixed_fixture.TRAFFIC}.json"}
    for rel in there - new:
        assert filecmp.cmp(os.path.join(ROOT, "benchmark", rel), os.path.join(bench, rel),
                           shallow=False), rel
    assert new <= there
    before = manifest.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    after = manifest.load_json(os.path.join(mixed, "BENCHMARK.json"))
    assert after["workloads"][:-1] == before["workloads"]
    assert after["configs"][:-1] == before["configs"]
