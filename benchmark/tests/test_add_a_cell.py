"""The dry run the README promises: a later PR adds a type reference, a
configuration, a traffic mix, a per-layer metric and a cell as NEW files
plus NEW manifest entries, edits no file that is there, and the harness
runs the new cell. Done here in a temp copy, on a type (GCOUNT) the
benchmark does not know."""

import filecmp
import json
import os
import shutil
import subprocess
import sys

from benchmark.harness import manifest

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
        assert verb == "INC"
        np.add.at(self.total, keys, a.astype(np.uint64))

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
             "ops": [{"cmd": "GCOUNT INC {key} {amount}", "share": 3, "class": "write"},
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
