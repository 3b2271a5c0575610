"""The harness offers the six one-type cells byte-identical work to what
PR 41's parent offered them: ``data/identity.json`` holds, recorded from the
parent's harness at each cell's ``rehearse`` sizes and two seeds, the
SHA-256 of the snapshot file, of the first 10,000 commands of every stream
worker (rendered on a fixed clock, with each command's logged op, key and
two arguments), of the first 500 probes' reads and writes and of the warm
bursts' commands. A harness change that moves one of them gives the node
other work, and the ledger's lines before and after it no longer compare.

The recording was made by ``record_identity.py`` (beside this file), which
drives the PARENT's harness through the parent's own functions; where git
has the parent's commit, the second test makes the recording again from it
and holds it to the committed file.

A cell that a later PR adds has no recording: the parent to hold it to is
the PR that brings it."""

import argparse
import hashlib
import json
import os
import subprocess
import sys

import pytest

from benchmark import run as bench
from benchmark.harness import loadgen

HERE = os.path.dirname(os.path.abspath(__file__))
with open(os.path.join(HERE, "data", "identity.json")) as f:
    RECORDED = json.load(f)
COMMANDS, PROBES = 10_000, 500
T_BEGIN = 1000.0


class FakeLink:
    def __init__(self, ident: int):
        self.ident, self.seq = ident, 0


def sha(parts) -> str:
    h = hashlib.sha256()
    for p in parts:
        h.update(p)
    return h.hexdigest()


def fingerprint(workload: str, seed: int) -> dict:
    args = argparse.Namespace(workload=workload, seed=seed, seconds=3.0, trace=0, rehearse=True)
    run = bench.Run(args)
    out: dict = {"streams": {}}
    try:
        run.write_state()
        with open(os.path.join(run.node.data_dir, "snapshot.jylis"), "rb") as f:
            out["snapshot"] = hashlib.sha256(f.read()).hexdigest()
        for cfg in run.worker_cfgs(run.traffic, T_BEGIN, T_BEGIN + 5.0):
            if cfg["kind"] == "probe":
                keys, amounts, write_tpl, read_tpl, fmt = loadgen.probe_draws(cfg, PROBES)
                out["probes"] = sha(cmd for k, a in zip(keys, amounts)
                                    for cmd in (read_tpl.render(fmt % int(k)),
                                                write_tpl.render(fmt % int(k), int(a))))
                continue
            n_links = cfg["connections"] if cfg["kind"] == "closed" else len(cfg["targets"])
            links = [FakeLink(cfg["conn_base"] + i) for i in range(n_links)]
            draws = loadgen.Draws(cfg, COMMANDS)
            parts = []
            for k in range(COMMANDS):
                data, *logged = draws.next(links[k % n_links], T_BEGIN + k * 0.00025)
                parts += [data, b"%d %d %d %d;" % tuple(logged)]
            out["streams"][f"{cfg['stream']}/{cfg['worker']}"] = sha(parts)
        bursts = [cmd for *_, cmds in run.warm_bursts() for cmd in cmds]
        if bursts:
            out["warm_bursts"] = sha(bursts)
    finally:
        run.close(False)
    return out


@pytest.mark.parametrize("workload", sorted(RECORDED))
def test_the_harness_offers_the_cell_the_work_the_parent_offered(workload):
    for seed, want in RECORDED[workload].items():
        assert fingerprint(workload, int(seed)) == want, (workload, seed)


PARENT = "ad57760f2f8ff36e114be2ea5de3ad2c8d833c50"  # PR 41's parent (PR 40's tree)


def test_the_recording_is_what_the_parents_harness_makes(tmp_path):
    """``record_identity.py`` against a checkout of the parent reproduces
    ``data/identity.json`` byte for byte. A checkout that is no git
    repository (the driver's), or has lost the commit, cannot show it."""
    root = bench.ROOT
    parent = tmp_path / "parent"
    parent.mkdir()
    tar = tmp_path / "parent.tar"
    try:
        p = subprocess.run(["git", "-C", root, "archive", "-o", str(tar), PARENT, "BENCHMARK.json",
                            "benchmark", "jylis_tpu", "native"], capture_output=True, text=True)
        why = p.stderr.strip()[-200:] if p.returncode else ""
    except OSError as e:
        why = str(e)
    if why:
        pytest.skip(f"git has no parent commit here: {why}")
    subprocess.run(["tar", "-xf", str(tar), "-C", str(parent)], check=True)
    out = tmp_path / "identity.json"
    p = subprocess.run([sys.executable, os.path.join(HERE, "record_identity.py"), str(parent),
                        str(out)], capture_output=True, text=True, timeout=600,
                       env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert p.returncode == 0, p.stderr[-3000:]
    with open(os.path.join(HERE, "data", "identity.json"), "rb") as f:
        assert out.read_bytes() == f.read()
