"""A throwaway deployment of TWO types, laid over a copy of the benchmark:
the proof that the harness takes a configuration with a ``types`` list. It
is NOT a cell of ``BENCHMARK.json`` and defines nothing for a later PR: the
`model_config` issue that brings a mixed-type deployment names its own
source and sizes.

`TREG` (``ycsb-treg-1m-r3``'s state recipe: 1 KB records) and `PNCOUNT`
(``pncount-1m-r64``'s: 64 replica ids) side by side on equal ``keys``, read
from those two configuration files, at two CPU peers; one closed-loop
stream at the node and one open-loop stream at the peers, each a quarter
`TREG GET`, `TREG SET`, `PNCOUNT GET`, `PNCOUNT INC` on the same scrambled
Zipfian index: operation ``j`` meets user ``j``'s record or user ``j``'s
counter. `warm_bursts` is a LIST (one entry, PNCOUNT's drain shapes; the
TREG drain's are compiled at boot).

    python3 benchmark/tests/mixed_fixture.py <directory>

copies ``BENCHMARK.json`` and ``benchmark/`` there with the fixture laid
over; run the cell from that directory with the repo on ``PYTHONPATH``.
"""

from __future__ import annotations

import json
import os
import shutil
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
CONFIG, TRAFFIC = "mixed-treg-pncount-r3", "quarters-r3"
CELL = f"{CONFIG}.quarters"
OPS = [{"cmd": "TREG GET {key}", "share": 1, "class": "read"},
       {"cmd": "TREG SET {key} {value:1000} {ts}", "share": 1, "class": "write"},
       {"cmd": "PNCOUNT GET {key}", "share": 1, "class": "read"},
       {"cmd": "PNCOUNT INC {key} {amount}", "share": 1, "class": "write"}]


def _load(root: str, rel: str) -> dict:
    with open(os.path.join(root, rel)) as f:
        return json.load(f)


def config(root: str) -> dict:
    treg = _load(root, "benchmark/configs/ycsb-treg-1m-r3.json")
    pn = _load(root, "benchmark/configs/pncount-1m-r64.json")
    block = lambda c: {"type": c["type"], "state": c["state"], "check": c["check"],
                       "rehearse": {k: c["rehearse"][k] for k in ("state", "check")}}
    assert treg["state"]["keys"] == pn["state"]["keys"]
    return {"name": CONFIG, "source": "a throwaway two-type deployment (PR 41's proof)",
            "deployment": "ycsb-treg-1m-r3's cluster holding pncount-1m-r64's counters beside "
                          "its records: one user index across both types",
            "types": [block(treg), block(pn)], "peers": 2,
            "node_flags": treg["node_flags"], "guarantees": treg["guarantees"],
            "reduced": {}, "assumed": {},
            "rehearse": {"node_flags": treg["rehearse"]["node_flags"]}}


def traffic() -> dict:
    def streams(workers, connections, rate):
        zipf = {"dist": "zipfian", "theta": 0.99}
        return [{"name": "clients", "loop": "closed", "target": "node", "workers": workers,
                 "connections": connections, "depth": 1, "counted": True, "keys": zipf,
                 "amount": [1, 1000], "ops": OPS},
                {"name": "peer_clients", "loop": "open", "target": "peers",
                 "workers": min(workers, 2), "rate_per_s": rate, "keys": zipf,
                 "amount": [1, 1000], "ops": OPS}]

    def bursts(sizes):
        return [{"write": "PNCOUNT INC {key} {amount}", "read": "PNCOUNT GET {key}",
                 "write_at": "peers", "read_at": "node", "sizes": sizes, "settle_ms": 300,
                 "drain_counter": 'jylis_drain_total{type="PNCOUNT",kind="batches"}',
                 "flush_ms": 600}]

    # the rehearsal warms the same nine shapes: on a loaded sandbox a drain of its 5,000 keys
    # has been seen to pass 1,024 rows inside the window (a 2,048-row `_drain_pn` compiled there)
    sizes = [16, 32, 64, 128, 256, 512, 1024, 2048, 4096]
    return {"name": TRAFFIC, "warm_seconds": 6, "streams": streams(4, 64, 6600),
            "warm_bursts": bursts(sizes),
            "rehearse": {"warm_seconds": 2, "streams": streams(2, 8, 400),
                         "warm_bursts": bursts(sizes)}}


def lay_over(dest: str, root: str = ROOT) -> str:
    """Copy the benchmark to ``dest`` and add the fixture as NEW files and
    NEW manifest entries. Returns the cell's name."""
    os.makedirs(dest, exist_ok=True)
    bench = os.path.join(dest, "benchmark")
    shutil.copytree(os.path.join(root, "benchmark"), bench, dirs_exist_ok=True,
                    ignore=shutil.ignore_patterns("out", "__pycache__", ".pytest_cache"))
    with open(os.path.join(bench, "configs", CONFIG + ".json"), "w") as f:
        json.dump(config(root), f, indent=1)
    with open(os.path.join(bench, "traffic", TRAFFIC + ".json"), "w") as f:
        json.dump(traffic(), f, indent=1)
    m = _load(root, "BENCHMARK.json")
    m["configs"].append({"name": CONFIG, "source": "a throwaway two-type deployment",
                         "file": f"benchmark/configs/{CONFIG}.json", "reduced": [],
                         "why": "PR 41's proof"})
    m["workloads"].append({"name": CELL, "config": CONFIG, "traffic": TRAFFIC, "chips": 1,
                           "why": "PR 41's proof"})
    # the end-to-end and per-layer metrics of the three-replica TREG cell
    for e in m["end_to_end"] + m["per_layer"]:
        if "ycsb-treg-1m-r3.a" in e.get("workloads", []):
            e["workloads"].append(CELL)
    with open(os.path.join(dest, "BENCHMARK.json"), "w") as f:
        json.dump(m, f)
    return CELL


if __name__ == "__main__":
    print(lay_over(sys.argv[1]))
