"""How ``data/identity.json`` was made: the identity fingerprints of the six
cells, recorded through the harness of PR 41's PARENT (commit ad57760, PR
40's tree), which has none of the functions ``test_identity.py`` calls on
the changed harness. This file drives the parent's own (`Run.recipe`, its
one-reference `state.write_snapshots`, module-level `worker_configs`, the
probe and warm-burst draws spelt out as `run_probe` and `warm_shapes` made
them there):

    mkdir -p .scratch/parent41 && git archive ad57760 | tar -x -C .scratch/parent41
    python3 benchmark/tests/record_identity.py .scratch/parent41 identity.json

At each cell's ``rehearse`` sizes and seeds 1 and 2**31 + 41: SHA-256 of the
snapshot file, of the first 10,000 commands of every stream worker (on a
fixed clock, with each command's logged op, key and two arguments), of the
first 500 probes' reads and writes, of the warm bursts' commands. It runs on
the CPU in ~15 s and boots no node. `test_identity.py` runs it against the
parent where git has that commit and holds its output to the committed file,
byte for byte."""
import argparse
import hashlib
import json
import os
import sys

ROOT = sys.argv[1]
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "benchmark"))
import run as bench  # noqa: E402
import numpy as np  # noqa: E402
from benchmark.harness import gen, loadgen, manifest, state  # noqa: E402

SEEDS = [1, 2**31 + 41]
COMMANDS = 10_000
T_BEGIN = 1000.0


class FakeLink:
    def __init__(self, ident):
        self.ident, self.seq = ident, 0


def sha(parts):
    h = hashlib.sha256()
    for p in parts:
        h.update(p)
    return h.hexdigest()


def fingerprint(workload, seed):
    args = argparse.Namespace(workload=workload, seed=seed, seconds=3.0, trace=0, rehearse=True)
    run = bench.Run(args)
    out = {}
    try:
        recipe, config = run.recipe, run.config
        hot = gen.hottest(recipe["keys"], recipe["keys"])
        ref = run.cell.reference_module().Reference(
            recipe, seed, bench.replica_id(run.node.addr),
            [bench.replica_id(p.addr) for p in run.peers], hot, gen.Values(seed))
        state.write_snapshots(ref, config["type"], [n.data_dir for n in run.everyone])
        with open(os.path.join(run.node.data_dir, "snapshot.jylis"), "rb") as f:
            out["snapshot"] = hashlib.sha256(f.read()).hexdigest()
        # streams
        traffic = run.traffic
        targets = {"node": [7001], "peers": [7002 + i for i in range(len(run.peers))]}
        base = {"seed": seed, "n_keys": recipe["keys"], "key_format": recipe["key_format"],
                "t_begin": T_BEGIN, "t1": T_BEGIN + 5.0}
        cfgs = bench.worker_configs(traffic, targets, base)
        out["streams"] = {}
        for cfg in cfgs:
            if cfg["kind"] == "probe":
                p = cfg["probe"]
                rng = np.random.default_rng([cfg["seed"], cfg["stream_index"], cfg["worker"]])
                total = 500
                keys = gen.KeyDist(p["keys"], cfg["n_keys"]).draw(rng, total)
                lo, hi = p["amount"]
                amounts = rng.integers(lo, hi + 1, total, dtype=np.uint64)
                w, r = gen.Template(p["write"]), gen.Template(p["read"])
                kf = cfg["key_format"].encode()
                parts = []
                for k, a in zip(keys, amounts):
                    kb = kf % int(k)
                    parts += [r.render(kb), w.render(kb, int(a))]
                out["probes"] = sha(parts)
                continue
            n_links = cfg["connections"] if cfg["kind"] == "closed" else len(cfg["targets"])
            links = [FakeLink(cfg["conn_base"] + i) for i in range(n_links)]
            draws = loadgen.Draws(cfg, COMMANDS)
            parts = []
            for k in range(COMMANDS):
                data, op, key, a, b = draws.next(links[k % n_links], T_BEGIN + k * 0.00025)
                parts.append(data)
                parts.append(b"%d %d %d %d;" % (op, key, a, b))
            out["streams"][f"{cfg['stream']}/{cfg['worker']}"] = sha(parts)
        spec = traffic.get("warm_bursts")
        if spec:
            write_tpl = gen.Template(spec["write"])
            rng = np.random.default_rng([seed, 0x5742])
            fmt = recipe["key_format"].encode()
            parts = []
            for size in spec["sizes"]:
                size = min(size, recipe["keys"])
                keys = rng.choice(recipe["keys"], size, replace=False)
                amounts = rng.integers(1, 1000, size, dtype=np.uint64)
                parts += [write_tpl.render(fmt % int(k), int(a)) for k, a in zip(keys, amounts)]
            out["warm_bursts"] = sha(parts)
    finally:
        run.close(False)
    return out


if __name__ == "__main__":
    cells = [w["name"] for w in manifest.load_json(os.path.join(ROOT, "BENCHMARK.json"))["workloads"]]
    table = {c: {str(s): fingerprint(c, s) for s in SEEDS} for c in cells}
    with open(sys.argv[2], "w") as f:
        json.dump(table, f, indent=1, sort_keys=True)
