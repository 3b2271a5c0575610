#!/usr/bin/env python3
"""The control of `correct`: the reference put in the program's place and
computed in the nearest precision below the one the configuration states
(float64 in place of exact 64-bit integers). It has to come out NOT correct:
for each type the configuration states in turn, that type's answers in the
lower precision (the others exact) must fail the comparison.

    python3 benchmark/control.py --workload <name> --seed <n> [--rehearse]

No node runs: the cell's state is made from the seed at the cell's size, a
window's worth of acknowledged writes is drawn from the cell's traffic mix
by the same generator, the keys are chosen as a run chooses them, and the
exact answers are compared with the lower-precision answers under the same
limit (0 mismatched reads). The benchmark's own runs never run this.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmark.harness import check, gen, manifest  # noqa: E402


def synthetic_logs(traffic: dict, recipes: dict, seed: int, writes: int) -> list[dict]:
    """Acknowledged writes as the workers would log them: ``writes`` in
    all, split over the mix's write ops by their shares. ``recipes``: type
    name -> its state recipe."""
    rng = np.random.default_rng([seed, 0x43544C])
    logs = []
    for si, stream in enumerate(traffic["streams"]):
        templates, probs = gen.op_table(stream["ops"])
        classes = [op["class"] for op in stream["ops"]]
        if "write" not in classes:
            continue
        ops = rng.choice(len(probs), writes, p=probs).astype(np.uint8)
        keys = gen.draw_keys(rng, stream["keys"],
                             [recipes[t.type_name]["keys"] for t in templates], ops)
        lo, hi = stream.get("amount", [1, 1])
        a = rng.integers(lo, hi + 1, writes, dtype=np.uint64)
        b = np.zeros(writes, np.uint64)
        if any("ts" in t.fields for t in templates):
            conn = rng.integers(0, stream.get("connections", 1), writes)
            when = np.sort(rng.random(writes)) * 30.0
            a = np.array([gen.make_ts(float(t), i, int(c))
                          for i, (t, c) in enumerate(zip(when, conn))], np.uint64)
            b = (conn.astype(np.uint64) << np.uint64(40)) | np.arange(writes, dtype=np.uint64)
        logs.append({"op": ops, "key": keys, "a": a, "b": b,
                     "acked": np.ones(writes, bool), "classes": classes,
                     "types": [t.type_name for t in templates],
                     "verbs": [t.verb for t in templates],
                     "texts": [t.text for t in templates]})
    return logs


def control(workload: str, seed: int, rehearse: bool, writes: int,
            root: str = manifest.ROOT) -> dict:
    cell = manifest.Cell(workload, root)
    config = manifest.sized(cell.config, rehearse)
    types = manifest.types_of(config, rehearse)
    recipes = {t["type"]: t["state"] for t in types}
    traffic = manifest.sized(cell.traffic, rehearse)
    refs, hot = check.references(cell, recipes, seed, 1, [2, 3, 4][: config["peers"]])
    fed = check.feed_reference(refs, synthetic_logs(traffic, recipes, seed, writes))
    by_type = {}
    for block in types:
        name = block["type"]
        keys = check.choose_keys(seed, block, *fed[name], hot[name])
        exact = refs[name].expected(keys)
        lower = refs[name].expected_lower_precision(keys)
        by_type[name] = {"compared": len(keys),
                         "control_mismatched": sum(1 for e, g in zip(exact, lower) if e != g)}
    # the control must fail for EACH type: the weakest one is the reading
    weakest = min(by_type.values(), key=lambda r: r["control_mismatched"])
    return {"workload": workload, "seed": seed, **weakest, "limit": check.LIMIT,
            "control_correct": weakest["control_mismatched"] <= check.LIMIT,
            "by_type": by_type}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--writes", type=int, default=500_000)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()
    out = control(args.workload, args.seed, args.rehearse, args.writes)
    print(json.dumps(out))
    return 0 if not out["control_correct"] else 1


if __name__ == "__main__":
    sys.exit(main())
