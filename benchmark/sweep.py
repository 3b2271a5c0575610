#!/usr/bin/env python3
"""The fan-in rate sweep: boot one deployment, step the open-loop stream's
total rate, and print for each rate the probes' lag in the first and last
third of the window. The knee is the highest rate at which the last third
is within 1.25x of the first (no growing backlog); the cell runs at 0.8 of
it, written into the traffic file by hand with the table in PERF.md.

    python3 benchmark/sweep.py --workload pncount-1m-r64.fanin --seed 1 \
        --seconds 20 --rates 20000,40000,60000
"""

from __future__ import annotations

import copy
import json
import sys

import run as bench  # noqa: E402  (pins this process to the CPU first)
import numpy as np  # noqa: E402

from benchmark.harness import measure  # noqa: E402


def main() -> int:
    ap = bench.argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--stream", default="peer_writes")
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()
    args.trace = 0
    run = bench.Run(args)
    failed = True
    rows = []
    try:
        run.boot()
        for rate in [float(r) for r in args.rates.split(",")]:
            traffic = copy.deepcopy(run.traffic)
            for s in traffic["streams"]:
                if s["name"] == args.stream:
                    s["rate_per_s"] = rate
            d = run.drive(traffic, args.seconds)
            win = d["window"]
            row = {"rate_per_s": rate, "ops_per_s": win.ops_per_s(),
                   "read_p95_ms": win.class_p95_ms("read")[0]}
            for lg in win.logs:
                if lg["kind"] != "probe":
                    continue
                third = args.seconds / 3
                for name, a, b in (("first", 0, third), ("middle", third, 2 * third),
                                   ("last", 2 * third, args.seconds),
                                   ("whole", 0, args.seconds)):
                    m = (lg["sent"] >= win.t0 + a) & (lg["sent"] < win.t0 + b)
                    lag = np.where(lg["status"][m] == 1, lg["lat"][m], lg["timeout_s"])
                    row[f"lag_{name}_p50_ms"] = measure.percentile(lag, 0.5) * 1e3
                    row[f"lag_{name}_p95_ms"] = measure.percentile(lag, 0.95) * 1e3
                    row[f"probes_{name}"] = int(m.sum())
            late = win.lateness_ms()
            row["lateness_p99_ms"] = late["p99"] if late else None
            drained = sum(d["after"].get(k, 0) - d["before"].get(k, 0) for k in d["after"]
                          if k.startswith("jylis_drain_total") and 'kind="batches"' in k)
            row["drains"] = drained
            rows.append(row)
            bench.say("sweep " + json.dumps(row))
        correct = run.verify()
        bench.say(f"sweep correct: {correct}")
        failed = False
    finally:
        run.close(failed)
    print(json.dumps({"sweep": rows, "correct": correct}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
