#!/usr/bin/env python3
"""The knee of a cell's open-loop peer stream (PERF.md section 6, PR 32's
procedure): a FRESH boot of the cell's deployment at each rate, one window
of the cell's own mix with that stream's total rate replaced, and for each
rate the stream's own latency p95 and p50 in the first and in the last
third of the window. PR 32's rule (the highest rate whose last third's p95
stays within 1.25 of its first third's and at which nothing fails) is
printed as `last_over_first`; where a stall of seconds that ENDS falls in
the window (a CPU peer's full drain of its UJSON store, about every 16 s)
that ratio reads 0.003 or 70 by where the stall lands, at every rate, and
decides nothing. `sustained` is the rule that can fail: nothing failed,
the generator kept its schedule (lateness p99 under `LATE_MS`: no
backpressure from the target), the last third's MEDIAN within 1.25 of
the first third's or under `QUEUED_MS` (a queue that grows moves the
median; a stall that ends moves only the p95 of the third it falls in),
and the rate not above the node's own ops_per_s beside it. The knee is
the highest sustained rate; a cell runs at 0.8 of it, written into the
traffic file by hand with this table.

    python3 scripts/knee_sweep.py --workload ycsb-ujson-1kx1k-r3.b --seed 1 \
        --seconds 30 --rates 500,1000,1500,2000 [--stream peer_clients]

Through the chip tool, like ``benchmark/run.py`` (``--rehearse`` for the
plumbing on the CPU). ``benchmark/sweep.py`` is the other sweep: one boot
stepped through the rates, reading the probes' lag (the fan-in cell).
"""

from __future__ import annotations

import copy
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "benchmark"))

import run as bench  # noqa: E402  (pins this process to the CPU first)
import numpy as np  # noqa: E402

from benchmark.harness import measure  # noqa: E402
from benchmark.harness.loadgen import OK  # noqa: E402


QUEUED_MS = 5.0  # a median under this is a reply that did not queue
LATE_MS = 10.0  # generator lateness p99 over this: the target pushed back


def thirds(window: measure.Window, stream: str) -> dict:
    """The stream's latency p95 and p50 (failures at the window's length)
    by thirds of the window."""
    row: dict = {}
    logs = [lg for lg in window.logs if lg.get("stream") == stream]
    third = window.seconds / 3
    for name, a, b in (("first", 0, third), ("middle", third, 2 * third),
                       ("last", 2 * third, window.seconds)):
        lats = []
        for lg in logs:
            m = (lg["sched"] >= window.t0 + a) & (lg["sched"] < window.t0 + b)
            lat = lg["lat"][m].astype(np.float64)
            lat[lg["status"][m] != OK] = window.seconds
            lats.append(lat)
        lats = np.concatenate(lats)
        row[f"p95_{name}_ms"] = measure.percentile(lats, 0.95) * 1e3
        row[f"p50_{name}_ms"] = measure.percentile(lats, 0.5) * 1e3
    row["last_over_first"] = row["p95_last_ms"] / row["p95_first_ms"]
    row["p50_last_over_first"] = row["p50_last_ms"] / row["p50_first_ms"]
    return row


def main() -> int:
    ap = bench.argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--stream", default="peer_clients")
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()
    args.trace = 0
    rows = []
    for i, rate in enumerate(float(r) for r in args.rates.split(",")):
        args.seed += i  # a seed of its own for every boot
        run = bench.Run(args)
        failed = True
        try:
            run.boot()
            traffic = copy.deepcopy(run.traffic)
            for s in traffic["streams"]:
                if s["name"] == args.stream:
                    s["rate_per_s"] = rate
            d = run.drive(traffic, args.seconds)
            win = d["window"]
            row = {"rate_per_s": rate, "seed": args.seed, **thirds(win, args.stream)}
            row["node_ops_per_s"] = win.ops_per_s()
            row["node_read_p95_ms"] = win.class_p95_ms("read")[0]
            row["failed"] = win.attempted_failed()[1]
            row["failed_by"] = win.failures()
            late = win.lateness_ms()
            row["lateness_p99_ms"] = late["p99"] if late else None
            row["compiles_in_window"] = d["compiles"]
            row["correct"] = bool(run.verify())
            row["sustained"] = bool(
                not row["failed"]
                and row["lateness_p99_ms"] < LATE_MS
                and (row["p50_last_over_first"] <= 1.25
                     or row["p50_last_ms"] < QUEUED_MS)
                and rate <= row["node_ops_per_s"]
            )
            rows.append(row)
            bench.say("knee " + json.dumps(row))
            failed = False
        finally:
            run.close(failed)
    print(json.dumps({"knee_sweep": rows}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
