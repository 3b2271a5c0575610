"""jmodel CLI: `python -m scripts.jmodel` (what `make model-smoke` runs).

Modes:

* ``--smoke`` — the per-commit gate: bounded exploration of all three
  configurations (2-node, 3-node, 3-node-2-region) at the committed depths,
  asserting every invariant AND the recorded coverage floor
  (``model_min_states`` in scripts/jlint/budget.json — a refactor that
  silently collapses the explored space fails loudly). ``--budget``
  additionally enforces ``model_budget_seconds`` (exit 3 on breach),
  exactly like jlint's lint budget.
* ``--config NAME --depth N`` — one exploration, tunable (the soak
  tier runs deeper via tests/test_model.py).
* ``--replay FILE`` — replay one schedule file; exit 0 if every
  invariant holds (the regression expectation), 1 otherwise.

A violation found in any mode serialises its MINIMIZED schedule to
``jmodel_counterexample.json``: triage it, fix the defect, then commit
the schedule under ``tests/model/`` with ``"expect": "pass"`` so the
fix replays forever (the PR 3 / PR 7 found-defect discipline).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

from . import model_periods
from .explore import Explorer, replay_schedule
from .world import CONFIG_NAMES

BUDGET_PATH = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    "jlint", "budget.json",
)

# committed smoke parameters (depth, quiesce-every): deep enough that
# the three frontiers together clear the recorded model_min_states
# floor (budget.json), shallow enough for the per-commit budget. The
# v10 sessions/regions axes (a mint action per group, the regions3
# config with its bridge relays and session invariants) grow the
# frontier again on top of v9's bdec/bxfer growth; the soak tier
# (tests/test_model.py -m soak) goes deeper on every axis.
# nodes2 drops from depth 6 to 5 with the v10 mint axis: the sessions
# action roughly doubled its per-depth branching, and depth 6 alone ran
# 112k states / 305s — past the whole budget. Depth 5 keeps the config
# at ~23k states while the two NEW-coverage configs (regions, plus
# nodes3's gossip discovery) spend the rest of the box.
SMOKE_PARAMS = {
    "nodes2": (5, 24),
    "nodes3": (4, 16),
    "regions3": (4, 16),
}

COUNTEREXAMPLE_PATH = "jmodel_counterexample.json"


def _load_budget() -> dict:
    try:
        with open(BUDGET_PATH, encoding="utf-8") as f:
            return json.load(f)
    except (OSError, ValueError):
        return {}


def _run_one(config: str, depth: int, quiesce_every: int) -> "Result":
    ex = Explorer(config, depth, quiesce_every=quiesce_every)
    t0 = time.perf_counter()
    result = ex.run()
    dt = time.perf_counter() - t0
    print(
        f"jmodel: {config} depth {depth}: {result.states} distinct states, "
        f"{result.leaves} leaves ({result.quiesced} quiesced) in {dt:.1f}s"
    )
    return result


def _report_violation(result) -> None:
    v = result.violation
    print(
        f"jmodel: INVARIANT VIOLATED in {result.config}: "
        f"{v['invariant']} — {v['detail']}",
        file=sys.stderr,
    )
    with open(COUNTEREXAMPLE_PATH, "w", encoding="utf-8") as f:
        json.dump(result.schedule, f, indent=1)
        f.write("\n")
    print(
        f"jmodel: minimized schedule ({len(result.schedule['actions'])} "
        f"actions) written to {COUNTEREXAMPLE_PATH} — fix the defect, "
        "then commit it under tests/model/ with expect=pass",
        file=sys.stderr,
    )


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="jmodel")
    ap.add_argument("--config", choices=CONFIG_NAMES)
    ap.add_argument("--depth", type=int, default=5)
    ap.add_argument(
        "--quiesce-every", type=int, default=16,
        help="run the full quiescence check on every Nth depth-bound leaf",
    )
    ap.add_argument("--smoke", action="store_true",
                    help="bounded exploration of all configs + coverage floor")
    ap.add_argument("--budget", action="store_true",
                    help="fail (exit 3) past model_budget_seconds")
    ap.add_argument("--replay", metavar="FILE",
                    help="replay one schedule file")
    args = ap.parse_args(argv)

    if args.replay:
        with open(args.replay, encoding="utf-8") as f:
            data = json.load(f)
        with model_periods():
            violation = replay_schedule(data)
        if violation is None:
            print(f"jmodel: replay {args.replay}: all invariants hold")
            return 0
        print(f"jmodel: replay {args.replay}: {violation}", file=sys.stderr)
        return 1

    t0 = time.perf_counter()
    results = []
    with model_periods():
        if args.smoke:
            for config, (depth, quiesce_every) in SMOKE_PARAMS.items():
                results.append(_run_one(config, depth, quiesce_every))
                if results[-1].violation:
                    break
        elif args.config:
            results.append(
                _run_one(args.config, args.depth, args.quiesce_every)
            )
        else:
            ap.error("one of --smoke / --config / --replay is required")
    total_states = sum(r.states for r in results)
    total_s = time.perf_counter() - t0

    for r in results:
        if r.violation:
            _report_violation(r)
            return 1

    rc = 0
    if args.smoke:
        budget = _load_budget()
        floor = budget.get("model_min_states")
        print(
            f"jmodel: smoke total {total_states} distinct states across "
            f"{len(results)} configs in {total_s:.1f}s"
            + (f" (floor {floor})" if floor else "")
        )
        if floor and total_states < floor:
            print(
                f"jmodel: COVERAGE COLLAPSED — {total_states} states < "
                f"recorded floor {floor} (scripts/jlint/budget.json). A "
                "protocol or explorer change shrank the reachable space; "
                "understand why before re-recording.",
                file=sys.stderr,
            )
            rc = 1
        bound = budget.get("model_budget_seconds")
        if args.budget and bound and total_s > bound:
            print(
                f"jmodel: BUDGET EXCEEDED — {total_s:.1f}s > {bound:.1f}s "
                "(scripts/jlint/budget.json model_budget_seconds)",
                file=sys.stderr,
            )
            rc = rc or 3
    return rc


if __name__ == "__main__":
    raise SystemExit(main())
