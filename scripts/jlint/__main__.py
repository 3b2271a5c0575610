"""jlint CLI: `python -m scripts.jlint` (what `make lint` runs).

Exit 0 only when every pass is clean: no unsuppressed finding, no stale
baseline entry or inline suppression, no manifest drift. One semantic
core (scripts/jlint/core.py) is built per run — content-hash-cached
ASTs, call graph, per-function summaries — and all ten passes (1-5, 7-11)
consume it.

* ``--write-manifest`` regenerates every committed manifest (parity,
  failpoints, metrics, codec, lattice + the generated lattice
  property harness, protocol atlas, semantics + the generated
  differential fuzz harness) in place and exits: commit the diff.
* ``--write-corpus`` regenerates the golden codec corpus
  (tests/golden/codec_corpus.json) from the current codec manifest and
  the golden semantic-fuzz corpus (tests/golden/semfuzz_corpus.json)
  from the current semantics manifest (imports the product; run after
  any --write-manifest that changed either manifest).
* ``--out PATH`` writes machine-readable findings JSON (rule, path,
  line, message, suppressed) plus per-pass wall times — the CI artifact
  finding-count drift is diffed across.
* ``--budget`` enforces the recorded wall-time bound in
  scripts/jlint/budget.json: ten passes must not erode the commit
  loop, so `make lint` fails if the run blows the budget.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

from . import (
    ROOT,
    apply_baseline,
    apply_suppressions,
    check_inline_suppressions,
    load_baseline,
)
from . import (
    pass_async,
    pass_codec,
    pass_failpoints,
    pass_jax,
    pass_lattice,
    pass_locks,
    pass_metrics,
    pass_parity,
    pass_protocol,
    pass_semantics,
)
from .core import Project

# pass 1 + JL001 cover the product and its scripts; tests are excluded
# (fixtures deliberately violate the rules), and jlint's own fixtures
# live inside string literals so the package itself stays in scope
ASYNC_SCOPE = ("jylis_tpu", "scripts")
JAX_SCOPE = ("jylis_tpu/ops",)

BUDGET_PATH = os.path.join(os.path.dirname(os.path.abspath(__file__)), "budget.json")

N_PASSES = 10  # 1-5 and 7-11: pass 6 went with the multi-lane mode


def run_all(
    root: str = ROOT,
    verbose: bool = False,
    out_path: str | None = None,
    budget: bool = False,
) -> int:
    times: dict[str, float] = {}
    t0 = time.perf_counter()
    try:
        project = Project.load(root, ASYNC_SCOPE)
    except SystemExit as e:
        # a file that no longer parses: the diagnostic already printed;
        # still write the artifact so the red build's upload explains
        # itself instead of silently missing
        if out_path:
            with open(out_path, "w", encoding="utf-8") as f:
                json.dump(
                    {"findings": [], "error": "unparseable source — see "
                     "stderr diagnostic", "exit": e.code or 2}, f, indent=2,
                )
                f.write("\n")
        return e.code or 2
    times["load"] = time.perf_counter() - t0

    def timed(name, fn, *args):
        t = time.perf_counter()
        result = fn(*args)
        times[name] = times.get(name, 0.0) + (time.perf_counter() - t)
        return result

    async_sources = project.sources
    jax_sources = [
        s for s in async_sources
        if s.rel.startswith(JAX_SCOPE[0].replace("/", os.sep))
    ]
    # line-anchored, slug-suppressable passes first: their pre-suppression
    # union is what the inline-staleness check (JL003) runs against
    findings = timed("1:async", pass_async.run, async_sources)
    findings += timed("1:async", pass_async.run_interprocedural, project)
    findings += timed("2:jax", pass_jax.run, jax_sources)
    findings += timed("8:lattice", pass_lattice.run, project)
    findings += timed("9:locks", pass_locks.run, project)
    by_rel = project.by_rel
    hygiene = timed("0:suppressions", check_inline_suppressions, findings, by_rel)
    apply_suppressions(findings, by_rel)
    problems = apply_baseline(findings, load_baseline())
    findings += timed("3:parity", pass_parity.check)
    findings += timed("4:failpoints", pass_failpoints.check)
    findings += timed("5:metrics", pass_metrics.check)
    findings += timed("7:codec", pass_codec.check)
    findings += timed("10:protocol", pass_protocol.check)
    findings += timed("11:semantics", pass_semantics.check)
    findings += timed("8:lattice", pass_lattice.check_manifest, project)
    findings += problems
    findings += hygiene

    bad = [f for f in findings if not f.suppressed]
    shown = findings if verbose else bad
    for f in sorted(shown, key=lambda f: (f.path, f.line, f.rule)):
        tag = " (suppressed)" if f.suppressed else ""
        print(f.render() + tag)
    n_sup = sum(1 for f in findings if f.suppressed)
    total = time.perf_counter() - t0
    print(
        f"jlint: {len(bad)} finding(s), {n_sup} suppressed "
        f"({len(async_sources)} files, {N_PASSES} passes, {total:.2f}s)"
    )
    if verbose:
        for name in sorted(times):
            print(f"  {name:>16}: {times[name] * 1000:7.1f} ms")

    rc = 1 if bad else 0
    # budget BEFORE the artifact, so the recorded exit matches the
    # process's: an over-budget clean run must not upload "exit": 0
    if budget:
        try:
            with open(BUDGET_PATH, encoding="utf-8") as f:
                bound = json.load(f)["budget_seconds"]
        except (OSError, KeyError, ValueError):
            print("jlint: budget.json missing/unreadable — recording skipped",
                  file=sys.stderr)
            bound = None
        if bound is not None and total > bound:
            print(
                f"jlint: BUDGET EXCEEDED — {total:.2f}s > {bound:.1f}s "
                "(scripts/jlint/budget.json). Eleven passes must not erode "
                "the commit loop: profile with -v, fix the slow pass, or "
                "re-record the bound with a justification.",
                file=sys.stderr,
            )
            rc = rc or 3
    if out_path:
        payload = {
            "findings": [
                {
                    "rule": f.rule, "path": f.path, "line": f.line,
                    "msg": f.msg, "suppressed": f.suppressed,
                    "baseline": f.baseline,
                }
                for f in sorted(
                    findings, key=lambda f: (f.path, f.line, f.rule)
                )
            ],
            "counts": {
                "unsuppressed": len(bad),
                "suppressed": n_sup,
                "files": len(async_sources),
                "passes": N_PASSES,
                # ROADMAP item 1's native-surface gap as a tracked number:
                # commands only the Python oracle serves (MAP/BCOUNT/
                # SESSION/…) — moving it means re-recording the parity
                # manifest
                "python_only": sum(
                    len(v)
                    for v in pass_parity.build_manifest()[
                        "python_only"
                    ].values()
                ),
            },
            "pass_seconds": {k: round(v, 4) for k, v in sorted(times.items())},
            "total_seconds": round(total, 4),
            "exit": rc,
        }
        with open(out_path, "w", encoding="utf-8") as f:
            json.dump(payload, f, indent=2)
            f.write("\n")
    return rc


def write_manifests(project: Project | None = None) -> None:
    manifest = pass_parity.write_manifest()
    n = sum(len(v) for v in manifest["native"].values())
    p = sum(len(v) for v in manifest["python"].values())
    print(f"parity manifest written: {n} native, {p} python commands")
    fps = pass_failpoints.write_manifest()
    todo = sum(1 for d in fps.values() if d == pass_failpoints.PLACEHOLDER)
    print(
        f"failpoints manifest written: {len(fps)} failpoints"
        + (f" ({todo} need descriptions)" if todo else "")
    )
    mets = pass_metrics.write_manifest()
    todo = sum(1 for d in mets.values() if d == pass_metrics.PLACEHOLDER)
    print(
        f"metrics manifest written: {len(mets)} metrics"
        + (f" ({todo} need descriptions)" if todo else "")
    )
    cdc = pass_codec.write_manifest()
    print(
        f"codec manifest written: {len(cdc['units'])} units, "
        f"schema v{cdc['schema_version']} (+legacy "
        f"{cdc['legacy_snapshot_versions']}) — if it changed, re-record "
        "the corpus with --write-corpus"
    )
    if project is None:
        project = Project.load(ROOT, ASYNC_SCOPE)
    lat = pass_lattice.write_manifest(project)
    print(
        f"lattice manifest written: {len(lat['merge_roots'])} merge roots, "
        f"{len(lat['types'])} harness types (tests/test_lattice_laws.py "
        "regenerated)"
    )
    proto = pass_protocol.write_manifest()
    n_entries = sum(len(v) for v in proto["sections"].values())
    todo = sum(
        1
        for sec in proto["sections"].values()
        for e in sec.values()
        if e["note"] == pass_protocol.PLACEHOLDER
    )
    print(
        f"protocol manifest written: {n_entries} transitions across "
        f"{len(proto['sections'])} sections"
        + (f" ({todo} need notes)" if todo else "")
    )
    sem = pass_semantics.write_manifest()
    todo = sum(
        1
        for e in sem["commands"].values()
        if e["note"] == pass_semantics.PLACEHOLDER
    )
    diverged = sum(
        1 for e in sem["commands"].values() if e["divergences"]
    )
    print(
        f"semantics manifest written: {len(sem['commands'])} commands, "
        f"{diverged} with divergences (tests/test_semantic_fuzz.py "
        "regenerated)"
        + (f" ({todo} need notes)" if todo else "")
        + " — if it changed, re-record the corpus with --write-corpus"
    )


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="jlint")
    ap.add_argument(
        "--write-manifest", action="store_true",
        help="regenerate every committed manifest (parity, failpoints, "
        "metrics, codec, lattice + property harness, protocol, "
        "semantics + fuzz harness; descriptions preserved) and exit",
    )
    ap.add_argument(
        "--write-corpus", action="store_true",
        help="regenerate tests/golden/codec_corpus.json from the current "
        "codec manifest (imports the product) and exit",
    )
    ap.add_argument(
        "--out", metavar="PATH",
        help="write machine-readable findings JSON (the CI artifact)",
    )
    ap.add_argument(
        "--budget", action="store_true",
        help="fail (exit 3) when the run exceeds the recorded wall-time "
        "bound in scripts/jlint/budget.json",
    )
    ap.add_argument(
        "-v", "--verbose", action="store_true",
        help="also print suppressed findings and per-pass times",
    )
    args = ap.parse_args(argv)
    if args.write_manifest:
        write_manifests()
        return 0
    if args.write_corpus:
        corpus = pass_codec.write_corpus()
        print(
            f"codec corpus written: {len(corpus['entries'])} entries "
            f"pinned to manifest {corpus['manifest_sha256'][:12]}"
        )
        from .. import gen_semfuzz

        sem = pass_semantics._load_committed()
        fuzz = gen_semfuzz.write_corpus(sem, pass_semantics.manifest_sha())
        print(
            f"semfuzz corpus written: {len(fuzz['streams'])} streams "
            f"pinned to manifest {fuzz['manifest_sha256'][:12]}"
        )
        return 0
    return run_all(verbose=args.verbose, out_path=args.out, budget=args.budget)


if __name__ == "__main__":
    raise SystemExit(main())
