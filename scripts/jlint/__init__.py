"""jlint: the repo-native static analyzer (`make lint`, part of `make ci`).

The repo spans three domains where bugs are silent until they cost a
re-record, and each gets a dedicated analysis pass:

* **Pass 1 — async/thread safety** (`pass_async`, rules JL1xx): the
  asyncio serving loop shares state with the journal writer thread and
  with `asyncio.to_thread` drains. Blocking calls on the loop, shared
  attributes mutated from both sides without a declared guard,
  read-modify-write sequences spanning an ``await``, and blocking disk
  I/O performed while holding a thread lock are all flagged.
* **Pass 2 — JAX trace discipline** (`pass_jax`, rules JL2xx) over
  ``jylis_tpu/ops/``: host syncs reachable from ``@jax.jit`` functions,
  data-dependent Python branching on traced values, dtype-implicit
  array constructors outside the documented x64 guards, and jit
  construction inside hot functions (per-call recompilation).
* **Pass 3 — RESP surface parity** (`pass_parity`, rules JL3xx): the
  native engine's command dispatch (``native/serve_engine.cpp``) is
  extracted alongside the Python oracle dispatch (``models/repo_*.py``)
  into a committed parity manifest; a command served natively without a
  Python oracle path fails, and any drift between the sources and the
  committed manifest fails — PR 2's hand-checked parity as a mechanical
  invariant.
* **Pass 4 — failpoint manifest parity** (`pass_failpoints`, rules
  JL4xx): every ``faults.point(...)`` name in the product tree must be
  a string literal declared in the committed
  ``scripts/jlint/failpoints_manifest.json`` with a one-line
  description; undeclared, stale, or undescribed names fail, so the
  set of injectable failure seams stays reviewed and documented.
* **Pass 5 — metrics manifest parity** (`pass_metrics`, rules JL5xx):
  every histogram/gauge/trace-event name in the observability layer
  (``.hist()`` / ``.gauge_set()`` / ``.trace_event()`` /
  ``timed_drain()`` call sites) must be a string literal declared in
  the committed ``scripts/jlint/metrics_manifest.json`` AND
  pre-registered in ``jylis_tpu/obs/__init__.py``; stale entries and
  dead declarations fail, so the scrapeable surface stays reviewed.
* Pass 6 (rules JL6xx) is RETIRED: it declared the module-level
  mutables that the multi-lane mode copied per process, and went with
  that mode in PR 45. The other passes keep their numbers.

jlint v2 adds a shared INTERPROCEDURAL core (``core.py`` +
``graph.py``: per-project module/call graph with no-false-edge
resolution, per-function held-locks/blocking/await summaries,
content-hash-cached ASTs) that upgrades pass 1's JL101 to see blocking
calls through the call graph and powers three semantic passes:

* **Pass 7 — codec round-trip symmetry** (`pass_codec`, JL70x): the
  paired encoders/decoders of all three wire/disk formats extract to
  field-sequence tokens committed in ``codec_manifest.json``; order/
  width/endianness drift, unconsumed fields, over-reads, and manifest
  drift fail. The manifest drives the golden corpus
  (``tests/golden/codec_corpus.json``, ``--write-corpus``).
* **Pass 8 — CRDT lattice-law discipline** (`pass_lattice`, JL80x):
  wall-clock reads reachable from merge/join/apply paths, unordered
  iteration feeding digests/wire/flushes, delta mutation after sink
  aliasing, replica-id branches in joins; ``lattice_manifest.json``
  documents each obligation and GENERATES the dynamic property harness
  (``tests/test_lattice_laws.py``: join commutativity/associativity/
  idempotence over seeded random deltas for all five types).
* **Pass 9 — cross-thread lock order** (`pass_locks`, JL90x): await
  while holding a threading lock, lock-acquisition cycles over the
  global lock graph, and blocking I/O reachable under a held lock
  interprocedurally (the case pass 1's syntactic JL104 missed).
* **Pass 10 — protocol atlas** (`pass_protocol`, JL100x): the full
  transition relation of the cluster protocol — (role, state, message)
  → permitted effects (sends, converges, state mutations, teardown
  reasons, metric/trace emissions) — extracted from ``cluster.py``'s
  handler dispatch, handshake, sync machinery and dial state machine
  into the committed ``protocol_manifest.json``. Undeclared effects,
  silent fall-throughs, and manifest drift fail; jmodel
  (``scripts/jmodel``) explores the same protocol dynamically.

jlint v3 adds the cross-language seam:

* **Pass 11 — RESP semantic parity** (`pass_semantics`, JL110x): a
  purpose-built C++ front-end (``cpp_ast.py`` — tokenizer + recursive
  descent over the disciplined subset ``native/`` is written in, no
  libclang) symbolically extracts every natively-served command's
  argument grammar, numeric bounds, validators, reply shape and error
  mode from ``native/serve_engine.cpp``/``resp_parser.cpp``/
  ``engine.h``, diffs them against the Python oracle's dispatch ASTs
  into the committed ``semantics_manifest.json`` (JL1101 grammar/
  bounds/transport/threshold divergence, JL1102 reply-shape/error
  divergence, JL1103 drift/stale/placeholder/coverage/stale-harness),
  and GENERATES the differential fuzz harness
  (``tests/test_semantic_fuzz.py`` via ``scripts/gen_semfuzz.py``:
  seeded valid/boundary/mutated-invalid command streams byte-compared
  through both server paths, corpus sha-pinned in ``tests/golden/``).

Plus the hygiene rules: JL001 (``except Exception`` / bare ``except``
without justification), JL002 (an inline suppression carrying no
reason), JL003 (a stale inline suppression whose rule no longer fires
at that site), and JL000 (stale/malformed baseline entries).

Suppression works at two levels, both requiring a human-readable reason:

* inline: a ``# jlint: <slug>`` comment on the flagged line, or
  anywhere in the contiguous comment block directly above it (slugs
  per rule in ``RULES``; e.g. ``# jlint: shared-ok —
  writer-owns-file protocol``). Reason-less markers fail (JL002);
  markers whose rule no longer fires at the site fail (JL003);
* the committed baseline (``scripts/jlint/baseline.json``): entries of
  ``{"rule", "file", "match", "reason"}`` where ``match`` must appear in
  the flagged source line. A baseline entry that no longer matches any
  finding is STALE and fails the run, so suppressions can't outlive the
  code they excuse.

Run ``python -m scripts.jlint`` from the repo root (what ``make lint``
does, plus ``--budget --out lint_findings.json``); ``--write-manifest``
regenerates every committed manifest and the generated lattice +
semantic-fuzz harnesses, ``--write-corpus`` re-records the golden
codec and semantic-fuzz corpora.
"""

from __future__ import annotations

import ast
import json
import os
import tokenize
from dataclasses import dataclass, field

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
BASELINE_PATH = os.path.join(os.path.dirname(os.path.abspath(__file__)), "baseline.json")
MANIFEST_PATH = os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "parity_manifest.json"
)

# rule id -> (inline suppression slug, one-line description)
RULES = {
    "JL000": (None, "stale or malformed baseline suppression entry"),
    "JL002": (None, "inline `# jlint:` suppression carries no reason"),
    "JL003": (None, "stale inline suppression: its rule no longer fires at that line"),
    "JL001": ("broad-ok", "broad `except Exception`/bare except without justification"),
    "JL101": ("blocking-ok", "known-blocking call inside `async def` without executor dispatch"),
    "JL102": ("shared-ok", "attribute mutated from both a worker thread and the event loop without a declared guard"),
    "JL103": ("rmw-ok", "read-modify-write of a shared attribute spanning an `await`"),
    "JL104": ("lockio-ok", "blocking disk I/O while holding a thread lock/condition"),
    "JL201": ("hostsync-ok", "host sync (.item()/float()/np.asarray) reachable from a @jax.jit function"),
    "JL202": ("branch-ok", "data-dependent Python branch on a traced value inside a jit function"),
    "JL203": ("dtype-ok", "dtype-implicit array constructor in jit code outside an x64 guard"),
    "JL204": ("jit-ok", "jax.jit constructed inside a function body (per-call recompilation)"),
    "JL301": (None, "command served natively without a Python oracle path (or vice versa, unlisted)"),
    "JL302": (None, "parity manifest drift: committed manifest != extracted surfaces"),
    "JL401": (None, "failpoint name non-literal or not declared in failpoints_manifest.json"),
    "JL402": (None, "failpoints manifest entry stale, missing, or undescribed"),
    "JL501": (None, "metric name non-literal, not declared in metrics_manifest.json, or not pre-registered in obs"),
    "JL502": (None, "metrics manifest / obs declaration stale, missing, or undescribed"),
    "JL701": (None, "codec encoder/decoder field sequences diverge (order/width/endianness drift)"),
    "JL702": (None, "codec field written but never consumed, or decoder reads past the wire shape"),
    "JL703": (None, "codec manifest drift or missing (--write-manifest regenerates)"),
    "JL801": ("wallclock-ok", "wall-clock read reachable from a merge/join/apply path"),
    "JL802": ("order-ok", "unordered dict/set iteration feeding a digest, wire encoding, or flush export"),
    "JL803": ("alias-ok", "delta/batch mutated in place after aliasing into a journal/broadcast/held sink"),
    "JL804": ("ridbranch-ok", "replica-id-dependent branch inside a join path"),
    "JL805": (None, "lattice manifest or generated property harness stale, missing, or undescribed"),
    "JL901": ("awaitlock-ok", "`await` while holding a threading lock"),
    "JL902": (None, "lock-acquisition cycle across the thread/loop seams (potential deadlock)"),
    "JL903": ("lockio-ok", "blocking call reachable under a held lock through the call graph"),
    "JL1001": (None, "cluster protocol handler effect outside the committed atlas (protocol_manifest.json)"),
    "JL1002": (None, "undeclared (role, state, msg) fall-through or silent ignore in a cluster protocol handler"),
    "JL1003": (None, "protocol manifest drift, missing, or undescribed (--write-manifest regenerates)"),
    "JL1101": (None, "native command grammar/bounds diverge from the Python oracle (arity, u64 args, transport limits, thresholds)"),
    "JL1102": (None, "native RESP reply shape or error classes diverge from the Python oracle"),
    "JL1103": (None, "semantics manifest drift/stale/placeholder, uncovered native command, or stale generated fuzz harness"),
}

# slug -> every rule that honors it (JL104/JL903 share lockio-ok; the
# inline-staleness check JL003 treats a suppression as live when ANY of
# its slug's rules fires at the site)
SLUG_RULES: dict[str, set[str]] = {}
for _rule, (_slug, _desc) in RULES.items():
    if _slug:
        SLUG_RULES.setdefault(_slug, set()).add(_rule)


@dataclass
class Finding:
    rule: str
    path: str  # repo-relative
    line: int
    msg: str
    src: str = ""  # stripped source line, what baseline `match` runs against
    suppressed: bool = False
    baseline: bool = False

    def render(self) -> str:
        return f"{self.path}:{self.line}: {self.rule} {self.msg}"


@dataclass
class Source:
    """One parsed Python file plus the comment map suppressions need."""

    path: str  # absolute
    rel: str  # repo-relative
    text: str
    tree: ast.AST
    lines: list[str] = field(default_factory=list)
    comments: dict[int, str] = field(default_factory=dict)  # line -> comment text

    @classmethod
    def load(cls, path: str, root: str = ROOT, tree: ast.AST | None = None) -> "Source":
        """Parse `path` (or adopt a pre-parsed `tree` — the core's
        content-hash AST cache passes one) into a Source. ONE
        construction path: field additions and comment-scan rules live
        here only."""
        with open(path, encoding="utf-8") as f:
            text = f.read()
        if tree is None:
            tree = ast.parse(text, filename=path)
        src = cls(
            path=path,
            rel=os.path.relpath(path, root),
            text=text,
            tree=tree,
            lines=text.splitlines(),
        )
        # tokenize for comments: `# jlint: slug` anywhere in a comment
        try:
            for tok in tokenize.generate_tokens(iter(text.splitlines(True)).__next__):
                if tok.type == tokenize.COMMENT:
                    src.comments[tok.start[0]] = tok.string
        except tokenize.TokenError:
            pass
        return src

    def line_src(self, lineno: int) -> str:
        if 1 <= lineno <= len(self.lines):
            return self.lines[lineno - 1].strip()
        return ""

    def _is_comment_line(self, lineno: int) -> bool:
        """True when the line holds nothing but a comment."""
        return lineno in self.comments and self.line_src(lineno).startswith("#")

    def has_suppression(self, lineno: int, slug: str) -> bool:
        """`# jlint: <slug>` on the line itself, or anywhere in the
        contiguous comment block directly above it (multi-line
        justifications are encouraged, not penalised). The slug is
        matched as an exact token — the same parse the JL002/JL003
        hygiene uses — so a typo'd slug never suppresses by substring
        while being invisible to the staleness check."""
        if comment_slug(self.comments.get(lineno, "")) == slug:
            return True
        ln = lineno - 1
        while ln >= 1 and self._is_comment_line(ln):
            if comment_slug(self.comments.get(ln, "")) == slug:
                return True
            ln -= 1
        return False

    def suppression_target(self, lineno: int) -> int:
        """The code line a suppression comment at `lineno` covers: the
        line itself when the comment rides code, else the first code
        line below the comment block."""
        if not self._is_comment_line(lineno):
            return lineno
        ln = lineno + 1
        while ln <= len(self.lines) and self._is_comment_line(ln):
            ln += 1
        return ln


def parent_map(tree: ast.AST) -> dict[ast.AST, ast.AST]:
    parents: dict[ast.AST, ast.AST] = {}
    for node in ast.walk(tree):
        for child in ast.iter_child_nodes(node):
            parents[child] = node
    return parents


def dotted_name(node: ast.AST) -> str:
    """'os.fsync' for Attribute chains, 'open' for Names, '' otherwise."""
    parts: list[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
    elif parts:
        parts.append("?")  # call on a computed receiver: keep the tail
    return ".".join(reversed(parts))


def iter_py_files(root: str, subdirs: tuple[str, ...]) -> list[str]:
    out = []
    for sub in subdirs:
        base = os.path.join(root, sub)
        for dirpath, dirnames, filenames in os.walk(base):
            dirnames[:] = [d for d in dirnames if d != "__pycache__"]
            for f in sorted(filenames):
                if f.endswith(".py"):
                    out.append(os.path.join(dirpath, f))
    return sorted(out)


def apply_suppressions(findings: list[Finding], sources: dict[str, "Source"]) -> None:
    """Mark findings carrying a matching inline `# jlint: <slug>` comment."""
    for f in findings:
        slug = RULES[f.rule][0]
        src = sources.get(f.path)
        if slug and src is not None and src.has_suppression(f.line, slug):
            f.suppressed = True


def load_baseline(path: str = BASELINE_PATH) -> list[dict]:
    if not os.path.exists(path):
        return []
    with open(path, encoding="utf-8") as f:
        return json.load(f)


def apply_baseline(
    findings: list[Finding], baseline: list[dict]
) -> list[Finding]:
    """Suppress findings matched by baseline entries; return JL900
    findings for entries that are malformed or match nothing (stale)."""
    problems: list[Finding] = []
    for i, entry in enumerate(baseline):
        rule = entry.get("rule", "")
        file_ = entry.get("file", "")
        match = entry.get("match", "")
        reason = entry.get("reason", "")
        if not (rule and file_ and match) or not reason.strip():
            problems.append(
                Finding(
                    "JL000", BASELINE_PATH_REL, i + 1,
                    f"baseline entry {i} malformed or missing a reason: {entry!r}",
                )
            )
            continue
        hit = False
        for f in findings:
            if (
                f.rule == rule
                and f.path == file_
                and match in f.src
                and not f.suppressed
            ):
                f.suppressed = True
                f.baseline = True
                hit = True
        if not hit:
            problems.append(
                Finding(
                    "JL000", BASELINE_PATH_REL, i + 1,
                    f"stale baseline entry {i}: no current {rule} finding in "
                    f"{file_} matches {match!r} — delete the entry",
                )
            )
    return problems


BASELINE_PATH_REL = os.path.relpath(BASELINE_PATH, ROOT)


def comment_slug(comment: str) -> str | None:
    """The exact `jlint: <slug>` token in a comment, or None. One
    parser for suppression matching AND the JL002/JL003 hygiene, so a
    slug that suppresses is always one the hygiene can see."""
    if "jlint:" not in comment:
        return None
    after = comment.split("jlint:", 1)[1].strip()
    slug = ""
    for ch in after:
        if ch.isalnum() or ch == "-":
            slug += ch
        else:
            break
    return slug or None


def _suppression_sites(src: "Source"):
    """(line, slug, reason) for every `# jlint: <slug>` comment. The
    reason is whatever explanatory text the comment carries besides the
    marker itself — before it (`# boot path — jlint: lockio-ok`) or
    after it (`# jlint: shared-ok (caller holds _cv)`)."""
    for line, comment in sorted(src.comments.items()):
        slug = comment_slug(comment)
        if slug is None or slug not in SLUG_RULES:
            continue
        before, after = comment.split("jlint:", 1)
        after = after.strip()
        reason = (before.lstrip("#").strip() + " " + after[len(slug):].strip()).strip()
        yield line, slug, reason


def check_inline_suppressions(
    all_findings: list[Finding], sources: dict[str, "Source"]
) -> list[Finding]:
    """Inline-suppression hygiene (the baseline-staleness discipline
    extended to inline sites): every `# jlint: <slug>` must carry a
    reason (JL002), and must still have a matching finding on its line
    or the line below (JL003 — a suppression outliving the code it
    excused is deleted, not inherited by whatever lands there next)."""
    # (rel, line, rule) for every PRE-suppression finding
    fired: set[tuple[str, int, str]] = {
        (f.rule, f.path, f.line) for f in all_findings
    }
    out: list[Finding] = []
    for rel, src in sorted(sources.items()):
        for line, slug, reason in _suppression_sites(src):
            if len([c for c in reason if c.isalpha()]) < 4:
                out.append(
                    Finding(
                        "JL002", rel, line,
                        f"inline suppression `jlint: {slug}` carries no "
                        "reason — say WHY the rule does not apply here "
                        "(e.g. `# jlint: "
                        f"{slug} — writer-owns-file protocol`)",
                        src.line_src(line),
                    )
                )
            target = src.suppression_target(line)
            live = any(
                (rule, rel, ln) in fired
                for rule in SLUG_RULES[slug]
                for ln in (line, target)
            )
            if not live:
                out.append(
                    Finding(
                        "JL003", rel, line,
                        f"stale inline suppression `jlint: {slug}`: no "
                        f"{'/'.join(sorted(SLUG_RULES[slug]))} finding "
                        "fires at this line any more — delete the comment",
                        src.line_src(line),
                    )
                )
    return out
