"""Pass 5 — metrics manifest parity (rules JL501/JL502).

The observability layer (jylis_tpu/obs/) works by NAME exactly like the
failpoints registry: ``registry.hist("journal.fsync")`` at the seam,
``SYSTEM LATENCY`` / the Prometheus endpoint to read it. A typo'd name
is a KeyError at runtime — but only on the path that typo'd it — and a
histogram/gauge/trace event added without documentation is invisible to
operators. Same cure as pass 4, same mechanics:

* every ``.hist(...)`` / ``.seam(...)`` / ``.gauge_set(...)`` /
  ``.tally(...)`` / ``.note_serving(...)`` call in the product tree
  must use a STRING LITERAL name, every ``.trace_event(...)`` literal
  subsystem+event args, and every ``timed_drain("<TYPE>", ...)``
  decorator a literal type (its histogram is ``drain.<TYPE>``); each
  resulting name must appear in the committed
  ``scripts/jlint/metrics_manifest.json`` with a one-line description
  (JL501);
* every manifest entry must still have a call site and a
  non-placeholder description (JL502: stale / undescribed);
* every histogram/gauge/tally/serving-counter name must be
  pre-registered in ``jylis_tpu/obs/__init__.py``'s
  SEAMS/GAUGES/TALLIES/SERVING tuples (and every
  declared name used), so a scrape shows the full surface from boot and
  the declarations can't rot (JL501/JL502).

``python -m scripts.jlint --write-manifest`` regenerates the manifest,
preserving existing descriptions; new names get a placeholder that
fails JL502 until a human describes the metric. The CI metrics-smoke
step (scripts/metrics_smoke.py) reads the same manifest to assert every
histogram/gauge is actually present in a live node's scrape.

Manifest keys are ``<kind>:<name>`` with kind in {hist, gauge, counter,
serving, trace}; trace names are ``<subsystem>.<event>``.
"""

from __future__ import annotations

import ast
import json
import os

from . import Finding, ROOT, iter_py_files
from .core import load_source

METRICS_MANIFEST_PATH = os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "metrics_manifest.json"
)

OBS_INIT_REL = os.path.join("jylis_tpu", "obs", "__init__.py")

SCOPE = ("jylis_tpu",)

PLACEHOLDER = "(describe this metric)"

# attr-tail -> (kind, how many leading literal args form the name)
_CALL_KINDS = {
    "hist": ("hist", 1),
    # registry.seam("<name>"): the same histogram behind the span
    # instrument (obs/span.py)
    "seam": ("hist", 1),
    "gauge_set": ("gauge", 1),
    # registry.tally("drain.<TYPE>.<kind>", n): an exact event counter
    "tally": ("counter", 1),
    # registry.note_serving("<kind>"[, n]): an exact serving-path
    # counter, a `kind` of jylis_serving_total
    "note_serving": ("serving", 1),
    "trace_event": ("trace", 2),
}


def _attr_tail(node: ast.AST) -> str:
    if isinstance(node, ast.Attribute):
        return node.attr
    if isinstance(node, ast.Name):
        return node.id
    return ""


def _literal_strs(args: list[ast.expr], n: int) -> list[str] | None:
    if len(args) < n:
        return None
    out = []
    for a in args[:n]:
        if isinstance(a, ast.Constant) and isinstance(a.value, str):
            out.append(a.value)
        else:
            return None
    return out


def extract_sites(
    root: str = ROOT, scope: tuple[str, ...] = SCOPE
) -> tuple[dict[str, list[tuple[str, int]]], list[Finding]]:
    """{``kind:name``: [(rel path, line)]} for every literal-named
    metric call, plus JL501 findings for non-literal names."""
    sites: dict[str, list[tuple[str, int]]] = {}
    problems: list[Finding] = []
    for path in iter_py_files(root, scope):
        src = load_source(path, root)  # content-hash AST cache
        for node in ast.walk(src.tree):
            if not isinstance(node, ast.Call):
                continue
            tail = _attr_tail(node.func)
            if tail in _CALL_KINDS:
                kind, n = _CALL_KINDS[tail]
                # only method-style calls (obj.hist(...)): a bare
                # function named `hist` elsewhere is not the registry
                if not isinstance(node.func, ast.Attribute):
                    continue
                lits = _literal_strs(node.args, n)
                if lits is None:
                    problems.append(
                        Finding(
                            "JL501", src.rel, node.lineno,
                            f"{tail}() name must be {n} leading string "
                            "literal(s) — a computed metric name cannot "
                            "be audited against the manifest",
                            src.line_src(node.lineno),
                        )
                    )
                    continue
                name = f"{kind}:{'.'.join(lits)}"
                sites.setdefault(name, []).append((src.rel, node.lineno))
            elif tail == "timed_drain":
                lits = _literal_strs(node.args, 1)
                if lits is None:
                    problems.append(
                        Finding(
                            "JL501", src.rel, node.lineno,
                            "timed_drain() type must be a string literal "
                            "(it names the drain.<TYPE> histogram)",
                            src.line_src(node.lineno),
                        )
                    )
                    continue
                name = f"hist:drain.{lits[0]}"
                sites.setdefault(name, []).append((src.rel, node.lineno))
    return sites, problems


# (manifest kind, its obs/__init__.py tuple, the word for one, what a
# call site does to it): the pre-registration parity `check` enforces
_PARITY = (
    ("hist", "SEAMS", "histogram", "records into"),
    ("gauge", "GAUGES", "gauge", "sets"),
    ("counter", "TALLIES", "tally", "adds to"),
    ("serving", "SERVING", "serving counter", "adds to"),
)
_DECLARED = tuple(p[1] for p in _PARITY)


def declared_names(root: str = ROOT) -> tuple[set[str], ...]:
    """(SEAMS, GAUGES, TALLIES, SERVING) parsed from jylis_tpu/obs/__init__.py by
    AST — jlint must not import the product package (jylis_tpu imports
    jax at import time)."""
    path = os.path.join(root, OBS_INIT_REL)
    found: dict[str, set[str]] = {name: set() for name in _DECLARED}
    declared = tuple(found[name] for name in _DECLARED)
    if not os.path.exists(path):
        return declared
    tree = ast.parse(open(path, encoding="utf-8").read())
    for node in ast.walk(tree):
        if not isinstance(node, ast.Assign):
            continue
        for tgt in node.targets:
            if not isinstance(tgt, ast.Name) or tgt.id not in _DECLARED:
                continue
            if isinstance(node.value, (ast.Tuple, ast.List)):
                found[tgt.id].update(
                    e.value
                    for e in node.value.elts
                    if isinstance(e, ast.Constant) and isinstance(e.value, str)
                )
    return declared


def load_manifest(path: str = METRICS_MANIFEST_PATH) -> dict[str, str]:
    if not os.path.exists(path):
        return {}
    with open(path, encoding="utf-8") as f:
        return json.load(f).get("metrics", {})


def write_manifest(path: str = METRICS_MANIFEST_PATH) -> dict[str, str]:
    """Regenerate from the extracted call sites, preserving committed
    descriptions; new names get a placeholder JL502 rejects until a
    human replaces it."""
    sites, _ = extract_sites()
    existing = load_manifest(path)
    entries = {name: existing.get(name, PLACEHOLDER) for name in sorted(sites)}
    with open(path, "w", encoding="utf-8") as f:
        json.dump(
            {
                "_comment": (
                    "Generated by `python -m scripts.jlint "
                    "--write-manifest` from .hist()/.gauge_set()/"
                    ".tally()/.trace_event()/timed_drain() call sites "
                    "under jylis_tpu/. Keys are kind:name "
                    "(hist/gauge/counter/trace). "
                    "Descriptions are human-written and preserved across "
                    "regeneration; `make lint` fails on undeclared names "
                    "(JL501) and on stale or placeholder entries (JL502). "
                    "The CI metrics-smoke scrapes a live node and asserts "
                    "every hist/gauge/counter entry here is present."
                ),
                "metrics": entries,
            },
            f, indent=2, sort_keys=True,
        )
        f.write("\n")
    return entries


def check(
    manifest_path: str = METRICS_MANIFEST_PATH,
    sites: dict[str, list[tuple[str, int]]] | None = None,
    pre_problems: list[Finding] | None = None,
    declared: tuple[set[str], ...] | None = None,
) -> list[Finding]:
    if sites is None:
        sites, pre_problems = extract_sites()
    out = list(pre_problems or [])
    rel = os.path.relpath(manifest_path, ROOT)
    manifest = load_manifest(manifest_path)
    if not manifest and sites:
        out.append(
            Finding(
                "JL502", rel, 1,
                "metrics manifest missing or empty — run `python -m "
                "scripts.jlint --write-manifest`, describe each metric, "
                "commit",
                "",
            )
        )
        return out
    for name in sorted(sites):
        if name not in manifest:
            where, line = sites[name][0]
            out.append(
                Finding(
                    "JL501", where, line,
                    f"metric `{name}` is not declared in {rel} — run "
                    "`python -m scripts.jlint --write-manifest` and "
                    "describe it",
                    name,
                )
            )
    for name, desc in sorted(manifest.items()):
        if name not in sites:
            out.append(
                Finding(
                    "JL502", rel, 1,
                    f"stale manifest entry `{name}`: no call site uses "
                    "it — delete the entry (--write-manifest "
                    "regenerates)",
                    name,
                )
            )
        elif not desc.strip() or desc.strip() == PLACEHOLDER:
            out.append(
                Finding(
                    "JL502", rel, 1,
                    f"metric `{name}` has no description — replace the "
                    "placeholder with one line saying what it measures",
                    name,
                )
            )
    # pre-registration parity: every used hist/gauge/tally name must be
    # in obs.SEAMS/GAUGES/TALLIES (or it KeyErrors at runtime), and every
    # declared name must be used (or the scrape advertises a dead metric)
    declared = declared if declared is not None else declared_names()
    for (kind, tup, what, verb), names in zip(_PARITY, declared):
        used = {
            n[len(kind) + 1:] for n in sites if n.startswith(kind + ":")
        }
        for name in sorted(used - names):
            where, line = sites[f"{kind}:{name}"][0]
            out.append(
                Finding(
                    "JL501", where, line,
                    f"{what} `{name}` is not pre-registered in "
                    f"{OBS_INIT_REL} {tup} (KeyError at runtime)",
                    name,
                )
            )
        for name in sorted(names - used):
            out.append(
                Finding(
                    "JL502", OBS_INIT_REL, 1,
                    f"{tup} declares {what} `{name}` but no "
                    f"call site {verb} it — delete the declaration",
                    name,
                )
            )
    return out
