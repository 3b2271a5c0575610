"""Pass 3 — RESP surface parity (rules JL301/JL302).

PR 2 settled the full steady-state command surface of all five data
types in the native engine, checked against the Python oracle by
hand-written differential fuzz. Nothing prevented drift: a command
class added to ``native/serve_engine.cpp`` without a matching oracle
path in ``models/repo_*.py`` (or vice versa) would ship silently and
surface as a wire-level divergence between serving paths.

This pass extracts both dispatch surfaces mechanically:

* native: the ``word_is(buf, offs[0], …, "TYPE")`` /
  ``word_is(buf, offs[1], …, "SUB")`` guards in ``serve_engine.cpp``
  (the counter block shares GCOUNT/PNCOUNT dispatch; a ``which == 1``
  qualifier restricts a subcommand to PNCOUNT);
* python: the ``op == b"SUB"`` comparisons inside each repo class's
  ``apply`` method, keyed by the class's ``name`` attribute.

They are folded into a committed manifest
(``scripts/jlint/parity_manifest.json``):

* ``native`` / ``python``: the extracted surfaces;
* ``python_only``: commands the oracle serves that the engine defers
  by design (TLOG TRIM/TRIMAT/CLR dispatch device drains; SYSTEM is
  host-only) — every such command must be listed here, so going
  native-first is always a conscious, reviewed change.

JL301 fires when a command is served natively with no Python oracle
path, or a Python command is neither native nor listed python-only.
JL302 fires when the committed manifest differs from the extracted
surfaces — ``python -m scripts.jlint --write-manifest`` regenerates it,
and the git diff is the review surface.
"""

from __future__ import annotations

import ast
import json
import os
import re

from . import Finding, MANIFEST_PATH, ROOT

SERVE_ENGINE = os.path.join(ROOT, "native", "serve_engine.cpp")
REPO_GLOB_DIR = os.path.join(ROOT, "jylis_tpu", "models")

_TYPE_RE = re.compile(r'word_is\(buf,\s*offs\[0\],\s*lens\[0\],\s*"(\w+)"\)')
_SUB_RE = re.compile(r'word_is\(buf,\s*offs\[1\],\s*lens\[1\],\s*"(\w+)"\)')
# a COMPOSED type (MAP) names its inner type at offs[1] and its
# subcommand at offs[2]: a type whose block has offs[2] guards takes its
# subcommands from those, and its offs[1] words are inner types
_SUB2_RE = re.compile(r'word_is\(buf,\s*offs\[2\],\s*lens\[2\],\s*"(\w+)"\)')


def extract_native(path: str = SERVE_ENGINE) -> dict[str, list[str]]:
    """{TYPE: sorted [SUB]} from the engine's dispatch guards."""
    with open(path, encoding="utf-8") as f:
        text = f.read()
    events: list[tuple[int, str, str]] = []
    for m in _TYPE_RE.finditer(text):
        events.append((m.start(), "type", m.group(1)))
    for m in _SUB_RE.finditer(text):
        events.append((m.start(), "sub", m.group(1)))
    for m in _SUB2_RE.finditer(text):
        events.append((m.start(), "sub2", m.group(1)))
    events.sort()
    surface: dict[str, set[str]] = {}
    composed: dict[str, set[str]] = {}
    active: list[str] = []
    last_kind = None
    for pos, kind, word in events:
        if kind == "type":
            if last_kind == "type":
                active.append(word)  # adjacent guards share one block
            else:
                active = [word]
            surface.setdefault(word, set())
        elif kind == "sub2":
            for t in active:
                composed.setdefault(t, set()).add(word)
            continue  # adjacency of type guards is not its business
        else:
            # a `which == 1 && … word_is(…)` qualifier in the shared
            # counter block restricts the subcommand to PNCOUNT
            window = text[max(0, pos - 200) : pos]
            stmt = window.rsplit(";", 1)[-1]
            targets = active
            if "which == 1" in stmt:
                targets = [t for t in active if t == "PNCOUNT"] or active
            for t in targets:
                surface[t].add(word)
        last_kind = kind
    surface.update(composed)
    return {t: sorted(subs) for t, subs in sorted(surface.items())}


def extract_python(models_dir: str = REPO_GLOB_DIR) -> dict[str, list[str]]:
    """{TYPE: sorted [SUB]} from every repo class's `apply` dispatch."""
    surface: dict[str, set[str]] = {}
    for fname in sorted(os.listdir(models_dir)):
        if not (fname.startswith("repo_") and fname.endswith(".py")):
            continue
        path = os.path.join(models_dir, fname)
        with open(path, encoding="utf-8") as f:
            tree = ast.parse(f.read(), filename=path)
        for cls in ast.walk(tree):
            if not isinstance(cls, ast.ClassDef):
                continue
            name = None
            for stmt in cls.body:
                if (
                    isinstance(stmt, ast.Assign)
                    and len(stmt.targets) == 1
                    and isinstance(stmt.targets[0], ast.Name)
                    and stmt.targets[0].id == "name"
                    and isinstance(stmt.value, ast.Constant)
                    and isinstance(stmt.value.value, str)
                ):
                    name = stmt.value.value
            apply_fn = next(
                (
                    m for m in cls.body
                    if isinstance(m, ast.FunctionDef) and m.name == "apply"
                ),
                None,
            )
            if name is None or apply_fn is None:
                continue
            subs = surface.setdefault(name, set())
            for node in ast.walk(apply_fn):
                if not isinstance(node, ast.Compare):
                    continue
                operands = [node.left] + list(node.comparators)
                # `op in (b"INC", b"DEC")` dispatches through a tuple:
                # unpack container comparators into their elements
                flat: list[ast.expr] = []
                for o in operands:
                    if isinstance(o, (ast.Tuple, ast.List, ast.Set)):
                        flat.extend(o.elts)
                    else:
                        flat.append(o)
                operands = flat
                consts = [
                    o.value for o in operands
                    if isinstance(o, ast.Constant) and isinstance(o.value, bytes)
                ]
                names = [
                    o.id for o in operands if isinstance(o, ast.Name)
                ]
                if consts and ("op" in names or any(
                    isinstance(o, ast.Subscript) for o in operands
                )):
                    for c in consts:
                        word = c.decode("ascii", "replace")
                        if word.isupper() and word.isalpha():
                            subs.add(word)
    return {t: sorted(subs) for t, subs in sorted(surface.items())}


def build_manifest(
    native: dict[str, list[str]] | None = None,
    python: dict[str, list[str]] | None = None,
) -> dict:
    native = native if native is not None else extract_native()
    python = python if python is not None else extract_python()
    python_only: dict[str, list[str]] = {}
    for t, subs in python.items():
        nat = set(native.get(t, []))
        only = sorted(set(subs) - nat)
        if only:
            python_only[t] = only
    return {
        "_comment": (
            "Generated by `python -m scripts.jlint --write-manifest` from "
            "native/serve_engine.cpp and jylis_tpu/models/repo_*.py — do "
            "not edit by hand. `make lint` fails on drift (JL302) and on "
            "any natively-served command with no Python oracle path "
            "(JL301)."
        ),
        "native": native,
        "python": python,
        "python_only": python_only,
    }


def write_manifest(path: str = MANIFEST_PATH) -> dict:
    manifest = build_manifest()
    with open(path, "w", encoding="utf-8") as f:
        json.dump(manifest, f, indent=2, sort_keys=True)
        f.write("\n")
    return manifest


def check(
    manifest_path: str = MANIFEST_PATH,
    native: dict[str, list[str]] | None = None,
    python: dict[str, list[str]] | None = None,
) -> list[Finding]:
    out: list[Finding] = []
    current = build_manifest(native, python)
    rel = os.path.relpath(manifest_path, ROOT)

    # JL301: native without oracle / python neither native nor declared
    for t, subs in current["native"].items():
        py = set(current["python"].get(t, []))
        for sub in subs:
            if sub not in py:
                out.append(
                    Finding(
                        "JL301", "native/serve_engine.cpp", 1,
                        f"`{t} {sub}` is served natively but has no Python "
                        "oracle path in models/ — the oracle defines the "
                        "semantics; add the Python path first",
                        f"{t} {sub}",
                    )
                )
    for t, subs in current["python"].items():
        nat = set(current["native"].get(t, []))
        declared = set(current["python_only"].get(t, []))
        for sub in subs:
            if sub not in nat and sub not in declared:
                out.append(
                    Finding(
                        "JL301", rel, 1,
                        f"`{t} {sub}` exists in Python but is neither served "
                        "natively nor listed python_only in the manifest",
                        f"{t} {sub}",
                    )
                )

    # JL302: committed manifest drift
    if not os.path.exists(manifest_path):
        out.append(
            Finding(
                "JL302", rel, 1,
                "parity manifest missing — run `python -m scripts.jlint "
                "--write-manifest` and commit it",
                "",
            )
        )
        return out
    with open(manifest_path, encoding="utf-8") as f:
        committed = json.load(f)
    for key in ("native", "python", "python_only"):
        if committed.get(key) != current[key]:
            out.append(
                Finding(
                    "JL302", rel, 1,
                    f"parity manifest drift in `{key}`: committed "
                    f"{json.dumps(committed.get(key), sort_keys=True)} != "
                    f"extracted {json.dumps(current[key], sort_keys=True)} — "
                    "run `python -m scripts.jlint --write-manifest`, review "
                    "the diff, commit",
                    key,
                )
            )
    return out
