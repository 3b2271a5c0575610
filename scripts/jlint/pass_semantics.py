"""Pass 11 — cross-language RESP semantic parity (JL1101/JL1102/JL1103).

Pass 3 proved both serving paths dispatch the same command *names*;
nothing checked that they agree on what those commands *mean*. This
pass extracts, for every natively-served command, the full argument
grammar (arity, strict/optional u64 args, validation predicates), the
RESP reply shapes, the error classes, and the defer predicates — from
``native/serve_engine.cpp`` via the ``cpp_ast`` front-end (tokenizer +
recursive descent over the disciplined subset native/ uses, no
libclang) — and the same facts from the Python oracle's
``models/repo_*.py`` dispatch via the stdlib ``ast`` module with
one-level ``self._helper`` inlining. The two sides are diffed into a
committed manifest (``scripts/jlint/semantics_manifest.json``):

* ``commands``: per-command native/python grammar + mechanical
  ``divergences``; ``justified`` (hand-edited: divergence strings that
  are by-design) and ``note`` survive ``--write-manifest``;
* ``transport``: RESP parser limits (line/bulk/array) on both sides;
* ``thresholds``: drain thresholds that must match numerically across
  the seam (native constexpr vs Python module constants).

Reply shapes use one canonical vocabulary on both sides: ``"+OK"``,
``":u64"``, ``":i64"``, ``"$-1"``, ``"$bulk"``, ``"*0"``,
``"*2[$bulk,:u64]"``, ``"*n[*2[$bulk,:u64]]"``.

JL1101 fires on an unjustified grammar/bounds divergence (arity, u64
args, optional args, transport limits, thresholds); JL1102 on an
unjustified reply-shape or error-class divergence; JL1103 on
manifest drift, a stale ``justified`` entry, a placeholder note, a
natively-served command (per pass 3) the manifest does not cover, or a
stale generated fuzz harness (``tests/test_semantic_fuzz.py`` — see
``scripts/gen_semfuzz.py``). ``python -m scripts.jlint
--write-manifest`` regenerates the mechanical parts and the harness;
``--write-corpus`` re-records the fuzz corpus pinned to the manifest's
sha256, so a manifest edit without a re-record fails in tier-1.
"""

from __future__ import annotations

import ast
import hashlib
import json
import os
import re

from . import Finding, ROOT
from . import cpp_ast
from . import pass_parity

SERVE_ENGINE = os.path.join(ROOT, "native", "serve_engine.cpp")
RESP_PARSER = os.path.join(ROOT, "native", "resp_parser.cpp")
ENGINE_H = os.path.join(ROOT, "native", "engine.h")
MODELS_DIR = os.path.join(ROOT, "jylis_tpu", "models")
RESP_PY = os.path.join(ROOT, "jylis_tpu", "server", "resp.py")
SEMANTICS_MANIFEST_PATH = os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "semantics_manifest.json"
)
HARNESS_PATH = os.path.join(ROOT, "tests", "test_semantic_fuzz.py")

PLACEHOLDER = "(explain this command's cross-language contract)"

# drain thresholds that must agree numerically across the language seam
# (native constexpr name, native unit, python module, python constant)
_THRESHOLDS = [
    ("TREG_PENDING_DRAIN", SERVE_ENGINE,
     os.path.join(MODELS_DIR, "repo_treg.py"), "PENDING_DRAIN_THRESHOLD"),
    ("ROW_DRAIN_THRESHOLD", ENGINE_H,
     os.path.join(MODELS_DIR, "tlog_table.py"), "ROW_DRAIN_THRESHOLD"),
    ("PENDING_DRAIN_THRESHOLD", ENGINE_H,
     os.path.join(MODELS_DIR, "tlog_table.py"), "PENDING_DRAIN_THRESHOLD"),
    ("MAP_PENDING_DRAIN", SERVE_ENGINE,
     os.path.join(MODELS_DIR, "repo_map.py"), "PENDING_DRAIN_THRESHOLD"),
]


def manifest_sha(path: str = SEMANTICS_MANIFEST_PATH) -> str:
    with open(path, "rb") as f:
        return hashlib.sha256(f.read()).hexdigest()


# ---- native extraction (cpp_ast) -------------------------------------------

_GUARD0 = re.compile(
    r'argc >= (\d+) && word_is \( buf , offs \[ 0 \] , lens \[ 0 \] , '
    r'"(\w+)" \)'
)
_GUARD1 = re.compile(
    r'argc >= (\d+) && word_is \( buf , offs \[ 1 \] , lens \[ 1 \] , '
    r'"(\w+)" \)'
)
# a composed type's block (MAP): the inner type at offs[1] beside the
# type guard, the subcommand at offs[2] (`argc == N`: an exact arity)
_INNER = re.compile(
    r'word_is \( buf , offs \[ 1 \] , lens \[ 1 \] , "(\w+)" \)'
)
_GUARD2 = re.compile(
    r'argc (?:>=|==) (\d+) && word_is \( buf , offs \[ 2 \] , '
    r'lens \[ 2 \] , "(\w+)" \)'
)
_BOOL_GUARD = re.compile(
    r'bool is_(\w+) = argc >= (\d+) && word_is \( buf , offs \[ 1 \] , '
    r'lens \[ 1 \] , "(\w+)" \)'
)
_OFFS_IDX = re.compile(r"offs \[ (\d+) \]")

# source-literal spellings of the fixed reply fragments
_LIT_OK = '"+OK\\r\\n"'
_LIT_NULL = '"$-1\\r\\n"'
_LIT_ARR0 = '"*0\\r\\n"'
_LIT_ARR2 = '"*2\\r\\n$"'


def _iter_item_lists(block, depth=0):
    """Yield (token/group item list, loop depth) for every expression
    context in a statement tree."""
    for st in block.stmts:
        if isinstance(st, cpp_ast.ExprStmt):
            yield st.items, depth
        elif isinstance(st, cpp_ast.Return):
            yield st.value, depth
        elif isinstance(st, cpp_ast.If):
            yield st.cond, depth
            yield from _iter_item_lists(st.then, depth)
            if st.orelse is not None:
                yield from _iter_item_lists(st.orelse, depth)
        elif isinstance(st, cpp_ast.Loop):
            yield st.header, depth
            yield from _iter_item_lists(st.body, depth + 1)
        elif isinstance(st, cpp_ast.Block):
            yield from _iter_item_lists(st, depth)


def _block_text(block) -> str:
    return " ; ".join(
        cpp_ast.render(items) for items, _ in _iter_item_lists(block)
    )


def _native_replies(blocks, which_value=None) -> list[str]:
    """Canonical reply shapes emitted by a subcommand body."""
    lits: list[tuple[str, int]] = []
    fmts: list[str] = []
    data_memcpy = False
    for block in blocks:
        for items, depth in _iter_item_lists(block):
            for t in cpp_ast.flat_tokens(items):
                if t.kind == "str":
                    lits.append((t.text, depth))
            for g in cpp_ast.find_calls(items, "fmt_int_reply"):
                a = cpp_ast.split_args(g)
                if len(a) >= 3:
                    fmts.append(cpp_ast.render(a[2]))
            for g in cpp_ast.find_calls(items, "memcpy"):
                a = cpp_ast.split_args(g)
                if len(a) >= 2 and "-> data ( )" in cpp_ast.render(a[1]):
                    data_memcpy = True
    reps: set[str] = set()
    for text, _ in lits:
        if text == _LIT_OK:
            reps.add("+OK")
        elif text == _LIT_NULL:
            reps.add("$-1")
        elif text == _LIT_ARR0:
            reps.add("*0")
    comp = [d for text, d in lits if text == _LIT_ARR2]
    # `fmt_pair(...)` writes the same pair (serve_engine.cpp)
    for block in blocks:
        for items, depth in _iter_item_lists(block):
            comp += [depth for _g in cpp_ast.find_calls(items, "fmt_pair")]
    if comp:
        # the pair-array composite swallows its own $bulk/:u64 parts
        if any(d > 0 for d in comp):
            reps.add("*n[*2[$bulk,:u64]]")
        if any(d == 0 for d in comp):
            reps.add("*2[$bulk,:u64]")
    else:
        if data_memcpy:
            reps.add("$bulk")  # memoised oracle-rendered bulk reply
        for signed in fmts:
            if signed == "true":
                reps.add(":i64")
            elif signed == "false":
                reps.add(":u64")
            else:  # `which == 1`: signed exactly for PNCOUNT
                reps.add(":i64" if which_value == 1 else ":u64")
    return sorted(reps)


def _native_args(blocks) -> tuple[list[int], list[int]]:
    """(strict u64 client-arg indexes, optional u64 client-arg indexes)
    from the `parse_amount` guards: a failed strict parse defers to the
    oracle's help path; a failed optional parse means "all"."""
    u64: set[int] = set()
    opt: set[int] = set()
    for block in blocks:
        for st in cpp_ast.walk(block):
            if not isinstance(st, cpp_ast.If):
                continue
            calls = list(cpp_ast.find_calls(st.cond, "parse_amount"))
            if not calls:
                continue
            a = cpp_ast.split_args(calls[0])
            m = _OFFS_IDX.search(cpp_ast.render(a[0])) if a else None
            if m is None:
                continue
            idx = int(m.group(1))
            then_txt = _block_text(st.then)
            if "UINT64_MAX" in then_txt and "defer ( )" not in then_txt:
                opt.add(idx)
            else:
                u64.add(idx)
    return sorted(u64), sorted(opt)


def _native_defers(blocks) -> list[str]:
    """Rendered guard conditions of every `return defer()` — the exact
    predicates under which the engine bounces to the oracle. A `return
    no_room(need)` (the reply does not fit the reply buffer) defers only
    past the buffer's ceiling: below it the buffer is flushed or grown."""
    out: list[str] = []

    def rec(block, conds):
        for st in block.stmts:
            if isinstance(st, cpp_ast.Return):
                value = cpp_ast.render(st.value)
                if value == "defer ( )":
                    out.append(" && ".join(conds) if conds else "fallthrough")
                elif value == "no_room ( need )":
                    out.append(" && ".join(conds + ["need > out_ceil"]))
            elif isinstance(st, cpp_ast.If):
                c = cpp_ast.render(st.cond)
                rec(st.then, conds + [c])
                if st.orelse is not None:
                    rec(st.orelse, conds + [f"! ( {c} )"])
            elif isinstance(st, cpp_ast.Loop):
                rec(st.body, conds)
            elif isinstance(st, cpp_ast.Block):
                rec(st, conds)

    for b in blocks:
        rec(b, [])
    seen: set[str] = set()
    uniq = []
    for d in out:
        if d not in seen:
            seen.add(d)
            uniq.append(d)
    return uniq


def _native_error_mode(blocks) -> str:
    for block in blocks:
        for items, _ in _iter_item_lists(block):
            for t in cpp_ast.flat_tokens(items):
                if t.kind == "str" and t.text.startswith('"-'):
                    return "inline-error"
    return "defer"


def _native_grammar(min_argc, blocks, which_value=None,
                    validators=None) -> dict:
    u64, opt = _native_args(blocks)
    return {
        "min_argc": min_argc,
        "u64_args": u64,
        "opt_u64_args": opt,
        "validators": validators or [],
        "replies": _native_replies(blocks, which_value),
        "error_mode": _native_error_mode(blocks),
        "defers": _native_defers(blocks),
    }


def _extract_counter_block(block, which_types, out) -> None:
    polarity_body = None
    guards = []  # (sub, min_argc, restrict_to_which, then_block)
    for st in block.stmts:
        if not isinstance(st, cpp_ast.If):
            continue
        cond = cpp_ast.render(st.cond)
        m = _GUARD1.search(cond)
        if m:
            then_txt = _block_text(st.then)
            pm = re.search(r"polarity = (\d+)", then_txt)
            restrict = 1 if "which == 1" in cond else None
            guards.append(
                (m.group(2), int(m.group(1)), restrict, st.then, pm is not None)
            )
        elif cond == "polarity >= 0":
            polarity_body = st.then
    for sub, min_argc, restrict, then, is_polarity in guards:
        blocks = [polarity_body] if is_polarity and polarity_body else [then]
        for wv, tname in sorted(which_types.items()):
            if restrict is not None and wv != restrict:
                continue
            out[f"{tname} {sub}"] = _native_grammar(min_argc, blocks, wv)


def _extract_ujson_block(tname, block, out) -> None:
    shared = []  # the write-path statements after the bool guards
    flags: dict[str, tuple[str, int]] = {}  # is_<x> suffix -> (SUB, argc)
    for st in block.stmts:
        if isinstance(st, cpp_ast.If):
            m = _GUARD1.search(cpp_ast.render(st.cond))
            if m:
                out[f"{tname} {m.group(2)}"] = _native_grammar(
                    int(m.group(1)), [st.then]
                )
                continue
        if isinstance(st, cpp_ast.ExprStmt):
            m = _BOOL_GUARD.search(cpp_ast.render(st.items))
            if m:
                flags[m.group(1)] = (m.group(3), int(m.group(2)))
                continue
        shared.append(st)
    if not flags:
        return
    shared_block = cpp_ast.Block(shared)
    # per-sub value validators from the flag-guarded ok assignments
    validators: dict[str, list] = {sub: [] for sub, _ in flags.values()}
    for st in cpp_ast.walk(shared_block):
        if not isinstance(st, cpp_ast.If):
            continue
        cond = cpp_ast.render(st.cond)
        then_txt = _block_text(st.then)
        for suffix, (sub, _) in flags.items():
            if f"is_{suffix}" not in cond:
                continue
            for check in ("ujson_prim_ok", "ujson_doc_ok"):
                if check in then_txt:
                    validators[sub].append({"arg": "last", "check": check})
    if "utf8_valid" in _block_text(shared_block):
        for sub in validators:
            validators[sub].append({"arg": "path", "check": "utf8_valid"})
    for sub, min_argc in flags.values():
        out[f"{tname} {sub}"] = _native_grammar(
            min_argc, [shared_block], validators=validators[sub]
        )


def extract_native(path: str = SERVE_ENGINE) -> dict[str, dict]:
    """{"TYPE SUB": grammar} from the engine's dispatch statement tree."""
    unit = cpp_ast.parse_file(path)
    fn = unit.functions["jy_eng_scan_apply2"]
    loop = [s for s in fn.body.stmts if isinstance(s, cpp_ast.Loop)][-1]
    which_types: dict[int, str] = {}
    out: dict[str, dict] = {}
    for st in loop.body.stmts:
        if not isinstance(st, cpp_ast.If):
            continue
        cond = cpp_ast.render(st.cond)
        m = _GUARD0.search(cond)
        if m:
            then_txt = _block_text(st.then)
            wm = re.fullmatch(r"which = (\d+)", then_txt)
            if wm:
                which_types[int(wm.group(1))] = m.group(2)
            elif m.group(2) == "UJSON":
                _extract_ujson_block(m.group(2), st.then, out)
            elif _INNER.search(cond):
                # "MAP TREG GET": the command's words up to its verb
                head = f"{m.group(2)} {_INNER.search(cond).group(1)}"
                for sst in st.then.stmts:
                    if not isinstance(sst, cpp_ast.If):
                        continue
                    sm = _GUARD2.search(cpp_ast.render(sst.cond))
                    if sm:
                        out[f"{head} {sm.group(2)}"] = _native_grammar(
                            int(sm.group(1)), [sst.then]
                        )
            else:
                inner: dict[str, dict] = {}
                for sst in st.then.stmts:
                    if not isinstance(sst, cpp_ast.If):
                        continue
                    sm = _GUARD1.search(cpp_ast.render(sst.cond))
                    if sm:
                        inner[f"{m.group(2)} {sm.group(2)}"] = _native_grammar(
                            int(sm.group(1)), [sst.then]
                        )
                out.update(inner)
        elif cond == "which >= 0":
            _extract_counter_block(st.then, which_types, out)
    return out


# ---- python extraction (stdlib ast + one-level helper inlining) ------------


def _fold_int(node):
    """Constant-fold an int expression (literals and + - * //)."""
    if isinstance(node, ast.Constant) and isinstance(node.value, int):
        return node.value
    if isinstance(node, ast.BinOp):
        left, right = _fold_int(node.left), _fold_int(node.right)
        if left is None or right is None:
            return None
        if isinstance(node.op, ast.Mult):
            return left * right
        if isinstance(node.op, ast.Add):
            return left + right
        if isinstance(node.op, ast.Sub):
            return left - right
        if isinstance(node.op, ast.FloorDiv):
            return left // right
    return None


def _is_name_call(node, name):
    return (
        isinstance(node, ast.Call)
        and isinstance(node.func, ast.Name)
        and node.func.id == name
    )


def _self_methods(nodes) -> set[str]:
    """Every self.<method> referenced anywhere under the given nodes."""
    found: set[str] = set()
    for root in nodes:
        for n in ast.walk(root):
            if (
                isinstance(n, ast.Attribute)
                and isinstance(n.value, ast.Name)
                and n.value.id == "self"
            ):
                found.add(n.attr)
    return found


def _py_facts(stmts, resolve) -> dict:
    """Argument-grammar facts from a dispatch branch: need()/parse_u64
    indexes on the literal name `args`, `len(args) < N` raises, and
    ValueError->ParseError value validation — helpers resolved through
    `resolve` are scanned too (transitively, cycle-safe)."""
    bodies: list = list(stmts)
    seen_methods: set[str] = set()
    frontier = _self_methods(bodies)
    while frontier:
        nxt: set[str] = set()
        for m in frontier:
            if m in seen_methods:
                continue
            seen_methods.add(m)
            fn = resolve(m)
            if fn is not None:
                bodies.extend(fn.body)
                nxt |= _self_methods(fn.body)
        frontier = nxt - seen_methods
    needs: set[int] = set()
    u64: set[int] = set()
    opt: set[int] = set()
    len_min = 0
    value_parse = False
    raises = False
    for root in bodies:
        for n in ast.walk(root):
            if isinstance(n, ast.Call):
                args_first = (
                    n.args
                    and isinstance(n.args[0], ast.Name)
                    and n.args[0].id == "args"
                )
                if _is_name_call(n, "need") and args_first and len(n.args) == 2:
                    idx = _fold_int(n.args[1])
                    if idx is not None:
                        needs.add(idx)
                if (
                    _is_name_call(n, "parse_opt_count")
                    and args_first
                    and len(n.args) == 2
                ):
                    idx = _fold_int(n.args[1])
                    if idx is not None:
                        opt.add(idx)
                if _is_name_call(n, "parse_u64") and n.args:
                    a = n.args[0]
                    idx = None
                    if _is_name_call(a, "need") and len(a.args) == 2:
                        idx = _fold_int(a.args[1])
                    elif (
                        isinstance(a, ast.Subscript)
                        and isinstance(a.value, ast.Name)
                        and a.value.id == "args"
                    ):
                        idx = _fold_int(a.slice)
                    if idx is not None:
                        u64.add(idx)
            if isinstance(n, ast.Compare) and len(n.ops) == 1:
                left = n.left
                if (
                    isinstance(n.ops[0], ast.Lt)
                    and _is_name_call(left, "len")
                    and left.args
                    and isinstance(left.args[0], ast.Name)
                    and left.args[0].id == "args"
                ):
                    bound = _fold_int(n.comparators[0])
                    if bound is not None:
                        len_min = max(len_min, bound)
            if isinstance(n, ast.Try):
                catches_value_error = any(
                    h.type is not None and "ValueError" in ast.dump(h.type)
                    for h in n.handlers
                )
                reraises = any(
                    isinstance(x, ast.Raise)
                    for h in n.handlers
                    for x in ast.walk(h)
                )
                if catches_value_error and reraises:
                    value_parse = True
            if isinstance(n, ast.Raise):
                raises = True
    min_py = len_min
    if needs:
        min_py = max(min_py, max(needs) + 1)
    if u64:
        min_py = max(min_py, max(u64) + 1)
    validators = []
    if value_parse:
        validators.append({"arg": "last", "check": "value_parse"})
    return {
        # oracle `args` excludes the type word: client argc = len + 1
        "min_argc": min_py + 1,
        "u64_args": sorted(i + 1 for i in u64),
        "opt_u64_args": sorted(i + 1 for i in opt),
        "validators": validators,
        "errors": (
            ["ParseError -> datatype help"]
            if (raises or needs or u64)
            else []
        ),
    }


def _resp_event(call) -> str | None:
    """Canonical reply event for a `resp.<method>(...)` call."""
    if not (
        isinstance(call, ast.Call)
        and isinstance(call.func, ast.Attribute)
        and isinstance(call.func.value, ast.Name)
        and call.func.value.id == "resp"
    ):
        return None
    m = call.func.attr
    if m == "ok":
        return "+OK"
    if m == "u64":
        return ":u64"
    if m == "i64":
        return ":i64"
    if m == "string":
        return "$bulk"
    if m == "null":
        return "$-1"
    if m == "array_start":
        n = _fold_int(call.args[0]) if call.args else None
        if n == 0:
            return "*0"
        if n is None:
            return "*n["
        return f"*{n}["
    return None


def _alts_stmts(stmts, resolve, visited) -> set:
    alts = {((), False)}
    for s in stmts:
        new = set()
        for ev, done in alts:
            if done:
                new.add((ev, done))
                continue
            for ev2, done2 in _alts_stmt(s, resolve, visited):
                new.add((ev + ev2, done2))
        alts = new
    return alts


def _alts_stmt(s, resolve, visited) -> set:
    if isinstance(s, (ast.Return, ast.Raise)):
        return {((), True)}
    if isinstance(s, ast.If):
        return _alts_stmts(s.body, resolve, visited) | _alts_stmts(
            s.orelse, resolve, visited
        )
    if isinstance(s, (ast.For, ast.While)):
        inner = _alts_stmts(s.body, resolve, visited)
        outs = set()
        for ev, _ in inner:
            outs.add(((("loop", ev),), False) if ev else ((), False))
        return outs or {((), False)}
    if isinstance(s, ast.Try):
        outs = _alts_stmts(s.body, resolve, visited)
        for h in s.handlers:
            outs |= _alts_stmts(h.body, resolve, visited)
        return outs
    if isinstance(s, ast.Expr) and isinstance(s.value, ast.Call):
        ev = _resp_event(s.value)
        if ev is not None:
            return {((ev,), False)}
        call = s.value
        if (
            isinstance(call.func, ast.Attribute)
            and isinstance(call.func.value, ast.Name)
            and call.func.value.id == "self"
            and any(
                isinstance(a, ast.Name) and a.id == "resp" for a in call.args
            )
            and call.func.attr not in visited
        ):
            fn = resolve(call.func.attr)
            if fn is not None:
                return _alts_stmts(
                    fn.body, resolve, visited | {call.func.attr}
                )
    return {((), False)}


def _canon_events(events) -> list[str]:
    out: list[str] = []
    i = 0
    while i < len(events):
        e = events[i]
        if isinstance(e, tuple) and e and e[0] == "loop":
            out.append("loop(" + ",".join(_canon_events(e[1])) + ")")
            i += 1
            continue
        if isinstance(e, str) and e.startswith("*") and e.endswith("["):
            hdr = e[1:-1]
            i += 1
            if hdr == "n":
                if (
                    i < len(events)
                    and isinstance(events[i], tuple)
                    and events[i][0] == "loop"
                ):
                    inner = _canon_events(events[i][1])
                    i += 1
                else:
                    inner = []
                out.append("*n[" + ",".join(inner) + "]")
            else:
                k = int(hdr)
                elems: list[str] = []
                while len(elems) < k and i < len(events):
                    elems.extend(_canon_events([events[i]]))
                    i += 1
                out.append(f"*{k}[" + ",".join(elems) + "]")
            continue
        out.append(e)
        i += 1
    return out


def _py_replies(stmts, resolve) -> list[str]:
    shapes: set[str] = set()
    for ev, _ in _alts_stmts(stmts, resolve, set()):
        if not ev:
            continue  # pure-error path: no reply events
        shapes.add("+".join(_canon_events(list(ev))))
    return sorted(shapes)


def extract_python(models_dir: str = MODELS_DIR) -> dict[str, dict]:
    """{"TYPE SUB": grammar} from every repo class's `apply` dispatch."""
    out: dict[str, dict] = {}
    for fname in sorted(os.listdir(models_dir)):
        if not (fname.startswith("repo_") and fname.endswith(".py")):
            continue
        path = os.path.join(models_dir, fname)
        with open(path, encoding="utf-8") as f:
            tree = ast.parse(f.read(), filename=path)
        classes = {
            c.name: c for c in tree.body if isinstance(c, ast.ClassDef)
        }
        methods = {
            cname: {
                m.name: m
                for m in c.body
                if isinstance(m, ast.FunctionDef)
            }
            for cname, c in classes.items()
        }

        def make_resolver(cname):
            def resolve(mname):
                cur = cname
                while cur is not None:
                    if mname in methods.get(cur, {}):
                        return methods[cur][mname]
                    bases = [
                        b.id
                        for b in classes[cur].bases
                        if isinstance(b, ast.Name) and b.id in classes
                    ]
                    cur = bases[0] if bases else None
                return None

            return resolve

        for cname, cls in classes.items():
            tname = None
            for stmt in cls.body:
                if (
                    isinstance(stmt, ast.Assign)
                    and len(stmt.targets) == 1
                    and isinstance(stmt.targets[0], ast.Name)
                    and stmt.targets[0].id == "name"
                    and isinstance(stmt.value, ast.Constant)
                    and isinstance(stmt.value.value, str)
                ):
                    tname = stmt.value.value
            resolve = make_resolver(cname)
            apply_fn = methods.get(cname, {}).get("apply")
            if tname is None or apply_fn is None:
                continue
            for st in apply_fn.body:
                # a dispatch branch is a top-level `if` whose test is a
                # BARE compare of `op` against bytes constants (guards
                # like `op in (...) and len(args) >= 2` are preludes)
                if not (isinstance(st, ast.If) and isinstance(st.test, ast.Compare)):
                    continue
                operands = [st.test.left] + list(st.test.comparators)
                flat: list[ast.expr] = []
                for o in operands:
                    if isinstance(o, (ast.Tuple, ast.List, ast.Set)):
                        flat.extend(o.elts)
                    else:
                        flat.append(o)
                if not any(
                    isinstance(o, ast.Name) and o.id == "op" for o in flat
                ):
                    continue
                subs = [
                    o.value.decode("ascii", "replace")
                    for o in flat
                    if isinstance(o, ast.Constant)
                    and isinstance(o.value, bytes)
                ]
                subs = [s for s in subs if s.isupper() and s.isalpha()]
                if not subs:
                    continue
                rec = _py_facts(st.body, resolve)
                rec["replies"] = _py_replies(st.body, resolve)
                for sub in subs:
                    out[f"{tname} {sub}"] = rec
    return out


# ---- transport + thresholds ------------------------------------------------


def _eval_cpp_int(text: str):
    total = 1
    for part in text.split("*"):
        digits = re.sub(r"[A-Za-z']", "", part).strip()
        if not digits.isdigit():
            return None
        total *= int(digits)
    return total


def extract_transport() -> dict:
    unit = cpp_ast.parse_file(RESP_PARSER)
    native = {
        name: _eval_cpp_int(unit.constants.get(name, ""))
        for name in ("MAX_LINE", "MAX_BULK", "MAX_ARRAY")
    }
    with open(RESP_PY, encoding="utf-8") as f:
        tree = ast.parse(f.read(), filename=RESP_PY)
    bulk = None
    guards: set[int] = set()
    for n in ast.walk(tree):
        if (
            isinstance(n, ast.Assign)
            and len(n.targets) == 1
            and isinstance(n.targets[0], ast.Name)
            and n.targets[0].id == "_MAX_BULK"
        ):
            bulk = _fold_int(n.value)
        if isinstance(n, ast.Compare) and len(n.ops) == 1 and isinstance(
            n.ops[0], ast.Gt
        ):
            v = _fold_int(n.comparators[0])
            if v is not None and v > 1:
                guards.add(v)
    guards.discard(bulk)
    python = {
        "MAX_LINE": min(guards) if guards else None,
        "MAX_BULK": bulk,
        "MAX_ARRAY": max(guards) if guards else None,
    }
    divergences = [
        f"transport: native {name}={native[name]} != oracle {python[name]}"
        for name in ("MAX_LINE", "MAX_BULK", "MAX_ARRAY")
        if native[name] != python[name]
    ]
    return {"native": native, "python": python, "divergences": divergences}


def extract_thresholds() -> dict:
    units: dict[str, cpp_ast.Unit] = {}
    py_consts: dict[str, dict[str, int]] = {}
    out: dict[str, dict] = {}
    for cname, cpath, ppath, pname in _THRESHOLDS:
        if cpath not in units:
            units[cpath] = cpp_ast.parse_file(cpath)
        if ppath not in py_consts:
            with open(ppath, encoding="utf-8") as f:
                tree = ast.parse(f.read(), filename=ppath)
            consts: dict[str, int] = {}
            for n in ast.walk(tree):
                if (
                    isinstance(n, ast.Assign)
                    and len(n.targets) == 1
                    and isinstance(n.targets[0], ast.Name)
                ):
                    v = _fold_int(n.value)
                    if v is not None:
                        consts[n.targets[0].id] = v
            py_consts[ppath] = consts
        native = _eval_cpp_int(units[cpath].constants.get(cname, ""))
        python = py_consts[ppath].get(pname)
        rec = {"native": native, "python": python, "divergences": []}
        if native != python:
            rec["divergences"] = [
                f"threshold: native {cname}={native} != oracle "
                f"{pname}={python}"
            ]
        out[cname] = rec
    return out


# ---- manifest --------------------------------------------------------------


def _diff(native: dict, python: dict) -> list[str]:
    out: list[str] = []
    if native["min_argc"] != python["min_argc"]:
        out.append(
            f"arity: native min_argc {native['min_argc']} != oracle "
            f"{python['min_argc']}"
        )
    if native["u64_args"] != python["u64_args"]:
        out.append(
            f"u64-args: native {native['u64_args']} != oracle "
            f"{python['u64_args']}"
        )
    if native["opt_u64_args"] != python["opt_u64_args"]:
        out.append(
            f"opt-u64-args: native {native['opt_u64_args']} != oracle "
            f"{python['opt_u64_args']}"
        )
    if native["replies"] != python["replies"]:
        out.append(
            f"replies: native {native['replies']} != oracle "
            f"{python['replies']}"
        )
    if native["error_mode"] != "defer":
        out.append(
            "errors: native emits inline error replies; the oracle's "
            "ParseError help path is the only error surface"
        )
    return out


def _load_committed(path: str = SEMANTICS_MANIFEST_PATH) -> dict:
    try:
        with open(path, encoding="utf-8") as f:
            return json.load(f)
    except (OSError, ValueError):
        return {}


def build_manifest(old: dict | None = None) -> dict:
    if old is None:
        old = _load_committed()
    native = extract_native()
    python = extract_python()
    old_cmds = old.get("commands", {})
    commands: dict[str, dict] = {}
    for key in sorted(native):
        nat = native[key]
        # the oracle dispatches a composed type's command ("MAP TREG
        # GET") on its type and verb, whatever the inner type
        words = key.split(" ")
        py = python.get(f"{words[0]} {words[-1]}")
        divergences = (
            _diff(nat, py)
            if py is not None
            else ["oracle-missing: no Python dispatch path extracted"]
        )
        commands[key] = {
            "native": nat,
            "python": py,
            "divergences": divergences,
            "justified": old_cmds.get(key, {}).get("justified", []),
            "note": old_cmds.get(key, {}).get("note", PLACEHOLDER),
        }
    return {
        "_comment": (
            "Generated by `python -m scripts.jlint --write-manifest` from "
            "native/serve_engine.cpp (via scripts/jlint/cpp_ast.py), "
            "native/resp_parser.cpp, native/engine.h and "
            "jylis_tpu/models/repo_*.py. Grammar, replies, divergences, "
            "transport and thresholds are mechanical — do not edit; "
            "`justified` and `note` are human-written and preserved. "
            "`make lint` fails on drift or placeholder notes (JL1103), "
            "unjustified grammar/bounds divergence (JL1101), and "
            "unjustified reply-shape/error divergence (JL1102). After any "
            "change, re-record the fuzz corpus with --write-corpus."
        ),
        "commands": commands,
        "transport": extract_transport(),
        "thresholds": extract_thresholds(),
    }


def write_manifest(path: str = SEMANTICS_MANIFEST_PATH) -> dict:
    manifest = build_manifest()
    with open(path, "w", encoding="utf-8") as f:
        json.dump(manifest, f, indent=2, sort_keys=True)
        f.write("\n")
    from .. import gen_semfuzz

    with open(HARNESS_PATH, "w", encoding="utf-8") as f:
        f.write(gen_semfuzz.render_harness(manifest))
    return manifest


# ---- check -----------------------------------------------------------------


def check(
    manifest_path: str = SEMANTICS_MANIFEST_PATH,
    harness_path: str = HARNESS_PATH,
) -> list[Finding]:
    out: list[Finding] = []
    rel = os.path.relpath(manifest_path, ROOT)
    committed = _load_committed(manifest_path)
    if not committed:
        out.append(
            Finding(
                "JL1103", rel, 1,
                "semantics manifest missing/unreadable — run `python -m "
                "scripts.jlint --write-manifest` and commit it",
                "",
            )
        )
        return out
    current = build_manifest(committed)
    cur_cmds = current["commands"]
    com_cmds = committed.get("commands", {})

    for key, rec in cur_cmds.items():
        crec = com_cmds.get(key)
        if crec is None:
            out.append(
                Finding(
                    "JL1103", rel, 1,
                    f"`{key}` is served natively but absent from the "
                    "semantics manifest — run --write-manifest, describe "
                    "the contract, commit",
                    key,
                )
            )
            continue
        for fieldname in ("native", "python", "divergences"):
            if crec.get(fieldname) != rec[fieldname]:
                out.append(
                    Finding(
                        "JL1103", rel, 1,
                        f"semantics manifest drift: `{key}` / "
                        f"`{fieldname}` committed "
                        f"{json.dumps(crec.get(fieldname), sort_keys=True)} "
                        f"!= extracted "
                        f"{json.dumps(rec[fieldname], sort_keys=True)} — "
                        "run --write-manifest, review, commit",
                        key,
                    )
                )
        justified = crec.get("justified", [])
        for j in justified:
            if j not in rec["divergences"]:
                out.append(
                    Finding(
                        "JL1103", rel, 1,
                        f"stale justification on `{key}`: "
                        f"{json.dumps(j)} no longer matches any extracted "
                        "divergence — delete it",
                        key,
                    )
                )
        note = crec.get("note", "")
        if not str(note).strip() or note == PLACEHOLDER:
            out.append(
                Finding(
                    "JL1103", rel, 1,
                    f"`{key}` has no note — one line on the cross-language "
                    "contract (what the engine serves, when it defers)",
                    key,
                )
            )
        for d in rec["divergences"]:
            if d in justified:
                continue
            rule = (
                "JL1102"
                if d.startswith(("replies", "errors", "oracle-missing"))
                else "JL1101"
            )
            out.append(
                Finding(
                    rule, "native/serve_engine.cpp", 1,
                    f"`{key}` diverges from the oracle: {d} — fix the "
                    "divergence (with a pinning test) or add the exact "
                    "string to the manifest's `justified` list with a note",
                    key,
                )
            )
    for key in com_cmds:
        if key not in cur_cmds:
            out.append(
                Finding(
                    "JL1103", rel, 1,
                    f"manifest entry `{key}` no longer matches any "
                    "natively-served command — run --write-manifest",
                    key,
                )
            )

    for section in ("transport", "thresholds"):
        if committed.get(section) != current[section]:
            out.append(
                Finding(
                    "JL1103", rel, 1,
                    f"semantics manifest drift in `{section}` — run "
                    "--write-manifest, review, commit",
                    section,
                )
            )
    for d in current["transport"]["divergences"]:
        out.append(
            Finding("JL1101", "native/resp_parser.cpp", 1,
                    f"{d} — the parsers must reject identical inputs", d)
        )
    for name, rec in current["thresholds"].items():
        for d in rec["divergences"]:
            out.append(
                Finding(
                    "JL1101", "native/serve_engine.cpp", 1,
                    f"{d} — the native defer predicate and the oracle "
                    "drain predicate must agree",
                    d,
                )
            )

    # coverage: every pass-3 native command must have a manifest entry
    covered = {(k.split(" ")[0], k.split(" ")[-1]) for k in cur_cmds}
    for t, subs in pass_parity.extract_native().items():
        for sub in subs:
            if (t, sub) not in covered:
                out.append(
                    Finding(
                        "JL1103", rel, 1,
                        f"`{t} {sub}` is dispatched natively (pass 3) but "
                        "the semantic extractor produced no entry — "
                        "cpp_ast extraction is incomplete",
                        f"{t} {sub}",
                    )
                )

    # generated differential-fuzz harness must match a fresh render
    from .. import gen_semfuzz

    hrel = os.path.relpath(harness_path, ROOT)
    try:
        with open(harness_path, encoding="utf-8") as f:
            committed_harness = f.read()
    except OSError:
        committed_harness = None
    if committed_harness != gen_semfuzz.render_harness(current):
        out.append(
            Finding(
                "JL1103", hrel, 1,
                "generated semantic-fuzz harness is stale or missing — "
                "run `python -m scripts.jlint --write-manifest` and commit "
                "the regenerated file",
                "",
            )
        )
    return out
