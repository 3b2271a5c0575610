"""Pass 10 — protocol atlas (rules JL1001/JL1002/JL1003).

The cluster protocol is ~6 message kinds × an active/passive role split
× a per-address dial state machine × the sync-serve machinery. Until this pass its full
transition relation lived only in the heads of whoever last read
``cluster.py``; the drill matrix samples behaviours, it does not pin
them. This pass extracts, statically, what every handler is PERMITTED
to do and commits it as ``scripts/jlint/protocol_manifest.json`` — the
atlas jmodel (scripts/jmodel) explores and the next protocol rewrite
(digest-driven delta intervals) diffs itself against.

What is extracted, per (section, key):

* ``role:active`` / ``role:passive`` — one entry per ``isinstance(msg,
  X)`` branch of ``_active_msg`` / ``_passive_msg`` plus the
  ``<fallthrough>`` tail, mapping the branch to its canonical *effect
  tokens*: sends by message type (``send:MsgPong``), broadcasts,
  converge calls (``converge:data`` / ``converge:addrs``), state
  mutations (``set:``/``mut:``), teardown reasons (``drop:UNEXPECTED``),
  declared message drops (``msg_drop:pong_unsolicited``), metric /
  trace / histogram / gauge emissions, task spawns and failpoints.
* ``handshake`` — the pre-established state, split per role (the
  ``if active:`` branches of ``_handshake``).
* ``sync`` — the request/serve machinery (``_maybe_request_sync``,
  ``_request_sync``, ``_serve_syncs``, ``_data_frames``,
  ``_system_frames``, ``_stream_sync``, ``_send_frame``).
* ``dial`` — the per-address dial state machine (``_heartbeat``,
  ``_sync_actives``, ``_dial``, ``_active_missed``,
  ``_inbound_contact``, ``_drop``).
* ``send`` — the broadcast/held-queue path (``broadcast_deltas``,
  ``_flush_held``, ``_send_push``, ``_send_to_actives``, ``_send``,
  ``_broadcast_msg``).
* ``recv`` — the message pump (``_accept``, ``_read_loop``): framing,
  CRC and codec teardown reasons, the pre-handshake gate.

Rules:

* **JL1001** — a handler produces an effect the committed manifest does
  not declare (or a whole branch/section the manifest lacks): new
  behaviour entered the protocol unreviewed.
* **JL1002** — an undeclared fall-through: a message type from msg.py
  with no ``isinstance`` branch in a role handler whose
  ``<fallthrough>`` tail is effect-free, or any branch whose effect set
  is EMPTY — a silent ignore. Every ignore must be a declared drop
  (``Cluster._drop_msg``: counted + traced) with a reason.
* **JL1003** — manifest drift the other way: declared effects no
  handler produces any more, stale entries, a missing manifest, or a
  missing/placeholder note. ``python -m scripts.jlint --write-manifest``
  regenerates the effect sets, preserving the human-written notes.
"""

from __future__ import annotations

import ast
import json
import os

from . import Finding, ROOT, dotted_name
from .core import load_source

PROTOCOL_MANIFEST_PATH = os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "protocol_manifest.json"
)

CLUSTER_REL = os.path.join("jylis_tpu", "cluster", "cluster.py")
MSG_REL = os.path.join("jylis_tpu", "cluster", "msg.py")

PLACEHOLDER = "(describe this transition)"

HANDLERS = {"role:active": "_active_msg", "role:passive": "_passive_msg"}
SYNC_FUNCS = (
    "_maybe_request_sync", "_request_sync", "_serve_syncs",
    "_chunk_frames", "_data_frames", "_range_frames", "_serve_ranges",
    "_handle_tree", "_continue_ranges", "_force_range_repair",
    "_track_seq", "_system_frames", "_stream_sync", "_send_frame",
)
DIAL_FUNCS = (
    "_heartbeat", "_sync_actives", "_dial", "_active_missed",
    "_inbound_contact", "_drop",
)
RECV_FUNCS = ("_accept", "_read_loop")
SEND_FUNCS = (
    "broadcast_deltas", "_log_delta", "_retransmit_unacked",
    "_send_reset", "_flush_held", "_send_push", "_send_to_actives",
    "_send", "_broadcast_msg",
)

# query-only helpers whose calls are not effects (they mutate nothing
# and send nothing); everything else a handler calls on self is recorded
_PURE_HELPERS = frozenset(
    {
        "_wire", "_conn_desc", "_peer_key", "_backoff_ticks",
        "_worth_holding", "_worst_lag_ms", "_backlog_ms", "lag_snapshot",
        "metrics_totals",
    }
)

# receiver-method calls that mutate protocol state when the receiver is
# rooted at self/conn (deque/list/set/dict mutators + close/cancel)
_MUTATORS = frozenset(
    {
        "append", "appendleft", "pop", "popleft", "add", "discard",
        "remove", "clear", "extend", "update", "close", "cancel",
    }
)


# ---- effect extraction ------------------------------------------------------


def _rooted(dotted: str) -> bool:
    return dotted.startswith("self.") or dotted.startswith("conn.")


def _msg_ctor(node: ast.AST) -> str | None:
    """`MsgPong()` / `MsgSyncDone()` argument -> the message class name."""
    if isinstance(node, ast.Call):
        name = dotted_name(node.func).split(".")[-1]
        if name.startswith("Msg"):
            return name
    return None


def _const_attr(node: ast.AST, owner: str) -> str | None:
    """`Drop.IDLE` / `MsgDrop.PONG_UNMATCHED` -> the constant name."""
    if (
        isinstance(node, ast.Attribute)
        and isinstance(node.value, ast.Name)
        and node.value.id == owner
    ):
        return node.attr
    return None


def _classify_call(call: ast.Call) -> str | None:
    name = dotted_name(call.func)
    if name == "self._send" and len(call.args) >= 2:
        ctor = _msg_ctor(call.args[1])
        return f"send:{ctor or '?'}"
    if name == "self._broadcast_msg" and call.args:
        ctor = _msg_ctor(call.args[0])
        return f"broadcast:{ctor or '?'}"
    if name == "self._send_to_actives":
        return "broadcast:frame"
    if name.endswith(".send_raw"):
        return "send:raw"
    if name == "self._drop":
        reason = "EOF"
        if len(call.args) >= 2:
            reason = _const_attr(call.args[1], "Drop") or "?"
        for kw in call.keywords:
            if kw.arg == "reason":
                reason = _const_attr(kw.value, "Drop") or "?"
        return f"drop:{reason}"
    if name == "self._drop_msg" and len(call.args) >= 2:
        const = _const_attr(call.args[1], "MsgDrop")
        return f"msg_drop:{const or '?'}"
    if name == "self._database.converge_async":
        return "converge:data"
    if name == "self._converge_addrs":
        return "converge:addrs"
    if name.startswith("self._database."):
        return f"db:{name.split('.')[-1]}"
    if name == "self._record_push_lag":
        return "lag:push"
    if name == "self._note_lag":
        return "lag:note"
    if name in ("self._h_rtt.record", "self._h_lag.record"):
        seam = "cluster.rtt" if "_h_rtt" in name else "cluster.converge_lag"
        return f"hist:{seam}"
    if name == "self._reg.trace_event":
        lits = [
            a.value
            for a in call.args[:2]
            if isinstance(a, ast.Constant) and isinstance(a.value, str)
        ]
        return "trace:" + (".".join(lits) if len(lits) == 2 else "?")
    if name == "self._reg.gauge_set":
        a = call.args[0] if call.args else None
        lit = a.value if isinstance(a, ast.Constant) else "?"
        return f"gauge:{lit}"
    if name in ("faults.point", "faults.async_point"):
        a = call.args[0] if call.args else None
        lit = a.value if isinstance(a, ast.Constant) else "?"
        return f"failpoint:{lit}"
    if name.endswith("create_task") and call.args:
        inner = call.args[0]
        if isinstance(inner, ast.Call):
            target = dotted_name(inner.func)
            if target.startswith("self._database."):
                return f"task:db.{target.split('.')[-1]}"
            if target.startswith("self."):
                return f"task:{target.split('.', 1)[1]}"
        return "task:?"
    if isinstance(call.func, ast.Attribute):
        meth = call.func.attr
        recv = dotted_name(call.func.value)
        if meth in _MUTATORS and _rooted(recv + "."):
            return f"mut:{recv}.{meth}"
    parts = name.split(".")
    if len(parts) == 2 and parts[0] == "self":
        meth = parts[1]
        if meth in _PURE_HELPERS or meth.startswith("_log"):
            return None
        return f"call:{meth}"
    return None


def _target_effects(
    target: ast.AST, out: set[str], aliases: dict[str, str] | None = None
) -> None:
    if isinstance(target, ast.Tuple):
        for elt in target.elts:
            _target_effects(elt, out, aliases)
        return
    if isinstance(target, ast.Attribute):
        dotted = dotted_name(target)
        if _rooted(dotted):
            out.add(f"set:{dotted}")
        elif aliases:
            root = dotted.split(".")[0]
            if root in aliases:
                # mutation through a local alias of a self-rooted
                # collection entry (a _PeerState, typically)
                out.add(f"set:{aliases[root]}[]")
        return
    if isinstance(target, ast.Subscript):
        dotted = dotted_name(target.value)
        if dotted == "self._stats":
            key = target.slice
            lit = key.value if isinstance(key, ast.Constant) else "?"
            out.add(f"stat:{lit}")
        elif _rooted(dotted):
            out.add(f"set:{dotted}[]")


def _rooted_source(value: ast.AST) -> str | None:
    """The self-rooted collection a local alias points into:
    `st = self._peers.get(addr)` -> 'self._peers'. Conservative: the
    FIRST self-rooted attribute anywhere in the value expression."""
    for node in ast.walk(value):
        if isinstance(node, ast.Attribute):
            dotted = dotted_name(node)
            if dotted.startswith("self.") and "." not in dotted[5:]:
                return dotted
    return None


def _collect_aliases(stmts) -> dict[str, str]:
    """{local name: 'self.<collection>'} for locals bound from a
    self-rooted lookup (or bound alongside one in a chained assignment,
    `st = self._peers[addr] = _PeerState()`). Mutating such a local IS
    mutating protocol state; without this, `st.fails = 0` would be an
    invisible effect. `self`/`conn` stay direct-rooted, never aliased."""
    aliases: dict[str, str] = {}
    for stmt in stmts:
        for node in ast.walk(stmt):
            if not isinstance(node, ast.Assign):
                continue
            src = _rooted_source(node.value)
            if src is None:
                for t in node.targets:
                    if isinstance(t, (ast.Subscript, ast.Attribute)):
                        src = _rooted_source(t)
                        if src is not None:
                            break
            if src is None:
                continue
            for t in node.targets:
                if isinstance(t, ast.Name) and t.id not in ("self", "conn"):
                    aliases[t.id] = src
    return aliases


def collect_effects(stmts) -> set[str]:
    """The canonical effect tokens of a statement list (whole subtree)."""
    out: set[str] = set()
    aliases = _collect_aliases(stmts)
    for stmt in stmts:
        for node in ast.walk(stmt):
            if isinstance(node, ast.Call):
                eff = _classify_call(node)
                if eff is not None:
                    out.add(eff)
            elif isinstance(node, (ast.Assign, ast.AugAssign)):
                targets = (
                    node.targets if isinstance(node, ast.Assign)
                    else [node.target]
                )
                for t in targets:
                    _target_effects(t, out, aliases)
            elif isinstance(node, ast.Delete):
                for t in node.targets:
                    base = t.value if isinstance(t, ast.Subscript) else t
                    dotted = dotted_name(base)
                    if _rooted(dotted):
                        out.add(f"mut:{dotted}.del")
    return out


# ---- handler / section extraction -------------------------------------------


def _isinstance_msgs(test: ast.AST) -> list[str] | None:
    """`isinstance(msg, X)` / `isinstance(msg, (X, Y))` -> class names."""
    if not (
        isinstance(test, ast.Call)
        and isinstance(test.func, ast.Name)
        and test.func.id == "isinstance"
        and len(test.args) == 2
        and isinstance(test.args[0], ast.Name)
        and test.args[0].id == "msg"
    ):
        return None
    spec = test.args[1]
    elts = spec.elts if isinstance(spec, ast.Tuple) else [spec]
    names = [dotted_name(e).split(".")[-1] for e in elts]
    return [n for n in names if n] or None


def _handler_branches(fn: ast.AST) -> dict[str, dict]:
    """{msg class -> {effects, line}} + the '<fallthrough>' tail entry."""
    out: dict[str, dict] = {}
    tail: list[ast.AST] = []
    for stmt in fn.body:
        msgs = (
            _isinstance_msgs(stmt.test) if isinstance(stmt, ast.If) else None
        )
        if msgs:
            effects = sorted(collect_effects(stmt.body))
            for m in msgs:
                out[m] = {"effects": effects, "line": stmt.lineno}
            tail.extend(stmt.orelse)
        else:
            tail.append(stmt)
    out["<fallthrough>"] = {
        "effects": sorted(collect_effects(tail)),
        "line": fn.lineno,
    }
    return out


def _handshake_roles(fn: ast.AST) -> dict[str, dict]:
    """Split `_handshake` effects per role on its `if active:` branches;
    statements outside those ifs count for both roles."""
    eff = {"active": set(), "passive": set()}

    def go(stmts, roles):
        for stmt in stmts:
            if (
                isinstance(stmt, ast.If)
                and isinstance(stmt.test, ast.Name)
                and stmt.test.id == "active"
            ):
                if "active" in roles:
                    go(stmt.body, ("active",))
                if "passive" in roles:
                    go(stmt.orelse, ("passive",))
            else:
                found = collect_effects([stmt])
                for r in roles:
                    eff[r] |= found

    go(fn.body, ("active", "passive"))
    return {
        role: {"effects": sorted(effs), "line": fn.lineno}
        for role, effs in eff.items()
    }


def _cluster_methods(tree: ast.AST) -> dict[str, ast.AST]:
    """Every method of the (first) class defining `_active_msg` — the
    Cluster class in the product, whatever the fixture calls it."""
    classes = [n for n in tree.body if isinstance(n, ast.ClassDef)]
    for cls in classes:
        methods = {
            m.name: m
            for m in cls.body
            if isinstance(m, (ast.FunctionDef, ast.AsyncFunctionDef))
        }
        if "_active_msg" in methods:
            return methods
    return {}


def message_classes(root: str = ROOT, msg_rel: str = MSG_REL) -> list[str]:
    path = os.path.join(root, msg_rel)
    if not os.path.exists(path):
        return []
    src = load_source(path, root)
    return sorted(
        n.name
        for n in src.tree.body
        if isinstance(n, ast.ClassDef) and n.name.startswith("Msg")
    )


def extract(
    root: str = ROOT,
    cluster_rel: str = CLUSTER_REL,
    msg_rel: str = MSG_REL,
) -> dict:
    """The atlas as extracted from the source right now:
    {"messages": [...], "sections": {section: {key: {effects, line}}}}.
    Sections whose function is absent (partial fixtures) are skipped."""
    src = load_source(os.path.join(root, cluster_rel), root)
    methods = _cluster_methods(src.tree)
    sections: dict[str, dict[str, dict]] = {}
    for section, fname in HANDLERS.items():
        fn = methods.get(fname)
        if fn is not None:
            sections[section] = _handler_branches(fn)
    if "_handshake" in methods:
        sections["handshake"] = _handshake_roles(methods["_handshake"])
    for section, names in (("sync", SYNC_FUNCS), ("dial", DIAL_FUNCS),
                           ("send", SEND_FUNCS), ("recv", RECV_FUNCS)):
        entries = {}
        for fname in names:
            fn = methods.get(fname)
            if fn is not None:
                entries[fname] = {
                    "effects": sorted(collect_effects(fn.body)),
                    "line": fn.lineno,
                }
        if entries:
            sections[section] = entries
    return {
        "messages": message_classes(root, msg_rel),
        "sections": sections,
        "rel": src.rel,
    }


# ---- manifest ---------------------------------------------------------------


def load_manifest(path: str = PROTOCOL_MANIFEST_PATH) -> dict | None:
    if not os.path.exists(path):
        return None
    with open(path, encoding="utf-8") as f:
        return json.load(f)


def write_manifest(
    path: str = PROTOCOL_MANIFEST_PATH,
    root: str = ROOT,
    cluster_rel: str = CLUSTER_REL,
    msg_rel: str = MSG_REL,
) -> dict:
    """Regenerate the effect sets from the source, preserving the
    human-written notes; new entries get a placeholder JL1003 rejects
    until a human describes the transition."""
    atlas = extract(root, cluster_rel, msg_rel)
    existing = load_manifest(path) or {"sections": {}}
    sections = {}
    for section, entries in sorted(atlas["sections"].items()):
        old = existing.get("sections", {}).get(section, {})
        sections[section] = {
            key: {
                "effects": entry["effects"],
                "note": old.get(key, {}).get("note", PLACEHOLDER),
            }
            for key, entry in sorted(entries.items())
        }
    manifest = {
        "_comment": (
            "Generated by `python -m scripts.jlint --write-manifest` "
            "from jylis_tpu/cluster/cluster.py's handler dispatch, "
            "handshake, sync machinery, dial state machine and send "
            "path. Effects are mechanical; notes are human-written and "
            "preserved across regeneration. `make lint` fails on "
            "handler effects outside this manifest (JL1001), on silent "
            "(role, msg) fall-throughs (JL1002), and on drift/"
            "placeholder notes (JL1003). jmodel (scripts/jmodel) "
            "explores the same protocol dynamically."
        ),
        "schema": 1,
        "messages": atlas["messages"],
        "sections": sections,
    }
    with open(path, "w", encoding="utf-8") as f:
        json.dump(manifest, f, indent=2, sort_keys=True)
        f.write("\n")
    return manifest


# effect families that count as "observable" for the silent-ignore rule:
# a branch producing none of these does nothing a peer, operator, or
# metric can see — the exact fall-through class JL1002 forbids
_OBSERVABLE = (
    "send:", "broadcast:", "drop:", "msg_drop:", "converge:", "stat:",
    "trace:", "hist:", "gauge:", "task:", "call:", "set:", "mut:",
    "lag:", "db:", "failpoint:",
)


def _is_silent(effects: list[str]) -> bool:
    return not any(e.startswith(_OBSERVABLE) for e in effects)


def check(
    manifest_path: str = PROTOCOL_MANIFEST_PATH,
    atlas: dict | None = None,
    root: str = ROOT,
) -> list[Finding]:
    if atlas is None:
        atlas = extract(root)
    out: list[Finding] = []
    rel = os.path.relpath(manifest_path, ROOT)
    src_rel = atlas.get("rel", CLUSTER_REL)
    manifest = load_manifest(manifest_path)
    if manifest is None:
        out.append(
            Finding(
                "JL1003", rel, 1,
                "protocol manifest missing — run `python -m scripts.jlint "
                "--write-manifest`, describe each transition, commit",
                "",
            )
        )
        return out
    if manifest.get("messages") != atlas["messages"]:
        out.append(
            Finding(
                "JL1003", rel, 1,
                "message inventory drift: msg.py defines "
                f"{atlas['messages']} but the manifest declares "
                f"{manifest.get('messages')} — --write-manifest "
                "regenerates",
                "",
            )
        )
    man_sections = manifest.get("sections", {})
    for section, entries in sorted(atlas["sections"].items()):
        man_entries = man_sections.get(section, {})
        for key, entry in sorted(entries.items()):
            committed = man_entries.get(key)
            if committed is None:
                out.append(
                    Finding(
                        "JL1001", src_rel, entry["line"],
                        f"protocol atlas: `{section}` / `{key}` is not "
                        f"declared in {rel} — run --write-manifest and "
                        "describe the transition",
                        key,
                    )
                )
                continue
            extra = sorted(set(entry["effects"]) - set(committed["effects"]))
            if extra:
                out.append(
                    Finding(
                        "JL1001", src_rel, entry["line"],
                        f"`{section}` / `{key}` produces effects outside "
                        f"the manifest: {extra} — new protocol behaviour "
                        "must be declared (--write-manifest) and reviewed",
                        key,
                    )
                )
            stale = sorted(set(committed["effects"]) - set(entry["effects"]))
            if stale:
                out.append(
                    Finding(
                        "JL1003", rel, 1,
                        f"`{section}` / `{key}` declares effects no "
                        f"handler produces: {stale} — drift; "
                        "--write-manifest regenerates",
                        key,
                    )
                )
            note = committed.get("note", "")
            if not note.strip() or note.strip() == PLACEHOLDER:
                out.append(
                    Finding(
                        "JL1003", rel, 1,
                        f"`{section}` / `{key}` has no note — one line "
                        "saying what this transition means to the "
                        "protocol",
                        key,
                    )
                )
        for key in sorted(set(man_entries) - set(entries)):
            out.append(
                Finding(
                    "JL1003", rel, 1,
                    f"stale manifest entry `{section}` / `{key}`: no "
                    "such branch/function any more — --write-manifest "
                    "regenerates",
                    key,
                )
            )
    for section in sorted(set(man_sections) - set(atlas["sections"])):
        # a WHOLE section whose machinery left the source (extract()
        # skips absent functions) — entry-level drift can't see it
        out.append(
            Finding(
                "JL1003", rel, 1,
                f"stale manifest section `{section}`: none of its "
                "functions exist in the source any more — "
                "--write-manifest regenerates",
                section,
            )
        )
    # coverage + silent-ignore (JL1002): every message class must hit an
    # isinstance branch or an effectful fall-through in BOTH roles, and
    # no branch may be a silent ignore
    for section in ("role:active", "role:passive"):
        entries = atlas["sections"].get(section)
        if entries is None:
            continue
        fallthrough = entries.get("<fallthrough>", {"effects": []})
        for key, entry in sorted(entries.items()):
            if _is_silent(entry["effects"]) and key != "<fallthrough>":
                out.append(
                    Finding(
                        "JL1002", src_rel, entry["line"],
                        f"`{section}` / `{key}` ignores the message with "
                        "NO observable effect — make it a declared drop "
                        "(Cluster._drop_msg: counted + traced) or handle "
                        "it",
                        key,
                    )
                )
        for msg in atlas["messages"]:
            if msg in entries:
                continue
            if _is_silent(fallthrough["effects"]):
                out.append(
                    Finding(
                        "JL1002", src_rel, fallthrough.get("line", 1),
                        f"`{section}` has no branch for `{msg}` and its "
                        "fall-through is silent — an undeclared "
                        "(role, state, msg) hole in the protocol",
                        msg,
                    )
                )
    return out
