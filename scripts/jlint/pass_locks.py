"""Pass 9 — cross-thread lock-order analysis (rules JL901/JL902/JL903).

The node is one asyncio loop + a journal writer thread + to_thread
drain workers, coordinating through a handful
of threading locks and condition variables. The three failure shapes
this pass mechanises are the ones reviews kept having to re-derive by
hand from multi-function context:

* **JL901 — await while holding a threading lock**: a sync ``with
  <lock>`` whose body awaits parks the COROUTINE but not the lock; any
  thread (and any other coroutine reaching the same lock) deadlocks or
  stalls behind a suspended owner. (An ``async with`` is the loop's own
  serialisation and is fine.)
* **JL902 — lock-acquisition cycle**: the global lock graph — an edge
  A→B whenever B is acquired while A is held, in one function or
  through any resolved call chain — must be acyclic, across the
  thread/loop seams. A cycle is a potential deadlock the drill
  matrix can only hit probabilistically; here it is structural.
  Lock identity is class-scoped (``Journal._cv``); acquiring the SAME
  attribute on several instances (the ordered ``Database.all_locks``
  pattern) is a self-edge and deliberately ignored — instance order is
  not statically visible.
* **JL903 — blocking I/O reachable under a held lock,
  interprocedurally**: pass 1's JL104 sees only the syntactically
  enclosing function (the journal-rotation stall it missed, PR 3's
  JL104 fix, was exactly a callee doing the fsync). This walks the
  blocking closure from every call made with a lock held: fsync /
  rename / open / sleep two frames down still serialises every other
  thread behind the disk.

All three consume the core's held-locks/call summaries
(scripts/jlint/core.py); resolution follows graph.py's no-false-edge
discipline, so every finding names a concrete witness chain.
"""

from __future__ import annotations

from . import Finding


def check_await_under_lock(project) -> list[Finding]:
    out: list[Finding] = []
    for fi in project.functions.values():
        if not fi.is_async:
            continue
        src = project.by_rel.get(fi.rel)
        for lineno, locks in fi.awaits:
            if locks:
                out.append(
                    Finding(
                        "JL901", fi.rel, lineno,
                        f"`await` while holding threading lock(s) "
                        f"{sorted(set(locks))} in `{fi.name}` — the "
                        "coroutine parks but the lock stays held; every "
                        "thread (and coroutine) behind it stalls until "
                        "this coroutine is resumed",
                        src.line_src(lineno) if src is not None else "",
                    )
                )
    return out


def check_lock_cycles(project) -> list[Finding]:
    edges = project.lock_edges()
    # adjacency over named locks; self-edges (same class attribute,
    # different instances) are excluded by lock_edges already
    adj: dict[str, set[str]] = {}
    for (a, b) in edges:
        adj.setdefault(a, set()).add(b)
    out: list[Finding] = []
    seen_cycles: set[tuple[str, ...]] = set()

    def dfs(start: str, node: str, path: list[str], visited: set[str]):
        for nxt in sorted(adj.get(node, ())):
            if nxt == start and len(path) > 1:
                cyc = tuple(sorted(path))
                if cyc in seen_cycles:
                    continue
                seen_cycles.add(cyc)
                rel, line, via = edges[(path[-1], start)]
                out.append(
                    Finding(
                        "JL902", rel, line,
                        "lock-acquisition cycle: "
                        + " -> ".join(path + [start])
                        + f" (edge witnessed {via}) — a potential "
                        "deadlock across the thread/loop seams; break "
                        "the cycle or collapse the locks",
                        "",
                    )
                )
            elif nxt not in visited and nxt in adj:
                dfs(start, nxt, path + [nxt], visited | {nxt})

    for start in sorted(adj):
        dfs(start, start, [start], {start})
    return out


def check_blocking_under_lock(project) -> list[Finding]:
    """Interprocedural JL104: a call site with a lock held whose SYNC
    callee closure reaches a blocking primitive."""
    closure = project.blocking_closure()
    out: list[Finding] = []
    for fi in project.functions.values():
        src = project.by_rel.get(fi.rel)
        for site in fi.calls:
            if not site.locks:
                continue
            for t in site.targets:
                chain = closure.get(t)
                if chain is None:
                    continue
                out.append(
                    Finding(
                        "JL903", fi.rel, site.lineno,
                        f"call `{site.raw}` under held lock(s) "
                        f"{sorted(set(site.locks))} reaches blocking "
                        f"`{chain[-1]}` via {' -> '.join(chain)} — every "
                        "other thread (the event loop included) blocks "
                        "behind the I/O; move it outside the lock or "
                        "declare the protocol",
                        src.line_src(site.lineno) if src is not None else "",
                    )
                )
                break  # one finding per call site
    return out


def run(project) -> list[Finding]:
    return (
        check_await_under_lock(project)
        + check_lock_cycles(project)
        + check_blocking_under_lock(project)
    )
