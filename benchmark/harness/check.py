"""How `correct` is decided: what the served path answers, read back after
the window from the node and from every live peer, against the plain
reference. Exact comparison: the limit on mismatched reads is 0."""

from __future__ import annotations

import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np

from . import gen, resp
from .nodes import HOST

LIMIT = 0  # mismatched reads allowed (an exact comparison)


def references(cell, recipes: dict, seed: int, own_rid: int, peer_rids: list[int]):
    """A reference behind every stated type, all from the one seed:
    (type name -> reference, type name -> its key indices, hottest first).
    ``recipes``: type name -> its state recipe."""
    values = gen.Values(seed)
    refs, hot = {}, {}
    for name, recipe in recipes.items():
        hot[name] = gen.hottest(recipe["keys"], recipe["keys"])
        refs[name] = cell.reference_module(name).Reference(
            recipe, seed, own_rid, peer_rids, hot[name], values)
    return refs, hot


def feed_reference(refs: dict, logs: list[dict]) -> dict[str, tuple[np.ndarray, np.ndarray]]:
    """Apply every ACKNOWLEDGED write of every log to the reference of ITS
    type (a log's ``types[i]`` is the first word of op ``i``'s template).
    A reference that defines ``apply_op(template_text, keys, a, b)`` is
    handed the op's whole template, so that it can tell two ops apart by
    more than the verb (a literal field, a literal path); the others get
    ``apply(verb, keys, a, b)``. Returns, per type of ``refs``, (keys
    written, keys with a write that was not acknowledged: those may hold
    either value and are left out of the comparison)."""
    written = {name: [] for name in refs}
    doubtful = {name: [] for name in refs}
    for lg in logs:
        ops = lg["op"]
        for i, verb in enumerate(lg["verbs"]):
            if lg["classes"][i] != "write":
                continue
            name = lg["types"][i]
            if name not in refs:
                raise KeyError(f"a write of {name} ({verb}), and the configuration states "
                               f"only {sorted(refs)}")
            ref = refs[name]
            mine = ops == i
            ok = mine & lg["acked"]
            if ok.any():
                if hasattr(ref, "apply_op"):
                    ref.apply_op(lg["texts"][i], lg["key"][ok], lg["a"][ok], lg["b"][ok])
                else:
                    ref.apply(verb, lg["key"][ok], lg["a"][ok], lg["b"][ok])
            written[name].append(lg["key"][mine])
            doubtful[name].append(lg["key"][mine & ~lg["acked"]])
    cat = lambda parts: np.unique(np.concatenate(parts)) if parts else np.zeros(0, np.int64)
    return {name: (cat(written[name]), cat(doubtful[name])) for name in refs}


def choose_keys(seed: int, block: dict, written: np.ndarray, doubtful: np.ndarray,
                hot: np.ndarray) -> np.ndarray:
    """The keys of one type (``block``: its ``{"type", "state", "check"}``)
    to read back: a seeded sample (half of it from the hottest keys) plus
    every key written, less the doubtful ones."""
    rng = np.random.default_rng([seed, 0x434B])
    n_keys, sample = block["state"]["keys"], block["check"]["sample"]
    hot = hot[: block["state"].get("foreign_keys", 4096)]
    half = sample // 2
    picks = [rng.integers(0, n_keys, sample - half),
             rng.choice(hot, min(half, len(hot)), replace=False), written]
    keys = np.unique(np.concatenate(picks).astype(np.int64))
    return np.setdiff1d(keys, doubtful)


def read_back(port: int, ref, keys: np.ndarray) -> list:
    cmds = [resp.pack(*ref.read_command(int(k))) for k in keys]
    with resp.Conn(HOST, port, timeout=300) as c:
        return c.pipeline(cmds)


def compare(targets: dict[str, int], ref, keys: np.ndarray, expected: list,
            settle_s: float, say, type_name: str) -> dict[str, dict]:
    """Read one type's ``keys`` at every target; keys that differ are read
    again every half second until they agree or ``settle_s`` has passed
    (deltas in flight when the window closed are still converging). A
    verdict line names the node AND the type."""

    def one(name: str, port: int) -> dict:
        t_start = time.monotonic()
        todo = np.arange(len(keys))
        first = None
        while True:
            got = read_back(port, ref, keys[todo])
            bad = [j for j, g in zip(todo, got) if g != expected[j]]
            if first is None:
                first = len(bad)
            if not bad or time.monotonic() - t_start > settle_s:
                break
            todo = np.asarray(bad)
            time.sleep(0.5)
        example = None
        if bad:
            j = bad[0]
            example = f"key {int(keys[j])}: got {str(got[list(todo).index(j)])[:80]} want {str(expected[j])[:80]}"
        return {"compared": len(keys), "mismatched": len(bad), "limit": LIMIT,
                "mismatched_at_first_read": first,
                "settled_s": time.monotonic() - t_start, "example": example}

    with ThreadPoolExecutor(max_workers=len(targets)) as pool:
        futures = {name: pool.submit(one, name, port) for name, port in targets.items()}
        out = {name: f.result() for name, f in futures.items()}
    for name, r in out.items():
        say(f"correct[{name} {type_name}]: mismatched reads {r['mismatched']} of {r['compared']} "
            f"(limit {r['limit']}; {r['mismatched_at_first_read']} at the first read, "
            f"settled in {r['settled_s']:.1f}s)" + (f" e.g. {r['example']}" if r["example"] else ""))
    return out
