"""How `correct` is decided: what the served path answers, read back after
the window from the node and from every live peer, against the plain
reference. Exact comparison: the limit on mismatched reads is 0."""

from __future__ import annotations

import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np

from . import resp
from .nodes import HOST

LIMIT = 0  # mismatched reads allowed (an exact comparison)


def feed_reference(ref, logs: list[dict]) -> tuple[np.ndarray, np.ndarray]:
    """Apply every ACKNOWLEDGED write of every log to the reference.
    Returns (keys written, keys with a write that was not acknowledged:
    those may hold either value and are left out of the comparison)."""
    written, doubtful = [], []
    for lg in logs:
        ops = lg["op"]
        for i, verb in enumerate(lg["verbs"]):
            if lg["classes"][i] != "write":
                continue
            mine = ops == i
            ok = mine & lg["acked"]
            if ok.any():
                ref.apply(verb, lg["key"][ok], lg["a"][ok], lg["b"][ok])
            written.append(lg["key"][mine])
            doubtful.append(lg["key"][mine & ~lg["acked"]])
    cat = lambda parts: np.unique(np.concatenate(parts)) if parts else np.zeros(0, np.int64)
    return cat(written), cat(doubtful)


def choose_keys(ref, seed: int, n_keys: int, sample: int, written: np.ndarray,
                doubtful: np.ndarray, hot: np.ndarray) -> np.ndarray:
    """A seeded sample (half of it from the hottest keys) plus every key
    written, less the doubtful ones."""
    rng = np.random.default_rng([seed, 0x434B])
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
            settle_s: float, say) -> dict[str, dict]:
    """Read ``keys`` at every target; keys that differ are read again every
    half second until they agree or ``settle_s`` has passed (deltas in
    flight when the window closed are still converging)."""

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
        say(f"correct[{name}]: mismatched reads {r['mismatched']} of {r['compared']} "
            f"(limit {r['limit']}; {r['mismatched_at_first_read']} at the first read, "
            f"settled in {r['settled_s']:.1f}s)" + (f" e.g. {r['example']}" if r["example"] else ""))
    return out
