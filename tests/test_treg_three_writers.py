"""Three real nodes in a full mesh take conflicting TREG SETs at once.

What a CRDT store is bought for: the same hot keys are written at all
three nodes inside one flush interval, so last-writer-wins is decided
BETWEEN nodes (delta flush -> codec -> cluster apply -> converge), not
between connections of one node. After convergence every node must answer
every key with the greatest ``(timestamp, value)`` ever acknowledged
anywhere — a plain reference written here, importing nothing of the
program — and so agree with the others.

Two phases, both seeded: timestamps that never tie across the writers,
then deliberately EQUAL timestamps carrying different values (the merge
rule's second half: equal timestamps fall to the greater value), some of
which share their first eight bytes and more, so that a device drain's
rank prefix cannot settle them either.
"""

import random
import threading
import time

import jylis_tpu  # noqa: F401
from jylis_tpu.client import Client

from procutil import connect_client, free_port, spawn_node, stop_node

HOT_KEYS = [b"hot:%02d" % i for i in range(24)]
WRITES_PER_NODE = 400
TS_BASE = 1 << 50
LIMIT_S = 90.0  # the whole test's own time limit for convergence


def _value(rng: random.Random) -> bytes:
    """1 KB records mostly (the benchmark's shape), a few short ones, and
    a family sharing a 40-byte prefix (rank-prefix ties in a drain)."""
    pick = rng.random()
    if pick < 0.2:
        return b"shared-prefix-" * 3 + bytes(rng.choices(b"ab", k=rng.randint(1, 6)))
    size = 1000 if pick < 0.8 else rng.randint(1, 32)
    return bytes(rng.choices(b"abcdefghijklmnopqrstuvwxyz0123456789", k=size))


def _plan(seed: int) -> list[list[tuple[bytes, bytes, int]]]:
    """Per node, its SETs in order as (key, value, timestamp)."""
    rng = random.Random(seed)
    plans = [[] for _ in range(3)]
    # distinct timestamps: writer w owns the residue w mod 3, and walks
    # time forwards and backwards so stale writes arrive from other nodes
    for w in range(3):
        for i in range(WRITES_PER_NODE):
            tick = rng.randrange(WRITES_PER_NODE)
            plans[w].append((rng.choice(HOT_KEYS), _value(rng), TS_BASE + 3 * tick + w))
    # equal timestamps, different values: all three write every key at one
    # timestamp above everything before it
    for k, key in enumerate(HOT_KEYS):
        ts = TS_BASE + 10 * WRITES_PER_NODE + k
        for w in range(3):
            plans[w].append((key, _value(rng), ts))
    for plan in plans:
        tail = plan[WRITES_PER_NODE:]
        rng.shuffle(tail)
        plan[WRITES_PER_NODE:] = tail
    return plans


def _reference(plans) -> dict[bytes, tuple[int, bytes]]:
    """The greatest (timestamp, value) per key over every write."""
    best: dict[bytes, tuple[int, bytes]] = {}
    for plan in plans:
        for key, value, ts in plan:
            if key not in best or (ts, value) > best[key]:
                best[key] = (ts, value)
    return best


def _write_all(port: int, plan, acked: list, errors: list) -> None:
    try:
        with Client("127.0.0.1", port, timeout=60) as c:
            for i in range(0, len(plan), 16):  # small pipelines: the writers interleave
                chunk = plan[i : i + 16]
                replies = c.pipeline_execute(
                    [("TREG", "SET", key, value, str(ts)) for key, value, ts in chunk])
                acked.extend(r == b"OK" for r in replies)
    except Exception as e:  # noqa: BLE001 — reported by the asserting thread
        errors.append(e)


def _read_all(port: int) -> dict[bytes, tuple[int, bytes] | None]:
    with Client("127.0.0.1", port, timeout=60) as c:
        replies = c.pipeline_execute([("TREG", "GET", key) for key in HOT_KEYS])
    return {key: None if r is None else (r[1], r[0]) for key, r in zip(HOT_KEYS, replies)}


def test_three_writers_converge_to_the_plain_reference():
    ports = [free_port() for _ in range(3)]
    cports = [free_port() for _ in range(3)]
    fast = ("--heartbeat-time", "0.2")
    seed_addr = f"127.0.0.1:{cports[0]}:one"
    procs = [spawn_node(ports[0], cports[0], "one", *fast)]
    procs += [spawn_node(ports[i], cports[i], name, *fast, "--seed-addrs", seed_addr)
              for i, name in ((1, "two"), (2, "three"))]
    try:
        for port, proc in zip(ports, procs):
            connect_client(port, proc=proc).close()
        # the full mesh: every node holds an established connection to both others
        deadline = time.time() + LIMIT_S
        while True:
            established = []
            for port in ports:
                with Client("127.0.0.1", port, timeout=30) as c:
                    lines = c.execute_command("SYSTEM", "METRICS")
                text = b"\n".join(x if isinstance(x, bytes) else b"" for x in _flat(lines))
                established.append(b"peers_established 2" in text)
            if all(established):
                break
            assert time.time() < deadline, f"no full mesh: {established}"
            time.sleep(0.2)

        plans = _plan(seed=26)
        want = _reference(plans)
        acked, errors = [[], [], []], []
        threads = [threading.Thread(target=_write_all, args=(ports[w], plans[w], acked[w], errors))
                   for w in range(3)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(LIMIT_S)
        assert not errors, errors
        assert all(len(a) == len(p) and all(a) for a, p in zip(acked, plans)), \
            "every SET must be acknowledged"

        deadline = time.time() + LIMIT_S
        while True:
            got = [_read_all(port) for port in ports]
            if all(g == want for g in got):
                break
            if time.time() > deadline:
                for name, g in zip(("one", "two", "three"), got):
                    wrong = [k for k in HOT_KEYS if g[k] != want[k]]
                    assert not wrong, (
                        f"node {name}: {len(wrong)} of {len(HOT_KEYS)} keys differ from the "
                        f"reference, e.g. {wrong[0]!r}: got ts {g[wrong[0]] and g[wrong[0]][0]} "
                        f"want ts {want[wrong[0]][0]}")
            time.sleep(0.2)
        assert got[0] == got[1] == got[2]
        # the equal-timestamp phase decided every key: its timestamps are the
        # greatest, and each fell to the greatest of the three values
        for k, key in enumerate(HOT_KEYS):
            ts, value = want[key]
            assert ts == TS_BASE + 10 * WRITES_PER_NODE + k
            assert value == max(v for plan in plans for kk, v, t in plan if kk == key and t == ts)
    finally:
        for proc in procs:
            stop_node(proc)


def _flat(reply):
    if isinstance(reply, list):
        for r in reply:
            yield from _flat(r)
    else:
        yield reply
