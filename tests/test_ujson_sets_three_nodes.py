"""Three real nodes keep seeded add/remove sets resident and take joins
and leaves at all three (the deployment of `ycsb-ujson-1kx1k-r3`, tiny).

Each node boots with ``--ujson-resident-min-leaves`` from the same
snapshot, made by the benchmark's plain reference through the program's
snapshot writer: recovery admits every document to the device-resident
store, the boot sizes the planes and compiles the folds. Clients then
INS / RM / GET at all three nodes at once (the native engine banks the
writes, its render memo answers the repeat reads), deltas cross both links
through the codec, and after convergence every node must answer every set
as the reference does — whole sets, exactly — with every document still
resident and not one demotion by a write. A node booted WITHOUT the flag
from the same bytes keeps the fan-in rule: nothing resident.
"""

import threading
import time

import numpy as np

import jylis_tpu  # noqa: F401
from jylis_tpu import persist
from jylis_tpu.client import Client
from jylis_tpu.models.database import DATA_TYPE_NAMES

import benchref
from procutil import connect_client, free_port, spawn_node, stop_node

SEED = 2**31 + 39
KEYS, MEMBERS = 12, 40
WRITES_PER_NODE = 150
LIMIT_S = 90.0


def _snapshot(ref, data_dir) -> None:
    data_dir.mkdir()
    batch = ref.snapshot_batch()
    persist.write_snapshot(
        [(n, batch if n == "UJSON" else []) for n in DATA_TYPE_NAMES + ("SYSTEM",)],
        str(data_dir / "snapshot.jylis"))


def _plan(seed: int, node: int):
    """(verb, key index, id) per write: joins carry ids no other operation
    makes (the node in the low bits), leaves name base ids."""
    rng = np.random.default_rng([seed, node])
    plan = []
    for i in range(WRITES_PER_NODE):
        key = int(rng.integers(0, 5))  # a hot set, written at every node
        if rng.random() < 0.5:
            plan.append(("INS", key, benchref.UJSON.FIRST_CLIENT_ID + 8 * i + node))
        else:
            plan.append(("RM", key, 10**18 + int(rng.integers(0, MEMBERS))))
    return plan


def _drive(port: int, ref, plan, acked: list, errors: list) -> None:
    try:
        with Client("127.0.0.1", port, timeout=60) as c:
            for i in range(0, len(plan), 8):  # small pipelines: the writers interleave
                chunk = plan[i : i + 8]
                cmds = []
                for verb, key, ident in chunk:
                    cmds.append(("UJSON", verb, ref.key(key), "members", str(ident)))
                    cmds.append(("UJSON", "GET", ref.key(key), "members"))  # read your write
                replies = c.pipeline_execute(cmds)
                for (verb, key, ident), ok, seen in zip(chunk, replies[::2], replies[1::2]):
                    acked.append(ok == b"OK")
                    if (str(ident).encode() in seen) != (verb == "INS"):
                        errors.append(f"{verb} {ident} not read back at once on :{port}")
    except Exception as e:  # noqa: BLE001 — reported by the asserting thread
        errors.append(e)


def _metrics(port: int) -> dict[str, int]:
    with Client("127.0.0.1", port, timeout=30) as c:
        lines = c.execute_command("SYSTEM", "METRICS")
    out = {}
    for line in lines:
        parts = (line.decode() if isinstance(line, bytes) else str(line)).split()
        if len(parts) == 3 and parts[0] == "UJSON" and parts[2].lstrip("-").isdigit():
            out[parts[1]] = int(parts[2])
    return out


def _read_all(port: int, ref) -> list:
    with Client("127.0.0.1", port, timeout=60) as c:
        return c.pipeline_execute([tuple(w.decode() for w in ref.read_command(k))
                                   for k in range(KEYS)])


def test_three_nodes_keep_sets_resident_and_converge_to_the_plain_reference(tmp_path):
    ref = benchref.ujson_reference(SEED, keys=KEYS, members=MEMBERS)
    names = ("one", "two", "three")
    for name in names:
        _snapshot(ref, tmp_path / name)
    ports = [free_port() for _ in range(3)]
    cports = [free_port() for _ in range(3)]
    flags = ("--heartbeat-time", "0.2", "--ujson-resident-min-leaves", "30")
    seed_addr = f"127.0.0.1:{cports[0]}:one"
    procs = [spawn_node(ports[0], cports[0], "one", *flags, "--data-dir", str(tmp_path / "one"))]
    procs += [spawn_node(ports[i], cports[i], names[i], *flags, "--data-dir",
                         str(tmp_path / names[i]), "--seed-addrs", seed_addr) for i in (1, 2)]
    try:
        for port, proc in zip(ports, procs):
            connect_client(port, proc=proc).close()
        for port in ports:  # recovery admitted every document, the base answers are there
            assert _read_all(port, ref) == ref.expected(range(KEYS))
            m = _metrics(port)
            assert m["resident_rows"] == KEYS == m["admits"], m
        plans = [_plan(SEED, w) for w in range(3)]
        acked, errors = [[], [], []], []
        threads = [threading.Thread(target=_drive, args=(ports[w], ref, plans[w], acked[w], errors))
                   for w in range(3)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(LIMIT_S)
        assert not errors, errors[:3]
        assert all(len(a) == WRITES_PER_NODE and all(a) for a in acked), \
            "every INS and RM must be acknowledged"
        for plan in plans:
            for verb in ("INS", "RM"):
                mine = [(k, i) for v, k, i in plan if v == verb]
                ref.apply(verb, np.array([k for k, _ in mine]),
                          np.array([i for _, i in mine], np.uint64), np.zeros(len(mine), np.uint64))
        want = ref.expected(range(KEYS))
        deadline = time.time() + LIMIT_S
        while True:
            got = [_read_all(port, ref) for port in ports]
            if all(g == want for g in got):
                break
            assert time.time() < deadline, [
                (name, [k for k in range(KEYS) if g[k] != want[k]]) for name, g in zip(names, got)]
            time.sleep(0.3)
        for port in ports:
            m = _metrics(port)
            assert m["resident_rows"] == KEYS and m["demote_write"] == 0, m
            assert m["local_writes"] == WRITES_PER_NODE and m["row_deltas"] > 0, m
            assert m["foreign_deltas"] > KEYS, m  # the restore's, then the peers'
    finally:
        for proc in procs:
            stop_node(proc)


def test_without_the_flag_the_same_snapshot_restores_to_the_host_lattice(tmp_path):
    ref = benchref.ujson_reference(SEED, keys=KEYS, members=MEMBERS)
    _snapshot(ref, tmp_path / "plain")
    port = free_port()
    proc = spawn_node(port, free_port(), "plain", "--data-dir", str(tmp_path / "plain"))
    try:
        connect_client(port, proc=proc).close()
        assert _read_all(port, ref) == ref.expected(range(KEYS))
        with Client("127.0.0.1", port, timeout=30) as c:
            assert c.execute_command("UJSON", "RM", ref.key(1), "members", str(10**18 + 3)) == b"OK"
            # the engine banked the write; the read applies the bank first
            assert str(10**18 + 3).encode() not in c.execute_command(
                "UJSON", "GET", ref.key(1), "members")
        m = _metrics(port)
        assert m.get("resident_rows", 0) == 0 and m.get("admits", 0) == 0, m
        assert m["local_writes"] == 1 and m.get("row_deltas", 0) == 0, m
    finally:
        stop_node(proc)
