"""Three in-process replicas write DIFFERENT fields of one record at once
through the native path (the engine's burst), and the same field with
competing timestamps; after their flushes cross, every replica holds every
write, one field's winner is the last writer's, the digests are equal, and
they equal those of three replicas on the Python tables fed the same
commands. This is what the one-value record cannot give: two writers of two
fields of one TREG blob lose one write to last-writer-wins."""

import pytest

import jylis_tpu  # noqa: F401
from jylis_tpu.models.database import Database
from jylis_tpu.server.resp import Respond

from procutil import scan_bytes

RIDS = (11, 22, 33)


def pack(*words: bytes) -> bytes:
    return b"*%d\r\n" % len(words) + b"".join(b"$%d\r\n%s\r\n" % (len(w), w) for w in words)


def serve(db, cmds: list[tuple]) -> bytes:
    """Through the engine's burst where there is one, else the oracle."""
    if db.native_engine is None:
        out = bytearray()
        for cmd in cmds:
            db.apply(Respond(out.extend), list(cmd))
        return bytes(out)
    buf = bytearray(b"".join(pack(*c) for c in cmds))
    rc, consumed, replies, unhandled, changed = scan_bytes(db.native_engine, buf)
    assert rc == 0 and consumed == len(buf) and unhandled is None
    assert changed[5] == sum(1 for c in cmds if c[2] == b"SET")
    return replies


def exchange(dbs, sinks) -> None:
    """Every replica's flushed MAP batches reach the other two (one
    sink a replica for the whole test: a write may flush on its own)."""
    flushed = []
    for db, sink in zip(dbs, sinks):
        db.flush_deltas(sink.append)
        flushed.append([batch for name, batch in sink if name == "MAP"])
        sink.clear()
    for i, db in enumerate(dbs):
        for j, batches in enumerate(flushed):
            if i != j:
                for batch in batches:
                    db.converge_deltas(("MAP", list(batch)))


def play(engine: str):
    dbs = [Database(identity=rid, engine=engine) for rid in RIDS]
    if engine == "auto" and dbs[0].native_engine is None:
        pytest.skip("no native engine on this host")
    sinks = [[] for _ in dbs]
    # round 1: each replica writes its own fields of user1, all three write field9
    for n, db in enumerate(dbs):
        cmds = [(b"MAP", b"TREG", b"SET", b"user1", b"field%d" % (3 * n + j),
                 b"from-%d-%d" % (n, j), b"%d" % (100 + n)) for j in range(3)]
        cmds.append((b"MAP", b"TREG", b"SET", b"user1", b"field9", b"nine-by-%d" % n,
                     b"%d" % (500 + n)))
        assert serve(db, cmds) == b"+OK\r\n" * 4
    exchange(dbs, sinks)
    # round 2: a DEL at one replica beside a SET of the same field at another (add-wins),
    # and equal timestamps on one field (the greater value wins everywhere)
    out = bytearray()
    dbs[0].apply(Respond(out.extend), [b"MAP", b"TREG", b"DEL", b"user1", b"field0"])
    serve(dbs[1], [(b"MAP", b"TREG", b"SET", b"user1", b"field0", b"revived", b"900")])
    serve(dbs[1], [(b"MAP", b"TREG", b"SET", b"user1", b"tie", b"tie-b", b"700")])
    serve(dbs[2], [(b"MAP", b"TREG", b"SET", b"user1", b"tie", b"tie-c", b"700")])
    exchange(dbs, sinks)
    return dbs


@pytest.mark.parametrize("engine", ["auto", "python"])
def test_concurrent_writes_of_different_fields_all_survive_at_all_three(engine):
    dbs = play(engine)
    reads = [serve(db, [(b"MAP", b"TREG", b"GETALL", b"user1")]) for db in dbs]
    assert reads[0] == reads[1] == reads[2]
    record = reads[0]
    assert record.startswith(b"*22\r\n")  # field0..field9 and tie, all live
    for n in range(3):
        for j in range(3):
            assert b"from-%d-%d" % (n, j) in record or (n, j) == (0, 0)
    assert b"nine-by-2\r\n:502" in record and b"nine-by-0" not in record
    assert b"revived\r\n:900" in record  # the concurrent SET outlives the DEL
    assert b"tie-c\r\n:700" in record and b"tie-b" not in record
    digests = [db._sync_digest_blocking() for db in dbs]
    assert digests[0] == digests[1] == digests[2]
    for db in dbs:
        db.drain_all()
        assert db._sync_digest_blocking() == digests[0]


def test_the_native_replicas_and_the_python_replicas_hold_the_same_state():
    native, oracle = play("auto"), play("python")
    for a, b in zip(native, oracle):
        assert a._sync_digest_blocking() == b._sync_digest_blocking()
        ra, rb = a.manager("MAP").repo, b.manager("MAP").repo
        assert sorted(ra.dump_state()) == sorted(rb.dump_state())
    # and every replica's device table holds the three replicas' columns
    repo = native[0].manager("MAP").repo
    eng = native[0].native_engine
    assert sorted(eng.map_rids()) == sorted(RIDS)
    import numpy as np

    row = eng.map_find(b"user1", b"field9")
    cells = np.asarray(repo.device_rows([row])[0])[0]
    r = len(cells) // 4
    assert sorted(int(c) for c in cells[2 * r:3 * r] if c) == [1, 1, 1]  # one edit each
