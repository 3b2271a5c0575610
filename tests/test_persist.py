"""Snapshot / restore tests.

The snapshot is a full-state delta dump in the cluster wire format
(persist.py), so restore is plain lattice convergence — exercised here per
data type, across identities, and for the join-with-live-state property
that makes stale snapshots safe.
"""

import numpy as np  # noqa: F401

import jylis_tpu  # noqa: F401
import pytest

from jylis_tpu import persist
from jylis_tpu.models.database import Database
from jylis_tpu.server.resp import Respond


class Cap:
    def __init__(self):
        self.buf = b""

    def __call__(self, b):
        self.buf += b


def call(db, *args):
    cap = Cap()
    db.apply(Respond(cap), [a if isinstance(a, bytes) else a.encode() for a in args])
    return cap.buf


def populate(db):
    call(db, "GCOUNT", "INC", "g", "7")
    call(db, "PNCOUNT", "INC", "p", "40")
    call(db, "PNCOUNT", "DEC", "p", "2")
    call(db, "TREG", "SET", "r", "hello", "9")
    call(db, "TLOG", "INS", "l", "a", "3")
    call(db, "TLOG", "INS", "l", "b", "5")
    call(db, "TLOG", "TRIMAT", "l", "4")
    call(db, "UJSON", "SET", "u", "name", '"alice"')
    call(db, "UJSON", "RM", "u", "name", '"alice"')
    call(db, "UJSON", "INS", "u", "tag", "1")
    call(db, "TENSOR", "SET", "t", "MAX", "0", b"\x00\x00\x80?\x00\x00\x00\xc0")
    # composed types (schema v9): MAP fields over three inner lattices
    # (one tombstoned — the tombstone must survive the round trip) and a
    # BCOUNT with spent escrow
    call(db, "MAP", "TREG", "SET", "m", "fr", "val", "11")
    call(db, "MAP", "GCOUNT", "SET", "m", "fg", "6")
    call(db, "MAP", "TLOG", "SET", "m", "fl", "entry", "2")
    call(db, "MAP", "TREG", "SET", "m", "dead", "x", "1")
    call(db, "MAP", "TREG", "DEL", "m", "dead")
    call(db, "BCOUNT", "GRANT", "b", "50")
    call(db, "BCOUNT", "INC", "b", "20")
    call(db, "BCOUNT", "DEC", "b", "5")
    db.system.inslog("a log line")


READS = {
    ("GCOUNT", "GET", "g"): b":7\r\n",
    ("PNCOUNT", "GET", "p"): b":38\r\n",
    ("TREG", "GET", "r"): b"*2\r\n$5\r\nhello\r\n:9\r\n",
    ("TLOG", "GET", "l"): b"*1\r\n*2\r\n$1\r\nb\r\n:5\r\n",
    ("UJSON", "GET", "u", "tag"): b"$1\r\n1\r\n",
    ("UJSON", "GET", "u", "name"): b"$0\r\n\r\n",  # removed stays removed
    # [1.0, -2.0] little-endian f32 (binary-safe bulk payload)
    ("TENSOR", "GET", "t"): (
        b"*3\r\n$3\r\nMAX\r\n$8\r\n\x00\x00\x80?\x00\x00\x00\xc0\r\n:0\r\n"
    ),
    ("MAP", "TREG", "GET", "m", "fr"): b"*2\r\n$3\r\nval\r\n:11\r\n",
    ("MAP", "GCOUNT", "GET", "m", "fg"): b":6\r\n",
    ("MAP", "TLOG", "GET", "m", "fl"): b"*1\r\n*2\r\n$5\r\nentry\r\n:2\r\n",
    ("MAP", "TREG", "GET", "m", "dead"): b"$-1\r\n",  # removed stays removed
    ("MAP", "TREG", "KEYS", "m"): b"*1\r\n$2\r\nfr\r\n",
    ("BCOUNT", "GET", "b"): b"*2\r\n:15\r\n:50\r\n",
}


def test_roundtrip_all_types(tmp_path):
    db = Database(identity=1)
    populate(db)
    path = str(tmp_path / "snap.jylis")
    persist.save_snapshot(db, path)

    db2 = Database(identity=1)
    n = persist.load_snapshot(db2, path)
    assert n == 9  # one batch per data type
    for req, want in READS.items():
        assert call(db2, *req) == want, req
    # the restored SYSTEM log still has the line
    assert b"a log line" in call(db2, "SYSTEM", "GETLOG")


def test_own_counter_state_survives(tmp_path):
    """Post-restore INCs must still advance the counter — the node's own
    column is private monotonic state."""
    db = Database(identity=1)
    call(db, "GCOUNT", "INC", "g", "7")
    call(db, "PNCOUNT", "INC", "p", "5")
    path = str(tmp_path / "snap.jylis")
    persist.save_snapshot(db, path)

    db2 = Database(identity=1)
    persist.load_snapshot(db2, path)
    call(db2, "GCOUNT", "INC", "g", "3")
    assert call(db2, "GCOUNT", "GET", "g") == b":10\r\n"
    call(db2, "PNCOUNT", "DEC", "p", "1")
    assert call(db2, "PNCOUNT", "GET", "p") == b":4\r\n"


def test_stale_snapshot_joins_with_live_state(tmp_path):
    """Loading an OLD snapshot into a node that moved on must be a no-op
    for anything newer (lattice join, not replay)."""
    db = Database(identity=1)
    call(db, "TREG", "SET", "r", "old", "5")
    path = str(tmp_path / "snap.jylis")
    persist.save_snapshot(db, path)
    call(db, "TREG", "SET", "r", "new", "8")
    persist.load_snapshot(db, path)
    assert call(db, "TREG", "GET", "r") == b"*2\r\n$3\r\nnew\r\n:8\r\n"


def test_restore_under_other_identity(tmp_path):
    """A snapshot from node A restored on node B keeps A's counter columns
    (it is replicated state, not B's own)."""
    db = Database(identity=1)
    call(db, "GCOUNT", "INC", "g", "7")
    path = str(tmp_path / "snap.jylis")
    persist.save_snapshot(db, path)
    db2 = Database(identity=2)
    persist.load_snapshot(db2, path)
    call(db2, "GCOUNT", "INC", "g", "1")
    assert call(db2, "GCOUNT", "GET", "g") == b":8\r\n"


def test_corrupt_and_mismatched_files(tmp_path):
    db = Database(identity=1)
    bad = tmp_path / "bad"
    bad.write_bytes(b"not a snapshot at all")
    with pytest.raises(persist.SnapshotError):
        persist.load_snapshot(db, str(bad))
    sig = tmp_path / "sig"
    sig.write_bytes(persist.MAGIC + b"\x00" * 32)
    with pytest.raises(persist.SnapshotError):
        persist.load_snapshot(db, str(sig))
    trunc = tmp_path / "trunc"
    populate(db)
    ok = tmp_path / "ok"
    persist.save_snapshot(db, str(ok))
    trunc.write_bytes(ok.read_bytes()[:-10])
    with pytest.raises(persist.SnapshotError):
        persist.load_snapshot(Database(identity=1), str(trunc))


def test_truncation_at_frame_boundary_detected(tmp_path):
    """A file cut exactly between frames parses cleanly but must still be
    rejected (it restores only a subset of the data types)."""
    from jylis_tpu.cluster.framing import HEADER_SIZE, parse_header

    db = Database(identity=1)
    populate(db)
    path = tmp_path / "snap.jylis"
    persist.save_snapshot(db, str(path))
    blob = path.read_bytes()
    sig_end = len(persist.MAGIC) + 32
    first_len = parse_header(blob[sig_end : sig_end + HEADER_SIZE])
    cut = tmp_path / "cut.jylis"
    cut.write_bytes(blob[: sig_end + HEADER_SIZE + first_len])
    with pytest.raises(persist.SnapshotError, match="type batches"):
        persist.load_snapshot(Database(identity=1), str(cut))


def test_write_snapshot_from_async_dump(tmp_path):
    """The online-snapshot path: per-type async dumps written atomically
    load back into a fresh database identically to save_snapshot."""
    import asyncio

    db = Database(identity=7)
    call(db, "GCOUNT", "INC", "g", "5")
    call(db, "TLOG", "INS", "l", "e", "9")
    call(db, "TREG", "SET", "r", "v", "3")
    call(db, "UJSON", "SET", "d", "k", '"x"')
    path = str(tmp_path / "online.jylis")
    batches = asyncio.run(db.dump_state_async())
    persist.write_snapshot(batches, path)
    fresh = Database(identity=8)
    assert persist.load_snapshot(fresh, path) == len(list(fresh.managers()))
    assert call(fresh, "GCOUNT", "GET", "g") == b":5\r\n"
    assert call(fresh, "TLOG", "GET", "l") == b"*1\r\n*2\r\n$1\r\ne\r\n:9\r\n"
    assert call(fresh, "TREG", "GET", "r") == b"*2\r\n$1\r\nv\r\n:3\r\n"
    assert call(fresh, "UJSON", "GET", "d", "k") == b'$3\r\n"x"\r\n'


def test_online_snapshot_survives_sigkill(tmp_path):
    """The point of --snapshot-interval: a node that is KILLED (no clean
    shutdown) restarts with every write that made it into the last
    online snapshot."""
    import os
    import signal
    import time

    from procutil import connect_client, free_port, spawn_node, stop_node

    data = str(tmp_path / "data")
    port, cport = free_port(), free_port()
    extra = ("--data-dir", data, "--snapshot-interval", "0.3")

    proc = spawn_node(port, cport, "snapnode", *extra)
    try:
        c = connect_client(port, proc=proc)
        assert c.execute_command("GCOUNT", "INC", "crash", 41) == b"OK"
        assert c.execute_command("TLOG", "INS", "log", "survivor", 7) == b"OK"
        # wait for an online snapshot to exist, then for one MORE cycle
        # (mtime advances) so the writes above are certainly included
        snap = os.path.join(data, "snapshot.jylis")
        deadline = time.time() + 60
        while not os.path.exists(snap) and time.time() < deadline:
            time.sleep(0.1)
        assert os.path.exists(snap), "online snapshot never appeared"
        first = os.path.getmtime(snap)
        while os.path.getmtime(snap) == first and time.time() < deadline:
            time.sleep(0.1)
    finally:
        proc.send_signal(signal.SIGKILL)  # no clean shutdown, no final dump
        proc.wait(timeout=30)

    proc = spawn_node(port, cport, "snapnode", *extra)
    try:
        c = connect_client(port, proc=proc)
        deadline = time.time() + 30
        got = None
        while time.time() < deadline:
            got = c.execute_command("GCOUNT", "GET", "crash")
            if got == 41:
                break
            time.sleep(0.2)
        assert got == 41, got
        assert c.execute_command("TLOG", "SIZE", "log") == 1
    finally:
        stop_node(proc)


def test_legacy_snapshot_truncated_at_frame_boundary_refused(tmp_path):
    """Review fix: a legacy header pins its ERA's exact type-batch
    count (or the current shape, for re-headered files) — a legacy
    file truncated at a frame boundary must refuse, not silently load
    a partial keyspace."""
    from jylis_tpu.cluster import codec
    from jylis_tpu.cluster.framing import FrameReader

    db = Database(identity=1)
    populate(db)
    path = tmp_path / "snap"
    persist.save_snapshot(db, str(path))
    blob = path.read_bytes()
    legacy = codec.legacy_snapshot_signatures()[0]
    sig_end = len(persist.MAGIC) + len(legacy)
    # split the body at frame boundaries, keep only 3 whole frames
    frames = FrameReader(max_frame=1 << 62)
    frames.append(blob[sig_end:])
    bodies = list(frames)
    from jylis_tpu.cluster.framing import frame as mk_frame

    partial = persist.MAGIC + legacy + b"".join(
        mk_frame(codec.encode(codec.decode(b))) for b in bodies[:3]
    )
    bad = tmp_path / "snap_partial"
    bad.write_bytes(partial)
    with pytest.raises(persist.SnapshotError):
        persist.load_snapshot(Database(identity=1), str(bad))


def test_legacy_v2_snapshot_header_loads(tmp_path):
    """Snapshots written by the v2-era release stamped the FULL schema
    signature; the delta encodings are unchanged, so this build must
    load them (ADVICE round 4: an upgrade must not strand a single-node
    deployment's only data copy)."""
    from jylis_tpu.cluster import codec

    db = Database(identity=1)
    populate(db)
    path = tmp_path / "snap"
    persist.save_snapshot(db, str(path))
    blob = path.read_bytes()
    for v, legacy in enumerate(codec.legacy_snapshot_signatures(), start=1):
        assert len(legacy) == len(codec.delta_signature())
        sig_end = len(persist.MAGIC) + len(legacy)
        old_style = persist.MAGIC + legacy + blob[sig_end:]
        old_path = tmp_path / f"snap_v{v}"
        old_path.write_bytes(old_style)
        db2 = Database(identity=1)
        assert persist.load_snapshot(db2, str(old_path)) > 0
        for args, want in READS.items():
            assert call(db2, *args) == want, (v, args)


# ---- lane-named files: restored at boot, never written ---------------------
#
# A multi-lane node (a mode retired in PR 45) wrote snapshot.lane<k>.jylis
# and journal.lane<k>.jylis. A data directory that holds them still boots
# whole, and the node writes only its own snapshot.jylis / journal.jylis.


def test_list_snapshots_names_own_and_lane_files_only(tmp_path):
    assert persist.SNAPSHOT_NAME == "snapshot.jylis"
    for name in (
        "snapshot.jylis", "snapshot.lane0.jylis", "snapshot.lane3.jylis",
        "snapshot.jylis.tmp", "snapshot.lane1.jylis.unreadable",
        "journal.lane0.jylis", "lanes.json",
    ):
        (tmp_path / name).write_bytes(b"")
    assert persist.list_snapshots(str(tmp_path)) == [
        str(tmp_path / n)
        for n in ("snapshot.jylis", "snapshot.lane0.jylis", "snapshot.lane3.jylis")
    ]


def _lane_snapshot(path, identity: int, key: str, n: int) -> None:
    db = Database(identity=identity)
    call(db, "GCOUNT", "INC", key, str(n))
    persist.save_snapshot(db, str(path))


def journal_write(path, name: str, batch, torn: bool = False) -> None:
    """One batch in a fresh segment at ``path``; ``torn`` leaves a torn
    trailing frame behind it."""
    from jylis_tpu.journal import Journal

    j = Journal(str(path), fsync="off")
    j.open()
    j.append(name, batch)
    j.flush()
    j.close()
    if torn:
        with open(path, "ab") as f:
            f.write(b"\x00\x01\x02")


@pytest.mark.parametrize(
    "snapshots,segments",
    [(True, False), (False, True), (True, True)],
    ids=["snapshots-only", "segments-only", "both"],
)
def test_node_boots_a_multilane_data_dir_whole(tmp_path, snapshots, segments):
    """`main.run` on a directory a multi-lane node left: every
    lane-named snapshot and segment (one with a torn tail) converges
    beside the node's own journal.jylis, the lane files stay
    byte-identical, and a clean shutdown writes only snapshot.jylis."""
    import asyncio
    import os
    import signal

    from jylis_tpu import main as main_mod
    from procutil import free_port
    from test_cluster import resp_call

    want = {b"own": 2}
    journal_write(tmp_path / "journal.jylis", "GCOUNT", [(b"own", {7: 2})])
    if snapshots:
        _lane_snapshot(tmp_path / "snapshot.lane0.jylis", 11, "s0", 3)
        _lane_snapshot(tmp_path / "snapshot.lane1.jylis", 12, "s1", 4)
        want.update({b"s0": 3, b"s1": 4})
    if segments:
        journal_write(
            tmp_path / "journal.lane1.jylis", "GCOUNT", [(b"j1", {13: 5})],
            torn=True,
        )
        want[b"j1"] = 5
    lane_files = {
        p.name: p.read_bytes() for p in tmp_path.iterdir() if ".lane" in p.name
    }
    assert len(lane_files) == 2 * snapshots + segments
    port, cport = free_port(), free_port()

    async def drive():
        node = asyncio.create_task(
            main_mod.run([
                "--port", str(port), "--addr", f"127.0.0.1:{cport}:lanedir",
                "--data-dir", str(tmp_path), "--log-level", "error",
            ])
        )
        got = {}
        try:
            deadline = asyncio.get_running_loop().time() + 240
            while True:
                assert not node.done(), node.exception()
                try:
                    for key in want:
                        got[key] = await resp_call(
                            port,
                            b"*3\r\n$6\r\nGCOUNT\r\n$3\r\nGET\r\n$%d\r\n%s\r\n"
                            % (len(key), key),
                        )
                    break
                except OSError:
                    assert asyncio.get_running_loop().time() < deadline
                    await asyncio.sleep(0.1)
        finally:
            # the node's own SIGTERM handler (Dispose.on_signal) is on
            # this loop by the time the port answers: a clean shutdown
            os.kill(os.getpid(), signal.SIGTERM)
            await asyncio.wait_for(node, 120)
        return got

    got = asyncio.run(drive())
    assert got == {k: b":%d\r\n" % n for k, n in want.items()}
    after = {p.name: p for p in tmp_path.iterdir()}
    for name, blob in lane_files.items():
        assert after[name].read_bytes() == blob, name
    written = {
        n for n in after if n not in lane_files and not n.startswith("epoch.")
    }
    assert written == {"snapshot.jylis", "journal.jylis"}
    # the shutdown snapshot holds the union: a second boot needs no lane file
    db = Database(identity=1)
    persist.load_snapshot(db, str(after["snapshot.jylis"]))
    for key, n in want.items():
        assert call(db, "GCOUNT", "GET", key) == b":%d\r\n" % n
