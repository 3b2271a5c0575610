"""Delta write-ahead journal tests (journal/journal.py).

The journal is the snapshot's streaming sibling: framed wire-delta
batches behind the same schema-signature guard, recovered by lattice
convergence. Covered here: append/replay round trips per data type, the
flush-path wiring (Database.set_journal -> manager._emit), the fsync /
size-trigger bookkeeping, rotation (including a failed-compaction fold),
and the corruption classes — torn trailing frame (recovered, tail
truncated), mid-file bit flip (refused, moved aside ``.unreadable``),
schema-signature mismatch (refused, moved aside), empty/missing file —
all driven through ``journal.recover``, the exact function main.py's
boot path calls.
"""

import os

import numpy as np  # noqa: F401

import jylis_tpu  # noqa: F401
import pytest

from jylis_tpu import journal as journal_mod
from jylis_tpu.journal import Journal, JournalError
from jylis_tpu.models.database import Database
from jylis_tpu.server.resp import Respond
from jylis_tpu.utils import metrics

from test_persist import READS, Cap, call, journal_write, populate


def flush_all(db, journal) -> None:
    """The serving flush path, direct-driven: register a discard sink and
    flush every repo (manager._emit journals before the sink sees it)."""
    db.set_journal(journal)
    db.flush_deltas(lambda deltas: None)


def make_journal(tmp_path, **kw):
    j = Journal(str(tmp_path / "journal.jylis"), fsync="off", **kw)
    j.open()
    return j


def test_roundtrip_all_types(tmp_path):
    db = Database(identity=1)
    populate(db)
    j = make_journal(tmp_path)
    flush_all(db, j)
    j.close()

    db2 = Database(identity=1)
    n = journal_mod.recover(db2, j.path)
    assert n > 0
    for req, want in READS.items():
        assert call(db2, *req) == want, req
    assert b"a log line" in call(db2, "SYSTEM", "GETLOG")


def test_own_counter_state_survives_replay(tmp_path):
    """Replay must restore the node's own counter column as OWN state
    (load_state, not bare converge) or post-recovery INCs vanish under
    the pending max — the same contract snapshots keep."""
    db = Database(identity=1)
    call(db, "GCOUNT", "INC", "g", "7")
    call(db, "PNCOUNT", "INC", "p", "5")
    j = make_journal(tmp_path)
    flush_all(db, j)
    j.close()

    db2 = Database(identity=1)
    journal_mod.recover(db2, j.path)
    call(db2, "GCOUNT", "INC", "g", "3")
    assert call(db2, "GCOUNT", "GET", "g") == b":10\r\n"
    call(db2, "PNCOUNT", "DEC", "p", "1")
    assert call(db2, "PNCOUNT", "GET", "p") == b":4\r\n"


def test_journal_joins_with_snapshot_overlap(tmp_path):
    """Snapshot + journal overlap converges, never double-counts: the
    recovery ordering (snapshot, then journal tail) is safe even when
    the journal holds batches the snapshot already covers."""
    from jylis_tpu import persist

    db = Database(identity=1)
    call(db, "GCOUNT", "INC", "g", "7")
    j = make_journal(tmp_path)
    flush_all(db, j)  # journaled...
    snap = str(tmp_path / "snap.jylis")
    persist.save_snapshot(db, snap)  # ...AND snapshotted
    call(db, "GCOUNT", "INC", "g", "2")  # journal-only tail
    db.flush_deltas(lambda deltas: None)
    j.close()

    db2 = Database(identity=1)
    persist.load_snapshot(db2, snap)
    journal_mod.recover(db2, j.path)
    assert call(db2, "GCOUNT", "GET", "g") == b":9\r\n"


def test_system_keepalive_not_journaled(tmp_path):
    j = make_journal(tmp_path)
    before = j.size()
    j.append("SYSTEM", [(b"_log", ([], 0))])  # deltas_size()==1 quirk
    j.append("GCOUNT", [])  # empty batch
    j.flush()
    assert j.size() == before
    j.append("SYSTEM", [(b"_log", ([(b"line", 3)], 0))])  # real content
    j.flush()
    assert j.size() > before
    j.close()


def test_torn_trailing_frame_truncated_and_recovered(tmp_path):
    """A crash mid-append leaves a partial trailing frame: recovery
    converges every complete batch, cuts the tail, and the journal is
    appendable again."""
    db = Database(identity=1)
    call(db, "GCOUNT", "INC", "g", "7")
    call(db, "TREG", "SET", "r", "hello", "9")
    j = make_journal(tmp_path)
    flush_all(db, j)
    j.close()
    whole = os.path.getsize(j.path)
    with open(j.path, "ab") as f:  # torn append: half a frame of a batch
        f.write(b"\x06" + (900).to_bytes(8, "big") + b"partial body")

    db2 = Database(identity=1)
    n = journal_mod.recover(db2, j.path)
    assert n > 0
    assert os.path.getsize(j.path) == whole  # tail cut, good frames kept
    assert not os.path.exists(j.path + ".unreadable")
    assert call(db2, "GCOUNT", "GET", "g") == b":7\r\n"
    assert call(db2, "TREG", "GET", "r") == b"*2\r\n$5\r\nhello\r\n:9\r\n"

    # the truncated file reopens for append and keeps working
    j2 = Journal(j.path, fsync="off")
    j2.open()
    j2.append("GCOUNT", [(b"g", {1: 8})])
    j2.close()
    db3 = Database(identity=1)
    assert journal_mod.recover(db3, j.path) == n + 1
    assert call(db3, "GCOUNT", "GET", "g") == b":8\r\n"


def test_mid_file_bitflip_refused_and_moved_aside(tmp_path):
    """A flipped byte inside a frame is corruption, not truncation: the
    CRC refuses the file, nothing converges, and the segment moves aside
    as .unreadable (like snapshots) so boot proceeds without it."""
    db = Database(identity=1)
    populate(db)
    j = make_journal(tmp_path)
    flush_all(db, j)
    j.close()
    blob = bytearray(open(j.path, "rb").read())
    flip_at = journal_mod.journal.HEADER_LEN + 9 + 6  # first frame's body
    blob[flip_at] ^= 0x40
    open(j.path, "wb").write(bytes(blob))

    db2 = Database(identity=1)
    with pytest.raises(JournalError, match="CRC"):
        journal_mod.replay_journal(db2, j.path)
    # nothing converged by the refused replay
    assert call(db2, "GCOUNT", "GET", "g") == b":0\r\n"
    # the boot path moves it aside and carries on
    assert journal_mod.recover(db2, j.path) == 0
    assert not os.path.exists(j.path)
    assert os.path.exists(j.path + ".unreadable")


def test_schema_signature_mismatch_moved_aside(tmp_path):
    path = str(tmp_path / "journal.jylis")
    open(path, "wb").write(journal_mod.MAGIC + b"\x00" * 32)
    db = Database(identity=1)
    with pytest.raises(JournalError, match="signature"):
        journal_mod.replay_journal(db, path)
    assert journal_mod.recover(db, path) == 0
    assert os.path.exists(path + ".unreadable")
    # and a non-journal file is refused outright
    bad = str(tmp_path / "bad")
    open(bad, "wb").write(b"definitely not a journal")
    with pytest.raises(JournalError, match="not a journal"):
        journal_mod.replay_journal(db, bad)


def test_legacy_delta_signature_replays_and_restamps(tmp_path):
    """A pre-v7 journal (the v1-v6 delta signature — delta/TENSOR did
    not exist yet) must replay, and the segment must be REWRITTEN under
    the current signature before this build appends new-schema frames
    to it: the header must always describe every frame in the file."""
    import struct as _struct
    import zlib as _zlib

    from jylis_tpu.cluster import codec
    from jylis_tpu.cluster.framing import frame
    from jylis_tpu.cluster.msg import MsgPushDeltas

    path = str(tmp_path / "journal.jylis")
    # old-type frames encode byte-identically across the signature bump,
    # so the current encoder produces a faithful legacy file
    payload = codec.encode(MsgPushDeltas("GCOUNT", ((b"leg", {1: 5}),)))
    with open(path, "wb") as f:
        f.write(journal_mod.MAGIC + codec.legacy_delta_signatures()[0])
        f.write(frame(_struct.pack(">I", _zlib.crc32(payload)) + payload))
    db = Database(identity=1)
    assert journal_mod.replay_journal(db, path) == 1
    assert call(db, "GCOUNT", "GET", "leg") == b":5\r\n"
    # the segment now stamps the CURRENT delta signature...
    hdr = open(path, "rb").read(journal_mod.HEADER_LEN)
    assert hdr[len(journal_mod.MAGIC):] == codec.delta_signature()
    # ...and appending current-schema frames keeps it fully replayable
    j = Journal(path, fsync="always")
    j.open()
    db2 = Database(identity=1)
    call(db2, "TENSOR", "SET", "t", "MAX", "0",
         b"\x00\x00\x80?\x00\x00\x00\xc0")
    db2.set_journal(j)
    db2.flush_deltas(lambda b: None)
    j.flush()
    j.close()
    db3 = Database(identity=2)
    assert journal_mod.replay_journal(db3, path) == 2
    assert call(db3, "GCOUNT", "GET", "leg") == b":5\r\n"
    assert call(db3, "TENSOR", "GET", "t") == (
        b"*3\r\n$3\r\nMAX\r\n$8\r\n\x00\x00\x80?\x00\x00\x00\xc0\r\n:0\r\n"
    )


def test_empty_and_missing_journal(tmp_path):
    db = Database(identity=1)
    path = str(tmp_path / "journal.jylis")
    assert journal_mod.recover(db, path) == 0  # missing: clean boot
    open(path, "wb").close()
    assert journal_mod.recover(db, path) == 0  # empty: torn creation
    # a bare header (no batches) is a valid, empty journal
    j = Journal(path, fsync="off")
    j.open()
    j.close()
    assert journal_mod.recover(db, path) == 0
    assert not os.path.exists(path + ".unreadable")


def test_rotation_retires_and_failed_compaction_folds(tmp_path):
    """rotate_begin parks the active segment as .retiring; a rotation
    whose snapshot never landed folds the next segment INTO the retiring
    one instead of dropping either; recovery replays retiring + active;
    rotate_commit deletes the retired segment."""
    j = make_journal(tmp_path)
    j.append("GCOUNT", [(b"a", {1: 1})])
    j.rotate_begin()  # batch 1 parked in .retiring
    assert os.path.exists(j.retiring_path())
    j.append("GCOUNT", [(b"b", {1: 2})])
    j.rotate_begin()  # snapshot "failed": batch 2 folds into .retiring
    j.append("GCOUNT", [(b"c", {1: 3})])
    j.close()

    db = Database(identity=1)
    assert journal_mod.recover(db, j.path) == 3
    for key, want in ((b"a", b":1\r\n"), (b"b", b":2\r\n"), (b"c", b":3\r\n")):
        assert call(db, "GCOUNT", "GET", key) == want

    j2 = Journal(j.path, fsync="off")
    j2.open()
    j2.rotate_commit()
    assert not os.path.exists(j.retiring_path())
    j2.close()


def test_size_trigger_notifies_once_per_segment(tmp_path):
    calls = []
    j = Journal(str(tmp_path / "j.jylis"), fsync="off", max_bytes=1)
    j.rotate_notify = lambda: calls.append(1)
    j.open()
    j.append("GCOUNT", [(b"a", {1: 1})])
    j.append("GCOUNT", [(b"b", {1: 2})])
    j.flush()
    assert len(calls) == 1  # latched until the segment rotates
    j.rotate_begin()
    j.append("GCOUNT", [(b"c", {1: 3})])
    j.flush()
    assert len(calls) == 2
    j.rotate_commit()
    j.close()


def test_rotation_request_survives_late_hook_install(tmp_path):
    """An append that crosses the size threshold BEFORE the compaction
    loop installs rotate_notify must not latch the request away: the
    next append after the hook exists still asks, and needs_rotation()
    lets the loop catch a segment already oversized at install time."""
    j = Journal(str(tmp_path / "j.jylis"), fsync="off", max_bytes=1)
    j.open()
    j.append("GCOUNT", [(b"a", {1: 1})])  # no hook installed yet
    j.flush()
    assert j.needs_rotation()
    calls = []
    j.rotate_notify = lambda: calls.append(1)
    j.append("GCOUNT", [(b"b", {1: 2})])
    j.flush()
    assert calls, "rotation request was latched away before the hook"
    j.close()


def test_metrics_counters_and_lines(tmp_path):
    before = dict(metrics.journal_counters)
    j = make_journal(tmp_path)
    j.append("GCOUNT", [(b"k", {1: 5})])
    j.close()
    assert metrics.journal_counters["appends"] == before["appends"] + 1
    assert metrics.journal_counters["bytes"] > before["bytes"]
    lines = metrics.metric_lines()
    assert any(line.startswith("JOURNAL appends ") for line in lines)
    db = Database(identity=1)
    assert journal_mod.recover(db, j.path) == 1
    # replay counters land in the replaying DATABASE's registry (the
    # per-instance MetricsRegistry), not the process default
    assert db.metrics.journal_counters["replayed_batches"] >= 1


def test_fsync_policies_count(tmp_path):
    t = [0.0]
    before = metrics.journal_counters["fsyncs"]
    j = Journal(
        str(tmp_path / "j.jylis"), fsync="always", clock=lambda: t[0]
    )
    j.open()
    j.append("GCOUNT", [(b"a", {1: 1})])
    j.append("GCOUNT", [(b"b", {1: 2})])
    j.close()
    always = metrics.journal_counters["fsyncs"] - before
    assert always >= 2  # one per append (+ segment-header sync bookkeeping)

    before = metrics.journal_counters["fsyncs"]
    j = Journal(
        str(tmp_path / "j2.jylis"),
        fsync="interval",
        fsync_interval=10.0,
        clock=lambda: t[0],
    )
    j.open()
    j.append("GCOUNT", [(b"a", {1: 1})])  # within the interval: no sync
    t[0] += 11.0
    j.append("GCOUNT", [(b"b", {1: 2})])  # interval elapsed: syncs
    j.close()
    assert metrics.journal_counters["fsyncs"] - before == 1


def test_interval_fsync_covers_idle_tail(tmp_path):
    """The --journal-fsync-interval bound must hold WITHOUT further
    traffic: after one unsynced append, the writer thread itself fsyncs
    once the interval comes due (a lazy next-append-only sync would
    leave an idle tail at power-loss risk indefinitely)."""
    import time

    before = metrics.journal_counters["fsyncs"]
    j = Journal(
        str(tmp_path / "j.jylis"), fsync="interval", fsync_interval=0.05
    )
    j.open()
    j.append("GCOUNT", [(b"a", {1: 1})])
    j.flush()  # written; first append is within the interval of open()
    deadline = time.time() + 10
    while (
        metrics.journal_counters["fsyncs"] == before
        and time.time() < deadline
    ):
        time.sleep(0.02)
    assert metrics.journal_counters["fsyncs"] > before, (
        "idle tail never fsynced"
    )
    j.close()


def test_node_boot_recovers_from_journal_alone(tmp_path):
    """End to end through the REAL process boot path: a node with the
    journal on but online snapshots OFF is SIGKILLed; the restart
    recovers every flushed write from DIR/journal.jylis with no snapshot
    and no peers."""
    import signal
    import time

    from procutil import connect_client, free_port, spawn_node, stop_node

    data = str(tmp_path / "data")
    port, cport = free_port(), free_port()
    extra = (
        "--data-dir", data, "--heartbeat-time", "0.2",
        "--journal-fsync-interval", "0.05",
    )
    proc = spawn_node(port, cport, "jrnlnode", *extra)
    try:
        c = connect_client(port, proc=proc)
        assert c.execute_command("GCOUNT", "INC", "crash", 41) == b"OK"
        assert c.execute_command("TLOG", "INS", "log", "survivor", 7) == b"OK"
        # quiesce on the journal's own counters: appends count AFTER the
        # writer thread lands a batch on disk, so >= 2 means BOTH type
        # batches are durable (polling file size alone races the
        # writer's queue lag on the second batch)
        deadline = time.time() + 60
        appends = 0
        while time.time() < deadline:
            appends = sum(
                int(line.rsplit(b" ", 1)[1])
                for line in c.execute_command("SYSTEM", "METRICS")
                if line.startswith(b"JOURNAL appends")
            )
            if appends >= 2:
                break
            time.sleep(0.1)
        assert appends >= 2, "both flushed batches never reached the journal"
        jpath = os.path.join(data, "journal.jylis")
        assert os.path.getsize(jpath) > journal_mod.journal.HEADER_LEN
    finally:
        proc.send_signal(signal.SIGKILL)  # no clean shutdown, no snapshot
        proc.wait(timeout=30)
    assert not os.path.exists(os.path.join(data, "snapshot.jylis"))

    proc = spawn_node(port, cport, "jrnlnode", *extra)
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
        assert c.execute_command("TLOG", "GET", "log") == [[b"survivor", 7]]
        metrics_reply = c.execute_command("SYSTEM", "METRICS")
        assert any(
            line.startswith(b"JOURNAL replayed_batches")
            for line in metrics_reply
        )
    finally:
        stop_node(proc)


def test_rotation_never_blocks_appends(tmp_path, monkeypatch):
    """Pins the jlint JL104 fix: rotate_begin must do its fsync/fold/
    rename disk I/O OUTSIDE the condition variable. With the old
    cv-held-across-I/O rotation, the serving loop's append() blocked
    behind the disk for the whole rotation (up to a 64 MB segment fold);
    now appends enqueue at memory speed while the writer sleeps under
    the _paused hand-off, and every batch appended mid-rotation lands in
    the FRESH segment."""
    import threading
    import time as time_mod

    j = Journal(str(tmp_path / "j.jylis"), fsync="always")
    j.open()
    j.append("GCOUNT", [(b"before", {1: 1})])
    j.flush()

    real_fsync = os.fsync
    slow = threading.Event()

    def slow_fsync(fd):
        slow.set()
        time_mod.sleep(0.5)  # a slow disk under rotation
        real_fsync(fd)

    monkeypatch.setattr(os, "fsync", slow_fsync)
    rot = threading.Thread(target=j.rotate_begin)
    rot.start()
    assert slow.wait(10), "rotation never reached its fsync"
    t0 = time_mod.monotonic()
    j.append("GCOUNT", [(b"during", {1: 2})])
    append_s = time_mod.monotonic() - t0
    rot.join(timeout=30)
    assert not rot.is_alive()
    monkeypatch.setattr(os, "fsync", real_fsync)
    assert append_s < 0.2, (
        f"append blocked {append_s:.3f}s behind rotation disk I/O"
    )
    j.flush()
    j.close()

    # the mid-rotation batch landed in the FRESH segment (the retired
    # one holds only the pre-rotation batch)
    msgs, _, _ = journal_mod.journal.read_journal(j.path)
    assert [m.batch[0][0] for m in msgs] == [b"during"]
    msgs, _, _ = journal_mod.journal.read_journal(j.retiring_path())
    assert [m.batch[0][0] for m in msgs] == [b"before"]


def test_failed_rotation_resumes_writer_and_retries(tmp_path, monkeypatch):
    """A rotation that dies on disk I/O must clear the writer pause and
    the rotation latch, record the error, and RE-ASK for rotation when
    the writer next drops an undurable batch — in size-triggered-only
    mode (--snapshot-interval 0) that re-ask is the only thing that can
    ever re-open the segment."""
    asks = []
    j = make_journal(tmp_path)
    j.rotate_notify = lambda: asks.append(1)
    j.append("GCOUNT", [(b"a", {1: 1})])
    j.flush()

    real_replace = os.replace

    def boom(src, dst):
        raise OSError("disk gone")

    monkeypatch.setattr(os, "replace", boom)
    j.rotate_begin()  # swallows the OSError, resumes unpaused
    assert isinstance(j.last_error, OSError)
    # _f is None: the next batch drains undurable — counted, and the
    # writer re-asks for the rotation that would re-open the segment
    j.append("GCOUNT", [(b"dropped", {1: 2})])
    j.flush()
    assert asks, "writer never re-asked for rotation after the failure"
    # the disk "comes back": the retried rotation re-opens the segment
    # and journaling resumes
    monkeypatch.setattr(os, "replace", real_replace)
    j.rotate_begin()
    j.append("GCOUNT", [(b"recovered", {1: 3})])
    j.flush()
    j.close()
    msgs, _, _ = journal_mod.journal.read_journal(j.path)
    assert [m.batch[0][0] for m in msgs] == [b"recovered"]


def test_rotation_failed_after_rename_still_recovers(tmp_path, monkeypatch):
    """A rotation that renamed the active segment aside but died before
    opening the fresh one must not wedge every retry on the missing
    file: the retry re-opens a fresh segment and journaling resumes."""
    j = make_journal(tmp_path)
    j.append("GCOUNT", [(b"a", {1: 1})])
    j.flush()

    real_open_fresh = Journal._open_fresh_file

    def boom(self):
        raise OSError("EMFILE")

    monkeypatch.setattr(Journal, "_open_fresh_file", boom)
    j.rotate_begin()  # rename happened, fresh open failed
    assert isinstance(j.last_error, OSError)
    assert os.path.exists(j.retiring_path())
    assert not os.path.exists(j.path)

    monkeypatch.setattr(Journal, "_open_fresh_file", real_open_fresh)
    j.rotate_begin()  # retry: no active segment to retire, just re-open
    j.append("GCOUNT", [(b"recovered", {1: 2})])
    j.flush()
    j.close()
    msgs, _, _ = journal_mod.journal.read_journal(j.path)
    assert [m.batch[0][0] for m in msgs] == [b"recovered"]
    # the pre-failure batch is still in the retired segment
    msgs, _, _ = journal_mod.journal.read_journal(j.retiring_path())
    assert [m.batch[0][0] for m in msgs] == [b"a"]


def test_concurrent_rotations_serialise(tmp_path, monkeypatch):
    """Shutdown's final rotation can overlap the compaction loop's
    in-flight one (cancelling the loop task cannot stop its to_thread
    worker): the _paused hand-off must serialise them — both complete,
    the active segment stays valid, and nothing leaks a detached file."""
    import threading
    import time as time_mod

    j = Journal(str(tmp_path / "j.jylis"), fsync="always")
    j.open()
    j.append("GCOUNT", [(b"a", {1: 1})])
    j.flush()

    real_fsync = os.fsync

    def slow_fsync(fd):
        time_mod.sleep(0.2)
        real_fsync(fd)

    monkeypatch.setattr(os, "fsync", slow_fsync)
    threads = [threading.Thread(target=j.rotate_begin) for _ in range(2)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=30)
    assert not any(t.is_alive() for t in threads)
    monkeypatch.setattr(os, "fsync", real_fsync)
    assert j.last_error is None, j.last_error
    assert j._f is not None, "a rotation left the journal with no segment"
    j.append("GCOUNT", [(b"b", {1: 2})])
    j.flush()
    j.close()
    msgs, _, _ = journal_mod.journal.read_journal(j.path)
    assert [m.batch[0][0] for m in msgs] == [b"b"]


def test_shutdown_closes_journal_off_the_loop(tmp_path):
    """Pins the jlint JL101 fix in main.Dispose._shutdown: journal.close
    joins the writer thread and fsyncs, so it must run via
    asyncio.to_thread, never on the event loop itself."""
    import asyncio
    import threading

    from jylis_tpu.main import Dispose

    closed_on: list = []

    class _Journal:
        def close(self):
            closed_on.append(threading.current_thread())

    class _Server:
        async def dispose(self):
            pass

    class _Cluster:
        def dispose(self):
            pass

    class _Db:
        async def clean_shutdown_async(self):
            pass

    async def drive():
        d = Dispose(_Db(), _Server(), _Cluster(), snapshot_path="",
                    journal=_Journal())
        await d._shutdown()
        return threading.current_thread()

    loop_thread = asyncio.run(drive())
    assert closed_on and closed_on[0] is not loop_thread, (
        "journal.close ran on the event-loop thread"
    )


def test_shutdown_survives_journal_close_failure(tmp_path):
    """A journal whose final flush/fsync raises (full disk at shutdown)
    must not abort _shutdown's finally block: the listeners still stop
    and `done` is still set, or the node would hang until SIGKILL."""
    import asyncio

    from jylis_tpu.main import Dispose

    disposed = []

    class _Journal:
        def close(self):
            raise OSError("disk full")

    class _Server:
        async def dispose(self):
            disposed.append("server")

    class _Cluster:
        def dispose(self):
            disposed.append("cluster")

    class _Db:
        async def clean_shutdown_async(self):
            pass

    async def drive():
        d = Dispose(_Db(), _Server(), _Cluster(), snapshot_path="",
                    journal=_Journal())
        await d._shutdown()
        return d.done.is_set()

    assert asyncio.run(drive()) is True
    assert disposed == ["cluster", "server"]


# ---- lane-named segments: read at boot, never written ----------------------
#
# A multi-lane node (a mode retired in PR 45) wrote journal.lane<k>.jylis
# beside or instead of journal.jylis. The boot still converges every one
# of them and mutates only its own segment.


def test_list_segments_names_own_and_lane_files_only(tmp_path):
    assert journal_mod.SEGMENT_NAME == "journal.jylis"
    for name in (
        "journal.jylis", "journal.lane0.jylis", "journal.lane12.jylis",
        "journal.jylis.retiring", "journal.lane1.jylis.unreadable",
        "snapshot.lane0.jylis", "journal.lane.txt", "lanes.json",
    ):
        (tmp_path / name).write_bytes(b"")
    assert journal_mod.list_segments(str(tmp_path)) == [
        str(tmp_path / n)
        for n in ("journal.jylis", "journal.lane0.jylis", "journal.lane12.jylis")
    ]


def test_recover_all_merges_every_lane_segment(tmp_path):
    d = str(tmp_path)
    journal_write(
        os.path.join(d, "journal.lane0.jylis"), "GCOUNT", [(b"a", {1: 5})]
    )
    journal_write(
        os.path.join(d, "journal.lane1.jylis"), "GCOUNT", [(b"b", {2: 7})]
    )
    journal_write(
        os.path.join(d, "journal.jylis"), "GCOUNT", [(b"c", {3: 9})]
    )
    db = Database(identity=42)
    n = journal_mod.recover_all(db, d, os.path.join(d, "journal.jylis"))
    assert n == 3
    for key, want in ((b"a", b":5\r\n"), (b"b", b":7\r\n"), (b"c", b":9\r\n")):
        assert call(db, "GCOUNT", "GET", key) == want, key


def test_recover_all_never_mutates_foreign_torn_tail(tmp_path):
    d = str(tmp_path)
    own = os.path.join(d, "journal.jylis")
    foreign = os.path.join(d, "journal.lane1.jylis")
    journal_write(own, "GCOUNT", [(b"a", {1: 5})])
    journal_write(foreign, "GCOUNT", [(b"b", {2: 7})], torn=True)
    size_before = os.path.getsize(foreign)
    db = Database(identity=42)
    n = journal_mod.recover_all(db, d, own)
    assert n == 2  # both complete batches converged
    # the foreign file was not truncated and not moved aside
    assert os.path.getsize(foreign) == size_before
    assert not os.path.exists(foreign + ".unreadable")


def test_recover_all_skips_corrupt_foreign_segment(tmp_path):
    d = str(tmp_path)
    own = os.path.join(d, "journal.jylis")
    foreign = os.path.join(d, "journal.lane1.jylis")
    journal_write(own, "GCOUNT", [(b"a", {1: 5})])
    with open(foreign, "wb") as f:
        f.write(b"not a journal at all")
    db = Database(identity=42)
    n = journal_mod.recover_all(db, d, own)
    assert n == 1
    # never mutate a file this node does not write, even an unreadable one
    assert os.path.exists(foreign)
    assert not os.path.exists(foreign + ".unreadable")
