"""Deliberately jax-free multi-threaded drive of the native serving
engine — the `make sanitize-threads` vehicle.

The TSAN build (`make sanitize-threads`) runs this module with
libtsan LD_PRELOADed. Two invariants are certified:

* **Engine isolation** — distinct ``ServeEngine`` instances carry no
  hidden shared C++ state (statics, shared buffers, a shared
  interner). ctypes releases the GIL around every FFI call, so the
  per-thread bursts below genuinely run concurrently inside the
  library; any cross-engine write TSAN sees is a product bug, because
  a node runs one engine per asyncio loop.
* **External-mutex discipline** — a single engine shared across
  threads is race-free when every call is serialized by one lock
  (the product's implicit contract: the owning event loop is that
  lock). TSAN proves no engine call path touches state that escapes
  the critical section (e.g. an unsynchronized static scratch buffer
  would race even under the mutex between release/acquire pairs).

* **One lock a TYPE, not one lock an engine** — the server's rounds
  hold the repo locks of the types their commands name and no other
  (server.py `_apply_native`, `scan_apply`'s ``held``), so a round of
  TREG commands on the loop thread runs WHILE another type's drain
  works on its own table in a worker thread, with no lock in common.
  TSAN proves a command of type X touches no state of type Y inside the
  library (tables, memo, queue, interner, `served[]`).

* **The reply sender** (native/reply_sender.cpp) — the one piece of the
  library with a thread of its own: two producers hand replies to it
  (the hand-off keeps the GIL, as the server's does; open, close and
  the counters' read drop it) while it sends, meets sockets that do
  not take a job whole, and loses connections under its hands.

In the regular suite this doubles as a plain concurrency smoke (the
invariants hold under the GIL too — assertion failures here mean
cross-engine state leaked regardless of the data-race question).

Keep this module importable without jax: no jylis_tpu.models /
jylis_tpu.ops imports (JYLIS_SANITIZE gates the jax import in
tests/conftest.py).
"""

from __future__ import annotations

import ctypes
import select
import socket
import threading

import numpy as np
import pytest

from jylis_tpu.native import lib
from jylis_tpu.native.engine import ServeEngine

N_THREADS = 6
N_ROUNDS = 40


@pytest.fixture
def cdll():
    c = lib()
    assert c is not None, "native library must build in this environment"
    return c


def resp(*args: bytes) -> bytes:
    out = b"*%d\r\n" % len(args)
    for a in args:
        out += b"$%d\r\n%s\r\n" % (len(a), a)
    return out


def drain_native(eng, burst: bytes):
    """Same drain loop as test_native_drive (tests/ is not a package,
    so the helper is restated rather than imported)."""
    buf = bytearray(burst)
    replies = b""
    deferred = []
    while True:
        rc, consumed, n, unhandled, _changed = eng.scan_apply(buf)
        replies += eng.reply_bytes(n)
        del buf[:consumed]
        if rc == 1:
            deferred.append(unhandled)
            continue
        if rc == 2:
            continue
        return rc, replies, deferred, bytes(buf)


def _full_surface_burst(tag: bytes, i: int) -> bytes:
    """One burst over all five natively-served types, keys salted by
    thread tag so per-engine results are predictable."""
    k = tag + b"-%d" % (i % 4)
    return (
        resp(b"GCOUNT", b"INC", k, b"3")
        + resp(b"GCOUNT", b"GET", k)
        + resp(b"PNCOUNT", b"INC", k, b"2")
        + resp(b"PNCOUNT", b"DEC", k, b"1")
        + resp(b"TREG", b"SET", k, tag + b"-v%d" % i, b"%d" % (i + 1))
        + resp(b"TREG", b"GET", k)
        + resp(b"TLOG", b"INS", k, b"e%d" % i, b"%d" % (i + 1))
        + resp(b"TLOG", b"SIZE", k)
        + resp(b"UJSON", b"SET", k, b"n", b"%d" % i)
        + resp(b"UJSON", b"CLR", k)
    )


def _run_threads(workers):
    errors: list[BaseException] = []

    def wrap(fn):
        def run():
            try:
                fn()
            except BaseException as e:  # noqa: BLE001 — re-raised below
                errors.append(e)

        return run

    threads = [threading.Thread(target=wrap(w)) for w in workers]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    if errors:
        raise errors[0]


def test_concurrent_engines_are_isolated(cdll):
    """One engine per thread, full-surface bursts in parallel: every
    thread's replies and drain counts must be exactly what a solo run
    produces — and TSAN must see no cross-engine access."""
    results: dict[bytes, tuple] = {}
    lock = threading.Lock()

    def worker(tag: bytes):
        eng = ServeEngine(cdll)
        replies = b""
        for i in range(N_ROUNDS):
            rc, out, deferred, rest = drain_native(
                eng, _full_surface_burst(tag, i)
            )
            assert (rc, rest) == (0, b"") and not deferred
            replies += out
        summary = (
            replies,
            eng.served_counts(),
            sorted(eng.treg_flush_deltas()),
            len(eng.uq_drain()),
        )
        with lock:
            results[tag] = summary

    _run_threads(
        [lambda t=b"t%d" % n: worker(t) for n in range(N_THREADS)]
    )
    assert len(results) == N_THREADS
    # every engine saw only its own traffic: identical shapes, keys
    # salted by tag, and the reply streams are the solo-run streams
    solo = ServeEngine(cdll)
    expect = b""
    for i in range(N_ROUNDS):
        rc, out, deferred, _ = drain_native(
            solo, _full_surface_burst(b"t0", i)
        )
        assert rc == 0 and not deferred
        expect += out
    assert results[b"t0"][0] == expect
    for tag, (_, served, deltas, uq) in results.items():
        assert served["GCOUNT"] == 2 * N_ROUNDS
        assert {k for k, _ in deltas} == {
            tag + b"-%d" % j for j in range(4)
        }
        assert uq == 2 * N_ROUNDS


def test_shared_engine_under_external_mutex(cdll):
    """One engine, many threads, one lock around every call — the
    product's serialization contract. The final counter state must be
    the arithmetic sum, and TSAN must be silent (no engine code path
    may touch state outside the critical section)."""
    eng = ServeEngine(cdll)
    mu = threading.Lock()

    def worker(n: int):
        for i in range(N_ROUNDS):
            with mu:
                rc, out, deferred, rest = drain_native(
                    eng,
                    resp(b"GCOUNT", b"INC", b"shared", b"1")
                    + resp(b"PNCOUNT", b"INC", b"shared", b"2")
                    + resp(b"PNCOUNT", b"DEC", b"shared", b"1")
                    + resp(b"TLOG", b"INS", b"shared", b"e%d-%d" % (n, i),
                           b"%d" % (n * N_ROUNDS + i + 1)),
                )
                assert (rc, rest) == (0, b"") and not deferred
                assert out.count(b"+OK\r\n") == 4

    _run_threads([lambda n=n: worker(n) for n in range(N_THREADS)])
    with mu:
        rc, out, _, _ = drain_native(
            eng,
            resp(b"GCOUNT", b"GET", b"shared")
            + resp(b"PNCOUNT", b"GET", b"shared")
            + resp(b"TLOG", b"SIZE", b"shared"),
        )
    total = N_THREADS * N_ROUNDS
    assert out == b":%d\r\n:%d\r\n:%d\r\n" % (total, total, total)


def test_treg_bulk_drain_under_mutex_while_serving(cdll):
    """The TREG drain's bulk calls racing (under the mutex) with SET/GET
    bursts on the same registers: a drainer thread exports the pending
    window as batch planes, asks the tie call about every exported row
    and folds, while writers keep filling the window. Every register
    must end at its last writer's value, and no exported id may be
    negative."""
    eng = ServeEngine(cdll)
    mu = threading.Lock()
    n_keys = 16

    def writer(n: int):
        for i in range(N_ROUNDS):
            k = b"reg-%d" % ((n + i) % n_keys)
            with mu:
                rc, out, deferred, rest = drain_native(
                    eng,
                    resp(b"TREG", b"SET", k, b"shared-prefix-%d-%d" % (n, i),
                         b"%d" % (i + 1))
                    + resp(b"TREG", b"GET", k),
                )
                assert (rc, rest) == (0, b"") and not deferred
                assert out.startswith(b"+OK\r\n*2\r\n")

    def drainer():
        for _ in range(N_ROUNDS):
            with mu:
                n = eng.treg_pend_count()
                if not n:
                    continue
                ki = np.empty(n, np.int32)
                d = [np.zeros(n, np.uint32) for _ in range(4)] + [
                    np.full(n, -1, np.int32)
                ]
                assert eng.treg_export_planes(ki, *d, False) == n
                assert (d[4] >= 0).all()
                rows, vids = eng.treg_settle_ties(ki)
                assert set(rows.tolist()) <= set(ki.tolist())
                assert (vids >= 0).all()
                eng.treg_fold_pend()

    _run_threads([lambda n=n: writer(n) for n in range(4)] + [drainer])
    with mu:
        for j in range(n_keys):
            ts, val = eng.treg_winner(eng.treg_find(b"reg-%d" % j))
            # the highest timestamp any writer gave this register, and
            # among its writers the bytewise-largest value
            want = max(
                (i + 1, b"shared-prefix-%d-%d" % (n, i))
                for n in range(4)
                for i in range(N_ROUNDS)
                if (n + i) % n_keys == j
            )
            assert (ts, val) == want


def test_a_round_of_one_type_runs_beside_the_holders_of_the_others(cdll):
    """No mutex in common: the "loop" thread serves rounds that hold ONE
    type (`scan_apply(buf, held)`: the only user of the engine's reply
    and argument scratch) while a "TLOG drain" thread and a "UJSON fold"
    thread and a "MAP drain" thread, each the one holder of ITS type's
    lock, work on their own tables through the calls a drain makes. A
    round stops before the
    first command of a type it does not hold (rc 5), so nothing of the
    loop's ever reaches the tables the other two are in."""
    eng = ServeEngine(cdll)
    treg, counters = 1 << 2, 1 << 0 | 1 << 1
    stopped = []

    def loop():
        for i in range(N_ROUNDS * 4):
            k = b"reg-%d" % (i % 8)
            buf = bytearray(
                resp(b"TREG", b"SET", k, b"v%d" % i, b"%d" % (i + 1))
                + resp(b"TREG", b"GET", k)
                + resp(b"TLOG", b"INS", b"log-0", b"never", b"1")
            )
            rc, consumed, n, _u, changed = eng.scan_apply(buf, treg)
            assert rc == 5 and changed[2] == 1 and not any(changed[3:])
            assert eng.reply_bytes(n).startswith(b"+OK\r\n*2\r\n")
            del buf[:consumed]
            stopped.append(bytes(buf[:4]))
            buf = bytearray(resp(b"GCOUNT", b"INC", k, b"1") + resp(b"PNCOUNT", b"DEC", k, b"1"))
            assert eng.scan_apply(buf, counters)[:2] == (0, len(buf))

    def tlog_drain():
        for i in range(N_ROUNDS * 4):
            row = eng.tlog_upsert(b"log-%d" % (i % 4))
            eng.tlog_conv_entry(row, i + 1, b"foreign-%d" % i)
            eng.tlog_ins(row, 10_000 + i, b"local-%d" % i)
            assert eng.tlog_pend_total() >= 1
            eng.tlog_export_pend_bulk([row])
            eng.tlog_merged_entries(row)
            eng.tlog_flush_deltas()

    def ujson_fold():
        for i in range(N_ROUNDS * 4):
            key = b"doc-%d" % (i % 4)
            eng.uj_memo_put(key, [b"members"], b"$1\r\n1\r\n")
            assert eng.uj_memo_len(key) >= 1
            eng.uj_invalidate(key, [b"members"], False)
            eng.uq_drain()

    def map_drain():
        import numpy as np

        eng.map_set_rid(7)
        for i in range(N_ROUNDS * 4):
            key, field = b"rec-%d" % (i % 4), b"field%d" % (i % 3)
            eng.map_set(key, field, 7, i + 1, b"local-%d" % i)
            eng.map_join_unit(
                b"\x05" + key + field, {9: i + 1}, {}, 10_000 + i, b"foreign-%d" % i
            )
            n = eng.map_pend_count()
            assert n >= 1 and eng.map_get(eng.map_find(key, field))[1] >= 10_000
            planes = [np.zeros(16, np.uint32) for _ in range(4)]
            eng.map_export_planes(
                np.empty(16, np.int32), np.zeros((16, 32), np.uint32), *planes,
                np.full(16, -1, np.int32), False,
            )
            eng.map_clear_pend()
            eng.map_wire(eng.map_take_dirty())

    _run_threads([loop, tlog_drain, ujson_fold, map_drain])
    assert eng.map_rows() == 12 and eng.served_counts()["MAP"] == 0
    assert set(stopped) == {b"*5\r\n"}  # the TLOG INS, untouched every time
    assert eng.tlog_find(b"log-0") >= 0 and eng.treg_rows() == 8
    pend = eng.tlog_export_pend(eng.tlog_find(b"log-0"))
    assert len(pend) == 2 * N_ROUNDS and all(v != b"never" for _ts, v in pend)
    served = eng.served_counts()
    assert served["TREG"] == N_ROUNDS * 8 and served["TLOG"] == 0


def test_memo_install_invalidate_under_mutex(cdll):
    """The UJSON render-memo lifecycle under contention: installer
    threads publish renders (the oracle's job), writer threads bank
    writes that invalidate prefixes, reader threads serve GETs. All
    serialized by the mutex; the memo must end coherent and every
    served render must be one the installers published."""
    eng = ServeEngine(cdll)
    mu = threading.Lock()
    render = b"$7\r\n{\"n\":1}\r\n"

    def installer():
        for _ in range(N_ROUNDS):
            with mu:
                eng.uj_memo_put(b"doc", [], render)
                eng.uj_memo_put(b"doc", [b"n"], b"$1\r\n1\r\n")

    def writer():
        for i in range(N_ROUNDS):
            with mu:
                rc, out, deferred, _ = drain_native(
                    eng, resp(b"UJSON", b"SET", b"doc", b"n", b"%d" % i)
                )
                assert rc == 0 and not deferred
                assert out == b"+OK\r\n"
                assert eng.uj_memo_len(b"doc") == 0  # prefix invalidated

    def reader():
        for _ in range(N_ROUNDS):
            with mu:
                rc, out, deferred, _ = drain_native(
                    eng, resp(b"UJSON", b"GET", b"doc")
                )
                assert rc == 0
                # either a miss (deferred to the oracle) or the
                # installed render, never a torn/stale byte string
                if deferred:
                    assert deferred == [[b"UJSON", b"GET", b"doc"]]
                    assert out == b""
                else:
                    assert out == render

    _run_threads([installer, installer, writer, reader, reader])
    with mu:
        assert eng.uj_memo_len(b"doc") in (0, 2)
        assert eng.uq_count() == 0 or eng.uq_drain() is not None


def test_interner_compaction_under_load(cdll):
    """TLOG value-interner compaction racing (under the mutex) with
    fresh INS traffic on other rows: compaction remaps vids while the
    ingest path interns new values. Every merged entry must still
    resolve to its original bytes afterwards."""
    eng = ServeEngine(cdll)
    mu = threading.Lock()
    with mu:
        row = eng.tlog_upsert(b"hot")
        eng.tlog_ins(row, 1, b"keep-0")
        assert eng.tlog_size(row) == 1  # build the merged-view memo
        for i in range(1, 4000):
            eng.tlog_ins(row, 1 + i, b"garbage-%d" % i)
        eng.tlog_flush_deltas()

    def compactor():
        with mu:
            # drain trims to the top 2 entries -> most vids garbage
            eng.tlog_finish_row(row, 2, 3999)
            eng.tlog_finish_end()
        for _ in range(N_ROUNDS):
            with mu:
                eng.tlog_compact()

    def ingester(n: int):
        for i in range(N_ROUNDS):
            with mu:
                rc, out, deferred, _ = drain_native(
                    eng,
                    resp(b"TLOG", b"INS", b"cold-%d" % n,
                         b"live-%d-%d" % (n, i), b"%d" % (i + 1)),
                )
                assert rc == 0 and not deferred and out == b"+OK\r\n"

    _run_threads([compactor] + [lambda n=n: ingester(n) for n in range(3)])
    with mu:
        size = eng.tlog_size(row)
        assert size == eng.tlog_len_cache(row)
        ents = eng.tlog_merged_entries(row)
        assert ents is not None and len(ents) == size
        for _, val in ents:
            assert val.startswith((b"keep-", b"garbage-"))
        for n in range(3):
            r = eng.tlog_find(b"cold-%d" % n)
            assert eng.tlog_size(r) == N_ROUNDS


def test_two_producers_hand_off_to_the_reply_sender(cdll):
    """Two producers, four connections each, against one engine's
    sender thread: every connection's bytes arrive in hand-off order
    (small replies from the reply array, large ones that a socket pair's
    buffer cannot take whole), a third thread reads the counters all
    the while, a connection closed with bytes pending has them written
    out before the sender lets go of it, and the stop drops what a
    closed connection's socket still refuses. Under TSAN: the queue,
    the counters, the close and the stop are race-free."""
    eng = ServeEngine(cdll)
    n_conns, n_msgs = 4, 60
    done = threading.Event()
    out_mu = threading.Lock()

    def message(tag: int, c: int, i: int) -> bytes:
        body = b"%d/%d/%d;" % (tag, c, i)
        return body * (20_000 if i % 20 == 7 else 3)

    def producer(tag: int):
        pairs = [socket.socketpair() for _ in range(n_conns)]
        conns = [eng.sender_open(a.fileno(), 16 << 10, 64 << 10) for a, _ in pairs]
        assert all(c > 0 for c in conns)
        want = [b"".join(message(tag, c, i) for i in range(n_msgs)) for c in range(n_conns)]
        got = [bytearray() for _ in range(n_conns)]

        def read_all():
            by_sock = {b: got[c] for c, (_, b) in enumerate(pairs)}
            while any(len(got[c]) < len(want[c]) for c in range(n_conns)):
                ready, _, _ = select.select(list(by_sock), [], [], 10)
                assert ready, "the sender stalled"
                for b in ready:
                    by_sock[b] += b.recv(1 << 16)

        reader = threading.Thread(target=read_all)
        reader.start()
        for i in range(n_msgs):
            for c, conn in enumerate(conns):
                data = message(tag, c, i)
                if len(data) < 1024:
                    # as a burst's replies leave: from the engine's reply
                    # array, which the producers take turns at as bursts
                    # do on the loop
                    with out_mu:
                        ctypes.memmove(eng._out, data, len(data))
                        assert eng.sender_send(conn, len(data)) >= 0
                else:
                    assert eng.sender_send(conn, len(data), data) >= 0
        reader.join(30)
        assert not reader.is_alive()
        assert [bytes(g) for g in got] == want
        for conn in conns:
            assert eng.sender_close(conn) == 0 and eng.sender_behind(conn) == 0
            assert eng.sender_send(conn, 4, b"late") == -1  # closed: never sent
        for a, b in pairs:
            a.close()
            b.close()

    def watcher():
        while not done.is_set():
            st = eng.sender_stats()
            assert st[0] >= st[1] and st[6] <= st[4]

    w = threading.Thread(target=watcher)
    w.start()
    try:
        _run_threads([lambda t=t: producer(t) for t in (1, 2)])
    finally:
        done.set()
        w.join(10)
    # a connection closed with bytes its socket will not take yet: the
    # thread writes them out as the peer reads, THEN lets go of its
    # descriptor, and the peer reads the end of the stream
    big = 4 << 20
    a, b = socket.socketpair()
    conn = eng.sender_open(a.fileno(), 16 << 10, 64 << 10)
    assert eng.sender_send(conn, big, b"x" * big) >= 0
    assert 0 < eng.sender_close(conn) <= big
    assert eng.sender_send(conn, 4, b"late") == -1
    a.close()  # the caller's descriptor goes first: the sender's stands
    b.settimeout(10)
    got = 0
    while chunk := b.recv(1 << 20):
        got += len(chunk)
    b.close()
    st = eng.sender_stats()
    assert got == big and st[3] == (2 * n_conns + 1) * 4 and st[6] == 0
    # one whose peer never reads: the stop drops what its socket refuses
    a, b = socket.socketpair()
    conn = eng.sender_open(a.fileno(), 16 << 10, 64 << 10)
    assert eng.sender_send(conn, big, b"y" * big) >= 0
    left = eng.sender_close(conn)
    assert 0 < left <= big and eng.sender_stats()[7] == 1
    eng.sender_stop()
    st2 = eng.sender_stats()
    assert 0 < st2[3] - st[3] <= left and st2[6] == 0 and st2[7] == 0
    assert st2[0] == 2 * n_conns * n_msgs + 2
    a.close()
    b.close()
