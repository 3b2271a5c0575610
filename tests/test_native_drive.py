"""Deliberately jax-free drive of the native serving engine — the
`make sanitize` vehicle.

The ASAN+UBSAN build (`make sanitize`) runs this module (plus the RESP
scanner differentials in test_native_resp.py) with the sanitizer runtime
LD_PRELOADed; jax cannot be imported there (jaxlib's pybind11 C++
exceptions abort under the ASAN interceptor), so everything here drives
``ServeEngine`` via ctypes only: full pipelined bursts through
``scan_apply`` over all six types, the reply-buffer flush (rc 2) and
defer (rc 1) boundaries, protocol errors, the UJSON render memo and
write queue, TLOG interner compaction, and the bulk delta exports. In
the regular suite it doubles as an engine integration test.

Keep this module importable without jax: no jylis_tpu.models /
jylis_tpu.ops imports.
"""

from __future__ import annotations

import numpy as np
import pytest

from jylis_tpu.native import lib
from jylis_tpu.native.engine import ServeEngine
from jylis_tpu.obs.registry import MetricsRegistry

from procutil import scan_bytes


@pytest.fixture
def eng() -> ServeEngine:
    cdll = lib()
    assert cdll is not None, "native library must build in this environment"
    e = ServeEngine(cdll)
    e.bind_metrics(MetricsRegistry())  # its own counts, not the process's
    return e


def reply_tallies(eng) -> tuple[int, int, int]:
    """(reply_grows, reply_buffer_bytes, oversize_defers) as counted."""
    t = eng.metrics.tallies
    return tuple(
        t["serving.ENGINE." + k]
        for k in ("reply_grows", "reply_buffer_bytes", "oversize_defers")
    )


def resp(*args: bytes) -> bytes:
    out = b"*%d\r\n" % len(args)
    for a in args:
        out += b"$%d\r\n%s\r\n" % (len(a), a)
    return out


def drain_native(eng, burst: bytes):
    """Feed a whole burst; collect replies and deferred commands until
    the engine stops (rc 0/-1/-2)."""
    buf = bytearray(burst)
    replies = b""
    deferred = []
    rc = 0
    while True:
        rc, consumed, out, unhandled, _changed = scan_bytes(eng, buf)
        replies += out
        del buf[:consumed]
        if rc == 1:
            deferred.append(unhandled)
            continue
        if rc == 2:
            continue
        return rc, replies, deferred, bytes(buf)


def test_counter_burst_and_reply_order(eng):
    burst = (
        resp(b"GCOUNT", b"INC", b"k", b"5")
        + resp(b"GCOUNT", b"GET", b"k")
        + resp(b"PNCOUNT", b"INC", b"k", b"9")
        + resp(b"PNCOUNT", b"DEC", b"k", b"11")
        + resp(b"PNCOUNT", b"GET", b"k")
        + resp(b"GCOUNT", b"GET", b"nope")
    )
    rc, replies, deferred, rest = drain_native(eng, burst)
    assert (rc, rest) == (0, b"")
    assert not deferred
    assert replies == b"+OK\r\n:5\r\n+OK\r\n+OK\r\n:-2\r\n:0\r\n"
    served = eng.served_counts()
    assert served["GCOUNT"] == 3 and served["PNCOUNT"] == 3


def test_gcount_dec_is_not_native(eng):
    """GCOUNT has no DEC: the command must defer to the Python oracle
    (which renders the help text) — parity manifest territory."""
    rc, replies, deferred, _ = drain_native(
        eng, resp(b"GCOUNT", b"DEC", b"k", b"1")
    )
    assert rc == 0 and replies == b""
    assert deferred == [[b"GCOUNT", b"DEC", b"k", b"1"]]


def test_treg_set_get_and_big_value_rc2(eng):
    rc, replies, deferred, _ = drain_native(
        eng,
        resp(b"TREG", b"SET", b"r", b"hello", b"7")
        + resp(b"TREG", b"GET", b"r")
        + resp(b"TREG", b"GET", b"missing"),
    )
    assert rc == 0 and not deferred
    assert replies == b"+OK\r\n*2\r\n$5\r\nhello\r\n:7\r\n$-1\r\n"

    # a value larger than the 64 KiB reply buffer: SET banks it fine;
    # with a small reply already buffered the engine first asks for a
    # flush (rc 2), and when the GET comes first in its burst the reply
    # buffer grows to the reply inside scan_apply: nothing defers
    big = b"v" * (1 << 17)
    rc, replies, deferred, _ = drain_native(
        eng,
        resp(b"TREG", b"SET", b"big", big, b"9")
        + resp(b"GCOUNT", b"INC", b"pad", b"1")
        + resp(b"TREG", b"GET", b"big"),
    )
    assert rc == 0 and not deferred
    assert replies == b"+OK\r\n+OK\r\n*2\r\n$131072\r\n" + big + b"\r\n:9\r\n"
    # the next power of two over 128 KiB + headers, counted
    assert reply_tallies(eng) == (1, 1 << 18, 0)


# ---- the reply buffer grows to the reply ------------------------------------

TS0 = 1_700_000_000_000_000_000  # 19 digits, as a client's clock gives them


def post(i: int) -> bytes:
    """A YCSB E post: 1,000 B."""
    return b"%06d" % i + b"p" * 994


def _posts(eng, key: bytes, n: int) -> bytes:
    """INS n posts and return what TLOG GET key must render for all of
    them: newest first."""
    vals = [post(i) for i in range(n)]
    for lo in range(0, n, 50):
        rc, replies, deferred, _ = drain_native(eng, b"".join(
            resp(b"TLOG", b"INS", key, vals[i], b"%d" % (TS0 + i))
            for i in range(lo, min(lo + 50, n))
        ))
        assert rc == 0 and not deferred
    return [
        b"*2\r\n$1000\r\n%s\r\n:%d\r\n" % (vals[i], TS0 + i)
        for i in reversed(range(n))
    ]


def _render(entries, count: int) -> bytes:
    return b"*%d\r\n" % count + b"".join(entries[:count])


@pytest.mark.parametrize("count", [63, 64, 65, 100, 1000])
def test_tlog_get_of_any_count_is_the_engines(eng, count):
    """63 posts of 1,000 B fit the 64 KiB the buffer starts with, 64 do
    not: from there on the buffer grows to the reply, and the engine's
    render is the one of the small reply, byte for byte."""
    entries = _posts(eng, b"thread", 1000)
    rc, replies, deferred, _ = drain_native(
        eng, resp(b"TLOG", b"GET", b"thread", b"%d" % count)
    )
    assert rc == 0 and not deferred
    assert replies == _render(entries, count)
    need = len(replies)
    cap = max(1 << 16, 1 << (need - 1).bit_length())
    assert reply_tallies(eng) == (int(count >= 64), cap, 0)


def test_small_and_oversize_replies_keep_command_order(eng):
    """A pipelined burst: the small replies in front of an outsize one
    are flushed first (rc 2), the re-entered call grows the buffer, and
    what follows the outsize reply comes after it."""
    entries = _posts(eng, b"thread", 200)
    burst = (
        resp(b"TLOG", b"SIZE", b"thread")
        + resp(b"TLOG", b"GET", b"thread", b"3")
        + resp(b"TLOG", b"GET", b"thread", b"150")
        + resp(b"GCOUNT", b"INC", b"c", b"4")
        + resp(b"TLOG", b"GET", b"thread")
        + resp(b"GCOUNT", b"GET", b"c")
    )
    buf = bytearray(burst)
    rc, consumed, replies, unhandled, _ = scan_bytes(eng, buf)
    assert rc == 2 and unhandled is None  # flush what settled, re-enter
    assert replies == b":200\r\n" + _render(entries, 3)
    assert reply_tallies(eng) == (0, 1 << 16, 0)  # not while replies wait
    rc, replies_rest, deferred, rest = drain_native(eng, bytes(buf[consumed:]))
    assert (rc, rest) == (0, b"") and not deferred
    assert replies_rest == (
        _render(entries, 150) + b"+OK\r\n" + _render(entries, 200) + b":4\r\n"
    )


def test_reply_buffer_grows_once_for_a_size_and_never_shrinks(eng):
    entries = _posts(eng, b"thread", 300)
    get100 = resp(b"TLOG", b"GET", b"thread", b"100")
    assert reply_tallies(eng) == (0, 1 << 16, 0)
    for _ in range(3):  # the same size again: the buffer is there
        rc, replies, deferred, _ = drain_native(eng, get100)
        assert rc == 0 and not deferred and replies == _render(entries, 100)
        assert reply_tallies(eng) == (1, 1 << 17, 0)
    first = eng._out
    drain_native(eng, resp(b"TLOG", b"GET", b"thread", b"2"))
    drain_native(eng, resp(b"TLOG", b"GET", b"thread", b"110"))  # fits 128 KiB
    assert eng._out is first and reply_tallies(eng) == (1, 1 << 17, 0)
    rc, replies, deferred, _ = drain_native(eng, resp(b"TLOG", b"GET", b"thread"))
    assert rc == 0 and not deferred and replies == _render(entries, 300)
    assert reply_tallies(eng) == (2, 1 << 19, 0)
    drain_native(eng, get100)
    assert reply_tallies(eng) == (2, 1 << 19, 0)
    assert len(eng._out) == 1 << 19


def test_reply_past_the_ceiling_defers_as_before(eng, monkeypatch):
    """Past the ceiling the command is the Python path's, exactly as a
    reply over 64 KiB was: flush first (rc 2), then deferred (rc 1) and
    consumed, the buffer left as it was."""
    from jylis_tpu.native import engine as engine_mod

    monkeypatch.setattr(engine_mod, "_OUT_CEIL", 1 << 17)
    entries = _posts(eng, b"thread", 200)
    burst = (
        resp(b"TLOG", b"SIZE", b"thread")
        + resp(b"TLOG", b"GET", b"thread", b"100")  # 102 KB: under the ceiling
        + resp(b"TLOG", b"GET", b"thread")  # 207 KB: over it
        + resp(b"TLOG", b"SIZE", b"thread")
    )
    rc, replies, deferred, rest = drain_native(eng, burst)
    assert (rc, rest) == (0, b"")
    assert replies == b":200\r\n" + _render(entries, 100) + b":200\r\n"
    assert deferred == [[b"TLOG", b"GET", b"thread"]]
    assert reply_tallies(eng) == (1, 1 << 17, 1)


def test_treg_lww_winner_rule(eng):
    burst = (
        resp(b"TREG", b"SET", b"r", b"aa", b"5")
        + resp(b"TREG", b"SET", b"r", b"zz", b"5")  # same ts: value wins
        + resp(b"TREG", b"SET", b"r", b"old", b"4")  # older ts: loses
        + resp(b"TREG", b"GET", b"r")
    )
    rc, replies, _, _ = drain_native(eng, burst)
    assert rc == 0
    assert replies.endswith(b"*2\r\n$2\r\nzz\r\n:5\r\n")


def _treg_batch(b: int):
    """A b-slot drain batch at the lattice identity, as RepoTREG builds
    it: [ts_hi, ts_lo, rank_hi, rank_lo, vid]."""
    return [np.zeros(b, np.uint32) for _ in range(4)] + [
        np.full(b, -1, np.int32)
    ]


def test_treg_bulk_export_settle_ties_and_fold(eng):
    """The drain's two bulk calls while the engine serves: bursts fill
    the pending window, `treg_export_planes` hands it over as batch
    planes (sparse, then dense), `treg_settle_ties` decides equal
    prefixes by the full strings, and the fold MOVES the winners into
    the drained cache, which the next burst reads and overwrites."""
    keys = [b"reg-%d" % i for i in range(40)]
    burst = b"".join(
        resp(b"TREG", b"SET", k, b"same-prefix-%04d" % i, b"%d" % ((1 << 33) + 5))
        for i, k in enumerate(keys)
    )
    rc, replies, deferred, _ = drain_native(eng, burst)
    assert rc == 0 and not deferred and replies == b"+OK\r\n" * 40
    n = eng.treg_pend_count()
    assert n == 40
    ki = np.empty(64, np.int32)
    d = _treg_batch(64)
    assert eng.treg_export_planes(ki, *d, False) == n
    assert ki[:n].tolist() == list(range(40))
    assert (d[0][:n] == 2).all() and (d[1][:n] == 5).all()  # ts hi/lo
    want_rank = int.from_bytes(b"same-pre", "big")
    assert (d[2][:n] == want_rank >> 32).all()
    assert (d[3][:n] == want_rank & 0xFFFFFFFF).all()
    assert (d[4][:n] == 0).all() and (d[4][n:] == -1).all()  # first generation
    dense = _treg_batch(64)
    assert eng.treg_export_planes(ki, *dense, True) == n  # slot = row
    for a, b in zip(d, dense):
        assert (a == b).all()
    eng.treg_fold_pend()
    assert eng.treg_pend_count() == 0

    # the next window: equal (ts, prefix), tails that win on even rows and
    # lose on odd ones, one identical re-delivery, one short value
    burst = b"".join(
        resp(
            b"TREG", b"SET", k,
            b"same-prefix-%04d" % (i + 1 if i % 2 == 0 else i - 1),
            b"%d" % ((1 << 33) + 5),
        )
        for i, k in enumerate(keys[:10])
    )
    burst += resp(b"TREG", b"SET", keys[10], b"same-prefix-0010", b"%d" % ((1 << 33) + 5))
    burst += resp(b"TREG", b"SET", keys[11], b"ab", b"1")
    rc, _, deferred, _ = drain_native(eng, burst + resp(b"TREG", b"GET", keys[0]))
    assert rc == 0 and not deferred
    n = eng.treg_pend_count()
    assert n == 12
    d = _treg_batch(16)
    ki = np.empty(16, np.int32)
    assert eng.treg_export_planes(ki, *d, False) == n
    assert d[4][:n].tolist() == [1] * 10 + [0, 1]  # a re-delivery keeps its id
    assert d[2][11] == int.from_bytes(b"ab\0\0", "big") and d[3][11] == 0
    asked = np.arange(12, dtype=np.int32)
    rows, vids = eng.treg_settle_ties(asked)
    assert rows.tolist() == [0, 2, 4, 6, 8] and vids.tolist() == [1] * 5
    assert asked.tolist() == list(range(12))  # the caller's array is not the scratch
    assert eng.treg_settle_ties(np.asarray([-1, 10**6], np.int32))[0].size == 0
    eng.treg_fold_pend()
    rc, replies, _, _ = drain_native(
        eng,
        resp(b"TREG", b"GET", keys[0])
        + resp(b"TREG", b"GET", keys[1])
        + resp(b"TREG", b"SET", keys[1], b"x" * 2000, b"%d" % (1 << 40))
        + resp(b"TREG", b"GET", keys[1]),
    )
    assert rc == 0
    assert replies == (
        b"*2\r\n$16\r\nsame-prefix-0001\r\n:8589934597\r\n"
        b"*2\r\n$16\r\nsame-prefix-0001\r\n:8589934597\r\n"
        b"+OK\r\n*2\r\n$2000\r\n" + b"x" * 2000 + b"\r\n:1099511627776\r\n"
    )
    with pytest.raises(ValueError):  # a batch the window does not fit
        eng.treg_export_planes(ki, *_treg_batch(0), False)


def test_tlog_ins_size_get_cutoff(eng):
    burst = (
        resp(b"TLOG", b"INS", b"l", b"e1", b"10")
        + resp(b"TLOG", b"INS", b"l", b"e2", b"20")
        + resp(b"TLOG", b"INS", b"l", b"e2", b"20")  # dup: merged view dedups
        + resp(b"TLOG", b"SIZE", b"l")
        + resp(b"TLOG", b"GET", b"l")
        + resp(b"TLOG", b"GET", b"l", b"1")
        + resp(b"TLOG", b"CUTOFF", b"l")
        + resp(b"TLOG", b"SIZE", b"missing")
        + resp(b"TLOG", b"GET", b"missing")
    )
    rc, replies, deferred, _ = drain_native(eng, burst)
    assert rc == 0 and not deferred
    assert replies == (
        b"+OK\r\n+OK\r\n+OK\r\n:2\r\n"
        b"*2\r\n*2\r\n$2\r\ne2\r\n:20\r\n*2\r\n$2\r\ne1\r\n:10\r\n"
        b"*1\r\n*2\r\n$2\r\ne2\r\n:20\r\n"
        b":0\r\n:0\r\n*0\r\n"
    )
    # TRIM dispatches a device drain: never native
    rc, _, deferred, _ = drain_native(eng, resp(b"TLOG", b"TRIM", b"l", b"1"))
    assert deferred == [[b"TLOG", b"TRIM", b"l", b"1"]]


def test_tlog_interner_compaction_remaps_live_vids(eng):
    # intern far more values than the compaction floor, then converge
    # the rows away so most become garbage
    row = eng.tlog_upsert(b"l")
    eng.tlog_ins(row, 1000, b"val-0")
    # build the merged-view memo now (a SIZE does it); subsequent ins
    # calls then maintain it, so the drain below carries a valid base
    assert eng.tlog_size(row) == 1
    for i in range(1, 9000):
        eng.tlog_ins(row, 1000 + i, b"val-%d" % i)  # ts 1000..9999
    eng.tlog_flush_deltas()  # drop the delta accumulator's references
    # a drain that trimmed to cutoff 9998 keeps exactly ts 9998, 9999:
    # the memo is current (ins maintains it), so the carried base is the
    # filtered memo and stays valid
    eng.tlog_finish_row(row, 2, 9998)
    eng.tlog_finish_end()
    assert eng.tlog_compact() in (True, False)
    # force: repeat until the floor logic actually compacts or stabilises
    for _ in range(3):
        if eng.tlog_compact():
            break
    size = eng.tlog_size(row)
    assert size == eng.tlog_len_cache(row)
    # the carried base must still resolve through the remapped interner
    ents = eng.tlog_merged_entries(row)
    assert ents is not None and len(ents) == size
    for ts, val in ents:
        assert val.startswith(b"val-")


def test_ujson_validate_bank_and_memo(eng):
    # valid writes bank natively (+OK), invalid ones defer to the oracle
    burst = (
        resp(b"UJSON", b"INS", b"d", b"tags", b'"x"')
        + resp(b"UJSON", b"SET", b"d", b"obj", b'{"a": [1, 2.5e3, null]}')
        + resp(b"UJSON", b"RM", b"d", b"tags", b'"x"')
        + resp(b"UJSON", b"CLR", b"d", b"obj")
        + resp(b"UJSON", b"INS", b"d", b"bad", b"{not json}")
        + resp(b"UJSON", b"INS", b"d", b"ctl", b'"a\x01b"')
        + resp(b"UJSON", b"SET", b"d", b"deep", b"[" * 100 + b"]" * 100)
    )
    rc, replies, deferred, _ = drain_native(eng, burst)
    assert rc == 0
    assert replies == b"+OK\r\n" * 4
    assert [d[1] for d in deferred] == [b"INS", b"INS", b"SET"]
    banked = eng.uq_drain()
    assert [b[0] for b in banked] == [b"INS", b"SET", b"RM", b"CLR"]
    assert banked[0] == [b"INS", b"d", b"tags", b'"x"']
    assert eng.uq_count() == 0

    # GET misses defer; after the oracle installs a render, it serves
    # natively; an overlapping write invalidates exactly the prefix
    rc, _, deferred, _ = drain_native(eng, resp(b"UJSON", b"GET", b"d"))
    assert deferred == [[b"UJSON", b"GET", b"d"]]
    eng.uj_memo_put(b"d", [], b"$9\r\n{\"a\":123}\r\n")
    eng.uj_memo_put(b"d", [b"a"], b"$3\r\n123\r\n")
    rc, replies, deferred, _ = drain_native(
        eng, resp(b"UJSON", b"GET", b"d") + resp(b"UJSON", b"GET", b"d", b"a")
    )
    assert rc == 0 and not deferred
    assert replies == b"$9\r\n{\"a\":123}\r\n$3\r\n123\r\n"
    assert eng.uj_memo_len(b"d") == 2
    # INS under a.b invalidates the renders at prefixes "" and "a"
    rc, replies, _, _ = drain_native(
        eng, resp(b"UJSON", b"INS", b"d", b"a", b"b", b"1")
    )
    assert replies == b"+OK\r\n"
    assert eng.uj_memo_len(b"d") == 0


def test_ujson_utf8_path_gate(eng):
    # invalid UTF-8 in a path component defers (the memo key must be
    # canonical bytes); valid raw UTF-8 banks natively
    rc, replies, deferred, _ = drain_native(
        eng,
        resp(b"UJSON", b"INS", b"d", b"\xff\xfe", b"1")
        + resp(b"UJSON", b"INS", b"d", "café".encode(), b"2"),
    )
    assert rc == 0
    assert replies == b"+OK\r\n"
    assert deferred == [[b"UJSON", b"INS", b"d", b"\xff\xfe", b"1"]]


def test_protocol_error_and_oversized_command(eng):
    rc, replies, deferred, rest = drain_native(
        eng, resp(b"GCOUNT", b"INC", b"k", b"1") + b"*1\r\n$bogus\r\n"
    )
    assert rc == -1
    assert replies == b"+OK\r\n"
    # an arg-count overflow reports rc -2 (caller grows and demotes)
    many = resp(*([b"GCOUNT", b"GET"] + [b"k"] * 2000))
    rc, _, _, _ = drain_native(eng, many)
    assert rc == -2


def test_split_burst_resumes_mid_command(eng):
    whole = resp(b"GCOUNT", b"INC", b"k", b"3") + resp(b"GCOUNT", b"GET", b"k")
    for cut in (1, 7, len(whole) // 2, len(whole) - 2):
        e = ServeEngine(lib())
        buf = bytearray(whole[:cut])
        rc, consumed, out, _, _ = scan_bytes(e, buf)
        assert rc == 0
        del buf[:consumed]
        buf += whole[cut:]
        rc, consumed, out2, _, _ = scan_bytes(e, buf)
        assert rc == 0
        assert (out + out2) == b"+OK\r\n:3\r\n"


def test_bulk_delta_exports(eng):
    rc, _, _, _ = drain_native(
        eng,
        resp(b"TREG", b"SET", b"r1", b"v1", b"1")
        + resp(b"TREG", b"SET", b"r2", b"v2", b"2")
        + resp(b"TLOG", b"INS", b"l1", b"e", b"5"),
    )
    assert rc == 0
    treg = eng.treg_flush_deltas()
    assert treg == [(b"r1", (b"v1", 1)), (b"r2", (b"v2", 2))]
    tlog = eng.tlog_flush_deltas()
    assert tlog == [(b"l1", ([(b"e", 5)], 0))]
    # cleared: a second flush exports nothing
    assert eng.treg_flush_deltas() == []
    assert eng.tlog_flush_deltas() == []


def test_map_field_table_burst_wire_and_drain_exports(eng):
    """The sixth type's table, end to end without jax: `MAP TREG` SET /
    GET / GETALL through `scan_apply` (and what it hands back), foreign
    units, a replica id that re-strides the planes, the wire forms a
    flush, a dump and a restore carry, the drain's batch planes (sparse
    and dense), ties, and the taken lists."""
    eng.map_set_rid(7)
    burst = b"".join(
        resp(b"MAP", b"TREG", b"SET", b"user%d" % (i % 5), b"field%d" % (i % 12),
             b"v%03d" % i + b"x" * (i % 40), b"%d" % (i + 1))
        for i in range(300)
    )
    burst += resp(b"MAP", b"TREG", b"GETALL", b"user3")
    burst += resp(b"MAP", b"TREG", b"GET", b"user3", b"field3")
    burst += resp(b"MAP", b"TREG", b"GET", b"user3", b"nope")
    burst += resp(b"MAP", b"TREG", b"GETALL", b"nobody")
    rc, replies, deferred, rest = drain_native(eng, burst)
    assert (rc, rest) == (0, b"") and deferred == []
    assert replies.startswith(b"+OK\r\n" * 300 + b"*24\r\n$6\r\nfield0\r\n*2\r\n$")
    assert replies.endswith(b"$-1\r\n*0\r\n")
    assert eng.served_counts()["MAP"] == 304 and eng.map_rows() == 60
    # field names come back in byte order: field0, field1, field10, field11, field2 ...
    rows = eng.map_record(b"user3")
    assert [eng.map_field_name(int(r)) for r in rows][:5] == [
        b"field0", b"field1", b"field10", b"field11", b"field2"]
    # what the engine hands back: DEL, KEYS, another inner type, another
    # arity, a bad timestamp, a key that holds a field of another type
    eng.map_mark_mixed(b"user4")
    handed = [
        (b"MAP", b"TREG", b"DEL", b"user1", b"field1"),
        (b"MAP", b"TREG", b"KEYS", b"user1"),
        (b"MAP", b"GCOUNT", b"GET", b"user1", b"hits"),
        (b"MAP", b"TREG", b"SET", b"user1", b"field1", b"v"),
        (b"MAP", b"TREG", b"SET", b"user1", b"field1", b"v", b"1", b"extra"),
        (b"MAP", b"TREG", b"SET", b"user1", b"field1", b"v", b"-1"),
        (b"MAP", b"TREG", b"GETALL", b"user4"),
        (b"MAP", b"TREG"),
    ]
    rc, replies, deferred, rest = drain_native(eng, b"".join(resp(*c) for c in handed))
    assert (rc, rest, replies) == (0, b"", b"") and deferred == [list(c) for c in handed]
    # a foreign unit from a new replica (re-strides ver/tomb), a DEL, a tie
    row = eng.map_find(b"user1", b"field1")
    before = eng.map_get(row)
    assert eng.map_join_unit(b"\x05user1field1", {9: 4, 11: 1}, {7: 1}, before[1], before[0] + b"!") == row
    assert eng.map_get(row) == (before[0] + b"!", before[1])  # equal ts: the greater value
    assert eng.map_join_unit(b"\x80", {}, {}, 1, b"v") == -1  # no (key, field) in it
    assert sorted(eng.map_rids()) == [7, 9, 11]
    assert eng.map_del(row) and eng.map_get(row) is None and not eng.map_del(row)
    # flush / dump / restore as wire bytes
    n, payload, starts = eng.map_wire(eng.map_take_dirty())
    assert n == 60 and len(starts) == 60 and starts[0] == 0 and eng.map_dirty_count() == 0
    n_all, dump, _ = eng.map_wire()
    assert n_all == 60 and len(dump) > len(b"v000") * 60
    from jylis_tpu.native.engine import map_wire_ok

    assert map_wire_ok(dump, 60) and not map_wire_ok(dump, 59) and not map_wire_ok(dump[:-1], 60)
    assert not map_wire_ok(dump.replace(b"\x04TREG", b"\x04TLOG", 1), 60)
    other = ServeEngine(lib())
    other.map_reserve(8, 64)
    other.map_load_wire(dump, 60)
    assert other.map_wire()[1] == dump and other.map_pend_count() == 60
    # the drain's planes: sparse (slot i) and dense (slot row), 4 x 8 replica columns
    for dense in (False, True):
        cap = 64
        ki = np.empty(cap, np.int32)
        cells = np.zeros((cap, 32), np.uint32)
        planes = [np.zeros(cap, np.uint32) for _ in range(4)]
        vid = np.full(cap, -1, np.int32)
        assert other.map_export_planes(ki, cells, *planes, vid, dense) == 60
        assert sorted(ki[:60].tolist()) == list(range(60)) and (vid[:60] >= 0).all()
        assert cells[:60, 16:24].sum() >= 60  # ver's low words: an edit a row
    with pytest.raises(ValueError):
        other.map_export_planes(np.empty(8, np.int32), np.zeros((8, 32), np.uint32),
                                *[np.zeros(8, np.uint32) for _ in range(4)],
                                np.full(8, -1, np.int32), False)
    rows, vids = other.map_settle_ties(np.array([3, 999, -1, 5], np.int32))
    assert rows.tolist() == [3, 5] and (vids >= 0).all()
    other.map_clear_pend()
    assert other.map_pend_count() == 0 and len(other.map_take_sync()) == 60
    eng.map_tally()
    assert eng.metrics.tallies["drain.MAP.sets"] == 300
    assert eng.metrics.tallies["drain.MAP.getalls"] == 2
    assert eng.metrics.tallies["drain.MAP.getall_fields"] == 12
