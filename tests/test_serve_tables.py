"""Differential tests: native TREG/TLOG tables + UJSON queue vs the
pure-Python backends.

The Python table backends (models/treg_table.PyTregTable,
models/tlog_table.PyTlogTable) are the semantic oracles; the native
engine must be observationally identical through every surface — repo
commands, cluster converge, drains, trims, flushes, snapshots — and the
server's all-types batch applier must produce byte-identical reply
streams against the pure-Python serving path.

Also pins the round-4 verdict's TLOG read-view edges (remote converge
interleaved with local INS, cutoff raises between SIZE and GET, order
materialisation after SIZE-only traffic) on BOTH backends.
"""

import asyncio

import numpy as np
import pytest

import jylis_tpu  # noqa: F401
from jylis_tpu.models.repo_tlog import RepoTLOG
from jylis_tpu.models.repo_treg import RepoTREG
from jylis_tpu.models.repo_ujson import RepoUJSON
from jylis_tpu.native.engine import make_engine

from procutil import scan_bytes

from test_tlog_tallies import lose_base


class R:
    def __init__(self):
        self.vals = []

    def __getattr__(self, name):
        return lambda *a: self.vals.extend((name, *a))


def have_native() -> bool:
    return make_engine() is not None


pytestmark = pytest.mark.skipif(
    not have_native(), reason="native engine unavailable (no toolchain)"
)


def both(a, b, cmd):
    ra, rb = R(), R()
    a.apply(ra, cmd)
    b.apply(rb, cmd)
    assert ra.vals == rb.vals, cmd
    return ra.vals


# ---- TREG ------------------------------------------------------------------


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_treg_repo_differential_random_workload(seed):
    from jylis_tpu.models.treg_table import NativeTregTable, PyTregTable

    rng = np.random.default_rng(seed)
    native = RepoTREG(identity=3)
    oracle = RepoTREG(identity=3, engine="python")
    assert isinstance(native._tbl, NativeTregTable)
    assert isinstance(oracle._tbl, PyTregTable)
    keys = [b"t%d" % i for i in range(8)]
    for step in range(400):
        k = keys[rng.integers(len(keys))]
        roll = rng.integers(10)
        if roll < 4:
            v = b"v%d" % rng.integers(6)
            ts = b"%d" % rng.integers(1, 50)
            both(native, oracle, [b"SET", k, v, ts])
        elif roll < 7:
            both(native, oracle, [b"GET", k])
        elif roll == 7:
            # cluster converge (same LWW rule, no delta)
            delta = (b"w%d" % rng.integers(6), int(rng.integers(1, 50)))
            native.converge(k, delta)
            oracle.converge(k, delta)
        elif roll == 8:
            assert native.deltas_size() == oracle.deltas_size()
            assert native.flush_deltas() == oracle.flush_deltas(), step
        else:
            native.drain()
            oracle.drain()
    for k in keys:
        both(native, oracle, [b"GET", k])
    assert native.dump_state() == oracle.dump_state()


def test_treg_equal_ts_value_tiebreak_both_backends():
    for engine in ("auto", "python"):
        repo = RepoTREG(identity=1, engine=engine)
        repo.apply(R(), [b"SET", b"k", b"bbb", b"7"])
        repo.apply(R(), [b"SET", b"k", b"aaa", b"7"])  # loses the tiebreak
        r = R()
        repo.apply(r, [b"GET", b"k"])
        assert r.vals == ["array_start", 2, "string", b"bbb", "u64", 7]
        repo.drain()  # winner survives the drain fold
        r = R()
        repo.apply(r, [b"GET", b"k"])
        assert r.vals == ["array_start", 2, "string", b"bbb", "u64", 7]


# ---- TLOG ------------------------------------------------------------------


def _tlog_pair():
    native = RepoTLOG(identity=1)
    oracle = RepoTLOG(identity=1, engine="python")
    from jylis_tpu.models.tlog_table import NativeTlogTable, PyTlogTable

    assert isinstance(native._tbl, NativeTlogTable)
    assert isinstance(oracle._tbl, PyTlogTable)
    return native, oracle


@pytest.mark.parametrize("seed", [0, 1, 2, 3])
def test_tlog_repo_differential_random_workload(seed):
    rng = np.random.default_rng(seed)
    native, oracle = _tlog_pair()
    keys = [b"l%d" % i for i in range(6)]
    for step in range(400):
        k = keys[rng.integers(len(keys))]
        roll = rng.integers(14)
        if roll < 4:
            # duplicates on purpose: small ts/value ranges collide often
            v = b"e%d" % rng.integers(8)
            ts = b"%d" % rng.integers(1, 40)
            both(native, oracle, [b"INS", k, v, ts])
        elif roll < 7:
            both(native, oracle, [b"SIZE", k])
        elif roll < 9:
            both(native, oracle, [b"GET", k, b"%d" % rng.integers(1, 20)])
        elif roll == 9:
            both(native, oracle, [b"CUTOFF", k])
        elif roll == 10:
            op = [b"TRIM", k, b"%d" % rng.integers(0, 6)]
            if rng.integers(2):
                op = [b"TRIMAT", k, b"%d" % rng.integers(1, 40)]
            both(native, oracle, op)
        elif roll == 11:
            ents = [
                (b"r%d" % rng.integers(8), int(rng.integers(1, 40)))
                for _ in range(rng.integers(1, 5))
            ]
            cut = int(rng.integers(0, 2) * rng.integers(1, 30))
            native.converge(k, (ents, cut))
            oracle.converge(k, (ents, cut))
        elif roll == 12:
            assert native.deltas_size() == oracle.deltas_size()
            assert native.flush_deltas() == oracle.flush_deltas(), step
        else:
            native.drain()
            oracle.drain()
    for k in keys:
        both(native, oracle, [b"SIZE", k])
        both(native, oracle, [b"GET", k])
    assert native.dump_state() == oracle.dump_state()


@pytest.mark.parametrize("engine", ["auto", "python"])
def test_tlog_remote_converge_interleaved_with_local_ins(engine):
    """Round-4 verdict item 7: the merged memo must invalidate (not
    corrupt) when a cluster converge lands between local INSes."""
    repo = RepoTLOG(identity=1, engine=engine)
    r = R()
    repo.apply(r, [b"INS", b"k", b"a", b"5"])
    assert_size(repo, 1)  # memo built
    repo.apply(r, [b"INS", b"k", b"b", b"6"])  # incremental set extension
    assert_size(repo, 2)
    repo.converge(b"k", ([(b"c", 7), (b"a", 5)], 0))  # dup of (a,5) + new
    repo.apply(r, [b"INS", b"k", b"d", b"8"])  # memo stale at this point
    assert_size(repo, 4)  # a,b,c,d — the dup (a,5) counts once
    out = R()
    repo.apply(out, [b"GET", b"k"])
    assert out.vals[0:2] == ["array_start", 4]
    # newest-first order materialised correctly after the rebuild
    # (per entry: 'array_start', 2, 'string', value, 'u64', ts)
    assert out.vals[5] == b"d" and out.vals[-3] == b"a"


@pytest.mark.parametrize("engine", ["auto", "python"])
def test_tlog_cutoff_raise_between_size_and_get(engine):
    """A TRIMAT between SIZE and GET must re-filter the merged view."""
    repo = RepoTLOG(identity=1, engine=engine)
    r = R()
    for i in range(6):
        repo.apply(r, [b"INS", b"k", b"v%d" % i, b"%d" % (i + 1)])
    assert_size(repo, 6)
    repo.apply(r, [b"TRIMAT", b"k", b"4"])  # drops ts 1..3
    assert_size(repo, 3)
    out = R()
    repo.apply(out, [b"GET", b"k"])
    assert out.vals[0:2] == ["array_start", 3]
    got_ts = [out.vals[i] for i in range(7, len(out.vals), 6)]
    assert got_ts == [6, 5, 4]
    # converge-only cutoff raise (no local trim) filters the same way
    repo.converge(b"k", ([], 6))
    assert_size(repo, 1)


@pytest.mark.parametrize("engine", ["auto", "python"])
def test_tlog_get_order_after_size_only_traffic(engine):
    """SIZE-only traffic leaves the sorted view unmaterialised; the first
    GET afterwards must produce exact (ts, value)-desc order."""
    repo = RepoTLOG(identity=1, engine=engine)
    r = R()
    ts_vals = [(3, b"c"), (9, b"x"), (3, b"a"), (7, b"m"), (9, b"b")]
    for ts, v in ts_vals:
        repo.apply(r, [b"INS", b"k", v, b"%d" % ts])
        repo.apply(r, [b"SIZE", b"k"])  # size-only: no order needed yet
    out = R()
    repo.apply(out, [b"GET", b"k"])
    vals = [out.vals[i] for i in range(5, len(out.vals), 6)]
    assert vals == [b"x", b"b", b"m", b"c", b"a"]  # ts desc, value desc


@pytest.mark.parametrize("engine", ["auto", "python"])
@pytest.mark.parametrize("seed", [0, 1])
def test_tlog_merged_view_fuzz_vs_drain_rebuilt(engine, seed):
    """Fuzz the incremental merged view against ground truth: after any
    op mix, SIZE/GET must equal the view a full drain produces."""
    rng = np.random.default_rng(seed)
    repo = RepoTLOG(identity=1, engine=engine)
    r = R()
    for _ in range(200):
        roll = rng.integers(6)
        if roll < 3:
            repo.apply(
                r,
                [b"INS", b"k", b"v%d" % rng.integers(6), b"%d" % rng.integers(1, 30)],
            )
        elif roll == 3:
            repo.converge(
                b"k",
                (
                    [(b"w%d" % rng.integers(6), int(rng.integers(1, 30)))],
                    int(rng.integers(0, 2) * rng.integers(1, 20)),
                ),
            )
        elif roll == 4:
            repo.apply(r, [b"TRIM", b"k", b"%d" % rng.integers(1, 10)])
        else:
            repo.drain()
        pre = R()
        repo.apply(pre, [b"SIZE", b"k"])
        pre_get = R()
        repo.apply(pre_get, [b"GET", b"k"])
        # ground truth: drain everything, then read back the device view
        repo.drain()
        post = R()
        repo.apply(post, [b"SIZE", b"k"])
        post_get = R()
        repo.apply(post_get, [b"GET", b"k"])
        assert pre.vals == post.vals
        assert pre_get.vals == post_get.vals


def test_tlog_drain_fold_differential_native_vs_python():
    """3,000 mixed commands on the native and the Python table at once:
    after every drain the two agree on which rows still hold their base,
    on every length and on the GET bytes, and no read ever went back to
    the device for a row (the drain's epilogue folds the pending window
    into the base: `finish_row` / `finish_drain_row` state one rule)."""
    from jylis_tpu.obs.registry import MetricsRegistry

    rng = np.random.default_rng(31)
    native, oracle = _tlog_pair()
    regs = []
    for repo in (native, oracle):
        repo.metrics = MetricsRegistry()
        regs.append(repo.metrics)
    keys = [b"t%d" % i for i in range(8)]

    def after_drain():
        for k in keys:
            rn, ro = native._tbl.find(k), oracle._tbl.find(k)
            assert (rn < 0) == (ro < 0)
            if rn < 0:
                continue
            assert native._tbl.base_valid(rn) and oracle._tbl.base_valid(ro), k
            assert native._tbl.len_cache(rn) == oracle._tbl.len_cache(ro), k
            assert _oracle_reply(native, [b"GET", k]) == _oracle_reply(oracle, [b"GET", k]), k
        for reg in regs:
            assert reg.tallies["drain.TLOG.row_gathers"] == 0
            assert reg.tallies["drain.TLOG.bases_lost"] == 0

    drains = 0
    for step in range(3000):
        k = keys[rng.integers(len(keys))]
        roll = rng.integers(20)
        if roll < 6:  # duplicates on purpose: small ts and value ranges collide
            both(native, oracle, [b"INS", k, b"e%d" % rng.integers(12), b"%d" % rng.integers(1, 200)])
        elif roll < 10:
            both(native, oracle, [b"GET", k, b"%d" % rng.integers(1, 30)])
        elif roll < 12:
            both(native, oracle, [b"SIZE", k])
        elif roll < 15:
            ents = [(b"r%d" % rng.integers(12), int(rng.integers(1, 200))) for _ in range(rng.integers(1, 6))]
            cut = int(rng.integers(0, 2) * rng.integers(1, 120))
            native.converge(k, (ents, cut))
            oracle.converge(k, (ents, cut))
        else:
            if roll == 15:
                both(native, oracle, [b"TRIMAT", k, b"%d" % rng.integers(1, 150)])
            elif roll == 16:
                both(native, oracle, [b"TRIM", k, b"%d" % rng.integers(1, 12)])
            elif roll == 17 and rng.integers(4) == 0:
                both(native, oracle, [b"CLR", k])
            else:
                native.drain()
                oracle.drain()
            drains += 1
            after_drain()
    assert drains > 500
    native.drain()
    oracle.drain()
    after_drain()
    assert native.dump_state() == oracle.dump_state()


def test_tlog_native_value_interner_stays_flat_under_churn():
    """INS/TRIM churn of ever-fresh values must not grow the native
    value table without bound (engine.h TlogTable::compact_values; the
    device-vid interner has the same guard in repo_tlog). Also pins the
    GET-order cache across the remap: a sorted view built BEFORE the
    compaction on a row the churn never touches (gen unchanged) holds
    pre-remap vids — compact_values must drop it, or the post-remap GET
    would render aliased values."""
    repo = RepoTLOG(identity=1)
    eng = repo.engine
    r = R()
    # cold row: build the scan-path sorted cache pre-compaction. The GET
    # between the INSes and the drain makes the merged memo current, so
    # the drain carries the base and the post-drain GET serves natively.
    repo.apply(r, [b"INS", b"cold", b"keepme", b"1"])
    repo.apply(r, [b"INS", b"cold", b"andme", b"2"])
    rc, _, _, _, _ = scan_bytes(eng, bytearray(b"TLOG GET cold\r\n"))
    assert rc == 0
    repo.drain()
    cold_expect = (
        b"*2\r\n*2\r\n$5\r\nandme\r\n:2\r\n*2\r\n$6\r\nkeepme\r\n:1\r\n"
    )
    rc, _, cold_before, _, _ = scan_bytes(eng, bytearray(b"TLOG GET cold\r\n"))
    assert rc == 0 and cold_before == cold_expect
    ts = 0
    keep = 4
    churned = 0
    for g in range(6):
        for k in range(4):
            for i in range(1024):  # distinct value every INS
                ts += 1
                churned += 1
                repo.apply(
                    r, [b"INS", b"log%d" % k, b"g%d-%d-%d" % (g, k, i), b"%d" % ts]
                )
            repo.apply(r, [b"TRIM", b"log%d" % k, b"%d" % keep])
        repo.drain()
    # next interned id == current table size; churn was ~24k distinct
    probe_vid = eng.tlog_intern(b"__probe__")
    assert churned > 20_000
    assert probe_vid < 2 * 8192 + 4 * keep + 64, probe_vid
    # the remap kept the live views exact
    out = R()
    repo.apply(out, [b"GET", b"log0", b"%d" % keep])
    assert out.vals[0] == "array_start" and out.vals[1] == keep
    assert out.vals[5].startswith(b"g5-0-")
    # ... and the cold row's native GET still renders the original
    # values: the pre-remap sorted cache was dropped, not reused
    rc, _, cold_after, _, _ = scan_bytes(eng, bytearray(b"TLOG GET cold\r\n"))
    assert rc == 0 and cold_after == cold_expect


def _oracle_reply(repo, args) -> bytes:
    """Drive a repo command through the real RESP reply writer — the
    byte-exact rendering the Python serving path produces."""
    from jylis_tpu.server.resp import Respond

    buf = bytearray()
    repo.apply(Respond(buf.extend), args)
    return bytes(buf)


def test_scan_apply_tlog_get_and_cutoff_byte_match_oracle():
    """TLOG GET/CUTOFF settled by the native batch applier
    (serve_engine.cpp) must render byte-identically to the Python repo
    through the real Respond writer: merged order (ts desc, value-bytes
    desc on ties), dedup, count semantics (missing / 0 / over-long /
    unparseable-means-all), and unknown keys."""
    native, oracle = _tlog_pair()
    for cmd in (
        [b"INS", b"k", b"bb", b"5"],
        [b"INS", b"k", b"aa", b"5"],  # tie: value-desc order
        [b"INS", b"k", b"zz", b"3"],
        [b"INS", b"k", b"aa", b"9"],
        [b"INS", b"k", b"aa", b"9"],  # exact duplicate: dedup
    ):
        both(native, oracle, cmd)
    gets = (
        [b"GET", b"k"],
        [b"CUTOFF", b"k"],
        [b"GET", b"k", b"2"],
        [b"GET", b"k", b"bogus"],  # unparseable count == all
        [b"GET", b"k", b"0"],
        [b"GET", b"k", b"999"],
        [b"GET", b"missing"],
        [b"CUTOFF", b"missing"],
    )
    burst = b"".join(b"TLOG " + b" ".join(a) + b"\r\n" for a in gets)
    rc, consumed, replies, unhandled, changed = scan_bytes(native.engine, bytearray(burst)
    )
    assert rc == 0 and consumed == len(burst) and unhandled is None
    assert changed == (0, 0, 0, 0, 0, 0)  # reads change nothing
    assert replies == b"".join(_oracle_reply(oracle, a) for a in gets)
    # non-quiescent reads served that: pend was never drained. Now drain
    # (memo is current after the GETs, so the base carries) and re-check
    # the quiescent serving path against the oracle
    native.drain()
    oracle.drain()
    rc, _, replies, _, _ = scan_bytes(native.engine, bytearray(b"TLOG GET k\r\nTLOG CUTOFF k\r\n")
    )
    assert rc == 0
    assert replies == _oracle_reply(oracle, [b"GET", b"k"]) + _oracle_reply(
        oracle, [b"CUTOFF", b"k"]
    )


def test_scan_apply_tlog_get_defers_when_base_unknown():
    """A drain whose folded base fails the length guard leaves the
    drained base unknown (finish_drain_row) — the native GET must bounce
    to Python, whose path pays the one-row device gather; SIZE keeps
    serving natively from the length cache."""
    native = RepoTLOG(identity=1)
    native.converge(b"k", ([(b"v", 7)], 0))
    native.drain()
    # a converge + drain keeps the base: only a failed guard loses it
    assert native._tbl.base_valid(native._tbl.find(b"k"))
    lose_base(native, b"k")
    rc, consumed, replies, unhandled, _ = scan_bytes(native.engine, bytearray(b"TLOG GET k\r\n")
    )
    assert rc == 1 and unhandled == [b"TLOG", b"GET", b"k"]
    assert replies == b""
    rc, _, replies, _, _ = scan_bytes(native.engine, bytearray(b"TLOG SIZE k\r\n")
    )
    assert rc == 0 and replies == b":1\r\n"
    # the Python path (where the server routes the defer) serves it
    assert _oracle_reply(native, [b"GET", b"k"]) == (
        b"*1\r\n*2\r\n$1\r\nv\r\n:7\r\n"
    )
    # and REPAIRS the drained base while at it (ADVICE round 5): the next
    # GET settles natively again instead of deferring forever
    rc, _, replies, unhandled, _ = scan_bytes(native.engine, bytearray(b"TLOG GET k\r\n")
    )
    assert rc == 0 and unhandled is None
    assert replies == b"*1\r\n*2\r\n$1\r\nv\r\n:7\r\n"


def test_scan_apply_tlog_get_big_reply_flushes_then_grows():
    """A GET whose reply outgrows the 64 KB the reply buffer starts
    with: mid-burst the engine flushes what settled first (rc 2), then
    alone it grows the buffer to the reply and serves it, byte for byte
    what the Python path renders — the TREG big-value convention."""
    native, oracle = _tlog_pair()
    both(native, oracle, [b"INS", b"k", b"x" * 70000, b"1"])
    burst = bytearray(b"TLOG SIZE k\r\nTLOG GET k\r\n")
    rc, consumed, replies, unhandled, _ = scan_bytes(native.engine, burst)
    assert rc == 2 and replies == b":1\r\n"
    assert consumed == len(b"TLOG SIZE k\r\n")
    del burst[:consumed]
    rc, consumed, replies, unhandled, _ = scan_bytes(native.engine, burst)
    assert rc == 0 and unhandled is None
    assert replies == _oracle_reply(oracle, [b"GET", b"k"])
    assert consumed == len(b"TLOG GET k\r\n")
    assert len(native.engine._out) == 1 << 17


# ---- UJSON queue + render memo ---------------------------------------------


def test_ujson_queue_flush_order_and_replies():
    eng = make_engine()
    native = RepoUJSON(identity=1, engine=eng)
    oracle = RepoUJSON(identity=1)
    # bank the full write surface through the engine exactly as the
    # server would: INS (escapes, UTF-8 \u, floats included), SET (full
    # JSON documents), RM and CLR
    wire = bytearray(
        b'UJSON INS u roles "admin"\r\n'
        b"UJSON INS u nums 3\r\n"
        b"UJSON INS u nums 1.5\r\n"
        b'UJSON INS u note "a\\nb"\r\n'
        b'UJSON INS u note "caf\\u00e9"\r\n'
        b"UJSON INS u deep er tags true\r\n"
        b'UJSON SET u cfg {"mode":"fast","n":[1,2]}\r\n'
        b'UJSON RM u nums 1.5\r\n'
        b"UJSON CLR u deep\r\n"
    )
    rc, consumed, replies, unhandled, changed = scan_bytes(eng, wire)
    assert rc == 0 and consumed == len(wire)
    assert replies == b"+OK\r\n" * 9
    assert changed == (0, 0, 0, 0, 9, 0)
    assert eng.uq_count() == 9
    for args in (
        [b"INS", b"u", b"roles", b'"admin"'],
        [b"INS", b"u", b"nums", b"3"],
        [b"INS", b"u", b"nums", b"1.5"],
        [b"INS", b"u", b"note", b'"a\\nb"'],
        [b"INS", b"u", b"note", b'"caf\\u00e9"'],
        [b"INS", b"u", b"deep", b"er", b"tags", b"true"],
        [b"SET", b"u", b"cfg", b'{"mode":"fast","n":[1,2]}'],
        [b"RM", b"u", b"nums", b"1.5"],
        [b"CLR", b"u", b"deep"],
    ):
        oracle.apply(R(), args)
    # any read path flushes the queue first
    ra, rb = R(), R()
    native.apply(ra, [b"GET", b"u"])
    oracle.apply(rb, [b"GET", b"u"])
    assert ra.vals == rb.vals
    assert eng.uq_count() == 0
    assert native.flush_deltas() == oracle.flush_deltas()


def _resp_array(parts: list[bytes]) -> bytearray:
    return bytearray(
        b"*%d\r\n" % len(parts)
        + b"".join(b"$%d\r\n%s\r\n" % (len(p), p) for p in parts)
    )


def test_ujson_engine_bounces_unsafe_values():
    """Values whose Python parse can fail must bounce (containers for
    INS/RM, malformed JSON, raw control bytes, leading zeros) — the +OK
    a banked command already shipped could otherwise be a lie. Classes
    that round 5 bounced but Python parses fine (floats, escapes, \\u,
    raw UTF-8, surrounding whitespace) now settle natively."""
    eng = make_engine()
    for bad in (
        b"{}", b"[1]", b"nan", b"", b'"a', b'"a\nb"', b"05", b"1.",
        b"+5", b'"bad\\x"', b"--5", b"1.5.5", b"tru",
    ):
        # RESP array framing: exact tokens (inline would split/eat spaces)
        parts = [b"UJSON", b"INS", b"u", b"p", bad]
        wire = _resp_array(parts)
        rc, _consumed, replies, unhandled, _ch = scan_bytes(eng, wire)
        assert rc == 1 and replies == b"", bad
        assert unhandled[0] == b"UJSON"
    assert eng.uq_count() == 0
    # SET takes containers — but still bounces malformed ones
    good = 0
    for doc, ok in (
        (b"{}", True), (b'{"a":[1,{"b":null}]}', True), (b"[1,2]", True),
        (b'{"a":}', False), (b"[1,", False), (b'{"a" 1}', False),
    ):
        parts = [b"UJSON", b"SET", b"u", b"p", doc]
        wire = _resp_array(parts)
        rc, _c, replies, _u, _ch = scan_bytes(eng, wire)
        if ok:
            good += 1
            assert rc == 0 and replies == b"+OK\r\n", doc
        else:
            assert rc == 1 and replies == b"", doc
    assert eng.uq_count() == good


def test_ujson_engine_bounces_huge_ints_and_bad_utf8_paths():
    """Two +OK-could-be-a-lie edges: an integer token past Python's
    int() digit limit makes json.loads raise at flush time, and a write
    whose path is not valid UTF-8 aliases (via errors='replace') with a
    byte-distinct memoised path — both must defer to Python, which
    renders the help / canonicalises the invalidation."""
    eng = make_engine()
    native = RepoUJSON(identity=1, engine=eng)
    big = b"1" * 5000
    rc, _, replies, unh, _ = scan_bytes(eng, _resp_array([b"UJSON", b"INS", b"u", b"p", big])
    )
    assert rc == 1 and replies == b""  # bounced: the apply would raise
    # both stacks turn the oversized int into ParseError (-> help reply
    # via the manager), not an unhandled crash mid-flush
    from jylis_tpu.models.base import ParseError

    oracle = RepoUJSON(identity=1)
    for repo in (native, oracle):
        with pytest.raises(ParseError):
            repo.apply(R(), [b"INS", b"u", b"p", big])
    # a float with as many digits parses fine (no int() limit): banks
    rc, _, replies, _, _ = scan_bytes(eng, _resp_array([b"UJSON", b"INS", b"u", b"p", b"1." + b"1" * 5000])
    )
    assert rc == 0 and replies == b"+OK\r\n"
    # invalid-UTF-8 path component: b"\xff" decodes to U+FFFD, the SAME
    # doc path as the valid encoding b"\xef\xbf\xbd" — the engine must
    # not bank it (its raw-byte invalidation would miss the memo key)
    native.apply(R(), [b"INS", b"u2", b"\xef\xbf\xbd", b"1"])
    before = _oracle_reply(native, [b"GET", b"u2", b"\xef\xbf\xbd"])
    rc, _, replies, unh, _ = scan_bytes(eng, _resp_array([b"UJSON", b"INS", b"u2", b"\xff", b"2"])
    )
    assert rc == 1 and replies == b""  # bank refused: path not UTF-8
    native.apply(R(), unh[1:])  # the deferred apply canonicalises
    after = _oracle_reply(native, [b"GET", b"u2", b"\xef\xbf\xbd"])
    assert after != before
    rc, _, replies, _, _ = scan_bytes(eng, bytearray(b"UJSON GET u2 \xef\xbf\xbd\r\n")
    )
    assert rc == 0 and replies == after  # fresh render, not a stale memo


def test_ujson_native_get_serves_memo_and_invalidates_precisely():
    """UJSON GET settles natively from the render memo the Python GET
    installed, byte-identically; a write invalidates exactly the
    overlapping paths (INS/RM by prefix, SET/CLR by subtree), so reads
    of disjoint subtrees keep settling across writes."""
    eng = make_engine()
    native = RepoUJSON(identity=1, engine=eng)
    for args in (
        [b"INS", b"u", b"profile", b'"p1"'],
        [b"INS", b"u", b"tags", b"1"],
    ):
        native.apply(R(), args)
    # never rendered: the native GET defers
    rc, _, replies, unhandled, _ = scan_bytes(eng, bytearray(b"UJSON GET u profile\r\n"))
    assert rc == 1 and unhandled == [b"UJSON", b"GET", b"u", b"profile"]
    # Python renders (and repairs the memo)...
    want = _oracle_reply(native, [b"GET", b"u", b"profile"])
    want_root = _oracle_reply(native, [b"GET", b"u"])
    # ...and the same GETs now settle natively on those exact bytes
    rc, _, replies, _, _ = scan_bytes(eng, bytearray(b"UJSON GET u profile\r\nUJSON GET u\r\n")
    )
    assert rc == 0 and replies == want + want_root
    served = eng.served_counts()["UJSON"]
    # a write at a DISJOINT path keeps the profile memo (still native)
    # but drops the root render (() is a prefix of every write path)
    rc, _, replies, unhandled, _ = scan_bytes(eng, bytearray(b"UJSON INS u tags 2\r\nUJSON GET u profile\r\n")
    )
    assert rc == 0 and replies == b"+OK\r\n" + want
    rc, _, _, unhandled, _ = scan_bytes(eng, bytearray(b"UJSON GET u\r\n"))
    assert rc == 1 and unhandled == [b"UJSON", b"GET", b"u"]
    # a write AT the memoised path invalidates it
    rc, _, replies, unhandled, _ = scan_bytes(eng, bytearray(b'UJSON RM u profile "p1"\r\nUJSON GET u profile\r\n')
    )
    assert rc == 1 and replies == b"+OK\r\n"
    assert unhandled == [b"UJSON", b"GET", b"u", b"profile"]
    # the Python path re-serves it correctly (queue flushed first: the
    # banked INS+RM are visible) and repairs the memo again
    after = _oracle_reply(native, [b"GET", b"u", b"profile"])
    assert after == b"$0\r\n\r\n"  # p1 removed
    rc, _, replies, _, _ = scan_bytes(eng, bytearray(b"UJSON GET u profile\r\n"))
    assert rc == 0 and replies == after
    assert eng.served_counts()["UJSON"] > served
    # absent keys defer and NEVER memoise (a read-only scan over
    # missing keys must not grow engine rows without bound)
    rc, _, _, unhandled, _ = scan_bytes(eng, bytearray(b"UJSON GET nope\r\n"))
    assert rc == 1 and unhandled == [b"UJSON", b"GET", b"nope"]
    assert _oracle_reply(native, [b"GET", b"nope"]) == b"$0\r\n\r\n"
    rc, _, _, unhandled, _ = scan_bytes(eng, bytearray(b"UJSON GET nope\r\n"))
    assert rc == 1 and unhandled == [b"UJSON", b"GET", b"nope"]
    assert eng.uj_memo_len(b"nope") == 0


def test_ujson_memo_invalidated_by_cluster_converge():
    """A remote delta can change any subtree: converge drops every
    render memo for the key, and the next GET re-renders through Python
    (the TLOG base-repair shape)."""
    from jylis_tpu.ops.ujson_host import UJSON

    eng = make_engine()
    native = RepoUJSON(identity=1, engine=eng)
    native.apply(R(), [b"INS", b"u", b"tags", b"1"])
    before = _oracle_reply(native, [b"GET", b"u", b"tags"])
    rc, _, replies, _, _ = scan_bytes(eng, bytearray(b"UJSON GET u tags\r\n"))
    assert rc == 0 and replies == before
    remote = UJSON()
    d = UJSON()
    remote.ins(7, ("tags",), "2", delta=d)
    native.converge(b"u", d)
    rc, _, _, unhandled, _ = scan_bytes(eng, bytearray(b"UJSON GET u tags\r\n"))
    assert rc == 1 and unhandled == [b"UJSON", b"GET", b"u", b"tags"]
    after = _oracle_reply(native, [b"GET", b"u", b"tags"])
    assert after != before
    rc, _, replies, _, _ = scan_bytes(eng, bytearray(b"UJSON GET u tags\r\n"))
    assert rc == 0 and replies == after


def _native_serve(native, eng, args) -> bytes:
    """Apply one UJSON command exactly as the server would: settle it in
    scan_apply when the engine can, route the deferred command through
    the repo (which repairs the memo) otherwise. Returns reply bytes."""
    parts = [b"UJSON", *args]
    wire = _resp_array(parts)
    rc, consumed, replies, unhandled, _ = scan_bytes(eng, wire)
    assert consumed == len(wire)
    if rc == 1:
        return replies + _oracle_reply(native, unhandled[1:])
    assert rc == 0
    return replies


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_ujson_scan_apply_differential_random_workload(seed):
    """Randomized socket-shaped differential over the full UJSON command
    surface: every command runs through the native engine (settle or
    defer-and-repair, exactly the server's loop) on one side and the
    pure-Python repo on the other — reply BYTES, flushed deltas and
    snapshots must all match, with escape/UTF-8/float INS values, SET
    documents, RM, CLR and cluster converge in the mix."""
    from jylis_tpu.ops.ujson_host import UJSON

    rng = np.random.default_rng(seed)
    eng = make_engine()
    native = RepoUJSON(identity=3, engine=eng)
    oracle = RepoUJSON(identity=3)
    keys = [b"u%d" % i for i in range(4)]
    paths = ([], [b"tags"], [b"deep", b"er"], [b"meta"])
    values = [
        b"3", b"-17", b"1.5", b"1e10", b'"a\\nb"', b'"caf\\u00e9"',
        b'"\xc3\xa9"', b"true", b"null", b'"plain"', b"0.25",
    ]
    docs = values + [b'{"a":1,"b":[1,2]}', b"[1,2]", b"{}"]
    for step in range(300):
        k = keys[rng.integers(len(keys))]
        path = list(paths[rng.integers(len(paths))])
        roll = rng.integers(10)
        if roll < 3:
            cmd = [b"INS", k, *path, values[rng.integers(len(values))]]
        elif roll < 5:
            cmd = [b"GET", k, *path]
        elif roll == 5:
            cmd = [b"SET", k, *path, docs[rng.integers(len(docs))]]
        elif roll == 6:
            cmd = [b"RM", k, *path, values[rng.integers(len(values))]]
        elif roll == 7:
            cmd = [b"CLR", k, *path]
        elif roll == 8:
            # cluster converge of the same remote delta into both
            remote = UJSON()
            d = UJSON()
            remote.ins(9, ("tags",), str(rng.integers(5)), delta=d)
            native.converge(k, d)
            oracle.converge(k, d)
            continue
        else:
            # banked writes ship their deltas after prepare_flush (the
            # manager's threaded flush hook) — then both sides agree
            native.prepare_flush()
            assert native.deltas_size() == oracle.deltas_size()
            assert native.flush_deltas() == oracle.flush_deltas(), step
            continue
        assert _native_serve(native, eng, cmd) == _oracle_reply(
            oracle, cmd
        ), (step, cmd)
    for k in keys:
        for path in paths:
            cmd = [b"GET", k, *path]
            assert _native_serve(native, eng, cmd) == _oracle_reply(oracle, cmd)
    assert native.dump_state() == oracle.dump_state()


# ---- server-level all-types differential -----------------------------------


async def _send_recv_all(port: int, payload: bytes) -> bytes:
    reader, writer = await asyncio.open_connection("127.0.0.1", port)
    writer.write(payload)
    await writer.drain()
    out = b""
    while True:
        try:
            chunk = await asyncio.wait_for(reader.read(1 << 16), timeout=0.6)
        except asyncio.TimeoutError:
            break
        if not chunk:
            break
        out += chunk
    writer.close()
    return out


@pytest.mark.parametrize("seed", [0, 1])
def test_server_all_types_stream_differential(seed):
    """Randomized socket-level fuzz over ALL five types: the same stream
    (writes, reads, trims, parse errors, split packets) must produce
    byte-identical replies on the native and pure-Python servers."""
    rng = np.random.default_rng(seed)
    keys = [b"k%d" % i for i in range(4)]
    cmds = []
    for _ in range(400):
        k = keys[rng.integers(len(keys))]
        roll = rng.integers(21)
        if roll < 2:
            cmds.append(b"GCOUNT INC %s %d" % (k, rng.integers(0, 1000)))
        elif roll < 4:
            op = b"INC" if rng.integers(2) else b"DEC"
            cmds.append(b"PNCOUNT %s %s %d" % (op, k, rng.integers(0, 1000)))
        elif roll < 5:
            cmds.append(b"GCOUNT GET %s" % k)
        elif roll < 6:
            cmds.append(b"PNCOUNT GET %s" % k)
        elif roll < 8:
            cmds.append(
                b"TREG SET %s val%d %d" % (k, rng.integers(9), rng.integers(1, 99))
            )
        elif roll < 10:
            cmds.append(b"TREG GET %s" % k)
        elif roll < 12:
            cmds.append(
                b"TLOG INS %s x%d %d" % (k, rng.integers(6), rng.integers(1, 50))
            )
        elif roll < 14:
            cmds.append(b"TLOG SIZE %s" % k)
        elif roll == 14:
            sub = rng.integers(4)
            if sub == 0:
                cmds.append(b"TLOG GET %s %d" % (k, rng.integers(1, 8)))
            elif sub == 1:
                cmds.append(b"TLOG GET %s" % k)  # count omitted == all
            elif sub == 2:
                cmds.append(b"TLOG GET %s zz" % k)  # unparseable == all
            else:
                cmds.append(b"TLOG CUTOFF %s" % k)
        elif roll == 15:
            sub = rng.integers(4)
            if sub == 0:
                cmds.append(b"TLOG CLR %s" % k)
            elif sub == 1:
                cmds.append(b"TLOG TRIMAT %s %d" % (k, rng.integers(1, 50)))
            else:
                cmds.append(b"TLOG TRIM %s %d" % (k, rng.integers(0, 5)))
        elif roll == 16:
            vals = (
                b"%d" % rng.integers(20), b"1.5", b"-0.25", b"1e3",
                b'"a\\nb"', b'"caf\\u00e9"', b'"\xc3\xa9"', b"true",
            )
            cmds.append(
                b"UJSON INS %s tags %s" % (k, vals[rng.integers(len(vals))])
            )
        elif roll == 17:
            paths = (b"", b" tags", b" meta", b" deep er")
            cmds.append(
                b"UJSON GET %s%s" % (k, paths[rng.integers(len(paths))])
            )
        elif roll == 18:
            docs = (b"7", b'"x"', b'{"a":1,"b":[1,2]}', b"[3,4]")
            cmds.append(
                b"UJSON SET %s meta %s" % (k, docs[rng.integers(len(docs))])
            )
        elif roll == 19:
            cmds.append(b"UJSON RM %s tags %d" % (k, rng.integers(20)))
        else:
            cmds.append(b"UJSON CLR %s deep" % k)
    wire = b"".join(c + b"\r\n" for c in cmds)
    cuts = sorted(rng.integers(1, len(wire), size=10).tolist())
    packets = [wire[a:b] for a, b in zip([0] + cuts, cuts + [len(wire)])]

    async def run_one(force_python: bool) -> bytes:
        from jylis_tpu.models.database import Database
        from jylis_tpu.server.server import Server
        from jylis_tpu.utils.config import Config
        from jylis_tpu.utils.log import Log

        cfg = Config()
        cfg.port = "0"
        cfg.log = Log.create_none()
        db = Database(identity=1, engine="python" if force_python else "auto")
        server = Server(cfg, db)
        await server.start()
        try:
            reader, writer = await asyncio.open_connection("127.0.0.1", server.port)
            out = b""
            for p in packets:
                writer.write(p)
                await writer.drain()
                try:
                    out += await asyncio.wait_for(reader.read(1 << 20), 0.05)
                except asyncio.TimeoutError:
                    pass
            while True:
                try:
                    chunk = await asyncio.wait_for(reader.read(1 << 20), 0.5)
                except asyncio.TimeoutError:
                    break
                if not chunk:
                    break
                out += chunk
            writer.close()
            return out
        finally:
            await server.dispose()

    a = asyncio.run(run_one(False))
    b = asyncio.run(run_one(True))
    assert a == b


def test_server_demote_then_recover_ordering_and_counters():
    """A >max-args command demotes its connection off the native engine
    mid-burst (server/server.py demote()): replies before, at and after
    the demotion point must stay in order and byte-match the pure-Python
    server; a FRESH connection settles natively again; and the SERVING
    metrics lines expose the native/demoted split plus the demotion
    event."""
    demoter = b"GCOUNT GET k " + b" ".join([b"x"] * 1100)
    cmds = (
        [b"GCOUNT INC k 5", b"GCOUNT GET k", b"TREG SET t v 3", b"TREG GET t"]
        + [demoter]
        + [b"GCOUNT INC k 2", b"GCOUNT GET k", b"TLOG INS l x 1",
           b"TLOG GET l", b"UJSON INS u tags 1", b"UJSON GET u tags"]
    )
    wire = b"".join(c + b"\r\n" for c in cmds)

    async def run_one(force_python: bool):
        from jylis_tpu.models.database import Database
        from jylis_tpu.server.server import Server
        from jylis_tpu.utils.config import Config
        from jylis_tpu.utils.log import Log

        cfg = Config()
        cfg.port = "0"
        cfg.log = Log.create_none()
        db = Database(identity=1, engine="python" if force_python else "auto")
        server = Server(cfg, db)
        await server.start()
        try:
            out = await _send_recv_all(server.port, wire)
            # a fresh connection is un-demoted: the engine serves it
            out2 = await _send_recv_all(server.port, b"GCOUNT GET k\r\n")
            metrics = await _send_recv_all(server.port, b"SYSTEM METRICS\r\n")
            return out, out2, metrics, db.serving_totals()
        finally:
            await server.dispose()

    na, na2, nm, totals = asyncio.run(run_one(False))
    pa, pa2, _pm, _pt = asyncio.run(run_one(True))
    assert na == pa  # in-order, byte-identical across the demotion point
    assert na2 == pa2 == b":7\r\n"
    # the fresh connection settled natively (GCOUNT count grew), the
    # demoted tail counted as Python-path commands, and the demotion
    # event itself is visible
    assert totals["native_cmds"] >= 5
    assert totals["demoted_cmds"] >= 6
    assert totals["demotions"] >= 1
    assert b"SERVING native_cmds" in nm and b"SERVING fallback_frac" in nm


def assert_size(repo, expect: int) -> None:
    r = R()
    repo.apply(r, [b"SIZE", b"k"])
    assert r.vals == ["u64", expect]
