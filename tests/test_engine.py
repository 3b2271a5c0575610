"""Engine-slice tests: command surface -> reply bytes, through the Respond
seam (no sockets), exactly how the reference tests drive Database.apply with
a fake Respond (test/test_cluster.pony:6-41, SURVEY.md section 4).

Covers every repo's command surface (which the reference's own tests do
NOT — SURVEY.md section 4 "what is not tested"), the help/error texts, the
proactive-flush throttle, and two-node delta convergence through
flush_deltas -> converge_deltas.
"""

import numpy as np
import pytest

import jylis_tpu  # noqa: F401
from jylis_tpu.models import Database
from jylis_tpu.server.resp import Respond

from procutil import scan_bytes


class Out:
    """Byte-collecting Respond sink (the reference's _ExpectRespond seam)."""

    def __init__(self):
        self.buf = bytearray()

    def sink(self, data: bytes):
        self.buf += data

    def take(self) -> bytes:
        out = bytes(self.buf)
        self.buf.clear()
        return out


@pytest.fixture()
def db():
    return Database(identity=1)


def run(db, *words) -> bytes:
    out = Out()
    db.apply(Respond(out.sink), [w.encode() if isinstance(w, str) else w for w in words])
    return out.take()


# -- GCOUNT ----------------------------------------------------------------


def test_gcount_inc_get(db):
    assert run(db, "GCOUNT", "GET", "k") == b":0\r\n"
    assert run(db, "GCOUNT", "INC", "k", "10") == b"+OK\r\n"
    assert run(db, "GCOUNT", "GET", "k") == b":10\r\n"
    assert run(db, "GCOUNT", "INC", "k", "15") == b"+OK\r\n"
    assert run(db, "GCOUNT", "GET", "k") == b":25\r\n"


def test_gcount_bad_value_gets_help(db):
    got = run(db, "GCOUNT", "INC", "k", "abc")
    assert got.startswith(b"-BADCOMMAND (could not parse command)\n")
    assert b"GCOUNT INC key value" in got


# -- PNCOUNT ---------------------------------------------------------------


def test_pncount_inc_dec(db):
    assert run(db, "PNCOUNT", "GET", "k") == b":0\r\n"
    run(db, "PNCOUNT", "INC", "k", "10")
    run(db, "PNCOUNT", "DEC", "k", "15")
    assert run(db, "PNCOUNT", "GET", "k") == b":-5\r\n"


# -- TREG ------------------------------------------------------------------


def test_treg_set_get(db):
    assert run(db, "TREG", "GET", "mykey") == b"$-1\r\n"
    assert run(db, "TREG", "SET", "mykey", "hello", "10") == b"+OK\r\n"
    assert run(db, "TREG", "GET", "mykey") == b"*2\r\n$5\r\nhello\r\n:10\r\n"
    run(db, "TREG", "SET", "mykey", "world", "15")
    assert run(db, "TREG", "GET", "mykey") == b"*2\r\n$5\r\nworld\r\n:15\r\n"
    run(db, "TREG", "SET", "mykey", "outdated", "5")
    assert run(db, "TREG", "GET", "mykey") == b"*2\r\n$5\r\nworld\r\n:15\r\n"


# -- TLOG ------------------------------------------------------------------


def test_tlog_surface(db):
    assert run(db, "TLOG", "GET", "chat") == b"*0\r\n"
    run(db, "TLOG", "INS", "chat", "one", "100")
    run(db, "TLOG", "INS", "chat", "two", "200")
    run(db, "TLOG", "INS", "chat", "three", "150")
    assert run(db, "TLOG", "SIZE", "chat") == b":3\r\n"
    got = run(db, "TLOG", "GET", "chat")
    assert got == (
        b"*3\r\n"
        b"*2\r\n$3\r\ntwo\r\n:200\r\n"
        b"*2\r\n$5\r\nthree\r\n:150\r\n"
        b"*2\r\n$3\r\none\r\n:100\r\n"
    )
    assert run(db, "TLOG", "GET", "chat", "1") == b"*1\r\n*2\r\n$3\r\ntwo\r\n:200\r\n"
    # unparseable count means "all" (reference quirk, repo_tlog.pony:49-50)
    assert run(db, "TLOG", "GET", "chat", "zzz").startswith(b"*3\r\n")
    run(db, "TLOG", "TRIM", "chat", "2")
    assert run(db, "TLOG", "SIZE", "chat") == b":2\r\n"
    assert run(db, "TLOG", "CUTOFF", "chat") == b":150\r\n"
    run(db, "TLOG", "TRIMAT", "chat", "200")
    assert run(db, "TLOG", "SIZE", "chat") == b":1\r\n"
    run(db, "TLOG", "CLR", "chat")
    assert run(db, "TLOG", "SIZE", "chat") == b":0\r\n"
    assert run(db, "TLOG", "CUTOFF", "chat") == b":201\r\n"
    # re-inserting below cutoff is silently ignored
    assert run(db, "TLOG", "INS", "chat", "old", "100") == b"+OK\r\n"
    assert run(db, "TLOG", "SIZE", "chat") == b":0\r\n"


def test_treg_reads_never_touch_device(db, monkeypatch):
    """TREG GET computes the LWW winner from the host cache + pending
    coalesce — ZERO device calls even right after writes and converges,
    and the answer matches the post-drain truth."""
    from jylis_tpu.models import repo_treg

    run(db, "TREG", "SET", "m", "alpha", "5")
    repo = db.manager("TREG").repo
    repo.converge(b"m", (b"zeta", 5))  # ts tie: larger value wins

    calls = {"n": 0}
    for name in ("_drain", "_drain_dense", "_patch_vids"):
        monkeypatch.setattr(
            repo_treg, name,
            lambda *a, **k: calls.__setitem__("n", calls["n"] + 1),
        )
    monkeypatch.setattr(
        type(repo), "_drain_sharded",
        lambda *a: calls.__setitem__("n", calls["n"] + 1),
    )
    assert run(db, "TREG", "GET", "m") == b"*2\r\n$4\r\nzeta\r\n:5\r\n"
    assert run(db, "TREG", "GET", "nope") == b"$-1\r\n"
    assert calls["n"] == 0
    monkeypatch.undo()
    repo.drain()  # post-drain truth agrees with the host compare
    assert run(db, "TREG", "GET", "m") == b"*2\r\n$4\r\nzeta\r\n:5\r\n"


def test_tlog_reads_never_drain(db, monkeypatch):
    """GET/SIZE/CUTOFF with pending entries serve the exact merged view
    host-side — no drain dispatch; answers equal the post-drain truth
    (union + dedup + cutoff filter, tlog.md:116-133)."""
    from jylis_tpu.models import repo_tlog

    run(db, "TLOG", "INS", "m", "base", "10")
    run(db, "TLOG", "GET", "m")  # drain + render cache for the base
    repo = db.manager("TLOG").repo
    run(db, "TLOG", "INS", "m", "new", "20")
    repo.converge(b"m", ([(b"base", 10), (b"old", 1)], 5))  # dup + cutoff 5

    calls = {"n": 0}
    monkeypatch.setattr(
        repo_tlog, "_drain_tlog",
        lambda *a: calls.__setitem__("n", calls["n"] + 1),
    )
    monkeypatch.setattr(
        type(repo), "_drain_sharded",
        lambda *a: calls.__setitem__("n", calls["n"] + 1),
    )
    want = (
        b"*2\r\n*2\r\n$3\r\nnew\r\n:20\r\n*2\r\n$4\r\nbase\r\n:10\r\n"
    )
    assert run(db, "TLOG", "GET", "m") == want  # deduped, desc
    assert run(db, "TLOG", "SIZE", "m") == b":2\r\n"
    assert run(db, "TLOG", "CUTOFF", "m") == b":5\r\n"
    assert calls["n"] == 0
    monkeypatch.undo()
    repo.drain()  # the device agrees with the host merge
    assert run(db, "TLOG", "GET", "m") == want
    assert run(db, "TLOG", "SIZE", "m") == b":2\r\n"
    assert run(db, "TLOG", "CUTOFF", "m") == b":5\r\n"


def test_tlog_quiescent_reads_skip_device(db, monkeypatch):
    """After a drain, repeated GET/SIZE/CUTOFF perform ZERO device calls:
    GET serves from the rendered row cache, SIZE/CUTOFF from the host
    length/cutoff caches (VERDICT r01 weak #3 — the counter repos' host
    shadow pattern applied to TLOG)."""
    from jylis_tpu.models import repo_tlog

    run(db, "TLOG", "INS", "chat", "one", "100")
    run(db, "TLOG", "INS", "chat", "two", "200")
    first = run(db, "TLOG", "GET", "chat")  # drains + builds render cache

    calls = {"get_row": 0, "drain": 0}
    monkeypatch.setattr(
        repo_tlog,
        "_get_row_tlog",
        lambda *a: calls.__setitem__("get_row", calls["get_row"] + 1),
    )
    monkeypatch.setattr(
        repo_tlog,
        "_drain_tlog",
        lambda *a: calls.__setitem__("drain", calls["drain"] + 1),
    )
    for _ in range(3):
        assert run(db, "TLOG", "GET", "chat") == first
        assert run(db, "TLOG", "SIZE", "chat") == b":2\r\n"
        assert run(db, "TLOG", "CUTOFF", "chat") == b":0\r\n"
        assert run(db, "TLOG", "GET", "missing") == b"*0\r\n"
    assert calls == {"get_row": 0, "drain": 0}


def test_tlog_render_cache_invalidated_by_merge(db):
    """A foreign delta (or local INS) touching the row must be visible on
    the next GET — the cache drops exactly the merged rows."""
    run(db, "TLOG", "INS", "chat", "one", "100")
    assert run(db, "TLOG", "GET", "chat") == b"*1\r\n*2\r\n$3\r\none\r\n:100\r\n"
    mgr = db.manager("TLOG")
    mgr.repo.converge(b"chat", ([(b"two", 200)], 0))
    assert run(db, "TLOG", "GET", "chat") == (
        b"*2\r\n*2\r\n$3\r\ntwo\r\n:200\r\n*2\r\n$3\r\none\r\n:100\r\n"
    )
    # trim also invalidates
    run(db, "TLOG", "TRIM", "chat", "1")
    assert run(db, "TLOG", "GET", "chat") == b"*1\r\n*2\r\n$3\r\ntwo\r\n:200\r\n"


def test_dense_drain_equivalence():
    """A small-capacity repo (batch covers >=1/4 of the keyspace -> dense
    elementwise drain) must serve identical values to a large-capacity one
    (sparse scatter drain) on the same operations."""
    from jylis_tpu.models.repo_counters import RepoGCOUNT, RepoPNCOUNT
    from jylis_tpu.models.repo_treg import RepoTREG

    rng = np.random.default_rng(3)
    keys = [b"k%d" % i for i in range(12)]
    decs = {k: int(rng.integers(1, 4)) for k in keys}

    for cls in (RepoGCOUNT, RepoPNCOUNT):
        small = cls(identity=1, key_cap=16, rep_cap=4)  # dense path
        big = cls(identity=1, key_cap=4096, rep_cap=4)  # sparse path
        for repo in (small, big):
            for k in keys:
                repo.converge(
                    k, {7: 5} if cls is RepoGCOUNT else ({7: 5}, {9: decs[k]})
                )
            repo.drain()
        for k in keys:
            assert small._get_value(k) == big._get_value(k), (cls.__name__, k)

    small = RepoTREG(identity=1, key_cap=16)
    big = RepoTREG(identity=1, key_cap=4096)
    shared = b"longsharedprefix-"  # >8 bytes: rank collision -> device tie
    for repo in (small, big):
        for i, k in enumerate(keys):
            repo.converge(k, (b"v%d" % i, 10 + i))
            # drain between the colliding writes so the tie reaches the
            # device (one-drain writes coalesce host-side first)
            repo.converge(k, (shared + (b"aaa" if i % 2 else b"zzz"), 100))
        repo.drain()
        for i, k in enumerate(keys):
            repo.converge(k, (shared + (b"zzz" if i % 2 else b"aaa"), 100))
        repo.drain()  # tie rows resolve on host: zzz must win either order
    sdump, bdump = dict(small.dump_state()), dict(big.dump_state())
    for i, k in enumerate(keys):
        srow, brow = small._tbl.find(k), big._tbl.find(k)
        assert int(small._state.ts_lo[srow]) == int(big._state.ts_lo[brow]) == 100
        # the mirror's id is the row's generation: it moved where the tie's
        # winner came second (odd rows), and stayed where it came first
        assert int(small._state.vid[srow]) == int(big._state.vid[brow]) == i % 2
        assert sdump[k] == bdump[k] == (shared + b"zzz", 100)
        for repo in (small, big):
            out = Out()
            repo.apply(Respond(out.sink), [b"GET", k])
            assert out.take() == b"*2\r\n$20\r\n%s\r\n:100\r\n" % (shared + b"zzz")


# -- UJSON -----------------------------------------------------------------


def test_ujson_surface(db):
    assert run(db, "UJSON", "GET", "u") == b"$0\r\n\r\n"
    run(db, "UJSON", "SET", "u", '{"a":1,"b":{"c":true}}')
    assert run(db, "UJSON", "GET", "u", "a") == b"$1\r\n1\r\n"
    assert run(db, "UJSON", "GET", "u", "b") == b'$10\r\n{"c":true}\r\n'
    run(db, "UJSON", "INS", "u", "roles", '"admin"')
    run(db, "UJSON", "INS", "u", "roles", '"user"')
    assert run(db, "UJSON", "GET", "u", "roles") == b'$16\r\n["admin","user"]\r\n'
    run(db, "UJSON", "RM", "u", "roles", '"admin"')
    assert run(db, "UJSON", "GET", "u", "roles") == b'$6\r\n"user"\r\n'
    run(db, "UJSON", "CLR", "u", "b")
    assert run(db, "UJSON", "GET", "u", "b") == b"$0\r\n\r\n"
    # invalid JSON -> help
    got = run(db, "UJSON", "SET", "u", "{not json")
    assert got.startswith(b"-BADCOMMAND")


# -- SYSTEM ----------------------------------------------------------------


def test_system_getlog(db):
    db.system.inslog("node started")
    db.system.inslog("something happened")
    got = run(db, "SYSTEM", "GETLOG")
    assert got.startswith(b"*2\r\n")
    assert b"something happened" in got
    got1 = run(db, "SYSTEM", "GETLOG", "1")
    assert got1.startswith(b"*1\r\n")


# -- routing / help --------------------------------------------------------


def test_unknown_type_lists_datatypes(db):
    got = run(db, "NOPE", "GET", "k")
    assert got.startswith(b"-BADCOMMAND (could not parse command)\n")
    for t in (b"TREG", b"TLOG", b"GCOUNT", b"PNCOUNT", b"UJSON", b"SYSTEM"):
        assert t in got


def test_unknown_op_lists_type_ops(db):
    got = run(db, "TREG", "FROB", "k")
    assert b"The following are valid operations for this data type:" in got
    assert b"TREG GET key" in got
    assert b"TREG SET key value timestamp" in got


def test_known_op_bad_args_shows_usage(db):
    got = run(db, "TREG", "SET", "k")
    assert b"This operation expects the arguments in the following form:" in got
    assert b"TREG SET key value timestamp" in got


# -- delta flow ------------------------------------------------------------


def collect_flush(db):
    batches = []
    db.flush_deltas(lambda named: batches.append(named))
    return batches


def test_two_node_convergence_all_types(db):
    """Node A mutates every type; its flushed deltas converge node B to the
    same observable state (the reference's TestCluster assertion, minus the
    wire — that arrives with the cluster layer)."""
    a = db
    b = Database(identity=2)

    run(a, "GCOUNT", "INC", "k", "7")
    run(a, "PNCOUNT", "INC", "k", "10")
    run(a, "PNCOUNT", "DEC", "k", "4")
    run(a, "TREG", "SET", "r", "v1", "9")
    run(a, "TLOG", "INS", "l", "entry", "50")
    run(a, "UJSON", "SET", "u", '{"x":[1,2]}')
    a.system.inslog("hello from a")

    for named in collect_flush(a):
        b.converge_deltas(named)

    assert run(b, "GCOUNT", "GET", "k") == b":7\r\n"
    assert run(b, "PNCOUNT", "GET", "k") == b":6\r\n"
    assert run(b, "TREG", "GET", "r") == b"*2\r\n$2\r\nv1\r\n:9\r\n"
    assert run(b, "TLOG", "GET", "l") == b"*1\r\n*2\r\n$5\r\nentry\r\n:50\r\n"
    assert run(b, "UJSON", "GET", "u") == b'$11\r\n{"x":[1,2]}\r\n'
    assert b"hello from a" in run(b, "SYSTEM", "GETLOG")

    # cross-write: both nodes INC, both converge, both read the same total
    run(b, "GCOUNT", "INC", "k", "3")
    for named in collect_flush(b):
        a.converge_deltas(named)
    assert run(a, "GCOUNT", "GET", "k") == b":10\r\n"


def test_proactive_flush_throttle():
    clock = [100.0]
    db = Database(identity=1)
    mgr = db.manager("GCOUNT")
    mgr._clock = lambda: clock[0]
    sent = []
    db.flush_deltas(lambda named: sent.append(named))
    sent.clear()

    run(db, "GCOUNT", "INC", "k", "1")  # first mutation flushes immediately
    assert len(sent) == 1
    run(db, "GCOUNT", "INC", "k", "1")  # throttled
    assert len(sent) == 1
    clock[0] += 0.6
    run(db, "GCOUNT", "INC", "k", "1")  # past the window: flushes again
    assert len(sent) == 2


def test_shutdown_rejects_commands(db):
    db.clean_shutdown()
    got = run(db, "GCOUNT", "GET", "k")
    assert got.startswith(b"-SHUTDOWN")


def test_many_keys_growth(db):
    """Push past the initial key capacity to exercise state growth."""
    for i in range(100):
        run(db, "GCOUNT", "INC", "key%d" % i, str(i + 1))
    assert run(db, "GCOUNT", "GET", "key99") == b":100\r\n"
    vals = [run(db, "GCOUNT", "GET", "key%d" % i) for i in range(0, 100, 17)]
    assert vals == [b":%d\r\n" % (i + 1) for i in range(0, 100, 17)]


def test_counter_gets_skip_device_when_local_only(db):
    """Read-your-writes host shadow: GETs after purely-local INC/DEC are
    served from the exact host value cache with NO device drain; a foreign
    delta makes exactly the next GET drain."""
    counters = db.metrics.counters  # the per-Database registry's view
    counters.pop("GCOUNT", None)
    for i in range(5):
        run(db, "GCOUNT", "INC", "k", "3")
        assert run(db, "GCOUNT", "GET", "k") == b":%d\r\n" % (3 * (i + 1))
    assert counters["GCOUNT"]["batches"] == 0  # no drains

    mgr = db.manager("GCOUNT")
    mgr.repo.converge(b"k", {999: 100})
    assert run(db, "GCOUNT", "GET", "k") == b":115\r\n"
    assert counters["GCOUNT"]["batches"] == 1  # exactly one drain

    # and PNCOUNT wraps its eager adjust into the signed read domain
    run(db, "PNCOUNT", "DEC", "pk", "5")
    assert run(db, "PNCOUNT", "GET", "pk") == b":-5\r\n"
    # a DEC past the i64 boundary must wrap exactly like the device's
    # modular bitcast read: -(2^63+5) -> 2^63-5
    run(db, "PNCOUNT", "DEC", "pk2", str(2**63 + 5))
    want = b":%d\r\n" % (2**63 - 5)
    assert run(db, "PNCOUNT", "GET", "pk2") == want  # eager host path
    db.manager("PNCOUNT").repo.converge(b"pk2", ({}, {}))  # force a drain
    assert run(db, "PNCOUNT", "GET", "pk2") == want  # device path agrees


def test_system_metrics_command(db):
    """SYSTEM METRICS (extension): live per-type drain counters over
    RESP — drains become visible without waiting for the shutdown
    report."""
    before = int(db.metrics.counters["TLOG"]["batches"])
    run(db, "TLOG", "INS", "m:met", "x", "5")
    db.manager("TLOG").repo.drain()
    out = run(db, "SYSTEM", "METRICS")
    assert out.startswith(b"*")
    assert b"TLOG drains" in out
    # the counter moved past its pre-test value
    lines = [l for l in out.split(b"\r\n") if l.startswith(b"TLOG drains")]
    assert lines, out
    # parse "TLOG drains N" from the bulk payload
    n = int(lines[0].rsplit(b" ", 1)[1])
    assert n >= before + 1
    # unknown op still errors with the (extended) help table
    err = run(db, "SYSTEM", "NOPE")
    assert err.startswith(b"-BADCOMMAND") and b"METRICS" in err


def _metric_value(out: bytes, prefix: bytes) -> int:
    lines = [l for l in out.split(b"\r\n") if l.startswith(prefix)]
    assert lines, out
    return int(lines[0].rsplit(b" ", 1)[1])


def test_system_metrics_counts_served_commands(db):
    """METRICS "cmds" lines (extension): commands served per type,
    counted on BOTH serving paths — Python dispatch (manager._apply_core
    -> the per-Database tally) and the native batch applier
    (Engine::served, merged in via RepoSYSTEM.served_fn)."""
    run(db, "GCOUNT", "INC", "m:srv", "1")
    run(db, "GCOUNT", "GET", "m:srv")
    total = _metric_value(run(db, "SYSTEM", "METRICS"), b"GCOUNT cmds")
    assert total == 2  # per-instance tally: exactly this test's commands
    eng = db.native_engine
    if eng is not None:
        rc, _, replies, _, _ = scan_bytes(eng, bytearray(b"GCOUNT INC m:srv 1\r\nGCOUNT GET m:srv\r\n")
        )
        assert rc == 0 and replies == b"+OK\r\n:2\r\n"
        assert eng.served_counts()["GCOUNT"] == 2
        assert _metric_value(
            run(db, "SYSTEM", "METRICS"), b"GCOUNT cmds"
        ) == total + 2
    # a second Database sees none of the first's counts (per-instance
    # wiring, unlike the process-global drain counters)
    other = Database(identity=2, engine="python")
    out = run(other, "SYSTEM", "METRICS")
    assert not [
        l for l in out.split(b"\r\n") if l.startswith(b"GCOUNT cmds")
    ], out
    run(other, "GCOUNT", "GET", "m:srv")
    assert _metric_value(run(other, "SYSTEM", "METRICS"), b"GCOUNT cmds") == 1
