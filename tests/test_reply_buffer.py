"""The engine's reply buffer grows to the reply (PR 33): replies of 64 KiB
and more are the native burst's, held byte for byte to the Python
oracle's render (`Respond`), over `scan_apply`, over a socket, and
through jlint pass 11's differential harness; the three counts
(`reply_grows`, `reply_buffer_bytes`, `oversize_defers`) are read on the
surfaces the drain tallies use. The jax-free half (growth, order,
ceiling) is tests/test_native_drive.py."""

from __future__ import annotations

import asyncio
import os
import sys

import pytest

import jylis_tpu  # noqa: F401
from jylis_tpu.models.database import Database
from jylis_tpu.models.repo_tlog import RepoTLOG
from jylis_tpu.models.repo_treg import RepoTREG
from jylis_tpu.models.repo_ujson import RepoUJSON
from jylis_tpu.native import engine as engine_mod
from jylis_tpu.native.engine import make_engine
from jylis_tpu.obs import prom
from jylis_tpu.server.resp import Respond
from jylis_tpu.utils.metrics import metric_lines
from procutil import scan_bytes
from test_native_drive import TS0, post, resp
from test_serve_tables import _oracle_reply as oracle_reply

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from scripts import gen_semfuzz  # noqa: E402

pytestmark = pytest.mark.skipif(
    make_engine() is None, reason="native engine unavailable (no toolchain)"
)

def engine_reply(eng, *args: bytes) -> bytes:
    """One command through the native burst; it must settle there."""
    rc, consumed, replies, unhandled, _ = scan_bytes(eng, bytearray(resp(*args)))
    assert rc == 0 and unhandled is None, (rc, unhandled)
    return replies


def tlog_pair(n: int, pending: int):
    """A native and a Python-table repo holding the same n posts of
    1,000 B: drained but for the newest `pending`, which wait in the
    row's pending window (duplicates of drained posts among them)."""
    native, oracle = RepoTLOG(identity=1), RepoTLOG(identity=1, engine="python")
    drained = n - pending
    for repo in (native, oracle):
        r = Respond(bytearray().extend)
        for i in range(drained):
            repo.apply(r, [b"INS", b"thread", post(i), b"%d" % (TS0 + i)])
        repo.drain()
        for i in range(drained, n):
            repo.apply(r, [b"INS", b"thread", post(i), b"%d" % (TS0 + i)])
        for i in range(0, drained, max(drained // 5, 1))[:pending]:
            repo.apply(r, [b"INS", b"thread", post(i), b"%d" % (TS0 + i)])
    return native, oracle


@pytest.mark.parametrize("pending", [0, 40])
@pytest.mark.parametrize("count", [63, 64, 65, 100, 1000])
def test_tlog_get_matches_the_oracle_across_the_boundary(count, pending):
    native, oracle = tlog_pair(1000, pending)
    got = engine_reply(native.engine, b"TLOG", b"GET", b"thread", b"%d" % count)
    assert got == oracle_reply(oracle, [b"GET", b"thread", b"%d" % count])
    assert got.startswith(b"*%d\r\n*2\r\n$1000\r\n" % count)
    assert (len(native.engine._out) > 1 << 16) == (count >= 64)


def test_treg_get_of_a_128_kib_value_matches_the_oracle():
    native, oracle = RepoTREG(identity=1), RepoTREG(identity=1, engine="python")
    value = bytes(range(256)) * 512
    for repo in (native, oracle):
        repo.apply(Respond(bytearray().extend), [b"SET", b"big", value, b"9"])
    got = engine_reply(native.engine, b"TREG", b"GET", b"big")
    assert got == oracle_reply(oracle, [b"GET", b"big"])
    assert len(got) > 1 << 17 and len(native.engine._out) == 1 << 18


def test_ujson_get_over_64_kib_matches_the_oracle():
    eng = make_engine()
    native, oracle = RepoUJSON(identity=1, engine=eng), RepoUJSON(identity=1)
    for repo in (native, oracle):
        r = Respond(bytearray().extend)
        for i in range(1500):
            repo.apply(r, [b"INS", b"doc", b"tags", b'"%05d%s"' % (i, b"t" * 60)])
    want = oracle_reply(oracle, [b"GET", b"doc"])
    assert len(want) > 1 << 16
    # the first GET misses the render memo and installs it; the next is
    # the engine's, whatever its size
    assert oracle_reply(native, [b"GET", b"doc"]) == want
    assert engine_reply(eng, b"UJSON", b"GET", b"doc") == want
    assert len(eng._out) == 1 << 17


def serve(db: Database, payload: bytes, expect: int) -> bytes:
    """Boot a Server on ``db``, send ``payload`` on one connection and
    read ``expect`` reply bytes."""
    from jylis_tpu.server.server import Server
    from jylis_tpu.utils.config import Config
    from jylis_tpu.utils.log import Log

    async def run() -> bytes:
        cfg = Config()
        cfg.port = "0"
        cfg.log = Log.create_none()
        server = Server(cfg, db)
        await server.start()
        try:
            reader, writer = await asyncio.open_connection("127.0.0.1", server.port)
            writer.write(payload)
            await writer.drain()
            out = await asyncio.wait_for(reader.readexactly(expect), 30)
            writer.close()
            return out
        finally:
            await server.dispose()

    return asyncio.run(run())


def thread_wire(n: int) -> bytes:
    return b"".join(
        resp(b"TLOG", b"INS", b"thread", post(i), b"%d" % (TS0 + i))
        for i in range(n)
    )


def test_a_served_connection_reads_a_1_mb_get_whole():
    """1,000 posts of 1,000 B over a socket: the reply of ~1 MB comes
    from the native burst (no command of the connection took the Python
    path) and equals the Python server's."""
    payload = thread_wire(1000) + resp(b"TLOG", b"GET", b"thread")
    oracle = RepoTLOG(identity=1, engine="python")
    r = Respond(bytearray().extend)
    for i in range(1000):
        oracle.apply(r, [b"INS", b"thread", post(i), b"%d" % (TS0 + i)])
    want = b"+OK\r\n" * 1000 + oracle_reply(oracle, [b"GET", b"thread"])
    assert len(want) > 1_000_000
    db = Database(identity=1)
    assert serve(db, payload, len(want)) == want
    assert db.serving_totals()["demoted_cmds"] == 0
    assert db.serving_totals()["native_cmds"] == 1001
    assert db.metrics.tallies["serving.ENGINE.reply_buffer_bytes"] == 1 << 20
    assert serve(Database(identity=1, engine="python"), payload, len(want)) == want


def test_a_reply_past_the_ceiling_is_still_answered_right(monkeypatch):
    """With the ceiling patched to 128 KiB a GET of 200 posts defers to
    the Python path, which renders it in bounded flushes: the same
    bytes in the same place of the stream, and the defer is counted."""
    monkeypatch.setattr(engine_mod, "_OUT_CEIL", 1 << 17)
    payload = (
        thread_wire(200)
        + resp(b"TLOG", b"GET", b"thread", b"100")
        + resp(b"TLOG", b"GET", b"thread")
        + resp(b"TLOG", b"SIZE", b"thread")
    )
    oracle = RepoTLOG(identity=1, engine="python")
    r = Respond(bytearray().extend)
    for i in range(200):
        oracle.apply(r, [b"INS", b"thread", post(i), b"%d" % (TS0 + i)])
    want = (
        b"+OK\r\n" * 200
        + oracle_reply(oracle, [b"GET", b"thread", b"100"])
        + oracle_reply(oracle, [b"GET", b"thread"])
        + b":200\r\n"
    )
    db = Database(identity=1)
    assert serve(db, payload, len(want)) == want
    t = db.metrics.tallies
    assert t["serving.ENGINE.oversize_defers"] == 1
    assert t["serving.ENGINE.reply_grows"] == 1
    assert t["serving.ENGINE.reply_buffer_bytes"] == 1 << 17
    assert db.serving_totals()["demoted_cmds"] == 1


def test_the_three_counts_are_on_the_scrape_in_system_metrics_and_in_the_shutdown_line():
    db = Database(identity=1)
    assert db.metrics.tallies["serving.ENGINE.reply_buffer_bytes"] == 1 << 16
    eng = db.native_engine
    for i in range(100):
        engine_reply(eng, b"TLOG", b"INS", b"thread", post(i), b"%d" % (TS0 + i))
    for count in (b"10", b"100", b"100", b"70"):
        engine_reply(eng, b"TLOG", b"GET", b"thread", count)
    text = prom.render(db)
    for kind, n in (("reply_grows", 1), ("reply_buffer_bytes", 1 << 17), ("oversize_defers", 0)):
        assert f'jylis_drain_total{{type="ENGINE",kind="{kind}"}} {n}' in text
    lines = metric_lines(registry=db.metrics)
    assert "ENGINE reply_grows 1" in lines
    assert "ENGINE reply_buffer_bytes 131072" in lines
    assert "ENGINE oversize_defers 0" in lines
    # the buffer's three lead the ENGINE part; the reply sender's follow
    assert (
        "ENGINE: 1 reply_grows, 131072 reply_buffer_bytes, 0 oversize_defers, "
        "0 sender_sends, "
    ) in db.metrics.report()
    # a node on the Python tables has no such buffer: explicit zeros
    assert "ENGINE reply_buffer_bytes 0" in metric_lines(
        registry=Database(identity=1, engine="python").metrics
    )


BIG = b"B" * 70_000  # one value over the 64 KiB the buffer starts with


@pytest.mark.parametrize(
    "stream",
    [
        # TLOG: 63 / 64 / 65 posts on either side of the boundary, then all
        [[b"TLOG", b"INS", b"k", post(i), b"%d" % (TS0 + i)] for i in range(120)]
        + [[b"TLOG", b"GET", b"k", n] for n in (b"63", b"64", b"65", b"100", b"zz")]
        + [[b"TLOG", b"SIZE", b"k"], [b"TLOG", b"GET", b"k"]],
        # TREG and UJSON: values of 70 KB and 128 KiB between small commands
        [
            [b"TREG", b"SET", b"k", BIG, b"7"],
            [b"GCOUNT", b"INC", b"c", b"1"],
            [b"TREG", b"GET", b"k"],
            [b"TREG", b"SET", b"k", BIG + BIG, b"8"],
            [b"TREG", b"GET", b"k"],
            [b"TREG", b"GET", b"missing"],
            [b"UJSON", b"SET", b"u", b"doc", b'"' + BIG + b'"'],
            [b"UJSON", b"GET", b"u"],
            [b"UJSON", b"GET", b"u", b"doc"],
            [b"UJSON", b"GET", b"u"],
            [b"GCOUNT", b"GET", b"c"],
        ],
    ],
    ids=["tlog", "treg-ujson"],
)
def test_pass_11s_differential_holds_across_the_boundary(stream):
    """jlint pass 11's harness (the full Server twice, native engine and
    forced-Python oracle, replies byte-compared) on streams whose
    replies cross 64 KiB."""
    gen_semfuzz.run_stream_differential(stream, split=5)
