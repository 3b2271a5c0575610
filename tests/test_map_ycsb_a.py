"""The served path of `MAP TREG` against the benchmark's plain reference
(`benchmark/reference/MAP.py`) at 2,000 records x 10 fields under the mix
of the cell `ycsb-map-1mx10.a`: a real `Server` over a socket, the state
restored from the reference's snapshot through the snapshot codec, 50%
`GETALL` and 50% `SET` of one field drawn of ten over scrambled Zipfian
keys with 61-bit timestamps, pipelined. Every record read on the way is the
reference's at that point, every record read whole at the end is the
reference's, and the float64 control is not. Both stacks: the native engine
(commands settled in the burst, drains to the device table) and the Python
tables."""

import asyncio
import os
import tempfile

import numpy as np
import pytest

import jylis_tpu  # noqa: F401
from benchref import gen, map_reference, resp as bench_resp
from jylis_tpu import persist
from jylis_tpu.models.database import DATA_TYPE_NAMES

from test_async_serving import make_server

OPS = 24000  # its ~12,000 SETs change 4,096 distinct field rows once: one threshold drain
DEPTH = 16


def pack(*words: bytes) -> bytes:
    return b"*%d\r\n" % len(words) + b"".join(b"$%d\r\n%s\r\n" % (len(w), w) for w in words)


def stream(seed: int, ref):
    rng = np.random.default_rng([seed, 0x4D59])
    n, f = ref.recipe["keys"], ref.recipe["fields"]
    keys = gen.KeyDist({"dist": "zipfian", "theta": 0.99}, n).draw(rng, OPS).tolist()
    reads = (rng.random(OPS) < 0.5).tolist()
    fields = rng.integers(0, f, OPS).tolist()
    out = []
    for i, (k, read, j) in enumerate(zip(keys, reads, fields)):
        if read:
            out.append(("GETALL", k, 0, 0, 0))
        else:
            ts = gen.make_ts(i / 300.0, i, i % 64)
            assert ts.bit_length() == 61
            out.append(("SET", k, j, ts, (9 << 40) | i))
    return out


async def play(engine: str, seed: int):
    ref = map_reference(seed)
    server, db = make_server(engine=engine)
    if engine == "auto" and db.native_engine is None:
        pytest.skip("no native engine on this host")
    # the state arrives as a node's does: one snapshot, through boot recovery
    with tempfile.TemporaryDirectory() as d:
        path = os.path.join(d, "snapshot.jylis")
        persist.write_snapshot(
            ((n, ref.snapshot_batch() if n == "MAP" else [])
             for n in DATA_TYPE_NAMES + ("SYSTEM",)), path)
        assert persist.load_snapshot(db, path) == len(DATA_TYPE_NAMES) + 1
    db.warm_drain_shapes()
    await server.start()
    try:
        reader, writer = await asyncio.open_connection("127.0.0.1", server.port)
        parser = bench_resp.Parser()

        async def replies(n: int) -> list:
            out = []
            while len(out) < n:
                r = parser.pop()
                if r is bench_resp.Parser.MORE:
                    chunk = await asyncio.wait_for(reader.read(1 << 18), 20)
                    assert chunk, "the server closed the connection"
                    parser.feed(chunk)
                else:
                    out.append(r)
            return out

        ops = stream(seed, ref)
        size = ref.recipe["value_bytes"]
        for at in range(0, len(ops), DEPTH):
            chunk = ops[at:at + DEPTH]
            wire, want = [], []
            for verb, k, j, ts, nonce in chunk:
                if verb == "GETALL":
                    wire.append(pack(*ref.read_command(k)))
                    want.append(ref.expected([k])[0])
                else:
                    wire.append(pack(b"MAP", b"TREG", b"SET", ref.key(k), b"field%d" % j,
                                     ref.values.make(nonce, size), b"%d" % ts))
                    want.append(b"OK")
                    ref.apply_op("MAP TREG SET {key} field%d {value:100} {ts}" % j,
                                 np.array([k]), np.array([ts], np.uint64),
                                 np.array([nonce], np.uint64))
            writer.write(b"".join(wire))
            got = await replies(len(chunk))
            for g, w, op in zip(got, want, chunk):
                assert g == w, (op[:3], str(g)[:100], str(w)[:100])
        everything = list(range(ref.recipe["keys"]))
        writer.write(b"".join(pack(*ref.read_command(k)) for k in everything))
        final = await replies(len(everything))
        writer.close()
        return final, ref, db
    finally:
        await server.dispose()


@pytest.mark.parametrize("engine", ["auto", "python"])
def test_every_record_read_on_the_way_and_at_the_end_is_the_references(engine):
    final, ref, db = asyncio.run(asyncio.wait_for(play(engine, 2**31 + 46), 300))
    everything = range(ref.recipe["keys"])
    assert final == ref.expected(everything)
    assert final != ref.expected_lower_precision(everything)
    # a record is ten fields in name order, each [100 bytes, a 61-bit timestamp]
    assert all(len(rec) == 20 and rec[0::2] == [b"field%d" % j for j in range(10)]
               for rec in final)
    assert all(len(v) == 100 for rec in final for v, _ts in rec[1::2])
    written = sum(1 for rec in final for _v, ts in rec[1::2]
                  if ts >= gen.TS_EPOCH_MS << gen.TS_SHIFT)
    assert written > 500, "updates landed on many fields"
    assert any(0 < sum(ts >= gen.TS_EPOCH_MS << gen.TS_SHIFT for _v, ts in rec[1::2]) < 10
               for rec in final), "a record with some fields updated and some not"
    serving = db.serving_totals()
    tallies = dict(((t, k), n) for t, k, n in db.metrics.tally_stats())
    sets = sum(1 for op in stream(2**31 + 46, ref) if op[0] == "SET")
    assert tallies[("MAP", "sets")] == sets
    assert tallies[("MAP", "getalls")] == OPS - sets + ref.recipe["keys"]
    assert tallies[("MAP", "getall_fields")] == 10 * tallies[("MAP", "getalls")]
    if engine == "auto":
        # the engine settled them: only writes that met the window one
        # row short of the threshold came to the Python path, and the one
        # that filled it ran the device drain
        assert serving["demotions"] == 0 and serving["busy_routed_cmds"] == 0
        assert 1 <= serving["deferred_cmds"] <= 64
        assert serving["native_cmds"] + serving["deferred_cmds"] == OPS + ref.recipe["keys"]
        drains = db.metrics.counters["MAP"]
        assert drains["batches"] >= 2 and drains["keys"] >= 20000 + 4096  # boot's, the threshold's
        served = db.native_engine.served_counts()
        assert served["MAP"] == serving["native_cmds"]


def test_the_device_table_holds_what_the_host_table_answers():
    final, ref, db = asyncio.run(asyncio.wait_for(play("auto", 99), 300))
    repo = db.manager("MAP").repo
    repo.drain()
    eng = db.native_engine
    rng = np.random.default_rng(5)
    for k in rng.integers(0, ref.recipe["keys"], 60).tolist():
        rows = [eng.map_find(ref.key(k), b"field%d" % j) for j in range(10)]
        cells, ts_hi, ts_lo, _rh, _rl, vid = (np.asarray(p) for p in repo.device_rows(rows))
        got = ((ts_hi.astype(np.uint64) << np.uint64(32)) | ts_lo).tolist()
        assert got == [ts for _v, ts in final[k][1::2]]
        assert (vid >= 0).all() and (cells.sum(axis=1) >= 1).all()
