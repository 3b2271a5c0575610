"""`MAP TREG` on the native field table and its device table against the
oracle (`PyMapTable` + `MapCRDT`, ``engine="python"``): seeded histories of
SET / DEL / foreign units of TREG fields (a DEL beside a concurrent SET:
add-wins; a SET after a DEL; ties on equal timestamps; an 8-byte prefix
shared by two values at one timestamp), a key that also holds a GCOUNT
field, and a dump -> load round trip. Every reply is byte-equal, every
flush and dump carries the same units, the digests are equal, and after
every drain the device rows gathered back hold the oracle's
(ver, tomb, ts) and the rank of its value."""

import random

import numpy as np
import pytest

import jylis_tpu  # noqa: F401
from jylis_tpu.cluster import codec
from jylis_tpu.models.repo_map import PENDING_DRAIN_THRESHOLD, RepoMAP
from jylis_tpu.native.engine import make_engine
from jylis_tpu.obs.registry import MetricsRegistry
from jylis_tpu.ops.compose import pack_field
from jylis_tpu.ops.interner import prefix_rank
from jylis_tpu.server.resp import Respond

KEYS = [b"user%d" % i for i in range(6)]
FIELDS = [b"field%d" % j for j in range(10)] + [b"f", b"field10"]
VALUES = [b"", b"a", b"abcdefgh-1", b"abcdefgh-2", b"zz", b"\x00\xff\r\n"]


def pair():
    eng = make_engine()
    if eng is None:
        pytest.skip("no native engine on this host")
    native = RepoMAP(identity=1, engine=eng)
    native.metrics = MetricsRegistry()  # its own tallies, not the process's
    return native, RepoMAP(identity=1, engine="python")


def call(repo, *words: bytes) -> bytes:
    out = bytearray()
    resp = Respond(out.extend)
    repo.apply(resp, list(words))
    return bytes(out)


def both(native, oracle, *words: bytes) -> bytes:
    got, want = call(native, *words), call(oracle, *words)
    assert got == want, (words, got[:120], want[:120])
    return got


def history(seed: int, n: int):
    """Commands of one replica and the foreign units of two others (rids
    2 and 3, each an oracle repo of its own writing the same records)."""
    rng = random.Random(seed)
    remotes = [RepoMAP(identity=rid, engine="python") for rid in (2, 3)]
    for i in range(n):
        key, field = rng.choice(KEYS), rng.choice(FIELDS)
        roll = rng.random()
        ts = rng.choice([5, 5, 7, 1 << 40, (1 << 61) + i, i])
        if roll < 0.45:
            yield "cmd", (b"TREG", b"SET", key, field, rng.choice(VALUES), b"%d" % ts)
        elif roll < 0.55:
            yield "cmd", (b"TREG", b"DEL", key, field)
        elif roll < 0.70:
            yield "cmd", (b"TREG", rng.choice([b"GET", b"GETALL", b"KEYS"]), key, field)
        elif roll < 0.75:  # the key also holds a field of another inner type
            yield "cmd", (b"GCOUNT", b"SET", key, rng.choice([b"hits", field]), b"3")
        elif roll < 0.80:
            yield "cmd", (b"GCOUNT", rng.choice([b"GET", b"GETALL", b"KEYS"]), key, b"hits")
        else:  # a peer's edit of the same records: SET, or DEL of what IT has seen
            remote = rng.choice(remotes)
            if rng.random() < 0.7:
                call(remote, b"TREG", b"SET", key, field, rng.choice(VALUES), b"%d" % ts)
            else:
                call(remote, b"TREG", b"DEL", key, field)
            for unit in remote.flush_deltas():
                yield "unit", unit
        if i % 97 == 96:
            yield "drain", None


def device_equals_oracle(native, oracle) -> int:
    """Every TREG field of the oracle, read back from the device table."""
    eng = native.engine
    rids = eng.map_rids()
    checked = 0
    for key, m in oracle._tbl.maps.items():
        for field, f in m.fields.items():
            if f.itype != "TREG":
                continue
            row = eng.map_find(key, field)
            assert row >= 0, (key, field)
            cells, ts_hi, ts_lo, rank_hi, rank_lo, vid = (
                np.asarray(p)[0] for p in native.device_rows([row])
            )
            r = len(cells) // 4
            ver = {rid: (int(cells[c]) << 32) | int(cells[2 * r + c]) for c, rid in enumerate(rids)}
            tomb = {rid: (int(cells[r + c]) << 32) | int(cells[3 * r + c])
                    for c, rid in enumerate(rids)}
            assert {k: v for k, v in ver.items() if v} == f.ver, (key, field)
            assert {k: v for k, v in tomb.items() if v} == f.tomb, (key, field)
            value, ts = f.val
            assert (int(ts_hi) << 32) | int(ts_lo) == ts
            assert (int(rank_hi) << 32) | int(rank_lo) == prefix_rank(value)
            assert (int(vid) >= 0) == ((ts, value) != (0, b""))
            checked += 1
    return checked


def same_state(native, oracle) -> None:
    assert sorted(native.dump_state()) == sorted(oracle.dump_state())
    keys = sorted(k for k, _u in oracle.dump_state())
    assert [native.sync_canon(k) for k in keys] == [oracle.sync_canon(k) for k in keys]


@pytest.mark.parametrize("seed", [7, 2**31 + 46])
def test_histories_of_set_del_and_foreign_units_match_the_oracle(seed):
    native, oracle = pair()
    drains = 0
    for kind, what in history(seed, 1500):
        if kind == "cmd":
            both(native, oracle, *what)
        elif kind == "unit":
            native.converge(*what)
            oracle.converge(*what)
        else:
            native.drain()
            oracle.drain()
            drains += 1
            assert device_equals_oracle(native, oracle) > 0
            assert sorted(native.flush_deltas()) == sorted(oracle.flush_deltas())
            assert sorted(native.sync_dirty_keys()) == sorted(oracle.sync_dirty_keys())
    assert drains > 5
    native.drain()
    same_state(native, oracle)
    assert device_equals_oracle(native, oracle) >= 30
    # every record read whole, and the fields of the other inner type
    for key in KEYS:
        both(native, oracle, b"TREG", b"GETALL", key)
        both(native, oracle, b"GCOUNT", b"GETALL", key)
        both(native, oracle, b"TREG", b"KEYS", key)


def test_a_del_beside_a_concurrent_set_a_set_after_a_del_and_ties():
    native, oracle = pair()
    peer = RepoMAP(identity=2, engine="python")
    for repo in (native, oracle, peer):
        call(repo, b"TREG", b"SET", b"u", b"field3", b"old", b"10")
    # the peer has not seen this node's edit; its DEL covers only its own
    call(peer, b"TREG", b"DEL", b"u", b"field3")
    for unit in peer.flush_deltas():
        native.converge(*unit)
        oracle.converge(*unit)
    assert both(native, oracle, b"TREG", b"GET", b"u", b"field3").startswith(b"*2\r\n$3\r\nold")
    # a local DEL covers everything seen: the field leaves GET, KEYS and GETALL
    both(native, oracle, b"TREG", b"DEL", b"u", b"field3")
    assert both(native, oracle, b"TREG", b"GET", b"u", b"field3") == b"$-1\r\n"
    assert both(native, oracle, b"TREG", b"GETALL", b"u") == b"*0\r\n"
    assert both(native, oracle, b"TREG", b"DEL", b"u", b"field3") == b"+OK\r\n"  # nothing live
    # a SET after the DEL revives it; the register is a join, so an older write stays lost
    both(native, oracle, b"TREG", b"SET", b"u", b"field3", b"older", b"9")
    assert both(native, oracle, b"TREG", b"GET", b"u", b"field3").startswith(b"*2\r\n$3\r\nold")
    # equal timestamps fall to the greater value, whichever arrives first,
    # here two values that share their first 8 bytes (a device prefix tie)
    both(native, oracle, b"TREG", b"SET", b"u", b"field3", b"abcdefgh-1", b"77")
    native.drain()
    both(native, oracle, b"TREG", b"SET", b"u", b"field3", b"abcdefgh-2", b"77")
    both(native, oracle, b"TREG", b"SET", b"u", b"field3", b"abcdefgh-0", b"77")
    native.drain()
    oracle.drain()
    assert both(native, oracle, b"TREG", b"GET", b"u", b"field3") == (
        b"*2\r\n$10\r\nabcdefgh-2\r\n:77\r\n")
    assert native.metrics.tallies["drain.MAP.tie_rows"] == 1  # -2 met -1 on the device
    assert device_equals_oracle(native, oracle) == 1
    same_state(native, oracle)


def test_a_type_change_on_one_field_goes_the_way_of_the_greater_type_name():
    native, oracle = pair()
    # GCOUNT first, then TREG (the greater name) takes the field over
    both(native, oracle, b"GCOUNT", b"SET", b"k", b"f", b"5")
    both(native, oracle, b"TREG", b"SET", b"k", b"f", b"v", b"1")
    assert both(native, oracle, b"GCOUNT", b"GET", b"k", b"f") == b"$-1\r\n"
    assert both(native, oracle, b"TREG", b"GETALL", b"k") == b"*2\r\n$1\r\nf\r\n*2\r\n$1\r\nv\r\n:1\r\n"
    # and a later GCOUNT write of that field is dominated: acknowledged, no effect
    both(native, oracle, b"GCOUNT", b"SET", b"k", b"f", b"9")
    both(native, oracle, b"GCOUNT", b"SET", b"k", b"g", b"2")
    assert both(native, oracle, b"GCOUNT", b"GETALL", b"k") == b"*2\r\n$1\r\ng\r\n:2\r\n"
    # foreign units in both orders
    other = RepoMAP(identity=3, engine="python")
    call(other, b"PNCOUNT", b"SET", b"k", b"h", b"-4")
    call(other, b"TREG", b"SET", b"k", b"g", b"w", b"3")
    for unit in other.flush_deltas():
        native.converge(*unit)
        oracle.converge(*unit)
    native.drain()
    oracle.drain()
    for t in (b"TREG", b"GCOUNT", b"PNCOUNT"):
        both(native, oracle, t, b"GETALL", b"k")
    same_state(native, oracle)
    assert sorted(native.flush_deltas()) == sorted(oracle.flush_deltas())


def test_dump_load_round_trip_through_the_snapshot_codec_is_one_buffer():
    native, oracle = pair()
    for kind, what in history(11, 600):
        if kind == "cmd":
            both(native, oracle, *what)
        elif kind == "unit":
            native.converge(*what)
            oracle.converge(*what)
    dump = native.dump_state()
    body = codec.encode(codec.MsgPushDeltas("MAP", dump))
    assert body == codec.encode(codec.MsgPushDeltas("MAP", tuple(sorted(oracle.dump_state()))))
    msg = codec.decode(body, lazy=True)
    # the GCOUNT fields make it a batch the native reader does not take whole
    assert isinstance(msg.batch, tuple)
    fresh_native = RepoMAP(identity=1, engine=make_engine())
    fresh_native.load_state(list(msg.batch))
    same_state(fresh_native, oracle)
    # a TREG-only state travels as its wire bytes, table to table
    only = RepoMAP(identity=1, engine=make_engine())
    want = RepoMAP(identity=1, engine="python")
    units = [u for u in oracle.dump_state() if u[1][0] == "TREG"]
    only.load_state(units)
    want.load_state(units)
    wire = only.dump_state()
    # a message holds a batch already in wire form as it is, a list as a
    # tuple; either way its keys are read without a unit decoded twice
    assert codec.MsgPushDeltas("MAP", wire).batch is wire
    assert codec.MsgPushDeltas("MAP", list(wire)).batch == tuple(sorted(units))
    assert codec.keys_of(wire) == codec.keys_of(sorted(units)) == [k for k, _u in sorted(units)]
    body = codec.encode(codec.MsgPushDeltas("MAP", wire))
    msg = codec.decode(body, lazy=True)
    assert isinstance(msg.batch, codec.WireBatch) and len(msg.batch) == len(units)
    assert codec.decode(body) == codec.MsgPushDeltas("MAP", tuple(sorted(units)))
    again = RepoMAP(identity=1, engine=make_engine(), field_cap=8)
    again.load_state(msg.batch)
    # the load sizes the tables from the batch's own count: one growth
    assert again._field_cap >= len(units) > 8
    same_state(again, want)
    assert device_equals_oracle(again, want) == len(units)
    # a truncated payload is not the native reader's: the oracle refuses it
    with pytest.raises(codec.CodecError):
        codec.decode(body[:-1], lazy=True)


def test_the_threshold_drain_runs_at_4096_changed_rows_and_reads_never_drain():
    native, oracle = pair()
    assert PENDING_DRAIN_THRESHOLD == 4096
    for i in range(PENDING_DRAIN_THRESHOLD - 1):
        call(native, b"TREG", b"SET", b"user%d" % (i // 10), b"field%d" % (i % 10), b"v", b"%d" % i)
    assert native._nat.pend_count() == PENDING_DRAIN_THRESHOLD - 1
    call(native, b"TREG", b"GETALL", b"user0")
    assert native._nat.pend_count() == PENDING_DRAIN_THRESHOLD - 1  # a read drains nothing
    assert native.may_drain([b"TREG", b"SET", b"user0", b"field0", b"v", b"9"])
    assert not native.may_drain([b"TREG", b"GETALL", b"user0"])
    call(native, b"TREG", b"SET", b"user0", b"field0", b"w", b"99998")  # a row already pending
    assert native._nat.pend_count() == PENDING_DRAIN_THRESHOLD - 1
    call(native, b"TREG", b"SET", b"fresh", b"field0", b"w", b"99999")
    assert native._nat.pend_count() == 0  # the write that tipped it drained
    rows = [0, native.engine.map_find(b"fresh", b"field0")]
    assert np.asarray(native.device_rows(rows)[2]).tolist() == [99998, 99999]


def test_an_inner_type_named_above_treg_is_refused_where_the_tables_split(monkeypatch):
    """A field's type is settled by the greater type NAME, and the native
    table holds TREG's rows on the promise that nothing outranks them."""
    from jylis_tpu.ops import compose

    eng = make_engine()
    if eng is None:
        pytest.skip("no native engine on this host")
    monkeypatch.setitem(compose.REGISTRY, "UREG", compose.REGISTRY["TREG"])
    with pytest.raises(RuntimeError, match="above TREG"):
        RepoMAP(identity=1, engine=eng)
    RepoMAP(identity=1, engine="python")  # one table: any registry
