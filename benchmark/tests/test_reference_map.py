"""The plain reference of MAP holding YCSB's record, against hand-worked
cases: one field written an update and nine left alone, last writer wins a
field, equal timestamps fall to the greater value, the snapshot is one unit
a field under the packed (key, field) wire key, a read is the whole record
in name order, and the float64 control differs."""

import numpy as np
import pytest

from benchmark.harness import gen, manifest

MAP = manifest.load_module(manifest.BENCH + "/reference/MAP.py")
RECIPE = {"keys": 30, "fields": 10, "value_bytes": 100, "key_format": "user%07d",
          "ts_ceiling": gen.TS_EPOCH_MS << gen.TS_SHIFT}
SET = "MAP TREG SET {key} field%d {value:100} {ts}"


def ref(seed=1, **sizes):
    recipe = dict(RECIPE, **sizes)
    return MAP.Reference(recipe, seed, 77, [], gen.hottest(recipe["keys"], recipe["keys"]),
                         gen.Values(seed))


def u64(*xs):
    return np.array(xs, np.uint64)


def test_the_base_record_is_ten_fields_in_name_order_each_100_bytes():
    r = ref()
    rec = r.expected([4])[0]
    assert rec[0::2] == [b"field%d" % j for j in range(10)]
    for j, (value, ts) in enumerate(rec[1::2]):
        assert value == r.values.make(MAP.BASE_NONCE | (4 * 10 + j), 100) and len(value) == 100
        assert ts == int(r.ts[40 + j]) and 1 << 40 <= ts < RECIPE["ts_ceiling"]
    assert r.read_command(4) == (b"MAP", b"TREG", b"GETALL", b"user0000004")
    assert len({ts for _v, ts in rec[1::2]}) == 10  # each field its own timestamp


def test_a_reply_orders_more_than_ten_fields_by_bytes_not_by_number():
    r = ref(fields=12)
    assert r.expected([0])[0][0::2] == sorted(b"field%d" % j for j in range(12))
    assert r.expected([0])[0][0::2][:3] == [b"field0", b"field1", b"field10"]


def test_an_update_writes_one_field_and_leaves_nine():
    r = ref()
    before = r.expected([7])[0]
    t = gen.make_ts(1.0, 1, 3)
    r.apply_op(SET % 3, np.array([7]), u64(t), u64(111))
    after = r.expected([7])[0]
    assert after[2 * 3 + 1] == [r.values.make(111, 100), t]
    assert [x for i, x in enumerate(after) if i != 7] == [x for i, x in enumerate(before) if i != 7]
    # another field of the same record at the same moment: both stay
    r.apply_op(SET % 8, np.array([7]), u64(t + 1), u64(222))
    rec = r.expected([7])[0]
    assert rec[7] == [r.values.make(111, 100), t] and rec[17] == [r.values.make(222, 100), t + 1]


def test_one_field_is_last_writer_wins_and_a_stale_write_loses():
    r = ref()
    t = gen.make_ts(2.0, 5, 9)
    r.apply_op(SET % 0, np.array([2, 2, 2, 3]), u64(t + 9, t, 5, 5), u64(1, 2, 3, 4))
    assert r.expected([2])[0][1] == [r.values.make(1, 100), t + 9]
    assert r.expected([3])[0][1][1] == int(r.ts[30]) != 5  # ts 5 is older than the base
    r.apply_op(SET % 0, np.array([2]), u64(t + 3), u64(9))  # arrives later, is older
    assert r.expected([2])[0][1] == [r.values.make(1, 100), t + 9]


def test_equal_timestamps_fall_to_the_greater_value_in_any_order():
    t = gen.make_ts(3.0, 1, 1)
    lo, hi = sorted([21, 22], key=lambda n: gen.Values(1).make(n, 100))
    for first, second in ((lo, hi), (hi, lo)):
        r = ref()
        r.apply_op(SET % 5, np.array([1]), u64(t), u64(first))
        r.apply_op(SET % 5, np.array([1]), u64(t), u64(second))
        assert r.expected([1])[0][11] == [r.values.make(hi, 100), t]
        r = ref()  # and inside one batch
        r.apply_op(SET % 5, np.array([1, 1]), u64(t, t), u64(first, second))
        assert r.expected([1])[0][11] == [r.values.make(hi, 100), t]


def test_the_snapshot_is_one_treg_unit_a_field_under_the_packed_wire_key():
    r = ref()
    state = r.snapshot_batch()
    assert len(state) == 300
    # by hand: record 4's field6, cell 46 of the payload's 300 units
    value, ts = r.expected([4])[0][13]
    unit = (b"\x12" + b"\x0buser0000004field6"  # bytes(varint(11) + key + field)
            + b"\x04TREG" + b"\x01\x4d\x01" + b"\x00"  # the type, {77: 1}, {}
            + b"\x64" + value + MAP.varint(ts))
    assert len(value) == 100 and state.payload.count(unit) == 1
    assert state.payload.index(unit) == sum(  # units differ in their timestamp's bytes only
        len(unit) - len(MAP.varint(ts)) + len(MAP.varint(int(t))) for t in r.ts[:46])
    long_key = MAP.pack_field(b"k" * 300, b"f")
    assert long_key[:2] == bytes([300 & 0x7F | 0x80, 300 >> 7]) and len(long_key) == 303
    assert [MAP.varint(n) for n in (0, 127, 128, 2**63)] == [
        b"\x00", b"\x7f", b"\x80\x01", b"\x80" * 9 + b"\x01"]


@pytest.mark.parametrize("recipe", [
    {}, {"keys": 120, "fields": 12, "key_format": "u%d", "value_bytes": 12},
    {"value_bytes": 17}, {"ts_ceiling": (1 << 40) + 300}])
def test_the_programs_decoder_reads_the_snapshots_bytes_as_the_state(recipe):
    """The reference's own encoder against the program's decoder and
    encoder, over keys, names, values and timestamps of uneven lengths."""
    from jylis_tpu.cluster import codec

    r = MAP.Reference({**ref().recipe, **recipe}, 5, 77, [], None, gen.Values(5))
    state = r.snapshot_batch()
    units = list(codec.WireBatch(len(state), bytes(state.payload)))
    assert codec.WireBatch.of_units(units).payload == state.payload
    f = r.recipe["fields"]
    for c, (key, unit) in enumerate(units):
        i, j = divmod(c, f)
        assert key == MAP.pack_field(r.key(i), b"field%d" % j)
        assert unit == ("TREG", {77: 1}, {}, (
            r.values.make(MAP.BASE_NONCE | c, r.recipe["value_bytes"]), int(r.ts[c])))


def test_the_snapshot_is_no_sequence_so_a_writer_that_wants_tuples_raises_at_once():
    """How a program that cannot run the deployment fails: PR 45's snapshot
    writer makes a tuple of the batch first, and raises on this one
    before a byte is encoded or a node spawned."""
    state = ref().snapshot_batch()
    with pytest.raises(TypeError):
        tuple(state)
    assert "payload" not in vars(state)  # nothing was encoded for it


def test_the_template_names_the_field_and_anything_else_is_refused():
    r = ref()
    assert [r.field_of(SET % j) for j in (0, 9)] == [0, 9]
    for bad in ("MAP TREG DEL {key} field1", "TREG SET {key} {value:100} {ts}",
                "MAP GCOUNT SET {key} field1 {amount}"):
        with pytest.raises(ValueError):
            r.apply_op(bad, np.array([1]), u64(1), u64(1))
    with pytest.raises(ValueError):
        r.field_of("MAP TREG SET {key} nofield {value:100} {ts}")


@pytest.mark.parametrize("seed", [1, 2**31 + 7])
def test_the_float64_control_differs(seed):
    r = ref(seed, keys=250)
    keys = list(range(250))
    exact, lower = r.expected(keys), r.expected_lower_precision(keys)
    wrong = sum(1 for e, g in zip(exact, lower) if e != g)
    assert wrong > 240  # a record has ten ~61-bit timestamps: one rounds in nearly every one
    assert all(e[0::2] == g[0::2] for e, g in zip(exact, lower))  # the names stay
