"""The plain TLOG reference against hand-worked cases."""

import numpy as np
import pytest

from benchmark.harness import gen, manifest

TL = manifest.load_module(manifest.BENCH + "/reference/TLOG.py")
RECIPE = {"keys": 6, "entries": 4, "value_bytes": 32, "key_format": "t%02d",
          "ts_epoch_ms": gen.TS_EPOCH_MS, "ts_shift": gen.TS_SHIFT, "base_days": 30}
EPOCH = gen.TS_EPOCH_MS << gen.TS_SHIFT


def tl(seed=1):
    return TL.Reference(RECIPE, seed, 99, [], gen.hottest(6, 6), gen.Values(seed))


def ins(ref, key, ts, nonce):
    ref.apply("INS", np.array([key]), np.array([ts], np.uint64), np.array([nonce], np.uint64))


def trimat(ref, key, ts):
    ref.apply("TRIMAT", np.array([key]), np.array([ts], np.uint64), np.zeros(1, np.uint64))


def test_base_logs_are_older_than_the_clients_clock_and_unique_within_a_key():
    ref = tl()
    assert ref.base_ts.shape == (6, 4) and int(ref.base_ts.max()) < EPOCH
    assert int(ref.base_ts.min()) >= (gen.TS_EPOCH_MS - 30 * 86_400_000) << gen.TS_SHIFT
    assert int(ref.base_ts.min()).bit_length() == 61
    for i in range(6):
        log = ref.expected([i])[0]
        stamps = [ts for _v, ts in log]
        assert len(set(stamps)) == 4 and stamps == sorted(stamps, reverse=True)
        assert all(len(v) == 32 for v, _ts in log)
    batch = dict(ref.snapshot_batch())
    entries, cutoff = batch[b"t03"]
    assert cutoff == 0 and sorted([v, ts] for v, ts in entries) == sorted(ref.expected([3])[0])
    assert ref.read_command(3) == (b"TLOG", b"GET", b"t03")
    assert tl(2).expected([0]) != ref.expected([0]), "the state is made from the seed"


def test_an_exact_duplicate_is_dropped_and_a_new_value_at_an_old_timestamp_is_not():
    ref = tl()
    ins(ref, 2, EPOCH + 5, 1234)
    ins(ref, 2, EPOCH + 5, 1234)  # the same post again: one entry
    assert len(ref.expected([2])[0]) == 5
    ins(ref, 2, EPOCH + 5, 1235)  # another value at that timestamp: a second entry
    log = ref.expected([2])[0]
    assert len(log) == 6 and log[0][1] == log[1][1] == EPOCH + 5
    base_value, base_ts = ref.expected([4])[0][-1]
    ref.added[4].add((base_ts, TL.BASE_NONCE | (4 * 4 + [ts for ts in ref.base_ts[4].tolist()].index(base_ts))))
    assert len(ref.expected([4])[0]) == 4, "a base post sent again is still one entry"


def test_a_tie_in_timestamp_is_ordered_by_value_descending():
    ref = tl()
    values = gen.Values(1)
    for nonce in (7, 0xFFFF, 0x10):
        ins(ref, 0, EPOCH + 9, nonce)
    top = ref.expected([0])[0][:3]
    assert [ts for _v, ts in top] == [EPOCH + 9] * 3
    made = sorted((values.make(n, 32) for n in (7, 0xFFFF, 0x10)), reverse=True)
    assert [v for v, _ts in top] == made and made[0] == values.make(0xFFFF, 32)


def test_a_cutoff_equal_to_a_timestamp_keeps_that_entry_and_is_a_maximum():
    ref = tl()
    stamps = sorted(ref.base_ts[1].tolist())
    trimat(ref, 1, stamps[1])  # equal to the second oldest: it stays, the oldest goes
    assert [ts for _v, ts in ref.expected([1])[0]] == sorted(stamps[1:], reverse=True)
    trimat(ref, 1, stamps[0])  # a lower cutoff later: nothing comes back
    assert len(ref.expected([1])[0]) == 3 and ref.cutoff[1] == stamps[1]
    trimat(ref, 1, stamps[1] + 1)
    assert [ts for _v, ts in ref.expected([1])[0]] == sorted(stamps[2:], reverse=True)
    ins(ref, 1, stamps[0], 77)  # an INS below the cutoff is acknowledged and not in the log
    assert len(ref.expected([1])[0]) == 2
    assert dict(ref.snapshot_batch())[b"t01"][1] == stamps[1] + 1
    assert ref.expected([0, 2, 3]) == tl().expected([0, 2, 3]), "other keys are untouched"


@pytest.mark.parametrize("order", [(0, 1, 2), (2, 1, 0), (1, 2, 0)])
def test_the_order_of_acknowledged_writes_does_not_matter(order):
    stamps = sorted(tl().base_ts[5].tolist())
    writes = [("TRIMAT", stamps[2], 0), ("TRIMAT", stamps[1], 0), ("INS", EPOCH + 3, 41)]
    ref = tl()
    for i in order:
        verb, a, b = writes[i]
        ref.apply(verb, np.array([5]), np.array([a], np.uint64), np.array([b], np.uint64))
    assert [ts for _v, ts in ref.expected([5])[0]] == [EPOCH + 3, stamps[3], stamps[2]]


def test_the_lower_precision_control_differs_and_other_writes_are_refused():
    ref = tl()
    # two posts 2^8 apart in a 61-bit timestamp: float64 holds 53 bits of them
    ins(ref, 3, EPOCH + 0x101, 5)
    ins(ref, 3, EPOCH + 0x1FF, 6)
    exact, lower = ref.expected([3])[0], ref.expected_lower_precision([3])[0]
    assert len(exact) == len(lower) == 6 and exact != lower
    assert exact[0][1] - exact[1][1] == 0xFE and lower[0][1] - lower[1][1] in (0, 0x100)
    assert ref.expected(range(6)) != ref.expected_lower_precision(range(6))
    with pytest.raises(ValueError):
        ref.apply("TRIM", np.array([0]), np.array([1], np.uint64), np.zeros(1, np.uint64))
