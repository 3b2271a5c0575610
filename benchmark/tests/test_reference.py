"""The plain references against hand-worked cases."""

import numpy as np
import pytest

from benchmark.harness import gen, manifest

PN = manifest.load_module(manifest.BENCH + "/reference/PNCOUNT.py")
TR = manifest.load_module(manifest.BENCH + "/reference/TREG.py")
RECIPE = {"keys": 50, "replica_ids": 8, "foreign_keys": 5, "key_format": "k%03d"}


def pn(seed=1):
    return PN.Reference(RECIPE, seed, 99, [7, 8, 9], gen.hottest(50, 50))


def test_pncount_base_is_the_sum_of_its_snapshot_columns():
    ref = pn()
    batch = dict(ref.snapshot_batch())
    assert len(batch) == 50
    for i in (0, 17, int(ref.hot[0]), int(ref.hot[4])):
        dp, dn = batch[b"k%03d" % i]
        assert dp[99] == int(ref.own_p[i])
        want = PN.wrap_i64(sum(dp.values()) - sum(dn.values()))
        assert ref.expected([i]) == [want]
    dp, _dn = batch[b"k%03d" % int(ref.hot[0])]
    assert len(dp) == 8 and {7, 8, 9} <= set(dp)  # own + 7 foreign, peers among them
    cold = next(i for i in range(50) if i not in set(ref.hot.tolist()))
    assert set(batch[b"k%03d" % cold][0]) == {99}


@pytest.mark.parametrize("verb,sign", [("INC", 1), ("DEC", -1)])
def test_pncount_apply_adds_acknowledged_amounts(verb, sign):
    ref = pn()
    before = ref.expected([3, 4])
    ref.apply(verb, np.array([3, 3, 4]), np.array([5, 6, 1 << 40], np.uint64), np.zeros(3))
    after = ref.expected([3, 4])
    assert after[0] == PN.wrap_i64(before[0] + sign * 11)
    assert after[1] == PN.wrap_i64(before[1] + sign * (1 << 40))


def test_pncount_wraps_like_i64_and_holds_values_beyond_2_53():
    assert PN.wrap_i64((1 << 63) + 5) == -(1 << 63) + 5
    assert PN.wrap_i64(-1) == -1 and PN.wrap_i64(1 << 64) == 0
    big = dict(RECIPE, keys=4000, foreign_keys=400)
    ref = PN.Reference(big, 1, 99, [7, 8, 9], gen.hottest(4000, 4000))
    assert int(ref.own_p.max()) > 1 << 53 and int(ref.own_n.max()) > 1 << 53
    assert int(ref.f_p.max()) > 1 << 53 and int(ref.f_n.max()) > 1 << 53
    keys = list(range(4000))
    assert ref.expected(keys) != ref.expected_lower_precision(keys)


def test_pncount_rejects_an_unknown_write():
    with pytest.raises(ValueError):
        pn().apply("SET", np.array([1]), np.array([1], np.uint64), np.zeros(1))


def treg(seed=1):
    recipe = {"keys": 20, "value_bytes": 40, "key_format": "u%02d",
              "ts_ceiling": gen.TS_EPOCH_MS << gen.TS_SHIFT}
    return TR.Reference(recipe, seed, 0, [], gen.hottest(20, 20), gen.Values(seed))


def test_treg_last_writer_wins_and_a_stale_write_loses():
    ref = treg()
    base = ref.expected([5])[0]
    assert len(base[0]) == 40 and base[0] == ref.values.make(TR.BASE_NONCE | 5, 40)
    t_new = gen.make_ts(1.0, 1, 3)
    ref.apply("SET", np.array([5, 5, 6]), np.array([t_new + 9, t_new, 5], np.uint64),
              np.array([111, 222, 333], np.uint64))
    assert ref.expected([5])[0] == [ref.values.make(111, 40), t_new + 9]
    assert ref.expected([6])[0][1] == int(ref.ts[6]) != 5  # ts 5 is older than the base
    assert ref.expected_lower_precision([5])[0][1] != t_new + 9  # f64 drops low bits


def test_treg_snapshot_is_the_base_state():
    ref = treg()
    batch = ref.snapshot_batch()
    assert batch[7] == (b"u07", (ref.values.make(TR.BASE_NONCE | 7, 40), int(ref.ts[7])))
