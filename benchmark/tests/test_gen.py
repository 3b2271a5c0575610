"""The general traffic generator."""

import numpy as np
import pytest

from benchmark.harness import gen, measure
from benchmark.harness.loadgen import BUSY, OK


def test_template_renders_resp_and_knows_its_fields():
    t = gen.Template("TREG SET {key} {value:8} {ts}")
    assert (t.type_name, t.verb, t.value_size) == ("TREG", "SET", 8)
    assert t.fields == {"key", "value", "ts"}
    assert t.render(b"k1", ts=42, value=b"abcdefgh") == (
        b"*5\r\n$4\r\nTREG\r\n$3\r\nSET\r\n$2\r\nk1\r\n$8\r\nabcdefgh\r\n$2\r\n42\r\n")
    with pytest.raises(ValueError):
        gen.Template("X Y {nope}")


def test_timestamps_never_tie_and_stay_under_62_bits():
    seen = {gen.make_ts(t / 1000, seq, conn)
            for t in range(3) for seq in range(50) for conn in range(64)}
    assert len(seen) == 3 * 50 * 64
    assert max(seen) < 1 << 62 and min(seen) >= gen.TS_EPOCH_MS << gen.TS_SHIFT


def test_values_come_from_the_seed_and_the_nonce_alone():
    a, b = gen.Values(7), gen.Values(7)
    assert a.make(123, 1000) == b.make(123, 1000) and len(a.make(123, 1000)) == 1000
    assert a.make(123, 1000) != a.make(124, 1000)
    assert gen.Values(8).make(123, 1000) != a.make(123, 1000)


def test_zipfian_hot_set_is_the_scramble_prefix_whatever_the_seed():
    d = gen.KeyDist({"dist": "zipfian", "theta": 0.99}, 10_000)
    hot = set(gen.hottest(10_000, 100).tolist())
    for seed in (1, 2):
        keys = d.draw(np.random.default_rng(seed), 20_000)
        assert 0.45 < np.isin(keys, list(hot)).mean() < 0.65  # ~55% on 1% of keys
    assert sorted(gen.scramble(1000).tolist()) == list(range(1000))


def test_window_counts_failures_as_missing_the_latency():
    log = {"kind": "closed", "counted": True, "classes": ["read", "write"],
           "op": np.array([0, 0, 1, 0]), "sched": np.array([1.0, 2.0, 2.5, 9.0]),
           "lat": np.array([0.001, 0.002, 0.003, 0.001], np.float32),
           "status": np.array([OK, BUSY, OK, OK], np.uint8)}
    win = measure.Window([log], 0.5, 4.5)
    assert win.ops_per_s() == 2 / 4.0  # the BUSY one did not complete, 9.0 is outside
    p95, n = win.class_p95_ms("read")
    assert n == 2 and p95 == 4000.0  # the refused read counts as the window's length
    assert win.attempted_failed() == (3, 1)
    assert measure.percentile(np.arange(1, 101), 0.95) == 95
