"""The TREG reference under writers at several nodes: what the comparison
that decides `correct` rests on in a cell whose three replicas all take
SETs. The same acknowledged SETs, split over three logs as the harness
holds them (one per load worker, whichever node it wrote at), give the
same `expected` in every order of the logs, and a stale write from
another node loses to a newer one that was fed first."""

import itertools

import numpy as np
import pytest

from benchmark.harness import check, gen, manifest

TR = manifest.load_module(manifest.BENCH + "/reference/TREG.py")
RECIPE = {"keys": 40, "value_bytes": 48, "key_format": "u%02d",
          "ts_ceiling": gen.TS_EPOCH_MS << gen.TS_SHIFT}
KEYS = list(range(RECIPE["keys"]))



def feed(ref, logs):
    """`check.feed_reference` for logs of the one type TREG."""
    for lg in logs:
        lg.setdefault("types", ["TREG"] * len(lg["verbs"]))
    return check.feed_reference({"TREG": ref}, logs)["TREG"]

def reference(seed: int):
    return TR.Reference(RECIPE, seed, 0, [1, 2], gen.hottest(40, 40), gen.Values(seed))


def three_logs(seed: int, writes: int = 600) -> list[dict]:
    """SETs on a hot set of 12 keys as three workers would log them: worker
    w holds connections 2w and 2w+1 (ids unique across nodes, so no two
    timestamps tie), writes over the same 3 s, and one write in 9 was not
    acknowledged."""
    rng = np.random.default_rng([seed, 0x4D57])
    logs = []
    for w in range(3):
        conn = rng.integers(2 * w, 2 * w + 2, writes)
        when = np.sort(rng.random(writes)) * 3.0
        seq = np.arange(1, writes + 1)
        ts = np.array([gen.make_ts(float(t), int(s), int(c))
                       for t, s, c in zip(when, seq, conn)], np.uint64)
        nonce = (conn.astype(np.uint64) << np.uint64(40)) | seq.astype(np.uint64)
        logs.append({"kind": "open" if w else "closed", "op": np.zeros(writes, np.uint8),
                     "key": rng.integers(0, 12, writes).astype(np.int64), "a": ts, "b": nonce,
                     "acked": rng.random(writes) > 1 / 9, "verbs": ["SET"],
                     "classes": ["write"]})
    return logs


@pytest.mark.parametrize("seed", [3, 2**31 + 26])
def test_every_order_of_three_writers_logs_gives_the_same_expected(seed):
    logs = three_logs(seed)
    all_ts = np.concatenate([lg["a"] for lg in logs])
    assert len(np.unique(all_ts)) == len(all_ts), "the generator's timestamps never tie"
    answers = []
    for order in itertools.permutations(range(3)):
        ref = reference(seed)
        written, doubtful = feed(ref, [logs[i] for i in order])
        answers.append((ref.expected(KEYS), written.tolist(), doubtful.tolist()))
    assert all(a == answers[0] for a in answers[1:])
    # and it is the plain rule: per key the greatest acknowledged timestamp, base included
    ref = reference(seed)
    expected = answers[0][0]
    for k in range(12):
        best_ts, best_nonce = int(ref.ts[k]), TR.BASE_NONCE | k
        for lg in logs:
            for key, ts, nonce, ok in zip(lg["key"], lg["a"], lg["b"], lg["acked"]):
                if ok and int(key) == k and int(ts) > best_ts:
                    best_ts, best_nonce = int(ts), int(nonce)
        assert expected[k] == [ref.values.make(best_nonce, 48), best_ts]
    assert any(expected[k][1] > int(ref.ts[k]) for k in range(12))
    assert expected[20] == [ref.values.make(TR.BASE_NONCE | 20, 48), int(ref.ts[20])]


def test_a_stale_write_from_another_node_loses():
    """Hand-worked: node A's client writes key 7 at second 2.0; node B's
    client wrote it at second 1.5, and its log reaches the reference
    afterwards (as B's delta reaches A after A's own write): A's stays.
    Key 8 the other way round: B's later write replaces A's."""
    ref = reference(1)
    a_new, b_old = gen.make_ts(2.0, 10, 3), gen.make_ts(1.5, 99, 65)
    a_old, b_new = gen.make_ts(0.5, 11, 0), gen.make_ts(2.5, 100, 65)
    assert b_old < a_new and a_old < b_new
    log_a = {"op": np.zeros(2, np.uint8), "key": np.array([7, 8]),
             "a": np.array([a_new, a_old], np.uint64), "b": np.array([701, 801], np.uint64),
             "acked": np.ones(2, bool), "verbs": ["SET"], "classes": ["write"]}
    log_b = {"op": np.zeros(2, np.uint8), "key": np.array([7, 8]),
             "a": np.array([b_old, b_new], np.uint64), "b": np.array([702, 802], np.uint64),
             "acked": np.ones(2, bool), "verbs": ["SET"], "classes": ["write"]}
    feed(ref, [log_a, log_b])
    assert ref.expected([7, 8]) == [[ref.values.make(701, 48), a_new],
                                    [ref.values.make(802, 48), b_new]]
    # the control (timestamps through float64) loses the connection id in the low bits
    assert ref.expected_lower_precision([7])[0][1] != a_new
