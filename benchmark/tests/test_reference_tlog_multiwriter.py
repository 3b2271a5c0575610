"""The TLOG reference under writers at three nodes: what the comparison
that decides `correct` rests on in `ycsb-tlog-1kx1k-r3.e`, where posts are
appended at every replica and cutoffs raised at the peers only. The same
acknowledged INS and TRIMAT, split over the logs as the harness holds them
(one per load worker, whichever node it wrote at), give the same
`expected` in every order of the logs: a log is a union and a cutoff a
maximum."""

import itertools

import numpy as np
import pytest

from benchmark.harness import check, gen, manifest

TL = manifest.load_module(manifest.BENCH + "/reference/TLOG.py")
RECIPE = {"keys": 16, "entries": 30, "value_bytes": 40, "key_format": "t%02d",
          "ts_epoch_ms": gen.TS_EPOCH_MS, "ts_shift": gen.TS_SHIFT, "base_days": 30}
KEYS = list(range(RECIPE["keys"]))



def feed(ref, logs):
    """`check.feed_reference` for logs of the one type TLOG."""
    for lg in logs:
        lg.setdefault("types", ["TLOG"] * len(lg["verbs"]))
    return check.feed_reference({"TLOG": ref}, logs)["TLOG"]

def reference(seed: int):
    return TL.Reference(RECIPE, seed, 0, [1, 2], gen.hottest(16, 16), gen.Values(seed))


def logs_of_three_nodes(seed: int, posts: int = 300, trims: int = 40) -> list[dict]:
    """Posts on a hot set of 6 threads as three workers would log them
    (worker w holds connections 2w and 2w+1: ids unique across nodes, so no
    two timestamps tie; one post in 9 was not acknowledged), and one
    sweeper's log of TRIMATs taken at the peers, cutoffs anywhere from the
    oldest base post to the middle of the run."""
    rng = np.random.default_rng([seed, 0x4D58])
    ref = reference(seed)
    logs = []
    for w in range(3):
        conn = rng.integers(2 * w, 2 * w + 2, posts)
        when = np.sort(rng.random(posts)) * 3.0
        seq = np.arange(1, posts + 1)
        ts = np.array([gen.make_ts(float(t), int(s), int(c))
                       for t, s, c in zip(when, seq, conn)], np.uint64)
        nonce = (conn.astype(np.uint64) << np.uint64(40)) | seq.astype(np.uint64)
        logs.append({"kind": "open" if w else "closed", "op": np.ones(posts, np.uint8),
                     "key": rng.integers(0, 6, posts).astype(np.int64), "a": ts, "b": nonce,
                     "acked": rng.random(posts) > 1 / 9, "verbs": ["GET", "INS"],
                     "classes": ["read", "write"]})
    lo, hi = int(ref.base_ts.min()), gen.make_ts(1.5, 0, 0)
    logs.append({"kind": "open", "op": np.zeros(trims, np.uint8),
                 "key": rng.integers(0, 8, trims).astype(np.int64),
                 "a": rng.integers(lo, hi, trims, dtype=np.uint64), "b": np.zeros(trims, np.uint64),
                 "acked": np.ones(trims, bool), "verbs": ["TRIMAT"], "classes": ["write"]})
    return logs


@pytest.mark.parametrize("seed", [5, 2**31 + 32])
def test_every_order_of_the_three_nodes_logs_gives_the_same_expected(seed):
    logs = logs_of_three_nodes(seed)
    all_ts = np.concatenate([lg["a"] for lg in logs[:3]])
    assert len(np.unique(all_ts)) == len(all_ts), "the generator's timestamps never tie"
    answers = []
    for order in itertools.permutations(range(4)):
        ref = reference(seed)
        written, doubtful = feed(ref, [logs[i] for i in order])
        answers.append((ref.expected(KEYS), written.tolist(), doubtful.tolist()))
    assert all(a == answers[0] for a in answers[1:])
    # and it is the plain rule: union of base and acknowledged posts, at or above the greatest cutoff
    ref = reference(seed)
    expected = answers[0][0]
    trimmed = grown = 0
    for k in KEYS:
        cut = max([int(a) for lg in logs[3:] for key, a in zip(lg["key"], lg["a"]) if int(key) == k],
                  default=0)
        log = {(int(ts), ref.values.make(TL.BASE_NONCE | (k * 30 + j), 40))
               for j, ts in enumerate(ref.base_ts[k])}
        for lg in logs[:3]:
            log |= {(int(ts), ref.values.make(int(nonce), 40))
                    for key, ts, nonce, ok in zip(lg["key"], lg["a"], lg["b"], lg["acked"])
                    if ok and int(key) == k}
        want = sorted((e for e in log if e[0] >= cut), reverse=True)
        assert expected[k] == [[value, ts] for ts, value in want]
        trimmed += len(want) < len(log)
        grown += len(log) > 30
    assert trimmed >= 3 and grown == 6
    assert len(expected[12]) == 30, "a thread nobody wrote or trimmed keeps its base posts"


def test_a_cutoff_from_another_node_trims_a_post_taken_here_before_it():
    """Hand-worked: node A's client posts to thread 3 at second 1.0 and at
    second 2.0; the sweeper, at node B, raises the thread's cutoff to
    second 1.5 and, earlier in ITS log, to second 0.5. Whichever log
    reaches the reference first, the thread keeps the post of second 2.0
    and loses the one of second 1.0 and every base post."""
    old, new = gen.make_ts(1.0, 1, 3), gen.make_ts(2.0, 2, 3)
    low, cut = gen.make_ts(0.5, 0, 0), gen.make_ts(1.5, 0, 0)
    posts = {"op": np.zeros(2, np.uint8), "key": np.array([3, 3]),
             "a": np.array([old, new], np.uint64), "b": np.array([31, 32], np.uint64),
             "acked": np.ones(2, bool), "verbs": ["INS"], "classes": ["write"]}
    trims = {"op": np.zeros(2, np.uint8), "key": np.array([3, 3]),
             "a": np.array([cut, low], np.uint64), "b": np.zeros(2, np.uint64),
             "acked": np.ones(2, bool), "verbs": ["TRIMAT"], "classes": ["write"]}
    for order in ([posts, trims], [trims, posts]):
        ref = reference(1)
        feed(ref, order)
        assert ref.expected([3]) == [[[ref.values.make(32, 40), new]]]
        assert len(ref.expected([4])[0]) == 30
