"""`RepoTLOG` against the benchmark's plain reference (`benchmark/reference/
TLOG.py`) on a seeded stream shaped like the cell `ycsb-tlog-1kx1k.e`: INS,
TRIMAT and GET-with-count over Zipfian keys with 61-bit timestamps, exact
duplicates re-sent, other values at a timestamp the log already holds, and
a hot row that outgrows len_cap several times. Both table backends. A
cutoff is a maximum and a log a set: the same TRIMATs in another order
leave the same logs."""

import numpy as np
import pytest

import jylis_tpu  # noqa: F401
from benchref import Replies, gen, tlog_reference
from jylis_tpu.models.repo_tlog import RepoTLOG

ENGINES = ["auto", "python"]
OPS = 1400


def one(x, dtype=np.uint64):
    return np.array([x], dtype)


def stream(seed: int):
    """[(verb, key, a, b)]: a is the count, the timestamp or the cutoff."""
    rng = np.random.default_rng([seed, 0x59])
    ref = tlog_reference(seed)
    n = ref.recipe["keys"]
    keys = gen.KeyDist({"dist": "zipfian", "theta": 0.99}, n).draw(rng, OPS)
    kinds = rng.choice(5, OPS, p=[0.40, 0.35, 0.10, 0.075, 0.075])
    sent: list[tuple[int, int, int]] = []
    oldest = int(ref.base_ts.min())
    out = []
    for i, (k, kind) in enumerate(zip(keys.tolist(), kinds.tolist())):
        if kind == 1:
            out.append(("GET", k, int(rng.integers(1, 101)), 0))
        elif kind == 2:  # a cutoff somewhere in the base timestamps' older half
            span = (gen.TS_EPOCH_MS << gen.TS_SHIFT) - oldest
            out.append(("TRIMAT", k, oldest + int(rng.integers(0, span // 2)), 0))
        elif kind == 3 and sent:  # an exact duplicate: same key, timestamp, value
            out.append(("INS", *sent[int(rng.integers(len(sent)))]))
        elif kind == 4 and sent:  # a tie: another value at a timestamp the key holds
            k2, ts, _nonce = sent[int(rng.integers(len(sent)))]
            out.append(("INS", k2, ts, (1 << 50) | i))
        else:
            ts = gen.make_ts(i / 50.0, i, i % 64)
            assert ts.bit_length() == 61
            sent.append((k, ts, (7 << 40) | i))
            out.append(("INS", *sent[-1]))
    return out


def play(engine: str, seed: int, reverse_trims: bool):
    """Drive repo and reference through the stream, every GET compared on
    its way; returns (every key's whole log at the end, the reference,
    the repo)."""
    ops = stream(seed)
    if reverse_trims:
        at = [i for i, op in enumerate(ops) if op[0] == "TRIMAT"]
        for i, j in zip(at, reversed(at)):
            if i < j:
                ops[i], ops[j] = ops[j], ops[i]
    ref = tlog_reference(seed)
    repo = RepoTLOG(identity=1, mesh=None, engine=engine)
    repo.load_state(ref.snapshot_batch())
    repo.drain()
    wire = Replies()
    size = ref.recipe["value_bytes"]
    for verb, k, a, b in ops:
        key = ref.key(k)
        if verb == "GET":
            assert wire.call(repo, b"GET", key, b"%d" % a) == ref.expected([k])[0][:a]
        elif verb == "INS":
            assert wire.call(repo, b"INS", key, ref.values.make(b, size), b"%d" % a) == b"OK"
            ref.apply("INS", one(k, np.int64), one(a), one(b))
        else:
            assert wire.call(repo, b"TRIMAT", key, b"%d" % a) == b"OK"
            ref.apply("TRIMAT", one(k, np.int64), one(a), one(0))
    everything = range(ref.recipe["keys"])
    return [wire.call(repo, *ref.read_command(k)[1:]) for k in everything], ref, repo


@pytest.mark.parametrize("reverse_trims", [False, True], ids=["trims-as-sent", "trims-reversed"])
@pytest.mark.parametrize("engine", ENGINES)
def test_every_read_equals_the_reference(engine, reverse_trims):
    logs, ref, repo = play(engine, 2**31 + 30, reverse_trims)
    assert logs == ref.expected(range(ref.recipe["keys"]))
    assert logs != ref.expected_lower_precision(range(ref.recipe["keys"]))
    longest = max(len(log) for log in logs)
    assert repo._len_cap >= 128 > 32 and longest > 64, "the hot row outgrew len_cap more than once"
    assert any(0 < len(log) < ref.recipe["entries"] for log in logs), "a trim cut into a base log"
    ties = sum(1 for log in logs for x, y in zip(log, log[1:]) if x[1] == y[1])
    assert ties > 5, "equal timestamps are in the logs, ordered by value"
    assert all(x[0] > y[0] for log in logs for x, y in zip(log, log[1:]) if x[1] == y[1])


@pytest.mark.parametrize("engine", ENGINES)
def test_trimats_in_another_order_leave_the_same_logs(engine):
    forward, ref_f, _ = play(engine, 99, False)
    backward, ref_b, _ = play(engine, 99, True)
    assert forward == backward
    assert ref_f.cutoff == ref_b.cutoff and max(ref_f.cutoff) > 0
