"""Interner lifecycle: value churn must not grow host memory without
bound (VERDICT round-2 weak spot 1 — ops/interner.py was append-only for
the process lifetime). Epoch compaction rebuilds the table from the live
set at drain boundaries and remaps the device planes; these tests churn
far more distinct values than stay live and assert the table tracks the
LIVE state while reads remain exact. TREG left the interner (PR 27): its
device ids are per-row generations kept by the table, so the same churn is
asserted against what bounds THAT id space, the number of drains."""

import numpy as np
import pytest

import jylis_tpu  # noqa: F401
from jylis_tpu.ops.interner import Interner


class _R:
    def __init__(self):
        self.vals = []

    def __getattr__(self, name):
        return lambda *a: self.vals.extend(a)


def test_interner_compact_remaps_and_drops_dead():
    it = Interner()
    ids = [it.intern(b"v%d" % i) for i in range(100)]
    live = ids[::7]
    remap = it.compact(live)
    assert len(it) == len(live)
    for oid in ids:
        if oid in live:
            assert it.lookup(int(remap[oid])) == b"v%d" % oid
        else:
            assert remap[oid] == -1
    # new interning reuses the compacted space without collisions
    nid = it.intern(b"fresh")
    assert it.lookup(nid) == b"fresh"
    assert it.rank(nid) > 0


def test_treg_set_churn_keeps_interner_flat():
    from jylis_tpu.models import repo_treg as mod

    repo = mod.RepoTREG(identity=1)
    n_keys, rounds = 256, 40  # 10k distinct values over 256 live registers
    r = _R()
    ts = 0
    for g in range(rounds):
        for k in range(n_keys):
            ts += 1
            repo.apply(
                r, [b"SET", b"k%d" % k, b"gen%d-val%d" % (g, k), b"%d" % ts]
            )
        repo.drain()
    # the id space: a row's id in the device mirror is its generation, one
    # step per drain that changed the row, whatever the number of distinct
    # values churned through it; the repo keeps no table of values at all
    vid = np.asarray(repo._state.vid)[:n_keys]
    assert vid.min() >= 0 and vid.max() < rounds, (vid.min(), vid.max())
    assert not hasattr(repo, "_interner") and not hasattr(repo, "_cache")
    # exact reads survive every drain
    for k in (0, 17, n_keys - 1):
        out = _R()
        repo.apply(out, [b"GET", b"k%d" % k])
        want_ts = (rounds - 1) * n_keys + k + 1
        assert out.vals == [
            2,
            b"gen%d-val%d" % (rounds - 1, k),
            want_ts,
        ], out.vals
    # the snapshot dump reads the same host winner
    dump = dict(repo.dump_state())
    assert dump[b"k3"][0] == b"gen%d-val%d" % (rounds - 1, 3)


def test_tlog_ins_trim_churn_keeps_interner_flat():
    from jylis_tpu.models import repo_tlog as mod

    repo = mod.RepoTLOG(identity=1)
    r = _R()
    ts = 0
    keep = 4
    rounds, per_round, n_keys = 30, 64, 8  # ~15k distinct values churned
    for g in range(rounds):
        for k in range(n_keys):
            for i in range(per_round):
                ts += 1
                repo.apply(
                    r,
                    [b"INS", b"log%d" % k, b"g%d-e%d-%d" % (g, k, i), b"%d" % ts],
                )
        repo.drain()
        for k in range(n_keys):
            repo.apply(r, [b"TRIM", b"log%d" % k, b"%d" % keep])
    live = sum(repo._tbl.len_cache(r) for r in range(repo._tbl.rows()))
    assert live == keep * n_keys
    bound = 2 * live + mod.COMPACT_SLACK
    assert len(repo._interner) <= bound, len(repo._interner)
    # the kept entries render exactly (newest-first) after compactions
    out = _R()
    repo.apply(out, [b"GET", b"log0", b"%d" % keep])
    assert out.vals[0] == keep
    got = [out.vals[i + 1] for i in range(1, 3 * keep, 3)]
    want = [
        b"g%d-e0-%d" % (rounds - 1, i)
        for i in range(per_round - 1, per_round - 1 - keep, -1)
    ]
    assert got == want, (got, want)


def test_tlog_compaction_preserves_dump_state():
    from jylis_tpu.models import repo_tlog as mod

    repo = mod.RepoTLOG(identity=1)
    r = _R()
    # force a compaction epoch with a tiny slack
    old = mod.COMPACT_SLACK
    mod.COMPACT_SLACK = 8
    try:
        for i in range(64):
            repo.apply(r, [b"INS", b"log", b"old%d" % i, b"%d" % (i + 1)])
        repo.drain()
        repo.apply(r, [b"TRIM", b"log", b"2"])
        for i in range(64):
            repo.apply(r, [b"INS", b"log", b"new%d" % i, b"%d" % (100 + i)])
        repo.drain()  # compaction runs here (table >> live)
        dump = dict(repo.dump_state())
        entries, cutoff = dump[b"log"]
        values = {v for v, _ts in entries}
        assert b"new63" in values and b"old63" in values
        assert all(ts >= cutoff for _v, ts in entries)
    finally:
        mod.COMPACT_SLACK = old
