"""`RepoTREG.warm_drain_shapes`: after it, neither the threshold drain a
local SET trips (exactly PENDING_DRAIN_THRESHOLD rows) nor the larger one
a foreign batch causes when it lands on a nearly full pending window
(threshold + batch rows: the next bucket up) compiles a program, and the
warm-up itself leaves the state as it was."""

import pytest

import jylis_tpu  # noqa: F401
from jylis_tpu.models import repo_treg
from jylis_tpu.models.database import Database
from jylis_tpu.models.repo_treg import PENDING_DRAIN_THRESHOLD, RepoTREG

KEYS = 40000  # recovered keyspace: capacity 65,536, so both shapes drain sparse (DENSE_FRACTION)


class _Resp:
    def __getattr__(self, name):
        return lambda *a: None


@pytest.mark.parametrize("engine", ["auto", "python"])
def test_no_program_compiles_in_a_threshold_drain_after_the_warm_up(engine):
    repo = RepoTREG(identity=1, key_cap=1024, mesh=None, engine=engine)
    repo.load_state([(b"k%05d" % i, (b"base%d" % i, 10 + i)) for i in range(KEYS)])
    repo.warm_drain_shapes()  # drains what recovery buffered, then compiles
    assert repo._tbl.pend_count() == 0 and repo._key_cap == 65536
    before = {i: repo.sync_canon(b"k%05d" % i) for i in (0, 17, KEYS - 1)}
    compiled = repo_treg._drain._cache_size()

    # local SETs: the drain trips inside apply at exactly the threshold
    resp = _Resp()
    for i in range(PENDING_DRAIN_THRESHOLD):
        assert repo._tbl.pend_count() == i
        repo.apply(resp, [b"SET", b"k%05d" % i, b"local%d" % i, b"%d" % (10**6 + i)])
    assert repo._tbl.pend_count() == 0, "the threshold SET drained"
    assert repo_treg._drain._cache_size() == compiled

    # a foreign batch landing on a nearly full window: more than the threshold at once
    for i in range(PENDING_DRAIN_THRESHOLD - 1):
        repo.apply(resp, [b"SET", b"k%05d" % i, b"again%d" % i, b"%d" % (2 * 10**6 + i)])
    for i in range(PENDING_DRAIN_THRESHOLD - 1, PENDING_DRAIN_THRESHOLD + 700):
        repo.converge(b"k%05d" % i, (b"foreign%d" % i, 3 * 10**6 + i))
    assert repo.drain_overdue() and repo._tbl.pend_count() == PENDING_DRAIN_THRESHOLD + 700
    repo.drain()
    assert repo_treg._drain._cache_size() == compiled
    assert repo.sync_canon(b"k%05d" % (KEYS - 1)) == before[KEYS - 1]  # never written again
    assert repo.sync_canon(b"k00017") == repr((2 * 10**6 + 17, b"again17")).encode()


def test_a_capacity_that_drains_dense_compiles_no_sparse_program():
    repo = RepoTREG(identity=1, key_cap=1024, mesh=None, engine="python")
    repo.load_state([(b"k%05d" % i, (b"v", 10 + i)) for i in range(5000)])
    compiled = repo_treg._drain._cache_size()
    repo.warm_drain_shapes()
    assert repo._key_cap == 8192 and repo._tbl.pend_count() == 0
    assert repo_treg._drain._cache_size() == compiled


def test_the_warm_up_changes_no_state_and_every_repo_takes_the_call():
    db = Database(identity=7)
    db.converge_deltas(("TREG", [(b"a", (b"v", 5)), (b"b", (b"w", 6))]))
    digest = db.manager("TREG").repo.sync_canon(b"a"), db.manager("TREG").repo.sync_canon(b"b")
    db.warm_drain_shapes()
    repo = db.manager("TREG").repo
    assert (repo.sync_canon(b"a"), repo.sync_canon(b"b")) == digest
    if repo._mesh is None:  # (the suite's 8 virtual devices serve from a mesh: left alone)
        assert int(repo._state.ts_lo[repo._tbl.find(b"a")]) == 5  # drained: the device mirror holds it
