"""The counters' foreign window (models/counter_table.py, native/
counter_engine.cpp): a slice of foreign deltas folds in by one table call
and a drain's batch leaves as one ready `[P | N]` matrix. Every case runs
on both backends against a per-key dict fold written here, the plain form
of what the window replaced."""

import asyncio

import numpy as np
import pytest

import jylis_tpu  # noqa: F401
from jylis_tpu import persist
from jylis_tpu.models import repo_counters
from jylis_tpu.models.database import Database
from jylis_tpu.models.manager import RepoManager
from jylis_tpu.models.repo_counters import RepoGCOUNT, RepoPNCOUNT
from jylis_tpu.native.engine import make_engine
from jylis_tpu.obs.registry import MetricsRegistry
from jylis_tpu.ops import planes

U64 = (1 << 64) - 1
ME = 5

NATIVE = make_engine() is not None
ENGINES = ["python", "auto"] if NATIVE else ["python"]
TYPES = [RepoGCOUNT, RepoPNCOUNT]
both = pytest.mark.parametrize(
    "engine",
    [
        "python",
        pytest.param(
            "auto",
            marks=pytest.mark.skipif(not NATIVE, reason="native engine unavailable"),
        ),
    ],
)
types = pytest.mark.parametrize("cls", TYPES)
# the tests' platform shows 8 devices, so "auto" is the mesh branch of a
# drain and None the one-device branches (sparse, dense)
meshes = pytest.mark.parametrize("mesh", ["auto", None], ids=["mesh", "one"])


class R:
    def __init__(self):
        self.vals = []

    def __getattr__(self, name):
        return lambda *a: self.vals.extend(a)


class Fold:
    """Per key, per polarity, {rid: max}: the dict fold a converge is,
    with this node's own INC/DEC totals beside it."""

    def __init__(self, cls):
        self.pn = cls is RepoPNCOUNT
        self.cols: dict[bytes, tuple[dict, dict]] = {}
        self.own: dict[bytes, list] = {}

    def converge(self, key, delta):
        cur = self.cols.setdefault(key, ({}, {}))
        for pol, d in enumerate(delta if self.pn else (delta,)):
            for rid, v in d.items():
                cur[pol][rid] = max(cur[pol].get(rid, 0), v)

    def inc(self, key, pol, amount):
        own = self.own.setdefault(key, [None, None])
        own[pol] = ((own[pol] or 0) + amount) & U64

    def columns(self, key):
        """The joined columns: what the device and the digest hold."""
        out = []
        for pol in (0, 1):
            d = dict(self.cols.get(key, ({}, {}))[pol])
            own = self.own.get(key, [None, None])[pol]
            if own is not None and own > d.get(ME, 0):
                d[ME] = own
            out.append(d)
        return out

    def value(self, key):
        p, n = self.columns(key)
        v = (sum(p.values()) - sum(n.values())) & U64
        if self.pn and v >= 1 << 63:
            v -= 1 << 64
        return v

    def canon(self, key):
        """`sync_canon`'s bytes as the parent spelt them."""
        p, n = (sorted((r, v) for r, v in d.items() if v) for d in self.columns(key))
        if self.pn:
            return repr((p, n)).encode() if p or n else None
        return repr(p).encode() if p else None


def get(repo, key):
    r = R()
    repo.apply(r, [b"GET", key])
    return r.vals[0]


def plane(repo):
    """The device plane as u64 columns, by (key, replica id)."""
    cells = planes.unpack64_np(np.asarray(repo._state))
    out = {}
    for row in range(repo._tbl.rows()):
        for pol in range(repo._npol):
            for col, rid in enumerate(repo._rid_of):
                v = int(cells[row, pol * repo._rep_cap + col])
                if v:
                    out[repo._tbl.key_of(row), pol, rid] = v
    return out


def want_plane(fold, keys):
    return {
        (k, pol, rid): v
        for k in keys
        for pol, d in enumerate(fold.columns(k))
        for rid, v in d.items()
        if v
    }


def delta_for(cls, dp, dn):
    return (dp, dn) if cls is RepoPNCOUNT else dp


def random_slices(cls, seed, n_slices=6, n_keys=24):
    rng = np.random.default_rng(seed)
    keys = [b"k%d" % i for i in range(n_keys)]
    rids = [3, 4, ME, 9, (1 << 63) + 11]
    big = [1 << 63, U64, (1 << 63) - 1, (1 << 64) - 2]
    for _ in range(n_slices):
        batch = []
        for _ in range(int(rng.integers(1, 40))):
            key = keys[int(rng.integers(n_keys))]  # repeats within a slice
            cols = []
            for _pol in (0, 1):
                d = {}
                for _ in range(int(rng.integers(0, 4))):  # 0: an empty polarity
                    v = big[int(rng.integers(4))] if rng.integers(8) == 0 else int(
                        rng.integers(0, 1 << 40)
                    )
                    d[rids[int(rng.integers(len(rids)))]] = v
                cols.append(d)
            batch.append((key, delta_for(cls, *cols)))
        yield batch


@both
@types
@meshes
@pytest.mark.parametrize("seed", [0, 1, 2])
def test_random_slices_match_the_dict_fold(cls, engine, mesh, seed):
    repo = cls(identity=ME, engine=engine, mesh=mesh)
    fold = Fold(cls)
    rng = np.random.default_rng([seed, 7])
    for batch in random_slices(cls, seed):
        repo.converge_batch(batch)
        for key, delta in batch:
            fold.converge(key, delta)
        if rng.integers(2):
            repo.drain()
    keys = sorted(fold.cols)
    for k in keys:
        assert repo.sync_canon(k) == fold.canon(k), k  # before any drain of it
        assert get(repo, k) == fold.value(k), k
    assert plane(repo) == want_plane(fold, keys)


@both
@types
def test_the_one_key_form_is_the_same_fold(cls, engine):
    one, many = cls(identity=ME, engine=engine), cls(identity=ME, engine=engine)
    for batch in random_slices(cls, 11):
        many.converge_batch(batch)
        for key, delta in batch:
            one.converge(key, delta)
    one.drain(), many.drain()
    assert plane(one) == plane(many)
    assert one.dump_state() == many.dump_state()
    assert sorted(one.sync_dirty_keys()) == sorted(many.sync_dirty_keys())


@both
@types
def test_an_echo_of_the_own_column_joins_the_own_contribution(cls, engine):
    repo = cls(identity=ME, engine=engine)
    fold = Fold(cls)
    repo.apply(R(), [b"INC", b"k", b"10"])
    fold.inc(b"k", 0, 10)
    for echoed in (7, 50):  # below the own value, then above it
        d = delta_for(cls, {ME: echoed, 3: 1}, {})
        repo.converge_batch([(b"k", d)])
        fold.converge(b"k", d)
        assert repo.sync_canon(b"k") == fold.canon(b"k")
        assert get(repo, b"k") == fold.value(b"k")
    assert plane(repo) == want_plane(fold, [b"k"])
    # the own column a flush ships is the own contribution, not the echo
    flushed = dict(repo.flush_deltas())[b"k"]
    assert (flushed[0] if cls is RepoPNCOUNT else flushed) == {ME: 10}


@both
@types
@meshes
def test_keys_and_replica_ids_that_outgrow_the_plane_between_two_drains(cls, engine, mesh):
    repo = cls(identity=ME, engine=engine, mesh=mesh, key_cap=16, rep_cap=4)
    fold = Fold(cls)

    def push(keys, rids):
        batch = [
            (b"g%d" % i, delta_for(cls, {r: i + r for r in rids}, {rids[0]: i + 1}))
            for i in keys
        ]
        repo.converge_batch(batch)
        for key, delta in batch:
            fold.converge(key, delta)

    push(range(4), [1, 2])
    repo.drain()
    assert (repo._key_cap, repo._rep_cap) == (16, 4)
    push(range(2, 70), [1, 2, 3, 4, 6, 7, 8, 9, 10])  # 70 keys, 9 ids (+ own)
    repo.apply(R(), [b"INC", b"g1", b"5"])
    fold.inc(b"g1", 0, 5)
    repo.drain()
    assert repo._key_cap >= 70 and repo._rep_cap >= 10
    keys = sorted(fold.cols)
    assert plane(repo) == want_plane(fold, keys)
    for k in keys:
        assert get(repo, k) == fold.value(k), k


@both
@types
def test_dense_and_sparse_drains_give_the_same_plane_and_values(cls, engine):
    dense = cls(identity=ME, engine=engine, mesh=None, key_cap=64)
    sparse = cls(identity=ME, engine=engine, mesh=None, key_cap=4096)
    ran = {"dense": 0, "sparse": 0}

    def count(repo, which):
        real = getattr(repo, "_drain_" + which)

        def run(*a):
            ran[which] += 1
            return real(*a)

        setattr(repo, "_drain_" + which, run)

    for repo in (dense, sparse):
        count(repo, "dense"), count(repo, "sparse")
    fold = Fold(cls)
    for batch in random_slices(cls, 5, n_slices=3, n_keys=40):
        for repo in (dense, sparse):
            repo.converge_batch(batch)
        for key, delta in batch:
            fold.converge(key, delta)
    assert dense._tbl.drain_count() * repo_counters.DENSE_FRACTION >= dense._key_cap
    dense.drain(), sparse.drain()
    assert ran == {"dense": 1, "sparse": 1}
    keys = sorted(fold.cols)
    assert plane(dense) == plane(sparse) == want_plane(fold, keys)
    for k in keys:
        assert get(dense, k) == get(sparse, k) == fold.value(k), k


@both
@types
def test_a_drain_whose_device_call_raises_once_loses_nothing(cls, engine):
    repo = cls(identity=ME, engine=engine, mesh=None)
    fold = Fold(cls)
    repo.apply(R(), [b"INC", b"own", b"3"])
    fold.inc(b"own", 0, 3)
    batch = next(random_slices(cls, 3))
    repo.converge_batch(batch)
    for key, delta in batch:
        fold.converge(key, delta)
    real, calls = repo._drain_sparse, []

    def flaky(*a):
        calls.append(1)
        if len(calls) == 1:
            raise RuntimeError("device lost")
        return real(*a)

    repo._drain_sparse = flaky
    owed = repo._tbl.drain_count()
    with pytest.raises(RuntimeError, match="device lost"):
        repo.drain()
    assert repo._tbl.drain_count() == owed  # the window is whole
    assert repo.may_drain([b"GET", batch[0][0]])
    keys = sorted({*fold.cols, b"own"})
    for k in keys:  # the first GET of a foreign row is the retry
        assert get(repo, k) == fold.value(k), k
    assert len(calls) == 2 and repo._tbl.drain_count() == 0
    assert plane(repo) == want_plane(fold, keys)


def _canons(repo, keys):
    return {k: repo.sync_canon(k) for k in keys}


@types
def test_sync_canon_is_the_parents_bytes_on_both_backends(cls, tmp_path):
    """After converge, after `load_state`, and after a restart from a
    snapshot file: the digest's canon bytes are those of the dict fold."""
    name = cls.name
    fold = Fold(cls)
    dbs = [Database(identity=ME, engine=e) for e in ENGINES]
    for batch in random_slices(cls, 21):
        for db in dbs:
            db.converge_deltas((name, batch))
        for key, delta in batch:
            fold.converge(key, delta)
    for db in dbs:  # own writes too, so the own column is in the canon
        db.manager(name).repo.apply(R(), [b"INC", b"k1", b"77"])
    fold.inc(b"k1", 0, 77)
    keys = sorted(fold.cols)
    want = {k: fold.canon(k) for k in keys}
    for db in dbs:
        assert _canons(db.manager(name).repo, keys) == want
    dumps = [db.manager(name).repo.dump_state() for db in dbs]
    assert all(d == dumps[0] for d in dumps)
    for i, (engine, db) in enumerate(zip(ENGINES, dbs)):
        # load_state: a restore adopts the own column as the own contribution
        fresh = cls(identity=ME, engine=engine)
        fresh.load_state(dumps[0])
        assert _canons(fresh, keys) == want
        fresh.apply(R(), [b"INC", b"k1", b"1"])  # on top of the adopted own value
        own_p, own_n = (d.get(ME) for d in fold.columns(b"k1"))
        assert dict(fresh.flush_deltas())[b"k1"] == delta_for(
            cls, {ME: own_p + 1}, {ME: own_n} if own_n else {}
        )
        # a restart from a snapshot file
        path = str(tmp_path / f"snap{i}.jylis")
        persist.save_snapshot(db, path)
        again = Database(identity=ME, engine=engine)
        persist.load_snapshot(again, path)
        repo = again.manager(name).repo
        assert _canons(repo, keys) == want
        assert repo._tbl.drain_count() == 0  # the restore drained
        for k in keys:
            assert get(repo, k) == fold.value(k), k


@both
@types
def test_tallies_count_keys_and_cells_a_slice(cls, engine):
    repo = cls(identity=ME, engine=engine)
    repo.metrics = reg = MetricsRegistry()
    t = f"drain.{cls.name}."
    batch = [
        (b"a", delta_for(cls, {1: 5, 2: 6}, {1: 1})),
        (b"a", delta_for(cls, {1: 9}, {})),
        (b"b", delta_for(cls, {}, {})),
    ]
    cells = 4 if cls is RepoPNCOUNT else 3
    repo.converge_batch(batch)
    assert [reg.tallies[t + k] for k in ("converged_keys", "batched_keys", "foreign_cells")] == [3, 3, cells]
    repo.converge(b"c", delta_for(cls, {3: 1}, {}))
    assert [reg.tallies[t + k] for k in ("converged_keys", "batched_keys", "foreign_cells")] == [4, 3, cells + 1]
    assert repo.may_drain([b"GET", b"b"])  # an empty delta still marks its row


@both
@types
def test_the_manager_hands_a_counter_repo_the_slice(cls, engine):
    repo = cls(identity=ME, engine=engine)
    slices = []
    real = repo.converge_batch
    repo.converge_batch = lambda batch: (slices.append(len(batch)), real(batch))
    mgr = RepoManager(cls.name, repo, None)
    n = 2 * RepoManager.CONVERGE_SLICE + 10
    batch = [(b"m%d" % i, delta_for(cls, {3: i + 1}, {})) for i in range(n)]
    asyncio.run(mgr.converge_async(batch))
    assert slices == [256, 256, 10]
    assert get(repo, b"m7") == 8 and get(repo, b"m%d" % (n - 1)) == n


@both
@types
@pytest.mark.parametrize("order", ["wire", "reversed"])
def test_a_decoded_push_folds_from_the_arrays_its_lazy_deltas_bank(cls, engine, order):
    """The native decode's deltas are lazy views over one pair of arrays
    (native/codec.py): a slice of them, consecutive, folds from those
    arrays with no dict built; out of order, from the dicts they denote."""
    from jylis_tpu.cluster import codec
    from jylis_tpu.cluster.msg import MsgPushDeltas
    from jylis_tpu.native import codec as ncodec

    sent = [b for batch in random_slices(cls, 31) for b in batch]
    got = list(codec.decode(codec.encode(MsgPushDeltas(cls.name, tuple(sent)))).batch)
    assert got == sent
    lazy = ncodec.flatten_lazy([d for _k, d in got[:7]])
    if ncodec.lib() is not None:
        counts, rids, vals = lazy
        assert len(counts) == 7 * (2 if cls is RepoPNCOUNT else 1)
        assert sum(counts) == len(rids) == len(vals)
        assert ncodec.flatten_lazy([d for _k, d in got[6::-1]]) is None
    if order == "reversed":
        got.reverse()
    repo, fold = cls(identity=ME, engine=engine), Fold(cls)
    for i in range(0, len(got), 16):
        repo.converge_batch(got[i : i + 16])
    for key, delta in sent:
        fold.converge(key, delta)
    keys = sorted(fold.cols)
    for k in keys:
        assert repo.sync_canon(k) == fold.canon(k), k
        assert get(repo, k) == fold.value(k), k
    assert plane(repo) == want_plane(fold, keys)
