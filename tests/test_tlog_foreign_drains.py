"""`RepoTLOG` as a replica that takes no trims of its own (the cell
`ycsb-tlog-1kx1k-r3.e`): posts and cutoffs arrive through `converge`, as
a peer's pushes do, beside a few local INS, and nothing drains the log but
the table's bounds. After the boot's warming a drain is overdue once as
many entries are pending, over all rows, as ONE floor-shaped batch holds,
and a drain that outgrows the floor's shape runs as passes of it, so no
drain of a booted node runs a program that was not compiled ahead. Both
table backends, against the benchmark's plain reference."""

import numpy as np
import pytest

import jylis_tpu  # noqa: F401
from benchref import Replies, gen, tlog_reference
from jylis_tpu.models import repo_tlog
from jylis_tpu.models.repo_tlog import DRAIN_ROWS_FLOOR, DRAIN_WIDTH_FLOOR, RepoTLOG
from jylis_tpu.models.tlog_table import NO_ENTRIES_BOUND
from jylis_tpu.obs.registry import MetricsRegistry

ENGINES = ["auto", "python"]
KEYS = 96


class Replica:
    """A booted repo of 96 threads x 200 posts, its reference, and a record
    of every dispatch's shape."""

    def __init__(self, engine: str, seed: int, warm: bool = True):
        self.ref = tlog_reference(seed, keys=KEYS, entries=200)
        self.repo = repo = RepoTLOG(identity=1, mesh=None, engine=engine)
        repo.metrics = self.reg = MetricsRegistry()
        repo.load_state(self.ref.snapshot_batch())
        repo.drain()
        if warm:
            repo.warm_drain_shapes()
        self.rng = np.random.default_rng([seed, 0x7233])
        self.dist = gen.KeyDist({"dist": "zipfian", "theta": 0.99}, KEYS)
        self.i = 0
        self.shapes: list[tuple[int, int]] = []
        self.wire = Replies()
        self.sparse = sparse = repo_tlog._drain_tlog  # the jitted program itself

        def recorded(state, ki, d_ts, *rest):
            if ki[0] != repo_tlog.PAD_ROW:  # a drain, not the warm thread's all-pads compile call
                assert not warm or tuple(state.shape) in repo._warmed, "a drain on planes nobody compiled for"
                self.shapes.append(d_ts.shape)
            return sparse(state, ki, d_ts, *rest)

        self.patch = ("_drain_tlog", recorded)

    def tally(self, kind: str) -> int:
        return self.reg.tallies["drain.TLOG." + kind]

    def post(self, k: int):
        """One fresh (value, ts) for thread ``k``, the reference told."""
        self.i += 1
        i = self.i
        ts, nonce = gen.make_ts(i / 200.0, i, 64 + i % 6), (9 << 40) | i
        self.ref.apply("INS", np.array([k]), np.array([ts], np.uint64), np.array([nonce], np.uint64))
        return self.ref.values.make(nonce, self.ref.size), ts

    def cutoff(self, k: int) -> int:
        lo = int(self.ref.base_ts[k].min())
        cut = lo + int(self.rng.integers(1 << 44))
        self.ref.apply("TRIMAT", np.array([k]), np.array([cut], np.uint64), np.array([0], np.uint64))
        return cut

    def push(self, posts: int, cutoffs: int) -> None:
        """A peer's flush: ``posts`` posts on Zipfian threads coalesced per
        key, ``cutoffs`` cutoffs on uniform ones, through `converge`, then
        what `RepoManager.converge_async` does after a batch."""
        batch: dict[int, tuple[list, int]] = {}
        for k in self.dist.draw(self.rng, posts).tolist():
            batch.setdefault(k, ([], 0))[0].append(self.post(k))
        for k in self.rng.integers(0, KEYS, cutoffs).tolist():
            ents, cut = batch.get(k, ([], 0))
            batch[k] = (ents, max(cut, self.cutoff(k)))
        for k, delta in sorted(batch.items()):
            self.repo.converge(self.ref.key(k), delta)
        if self.repo.drain_overdue():
            self.repo.drain()

    def agrees(self) -> None:
        every = range(KEYS)
        got = [self.wire.call(self.repo, *self.ref.read_command(k)[1:]) for k in every]
        assert got == self.ref.expected(every)


@pytest.mark.parametrize("engine", ENGINES)
def test_a_replica_that_takes_no_trims_drains_at_its_bound_in_compiled_shapes(engine, monkeypatch):
    node = Replica(engine, 2**31 + 32)
    repo, tbl = node.repo, node.repo._tbl
    monkeypatch.setattr(repo_tlog, *node.patch)
    assert tbl.entries_bound == DRAIN_ROWS_FLOOR * DRAIN_WIDTH_FLOOR == 1024
    assert repo._warmed == {(1024, 512), (1024, 1024), (1024, 2048)}
    assert node.tally("overdue") == 0  # 200 posts a row: the restore's drain met no bound of a cold table
    batches0, passes0 = node.reg.counters["TLOG"]["batches"], node.tally("passes")
    entries0, foreign0 = node.tally("entries"), node.tally("foreign_entries")
    assert foreign0 == KEYS * 200 and node.tally("foreign_cutoffs") == 0
    dense0 = repo_tlog._drain_tlog_dense._cache_size()
    sparse0 = node.sparse._cache_size()
    local = 0
    for round_ in range(120):
        node.push(posts=40, cutoffs=2)
        assert tbl.pend_total() < 1024 + 40, "a push that crosses the bound is drained before the next"
        if repo._warming is not None:  # seconds pass between a cell's drains, none between these
            repo._warming.result(timeout=300)
        for k in node.dist.draw(node.rng, 8).tolist():  # the node's own posters
            value, ts = node.post(k)
            assert node.wire.call(repo, b"INS", node.ref.key(k), value, b"%d" % ts) == b"OK"
            local += 1
        k = int(node.dist.draw(node.rng, 1)[0])  # and a reader: never drains
        count = int(node.rng.integers(1, 101))
        assert node.wire.call(repo, b"GET", node.ref.key(k), b"%d" % count) == node.ref.expected([k])[0][:count]
    # less than a floor batch is left pending, no trim ran, every drain was overdue
    assert tbl.pend_total() < 1024 and not repo.drain_overdue()
    assert node.tally("trims") == 0 and node.tally("overdue") == node.reg.counters["TLOG"]["batches"] - batches0 >= 5
    assert node.tally("foreign_entries") - foreign0 == 120 * 40
    assert 120 <= node.tally("foreign_cutoffs") <= 240  # 240 sent; one below the key's view raises nothing
    # every dispatch ran the floor's shape on planes it was compiled for (at boot, or by the warm
    # thread once the hot row passed WARM_FILL of 1,024); no other program was compiled
    assert set(node.shapes) == {(DRAIN_ROWS_FLOOR, DRAIN_WIDTH_FLOOR)}
    assert node.sparse._cache_size() - sparse0 <= len(repo._warmed) - 3 <= 1  # (0 if this process had it)
    assert repo_tlog._drain_tlog_dense._cache_size() == dense0
    assert node.tally("passes") - passes0 == len(node.shapes) > 3 * node.tally("overdue"), "96 rows, a hot one among them"
    repo.drain()
    assert node.tally("entries") - entries0 == 120 * 40 + local
    assert node.tally("bases_lost") == 0 and node.tally("row_gathers") == 0
    node.agrees()


@pytest.mark.parametrize("engine", ENGINES)
def test_a_batch_larger_than_the_floor_runs_as_passes_of_the_floor(engine, monkeypatch):
    """A rejoining peer's batch: every thread at once, one of them with 70
    posts. 96 rows x 70 wide would be a (256, 256) program nobody compiled;
    it runs as two row chunks of the first 16 entries, then the one deep
    row alone, 16 at a time."""
    node = Replica(engine, 2**31 + 33)
    repo = node.repo
    monkeypatch.setattr(repo_tlog, *node.patch)
    batches0, passes0 = node.reg.counters["TLOG"]["batches"], node.tally("passes")
    sparse0 = node.sparse._cache_size()
    deep = 5
    batch = {k: ([node.post(k) for _ in range(70 if k == deep else 1 + k % 3)], node.cutoff(k) if k % 7 == 0 else 0)
             for k in range(KEYS)}
    for k, delta in batch.items():
        repo.converge(node.ref.key(k), delta)
    assert not repo.drain_overdue()  # under a floor batch's 1,024 entries: a snapshot's drain, then
    repo.drain()
    assert node.reg.counters["TLOG"]["batches"] == batches0 + 1 and node.tally("overdue") == 0
    assert node.tally("passes") - passes0 == len(node.shapes) == 2 + 4  # 96 rows; then 70 = 16 + 3 x 16 + 6
    assert set(node.shapes) == {(DRAIN_ROWS_FLOOR, DRAIN_WIDTH_FLOOR)}
    assert node.sparse._cache_size() == sparse0
    assert node.tally("bases_lost") == 0 and not repo.drain_overdue()
    node.agrees()
    # a TRIM by count of the deep row rides the LAST of its passes
    for _ in range(40):
        repo.converge(node.ref.key(deep), ([node.post(deep)], 0))
    before = len(node.wire.call(repo, b"GET", node.ref.key(deep)))
    del node.shapes[:]
    assert node.wire.call(repo, b"TRIM", node.ref.key(deep), b"100") == b"OK"
    assert len(node.shapes) == 3 and before >= 200 + 110
    assert len(node.wire.call(repo, b"GET", node.ref.key(deep))) == 100


def test_a_repo_with_nothing_compiled_ahead_has_no_entries_bound_and_drains_in_one_dispatch():
    node = Replica("python", 7, warm=False)
    repo, tbl = node.repo, node.repo._tbl
    assert tbl.entries_bound == NO_ENTRIES_BOUND
    for _ in range(40):
        node.push(posts=40, cutoffs=2)
    assert not repo.drain_overdue() and node.tally("overdue") == 0
    assert tbl.pend_total() == 1600 and max(tbl.pend_len(r) for r in range(KEYS)) > DRAIN_WIDTH_FLOOR
    passes0 = node.tally("passes")
    repo.drain()
    assert node.tally("passes") == passes0 + 1
    node.agrees()
