"""`RepoTLOG.warm_drain_shapes` and the compile-ahead: after the boot's
warming, a seeded run of the cell's mix (`ycsb-tlog-1kx1k.e`: GET with a
count, INS, TRIMAT, Zipfian keys; the INS share raised so that the hot row
outgrows len_cap twice inside a test) compiles nothing on the serving
path, every `grow` lands on a plane shape whose programs were compiled
before it (the boot compiles its width and the next two), and the programs
of a len_cap beyond those are compiled on the warm thread once the longest
row passes WARM_FILL of the width before it."""

import numpy as np
import pytest

import jylis_tpu  # noqa: F401
from benchref import Replies, gen, tlog_reference
from jylis_tpu.models import repo_tlog
from jylis_tpu.models.database import Database
from jylis_tpu.models.repo_tlog import (
    DRAIN_ROWS_FLOOR, DRAIN_WIDTH_FLOOR, WARM_FILL, WARM_MIN_LEN, RepoTLOG, drain_bucket)

PROGRAMS = ("_drain_tlog", "_drain_tlog_dense", "_get_row_tlog", "_grow_tlog")


def compiled() -> dict[str, int]:
    return {name: getattr(repo_tlog, name)._cache_size() for name in PROGRAMS}


class Mix:
    """The cell's three commands on a booted repo, from a seed."""

    def __init__(self, engine: str, seed: int):
        self.ref = tlog_reference(seed, keys=64, entries=200)
        self.repo = RepoTLOG(identity=1, mesh=None, engine=engine)
        self.repo.load_state(self.ref.snapshot_batch())
        self.wire = Replies()
        self.rng = np.random.default_rng([seed, 0x77])
        self.dist = gen.KeyDist({"dist": "zipfian", "theta": 0.99}, 64)
        self.i = 0
        self.grown_to: list[tuple[int, int]] = []

    def boot(self) -> None:
        """The boot's warming; from here on every grow must find its shape prepared."""
        self.repo.warm_drain_shapes()
        grow = self.repo._grow

        def checked(key_cap, len_cap):
            assert (key_cap, len_cap) in self.repo._warmed, "a grow onto a shape nobody prepared"
            self.grown_to.append((key_cap, len_cap))
            grow(key_cap, len_cap)

        self.repo._grow = checked

    def run_until(self, longest: int) -> None:
        repo, ref, rng = self.repo, self.ref, self.rng
        while repo._longest < longest:
            self.i += 1
            i, k = self.i, int(self.dist.draw(rng, 1)[0])
            key = ref.key(k)
            if i % 20 == 0:  # the sweeper: a drain of what the last 19 commands wrote
                lo = int(ref.base_ts.min())
                self.wire.call(repo, b"TRIMAT", key, b"%d" % (lo + int(rng.integers(1 << 40))))
            elif i % 4 == 0:
                count = int(rng.integers(1, 101))
                assert len(self.wire.call(repo, b"GET", key, b"%d" % count)) == count
            else:
                ts = gen.make_ts(i / 1000.0, i, i % 64)
                self.wire.call(repo, b"INS", key, ref.values.make(i, 48), b"%d" % ts)
            assert i < 40000, "the hot row stopped growing"


@pytest.mark.parametrize("engine", ["auto", "python"])
def test_nothing_compiles_while_serving_after_the_warm_up(engine):
    mix = Mix(engine, 2**31 + 5)
    repo = mix.repo
    mix.boot()  # drains what recovery buffered; 200 of 256 slots: regrows, then compiles
    assert repo._len_cap == 512 and repo._longest == 200
    boot = {(1024, 512), (1024, 1024), (1024, 2048)}
    assert repo._warmed == boot and mix.grown_to == []
    ready = compiled()

    # to the first grow (a row passes 512), then past WARM_FILL of the new
    # width: the boot compiled the width after it too, no warm thread starts
    mix.run_until(int(WARM_FILL * 1024) + 8)
    assert repo._len_cap == 1024 and mix.grown_to == [(1024, 1024)]
    assert compiled() == ready, "a program compiled with clients waiting"
    assert repo._warming is None and repo._warmed == boot

    # the second grow meets its programs ready
    mix.run_until(1100)
    assert repo._len_cap == 2048 and mix.grown_to == [(1024, 1024), (1024, 2048)]
    assert compiled() == ready and repo._warming is None

    # past WARM_FILL of 2,048: the next width's programs compile on the warm thread
    mix.run_until(int(WARM_FILL * 2048) + 8)
    assert repo._warming is not None
    repo._warming.result(timeout=300)
    assert (1024, 4096) in repo._warmed
    ahead = compiled()
    # (one more drain program, unless an earlier test of this process left it compiled)
    assert ahead["_drain_tlog"] - ready["_drain_tlog"] in (0, 1)
    assert ahead["_drain_tlog_dense"] == ready["_drain_tlog_dense"]
    reg = repo_tlog.resolve_registry(repo)
    assert reg.tallies["drain.TLOG.grows"] >= 3  # the boot's, and the two above
    hot = max(range(64), key=lambda k: repo._tbl.len_cache(repo._tbl.find(mix.ref.key(k))))
    assert len(mix.wire.call(repo, b"GET", mix.ref.key(hot))) >= 1536


def test_a_young_keyspace_and_the_mesh_are_left_to_their_first_drain():
    repo = RepoTLOG(identity=1, mesh=None, engine="python")
    repo.load_state([(b"k%d" % i, ([(b"v%d" % j, 10 + j) for j in range(40)], 0)) for i in range(8)])
    before = compiled()
    repo.warm_drain_shapes()
    assert repo._len_cap == 64 < WARM_MIN_LEN and repo._warmed == set()
    after = compiled()
    assert after["_get_row_tlog"] == before["_get_row_tlog"]
    assert after["_drain_tlog"] <= before["_drain_tlog"] + 1  # the boot drain's own, no more
    db = Database(identity=7)  # the suite's 8 virtual devices serve from a mesh
    db.converge_deltas(("TLOG", [(b"a", ([(b"v", 5)], 0))]))
    db.warm_drain_shapes()
    repo = db.manager("TLOG").repo
    assert repo.sync_canon(b"a") == repr(([(5, b"v")], 0)).encode()
    assert repo._mesh is None or repo._warmed == set()


@pytest.mark.parametrize("n,floor,want", [
    (0, DRAIN_ROWS_FLOOR, 64), (1, DRAIN_ROWS_FLOOR, 64), (64, DRAIN_ROWS_FLOOR, 64),
    (65, DRAIN_ROWS_FLOOR, 256), (1000, DRAIN_ROWS_FLOOR, 1024), (1025, DRAIN_ROWS_FLOOR, 4096),
    (1, DRAIN_WIDTH_FLOOR, 16), (16, DRAIN_WIDTH_FLOOR, 16), (17, DRAIN_WIDTH_FLOOR, 64),
    (1000, DRAIN_WIDTH_FLOOR, 1024), (1024, DRAIN_WIDTH_FLOOR, 1024),
])
def test_the_drain_lattice_has_a_floor_and_steps_of_four(n, floor, want):
    assert drain_bucket(n, floor) == want
