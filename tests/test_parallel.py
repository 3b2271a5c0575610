"""Sharded merge-path tests on the virtual 8-device CPU mesh.

conftest.py forces 8 virtual CPU devices — the same environment the
driver's dryrun_multichip uses — so these tests validate that the
multi-chip shardings compile and execute without real chips.
"""

import jax
import numpy as np
import pytest

import jylis_tpu  # noqa: F401
from jylis_tpu.ops import planes
from jylis_tpu.parallel import (
    converge_sharded,
    join_replica_axis,
    make_mesh,
    read_all_sharded,
    route_batch,
    shard_plane,
)


def test_mesh_shapes():
    mesh = make_mesh(8)
    assert mesh.devices.shape == (1, 8)
    mesh2 = make_mesh(8, rep=4)
    assert mesh2.devices.shape == (4, 2)
    with pytest.raises(ValueError):
        make_mesh(8, rep=3)
    with pytest.raises(ValueError):
        make_mesh(1000)


def test_route_batch_blocks_pads_and_coalesces():
    rows = np.array([0, 5, 17, 18, 33, 5], np.int32)  # 5 duplicated
    deltas = np.arange(6 * 2, dtype=np.uint64).reshape(6, 2)
    local_rows, d = route_batch(rows, deltas, n_shards=4, rows_per_shard=16)
    lr = local_rows.reshape(4, -1)
    assert lr.shape[1] == 2  # padded to the max shard load
    assert list(lr[0]) == [0, 5]
    assert list(lr[1]) == [1, 2]
    assert lr[2][0] == 1
    # pad slots: far out of range AND unique within each shard's slice, so
    # the device-side unique_indices hint stays honest
    assert all(p > 1 << 20 for p in (lr[2][1], lr[3][0], lr[3][1]))
    for shard in lr:
        assert len(set(map(int, shard))) == len(shard)
    # duplicate row 5 max-combined: deltas[1]=[2,3], deltas[5]=[10,11]
    dl = planes.unpack64_np(d).reshape(4, 2, 2)
    np.testing.assert_array_equal(dl[0, 1], [10, 11])


def test_sharded_converge_matches_single_chip():
    rng = np.random.default_rng(0)
    K, R, B = 128, 8, 64
    n = 8
    mesh = make_mesh(n)
    reference = np.zeros((K, R), np.uint64)
    cells = shard_plane(mesh, np.zeros((K, 2 * R), np.uint32))
    for _ in range(3):
        rows = rng.integers(0, K, B).astype(np.int32)
        deltas = rng.integers(0, 1 << 48, (B, R)).astype(np.uint64)
        np.maximum.at(reference, rows, deltas)
        lr, d = route_batch(rows, deltas, n, K // n)
        cells = converge_sharded(mesh, cells, lr, d)
    got = planes.unpack64_np(jax.device_get(cells))
    np.testing.assert_array_equal(got, reference)
    sums = np.asarray(jax.device_get(read_all_sharded(mesh, cells)))
    np.testing.assert_array_equal(sums, reference.sum(axis=1, dtype=np.uint64))


@pytest.mark.parametrize("kind", ["g", "pn"])
def test_sharded_drain_matches_single_chip_at_cell_widths(kind):
    """The mesh drain and the one-chip drain on the north star's own widths
    (64 replica ids: a GCOUNT row is one 128-lane tile, a PNCOUNT row two)
    with 63-bit values: same plane, same per-row values, bit for bit."""
    from jylis_tpu.models.base import pad_rows
    from jylis_tpu.models.repo_counters import _drain_g, _drain_pn
    from jylis_tpu.ops import gcount, pncount
    from jylis_tpu.parallel import drain_sharded_g, drain_sharded_pn, route_drain64

    ops, one_chip, sharded = (
        (pncount, _drain_pn, drain_sharded_pn)
        if kind == "pn"
        else (gcount, _drain_g, drain_sharded_g)
    )
    rng = np.random.default_rng(29)
    K, R, B, n = 256, 64, 100, 8
    C = 2 * R if kind == "pn" else R
    mesh = make_mesh(n)
    single = ops.init(K, R)
    cells = shard_plane(mesh, np.zeros(single.shape, np.uint32))
    for _ in range(2):
        rows = np.concatenate([[0, K - 1], 1 + rng.permutation(K - 2)[: B - 2]])
        deltas = rng.integers(1 << 53, 1 << 63, (B, C), dtype=np.uint64)
        ki = pad_rows(128)
        ki[:B] = rows
        padded = np.zeros((128, C), np.uint64)
        padded[:B] = deltas
        single, want = one_chip(single, ki, planes.pack64_np(padded))
        lr, payload, slots = route_drain64(rows, deltas, n, K // n)
        cells, sums = sharded(mesh, cells, lr, planes.pack64_np(payload))
        np.testing.assert_array_equal(jax.device_get(cells), np.asarray(single))
        by_row = dict(zip(rows.tolist(), np.asarray(want)[:B].tolist()))
        sums = np.asarray(jax.device_get(sums))
        live = slots >= 0
        assert live.sum() == B
        assert sums[live].tolist() == [by_row[g] for g in slots[live].tolist()]


class _R:
    """Minimal resp sink for driving repos directly."""

    def __init__(self):
        self.vals = []

    def u64(self, v):
        self.vals.append(v)

    def i64(self, v):
        self.vals.append(v)

    def ok(self):
        pass


def test_serving_repos_auto_shard_disjoint_key_blocks():
    """Under the 8-device harness the counter repos serve keys-sharded:
    each device owns a disjoint, contiguous block of key rows covering the
    whole keyspace (VERDICT round-1 item 2)."""
    from jylis_tpu.models.repo_counters import RepoGCOUNT

    repo = RepoGCOUNT(identity=7)
    assert repo._mesh is not None and repo._n_shards == 8
    k = repo._key_cap
    blocks = []
    for shard in repo._state.addressable_shards:
        (rows, cols) = shard.index
        blocks.append((rows.start or 0, rows.stop if rows.stop else k))
        assert cols == slice(None) or (cols.start or 0) == 0  # all replica cols resident
    blocks.sort()
    assert blocks[0][0] == 0 and blocks[-1][1] == k
    for (a0, a1), (b0, b1) in zip(blocks, blocks[1:]):
        assert a1 == b0  # contiguous, non-overlapping
    assert len({b[0] for b in blocks}) == 8


def test_sharded_engine_convergence_two_nodes():
    """Two engine repos (different identities), both in mesh mode, exchange
    flushed deltas and converge to identical values — the reference's
    anti-entropy round (repo_gcount.pony:25-60) on the sharded path."""
    from jylis_tpu.models.repo_counters import RepoGCOUNT, RepoPNCOUNT

    a, b = RepoGCOUNT(identity=1), RepoGCOUNT(identity=2)
    rng = np.random.default_rng(3)
    keys = [b"k%d" % i for i in range(300)]  # > one shard block's worth
    model = {k: 0 for k in keys}
    for repo in (a, b):
        for k in keys:
            amt = int(rng.integers(1, 1000))
            repo.apply(_R(), [b"INC", k, str(amt).encode()])
            model[k] += amt
    for src, dst in ((a, b), (b, a)):
        for key, delta in src.flush_deltas():
            dst.converge(key, delta)
    for repo in (a, b):
        for k in keys:
            r = _R()
            repo.apply(r, [b"GET", k])
            assert r.vals == [model[k]], k

    pa, pb = RepoPNCOUNT(identity=1), RepoPNCOUNT(identity=2)
    pmodel = {k: 0 for k in keys}
    for repo in (pa, pb):
        for k in keys:
            amt = int(rng.integers(1, 1000))
            op = b"INC" if rng.integers(2) else b"DEC"
            repo.apply(_R(), [op, k, str(amt).encode()])
            pmodel[k] += amt if op == b"INC" else -amt
    for src, dst in ((pa, pb), (pb, pa)):
        for key, delta in src.flush_deltas():
            dst.converge(key, delta)
    for repo in (pa, pb):
        for k in keys:
            r = _R()
            repo.apply(r, [b"GET", k])
            assert r.vals == [pmodel[k]], k


def test_sharded_repo_grows_past_initial_capacity():
    """Growth re-places the planes sharded and keeps values intact."""
    from jylis_tpu.models.repo_counters import RepoGCOUNT

    repo = RepoGCOUNT(identity=5, key_cap=16)
    n = 200  # forces several grows past 16
    for i in range(n):
        repo.apply(_R(), [b"INC", b"g%d" % i, b"%d" % (i + 1)])
    # foreign deltas force a real sharded drain
    repo.converge(b"g0", {99: 7})
    repo.drain()
    assert repo._state.shape[0] >= n
    assert len(repo._state.addressable_shards) == 8
    for i in range(n):
        r = _R()
        repo.apply(r, [b"GET", b"g%d" % i])
        assert r.vals == [(i + 1) + (7 if i == 0 else 0)]


def test_sharded_treg_convergence_and_ties():
    """TREG in mesh mode: two repos exchange deltas and agree, including
    a same-timestamp value tie that the host must resolve by string order
    (docs treg.md:56-63) through the routed patch scatter."""
    from jylis_tpu.models.repo_treg import RepoTREG

    class _T:
        def __init__(self):
            self.out = []

        def ok(self):
            pass

        def null(self):
            self.out.append(None)

        def array_start(self, n):
            pass

        def string(self, s):
            self.out.append(s)

        def u64(self, v):
            self.out.append(v)

    a, b = RepoTREG(identity=1), RepoTREG(identity=2)
    assert a._mesh is not None and a._n_shards == 8
    assert len(a._state.vid.addressable_shards) == 8
    rng = np.random.default_rng(5)
    keys = [b"r%d" % i for i in range(200)]
    model: dict[bytes, tuple[int, bytes]] = {}
    for repo in (a, b):
        for k in keys:
            ts = int(rng.integers(1, 1000))
            val = b"v%d" % rng.integers(100)
            repo.apply(_T(), [b"SET", k, val, str(ts).encode()])
            cur = model.get(k)
            if cur is None or (ts, val) > cur:
                model[k] = (ts, val)
    # a tie: same ts, different values -> larger string wins on both nodes
    a.apply(_T(), [b"SET", b"tie", b"apple", b"777"])
    b.apply(_T(), [b"SET", b"tie", b"zebra", b"777"])
    model[b"tie"] = (777, b"zebra")
    for src, dst in ((a, b), (b, a)):
        for key, delta in src.flush_deltas():
            dst.converge(key, delta)
    for repo in (a, b):
        for k in keys + [b"tie"]:
            t = _T()
            repo.apply(t, [b"GET", k])
            want_ts, want_val = model[k]
            assert t.out == [want_val, want_ts], (k, t.out)


def test_sharded_tlog_convergence_trim_and_overflow():
    """TLOG in mesh mode: cross-node log convergence, TRIM through the
    routed trim kernel, and the overflow-retry grow path."""
    from jylis_tpu.models.repo_tlog import RepoTLOG

    class _T:
        def __init__(self):
            self.out = []

        def ok(self):
            pass

        def array_start(self, n):
            self.out.append(("arr", n))

        def string(self, s):
            self.out.append(s)

        def u64(self, v):
            self.out.append(v)

    a, b = RepoTLOG(identity=1, len_cap=4), RepoTLOG(identity=2, len_cap=4)
    assert a._mesh is not None
    assert a._state.wide  # mesh states use the fixed 3-plane layout
    assert len(a._state.ntl.addressable_shards) == 8
    keys = [b"log%d" % i for i in range(40)]
    for repo, base in ((a, 0), (b, 1000)):
        for k in keys:
            for t in range(6):  # 6 entries > len_cap 4: exercises grow
                repo.apply(_T(), [b"INS", k, b"e%d" % (base + t), b"%d" % (base + t + 1)])
    for src, dst in ((a, b), (b, a)):
        for key, delta in src.flush_deltas():
            dst.converge(key, delta)
    for k in keys:
        ra, rb = _T(), _T()
        a.apply(ra, [b"GET", k])
        b.apply(rb, [b"GET", k])
        assert ra.out == rb.out and ra.out[0] == ("arr", 12), k
    # sizes agree cross-node after the sharded drains
    sa, sb = _T(), _T()
    a.apply(sa, [b"SIZE", keys[0]])
    b.apply(sb, [b"SIZE", keys[0]])
    assert sa.out == sb.out == [12]
    # TRIM through the routed kernel: keep 3 newest, cutoff replicates
    a.apply(_T(), [b"TRIM", keys[0], b"3"])
    st = _T()
    a.apply(st, [b"SIZE", keys[0]])
    assert st.out == [3]
    for key, delta in a.flush_deltas():
        b.converge(key, delta)
    sb2 = _T()
    b.apply(sb2, [b"SIZE", keys[0]])
    assert sb2.out == [3]


def test_join_replica_axis_is_lattice_join():
    rng = np.random.default_rng(1)
    S, K = 8, 64  # 2 local rows per rep shard: exercises the local fold
    mesh = make_mesh(8, rep=4)
    states = rng.integers(0, 1 << 62, (S, K)).astype(np.uint64)
    s_hi, s_lo = planes.split64_np(states)
    from jax.sharding import NamedSharding, PartitionSpec as P

    sh = NamedSharding(mesh, P("rep", "keys"))
    jhi, jlo = join_replica_axis(
        mesh, jax.device_put(s_hi, sh), jax.device_put(s_lo, sh)
    )
    joined = planes.combine64_np(
        np.asarray(jax.device_get(jhi)), np.asarray(jax.device_get(jlo))
    )
    want = np.broadcast_to(states.max(axis=0), (S, K))
    np.testing.assert_array_equal(joined, want)
