"""The TREG drain's two bulk table calls (`export_planes`, `settle_ties`)
and the generation id rule, on both table backends and through the repo.

The pending window leaves its table as the drain kernel's batch planes in
one call, and the rows whose 8-byte prefix tied on the device go back in
one call. `PyTregTable` is the oracle and `NativeTregTable` the engine's
view; both must fill the same planes bit for bit, give the same tie
verdicts and hold the same winners. Through `RepoTREG` the device mirror's
ts and rank planes must be the plain LWW winner's, whatever mix of sparse,
dense and sharded drains brought them there.
"""

import numpy as np
import pytest

import jylis_tpu  # noqa: F401
from jylis_tpu.models.repo_treg import DENSE_FRACTION, RepoTREG, batch_planes
from jylis_tpu.models.treg_table import NativeTregTable, PyTregTable
from jylis_tpu.native.engine import make_engine
from jylis_tpu.obs.registry import MetricsRegistry
from jylis_tpu.ops import planes
from jylis_tpu.ops.interner import prefix_rank
from jylis_tpu.utils.batching import bucket, pad_rows
from jylis_tpu.utils.metrics import metric_lines

needs_native = pytest.mark.skipif(
    make_engine() is None, reason="native engine unavailable (no toolchain)"
)

PREFIX = b"8bytes!!"  # every value under it ranks equal on the device


def _value(rng) -> bytes:
    """The shapes the rank and the tie rule must get right: empty, shorter
    than the 8-byte prefix, exactly 8, and equal prefixes with tails."""
    roll = rng.integers(6)
    if roll == 0:
        return b""
    if roll == 1:
        return bytes(rng.integers(0, 256, rng.integers(1, 8), dtype=np.uint8))
    if roll == 2:
        return PREFIX
    if roll == 3:
        return PREFIX + b"\x00" * int(rng.integers(1, 3))
    return PREFIX + b"tail%d" % rng.integers(4)


def _batch(n: int, dense: bool, cap: int):
    """[ki, ts_hi, ts_lo, rank_hi, rank_lo, vid] as RepoTREG hands them to
    the table: pads and identity everywhere, nothing written yet."""
    b = cap if dense else bucket(n)
    return [np.empty(n, np.int32) if dense else pad_rows(b), *batch_planes(b)]


def _export(tbl, dense: bool, cap: int):
    n = tbl.pend_count()
    ki, *d = _batch(n, dense, cap)
    assert tbl.export_planes(ki, *d, dense) == n
    return [ki, *d]


def _reference_planes(pend: dict, gens: dict, winners: dict, dense, cap):
    """What the export must hold, from a plain dict model of the table."""
    ki, *d = _batch(len(pend), dense, cap)
    for i, (row, (ts, v)) in enumerate(pend.items()):
        slot = row if dense else i
        rank = prefix_rank(v)
        ki[i] = row
        d[0][slot], d[1][slot] = ts >> 32, ts & 0xFFFFFFFF
        d[2][slot], d[3][slot] = rank >> 32, rank & 0xFFFFFFFF
        same = winners.get(row) == (ts, v)
        d[4][slot] = gens[row] if same else gens.get(row, -1) + 1
    return [ki, *d]


@needs_native
@pytest.mark.parametrize("seed", [0, 1, 2, 3])
def test_tables_export_the_same_planes_ties_and_dump(seed):
    rng = np.random.default_rng(seed)
    py, nat = PyTregTable(), NativeTregTable(make_engine())
    keys = [b"k%02d" % i for i in range(24)]
    cap = 32
    pend: dict[int, tuple[int, bytes]] = {}  # the plain model
    winners: dict[int, tuple[int, bytes]] = {}
    gens: dict[int, int] = {}
    for _round in range(12):
        for _ in range(int(rng.integers(1, 40))):
            key = keys[rng.integers(len(keys))]
            # few timestamps, one of them above 32 bits: equal-ts ties are
            # common, and both halves of the ts planes are exercised
            ts = int(rng.choice([0, 1, 2, (1 << 40) + 3]))
            value = _value(rng)
            rows = {t.upsert(key) for t in (py, nat)}
            assert len(rows) == 1
            row = rows.pop()
            if row in winners and rng.integers(4) == 0:
                ts, value = winners[row]  # re-delivery of the drained winner
            for t in (py, nat):
                t.write(row, ts, value)  # several writes a row a window
            if row not in pend or (ts, value) > pend[row]:
                pend[row] = (ts, value)
        assert py.pend_count() == nat.pend_count() == len(pend)
        for dense in (False, True):
            want = _reference_planes(pend, gens, winners, dense, cap)
            for tbl in (py, nat):
                for got, exp in zip(_export(tbl, dense, cap), want):
                    np.testing.assert_array_equal(got, exp)
        # the tie call, asked about MORE than the device would flag: every
        # pending row, and one that is not pending at all
        idle = [r for r in range(py.rows()) if r not in pend][:1]
        ask = np.asarray(list(pend) + idle, np.int32)
        (p_rows, p_vids), (n_rows, n_vids) = py.settle_ties(ask), nat.settle_ties(ask)
        np.testing.assert_array_equal(p_rows, n_rows)
        np.testing.assert_array_equal(p_vids, n_vids)
        assert p_rows.dtype == n_rows.dtype == p_vids.dtype == n_vids.dtype == np.int32
        assert p_rows.tolist() == [
            r for r, p in pend.items() if r not in winners or p > winners[r]
        ]
        for t in (py, nat):
            t.fold_pend()
        for row, p in pend.items():
            if row not in winners or p > winners[row]:
                gens[row] = gens.get(row, -1) + 1
                winners[row] = p
        pend.clear()
        assert py.pend_count() == nat.pend_count() == 0
        assert py.dump() == nat.dump()
        assert dict(py.dump()) == {
            py.key_of(r): (v, ts) for r, (ts, v) in winners.items()
        }


@needs_native
def test_the_export_refuses_arrays_that_do_not_fit():
    nat = NativeTregTable(make_engine())
    for i in range(20):
        nat.write(nat.upsert(b"k%d" % i), 1, b"v")
    good = _export(nat, False, 32)
    with pytest.raises(ValueError):  # a batch shorter than the window
        nat.export_planes(good[0], *[a[:16] for a in good[1:]], False)
    with pytest.raises(ValueError):  # a dense keyspace the rows do not fit
        nat.export_planes(good[0], *[a[:16].copy() for a in good[1:]], True)
    with pytest.raises(ValueError):  # the wrong dtype
        nat.export_planes(good[0].astype(np.int64), *good[1:], False)
    assert nat.pend_count() == 20  # nothing was cleared


def test_the_generation_wraps_inside_the_non_negative_half():
    py = PyTregTable()
    row = py.upsert(b"k")
    py.write(row, 1, b"a")
    py.fold_pend()
    py._gen[row] = 0x7FFFFFFF
    py.write(row, 2, b"b")
    planes = _export(py, False, 16)
    assert planes[5][0] == 0  # differs from its neighbour, still >= 0


class _Resp:
    def __getattr__(self, name):
        return lambda *a: None


def _mirror(repo, n):
    ts_hi, ts_lo, rank_hi, rank_lo, vid = (np.asarray(p)[:n] for p in repo._state)
    return planes.combine64_np(ts_hi, ts_lo), planes.combine64_np(rank_hi, rank_lo), vid


ENGINES = ["python", pytest.param("auto", marks=needs_native)]


@pytest.mark.parametrize("mesh", [None, "auto"], ids=["one-device", "mesh"])
@pytest.mark.parametrize("engine", ENGINES)
def test_sparse_and_boot_sized_dense_drains_hold_the_winner_bit_for_bit(engine, mesh):
    """A boot-sized batch (every key pending: the dense program on one
    device) and then threshold-like sparse batches with colliding prefixes:
    after each drain the mirror's ts and rank planes are the plain LWW
    winner's, and a set register's id is >= 0."""
    rng = np.random.default_rng(5)
    repo = RepoTREG(identity=1, key_cap=16, mesh=mesh, engine=engine)
    n_keys = 3000
    keys = [b"key%05d" % i for i in range(n_keys)]
    model: dict[bytes, tuple[int, bytes]] = {}

    def write(key, ts, value):
        repo.converge(key, (value, ts))
        if key not in model or (ts, value) > model[key]:
            model[key] = (ts, value)

    def check():
        ts, rank, vid = _mirror(repo, n_keys)
        rows = [repo._tbl.find(k) for k in keys]
        want_ts = np.zeros(n_keys, np.uint64)
        want_rank = np.zeros(n_keys, np.uint64)
        for k, row in zip(keys, rows):
            if k in model:
                want_ts[row] = model[k][0]
                want_rank[row] = prefix_rank(model[k][1])
        np.testing.assert_array_equal(ts, want_ts)
        np.testing.assert_array_equal(rank, want_rank)
        written = np.asarray([r for k, r in zip(keys, rows) if k in model])
        assert (vid[written] >= 0).all()
        assert dict(repo.dump_state()) == {k: (v, t) for k, (t, v) in model.items()}

    for k in keys:
        write(k, int(rng.integers(0, 3)), _value(rng))
    assert repo._tbl.pend_count() * DENSE_FRACTION >= 4096  # dense on one device
    repo.drain()
    assert repo._key_cap == 4096
    check()
    for _round in range(4):
        for i in rng.choice(n_keys, 300, replace=False):
            write(keys[i], int(rng.integers(0, 3)), _value(rng))
        assert repo._tbl.pend_count() * DENSE_FRACTION < repo._key_cap  # sparse
        repo.drain()
        check()


@pytest.mark.parametrize("engine", ENGINES)
def test_the_mesh_payload_columns_give_the_mirror_one_device_builds(engine):
    """`_drain_sharded` takes [ts, rank, vid] from the same export: its
    five planes, ids included, equal the single-device drain's after the
    same writes (ties and re-deliveries among them)."""
    rng = np.random.default_rng(9)
    one = RepoTREG(identity=1, key_cap=1024, mesh=None, engine=engine)
    mesh = RepoTREG(identity=1, key_cap=1024, mesh="auto", engine=engine)
    if mesh._mesh is None:
        pytest.skip("one visible device: no mesh path")
    keys = [b"m%03d" % i for i in range(200)]
    for _round in range(6):
        for i in rng.choice(len(keys), 60, replace=False):
            delta = (_value(rng), int(rng.integers(0, 2)))
            one.converge(keys[i], delta)
            mesh.converge(keys[i], delta)
        one.drain()
        mesh.drain()
        for a, b in zip(_mirror(one, len(keys)), _mirror(mesh, len(keys))):
            np.testing.assert_array_equal(a, b)
    assert one.dump_state() == mesh.dump_state()


@pytest.mark.parametrize("engine", ENGINES)
def test_the_two_counters_read_bulk_rows_and_tie_rows(engine):
    repo = RepoTREG(identity=1, mesh=None, engine=engine)
    repo.metrics = reg = MetricsRegistry()
    resp = _Resp()
    for i in range(10):
        repo.apply(resp, [b"SET", b"k%d" % i, PREFIX + b"-b", b"7"])
    repo.drain()
    for i in range(4):  # equal ts, equal prefix, another tail: device ties
        repo.apply(resp, [b"SET", b"k%d" % i, PREFIX + (b"-c" if i % 2 else b"-a"), b"7"])
    repo.apply(resp, [b"SET", b"k9", PREFIX + b"-b", b"7"])  # a re-delivery: no tie
    repo.drain()
    bulk = 15 if engine == "auto" else 0  # the Python tables assemble row by row
    treg = {k: v for k, v in reg.tallies.items() if k.startswith("drain.TREG.")}
    assert treg == {"drain.TREG.bulk_rows": bulk, "drain.TREG.tie_rows": 4}
    assert not any(v for k, v in reg.tallies.items() if k not in treg)
    lines = metric_lines(registry=reg)
    assert "TREG keys 15" in lines
    assert f"TREG bulk_rows {bulk}" in lines and "TREG tie_rows 4" in lines
    assert reg.report().endswith(f"15 keys, {reg.counters['TREG']['seconds'] * 1e3:.1f}ms device, {bulk} bulk_rows, 4 tie_rows")
    for i in range(4):  # the full strings decided, whichever came first
        row = repo._tbl.find(b"k%d" % i)
        assert repo._tbl.winner(row) == (7, PREFIX + (b"-c" if i % 2 else b"-b"))
    with pytest.raises(KeyError):
        reg.tally("drain.TREG.no_such_count", 1)
