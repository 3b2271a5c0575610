"""Property + differential tests for the GCOUNT/PNCOUNT device kernels.

Covers the lattice laws (commutativity, associativity, idempotence — the
convergence guarantee the reference gets from pony-crdt) and agreement with
the pure-Python reference lattices under random workloads, mirroring the
documented semantics at docs/_docs/types/gcount.md:43-47 and
pncount.md:49-55. The kernels store u64 counters as one u32 plane of hi|lo
cells (ops/planes.py), so values straddling the 2^32 boundary are exercised
explicitly.
"""

import numpy as np
import pytest

import jylis_tpu  # noqa: F401  (enables x64)
from jylis_tpu.ops import gcount, hostref, planes, pncount

K, R = 64, 8


def rand_counts(rng) -> np.ndarray:
    # spread across the full u64 range so hi-plane compares matter
    return np.asarray(rng.integers(0, 2**63, size=(K, R)), dtype=np.uint64)


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_gcount_lattice_laws(seed):
    rng = np.random.default_rng(seed)
    a, b, c = (gcount.from_counts(rand_counts(rng)) for _ in range(3))
    ab = gcount.join(a, b)
    ba = gcount.join(b, a)
    np.testing.assert_array_equal(gcount.to_counts(ab), gcount.to_counts(ba))
    ab_c = gcount.join(ab, c)
    a_bc = gcount.join(a, gcount.join(b, c))
    np.testing.assert_array_equal(gcount.to_counts(ab_c), gcount.to_counts(a_bc))
    aa = gcount.join(a, a)
    np.testing.assert_array_equal(gcount.to_counts(aa), gcount.to_counts(a))


def test_join_decides_on_low_plane_when_hi_equal():
    a = gcount.from_counts(np.full((2, 2), (7 << 32) | 5, np.uint64))
    b = gcount.from_counts(np.full((2, 2), (7 << 32) | 9, np.uint64))
    joined = gcount.to_counts(gcount.join(a, b))
    np.testing.assert_array_equal(joined, np.full((2, 2), (7 << 32) | 9, np.uint64))


def test_gcount_matches_hostref():
    rng = np.random.default_rng(7)
    state = gcount.init(K, R)
    refs = [hostref.GCounter() for _ in range(K)]

    # random increments, applied in batches to the device state; the device
    # increment requires unique coordinates, so coalesce per batch first
    for _ in range(20):
        n = int(rng.integers(1, 32))
        ki = rng.integers(0, K, size=n)
        ri = rng.integers(0, R, size=n)
        amt = rng.integers(0, 1000, size=n)
        acc: dict[tuple[int, int], int] = {}
        for k, r, a in zip(ki, ri, amt):
            acc[(int(k), int(r))] = acc.get((int(k), int(r)), 0) + int(a)
            refs[int(k)].increment(int(r), int(a))
        coords = list(acc)
        state = gcount.increment(
            state,
            np.array([c[0] for c in coords], np.int32),
            np.array([c[1] for c in coords], np.int32),
            np.array([acc[c] for c in coords], np.uint64),
        )

    got = np.asarray(gcount.read_all(state))
    want = np.array([c.value() for c in refs], dtype=np.uint64)
    np.testing.assert_array_equal(got, want)


def test_increment_carries_across_u32_boundary():
    state = gcount.init(2, 1)
    big = np.array([(1 << 32) - 3], np.uint64)
    ki = np.array([0], np.int32)
    ri = np.array([0], np.int32)
    state = gcount.increment(state, ki, ri, big)
    state = gcount.increment(state, ki, ri, np.array([10], np.uint64))
    assert int(np.asarray(gcount.read_all(state))[0]) == (1 << 32) + 7


def test_gcount_converge_batch_with_duplicate_keys():
    """converge_batch requires unique rows; planes.coalesce is the
    documented host-side combiner for batches that have duplicates."""
    state = gcount.init(4, 2)
    ki = np.array([1, 1, 3], dtype=np.int32)
    deltas = np.array([[5, 0], [3, 9], [2, 2]], dtype=np.uint64)
    uki, udeltas = planes.coalesce(ki, deltas)
    state = gcount.converge_batch(state, uki, planes.pack64_np(udeltas))
    got = gcount.to_counts(state)
    np.testing.assert_array_equal(got[1], [5, 9])  # elementwise max of dup rows
    np.testing.assert_array_equal(got[3], [2, 2])
    np.testing.assert_array_equal(got[0], [0, 0])


def _converge_u64(state, ki, p, n):
    d = planes.pack64_np(np.concatenate([p, n], axis=1))
    return pncount.converge_batch(state, ki, d)


def test_pncount_random_convergence_order_independent():
    """N replicas make random INC/DEC, exchange full deltas in random orders;
    every replica must converge to the same value as the host oracle."""
    rng = np.random.default_rng(3)
    n_rep = 4
    oracle = [hostref.PNCounter() for _ in range(K)]

    # each replica's own contribution as (K, R) P/N matrices
    contrib_p = np.zeros((n_rep, K, n_rep), dtype=np.uint64)
    contrib_n = np.zeros((n_rep, K, n_rep), dtype=np.uint64)
    for rep in range(n_rep):
        for _ in range(50):
            k = int(rng.integers(0, K))
            amt = int(rng.integers(1, 100))
            if rng.random() < 0.5:
                contrib_p[rep, k, rep] += amt
                oracle[k].increment(rep, amt)
            else:
                contrib_n[rep, k, rep] += amt
                oracle[k].decrement(rep, amt)

    want = np.array([c.value() for c in oracle], dtype=np.int64)
    all_keys = np.arange(K, dtype=np.int32)
    for seed in range(3):  # three random delivery orders
        order = np.random.default_rng(seed).permutation(n_rep)
        state = pncount.init(K, n_rep)
        for rep in order:
            state = _converge_u64(state, all_keys, contrib_p[rep], contrib_n[rep])
            # duplicate delivery is harmless (idempotent join)
            state = _converge_u64(state, all_keys, contrib_p[rep], contrib_n[rep])
        got = np.asarray(pncount.read_all(state))
        np.testing.assert_array_equal(got, want)


def test_pncount_negative_values():
    state = pncount.init(2, 1)
    state = pncount.decrement(
        state,
        np.array([0], dtype=np.int32),
        np.array([0], dtype=np.int32),
        np.array([15], dtype=np.uint64),
    )
    state = pncount.increment(
        state,
        np.array([0], dtype=np.int32),
        np.array([0], dtype=np.int32),
        np.array([10], dtype=np.uint64),
    )
    got = np.asarray(pncount.read_all(state))
    assert got[0] == -5
    assert got[1] == 0


def test_grow_preserves_state():
    state = gcount.init(2, 2)
    state = gcount.increment(
        state,
        np.array([1], dtype=np.int32),
        np.array([1], dtype=np.int32),
        np.array([42], dtype=np.uint64),
    )
    state = gcount.grow(state, 8, 4)
    assert gcount.to_counts(state).shape == (8, 4)
    assert int(np.asarray(gcount.read_all(state))[1]) == 42


def test_rowsum_wraps_mod_2_64():
    # wrapping sum semantics (Pony U64 +) preserved by the u16-split path
    counts = np.full((1, 4), (1 << 63) + 5, np.uint64)
    state = gcount.from_counts(counts)
    got = int(np.asarray(gcount.read_all(state))[0])
    assert got == (4 * ((1 << 63) + 5)) % (1 << 64)


# ---- the jitted sparse drains a client reaches (models/repo_counters.py) ----


def _hostref_drain(oracle, kind, rows, d):
    """Converge one (rows, C) u64 delta batch into the per-row oracle
    counters; returns the batch rows' values as the drain's sums are."""
    r = d.shape[1] // (2 if kind == "pn" else 1)
    out = []
    for row, vals in zip(rows, d):
        other = hostref.PNCounter() if kind == "pn" else hostref.GCounter()
        for col in np.flatnonzero(vals):
            if kind == "g":
                other.counts[int(col)] = int(vals[col])
            elif col < r:
                other.p.counts[int(col)] = int(vals[col])
            else:
                other.n.counts[int(col - r)] = int(vals[col])
        mine = oracle.setdefault(int(row), type(other)())
        mine.converge(other)
        out.append(mine.value())
    return out


def _oracle_counts(oracle, kind, k, r):
    c = 2 * r if kind == "pn" else r
    want = np.zeros((k, c), np.uint64)
    for row, ctr in oracle.items():
        if kind == "g":
            for col, v in ctr.counts.items():
                want[row, col] = v
        else:
            for col, v in ctr.p.counts.items():
                want[row, col] = v
            for col, v in ctr.n.counts.items():
                want[row, r + col] = v
    return want


@pytest.mark.parametrize("kind", ["g", "pn"])
@pytest.mark.parametrize("r", [8, 64])
@pytest.mark.parametrize("fill", ["one", "37pct", "full"])
@pytest.mark.parametrize("b", [16, 1024, 16384])
def test_jitted_sparse_drain_matches_hostref(kind, r, fill, b):
    """`_drain_g` / `_drain_pn` as `drain()` calls them: a padded bucket
    (the rest `pad_rows`), 63-bit values on both polarities, rows 0 and K-1,
    two drains in a row. State AND returned sums bit for bit against
    ops/hostref.py; the donated plane is consumed by each call."""
    from jylis_tpu.models.base import pad_rows
    from jylis_tpu.models.repo_counters import _drain_g, _drain_pn

    ops, drain = (pncount, _drain_pn) if kind == "pn" else (gcount, _drain_g)
    rng = np.random.default_rng([kind == "pn", r, len(fill), b])
    k = max(2 * b, 64)
    c = 2 * r if kind == "pn" else r
    n = {"one": 1, "37pct": max(2, (37 * b) // 100), "full": b}[fill]
    # a small batch fills every column; a big one 8 random ones a row and
    # polarity, which still reaches every lane and wraps the row sums
    per_row = c if b == 16 else 8 * (c // r)

    state = ops.init(k, r)
    oracle: dict = {}
    prev_inner = None
    for step in range(2):
        if n == 1:
            rows = np.array([0 if step == 0 else k - 1])
        else:
            inner = rng.permutation(np.arange(1, k - 1))[: n - 2]
            if prev_inner is not None:  # half of the rows meet their old cells
                keep = prev_inner[: (n - 2) // 2]
                inner = np.unique(np.concatenate([keep, inner]))[: n - 2]
            prev_inner = inner
            rows = rng.permutation(np.concatenate([[0, k - 1], inner]))
        d = np.zeros((len(rows), c), np.uint64)
        cols = np.argsort(rng.random((len(rows), c)), axis=1)[:, :per_row]
        vals = rng.integers(1 << 53, 1 << 62, size=cols.shape, dtype=np.uint64)
        np.put_along_axis(d, cols, vals, axis=1)
        ki = pad_rows(b)
        ki[: len(rows)] = rows
        padded = np.zeros((b, c), np.uint64)
        padded[: len(rows)] = d

        donated = state
        state, sums = drain(donated, ki, planes.pack64_np(padded))
        assert donated.is_deleted()
        want_sums = _hostref_drain(oracle, kind, rows, d)
        got = np.asarray(sums)[: len(rows)]
        assert got.dtype == (np.int64 if kind == "pn" else np.uint64)
        assert [int(v) for v in got] == want_sums
        np.testing.assert_array_equal(
            planes.unpack64_np(np.asarray(state)), _oracle_counts(oracle, kind, k, r)
        )
