"""Differential tests: device ORSWOT join vs the authoritative host
lattice (ops/ujson_host.py) on random workloads."""

import numpy as np
import pytest

import jylis_tpu  # noqa: F401
from jylis_tpu.ops import ujson_device as dev
from jylis_tpu.ops.ujson_host import UJSON


class PayInterner:
    def __init__(self):
        self.ids = {}
        self.rev = []

    def __call__(self, path, token):
        key = (path, token)
        if key not in self.ids:
            self.ids[key] = len(self.rev)
            self.rev.append(key)
        return self.ids[key]

    def lookup(self, pid):
        return self.rev[pid]


def copy_doc(doc: UJSON) -> UJSON:
    c = UJSON()
    c.entries = dict(doc.entries)
    c.ctx.vv = dict(doc.ctx.vv)
    c.ctx.cloud = set(doc.ctx.cloud)
    return c


def random_mutations(rng, doc, replica, n_ops, delta=None):
    paths = [("a",), ("b",), ("a", "x"), ("c", "y", "z")]
    for _ in range(n_ops):
        op = rng.integers(4)
        path = paths[rng.integers(len(paths))]
        if op == 0:
            doc.set_doc(replica, path, str(int(rng.integers(100))), delta=delta)
        elif op == 1:
            doc.ins(replica, path, str(int(rng.integers(100))), delta=delta)
        elif op == 2:
            vals = [t for p, t in doc.entries.values() if p == path]
            if vals:
                doc.rm(replica, path, vals[0], delta=delta)
        else:
            doc.clr(replica, path, delta=delta)


def roundtrip_join(a: UJSON, b: UJSON, shift=None):
    """Join a⊔b via the device kernels, decoded back to a host doc.
    shift=None plans the layout (int32 when it fits); 32 forces u64."""
    pay = PayInterner()
    rid_cols: dict[int, int] = {}
    if shift is None:
        shift = dev.plan_shift([a, b], n_rep=8)
    batch = dev.encode_docs([a, b], rid_cols, pay, n_rep=8, shift=shift)
    one = dev.join_batch(
        dev.DocBatch(*(p[:1] for p in batch)),
        dev.DocBatch(*(p[1:] for p in batch)),
        shift=shift,
    )
    cols_rid = {c: r for r, c in rid_cols.items()}
    return dev.decode_doc(one, 0, cols_rid, pay.lookup, shift=shift)


def assert_same_doc(got: UJSON, want: UJSON):
    assert got.entries == want.entries
    # contexts may compact differently; what matters is identical coverage
    dots = set(got.entries) | set(want.entries) | want.ctx.cloud | got.ctx.cloud
    for r, s in list(want.ctx.vv.items()) + list(got.ctx.vv.items()):
        dots.add((r, s))
        dots.add((r, s + 1))
    for d in dots:
        assert got.ctx.contains(d) == want.ctx.contains(d), d
    assert got.render() == want.render()


@pytest.mark.parametrize("seed", range(6))
@pytest.mark.parametrize("shift", [None, 32])  # planned int32 + forced u64
def test_pairwise_join_matches_host(seed, shift):
    rng = np.random.default_rng(seed)
    a, b = UJSON(), UJSON()
    random_mutations(rng, a, replica=1, n_ops=12)
    random_mutations(rng, b, replica=2, n_ops=12)
    # partial cross-knowledge: b sees an early snapshot of a
    snap = copy_doc(a)
    random_mutations(rng, a, replica=1, n_ops=6)
    b.converge(snap)
    random_mutations(rng, b, replica=2, n_ops=6)

    want = copy_doc(a)
    want.converge(b)
    got = roundtrip_join(a, b, shift=shift)
    assert_same_doc(got, want)


def test_plan_shift_narrow_and_wide():
    a = UJSON()
    a.ins(1, ("k",), "1")
    assert dev.plan_shift([a], n_rep=8) == 31 - 3
    big = UJSON()
    big.ctx.vv[2] = 1 << 30  # seq too large for a narrow layout
    assert dev.plan_shift([a, big], n_rep=8) == 32
    # the all-ones seq is reserved: it would pack to the PAD sentinel
    edge = UJSON()
    edge.ctx.vv[2] = (1 << 28) - 1
    assert dev.plan_shift([edge], n_rep=8) == 32


def test_encode_rejects_seqs_beyond_device_layouts():
    """vv seqs past u32 cannot be represented on device; encode refuses
    (clamping would shrink coverage and resurrect removed entries) and
    the serving repo falls back to the host lattice."""
    big = UJSON()
    big.ctx.vv[3] = 1 << 33
    with pytest.raises(OverflowError):
        dev.encode_docs([big], {}, lambda p, t: 0, n_rep=4, shift=32)

    from jylis_tpu.models import repo_ujson as mod

    remote = UJSON()
    remote.ctx.vv[7] = 1 << 33  # huge causal history
    d = UJSON()
    remote.ins(7, ("k",), "5", delta=d)
    d.ctx.vv[7] = 1 << 33  # delta carries the wide context

    repo = mod.RepoUJSON(identity=1)
    old = mod.DEVICE_FANIN_MIN
    try:
        mod.DEVICE_FANIN_MIN = 1  # force the device path attempt
        repo.converge(b"doc", d)
        r = []

        class _R:
            def string(self, s):
                r.append(s)

            def ok(self):
                pass

        repo.apply(_R(), [b"GET", b"doc", b"k"])
        assert r == ["5"]  # host fallback converged it
    finally:
        mod.DEVICE_FANIN_MIN = old


def test_add_wins_concurrent_rm_ins():
    """The documented add-wins case (ujson.md:134-182): concurrent RM and
    re-INS of the same (path, value) — the insert survives the join."""
    a, b = UJSON(), UJSON()
    a.ins(1, ("tags",), '"blue"')
    b.converge(copy_doc(a))
    da, db = UJSON(), UJSON()
    a.rm(1, ("tags",), '"blue"', delta=da)
    b.ins(2, ("tags",), '"blue"', delta=db)

    want = copy_doc(a)
    want.converge(b)
    got = roundtrip_join(a, b)
    assert_same_doc(got, want)
    assert got.render(("tags",)) == '"blue"'


@pytest.mark.parametrize("n_rep,edits", [(8, 10), (16, 5)])
def test_fold_deltas_matches_sequential_convergence(n_rep, edits):
    """The anti-entropy fan-in: fold all deltas on device in log depth,
    broadcast-join into every replica, compare against the host oracle
    converging every delta sequentially."""
    rng = np.random.default_rng(7)
    replicas = [UJSON() for _ in range(n_rep)]
    deltas = []
    for r, doc in enumerate(replicas):
        for _ in range(edits):
            d = UJSON()
            random_mutations(rng, doc, replica=r, n_ops=1, delta=d)
            deltas.append(d)

    # host oracle: every replica converges every delta
    want = [copy_doc(doc) for doc in replicas]
    for doc in want:
        for d in deltas:
            doc.converge(d)
    renders = {doc.render() for doc in want}
    assert len(renders) == 1

    pay = PayInterner()
    rid_cols: dict[int, int] = {}
    shift = dev.plan_shift(deltas + replicas, n_rep=n_rep)
    dbatch = dev.encode_docs(deltas, rid_cols, pay, n_rep=n_rep, shift=shift)
    folded = dev.compact(dev.fold_deltas(dbatch, shift=shift))
    rbatch = dev.encode_docs(replicas, rid_cols, pay, n_rep=n_rep, shift=shift)
    joined = dev.broadcast_join(rbatch, folded, shift=shift, sort_output=False)
    cols_rid = {c: r for r, c in rid_cols.items()}
    for got, want_doc in zip(
        dev.decode_batch(joined, cols_rid, pay.lookup, shift=shift), want
    ):
        assert_same_doc(got, want_doc)

    # the single-dispatch fused path agrees
    fused = dev.fold_and_broadcast(rbatch, dbatch, shift=shift)
    for got, want_doc in zip(
        dev.decode_batch(fused, cols_rid, pay.lookup, shift=shift), want
    ):
        assert_same_doc(got, want_doc)


def test_repo_device_fold_matches_host_loop(monkeypatch):
    """RepoUJSON drains a big per-key fan-in through the device fold;
    result must match a repo converging the same deltas on the host loop."""
    from jylis_tpu.models import repo_ujson as mod

    class _R:
        def __init__(self):
            self.vals = []

        def string(self, s):
            self.vals.append(s)

        def ok(self):
            pass

    def build_deltas():
        rng = np.random.default_rng(11)
        src = [UJSON() for _ in range(6)]
        out = []
        for r, doc in enumerate(src):
            for _ in range(4):
                d = UJSON()
                random_mutations(rng, doc, replica=r + 10, n_ops=1, delta=d)
                out.append(d)
        return out

    deltas = build_deltas()

    monkeypatch.setattr(mod, "DEVICE_FANIN_MIN", 4)  # force the device path
    dev_repo = mod.RepoUJSON(identity=1)
    for d in deltas:
        dev_repo.converge(b"doc", d)
    assert dev_repo.may_drain([b"GET", b"doc"])
    r1 = _R()
    dev_repo.apply(r1, [b"GET", b"doc"])

    monkeypatch.setattr(mod, "DEVICE_FANIN_MIN", 10_000)  # host loop
    host_repo = mod.RepoUJSON(identity=1)
    for d in build_deltas():
        host_repo.converge(b"doc", d)
    assert not host_repo.may_drain([b"GET", b"doc"])
    r2 = _R()
    host_repo.apply(r2, [b"GET", b"doc"])

    assert r1.vals == r2.vals and r1.vals[0] != ""


def test_repo_observed_remove_sees_buffered_deltas(monkeypatch):
    """RM after a buffered remote INS must observe (and remove) it —
    mutators drain their key first."""
    from jylis_tpu.models import repo_ujson as mod

    class _R:
        def __init__(self):
            self.vals = []

        def string(self, s):
            self.vals.append(s)

        def ok(self):
            pass

    remote = UJSON()
    d = UJSON()
    remote.ins(7, ("tags",), '"x"', delta=d)

    repo = mod.RepoUJSON(identity=1)
    repo.converge(b"doc", d)  # buffered, not yet observed
    repo.apply(_R(), [b"RM", b"doc", b"tags", b'"x"'])
    r = _R()
    repo.apply(r, [b"GET", b"doc", b"tags"])
    assert r.vals == [""]  # the RM observed the buffered INS


def test_compact_preserves_rows():
    a = UJSON()
    a.ins(1, ("k",), "1")
    a.ins(1, ("k",), "2")
    b = UJSON()
    b.ins(2, ("k",), "3")
    pay = PayInterner()
    rid_cols: dict[int, int] = {}
    shift = dev.plan_shift([a, b], n_rep=4)
    batch = dev.encode_docs([a, b], rid_cols, pay, n_rep=4, shift=shift)
    wide = dev.join_batch(batch, batch, shift=shift)  # self-join: no-op
    slim = dev.compact(wide)
    assert slim.dots.shape[-1] <= wide.dots.shape[-1]
    cols_rid = {c: r for r, c in rid_cols.items()}
    got_a = dev.decode_doc(slim, 0, cols_rid, pay.lookup, shift=shift)
    assert_same_doc(got_a, a)


@pytest.mark.parametrize("shift_mode", ["planned", 32])
def test_fold_segments_matches_per_key_folds(shift_mode):
    """Segmented fold: K keys' fan-ins in one (K, D, W) dispatch must
    equal each key's own sequential host convergence — including ragged
    group sizes that pad with identity rows."""
    rng = np.random.default_rng(23)
    groups = []
    for k, size in enumerate([1, 3, 7, 4]):
        doc = UJSON()
        g = []
        for _ in range(size):
            d = UJSON()
            random_mutations(rng, doc, replica=100 + k, n_ops=2, delta=d)
            g.append(d)
        groups.append(g)

    flat = [d for g in groups for d in g]
    pay = PayInterner()
    rid_cols: dict[int, int] = {}
    shift = dev.plan_shift(flat, n_rep=8) if shift_mode == "planned" else 32
    batch = dev.encode_doc_groups(groups, rid_cols, pay, n_rep=8, shift=shift)
    assert batch.dots.ndim == 3 and batch.dots.shape[0] == len(groups)
    folded = dev.fold_segments(batch, shift=shift)
    cols_rid = {c: r for r, c in rid_cols.items()}
    got = dev.decode_batch(folded, cols_rid, pay.lookup, shift=shift)

    for g, got_doc in zip(groups, got):
        want = UJSON()
        for d in g:
            want.converge(d)
        assert_same_doc(got_doc, want)


def test_repo_segmented_drain_matches_host_loop(monkeypatch):
    """A full drain with many pending keys takes the segmented device
    path (one dispatch) and must match the pure host loop repo."""
    from jylis_tpu.models import repo_ujson as mod

    class _R:
        def __init__(self):
            self.vals = []

        def string(self, s):
            self.vals.append(s)

        def ok(self):
            pass

    def feed(repo):
        rng = np.random.default_rng(31)
        for k in range(5):
            key = b"doc%d" % k
            doc = UJSON()
            for r in range(4):
                for _ in range(2):
                    d = UJSON()
                    random_mutations(
                        rng, doc, replica=50 + r, n_ops=1, delta=d
                    )
                    repo.converge(key, d)

    monkeypatch.setattr(mod, "SEG_FANIN_MIN", 4)  # force the segmented path
    seg_repo = mod.RepoUJSON(identity=1)
    feed(seg_repo)
    seg_repo.drain()
    assert seg_repo._pend_total == 0 and not seg_repo._pend

    monkeypatch.setattr(mod, "SEG_FANIN_MIN", 10_000)
    monkeypatch.setattr(mod, "DEVICE_FANIN_MIN", 10_000)  # pure host loop
    host_repo = mod.RepoUJSON(identity=1)
    feed(host_repo)
    host_repo.drain()

    for k in range(5):
        r1, r2 = _R(), _R()
        seg_repo.apply(r1, [b"GET", b"doc%d" % k])
        host_repo.apply(r2, [b"GET", b"doc%d" % k])
        assert r1.vals == r2.vals, k
        assert r1.vals[0] != ""
