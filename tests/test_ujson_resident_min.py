"""UJSON residency by size (``--ujson-resident-min-leaves``).

Flag 0 is the fan-in rule letter for letter: nothing is admitted at
restore, a local write on a resident key demotes it, no shape is pinned.
Flag N admits a document of N or more leaves when it is restored or when a
write grows it there, keeps it resident under local INS / RM / SET / CLR
(each a row delta), answers as the host lattice does (seeded fuzz against
``ops/ujson_host.py``, foreign deltas interleaved), serves from the ROW:
the boot leaves no decoded view and a fold drops the view of every key it
folds, so every answer stands on a row the device holds, keeps observed-remove
exact, falls back to the host lattice on a sequence number past u32, and
compiles its fold programs at boot so that a stream of small drains
compiles nothing.
"""

import random

import pytest

import jylis_tpu  # noqa: F401
from jylis_tpu.models.database import Database
from jylis_tpu.models import repo_ujson
from jylis_tpu.ops import ujson_resident
from jylis_tpu.ops.ujson_host import UJSON
from jylis_tpu.utils.config import config_from_cli

import benchref

PATH = ("members",)
ME, LOADER, PEER_A, PEER_B = 1, 7, 21, 22


def _doc(leaves: int, rid: int = LOADER, base: int = 10**18) -> UJSON:
    d = UJSON()
    for j in range(leaves):
        d.ins(rid, PATH, str(base + j))
    return d


def _clone(doc: UJSON) -> UJSON:
    out = UJSON()
    out.converge(doc)
    return out


class _Peer:
    """Another replica writing on its own copy of a document and shipping
    each write's delta, as a node's flush does."""

    def __init__(self, rid: int, leaves: int = 0):
        self.rid, self.doc = rid, _doc(leaves)

    def ins(self, value: str) -> UJSON:
        delta = UJSON()
        self.doc.ins(self.rid, PATH, value, delta)
        return delta

    def rm(self, value: str) -> UJSON:
        delta = UJSON()
        self.doc.rm(self.rid, PATH, value, delta)
        return delta


def _node(min_leaves: int, docs: dict[bytes, UJSON], mesh: bool = False):
    """A Database as main.py boots it: the flag set, state restored through
    load_state, then the boot's warm. On ONE device unless ``mesh``, as the
    benchmark's chip serves (the tests' eight virtual devices would put the
    store on the serving mesh's row-aligned fold)."""
    db = Database(identity=ME)
    db.set_ujson_resident_min(min_leaves)
    repo = db._map[b"UJSON"].repo
    if not mesh:
        repo._mesh = None
    repo.load_state([(k, _clone(d)) for k, d in docs.items()])
    db.warm_drain_shapes()
    return db, repo


def _tally(db, kind: str) -> int:
    return db.metrics.tallies["drain.UJSON." + kind]


def _get(repo, key: bytes) -> str:
    return benchref.Replies().call(repo, b"GET", key, b"members").decode()


def _write(repo, op: bytes, key: bytes, value: str) -> None:
    assert benchref.Replies().call(repo, op, key, b"members", value.encode()) == b"OK"


def test_the_flag_defaults_to_zero_and_is_refused_below_it(capsys):
    assert config_from_cli([]).ujson_resident_min_leaves == 0
    assert config_from_cli(
        ["--ujson-resident-min-leaves", "512"]).ujson_resident_min_leaves == 512
    with pytest.raises(SystemExit):
        config_from_cli(["--ujson-resident-min-leaves", "-1"])
    assert "--ujson-resident-min-leaves" in capsys.readouterr().err


def test_flag_zero_admits_nothing_at_restore_and_pins_no_shape():
    db, repo = _node(0, {b"big%d" % i: _doc(100) for i in range(4)})
    repo.drain()
    assert repo._res is None and not repo._grown
    assert sorted(repo._data) == [b"big%d" % i for i in range(4)]
    assert _tally(db, "admits") == 0 and _tally(db, "resident_rows") == 0
    assert not any("UJSON" == name for name, _s, _n in db.device_layout())


def test_flag_zero_keeps_the_fan_in_rule_a_local_write_demotes():
    """The parent's behaviour, pinned: promotion by fan-in only, the
    decoded view dropped by every fold, a local write sends the document
    back to the host lattice, folds in the shapes their data gives."""
    db, repo = _node(0, {b"k": _doc(100)})
    repo.drain()  # the restored document: a host fold, as ever
    assert repo._res is None
    peer = _Peer(PEER_A)
    for i in range(repo_ujson.DEVICE_FANIN_MIN):
        repo.converge(b"k", peer.ins(str(3 * 10**18 + i)))
    assert repo.drain_overdue()
    repo.drain()
    assert repo._is_resident(b"k") and b"k" not in repo._res_cache
    assert not repo._res._pinned and repo._res._min_w == 0 == repo._res._min_c
    assert _tally(db, "admits") == 1
    assert _tally(db, "device_deltas") == repo_ujson.DEVICE_FANIN_MIN
    _write(repo, b"INS", b"k", "5")
    assert not repo._is_resident(b"k") and b"k" in repo._data
    assert _tally(db, "demote_write") == 1 and _tally(db, "row_deltas") == 0
    assert _tally(db, "resident_rows") == 0
    assert len(repo._data[b"k"].entries) == 100 + repo_ujson.DEVICE_FANIN_MIN + 1


def test_flag_n_admits_at_restore_what_has_the_size_and_sizes_the_store():
    docs = {b"big%02d" % i: _doc(100) for i in range(20)}
    docs.update({b"small%d" % i: _doc(10) for i in range(3)})
    db, repo = _node(50, docs)
    assert sorted(repo._res.keys()) == sorted(k for k in docs if k.startswith(b"big"))
    assert sorted(repo._data) == sorted(k for k in docs if k.startswith(b"small"))
    assert _tally(db, "admits") == 20 == _tally(db, "resident_rows")
    # power-of-two rows x slots, twice the widest row's slots
    assert repo._res.plane_shape() == (32, 256)
    assert ("UJSON", (32, 256), 1) in db.device_layout()
    # the boot leaves no view: a document's first read decodes its row
    assert not repo._res_cache and _tally(db, "row_reads") == 0
    for k, d in docs.items():
        assert _get(repo, k) == d.render(PATH)
    assert _tally(db, "row_reads") == 20 and len(repo._res_cache) == 20


def _spy_reads(repo, monkeypatch) -> list[bytes]:
    """The keys whose rows the repo gathers from the device from here on."""
    read, keys = repo._res.read, []

    def spy(key):
        keys.append(key)
        return read(key)

    monkeypatch.setattr(repo._res, "read", spy)
    return keys


def test_a_fold_drops_the_views_it_folded_and_the_next_read_decodes_the_row(monkeypatch):
    """The resident row is what a read depends on: after a fold (a key's
    own at ROW_FOLD_MIN row deltas, or a full drain) the keys it folded
    have no view, whether or not the view had absorbed the deltas, and
    the next GET gathers what the device folded."""
    db, repo = _node(30, {b"hot": _doc(40), b"idle": _doc(40)})
    oracle = _doc(40)
    gathered = _spy_reads(repo, monkeypatch)
    assert _get(repo, b"idle") == oracle.render(PATH)
    for i in range(repo_ujson.ROW_FOLD_MIN):
        _write(repo, b"INS", b"hot", str(2 * 10**18 + i))
        oracle.ins(ME, PATH, str(2 * 10**18 + i))
    assert b"hot" not in repo._pend and b"hot" not in repo._res_cache
    assert _tally(db, "device_deltas") == 0  # the view had absorbed all 32
    assert gathered == [b"idle", b"hot"]  # each document's first touch
    _write(repo, b"RM", b"hot", str(10**18))  # on the row, decoded again
    oracle.rm(ME, PATH, str(10**18))
    assert gathered == [b"idle", b"hot", b"hot"]
    d = _Peer(PEER_A, 40).ins(str(3 * 10**18))  # absorbed by nothing
    repo.converge(b"hot", d)
    oracle.converge(d)
    repo.drain()
    assert b"hot" not in repo._res_cache and b"idle" in repo._res_cache
    assert _tally(db, "device_deltas") == 1 and len(gathered) == 3
    assert _get(repo, b"hot") == oracle.render(PATH)
    assert gathered[3:] == [b"hot"] and _tally(db, "row_reads") == 4


def test_a_missing_view_goes_to_a_thread_only_while_a_fold_is_in_flight(monkeypatch):
    """`may_drain` (what the manager sends to a worker thread): under
    residency by size a read or write of a document with no view is one
    gather, on the loop unless the device is busy with a fold; a pending
    list past the trickle budget goes to a thread as ever; under the
    fan-in rule any resident key with no view does."""
    db, repo = _node(30, {b"a": _doc(40), b"b": _doc(40)})
    _get(repo, b"b")
    assert b"a" not in repo._res_cache and b"b" in repo._res_cache
    repo._res.block()
    get_a, get_b = [b"GET", b"a", b"members"], [b"GET", b"b", b"members"]
    assert not repo.may_drain(get_a) and not repo.may_drain(get_b)
    monkeypatch.setattr(repo._res, "busy", lambda: True)
    assert repo.may_drain(get_a) and not repo.may_drain(get_b)
    monkeypatch.setattr(repo._res, "busy", lambda: False)
    peer = _Peer(PEER_A, 40)
    for i in range(repo_ujson.TRICKLE_MAX + 1):
        repo.converge(b"b", peer.ins(str(3 * 10**18 + i)))
    assert repo.may_drain(get_b)
    db0, repo0 = _node(0, {b"k": _doc(40)})
    for i in range(repo_ujson.DEVICE_FANIN_MIN):
        repo0.converge(b"k", peer.ins(str(4 * 10**18 + i)))
    repo0.drain()
    repo0._res.block()
    assert repo0._is_resident(b"k") and repo0.may_drain([b"GET", b"k", b"members"])


def test_flag_n_admits_a_document_when_a_write_grows_it_to_the_size():
    db, repo = _node(50, {b"k": _doc(48)})
    assert repo._res is None
    _write(repo, b"INS", b"k", "1")
    repo.drain()
    assert not repo._is_resident(b"k")  # 49 leaves
    _write(repo, b"INS", b"k", "2")
    repo.drain()
    assert repo._is_resident(b"k") and b"k" not in repo._data
    assert _tally(db, "admits") == 1 and _tally(db, "demote_write") == 0
    # and a foreign delta can grow one there too
    db2, repo2 = _node(50, {b"k": _doc(49)})
    d = _Peer(PEER_A).ins("9")
    repo2.converge(b"k", d)
    repo2.drain()
    assert repo2._is_resident(b"k")
    want = _doc(49)
    want.converge(d)
    assert _get(repo2, b"k") == want.render(PATH)


@pytest.mark.parametrize("seed", [3, 39, 2**31 + 39, 5])
def test_a_resident_document_under_local_writes_never_demotes_and_answers_as_the_host_lattice(seed):
    """Seeded fuzz: local INS / RM / SET / CLR on resident documents with
    two peers' deltas interleaved; after every step the served read equals
    the host lattice's (decoded from the row after every fold of its key),
    at the end so does the device row itself."""
    rng = random.Random(seed)
    keys = [b"doc%d" % i for i in range(6)]
    db, repo = _node(30, {k: _doc(60) for k in keys})
    oracle = {k: _doc(60) for k in keys}
    # each peer writes on its own copy and ships deltas, as a node does
    peers = {rid: {k: _doc(60) for k in keys} for rid in (PEER_A, PEER_B)}
    shipped = {rid: {k: [] for k in keys} for rid in peers}
    fresh = 0
    for step in range(900):
        k = rng.choice(keys)
        roll = rng.random()
        fresh += 1
        if roll < 0.30:
            v = str(2 * 10**18 + fresh)
            _write(repo, b"INS", k, v)
            oracle[k].ins(ME, PATH, v)
        elif roll < 0.55:
            v = str(10**18 + rng.randrange(60))
            _write(repo, b"RM", k, v)
            oracle[k].rm(ME, PATH, v)
        elif roll < 0.58:
            doc = '{"members":[%d,%d]}' % (fresh, fresh + 10**6)
            assert benchref.Replies().call(repo, b"SET", k, doc.encode()) == b"OK"
            oracle[k].set_doc(ME, (), doc)
        elif roll < 0.60:
            assert benchref.Replies().call(repo, b"CLR", k, b"members") == b"OK"
            oracle[k].clr(ME, PATH)
        elif roll < 0.90:
            rid = rng.choice(list(peers))
            delta = UJSON()
            if rng.random() < 0.6:
                peers[rid][k].ins(rid, PATH, str(4 * 10**18 + fresh), delta)
            else:
                peers[rid][k].rm(rid, PATH, str(10**18 + rng.randrange(60)), delta)
            shipped[rid][k].append(delta)
            repo.converge(k, delta)
            oracle[k].converge(delta)
        elif roll < 0.93:
            repo.drain()
        assert repo._is_resident(k)
        if step % 7 == 0:
            assert _get(repo, k) == oracle[k].render(PATH), (seed, step, k)
    repo.drain()
    assert _tally(db, "demote_write") == 0 == _tally(db, "demote_overflow")
    assert _tally(db, "resident_rows") == len(keys)
    assert _tally(db, "row_deltas") > 300
    for k in keys:
        assert _get(repo, k) == oracle[k].render(PATH)
        assert repo._res.read(k).render(PATH) == oracle[k].render(PATH)
    # what this node flushed is what the host shape would have: a peer
    # that joins it agrees with the oracle
    flushed = dict(repo.flush_deltas())
    for k in keys:
        other = peers[PEER_A][k]
        for d in shipped[PEER_B][k]:
            other.converge(d)
        if k in flushed:
            other.converge(flushed[k])
        assert other.render(PATH) == oracle[k].render(PATH)


def test_a_leave_removes_what_this_node_has_observed_and_a_concurrent_join_survives():
    """Observed-remove stays exact on a resident document: a peer's INS of
    the same value, not yet seen here, survives this node's RM."""
    db, repo = _node(30, {b"g": _doc(40)})
    member = str(10**18 + 5)
    concurrent = UJSON()
    _doc(40).ins(PEER_A, PATH, member, concurrent)  # the same value, a new dot
    _write(repo, b"RM", b"g", member)  # observes only the loader's dot
    assert member not in _get(repo, b"g")
    repo.converge(b"g", concurrent)  # arrives after the leave: add wins
    assert member in _get(repo, b"g")
    repo.drain()
    assert member in repo._res.read(b"g").render(PATH)
    # seen first, the same leave removes both dots
    db2, repo2 = _node(30, {b"g": _doc(40)})
    repo2.converge(b"g", concurrent)
    _write(repo2, b"RM", b"g", member)
    repo2.drain()
    assert member not in _get(repo2, b"g")
    assert member not in repo2._res.read(b"g").render(PATH)
    assert repo2._is_resident(b"g") and _tally(db2, "demote_write") == 0


def test_a_sequence_number_past_u32_falls_back_to_the_host_lattice_counted():
    big = _doc(40)
    big.ctx.vv[ME] = 0xFFFFFFFF  # this node's next dot is past every layout
    db, repo = _node(30, {b"k": big, b"ok": _doc(40)})
    assert repo._is_resident(b"ok")
    _write(repo, b"INS", b"k", "77")
    _write(repo, b"INS", b"ok", "78")
    repo.drain()
    assert not repo._is_resident(b"k") and b"k" in repo._host_only
    assert repo._is_resident(b"ok")
    assert _tally(db, "demote_overflow") >= 1 and _tally(db, "demote_write") == 0
    want = _clone(big)
    want.ins(ME, PATH, "77")
    assert _get(repo, b"k") == want.render(PATH)
    _write(repo, b"RM", b"k", "77")  # and keeps serving from the host
    assert "77" not in _get(repo, b"k")


def test_the_byte_budget_refuses_a_sized_admission_counted(monkeypatch):
    monkeypatch.setattr(ujson_resident.ResidentStore, "BYTE_BUDGET", 1)
    db, repo = _node(30, {b"a": _doc(40)})
    assert repo._is_resident(b"a")  # an empty store is never full
    repo.load_state([(b"b", _doc(40))])
    repo.drain()
    assert not repo._is_resident(b"b") and b"b" in repo._data
    assert _tally(db, "demote_budget") == 1
    assert _get(repo, b"b") == _doc(40).render(PATH)


def test_the_boot_compiles_what_a_stream_of_small_drains_meets():
    """After the warm, local writes, foreign deltas, per-key folds and a
    full drain run in the programs the boot compiled: the fold's jit cache
    does not grow, and the planes keep their shape."""
    keys = [b"doc%02d" % i for i in range(10)]
    db, repo = _node(30, {k: _doc(60) for k in keys})
    shape = repo._res.plane_shape()
    before = ujson_resident.fold_join_subset._cache_size()
    rng = random.Random(1)
    peers = {k: _Peer(PEER_A) for k in keys}
    for step in range(150):  # ~15 joins a document: inside the planes' room
        k = rng.choice(keys)
        if step % 3:
            _write(repo, b"INS", k, str(2 * 10**18 + step))
        else:
            repo.converge(k, peers[k].ins(str(3 * 10**18 + step)))
        if step % 40 == 39:
            _get(repo, k)
        if step % 50 == 49:
            repo.drain()
    other = _Peer(PEER_B)
    for i in range(repo_ujson.TRICKLE_MAX + 5):  # a device-only fold of one key
        repo.converge(keys[0], other.ins(str(5 * 10**18 + i)))
    _get(repo, keys[0])
    repo.drain()
    assert ujson_resident.fold_join_subset._cache_size() == before
    assert repo._res.plane_shape() == shape
    # a foreign delta reaches its document by a host fold of the view or
    # by the device fold alone, never both (no view was evicted here)
    assert _tally(db, "device_deltas") >= repo_ujson.TRICKLE_MAX + 5
    assert (_tally(db, "device_deltas") + _tally(db, "host_deltas")
            == _tally(db, "foreign_deltas"))  # the restore's ten host-folded


def test_a_delta_too_wide_for_the_pinned_grid_rewrites_its_row_from_the_view():
    """A peer's flush that coalesced more writes of one key than a grid row
    holds (or a SET of a document) does not fold in a new shape: the view
    absorbs the key's list and the row is rewritten from it."""
    db, repo = _node(30, {b"k": _doc(60), b"other": _doc(60)})
    before = ujson_resident.fold_join_subset._cache_size()
    shape = repo._res.plane_shape()
    peer = _Peer(PEER_A, 60)
    wide = UJSON()
    for i in range(ujson_resident.ResidentStore.MENU_W + 3):  # one coalesced flush
        peer.doc.ins(PEER_A, PATH, str(3 * 10**18 + i), wide)
        peer.doc.rm(PEER_A, PATH, str(10**18 + i), wide)
    assert not repo._res.fits(wide)
    _write(repo, b"INS", b"k", "7")  # a row delta queued before it
    repo.converge(b"k", wide)
    repo.converge(b"other", peer.ins("8"))
    oracle = _doc(60)
    oracle.ins(ME, PATH, "7")
    oracle.converge(wide)
    repo.drain()
    assert _tally(db, "row_rewrites") == 1 and b"k" not in repo._pend
    assert ujson_resident.fold_join_subset._cache_size() == before
    assert repo._res.plane_shape() == shape and repo._is_resident(b"k")
    assert _get(repo, b"k") == oracle.render(PATH)
    assert repo._res.read(b"k").render(PATH) == oracle.render(PATH)
    doc = '{"members":[%s]}' % ",".join(str(i) for i in range(40))
    assert benchref.Replies().call(repo, b"SET", b"k", doc.encode()) == b"OK"
    repo.drain()  # the SET's one delta: 40 entries and every observed dot
    assert _tally(db, "row_rewrites") == 2
    assert repo._res.read(b"k").render(PATH) == "[" + ",".join(sorted(map(str, range(40)))) + "]"


def test_a_drain_that_rewrites_two_rows_runs_the_one_row_placement_twice():
    """Two keys with a delta too wide for the grid in ONE drain: the boot
    compiled the one-row placement only, and the drain compiles no other
    (on the chip a two-row placement compiled inside a measured window)."""
    keys = [b"k%d" % i for i in range(3)]
    db, repo = _node(30, {k: _doc(60) for k in keys})
    before = ujson_resident.place_rows._cache_size()
    oracle = {k: _doc(60) for k in keys}
    for n, k in enumerate(keys[:2]):
        peer, wide = _Peer(PEER_A, 60), UJSON()
        for i in range(ujson_resident.ResidentStore.MENU_W + 2):
            peer.doc.ins(PEER_A, PATH, str(3 * 10**18 + 100 * n + i), wide)
        repo.converge(k, wide)
        oracle[k].converge(wide)
    repo.drain()
    assert _tally(db, "row_rewrites") == 2
    assert ujson_resident.place_rows._cache_size() == before
    for k in keys:
        assert repo._res.read(k).render(PATH) == oracle[k].render(PATH)
        assert _get(repo, k) == oracle[k].render(PATH)


def test_a_long_list_folds_in_passes_of_the_pinned_programs():
    """More deltas than a program is deep, on more keys than the deep
    programs hold: the full drain folds every key's first deltas in the
    every-row program and the rest in passes of the smaller ones."""
    keys = [b"doc%02d" % i for i in range(70)]
    db, repo = _node(30, {k: _doc(40) for k in keys})
    store = repo._res
    assert store._passes() == [(4, 64), (64, 32), (128, 4)]
    before = ujson_resident.fold_join_subset._cache_size()
    peers = {k: _Peer(PEER_A, 40) for k in keys}
    oracle = {k: _doc(40) for k in keys}
    for n, k in enumerate(keys):
        for i in range(1 + (40 if n < 2 else 6 if n < 10 else 1)):
            d = peers[k].ins(str(3 * 10**18 + 1000 * n + i))
            repo.converge(k, d)
            oracle[k].converge(d)
    repo.drain()
    assert ujson_resident.fold_join_subset._cache_size() == before
    for k in keys:
        assert store.read(k).render(PATH) == oracle[k].render(PATH), k
        assert _get(repo, k) == oracle[k].render(PATH)


def test_which_path_a_foreign_delta_took_and_what_it_walked_is_counted():
    db, repo = _node(30, {b"k": _doc(50)})
    base = {kind: _tally(db, kind) for kind in ("host_deltas", "host_walked", "foreign_deltas")}
    peer = _Peer(PEER_A)
    for i in range(3):
        repo.converge(b"k", peer.ins(str(3 * 10**18 + i)))
    _get(repo, b"k")  # the read path's trickle: three host folds into a 50-leaf view
    assert _tally(db, "foreign_deltas") - base["foreign_deltas"] == 3
    assert _tally(db, "host_deltas") - base["host_deltas"] == 3
    # entries EXAMINED, the deltas' size and not the view's (50 + 51 + 52
    # until PR 43): the peer's seq 1 ships as a version vector over a
    # replica with no live dot here (0), seqs 2 and 3 as a cloud dot each
    assert _tally(db, "host_walked") - base["host_walked"] == 0 + 1 + 1
    assert _tally(db, "device_deltas") == 0
    assert db.metrics.hist("ujson.host_fold").snapshot()["count"] >= 3
    assert db.metrics.hist("ujson.render").snapshot()["count"] == 1
    repo.drain()  # absorbed already: the fold walks nothing more
    assert _tally(db, "host_deltas") - base["host_deltas"] == 3
    assert _tally(db, "device_deltas") == 0 and b"k" not in repo._pend
    assert db.metrics.hist("drain.UJSON").snapshot()["count"] == 1


def test_the_counters_and_spans_are_on_the_metrics_endpoint_and_the_shutdown_line():
    from jylis_tpu.obs.prom import render

    db, repo = _node(30, {b"k": _doc(50)})
    _write(repo, b"INS", b"k", "1")
    _get(repo, b"k")
    text = render(db)
    for kind in ("admits", "readmits", "demote_write", "demote_overflow", "demote_budget",
                 "resident_rows", "foreign_deltas", "device_deltas", "host_deltas",
                 "host_walked", "local_writes", "row_deltas", "row_rewrites", "row_reads",
                 "render_sorts"):
        assert f'jylis_drain_total{{type="UJSON",kind="{kind}"}}' in text, kind
    assert 'jylis_drain_total{type="UJSON",kind="row_deltas"} 1' in text
    for seam in ("drain.UJSON", "ujson.render", "ujson.host_fold"):
        assert f'jylis_seam_latency_seconds_count{{seam="{seam}"}}' in text
    line = db.metrics.report()
    assert "1 admits" in line and "1 local_writes" in line and "1 row_deltas" in line
    assert "1 render_sorts" in line


def test_a_snapshot_of_resident_documents_restores_to_the_same_answers(tmp_path):
    from jylis_tpu import persist

    keys = [b"doc%d" % i for i in range(5)]
    db, repo = _node(30, {k: _doc(40) for k in keys})
    for i, k in enumerate(keys):
        _write(repo, b"INS", k, str(2 * 10**18 + i))
        _write(repo, b"RM", k, str(10**18 + i))
    want = {k: _get(repo, k) for k in keys}
    path = str(tmp_path / "snapshot.jylis")
    from jylis_tpu.models.database import DATA_TYPE_NAMES

    batch = repo.dump_state()
    persist.write_snapshot([(n, batch if n == "UJSON" else [])
                            for n in DATA_TYPE_NAMES + ("SYSTEM",)], path)
    db2 = Database(identity=ME)
    db2.set_ujson_resident_min(30)
    persist.load_snapshot(db2, path)
    db2.warm_drain_shapes()
    repo2 = db2._map[b"UJSON"].repo
    assert sorted(repo2._res.keys()) == keys and not repo2._data
    assert {k: _get(repo2, k) for k in keys} == want


def test_on_a_serving_mesh_the_row_aligned_fold_keeps_documents_resident_too():
    keys = [b"doc%d" % i for i in range(5)]
    db, repo = _node(30, {k: _doc(40) for k in keys}, mesh=True)
    if repo._mesh is None:
        pytest.skip("one device: no serving mesh")
    peer = _Peer(PEER_A, 40)
    oracle = _doc(40)
    for i in range(30):
        v = str(2 * 10**18 + i)
        _write(repo, b"INS", keys[0], v)
        oracle.ins(ME, PATH, v)
        d = peer.rm(str(10**18 + i))
        repo.converge(keys[0], d)
        oracle.converge(d)
    repo.drain()
    assert repo._is_resident(keys[0]) and _tally(db, "demote_write") == 0
    assert _get(repo, keys[0]) == oracle.render(PATH)
    assert repo._res.read(keys[0]).render(PATH) == oracle.render(PATH)
