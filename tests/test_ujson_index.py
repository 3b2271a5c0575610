"""The index `UJSON` keeps beside its dot-store, held to the plain walks.

`ops/ujson_host.py` answers `render`, `rm` and `converge` from an index
(path -> token -> dots, a sorted token order per rendered path, replica ->
live seqs) that its mutators maintain, so that an operation on a
1,000-member set costs what it changes. The three walks of every entry
that it replaced are the ORACLE here (`Plain`): each generated history
runs on both, step by step, and after every step the two must hold the
same entries and context (representational equality), render the same
bytes at every path, and the kept index must be what a fresh build over
the entries gives. A second group holds the COST without a clock, through
the repo's counters: a one-dot foreign delta into a 1,000-member view
examines a handful of entries, a GET after a write reads the kept order,
a fresh view's first GET sorts once.
"""

import json
import random

import pytest

import jylis_tpu  # noqa: F401
from jylis_tpu.cluster import codec
from jylis_tpu.ops.ujson_host import UJSON, CausalContext, _Index, parse_value
from jylis_tpu.ops.ujson_wire import WireUJSON

from test_ujson_resident_min import LOADER, ME, PATH, PEER_A, _Peer, _doc, _get, _node, _tally, _write


class Plain(UJSON):
    """The document with every query a walk of the entries: what
    `UJSON.render`, `rm`, `_under` and `converge` were before the index.
    The mutators' bookkeeping (`_add_leaf`, `_remove_dots`) is shared; the
    index hooks do nothing, so no index is ever consulted."""

    __slots__ = ()

    def _idx_add(self, dot, path, token):
        pass

    def _idx_drop(self, dot, path, token):
        pass

    def _under(self, path):
        n = len(path)
        return [d for d, (p, _) in self.entries.items() if p[:n] == path]

    def render(self, path=()):
        n = len(path)
        values, children = set(), set()
        for p, token in self.entries.values():
            if p[:n] != path:
                continue
            if len(p) == n:
                values.add(token)
            else:
                children.add(p[n])
        if not values and not children:
            return ""
        rendered_map = None
        if children:
            rendered_map = (
                "{"
                + ",".join(json.dumps(k) + ":" + self.render(path + (k,)) for k in sorted(children))
                + "}"
            )
        vals = sorted(values)
        if rendered_map is None:
            return vals[0] if len(vals) == 1 else "[" + ",".join(vals) + "]"
        if not vals:
            return rendered_map
        return "[" + ",".join(vals + [rendered_map]) + "]"

    def rm(self, replica, path, value, delta=None):
        token = parse_value(value)
        self._remove_dots(
            [d for d, pv in self.entries.items() if pv == (path, token)], delta
        )

    def converge(self, other):
        changed = False
        for d in list(self.entries):
            if d not in other.entries and other.ctx.contains(d):
                del self.entries[d]
                changed = True
        for d, pv in other.entries.items():
            if d not in self.entries and not self.ctx.contains(d):
                self.entries[d] = pv
                changed = True
        before = (dict(self.ctx.vv), set(self.ctx.cloud))
        self.ctx.join(other.ctx)
        return changed or (self.ctx.vv, self.ctx.cloud) != before


def _copy(doc: UJSON, cls=UJSON) -> UJSON:
    """The same state in fresh containers, with no index."""
    out = cls()
    out.entries = dict(doc.entries)
    out.ctx.vv = dict(doc.ctx.vv)
    out.ctx.cloud = set(doc.ctx.cloud)
    return out


def _wire(doc: UJSON) -> WireUJSON:
    """The delta as a node receives it: wire bytes, nothing materialised."""
    raw = bytearray()
    codec._w_ujson(raw, doc)
    seqs = [s for _, s in doc.entries] + list(doc.ctx.vv.values()) + [s for _, s in doc.ctx.cloud]
    return WireUJSON(
        bytes(raw), len(doc.entries), len(doc.ctx.vv), len(doc.ctx.cloud), max(seqs, default=0)
    )


def _same(real: UJSON, plain: UJSON) -> None:
    assert real.entries == plain.entries
    assert real.ctx == plain.ctx and real == plain
    prefixes = {p[:i] for p, _ in plain.entries.values() for i in range(len(p) + 1)}
    for path in prefixes | {(), ("nowhere",), ("a", "nowhere")}:
        assert real.render(path) == plain.render(path), path
    # the kept index is what a fresh build over the entries gives
    idx, fresh = real._idx, _Index(real.entries)
    assert idx.of is real.entries
    assert {p: {t: sorted(ds) for t, ds in toks.items()} for p, toks in idx.paths.items()} == {
        p: {t: sorted(ds) for t, ds in toks.items()} for p, toks in fresh.paths.items()
    }
    assert dict(idx.seqs) == dict(fresh.seqs)
    assert idx.order == {p: sorted(idx.paths[p]) for p in idx.order}


class Pair:
    """One replica, twice: the indexed document and the plain one, each
    with the flush delta its writes accumulate."""

    def __init__(self, rid: int):
        self.rid = rid
        self.real, self.plain = UJSON(), Plain()
        self.d_real, self.d_plain = UJSON(), Plain()

    def write(self, op: str, path, *value) -> None:
        getattr(self.real, op)(self.rid, path, *value, self.d_real)
        getattr(self.plain, op)(self.rid, path, *value, self.d_plain)
        self.check()

    def check(self) -> None:
        _same(self.real, self.plain)
        assert self.d_real.entries == self.d_plain.entries and self.d_real.ctx == self.d_plain.ctx

    def flush(self) -> tuple[UJSON, UJSON]:
        """The accumulated delta, as a flush ships it (same for both)."""
        out = (self.d_real, _copy(self.d_real, Plain))
        self.d_real, self.d_plain = UJSON(), Plain()
        return out

    def state(self) -> tuple[UJSON, UJSON]:
        """The whole document as a delta: a sync, a restore."""
        return _copy(self.real), _copy(self.real, Plain)

    def join(self, delta: tuple[UJSON, UJSON]) -> None:
        a = self.real.converge(delta[0])
        b = self.plain.converge(delta[1])
        assert a == b
        self.check()


def _value(rng) -> str:
    return rng.choice(['"v%d"' % rng.randrange(12), str(rng.randrange(6)), "true", "null", "1.0"])


def _random_write(rng, pair: Pair, paths) -> None:
    op = rng.choice(["ins", "ins", "ins", "rm", "rm", "set_doc", "clr"])
    path = rng.choice(paths)
    if op == "set_doc":
        pair.write(op, path, rng.choice(_DOCS))
    elif op == "clr":
        pair.write(op, path)
    else:
        pair.write(op, path, _value(rng))


_DOCS = [
    '["v1","v2",3]',
    '{"x":1,"y":["p","q"]}',
    '[7,{"deep":{"er":"z"}},"v3"]',
    '"alone"',
    "[]",
    '{"x":{"k":[1,2]}}',
]
FLAT = [(), ("members",)]
# every path here also has children under it once a SET of a map lands
NESTED = [(), ("a",), ("a", "x"), ("a", "x", "k"), ("b",), ("a", "deep")]


def history_flat_sets(rng):
    p = Pair(1)
    for _ in range(150):
        _random_write(rng, p, FLAT)


def history_nested_maps_with_children_under_a_rendered_path(rng):
    p = Pair(1)
    p.write("set_doc", ("a",), '{"x":{"k":[1,2]},"y":"leaf"}')
    p.write("ins", ("a",), '"beside-the-map"')  # values AND children at ("a",)
    assert p.real.render(("a",)) == '["beside-the-map",{"x":{"k":[1,2]},"y":"leaf"}]'
    for _ in range(150):
        _random_write(rng, p, NESTED)


def history_one_token_inserted_at_two_replicas(rng):
    a, b = Pair(1), Pair(2)
    a.write("ins", PATH, '"same"')
    b.write("ins", PATH, '"same"')
    b.write("ins", PATH, '"other"')
    a.join(b.flush())
    assert len(a.real.entries) == 3 and a.real.render(PATH) == '["other","same"]'
    a.write("rm", PATH, '"same"')  # takes both dots
    assert a.real.render(PATH) == '"other"' and len(a.real.entries) == 1
    b.join(a.flush())
    assert b.real.render(PATH) == '"other"'


def _three(rng, ship):
    """Three replicas writing at random; `ship(src)` makes the delta."""
    reps = [Pair(r) for r in (1, 2, 3)]
    for step in range(120):
        src = rng.choice(reps)
        _random_write(rng, src, FLAT + NESTED[1:3])
        if step % 3 == 2:
            delta = ship(src)
            for dst in reps:
                if dst is not src and rng.random() < 0.8:
                    dst.join(delta)
    for src in reps:
        delta = src.state()
        for dst in reps:
            dst.join(delta)
    assert len({r.real.render(()) for r in reps}) == 1


def history_deltas_whose_context_is_a_cloud(rng):
    # a replica's first write of a document mints seq 1, which compacts
    # into the delta's version vector: spend it, so every flush is cloud
    def ship(src):
        real, plain = src.flush()
        for d in (real, plain):
            d.ctx.cloud |= {(r, s) for r, top in d.ctx.vv.items() for s in range(1, top + 1)}
            d.ctx.vv = {}
        assert not real.ctx.vv
        return real, plain

    _three(rng, ship)


def history_deltas_whose_context_is_a_version_vector(rng):
    _three(rng, lambda src: src.state())


def history_deltas_whose_context_is_both(rng):
    def ship(src):
        real, plain = src.flush()
        if rng.random() < 0.5:
            return real, plain
        # the flush joined into the whole state's context: vv and cloud
        whole = src.state()
        for d, w in zip((real, plain), whole):
            d.ctx.vv = dict(w.ctx.vv)
            d.ctx.cloud |= {(9, 5), (9, 7)}
        return real, plain

    _three(rng, ship)


def history_a_wire_delta_that_materialises_late(rng):
    a, b = Pair(1), Pair(2)
    for _ in range(40):
        _random_write(rng, a, FLAT)
        _random_write(rng, b, FLAT)
        real, plain = b.flush()
        w = _wire(real)
        assert not w._mat
        a.join((w, plain))
        assert w._mat
    # and as the RECEIVER: a WireUJSON is a document too (made without
    # __init__: no index slot, no counters until something asks)
    w, plain = _wire(a.real), _copy(a.real, Plain)
    delta = b.state()
    assert w.render(()) == plain.render(())
    w.converge(delta[0])
    plain.converge(delta[1])
    w.rm(1, (), '"v1"')
    plain.rm(1, (), '"v1"')
    _same(w, plain)


def history_entries_installed_wholesale_after_the_index_was_built(rng):
    a, b = Pair(1), Pair(2)
    for _ in range(30):
        _random_write(rng, a, FLAT)
        _random_write(rng, b, NESTED)
    a.real.render(())
    old = a.real._idx
    for doc in (a.real, a.plain):  # a fixture's, a materialisation's way
        doc.entries = dict(b.real.entries)
        doc.ctx = CausalContext()
        doc.ctx.join(b.real.ctx)
    a.check()
    assert a.real._idx is not old
    for _ in range(60):
        _random_write(rng, a, NESTED)
    a.join(b.state())


def history_a_delta_applied_twice(rng):
    a, b = Pair(1), Pair(2)
    for _ in range(60):
        _random_write(rng, a, FLAT)
        _random_write(rng, b, FLAT)
        real, plain = b.flush()
        a.join((real, plain))
        before = _copy(a.real)
        assert a.real.converge(real) is False  # idempotent: nothing changes
        assert a.real == before
        a.join((real, plain))


def history_two_deltas_in_both_orders(rng):
    base, b, c = Pair(1), Pair(2), Pair(3)
    for _ in range(40):
        _random_write(rng, base, FLAT + NESTED[1:3])
        seed = base.flush()
        b.join(seed)
        c.join(seed)
        for _ in range(rng.randrange(1, 4)):
            _random_write(rng, b, FLAT + NESTED[1:3])
            _random_write(rng, c, FLAT + NESTED[1:3])
        db, dc = b.flush(), c.flush()
        other = Pair(1)
        other.real, other.plain = _copy(base.real), _copy(base.real, Plain)
        base.join(db)
        base.join(dc)
        other.join(dc)
        other.join(db)
        assert other.real == base.real and other.real.render(()) == base.real.render(())
        b.join(dc)
        c.join(db)


HISTORIES = [
    history_flat_sets,
    history_nested_maps_with_children_under_a_rendered_path,
    history_one_token_inserted_at_two_replicas,
    history_deltas_whose_context_is_a_cloud,
    history_deltas_whose_context_is_a_version_vector,
    history_deltas_whose_context_is_both,
    history_a_wire_delta_that_materialises_late,
    history_entries_installed_wholesale_after_the_index_was_built,
    history_a_delta_applied_twice,
    history_two_deltas_in_both_orders,
]


@pytest.mark.parametrize("seed", [0, 1, 2])
@pytest.mark.parametrize("history", HISTORIES, ids=lambda h: h.__name__[len("history_"):])
def test_the_indexed_document_is_the_plain_walks_document(history, seed):
    history(random.Random(1000 * seed + 43))


# ---- the cost, without a clock ---------------------------------------------


def test_a_one_dot_foreign_delta_examines_a_handful_of_a_1000_member_view():
    db, repo = _node(500, {b"k": _doc(1000)})
    oracle = _doc(1000)
    assert _get(repo, b"k") == oracle.render(PATH)  # the view, decoded
    peer = _Peer(PEER_A, 1000)
    peer.ins("1")  # the peer's seq 1 would ship as a version vector
    deltas = [peer.ins(str(3 * 10**18)), peer.rm(str(10**18 + 5)), peer.ins(str(3 * 10**18 + 1))]
    folded = _tally(db, "host_deltas")  # the boot's: the restored document
    for n, d in enumerate(deltas, 1):
        assert not d.ctx.vv and len(d.ctx.cloud) == 1
        walked = _tally(db, "host_walked")
        repo.converge(b"k", d)
        oracle.converge(d)
        assert _get(repo, b"k") == oracle.render(PATH)  # the read's trickle folds it
        assert _tally(db, "host_deltas") - folded == n
        assert _tally(db, "host_walked") - walked <= 2  # not ~1,000
    assert len(repo._res_cache[b"k"].entries) == 1001


def test_a_get_after_a_write_reads_the_kept_order_and_a_fresh_view_sorts_once():
    db, repo = _node(500, {b"k": _doc(1000)})
    oracle = _doc(1000)
    assert _tally(db, "render_sorts") == 0 and not repo._res_cache
    assert _get(repo, b"k") == oracle.render(PATH)
    assert _tally(db, "render_sorts") == 1  # a freshly decoded view's first
    assert _get(repo, b"k") == oracle.render(PATH)
    for op, value in ((b"INS", str(5)), (b"RM", str(10**18 + 7)), (b"INS", str(10**18 + 7)),
                      (b"RM", str(5))):
        _write(repo, op, b"k", value)
        getattr(oracle, op.decode().lower())(ME, PATH, value)
        assert _get(repo, b"k") == oracle.render(PATH)
        assert _get(repo, b"k") == oracle.render(PATH)
    assert _tally(db, "render_sorts") == 1  # the writes kept the order
    repo._drain_key(b"k", fold=True)  # the fold drops the view
    assert b"k" not in repo._res_cache
    assert _get(repo, b"k") == oracle.render(PATH)
    assert _tally(db, "render_sorts") == 2 and _tally(db, "row_reads") == 2


def test_a_whole_state_delta_walks_the_replicas_seqs_and_gives_the_plain_answer():
    db, repo = _node(500, {b"k": _doc(1000)})
    _get(repo, b"k")
    # the loader's replica, as a peer that removed half of the set sends
    # its whole state: a version vector over all 1,000 of the base's dots
    peer = _doc(1000)
    for j in range(0, 1000, 2):
        peer.rm(LOADER, PATH, str(10**18 + j))
    peer.ins(PEER_A, PATH, "77")
    assert peer.ctx.vv[LOADER] == 1000 and not peer.ctx.cloud
    plain = _copy(_doc(1000), Plain)
    plain.converge(_copy(peer, Plain))
    walked = _tally(db, "host_walked")
    view = repo._res_cache[b"k"]
    repo._host_fold(view, [_copy(peer)])
    assert view == plain and view.render(PATH) == plain.render(PATH)
    assert len(view.entries) == 501
    assert _tally(db, "host_walked") - walked == 1000  # as dear as the walk, no dearer
