"""UJSON: nested observed-remove maps/sets with causal add-wins semantics.

Host-side authoritative implementation of the documented lattice
(docs/_docs/types/ujson.md:134-182): a UJSON node is a flat set of
(path, primitive-value) pairs, each tagged with a causal dot
(replica-id, seq); removal is by causal context (observed-remove), and a
concurrent insert of an identical pair beats its removal (add-wins).
Reference repo driving it: jylis/repo_ujson.pony:28-110.

The dot-store is an ORSWOT-style delta CRDT (Almeida et al.,
"Efficient State-based CRDTs by Delta-Mutation", PAPERS.md): a mutation's
delta carries only the new entries plus a causal context covering the new
dots and every removed dot. Joins are: keep an entry iff it is present in
both sides, or present in one side and its dot is NOT covered by the other
side's context (i.e. the other side never observed it — it survives).

This lattice lives on the host for SERVING: per-document data is tiny and
pointer-heavy. The anti-entropy fan-in — joining many deltas into many
replicas — is tensorised in ops/ujson_device.py (sorted packed-dot rows,
vv planes, log-depth delta folds), differentially tested against this
oracle.

Values are stored as canonical JSON tokens (the exact primitive serialisation,
e.g. '"user"', '42', 'true', 'null') so value identity is representation
identity — 1 and 1.0 stay distinct, matching string-typed storage in the
reference.
"""

from __future__ import annotations

import json
from bisect import bisect_left, insort
from collections import defaultdict

Dot = tuple[int, int]  # (replica-id, seq)
Path = tuple[str, ...]


class CausalContext:
    """Compacted causal history: per-replica contiguous max (version vector)
    plus a cloud of out-of-band dots (ujson.md:176 — compaction keeps this
    bounded)."""

    __slots__ = ("vv", "cloud")

    def __init__(self):
        self.vv: dict[int, int] = {}
        self.cloud: set[Dot] = set()

    def contains(self, dot: Dot) -> bool:
        r, s = dot
        return s <= self.vv.get(r, 0) or dot in self.cloud

    def __eq__(self, other) -> bool:
        """REPRESENTATIONAL equality (vv and cloud as stored) — what the
        wire codec round-trips; two contexts with identical coverage but
        different compaction states compare unequal."""
        return (
            isinstance(other, CausalContext)
            and self.vv == other.vv
            and self.cloud == other.cloud
        )

    # defining __eq__ sets __hash__ to None implicitly; keep that intent
    # EXPLICIT: contexts are mutable lattice state and must never be
    # dict keys or set members (a silent identity-hash would let two
    # equal contexts land in different buckets)
    __hash__ = None

    def add(self, dot: Dot) -> None:
        self.cloud.add(dot)
        self.compact()

    def next_dot(self, replica: int) -> Dot:
        """Mint the next contiguous dot for a replica (local mutations only)."""
        s = self.vv.get(replica, 0) + 1
        self.vv[replica] = s
        return (replica, s)

    def join(self, other: "CausalContext") -> None:
        for r, s in other.vv.items():
            if s > self.vv.get(r, 0):
                self.vv[r] = s
        self.cloud |= other.cloud
        self.compact()

    def compact(self) -> None:
        moved = True
        while moved:
            moved = False
            for dot in list(self.cloud):
                r, s = dot
                top = self.vv.get(r, 0)
                if s == top + 1:
                    self.vv[r] = s
                    self.cloud.discard(dot)
                    moved = True
                elif s <= top:
                    self.cloud.discard(dot)
                    moved = True


def parse_doc(doc: str) -> list[tuple[Path, str]]:
    """Parse a JSON document into its UJSON leaves: (relative-path, token).

    Maps extend the path; sets (JSON arrays) do NOT contribute path
    components, which is exactly why nested sets flatten and sibling maps
    in a set merge (ujson.md:165-170).
    """
    data = json.loads(doc)
    leaves: list[tuple[Path, str]] = []

    def walk(node, path: Path):
        if isinstance(node, dict):
            for k, v in node.items():
                walk(v, path + (str(k),))
        elif isinstance(node, list):
            for v in node:
                walk(v, path)
        else:
            leaves.append((path, json.dumps(node)))

    walk(data, ())
    return leaves


def parse_value(doc: str) -> str:
    """Parse a single JSON primitive (INS/RM argument) to its token; raises
    ValueError on maps/sets (ujson.md:83)."""
    data = json.loads(doc)
    if isinstance(data, (dict, list)):
        raise ValueError("value must be a JSON primitive")
    return json.dumps(data)


class _Index:
    """What `UJSON` keeps beside its dot-store so that an operation costs
    what it changes and not the whole document (see the class)."""

    __slots__ = ("of", "paths", "order", "seqs")

    def __init__(self, entries: dict[Dot, tuple[Path, str]]):
        self.of = entries  # the dict this index describes, by identity
        # path -> token -> the dots that carry (path, token): a tuple,
        # one dot long but for a pair inserted concurrently
        paths: dict[Path, dict[str, tuple[Dot, ...]]] = {}
        last = toks = None
        for d, (p, t) in entries.items():
            if p is not last:  # a set's leaves share their path object
                last = p
                toks = paths.get(p)
                if toks is None:
                    toks = paths[p] = {}
            if t in toks:
                toks[t] += (d,)
            else:
                toks[t] = (d,)
        # replica -> the seqs of its live dots
        seqs: defaultdict[int, set[int]] = defaultdict(set)
        for r, s in entries:
            seqs[r].add(s)
        self.paths = paths
        self.seqs = seqs
        # path -> its distinct tokens in sorted order, from the path's
        # first render on (kept by ordered insert and delete after it)
        self.order: dict[Path, list[str]] = {}


class UJSON:
    """One document: dot-store + causal context, with delta-mutators.

    Every mutator takes an optional ``delta`` UJSON accumulating the minimal
    joinable state of the mutation (the reference's delta-accumulator
    pattern, repo_ujson.pony:53-66); deltas for the same document within a
    flush window coalesce by join.

    ``walked`` and ``sorts`` count work, for whoever serves the document
    (models/repo_ujson.py): entries `converge` examined for removal, and
    token orders `render` had to sort instead of reading them.
    """

    __slots__ = ("entries", "ctx", "_idx", "walked", "sorts")

    def __init__(self):
        self.entries: dict[Dot, tuple[Path, str]] = {}
        self.ctx = CausalContext()
        self._idx: _Index | None = None
        self.walked = 0
        self.sorts = 0

    # -- the index over the dot-store ---------------------------------------
    #
    # A set of 1,000 members takes writes, foreign deltas and reads one
    # member at a time, and a walk of every entry for each of them is the
    # floor of a serving mix ("Big(ger) Sets", arXiv:1605.06424: a write's
    # cost should be flat in the set's cardinality). The index has three
    # levels, built together at the first operation that needs one and
    # maintained by the internal mutators:
    #
    # * path -> token -> dots: set_doc/rm/clr observe (then remove) the
    #   dots at or under a path, rm those of one token; render reads the
    #   tokens AT a path and finds the children under it among the path
    #   keys;
    # * path -> sorted distinct tokens: what render joins. Sorted at the
    #   path's first render, then kept by ordered insert and delete;
    # * replica -> live seqs: converge's candidates for removal under the
    #   other side's version vector.
    #
    # It is keyed on the entries dict's IDENTITY, so consumers that
    # install a fresh entries dict wholesale (WireUJSON._materialize, test
    # fixtures) invalidate it by construction. Code outside this class
    # must never mutate an existing entries dict in place after the doc
    # has served an operation — decode paths populate entries only at
    # construction, before any index exists. (getattr: a WireUJSON is
    # made without __init__, on the receive hot path.)

    def _kept(self) -> _Index | None:
        """The index, if one was built over the entries dict that stands."""
        idx = getattr(self, "_idx", None)
        return idx if idx is not None and idx.of is self.entries else None

    def _index(self) -> _Index:
        idx = self._kept()
        if idx is None:
            idx = self._idx = _Index(self.entries)
        return idx

    def _idx_add(self, dot: Dot, path: Path, token: str) -> None:
        idx = self._kept()
        if idx is None:
            return
        toks = idx.paths.get(path)
        if toks is None:
            toks = idx.paths[path] = {}
        have = toks.get(token)
        if have is None:
            toks[token] = (dot,)
            order = idx.order.get(path)
            if order is not None:
                insort(order, token)
        else:
            toks[token] = have + (dot,)
        idx.seqs[dot[0]].add(dot[1])

    def _idx_drop(self, dot: Dot, path: Path, token: str) -> None:
        idx = self._kept()
        if idx is None:
            return
        toks = idx.paths[path]
        left = tuple(d for d in toks[token] if d != dot)
        if left:
            toks[token] = left
        else:
            del toks[token]
            order = idx.order.get(path)
            if not toks:
                del idx.paths[path]
                idx.order.pop(path, None)
            elif order is not None:
                del order[bisect_left(order, token)]
        live = idx.seqs[dot[0]]
        live.discard(dot[1])
        if not live:
            del idx.seqs[dot[0]]

    def __eq__(self, other) -> bool:
        """Representational equality (see CausalContext.__eq__): used by
        message equality in the codec differential tests."""
        return (
            isinstance(other, UJSON)
            and self.entries == other.entries
            and self.ctx == other.ctx
        )

    __hash__ = None  # see CausalContext.__hash__: mutable, never hashable

    # ---- queries ----------------------------------------------------------

    def _under(self, path: Path) -> list[Dot]:
        n = len(path)
        out: list[Dot] = []
        for p, toks in self._index().paths.items():
            if p[:n] == path:
                for dots in toks.values():
                    out.extend(dots)
        return out

    def is_empty(self) -> bool:
        return not self.entries

    def render(self, path: Path = ()) -> str:
        """Render the subtree at path as compact JSON; "" when absent
        (ujson.md:34-38). Set/map member order is unspecified by the
        semantics; we emit a deterministic sorted order."""
        idx = self._index()
        n = len(path)
        children = sorted({p[n] for p in idx.paths if len(p) > n and p[:n] == path})
        vals: list[str] = []
        if path in idx.paths:
            vals = idx.order.get(path)
            if vals is None:
                vals = idx.order[path] = sorted(idx.paths[path])
                self.sorts = getattr(self, "sorts", 0) + 1
        if not children:
            if not vals:
                return ""
            return vals[0] if len(vals) == 1 else "[" + ",".join(vals) + "]"
        rendered_map = (
            "{" + ",".join(json.dumps(k) + ":" + self.render(path + (k,)) for k in children) + "}"
        )
        if not vals:
            return rendered_map
        return "[" + ",".join(vals + [rendered_map]) + "]"

    # ---- mutators ---------------------------------------------------------

    def _remove_dots(self, dots, delta: "UJSON | None") -> None:
        """Observed-remove: drop entries and record their dots in our context
        and in the delta's context (no delta entries -> receiver removes).
        A dot the SAME delta window added must also drop out of the
        delta's entries: an entry whose dot its own context covers reads
        as LIVE to any converger, so leaving it would resurrect the
        removed value on every receiver that had not yet seen the add
        (same-window SET+RM over anti-entropy, journal replay)."""
        for d in dots:
            pv = self.entries.pop(d, None)
            if pv is not None:
                self._idx_drop(d, *pv)
            self.ctx.add(d)
            if delta is not None:
                dpv = delta.entries.pop(d, None)
                if dpv is not None:
                    delta._idx_drop(d, *dpv)
                delta.ctx.add(d)

    def _add_leaf(self, replica: int, path: Path, token: str, delta) -> None:
        dot = self.ctx.next_dot(replica)
        self.entries[dot] = (path, token)
        self._idx_add(dot, path, token)
        if delta is not None:
            delta.entries[dot] = (path, token)
            delta._idx_add(dot, path, token)
            delta.ctx.add(dot)

    def set_doc(self, replica: int, path: Path, doc: str, delta=None) -> None:
        """SET: clear the subtree (observed dots only), then add the parsed
        leaves under fresh dots (ujson.md:44-61)."""
        leaves = parse_doc(doc)
        self._remove_dots(self._under(path), delta)
        for sub, token in leaves:
            self._add_leaf(replica, path + sub, token, delta)

    def ins(self, replica: int, path: Path, value: str, delta=None) -> None:
        """INS: add one primitive alongside existing values (ujson.md:77-89)."""
        self._add_leaf(replica, path, parse_value(value), delta)

    def rm(self, replica: int, path: Path, value: str, delta=None) -> None:
        """RM: remove the observed dots of one exact (path, value) pair
        (ujson.md:91-103)."""
        token = parse_value(value)
        self._remove_dots(self._index().paths.get(path, {}).get(token, ()), delta)

    def clr(self, replica: int, path: Path, delta=None) -> None:
        """CLR: remove all observed dots at or under path (ujson.md:63-75)."""
        self._remove_dots(self._under(path), delta)

    # ---- lattice ----------------------------------------------------------

    def converge(self, other: "UJSON") -> bool:
        """ORSWOT join; returns True if local state changed. Its cost
        follows the OTHER side: the dots of its context's cloud, the live
        seqs here of each replica in its version vector, its entries."""
        mine, theirs, octx = self.entries, other.entries, other.ctx
        # entries present only here, observed (covered) by other -> removed:
        # only a dot of the cloud, or one at or under the version vector's
        # seq for its replica, can be covered
        gone = [d for d in octx.cloud if d in mine and d not in theirs]
        walked = len(octx.cloud)
        if octx.vv:
            seqs = self._index().seqs
            for r, top in octx.vv.items():
                live = seqs.get(r)
                if live:
                    walked += len(live)
                    gone.extend(
                        (r, s) for s in live if s <= top and (r, s) not in theirs
                    )
        self.walked = getattr(self, "walked", 0) + walked
        changed = False
        for d in gone:
            pv = mine.pop(d, None)
            if pv is not None:
                self._idx_drop(d, *pv)
                changed = True
        # entries present only there, not covered by us -> added
        for d, pv in theirs.items():
            if d not in mine and not self.ctx.contains(d):
                mine[d] = pv
                self._idx_add(d, *pv)
                changed = True
        before = (dict(self.ctx.vv), set(self.ctx.cloud))
        self.ctx.join(octx)
        if (self.ctx.vv, self.ctx.cloud) != before:
            changed = True
        return changed
