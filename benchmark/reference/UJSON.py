"""Plain reference for UJSON add/remove sets: per key one set of member ids
at one path. A set is add-wins observed-remove (docs/types/ujson.md): an
acknowledged ``INS`` adds its id, an acknowledged ``RM`` removes the id if
the node that took it had seen it, a ``RM`` of an id that is not there is a
no-op. The ids are drawn so that the replay needs no order (the
configuration's ``assumed``): a client's ``INS`` carries a fresh id that no
other operation of the cluster names, and a ``RM`` names a base id, which
every node holds from the snapshot on (a ``RM`` of an id that is neither is
a no-op; one of an id a client joined under is refused when the answers are
asked for: it would need an order). So

    final set = (base ids - acknowledged RMs) + acknowledged INSes

whatever node took which write when. ``GET key path`` renders the set as the
documented JSON: members in sorted token order between brackets, one member
bare, none as the empty string. Imports nothing of the program.

State recipe: ``keys`` documents, key ``i`` is ``key_format % i``, each one
set at ``path`` of the ``members`` consecutive whole numbers from
``id_base`` (19 digits, below the generator's first timestamp). What the
seed decides is the causal history behind that state: the replica id of the
loader that wrote the base (one 63-bit draw) and, per document, which
sequence number of the loader added which id (a permutation), so the bytes
of the snapshot and the dots a ``RM`` has to name differ from seed to seed
while the sets, and with them the work, stay the same.
"""

from __future__ import annotations

import numpy as np

NAME = "UJSON"
# the generator's clock starts at TS_EPOCH_MS << TS_SHIFT (harness/gen.py):
# every id a client makes lies at or above it, every base id below
FIRST_CLIENT_ID = 1_767_225_600_000 << 20


class _Context:
    """A causal context in the snapshot's shape: the loader's contiguous
    run as a version vector, no out-of-band dots."""

    def __init__(self, vv: dict[int, int]):
        self.vv = vv
        self.cloud: set = set()


class _Document:
    """A full document in UJSON's wire-delta shape (entries by dot, causal
    context), as the program's snapshot writer reads it."""

    def __init__(self, entries: dict, vv: dict[int, int]):
        self.entries = entries
        self.ctx = _Context(vv)


class Reference:
    def __init__(self, recipe: dict, seed: int, own_rid: int, peer_rids: list[int],
                 hot_keys: np.ndarray, values=None):
        self.recipe = recipe
        n, m = recipe["keys"], recipe["members"]
        self.id_base = int(recipe["id_base"])
        if self.id_base + m > FIRST_CLIENT_ID:
            raise ValueError("base ids must lie below every id a client makes")
        self.members = m
        self.path = recipe["path"]
        self.key_format = recipe["key_format"].encode()
        rng = np.random.default_rng([seed, 0x554A])
        self.loader_rid = int(rng.integers(1, 1 << 63))
        # seq_of[i, j]: the loader's sequence number (1-based) that added
        # base id ``id_base + j`` to document i
        self.seq_of = rng.permuted(np.tile(np.arange(1, m + 1, dtype=np.int64), (n, 1)), axis=1)
        # acknowledged writes per key: base offsets removed, client ids added
        self.removed: list[set[int]] = [set() for _ in range(n)]
        self.added: list[set[int]] = [set() for _ in range(n)]
        # leaves of ids that are no base ids: no-ops as long as nobody
        # joins under them (checked when the answers are asked for)
        self.left_unknown: list[set[int]] = [set() for _ in range(n)]

    def key(self, i: int) -> bytes:
        return self.key_format % i

    def snapshot_batch(self):
        """Full state in UJSON's wire-delta shape: (key, document)."""
        path, rid, base = (self.path,), self.loader_rid, self.id_base
        out = []
        for i in range(self.recipe["keys"]):
            entries = {(rid, seq): (path, str(base + j))
                       for j, seq in enumerate(self.seq_of[i].tolist())}
            out.append((self.key(i), _Document(entries, {rid: self.members})))
        return out

    def apply(self, verb: str, keys: np.ndarray, a: np.ndarray, b: np.ndarray) -> None:
        """Acknowledged ``INS`` (ids ``a``) and ``RM`` (ids ``a``). Sets of
        ids that never meet (see the module's first lines): any order."""
        if verb == "INS":
            for k, ident in zip(keys.tolist(), a.tolist()):
                if ident < FIRST_CLIENT_ID:
                    raise ValueError(f"INS of {ident}: not a client's id")
                self.added[k].add(ident)
        elif verb == "RM":
            lo, hi = self.id_base, self.id_base + self.members
            for k, ident in zip(keys.tolist(), a.tolist()):
                if lo <= ident < hi:
                    self.removed[k].add(ident - lo)
                else:  # not a member: a no-op, acknowledged
                    self.left_unknown[k].add(ident)
        else:
            raise ValueError(f"the UJSON set reference has no write {verb!r}")

    def read_command(self, i: int) -> tuple[bytes, ...]:
        """The whole set: one read per key checks every member."""
        return (b"UJSON", b"GET", self.key(i), self.path.encode())

    def _ids(self, i: int) -> list[int]:
        if self.left_unknown[i] & self.added[i]:
            raise ValueError("a leave names an id a client joined under: the replay "
                             "would need the order the nodes saw them in")
        gone = self.removed[i]
        base = [self.id_base + j for j in range(self.members) if j not in gone]
        return base + list(self.added[i])

    @staticmethod
    def _render(tokens: set[str]) -> bytes:
        ordered = sorted(tokens)
        if not ordered:
            return b""
        if len(ordered) == 1:
            return ordered[0].encode()
        return ("[" + ",".join(ordered) + "]").encode()

    def expected(self, keys) -> list:
        return [self._render({str(x) for x in self._ids(int(i))}) for i in keys]

    def expected_lower_precision(self, keys) -> list:
        """What a path holding ids as float64 would answer: 19-digit ids
        under 2^7 apart collapse onto one member."""
        return [self._render({str(int(np.float64(x))) for x in self._ids(int(i))})
                for i in keys]
