"""Plain reference for MAP holding YCSB's record: per record and field, the
greatest ``(timestamp, value)`` among the base field and every acknowledged
``MAP TREG SET`` of THAT field (a write of one field never displaces
another; one field is last writer wins, equal timestamps fall to the
greater value). A read is the whole record (``MAP TREG GETALL``): every
field, in ascending byte order of the field names, each with its value and
timestamp. Imports nothing of the program.

State recipe: ``keys`` records, record ``i`` is ``key_format % i``, each of
``fields`` fields named ``field0`` ... (YCSB's CoreWorkload names), every
base field with a timestamp drawn from [2^40, ts_ceiling) and
``value_bytes`` bytes made from the nonce ``2^62 | (i * fields + j)`` by the
generator's `Values`. Values are rebuilt from their nonce on demand. The
snapshot is MAP's wire-delta shape: one unit a FIELD under the packed
``(key, field)`` wire key, ``("TREG", {writer: 1}, {}, (value, ts))``: one
edit by the node itself, no tombstone. It is handed over ENCODED (`WireState`):
10^7 units as one buffer of the bytes a push message carries, written here
by this file's own encoder, so the harness holds 1.5 GB of bytes and not
3 x 10^7 tuples, and the program's decoder is held to an encoder that is
not its own. The program's snapshot writer takes a batch in that form since
PR 46; one that does not (PR 45's) raises on it before any node is spawned,
which is how a program that cannot run this deployment fails: at once.
"""

from __future__ import annotations

from functools import cached_property

import numpy as np

NAME = "MAP"
BASE_NONCE = 1 << 62


def varint(n: int) -> bytes:
    """LEB128: seven bits a byte, low bits first, the top bit set on all
    but the last."""
    out = bytearray()
    while True:
        b, n = n & 0x7F, n >> 7
        out.append(b | 0x80 if n else b)
        if not n:
            return bytes(out)


def pack_field(key: bytes, field: bytes) -> bytes:
    """The wire key of one field: the key's length as a varint, the key,
    the field name."""
    return varint(len(key)) + key + field


def varint_columns(a: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    """`varint` of every u64 of ``a``: (bytes (n, longest) u8, how many of
    each row's bytes count (n,))."""
    a = a.astype(np.uint64)
    cols = np.empty((len(a), 10), np.uint8)
    width = np.ones(len(a), np.int64)
    for p in range(10):
        rest = a >> np.uint64(7 * p)
        more = (rest >> np.uint64(7)) != 0
        cols[:, p] = (rest & np.uint64(0x7F)).astype(np.uint8) | (more.astype(np.uint8) << 7)
        width += more
    return cols[:, : int(width.max())], width


def padded(items: list[bytes]) -> tuple[np.ndarray, np.ndarray]:
    """Byte strings as rows (n, longest) u8, and their lengths."""
    width = np.array([len(b) for b in items], np.int64)
    w = int(width.max())
    rows = np.frombuffer(b"".join(b.ljust(w, b"\0") for b in items), np.uint8)
    return rows.reshape(len(items), w), width


def join_rows(segments: list[tuple[np.ndarray, np.ndarray]]) -> bytes:
    """Rows of several (bytes (n, w), lengths (n,)) laid side by side and
    each row's counted bytes run together, row after row: everything but
    the few stretches that a shorter entry leaves uncounted."""
    rows = np.concatenate([m for m, _w in segments], axis=1)
    n, stride = rows.shape
    blob, gaps, at = rows.tobytes(), [], 0
    for m, w in segments:
        short = np.flatnonzero(w < m.shape[1])
        gaps.append(np.stack([short * stride + at + w[short],
                              short * stride + at + m.shape[1]], axis=1))
        at += m.shape[1]
    gaps = np.concatenate(gaps)
    gaps = gaps[np.argsort(gaps[:, 0])]
    return b"".join(blob[a:b] for a, b in zip([0] + gaps[:, 1].tolist(),
                                              gaps[:, 0].tolist() + [n * stride]))


class WireState:
    """A type's whole state as ONE buffer in its wire form: ``len()`` units,
    ``payload`` their bytes, made when first asked for. Not iterable: there
    is no tuple a unit to hand out."""

    def __init__(self, count: int, make_payload):
        self.count = count
        self._make = make_payload

    def __len__(self) -> int:
        return self.count

    @cached_property
    def payload(self) -> bytes:
        return self._make()


class Reference:
    def __init__(self, recipe: dict, seed: int, own_rid: int, peer_rids: list[int],
                 hot_keys: np.ndarray, values=None):
        self.recipe = recipe
        n, f = recipe["keys"], recipe["fields"]
        self.names = [b"field%d" % j for j in range(f)]
        self.order = sorted(range(f), key=lambda j: self.names[j])  # a reply's order
        rng = np.random.default_rng([seed, 0x4D4150])
        # flat: field j of record i is cell i * fields + j
        self.ts = rng.integers(1 << 40, recipe["ts_ceiling"], n * f, dtype=np.uint64)
        self.nonce = np.arange(n * f, dtype=np.uint64) | np.uint64(BASE_NONCE)
        self.size = recipe["value_bytes"]
        self.values = values
        self.own_rid = own_rid
        self.key_format = recipe["key_format"].encode()

    def key(self, i: int) -> bytes:
        return self.key_format % i

    def snapshot_batch(self) -> WireState:
        return WireState(self.recipe["keys"] * len(self.names), self._encode_state)

    def _encode_state(self) -> bytearray:
        """Every base field's unit, record by record, field by field:
        bytes(packed key) | str "TREG" | {own_rid: 1} | {} | bytes(value) |
        varint(ts), a length a varint and a dict its count then its
        (replica, counter) pairs."""
        size, names, f = self.size, self.names, len(self.names)
        # between a unit's key and its value nothing varies but the field
        mid, mid_w = padded([name + b"\x04TREG" + b"\x01" + varint(self.own_rid) + b"\x01"
                             + b"\x00" + varint(size) for name in names])
        name_w = np.array([len(name) for name in names], np.int64)
        out, step = bytearray(), 20_000
        for i0 in range(0, self.recipe["keys"], step):
            i1 = min(i0 + step, self.recipe["keys"])
            n = i1 - i0
            # a record's part of the packed key, once for each of its fields
            head, head_w = padded([pack_field(self.key_format % i, b"") for i in range(i0, i1)])
            head, head_w = np.repeat(head, f, axis=0), np.repeat(head_w, f)
            cells = np.arange(i0 * f, i1 * f, dtype=np.uint64)
            value = self.values_of(cells | np.uint64(BASE_NONCE))
            out += join_rows([
                varint_columns(head_w + np.tile(name_w, n)), (head, head_w),
                (np.tile(mid, (n, 1)), np.tile(mid_w, n)),
                (value, np.full(n * f, size)), varint_columns(self.ts[i0 * f : i1 * f])])
        return out

    def values_of(self, nonce: np.ndarray) -> np.ndarray:
        """`Values.make` of every nonce, as rows (n, value_bytes) u8: sixteen
        hexadecimal digits of the nonce, then the pool from where the nonce
        hashes to."""
        size, pool = self.size, np.frombuffer(self.values.pool, np.uint8)
        octets = nonce.astype(">u8").view(np.uint8).reshape(-1, 8)
        hexed = np.frombuffer(b"0123456789abcdef", np.uint8)[
            np.stack([octets >> 4, octets & 15], axis=2).reshape(-1, 16)]
        if size <= 16:
            return hexed[:, :size]
        off = ((nonce * np.uint64(0x9E3779B97F4A7C15)) >> np.uint64(24)) % np.uint64(len(pool) - size)
        slices = np.lib.stride_tricks.sliding_window_view(pool, size - 16)
        return np.concatenate([hexed, slices[off.astype(np.int64)]], axis=1)

    def field_of(self, template_text: str) -> int:
        """``MAP TREG SET {key} field3 {value:100} {ts}``: its field."""
        words = template_text.split(" ")
        if words[:3] != ["MAP", "TREG", "SET"]:
            raise ValueError(f"MAP has no write {template_text!r}")
        return self.names.index(words[4].encode())

    def apply_op(self, template_text: str, keys: np.ndarray, a: np.ndarray,
                 b: np.ndarray) -> None:
        """Acknowledged SETs of ONE field (the template's literal field
        name): timestamps ``a``, value nonces ``b``."""
        cells = keys.astype(np.int64) * len(self.names) + self.field_of(template_text)
        a, b = a.astype(np.uint64), b.astype(np.uint64)
        # the greatest timestamp of a cell decides: sort by (cell, ts), keep
        # each cell's last; equal timestamps (the generator makes none, a
        # test does) fall to the greater value
        order = np.lexsort((a, cells))
        c, t, v = cells[order], a[order], b[order]
        for x in np.flatnonzero((c[1:] == c[:-1]) & (t[1:] == t[:-1])):
            if self._value(v[x]) > self._value(v[x + 1]):
                v[x + 1] = v[x]
        last = np.ones(len(c), bool)
        last[:-1] = c[1:] != c[:-1]
        wc, wt, wv = c[last], t[last], v[last]
        better = wt > self.ts[wc]
        for x in np.flatnonzero(wt == self.ts[wc]):
            better[x] = self._value(wv[x]) > self._value(self.nonce[wc[x]])
        self.ts[wc[better]] = wt[better]
        self.nonce[wc[better]] = wv[better]

    def _value(self, nonce) -> bytes:
        return self.values.make(int(nonce), self.size)

    def read_command(self, i: int) -> tuple[bytes, ...]:
        return (b"MAP", b"TREG", b"GETALL", self.key(i))

    def _record(self, i: int, ts_of) -> list:
        f, out = len(self.names), []
        for j in self.order:
            c = int(i) * f + j
            out += [self.names[j], [self._value(self.nonce[c]), ts_of(self.ts[c])]]
        return out

    def expected(self, keys) -> list:
        return [self._record(i, int) for i in keys]

    def expected_lower_precision(self, keys) -> list:
        """What a path holding timestamps in float64 would answer."""
        return [self._record(i, lambda t: int(np.float64(t))) for i in keys]
