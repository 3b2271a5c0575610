"""The one general traffic generator: keys, amounts, values, timestamps and
command templates, all drawn from the seed. A traffic mix is a data file
under ``benchmark/traffic/``; nothing here knows a mix or a type by name.

Template fields (``"TREG SET {key} {value:1000} {ts}"``):

``{key}``       the key's bytes: ``key_format % index`` with the
                ``key_format`` of the template's type (its first word),
                the index drawn from the stream's ``keys`` distribution
                over THAT type's keyspace (`draw_keys`);
``{amount}``    a whole number drawn uniformly from the stream's
                ``amount`` range (both ends included);
``{value:N}``   N bytes made from a per-operation nonce (`Values.make`):
                the reference rebuilds them from the nonce alone;
``{ts}``        a timestamp no two operations of a run share:
                ``(epoch_ms + ms since the run began) << 20 | seq << 8 |
                connection``: client milliseconds in the high bits, the
                connection in the low bits, so that competing writes never
                tie and the compare runs over ~61 bits.
"""

from __future__ import annotations

import re

import numpy as np

U64 = (1 << 64) - 1
# The scramble is NOT drawn from --seed: every seed meets the same hot set
# (the same sizes and arrivals, in another order), so that runs on
# different seeds do the same work.
SCRAMBLE_SEED = 0x6A796C6973
# 2026-01-01T00:00:00Z in ms: the virtual clock's zero, above every base
# timestamp a state recipe draws (those end below ``TS_EPOCH_MS << 20``).
TS_EPOCH_MS = 1_767_225_600_000
TS_SHIFT = 20
_FIELD = re.compile(r"^\{(key|amount|ts|value):?(\d*)\}$")


def scramble(n: int) -> np.ndarray:
    """Rank -> key index: a fixed permutation of ``range(n)`` (YCSB's
    scrambled Zipfian hashes ranks and lets them collide; a permutation
    keeps the hot set's size exact)."""
    return np.random.default_rng(SCRAMBLE_SEED).permutation(n).astype(np.int64)


def hottest(n: int, count: int) -> np.ndarray:
    """Key indices of the ``count`` lowest Zipf ranks under `scramble`."""
    return scramble(n)[:count]


class KeyDist:
    """``{"dist": "zipfian", "theta": 0.99}`` (scrambled) or
    ``{"dist": "uniform"}`` over ``n`` keys."""

    def __init__(self, spec: dict, n: int):
        self.n = n
        self.kind = spec["dist"]
        if self.kind == "zipfian":
            w = 1.0 / np.power(np.arange(1, n + 1, dtype=np.float64), spec["theta"])
            self.cdf = np.cumsum(w / w.sum())
            self.perm = scramble(n)
        elif self.kind != "uniform":
            raise ValueError(f"unknown key distribution {self.kind!r}")

    def draw(self, rng: np.random.Generator, count: int) -> np.ndarray:
        if self.kind == "uniform":
            return rng.integers(0, self.n, count)
        ranks = np.searchsorted(self.cdf, rng.random(count))
        return self.perm[np.minimum(ranks, self.n - 1)]


def draw_keys(rng: np.random.Generator, spec: dict, sizes: list[int],
              ops: np.ndarray) -> np.ndarray:
    """The key index of each drawn operation, over its OWN type's keyspace:
    ``sizes[i]`` is the number of keys of the type that op ``i`` names.
    One draw of ``len(ops)`` indices per distinct size, in the order the
    ops first name it, so a stream of one type draws as it always did, and
    types that state the same ``keys`` share the drawn index: operation
    ``j`` meets the same entity whichever of them it names (user 17's
    timeline and user 17's follower set)."""
    drawn: dict[int, np.ndarray] = {}
    for n in sizes:
        if n not in drawn:
            drawn[n] = KeyDist(spec, n).draw(rng, len(ops))
    if len(drawn) == 1:
        return drawn[sizes[0]]
    keys = np.empty(len(ops), np.int64)
    for i, n in enumerate(sizes):
        mine = ops == i
        keys[mine] = drawn[n][mine]
    return keys


class Values:
    """N-byte values from a 64-bit nonce: 16 hex digits of the nonce, then a
    slice of a seed-made pool at an offset hashed from the nonce. Worker
    and reference both hold the pool, so a value is never stored."""

    POOL = 1 << 20

    def __init__(self, seed: int):
        self.pool = np.random.default_rng([seed, 0x56414C]).bytes(self.POOL)

    def make(self, nonce: int, size: int) -> bytes:
        nonce &= U64
        head = b"%016x" % nonce
        if size <= 16:
            return head[:size]
        off = ((nonce * 0x9E3779B97F4A7C15) & U64) >> 24
        off %= self.POOL - size
        return head + self.pool[off : off + size - 16]


def make_ts(elapsed_s: float, seq: int, conn: int) -> int:
    ms = TS_EPOCH_MS + int(elapsed_s * 1000)
    return (ms << TS_SHIFT) | ((seq & 0xFFF) << 8) | (conn & 0xFF)


class Template:
    """A command template compiled to words: bytes, or a field to fill."""

    def __init__(self, text: str):
        self.text = text
        self.words: list = []
        self.value_size = 0
        for word in text.split(" "):
            m = _FIELD.match(word)
            if m is None:
                if "{" in word:
                    raise ValueError(f"unknown field {word!r} in {text!r}")
                self.words.append(word.encode())
            else:
                self.words.append(m.group(1))
                if m.group(1) == "value":
                    self.value_size = int(m.group(2) or 0)
                    if self.value_size < 1:
                        raise ValueError(f"{{value:N}} needs a size in {text!r}")
        self.type_name, self.verb = text.split(" ")[:2]
        self.fields = {w for w in self.words if isinstance(w, str)}

    def render(self, key: bytes, amount: int = 0, ts: int = 0,
               value: bytes = b"") -> bytes:
        parts = [b"*%d\r\n" % len(self.words)]
        for w in self.words:
            if w == "key":
                w = key
            elif w == "amount":
                w = b"%d" % amount
            elif w == "ts":
                w = b"%d" % ts
            elif w == "value":
                w = value
            parts.append(b"$%d\r\n%s\r\n" % (len(w), w))
        return b"".join(parts)


def op_table(ops: list[dict]) -> tuple[list[Template], np.ndarray]:
    """Templates of a stream's ops and their shares as probabilities."""
    templates = [Template(op["cmd"]) for op in ops]
    shares = np.array([float(op["share"]) for op in ops])
    return templates, shares / shares.sum()
