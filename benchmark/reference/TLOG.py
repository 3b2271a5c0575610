"""Plain reference for TLOG: per key, the SET of ``(timestamp, value)`` over
the base log and every acknowledged INS (an exact duplicate is one entry),
and a cutoff that is the greatest acknowledged TRIMAT; the log is the
entries with ``timestamp >= cutoff``, newest first, equal timestamps by
value descending. Imports nothing of the program.

State recipe: ``keys`` logs, key ``i`` is ``key_format % i``, each of
``entries`` posts of ``value_bytes`` bytes made from the nonce
``2^62 | (i * entries + j)`` by the generator's `Values`. Base timestamps
are client timestamps of the ``base_days`` days before ``ts_epoch_ms`` (the
generator's virtual clock starts there, so every client timestamp is newer
than every base one): ``(ms << ts_shift) | j`` with ``ms`` drawn uniformly
from that span, so no two posts of a key share a timestamp and the
compare runs over ~61 bits. Base cutoff 0. Values are rebuilt from their
nonce on demand, never stored.
"""

from __future__ import annotations

import numpy as np

NAME = "TLOG"
BASE_NONCE = 1 << 62


class Reference:
    def __init__(self, recipe: dict, seed: int, own_rid: int, peer_rids: list[int],
                 hot_keys: np.ndarray, values=None):
        self.recipe = recipe
        n, m = recipe["keys"], recipe["entries"]
        rng = np.random.default_rng([seed, 0x544C])
        span = recipe["base_days"] * 86_400_000
        ms = recipe["ts_epoch_ms"] - span + rng.integers(0, span, (n, m))
        self.base_ts = ((ms.astype(np.uint64) << np.uint64(recipe["ts_shift"]))
                        | np.arange(m, dtype=np.uint64)[None, :])
        self.entries = m
        self.size = recipe["value_bytes"]
        self.values = values
        self.key_format = recipe["key_format"].encode()
        # acknowledged INS per key, as (ts, nonce); the base stays in its array
        self.added: list[set[tuple[int, int]]] = [set() for _ in range(n)]
        self.cutoff = [0] * n

    def key(self, i: int) -> bytes:
        return self.key_format % i

    def _log(self, i: int) -> set[tuple[int, int]]:
        """Every (ts, nonce) of key ``i``, trimmed or not."""
        first = i * self.entries
        base = {(ts, BASE_NONCE | (first + j))
                for j, ts in enumerate(self.base_ts[i].tolist())}
        return base | self.added[i]

    def snapshot_batch(self):
        """Full state in TLOG's wire-delta shape: (key, ([(value, ts)], cutoff))."""
        make, size = self.values.make, self.size
        return [(self.key(i), ([(make(nonce, size), ts) for ts, nonce in sorted(self._log(i))],
                               self.cutoff[i]))
                for i in range(self.recipe["keys"])]

    def apply(self, verb: str, keys: np.ndarray, a: np.ndarray, b: np.ndarray) -> None:
        """Acknowledged ``INS`` (timestamps ``a``, value nonces ``b``) and
        ``TRIMAT`` (cutoff timestamps ``a``). A cutoff is a maximum and a
        log a set, so the order of the writes does not matter."""
        if verb == "INS":
            for k, ts, nonce in zip(keys.tolist(), a.tolist(), b.tolist()):
                self.added[k].add((ts, nonce))
        elif verb == "TRIMAT":
            for k, ts in zip(keys.tolist(), a.tolist()):
                if ts > self.cutoff[k]:
                    self.cutoff[k] = ts
        else:
            raise ValueError(f"TLOG has no write {verb!r}")

    def read_command(self, i: int) -> tuple[bytes, ...]:
        """The whole log (``GET`` with no count): one read per key checks
        every entry, the order and the trim."""
        return (b"TLOG", b"GET", self.key(i))

    def _answer(self, i: int, through) -> list:
        make, size = self.values.make, self.size
        cut = through(self.cutoff[i])
        log = {(t, make(nonce, size)) for ts, nonce in self._log(i) if (t := through(ts)) >= cut}
        return [[value, ts] for ts, value in sorted(log, reverse=True)]

    def expected(self, keys) -> list:
        return [self._answer(int(i), int) for i in keys]

    def expected_lower_precision(self, keys) -> list:
        """What a path holding timestamps in float64 would answer: posts
        under 2^8 apart in a 61-bit timestamp collapse onto one."""
        return [self._answer(int(i), lambda ts: int(np.float64(ts))) for i in keys]
