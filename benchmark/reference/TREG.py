"""Plain reference for TREG: per key, the greatest ``(timestamp, value)``
among the base record and every acknowledged SET (last writer wins; equal
timestamps fall to the greater value). Imports nothing of the program.

State recipe: ``keys`` records, key ``i`` is ``key_format % i``; every base
record has a timestamp drawn from [2^40, ts_ceiling) and ``value_bytes``
bytes made from the nonce ``2^62 | i`` by the generator's `Values` (YCSB's
10 fields x 100 B as one value: an update rewrites the whole record).
Values are rebuilt from their nonce on demand, never stored.
"""

from __future__ import annotations

import numpy as np

NAME = "TREG"
BASE_NONCE = 1 << 62


class Reference:
    def __init__(self, recipe: dict, seed: int, own_rid: int, peer_rids: list[int],
                 hot_keys: np.ndarray, values=None):
        self.recipe = recipe
        n = recipe["keys"]
        rng = np.random.default_rng([seed, 0x5452])
        self.ts = rng.integers(1 << 40, recipe["ts_ceiling"], n, dtype=np.uint64)
        self.nonce = np.arange(n, dtype=np.uint64) | np.uint64(BASE_NONCE)
        self.size = recipe["value_bytes"]
        self.values = values
        self.key_format = recipe["key_format"].encode()

    def key(self, i: int) -> bytes:
        return self.key_format % i

    def snapshot_batch(self):
        make, size = self.values.make, self.size
        ts = self.ts.tolist()
        return [(self.key_format % i, (make(BASE_NONCE | i, size), ts[i]))
                for i in range(self.recipe["keys"])]

    def apply(self, verb: str, keys: np.ndarray, a: np.ndarray, b: np.ndarray) -> None:
        """Acknowledged ``SET``s: timestamps ``a``, value nonces ``b``."""
        if verb != "SET":
            raise ValueError(f"TREG has no write {verb!r}")
        a = a.astype(np.uint64)
        # the generator never repeats a timestamp, so the greatest
        # timestamp of a key decides: sort by (key, ts), keep each key's last
        order = np.lexsort((a, keys))
        k, t, v = keys[order], a[order], b[order]
        last = np.ones(len(k), bool)
        last[:-1] = k[1:] != k[:-1]
        wk, wt, wv = k[last], t[last], v[last]
        better = wt > self.ts[wk]
        self.ts[wk[better]] = wt[better]
        self.nonce[wk[better]] = wv[better]

    def read_command(self, i: int) -> tuple[bytes, ...]:
        return (b"TREG", b"GET", self.key(i))

    def expected(self, keys) -> list:
        return [[self.values.make(int(self.nonce[i]), self.size), int(self.ts[i])]
                for i in keys]

    def expected_lower_precision(self, keys) -> list:
        """What a path holding timestamps in float64 would answer."""
        return [[self.values.make(int(self.nonce[i]), self.size),
                 int(np.float64(self.ts[i]))] for i in keys]
