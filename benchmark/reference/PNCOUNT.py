"""Plain reference for PNCOUNT: per key, the sum of every replica's P column
minus the sum of every replica's N column, in wrapped 64-bit arithmetic
(numpy ``uint64``). Imports nothing of the program and takes nothing it made.

A column only ever grows (an INC adds to the writer's own P column, a DEC to
its own N column) and the value is a sum over columns, so which replica
took a write does not matter to the value: base totals plus every
acknowledged amount.

State recipe (the configuration's ``state`` block):
  keys            number of keys; key ``i`` is ``key_format % i``
  replica_ids     columns per polarity (the node, its live peers, the rest
                  synthetic ids drawn from the seed)
  foreign_keys    how many of the hottest keys (lowest Zipf ranks under the
                  generator's scramble) carry every foreign column
Every key carries the node's own column. One value in 16 is drawn from
[2^53, 2^62) and the rest from [1, 2^20), in both polarities, so that a
path through u32, f32 or f64 cannot give the right answers.
"""

from __future__ import annotations

import numpy as np

NAME = "PNCOUNT"
U64 = (1 << 64) - 1


def _magnitudes(rng: np.random.Generator, shape) -> np.ndarray:
    small = rng.integers(1, 1 << 20, shape, dtype=np.uint64)
    big = rng.integers(1 << 53, 1 << 62, shape, dtype=np.uint64)
    return np.where(rng.integers(0, 16, shape) == 0, big, small)


def wrap_i64(v: int) -> int:
    v &= U64
    return v - (1 << 64) if v >= (1 << 63) else v


class Reference:
    def __init__(self, recipe: dict, seed: int, own_rid: int, peer_rids: list[int],
                 hot_keys: np.ndarray, values=None):
        self.recipe = recipe
        n = recipe["keys"]
        rng = np.random.default_rng([seed, 0x504E])
        nf = recipe["replica_ids"] - 1
        rids = set(peer_rids)
        while len(rids) < nf:
            r = int(rng.integers(1, 1 << 63))
            if r != own_rid:
                rids.add(r)
        self.own_rid = own_rid
        self.foreign_rids = sorted(rids)
        self.own_p = _magnitudes(rng, n)
        self.own_n = np.where(rng.integers(0, 8, n) == 0, _magnitudes(rng, n),
                              np.uint64(0)).astype(np.uint64)
        self.hot = np.asarray(hot_keys[: recipe["foreign_keys"]], dtype=np.int64)
        shape = (len(self.hot), nf)
        self.f_p = _magnitudes(rng, shape)
        self.f_n = np.where(rng.integers(0, 2, shape) == 0, _magnitudes(rng, shape),
                            np.uint64(0)).astype(np.uint64)
        self.key_format = recipe["key_format"].encode()
        # running totals; uint64 addition wraps, which is the semantics
        self.p = self.own_p.copy()
        self.n = self.own_n.copy()
        self.p[self.hot] += self.f_p.sum(axis=1, dtype=np.uint64)
        self.n[self.hot] += self.f_n.sum(axis=1, dtype=np.uint64)
        # the same totals as a float64 path would hold them (the control)
        self.p_f64 = self.own_p.astype(np.float64)
        self.n_f64 = self.own_n.astype(np.float64)
        self.p_f64[self.hot] += self.f_p.astype(np.float64).sum(axis=1)
        self.n_f64[self.hot] += self.f_n.astype(np.float64).sum(axis=1)

    def key(self, i: int) -> bytes:
        return self.key_format % i

    def snapshot_batch(self):
        """Full state in the type's wire-delta shape: ``(key, ({rid: p},
        {rid: n}))``, zero cells left out."""
        own = self.own_rid
        rids = self.foreign_rids
        hot_row = {int(k): j for j, k in enumerate(self.hot)}
        own_p, own_n = self.own_p.tolist(), self.own_n.tolist()
        out = []
        for i in range(self.recipe["keys"]):
            dp = {own: own_p[i]}
            dn = {own: own_n[i]} if own_n[i] else {}
            j = hot_row.get(i)
            if j is not None:
                dp.update(zip(rids, self.f_p[j].tolist()))
                dn.update((r, v) for r, v in zip(rids, self.f_n[j].tolist()) if v)
            out.append((self.key_format % i, (dp, dn)))
        return out

    def apply(self, verb: str, keys: np.ndarray, a: np.ndarray, b: np.ndarray) -> None:
        """Acknowledged writes: ``INC``/``DEC`` of amounts ``a`` at ``keys``."""
        if verb not in ("INC", "DEC"):
            raise ValueError(f"PNCOUNT has no write {verb!r}")
        a = a.astype(np.uint64)
        tot, tot_f = (self.p, self.p_f64) if verb == "INC" else (self.n, self.n_f64)
        np.add.at(tot, keys, a)
        np.add.at(tot_f, keys, a.astype(np.float64))

    def read_command(self, i: int) -> tuple[bytes, ...]:
        return (b"PNCOUNT", b"GET", self.key(i))

    def expected(self, keys) -> list:
        return [wrap_i64(int(self.p[i]) - int(self.n[i])) for i in keys]

    def expected_lower_precision(self, keys) -> list:
        """What a float64 path would answer: the control of `correct`."""
        return [wrap_i64(int(self.p_f64[i] - self.n_f64[i])) for i in keys]
