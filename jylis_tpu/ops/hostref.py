"""Pure-Python reference lattices.

Direct, obviously-correct implementations of the documented CRDT semantics
(docs/_docs/types/*.md "Detailed Semantics"). Two jobs:

1. differential-test oracle for the device kernels (tests/),
2. the SYSTEM log's tiny single-key TLog (models/repo_system.py), where a
   device round-trip would be absurd.

These are NOT the serving path — the serving path is the device kernels.
"""

from __future__ import annotations

from dataclasses import dataclass, field


class GCounter:
    """Replica-id -> u64 map; join = per-id max; value = wrapping sum.

    Semantics: docs/_docs/types/gcount.md:43-47.
    """

    __slots__ = ("counts",)
    _MASK = (1 << 64) - 1

    def __init__(self):
        self.counts: dict[int, int] = {}

    def increment(self, replica: int, amount: int) -> None:
        self.counts[replica] = (self.counts.get(replica, 0) + amount) & self._MASK

    def value(self) -> int:
        return sum(self.counts.values()) & self._MASK

    def converge(self, other: "GCounter") -> bool:
        changed = False
        for rid, v in other.counts.items():
            if v > self.counts.get(rid, -1):
                self.counts[rid] = v
                changed = True
        return changed


class PNCounter:
    """Two GCounters; value = P - N as signed 64-bit (modular).

    Semantics: docs/_docs/types/pncount.md:49-55.
    """

    __slots__ = ("p", "n")

    def __init__(self):
        self.p = GCounter()
        self.n = GCounter()

    def increment(self, replica: int, amount: int) -> None:
        self.p.increment(replica, amount)

    def decrement(self, replica: int, amount: int) -> None:
        self.n.increment(replica, amount)

    def value(self) -> int:
        raw = (self.p.value() - self.n.value()) & ((1 << 64) - 1)
        return raw - (1 << 64) if raw >= (1 << 63) else raw

    def converge(self, other: "PNCounter") -> bool:
        a = self.p.converge(other.p)
        b = self.n.converge(other.n)
        return a or b


class TReg:
    """LWW register over (value: bytes, ts: u64).

    Pair A beats B iff ts_A > ts_B or (ts equal and value_A > value_B
    bytewise) — docs/_docs/types/treg.md:60-63. Unset is (b"", 0) and loses
    to any written pair (a written pair at ts 0 with value b"" equals it).
    """

    __slots__ = ("value", "ts", "is_set")

    def __init__(self):
        self.value: bytes = b""
        self.ts: int = 0
        self.is_set = False

    def write(self, value: bytes, ts: int) -> None:
        if not self.is_set or (ts, value) > (self.ts, self.value):
            self.value, self.ts, self.is_set = value, ts, True

    def read(self):
        return (self.value, self.ts) if self.is_set else None

    def converge(self, other: "TReg") -> bool:
        if other.is_set and (
            not self.is_set or (other.ts, other.value) > (self.ts, self.value)
        ):
            self.value, self.ts, self.is_set = other.value, other.ts, True
            return True
        return False


@dataclass
class TLog:
    """Timestamp-sorted log with grow-only cutoff.

    Entries are (value: bytes, ts: u64), sorted ts desc then value desc;
    duplicates (equal ts AND value) are dropped; entries with ts < cutoff
    are dropped; cutoffs merge by max — docs/_docs/types/tlog.md:116-133.
    """

    entries: list[tuple[bytes, int]] = field(default_factory=list)
    cutoff: int = 0

    def insert(self, value: bytes, ts: int) -> bool:
        if ts < self.cutoff or (value, ts) in self.entries:
            return False
        self.entries.append((value, ts))
        self.entries.sort(key=lambda e: (e[1], e[0]), reverse=True)
        return True

    def size(self) -> int:
        return len(self.entries)

    def latest(self, count: int | None = None) -> list[tuple[bytes, int]]:
        return self.entries if count is None else self.entries[:count]

    def trim(self, count: int) -> None:
        """Raise cutoff to ts of entry at index count-1 (tlog.md:54-60);
        count 0 behaves like clear; negative counts are a no-op (the
        reference parses count as unsigned)."""
        if count == 0:
            self.clear()
        elif 0 < count <= len(self.entries):
            self.raise_cutoff(self.entries[count - 1][1])

    def raise_cutoff(self, ts: int) -> None:
        if ts > self.cutoff:
            self.cutoff = ts
            self.entries = [e for e in self.entries if e[1] >= self.cutoff]

    def clear(self) -> None:
        """Cutoff = latest ts + 1; no-op on an empty log (tlog.md:62-66)."""
        if self.entries:
            self.raise_cutoff(self.entries[0][1] + 1)

    def converge(self, other: "TLog") -> bool:
        before = (len(self.entries), self.cutoff)
        merged = set(self.entries) | set(other.entries)
        self.cutoff = max(self.cutoff, other.cutoff)
        self.entries = sorted(
            (e for e in merged if e[1] >= self.cutoff),
            key=lambda e: (e[1], e[0]),
            reverse=True,
        )
        return (len(self.entries), self.cutoff) != before
