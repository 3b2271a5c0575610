"""The bytes a drain MUST move, worked from shapes, and the chip's peaks.

A roofline share is ``(needed bytes / peak bytes per second) / kernel
time``: needed bytes are the algorithm's, at the rows a drain really
carries (padding to a bucket is waste, so it is not counted), and the time
is the device time of that program's runs in the window, from the trace.
All four drains are bound by memory: they do a handful of integer compares
per 4-byte word and no matrix arithmetic, so ``ops / peak FLOP/s`` is
orders of magnitude under ``bytes / peak bytes/s``.
"""

from __future__ import annotations

import json
import os

_PEAKS = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "peaks.json")
WORD = 4  # every plane is u32


def peaks(device_kind: str) -> dict:
    """Peaks of one chip of ``device_kind``. A device that is not in the
    table is an error, never a default."""
    with open(_PEAKS) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in peaks.json")
    return table[device_kind]


def pncount_sparse_bytes(rows: int, replicas: int) -> int:
    """A sparse PNCOUNT drain of ``rows`` keys (`_drain_pn`): the state is
    4 u32 planes (P hi/lo, N hi/lo) of ``replicas`` columns. Per row it
    reads the row index (8 B: int64), reads the 4 delta planes' row
    (4*R words), gathers the 4 state planes' row (4*R words), scatters the
    joined row back (4*R words) and writes the row's wrapped sum (8 B):
    ``rows * (12 * R * 4 + 16)``."""
    return rows * (12 * replicas * WORD + 16)


def pncount_dense_bytes(key_cap: int, replicas: int) -> int:
    """A dense PNCOUNT drain (`_drain_pn_dense`, a batch over a quarter of
    the keyspace): every plane streamed once in, once out, the 4 delta
    planes once in, and one 8-byte sum per key out:
    ``key_cap * (12 * R * 4 + 8)``."""
    return key_cap * (12 * replicas * WORD + 8)


def treg_sparse_bytes(rows: int) -> int:
    """A sparse TREG drain of ``rows`` registers (`_drain`): the state is 5
    u32 vectors (ts hi/lo, value-rank hi/lo, value id). Per row: the index
    (8 B), the 5 incoming words, the 5 gathered state words, the 5 words
    scattered back, and what is read back to the host's cache: the tie flag
    (1 B), ts hi/lo and the value id (12 B): ``rows * (15 * 4 + 21)``."""
    return rows * (15 * WORD + 21)


def treg_dense_bytes(key_cap: int) -> int:
    """A dense TREG drain (`_drain_dense`): 5 vectors in, 5 out, 5 incoming,
    and tie + ts hi/lo + value id read back for every key:
    ``key_cap * (15 * 4 + 13)``."""
    return key_cap * (15 * WORD + 13)


BYTES = {
    "pncount": (pncount_sparse_bytes, pncount_dense_bytes),
    "treg": (lambda rows, _r: treg_sparse_bytes(rows), lambda cap, _r: treg_dense_bytes(cap)),
}


def share(needed_bytes: float, kernel_s: float, device_kind: str) -> float:
    """Roofline share in percent: least possible time over measured time."""
    least = needed_bytes / (peaks(device_kind)["hbm_bytes_per_s"])
    return 100.0 * least / kernel_s
