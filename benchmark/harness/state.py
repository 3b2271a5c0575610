"""Seed-made state, loaded by the node's own boot recovery: the reference's
full state goes through the program's snapshot writer (its file format is
the program's to define) into each node's data dir: ONE snapshot holding
every stated type's batch under its own name. The bytes are the same for
the node and every peer, so their digests match at join and no rejoin sync
runs."""

from __future__ import annotations

import os


def write_snapshots(refs: dict, data_dirs: list[str]) -> int:
    """``refs``: type name -> its reference. Returns the file's size."""
    from jylis_tpu import persist
    from jylis_tpu.models.database import DATA_TYPE_NAMES

    unknown = set(refs) - set(DATA_TYPE_NAMES)
    if unknown:
        raise ValueError(f"the program has no data type {sorted(unknown)}")
    first = os.path.join(data_dirs[0], "snapshot.jylis")
    for d in data_dirs:
        os.makedirs(d, exist_ok=True)
    persist.write_snapshot(
        # a generator: one type's batch in memory at a time
        ((n, refs[n].snapshot_batch() if n in refs else [])
         for n in DATA_TYPE_NAMES + ("SYSTEM",)),
        first,
    )
    for d in data_dirs[1:]:
        os.link(first, os.path.join(d, "snapshot.jylis"))
    return os.path.getsize(first)
