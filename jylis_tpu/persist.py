"""Snapshot / restore: durability the reference never shipped.

The reference leaves persistence as an explicit TODO
(repo_manager.pony:100,107 "disk persistence?"); its only durability is
replication. This module adds optional snapshots with a CRDT-shaped
design: **a snapshot IS a full-state delta dump** — for every data type,
every key's complete joinable state in the exact per-type wire-delta
format the cluster codec already speaks (cluster/codec.py). Restoring is
just converging the batches back in, so restore composes correctly with
anything that happened meanwhile: load a stale snapshot into a live node
and the lattice join sorts it out — no log replay, no ordering concerns.

File format: magic, the codec DELTA-schema signature (a snapshot whose
per-type delta encodings are incompatible is refused, but transport-only
schema bumps — new message kinds, handshake changes — keep old snapshots
loadable: they contain only delta frames), then one framed MsgPushDeltas
per data type.
"""

from __future__ import annotations

import os

from . import faults
from .cluster import codec
from .cluster.framing import FrameReader, FramingError, frame
from .cluster.msg import MsgPushDeltas

MAGIC = b"JYLSNAP1"

# the one snapshot a node writes; `snapshot.lane<k>.jylis` files are what
# a multi-lane node (a mode retired in PR 45) left: restored at boot,
# never written (docs/durability.md)
SNAPSHOT_NAME = "snapshot.jylis"

# how many type batches a snapshot of each legacy era actually wrote:
# the v1-v3 full-signature era and the v4-v6 delta-signature era both
# had five data types + SYSTEM; the v7/v8 era added TENSOR. Keyed by
# the header digests in codec.legacy_snapshot_signatures() order
# (v1, v2, v3, v1-v6 delta, v7/v8 delta).
_LEGACY_TYPE_BATCHES = dict(
    zip(codec.legacy_snapshot_signatures(), (6, 6, 6, 6, 7))
)


def list_snapshots(data_dir: str) -> list[str]:
    """Every snapshot file in ``data_dir``, the node's own and any
    lane-named one, sorted — boot restores all of them (restore is
    lattice convergence; overlap is a no-op)."""
    out = []
    for fname in sorted(os.listdir(data_dir)):
        if fname == SNAPSHOT_NAME or (
            fname.startswith("snapshot.lane") and fname.endswith(".jylis")
        ):
            out.append(os.path.join(data_dir, fname))
    return out


def save_snapshot(database, path: str) -> None:
    """Atomic (write-then-rename) full-state snapshot of every repo."""
    write_snapshot(
        ((mgr.name, mgr.repo.dump_state()) for mgr in database.managers()),
        path,
    )


def write_snapshot(batches, path: str) -> None:
    """Atomic snapshot from pre-dumped (name, batch) pairs — the online
    snapshot path dumps each type under its own repo lock
    (Database.dump_state_async) and hands the batches here; a crash
    mid-write leaves the previous file intact (write-then-rename)."""
    tmp = path + ".tmp"
    with open(tmp, "wb") as f:
        f.write(MAGIC)
        f.write(codec.delta_signature())
        for name, batch in batches:
            # snapshot.write (per type frame): error -> OSError out of
            # here, the snapshot loop / shutdown path logs and the
            # journal keeps the deltas; corrupt/drop -> the NEXT boot's
            # load validation refuses the file and moves it aside
            data = faults.point(
                "snapshot.write",
                frame(codec.encode(MsgPushDeltas(name, batch))),
            )
            if data is not None:
                f.write(data)
    os.replace(tmp, path)


class SnapshotError(Exception):
    pass


def load_snapshot(database, path: str) -> int:
    """Converge a snapshot file into the database; returns the number of
    type-batches loaded. Raises SnapshotError on ANY unreadable, corrupt,
    incompatible, or incomplete file (the caller decides whether that is
    fatal — nothing is converged unless the whole file validates)."""
    try:
        with open(path, "rb") as f:
            blob = f.read()
        # snapshot.load: error -> "cannot read" below; corrupt -> the
        # validation path refuses (caller moves the file aside, node
        # heals from peers); drop -> treated as unreadable
        blob = faults.point("snapshot.load", blob)
    except OSError as e:
        raise SnapshotError(f"cannot read snapshot: {e}") from None
    if blob is None:
        raise SnapshotError("snapshot dropped by failpoint")
    if blob[: len(MAGIC)] != MAGIC:
        raise SnapshotError("not a snapshot file")
    sig_end = len(MAGIC) + len(codec.delta_signature())
    header = blob[len(MAGIC) : sig_end]
    accepted = (codec.delta_signature(),) + codec.legacy_snapshot_signatures()
    if header not in accepted:
        # NOT recoverable by this build: main.py moves the file aside as
        # .unreadable rather than deleting it
        raise SnapshotError("snapshot schema signature mismatch")
    # snapshots are read whole from local disk: no adversarial peer to
    # bound against, so lift the wire-oriented frame cap
    frames = FrameReader(max_frame=1 << 62)
    frames.append(blob[sig_end:])
    msgs = []
    try:
        for body in frames:
            # lazy: a MAP batch the native field table can read stays
            # its (checked) wire bytes, loaded in one call below
            msg = codec.decode(body, lazy=True)
            if not isinstance(msg, MsgPushDeltas):
                raise SnapshotError("unexpected message in snapshot")
            msgs.append(msg)
    except (codec.CodecError, FramingError) as e:
        raise SnapshotError(f"corrupt snapshot: {e}") from None
    if frames.pending():
        raise SnapshotError("truncated snapshot (partial trailing frame)")
    expected = len(list(database.managers()))
    if header == codec.delta_signature():
        if len(msgs) != expected:
            raise SnapshotError(
                f"snapshot has {len(msgs)} type batches, expected "
                f"{expected} (truncated at a frame boundary?)"
            )
    else:
        # a legacy-era snapshot carries EXACTLY its era's type count
        # (types added since then are simply not in the file) — the
        # exact check keeps frame-boundary truncation detectable for
        # legacy files too. The current count is also accepted: a
        # current-shape file under a legacy header is byte-loadable
        # (the delta encodings it names are a subset), and the legacy
        # round-trip tests exercise exactly that shape.
        era = _LEGACY_TYPE_BATCHES.get(header)
        allowed = {expected} if era is None else {era, expected}
        if len(msgs) not in allowed:
            raise SnapshotError(
                f"legacy snapshot has {len(msgs)} type batches, "
                f"expected one of {sorted(allowed)} (truncated at a "
                "frame boundary?)"
            )
    # fully validated: only now touch the database
    for msg in msgs:
        database.manager(msg.name).repo.load_state(msg.batch)
    # restored state lands on the device NOW: converge only buffers, and
    # leaving a whole snapshot in host pending buffers would bypass the
    # drain thresholds and tax every read with the merge path
    database.drain_all()
    return len(msgs)
