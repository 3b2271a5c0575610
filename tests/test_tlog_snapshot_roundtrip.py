"""Snapshot -> boot recovery -> `dump_state` of a 64 x 32 log state: what
the benchmark's reference writes through the program's snapshot writer
(`benchmark/harness/state.py`) comes back from the restored repo entry for
entry, and survives a second trip through the snapshot format."""

import os

import pytest

import jylis_tpu  # noqa: F401
from benchref import tlog_reference
from jylis_tpu import persist
from jylis_tpu.models.database import DATA_TYPE_NAMES, Database


def canon(batch):
    """A TLOG batch with each log's entries in one order."""
    return [(key, (sorted(entries), cutoff)) for key, (entries, cutoff) in sorted(batch)]


def write(batch, path):
    persist.write_snapshot(
        [(n, batch if n == "TLOG" else []) for n in DATA_TYPE_NAMES + ("SYSTEM",)], path)


@pytest.mark.parametrize("engine", ["auto", "python"])
def test_a_restored_log_state_dumps_what_the_reference_wrote(tmp_path, engine):
    ref = tlog_reference(2**31 + 9, keys=64, entries=32, value_bytes=64)
    ref.cutoff[3] = int(ref.base_ts[3].min()) - 1  # a cutoff below every entry travels too
    want = canon(ref.snapshot_batch())
    assert len(want) == 64 and all(len(e) == 32 for _k, (e, _c) in want)
    first = os.path.join(tmp_path, "first.jylis")
    write(ref.snapshot_batch(), first)

    db = Database(identity=1, engine=engine)
    assert persist.load_snapshot(db, first) >= 1
    db.warm_drain_shapes()  # the boot drain
    repo = db.manager("TLOG").repo
    got = repo.dump_state()
    assert canon(got) == want
    for _key, (entries, _cutoff) in got:  # newest first, as the device holds a row
        stamps = [ts for _v, ts in entries]
        assert stamps == sorted(stamps, reverse=True)

    second = os.path.join(tmp_path, "second.jylis")
    write(got, second)
    again = Database(identity=2, engine=engine)
    persist.load_snapshot(again, second)
    assert canon(again.manager("TLOG").repo.dump_state()) == want
    k = ref.key(3)
    assert again.manager("TLOG").repo.sync_canon(k) == repo.sync_canon(k)
