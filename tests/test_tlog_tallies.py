"""The TLOG tallies beside `drain.TLOG` count what they say, on both
table backends, and show on every surface the TREG pair shows on:
`jylis_drain_total{type="TLOG",kind=...}`, `SYSTEM METRICS`, the shutdown
log's `merge metrics:` line. And what `bases_lost` and `row_gathers`
bracket: a drain keeps the row it drained (its epilogue folds the pending
window into the base the host holds), so no read goes to the device for a
row unless the fold failed the length guard."""

import pytest

import jylis_tpu  # noqa: F401
from jylis_tpu.models.repo_tlog import RepoTLOG
from jylis_tpu.obs.registry import MetricsRegistry
from jylis_tpu.utils.metrics import metric_lines

from procutil import scan_bytes

ENGINES = ["auto", "python"]
KINDS = ("entries", "trims", "grows", "bases_lost", "row_gathers", "view_sorts")


class _Resp:
    def __getattr__(self, name):
        return lambda *a: None


def fresh(engine):
    repo = RepoTLOG(identity=1, mesh=None, engine=engine)
    repo.metrics = reg = MetricsRegistry()
    return repo, reg, lambda: {k: reg.tallies["drain.TLOG." + k] for k in KINDS}


def get(repo, key, *count):
    """`TLOG GET key [count]` as [(ts, value)]: the reply through a recorder."""
    out = []

    class _Rec(_Resp):
        def string(self, v):
            out.append(v)

        def u64(self, ts):
            out[-1] = (ts, out[-1])

    repo.apply(_Rec(), [b"GET", key, *count])
    return out


def reference(entries, cut=0):
    """The log a reader must see: deduplicated, filtered by the cutoff,
    (ts, value) descending."""
    return sorted({(ts, v) for v, ts in entries if ts >= cut}, reverse=True)


def lose_base(repo, key):
    """Force the epilogue's length guard to fail through the repo's own
    `_finish_rows`: it is told one entry more than the fold holds, then
    (on the table alone) the true length again; the base stays unknown."""
    row = repo._tbl.find(key)
    n, cut = repo._tbl.len_cache(row), repo._tbl.cut_cache(row)
    repo._finish_rows([(row, n + 1, cut)])
    assert repo._tbl.finish_row(row, n, cut) is False
    return row


@pytest.mark.parametrize("engine", ENGINES)
def test_a_drain_of_three_rows_of_four_entries_is_twelve_entries_and_one_batch(engine):
    repo, reg, read = fresh(engine)
    resp = _Resp()
    for k in range(3):
        for j in range(4):
            repo.apply(resp, [b"INS", b"k%d" % k, b"v%d" % j, b"%d" % (100 + j)])
    assert read()["entries"] == 0 and reg.counters["TLOG"]["batches"] == 0
    repo.drain()
    assert read() == {"entries": 12, "trims": 0, "grows": 0, "bases_lost": 0, "row_gathers": 0, "view_sorts": 0}
    assert reg.counters["TLOG"]["batches"] == 1 and reg.counters["TLOG"]["keys"] == 3
    repo.drain()  # nothing pending: no dispatch, nothing counted
    assert read()["entries"] == 12 and reg.counters["TLOG"]["batches"] == 1


@pytest.mark.parametrize("engine", ENGINES)
def test_trims_count_the_drains_a_trim_forced(engine):
    repo, reg, read = fresh(engine)
    resp = _Resp()
    for j in range(6):
        repo.apply(resp, [b"INS", b"a", b"v%d" % j, b"%d" % (100 + j)])
    repo.apply(resp, [b"TRIMAT", b"a", b"102"])
    repo.apply(resp, [b"TRIM", b"a", b"2"])
    repo.apply(resp, [b"CLR", b"a"])
    assert read()["trims"] == 3 and reg.counters["TLOG"]["batches"] == 3
    assert read()["entries"] == 6, "the first trim carried the six pending entries, the others none"


@pytest.mark.parametrize("engine", ENGINES)
def test_a_row_that_outgrows_len_cap_is_a_grow(engine):
    repo, reg, read = fresh(engine)
    resp = _Resp()
    for j in range(16):
        repo.apply(resp, [b"INS", b"a", b"v%d" % j, b"%d" % (100 + j)])
    repo.drain()
    assert read()["grows"] == 0 and repo._len_cap == 16
    repo.apply(resp, [b"INS", b"a", b"one-more", b"500"])
    repo.drain()
    assert read()["grows"] == 1 and repo._len_cap == 32


@pytest.mark.parametrize("engine", ENGINES)
def test_a_restored_row_is_read_without_a_gather_and_a_quiescent_get_sorts_once(engine):
    repo, reg, read = fresh(engine)
    resp = _Resp()
    # restored rows: a new row's drained part is empty and known, so the
    # boot's drain folds the snapshot's entries into a base the host keeps
    a = [(b"v%d" % j, 10 + j) for j in range(5)]
    repo.load_state([(b"a", (a, 0)), (b"b", ([(b"w%d" % j, 10 + j) for j in range(5)], 0))])
    repo.drain()
    assert read()["row_gathers"] == 0 and read()["view_sorts"] == 0 and read()["bases_lost"] == 0
    assert not repo.may_drain([b"GET", b"a"]), "nothing to fetch: the read stays on the loop"
    assert get(repo, b"a") == reference(a)
    assert read()["row_gathers"] == 0 and read()["view_sorts"] == 1
    for _ in range(3):  # quiescent: the rendered row serves it
        repo.apply(resp, [b"GET", b"a", b"2"])
        repo.apply(resp, [b"SIZE", b"a"])
    assert read()["row_gathers"] == 0 and read()["view_sorts"] == 1
    # a pending entry moves the row's generation: the merged view is sorted once, then kept
    repo.apply(resp, [b"INS", b"a", b"new", b"99"])
    assert get(repo, b"a") == reference(a + [(b"new", 99)])
    repo.apply(resp, [b"GET", b"a", b"3"])
    assert read()["row_gathers"] == 0 and read()["view_sorts"] == 2
    repo.apply(resp, [b"SIZE", b"b"])  # quiescent SIZE: the length cache
    assert read()["row_gathers"] == 0 and read()["bases_lost"] == 0


@pytest.mark.parametrize("engine", ENGINES)
def test_the_read_write_sweep_cycle_never_goes_back_to_the_device(engine):
    """PR 30's cycle: GET (a gather's `set_base` left the memo stale), INS
    (no memo upkeep), another key's TRIMAT drains every touched row, the
    base is thrown away, GET gathers again: one gather a turn, for ever."""
    repo, reg, read = fresh(engine)
    resp = _Resp()
    posts = [(b"p%d" % j, 1000 + j) for j in range(8)]
    repo.load_state([(b"thread", (posts, 0)), (b"other", ([(b"o", 5)], 0))])
    repo.drain()
    for turn in range(5):
        assert not repo.may_drain([b"GET", b"thread", b"4"])
        assert get(repo, b"thread", b"4") == reference(posts)[:4]
        posts.append((b"new%d" % turn, 2000 + turn))
        repo.apply(resp, [b"INS", b"thread", b"new%d" % turn, b"%d" % (2000 + turn)])
        repo.apply(resp, [b"TRIMAT", b"other", b"%d" % (turn + 1)])
        assert repo._tbl.base_valid(repo._tbl.find(b"thread"))
        assert not repo.may_drain([b"GET", b"thread"])
        assert get(repo, b"thread") == reference(posts)
    assert read()["row_gathers"] == 0 and read()["bases_lost"] == 0
    assert read()["trims"] == 5 and read()["entries"] == 9 + 5


TRIMS = {
    "trimat": ([b"TRIMAT", b"k", b"14"], 14),
    "trim_by_count": ([b"TRIM", b"k", b"3"], 17),  # the third newest entry's timestamp
    "clr": ([b"CLR", b"k"], 20),  # the newest entry's timestamp + 1
}


@pytest.mark.parametrize("engine", ENGINES)
@pytest.mark.parametrize("trim", sorted(TRIMS))
def test_a_trim_of_a_row_with_a_valid_base_keeps_it_valid(engine, trim):
    repo, reg, read = fresh(engine)
    resp = _Resp()
    ents = [(b"v%d" % j, 10 + j) for j in range(10)]
    repo.load_state([(b"k", (ents, 0))])
    repo.drain()
    row = repo._tbl.find(b"k")
    assert repo._tbl.base_valid(row)
    cmd, cut = TRIMS[trim]
    ents.append((b"late", 12))  # pending when the trim's drain comes, memo not current
    repo.converge(b"k", ([ents[-1]], 0))
    repo.apply(resp, cmd)
    assert repo._tbl.base_valid(row) and repo._tbl.cut_cache(row) == cut
    assert not repo.may_drain([b"GET", b"k"])
    assert get(repo, b"k") == reference(ents, cut)
    assert repo._tbl.len_cache(row) == len(reference(ents, cut))
    assert read()["row_gathers"] == 0 and read()["bases_lost"] == 0 and read()["trims"] == 1


@pytest.mark.parametrize("engine", ENGINES)
def test_foreign_entries_with_duplicates_and_a_cutoff_fold_into_the_base(engine):
    repo, reg, read = fresh(engine)
    resp = _Resp()
    repo.apply(resp, [b"INS", b"k", b"mine", b"7"])
    repo.drain()
    foreign = [(b"x", 3), (b"y", 9), (b"mine", 7), (b"y", 9), (b"z", 5), (b"x", 4)]
    repo.converge(b"k", (foreign, 5))  # no memo upkeep: not current at the drain
    repo.converge(b"k", ([(b"y", 9), (b"w", 8)], 0))
    repo.drain()
    row = repo._tbl.find(b"k")
    want = reference(foreign + [(b"mine", 7), (b"w", 8)], 5)
    assert repo._tbl.base_valid(row) and repo._tbl.len_cache(row) == len(want) == 4
    if repo.engine is not None:  # the native burst serves it: nothing deferred to Python
        rc, _, replies, unhandled, _ = scan_bytes(repo.engine, bytearray(b"TLOG GET k\r\n"))
        assert rc == 0 and unhandled is None
        assert replies.startswith(b"*4\r\n*2\r\n$1\r\ny\r\n:9\r\n")
    assert get(repo, b"k") == want
    assert read()["row_gathers"] == 0 and read()["bases_lost"] == 0


@pytest.mark.parametrize("engine", ENGINES)
def test_a_failed_length_guard_loses_the_base_and_the_next_get_repairs_it_once(engine):
    repo, reg, read = fresh(engine)
    resp = _Resp()
    ents = [(b"v%d" % j, 10 + j) for j in range(6)]
    repo.load_state([(b"k", (ents, 0))])
    repo.drain()
    row = lose_base(repo, b"k")
    assert read()["bases_lost"] == 1 and not repo._tbl.base_valid(row)
    assert repo.may_drain([b"GET", b"k"]), "the gather goes to a thread"
    assert not repo.may_drain([b"SIZE", b"k"]), "the length cache still answers"
    assert get(repo, b"k") == reference(ents)
    assert read()["row_gathers"] == 1 and repo._tbl.base_valid(row)
    # repaired: later reads, writes and drains stay on the host
    repo.apply(resp, [b"INS", b"k", b"more", b"50"])
    assert not repo.may_drain([b"GET", b"k"])
    repo.drain()
    assert repo._tbl.base_valid(row) and not repo.may_drain([b"GET", b"k"])
    assert get(repo, b"k") == reference(ents + [(b"more", 50)])
    assert read()["row_gathers"] == 1 and read()["bases_lost"] == 1
    # a base that is already unknown at a drain is lost again, and counted
    lose_base(repo, b"k")
    repo.apply(resp, [b"INS", b"k", b"last", b"60"])
    repo.drain()
    assert read()["bases_lost"] == 3 and not repo._tbl.base_valid(row)
    assert get(repo, b"k") == reference(ents + [(b"more", 50), (b"last", 60)])
    assert read()["row_gathers"] == 2


def test_the_tallies_are_on_the_scrape_in_system_metrics_and_in_the_shutdown_line():
    from jylis_tpu.models.database import Database
    from jylis_tpu.obs import prom

    db = Database(identity=3)
    resp = _Resp()
    for j in range(4):
        db.apply(resp, [b"TLOG", b"INS", b"k", b"v%d" % j, b"%d" % (10 + j)])
    db.apply(resp, [b"TLOG", b"TRIMAT", b"k", b"11"])
    text = prom.render(db)
    for kind, n in (("entries", 4), ("trims", 1), ("grows", 0), ("bases_lost", 0), ("row_gathers", 0)):
        assert f'jylis_drain_total{{type="TLOG",kind="{kind}"}} {n}' in text
    assert 'jylis_drain_total{type="TLOG",kind="view_sorts"}' in text
    lines = metric_lines(registry=db.metrics)
    assert "TLOG entries 4" in lines and "TLOG trims 1" in lines and "TLOG grows 0" in lines
    assert "TLOG bases_lost 0" in lines
    assert ", 4 entries, 1 trims, 0 grows, 0 bases_lost, 0 row_gathers, " in db.metrics.report()


@pytest.mark.parametrize("engine", ENGINES)
def test_foreign_entries_cutoffs_overdue_and_passes_count_what_they_say(engine):
    """`foreign_entries`: every (value, ts) `converge` buffers, duplicates
    included; `foreign_cutoffs`: cutoffs above the key's view; `overdue`:
    drains that began with a bound of the table tripped, not a trim's;
    `passes`: dispatches a single-chip drain was made of."""
    repo, reg, _read = fresh(engine)
    new = lambda: {k: reg.tallies["drain.TLOG." + k] for k in ("foreign_entries", "foreign_cutoffs", "overdue", "passes")}
    repo.converge(b"k", ([(b"a", 10), (b"b", 20), (b"a", 10)], 0))
    repo.converge(b"j", ([], 15))
    assert new() == {"foreign_entries": 3, "foreign_cutoffs": 1, "overdue": 0, "passes": 0}
    repo.converge(b"j", ([(b"c", 30)], 15))  # the cutoff is the view already: buffered once
    repo.converge(b"j", ([], 12))
    assert new() == {"foreign_entries": 4, "foreign_cutoffs": 1, "overdue": 0, "passes": 0}
    repo.apply(_Resp(), [b"TRIMAT", b"k", b"15"])  # a trim's drain, of everything pending: not overdue
    assert new()["overdue"] == 0 and new()["passes"] == 1 and reg.tallies["drain.TLOG.trims"] == 1
    assert get(repo, b"k") == [(20, b"b")] and get(repo, b"j") == [(30, b"c")]
    repo.converge(b"j", ([], 31))
    assert new()["foreign_cutoffs"] == 2
    # the entries bound (a cold table has none; a warmed repo sets what one floor batch holds)
    repo._tbl.set_entries_bound(4)
    for j in range(3):
        repo.converge(b"k", ([(b"v%d" % j, 40 + j)], 0))
    assert not repo.drain_overdue()
    repo.drain()  # a snapshot's or a shutdown's drain: no bound tripped
    assert new()["overdue"] == 0 and new()["passes"] == 2
    for j in range(4):
        repo.converge(b"k", ([(b"w%d" % j, 50 + j)], 0))
    assert repo.drain_overdue()
    repo.drain()  # what `RepoManager.converge_async` does then
    assert new() == {"foreign_entries": 11, "foreign_cutoffs": 2, "overdue": 1, "passes": 3}
    resp = _Resp()
    for j in range(4):  # a local INS that crosses the bound drains on the spot
        repo.apply(resp, [b"INS", b"k", b"x%d" % j, b"%d" % (60 + j)])
    assert new()["overdue"] == 2 and new()["foreign_entries"] == 11 and repo._tbl.pend_len(repo._tbl.find(b"k")) == 0
    assert len(get(repo, b"k")) == 1 + 3 + 4 + 4 and get(repo, b"j") == []


def test_the_new_tallies_are_on_the_scrape_in_system_metrics_and_in_the_shutdown_line():
    from jylis_tpu.models.database import Database
    from jylis_tpu.obs import prom

    db = Database(identity=4)
    db.converge_deltas(("TLOG", [(b"k", ([(b"v%d" % j, 10 + j) for j in range(5)], 12))]))
    db.manager("TLOG").repo.drain()
    text = prom.render(db)
    for kind, n in (("foreign_entries", 5), ("foreign_cutoffs", 1), ("overdue", 0)):
        assert f'jylis_drain_total{{type="TLOG",kind="{kind}"}} {n}' in text
    assert 'jylis_drain_total{type="TLOG",kind="passes"}' in text
    lines = metric_lines(registry=db.metrics)
    assert "TLOG foreign_entries 5" in lines and "TLOG foreign_cutoffs 1" in lines and "TLOG overdue 0" in lines
    assert ", 5 foreign_entries, 1 foreign_cutoffs, 0 overdue, " in db.metrics.report()
