"""The five TLOG tallies beside `drain.TLOG` count what they say, on both
table backends, and show on every surface the TREG pair shows on:
`jylis_drain_total{type="TLOG",kind=...}`, `SYSTEM METRICS`, the shutdown
log's `merge metrics:` line."""

import pytest

import jylis_tpu  # noqa: F401
from jylis_tpu.models.repo_tlog import RepoTLOG
from jylis_tpu.obs.registry import MetricsRegistry
from jylis_tpu.utils.metrics import metric_lines

ENGINES = ["auto", "python"]
KINDS = ("entries", "trims", "grows", "row_gathers", "view_sorts")


class _Resp:
    def __getattr__(self, name):
        return lambda *a: None


def fresh(engine):
    repo = RepoTLOG(identity=1, mesh=None, engine=engine)
    repo.metrics = reg = MetricsRegistry()
    return repo, reg, lambda: {k: reg.tallies["drain.TLOG." + k] for k in KINDS}


@pytest.mark.parametrize("engine", ENGINES)
def test_a_drain_of_three_rows_of_four_entries_is_twelve_entries_and_one_batch(engine):
    repo, reg, read = fresh(engine)
    resp = _Resp()
    for k in range(3):
        for j in range(4):
            repo.apply(resp, [b"INS", b"k%d" % k, b"v%d" % j, b"%d" % (100 + j)])
    assert read()["entries"] == 0 and reg.counters["TLOG"]["batches"] == 0
    repo.drain()
    assert read() == {"entries": 12, "trims": 0, "grows": 0, "row_gathers": 0, "view_sorts": 0}
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
def test_reads_count_gathers_and_sorts_and_a_quiescent_get_adds_none(engine):
    repo, reg, read = fresh(engine)
    resp = _Resp()
    # restored rows: the drain lands while no merged memo is current, so
    # the host does not hold the drained base and the first read gathers it
    repo.load_state([(b"a", ([(b"v%d" % j, 10 + j) for j in range(5)], 0)),
                     (b"b", ([(b"w%d" % j, 10 + j) for j in range(5)], 0))])
    repo.drain()
    assert read()["row_gathers"] == 0 and read()["view_sorts"] == 0
    repo.apply(resp, [b"GET", b"a"])
    assert read()["row_gathers"] == 1 and read()["view_sorts"] == 1
    for _ in range(3):  # quiescent: the rendered row serves it
        repo.apply(resp, [b"GET", b"a", b"2"])
        repo.apply(resp, [b"SIZE", b"a"])
    assert read()["row_gathers"] == 1 and read()["view_sorts"] == 1
    # a pending entry moves the row's generation: the merged view is sorted once, then kept
    repo.apply(resp, [b"INS", b"a", b"new", b"99"])
    repo.apply(resp, [b"GET", b"a"])
    repo.apply(resp, [b"GET", b"a", b"3"])
    assert read()["row_gathers"] == 1 and read()["view_sorts"] == 2
    repo.apply(resp, [b"SIZE", b"b"])  # quiescent SIZE: the length cache, no gather
    assert read()["row_gathers"] == 1


def test_the_tallies_are_on_the_scrape_in_system_metrics_and_in_the_shutdown_line():
    from jylis_tpu.models.database import Database
    from jylis_tpu.obs import prom

    db = Database(identity=3)
    resp = _Resp()
    for j in range(4):
        db.apply(resp, [b"TLOG", b"INS", b"k", b"v%d" % j, b"%d" % (10 + j)])
    db.apply(resp, [b"TLOG", b"TRIMAT", b"k", b"11"])
    text = prom.render(db)
    for kind, n in (("entries", 4), ("trims", 1), ("grows", 0), ("row_gathers", 0)):
        assert f'jylis_drain_total{{type="TLOG",kind="{kind}"}} {n}' in text
    assert 'jylis_drain_total{type="TLOG",kind="view_sorts"}' in text
    lines = metric_lines(registry=db.metrics)
    assert "TLOG entries 4" in lines and "TLOG trims 1" in lines and "TLOG grows 0" in lines
    assert ", 4 entries, 1 trims, 0 grows, 0 row_gathers, " in db.metrics.report()
