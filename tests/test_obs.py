"""Observability layer (jylis_tpu/obs/): histograms, trace ring,
per-Database registry, SYSTEM LATENCY/TRACE, Prometheus endpoint.

The histogram tests pin the log2-bucket quantile contract against numpy
percentiles on adversarial distributions (the reported value is the
matched bucket's UPPER bound, so it may exceed the true quantile by at
most one bucket — a factor of two — and never undershoots by more than
the quantile-definition wobble within a bucket). The trace-ring tests
pin bounded memory and overwrite order. The integration tests drive a
real Database/Server and assert every armed seam reports non-zero
percentiles through all three surfaces (METRICS lines, SYSTEM LATENCY,
Prometheus render).
"""

import asyncio
import json
import os
import random
import re

import numpy as np
import pytest

import jylis_tpu  # noqa: F401
from jylis_tpu.models.database import Database
from jylis_tpu.obs import GAUGES, SEAMS, TALLIES
from jylis_tpu.obs.hist import Histogram
from jylis_tpu.obs.registry import MetricsRegistry
from jylis_tpu.obs.trace import DETAIL_CAP, TraceRing
from jylis_tpu.server.server import Server
from jylis_tpu.utils import metrics
from jylis_tpu.utils.config import Config
from jylis_tpu.utils.log import Log


class _Resp:
    """Collects reply-protocol calls as (name, args) for assertions."""

    def __init__(self):
        self.calls = []

    def __getattr__(self, name):
        return lambda *a: self.calls.append((name, a))

    def strings(self):
        return [a[0] for n, a in self.calls if n == "string"]


# ---- histogram quantiles vs numpy ------------------------------------------


def _check_against_numpy(samples):
    h = Histogram()
    for s in samples:
        h.record(s)
    assert h.count == len(samples)
    assert h.max == pytest.approx(max(samples))
    for q in (0.50, 0.90, 0.99):
        got = h.percentile(q)
        # inverted_cdf = the order-statistic definition the histogram
        # implements (smallest value whose CDF reaches q); the default
        # linear interpolation invents values BETWEEN modes of a
        # bimodal distribution, which no bucket scheme can report
        ref = float(np.percentile(samples, q * 100, method="inverted_cdf"))
        if ref == 0.0:
            assert got == 0.0
            continue
        # upper-bound semantics: got lies in (ref/2, 2*ref] up to the
        # within-bucket wobble of the quantile definition — the bucket
        # holding the reference value has bounds within 2x of it
        assert got <= ref * 2.05, (q, got, ref)
        assert got >= ref * 0.5, (q, got, ref)


def test_histogram_uniform_and_constant():
    rng = random.Random(7)
    _check_against_numpy([rng.uniform(1e-6, 1e-3) for _ in range(5000)])
    _check_against_numpy([3.2e-4] * 1000)
    _check_against_numpy([1e-9])  # single sample


def test_histogram_adversarial_distributions():
    rng = random.Random(11)
    # bimodal with a 100x gap: p50 in the low mode, p99 in the high one
    bimodal = [rng.uniform(1e-5, 2e-5) for _ in range(900)] + [
        rng.uniform(1e-3, 2e-3) for _ in range(100)
    ]
    rng.shuffle(bimodal)
    _check_against_numpy(bimodal)
    # heavy tail spanning six decades
    heavy = [10 ** rng.uniform(-7, -1) for _ in range(4000)]
    _check_against_numpy(heavy)
    # near-boundary values: exact powers of two in ns
    _check_against_numpy([(1 << k) * 1e-9 for k in range(1, 40)] * 3)


def test_histogram_edge_cases():
    h = Histogram()
    assert h.percentile(0.5) == 0.0  # empty
    h.record(0.0)
    assert h.percentile(0.99) == 0.0  # zero bucket reports zero
    h.record(-1.0)  # clock hiccup: clamped, never raises
    h.record(1e12)  # absurd duration: clamped into the last bucket
    assert h.count == 3
    assert sum(h.buckets) == 3
    assert h.percentile(1.0) > 0


# ---- trace ring -------------------------------------------------------------


def test_trace_ring_bounded_and_overwrites_oldest():
    r = TraceRing(cap=8)
    for i in range(50):
        r.push("sub", "ev", reason=f"r{i}")
    assert len(r) == 8  # bounded
    reasons = [e[3] for e in r.dump()]
    assert reasons == [f"r{i}" for i in range(42, 50)]  # oldest gone
    assert [e[3] for e in r.dump(3)] == ["r47", "r48", "r49"]  # newest N
    # detail truncation bounds per-entry memory
    r.push("sub", "ev", detail="x" * 10_000)
    assert len(r.dump()[-1][4]) == DETAIL_CAP
    line = TraceRing.format(r.dump()[-1])
    assert "sub ev" in line and line.endswith("x" * 10)


# ---- registry ---------------------------------------------------------------


def test_registry_preregisters_all_declared_names():
    reg = MetricsRegistry()
    assert set(reg.hists) == set(SEAMS)
    assert set(reg.gauges) == set(GAUGES)
    assert set(reg.tallies) == set(TALLIES) and not any(reg.tallies.values())
    with pytest.raises(KeyError):
        reg.hist("not.a.seam")
    with pytest.raises(KeyError):
        reg.gauge_set("not.a.gauge", 1.0)


def test_registry_note_drain_feeds_histogram():
    reg = MetricsRegistry()
    reg.note_drain("TREG", 5, 0.001)
    assert reg.counters["TREG"]["batches"] == 1
    assert reg.hists["drain.TREG"].count == 1
    reg.note_drain("NOSUCH", 1, 0.001)  # un-seamed type: counters only
    assert reg.counters["NOSUCH"]["batches"] == 1


def test_registries_do_not_cross_talk():
    """The PR's satellite fix: two Databases in one process keep fully
    separate counters (the old module-global dicts shared them)."""
    a, b = Database(identity=1), Database(identity=2)
    default_before = int(
        metrics.DEFAULT.counters.get("GCOUNT", {"batches": 0})["batches"]
    )
    resp = _Resp()
    a.apply(resp, [b"GCOUNT", b"INC", b"k", b"1"])
    a.manager("GCOUNT").repo.converge(b"k", {9: 1})
    a.apply(resp, [b"GCOUNT", b"GET", b"k"])  # forces a drain on A
    assert a.metrics.counters["GCOUNT"]["batches"] == 1
    assert b.metrics.counters.get("GCOUNT") is None
    assert (
        int(metrics.DEFAULT.counters.get("GCOUNT", {"batches": 0})["batches"])
        == default_before
    )
    a.metrics.note_serving("demotions")
    assert b.metrics.serving_counters["demotions"] == 0


def test_journal_section_emits_zeros_once_enabled():
    """metric_lines: the JOURNAL section appears with explicit zeros as
    soon as journaling is enabled — dashboards see the full glossary
    from boot, not a section that pops in at the first nonzero."""
    reg = MetricsRegistry()
    assert not any(
        line.startswith("JOURNAL") for line in metrics.metric_lines(registry=reg)
    )
    reg.journal_enabled = True
    lines = metrics.metric_lines(registry=reg)
    got = [line for line in lines if line.startswith("JOURNAL")]
    assert got == [
        "JOURNAL appends 0",
        "JOURNAL bytes 0",
        "JOURNAL fsyncs 0",
        "JOURNAL replayed_batches 0",
        "JOURNAL errors 0",
    ]


def test_metric_lines_latency_section_shape():
    reg = MetricsRegistry()
    reg.hist("journal.fsync").record(0.0005)
    lines = metrics.metric_lines(registry=reg)
    lat = [line for line in lines if line.startswith("LATENCY")]
    assert any(
        re.fullmatch(r"LATENCY journal\.fsync\.p50_us \d+", line) for line in lat
    )
    assert "LATENCY journal.fsync.count 1" in lat
    # silent seams emit nothing in METRICS (they still show in LATENCY)
    assert not any("server.native_burst" in line for line in lat)


# ---- SYSTEM LATENCY / SYSTEM TRACE -----------------------------------------


def test_system_latency_and_trace_commands():
    db = Database(identity=3)
    resp = _Resp()
    db.metrics.hist("server.py_dispatch").record(0.002)
    db.metrics.trace_event("server", "demote", "", "conn 1")
    db.metrics.trace_event("cluster", "drop", "eof", "active x")
    db.apply(resp, [b"SYSTEM", b"LATENCY"])
    lines = resp.strings()
    # every declared seam reports, armed ones with non-zero percentiles
    assert len([line for line in lines if line.startswith("drain.")]) == 8
    (dispatch,) = [
        line for line in lines if line.startswith("server.py_dispatch ")
    ]
    m = re.fullmatch(
        r"server\.py_dispatch count 1 p50_us (\d+) p90_us \d+ "
        r"p99_us (\d+) max_us \d+",
        dispatch,
    )
    assert m and int(m.group(1)) > 0 and int(m.group(2)) > 0
    (silent,) = [
        line for line in lines if line.startswith("server.native_burst ")
    ]
    assert " count 0 " in silent

    resp2 = _Resp()
    db.apply(resp2, [b"SYSTEM", b"TRACE"])
    t = resp2.strings()
    assert len(t) == 2 and "server demote" in t[0] and "cluster drop eof" in t[1]
    resp3 = _Resp()
    db.apply(resp3, [b"SYSTEM", b"TRACE", b"1"])
    assert len(resp3.strings()) == 1 and "cluster drop" in resp3.strings()[0]
    # help advertises the new subcommands
    resp4 = _Resp()
    db.apply(resp4, [b"SYSTEM", b"NOPE"])
    err = [a[0] for n, a in resp4.calls if n == "err"]
    assert err and "LATENCY" in err[0] and "TRACE" in err[0]


# ---- server dispatch seams --------------------------------------------------


async def _drive_server(db, payload: bytes, n_replies: int) -> bytes:
    cfg = Config()
    cfg.port = "0"
    cfg.log = Log.create_none()
    server = Server(cfg, db)
    await server.start()
    try:
        reader, writer = await asyncio.open_connection("127.0.0.1", server.port)
        writer.write(payload)
        await writer.drain()
        got = b""
        while got.count(b"\r\n") < n_replies:
            chunk = await asyncio.wait_for(reader.read(1 << 16), timeout=5.0)
            if not chunk:
                break
            got += chunk
        writer.close()
        return got
    finally:
        await server.dispose()


def test_server_seams_record_both_paths():
    async def main():
        db = Database(identity=4)
        burst = (
            b"GCOUNT INC k 1\r\nGCOUNT GET k\r\n"
            b"SYSTEM VERSION\r\n"  # SYSTEM always defers to Python
        )
        await _drive_server(db, burst, 3)
        if db.native_engine is not None:
            assert db.metrics.hist("server.native_burst").count > 0
        assert db.metrics.hist("server.py_dispatch").count > 0
        for h in ("server.native_burst", "server.py_dispatch"):
            snap = db.metrics.hist(h).snapshot()
            if snap["count"]:
                assert snap["p50_s"] > 0 and snap["p99_s"] >= snap["p50_s"]

    asyncio.run(main())


def test_server_seams_disabled_registry_records_nothing():
    async def main():
        db = Database(identity=5)
        db.metrics.enabled = False
        await _drive_server(db, b"GCOUNT INC k 1\r\nSYSTEM VERSION\r\n", 2)
        assert db.metrics.hist("server.native_burst").count == 0
        assert db.metrics.hist("server.py_dispatch").count == 0

    asyncio.run(main())


# ---- Prometheus render ------------------------------------------------------

_SAMPLE_RE = re.compile(
    r"^[a-zA-Z_:][a-zA-Z0-9_:]*(\{[^{}]*\})? -?[0-9.eE+-]+$"
)


def test_prom_render_grammar_and_presence():
    from jylis_tpu.obs import prom

    db = Database(identity=6)
    resp = _Resp()
    db.apply(resp, [b"GCOUNT", b"INC", b"k", b"2"])
    db.metrics.hist("journal.append").record(0.0001)
    body = prom.render(db)
    for line in body.splitlines():
        if line and not line.startswith("#"):
            assert _SAMPLE_RE.match(line), line
    for seam in SEAMS:  # full surface from boot, zero counts included
        assert f'seam="{seam}"' in body
    for g in GAUGES:
        assert f'name="{g}"' in body
    for t in TALLIES:  # further kinds of the type's drain totals
        _, typ, kind = t.split(".")
        # all from zero, but the size of the engine's reply buffer
        boot = 1 << 16 if kind == "reply_buffer_bytes" and db.native_engine else 0
        assert f'jylis_drain_total{{type="{typ}",kind="{kind}"}} {boot}' in body
    assert 'jylis_cmds_total{type="GCOUNT"} 1' in body
    assert 'jylis_seam_latency_seconds_count{seam="journal.append"} 1' in body
    # and the manifest agrees with the declared surface (the CI smoke
    # asserts the same equivalence against a LIVE node's scrape)
    manifest_path = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "scripts", "jlint", "metrics_manifest.json",
    )
    manifest = json.load(open(manifest_path))["metrics"]
    assert {n[5:] for n in manifest if n.startswith("hist:")} == set(SEAMS)
    assert {n[6:] for n in manifest if n.startswith("gauge:")} == set(GAUGES)
    assert {n[8:] for n in manifest if n.startswith("counter:")} == set(TALLIES)


def test_prom_http_endpoint_serves_and_404s():
    from jylis_tpu.obs.prom import MetricsHTTP

    async def main():
        db = Database(identity=7)
        http = MetricsHTTP(db, port=0)
        await http.start()
        try:
            reader, writer = await asyncio.open_connection("127.0.0.1", http.port)
            writer.write(b"GET /metrics HTTP/1.1\r\nHost: x\r\n\r\n")
            await writer.drain()
            got = await asyncio.wait_for(reader.read(1 << 20), timeout=5.0)
            assert got.startswith(b"HTTP/1.1 200 OK")
            assert b"jylis_seam_latency_seconds" in got
            writer.close()
            reader, writer = await asyncio.open_connection("127.0.0.1", http.port)
            writer.write(b"GET /nope HTTP/1.1\r\n\r\n")
            await writer.drain()
            got = await asyncio.wait_for(reader.read(1 << 16), timeout=5.0)
            assert got.startswith(b"HTTP/1.1 404")
            writer.close()
        finally:
            await http.dispose()

    asyncio.run(main())


# ---- journal seams ----------------------------------------------------------


def test_journal_seams_record_append_and_fsync(tmp_path):
    from jylis_tpu.journal import Journal

    reg = MetricsRegistry()
    default_before = metrics.DEFAULT.hists["journal.append"].count
    j = Journal(str(tmp_path / "j.jylis"), fsync="always", registry=reg)
    j.open()
    j.append("GCOUNT", [(b"a", {1: 1})])
    j.append("GCOUNT", [(b"b", {1: 2})])
    j.close()
    assert reg.hists["journal.append"].count == 2
    assert reg.hists["journal.fsync"].count >= 2
    assert reg.journal_counters["appends"] == 2
    # per-instance: the process default saw none of it
    assert metrics.DEFAULT.hists["journal.append"].count == default_before


# ---- windowed snapshots (SYSTEM LATENCY WINDOW) -----------------------------


def test_histogram_mark_and_snapshot_since():
    h = Histogram()
    h.record(0.001)
    h.record(0.002)
    marked = h.mark()
    h.record(0.1)
    delta = h.snapshot_since(marked)
    assert delta["count"] == 1
    # the delta's quantiles see ONLY the post-mark sample
    assert delta["p50_s"] > 0.05
    # since-boot snapshot unchanged by the mark
    assert h.snapshot()["count"] == 3


def test_registry_window_stats_empty_then_delta():
    reg = MetricsRegistry()
    assert reg.window_stats(60.0) == (0.0, None)  # no mark yet
    reg.hist("journal.append").record(0.001)
    reg.window_deposit()
    reg.window_deposit()  # rate-limited: second deposit is dropped
    assert len(reg._window_marks) == 1
    reg.hist("journal.append").record(0.05)
    achieved, stats = reg.window_stats(0.001)
    assert achieved > 0.0 and stats is not None
    snap = dict(stats)["journal.append"]
    assert snap["count"] == 1  # pre-mark sample subtracted


def test_system_latency_window_command():
    import time as _time

    db = Database(identity=31)
    db.metrics.hist("journal.append").record(0.001)
    resp = _Resp()
    db.apply(resp, [b"SYSTEM", b"LATENCY"])  # deposits the first mark
    _time.sleep(1.1)  # past WINDOW_MIN_SPACING_S so a fresh mark lands
    db.metrics.hist("journal.append").record(0.002)
    resp2 = _Resp()
    db.apply(resp2, [b"SYSTEM", b"LATENCY", b"WINDOW", b"1"])
    lines = resp2.strings()
    assert lines[0].startswith("window_s ")
    (ja,) = [l for l in lines if l.startswith("journal.append ")]
    # only the post-mark sample: count 1, not 2
    assert re.fullmatch(
        r"journal\.append count 1 p50_us \d+ p90_us \d+ p99_us \d+", ja
    )
    # bad arguments fall back to the BADCOMMAND help, never a crash
    for bad in ([b"SYSTEM", b"LATENCY", b"WINDOW"],
                [b"SYSTEM", b"LATENCY", b"WINDOW", b"nope"],
                [b"SYSTEM", b"LATENCY", b"WINDOW", b"-3"]):
        r = _Resp()
        db.apply(r, bad)
        assert any(
            n == "err" and "SYSTEM LATENCY" in a[0] for n, a in r.calls
        ), bad


# ---- Prometheus cumulative _bucket series + converge_slo --------------------


def test_prom_bucket_series_cumulative_and_consistent():
    from jylis_tpu.obs import prom

    db = Database(identity=32)
    h = db.metrics.hist("journal.append")
    for s in (0.0001, 0.002, 0.002, 1.5):
        h.record(s)
    body = prom.render(db)
    pat = re.compile(
        r'jylis_seam_latency_log2_seconds_bucket\{seam="journal\.append"'
        r',le="([^"]+)"\} (\d+)'
    )
    pts = [(float(le), int(v)) for le, v in pat.findall(body)]
    assert pts, "no _bucket series for an armed seam"
    les = [le for le, _ in pts]
    assert les == sorted(les) and les[-1] == float("inf")
    vals = [v for _, v in pts]
    assert all(b >= a for a, b in zip(vals, vals[1:]))  # cumulative
    assert vals[-1] == 4
    m = re.search(
        r'jylis_seam_latency_log2_seconds_count\{seam="journal\.append"\}'
        r" (\d+)", body,
    )
    assert m and int(m.group(1)) == 4  # _count == +Inf bucket
    # every declared seam has a bucket series from boot (zero counts)
    for seam in SEAMS:
        assert f'_bucket{{seam="{seam}",le="+Inf"}}' in body


def test_prom_converge_slo_families_render():
    from jylis_tpu.obs import prom
    from jylis_tpu.obs import jtrace

    db = Database(identity=33)
    span = jtrace.append_hop(b"", jtrace.HOP_ORIGIN, "n1", "r1", 1000)
    db.metrics.spans.ingest(span, "n2", "r2", 1020)  # 20ms: under all
    db.metrics.spans.ingest(b"\xff", "n2", "r2", 0)  # malformed
    body = prom.render(db)
    assert 'jylis_converge_slo{le="50"} 1.000000' in body
    assert 'jylis_converge_slo_total{kind="sampled"} 1' in body
    assert 'jylis_converge_slo_total{kind="malformed"} 1' in body
    assert 'jylis_converge_slo_total{kind="ok_50"} 1' in body


# ---- serving-pipeline profiler seams ---------------------------------------


def test_pipeline_seams_record_over_live_connection():
    async def main():
        db = Database(identity=34)
        burst = (
            b"GCOUNT INC pk 1\r\nGCOUNT GET pk\r\nSYSTEM VERSION\r\n"
        )
        await _drive_server(db, burst, 3)
        for seam in ("pipeline.accept", "pipeline.read",
                     "pipeline.dispatch", "pipeline.reply_write"):
            assert db.metrics.hist(seam).count > 0, seam
        # accept is one sample per CONNECTION, not per command
        assert db.metrics.hist("pipeline.accept").count == 1
        # dispatch mirrors the per-burst/per-command serving seams
        served = (db.metrics.hist("server.native_burst").count
                  + db.metrics.hist("server.py_dispatch").count)
        assert db.metrics.hist("pipeline.dispatch").count == served

    asyncio.run(main())


def test_pipeline_parse_seam_times_python_path_commands():
    """pipeline.parse is a Python-path seam (a native burst parses in
    C++ inside pipeline.dispatch): force the fallback and each command
    gets an individually-timed parse."""

    async def main():
        db = Database(identity=38)
        db.native_engine = None
        burst = b"GCOUNT INC pk 1\r\nGCOUNT GET pk\r\nSYSTEM VERSION\r\n"
        await _drive_server(db, burst, 3)
        # one timed parse per command, plus the final None probe(s)
        assert db.metrics.hist("pipeline.parse").count >= 3
        assert db.metrics.hist("pipeline.dispatch").count == \
            db.metrics.hist("server.py_dispatch").count

    asyncio.run(main())


def test_pipeline_seams_disabled_registry_records_nothing():
    async def main():
        db = Database(identity=35)
        db.metrics.enabled = False
        await _drive_server(db, b"GCOUNT INC pk 1\r\n", 1)
        for seam in ("pipeline.accept", "pipeline.read", "pipeline.parse",
                     "pipeline.classify", "pipeline.dispatch",
                     "pipeline.reply_write"):
            assert db.metrics.hist(seam).count == 0, seam

    asyncio.run(main())


# ---- write heat -------------------------------------------------------------


def test_write_heat_counts_flushed_keys_per_bucket():
    from jylis_tpu.models.database import sync_bucket

    db = Database(identity=36)
    flushed = []
    resp = _Resp()
    db.apply(resp, [b"GCOUNT", b"INC", b"heat-a", b"1"])
    db.apply(resp, [b"GCOUNT", b"INC", b"heat-b", b"2"])
    db.flush_deltas(lambda deltas: flushed.append(deltas))
    assert flushed
    heat = db.metrics.write_heat["GCOUNT"]
    assert sum(heat) == 2
    assert heat[sync_bucket(b"heat-a")] >= 1
    assert heat[sync_bucket(b"heat-b")] >= 1


def test_write_heat_disabled_registry_counts_nothing():
    db = Database(identity=37)
    db.metrics.enabled = False
    resp = _Resp()
    db.apply(resp, [b"GCOUNT", b"INC", b"cold", b"1"])
    db.flush_deltas(lambda deltas: None)
    assert "GCOUNT" not in db.metrics.write_heat


# ---- the Python path's commands by cause -------------------------------------

_CAUSES = ("busy_routed_cmds", "deferred_cmds", "demoted_conn_cmds")


@pytest.mark.parametrize("cause", _CAUSES)
def test_python_path_commands_are_counted_by_cause(cause):
    """The three causes partition what the server's Python path
    dispatches: a chunk that met a held repo lock under an admission
    cap (busy()), a command the engine handed back, a connection with no
    engine. Their sum is
    demoted_cmds; all three are on SYSTEM METRICS and the scrape."""
    from jylis_tpu.obs import prom

    n = 5

    async def main():
        db = Database(identity=41)
        if db.native_engine is None and cause != "demoted_conn_cmds":
            pytest.skip("no native engine on this host")
        burst = b"TREG SET k v 1\r\n" + b"TREG GET k\r\n" * (n - 1)
        if cause == "busy_routed_cmds":
            # under --admission-cap, somebody holds a type's lock across
            # a yield: the whole chunk takes the Python path, and (of
            # another type) applies inline. Without a cap it would be
            # served in the engine beside the hold (test_repo_lock.py)
            db.set_admission_cap(8)
            lock = db.manager("GCOUNT")._lock
            await lock.acquire()
            await _drive_server(db, burst, n)
            lock.release()
        elif cause == "deferred_cmds":
            await _drive_server(db, b"SYSTEM VERSION\r\n" * n, n)
        else:
            db.native_engine = None
            await _drive_server(db, burst, n)
        return db

    db = asyncio.run(main())
    serving = db.serving_totals()
    assert serving[cause] == n
    assert [serving[c] for c in _CAUSES if c != cause] == [0, 0]
    assert sum(serving[c] for c in _CAUSES) == serving["demoted_cmds"] == n
    assert db.metrics.hist("serve.py_apply").count == n
    resp = _Resp()
    db.apply(resp, [b"SYSTEM", b"METRICS"])
    assert f"SERVING {cause} {n}" in [str(s) for s in resp.strings()]
    assert f'jylis_serving_total{{kind="{cause}"}} {n}\n' in prom.render(db)


def test_a_slept_burst_is_counted_on_every_surface():
    """A chunk of the type whose lock is held stays native and sleeps
    for it: one slept_bursts a burst (not a command), no busy_routed_cmds,
    on SYSTEM METRICS, the scrape and the shutdown log's line."""
    from jylis_tpu.obs import prom

    async def main():
        db = Database(identity=43)
        if db.native_engine is None:
            pytest.skip("no native engine on this host")
        lock = db.manager("TREG")._lock
        await lock.acquire()
        asyncio.get_running_loop().call_later(0.1, lock.release)
        got = await _drive_server(db, b"TREG SET k v 1\r\n" + b"TREG GET k\r\n" * 4, 5)
        assert got.startswith(b"+OK\r\n*2\r\n$1\r\nv\r\n:1\r\n")
        return db

    db = asyncio.run(main())
    serving = db.serving_totals()
    assert serving["slept_bursts"] == 1 and serving["native_cmds"] == 5
    assert serving["busy_routed_cmds"] == serving["demoted_cmds"] == 0
    assert db.metrics.hist("lock.wait_serve").count == 1
    resp = _Resp()
    db.apply(resp, [b"SYSTEM", b"METRICS"])
    lines = [str(s) for s in resp.strings()]
    assert "SERVING slept_bursts 1" in lines and "SERVING slept_bursts.TREG 1" in lines
    assert "SERVING native_bursts 1" in lines and "SERVING burst_locks 1" in lines
    scrape = prom.render(db)
    assert 'jylis_serving_total{kind="slept_bursts"} 1\n' in scrape
    assert 'jylis_slept_bursts_total{type="TREG"} 1\n' in scrape  # the type label
    assert 'jylis_serving_total{kind="bursts_beside_hold"} 0\n' in scrape
    assert db.metrics.report().endswith("; SERVING: 0 demotions, 0 busy_refusals, "
        "0 busy_routed_cmds, 0 deferred_cmds, 0 demoted_conn_cmds, "
        f"{serving['reply_bytes']} reply_bytes, 1 slept_bursts, 0 loop_sends, "
        "1 native_bursts, 1 burst_locks, 0 bursts_beside_hold, 0 inline_bursts")


def test_engine_reply_bytes_are_counted_per_burst():
    async def main():
        db = Database(identity=42)
        if db.native_engine is None:
            pytest.skip("no native engine on this host")
        got = await _drive_server(db, b"GCOUNT INC k 7\r\nGCOUNT GET k\r\n", 2)
        assert got == b"+OK\r\n:7\r\n"
        assert db.serving_totals()["reply_bytes"] == len(got)
        assert db.serving_totals()["deferred_cmds"] == 0

    asyncio.run(main())
