"""The host time budget (obs/span.py, obs/loop.py): one span instrument
with two sinks, the event loop's busy/CPU pair, the drain's three phases,
the lock waits, and the device-trace window (``SYSTEM PROFILE``).

The in-process tests pin the primitive's contract (unarmed: a float
token and a histogram increment, no profiler call; armed: an annotation
of the same name and interval). The real-process test drives a node
with ``JYLIS_PROFILE_DIR`` set through ``SYSTEM PROFILE START`` ...
``STOP`` and reads the xplane file it wrote.
"""

import asyncio
import fnmatch
import glob
import os
import subprocess
import sys
import time
import urllib.request

import pytest

import jylis_tpu  # noqa: F401
from jylis_tpu.models.database import Database
from jylis_tpu.obs import SEAMS, loop as loop_mod, prom, span
from jylis_tpu.obs.registry import MetricsRegistry
from jylis_tpu.utils import metrics

from procutil import REPO, SPAWN_CPU, connect_client, free_port, stop_node
from test_async_serving import SLOW, make_server, slow_down_drain
from test_server import send_recv

NEW_SEAMS = (
    "loop.busy",
    "lock.wait_serve",
    "lock.wait_cluster",
    "drain_phase.assemble",
    "drain_phase.device",
    "drain_phase.finish",
    "cluster.decode",
    "cluster.apply",
    "repo.flush",
)
# a served burst's stages and the repo-lock holds (PR 36)
SERVE_SEAMS = (
    "serve.route",
    "serve.tail",
    "serve.write_wait",
    "serve.py_apply",
    "lock.hold_serve",
    "lock.hold_converge",
    "lock.hold_flush",
    "lock.hold_sync",
)


class _Resp:
    def __init__(self):
        self.calls = []

    def __getattr__(self, name):
        return lambda *a: self.calls.append((name, a))


class _FakeAnnotation:
    made: list = []

    def __init__(self, name, **kwargs):
        self.name, self.kwargs = name, kwargs
        self.t_enter = self.t_exit = None
        _FakeAnnotation.made.append(self)

    def __enter__(self):
        self.t_enter = time.perf_counter()

    def __exit__(self, *exc):
        self.t_exit = time.perf_counter()


class _NoProfiler:
    """Stands in for ``jax.profiler``: any use of it fails the test."""

    def __getattr__(self, name):
        raise AssertionError(f"jax.profiler.{name} touched by an unarmed span")


# ---- the primitive ----------------------------------------------------------


def test_unarmed_span_is_a_float_and_a_histogram_increment(monkeypatch):
    monkeypatch.setattr(span, "_armed", False)
    monkeypatch.setattr(span, "_profiler", _NoProfiler())
    reg = MetricsRegistry()
    seam = reg.seam("repo.flush")
    tok = seam.begin(None, {"keys": 3})
    assert type(tok) is float  # the start stamp itself: no object per span
    time.sleep(0.01)
    seam.end(tok)
    h = reg.hist("repo.flush")
    assert h.count == 1 and 0.009 < h.total < 0.5
    # the registry's kill switch gates the clock reads too
    reg.enabled = False
    assert seam.begin() == 0.0
    seam.end(0.0)
    assert h.count == 1


def test_armed_span_emits_an_annotation_of_the_same_name_and_interval(monkeypatch):
    import jax.profiler

    _FakeAnnotation.made = []
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", _FakeAnnotation)
    monkeypatch.setattr(span, "_armed", True)
    reg = MetricsRegistry()
    seam = reg.seam("cluster.apply")
    tok = seam.begin(None, {"keys": 7})
    time.sleep(0.01)
    seam.end(tok)
    tok = seam.begin("another.label")
    seam.end(tok)
    first, second = _FakeAnnotation.made
    assert first.name == "cluster.apply" and first.kwargs == {"keys": 7}
    assert second.name == "another.label" and second.kwargs == {}
    h = reg.hist("cluster.apply")
    assert h.count == 2
    # one call recorded both: the annotation's interval is the seam's
    assert first.t_exit is not None and second.t_exit is not None
    assert abs((first.t_exit - first.t_enter) - (h.total - (second.t_exit - second.t_enter))) < 2e-3


def test_drain_records_its_three_phases_with_their_parent(monkeypatch):
    """Unarmed and armed: `timed_drain` hands the drain and its phase
    sums to the registry in one call, so they add up; armed, the phases
    are annotations named ``drain_<TYPE>.<phase>`` sharing ``seq``."""
    import jax.profiler

    db = Database(identity=1)
    repo = db.manager("PNCOUNT").repo
    repo.converge(b"k", ({7: 1}, {7: 1}))
    repo.drain()  # compiles
    reg = db.metrics
    base = [reg.hist("drain_phase." + p).total for p in metrics.DRAIN_PHASES]
    base_parent = reg.hist("drain.PNCOUNT").total
    _FakeAnnotation.made = []
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", _FakeAnnotation)
    monkeypatch.setattr(span, "_armed", True)
    repo.converge(b"k", ({7: 5}, {7: 2}))
    repo.drain()
    phases = [reg.hist("drain_phase." + p).total - b
              for p, b in zip(metrics.DRAIN_PHASES, base)]
    parent = reg.hist("drain.PNCOUNT").total - base_parent
    assert all(p > 0 for p in phases)
    assert sum(phases) <= parent and sum(phases) > 0.9 * parent
    names = [a.name for a in _FakeAnnotation.made]
    assert names == ["drain_PNCOUNT", "drain_PNCOUNT.assemble",
                     "drain_PNCOUNT.device", "drain_PNCOUNT.finish"]
    step, *parts = _FakeAnnotation.made
    assert step.kwargs["_r"] == 1 and step.kwargs["rows"] == 1
    assert {a.kwargs["seq"] for a in parts} == {step.kwargs["step_num"]}
    # nested: every phase lies inside the step's interval
    assert all(step.t_enter <= a.t_enter and a.t_exit <= step.t_exit for a in parts)
    # outside a timed drain the mark is a no-op
    metrics.drain_phase(repo, metrics.DEVICE)


@pytest.mark.parametrize("seam", NEW_SEAMS + SERVE_SEAMS)
def test_no_new_seam_reads_as_a_drain_seam(seam):
    """The benchmark's ``models.drain_*`` metrics read ``seam="drain.*"``:
    a new seam whose name matched would change their meaning."""
    assert seam in SEAMS
    for stat in ("sum", "count"):
        sample = f'jylis_seam_latency_seconds_{stat}{{seam="{seam}"}}'
        pattern = f'jylis_seam_latency_seconds_{stat}{{seam="drain.*"}}'
        assert not fnmatch.fnmatchcase(sample, pattern)


def test_new_seams_loop_cpu_and_device_gauge_are_on_the_scrape_from_boot():
    body = prom.render(Database(identity=3))
    for seam in NEW_SEAMS + SERVE_SEAMS:
        assert f'jylis_seam_latency_seconds_count{{seam="{seam}"}} 0\n' in body
        assert f'jylis_seam_latency_seconds_sum{{seam="{seam}"}} 0.000000000\n' in body
    assert "# TYPE jylis_loop_cpu_seconds_total counter\n" in body
    assert "jylis_loop_cpu_seconds_total 0.000000000\n" in body
    assert "# TYPE jylis_device_bytes gauge\n" in body


def test_device_bytes_reads_the_fullest_device(monkeypatch):
    import jax

    class Dev:
        def __init__(self, stats):
            self._stats = stats

        def memory_stats(self):
            return self._stats

    monkeypatch.setattr(jax, "local_devices", lambda: [
        Dev({"bytes_in_use": 10, "peak_bytes_in_use": 40, "bytes_limit": 100}),
        Dev({"bytes_in_use": 30, "peak_bytes_in_use": 35, "bytes_limit": 100}),
        Dev(None)])
    assert prom.device_bytes() == {"in_use": 30, "peak": 40, "limit": 100}
    body = prom.render(Database(identity=3))
    assert 'jylis_device_bytes{kind="peak"} 40\n' in body
    monkeypatch.setattr(jax, "local_devices", lambda: [Dev(None)])
    assert prom.device_bytes() == {}  # XLA:CPU reports nothing


# ---- the loop ----------------------------------------------------------------


def test_timing_selector_measures_busy_wall_and_cpu():
    """One iteration that burns CPU and one that sleeps (a blocking
    call): both are busy wall clock, only the first is CPU; waiting in
    ``select()`` is neither. A stall lands in the trace ring. The CPU
    is the loop thread's own clock, readable from any thread."""
    import threading

    reg = MetricsRegistry()
    assert reg.loop_cpu_s() == 0.0  # no timing selector attached
    seen = {}

    async def main():
        assert loop_mod.attach(reg)
        await asyncio.sleep(0)
        t = time.perf_counter()
        while time.perf_counter() - t < 0.08:  # burns CPU: a stall, too
            pass
        await asyncio.sleep(0)
        time.sleep(0.08)  # jlint would flag this in the product: blocks
        await asyncio.sleep(0.3)  # idle: inside select()
        await asyncio.sleep(0)
        # from another thread, which itself burns CPU meanwhile
        def other():
            t = time.perf_counter()
            while time.perf_counter() - t < 0.05:
                pass
            seen["cpu"] = reg.loop_cpu_s()
        th = threading.Thread(target=other)
        th.start()
        await asyncio.to_thread(th.join)

    asyncio.run(main(), loop_factory=loop_mod.new_event_loop)
    h = reg.hist("loop.busy")
    assert h.count >= 4
    # (bounds leave room for a loaded machine: tier 1 runs six workers)
    assert 0.15 < h.total < 0.45, h.total  # not the 0.3 s of waiting
    assert 0.04 < seen["cpu"] < h.total - 0.05, (seen, h.total)
    stalls = [e for e in reg.trace.dump() if e[1:3] == ("loop", "stall")]
    assert len(stalls) >= 2 and "ms in one iteration" in stalls[0][4]


def test_attach_under_a_plain_loop_is_refused():
    async def main():
        return loop_mod.attach(MetricsRegistry())

    assert asyncio.run(main()) is False


# ---- lock waits ---------------------------------------------------------------


@pytest.mark.parametrize("path", ["native", "python"])
def test_lock_wait_grows_behind_a_drain_and_native_burst_does_not(path):
    """A command that queues behind a repo lock held by a drain adds its
    wait to lock.wait_serve, whichever path it takes: a chunk of the
    held type sleeps as a native burst, whose own time (and with it
    pipeline.dispatch) does not grow; a Python-path command (what a
    chunk is under --admission-cap while a lock is held) sleeps inside
    its dispatch, so pipeline.dispatch (which PERF.md once read as loop
    work) includes the wait."""

    def waiter(port):
        return send_recv(port, b"GCOUNT INC x 1\r\n")

    async def main():
        server, db = make_server()
        if path == "python":
            db.set_admission_cap(8)  # more than wait: nobody is refused
        await server.start()
        reg = db.metrics
        try:
            await send_recv(server.port, b"GCOUNT INC warm 1\r\n")
            burst0 = reg.hist("server.native_burst").total
            slow_down_drain(db, "GCOUNT")
            db.manager("GCOUNT").repo.converge(b"k", {99: 5})
            slow = asyncio.create_task(send_recv(server.port, b"GCOUNT GET k\r\n"))
            await asyncio.sleep(0.05)  # the slow GET holds the GCOUNT lock
            assert db.manager("GCOUNT").busy()
            waiters = [asyncio.create_task(waiter(server.port)) for _ in range(3)]
            assert [await w for w in waiters] == [b"+OK\r\n"] * 3
            assert await slow == b":5\r\n"
            wait = reg.hist("lock.wait_serve")
            assert wait.count >= 4  # the GET's own uncontended take too
            assert wait.total > 3 * (SLOW - 0.2), wait.total  # summed over connections
            dispatch = reg.hist("pipeline.dispatch").total
            if path == "native":
                assert SLOW <= dispatch < wait.total  # the slow GET's own, no wait
                assert reg.serving_counters["slept_bursts"] == 3
            else:
                assert dispatch > wait.total
                assert reg.serving_counters["busy_routed_cmds"] == 3
            assert reg.hist("server.native_burst").total - burst0 < 0.1
            # the cluster's side of the same lock has its own seam
            assert reg.hist("lock.wait_cluster").count == 0
            await db.converge_async(("GCOUNT", [(b"k", {98: 1})]))
            assert reg.hist("lock.wait_cluster").count == 1
            assert reg.hist("cluster.apply").count == 1
        finally:
            await server.dispose()

    asyncio.run(main())


def test_flush_is_one_span_per_delta_flush():
    db = Database(identity=1)
    out = []
    db.flush_deltas(out.append)  # registers the sink; SYSTEM always ships
    h = db.metrics.hist("repo.flush")
    base = h.count
    db.apply(_Resp(), [b"GCOUNT", b"INC", b"k", b"1"])  # proactive flush
    assert h.count == base + 1
    assert any(name == "GCOUNT" for name, _batch in out)


# ---- the device-trace window ----------------------------------------------------


def _profile(db, *words):
    resp = _Resp()
    db.apply(resp, [b"SYSTEM", b"PROFILE", *words])
    return resp.calls


def test_profile_start_without_a_directory_is_an_error_and_starts_nothing(monkeypatch):
    import jax.profiler

    monkeypatch.delenv(span.PROFILE_DIR_ENV, raising=False)
    started = []
    monkeypatch.setattr(jax.profiler, "start_trace", lambda *a, **k: started.append(a))
    db = Database(identity=1)
    (kind, (text,)), = _profile(db, b"START", b"2")
    assert kind == "err" and text.startswith("NOPROFILE") and "JYLIS_PROFILE_DIR" in text
    assert started == [] and span._window is None and not span.armed()
    (kind, (text,)), = _profile(db, b"STOP")
    assert kind == "err" and "no trace window is open" in text
    # malformed: help, like any other SYSTEM parse failure
    for words in ((b"START", b"x"), (b"START", b"-1"), (b"START", b"nan"), (b"NOW",), ()):
        (kind, (text,)), = _profile(db, *words)
        assert kind == "err" and text.startswith("BADCOMMAND") and "PROFILE START" in text
        assert span._window is None


def test_profile_window_stops_by_itself_and_by_command(monkeypatch, tmp_path):
    """START [seconds] arms the spans, refuses a second window, stops
    after its seconds (capped at 60) and writes one xplane file per
    window; STOP ends one early."""
    monkeypatch.setenv(span.PROFILE_DIR_ENV, str(tmp_path))
    monkeypatch.setattr(span, "_armed", False)  # as if set before boot: see below
    db = Database(identity=1)
    (kind, (text,)), = _profile(db, b"START", b"0.4")
    assert kind == "string" and text == str(tmp_path).encode()
    assert span.armed() and span._window is not None
    (kind, (text,)), = _profile(db, b"START")
    assert kind == "err" and "is open" in text
    deadline = time.monotonic() + 20
    while span._window is not None and time.monotonic() < deadline:
        time.sleep(0.05)
    assert span._window is None, "the auto-stop did not fire"
    files = glob.glob(str(tmp_path / "plugins" / "profile" / "*" / "*.xplane.pb"))
    assert len(files) == 1
    assert span.armed()  # the directory is set: annotations stay armed
    # the default and the cap
    (kind, _), = _profile(db, b"START", b"3600")
    assert kind == "string" and span._window[1].interval == span.WINDOW_MAX_S
    (kind, (text,)), = _profile(db, b"STOP")
    assert kind == "string" and text == str(tmp_path).encode() and span._window is None
    assert span.stop_window() is None


def _scrape(port):
    with urllib.request.urlopen(f"http://127.0.0.1:{port}/metrics", timeout=30) as r:
        return r.read().decode()


def test_node_profile_window_writes_drains_and_phases_on_the_wall_clock(tmp_path):
    """A real node with JYLIS_PROFILE_DIR set: nothing is traced until
    SYSTEM PROFILE START; the window's xplane file holds ``drain_TLOG``
    steps with their three phases nested inside and adding up, at times
    that (plus ``profile_start_time``) lie inside the wall-clock stamps
    taken around the window. The node installs no SIGUSR1/SIGUSR2
    handler (the benchmark's shim owns them)."""
    from jax.profiler import ProfileData

    port, mport = free_port(), free_port()
    env = dict(os.environ, JYLIS_PROFILE_DIR=str(tmp_path))
    proc = subprocess.Popen(
        [sys.executable, "-c", SPAWN_CPU, "--port", str(port), "--addr",
         f"127.0.0.1:{free_port()}:span", "--log-level", "warn",
         "--metrics-port", str(mport)], cwd=REPO, env=env)
    try:
        c = connect_client(port, proc=proc)
        body = _scrape(mport)
        for seam in NEW_SEAMS:
            assert f'jylis_seam_latency_seconds_count{{seam="{seam}"}}' in body
        assert "jylis_loop_cpu_seconds_total " in body
        caught = [int(line.split()[1], 16) for line in
                  open(f"/proc/{proc.pid}/status") if line.startswith("SigCgt:")][0]
        import signal

        for sig in (signal.SIGUSR1, signal.SIGUSR2):
            assert not caught & (1 << (sig - 1)), f"the node handles {sig!r}"
        assert caught & (1 << (signal.SIGTERM - 1))  # the mask reads right
        c.execute_command("TLOG", "INS", "warm", "v", "1")
        c.execute_command("TLOG", "TRIM", "warm", "1")  # a drain outside any window
        assert not glob.glob(str(tmp_path / "plugins" / "profile" / "*" / "*.xplane.pb"))
        wall0 = time.time_ns()
        assert c.execute_command("SYSTEM", "PROFILE", "START", "20") == str(tmp_path).encode()
        for i in range(4):
            # a drain of 32 rows: long enough that a lost time slice
            # between two phases stays small beside it
            c.pipeline_execute([("TLOG", "INS", f"k{i}-{j}", "v", str(10 + j))
                                for j in range(32)])
            c.execute_command("TLOG", "TRIM", f"k{i}-0", "1")  # drains
        assert c.execute_command("SYSTEM", "PROFILE", "STOP") == str(tmp_path).encode()
        wall1 = time.time_ns()
        loop_line = [line for line in _scrape(mport).splitlines()
                     if line.startswith('jylis_seam_latency_seconds_count{seam="loop.busy"}')]
        assert float(loop_line[0].split()[-1]) > 0
        c.close()
    finally:
        stop_node(proc)
    files = glob.glob(str(tmp_path / "plugins" / "profile" / "*" / "*.xplane.pb"))
    assert len(files) == 1, files
    data = ProfileData.from_file(files[0])
    start = None
    steps, parts = [], []
    for plane in data.planes:
        if plane.name == "Task Environment":
            start = int(dict(plane.stats)["profile_start_time"])
        if plane.name == "/host:CPU":
            for line in plane.lines:
                for e in line.events:
                    if e.name == "drain_TLOG":
                        steps.append((e.start_ns, e.start_ns + e.duration_ns, dict(e.stats)))
                    elif e.name.startswith("drain_TLOG."):
                        parts.append((e.name, e.start_ns, e.duration_ns, dict(e.stats)))
    assert start is not None and len(steps) == 4
    for t0, t1, stats in steps:
        assert wall0 <= start + t0 and start + t1 <= wall1
        mine = [p for p in parts if p[3]["seq"] == stats["step_num"]]
        assert sorted(p[0] for p in mine) == [
            "drain_TLOG.assemble", "drain_TLOG.device", "drain_TLOG.finish"]
        assert all(t0 <= s and s + d <= t1 for _n, s, d, _st in mine)  # nested
        assert stats["rows"] == 32
    # the phases add up to their drains (to within 10%)
    whole = sum(t1 - t0 for t0, t1, _st in steps)
    assert abs(sum(p[2] for p in parts) - whole) < 0.1 * whole


# ---- a served burst's stages --------------------------------------------------

# the six that tile the handlers' share of loop.busy
STAGES = (
    "serve.route",
    "server.native_burst",
    "pipeline.reply_write",
    "serve.tail",
    "pipeline.parse",
    "serve.py_apply",
)


@pytest.mark.parametrize("conns", [1, 8])
def test_a_served_bursts_stages_tile_the_loops_busy_time(conns):
    """N native commands over real sockets, one a burst: route, engine
    burst, reply write and tail count one per burst and, with the two
    Python-path seams, add up to no more than the loop was busy. A take
    of free locks is the route's: no lock wait, no hold, is recorded."""
    per = 40
    n = conns * per

    async def main():
        server, db = make_server()
        if db.native_engine is None:
            pytest.skip("no native engine on this host")
        await server.start()
        assert loop_mod.attach(db.metrics)
        reg = db.metrics

        async def client(c):
            reader, writer = await asyncio.open_connection("127.0.0.1", server.port)
            for i in range(per):
                writer.write(b"TREG SET k%d v%d %d\r\n" % (c, i, i + 1))
                assert await reader.readexactly(5) == b"+OK\r\n"
            writer.close()

        try:
            await asyncio.gather(*(client(c) for c in range(conns)))
            await asyncio.sleep(0.05)  # the iteration of the last burst is over
        finally:
            await server.dispose()
        for seam in ("serve.route", "server.native_burst", "pipeline.reply_write",
                     "serve.tail"):
            assert reg.hist(seam).count == n, seam
        assert reg.hist("pipeline.read").count >= n
        stages = sum(reg.hist(s).total for s in STAGES)
        assert 0 < stages <= reg.hist("loop.busy").total
        for seam in ("lock.wait_serve", "serve.write_wait", "serve.py_apply",
                     "lock.hold_serve", "lock.hold_converge", "lock.hold_flush",
                     "lock.hold_sync"):
            assert reg.hist(seam).count == 0, seam
        assert reg.serving_counters["reply_bytes"] == 5 * n
        assert db.serving_totals()["native_cmds"] == n

    asyncio.run(main(), loop_factory=loop_mod.new_event_loop)


@pytest.mark.parametrize("held", [False, True])
def test_a_bursts_sleep_is_the_lock_waits_and_not_the_routes(held):
    """An unslept burst leaves lock.wait_serve where it was; one that
    slept behind a held lock adds the sleep to it and NOT to
    serve.route, which stops where the sleep began and goes on after."""

    async def main():
        server, db = make_server()
        if db.native_engine is None:
            pytest.skip("no native engine on this host")
        await server.start()
        reg = db.metrics
        lock = db.manager("GCOUNT")._lock  # the one lock the burst names
        try:
            if held:
                await lock.acquire()
            burst = asyncio.create_task(
                send_recv(server.port, b"GCOUNT INC x 1\r\n", 5))
            if held:
                while not lock._line:  # the clock starts where the sleep does
                    await asyncio.sleep(0.001)
                await asyncio.sleep(0.2)
                assert not burst.done()
                lock.release()
            assert await burst == b"+OK\r\n"
        finally:
            await server.dispose()
        wait, route = reg.hist("lock.wait_serve"), reg.hist("serve.route")
        assert route.count == 1 and reg.hist("server.native_burst").count == 1
        assert reg.serving_counters["inline_bursts"] == (0 if held else 1)
        if held:
            assert wait.count == 1 and wait.total >= 0.19
            assert route.total < 0.1, route.total
        else:
            assert wait.count == 0
        assert not db.manager("GCOUNT").busy()

    asyncio.run(main())


@pytest.mark.parametrize(
    "size,commands,waits", [(1000, 2, 0), (4 << 20, 2, 1), (4 << 20, 1, 0)]
)
def test_a_reply_the_socket_does_not_take_whole_is_one_write_wait(
    size, commands, waits
):
    """A reply past the high-water mark to a client that does not read,
    and a second command behind it: the handler waits in drain(), one
    serve.write_wait sample as long as the client kept it waiting, and
    none of it in the tail. A 1 KB reply goes out whole: no sample.
    ONE such command and nothing after it: the reply is with the sender
    (which the byte bound counts at once), and the handler, whose next
    hand-off would have told it of the refusal, sleeps for no consumer:
    it is back in its read, and there is no sample."""
    import socket

    async def main():
        server, db = make_server()
        if db.native_engine is None:
            pytest.skip("no native engine on this host")
        await server.start()
        reg = db.metrics
        loop = asyncio.get_running_loop()
        try:
            value = b"v" * size
            assert await send_recv(
                server.port, b"*5\r\n$4\r\nTREG\r\n$3\r\nSET\r\n$1\r\nk\r\n$%d\r\n%s\r\n$1\r\n1\r\n"
                % (size, value)) == b"+OK\r\n"
            tail0 = reg.hist("serve.tail").total
            sock = socket.socket()
            sock.setsockopt(socket.SOL_SOCKET, socket.SO_RCVBUF, 4096)
            sock.setblocking(False)
            await loop.sock_connect(sock, ("127.0.0.1", server.port))
            await loop.sock_sendall(sock, b"TREG GET k\r\n")
            await asyncio.sleep(0.05)
            end = b"\r\n:1\r\n"
            if commands == 2:
                # a second command: its reply's hand-off is where a
                # handler behind the sender learns that the socket
                # refused the first
                await loop.sock_sendall(sock, b"GCOUNT GET none\r\n")
                end += b":0\r\n"
            await asyncio.sleep(0.3)  # does not read
            if size > 1 << 20:  # held, and counted by the byte bound
                assert db.admission.queued_bytes > size // 4
            wait = reg.hist("serve.write_wait")
            assert wait.count == 0  # a waiting handler has recorded nothing yet
            got = b""
            while not got.endswith(end):
                got += await asyncio.wait_for(loop.sock_recv(sock, 1 << 20), 5)
            assert value in got
            sock.close()
            await asyncio.sleep(0.05)
            assert wait.count == waits
            if waits:
                assert wait.total >= 0.25, wait.total
                assert reg.hist("serve.tail").total - tail0 < 0.1
        finally:
            await server.dispose()

    asyncio.run(main())
