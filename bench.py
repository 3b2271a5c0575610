"""North-star benchmark: 1M-key × 64-replica PNCOUNT anti-entropy.

BASELINE.json: ">=10x merges/sec vs CPU" for the batched lattice-join merge
path. One "merge" = one per-key delta join into the store (the reference's
inner converge loop iteration, repo_manager.pony:92-93 ->
repo_pncount.pony:59-62, which runs one key at a time on one core).

Device path: a full anti-entropy sweep (every key carries a delta — the
north-star shape) runs through the DENSE serving kernel
(ops/pncount.join, the elementwise path the counter repos drain through
when a batch covers >=1/4 of the keyspace): each u32 plane is streamed
exactly once, no random-access gather/scatter. Deltas are pre-minted on
device (drains read deltas from memory, not an RNG) and varied per round
by a fused xor of the round counter. ROUNDS sweeps fuse into ONE dispatch
with `lax.scan`, so the per-dispatch cost is paid once per timed run.
Timing is synced by a 1-element readback and reported as the MEDIAN of
TIMED_RUNS timed executions.

The rate is per CHIP, so it is only printed from one: the JSON names
platform, device_kind and device count as jax reports them, and a backend
that is not a TPU exits non-zero instead (no fallback to the CPU).
Everything else behind --all/--full/--config/--smoke is a builder-side
CPU harness (serving nodes are spawned pinned to the CPU; in-process
kernels run on whatever backend the parent has) — none of it is a chip
measurement, and ROADMAP S0 replaces it.

CPU baselines: the SAME dense elementwise join in vectorised numpy
(median-of-N) — a far stronger baseline than the reference's per-key Pony
map loop. Every config reports a real vs_baseline (round-1 review flagged
the zeros).

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline"}.
"""

from __future__ import annotations

import json
import statistics
import time

import numpy as np

K = 1_000_000
R = 64
ROUNDS = 64
TIMED_RUNS = 3
CPU_RUNS = 5


def _median_rate(run_once, n=TIMED_RUNS) -> float:
    """run_once() -> (work_items, seconds); returns median items/sec."""
    rates = []
    for _ in range(n):
        work, dt = run_once()
        rates.append(work / dt)
    return statistics.median(rates)


def bench_device() -> float:
    import jax
    import jax.numpy as jnp

    from jylis_tpu.ops import pncount

    @jax.jit
    def sweep(state, d):
        def body(st, i):
            # vary the delta values each round with a fused xor of the
            # round counter — no extra HBM traffic, different lattice
            # values every round
            dd = pncount.PNCountState(
                d.p_hi ^ i, d.p_lo, d.n_hi ^ i, d.n_lo
            )
            return pncount.join(st, dd), None

        state, _ = jax.lax.scan(
            body, state, jnp.arange(ROUNDS, dtype=jnp.uint32)
        )
        return state

    def bits(j):
        return jax.random.bits(jax.random.key(j), (K, R), jnp.uint32)

    state = pncount.init(K, R)
    deltas = pncount.PNCountState(bits(0), bits(1), bits(2), bits(3))
    s1 = sweep(state, deltas)  # warmup compile + execute
    _ = np.asarray(jax.device_get(s1.p_hi.ravel()[0:1]))

    def once():
        t0 = time.perf_counter()
        s = sweep(state, deltas)
        _ = np.asarray(jax.device_get(s.p_hi.ravel()[0:1]))  # hard sync
        return K * ROUNDS, time.perf_counter() - t0

    return _median_rate(once)


def bench_cpu() -> float:
    rng = np.random.default_rng(0)
    p = np.zeros((K, R), np.uint64)
    n = np.zeros((K, R), np.uint64)
    dp = rng.integers(0, 1 << 63, (K, R), dtype=np.uint64)
    dn = rng.integers(0, 1 << 63, (K, R), dtype=np.uint64)

    def once():
        t0 = time.perf_counter()
        np.maximum(p, dp, out=p)  # the same dense elementwise join
        np.maximum(n, dn, out=n)
        return K, time.perf_counter() - t0

    once()  # touch pages
    return _median_rate(once, CPU_RUNS)


# ---- additional BASELINE.json configs (run with --config NAME / --all) -----


def config_gcount_smoke() -> dict:
    """Config 1: GCOUNT single-key INC/GET smoke, one node
    (repo_gcount.pony) — measured through the node's REAL serving
    surface: pipelined RESP over a loopback socket, parse + apply +
    reply. With a toolchain present the whole burst runs in the native
    serving engine (native/serve_engine.cpp) in one FFI call per read.
    Baseline: the reference's per-command work (data + delta-state map
    updates, value sum) as a bare Python dict loop.

    The extra `engine_only` field is the RECORDED roofline breakdown
    (round-4 verdict weak item 2): the identical burst applied straight
    through engine.scan_apply with no socket, so value/engine_only is
    the measured fraction of serving time the kernel socket path costs —
    the remaining "gap" to the baseline is protocol the dict loop never
    pays, not recoverable serving time."""
    import asyncio

    from jylis_tpu.models.database import Database
    from jylis_tpu.ops.hostref import GCounter
    from jylis_tpu.server.server import Server
    from jylis_tpu.utils.config import Config
    from jylis_tpu.utils.log import Log

    n = 5000  # commands per pipelined burst (half INC, half GET)
    payload = b"GCOUNT INC k 1\r\nGCOUNT GET k\r\n" * (n // 2)

    def engine_only_rate() -> float:
        """The same burst, engine table work + reply bytes only."""
        from jylis_tpu.native.engine import make_engine

        eng = make_engine()
        if eng is None:
            return 0.0
        buf = bytearray(payload)
        rates = []
        for _ in range(TIMED_RUNS):
            t0 = time.perf_counter()
            done = 0
            while done < len(payload):
                rc, consumed, _replies, _unh, _ch = eng.scan_apply(buf)
                del buf[:consumed]
                done += consumed
                assert rc in (0, 2), rc
            buf = bytearray(payload)
            rates.append(n / (time.perf_counter() - t0))
        return statistics.median(rates)

    async def measure():
        cfg = Config()
        cfg.port = "0"
        cfg.log = Log.create_none()
        db = Database(identity=1)
        server = Server(cfg, db)
        await server.start()
        try:
            reader, writer = await asyncio.open_connection(
                "127.0.0.1", server.port
            )

            async def burst():
                writer.write(payload)
                await writer.drain()
                got = 0
                while got < n:  # one \r\n per reply (+OK / :N)
                    chunk = await reader.read(1 << 20)
                    got += chunk.count(b"\r\n")

            await burst()  # warmup (jit-free path, but primes buffers)
            rates = []
            for _ in range(TIMED_RUNS):
                t0 = time.perf_counter()
                await burst()
                rates.append(n / (time.perf_counter() - t0))
            writer.close()
            return statistics.median(rates)
        finally:
            await server.dispose()

    dev = asyncio.run(measure())

    data: dict[bytes, GCounter] = {}
    deltas: dict[bytes, GCounter] = {}

    def cpu_once():
        t0 = time.perf_counter()
        for _ in range(n):
            # the reference INC applies to the data CRDT and the per-key
            # delta accumulator (repo_gcount.pony:57-60); GET sums the map
            data.setdefault(b"k", GCounter()).increment(1, 1)
            deltas.setdefault(b"k", GCounter()).increment(1, 1)
            data[b"k"].value()
        return 2 * n, time.perf_counter() - t0

    cpu = _median_rate(cpu_once, CPU_RUNS)
    engine_only = engine_only_rate()
    out = {
        "metric": "GCOUNT INC+GET smoke, one node (config 1)",
        "value": round(dev, 1),
        "unit": "commands/sec",
        "vs_baseline": round(dev / cpu, 2),
    }
    if engine_only:
        out["engine_only"] = round(engine_only, 1)
        out["socket_cost_frac"] = round(1 - dev / engine_only, 2)
    return out


class RespReplyCounter:
    """Incremental RESP *reply*-stream parser: counts complete top-level
    replies — simple/error/integer lines, bulk strings (incl. null) and
    arbitrarily nested arrays each count ONCE. The pre-round-6 harness
    counted line terminators, which over-counts exactly the structured
    read replies (TREG GET, TLOG GET, UJSON GET) and so silently
    excluded those command classes from every headline mix; this parser
    is what lets the `concurrent` record include them honestly."""

    def __init__(self):
        self._buf = bytearray()
        self._stack: list[int] = []  # open arrays' remaining elements
        self._done = 0

    @property
    def done(self) -> int:
        return self._done

    def feed(self, data: bytes) -> int:
        """Consume bytes; returns cumulative complete replies."""
        self._buf += data
        while self._step():
            pass
        return self._done

    def _complete(self) -> None:
        while self._stack:
            self._stack[-1] -= 1
            if self._stack[-1]:
                return
            self._stack.pop()
        self._done += 1

    def _step(self) -> bool:
        buf = self._buf
        eol = buf.find(b"\r\n")
        if eol < 0:
            return False
        t, body = buf[0:1], bytes(buf[1:eol])
        if t in (b"+", b"-", b":"):
            del buf[: eol + 2]
            self._complete()
            return True
        if t == b"$":
            n = int(body)
            if n < 0:  # null bulk
                del buf[: eol + 2]
                self._complete()
                return True
            end = eol + 2 + n + 2
            if len(buf) < end:
                return False
            del buf[:end]
            self._complete()
            return True
        if t == b"*":
            n = int(body)
            del buf[: eol + 2]
            if n <= 0:
                self._complete()
            else:
                self._stack.append(n)
            return True
        raise ValueError(f"bad RESP reply type byte {t!r}")


# >max-args command: trips the engine's rc -2, so server/server.py
# demote() moves the connection to the Python dispatch path for its
# remaining lifetime (the Python repo ignores the extra args and still
# replies :N — one reply, same as native)
def _demoter_cmd(i: int) -> bytes:
    return b"GCOUNT GET g%d " % i + b" ".join([b"x"] * 1100)


def _mix_burst(i: int, reps: int, demote: bool = False) -> tuple[bytes, int]:
    """One client's pipelined burst: all five data types, writes AND the
    structured reads — TREG GET, TLOG GET, UJSON GET and UJSON SET
    included (no excluded command class). The burst head re-INSerts the
    UJSON read subtree once, so the first UJSON GET of every burst
    re-renders (and re-memoises) through the Python path — the honest
    steady-state mix, not a never-invalidated best case."""
    cmds = [_demoter_cmd(i)] if demote else []
    cmds.append(b"UJSON INS u%d profile %d" % (i, i))
    for j in range(reps):
        cmds += [
            b"GCOUNT INC g%d 1" % i,
            b"GCOUNT GET g%d" % i,
            b"PNCOUNT INC p%d 2" % i,
            b"PNCOUNT DEC p%d 1" % i,
            b"PNCOUNT GET p%d" % i,
            b"TREG SET t%d v%d %d" % (i, j, j + 1),
            b"TREG GET t%d" % i,
            b"TLOG INS l%d x %d" % (i, j + 1),
            b"TLOG SIZE l%d" % i,
            b"TLOG GET l%d 4" % i,
            b"UJSON INS u%d tags %d" % (i, j),
            b"UJSON SET u%d meta %d" % (i, j),
            b"UJSON GET u%d profile" % i,
        ]
    return b"\r\n".join(cmds) + b"\r\n", len(cmds)


def _concurrent_rate(
    n_clients: int,
    sink: bool = False,
    journal_dir: str | None = None,
    reps: int = 60,
    bursts: int = 4,
    demote: bool = False,
    obs: bool = True,
) -> tuple[float, float]:
    """Whole-node (commands/sec, fallback_frac) with n_clients pipelined
    connections issuing the all-commands mix (_mix_burst, per-client
    keyspaces), replies counted by a real RESP parser. fallback_frac is
    the measured fraction of commands the Python dispatch path served
    during the timed phase (Database.serving_totals — the same split
    SYSTEM METRICS reports live). ``sink`` registers a discard delta
    sink (as the cluster heartbeat does in production), which arms the
    proactive flush path; ``journal_dir`` additionally attaches a delta
    write-ahead journal there — the sink-vs-sink+journal ratio isolates
    the journal's append+fsync cost on the serving path. ``demote``
    prepends one demoting command per connection (_demoter_cmd).
    ``obs=False`` disables the node's MetricsRegistry, which makes every
    observability seam skip its clock reads AND bucket increments — the
    with-vs-without ratio is the recorded `obs_cost_frac` (the full cost
    of always-on histograms, perf_counter calls included)."""
    import asyncio
    import os

    from jylis_tpu.models.database import Database
    from jylis_tpu.server.server import Server
    from jylis_tpu.utils.config import Config
    from jylis_tpu.utils.log import Log

    async def measure() -> tuple[float, float]:
        cfg = Config()
        cfg.port = "0"
        cfg.log = Log.create_none()
        db = Database(identity=1)
        if not obs:
            db.metrics.enabled = False
        journal = None
        if journal_dir is not None:
            from jylis_tpu.journal import Journal

            journal = Journal(
                os.path.join(journal_dir, "journal.jylis"),
                fsync="interval",
                registry=db.metrics,
            )
            journal.open()
            db.set_journal(journal)
        if sink:
            db.flush_deltas(lambda deltas: None)
        server = Server(cfg, db)
        await server.start()
        try:
            payloads = [_mix_burst(i, reps, demote) for i in range(n_clients)]

            async def client(i: int, timed: bool) -> int:
                payload, n_replies = payloads[i]
                reader, writer = await asyncio.open_connection(
                    "127.0.0.1", server.port
                )
                try:
                    rounds = bursts if timed else 1
                    for _ in range(rounds):
                        writer.write(payload)
                        await writer.drain()
                        counter = RespReplyCounter()
                        got = 0
                        while got < n_replies:
                            chunk = await reader.read(1 << 20)
                            if not chunk:
                                raise ConnectionError("server closed")
                            got = counter.feed(chunk)
                        # a real parser can (and must) assert exactness:
                        # over-counting is how reads got excluded before
                        assert got == n_replies, (got, n_replies)
                    return n_replies * rounds
                finally:
                    writer.close()

            # warmup: prime per-key state, the UJSON render memos, and
            # both serving paths
            await asyncio.gather(*(client(i, False) for i in range(n_clients)))
            before = db.serving_totals()
            t0 = time.perf_counter()
            done = await asyncio.gather(
                *(client(i, True) for i in range(n_clients))
            )
            dt = time.perf_counter() - t0
            after = db.serving_totals()
            native = after["native_cmds"] - before["native_cmds"]
            demoted = after["demoted_cmds"] - before["demoted_cmds"]
            frac = demoted / max(native + demoted, 1)
            return sum(done) / dt, frac
        finally:
            await server.dispose()
            if journal is not None:
                journal.close()

    return asyncio.run(measure())


CONN_SWEEP = (1, 4, 16, 64, 256)


def config_concurrent() -> dict:
    """Config 1b (round-4 verdict item 2; mix and counting re-recorded
    for round 6; connection sweep for the multi-lane round): whole-node
    serving throughput under CONCURRENT connections — a FULL sweep over
    1/4/16/64/256 pipelined clients issuing a mixed all-five-types
    workload with NO excluded command class (writes plus TREG GET, TLOG
    GET, UJSON GET and UJSON SET) against per-client keys, through the
    real RESP server, replies counted by a real RESP reply parser
    (RespReplyCounter — the old line-terminator count both mis-timed
    and excluded the structured reads). Recording the whole curve (not
    the old 1/16/64 three-point) makes lane-scaling shape a committed
    artifact: the single-loop node's flat curve — and any non-monotonic
    kink in it — is visible per point as `sweep`/`vs_one_conn`. The
    recorded fallback_frac is the measured fraction of the mix the
    Python dispatch path served (the headline is an all-commands native
    number only while it stays ≤ 0.05). Baseline: the same command mix
    as bare Python dict/list loops (the reference's per-command work),
    single-threaded — a baseline that pays no parsing, sockets, or
    replies."""
    from jylis_tpu.ops.hostref import GCounter, PNCounter

    import tempfile

    sweep: dict[str, float] = {}
    fallback = 0.0
    for n in CONN_SWEEP:
        r, fb = _concurrent_rate(n)
        sweep[str(n)] = round(r, 1)
        if n == 64:
            fallback = fb
    r1, r64 = sweep["1"], sweep["64"]
    # journal append overhead (docs/durability.md): same 64-conn run with
    # the delta sink registered — as the cluster heartbeat does on every
    # real node — with vs without a journal attached (fsync=interval).
    # Interleaved median-of-3 pairs: the ratio is what matters and
    # single-pass whole-node rates are noisy
    bases, withjs = [], []
    for _ in range(3):
        bases.append(_concurrent_rate(64, sink=True)[0])
        with tempfile.TemporaryDirectory() as td:
            withjs.append(_concurrent_rate(64, sink=True, journal_dir=td)[0])
    base = statistics.median(bases)
    withj = statistics.median(withjs)

    # always-on observability cost (obs/): the same 64-conn run with the
    # registry armed (the shipped default — histograms on every seam)
    # vs disabled (seams skip clock reads AND increments). Interleaved
    # PAIRS, ratio per pair, median of ratios: whole-node rates drift
    # run to run, and the paired ratio cancels that drift where two
    # independent medians would not.
    obs_ratios = []
    for _ in range(3):
        on = _concurrent_rate(64)[0]
        off = _concurrent_rate(64, obs=False)[0]
        obs_ratios.append(on / off)
    obs_cost = max(0.0, 1.0 - statistics.median(obs_ratios))

    # baseline: per-command reference work, no server — one dict/list op
    # per command of the mix (reads are lookups/slices, generous to the
    # baseline: the real TLOG GET renders a sorted merged view)
    n = 5000
    g: dict[bytes, GCounter] = {}
    p: dict[bytes, PNCounter] = {}
    t: dict[bytes, tuple] = {}
    tl: dict[bytes, list] = {}
    u: dict[bytes, set] = {}
    u2: dict[bytes, tuple] = {}

    def cpu_once():
        t0 = time.perf_counter()
        for j in range(n):
            g.setdefault(b"k", GCounter()).increment(1, 1)
            g[b"k"].value()
            p.setdefault(b"k", PNCounter()).increment(1, 2)
            p[b"k"].decrement(1, 1)
            p[b"k"].value()
            t[b"k"] = (b"v%d" % j, j)
            t.get(b"k")
            tl.setdefault(b"k", []).append((b"x", j))
            len(tl[b"k"])
            tl[b"k"][-4:]
            u.setdefault(b"k", set()).add(j)
            u2[b"k"] = (b"meta", j)
            u.get(b"k")
        return 13 * n, time.perf_counter() - t0

    cpu = _median_rate(cpu_once, CPU_RUNS)
    return {
        "metric": "mixed-type serving, 64 concurrent connections (config 1b)",
        "value": round(r64, 1),
        "unit": "commands/sec",
        "vs_baseline": round(r64 / cpu, 2),
        "sweep": sweep,
        "vs_one_conn_sweep": {
            n: round(r / r1, 2) for n, r in sweep.items() if n != "1"
        },
        "vs_one_conn": round(r64 / r1, 2),
        "fallback_frac": round(fallback, 4),
        "journal_cost_frac": round(max(0.0, 1 - withj / base), 2),
        "obs_cost_frac": round(obs_cost, 3),
    }


# ---- multi-lane serving (config concurrent-sharded) ------------------------

_SHARDED_SPAWN = (
    "import os\n"
    "os.environ.setdefault('JAX_PLATFORMS', 'cpu')\n"
    "import sys\n"
    "from jylis_tpu.main import main\n"
    "main(sys.argv[1:])\n"
)


def _free_port() -> int:
    from jylis_tpu.utils.net import free_port

    return free_port()


def _spawn_sharded_node(lanes: int):
    """A REAL node process (supervisor + SO_REUSEPORT lane workers for
    lanes > 1; the ordinary single process for lanes == 1) pinned to
    the CPU platform — the sharded config measures the host serving
    path, and N lane processes cannot share one accelerator anyway
    (docs/operations.md). Returns (proc, port)."""
    import os
    import socket
    import subprocess
    import sys

    port = _free_port()
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.Popen(
        [
            sys.executable, "-c", _SHARDED_SPAWN,
            "--lanes", str(lanes), "--port", str(port),
            "--addr", f"127.0.0.1:{_free_port()}:bench-sharded",
            "--log-level", "warn", "-T", "0.5",
        ],
        cwd=os.path.dirname(os.path.abspath(__file__)),
        env=env,
        stdout=subprocess.DEVNULL,  # the logo must not pollute --smoke JSON
    )
    deadline = time.time() + 180
    while time.time() < deadline:
        if proc.poll() is not None:
            raise RuntimeError("bench node died during startup")
        try:
            s = socket.create_connection(("127.0.0.1", port), timeout=2)
            s.sendall(b"GCOUNT GET boot\r\n")
            s.settimeout(2)
            ok = s.recv(64).startswith(b":")
            s.close()
            if ok:
                return proc, port
        except OSError:
            time.sleep(0.3)
    proc.kill()
    raise RuntimeError("bench node never came up")


def _sharded_client_worker(port, client_ids, reps, bursts, barrier, q):
    """One CLIENT process (multiprocessing spawn target): its share of
    the pipelined connections, warmed up, then a barrier-synchronised
    timed phase. The single-process harness behind `concurrent` is
    client-bound once the server spans cores, so the sharded config's
    load generator must span cores too. Reports (replies, wall_start,
    wall_end) — wall clock, because perf_counter is per-process."""
    import asyncio

    async def run():
        payloads = {i: _mix_burst(i, reps) for i in client_ids}
        conns = {}
        for i in client_ids:
            conns[i] = await asyncio.open_connection("127.0.0.1", port)

        async def burst(i, rounds):
            payload, n_replies = payloads[i]
            reader, writer = conns[i]
            done = 0
            for _ in range(rounds):
                writer.write(payload)
                await writer.drain()
                counter = RespReplyCounter()
                got = 0
                while got < n_replies:
                    chunk = await reader.read(1 << 20)
                    if not chunk:
                        raise ConnectionError("server closed")
                    got = counter.feed(chunk)
                assert got == n_replies, (got, n_replies)
                done += got
            return done

        await asyncio.gather(*(burst(i, 1) for i in client_ids))  # warmup
        barrier.wait()
        t0 = time.time()
        done = await asyncio.gather(*(burst(i, bursts) for i in client_ids))
        t1 = time.time()
        for _, writer in conns.values():
            writer.close()
        return sum(done), t0, t1

    q.put(asyncio.run(run()))


def _sharded_rate(
    port: int, conns: int, reps: int = 60, bursts: int = 8,
    workers: int | None = None,
) -> float:
    """Aggregate commands/sec against an already-running node at
    `port`, with the connections spread over multiple client
    PROCESSES. Rate = total replies / the union wall-clock window."""
    import multiprocessing as mp

    import os

    # one client process per SPARE core half, never more than the
    # connection count: oversubscribing a small host with client
    # processes measures scheduler thrash, not the node (a 4-worker
    # load generator on a 2-core box collapsed the 64-conn point 6×
    # below the 1-conn point)
    workers = workers or max(1, min(conns, 4, (os.cpu_count() or 2) // 2))
    ctx = mp.get_context("spawn")
    barrier = ctx.Barrier(workers)
    q = ctx.Queue()
    ids = [list(range(conns))[w::workers] for w in range(workers)]
    procs = [
        ctx.Process(
            target=_sharded_client_worker,
            args=(port, ids[w], reps, bursts, barrier, q),
        )
        for w in range(workers)
    ]
    for p in procs:
        p.start()
    results = [q.get(timeout=600) for _ in range(workers)]
    for p in procs:
        p.join(timeout=60)
    total = sum(r[0] for r in results)
    window = max(r[2] for r in results) - min(r[1] for r in results)
    return total / window


def _stop_sharded_node(proc) -> None:
    import subprocess

    proc.terminate()
    try:
        proc.wait(timeout=60)
    except subprocess.TimeoutExpired:
        proc.kill()
        proc.wait(timeout=10)


def config_concurrent_sharded() -> dict:
    """Multi-lane serving, recorded (ROADMAP item 1): the SAME
    all-commands mix as `concurrent`, against a REAL spawned node —
    `--lanes N` (one lane per host core, ≥ 2) vs `--lanes 1` on the
    same harness — with the load generator itself spread over client
    processes (the in-process `concurrent` harness shares one loop
    between server and clients, which is exactly the single-lane
    ceiling this config exists to break). Records the full connection
    sweep, the lanes-vs-single-lane ratio (`vs_baseline`), and the
    non-pipelined TREG GET p99 at 64 connections for both, plus
    `host_cores` — on a small host the kernel, the lanes, AND the
    clients contend for the same cores, so the scaling headroom is
    bounded by the machine and the record says so."""
    import os

    lanes = max(2, min(os.cpu_count() or 2, 8))
    out: dict = {
        "metric": f"mixed-type serving, {lanes}-lane node vs single-lane "
        "(concurrent-sharded)",
        "unit": "commands/sec",
        "lanes": lanes,
        "host_cores": os.cpu_count(),
        # the scaling question this config answers is only answerable
        # where there are cores to scale onto; the record says where it
        # was taken so a small-host ratio reads as a floor, not a verdict
        "note": "lanes, client processes, and kernel share host_cores; "
        "on few-core hosts the ratio is host-bound",
    }
    sweeps: dict[int, dict[str, float]] = {}
    p99s: dict[int, float] = {}
    for n_lanes in (lanes, 1):
        proc, port = _spawn_sharded_node(n_lanes)
        try:
            sweeps[n_lanes] = {
                str(c): round(
                    statistics.median(
                        _sharded_rate(port, c) for _ in range(3)
                    ),
                    1,
                )
                for c in (1, 4, 16, 64)
            }
            lat = _latency_once(64, rounds=40, port=port)
            p99s[n_lanes] = lat["treg_get"][1]
        finally:
            _stop_sharded_node(proc)
    sharded, single = sweeps[lanes], sweeps[1]
    out.update(
        value=sharded["64"],
        vs_baseline=round(sharded["64"] / single["64"], 2),
        sweep=sharded,
        vs_one_conn_sweep={
            c: round(r / sharded["1"], 2)
            for c, r in sharded.items() if c != "1"
        },
        single_lane_sweep=single,
        p99_us_treg_get_64=p99s[lanes],
        single_lane_p99_us_treg_get_64=p99s[1],
        p99_speedup_64=round(p99s[1] / p99s[lanes], 2),
    )
    return out


def config_serving_demotion() -> dict:
    """The demotion cliff as a recorded number (round-5 verdict item 6):
    the same 8-connection all-commands burst twice — once fully
    native-settleable, once with one demoting command per connection at
    the burst head (a >max-args command that trips the engine's rc -2 →
    server/server.py demote()). Demotion is sticky for the connection's
    lifetime, so inserting the demoter once or once-per-N is equivalent:
    everything after the first serves from the Python dispatch path, and
    the demoted rate IS that path's rate. vs_baseline is native/demoted
    — the per-connection cliff a demoting command class pays."""
    native, _ = _concurrent_rate(8)
    demoted, dem_frac = _concurrent_rate(8, demote=True)
    return {
        "metric": "native vs demoted serving, 8 connections (demotion cliff)",
        "value": round(native, 1),
        "unit": "commands/sec",
        "vs_baseline": round(native / demoted, 2),
        "demoted": round(demoted, 1),
        "demoted_fallback_frac": round(dem_frac, 4),
    }


# non-pipelined latency command classes (config_serving_latency); one
# %d per template = the per-client key suffix
_LAT_CLASSES = (
    ("gcount_inc", b"GCOUNT INC kg%d 1"),
    ("gcount_get", b"GCOUNT GET kg%d"),
    ("treg_set", b"TREG SET kt%d v 7"),
    ("treg_get", b"TREG GET kt%d"),
    ("tlog_ins", b"TLOG INS kl%d x 7"),
    ("tlog_get", b"TLOG GET kl%d 4"),
    ("ujson_ins", b"UJSON INS ku%d tags 1"),
    ("ujson_get", b"UJSON GET ku%d profile"),
)


def _latency_once(
    n_clients: int, rounds: int, port: int | None = None
) -> dict[str, tuple]:
    """{class: (p50_us, p99_us)} at n_clients concurrent NON-pipelined
    request/response connections: each client writes one command, waits
    for its complete reply (RespReplyCounter), and records the RTT —
    what an un-batched caller actually experiences, queuing included.
    With ``port`` the clients hit an already-running external node (the
    sharded config) instead of an in-process server."""
    import asyncio

    from jylis_tpu.models.database import Database
    from jylis_tpu.server.server import Server
    from jylis_tpu.utils.config import Config
    from jylis_tpu.utils.log import Log

    async def measure():
        server = None
        if port is None:
            cfg = Config()
            cfg.port = "0"
            cfg.log = Log.create_none()
            db = Database(identity=1)
            server = Server(cfg, db)
            await server.start()
        target = port if port is not None else server.port
        samples: dict[str, list[float]] = {n: [] for n, _ in _LAT_CLASSES}
        try:
            async def client(i: int) -> None:
                reader, writer = await asyncio.open_connection(
                    "127.0.0.1", target
                )
                try:
                    # prime per-key state and the UJSON render memo, then
                    # one untimed lap of every class (both paths warm)
                    primer = (
                        b"UJSON INS ku%d profile 1\r\n" % i
                        + b"UJSON GET ku%d profile\r\n" % i
                        + b"".join((t % i) + b"\r\n" for _, t in _LAT_CLASSES)
                    )
                    async def read_until(counter, want: int) -> None:
                        while counter.done < want:
                            chunk = await reader.read(1 << 16)
                            if not chunk:
                                raise ConnectionError("server closed")
                            counter.feed(chunk)

                    writer.write(primer)
                    await writer.drain()
                    await read_until(RespReplyCounter(), 2 + len(_LAT_CLASSES))
                    for _ in range(rounds):
                        for name, tpl in _LAT_CLASSES:
                            cmd = (tpl % i) + b"\r\n"
                            t0 = time.perf_counter()
                            writer.write(cmd)
                            await writer.drain()
                            await read_until(RespReplyCounter(), 1)
                            samples[name].append(time.perf_counter() - t0)
                finally:
                    writer.close()

            await asyncio.gather(*(client(i) for i in range(n_clients)))
        finally:
            if server is not None:
                await server.dispose()
        return samples

    samples = asyncio.run(measure())
    out = {}
    for name, xs in samples.items():
        xs.sort()
        p50 = xs[len(xs) // 2]
        p99 = xs[min(len(xs) - 1, int(len(xs) * 0.99))]
        out[name] = (round(p50 * 1e6, 1), round(p99 * 1e6, 1))
    return out


def config_serving_latency() -> dict:
    """Non-pipelined request/response latency (round-5 verdict item 2):
    p50/p99 per command class at 1/16/64 connections. The throughput
    configs measure pipelined bursts; this is the other axis — what one
    un-batched command costs end-to-end over a real socket, and how it
    degrades under connection concurrency (vs_baseline = TREG GET p99 at
    64 conns over p99 at 1 conn, the queuing factor)."""
    sweep = {str(n): _latency_once(n, rounds=150) for n in (1, 16, 64)}
    p50_64, p99_64 = sweep["64"]["treg_get"]
    p50_1, p99_1 = sweep["1"]["treg_get"]
    return {
        "metric": "non-pipelined latency per command class, 1/16/64 conns",
        "value": p99_64,
        "unit": "us p99 (TREG GET, 64 conns)",
        "vs_baseline": round(p99_64 / p99_1, 2),
        "p50_us_treg_get_1": p50_1,
        "p99_us_treg_get_1": p99_1,
        "p50_us_treg_get_64": p50_64,
        "p99_us_treg_get_64": p99_64,
        "latency_us": sweep,
    }


def config_pncount_100k() -> dict:
    """Config 2: PNCOUNT 100k keys, 8 replica columns, full-sweep converge
    (repo_pncount.pony) — the north-star dense kernel at the smaller shape,
    vs the same dense join in numpy."""
    import jax
    import jax.numpy as jnp

    from jylis_tpu.ops import pncount

    K2, R2, rounds = 100_000, 8, 2048

    @jax.jit
    def sweep(state, d):
        def body(st, i):
            dd = pncount.PNCountState(d.p_hi ^ i, d.p_lo, d.n_hi ^ i, d.n_lo)
            return pncount.join(st, dd), None

        state, _ = jax.lax.scan(body, state, jnp.arange(rounds, dtype=jnp.uint32))
        return state

    def bits(j):
        return jax.random.bits(jax.random.key(j), (K2, R2), jnp.uint32)

    state = pncount.init(K2, R2)
    deltas = pncount.PNCountState(bits(0), bits(1), bits(2), bits(3))
    s1 = sweep(state, deltas)
    _ = np.asarray(jax.device_get(s1.p_hi.ravel()[0:1]))

    def once():
        t0 = time.perf_counter()
        s = sweep(state, deltas)
        _ = np.asarray(jax.device_get(s.p_hi.ravel()[0:1]))
        return K2 * rounds, time.perf_counter() - t0

    dev = _median_rate(once)

    rng = np.random.default_rng(0)
    p = np.zeros((K2, R2), np.uint64)
    nn = np.zeros((K2, R2), np.uint64)
    dp = rng.integers(0, 1 << 63, (K2, R2), dtype=np.uint64)
    dn = rng.integers(0, 1 << 63, (K2, R2), dtype=np.uint64)

    def cpu_once():
        t0 = time.perf_counter()
        np.maximum(p, dp, out=p)
        np.maximum(nn, dn, out=nn)
        return K2, time.perf_counter() - t0

    cpu_once()
    cpu = _median_rate(cpu_once, CPU_RUNS)
    return {
        "metric": "PNCOUNT 100k-key x 8-replica converge (config 2)",
        "value": round(dev, 1),
        "unit": "merges/sec",
        "vs_baseline": round(dev / cpu, 2),
    }


def config_treg_1m() -> dict:
    """Config 3: TREG 1M-key random-timestamp SET merge (repo_treg.pony)
    through the dense LWW serving kernel, vs the same dense lexicographic
    take in numpy (5 planes both sides)."""
    import jax
    import jax.numpy as jnp

    from jylis_tpu.ops import treg

    K3, rounds = 1_000_000, 256

    # pre-generated base delta planes; each round perturbs them with cheap
    # elementwise mixes (XOR / multiply by odd constants) so every round
    # carries fresh contending deltas WITHOUT paying threefry RNG inside
    # the timed loop — the metric is merge throughput, and in serving,
    # deltas arrive from the network, they aren't generated on-chip
    def _bits(j):
        return jax.random.bits(jax.random.key(j), (K3,), jnp.uint32)

    base = tuple(_bits(c) for c in range(4))
    base_vid = jax.random.randint(jax.random.key(4), (K3,), 0, 1 << 30, jnp.int32)

    @jax.jit
    def sweep(state):
        def body(state, i):
            m1 = i * jnp.uint32(2654435761)  # Knuth odd-multiplier mixes
            m2 = i * jnp.uint32(0x9E3779B9)
            st, _tie = treg.converge_dense(
                state,
                base[0] ^ m1,
                base[1] + m2,
                base[2] ^ m2,
                base[3] + m1,
                (base_vid ^ jnp.int32(i)) & jnp.int32(0x3FFFFFFF),
            )
            return st, None

        state, _ = jax.lax.scan(body, state, jnp.arange(rounds, dtype=jnp.uint32))
        return state

    state = treg.init(K3)
    s1 = sweep(state)
    _ = np.asarray(jax.device_get(s1.ts_hi.ravel()[0:1]))

    def once():
        t0 = time.perf_counter()
        s = sweep(state)
        _ = np.asarray(jax.device_get(s.ts_hi.ravel()[0:1]))
        return K3 * rounds, time.perf_counter() - t0

    dev = _median_rate(once)

    # numpy dense LWW baseline: same (ts, rank) lexicographic take over the
    # same five planes (u64 ts/rank + vid)
    rng = np.random.default_rng(0)
    c_ts = np.zeros(K3, np.uint64)
    c_rank = np.zeros(K3, np.uint64)
    c_vid = np.full(K3, -1, np.int32)
    d_ts = rng.integers(0, 1 << 63, K3).astype(np.uint64)
    d_rank = rng.integers(0, 1 << 63, K3).astype(np.uint64)
    d_vid = rng.integers(0, 1 << 30, K3).astype(np.int32)

    def cpu_once():
        nonlocal c_ts, c_rank, c_vid
        t0 = time.perf_counter()
        take = (d_ts > c_ts) | ((d_ts == c_ts) & (d_rank > c_rank))
        c_ts = np.where(take, d_ts, c_ts)
        c_rank = np.where(take, d_rank, c_rank)
        c_vid = np.where(take, d_vid, c_vid)
        return K3, time.perf_counter() - t0

    cpu_once()
    cpu = _median_rate(cpu_once, CPU_RUNS)
    return {
        "metric": "TREG 1M-key LWW SET merge (config 3)",
        "value": round(dev, 1),
        "unit": "merges/sec",
        "vs_baseline": round(dev / cpu, 2),
    }


def config_tlog_trim() -> dict:
    """Config 4: TLOG 10k keys x 1k entries, merge + TRIM
    (repo_tlog.pony) — entries merged/sec through the segment-sort join,
    vs a vectorised numpy sort-merge-dedup-trim of the same workload."""
    import jax
    import jax.numpy as jnp

    from jylis_tpu.ops import tlog

    K4, L, chunk, rounds = 10_000, 1024, 128, 8
    ki = jnp.arange(K4, dtype=jnp.int32)
    counts = jnp.full((K4,), 512, jnp.int64)
    cut = jnp.zeros((K4,), jnp.uint64)

    # pre-minted base entries, varied per round with cheap elementwise
    # mixes (threefry inside the timed loop would measure RNG, not the
    # merge — deltas arrive from the network in serving)
    base_ts = jax.random.bits(jax.random.key(0), (K4, chunk), jnp.uint32)

    # all 8 merge rounds + the TRIM fuse into ONE dispatch (per-round
    # launches would measure dispatch overhead, not the segment-sort join)
    @jax.jit
    def run_device(state):
        def body(st, i):
            ts = (base_ts ^ (i * jnp.uint32(2654435761))).astype(
                jnp.uint64
            ) | jnp.uint64(1)
            vid = (ts & jnp.uint64(0x3FFFFFFF)).astype(jnp.int64)
            # dense path: the workload IS a full-keyspace anti-entropy
            # round, so delta rows align 1:1 with the keyspace
            st, _ovf = tlog.converge_batch(st, None, ts, vid, cut)
            return st, None

        # 8 x 128 = 1k entries per key, then TRIM every key to 512
        st, _ = jax.lax.scan(body, state, jnp.arange(rounds, dtype=jnp.uint32))
        return tlog.trim_batch(st, ki, counts)

    state = tlog.init(K4, L + chunk)
    s1 = run_device(state)  # compile before timing
    _ = np.asarray(jax.device_get(s1.length[0:1]))

    def once():
        t0 = time.perf_counter()
        s = run_device(state)
        _ = np.asarray(jax.device_get(s.length[0:1]))
        return K4 * chunk * rounds, time.perf_counter() - t0

    dev = _median_rate(once)

    # numpy baseline: same merge (sort desc + dedup) and final trim over a
    # (K4, n) buffer; ts/vid pack into one int64 sort key (bench data fits:
    # 32-bit ts, 31-bit vid; vid is ts-derived so ties dedup exactly)
    rng = np.random.default_rng(0)
    new_ts = (
        rng.integers(0, 1 << 32, (rounds, K4, chunk)).astype(np.uint64)
        | np.uint64(1)
    )
    new_vid = new_ts & np.uint64(0x7FFFFFFF)

    def cpu_once():
        t0 = time.perf_counter()
        buf = np.zeros((K4, 0), np.uint64)
        for i in range(rounds):
            packed = (new_ts[i] << np.uint64(31)) | new_vid[i]
            buf = np.concatenate([buf, packed], axis=1)
            buf = -np.sort(-buf, axis=1)  # desc
            dup = np.zeros_like(buf, dtype=bool)
            dup[:, 1:] = buf[:, 1:] == buf[:, :-1]
            # drop dups by pushing them to the tail (0 sorts last)
            buf = -np.sort(-(np.where(dup, np.uint64(0), buf)), axis=1)
        buf = buf[:, :512]  # TRIM every key to 512 entries
        return K4 * chunk * rounds, time.perf_counter() - t0

    cpu = _median_rate(cpu_once, 3)
    return {
        "metric": "TLOG 10k-key x 1k-entry merge+TRIM (config 4)",
        "value": round(dev, 1),
        "unit": "entries/sec",
        "vs_baseline": round(dev / cpu, 2),
    }


def config_ujson_32() -> dict:
    """Config 5: UJSON concurrent field edits across 32 replicas
    (repo_ujson.pony) — field-edit merges/sec with full convergence
    checking, over a multi-ROUND anti-entropy stream. Device path
    (ops/ujson_resident): the 32 replica documents are admitted to the
    device-resident store ONCE (inside the timed region — it amortises
    across rounds, which is the point of residency), then every round
    encodes ONLY that round's deltas; the store buffers the rounds and
    coalesces them into ONE (R*D, W) broadcast fold at the read barrier
    (fold_in_broadcast's lazy batching, round-5 verdict item 5) — one
    device dispatch where round 4 paid one per round. The host
    baseline is the reference's
    loop shape (repo_ujson.pony:96-110): every replica converges every
    delta, every round. Round 3 re-encoded all 32 replica documents
    host->device EVERY round (the admitted bottleneck, VERDICT round 3);
    the resident store never touches them again after admission."""
    from jylis_tpu.ops.ujson_host import UJSON
    from jylis_tpu.ops.ujson_resident import ResidentStore

    n_rep, edits, rounds = 32, 40, 8

    def make_workload():
        replicas = [UJSON() for _ in range(n_rep)]
        streams = []
        for rnd in range(rounds):
            deltas = []
            for r, doc in enumerate(replicas):
                for e in range(edits):
                    d = UJSON()
                    doc.set_doc(
                        r, (f"field{e % 8}",), str(rnd * 100000 + r * 1000 + e),
                        delta=d,
                    )
                    deltas.append(d)
            streams.append(deltas)
        return [UJSON() for _ in range(n_rep)], streams

    def device_once():
        # serving shape: each round's deltas arrive as ONE PushDeltas
        # wire body; the native splitter yields lazy wire deltas that
        # fold into every resident replica row without ever becoming
        # Python documents
        from jylis_tpu.cluster import codec as ccodec
        from jylis_tpu.cluster.msg import MsgPushDeltas
        from jylis_tpu.ops.ujson_wire import split_push_ujson

        replicas, streams = make_workload()
        bodies = []
        for deltas in streams:
            body = ccodec._encode_oracle(
                MsgPushDeltas("UJSON", tuple((b"x", d) for d in deltas))
            )
            bodies.append(body[body.index(b"UJSON") + 5 :])
        t0 = time.perf_counter()
        store = ResidentStore(n_rep=n_rep)
        store.admit([(b"rep%02d" % i, r) for i, r in enumerate(replicas)])
        for body, deltas in zip(bodies, streams):
            split = split_push_ujson(body)
            # no native library: the object path is the honest fallback
            ds = [d for _, d in split] if split is not None else deltas
            store.fold_in_broadcast(ds)
        store.block()
        dt = time.perf_counter() - t0
        renders = {doc.render() for _, doc in store.dump()}
        assert len(renders) == 1, "replicas diverged"
        return n_rep * sum(len(s) for s in streams), dt

    def host_once():
        replicas, streams = make_workload()
        t0 = time.perf_counter()
        for deltas in streams:
            for doc in replicas:
                for d in deltas:
                    doc.converge(d)
        dt = time.perf_counter() - t0
        renders = {doc.render() for doc in replicas}
        assert len(renders) == 1, "replicas diverged"
        return n_rep * sum(len(s) for s in streams), dt

    device_once()  # compile warmup
    rate = _median_rate(device_once)
    host = _median_rate(host_once, CPU_RUNS)
    return {
        "metric": "UJSON 32-replica concurrent edits (config 5)",
        "value": round(rate, 1),
        "unit": "delta merges/sec",
        "vs_baseline": round(rate / host, 2),
    }


def config_ujson_multikey() -> dict:
    """Config 5b: multi-key UJSON anti-entropy with device-RESIDENT
    documents (ops/ujson_resident) — K keys receive a deep fan-in as a
    stream of ROUNDS drains. Every drain encodes only that round's
    deltas (O(new deltas)) and folds them into the resident rows in ONE
    dispatch; the accumulated documents are never re-encoded or
    host-walked. Baselines: the host loop (the reference's converge
    shape, repo_ujson.pony:96-110 — O(doc) per delta, so O(D^2) per key
    over the stream) and the round-3 non-resident shape (re-encode +
    fold_segments + decode + host-converge per round,
    `vs_reencode`). Results are verified against the host oracle
    outside the timed region."""
    from jylis_tpu.ops import ujson_device as dev
    from jylis_tpu.ops.ujson_host import UJSON
    from jylis_tpu.ops.ujson_resident import ResidentStore

    n_keys, fanin, n_rep, rounds = 64, 512, 8, 8

    def make_workload():
        # distinct INS values: the doc grows with the fan-in, so the host
        # loop's per-delta full-doc scan (ujson_host.converge) is O(D^2)
        # per key while the device delta encode stays O(D) — the shape
        # deep anti-entropy fan-ins actually have
        streams = []
        docs = [UJSON() for _ in range(n_keys)]
        for rnd in range(rounds):
            groups = []
            for k, doc in enumerate(docs):
                g = []
                for e in range(fanin):
                    d = UJSON()
                    doc.ins(
                        100 + (e % n_rep), ("tags",),
                        str(k * 100000 + rnd * 1000 + e), delta=d,
                    )
                    g.append(d)
                groups.append(g)
            streams.append(groups)
        return streams

    keys = [b"doc%03d" % k for k in range(n_keys)]
    total = n_keys * fanin * rounds

    def verify_store(store, streams):
        docs = store.read_many(keys)  # one batched pull, not one per key
        for k, got in enumerate(docs):
            want = UJSON()
            for groups in streams:
                for d in groups[k]:
                    want.converge(d)
            assert got.render() == want.render(), "fold diverged from oracle"

    def wire_bodies(streams):
        """Each round as the PushDeltas body a peer would send (one
        (key, delta) pair per delta, the anti-entropy wire shape)."""
        from jylis_tpu.cluster import codec
        from jylis_tpu.cluster.msg import MsgPushDeltas

        bodies = []
        for groups in streams:
            batch = tuple(
                (keys[k], d) for k, g in enumerate(groups) for d in g
            )
            body = codec._encode_oracle(MsgPushDeltas("UJSON", batch))
            bodies.append(body[body.index(b"UJSON") + 5 :])
        return bodies

    def resident_once():
        # the serving shape: rounds arrive as WIRE bytes; each round is
        # split natively into lazy per-key deltas (the receive path) and
        # folded into the resident rows without ever building Python
        # document objects
        from jylis_tpu.ops.ujson_wire import split_push_ujson

        streams = make_workload()
        bodies = wire_bodies(streams)
        t0 = time.perf_counter()
        store = ResidentStore(n_rep=n_rep)
        store.admit([(key, UJSON()) for key in keys])
        for body, groups in zip(bodies, streams):
            split = split_push_ujson(body)
            if split is not None:
                pend = {}
                for key, d in split:
                    pend.setdefault(key, []).append(d)
            else:  # no native library: the object path is the fallback
                pend = dict(zip(keys, groups))
            store.fold_in(pend)
        store.block()
        dt = time.perf_counter() - t0
        verify_store(store, streams)
        return total, dt

    class _Pay:
        def __init__(self):
            self.ids = {}
            self.rev = []

        def __call__(self, path, token):
            key = (path, token)
            if key not in self.ids:
                self.ids[key] = len(self.rev)
                self.rev.append(key)
            return self.ids[key]

        def lookup(self, pid):
            return self.rev[pid]

    def reencode_once():
        # the round-3 drain shape: per round, encode the round's deltas,
        # fold them on device, pull the folded deltas back and
        # host-converge them into the accumulated host docs
        streams = make_workload()
        t0 = time.perf_counter()
        docs = [UJSON() for _ in range(n_keys)]
        pay = _Pay()
        rid_cols: dict[int, int] = {}
        for groups in streams:
            batch, shift = dev.encode_doc_groups_auto(
                groups, rid_cols, pay, n_rep=n_rep
            )
            folded = dev.fold_segments(batch, shift=shift)
            cols_rid = {c: r for r, c in rid_cols.items()}
            for doc, delta in zip(
                docs, dev.decode_batch(folded, cols_rid, pay.lookup, shift=shift)
            ):
                doc.converge(delta)
        dt = time.perf_counter() - t0
        return total, dt

    def host_once():
        streams = make_workload()
        t0 = time.perf_counter()
        docs = [UJSON() for _ in range(n_keys)]
        for groups in streams:
            for doc, g in zip(docs, groups):
                for d in g:
                    doc.converge(d)
        dt = time.perf_counter() - t0
        return total, dt

    resident_once()  # compile warmup
    rate = _median_rate(resident_once)
    reenc = _median_rate(reencode_once, 2)  # ~15s/run, deterministic
    # the host loop is ~80s/run (O(doc) per delta over a 4096-deep
    # fan-in is the whole point) and deterministic; two runs suffice
    host = _median_rate(host_once, 2)
    return {
        "metric": "UJSON 64-key x 8x512-delta resident fan-in (config 5b)",
        "value": round(rate, 1),
        "unit": "delta merges/sec",
        "vs_baseline": round(rate / host, 2),
        "vs_reencode": round(rate / reenc, 2),
    }


def config_codec_native() -> dict:
    """Native cluster codec (native/cluster_codec.cpp) vs the Python
    oracle on the MsgPushDeltas hot path: encode+decode of a PNCOUNT
    anti-entropy batch (5k keys x 4 replica entries per polarity), the
    wire work every heartbeat broadcast/converge performs. Round 5:
    encode ships spans in dict order (the C emitter sorts by rid on the
    wire) and decode banks LazyU64Map slices — the dicts materialise at
    the consumer (converge/equality), the ops/ujson_wire pattern."""
    from jylis_tpu.cluster import codec
    from jylis_tpu.cluster.msg import MsgPushDeltas
    from jylis_tpu.native import codec as ncodec
    from jylis_tpu.native import lib

    n_keys, n_rids = 5000, 4
    batch = tuple(
        (
            b"key:%08d" % k,
            (
                {r: (k * 7 + r) % (1 << 40) for r in range(n_rids)},
                {r: (k * 3 + r) % (1 << 40) for r in range(n_rids)},
            ),
        )
        for k in range(n_keys)
    )
    msg = MsgPushDeltas("PNCOUNT", batch)
    body = codec._encode_oracle(msg)

    def native_once():
        t0 = time.perf_counter()
        out = ncodec.encode_push(msg)
        got = ncodec.decode_push(body)
        dt = time.perf_counter() - t0
        assert out == body and got == msg
        return n_keys, dt

    def oracle_once():
        t0 = time.perf_counter()
        out = codec._encode_oracle(msg)
        got = codec._decode_oracle(body)
        dt = time.perf_counter() - t0
        assert out == body and got == msg
        return n_keys, dt

    oracle = _median_rate(oracle_once, CPU_RUNS)
    if lib() is None:
        return {
            "metric": "cluster codec PushDeltas encode+decode (native)",
            "value": round(oracle, 1),
            "unit": "keys/sec",
            "vs_baseline": 1.0,
        }
    native = _median_rate(native_once, CPU_RUNS)
    return {
        "metric": "cluster codec PushDeltas encode+decode (native)",
        "value": round(native, 1),
        "unit": "keys/sec",
        "vs_baseline": round(native / oracle, 2),
    }


def _sync_divergence(n_keys: int, divergent_buckets: int) -> dict:
    """Measure one rejoin's wire bytes BOTH ways through the real serve
    paths: the legacy whole-state dump (every frame `_data_frames`
    would ship) vs the schema-v8 range repair (the full MsgSyncRequest
    -> MsgDigestTree -> budgeted MsgRangeRequest/MsgPushDeltas/
    MsgSyncDone conversation, every frame length summed). The client
    store diverges on every key of `divergent_buckets` contiguous
    digest-tree buckets (~bucket_count/256 of the keyspace): the
    post-partition shape range repair is built for — divergence
    measured and pulled at RANGE granularity. Sub-bucket-uniform
    divergence degrades toward the dump (every bucket dirty); that
    granularity bound is documented in docs/replication.md, and the
    recorded config states its divergence layout beside the ratio.
    The conversation is verified, not trusted: the client converges
    every measured frame and must digest-match the server at the end."""
    import asyncio

    from jylis_tpu.cluster import codec as ccodec
    from jylis_tpu.cluster.cluster import Cluster
    from jylis_tpu.cluster.msg import (
        MsgDigestTree,
        MsgRangeRequest,
        MsgSyncDone,
        MsgSyncRequest,
    )
    from jylis_tpu.models.database import Database, sync_bucket
    from jylis_tpu.utils.address import Address
    from jylis_tpu.utils.config import Config
    from jylis_tpu.utils.log import Log

    def mk_cluster(name: str, db: Database) -> Cluster:
        cfg = Config()
        cfg.addr = Address("127.0.0.1", "0", name)
        cfg.log = Log.create_none()
        return Cluster(cfg, db, register_system=False)

    server = Database(identity=1)
    client = Database(identity=2)
    srepo = server.manager("PNCOUNT").repo
    crepo = client.manager("PNCOUNT").repo
    dirty = set(range(divergent_buckets))
    n_divergent = 0
    for i in range(n_keys):
        key = b"sd%07d" % i
        delta = ({2: i % 97 + 1}, {3: i % 13})
        srepo.converge(key, delta)
        crepo.converge(key, delta)
        if sync_bucket(key) in dirty:
            # the partition-window write the client missed
            srepo.converge(key, ({4: i % 31 + 2}, {}))
            n_divergent += 1
    sc = mk_cluster("sd-server", server)
    cc = mk_cluster("sd-client", client)

    async def measure():
        full_bytes = 0
        async for fr in sc._data_frames("PNCOUNT"):
            full_bytes += len(fr)

        # the range conversation, frame for frame
        range_bytes = 0
        digests = await client.sync_type_digests_async()
        range_bytes += len(cc._wire(ccodec.encode(MsgSyncRequest(digests))))
        tree = await server.sync_tree_async("PNCOUNT")
        range_bytes += len(
            sc._wire(ccodec.encode(MsgDigestTree("PNCOUNT", tree)))
        )
        mine = dict(await client.sync_tree_async("PNCOUNT"))
        theirs = dict(tree)
        divergent = sorted(
            b for b in set(mine) | set(theirs)
            if mine.get(b) != theirs.get(b)
        )
        budget = cc._range_budget
        for start in range(0, len(divergent), budget):
            chunk = tuple(divergent[start : start + budget])
            range_bytes += len(
                cc._wire(ccodec.encode(MsgRangeRequest("PNCOUNT", chunk)))
            )
            async for fr in sc._range_frames("PNCOUNT", chunk):
                range_bytes += len(fr)
                # converge what was measured: the ratio only counts if
                # the conversation actually heals the divergence
                checked = __import__(
                    "jylis_tpu.cluster.cluster", fromlist=["check_frame"]
                ).check_frame(fr[9:])
                assert checked is not None
                msg = ccodec.decode(checked[1])
                await client.converge_async((msg.name, list(msg.batch)))
            range_bytes += len(sc._wire(ccodec.encode(MsgSyncDone())))
        healed = (
            await server.sync_type_digests_async()
            == await client.sync_type_digests_async()
        )
        assert healed, "range conversation did not digest-match"
        return full_bytes, range_bytes, len(divergent)

    full_bytes, range_bytes, n_buckets = asyncio.run(measure())
    return {
        "metric": (
            "rejoin bytes: v8 Merkle-range repair vs whole-state dump "
            f"(PNCOUNT, {n_keys} keys, {n_divergent} divergent keys "
            f"range-local in {divergent_buckets}/256 buckets)"
        ),
        "value": round(full_bytes / range_bytes, 1),
        "unit": "x fewer bytes",
        "vs_baseline": round(full_bytes / range_bytes, 1),
        "keys": n_keys,
        "divergent_keys": n_divergent,
        "divergent_frac": round(n_divergent / n_keys, 4),
        "divergent_buckets": n_buckets,
        "full_dump_bytes": full_bytes,
        "range_repair_bytes": range_bytes,
    }


def config_sync_divergence() -> dict:
    """The anti-entropy v2 acceptance record: a 1M-key PNCOUNT store
    with <=5% of keys divergent (all keys of 12 contiguous digest-tree
    buckets — the range-local layout; see _sync_divergence on the
    granularity bound for sub-bucket-uniform divergence)."""
    return _sync_divergence(n_keys=1_000_000, divergent_buckets=12)


def config_codec_ujson() -> dict:
    """Native cluster codec on a UJSON-heavy batch (the round-3 verdict's
    gap: UJSON payloads always took the Python path, making UJSON
    anti-entropy and bootstrap-sync dumps Python-speed on the wire).
    Encode+decode of 2k keys x 8-entry documents with paths and causal
    context — the bootstrap-dump shape."""
    from jylis_tpu.cluster import codec
    from jylis_tpu.cluster.msg import MsgPushDeltas
    from jylis_tpu.native import codec as ncodec
    from jylis_tpu.native import lib
    from jylis_tpu.ops.ujson_host import UJSON

    n_keys, n_entries = 2000, 8
    batch = []
    for k in range(n_keys):
        u = UJSON()
        for e in range(n_entries):
            u.ctx.vv[100 + e] = k + e + 1
            u.entries[(100 + e, k + e + 1)] = (
                ("profile", f"field{e}"), f'"v{k * 10 + e}"',
            )
        u.ctx.cloud.add((999, k + 1))
        batch.append((b"doc:%06d" % k, u))
    msg = MsgPushDeltas("UJSON", tuple(batch))
    body = codec._encode_oracle(msg)

    def native_once():
        t0 = time.perf_counter()
        out = ncodec.encode_push(msg)
        got = ncodec.decode_push(body)
        dt = time.perf_counter() - t0
        assert out == body and got == msg
        return n_keys, dt

    def oracle_once():
        t0 = time.perf_counter()
        out = codec._encode_oracle(msg)
        got = codec._decode_oracle(body)
        dt = time.perf_counter() - t0
        assert out == body and got == msg
        return n_keys, dt

    oracle = _median_rate(oracle_once, CPU_RUNS)
    if lib() is None:
        return {
            "metric": "cluster codec UJSON encode+decode (native)",
            "value": round(oracle, 1),
            "unit": "keys/sec",
            "vs_baseline": 1.0,
        }
    native = _median_rate(native_once, CPU_RUNS)
    return {
        "metric": "cluster codec UJSON encode+decode (native)",
        "value": round(native, 1),
        "unit": "keys/sec",
        "vs_baseline": round(native / oracle, 2),
    }


# ---- TENSOR: the tensor-valued workload (ROADMAP item 3) -------------------

# the embedding-store shape the acceptance pins: >= 1M keys x >= 64-dim
# vectors, 64 synthetic replica sweeps folded in one batched device join
T_KEYS = 1_000_000
T_DIM = 64
T_REPLICAS = 64


def _tensor_arrays(keys: int, dim: int):
    import jax
    import jax.numpy as jnp

    from jylis_tpu.ops import tensor

    def bits(j):
        return jax.random.bits(jax.random.key(j), (keys, dim), jnp.uint32)

    state = tensor.init(keys, dim)
    # small ts range + few rid values so every lexicographic stage of
    # the select sees real traffic (all-distinct timestamps would settle
    # every cell at the first compare)
    deltas = tensor.TensorState(
        bits(0),
        jnp.zeros((keys, dim), jnp.uint32),
        bits(2) & jnp.uint32(3),
        bits(3) & jnp.uint32(7),
    )
    return state, deltas


def _tensor_sweep(join, rounds: int):
    import jax
    import jax.numpy as jnp

    from jylis_tpu.ops import tensor

    @jax.jit
    def sweep(st, d):
        def body(s, i):
            dd = tensor.TensorState(d.val ^ i, d.ts_hi, d.ts_lo ^ i, d.rid)
            return join(s, dd), None

        s, _ = jax.lax.scan(body, st, jnp.arange(rounds, dtype=jnp.uint32))
        return s

    return sweep


def _tensor_rate(sweep, state, deltas, keys: int, rounds: int) -> float:
    import jax

    s1 = sweep(state, deltas)
    _ = np.asarray(jax.device_get(s1.val.ravel()[0:1]))

    def once():
        t0 = time.perf_counter()
        s = sweep(state, deltas)
        _ = np.asarray(jax.device_get(s.val.ravel()[0:1]))  # hard sync
        return keys * rounds, time.perf_counter() - t0

    return _median_rate(once)


def _tensor_cpu_rate(keys: int, dim: int) -> float:
    """The SAME per-coordinate (ts, rid, okey) select in vectorised
    numpy — the strongest host baseline for this workload (a per-key
    Python loop would be thousands of times slower)."""
    from jylis_tpu.ops.tensor_host import okey_u32 as okey

    rng = np.random.default_rng(0)
    val = np.full((keys, dim), 0xFFFFFFFF, np.uint32)
    ts = np.zeros((keys, dim), np.uint64)
    rid = np.zeros((keys, dim), np.uint32)
    d_val = rng.integers(0, 1 << 32, (keys, dim), dtype=np.uint32)
    d_ts = rng.integers(0, 4, (keys, dim), dtype=np.uint64)
    d_rid = rng.integers(0, 8, (keys, dim), dtype=np.uint32)

    def once():
        t0 = time.perf_counter()
        take = (d_ts > ts) | (
            (d_ts == ts)
            & ((d_rid > rid) | ((d_rid == rid) & (okey(d_val) > okey(val))))
        )
        np.copyto(val, d_val, where=take)
        np.copyto(ts, d_ts, where=take)
        np.copyto(rid, d_rid, where=take)
        return keys, time.perf_counter() - t0

    once()  # touch pages
    return _median_rate(once, CPU_RUNS)


def config_tensor_merge() -> dict:
    """TENSOR dense per-coordinate join at the replicated-embedding
    shape: 1M keys x 64-dim f32 vectors, 64 synthetic replica sweeps
    folded in one `lax.scan` dispatch through the vmap'd (ts, rid,
    okey) select (ops/tensor.py) — thousands of vector merges as one
    device launch, the first workload in this repo a CPU CRDT store
    cannot plausibly serve. One "merge" = one whole-vector join (64
    coordinate joins); vs_baseline is against the same select in
    vectorised numpy."""
    state, deltas = _tensor_arrays(T_KEYS, T_DIM)
    from jylis_tpu.ops import tensor

    r_dev = _tensor_rate(
        _tensor_sweep(tensor.join_dense, T_REPLICAS),
        state, deltas, T_KEYS, T_REPLICAS,
    )
    r_cpu = _tensor_cpu_rate(T_KEYS, T_DIM)
    return {
        "metric": (
            "TENSOR dense per-coordinate join "
            "(1M keys x 64-dim, 64 replica sweeps)"
        ),
        "value": round(r_dev, 1),
        "unit": "vector merges/sec",
        "vs_baseline": round(r_dev / r_cpu, 2),
        "coord_merges_per_sec": round(r_dev * T_DIM, 1),
        "keys": T_KEYS,
        "dim": T_DIM,
        "replicas": T_REPLICAS,
    }


# Pallas settlement: block shape for the fused tensor-join kernel
# (flattened (N*D/128, 128) planes; 400x128x4B x 12 live planes ≈ 2.5 MB
# of VMEM per grid step — the retired PNCOUNT kernel's proven shape)
_PALLAS_LANES = 128
_PALLAS_BLOCK_ROWS = 400


def _pallas_tensor_join():
    """Build the fused tensor-join pallas_call: the same (ts, rid, okey)
    select as ops/tensor.join_dense in ONE hand-scheduled launch with
    input/output aliasing. Mosaic quirks inherited from the retired
    PNCOUNT kernel (ops/pallas_join.py, deleted this round with the
    losing bench recorded as rationale): express max as unsigned
    compares + selects (arith.maxui does not legalise), and trace under
    enable_x64(False) (the framework runs x64 for the u64 lattices;
    Mosaic rejects i64 grid indices)."""
    import jax
    import jax.numpy as jnp
    from functools import partial
    from jax.experimental import pallas as pl

    from jylis_tpu.ops import tensor

    def _kernel(av, ath, atl, ar, bv, bth, btl, br, ov, oth, otl, orr):
        # the PRODUCT's own row join on the loaded blocks: the settlement
        # bench must compare the exact semantics the serving kernel
        # ships, not a re-implementation (compare/select only inside, so
        # it legalises under Mosaic — no maxui)
        ov[...], oth[...], otl[...], orr[...] = tensor._join_row(
            av[...], ath[...], atl[...], ar[...],
            bv[...], bth[...], btl[...], br[...],
        )

    @partial(jax.jit, static_argnames=("interpret",), donate_argnums=(0,))
    def join_fused(state, deltas, interpret=False):
        k, d = state.val.shape
        rows = (k * d) // _PALLAS_LANES
        # largest block <= the target that divides the row count (shape
        # math is static at trace time)
        block = min(rows, _PALLAS_BLOCK_ROWS)
        while rows % block:
            block -= 1
        planes = [
            x.reshape(rows, _PALLAS_LANES) for x in (*state, *deltas)
        ]
        spec = pl.BlockSpec((block, _PALLAS_LANES), lambda i: (i, 0))
        with jax.enable_x64(False):
            out = pl.pallas_call(
                _kernel,
                grid=(rows // block,),
                in_specs=[spec] * 8,
                out_specs=[spec] * 4,
                out_shape=[
                    jax.ShapeDtypeStruct((rows, _PALLAS_LANES), jnp.uint32)
                ] * 4,
                input_output_aliases={0: 0, 1: 1, 2: 2, 3: 3},
                interpret=interpret,
            )(*planes)
        return tensor.TensorState(*(x.reshape(k, d) for x in out))

    return join_fused


def config_pallas_tensor_merge() -> dict:
    """The Pallas question, settled on the workload built for it: the
    fused element-wise tensor merge — the one shape reviews kept
    hypothesising a hand kernel should win — as a single Pallas launch
    with state aliasing, vs the XLA vmap'd dense join at the SAME
    shape. vs_baseline is pallas/xla: < 1.0 means XLA keeps the
    production path. On a TPU toolchain the kernel compiles via Mosaic;
    on a CPU-only host Pallas has no native lowering at all (interpret
    mode only), so the config compiles-or-falls-back and records which
    backend produced the number — either way the recorded ratio is the
    retirement evidence for hand kernels on bandwidth-bound joins."""
    join_fused = _pallas_tensor_join()

    keys, rounds, interpret = T_KEYS, 8, False
    try:
        state, deltas = _tensor_arrays(keys, T_DIM)
        r_pallas = _tensor_rate(
            _tensor_sweep(
                lambda s, d: join_fused(s, d), rounds
            ),
            state, deltas, keys, rounds,
        )
    except Exception as e:
        # ONLY the documented no-native-lowering case falls back — any
        # other failure (OOM, Mosaic legalization, API drift) must
        # surface, not be silently recorded as settlement evidence
        if "interpret mode" not in str(e).lower():
            raise
        # no native Pallas lowering on this backend: interpret mode at a
        # reduced key count (interpret is a per-block Python loop; the
        # full shape would take hours) — recorded as such
        interpret = True
        keys = 65_536
        rounds = 2
        state, deltas = _tensor_arrays(keys, T_DIM)
        r_pallas = _tensor_rate(
            _tensor_sweep(
                lambda s, d: join_fused(s, d, interpret=True), rounds
            ),
            state, deltas, keys, rounds,
        )
    from jylis_tpu.ops import tensor

    state, deltas = _tensor_arrays(keys, T_DIM)
    r_xla = _tensor_rate(
        _tensor_sweep(tensor.join_dense, rounds),
        state, deltas, keys, rounds,
    )
    return {
        "metric": (
            "Pallas fused tensor merge (same shape; "
            "baseline = XLA vmap'd dense join)"
        ),
        "value": round(r_pallas, 1),
        "unit": "vector merges/sec",
        "vs_baseline": round(r_pallas / r_xla, 4),
        "keys": keys,
        "dim": T_DIM,
        "replicas": rounds,
        "interpret": interpret,
    }


def _map_hot_field(n_fields: int) -> dict:
    """The decomposed-delta acceptance measurement (schema v9): a map
    with ``n_fields`` GCOUNT-valued fields, ONE hot field edited — the
    shipped replication bytes must be the edited FIELD's unit, never
    the map. Then the range tier: a replica diverging in that one field
    digest-matches after pulling only the hot field's bucket (a handful
    of hash-colliding fields at most), verified by digest equality."""
    import asyncio

    from jylis_tpu.cluster import codec as ccodec
    from jylis_tpu.cluster.msg import MsgPushDeltas
    from jylis_tpu.models.database import Database
    from jylis_tpu.ops.compose import unpack_field

    class _Null:
        def __getattr__(self, name):
            return lambda *a, **k: None

    server = Database(identity=1, engine="python")
    client = Database(identity=2, engine="python")
    resp = _Null()
    # ONE persistent outbox, registered before any write: the manager's
    # proactive flush emits into the registered sink, so a throwaway
    # lambda would strand deltas
    outbox = []
    server.flush_deltas(outbox.append)
    t0 = time.perf_counter()
    for i in range(n_fields):
        server.apply(resp, [b"MAP", b"GCOUNT", b"SET", b"m",
                            b"f%07d" % i, b"1"])
    build_s = time.perf_counter() - t0
    dump = server.manager("MAP").repo.dump_state()
    whole_map_bytes = len(ccodec.encode(MsgPushDeltas("MAP", tuple(dump))))
    client.converge_deltas(("MAP", list(dump)))

    # drain the build dirt, then the ONE hot edit
    server.flush_deltas(outbox.append)
    outbox.clear()
    server.apply(resp, [b"MAP", b"GCOUNT", b"SET", b"m", b"f0000077", b"1"])
    server.flush_deltas(outbox.append)
    maps = [b for n, b in outbox if n == "MAP"]
    assert len(maps) == 1 and len(maps[0]) == 1, [
        (n, len(b)) for n, b in outbox
    ]
    hot_bytes = len(ccodec.encode(MsgPushDeltas("MAP", tuple(maps[0]))))
    hot_frac = hot_bytes / whole_map_bytes

    # range repair: the client (which missed the hot edit) walks the
    # tree and pulls ONLY the divergent bucket's fields
    async def heal():
        ts = dict(await server.sync_tree_async("MAP"))
        tc = dict(await client.sync_tree_async("MAP"))
        divergent = sorted(
            b for b in set(ts) | set(tc) if ts.get(b) != tc.get(b)
        )
        batch = await server.dump_range_async("MAP", divergent)
        client.converge_deltas(("MAP", batch))
        healed = (
            await server.sync_type_digests_async()
            == await client.sync_type_digests_async()
        )
        return divergent, batch, healed

    divergent, batch, healed = asyncio.run(heal())
    assert healed, "range pull did not digest-match"
    pulled_fields = {unpack_field(k)[1] for k, _ in batch}
    assert b"f0000077" in pulled_fields
    range_bytes = len(ccodec.encode(MsgPushDeltas("MAP", tuple(batch))))
    return {
        "metric": (
            "MAP decomposed deltas: one hot-field edit vs whole-map ship "
            f"({n_fields} GCOUNT-valued fields)"
        ),
        "value": round(whole_map_bytes / hot_bytes, 1),
        "unit": "x fewer bytes",
        "vs_baseline": round(whole_map_bytes / hot_bytes, 1),
        "fields": n_fields,
        "hot_field_bytes": hot_bytes,
        "whole_map_bytes": whole_map_bytes,
        "hot_field_pct": round(hot_frac * 100, 4),
        "range_divergent_buckets": len(divergent),
        "range_pulled_fields": len(pulled_fields),
        "range_pulled_bytes": range_bytes,
        "build_fields_per_sec": round(n_fields / build_s, 1),
    }


def config_map_hot_field() -> dict:
    """The ISSUE's acceptance shape: 100k fields, one hot edit; the
    shipped bytes must be <= 2% of a whole-map ship (the recorded
    number is ~5 orders of magnitude under that bar — decomposition is
    structural, not statistical)."""
    out = _map_hot_field(n_fields=100_000)
    assert out["hot_field_pct"] <= 2.0, out
    assert out["range_pulled_fields"] < out["fields"] // 100, out
    return out


def _bcount_contention(n_replicas: int, bound: int) -> dict:
    """``n_replicas`` synthetic replicas (host BCount lattices — the
    same object the repo serves) racing decrements against ONE bound:
    every spend is locally escrow-checked, escrow rebalances by
    transfer during gossip rounds, and the run ends when the stock is
    exhausted. Recorded: accepted decrements (grants) per second, the
    refusal (OUTOFBOUND) rate, and the oversell count — which the
    escrow construction pins at ZERO by design, measured anyway."""
    import random

    from jylis_tpu.ops.bcount import BCount

    rng = random.Random(0xB0C0)
    seed = BCount()
    seed.grant(0, bound)
    seed.inc(0, bound)  # stock full: value == bound, escrow at rid 0
    # the uncontended ceiling first: one replica holding escrow spends
    # it locally — the O(1) rights-check hot path, no gossip tax
    solo = BCount.from_wire(seed.to_wire())
    t0 = time.perf_counter()
    for _ in range(bound):
        solo.dec(0, 1)
    local_rate = bound / (time.perf_counter() - t0)
    reps = [BCount.from_wire(seed.to_wire()) for _ in range(n_replicas)]
    accepted = refused = transfers = 0
    t0 = time.perf_counter()
    # each iteration: every replica attempts one decrement; every 8th
    # round is a gossip round (random pairwise full-view merges) in
    # which escrow-rich replicas shed half their rights to random peers
    round_i = 0
    while accepted < bound:
        round_i += 1
        for i in range(n_replicas):
            if reps[i].dec(i, 1):
                accepted += 1
                if accepted >= bound:
                    break
            else:
                refused += 1
        if round_i % 8 == 0 or accepted >= bound:
            for i in range(n_replicas):
                j = rng.randrange(n_replicas)
                if j != i:
                    reps[j].converge(BCount.from_wire(reps[i].to_wire()))
            for i in range(n_replicas):
                rights = reps[i].dec_rights(i)
                if rights > 1:
                    j = rng.randrange(n_replicas)
                    if j != i and reps[i].transfer(i, j, rights // 2):
                        transfers += 1
        if round_i > 100_000:  # liveness backstop; never hit in practice
            break
    elapsed = time.perf_counter() - t0
    # full mutual merge, then the safety ledger: sold exactly `bound`,
    # zero oversell, on every replica's converged view
    for i in range(n_replicas):
        for j in range(n_replicas):
            if i != j:
                reps[j].converge(BCount.from_wire(reps[i].to_wire()))
    finals = {(bc.value(), bc.bound()) for bc in reps}
    assert finals == {(bound - accepted, bound)}, finals
    oversell = sum(sum(bc.decs.values()) for bc in reps) // n_replicas - bound
    return {
        "metric": (
            f"BCOUNT escrow under contention: {n_replicas} replicas "
            f"racing decrements against one bound ({bound})"
        ),
        "value": round(accepted / elapsed, 1),
        "unit": "grants/sec",
        "replicas": n_replicas,
        "bound": bound,
        "grants": accepted,
        "refusals": refused,
        "refusal_rate": round(refused / max(accepted + refused, 1), 4),
        "transfers": transfers,
        "oversell": oversell,
        "gossip_rounds": round_i // 8,
        # end-to-end grants/sec (the `value`) pays the full-view gossip
        # merges; this is the escrow-in-hand local spend ceiling
        "local_grants_per_sec": round(local_rate, 1),
    }


def config_bcount_contention() -> dict:
    out = _bcount_contention(n_replicas=64, bound=100_000)
    assert out["oversell"] == 0, out
    return out


# ---- sessions & regions benches (schema v10) --------------------------------


def _zipf_ranks(n_keys: int, n: int, s: float = 0.99, seed: int = 7):
    """Deterministic Zipfian key ranks (YCSB's default skew s=0.99):
    the inverse-CDF over the truncated zeta weights."""
    rng = np.random.default_rng(seed)
    w = 1.0 / np.arange(1, n_keys + 1, dtype=np.float64) ** s
    p = w / w.sum()
    return rng.choice(n_keys, size=n, p=p)


def _workload_latency(
    conns: int,
    rounds: int,
    read_frac: float,
    n_keys: int = 4096,
    zipf: bool = True,
    session: bool = False,
    demote: bool = False,
) -> dict[str, tuple]:
    """{class: (p50_us, p99_us)} for a YCSB-style scenario: ``conns``
    non-pipelined connections issuing GCOUNT GET/INC over a shared
    keyspace with Zipfian (or uniform) key choice. ``session=True``
    issues every read as SESSION READ <token> (token minted once per
    conn via SESSION WRAP) — the session path's end-to-end cost.
    ``demote=True`` demotes each connection to the Python dispatch path
    first, which is the apples-to-apples baseline for the session
    surface (SESSION commands are python-path by design)."""
    import asyncio

    from jylis_tpu.models.database import Database
    from jylis_tpu.server.server import Server
    from jylis_tpu.utils.config import Config
    from jylis_tpu.utils.log import Log

    async def measure():
        cfg = Config()
        cfg.port = "0"
        cfg.log = Log.create_none()
        db = Database(identity=1)
        server = Server(cfg, db)
        await server.start()
        samples: dict[str, list[float]] = {"get": [], "inc": []}
        try:

            async def client(ci: int) -> None:
                rng = np.random.default_rng(1000 + ci)
                if zipf:
                    ranks = _zipf_ranks(n_keys, rounds, seed=100 + ci)
                else:
                    ranks = np.random.default_rng(100 + ci).integers(
                        0, n_keys, size=rounds
                    )
                reads = rng.random(rounds) < read_frac
                reader, writer = await asyncio.open_connection(
                    "127.0.0.1", server.port
                )
                try:

                    async def read_until(counter, want: int) -> None:
                        while counter.done < want:
                            chunk = await reader.read(1 << 16)
                            if not chunk:
                                raise ConnectionError("server closed")
                            counter.feed(chunk)

                    primer = b"GCOUNT INC zk0 1\r\nGCOUNT GET zk0\r\n"
                    want = 2
                    if demote:
                        primer = _demoter_cmd(ci) + b"\r\n" + primer
                        want += 1
                    writer.write(primer)
                    await writer.drain()
                    await read_until(RespReplyCounter(), want)
                    for r_i in range(rounds):
                        key = b"zk%d" % ranks[r_i]
                        if reads[r_i]:
                            payload = b"GCOUNT GET %s\r\n" % key
                            cls = "get"
                        else:
                            payload = b"GCOUNT INC %s 1\r\n" % key
                            cls = "inc"
                        t0 = time.perf_counter()
                        writer.write(payload)
                        await writer.drain()
                        await read_until(RespReplyCounter(), 1)
                        samples[cls].append(time.perf_counter() - t0)
                finally:
                    writer.close()

            async def session_client(ci: int) -> None:
                # like client(), but every read is SESSION READ with a
                # token minted once via SESSION WRAP — split out so the
                # non-session path above stays byte-simple
                rng = np.random.default_rng(1000 + ci)
                ranks = _zipf_ranks(n_keys, rounds, seed=100 + ci)
                reads = rng.random(rounds) < read_frac
                reader, writer = await asyncio.open_connection(
                    "127.0.0.1", server.port
                )
                try:

                    async def read_until(counter, want: int) -> None:
                        while counter.done < want:
                            chunk = await reader.read(1 << 16)
                            if not chunk:
                                raise ConnectionError("server closed")
                            counter.feed(chunk)

                    primer = b"GCOUNT INC zk0 1\r\nGCOUNT GET zk0\r\n"
                    want = 2
                    if demote:
                        primer = _demoter_cmd(ci) + b"\r\n" + primer
                        want += 1
                    writer.write(primer)
                    await writer.drain()
                    await read_until(RespReplyCounter(), want)
                    token = await _session_token_over_wire(
                        reader, writer, b"zk0"
                    )
                    for r_i in range(rounds):
                        key = b"zk%d" % ranks[r_i]
                        if reads[r_i]:
                            cmd = [b"SESSION", b"READ", token, b"GCOUNT",
                                   b"GET", key]
                            payload = b"*%d\r\n" % len(cmd) + b"".join(
                                b"$%d\r\n%s\r\n" % (len(w), w) for w in cmd
                            )
                            cls = "get"
                        else:
                            payload = b"GCOUNT INC %s 1\r\n" % key
                            cls = "inc"
                        t0 = time.perf_counter()
                        writer.write(payload)
                        await writer.drain()
                        await read_until(RespReplyCounter(), 1)
                        samples[cls].append(time.perf_counter() - t0)
                finally:
                    writer.close()

            runner = session_client if session else client
            await asyncio.gather(*(runner(i) for i in range(conns)))
        finally:
            await server.dispose()
        return samples

    samples = asyncio.run(measure())
    out = {}
    for name, xs in samples.items():
        if not xs:
            continue
        xs.sort()
        p50 = xs[len(xs) // 2]
        p99 = xs[min(len(xs) - 1, int(len(xs) * 0.99))]
        out[name] = (round(p50 * 1e6, 1), round(p99 * 1e6, 1))
    return out


def _plain_latency_under_load(bg_session: bool, fg_conns: int = 4,
                              bg_conns: int = 4, rounds: int = 150) -> tuple:
    """(p50_us, p99_us) of plain GCOUNT GETs on ``fg_conns`` foreground
    connections while ``bg_conns`` background connections issue either
    SESSION READ traffic (bg_session=True) or the same plain GETs at a
    MATCHED, paced rate (~500 ops/s per conn — an unpaced background
    saturates the 2-core recording host and measures scheduler
    contention, not the path). The with/without-session ratio isolates
    the session path's tax on the node's plain serving latency — the
    `serving-latency` overhead the acceptance bar bounds (same
    connection count, same op rate, the ONLY difference is whether the
    background rides the SESSION surface)."""
    import asyncio

    from jylis_tpu.models.database import Database
    from jylis_tpu.server.server import Server
    from jylis_tpu.utils.config import Config
    from jylis_tpu.utils.log import Log

    async def measure():
        cfg = Config()
        cfg.port = "0"
        cfg.log = Log.create_none()
        db = Database(identity=1)
        server = Server(cfg, db)
        await server.start()
        stop = asyncio.Event()
        samples: list[float] = []
        try:

            async def read_until(reader, counter, want: int) -> None:
                while counter.done < want:
                    chunk = await reader.read(1 << 16)
                    if not chunk:
                        raise ConnectionError("server closed")
                    counter.feed(chunk)

            async def background(ci: int) -> None:
                reader, writer = await asyncio.open_connection(
                    "127.0.0.1", server.port
                )
                try:
                    # BOTH background arms ride the python dispatch path
                    # (demoted): session commands are python-path by
                    # design, so a native-path plain background would
                    # measure the engine-vs-python gap, not the session
                    # machinery
                    writer.write(
                        _demoter_cmd(1000 + ci)
                        + b"\r\nGCOUNT INC bg%d 1\r\n" % ci
                    )
                    await writer.drain()
                    await read_until(reader, RespReplyCounter(), 2)
                    if bg_session:
                        tok = await _session_token_over_wire(
                            reader, writer, b"bg%d" % ci
                        )
                        cmd = [b"SESSION", b"READ", tok, b"GCOUNT",
                               b"GET", b"bg%d" % ci]
                        payload = b"*%d\r\n" % len(cmd) + b"".join(
                            b"$%d\r\n%s\r\n" % (len(w), w) for w in cmd
                        )
                    else:
                        payload = b"GCOUNT GET bg%d\r\n" % ci
                    while not stop.is_set():
                        writer.write(payload)
                        await writer.drain()
                        await read_until(reader, RespReplyCounter(), 1)
                        await asyncio.sleep(0.002)  # the matched pace
                finally:
                    writer.close()

            async def foreground(ci: int) -> None:
                reader, writer = await asyncio.open_connection(
                    "127.0.0.1", server.port
                )
                try:
                    writer.write(b"GCOUNT INC fg%d 1\r\n" % ci)
                    await writer.drain()
                    await read_until(reader, RespReplyCounter(), 1)
                    for _ in range(rounds):
                        t0 = time.perf_counter()
                        writer.write(b"GCOUNT GET fg%d\r\n" % ci)
                        await writer.drain()
                        await read_until(reader, RespReplyCounter(), 1)
                        samples.append(time.perf_counter() - t0)
                finally:
                    writer.close()

            bg = [
                asyncio.ensure_future(background(i))
                for i in range(bg_conns)
            ]
            await asyncio.sleep(0.1)  # background loops spinning
            await asyncio.gather(*(foreground(i) for i in range(fg_conns)))
            stop.set()
            await asyncio.gather(*bg, return_exceptions=True)
        finally:
            stop.set()
            await server.dispose()
        return samples

    samples = asyncio.run(measure())
    samples.sort()
    p50 = samples[len(samples) // 2]
    p99 = samples[min(len(samples) - 1, int(len(samples) * 0.99))]
    return (round(p50 * 1e6, 1), round(p99 * 1e6, 1))


async def _session_token_over_wire(reader, writer, key: bytes) -> bytes:
    """SESSION WRAP GCOUNT INC <key> 1 -> the minted token (binary-safe
    positional parse of the [reply, token] array)."""
    wrap = [b"SESSION", b"WRAP", b"GCOUNT", b"INC", key, b"1"]
    writer.write(
        b"*%d\r\n" % len(wrap)
        + b"".join(b"$%d\r\n%s\r\n" % (len(w), w) for w in wrap)
    )
    await writer.drain()
    buf = b""
    while True:
        chunk = await reader.read(1 << 16)
        if not chunk:
            raise ConnectionError("server closed")
        buf += chunk
        if not buf.startswith(b"*2\r\n+OK\r\n$"):
            if len(buf) >= 10:
                raise AssertionError(buf[:64])
            continue
        j = buf.find(b"\r\n", 10)
        if j < 0:
            continue
        n = int(buf[10:j])
        if len(buf) >= j + 2 + n + 2:
            return buf[j + 2 : j + 2 + n]


def config_workload_zipf() -> dict:
    """YCSB-style skewed workload (ROADMAP item 5b): Zipfian (s=0.99)
    hot keys over a 4096-key GCOUNT space, read-heavy (95/5) and
    write-heavy (50/50) scenarios at 16 non-pipelined connections,
    p50/p99 per command class — plus the session path measured
    apples-to-apples: SESSION READ vs a plain python-path GET on
    demoted connections (the SESSION surface is python-path by design;
    `session_overhead_frac` is the p50 tax of carrying the guarantee)."""
    read_heavy = _workload_latency(16, 150, read_frac=0.95)
    write_heavy = _workload_latency(16, 150, read_frac=0.50)
    uniform = _workload_latency(16, 150, read_frac=0.95, zipf=False)
    plain_py = _workload_latency(8, 120, read_frac=1.0, demote=True)
    sess_py = _workload_latency(
        8, 120, read_frac=1.0, demote=True, session=True
    )
    # the acceptance number: plain serving latency with a matched-rate
    # background differing ONLY in riding the SESSION surface —
    # median-of-5 paired runs after a discarded warmup pair (the
    # 2-core recording host's first runs carry scheduler noise from
    # the scenarios above)
    _plain_latency_under_load(bg_session=True, fg_conns=1, bg_conns=2,
                              rounds=100)  # warmup, discarded
    pairs = [
        (
            _plain_latency_under_load(
                bg_session=True, fg_conns=1, bg_conns=2, rounds=400
            ),
            _plain_latency_under_load(
                bg_session=False, fg_conns=1, bg_conns=2, rounds=400
            ),
        )
        for _ in range(5)
    ]
    # publish the PAIR whose ratio is the median, so the two recorded
    # latency tuples reproduce the recorded overhead exactly
    pairs.sort(key=lambda p: p[0][0] / max(p[1][0], 1e-9))
    with_sess, without_sess = pairs[len(pairs) // 2]
    serving_overhead = with_sess[0] / max(without_sess[0], 1e-9) - 1.0
    return {
        "metric": (
            "YCSB-style Zipfian workload (s=0.99, 4096 keys, 16 conns): "
            "p50/p99 per command class"
        ),
        "value": read_heavy["get"][1],
        "unit": "us p99 (GET, read-heavy zipf)",
        # skew factor: what the hot-key pile-up costs vs uniform keys
        "vs_baseline": round(
            read_heavy["get"][1] / max(uniform["get"][1], 1e-9), 2
        ),
        "read_heavy_us": read_heavy,
        "write_heavy_us": write_heavy,
        "uniform_read_us": uniform,
        "session_read_us": sess_py,
        "python_read_us": plain_py,
        "plain_get_us_with_session_load": with_sess,
        "plain_get_us_with_plain_load": without_sess,
        "serving_latency_overhead_frac": round(serving_overhead, 4),
        "note": (
            "serving_latency_overhead_frac = plain GET p50 with "
            "session-reading background connections over the same with "
            "plain-reading background at a MATCHED paced rate (paired, "
            "median of 5) — the session path's tax on serving-latency; "
            "acceptance <= 0.05. "
            "session_read_us vs python_read_us is the END-TO-END cost "
            "of a SESSION READ itself (bigger request, token decode + "
            "reply token, array reply) against a plain GET on the same "
            "python dispatch path — the price of carrying the "
            "guarantee, paid only by session commands."
        ),
    }


_WAN_SPAWN = (
    "from jylis_tpu.utils.vcpu import force_virtual_cpu; "
    "force_virtual_cpu(8); "
    "import sys; from jylis_tpu.main import main; main(sys.argv[1:])"
)


def _spawn_wan_node(
    port, cport, name, region, seed=None, failpoints="", demote_ticks=None,
    extra=(),
):
    import os
    import subprocess
    import sys

    argv = [
        sys.executable, "-c", _WAN_SPAWN, "--port", str(port),
        "--addr", f"127.0.0.1:{cport}:{name}", "--region", region,
        "--heartbeat-time", "0.2", "--log-level", "warn",
    ]
    if seed:
        argv += ["--seed-addrs", seed]
    if failpoints:
        argv += ["--failpoints", failpoints]
    if demote_ticks is not None:
        argv += ["--bridge-demote-ticks", str(demote_ticks)]
    argv += list(extra)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.Popen(
        argv,
        cwd=os.path.dirname(os.path.abspath(__file__)),
        env=env,
        stdout=subprocess.DEVNULL,
    )


def _wan_converge_lag(rtt_s: float, writes: int = 5) -> float:
    """Median write->visible lag (ms) from region r1's member node to
    region r2's node, with ``rtt_s`` of one-way WAN latency injected at
    the bridge relay seam (cluster.relay=sleep). Three REAL processes:
    r1 = {bridge a, member b}, r2 = {c}; the measured path is b -> a
    (intra) -> relay(+rtt) -> c."""
    import socket

    def call(port, cmd: bytes) -> bytes:
        s = socket.create_connection(("127.0.0.1", port), timeout=10)
        try:
            s.sendall(cmd)
            s.settimeout(10)
            return s.recv(1 << 16)
        finally:
            s.close()

    ports = [_free_port() for _ in range(3)]
    cports = sorted(_free_port() for _ in range(3))
    # the smallest address string is the deterministic bridge: give the
    # intended bridge the smallest cluster port (all ephemeral ports
    # print 5 digits, so numeric order IS string order)
    seed = f"127.0.0.1:{cports[0]}:wan-a"
    fp = f"cluster.relay=sleep:{rtt_s}" if rtt_s > 0 else ""
    procs = [
        _spawn_wan_node(ports[0], cports[0], "wan-a", "r1", failpoints=fp),
        _spawn_wan_node(ports[1], cports[1], "wan-b", "r1", seed=seed),
        _spawn_wan_node(ports[2], cports[2], "wan-c", "r2", seed=seed),
    ]
    try:
        deadline = time.time() + 180
        for p in ports:
            while True:
                if time.time() > deadline:
                    raise RuntimeError("wan node never came up")
                try:
                    if call(p, b"GCOUNT GET boot\r\n").startswith(b":"):
                        break
                except OSError:
                    time.sleep(0.3)
        # wait until the relay path works at all (topology settled)
        call(ports[1], b"GCOUNT INC warm 1\r\n")
        while call(ports[2], b"GCOUNT GET warm\r\n") != b":1\r\n":
            if time.time() > deadline:
                raise RuntimeError("relay path never converged")
            time.sleep(0.05)
        lags = []
        for i in range(writes):
            time.sleep(0.6)  # a fresh proactive-flush window per write
            key = b"w%d" % i
            t0 = time.perf_counter()
            assert call(ports[1], b"GCOUNT INC %s 1\r\n" % key) == b"+OK\r\n"
            while call(ports[2], b"GCOUNT GET %s\r\n" % key) != b":1\r\n":
                if time.perf_counter() - t0 > 60:
                    raise RuntimeError("write never became visible")
                time.sleep(0.002)
            lags.append((time.perf_counter() - t0) * 1e3)
        lags.sort()
        return lags[len(lags) // 2]
    finally:
        for pr in procs:
            pr.terminate()
        for pr in procs:
            try:
                pr.wait(timeout=30)
            except Exception:
                pr.kill()
                pr.wait(timeout=10)


# bridge failover phase (PR 15): demotion threshold the failover
# measurement runs with, and the in-config bound the recorded gap is
# asserted against. The gap's floor is demote_ticks x the 0.2 s
# heartbeat (the demotion window itself); on top ride the successor's
# dial + establishment sync + one relay hop (and the injected RTT),
# plus generous scheduling slack for a loaded recording host.
_WAN_FAILOVER_DEMOTE_TICKS = 8
_WAN_FAILOVER_HEARTBEAT_S = 0.2


def _wan_failover_bound_ms(rtt_ms: float) -> float:
    return (
        _WAN_FAILOVER_DEMOTE_TICKS * _WAN_FAILOVER_HEARTBEAT_S * 1e3
        + rtt_ms
        + 10_000.0
    )


def _wan_failover_gap(rtt_s: float) -> float:
    """Convergence gap (ms) across a bridge SIGKILL: 2 regions over 3
    real processes (r1 = {bridge a, member b}, r2 = {c}), traffic
    warmed through a's relay, then a is SIGKILLed and the clock runs
    from the kill until a fresh write on b becomes visible on c — the
    whole demotion + succession + redial + relay pipeline as one
    number, with ``rtt_s`` injected at the relay seam like the
    converge sweep."""
    import signal
    import socket

    def call(port, cmd: bytes) -> bytes:
        s = socket.create_connection(("127.0.0.1", port), timeout=10)
        try:
            s.sendall(cmd)
            s.settimeout(10)
            return s.recv(1 << 16)
        finally:
            s.close()

    ports = [_free_port() for _ in range(3)]
    cports = sorted(_free_port() for _ in range(3))
    seed = f"127.0.0.1:{cports[0]}:wan-a"
    fp = f"cluster.relay=sleep:{rtt_s}" if rtt_s > 0 else ""
    dt = _WAN_FAILOVER_DEMOTE_TICKS
    procs = [
        _spawn_wan_node(
            ports[0], cports[0], "wan-a", "r1", failpoints=fp,
            demote_ticks=dt,
        ),
        _spawn_wan_node(
            ports[1], cports[1], "wan-b", "r1", seed=seed,
            failpoints=fp, demote_ticks=dt,
        ),
        _spawn_wan_node(
            ports[2], cports[2], "wan-c", "r2", seed=seed,
            failpoints=fp, demote_ticks=dt,
        ),
    ]
    try:
        deadline = time.time() + 180
        for p in ports:
            while True:
                if time.time() > deadline:
                    raise RuntimeError("wan node never came up")
                try:
                    if call(p, b"GCOUNT GET boot\r\n").startswith(b":"):
                        break
                except OSError:
                    time.sleep(0.3)
        # warm: the incumbent's relay path works
        call(ports[1], b"GCOUNT INC warm 1\r\n")
        while call(ports[2], b"GCOUNT GET warm\r\n") != b":1\r\n":
            if time.time() > deadline:
                raise RuntimeError("relay path never converged")
            time.sleep(0.05)
        # SIGKILL the elected bridge; the clock runs from here
        procs[0].send_signal(signal.SIGKILL)
        procs[0].wait(timeout=30)
        t0 = time.perf_counter()
        assert call(ports[1], b"GCOUNT INC gap 1\r\n") == b"+OK\r\n"
        while call(ports[2], b"GCOUNT GET gap\r\n") != b":1\r\n":
            if time.perf_counter() - t0 > 120:
                raise RuntimeError("failover convergence gap exceeded 120s")
            time.sleep(0.01)
        return (time.perf_counter() - t0) * 1e3
    finally:
        for pr in procs:
            if pr.poll() is None:
                pr.terminate()
        for pr in procs:
            try:
                pr.wait(timeout=30)
            except Exception:
                pr.kill()
                pr.wait(timeout=10)


def config_wan_converge() -> dict:
    """Multi-region convergence lag vs injected WAN RTT (ROADMAP item
    5a): three real node processes in two regions (r1 = bridge + one
    member, r2 = one node), writes on the r1 MEMBER, visibility polled
    on the r2 node — the full member -> bridge -> relay -> remote-region
    path, with the WAN latency injected at the bridge's relay seam via
    the failpoint machinery (cluster.relay=sleep:RTT).

    PR 15 adds the bridge-kill phase: at each RTT tier the elected
    bridge is SIGKILLed and the convergence GAP — kill until a fresh
    member write is visible in the remote region again, through the
    demoted-and-succeeded bridge — is recorded and asserted against
    the in-config bound (demotion window + RTT + slack)."""
    sweep = {}
    failover = {}
    for rtt_ms in (0, 20, 80):
        sweep[str(rtt_ms)] = round(_wan_converge_lag(rtt_ms / 1e3), 1)
        gap = round(_wan_failover_gap(rtt_ms / 1e3), 1)
        bound = _wan_failover_bound_ms(rtt_ms)
        assert gap < bound, (
            f"failover gap {gap}ms at {rtt_ms}ms RTT breaches the "
            f"{bound:.0f}ms bound"
        )
        failover[str(rtt_ms)] = gap
    base = max(sweep["0"], 1e-9)
    return {
        "metric": (
            "multi-region convergence lag vs injected inter-region RTT "
            "(2 regions, 3 real nodes, bridge relay) + bridge-kill "
            "failover convergence gap"
        ),
        "value": sweep["80"],
        "unit": "ms median write->visible lag at 80ms injected RTT",
        # the injected-RTT tax over the zero-RTT relay path
        "vs_baseline": round(sweep["80"] / base, 2),
        "base_lag_ms": sweep["0"],
        "converge_lag_ms": sweep,
        # bridge failover (PR 15): SIGKILL-to-reconverged gap per RTT
        # tier, each asserted under the in-config bound above
        "failover_gap_ms": failover,
        "failover_gap_80_ms": failover["80"],
        "failover_demote_ticks": _WAN_FAILOVER_DEMOTE_TICKS,
        "failover_bound_ms": {
            rtt: round(_wan_failover_bound_ms(float(rtt)), 1)
            for rtt in ("0", "20", "80")
        },
        "note": (
            "lag is measured client-side: write acked on the r1 member "
            "until first successful read on the r2 node; the relay seam "
            "sleeps once per relayed batch, so lag ~ base + RTT. The "
            "failover gap runs the same path across a bridge SIGKILL: "
            "demotion (8 ticks x 0.2s heartbeat) + successor dial + "
            "establishment sync + relay; zero whole-state dumps by "
            "construction (the ladder heals the blip)"
        ),
    }


# overload-shed drill (this PR): the sustained-overload regime the
# admission layer is bench-pinned against. The protected class's p99.9
# at 4x offered load must stay within this factor of its 1x value —
# the "armor holds" contract docs/operations.md quotes.
_OVERLOAD_POLICY = "control>read>write>bulk"
_OVERLOAD_P999_FACTOR = 2.0
# client-observed MTTR bound: SIGKILL of the routed node until the
# ClusterClient's next read returns through a survivor.
_CLIENT_MTTR_BOUND_S = 3.0


def _overload_shed_run(
    procs, phase_s, mults, read_frac, warmup_s,
    base_rate=0.0, failpoints="", keys=256,
):
    """Boot one armed node (--admission-policy) and drive it with the
    open-loop loadgen harness (scripts/loadgen.py) through the
    sustained-overload phase ladder; returns loadgen's recorded JSON."""
    import json as _json
    import os
    import socket
    import subprocess
    import sys

    port, cport = _free_port(), _free_port()
    node = _spawn_wan_node(
        port, cport, "ov-a", "r1", failpoints=failpoints,
        extra=("--admission-policy", _OVERLOAD_POLICY),
    )
    try:
        deadline = time.time() + 180
        while True:
            if node.poll() is not None or time.time() > deadline:
                raise RuntimeError("overload node never came up")
            try:
                s = socket.create_connection(("127.0.0.1", port), timeout=5)
                s.close()
                break
            except OSError:
                time.sleep(0.3)
        here = os.path.dirname(os.path.abspath(__file__))
        argv = [
            sys.executable, os.path.join(here, "scripts", "loadgen.py"),
            "--port", str(port), "--procs", str(procs),
            "--phase-s", str(phase_s), "--mults", mults,
            "--keys", str(keys), "--read-frac", str(read_frac),
            "--warmup-s", str(warmup_s),
        ]
        if base_rate:
            argv += ["--base-rate", str(base_rate)]
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        r = subprocess.run(
            argv, capture_output=True, text=True, cwd=here, env=env,
            timeout=60.0 + len(mults.split(",")) * (phase_s + 25.0) + 60.0,
        )
        if r.returncode != 0:
            raise RuntimeError(f"loadgen failed: {r.stderr[-500:]}")
        return _json.loads(r.stdout)
    finally:
        if node.poll() is None:
            node.terminate()
        try:
            node.wait(timeout=30)
        except Exception:
            node.kill()
            node.wait(timeout=10)


def config_overload_shed() -> dict:
    """The sustained-overload drill regime (this PR's tentpole bench):
    one armed node, open-loop Zipfian load at a fixed 900 ops/s base,
    then held at 1x -> 2x -> 4x offered load. The base is pinned (not
    probe-calibrated) because on the 1-core reference host the probe
    ladder's run-to-run variance swings the 4x rate across the
    capacity boundary — some runs would never overload at all; 900
    sits comfortably under capacity at 1x and decisively over it at
    4x (loadgen's --base-rate recalibrates for other hosts). Reads are the protected class (rank 1, inside the
    protect floor); writes ride SESSION WRAP so the classifier's
    unwrapping — not first-word syntax — is what sheds them. In-config
    asserts: the protected class is NEVER shed, overload is declared
    (enter transitions recorded), the 4x phase sheds most writes and
    stays in the declared state, and protected p99.9 at 4x holds
    within _OVERLOAD_P999_FACTOR of its 1x value — the armor contract.
    Latency excludes a 2s per-phase warmup (the hysteresis entry
    transient, by design not steady state; counters cover the whole
    phase)."""
    out = _overload_shed_run(
        procs=2, phase_s=8.0, mults="1,2,4", read_frac=0.2, warmup_s=2.0,
        base_rate=900.0,
    )
    ph = {p["mult"]: p for p in out["phases"]}
    p1, p4 = ph[1.0], ph[4.0]
    assert all(
        p["shed_frac"]["read"] == 0.0 for p in out["phases"]
    ), f"protected class was shed: {out}"
    enters = sum(p["overload_delta"]["enters"] for p in out["phases"])
    assert enters >= 1, f"overload never declared: {out}"
    assert p4["shed_frac"]["write"] > 0.5, (
        f"4x shed fraction too low: {p4['shed_frac']}"
    )
    assert p4["overload_delta"]["state_after"] == 1, (
        f"4x phase should end in declared overload: {p4}"
    )
    p999_1 = p1["lat_ms"]["read"]["p999"]
    p999_4 = p4["lat_ms"]["read"]["p999"]
    assert p999_4 <= _OVERLOAD_P999_FACTOR * p999_1, (
        f"protected p99.9 {p999_4}ms at 4x breaches "
        f"{_OVERLOAD_P999_FACTOR}x its 1x value {p999_1}ms"
    )
    return {
        "metric": (
            "protected-class (read) p99.9 under sustained 4x overload "
            "(open-loop Zipfian, priority admission shedding writes)"
        ),
        "value": p999_4,
        "unit": "ms read p99.9 at 4x offered load (steady state)",
        # the armor contract: 4x tail over 1x tail, bound 2.0
        "vs_baseline": round(p999_4 / max(p999_1, 1e-9), 2),
        "policy": _OVERLOAD_POLICY,
        "base_rate_ops_s": out["base_rate"],
        "read_frac": out["read_frac"],
        "p999_bound_factor": _OVERLOAD_P999_FACTOR,
        # flat copies of the headline phase numbers (check_prose
        # claims read top-level fields only)
        "p999_1x_ms": p999_1,
        "shed_frac_write_4x": p4["shed_frac"]["write"],
        "phases": [
            {
                "mult": p["mult"],
                "read_p50_ms": p["lat_ms"]["read"]["p50"],
                "read_p99_ms": p["lat_ms"]["read"]["p99"],
                "read_p999_ms": p["lat_ms"]["read"]["p999"],
                "shed_frac": p["shed_frac"],
                "overload": p["overload_delta"],
            }
            for p in out["phases"]
        ],
        "note": (
            "writes are SESSION WRAP GCOUNT INC — shed by the "
            "classifier's unwrapping, not first-word syntax; the 2x "
            "phase rides the capacity edge (severe-shed flapping) and "
            "is recorded but not bounded; 4x pins severe shedding and "
            "the protected tail returns to its 1x shape"
        ),
    }


def config_client_failover() -> dict:
    """Client-observed MTTR across a SIGKILL of the routed node: the
    cluster-aware ClusterClient (jylis_tpu/client.py) discovers the
    3-node/2-region topology via SYSTEM TOPOLOGY, routes to its home
    region, and carries a session token. Each trial writes through the
    routed node, waits for the delta to replicate, SIGKILLs that node,
    and clocks kill -> the next successful routed read (token intact:
    read-your-writes holds through the failover). Two trials (the
    second fails over from the first's survivor), each bounded by
    _CLIENT_MTTR_BOUND_S in-config."""
    import signal
    import socket

    from jylis_tpu.client import ClusterClient

    def call(port, cmd: bytes) -> bytes:
        s = socket.create_connection(("127.0.0.1", port), timeout=10)
        try:
            s.sendall(cmd)
            s.settimeout(10)
            return s.recv(1 << 16)
        finally:
            s.close()

    ports = [_free_port() for _ in range(3)]
    cports = sorted(_free_port() for _ in range(3))
    seed = f"127.0.0.1:{cports[0]}:cf-a"
    dt = _WAN_FAILOVER_DEMOTE_TICKS
    procs = [
        _spawn_wan_node(
            ports[0], cports[0], "cf-a", "r1", demote_ticks=dt,
        ),
        _spawn_wan_node(
            ports[1], cports[1], "cf-b", "r1", seed=seed, demote_ticks=dt,
        ),
        _spawn_wan_node(
            ports[2], cports[2], "cf-c", "r2", seed=seed, demote_ticks=dt,
        ),
    ]
    cc = None
    try:
        deadline = time.time() + 180
        for p in ports:
            while True:
                if time.time() > deadline:
                    raise RuntimeError("failover node never came up")
                try:
                    if call(p, b"GCOUNT GET boot\r\n").startswith(b":"):
                        break
                except OSError:
                    time.sleep(0.3)
        # warm the mesh: a write on each node visible on every other
        call(ports[0], b"GCOUNT INC warm 1\r\n")
        while call(ports[2], b"GCOUNT GET warm\r\n") != b":1\r\n":
            if time.time() > deadline:
                raise RuntimeError("mesh never converged")
            time.sleep(0.05)
        cc = ClusterClient(
            [("127.0.0.1", p) for p in ports], region="r1", timeout=10,
        )
        trials = []
        for i in range(2):
            key = f"cf{i}"
            assert cc.write("GCOUNT", "INC", key, "5") == b"OK"
            victim_port = cc._ep[1]
            victim = procs[ports.index(victim_port)]
            want = b":5\r\n"
            for sp in ports:
                if sp == victim_port or procs[ports.index(sp)].poll() is not None:
                    continue
                while call(sp, b"GCOUNT GET %s\r\n" % key.encode()) != want:
                    if time.time() > deadline:
                        raise RuntimeError("delta never replicated")
                    time.sleep(0.05)
            victim.send_signal(signal.SIGKILL)
            victim.wait(timeout=30)
            t0 = time.perf_counter()
            assert cc.read("GCOUNT", "GET", key) == 5
            wall = time.perf_counter() - t0
            assert wall < _CLIENT_MTTR_BOUND_S, (
                f"trial {i}: client MTTR {wall:.3f}s breaches the "
                f"{_CLIENT_MTTR_BOUND_S}s bound"
            )
            trials.append(
                {
                    "mttr_wall_s": round(wall, 4),
                    "mttr_client_s": round(cc.stats["last_mttr_s"], 4),
                }
            )
        assert cc.stats["failovers"] >= 2, cc.stats
        worst = max(t["mttr_wall_s"] for t in trials)
        return {
            "metric": (
                "client-observed MTTR: SIGKILL of the routed node until "
                "the ClusterClient's next successful read (3 nodes, 2 "
                "regions, session token carried through failover)"
            ),
            "value": worst,
            "unit": "s worst-trial kill->read wall clock",
            "vs_baseline": round(worst / _CLIENT_MTTR_BOUND_S, 3),
            "mttr_bound_s": _CLIENT_MTTR_BOUND_S,
            "trials": trials,
            "client_stats": {
                k: (round(v, 4) if isinstance(v, float) else v)
                for k, v in cc.stats.items()
            },
            "note": (
                "mttr_client_s is the client's own first-failure-to-"
                "success clock (stats.last_mttr_s); the wall number "
                "additionally covers failure detection from the kill "
                "instant. Read-your-writes holds across the failover: "
                "the session token rides SESSION READ on the survivor"
            ),
        }
    finally:
        if cc is not None:
            cc.close()
        for pr in procs:
            if pr.poll() is None:
                pr.terminate()
        for pr in procs:
            try:
                pr.wait(timeout=30)
            except Exception:
                pr.kill()
                pr.wait(timeout=10)


CONFIGS = {
    "gcount-smoke": config_gcount_smoke,
    "concurrent": config_concurrent,
    "concurrent-sharded": config_concurrent_sharded,
    "serving-demotion": config_serving_demotion,
    "serving-latency": config_serving_latency,
    "pncount-100k": config_pncount_100k,
    "treg-1m": config_treg_1m,
    "tlog-trim": config_tlog_trim,
    "ujson-32": config_ujson_32,
    "ujson-multikey": config_ujson_multikey,
    "codec-native": config_codec_native,
    "codec-ujson": config_codec_ujson,
    "sync-divergence": config_sync_divergence,
    "tensor-merge": config_tensor_merge,
    "pallas-tensor-merge": config_pallas_tensor_merge,
    "map-hot-field": config_map_hot_field,
    "bcount-contention": config_bcount_contention,
    "workload-zipf": config_workload_zipf,
    "wan-converge": config_wan_converge,
    "overload-shed": config_overload_shed,
    "client-failover": config_client_failover,
}


def device_info() -> dict:
    """The backend as jax reports it — stamped on the north-star row."""
    import jax

    devs = jax.devices()
    return {
        "platform": devs[0].platform,
        "kind": devs[0].device_kind,
        "count": len(devs),
    }


def north_star() -> dict:
    """The per-chip rate. Refuses any backend that is not a TPU: a
    merges/sec/chip printed from XLA:CPU is not a slow number, it is a
    wrong one."""
    import sys

    dev = device_info()
    if dev["platform"] != "tpu":
        print(
            f"bench.py: the north-star rate is per TPU chip; jax found "
            f"platform={dev['platform']!r} kind={dev['kind']!r} "
            f"count={dev['count']} — refusing to print a rate",
            file=sys.stderr,
        )
        sys.exit(1)
    device = bench_device()
    cpu = bench_cpu()
    return {
        "metric": "PNCOUNT anti-entropy merges/sec/chip (1M keys x 64 replicas)",
        "value": round(device, 1),
        "unit": "merges/sec",
        "vs_baseline": round(device / cpu, 2),
        "device": dev,
    }


def smoke() -> None:
    """`make bench-smoke` (wired into `make ci`): a tiny-iteration pass
    over the serving-harness plumbing — the RESP reply counting, the
    fallback accounting, the demotion path and the latency loop — so
    none of it can rot between re-records. Asserts sanity, records
    nothing."""
    r, fb = _concurrent_rate(4, reps=8, bursts=2)
    assert r > 0 and 0.0 <= fb <= 1.0, (r, fb)
    rd, fbd = _concurrent_rate(2, reps=8, bursts=2, demote=True)
    # a demoted connection serves everything from the Python path
    assert rd > 0 and fbd > 0.5, (rd, fbd)
    # the obs-off comparison path (obs_cost_frac's denominator) serves
    ro, _ = _concurrent_rate(2, reps=8, bursts=2, obs=False)
    assert ro > 0, ro
    lat = _latency_once(2, rounds=6)
    assert all(p50 > 0 and p99 >= p50 for p50, p99 in lat.values()), lat
    # the sharded harness plumbing: a real 2-lane spawn, multi-process
    # clients, the external-port latency loop — tiny iterations, so the
    # machinery behind the concurrent-sharded record can't rot either
    proc, port = _spawn_sharded_node(2)
    try:
        rs = _sharded_rate(port, 4, reps=4, bursts=2)
        assert rs > 0, rs
        slat = _latency_once(2, rounds=4, port=port)
        assert all(p50 > 0 and p99 >= p50 for p50, p99 in slat.values()), slat
    finally:
        _stop_sharded_node(proc)
    # tiny-iteration tensor-merge: the harness behind the recorded
    # tensor-merge / pallas-tensor-merge rows — the XLA sweep, the numpy
    # baseline, AND the Pallas kernel (interpret mode, checked against
    # the XLA join bit-for-bit) so none of it rots between re-records
    from jylis_tpu.ops import tensor as _tensor

    tk, td, tr = 2048, 8, 2
    st, dl = _tensor_arrays(tk, td)
    rt = _tensor_rate(_tensor_sweep(_tensor.join_dense, tr), st, dl, tk, tr)
    assert rt > 0, rt
    assert _tensor_cpu_rate(tk, td) > 0
    join_fused = _pallas_tensor_join()
    st, dl = _tensor_arrays(tk, td)
    got = join_fused(st, dl, interpret=True)
    st, dl = _tensor_arrays(tk, td)
    want = _tensor.join_dense(st, dl)
    assert all(
        (np.asarray(g) == np.asarray(w)).all() for g, w in zip(got, want)
    )
    # tiny sync-divergence pass: the Merkle-range measurement harness
    # (tree exchange, budgeted walk, frame accounting, the digest-match
    # verification) at toy scale — the ratio itself is only meaningful
    # at the recorded 1M-key shape
    sd = _sync_divergence(n_keys=2048, divergent_buckets=12)
    assert sd["vs_baseline"] > 1.0, sd
    assert sd["divergent_keys"] > 0 and sd["range_repair_bytes"] > 0, sd
    # tiny composed-type passes: the decomposition measurement (one
    # field unit vs whole-map ship + the field-scoped range pull) and
    # the escrow contention harness (accept/refuse/transfer/merge loop,
    # zero oversell) at toy scale
    mh = _map_hot_field(n_fields=512)
    assert mh["hot_field_bytes"] < mh["whole_map_bytes"], mh
    assert mh["range_pulled_fields"] < mh["fields"], mh
    bc = _bcount_contention(n_replicas=8, bound=512)
    assert bc["oversell"] == 0 and bc["grants"] == 512, bc
    # tiny workload-zipf pass: the Zipfian sampler, both scenario
    # shapes, the SESSION WRAP/READ wire (binary token over RESP), and
    # the paced paired-load harness behind the recorded overhead number
    wl = _workload_latency(2, 6, read_frac=0.5)
    assert all(p50 > 0 and p99 >= p50 for p50, p99 in wl.values()), wl
    ws = _workload_latency(2, 6, read_frac=1.0, demote=True, session=True)
    assert ws["get"][0] > 0, ws
    pl = _plain_latency_under_load(
        bg_session=True, fg_conns=1, bg_conns=1, rounds=6
    )
    assert pl[0] > 0, pl
    # tiny wan-converge pass: 3 real regioned processes, one write,
    # the member -> bridge -> relay -> remote-region visibility path
    assert _wan_converge_lag(0.0, writes=1) > 0
    # tiny failover pass (PR 15): SIGKILL the elected bridge, measure
    # the demotion + succession + reconverge gap, hold the recorded
    # bound — the harness behind the failover_gap_ms record
    gap = _wan_failover_gap(0.0)
    assert 0 < gap < _wan_failover_bound_ms(0.0), gap
    # tiny overload-shed pass (this PR): the armed node + open-loop
    # loadgen pipeline behind the overload-shed record, with the
    # forced-shed failpoint standing in for real overload so the BUSY
    # accounting (shed, not error) is exercised deterministically at
    # 1s phases — the recorded regime only means anything at full scale
    ov = _overload_shed_run(
        procs=2, phase_s=1.0, mults="1,4", read_frac=0.7, warmup_s=0.0,
        base_rate=300.0, failpoints="admission.shed=error:40", keys=32,
    )
    ov_ok = sum(
        p["ok"][c] for p in ov["phases"] for c in ("read", "write")
    )
    ov_busy = sum(
        p["busy"][c] for p in ov["phases"] for c in ("read", "write")
    )
    assert ov_ok > 100 and ov_busy > 0, (ov_ok, ov_busy)
    assert all(
        p["err"][c] == 0 for p in ov["phases"] for c in ("read", "write")
    ), ov
    print(
        json.dumps(
            {
                "smoke": "ok",
                "concurrent_cps": round(r, 1),
                "fallback_frac": round(fb, 4),
                "demoted_cps": round(rd, 1),
                "sharded_cps": round(rs, 1),
                "tensor_merge_vps": round(rt, 1),
                "latency_us": lat,
            }
        )
    )


def main() -> None:
    import sys

    args = sys.argv[1:]
    if not args:
        print(json.dumps(north_star()))  # the driver's ONE line
    elif args[0] == "--smoke":
        smoke()
    elif args[0] == "--all":
        print(json.dumps(north_star()))
        for fn in CONFIGS.values():
            print(json.dumps(fn()))
    elif args[0] == "--full":
        # machine-recorded sweep: every config's JSON, committed per round
        # as BENCH_full.json so perf claims stay driver-auditable
        out = [dict(north_star(), config="north-star")]
        print(json.dumps(out[0]))
        for name, fn in CONFIGS.items():
            r = dict(fn(), config=name)
            out.append(r)
            print(json.dumps(r))
        with open("BENCH_full.json", "w") as f:
            json.dump(out, f, indent=1)
    elif args[0] == "--config" and len(args) > 1 and args[1] in CONFIGS:
        print(json.dumps(CONFIGS[args[1]]()))
    else:
        print(
            "usage: bench.py            the per-chip north-star rate "
            "(needs a TPU; exits 1 on any other backend)\n"
            "       bench.py --all | --full | --smoke | --config NAME\n"
            "         builder-side CPU harness: serving nodes are spawned "
            "pinned to the CPU,\n"
            "         so apart from the leading north-star row of "
            "--all/--full (TPU or exit 1)\n"
            "         nothing it prints is a chip measurement "
            "(ROADMAP S0 replaces it)\n"
            f"       NAME: {'|'.join(CONFIGS)}"
        )
        sys.exit(2)


if __name__ == "__main__":
    main()
