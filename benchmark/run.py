#!/usr/bin/env python3
"""One run of one cell of BENCHMARK.json.

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

State of every data type the configuration states, from the seed -> one
snapshot -> boot the node (``python -m jylis_tpu``, the only child not
pinned to the CPU) and its peers through their own boot recovery -> warm up
with the cell's own mix -> measure ``--seconds`` -> read every type back and
compare with its plain reference -> stop. The LAST line of stdout is the
result object (its last key, ``compared``, holds each number compared beside
its limit; the same on the last lines of stderr); everything else is on earlier lines or under
``benchmark/out/``. No fallback: a node on another platform than ``tpu``,
on the Python tables, with an error-level log line, or a failed phase ends
the run non-zero with no result line. ``--rehearse`` (tests, debugging)
takes the configuration's tiny sizes and accepts the CPU platform; its
result says ``"rehearsal": true`` and is not a chip result.
"""

from __future__ import annotations

import time

T_PROCESS = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import signal  # noqa: E402
import subprocess  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

# Children inherit the environment as launched; this process pins ITSELF to
# the CPU before jax is imported anywhere, and never touches the chip.
CHILD_ENV = dict(os.environ)
os.environ["JAX_PLATFORMS"] = "cpu"

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import numpy as np  # noqa: E402

from benchmark.harness import check, gen, manifest, measure, readers, resp, state  # noqa: E402
from benchmark.harness.loadgen import sleep_until  # noqa: E402
from benchmark.harness.nodes import HOST, Node, RunFailure  # noqa: E402

OUT = os.path.join(ROOT, "benchmark", "out")  # gitignored
LEAD_S = 3.0  # from starting the load workers to their first operation


def say(msg: str) -> None:
    print(f"[{time.monotonic() - T_PROCESS:7.2f}] {msg}", flush=True)


def ensure_native() -> str:
    """The nodes must serve from a native library built from THIS
    checkout's native/*.cpp: build it unless the one on disk carries the
    hash of these sources."""
    from jylis_tpu import native

    if not native.loads_checkout_build():
        raise RunFailure("JYLIS_NATIVE_SO or a bundled .so would be loaded, "
                         "not this checkout's build")
    if native.built_hash() != native.source_hash() and not native.build():
        raise RunFailure("native library build failed (g++, native/*.cpp)")
    if native.built_hash() != native.source_hash():
        raise RunFailure("native build stamp does not match native/*.cpp")
    return native.source_hash()


def replica_id(addr: str) -> int:
    from jylis_tpu.utils.address import Address

    return Address.from_string(addr).hash64()


def worker_configs(traffic: dict, targets: dict, base: dict) -> list[dict]:
    """Split the mix's streams and probes over worker processes."""
    out, conn = [], 0
    for si, stream in enumerate(traffic["streams"]):
        ports = [[HOST, p] for p in targets[stream["target"]]]
        n = stream.get("workers", 1)
        for w in range(n):
            cfg = dict(base, kind=stream["loop"], stream=stream["name"], stream_index=si,
                       worker=w, ops=stream["ops"], keys=stream["keys"],
                       amount=stream.get("amount", [1, 1]), targets=ports,
                       counted=bool(stream.get("counted")), conn_base=conn)
            if stream["loop"] == "closed":
                cfg["connections"] = stream["connections"] // n
                cfg["depth"] = stream.get("depth", 1)
                conn += cfg["connections"]
            else:
                cfg["rate_per_s"] = stream["rate_per_s"] / n
                conn += len(ports)
            out.append(cfg)
    probe = traffic.get("probes")
    if probe:
        out.append(dict(base, kind="probe", stream="probes",
                        stream_index=len(traffic["streams"]), worker=0, probe=probe,
                        read_targets=[[HOST, p] for p in targets[probe["read_at"]]],
                        write_targets=[[HOST, p] for p in targets[probe["write_at"]]],
                        counted=False, conn_base=conn))
    return out


def load_log(cfg: dict) -> dict:
    with np.load(cfg["out"]) as z:
        lg = {k: z[k] for k in z.files}
    lg["kind"], lg["counted"], lg["stream"] = cfg["kind"], cfg["counted"], cfg["stream"]
    if cfg["kind"] == "probe":
        templates = [gen.Template(cfg["probe"]["write"])]
        lg["classes"] = ["write"]
        lg["timeout_s"] = float(cfg["probe"]["timeout_s"])
        # a probe's write is acknowledged once the worker stamped ``sched``
        lg["acked"] = lg["sched"] > 0
    else:
        templates = [gen.Template(op["cmd"]) for op in cfg["ops"]]
        lg["classes"] = [op["class"] for op in cfg["ops"]]
        lg["acked"] = lg["status"] == 1
    # per op index: its type (whose reference follows it), verb and template
    lg["types"] = [t.type_name for t in templates]
    lg["verbs"] = [t.verb for t in templates]
    lg["texts"] = [t.text for t in templates]
    return lg


class Run:
    """One booted deployment: the node under test, its peers, and behind
    every data type the configuration states the reference that follows
    that type's acknowledged writes. `boot`, then `drive` one or more
    windows, then `verify` and `stop`; `close` always."""

    def __init__(self, args, root: str = ROOT):
        """``root``: where ``BENCHMARK.json`` and the files it names are
        read from (a test's copy with a cell laid over); the program, the
        load workers and ``benchmark/out`` are this checkout's."""
        self.args = args
        self.cell = cell = manifest.Cell(args.workload, root)
        self.rehearse = rehearse = args.rehearse
        self.config = config = manifest.sized(cell.config, rehearse)
        self.types = manifest.types_of(config, rehearse)
        self.recipes = {t["type"]: t["state"] for t in self.types}
        self.traffic = manifest.sized(cell.traffic, rehearse)
        src_hash = ensure_native()
        say(f"cell {cell.name}: config {config['name']}, traffic {self.traffic['name']}, "
            f"seed {args.seed}, window {args.seconds}s, trace {args.trace}; "
            f"native library from native/*.cpp ({src_hash[:12]})")
        os.makedirs(OUT, exist_ok=True)
        self.work = tempfile.mkdtemp(prefix="run-", dir=OUT)
        self.log_dir = os.path.join(OUT, "logs", cell.name)
        peer_env = dict(CHILD_ENV, JAX_PLATFORMS="cpu")
        node_env = dict(CHILD_ENV, JAX_LOG_COMPILES="1")
        if rehearse:
            node_env["JAX_PLATFORMS"] = "cpu"
        self.trace_dir = os.path.join(self.work, "trace")
        if args.trace:
            # the program then annotates its drains; WHEN the trace runs is
            # the shim's (harness/trace_shim/sitecustomize.py): the window
            os.makedirs(self.trace_dir)
            node_env["JYLIS_PROFILE_DIR"] = node_env["BENCH_TRACE_DIR"] = self.trace_dir
            shim = os.path.join(ROOT, "benchmark", "harness", "trace_shim")
            node_env["PYTHONPATH"] = os.pathsep.join(
                [shim] + ([node_env["PYTHONPATH"]] if node_env.get("PYTHONPATH") else []))
        flags = list(config["node_flags"])
        self.node = Node("bench-node", 0, os.path.join(self.work, "node"), node_env, flags)
        self.peers = [Node(f"bench-peer{i + 1}", i + 1, os.path.join(self.work, f"peer{i + 1}"),
                           peer_env, flags) for i in range(config["peers"])]
        self.everyone = [self.node] + self.peers
        for n in self.everyone:
            n.seed_addrs = [m.addr for m in self.everyone if m is not n]
        self.workers: list[subprocess.Popen] = []
        self.logs: list[dict] = []
        self.n_windows = 0
        self.refs: dict = {}  # type name -> its reference
        self.hot: dict = {}  # type name -> its key indices, hottest first
        self.compared: dict = {}  # "<node>.<TYPE>" -> [mismatched reads, limit]
        # node name -> port the LOAD dials in that node's place (the
        # read-back always dials the node itself): lets a test put a
        # tampering proxy under the timed path
        self.load_ports: dict[str, int] = {}

    def boot(self) -> None:
        """State from the seed through the program's snapshot format, then
        peers and node together, each through its own boot recovery."""
        self.write_state()
        for n in self.everyone:
            n.spawn()
        for n in self.everyone:
            n.wait_serving(900)
        node = self.node
        self.dev = dev = node.device()
        say(f"node serving {time.monotonic() - node.t_spawn:.1f}s after spawn: {dev}")
        want = "cpu" if self.rehearse else "tpu"
        if dev["platform"] != want or dev["count"] < self.cell.chips:
            raise RunFailure(f"the node came up on platform {dev['platform']!r} (kind "
                             f"{dev['kind']!r}, {dev['count']} device(s)); the cell needs "
                             f"{self.cell.chips} {want!r} device(s): no accelerator, no result")
        if node.time_of("serving engine: native") is None:
            raise RunFailure("the node serves from the Python tables, not the native engine")
        if node.time_of("snapshot restored") is None:
            raise RunFailure("the node did not restore the seed-made snapshot")
        for p in self.peers:
            if p.device()["platform"] != "cpu":
                raise RunFailure(f"{p.name} must be pinned to the CPU")
        n_peers = len(self.peers)
        deadline = time.monotonic() + 300
        while n_peers:
            established = [n.prom().get('jylis_cluster{key="peers_established"}', 0)
                           for n in self.everyone]
            if all(e >= n_peers for e in established):
                break
            if time.monotonic() > deadline:
                raise RunFailure(f"cluster mesh not established: {established}")
            time.sleep(0.25)
        say(f"cluster: {n_peers} live peers established on every node")
        # every node asks every peer for state once after it connects, and
        # computing its first digest over the whole keyspace takes many
        # seconds with every repo lock held: wait it out, so that it does
        # not land in the window. The snapshots are the same bytes, so the
        # digests match and no data moves ("sync: peer digest match").
        while n_peers:
            done = [n.prom().get('jylis_cluster{key="sync_done_recv"}', 0)
                    for n in self.everyone]
            if all(d >= n_peers for d in done):
                break
            if time.monotonic() > deadline:
                raise RunFailure(f"join sync not finished: sync_done_recv {done}")
            time.sleep(0.25)
        say("cluster: join sync done on every node (digests matched)")
        self.warm_shapes()

    def write_state(self) -> int:
        """A reference for every stated type, all from the one seed, and
        their full states as ONE snapshot in every node's data dir."""
        t = time.monotonic()
        self.refs, self.hot = check.references(
            self.cell, self.recipes, self.args.seed, replica_id(self.node.addr),
            [replica_id(p.addr) for p in self.peers])
        size = state.write_snapshots(self.refs, [n.data_dir for n in self.everyone])
        keys = " + ".join(f"{r['keys']} {name} keys" for name, r in self.recipes.items())
        say(f"state: {keys} from the seed, snapshot "
            f"{size / 1e6:.1f} MB in {time.monotonic() - t:.1f}s")
        return size

    def keyspaces(self) -> dict:
        """Per type, what a load worker needs to draw and render a key."""
        return {name: {"keys": r["keys"], "key_format": r["key_format"]}
                for name, r in self.recipes.items()}

    def warm_bursts(self):
        """(entry of the mix's ``warm_bursts``, its write template, the
        burst's index in the entry, key indices, amounts, commands) of every
        burst, from the seed. ``warm_bursts`` is one object or a list of
        them, an entry a type: the type is the entry's write template's."""
        spec = self.traffic.get("warm_bursts") or []
        for j, entry in enumerate([spec] if isinstance(spec, dict) else spec):
            write_tpl = gen.Template(entry["write"])
            recipe = self.recipes[write_tpl.type_name]
            rng = np.random.default_rng([self.args.seed, 0x5742] + ([j] if j else []))
            fmt = recipe["key_format"].encode()
            for i, size in enumerate(entry["sizes"]):
                size = min(size, recipe["keys"])
                keys = rng.choice(recipe["keys"], size, replace=False)
                amounts = rng.integers(1, 1000, size, dtype=np.uint64)
                cmds = [write_tpl.render(fmt % int(k), int(a)) for k, a in zip(keys, amounts)]
                yield entry, write_tpl, i, keys, amounts, cmds

    def warm_shapes(self) -> None:
        """Drain batches pad to powers of two, and every new size is a new
        compiled program: before the mix starts, bursts of exactly those
        sizes go in at the ``write_at`` nodes and one read at the node
        drains each, so that the window meets no new shape. The bursts are
        acknowledged writes like any other and reach the reference."""
        if not self.traffic.get("warm_bursts"):
            return  # nothing to warm: no connection is opened at the node
        t = time.monotonic()
        targets = {"node": [self.node], "peers": self.peers}
        conn = resp.Conn(HOST, self.node.port, timeout=120)
        n = 0
        for n, (spec, write_tpl, i, keys, amounts, cmds) in enumerate(self.warm_bursts(), 1):
            size = len(keys)
            read_tpl = gen.Template(spec["read"])
            fmt = self.recipes[write_tpl.type_name]["key_format"].encode()
            counter = spec["drain_counter"]
            nodes = targets[spec["write_at"]]
            before = self.node.prom().get(counter, 0)
            # a writer ships its deltas when a write finds the last flush
            # over 500 ms old (else at the 10 s heartbeat): all but one row
            # now, then the last row as the write that flushes the burst
            with resp.Conn(HOST, nodes[i % len(nodes)].port, timeout=120) as c:
                replies = c.pipeline(cmds[:-1])
                time.sleep(spec["flush_ms"] / 1000.0)
                replies += c.pipeline(cmds[-1:])
            acked = np.array([not isinstance(r, resp.Err) for r in replies])
            self.logs.append({"kind": "burst", "counted": False, "op": np.zeros(size, np.uint8),
                              "key": keys.astype(np.int64), "a": amounts,
                              "b": np.zeros(size, np.uint64), "acked": acked,
                              "types": [write_tpl.type_name], "verbs": [write_tpl.verb],
                              "texts": [write_tpl.text], "classes": ["write"]})
            deadline = time.monotonic() + 30
            while True:  # read until the burst has arrived and a drain has run
                time.sleep(spec["settle_ms"] / 1000.0)
                conn.pipeline([read_tpl.render(fmt % int(keys[0]))])
                if self.node.prom().get(counter, 0) > before:
                    break
                if time.monotonic() > deadline:
                    raise RunFailure(f"warm burst of {size} {write_tpl.type_name} rows "
                                     f"drained nothing")
        conn.close()
        say(f"warm bursts: {n} drain shapes in {time.monotonic() - t:.1f}s")

    def worker_cfgs(self, traffic: dict, t_begin: float, t1: float) -> list[dict]:
        """The load workers' configurations for the next window of a mix."""
        node = self.node
        targets = {"node": [self.load_ports.get(node.name, node.port)],
                   "peers": [self.load_ports.get(p.name, p.port) for p in self.peers]}
        base = {"seed": self.args.seed + 7919 * self.n_windows, "keyspaces": self.keyspaces(),
                "t_begin": t_begin, "t1": t1}
        return worker_configs(traffic, targets, base)

    def drive(self, traffic: dict, seconds: float) -> dict:
        """Warm up with the mix, then one measured window of it."""
        node, work = self.node, self.work
        t_begin = time.monotonic() + LEAD_S
        t0 = t_begin + traffic["warm_seconds"]
        t1 = t0 + seconds
        cfgs = self.worker_cfgs(traffic, t_begin, t1)
        self.workers = []
        for i, cfg in enumerate(cfgs):
            cfg["out"] = os.path.join(work, f"worker{self.n_windows}-{i}.npz")
            path = os.path.join(work, f"worker{self.n_windows}-{i}.json")
            with open(path, "w") as f:
                json.dump(cfg, f)
            self.workers.append(subprocess.Popen(
                [sys.executable, os.path.join(ROOT, "benchmark", "harness", "loadgen.py"), path],
                cwd=ROOT, env=dict(os.environ)))
        self.n_windows += 1
        sleep_until(t0)
        if self.args.trace:
            node.proc.send_signal(signal.SIGUSR1)
        wall0, before = time.time_ns(), node.prom()
        say(f"window opens: {t0 - T_PROCESS:.2f}s after process start")
        sleep_until(t1)
        wall1, after = time.time_ns(), node.prom()
        if self.args.trace:
            node.proc.send_signal(signal.SIGUSR2)
        for w in self.workers:
            rc = w.wait(timeout=120)
            if rc != 0:
                raise RunFailure(f"a load worker exited rc={rc}")
        logs = [load_log(cfg) for cfg in cfgs]
        self.logs += logs
        compiles = node.compiles_between(t0, t1)
        say(f"window closed: programs compiled inside it: {compiles} (should be 0)")
        for cfg, lg in zip(cfgs, logs):
            if "generator_cpu_share" in lg:
                say(f"  worker {cfg['stream']}/{cfg['worker']}: {len(lg['op'])} ops, "
                    f"generator CPU share {float(lg['generator_cpu_share']):.2f}")
        return {"window": measure.Window(logs, t0, t1), "t0": t0, "compiles": compiles,
                "before": before, "after": after, "wall0": wall0, "wall1": wall1}

    def verify(self) -> bool:
        """`correct`: every stated type read back at the node and at every
        live peer, each against its own reference; true only if every
        (node, type) is within the limit."""
        t = time.monotonic()
        fed = check.feed_reference(self.refs, self.logs)
        where = {n.name: n.port for n in self.everyone}
        n_keys = n_doubtful = 0
        for block in self.types:
            name, ref = block["type"], self.refs[block["type"]]
            written, doubtful = fed[name]
            keys = check.choose_keys(self.args.seed, block, written, doubtful, self.hot[name])
            verdicts = check.compare(where, ref, keys, ref.expected(keys),
                                     block["check"]["settle_seconds"], say, name)
            for node, v in verdicts.items():
                self.compared[f"{node}.{name}"] = [v["mismatched"], v["limit"]]
            n_keys += len(keys)
            n_doubtful += len(doubtful)
        correct = all(bad <= limit for bad, limit in self.compared.values())
        say(f"correct: {correct} ({n_keys} keys of {len(self.types)} type(s) at {len(where)} "
            f"node(s), {n_doubtful} doubtful keys left out, check took "
            f"{time.monotonic() - t:.1f}s)")
        return correct

    def stop(self) -> int | None:
        """SIGTERM the node: its shutdown log carries the device's memory
        peak (and, traced, the profiler writes its file at exit)."""
        t = time.monotonic()
        node = self.node
        for p in self.peers:
            p.kill()
        if not node.terminate("device memory:", 300):
            raise RunFailure(f"no device memory line after SIGTERM:\n{node.tail()}")
        peak = node.memory_peak_bytes()
        if self.args.trace and not os.path.exists(os.path.join(self.trace_dir, "written")):
            raise RunFailure("the traced node wrote no trace")
        node.kill()
        say(f"node stopped in {time.monotonic() - t:.1f}s, memory peak {peak}")
        problems = [f"{n.name}: {line}" for n in self.everyone for line in n.problems()]
        if problems:
            raise RunFailure("error-level log lines:\n" + "\n".join(problems[:20]))
        if peak is None and not self.rehearse:
            raise RunFailure("the device reported no peak memory")
        return peak

    def close(self, failed: bool) -> None:
        for w in self.workers:
            if w.poll() is None:
                w.kill()
            w.wait(timeout=30)
        for n in self.everyone:
            if failed and n.lines:
                print(f"---- {n.name}, end of log ----\n{n.tail(30)}", file=sys.stderr)
            n.kill()
            if n.lines:
                n.save_log(self.log_dir)
        shutil.rmtree(self.work, ignore_errors=True)


def run_cell(args) -> dict:
    run = Run(args)
    cell = run.cell
    failed = True
    try:
        run.boot()
        d = run.drive(run.traffic, args.seconds)
        win = d["window"]
        setup_s = d["t0"] - T_PROCESS
        e2e, counts = measure.end_to_end(win, [m["name"] for m in cell.end_to_end], setup_s)
        attempted, n_failed = win.attempted_failed()
        say(f"  attempted {attempted}, failed {n_failed} {win.failures() or ''}".rstrip())
        for name, v in e2e.items():
            say(f"  {name} = {v:.4f}" + (f" over {counts[name]} samples" if name in counts else ""))
        lags, _ = win.probes()
        if len(lags):
            say(f"  probe lag s: max {lags.max():.3f}, over 2 s: {int((lags > 2).sum())}, "
                f"over 10 s: {int((lags > 10).sum())} of {len(lags)}; the slowest, as "
                f"[second of the window, writer, lag]: {win.slowest_probes(8)}")
        late = win.lateness_ms()
        if late:
            say(f"  open-loop generator lateness ms: p50 {late['p50']:.3f} "
                f"p99 {late['p99']:.3f} max {late['max']:.3f}")
        correct = run.verify()
        peak = run.stop()
        dev = run.dev
        device = {"platform": dev["platform"], "kind": dev["kind"], "count": dev["count"],
                  "memory_peak_bytes": peak}
        result = {"correct": bool(correct), "attempted": attempted, "failed": n_failed,
                  "failed_by": win.failures()}
        units = {m["name"]: m["unit"] for m in cell.end_to_end + cell.per_layer}
        if args.trace:
            ctx = readers.Context(cell, d["before"], d["after"], d["wall0"], d["wall1"],
                                  run.node, run.trace_dir, run.rehearse, run.log_dir)
            layer = {}
            for m in cell.per_layer:
                v = readers.read(ctx, cell.layer_spec(m["name"]))
                if v is not None:
                    layer[m["name"]] = v
                    say(f"  {m['name']} = {v:.6g} {m['unit']}")
                else:
                    say(f"  {m['name']}: nothing to read")
            trace = ctx.trace()
            device["busy_s"], device["window_s"] = trace["busy_s"], trace["window_s"]
            result["metrics"] = {k: {"value": v, "unit": units[k]} for k, v in layer.items()}
            result["breakdown"] = trace["breakdown"]
            result["end_to_end_while_traced"] = e2e
        else:
            result["metrics"] = {k: {"value": v, "unit": units[k]} for k, v in e2e.items()}
        result["device"] = device
        result["compiles_in_window"] = d["compiles"]
        if run.rehearse:
            result["rehearsal"] = True
        # each number compared beside its limit, last in the line
        result["compared"] = {k: {"mismatched_reads": bad, "limit": limit}
                              for k, (bad, limit) in run.compared.items()}
        failed = False
        return result
    finally:
        run.close(failed)


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny sizes on the CPU platform: not a chip result")
    args = ap.parse_args(argv)
    # SIGTERM (a driver's time limit) must still stop the children
    signal.signal(signal.SIGTERM, lambda *_: sys.exit(143))
    try:
        result = run_cell(args)
    except RunFailure as e:
        print(f"run failed, no result: {e}", file=sys.stderr)
        return 1
    for name, c in result["compared"].items():
        print(f"compared {name}: mismatched reads {c['mismatched_reads']}, limit {c['limit']}",
              file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
