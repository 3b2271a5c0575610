#!/usr/bin/env python3
"""On-chip gate: the served node, end to end, at north-star state size.

    python3 chip_smoke.py            # needs a TPU; anything else exits non-zero

One full-replica node is started the way a user starts it — ``python -m
jylis_tpu --data-dir D --seed-addrs PEER`` as a child process — and driven
only through its normal doors: pipelined RESP from clients, a real cluster
connection from a peer, boot recovery. The deployment is the repo's own
(BASELINE.json): the north star (PNCOUNT, 1M keys, 64 replica ids) plus
config 3 (TREG, 1M keys, random-timestamp SET merge), with the other types
at whatever size reaches every serving kernel family.

* The 63 foreign replica columns come in over the cluster: this script
  writes a seed-made snapshot (persist.write_snapshot) into the data dir of
  ONE PEER NODE PINNED TO THE CPU — the peer is traffic, not the thing under
  test — the chip node joins it, and rejoin sync + heartbeat deltas carry
  the columns across. The peer also takes live writes while clients read.
* Every acknowledged write is read back and compared with a plain reference
  (numpy arithmetic on what the seed wrote, ops/hostref.py for TLOG), on the
  chip node AND on the CPU peer; SYSTEM DIGEST of the two must be equal (the
  same lattices on XLA:CPU — a full-keyspace differential). Then SIGTERM,
  exit code 0, boot again on the same data dir, same answers.
* One process per chip: THIS process pins itself to the CPU before jax is
  imported and never initialises a backend on the chip; children get the
  environment this script was launched with; only one child at a time is
  not pinned to the CPU.
* No fallback: the node's boot log names its platform and the smoke fails
  on any other than the expected one, on a node serving from the Python
  tables, on a native library not built by this run, on any error-level
  log line, on a non-zero exit, or on any failed phase. No ``except`` on
  this path turns a failure into exit 0.

The last two stdout lines are JSON objects. The second to last is the
summary (``{"ok": true, "device": {...}, ..., "claim": null}``): sizes
smaller than the source's are listed under ``reduced``, widths the source
does not give under ``assumed``. The last is the verdict a driver reads,
those two keys and no other: ``{"ok": true, "device": {"platform": ...,
"kind": ..., "count": ...}}``, the device as the node's jax reported it.
``--rehearse`` (tests, CPU debugging) shrinks the sizes and accepts the CPU
platform; its summary says ``"rehearsal": true`` and is not a chip result.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import re
import shutil
import signal
import socket
import subprocess
import sys
import tempfile
import threading
import time
from concurrent.futures import ThreadPoolExecutor

REPO = os.path.dirname(os.path.abspath(__file__))

# Children inherit the environment as launched; the parent pins ITSELF to
# the CPU, and must do so before jax is imported (jax reads it at import).
CHILD_ENV = dict(os.environ)
os.environ["JAX_PLATFORMS"] = "cpu"

import numpy as np  # noqa: E402

# in a directory that holds nothing else of the repo these imports fail,
# which is the non-zero exit the contract asks for
from jylis_tpu import COMPILE_CACHE_DIR, native, persist  # noqa: E402
from jylis_tpu.client import Client, pack_command  # noqa: E402
from jylis_tpu.models.database import DATA_TYPE_NAMES  # noqa: E402
from jylis_tpu.ops import hostref  # noqa: E402
from jylis_tpu.utils.address import Address  # noqa: E402
from jylis_tpu.utils.batching import bucket  # noqa: E402
from jylis_tpu.utils.net import free_port  # noqa: E402

U64 = (1 << 64) - 1
HOST = "127.0.0.1"
# FIXED cluster ports: a node's replica id is the hash of its advertised
# address, and replica ids are part of the state (counter columns, UJSON
# dots) — so with them fixed, one seed gives one SYSTEM DIGEST, on one chip
# or four, run after run. Below the ephemeral range, so free_port() (the
# RESP ports, the test suite) never hands them out.
CHIP_CLUSTER_PORT = 29471
PEER_CLUSTER_PORT = 29472
SCRATCH = os.path.join(REPO, ".scratch")  # gitignored
LOG_DIR = os.path.join(SCRATCH, "chip_smoke_logs")


class SmokeFailure(Exception):
    """A phase failed. Never caught on the smoke's path: it ends the run
    through the ``finally`` that stops the child processes."""


def check(cond, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def say(msg: str) -> None:
    print(f"[{time.strftime('%H:%M:%S')}] {msg}", flush=True)


# ---- sizes -----------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class Plan:
    """How big each leg is. The defaults are the deployment; ``tiny`` is
    the rehearsal."""

    keys: int = 1_000_000  # PNCOUNT and TREG keys (BASELINE: "1M")
    replicas: int = 64  # replica ids per PNCOUNT key space (BASELINE: 64)
    foreign_keys: int = 1 << 14  # keys carrying all 63 foreign columns
    treg_peer_keys: int = 1 << 16  # TREG keys the peer's snapshot also set
    treg_value_bytes: int = 16
    gcount_keys: int = 4096
    gcount_replicas: int = 8
    tlog_keys: int = 4
    tlog_entries: int = 1100  # > ROW_DRAIN_THRESHOLD (1024): ~1k-entry rows
    tlog_trim: int = 1000
    tensor_keys: int = 1200  # > PENDING_DRAIN_THRESHOLD (1024)
    tensor_dim: int = 64
    ujson_keys: int = 4
    ujson_fanin: int = 80  # > SEG_FANIN_MIN (64) deltas per key, from the peer
    live_keys: int = 2048  # keys the peer INCs live during the load
    reads: int = 2000  # sampled reads per big type
    conns: int = 4
    heartbeat: float = 10.0  # upstream's default (BASELINE.md); flush on write is 500 ms

    @classmethod
    def cut_to(cls, keys: int) -> "Plan":
        """The deployment cut to ``keys`` PNCOUNT/TREG keys, the sizes that
        hang off the key count cut in proportion."""
        full = cls()

        def part(n: int, floor: int) -> int:
            return min(n, max(floor, n * keys // full.keys))

        return dataclasses.replace(
            full, keys=keys, foreign_keys=part(full.foreign_keys, 64),
            treg_peer_keys=part(full.treg_peer_keys, 256),
            live_keys=part(full.live_keys, 64), gcount_keys=part(full.gcount_keys, 64),
        )

    @classmethod
    def tiny(cls) -> "Plan":
        return dataclasses.replace(
            cls.cut_to(5000), tlog_keys=2, reads=300, conns=2, heartbeat=0.3
        )

    def reduced(self) -> dict:
        """Cuts of SCALE against the source (BASELINE.json / upstream)."""
        full = Plan()
        out = {
            "foreign_cells": (
                f"{self.foreign_keys} of {self.keys} PNCOUNT keys carry the "
                f"{self.replicas - 1} foreign columns (every key carries the "
                "own column; all columns non-empty) — host-side dict cost of "
                "the cluster path inside the time limit"
            ),
            "tlog": f"{self.tlog_keys} keys x {self.tlog_entries} entries "
                    "(BASELINE config 4: 10k x 1k)",
            "ujson_replicas": "2 writers (BASELINE config 5: 32)",
        }
        if self.keys != full.keys:
            out["keys"] = f"{self.keys} (deployment: {full.keys})"
        if self.heartbeat != full.heartbeat:
            out["heartbeat_s"] = f"{self.heartbeat} (upstream default {full.heartbeat})"
        return out

    def assumed(self) -> dict:
        """Widths the source does not give."""
        return {
            "treg_value_bytes": self.treg_value_bytes,
            "tensor": f"MAX mode, dim {self.tensor_dim} f32",
            "key_bytes": 10,
            "pncount_values": "1/16 of foreign cells are 63-bit, rest < 2^20",
        }


# ---- seed-made data and the plain reference ----------------------------------


def pn_key(i: int) -> bytes:
    return b"pn:%07d" % i


def tr_key(i: int) -> bytes:
    return b"tr:%07d" % i


def gc_key(i: int) -> bytes:
    return b"gc:%07d" % i


def tl_key(i: int) -> bytes:
    return b"tl:%07d" % i


def te_key(i: int) -> bytes:
    return b"te:%07d" % i


def uj_key(i: int) -> bytes:
    return b"uj:%07d" % i


def wrap_i64(v: int) -> int:
    v &= U64
    return v - (1 << 64) if v >= (1 << 63) else v


class Data:
    """Everything the run writes, made from the seed, plus the expected
    answers by plain arithmetic (numpy u64 wrapping sums, tuple max for
    LWW, ops/hostref.TLog) — none of it the code under test."""

    def __init__(self, plan: Plan, seed: int, peer_rid: int):
        self.plan = p = plan
        rng = np.random.default_rng(seed)
        nf = p.replicas - 1  # foreign columns: nf-1 synthetic + the peer
        rids = set()
        while len(rids) < nf - 1:
            r = int(rng.integers(1, 1 << 63))
            if r != peer_rid:
                rids.add(r)
        self.foreign_rids = sorted(rids) + [peer_rid]  # peer is the last column

        # PNCOUNT: own column of EVERY key over RESP (INC all, DEC 1 in 8)
        self.pn_inc = rng.integers(1, 1000, p.keys).astype(np.uint64)
        self.pn_dec = np.where(
            np.arange(p.keys) % 8 == 0, rng.integers(1, 1000, p.keys), 0
        ).astype(np.uint64)
        # ... and the foreign columns of a spread of keys, via the peer
        self.fk = np.sort(rng.choice(p.keys, p.foreign_keys, replace=False))
        small = rng.integers(1, 1 << 20, (p.foreign_keys, nf)).astype(np.uint64)
        big = rng.integers(1 << 40, 1 << 63, (p.foreign_keys, nf)).astype(np.uint64)
        pick = rng.integers(0, 16, (p.foreign_keys, nf)) == 0
        self.f_p = np.where(pick, big, small)
        self.f_n = np.where(
            rng.integers(0, 2, (p.foreign_keys, nf)) == 0, 0, self.f_p >> np.uint64(3)
        ).astype(np.uint64)
        # live PNCOUNT INCs on the peer (its own column) during the load
        self.live = np.sort(rng.choice(p.keys, p.live_keys, replace=False))
        self.live_inc = rng.integers(1, 50, p.live_keys).astype(np.uint64)
        # after convergence: a handful more, so a SPARSE drain also runs
        self.late = self.fk[:: max(1, p.foreign_keys // 16)][:16]
        self.late_inc = np.arange(1, len(self.late) + 1, dtype=np.uint64)

        # GCOUNT: own INC over RESP, a few foreign columns via the peer
        self.gc_inc = rng.integers(1, 1000, p.gcount_keys).astype(np.uint64)
        self.gc_f = rng.integers(
            1, 1 << 40, (p.gcount_keys, p.gcount_replicas)
        ).astype(np.uint64)

        # TREG: every key SET over RESP at a random timestamp; the peer's
        # snapshot SET a spread of the same keys, so either side can win —
        # 1 in 16 of those at an EQUAL timestamp with a value sharing the
        # first 8 bytes (the device's prefix-rank tie, settled on the host)
        self.tr_ts = rng.integers(1, 1 << 62, p.keys, dtype=np.uint64)
        self.tr_tag = rng.integers(0, 1 << 31, p.keys)
        self.trk = np.sort(rng.choice(p.keys, p.treg_peer_keys, replace=False))
        tie = rng.integers(0, 16, p.treg_peer_keys) == 0
        self.trp_ts = np.where(  # both u64: a mixed where() would go via f64
            tie, self.tr_ts[self.trk],
            rng.integers(1, 1 << 62, p.treg_peer_keys, dtype=np.uint64),
        )
        self.trp_tag = rng.integers(0, 1 << 31, p.treg_peer_keys)

        # TLOG: the peer's snapshot holds a few old entries per key; the
        # clients INS ~1k more and TRIM. Key 0 carries 64-bit timestamps
        # (the wide plane layout), the rest fit the narrow one.
        self.tl_peer = [
            [(b"p%05d" % j, self._tl_ts(k, 1 + int(t)))
             for j, t in enumerate(rng.choice(1 << 16, 50, replace=False))]
            for k in range(p.tlog_keys)
        ]
        self.tl_ins = [
            [(b"e%05d" % j, self._tl_ts(k, (1 << 16) + int(t)))
             for j, t in enumerate(rng.choice(1 << 20, p.tlog_entries, replace=False))]
            for k in range(p.tlog_keys)
        ]

        # TENSOR (MAX): every key SET on the chip node, every other key
        # also SET on the peer — element-wise max, negatives included
        self.te_a = rng.normal(0, 10, (p.tensor_keys, p.tensor_dim)).astype("<f4")
        self.te_b = rng.normal(0, 10, (p.tensor_keys, p.tensor_dim)).astype("<f4")

        # UJSON: a few values per key on the chip node, a fan-in of
        # single-value deltas per key from the peer (one flush each)
        self.uj_own = [[10_000 + k * 10 + j for j in range(3)] for k in range(p.ujson_keys)]
        self.uj_rounds = 0  # fan-in rounds sent through the peer so far
        self.late_applied = False

    @staticmethod
    def _tl_ts(k: int, t: int) -> int:
        return t + (1 << 40) if k == 0 else t

    def tr_value(self, tag: int) -> bytes:
        # a shared 8-byte prefix: every equal-ts conflict is a prefix tie
        return (b"value-00%0*x" % (self.plan.treg_value_bytes - 8, int(tag)))[
            : self.plan.treg_value_bytes
        ]

    # -- the peer's snapshot (wire-delta shaped batches, persist.py) --------

    def peer_batches(self):
        p = self.plan
        rids = self.foreign_rids
        pn = []
        for j, i in enumerate(self.fk):
            rp, rn = self.f_p[j], self.f_n[j]
            pn.append((
                pn_key(int(i)),
                ({r: int(v) for r, v in zip(rids, rp)},
                 {r: int(v) for r, v in zip(rids, rn) if v}),
            ))
        gc = [
            (gc_key(i), {r: int(v) for r, v in zip(rids[-p.gcount_replicas:], self.gc_f[i])})
            for i in range(p.gcount_keys)
        ]
        tr = [
            (tr_key(int(i)), (self.tr_value(self.trp_tag[j] | (1 << 31)), int(self.trp_ts[j])))
            for j, i in enumerate(self.trk)
        ]
        tl = [(tl_key(k), (ents, 0)) for k, ents in enumerate(self.tl_peer)]
        by_name = {"PNCOUNT": pn, "GCOUNT": gc, "TREG": tr, "TLOG": tl}
        return [(n, by_name.get(n, [])) for n in DATA_TYPE_NAMES + ("SYSTEM",)]

    # -- expected answers ---------------------------------------------------

    def expect_pncount(self, idx: np.ndarray) -> list[int]:
        p = self.pn_inc.copy()
        n = self.pn_dec.copy()
        p[self.fk] += self.f_p.sum(axis=1, dtype=np.uint64)
        n[self.fk] += self.f_n.sum(axis=1, dtype=np.uint64)
        np.add.at(p, self.live, self.live_inc)
        if self.late_applied:
            np.add.at(p, self.late, self.late_inc)
        return [wrap_i64(int(p[i]) - int(n[i])) for i in idx]

    def expect_gcount(self, idx) -> list[int]:
        tot = self.gc_inc + self.gc_f.sum(axis=1, dtype=np.uint64)
        return [int(tot[i]) & U64 for i in idx]

    def expect_treg(self, idx) -> list[list]:
        peer = {int(i): j for j, i in enumerate(self.trk)}
        out = []
        for i in idx:
            i = int(i)
            best = (int(self.tr_ts[i]), self.tr_value(self.tr_tag[i]))
            j = peer.get(i)
            if j is not None:
                best = max(best, (int(self.trp_ts[j]),
                                  self.tr_value(self.trp_tag[j] | (1 << 31))))
            out.append([best[1], best[0]])
        return out

    def expect_tlog(self, k: int) -> hostref.TLog:
        log = hostref.TLog()
        for value, ts in self.tl_peer[k] + self.tl_ins[k]:
            log.insert(value, ts)
        log.trim(self.plan.tlog_trim)
        return log

    def expect_tensor(self, i: int) -> bytes:
        v = self.te_a[i]
        if i % 2 == 0:
            v = np.maximum(v, self.te_b[i])
        return v.astype("<f4").tobytes()

    def uj_peer(self, k: int, rnd: int) -> list[int]:
        base = 100_000 * (rnd + 1) + k * 1000
        return [base + j for j in range(self.plan.ujson_fanin)]

    def expect_ujson(self, k: int) -> list[int]:
        return sorted(
            self.uj_own[k]
            + [v for r in range(self.uj_rounds) for v in self.uj_peer(k, r)]
        )


# ---- child processes ---------------------------------------------------------


_DEVICE_RE = re.compile(
    r"device: platform=(\S+) kind='([^']*)' count=(\d+) mesh=(\S+)"
)


class Node:
    """One ``python -m jylis_tpu`` child and the log it writes."""

    def __init__(self, name: str, cport: int, data_dir: str, env: dict,
                 heartbeat: float, seed_addr: str = ""):
        self.name = name
        self.port = free_port()
        self.cport = cport
        self.addr = f"{HOST}:{self.cport}:{name}"
        self.rid = Address(HOST, str(self.cport), name).hash64()
        self.data_dir = data_dir
        self.env = env
        self.heartbeat = heartbeat
        self.seed_addr = seed_addr
        self.proc: subprocess.Popen | None = None
        self.lines: list[tuple[float, str]] = []  # (seconds since spawn, line)
        self._t0 = 0.0
        self._reader: threading.Thread | None = None

    def spawn(self) -> None:
        argv = [
            sys.executable, "-m", "jylis_tpu",
            "--port", str(self.port), "--addr", self.addr,
            "--data-dir", self.data_dir,
            "--heartbeat-time", str(self.heartbeat),
            "--log-level", "info",
        ]
        if self.seed_addr:
            argv += ["--seed-addrs", self.seed_addr]
        self.lines = []
        self._t0 = time.monotonic()
        self.proc = subprocess.Popen(
            argv, cwd=REPO, env=self.env, stdout=subprocess.DEVNULL,
            stderr=subprocess.PIPE, text=True, errors="replace",
        )
        self._reader = threading.Thread(target=self._read_log, daemon=True)
        self._reader.start()

    def _read_log(self) -> None:
        assert self.proc is not None and self.proc.stderr is not None
        for line in self.proc.stderr:
            self.lines.append((time.monotonic() - self._t0, line.rstrip("\n")))

    def log_time(self, needle: str) -> float | None:
        for t, line in list(self.lines):
            if needle in line:
                return t
        return None

    def log_match(self, pattern: re.Pattern):
        for _t, line in list(self.lines):
            m = pattern.search(line)
            if m:
                return m
        return None

    def wait_serving(self, timeout: float) -> float:
        """Seconds from spawn to the boot log's serving line (the RESP
        port answers by then: the line follows server.start())."""
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            assert self.proc is not None
            if self.proc.poll() is not None:
                raise SmokeFailure(
                    f"{self.name} exited rc={self.proc.returncode} during boot:\n"
                    + self.tail()
                )
            t = self.log_time("serving clients on port")
            if t is not None:
                with Client(HOST, self.port, timeout=60) as c:
                    check(c.execute_command("SYSTEM", "VERSION"), "no VERSION reply")
                return t
            time.sleep(0.1)
        raise SmokeFailure(f"{self.name} not serving after {timeout}s:\n" + self.tail())

    def device(self) -> dict:
        m = self.log_match(_DEVICE_RE)
        check(m is not None, f"{self.name}: no device line in the boot log")
        return {"platform": m.group(1), "kind": m.group(2),
                "count": int(m.group(3)), "mesh": m.group(4)}

    def save_log(self) -> None:
        """Append this boot's log to .scratch/chip_smoke_logs/<name>.log."""
        os.makedirs(LOG_DIR, exist_ok=True)
        with open(os.path.join(LOG_DIR, f"{self.name}.log"), "a") as f:
            f.write(f"---- boot, pid {self.proc.pid if self.proc else '?'} ----\n")
            f.writelines(f"{t:9.2f} {line}\n" for t, line in self.lines)

    def stop(self, timeout: float) -> int:
        """SIGTERM, wait, return the exit code (kill on timeout)."""
        assert self.proc is not None
        if self.proc.poll() is None:
            self.proc.send_signal(signal.SIGTERM)
        try:
            rc = self.proc.wait(timeout=timeout)
        except subprocess.TimeoutExpired:
            self.proc.kill()
            self.proc.wait(timeout=30)
            raise SmokeFailure(
                f"{self.name} ignored SIGTERM for {timeout}s:\n" + self.tail()
            ) from None
        if self._reader is not None:
            self._reader.join(timeout=10)
        self.save_log()
        return rc

    def kill(self) -> None:
        """Last resort of the run's ``finally``: a node still alive here
        was not stopped by a passing run."""
        if self.proc is not None and self.proc.poll() is None:
            self.proc.kill()
            self.proc.wait(timeout=30)
            self.save_log()
            print(f"{self.name} was still running; its log ends:\n{self.tail()}",
                  file=sys.stderr)

    def tail(self, n: int = 40) -> str:
        return "\n".join(f"  {self.name}| {line}" for _t, line in self.lines[-n:])

    def check_log_clean(self) -> None:
        bad = [
            line for _t, line in self.lines
            if line.startswith("(E) ") or line.startswith("Traceback (most recent")
        ]
        check(not bad, f"{self.name}: error-level log lines:\n" + "\n".join(bad[:20]))


def backend_initialised() -> bool:
    """Has THIS process initialised a jax backend? (It must not have, on
    the chip: a parent that touched jax holds the chip and the node child
    then fails or hangs. It is pinned to the CPU besides.)"""
    from jax._src import xla_bridge

    return xla_bridge.backends_are_initialized()


def build_native() -> str:
    """Build libjylis_native.so from native/*.cpp NOW (build() replaces
    whatever binary the tree came with); returns the source hash it was
    built from. The nodes then load exactly this build — the loader's
    staleness check is the same hash."""
    check(native.loads_checkout_build(),
          "JYLIS_NATIVE_SO or a bundled .so would be loaded, not this run's build")
    check(native.build(), "native library build failed (g++, native/*.cpp)")
    check(native.built_hash() == native.source_hash(),
          "native build stamp does not match native/*.cpp")
    return native.source_hash()


def cache_entries() -> int:
    try:
        return len(os.listdir(COMPILE_CACHE_DIR))
    except FileNotFoundError:
        return 0


# Run as `python -c` in a child of its own while NO node holds the chip:
# compiles the sparse PNCOUNT drain for the first device, at the block one
# device holds, and prints what the compiler made of it.
_DRAIN_COMPILE = r"""
import json, re, sys
import jax, jax.numpy as jnp
import jylis_tpu
from jylis_tpu.models.repo_counters import _drain_pn
k, w, b = map(int, sys.argv[1:])
dev = jax.devices()[0]
one = jax.sharding.SingleDeviceSharding(dev)
S = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one)
c = _drain_pn.lower(
    S((k, w), jnp.uint32), S((b,), jnp.int32), S((b, w), jnp.uint32)
).compile()
copies = re.findall(r"= u32\[%d,%d\]\S* copy\(" % (k, w), c.as_text())
print(json.dumps({
    "platform": dev.platform, "plane": [k, w], "rows": b,
    "plane_bytes": 4 * k * w,
    "temp_bytes": c.memory_analysis().temp_size_in_bytes,
    "plane_copies": len(copies),
}))
"""


def sparse_drain_compile(shape: list[int], devices: int, rows: int,
                         expect_platform: str) -> dict:
    """What the device's compiler makes of `_drain_pn` at the smoke's
    shapes: a sparse drain must touch its rows, not its plane (PERF.md, PR
    29: a row gather out of a column-major plane made the TPU compiler
    transpose the whole plane first). The copies are that compiler's, so
    off the accelerator there is nothing to check."""
    if expect_platform == "cpu":
        return {"skipped": "off-accelerator: the plane copies are the TPU compiler's"}
    out = subprocess.run(
        [sys.executable, "-c", _DRAIN_COMPILE,
         str(shape[0] // devices), str(shape[1]), str(rows)],
        cwd=REPO, env=CHILD_ENV, capture_output=True, text=True, timeout=600,
    )
    check(out.returncode == 0, f"sparse drain compile failed:\n{out.stderr[-2000:]}")
    got = json.loads(out.stdout.strip().splitlines()[-1])
    check(got["platform"] == expect_platform, f"compiled for {got['platform']!r}")
    check(got["plane_copies"] == 0 and got["temp_bytes"] < got["plane_bytes"],
          f"the sparse PNCOUNT drain copies its plane: {got}")
    return got


# ---- RESP legs ---------------------------------------------------------------


def bulk_write(port: int, commands, chunk: int = 2000) -> int:
    """Pipeline write commands in chunks; every reply must be +OK (an
    acknowledged write). Returns the number acknowledged."""
    acked = 0
    with socket.create_connection((HOST, port), timeout=600) as sock:
        batch: list[bytes] = []

        def flush() -> None:
            nonlocal acked
            sock.sendall(b"".join(batch))
            want = 5 * len(batch)
            got = bytearray()
            while len(got) < want:
                part = sock.recv(want - len(got))
                check(part, "connection closed mid-pipeline")
                got += part
            check(bytes(got) == b"+OK\r\n" * len(batch),
                  f"write not acknowledged: {bytes(got[:200])!r}")
            acked += len(batch)
            batch.clear()

        for c in commands:
            batch.append(c)
            if len(batch) >= chunk:
                flush()
        if batch:
            flush()
    return acked


def in_threads(jobs) -> list:
    """Run callables concurrently; the first failure is re-raised."""
    with ThreadPoolExecutor(max_workers=len(jobs)) as pool:
        return [f.result() for f in [pool.submit(fn) for fn in jobs]]


def pipelined(port: int, commands, chunk: int = 500) -> list:
    out = []
    with Client(HOST, port, timeout=600) as c:
        for i in range(0, len(commands), chunk):
            out.extend(c.pipeline_execute(commands[i : i + chunk]))
    return out


def digest(port: int) -> bytes:
    with Client(HOST, port, timeout=900) as c:
        return bytes(c.execute_command("SYSTEM", "DIGEST"))


def wait_converged(chip: Node, peer: Node, timeout: float, what: str) -> str:
    """Poll SYSTEM DIGEST on both nodes until equal (each call folds only
    the keys dirty since the last one)."""
    t0 = time.monotonic()
    while True:
        a, b = in_threads([lambda: digest(chip.port), lambda: digest(peer.port)])
        if a == b:
            say(f"{what}: digests equal after {time.monotonic() - t0:.1f}s ({a[:16].decode()}…)")
            return a.decode()
        check(time.monotonic() - t0 < timeout,
              f"{what}: digests still differ after {timeout}s "
              f"(chip {a[:16]!r} peer {b[:16]!r})")
        time.sleep(1.0)


def metrics(port: int) -> dict:
    with Client(HOST, port, timeout=120) as c:
        lines = c.execute_command("SYSTEM", "METRICS")
    out = {}
    for line in lines:
        section, key, value = bytes(line).decode().split(" ", 2)
        out[f"{section} {key}"] = float(value)
    return out


def load_chip(chip: Node, peer: Node, d: Data) -> dict:
    """The main load: every PNCOUNT and TREG key over RESP into the chip
    node on several pipelined connections, while the peer takes live
    writes and a reader keeps reading from the chip node."""
    p = d.plan
    stop_reading = threading.Event()

    def writer(lane: int):
        def gen():
            for i in range(lane, p.keys, p.conns):
                yield pack_command(b"PNCOUNT", b"INC", pn_key(i), b"%d" % d.pn_inc[i])
                if d.pn_dec[i]:
                    yield pack_command(b"PNCOUNT", b"DEC", pn_key(i), b"%d" % d.pn_dec[i])
                yield pack_command(b"TREG", b"SET", tr_key(i), d.tr_value(d.tr_tag[i]),
                                   b"%d" % d.tr_ts[i])
        return lambda: bulk_write(chip.port, gen())

    def peer_live():
        return bulk_write(peer.port, (
            pack_command(b"PNCOUNT", b"INC", pn_key(int(i)), b"%d" % d.live_inc[j])
            for j, i in enumerate(d.live)
        ), chunk=64)

    def writers():
        try:
            return in_threads([writer(k) for k in range(p.conns)] + [peer_live])
        finally:
            stop_reading.set()

    def reader():
        # reads while writes land: the answers move, so only the reply
        # SHAPE is checked here; exact values are checked after convergence.
        # GETs of keys holding foreign columns force PNCOUNT drains at the
        # capacities the planes grow through.
        rng = np.random.default_rng(7)
        n = 0
        with Client(HOST, chip.port, timeout=600) as c:
            while not stop_reading.is_set():
                fk = [int(i) for i in rng.choice(d.fk, 8)]
                anyk = [int(i) for i in rng.integers(0, p.keys, 8)]
                got = c.pipeline_execute(
                    [("PNCOUNT", "GET", pn_key(i)) for i in fk]
                    + [("TREG", "GET", tr_key(i)) for i in anyk]
                )
                check(all(isinstance(v, int) for v in got[:8]), f"PNCOUNT GET: {got[:8]!r}")
                check(all(v is None or (isinstance(v, list) and len(v) == 2)
                          for v in got[8:]), f"TREG GET: {got[8:]!r}")
                n += len(got)
                # each such GET drains EVERYTHING pending under the PNCOUNT
                # lock (writers wait): a reader that never pauses would
                # turn the load into back-to-back million-row drains
                stop_reading.wait(1.0)
        return n

    t0 = time.monotonic()
    acked, n_reads = in_threads([writers, reader])
    dt = time.monotonic() - t0
    n = sum(acked[:-1])
    say(f"load: {n} writes acknowledged by the chip node in {dt:.1f}s "
        f"({n / dt:.0f}/s), {acked[-1]} live on the peer, "
        f"{n_reads} reads served meanwhile")
    return {"writes_acked": n, "seconds": round(dt, 2), "peer_live_writes": acked[-1],
            "concurrent_reads": n_reads}


def small_types(chip: Node, peer: Node, d: Data) -> None:
    """GCOUNT, TLOG (~1k-entry rows + TRIM), TENSOR (past its drain
    threshold) over RESP on the chip node; TENSOR on the peer too."""
    p = d.plan
    n = bulk_write(chip.port, (
        pack_command(b"GCOUNT", b"INC", gc_key(i), b"%d" % d.gc_inc[i])
        for i in range(p.gcount_keys)
    ))
    for k in reversed(range(p.tlog_keys)):  # narrow rows first, key 0 widens
        n += bulk_write(chip.port, (
            pack_command(b"TLOG", b"INS", tl_key(k), v, b"%d" % ts) for v, ts in d.tl_ins[k]
        ))
        n += bulk_write(chip.port, [pack_command(b"TLOG", b"TRIM", tl_key(k), b"%d" % p.tlog_trim)])
    n += bulk_write(chip.port, (
        pack_command(b"TENSOR", b"SET", te_key(i), b"MAX", b"0", d.te_a[i].tobytes())
        for i in range(p.tensor_keys)
    ))
    m = bulk_write(peer.port, (
        pack_command(b"TENSOR", b"SET", te_key(i), b"MAX", b"0", d.te_b[i].tobytes())
        for i in range(0, p.tensor_keys, 2)
    ))
    say(f"small types: {n} writes acknowledged by the chip node, {m} by the peer")


def ujson_fanin(chip: Node, peer: Node, d: Data) -> None:
    """The UJSON segmented device fold needs >= SEG_FANIN_MIN pending
    remote deltas on several keys at once: the peer ships one delta per
    SESSION WRAPped write (a wrapped write forces a cluster flush), and
    the chip node folds them at its next full UJSON drain — which
    SYSTEM DIGEST runs. A periodic digest exchange landing mid-round
    splits the fan-in (a host fold, not a drain), so a round is repeated
    with fresh values until the device fold has run."""
    p = d.plan
    bulk_write(chip.port, (
        pack_command(b"UJSON", b"INS", uj_key(k), b"tags", b"%d" % v)
        for k in range(p.ujson_keys) for v in d.uj_own[k]
    ))
    for _ in range(4):
        vals = [d.uj_peer(k, d.uj_rounds) for k in range(p.ujson_keys)]
        d.uj_rounds += 1
        cmds = [
            ("SESSION", "WRAP", "UJSON", "INS", uj_key(k), "tags", str(vals[k][j]))
            for j in range(p.ujson_fanin) for k in range(p.ujson_keys)
        ]
        for reply in pipelined(peer.port, cmds, chunk=64):
            check(isinstance(reply, list) and reply[0] == b"OK",
                  f"SESSION WRAP: {reply!r}")
        time.sleep(1.0)  # in flight -> pending on the chip node
        digest(chip.port)
        if metrics(chip.port).get("UJSON drains", 0) > 0:
            say(f"ujson: device fold ran after {d.uj_rounds} fan-in round(s) of "
                f"{len(cmds)} single-delta flushes through the peer")
            return
    raise SmokeFailure("UJSON: no device fold after 4 fan-in rounds")


def late_sparse(chip: Node, peer: Node, d: Data, plane_rows: int) -> None:
    """A handful of live INCs on the peer once nothing else is pending: on
    the chip node they drain as a batch far under 1/DENSE_FRACTION of the
    keyspace — the sparse scatter path, on planes at their grown size.
    (Own writes never drain by themselves, so this must come AFTER a read
    has drained the load: before that, any drain is the million-row dense
    one.)"""
    before = metrics(chip.port)
    bulk_write(peer.port, (
        pack_command(b"PNCOUNT", b"INC", pn_key(int(i)), b"%d" % d.late_inc[j])
        for j, i in enumerate(d.late)
    ))
    d.late_applied = True
    want = d.expect_pncount(d.late)
    deadline = time.monotonic() + 120
    while True:
        got = pipelined(chip.port, [("PNCOUNT", "GET", pn_key(int(i))) for i in d.late])
        if got == want:
            break
        check(time.monotonic() < deadline, f"late INCs not visible: {got!r} != {want!r}")
        time.sleep(0.3)
    after = metrics(chip.port)
    drains = after.get("PNCOUNT drains", 0) - before.get("PNCOUNT drains", 0)
    rows = after.get("PNCOUNT keys", 0) - before.get("PNCOUNT keys", 0)
    check(drains > 0, "late INCs were read back without a PNCOUNT drain")
    check(rows * 4 < plane_rows,
          f"the late drain(s) covered {rows} rows: not the sparse path")
    say(f"sparse drain: {len(want)} late peer INCs visible on the chip node "
        f"({int(drains)} drain(s), {int(rows)} rows)")


def verify_reads(port: int, d: Data, who: str) -> int:
    """Seeded reads across every type against the plain reference."""
    p = d.plan
    rng = np.random.default_rng(11)
    n = 0

    def compare(name, keys, cmds, want):
        nonlocal n
        got = pipelined(port, cmds)
        bad = [(k, g, w) for k, g, w in zip(keys, got, want) if g != w]
        check(not bad, f"{who} {name}: {len(bad)}/{len(want)} reads differ from "
                       f"the reference, first: {bad[:3]!r}")
        n += len(want)

    half = p.reads // 2
    idx = np.concatenate([rng.choice(d.fk, min(half, len(d.fk)), replace=False),
                          rng.integers(0, p.keys, half), d.live[:64]])
    compare("PNCOUNT", idx, [("PNCOUNT", "GET", pn_key(int(i))) for i in idx],
            d.expect_pncount(idx))
    idx = np.concatenate([rng.choice(d.trk, min(half, len(d.trk)), replace=False),
                          rng.integers(0, p.keys, half)])
    compare("TREG", idx, [("TREG", "GET", tr_key(int(i))) for i in idx],
            d.expect_treg(idx))
    idx = rng.integers(0, p.gcount_keys, min(200, p.gcount_keys))
    compare("GCOUNT", idx, [("GCOUNT", "GET", gc_key(int(i))) for i in idx],
            d.expect_gcount(idx))
    for k in range(p.tlog_keys):
        ref = d.expect_tlog(k)
        compare(f"TLOG {k}", ["SIZE", "CUTOFF", "GET 10", "GET"],
                [("TLOG", "SIZE", tl_key(k)), ("TLOG", "CUTOFF", tl_key(k)),
                 ("TLOG", "GET", tl_key(k), 10), ("TLOG", "GET", tl_key(k))],
                [ref.size(), ref.cutoff, [[v, ts] for v, ts in ref.latest(10)],
                 [[v, ts] for v, ts in ref.latest()]])
    idx = rng.integers(0, p.tensor_keys, min(200, p.tensor_keys))
    compare("TENSOR", idx, [("TENSOR", "GET", te_key(int(i))) for i in idx],
            [[b"MAX", d.expect_tensor(int(i)), 0] for i in idx])
    got = pipelined(port, [("UJSON", "GET", uj_key(k), "tags") for k in range(p.ujson_keys)])
    for k, g in enumerate(got):
        check(sorted(json.loads(g)) == d.expect_ujson(k),
              f"{who} UJSON {k}: {g[:120]!r}")
        n += 1
    say(f"{who}: {n} reads equal to the reference")
    return n


# ---- the run -----------------------------------------------------------------

DRAIN_TYPES = ("GCOUNT", "PNCOUNT", "TREG", "TLOG", "UJSON", "TENSOR")
_STATE_RE = re.compile(r"(\w+) ([\dx]+) over (\d+) device")
_MEM_RE = re.compile(r"dev(\d+) in_use=(\w+) peak=(\w+)")


def shutdown_report(node: Node) -> dict:
    """Plane shapes, device spread and HBM from the shutdown log lines."""
    state, mem = {}, {}
    for _t, line in node.lines:
        if "device state:" in line:
            for name, shape, n in _STATE_RE.findall(line):
                state[name] = {"shape": [int(x) for x in shape.split("x")],
                               "devices": int(n)}
        if "device memory:" in line:
            for dev, in_use, peak in _MEM_RE.findall(line):
                mem[f"dev{dev}"] = {
                    "in_use": int(in_use) if in_use.isdigit() else None,
                    "peak": int(peak) if peak.isdigit() else None,
                }
    return {"state": state, "memory": mem}


def boot_report(node: Node, t_serving: float) -> dict:
    warm = node.log_match(re.compile(
        r"warmup: backend up in ([\d.]+)s, serving kernels ready in ([\d.]+)s"
    ))
    check(warm is not None, f"{node.name}: no warmup line in the boot log")
    t_warm = node.log_time("warmup: backend up in")
    t_rest = node.log_time("snapshot restored")
    return {
        "to_serving_s": round(t_serving, 2),
        "backend_init_s": float(warm.group(1)),
        "warmup_s": float(warm.group(2)),  # compile, or compile-cache load
        "restore_s": round(t_rest - t_warm, 2) if t_rest is not None else 0.0,
    }


def run(plan: Plan, seed: int, expect_platform: str, workdir: str) -> dict:
    t_start = time.monotonic()
    src_hash = build_native()
    say(f"native library built from native/*.cpp ({src_hash[:12]})")

    peer_env = dict(CHILD_ENV, JAX_PLATFORMS="cpu")
    peer = Node("smoke-peer", PEER_CLUSTER_PORT, os.path.join(workdir, "peer"),
                peer_env, plan.heartbeat)
    chip = Node("smoke-chip", CHIP_CLUSTER_PORT, os.path.join(workdir, "chip"),
                dict(CHILD_ENV), plan.heartbeat, seed_addr=peer.addr)
    check(peer.rid != chip.rid, "replica id collision")
    data = Data(plan, seed, peer.rid)
    os.makedirs(peer.data_dir)
    persist.write_snapshot(
        data.peer_batches(), os.path.join(peer.data_dir, "snapshot.jylis")
    )
    cells = int((data.f_p > 0).sum() + (data.f_n > 0).sum())
    say(f"peer snapshot written: {plan.foreign_keys} PNCOUNT keys x "
        f"{plan.replicas - 1} foreign replica ids ({cells} (key, replica, "
        f"polarity) cells), {plan.treg_peer_keys} TREG, {plan.gcount_keys} "
        f"GCOUNT, {plan.tlog_keys} TLOG keys")
    check(not backend_initialised(),
          "the smoke's parent initialised a jax backend before spawning")
    say("parent pinned to the CPU with no jax backend initialised; "
        "spawning the nodes")
    try:
        # the peer first and fully up: the chip node's first dial (at its
        # boot) then finds it, and its rejoin pull is served at once rather
        # than deferred behind a pull the peer started the other way
        peer.spawn()
        say(f"peer (CPU) serving after {peer.wait_serving(600):.1f}s")
        check(peer.device()["platform"] == "cpu", "the peer must be pinned to the CPU")

        entries_before = cache_entries()
        chip.spawn()
        boot1 = boot_report(chip, chip.wait_serving(900))
        dev = chip.device()
        say(f"chip node serving after {boot1['to_serving_s']}s "
            f"(warmup {boot1['warmup_s']}s): {dev}")
        check(dev["platform"] == expect_platform,
              f"the node came up on platform {dev['platform']!r} "
              f"(kind {dev['kind']!r}, {dev['count']} device(s)), not "
              f"{expect_platform!r}: no accelerator, no result")
        check(chip.log_time("serving engine: native") is not None,
              "the chip node serves from the Python tables, not the native engine")
        entries_boot1 = cache_entries()

        wait_converged(chip, peer, 600, "rejoin sync")
        load = load_chip(chip, peer, data)
        small_types(chip, peer, data)
        ujson_fanin(chip, peer, data)
        wait_converged(chip, peer, 900, "after load")
        # the first read of a row holding undrained foreign deltas drains
        # everything pending: the million-row dense join on the device
        n_reads = verify_reads(chip.port, data, "chip node")
        late_sparse(chip, peer, data, min(1 << 20, plan.keys))
        n_reads += verify_reads(peer.port, data, "CPU peer")
        digest1 = wait_converged(chip, peer, 300, "after late writes")

        m = metrics(chip.port)
        per_type = {
            t: {k: m.get(f"{t} {k}", 0) for k in ("drains", "keys", "device_ms")}
            for t in DRAIN_TYPES
        }
        for t in DRAIN_TYPES:
            check(per_type[t]["drains"] > 0,
                  f"{t} drains == 0: that kernel family never ran on the device "
                  f"({per_type})")
        check(m.get("SERVING native_cmds", 0) > 0, "SERVING native_cmds == 0")
        serving = {k: m.get(f"SERVING {k}") for k in ("native_cmds", "demoted_cmds",
                                                      "fallback_frac")}
        say(f"chip node metrics: {per_type} {serving}")

        t0 = time.monotonic()
        rc = chip.stop(600)
        say(f"chip node SIGTERM -> rc {rc} in {time.monotonic() - t0:.1f}s")
        check(rc == 0, f"chip node exit code {rc} on SIGTERM:\n" + chip.tail())
        chip.check_log_clean()
        report1 = shutdown_report(chip)
        say(f"shutdown report: {report1}")
        pn = report1["state"].get("PNCOUNT")
        check(pn is not None and pn["shape"][0] >= min(1 << 20, plan.keys)
              and pn["shape"][1] >= plan.replicas, f"PNCOUNT planes: {pn}")
        tr = report1["state"].get("TREG")
        check(tr is not None and tr["shape"][0] >= plan.keys, f"TREG planes: {tr}")
        for t in ("GCOUNT", "PNCOUNT", "TREG", "TLOG"):
            check(report1["state"][t]["devices"] == dev["count"],
                  f"{t} planes are on {report1['state'][t]['devices']} of "
                  f"{dev['count']} devices")

        # no node holds the chip between the two boots
        drain_compile = sparse_drain_compile(
            pn["shape"], dev["count"], bucket(plan.foreign_keys), expect_platform
        )
        say(f"sparse PNCOUNT drain as compiled: {drain_compile}")

        entries_before2 = cache_entries()
        chip.spawn()
        boot2 = boot_report(chip, chip.wait_serving(900))
        say(f"second boot serving after {boot2['to_serving_s']}s (warmup "
            f"{boot2['warmup_s']}s, snapshot restore {boot2['restore_s']}s)")
        check(chip.device() == dev, "second boot found another device")
        n_reads += verify_reads(chip.port, data, "chip node, second boot")
        digest2 = wait_converged(chip, peer, 600, "after restart")
        check(digest2 == digest1, "digest changed across the restart")
        m2 = metrics(chip.port)
        check(m2.get("PNCOUNT drains", 0) > 0, "restore ran no PNCOUNT drain")

        cache = {
            "dir": COMPILE_CACHE_DIR,
            "from_env": bool(CHILD_ENV.get("JAX_COMPILATION_CACHE_DIR")),
            "entries_before": entries_before,
            "entries_after_first_boot": entries_boot1,
            "entries_before_second_boot": entries_before2,
            "entries_after_second_boot": cache_entries(),
        }
        check(cache["entries_before_second_boot"] > 0, f"compile cache empty: {cache}")
        if entries_boot1 > entries_before:
            # the first boot compiled (a cold cache): the warm one must be
            # faster at the part the cache exists for, and like for like
            # (restore of the snapshot set aside) faster to serving
            check(boot2["warmup_s"] < boot1["warmup_s"],
                  f"warm warmup {boot2['warmup_s']}s not under cold {boot1['warmup_s']}s")
            check(boot2["to_serving_s"] - boot2["restore_s"] < boot1["to_serving_s"],
                  f"warm boot not faster: {boot2} vs {boot1}")

        rcs = in_threads([lambda: chip.stop(600), lambda: peer.stop(600)])
        check(rcs == [0, 0], f"exit codes on SIGTERM (chip, peer): {rcs}")
        chip.check_log_clean()
        peer.check_log_clean()

        return {
            "ok": True,
            "device": {k: dev[k] for k in ("platform", "kind", "count")},
            "mesh": dev["mesh"],
            "seed": seed,
            "sizes": {
                "pncount_keys": plan.keys, "pncount_planes": pn["shape"],
                "replica_ids": plan.replicas, "foreign_cells": cells,
                "treg_keys": plan.keys, "treg_planes": tr["shape"],
                "gcount_keys": plan.gcount_keys, "tlog_keys": plan.tlog_keys,
                "tlog_entries": plan.tlog_entries, "tensor_keys": plan.tensor_keys,
                "ujson_keys": plan.ujson_keys, "ujson_fanin": plan.ujson_fanin,
            },
            "reduced": plan.reduced(),
            "assumed": plan.assumed(),
            "load": load,
            "reads_equal_to_reference": n_reads,
            "digest": digest2,
            "per_type": per_type,
            "serving": serving,
            "over_the_wire": list(DRAIN_TYPES),
            "second_child": [],
            "sparse_drain_compile": drain_compile,
            "boot_first": boot1,
            "boot_second": boot2,
            "compile_cache": cache,
            "device_state": report1["state"],
            "hbm": report1["memory"],
            "native_source_hash": src_hash,
            "seconds": round(time.monotonic() - t_start, 1),
        }
    except BaseException:  # re-raised: only adds the nodes' own account
        for node in (chip, peer):
            print(f"---- {node.name}, end of log ----\n{node.tail(60)}",
                  file=sys.stderr)
        raise
    finally:
        chip.kill()
        peer.kill()


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=20260926)
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny sizes on the CPU platform (tests, debugging): "
                         "not a chip result")
    ap.add_argument("--keys", type=int, default=None,
                    help="cut the deployment to this many PNCOUNT/TREG keys "
                         "(a cheaper debugging run; listed under `reduced`)")
    args = ap.parse_args(argv)
    if args.keys is not None:
        plan = Plan.cut_to(args.keys)
    else:
        plan = Plan.tiny() if args.rehearse else Plan()
    # SIGTERM (a driver's timeout) must still stop the children: turn it
    # into an exception so the run's `finally` executes
    signal.signal(signal.SIGTERM, lambda *_: sys.exit(143))
    shutil.rmtree(LOG_DIR, ignore_errors=True)
    os.makedirs(SCRATCH, exist_ok=True)
    workdir = tempfile.mkdtemp(prefix="chip_smoke_", dir=SCRATCH)
    try:
        summary = run(plan, args.seed, "cpu" if args.rehearse else "tpu", workdir)
    finally:
        shutil.rmtree(workdir, ignore_errors=True)
    if args.rehearse:
        summary["rehearsal"] = True
    summary["claim"] = None
    print(json.dumps(summary))
    # the verdict line: exactly these keys, last on stdout
    print(json.dumps({"ok": summary["ok"], "device": summary["device"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
