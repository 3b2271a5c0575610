"""Child processes: the node under test and its CPU-pinned peers.

Copied in shape from ``chip_smoke.py`` (PR 21), not imported: a later PR may
change that gate. One process per chip: the parent pins ITSELF to the CPU
before jax is imported (``run.py`` does that first thing) and never touches
the chip; exactly one child, the node under test, is not pinned.
"""

from __future__ import annotations

import os
import re
import signal
import socket
import subprocess
import sys
import threading
import time
import urllib.request

from . import resp

HOST = "127.0.0.1"
REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
# FIXED cluster ports: a node's replica id is the hash of its advertised
# address and is part of the state (counter columns), so with the ports
# fixed one seed gives one state, run after run. Below the ephemeral range.
CLUSTER_PORT_BASE = 29481

_DEVICE_RE = re.compile(r"device: platform=(\S+) kind='([^']*)' count=(\d+) mesh=(\S+)")
_MEM_RE = re.compile(r"dev(\d+) in_use=(\w+) peak=(\w+)")
_METRICS_PORT_RE = re.compile(r"metrics endpoint on port: (\d+)")


class RunFailure(Exception):
    """The run cannot give a result: exit non-zero, print no result line."""


def free_port() -> int:
    with socket.socket() as s:
        s.bind((HOST, 0))
        return s.getsockname()[1]


class Node:
    """One ``python -m jylis_tpu`` child and the log it writes."""

    def __init__(self, name: str, index: int, data_dir: str, env: dict, flags: list[str]):
        self.name = name
        self.port = free_port()
        self.cport = CLUSTER_PORT_BASE + index
        self.addr = f"{HOST}:{self.cport}:{name}"
        self.data_dir = data_dir
        self.env = env
        self.flags = flags
        self.seed_addrs: list[str] = []
        self.proc: subprocess.Popen | None = None
        self.lines: list[tuple[float, str]] = []  # (time.monotonic(), line)
        self.t_spawn = 0.0
        self._reader: threading.Thread | None = None

    def spawn(self) -> None:
        argv = [sys.executable, "-m", "jylis_tpu", "--port", str(self.port),
                "--addr", self.addr, "--data-dir", self.data_dir,
                "--metrics-port", "-1", "--log-level", "info", *self.flags]
        if self.seed_addrs:
            argv += ["--seed-addrs", " ".join(self.seed_addrs)]
        self.t_spawn = time.monotonic()
        self.proc = subprocess.Popen(argv, cwd=REPO, env=self.env,
                                     stdout=subprocess.DEVNULL, stderr=subprocess.PIPE,
                                     text=True, errors="replace")
        self._reader = threading.Thread(target=self._read_log, daemon=True)
        self._reader.start()

    def _read_log(self) -> None:
        for line in self.proc.stderr:
            self.lines.append((time.monotonic(), line.rstrip("\n")))

    def match(self, pattern: re.Pattern):
        for _t, line in list(self.lines):
            m = pattern.search(line)
            if m:
                return m
        return None

    def time_of(self, needle: str) -> float | None:
        for t, line in list(self.lines):
            if needle in line:
                return t
        return None

    def wait_serving(self, timeout: float) -> None:
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            if self.proc.poll() is not None:
                raise RunFailure(f"{self.name} exited rc={self.proc.returncode} "
                                 f"during boot:\n{self.tail()}")
            if self.time_of("serving clients on port") is not None and (
                    self.match(_METRICS_PORT_RE)):
                with resp.Conn(HOST, self.port, timeout=60) as c:
                    if not c.call(b"SYSTEM", b"VERSION"):
                        raise RunFailure(f"{self.name}: no VERSION reply")
                return
            time.sleep(0.05)
        raise RunFailure(f"{self.name} not serving after {timeout}s:\n{self.tail()}")

    def device(self) -> dict:
        m = self.match(_DEVICE_RE)
        if m is None:
            raise RunFailure(f"{self.name}: no device line in the boot log")
        return {"platform": m.group(1), "kind": m.group(2), "count": int(m.group(3)),
                "mesh": m.group(4)}

    def memory_peak_bytes(self) -> int | None:
        """Peak HBM on the fullest device, from the shutdown log."""
        peaks = [int(peak) for _t, line in self.lines if "device memory:" in line
                 for _dev, _use, peak in _MEM_RE.findall(line) if peak.isdigit()]
        return max(peaks) if peaks else None

    def prom(self) -> dict[str, float]:
        """The node's /metrics exposition as ``{sample: value}``."""
        port = int(self.match(_METRICS_PORT_RE).group(1))
        with urllib.request.urlopen(f"http://{HOST}:{port}/metrics", timeout=60) as r:
            text = r.read().decode()
        out = {}
        for line in text.splitlines():
            if line and not line.startswith("#"):
                sample, _, value = line.rpartition(" ")
                try:
                    out[sample] = float(value)
                except ValueError:
                    pass
        return out

    def compiles_between(self, t0: float, t1: float) -> int:
        """Programs jax traced and compiled (or loaded from the cache) in
        [t0, t1], from the ``JAX_LOG_COMPILES`` lines of the node's log."""
        return sum(1 for t, line in list(self.lines)
                   if t0 <= t <= t1 and "Compiling " in line)

    def problems(self) -> list[str]:
        return [line for _t, line in list(self.lines)
                if line.startswith("(E) ") or line.startswith("Traceback (most recent")]

    def terminate(self, until: str, timeout: float) -> bool:
        """SIGTERM, then wait for the log line ``until`` (or the exit)."""
        if self.proc.poll() is None:
            self.proc.send_signal(signal.SIGTERM)
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            if self.time_of(until) is not None:
                return True
            if self.proc.poll() is not None:
                self._reader.join(timeout=5)
                return self.time_of(until) is not None
            time.sleep(0.05)
        return False

    def wait_exit(self, timeout: float) -> int | None:
        try:
            return self.proc.wait(timeout=timeout)
        except subprocess.TimeoutExpired:
            return None

    def kill(self) -> None:
        if self.proc is not None and self.proc.poll() is None:
            self.proc.kill()
        if self.proc is not None:
            self.proc.wait(timeout=60)
            if self._reader is not None:
                self._reader.join(timeout=5)

    def save_log(self, directory: str) -> None:
        os.makedirs(directory, exist_ok=True)
        with open(os.path.join(directory, f"{self.name}.log"), "w") as f:
            f.writelines(f"{t - self.t_spawn:9.2f} {line}\n" for t, line in self.lines)

    def tail(self, n: int = 40) -> str:
        return "\n".join(f"  {self.name}| {line}" for _t, line in self.lines[-n:])
