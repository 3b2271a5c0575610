"""Shared helpers for tests that spawn REAL node processes.

The client-conformance, persistence crash-recovery, and soak modules
each grew their own copy of the free-port / spawn-command /
connect-retry plumbing; this is the one home for it. (scripts/smoke3.py
deliberately keeps its own spawn line: it boots nodes on the 8-device
virtual mesh to exercise sharded serving, not the plain CPU platform.)
"""

from __future__ import annotations

import itertools
import os
import socket
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# spawn a node on the forced-CPU platform (jax.config.update wins over
# whatever JAX_PLATFORMS the environment names — a chip host's may name
# the chip, which belongs to one process at a time)
SPAWN_CPU = (
    "import jax; jax.config.update('jax_platforms','cpu'); "
    "import sys; from jylis_tpu.main import main; main(sys.argv[1:])"
)


# Where tests' nodes listen: BELOW the kernel's ephemeral range (32768 up,
# where bind(0) and every outbound connection of every worker's nodes take
# their ports, so a port drawn there and bound later can be gone by then),
# clear of the fixed ports of chip_smoke.py and benchmark/ (29471 up), in a
# span of this xdist worker's own, walked in order: no two workers draw the
# same port and one worker draws none twice before _PORT_SPAN draws.
_PORT_BASE, _PORT_SPAN, _PORT_SLOTS = 20000, 700, 12
_draws = itertools.count()


def scan_bytes(eng, buf):
    """`ServeEngine.scan_apply` with the burst's replies copied out of
    the engine's reply array as bytes (the loop door's two calls)."""
    rc, consumed, n, unhandled, changed = eng.scan_apply(buf)
    return rc, consumed, eng.reply_bytes(n), unhandled, changed


def free_port() -> int:
    # "gw3" -> slot 4; a run without xdist takes slot 0
    slot = int(os.environ.get("PYTEST_XDIST_WORKER", "gw-1")[2:]) + 1
    base = _PORT_BASE + slot % _PORT_SLOTS * _PORT_SPAN
    for _ in range(_PORT_SPAN):
        port = base + next(_draws) % _PORT_SPAN
        with socket.socket() as s:
            try:
                s.bind(("", port))  # every interface, as the cluster listener binds
            except OSError:
                continue  # held by something outside the suite
        return port
    raise RuntimeError(f"no free port in {base}..{base + _PORT_SPAN - 1}")


def spawn_node(port: int, cport: int, name: str, *extra: str) -> subprocess.Popen:
    return subprocess.Popen(
        [sys.executable, "-c", SPAWN_CPU, "--port", str(port), "--addr",
         f"127.0.0.1:{cport}:{name}", "--log-level", "warn", *extra],
        cwd=REPO,
    )


def connect_client(port: int, timeout_s: float = 120.0, proc=None):
    """jylis_tpu.client.Client to a node that may still be starting; fails
    fast if the process died."""
    from jylis_tpu.client import Client

    deadline = time.time() + timeout_s
    while time.time() < deadline:
        if proc is not None and proc.poll() is not None:
            raise RuntimeError("node process died during startup")
        try:
            return Client("127.0.0.1", port, timeout=60)
        except OSError:
            time.sleep(0.3)
    raise RuntimeError(f"node on :{port} never came up")


def stop_node(proc: subprocess.Popen, grace: float = 60.0) -> None:
    proc.terminate()
    try:
        proc.wait(timeout=grace)
    except subprocess.TimeoutExpired:
        proc.kill()
        proc.wait(timeout=10)
