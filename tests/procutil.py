"""Shared helpers for tests that spawn REAL node processes.

The client-conformance, persistence crash-recovery, and soak modules
each grew their own copy of the free-port / spawn-command /
connect-retry plumbing; this is the one home for it. (scripts/smoke3.py
deliberately keeps its own spawn line: it boots nodes on the 8-device
virtual mesh to exercise sharded serving, not the plain CPU platform.)
"""

from __future__ import annotations

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


def free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    p = s.getsockname()[1]
    s.close()
    return p


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
