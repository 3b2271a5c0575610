"""Force an N-device virtual CPU platform for hermetic multi-chip runs.

Tests and dry runs must never land on an accelerator: a chip belongs to one
process at a time, and a suite that spawns dozens of nodes would fight over
it. So the platform is pinned to the CPU three ways — the XLA device-count
flag, JAX_PLATFORMS in os.environ (what child processes inherit), and
``jax.config.update`` (which wins over an environment that names another
platform, as a chip host's may) — before jax initialises its backends.
Shared by tests/conftest.py (8-device harness) and the driver-facing
`__graft_entry__.dryrun_multichip` (N-device gate) so the two can't drift.
"""

from __future__ import annotations

import os
import re


def force_virtual_cpu(n_devices: int) -> None:
    flags = os.environ.get("XLA_FLAGS", "")
    want = f"--xla_force_host_platform_device_count={n_devices}"
    if "--xla_force_host_platform_device_count" in flags:
        flags = re.sub(
            r"--xla_force_host_platform_device_count=\d+", want, flags
        )
    else:
        flags = (flags + " " + want).strip()
    os.environ["XLA_FLAGS"] = flags
    os.environ["JAX_PLATFORMS"] = "cpu"

    import jax

    jax.config.update("jax_platforms", "cpu")
    if len(jax.devices()) < n_devices:
        # jax was already initialised (wrong platform or device count) —
        # reset backends, then pin the CPU device count via config (the
        # XLA_FLAGS route only applies to a first-time init)
        import jax.extend.backend

        jax.clear_caches()
        jax.extend.backend.clear_backends()
        jax.config.update("jax_num_cpu_devices", n_devices)
