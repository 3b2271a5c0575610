"""Test harness: force an 8-device virtual CPU platform BEFORE jax inits.

The suite must not touch an accelerator even on a host that has one (a chip
belongs to one process at a time; tests spawn dozens): the shared helper
pins the CPU via jax.config.update, which wins over whatever JAX_PLATFORMS
the environment names, so the suite runs hermetically on a virtual 8-device
CPU mesh — mirroring how the driver's dryrun_multichip check runs. The chip
is reached only by `python chip_smoke.py` (the on-chip gate) and
`python3 benchmark/run.py`, through the chip tool.

Under `make sanitize` (JYLIS_SANITIZE=1) jax must NOT be imported at all:
the ASAN runtime is LD_PRELOADed before jaxlib's pybind11 modules load,
and its __cxa_throw interceptor aborts on their C++ exceptions. The
sanitized subset (tests/test_native_resp.py, tests/test_native_drive.py)
is deliberately jax-free, so the mesh setup is skipped rather than
poisoning the run.
"""

import os

if not os.environ.get("JYLIS_SANITIZE"):
    from jylis_tpu.utils.vcpu import force_virtual_cpu

    force_virtual_cpu(8)
