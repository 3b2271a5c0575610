"""Mesh construction helpers.

One place decides how devices become a `jax.sharding.Mesh`, so tests (8
virtual CPU devices), the driver's dryrun (N virtual devices), and real
TPU pods all build meshes the same way.
"""

from __future__ import annotations

import jax
import numpy as np
from jax.sharding import Mesh


def make_mesh(
    n_devices: int | None = None, rep: int = 1, axis_names=("rep", "keys")
) -> Mesh:
    """A (rep × keys) mesh over the first ``n_devices`` devices.

    ``rep=1`` (the default) gives a pure keys-sharded mesh — the serving
    layout, where anti-entropy needs no collectives. ``rep>1`` carves a
    replica fan-in axis for `join_replica_axis` (the pmax join collective).
    """
    devs = jax.devices()
    if n_devices is None:
        n_devices = len(devs)
    if n_devices > len(devs):
        raise ValueError(f"need {n_devices} devices, have {len(devs)}")
    if n_devices % rep != 0:
        raise ValueError(f"n_devices {n_devices} not divisible by rep {rep}")
    grid = np.array(devs[:n_devices]).reshape(rep, n_devices // rep)
    return Mesh(grid, axis_names)


_SERVING_MESH: list = []  # memo cell: [Mesh | None] once resolved


def serving_mesh() -> Mesh | None:
    """The process-wide keys-sharded serving mesh, or None single-device.

    Repos call this at construction (mesh="auto"): with one visible device
    (a one-chip host, or the plain CPU platform) they keep the single-chip
    fast path; with several (a four-chip host, or the 8-virtual-device test
    harness) every plane-backed keyspace is born keys-sharded across all of
    them — on such a host the mesh path is the default, not an option.
    Memoised: jits specialise on the mesh as a static arg, so all repos
    must share one Mesh object.
    """
    if not _SERVING_MESH:
        # local devices, deliberately: a jylis node is one process on one
        # host, and its mesh is that host's chips. Spanning hosts inside
        # one node would make every drain a multi-controller SPMD program
        # — the wrong tool for an event-driven server. Cross-host scale is
        # the CLUSTER layer's job (gossip over DCN), same as the
        # reference's one-process-one-node model.
        n = len(jax.local_devices())
        _SERVING_MESH.append(make_mesh(n) if n > 1 else None)
    return _SERVING_MESH[0]
