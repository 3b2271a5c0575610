"""jmodel: bounded explicit-state exploration of the cluster protocol.

jlint pass 10 (the protocol atlas) pins what the protocol *is*; this
package exhaustively explores what it *does*. It drives the REAL
``jylis_tpu.cluster.Cluster`` handler code — dial state machine,
handshake, read loop, every message handler, the sync-serve machinery,
the held queue, and the region bridge — over an in-memory
deterministic network (``net.py``): a virtual clock that advances only
when the explorer says so, and an in-memory pipe transport injected
through the ``clock=`` / ``connect=`` seams ``Cluster`` grew for
exactly this. Nothing in the protocol is re-modelled; the only
substitutions are the wall clock, the TCP socket, and the Database
(a minimal host-side GCOUNT lattice speaking the real wire codec —
``world.ModelDatabase``).

The explorer (``explore.py``) enumerates delivery schedules — reorder
across connections, drop (connection kill), duplicate, partition,
crash-reboot-from-journal — over 2-node, 3-node and 3-node-2-region
configurations to a bounded depth, with state-hash deduplication and a
sleep-set partial-order reduction (independent actions on distinct
receiving instances are explored in one order, not all orders).
Invariants checked at every distinct state:

* lattice monotonicity — no (key, replica) cell ever regresses;
* held-queue FIFO order + bounded eviction accounting;
* dial-backoff boundedness and monotonicity up to the cap;

and at quiescence (deliver everything, heal everything, tick until
stable):

* digest match on every replica (the convergence
  guarantee the periodic digest exchange promises);
* no stranded rtt stamps (every Pong-soliciting send on a live conn
  eventually matched);
* no in-flight or held frames left.

A violation serialises as a MINIMIZED schedule file (ddmin over the
action trace) that replays as a regression test: the committed corpus
lives in ``tests/model/`` and ``tests/test_model.py`` replays it in
tier-1. ``make model-smoke`` (part of ``make ci``) runs the bounded
exploration against the recorded state floor and time budget in
``scripts/jlint/budget.json``; the full-depth exploration runs behind
``-m soak``.

Run ``python -m scripts.jmodel --help`` for the CLI.
"""

from __future__ import annotations

import contextlib

# Exploration-scale protocol periods: the real constants (50-tick sync
# period, 10-tick cooldown) put interesting behaviour hundreds of
# actions deep — far past any bounded-depth frontier. Shrinking the
# PERIODS (never the logic) is standard model-checking practice: every
# guard still compares the same quantities, only the windows are
# shorter. The patch is scoped and restored, and replay files embed it
# implicitly via the config name.
MODEL_PERIODS = {
    "SYNC_PERIOD_TICKS": 4,
    "SYNC_REQUEST_COOLDOWN": 2,
    "ANNOUNCE_EVERY": 2,
    "IDLE_TICKS_LIMIT": 6,
}


@contextlib.contextmanager
def model_periods():
    """Scope the shrunk protocol periods over a model run."""
    from jylis_tpu.cluster import cluster as cluster_mod

    saved = {k: getattr(cluster_mod, k) for k in MODEL_PERIODS}
    try:
        for k, v in MODEL_PERIODS.items():
            setattr(cluster_mod, k, v)
        yield cluster_mod
    finally:
        for k, v in saved.items():
            setattr(cluster_mod, k, v)
