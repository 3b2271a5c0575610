"""One span instrument, two sinks.

A span is an interval of host time at a declared seam. Always on, it
lands in the seam's log2 histogram (exact ``count`` / ``sum_s`` on
``/metrics`` and ``SYSTEM LATENCY``). ONLY while profiling is armed it
also becomes a ``jax.profiler.TraceAnnotation`` of the same interval,
so it lies on the ``/host:CPU`` plane of the same xplane file as the
device's ops, on the profiler's clock — an idle gap of the device can
be put down to a named host activity.

"Armed" means ``JYLIS_PROFILE_DIR`` is set (the directory a window
writes into; setting it starts nothing) or a window opened through
`start_window` (``SYSTEM PROFILE START``) is open. Unarmed, a span is
two ``perf_counter`` reads and one histogram increment: no jax import,
no object per span — `begin` hands back the start stamp itself as the
token. Armed, the token also carries the open annotation.

    tok = seam.begin()      # or seam.begin("drain_TREG.device", {"rows": n})
    ...
    seam.end(tok)

A token is threaded by the caller, so spans may overlap on one thread
(two connections waiting for the same lock) and may cross an ``await``;
the profiler records complete events and nesting on a thread's line
follows from containment. Begin and end run on the same thread.
"""

from __future__ import annotations

import os
import threading
from time import perf_counter

PROFILE_DIR_ENV = "JYLIS_PROFILE_DIR"
WINDOW_MAX_S = 60.0  # default and cap of SYSTEM PROFILE START [seconds]

_armed = bool(os.environ.get(PROFILE_DIR_ENV))
_profiler = None  # jax.profiler, imported at the first armed span

# the open trace window, if any: (directory, auto-stop timer)
_window: tuple[str, threading.Timer] | None = None
_window_lock = threading.Lock()


def armed() -> bool:
    return _armed


def _jax_profiler():
    global _profiler
    if _profiler is None:
        import jax.profiler

        _profiler = jax.profiler
    return _profiler


def begin(label: str, meta: dict | None = None):
    """Start a span: the token `elapsed` takes. ``meta`` becomes the
    annotation's keyword metadata (what a reader of the trace needs:
    rows, a drain's sequence number); it is not looked at unarmed."""
    if not _armed:
        return perf_counter()
    # the clock outside, the annotation inside: what making and closing
    # it costs falls within the seam's interval, so back-to-back spans
    # (a drain's phases) leave no unaccounted gap between them
    t0 = perf_counter()
    ann = _jax_profiler().TraceAnnotation(label, **(meta or {}))
    ann.__enter__()
    return (t0, ann)


def elapsed(token) -> float:
    """End the span `begin` started: seconds since, closing the
    annotation when there is one."""
    if token.__class__ is float:
        return perf_counter() - token
    t0, ann = token
    ann.__exit__(None, None, None)
    return perf_counter() - t0


class Seam:
    """One declared seam of one registry: `MetricsRegistry.seam(name)`
    resolves it once, the call site keeps it. The registry's ``enabled``
    switch gates the clock reads too (a disabled span hands back 0.0).
    The unarmed path is kept to one call and one clock read on each
    side: these run once per served burst and per lock taken, where the
    benchmark's chip host showed every Python call (my chip runs, PR 24)."""

    __slots__ = ("name", "hist", "_reg")

    def __init__(self, name: str, hist, registry):
        self.name, self.hist, self._reg = name, hist, registry

    def begin(self, label: str | None = None, meta: dict | None = None):
        if not self._reg.enabled:
            return 0.0
        if _armed:
            return begin(label or self.name, meta)
        return perf_counter()

    def end(self, token) -> float:
        """Close the span and hand back the seconds it recorded (0.0
        disabled): a caller whose own stage the span interrupts moves
        that stage's start stamp forward by it."""
        if token.__class__ is float:
            if not token:
                return 0.0
            seconds = perf_counter() - token
        else:
            seconds = elapsed(token)
        self.hist.record(seconds)
        return seconds


# ---- the device-trace window (SYSTEM PROFILE START / STOP) ---------------


def profile_dir() -> str:
    return os.environ.get(PROFILE_DIR_ENV, "")


def start_window(directory: str, seconds: float) -> str:
    """Open a device-trace window into ``directory`` with the Python
    tracer off (jax's default records every Python call: a served node's
    whole-life trace ran a 40 GiB machine out of memory), closed by
    `stop_window`, after ``seconds``, or at clean shutdown. Raises
    RuntimeError while one is open."""
    global _armed, _window
    jp = _jax_profiler()
    with _window_lock:
        if _window is not None:
            raise RuntimeError(f"a trace window is open into {_window[0]}")
        options = jp.ProfileOptions()
        options.python_tracer_level = 0
        jp.start_trace(directory, profiler_options=options)
        timer = threading.Timer(seconds, stop_window)
        timer.daemon = True
        timer.start()
        _window = (directory, timer)
        _armed = True
    return directory


def stop_window() -> str | None:
    """Close the open window and write its ``*.xplane.pb``; the
    directory, or None when no window was open."""
    global _armed, _window
    with _window_lock:
        if _window is None:
            return None
        directory, timer = _window
        timer.cancel()
        try:
            _jax_profiler().stop_trace()
        finally:
            _window = None
            _armed = bool(profile_dir())
    return directory
