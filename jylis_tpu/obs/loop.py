"""The event loop's own time: busy wall clock per iteration, and CPU.

asyncio's selector loop does nothing between one ``select()`` call and
its return but wait, and everything else between that return and the
next call: one iteration's callbacks. A selector that stamps both edges
therefore measures the loop with no hook inside asyncio — the selector
is public API (``asyncio.SelectorEventLoop(selector)``).

``loop.busy`` (histogram): ``select()`` returning -> the next
``select()`` call. ``count`` is iterations, ``sum_s`` busy wall clock:
two ``perf_counter`` reads and one histogram increment per iteration.
``jylis_loop_cpu_seconds_total`` (``MetricsRegistry.loop_cpu_s()``) is
the loop THREAD's CPU clock, read when somebody asks (a scrape), from
any thread. Outside its busy intervals the thread only sits in
``select()``, so this is the CPU of the same intervals plus what the
``select()`` calls themselves burn. It is NOT read per iteration: a
thread-CPU clock read is a real system call (6.0 us each on the
benchmark's sandboxed chip host, where two per iteration cost 10-20% of
a cell's throughput; my chip runs, PR 24). Busy wall minus CPU is time
the loop was runnable and did not run: the GIL held by a drain or
journal thread, or a blocking call. There is no profiler annotation per
iteration (the work inside carries its own); an iteration over
`STALL_S` pushes one ``loop stall`` event into the trace ring.
"""

from __future__ import annotations

import asyncio
import selectors
import threading
import time
from time import perf_counter

STALL_S = 0.050


class TimingSelector(selectors.DefaultSelector):
    """Times nothing until `attach` hands it a registry."""

    def __init__(self):
        super().__init__()
        self._reg = None
        self._hist = None
        self._t0 = 0.0  # 0.0: no busy interval open

    def attach(self, registry) -> None:
        """Called on the loop's own thread, whose CPU clock the registry
        reads from then on."""
        self._hist = registry.hist("loop.busy")
        clock = time.pthread_getcpuclockid(threading.get_ident())
        cpu0 = time.clock_gettime(clock)
        registry.loop_cpu_fn = lambda: time.clock_gettime(clock) - cpu0
        self._reg = registry

    def select(self, timeout=None, _select=selectors.DefaultSelector.select):
        hist = self._hist
        if hist is None:
            return _select(self, timeout)
        t0 = self._t0
        if t0:
            busy = perf_counter() - t0
            hist.record(busy)
            if busy > STALL_S:
                self._reg.trace_event(
                    "loop", "stall", "", f"{busy * 1e3:.0f} ms in one iteration"
                )
        events = _select(self, timeout)
        self._t0 = perf_counter() if self._reg.enabled else 0.0
        return events


def new_event_loop() -> asyncio.AbstractEventLoop:
    """The node's loop (main.py): a plain selector
    loop whose selector is a `TimingSelector`."""
    selector = TimingSelector()
    loop = asyncio.SelectorEventLoop(selector)
    loop.timing_selector = selector  # where `attach` finds it
    return loop


def attach(registry) -> bool:
    """Point the RUNNING loop's timing selector at ``registry``; False
    under a loop built elsewhere (tests on ``asyncio.run``), whose
    ``loop.busy`` then stays at zero."""
    selector = getattr(asyncio.get_running_loop(), "timing_selector", None)
    if selector is None:
        return False
    selector.attach(registry)
    return True
