"""The traced run's window control, from the benchmark's own files.

Only a ``--trace 1`` run puts this directory on the node's PYTHONPATH. The
program can trace (``JYLIS_PROFILE_DIR``) but cannot say when: its trace
starts at the first drain, during restore, and is written at clean
shutdown, and jax's default options also record every Python call. A
whole-life trace of a served node at this size ran the 40 GiB machine out
of memory (PERF.md, PR 23). Until the program has a start/stop surface of
its own (the ``tracing`` issue's first item), this shim gives it one
without touching a file of the program:

* the program's own ``start_trace`` / ``stop_trace`` calls become no-ops
  (it still emits its ``drain_<TYPE>`` step annotations, because it
  believes it is tracing);
* SIGUSR1 starts the real trace into ``BENCH_TRACE_DIR`` with the Python
  tracer off; SIGUSR2 stops it and writes the file. The harness sends them
  at the window's edges.
"""

import os
import signal

_DIR = os.environ.get("BENCH_TRACE_DIR")

if _DIR:
    import jax.profiler as _profiler

    _real_start, _real_stop = _profiler.start_trace, _profiler.stop_trace
    _profiler.start_trace = lambda *a, **k: None
    _profiler.stop_trace = lambda *a, **k: None

    def _start(_sig, _frame):
        options = _profiler.ProfileOptions()
        options.python_tracer_level = 0
        _real_start(_DIR, profiler_options=options)

    def _stop(_sig, _frame):
        _real_stop()
        with open(os.path.join(_DIR, "written"), "w") as f:
            f.write("1\n")

    signal.signal(signal.SIGUSR1, _start)
    signal.signal(signal.SIGUSR2, _stop)
