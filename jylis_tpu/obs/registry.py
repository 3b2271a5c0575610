"""Per-Database metrics registry.

Before this class the drain / journal / serving counters were
process-global module state in utils/metrics.py, with a documented
caveat: multiple Databases in one process (tests, the warmup
throwaway) cross-talked through them. The registry makes the whole
observability surface — counters, histograms, gauges, trace ring — a
per-`Database` instance passed down explicitly: Database creates one,
hands it to its repos (drain timing), the Server (dispatch seams), the
Journal (append/fsync seams), and the Cluster (round-trip + convergence
lag), and RepoSYSTEM reads it for `SYSTEM METRICS` / `LATENCY` /
`TRACE`. utils/metrics.py keeps a process-wide DEFAULT instance so
registry-less direct drives (standalone repos, a bare Journal) still
record somewhere.

``enabled`` is the one global switch the seams check before paying for
`perf_counter` pairs: with it off a run skips the FULL cost of
observation (clock reads included), not just the bucket increment.

Histogram and gauge names are pre-registered from obs.SEAMS/GAUGES —
`hist()` raises KeyError on an undeclared name, and jlint pass 5
(JL501/JL502) holds the call-site literals, the declarations, and the
manifest descriptions in lockstep.
"""

from __future__ import annotations

import time
from collections import defaultdict, deque

from . import GAUGES, SEAMS, SERVING, TALLIES
from .hist import Histogram
from .jtrace import SpanStats
from .span import Seam
from .trace import TraceRing

JOURNAL_KEYS = ("appends", "bytes", "fsyncs", "replayed_batches", "errors")

# windowed-quantile marks: how many point-in-time seam copies we keep,
# and the minimum spacing between deposits (an opportunistic deposit on
# every scrape/SYSTEM LATENCY call must not grow cost with poll rate)
WINDOW_MARKS = 64
WINDOW_MIN_SPACING_S = 1.0

HEAT_FANOUT = 256  # digest-tree leaf fanout (models/database.py SYNC_FANOUT)


class MetricsRegistry:
    def __init__(self, trace_cap: int = 512):
        self.enabled = True
        # per-type drain accumulators (batches / keys / HOST seconds
        # inside drain(): the reports call them device_ms, a name kept
        # for byte-stability; the drain_phase.* seams split them)
        self.counters: dict[str, dict[str, float]] = defaultdict(
            lambda: {"batches": 0, "keys": 0, "seconds": 0.0}
        )
        # delta write-ahead journal counters: appends / bytes / fsyncs
        # accrue on the writer thread, replayed_batches on boot
        # recovery, errors on ANY writer-side encode/write/fsync failure
        self.journal_counters: dict[str, int] = dict.fromkeys(JOURNAL_KEYS, 0)
        # True once a journal is attached (Database.set_journal): the
        # JOURNAL section of SYSTEM METRICS then shows explicit zeros
        # from boot instead of appearing at the first nonzero counter
        self.journal_enabled = False
        # serving-path (obs.SERVING): whole-connection demotions off the
        # native engine, per-command-class admission-control refusals
        # (manager.py), Python-path commands by cause and the engine's
        # reply bytes (server.py)
        self.serving_counters: dict[str, int] = dict.fromkeys(SERVING, 0)
        # slept_bursts by the type whose lock the burst slept for
        self.slept_by_type: dict[str, int] = {}
        self.hists: dict[str, Histogram] = {name: Histogram() for name in SEAMS}
        # the three phases of a drain (utils/metrics.timed_drain), in
        # DRAIN_PHASES order: recorded WITH their parent drain.<TYPE>
        # when the drain returns, so a scrape never sees a phase
        # without its drain and the three always add up to it
        self._h_phases = (
            self.hist("drain_phase.assemble"),
            self.hist("drain_phase.device"),
            self.hist("drain_phase.finish"),
        )
        # reads the event-loop thread's CPU clock, once a timing
        # selector is attached (obs/loop.py): see loop_cpu_s
        self.loop_cpu_fn = None
        # brings the native reply sender's tallies up to its library's
        # atomics (native/engine.py bind_metrics): see tally_stats
        self.sender_fn = None
        self.gauges: dict[str, float] = {name: 0.0 for name in GAUGES}
        # exact per-type drain event counts (obs.TALLIES); each has one
        # writer, a drain under its repo's lock
        self.tallies: dict[str, int] = dict.fromkeys(TALLIES, 0)
        self.trace = TraceRing(trace_cap)
        # provenance-span folds (obs/jtrace.py): per-hop + per-region-
        # pair convergence histograms, SLO counters, worst exemplars
        self.spans = SpanStats()
        # per-digest-tree-bucket write heat: type -> 256 counters over
        # sha256(key)[0], counted where deltas are emitted (manager.py
        # _emit) — the placement telemetry ROADMAP item 3 needs
        self.write_heat: dict[str, list[int]] = {}
        # windowed quantiles: (monotonic ts, {seam: Histogram.mark()})
        self._window_marks: deque = deque(maxlen=WINDOW_MARKS)

    # ---- counters ----------------------------------------------------------

    def note_drain(
        self, name: str, n_keys: int, seconds: float, phases=None
    ) -> None:
        c = self.counters[name]
        c["batches"] += 1
        c["keys"] += n_keys
        c["seconds"] += seconds
        h = self.hists.get("drain." + name)
        if h is not None:
            h.record(seconds)
        if phases is not None:
            for hp, s in zip(self._h_phases, phases):
                hp.record(s)

    def tally(self, name: str, n: int) -> None:
        if name not in self.tallies:
            raise KeyError(name)  # undeclared tally, fail loud
        self.tallies[name] += n

    def note_journal(self, counter: str, n: int = 1) -> None:
        self.journal_counters[counter] += n

    def note_serving(self, counter: str, n: int = 1) -> None:
        self.serving_counters[counter] += n

    def note_slept(self, type_name: str) -> None:
        """One native burst slept for ``type_name``'s repo lock: the
        `type` label of slept_bursts."""
        by = self.slept_by_type
        by[type_name] = by.get(type_name, 0) + 1

    def note_write_heat(self, name: str, bucket: int, n: int = 1) -> None:
        """One emitted delta batch touched ``bucket`` of ``name``'s
        digest tree (0..255). Lazy per-type vectors: a type that never
        writes costs nothing."""
        heat = self.write_heat.get(name)
        if heat is None:
            heat = self.write_heat[name] = [0] * HEAT_FANOUT
        heat[bucket] += n

    # ---- histograms / gauges / trace --------------------------------------

    def hist(self, name: str) -> Histogram:
        return self.hists[name]  # KeyError = undeclared seam, fail loud

    def loop_cpu_s(self) -> float:
        """CPU seconds of the event-loop thread since its timing
        selector was attached (jylis_loop_cpu_seconds_total); 0.0 under
        a loop built elsewhere."""
        return self.loop_cpu_fn() if self.loop_cpu_fn is not None else 0.0

    def seam(self, name: str) -> Seam:
        """The span instrument of one declared seam (obs/span.py): the
        same histogram, plus a profiler annotation while armed."""
        return Seam(name, self.hists[name], self)

    def gauge_set(self, name: str, value: float) -> None:
        if name not in self.gauges:
            raise KeyError(name)  # undeclared gauge, fail loud
        self.gauges[name] = value

    def trace_event(
        self, subsystem: str, event: str, reason: str = "", detail: str = ""
    ) -> None:
        if self.enabled:
            self.trace.push(subsystem, event, reason, detail)

    # ---- reporting ---------------------------------------------------------

    def type_stats(self):
        """(name, drains, keys, device_ms) per drained type (device_ms:
        host milliseconds inside drain(), see `counters`) — the ONE
        iteration the reporting surfaces share. list() snapshots the key
        set atomically under the GIL: note_drain runs in worker threads
        and may insert a type's key mid-request."""
        for name in sorted(list(self.counters)):
            c = self.counters.get(name)
            if c is not None:
                yield name, int(c["batches"]), int(c["keys"]), c["seconds"] * 1e3

    def tally_stats(self):
        """(type, kind, n) per declared tally, TALLIES order; the reply
        sender's are its library's atomics, read now."""
        if self.sender_fn is not None:
            self.sender_fn()
        for name in TALLIES:
            _, typ, kind = name.split(".")
            yield typ, kind, self.tallies[name]

    def seam_stats(self):
        """(name, snapshot) per declared seam, SEAMS order."""
        for name in SEAMS:
            yield name, self.hists[name].snapshot()

    # ---- windowed quantiles ------------------------------------------------

    def window_deposit(self) -> None:
        """Opportunistically deposit a point-in-time mark of every seam
        (called from the reporting surfaces — SYSTEM LATENCY, the
        scrape — never the hot path). Rate-limited so poll frequency
        can't inflate the cost; the ring keeps ~the last minute."""
        now = time.monotonic()
        if self._window_marks and (
            now - self._window_marks[-1][0] < WINDOW_MIN_SPACING_S
        ):
            return
        self._window_marks.append(
            (now, {name: self.hists[name].mark() for name in SEAMS})
        )

    def window_stats(self, seconds: float):
        """(achieved_window_s, [(name, delta_snapshot), ...]) against
        the deposited mark closest to ``seconds`` ago — delta-since-mark
        quantiles, so a regression on a long-running node isn't drowned
        by since-boot history. Returns (0.0, None) when no mark is old
        enough to subtract (callers report 'no window yet')."""
        if not self._window_marks:
            return 0.0, None
        now = time.monotonic()
        best = min(
            self._window_marks,
            key=lambda m: abs((now - m[0]) - seconds),
        )
        achieved = now - best[0]
        if achieved <= 0.0:
            return 0.0, None
        marks = best[1]
        return achieved, [
            (name, self.hists[name].snapshot_since(marks[name]))
            for name in SEAMS
        ]

    def report(self) -> str:
        tallies: dict[str, str] = defaultdict(str)
        live = set()  # tally types that counted anything
        for typ, kind, n in self.tally_stats():
            tallies[typ] += f", {n} {kind}"
            if n:
                live.add(typ)
        parts = [
            f"{name}: {drains} drains, {keys} keys, {ms:.1f}ms device"
            + tallies.pop(name, "")
            for name, drains, keys, ms in self.type_stats()
        ]
        # a type that counts and never drains (ENGINE, the reply buffer)
        parts += [f"{typ}: {t[2:]}" for typ, t in tallies.items() if typ in live]
        # the serving-path counters (obs.SERVING), once any has counted
        if any(self.serving_counters.values()):
            parts.append("SERVING: " + ", ".join(
                f"{n} {kind}" for kind, n in self.serving_counters.items()
            ))
        return "; ".join(parts) if parts else "no drains"
