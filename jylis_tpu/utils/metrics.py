"""Merge-path metrics: per-type drain counters and the drain's spans.

The reference's only observability is the replicated SYSTEM log
(SURVEY.md §2.6 — no tracing, no profiler, no metrics endpoint); §5.1
directs the rebuild to add profiler hooks around merge batches with
per-batch timing counters. The counters themselves live in a
per-Database :class:`~jylis_tpu.obs.registry.MetricsRegistry` (the
observability round retired the old process-global dicts, whose
documented caveat — Databases in one process cross-talking — had been
this module's known wart): every repo carries a ``metrics`` attribute
pointing at its Database's registry, and registry-less direct drives
(standalone repos, a bare Journal) fall back to the process-wide
``DEFAULT`` instance below. What stays here:

* every drain runs under `timed_drain`, accumulating per-type batch
  counts / batched-key counts / host seconds inside ``drain()`` AND a
  log2 latency histogram per type (``drain.<TYPE>`` in SYSTEM LATENCY) —
  dumped into the (replicated, queryable) SYSTEM log at clean shutdown;
* the drain body marks its three phases with `drain_phase` (assemble →
  device → finish), recorded into the ``drain_phase.*`` seams; while
  profiling is armed (obs/span.py) the drain is a ``drain_<TYPE>`` step
  annotation on the profiler's timeline with its phases nested inside
  it as ``drain_<TYPE>.<phase>``.
"""

from __future__ import annotations

import functools

from ..obs import SERVING, span
from ..obs.registry import JOURNAL_KEYS as _JOURNAL_KEYS  # noqa: F401 (re-export)
from ..obs.registry import MetricsRegistry

# the phases of a drain, in the order of MetricsRegistry._h_phases
ASSEMBLE, DEVICE, FINISH = 0, 1, 2
DRAIN_PHASES = ("assemble", "device", "finish")


class _DrainClock:
    """One repo's drain instrument, made at its first timed drain and
    reused (a repo drains under its lock, one drain at a time). `open`
    starts the ``drain_<TYPE>`` span and its first phase, `to` moves to
    another phase, `close` hands the drain and its three phase sums to
    the registry in ONE call."""

    __slots__ = (
        "name", "label", "labels", "seq", "tok", "ptok", "phase", "acc", "meta"
    )

    def __init__(self, name: str):
        self.name = name
        self.label = f"drain_{name}"
        self.labels = tuple(f"drain_{name}.{p}" for p in DRAIN_PHASES)
        self.seq = 0
        self.tok = None  # None: no drain open, `to` is a no-op
        self.acc = [0.0, 0.0, 0.0]

    def open(self, rows: int) -> None:
        self.seq += 1
        self.acc[:] = (0.0, 0.0, 0.0)
        if span.armed():
            # what a reader of the trace needs: the batch size, and the
            # sequence number the three phases share with their drain;
            # _r=1 makes the parent a profiler STEP (StepTraceAnnotation)
            self.meta = {"seq": self.seq, "rows": rows}
            step = {"_r": 1, "step_num": self.seq, "rows": rows}
        else:
            self.meta = step = None
        self.tok = span.begin(self.label, step)
        self.phase = ASSEMBLE
        self.ptok = span.begin(self.labels[ASSEMBLE], self.meta)

    def to(self, phase: int) -> None:
        self.acc[self.phase] += span.elapsed(self.ptok)
        self.phase = phase
        self.ptok = span.begin(self.labels[phase], self.meta)

    def close(self) -> float:
        self.acc[self.phase] += span.elapsed(self.ptok)
        tok, self.tok = self.tok, None
        return span.elapsed(tok)


def drain_phase(repo, phase: int) -> None:
    """Called by a drain body where its next phase starts: DEVICE at the
    jitted call (ends when the ``np.asarray`` that waits for its result
    returns), FINISH where results go back into the host cache. Until
    the first mark a drain is in ASSEMBLE. Outside a timed drain (obs
    disabled, or an empty drain) it does nothing."""
    dc = repo.__dict__.get("_drain_clock")
    if dc is not None and dc.tok is not None:
        dc.to(phase)


# The process-wide fallback registry for callers constructed without an
# explicit one (standalone repos in unit tests, a bare Journal, warmup
# before its throwaway Database exists). The module-level dict aliases
# keep the historical direct-drive surface working: they ARE the default
# registry's dicts, not copies.
DEFAULT = MetricsRegistry()
counters = DEFAULT.counters
journal_counters = DEFAULT.journal_counters
serving_counters = DEFAULT.serving_counters


def resolve_registry(obj) -> MetricsRegistry:
    """The registry ``obj`` carries (its owning Database's, wired as the
    ``metrics`` attribute), or the process DEFAULT for registry-less
    direct drives — THE fallback policy, shared by every consumer
    (timed_drain, RepoSYSTEM, Journal, Cluster) so it cannot drift."""
    return getattr(obj, "metrics", None) or DEFAULT


def note_journal(counter: str, n: int = 1) -> None:
    DEFAULT.note_journal(counter, n)


def note_drain(name: str, n_keys: int, seconds: float) -> None:
    DEFAULT.note_drain(name, n_keys, seconds)


def timed_drain(name: str, key_count):
    """Decorator for repo drain() methods: per-batch counters, a log2
    latency histogram (``drain.<name>``), the three phase seams, and —
    armed — the profiler annotations. ``key_count(self)`` returns the
    pending batch size. The registry resolves per call from the repo's
    ``metrics`` attribute (set by Database) so one decorated class
    serves any number of registry-carrying instances; jlint pass 5 maps
    the literal ``name`` here to the ``drain.<name>`` histogram in the
    metrics manifest. A drain that raises records nothing (as before);
    its annotations still close."""

    def wrap(fn):
        @functools.wraps(fn)
        def inner(self, *args, **kwargs):
            reg = resolve_registry(self)
            if not reg.enabled:
                return fn(self, *args, **kwargs)
            n = key_count(self)
            # a drain invoked with explicit work (e.g. TLOG's fused
            # trim=(row, count)) dispatches even with nothing pending —
            # time it as one key so pure-trim cost stays visible
            if n == 0 and not args and not any(
                v is not None for v in kwargs.values()
            ):
                return fn(self, *args, **kwargs)
            dc = self.__dict__.get("_drain_clock")
            if dc is None:
                dc = self._drain_clock = _DrainClock(name)
            dc.open(n)
            try:
                out = fn(self, *args, **kwargs)
            finally:
                seconds = dc.close()
            reg.note_drain(name, max(n, 1), seconds, dc.acc)
            return out

        return inner

    return wrap


def metric_lines(
    served: dict[str, int] | None = None,
    serving: dict[str, int] | None = None,
    cluster: dict[str, int] | None = None,
    registry: MetricsRegistry | None = None,
    session: dict[str, int] | None = None,
    overload: dict[str, int] | None = None,
) -> list[str]:
    """Flat `type counter value` lines — the SYSTEM METRICS reply body.
    ``served`` is the serving node's per-type commands-served totals
    (Database merges its Python-path tally with its engine's native
    counters and wires the result through RepoSYSTEM). ``serving`` is
    the native-vs-demoted split (native_cmds / demoted_cmds /
    demotions), emitted with the live fallback_frac.
    ``cluster`` is the node's peer lifecycle view (Cluster.metrics_totals:
    per-state peer counts, dial/eviction/sync counters, held-delta
    drops, and the convergence-lag/backlog gauges). ``registry`` is the
    node's MetricsRegistry (drain/journal counters + the latency
    histograms, emitted as `LATENCY <seam>.<stat>` lines); None falls
    back to the process DEFAULT. Existing line names stay byte-stable —
    new sections only append."""
    reg = registry if registry is not None else DEFAULT
    lines = [
        f"{name} cmds {n}" for name, n in sorted((served or {}).items()) if n
    ]
    if serving and any(serving.values()):
        for k in ("native_cmds", "demoted_cmds") + SERVING:
            lines.append(f"SERVING {k} {serving.get(k, 0)}")
        total = serving.get("native_cmds", 0) + serving.get("demoted_cmds", 0)
        if total:
            frac = serving.get("demoted_cmds", 0) / total
            lines.append(f"SERVING fallback_frac {frac:.4f}")
        lines.extend(
            f"SERVING slept_bursts.{name} {n}"
            for name, n in sorted(reg.slept_by_type.items())
        )
    if session is not None and any(session.values()):
        # session-guarantee counters (sessions.py): tokens minted,
        # reads served/waited, typed STALE/BADTOKEN refusals, adoption
        # events and the vector's live size — glossary in
        # docs/operations.md, contracts in docs/sessions.md
        lines.extend(
            f"SESSION {k} {v}" for k, v in sorted(session.items())
        )
    if overload is not None and overload.get("armed"):
        # overload armor (admission.py, docs/operations.md "Overload"):
        # the declared shed state, its transitions, per-class shed
        # counters and the live pressure signals — the section appears
        # whenever admission is armed (policy set or byte bound on),
        # explicit zeros included, so dashboards see it from boot
        lines.extend(
            f"OVERLOAD {k} {v}"
            for k, v in overload.items()
            if k != "armed"
        )
    if cluster is not None:
        # insertion order (states first, then counters) — a glossary
        # order, kept stable for dashboards
        lines.extend(f"CLUSTER {k} {v}" for k, v in cluster.items())
    for name, drains, keys, ms in reg.type_stats():
        lines.append(f"{name} drains {drains}")
        lines.append(f"{name} keys {keys}")
        lines.append(f"{name} device_ms {ms:.1f}")
    lines.extend(f"{t} {kind} {n}" for t, kind, n in reg.tally_stats())
    if reg.journal_enabled or any(reg.journal_counters.values()):
        # every JOURNAL_KEYS line whenever journaling is live — explicit
        # zeros from boot (e.g. fsyncs under --journal-fsync off), not a
        # section that pops into existence at the first nonzero counter
        for k in _JOURNAL_KEYS:
            lines.append(f"JOURNAL {k} {reg.journal_counters[k]}")
    for name, snap in reg.seam_stats():
        if snap["count"]:
            lines.append(f"LATENCY {name}.p50_us {snap['p50_s'] * 1e6:.0f}")
            lines.append(f"LATENCY {name}.p90_us {snap['p90_s'] * 1e6:.0f}")
            lines.append(f"LATENCY {name}.p99_us {snap['p99_s'] * 1e6:.0f}")
            lines.append(f"LATENCY {name}.max_us {snap['max_s'] * 1e6:.0f}")
            lines.append(f"LATENCY {name}.count {snap['count']}")
    return lines


def report() -> str:
    return DEFAULT.report()
