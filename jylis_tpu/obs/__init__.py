"""Always-on node observability: latency histograms, convergence lag,
and a bounded structured trace ring.

Until this package, every latency number the repo could show was
measured from OUTSIDE by a load generator, and `SYSTEM METRICS` was monotonic
counters only — the node itself could not answer "how long does a drain
take at p99?" or "how stale is the data a peer pushed me?". The
delta-CRDT literature frames exactly those two quantities as THE trade
the model makes (Almeida et al., arXiv:1410.2803: anti-entropy cost vs
staleness; Big(ger) Sets, arXiv:1605.06424: per-replica propagation
backlog), so they must be live on the node, not in offline bench
records. Three pillars:

* **Fixed-bucket log2 latency histograms** (`hist.Histogram`): 64
  power-of-two nanosecond buckets, record = one index computation + one
  list increment, no allocation — cheap enough to stay armed on the
  serving hot path permanently (what it costs on the chip host:
  PERF.md, PR 24). Wired into every timed seam the repo already has: native
  burst + Python dispatch (server), per-type drains
  (utils/metrics.timed_drain), journal append/fsync, and cluster
  heartbeat round-trips — and, through the span instrument
  (`span.Seam`: the same histogram plus, while profiling is armed, a
  profiler annotation of the same interval), into the host time
  budget: the event loop (`loop.py`), the repo-lock waits, a drain's
  three phases, cluster decode/apply and the delta flush.
* **Convergence-lag tracking**: every cluster transport frame carries
  its sender's wall-clock origin (schema v6, cluster/cluster.py);
  receivers record push→apply lag per peer into a `converge_lag_ms`
  gauge (EWMA) plus a node-wide anti-entropy `backlog_ms` gauge — the
  time dimension of the held-delta / deferred-sync counts the CLUSTER
  metrics section already carries.
* **A bounded structured trace ring** (`trace.TraceRing`): fixed-size
  deque of (ts_ms, subsystem, event, reason, detail) tuples fed by the
  same seams the failpoints manifest names, dumped by `SYSTEM TRACE
  [count]` and automatically on unclean shutdown.

Everything surfaces three ways: extended `SYSTEM METRICS` lines, the
`SYSTEM LATENCY` subcommand, and the opt-in `--metrics-port` HTTP
endpoint emitting Prometheus text exposition (`prom.py`).

Naming discipline: every histogram/gauge/trace-event name is a string
literal at its call site, declared and described in
`scripts/jlint/metrics_manifest.json` (jlint pass 5, rules
JL501/JL502), and every histogram/gauge/tally is pre-registered below
so a scrape shows the full surface (with zero counts) from boot.
"""

from __future__ import annotations

# Every latency histogram seam, pre-created in each MetricsRegistry so
# the Prometheus scrape and SYSTEM LATENCY show the complete surface
# from boot (zero counts included). jlint pass 5 cross-checks this
# tuple against the literal names at the call sites.
SEAMS = (
    "drain.TREG",
    "drain.TLOG",
    "drain.GCOUNT",
    "drain.PNCOUNT",
    "drain.TENSOR",
    "drain.MAP",
    "drain.BCOUNT",
    # UJSON's one device path: the resident store's fold of pending
    # deltas into its rows (models/repo_ujson.py _resident_fold); host
    # folds are ujson.host_fold below, not drains
    "drain.UJSON",
    "server.native_burst",
    "server.py_dispatch",
    "journal.append",
    "journal.fsync",
    "cluster.rtt",
    "cluster.converge_lag",
    # the serving-pipeline profiler (server.py): per-stage timers on
    # the RESP path, so ROADMAP item 1's socket-tax attribution is a
    # measured per-stage split instead of one externally derived ratio.
    # Stage semantics (docs/observability.md): accept = connection
    # setup (one sample per conn), read = one socket read await
    # (includes client idle — meaningful under saturation), parse =
    # one Python-path command parse, classify = admission classify +
    # gate (armed nodes only), dispatch = command settle on either
    # path (native bursts reuse the native_burst elapsed — no extra
    # clock read on the hot path), reply_write = one buffered write
    # flush to the transport.
    "pipeline.accept",
    "pipeline.read",
    "pipeline.parse",
    "pipeline.classify",
    "pipeline.dispatch",
    "pipeline.reply_write",
    # the host time budget (obs/span.py; exact starts and ends in
    # docs/observability.md). None of these names may begin with
    # "drain.": the benchmark reads seam="drain.*" as the per-type
    # drain() intervals, which the three phases below are PARTS of.
    "loop.busy",
    "lock.wait_serve",
    "lock.wait_cluster",
    "drain_phase.assemble",
    "drain_phase.device",
    "drain_phase.finish",
    "cluster.decode",
    "cluster.apply",
    "repo.flush",
    # a served burst's stages (server.py, docs/observability.md "A
    # served burst's stages"): with server.native_burst,
    # pipeline.reply_write and pipeline.parse they tile the handler's
    # share of loop.busy. route = read returned -> the round's locks held;
    # tail = reply with the transport -> the next read is called;
    # write_wait = a drain() called with bytes still in the transport
    # (NOT loop work); py_apply = RepoManager._apply_core on the loop
    # thread (a Python-path command's apply and reply render).
    "serve.route",
    "serve.tail",
    "serve.write_wait",
    "serve.py_apply",
    # repo-lock holds that can be seen held (models/manager.py _Hold,
    # docs/observability.md "Repo-lock holds"):
    # holding -> released, by who took the lock. A native burst records
    # none: it lets go within the task step it took in, and its hold IS
    # server.native_burst.
    "lock.hold_serve",
    "lock.hold_converge",
    "lock.hold_flush",
    "lock.hold_sync",
    # the UJSON repo's two host costs per document (models/
    # repo_ujson.py): the render of a GET that reached the repo (the
    # join of the path's kept token order, and on a view's first
    # render the index's build and the sort; a GET the engine's
    # memo answers renders nothing), and the host fold of ONE pending
    # delta into a document or a resident row's decoded view
    # (UJSON.converge, at the cost of the delta's own size)
    "ujson.render",
    "ujson.host_fold",
)

# Exact serving-path counters (`registry.note_serving`): on /metrics
# `jylis_serving_total{kind=...}` beside native_cmds / demoted_cmds
# (which Database.serving_totals adds from the engine's and the
# managers' own tallies), in SYSTEM METRICS the `SERVING <kind> <n>`
# lines. demotions: whole connections moved off the native engine for
# good. busy_refusals: commands refused by a per-class admission cap.
# The next three partition what the server's Python path dispatches, by
# cause: commands of a chunk the busy() rule routed (under
# --admission-cap, a repo lock was held when its bytes arrived: 0
# without a cap), commands the engine handed back (rc 1),
# commands of a connection with no engine or demoted for good.
# reply_bytes: bytes of engine replies handed to writers. slept_bursts:
# native bursts that found the repo lock their next command names held,
# slept for it holding nothing and then ran in the engine (or were
# demoted by a shutdown); by the type of that lock in
# `registry.slept_by_type` (jylis_slept_bursts_total{type=...}).
# loop_sends: reply writes the event loop made itself (`writer.write`:
# a connection with no sender behind it; beside ENGINE sender_sends).
# native_bursts: rounds, one call of the engine's scan_apply each.
# burst_locks: repo locks those rounds took, summed (a round takes the
# locks of the types its commands name). bursts_beside_hold: rounds that
# ran while a lock they did not need was held by somebody else.
# inline_bursts: rounds settled with no task (server.py `_Conn.serve`,
# every chunk of a loop iteration taken up in one callback); the rest of
# native_bursts are a slow-path task's (a sleep for a lock, a command
# handed back, a consumer that is behind, the byte bound).
SERVING = (
    "demotions",
    "busy_refusals",
    "busy_routed_cmds",
    "deferred_cmds",
    "demoted_conn_cmds",
    "reply_bytes",
    "slept_bursts",
    "loop_sends",
    "native_bursts",
    "burst_locks",
    "bursts_beside_hold",
    "inline_bursts",
)

# Exact event counters beside a type's drain totals, `drain.<TYPE>.<kind>`
# (`registry.tally`): on /metrics they are further `kind`s of
# jylis_drain_total, in SYSTEM METRICS `<TYPE> <kind> <n>` lines; and, on
# the same surfaces under the type ENGINE, the native engine's reply
# buffer (`serving.ENGINE.<kind>`). GCOUNT and PNCOUNT
# (models/repo_counters.py), added a slice, never a key: keys whose
# foreign deltas were folded into the table's foreign window (a peer's
# push, a restore, a journal replay), of those the ones that came as a
# slice through `converge_batch` (all of them, unless a caller still
# converges key by key), and the (polarity, replica id) cells those
# folds carried. TREG:
# rows the engine's bulk call assembled (0 on a node that serves from
# the Python tables: no compiler on the host), and rows whose 8-byte
# prefix tied on the device and were settled by the full strings. TLOG:
# what `drain.TLOG`'s batches and keys cannot tell apart (a drain of 8
# rows with 1 pending entry each and one of 8 rows with 500 each are both
# "8 keys"): pending entries a drain carried, drains a TRIM / TRIMAT / CLR
# forced, regrows of the planes, rows a drain left without a host base
# (its fold failed the length guard), one-row device gathers made for a
# read whose drained base the host did not hold (every one has a lost
# base before it), and whole-row sorts of a view by the Python read path;
# entries `converge` buffered (a peer's, a restore's, a journal replay's),
# cutoffs it raised, drains that began with a bound of the table tripped
# (not forced by a trim), and the dispatches drains were made of. UJSON
# (models/repo_ujson.py): documents admitted to the device-resident store
# and how many of those had been resident before, documents sent back to
# the host lattice by cause (a local write under the fan-in rule, a
# sequence number past u32 or an un-encodable document, the store's byte
# budget), rows resident now (admits less demotions), deltas `converge`
# buffered (a peer's, a restore's, a journal replay's), of those the ones
# that reached their document by a device fold alone and the ones a host
# fold joined in, the entries those joins examined for removal (the
# delta's context's size, not the document's), local writes
# applied, of those the ones that became a delta for a resident row
# (--ujson-resident-min-leaves), rows rewritten from their decoded
# view because a delta was too wide for the store's pinned grid, rows
# gathered and decoded for a document with no decoded view, and renders
# that had to sort a path's tokens because the view kept no order for
# it yet (a view's first render of the path). MAP (models/repo_map.py):
# SETs acknowledged, GETALLs answered and the fields those rendered (the
# native field table's own counters for `MAP TREG`, read when a surface
# reports, plus the Python path's), and field rows whose register's
# 8-byte prefix tied on the device and were settled by the host table.
# ENGINE:
# times the reply buffer was replaced by a larger one (a reply alone
# outgrew it), the bytes it holds now (it only grows, so the sum of its
# steps), and commands whose reply passed the buffer's ceiling and went
# to the Python path; and the reply sender's own atomics
# (native/reply_sender.cpp), read when a surface reports: reply writes
# handed to it, sends a socket did not take whole, hand-offs that found
# its thread asleep and woke it, bytes dropped at a reset or at dispose, the
# most bytes it ever held, and the microseconds its thread spent inside
# `send` and the zero-timeout `poll` that looks for POLLOUT (not its idle
# polling, not its sleep).
TALLIES = (
    "drain.GCOUNT.converged_keys",
    "drain.GCOUNT.batched_keys",
    "drain.GCOUNT.foreign_cells",
    "drain.PNCOUNT.converged_keys",
    "drain.PNCOUNT.batched_keys",
    "drain.PNCOUNT.foreign_cells",
    "drain.TREG.bulk_rows",
    "drain.TREG.tie_rows",
    "drain.TLOG.entries",
    "drain.TLOG.trims",
    "drain.TLOG.grows",
    "drain.TLOG.bases_lost",
    "drain.TLOG.row_gathers",
    "drain.TLOG.view_sorts",
    "drain.TLOG.foreign_entries",
    "drain.TLOG.foreign_cutoffs",
    "drain.TLOG.overdue",
    "drain.TLOG.passes",
    "drain.UJSON.admits",
    "drain.UJSON.readmits",
    "drain.UJSON.demote_write",
    "drain.UJSON.demote_overflow",
    "drain.UJSON.demote_budget",
    "drain.UJSON.resident_rows",
    "drain.UJSON.foreign_deltas",
    "drain.UJSON.device_deltas",
    "drain.UJSON.host_deltas",
    "drain.UJSON.host_walked",
    "drain.UJSON.local_writes",
    "drain.UJSON.row_deltas",
    "drain.UJSON.row_rewrites",
    "drain.UJSON.row_reads",
    "drain.UJSON.render_sorts",
    "drain.MAP.sets",
    "drain.MAP.getalls",
    "drain.MAP.getall_fields",
    "drain.MAP.tie_rows",
    "serving.ENGINE.reply_grows",
    "serving.ENGINE.reply_buffer_bytes",
    "serving.ENGINE.oversize_defers",
    "serving.ENGINE.sender_sends",
    "serving.ENGINE.sender_partial",
    "serving.ENGINE.sender_wakes",
    "serving.ENGINE.sender_dropped_bytes",
    "serving.ENGINE.sender_pending_max_bytes",
    "serving.ENGINE.sender_busy_us",
)

# Node-wide gauges (per-peer convergence lag lives on the Cluster and
# surfaces through SYSTEM LATENCY; only the folded node-wide values are
# registry gauges).
GAUGES = (
    "cluster.converge_lag_ms",
    "cluster.backlog_ms",
    # peers whose unacked delta gap fell off the retransmit window and
    # are owed a range repair (schema v8 anti-entropy); pinned at 0 by
    # the churn soak once every heal completes
    "cluster.interval_dirty_peers",
    # bridge failover (PR 15): 1 while this node is its region's
    # elected bridge (0 otherwise, and always 0 region-less), and the
    # live byte depth of the cross-bridge repair relay queue
    "cluster.bridge_is_self",
    "cluster.relay_queue_bytes",
    # overload armor (admission.py): the declared overload state (1
    # while shedding by class, 0 otherwise — hysteresis contract in
    # docs/operations.md) and the live total of un-drained reply bytes
    # the --admission-queue-bytes hard bound is enforced against
    "serving.overload",
    "serving.queued_bytes",
)
