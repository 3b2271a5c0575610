"""Config and CLI flag parsing.

Reference analog: config.pony:5-97. Same flags and defaults:
--addr/-a (host:port:name advertised to peers), --port/-p (RESP port),
--seed-addrs/-s (space-separated), --heartbeat-time/-T (seconds, float),
--system-log-trim (entries kept in SYSTEM GETLOG), --log-level/-L.

One deliberate divergence: the reference assigns short flag 'T' to BOTH
heartbeat-time and system-log-trim (config.pony:36,41 — a latent bug noted
in SURVEY.md section 5.6); here system-log-trim has no short flag.
"""

from __future__ import annotations

import argparse
import random
import sys
import time
from dataclasses import dataclass, field

from .address import Address
from .log import Log
from .namegen import generate_name


@dataclass
class Config:
    port: str = "6379"
    addr: Address = field(default_factory=lambda: Address.from_string("127.0.0.1:9999:"))
    seed_addrs: list[Address] = field(default_factory=list)
    heartbeat_time: float = 10.0
    system_log_trim: int = 200
    data_dir: str = ""  # extension: snapshot/restore (persist.py)
    snapshot_interval: float = 0.0  # extension: online snapshot cadence
    # extension: delta write-ahead journal (journal/journal.py) — on by
    # default whenever data_dir is set; the flags below tune it
    journal: bool = True
    journal_fsync: str = "interval"
    journal_fsync_interval: float = 0.2
    journal_max_bytes: int = 64 << 20
    # extension: UJSON residency by size (models/repo_ujson.py) — a
    # document of this many leaves or more lives in the device-resident
    # store from restore (or from the write that grows it there) on, and
    # stays there under local writes; 0 (default) leaves promotion to
    # the anti-entropy fan-in alone (docs/types/ujson.md, "On the TPU")
    ujson_resident_min_leaves: int = 0
    # extension: peer dial lifecycle (cluster.py) — connect timeout in
    # seconds and the exponential-backoff ceiling in heartbeat ticks
    dial_timeout: float = 5.0
    dial_backoff_cap: int = 32
    # extension: anti-entropy v2 tuning (cluster.py, schema v8) — the
    # retransmit window (sequenced delta batches kept for per-peer
    # ack-gap replay; a peer whose gap falls off is demoted to range
    # repair) and the range-repair budget (digest-tree buckets pulled/
    # served per round, the rejoin pacing knob)
    delta_log_cap: int = 1024
    range_budget: int = 64
    # extension: region-aware WAN peering (cluster.py, schema v10) —
    # empty (default) keeps the classic full mesh; a named region joins
    # its intra-region full mesh, with one deterministic bridge per
    # region speaking WAN (docs/operations.md, "Regions")
    region: str = ""
    # bridge failover (PR 15): heartbeat ticks of received-frame silence
    # after which an observer demotes an address from bridge election —
    # the next-smallest live address takes over with no election
    # traffic. With ANNOUNCE_EVERY=3 the default tolerates four missed
    # announce rounds before a handover (docs/operations.md, "Regions")
    bridge_demote_ticks: int = 12
    # extension: session guarantees (sessions.py, docs/sessions.md) —
    # how long a SESSION READ may wait for its token to be covered
    # before the typed STALE refusal
    session_wait_ms: int = 500
    # extension: per-command-class admission control (models/manager.py)
    # — commands of one data type queued behind its repo lock past this
    # cap get a typed BUSY refusal; 0 (default) disables
    admission_cap: int = 0
    # extension: overload armor (admission.py) — priority order + the
    # pressure thresholds for node-wide shedding; empty (default)
    # disables shedding (the queued-bytes bound below still applies)
    admission_policy: str = ""
    # hard bound on total un-drained reply bytes across connections: a
    # slow-consumer burst past it gets BUSY on EVERY class so the loop
    # can never OOM on parked replies; 0 disables
    admission_queue_bytes: int = 256 << 20
    # extension: deterministic fault injection (faults.py); same syntax
    # as the JYLIS_FAILPOINTS env var, armed at startup
    failpoints: str = ""
    # extension: opt-in Prometheus text-exposition endpoint (obs/prom.py);
    # 0 disables, -1 asks for an ephemeral port (logged at boot)
    metrics_port: int = 0
    # extension: delta provenance tracing (obs/jtrace.py, schema v11) —
    # one sequenced delta frame in N carries a hop-stamped trace span;
    # receivers fold spans into per-hop and per-region-pair convergence
    # histograms (SYSTEM TRACE SPANS). 0 disables minting entirely.
    trace_sample: int = 16
    # ... and the fleet-convergence SLO thresholds: the fraction of
    # sampled deltas fully applied within each of these milliseconds
    # bounds, exported as the jylis_converge_slo gauge family
    converge_slo_ms: str = "50,250,1000"
    log: Log = field(default_factory=Log.create_none)

    def normalize(self) -> None:
        if not self.addr.name:
            rng = random.Random(time.time_ns())
            self.addr = Address(self.addr.host, self.addr.port, generate_name(rng))


def build_parser() -> argparse.ArgumentParser:
    """Every flag the node takes (docs/operations.md, "Flags")."""
    parser = argparse.ArgumentParser(
        prog="jylis-tpu",
        description="TPU-native distributed in-memory database for CRDTs",
    )
    parser.add_argument(
        "-a", "--addr", default="127.0.0.1:9999:",
        help="The host:port:name to be advertised to other clustering nodes.",
    )
    parser.add_argument(
        "-p", "--port", default="6379",
        help="The port for accepting commands over RESP-protocol connections.",
    )
    parser.add_argument(
        "-s", "--seed-addrs", default="",
        help="A space-separated list of the host:port:name for other known nodes.",
    )
    parser.add_argument(
        "-T", "--heartbeat-time", type=float, default=10.0,
        help="The number of seconds between heartbeats in the clustering protocol.",
    )
    parser.add_argument(
        "--system-log-trim", type=int, default=200,
        help="The number of entries to retain in the distributed `SYSTEM GETLOG`.",
    )
    parser.add_argument(
        "--data-dir", default="",
        help="Directory for state snapshots: restored on boot, written on "
        "clean shutdown. Empty (default) disables persistence, like the "
        "reference.",
    )
    parser.add_argument(
        "--snapshot-interval", type=float, default=0.0,
        help="Seconds between ONLINE snapshots while serving (requires "
        "--data-dir). 0 (default) snapshots only at clean shutdown; a "
        "crash then loses everything since boot, so long-lived nodes "
        "should set an interval (writes are atomic; each type dumps "
        "under its own lock, so serving never pauses globally).",
    )
    parser.add_argument(
        "--no-journal", action="store_true",
        help="Disable the delta write-ahead journal. With --data-dir the "
        "journal is ON by default: every flushed delta batch appends to "
        "DIR/journal.jylis and is converged back on boot, closing the "
        "crash-loss window between snapshots (docs/durability.md).",
    )
    parser.add_argument(
        "--journal-fsync", choices=("always", "interval", "off"),
        default="interval",
        help="Journal fsync policy: 'always' fsyncs every append, "
        "'interval' fsyncs at most once per --journal-fsync-interval "
        "seconds (bounded power-loss window; a plain process crash loses "
        "nothing under any policy), 'off' leaves syncing to the OS.",
    )
    parser.add_argument(
        "--journal-fsync-interval", type=float, default=0.2,
        help="Seconds between journal fsyncs under --journal-fsync "
        "interval (the power-loss data-at-risk window).",
    )
    parser.add_argument(
        "--journal-max-bytes", type=int, default=64 << 20,
        help="Journal size that triggers compaction: a fresh snapshot is "
        "cut and the old journal segment retired (docs/durability.md).",
    )
    parser.add_argument(
        "--ujson-resident-min-leaves", type=int,
        default=Config.ujson_resident_min_leaves,
        help="UJSON documents of this many leaves or more are kept in "
        "the device-resident store: admitted when a snapshot or the "
        "journal restores them or when a write grows them to the size, "
        "the store sized and its fold programs compiled at boot, and "
        "local INS/RM/SET/CLR applied as row deltas instead of sending "
        "the document back to the host lattice. 0 (default) promotes by "
        "anti-entropy fan-in only (docs/types/ujson.md, 'On the TPU').",
    )
    parser.add_argument(
        "--dial-timeout", type=float, default=Config.dial_timeout,
        help="Seconds before an outbound cluster dial attempt is "
        "abandoned (a blackholed peer would otherwise hang for the "
        "OS's minutes-long TCP timeout). Failed dials back off "
        "exponentially up to --dial-backoff-cap heartbeat ticks.",
    )
    parser.add_argument(
        "--dial-backoff-cap", type=int, default=Config.dial_backoff_cap,
        help="Ceiling, in heartbeat ticks, for the exponential re-dial "
        "backoff to an unreachable peer (deterministic jitter of up to "
        "half the backoff is added). Inbound contact from the address "
        "resets its backoff immediately.",
    )
    parser.add_argument(
        "--delta-log-cap", type=int, default=Config.delta_log_cap,
        help="Sequenced delta batches kept in the retransmit window for "
        "per-peer ack-gap replay (schema v8 delta intervals). A peer "
        "whose unacked gap falls off the window is marked "
        "interval-dirty and demoted to Merkle-range repair — never a "
        "whole-state dump (docs/replication.md).",
    )
    parser.add_argument(
        "--range-budget", type=int, default=Config.range_budget,
        help="Digest-tree buckets (of 256) pulled/served per "
        "range-repair round: the rejoin pacing knob — smaller values "
        "spread a big heal over more rounds so one rejoining node "
        "cannot starve serving (docs/replication.md).",
    )
    parser.add_argument(
        "--region", default="",
        help="This node's region name for WAN-aware peering (schema "
        "v10): nodes of one region keep a cheap full mesh; exactly one "
        "deterministic bridge per region (the lexicographically "
        "smallest advertised address) dials the other regions' "
        "bridges and relays traffic with origin attribution preserved. "
        "Empty (default) keeps the classic full mesh. All nodes of a "
        "deployment should either set regions or not mix.",
    )
    parser.add_argument(
        "--bridge-demote-ticks", type=int,
        default=Config.bridge_demote_ticks,
        help="Heartbeat ticks of received-frame silence after which a "
        "node demotes an address from bridge election (regions only): "
        "a dead bridge is succeeded by the next-smallest live address "
        "within this bound, with no election traffic. The default "
        "tolerates four missed announce rounds; lower it for faster "
        "WAN failover at the cost of spurious handovers under load "
        "(harmless — relay dedup absorbs dual-bridge overlap).",
    )
    parser.add_argument(
        "--session-wait-ms", type=int, default=Config.session_wait_ms,
        help="Bounded wait for SESSION READ: how long a read holding a "
        "session token may wait for this replica's applied-interval "
        "vector to cover it before the typed STALE refusal "
        "(docs/sessions.md).",
    )
    parser.add_argument(
        "--admission-cap", type=int, default=Config.admission_cap,
        help="Per-command-class admission control: commands of one data "
        "type queued behind its repo lock past this cap are refused "
        "with a typed BUSY error, so a hot key's drain backlog "
        "degrades its own command class instead of the node. 0 "
        "(default) disables.",
    )
    parser.add_argument(
        "--admission-policy", default=Config.admission_policy,
        help="Overload armor (docs/operations.md, 'Overload'): the "
        "priority order for node-wide shedding plus optional pressure "
        "thresholds, e.g. 'control>read>write>bulk,lat=25,depth=128,"
        "protect=2'. While the node's declared OVERLOAD state is on "
        "(dispatch-latency EWMA past 'lat' ms or in-flight depth past "
        "'depth', with hysteresis), classes below the top 'protect' "
        "ranks are refused with a typed BUSY carrying a retry-after "
        "hint. SESSION WRAP/READ classify as their inner command. "
        "Empty (default) disables shedding.",
    )
    parser.add_argument(
        "--admission-queue-bytes", type=int,
        default=Config.admission_queue_bytes,
        help="Hard bound on total un-drained reply bytes across client "
        "connections (transport buffers + reply staging): past it every "
        "command class is refused BUSY until consumers drain, so a "
        "slow-consumer burst can never OOM the serving loop. 0 "
        "disables.",
    )
    parser.add_argument(
        "--failpoints", default="",
        help="Deterministic fault injection spec, e.g. "
        "'cluster.dial=error:3,journal.fsync=sleep:0.2' "
        "(name=action[:arg[:budget]], comma-separated; actions: error, "
        "sleep, corrupt, crash, drop). Also read from the "
        "JYLIS_FAILPOINTS environment variable; see "
        "docs/operations.md. Empty (default) injects nothing and "
        "costs nothing.",
    )
    parser.add_argument(
        "--metrics-port", type=int, default=0,
        help="Serve Prometheus text exposition on this HTTP port "
        "(GET /metrics): commands served, serving split, journal and "
        "cluster counters, latency-seam summaries, and the "
        "convergence-lag/backlog gauges — the same surface as SYSTEM "
        "METRICS, scrapeable without a Redis client. -1 binds an "
        "ephemeral port (logged at boot); 0 (default) disables.",
    )
    parser.add_argument(
        "--trace-sample", type=int, default=Config.trace_sample,
        help="Delta provenance tracing (docs/observability.md): one "
        "sequenced delta frame in N carries a trace span stamped at "
        "every hop (origin, bridge relay); the "
        "applying node folds it into per-hop and per-region-pair "
        "convergence-latency histograms (SYSTEM TRACE SPANS) and the "
        "convergence SLO gauges. Schema v11 transport field — v10 "
        "peers interoperate, unsampled frames cost one byte. 0 "
        "disables minting (received spans still fold).",
    )
    parser.add_argument(
        "--converge-slo-ms", default=Config.converge_slo_ms,
        help="Comma-separated millisecond thresholds for the "
        "fleet-convergence SLO gauges: each exports the fraction of "
        "sampled deltas (see --trace-sample) fully applied within "
        "that bound end to end (jylis_converge_slo, SYSTEM OBSERVE).",
    )
    parser.add_argument(
        "-L", "--log-level", default="info",
        help="Maximum level of detail for logging (error, warn, info, or debug).",
    )
    from .. import __version__

    parser.add_argument(
        "--version", action="version", version=f"jylis-tpu {__version__}",
    )
    return parser


def config_from_cli(argv: list[str] | None = None, log_out=None) -> Config:
    parser = build_parser()
    args = parser.parse_args(argv)
    if args.snapshot_interval > 0 and not args.data_dir:
        parser.error("--snapshot-interval requires --data-dir")

    config = Config()
    config.port = args.port
    config.addr = Address.from_string(args.addr)
    config.seed_addrs = [
        Address.from_string(s) for s in args.seed_addrs.split(" ") if s
    ]
    config.heartbeat_time = args.heartbeat_time
    config.system_log_trim = args.system_log_trim
    config.data_dir = args.data_dir
    config.snapshot_interval = args.snapshot_interval
    config.journal = not args.no_journal
    config.journal_fsync = args.journal_fsync
    config.journal_fsync_interval = args.journal_fsync_interval
    config.journal_max_bytes = args.journal_max_bytes
    if args.ujson_resident_min_leaves < 0:
        parser.error("--ujson-resident-min-leaves must be >= 0")
    config.ujson_resident_min_leaves = args.ujson_resident_min_leaves
    config.dial_timeout = args.dial_timeout
    config.dial_backoff_cap = args.dial_backoff_cap
    config.delta_log_cap = args.delta_log_cap
    config.range_budget = args.range_budget
    config.region = args.region
    config.bridge_demote_ticks = args.bridge_demote_ticks
    config.session_wait_ms = args.session_wait_ms
    config.admission_cap = args.admission_cap
    config.admission_policy = args.admission_policy
    if config.admission_policy:
        from ..admission import PolicySpecError, parse_policy

        try:
            parse_policy(config.admission_policy)
        except PolicySpecError as e:
            parser.error(f"--admission-policy: {e}")
    config.admission_queue_bytes = args.admission_queue_bytes
    config.failpoints = args.failpoints
    config.metrics_port = args.metrics_port
    if args.trace_sample < 0:
        parser.error("--trace-sample must be >= 0")
    config.trace_sample = args.trace_sample
    try:
        slo = [int(s) for s in args.converge_slo_ms.split(",") if s.strip()]
    except ValueError:
        slo = None
    if not slo or any(ms <= 0 for ms in slo):
        parser.error(
            "--converge-slo-ms must be comma-separated positive "
            f"milliseconds: {args.converge_slo_ms!r}"
        )
    config.converge_slo_ms = args.converge_slo_ms

    level = {"error": "err", "warn": "warn", "info": "info", "debug": "debug"}.get(
        args.log_level
    )
    if level is None:
        print(f"Unknown log-level: {args.log_level}")
        sys.exit(1)
    config.log = Log(level, log_out)

    config.normalize()
    return config
