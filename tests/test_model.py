"""jmodel: the schedule-replay corpus + bounded exploration tiers.

Tier-1 (per commit): every schedule file under ``tests/model/`` replays
with all invariants holding — the corpus accumulates one minimized
counterexample per fixed protocol defect (plus the schema-pinning
fixture), so a regression replays the exact interleaving that found the
bug. A small bounded exploration also runs per commit; the deep sweep
(bigger budgets, deeper frontier) rides ``-m soak``. ``make
model-smoke`` (scripts/jmodel --smoke) is the recorded-coverage gate
between the two.
"""

import glob
import json
import os

import pytest

from scripts.jmodel import MODEL_PERIODS, model_periods
from scripts.jmodel.explore import (
    SCHEDULE_SCHEMA,
    Explorer,
    minimize,
    replay_schedule,
    schedule_dict,
)
from scripts.jmodel.net import Link, Network, VirtualClock
from scripts.jmodel.world import CONFIG_NAMES, Violation, World

CORPUS = sorted(
    glob.glob(os.path.join(os.path.dirname(__file__), "model", "*.json"))
)


# ---- corpus ----------------------------------------------------------------


def test_corpus_exists_and_pins_schema():
    """The corpus directory ships with at least the schema fixture, and
    every committed schedule is a well-formed expect=pass regression."""
    assert CORPUS, "tests/model/ must hold at least the schema fixture"
    for path in CORPUS:
        with open(path, encoding="utf-8") as f:
            data = json.load(f)
        assert data["schema"] == SCHEDULE_SCHEMA, path
        assert data["config"] in CONFIG_NAMES, path
        assert data["expect"] == "pass", (
            f"{path}: a committed schedule must expect 'pass' — an "
            "invariant name means an UNFIXED defect was committed"
        )
        assert isinstance(data["actions"], list) and data["actions"], path


@pytest.mark.parametrize(
    "path", CORPUS, ids=[os.path.basename(p) for p in CORPUS]
)
def test_corpus_replays_clean(path):
    with open(path, encoding="utf-8") as f:
        data = json.load(f)
    with model_periods():
        violation = replay_schedule(data)
    assert violation is None, (
        f"{os.path.basename(path)} regressed: {violation} — the defect "
        "this schedule pinned has come back"
    )


def test_replay_skips_actions_the_protocol_no_longer_enables():
    """A schedule referencing a conn that never exists degrades to a
    weaker test (skipped action), never a spurious failure — corpus
    files must survive protocol evolution."""
    sched = schedule_dict(
        "nodes2",
        [("deliver", "A>B#9", "fwd"), ("tick", "A"), ("quiesce",)],
    )
    with model_periods():
        assert replay_schedule(sched) is None


# ---- explorer machinery ----------------------------------------------------


def test_quick_exploration_holds_all_invariants():
    with model_periods():
        result = Explorer("nodes2", 3).run()
    assert result.violation is None, result.violation
    assert result.states > 50
    assert result.quiesced >= 1  # the first leaf always quiesces


def test_exploration_is_deterministic():
    with model_periods():
        a = Explorer("nodes3", 2).run()
        b = Explorer("nodes3", 2).run()
    assert (a.states, a.leaves) == (b.states, b.leaves)


# ---- BCOUNT escrow invariant (schema v9) -----------------------------------


def test_bcount_decrement_transfer_schedules_hold_invariant():
    """Exhaustive (bounded) exploration of concurrent BCOUNT decrement
    and escrow-transfer schedules: `0 <= value <= bound` holds on every
    replica's view in EVERY explored state. The bcount-focused budget
    zeros the structural-fault axes so the frontier is spent on the
    contention interplay: decs racing transfers racing delivery."""
    with model_periods():
        result = Explorer(
            "nodes2",
            6,
            budgets={
                "bdecs": 2, "bxfers": 1, "writes": 0, "kills": 0,
                "crashes": 0, "partitions": 0, "dups": 0,
            },
            max_states=30_000,
        ).run()
    assert result.violation is None, result.violation
    assert result.states > 500


def test_broken_escrow_rule_yields_minimized_counterexample():
    """Arm the DELIBERATELY broken escrow rule (decrement without the
    local rights check — world.py escrow_unsafe) and the explorer must
    find `value < 0`, minimize the schedule to the over-drawing
    decrements alone, and produce a standalone-replayable artifact.
    The same schedule replayed against the CORRECT rule holds every
    invariant — the escrow check is exactly what the bound rests on."""
    with model_periods():
        result = Explorer(
            "nodes2",
            5,
            budgets={"bdecs": 3, "bxfers": 1},
            max_states=20_000,
            escrow_unsafe=True,
        ).run()
        assert result.violation is not None
        assert result.violation["invariant"] == "bcount_negative"
        sched = result.schedule
        assert sched["escrow_unsafe"] is True
        # minimized to the decrement core: nothing structural survives
        assert all(a[0] == "bdec" for a in sched["actions"]), sched["actions"]
        assert len(sched["actions"]) == 3  # bound 2 + 1 overdraw
        # the artifact replays standalone to the SAME violation
        v = replay_schedule(json.loads(json.dumps(sched)))
        assert v is not None and v.name == "bcount_negative"
        # and the correct rule survives the identical schedule
        safe = {k: v2 for k, v2 in sched.items() if k != "escrow_unsafe"}
        assert replay_schedule(safe) is None


def test_bcount_transfer_funds_remote_decrements():
    """Directed schedule: the seed-escrow replica transfers a right to
    B; after delivery B's previously-refused decrement succeeds, and the
    quiesced world digest-matches with value within bounds."""
    from scripts.jmodel.world import BCOUNT_KEY

    with model_periods():
        world = World("nodes2", {"bdecs": 2, "bxfers": 1})
        try:
            db_a, db_b = world.dbs["A"], world.dbs["B"]
            # B holds no escrow yet: the local check refuses (OUTOFBOUND)
            assert not db_b.local_bdec()
            assert db_b.refused_decs == 1
            assert db_a.local_bxfer(db_b.rid)
            world.quiesce()  # ships the transfer, heals everything
            assert db_b.local_bdec(), "delivered escrow must fund the dec"
            world.quiesce()
            bc_a = db_a.state_b[BCOUNT_KEY]
            assert bc_a.value() == 1 and bc_a.bound() == 2
            assert len(set(world._digests().values())) == 1
        finally:
            world.close()


def test_crash_reboot_recovers_local_writes_and_reconverges():
    with model_periods():
        world = World("nodes2")
        try:
            world.apply(("write", "A"))
            world.apply(("crash", "A"))
            # the journaled local writes survived the reboot (the seed
            # write on x, the extra write on the next cycled key)
            assert world.dbs["A"].state[b"x"][1] == 1
            assert world.dbs["A"].state[b"y"][1] == 1
            world.quiesce()
            assert len(set(world._digests().values())) == 1
        finally:
            world.close()


# ---- sessions & regions (schema v10) ---------------------------------------


def test_regions_world_prunes_to_sparse_topology_and_relays():
    """The regions3 config: after quiescence the topology is the policy
    one (bar<->baz never peered — their traffic transits foo's
    origin-preserving relays), every replica digest-matches, and every
    minted token is dominated everywhere (the quiesce session law)."""
    with model_periods():
        world = World("regions3")
        try:
            world.apply(("write", "bar"))
            world.apply(("mint", "bar"))
            world.quiesce()
            assert len(set(world._digests().values())) == 1
            bar = world.instances["bar"].cluster
            baz = world.instances["baz"].cluster
            bar_addr = str(world.instances["bar"].addr)
            baz_addr = str(world.instances["baz"].addr)
            assert baz_addr not in {str(a) for a in bar._actives}
            assert bar_addr not in {str(a) for a in baz._actives}
            # the relay chain actually carried traffic
            foo = world.instances["foo"].cluster
            assert foo._stats["relays_sent"] > 0
            # bar's token verifies on baz: the cross-region session path
            _g, vec, floor, _b = world.tokens[0]
            svec = world.dbs["baz"].sessions.vector()
            assert all(svec.get(r, 0) >= s for r, s in vec.items())
            assert world.dbs["baz"].state[b"y"][2] >= 1  # bar's write
        finally:
            world.close()


def test_session_exploration_holds_ryw_in_every_config():
    """Bounded exploration with a mint in every group: the session_ryw
    invariant (a token-satisfied read never observes a regression) and
    the quiescence domination law hold across every explored schedule
    of the regions config."""
    with model_periods():
        result = Explorer("regions3", 3, quiesce_every=8).run()
    assert result.violation is None, result.violation
    assert result.states > 200, result.states


def _drive_session_break(session_unsafe: bool):
    """Directed schedule for the broken-watermark demonstration: A's
    seed write reaches B, A mints, B crash-reboots (losing A's column —
    remote state is not journaled), A writes again and only the NEW seq
    reaches the rebooted B (its rejoin sync is held back). The unsafe
    watermark rule jumps over the gap and B falsely satisfies A's
    token; the safe rule parks the seq and stays honestly STALE."""
    with model_periods():
        w = World("nodes2", session_unsafe=session_unsafe)
        trace: list = []

        def do(a):
            trace.append(tuple(a))
            if w.apply(a):
                w.check_invariants()

        def pump():
            # deliver ONLY the A-dialed conn's frames: B's own rejoin
            # sync stays in flight, so the x column is still missing
            # when the post-crash seq push arrives
            for _ in range(4):
                for a in list(w.enabled_actions()):
                    if a[0] == "deliver" and a[1].startswith("A>"):
                        do(a)

        try:
            do(("tick", "A"))
            pump()
            do(("tick", "A"))
            pump()
            do(("mint", "A"))
            do(("crash", "B"))
            do(("write", "A"))
            for _ in range(6):
                do(("tick", "A"))
                pump()
            return None, trace
        except Violation as v:
            return v, trace
        finally:
            w.close()


def test_broken_session_watermark_yields_minimized_counterexample():
    """Arm the DELIBERATELY broken session-watermark rule (first-
    observed jump — sessions.SessionIndex unsafe mode) and the directed
    schedule must produce a token-satisfied read missing the token's
    write (session_ryw); ddmin shrinks it to a standalone-replayable
    artifact, and the SAME schedule against the correct contiguity rule
    holds every invariant — the strict watermark is exactly what
    read-your-writes rests on."""
    v, trace = _drive_session_break(session_unsafe=True)
    assert v is not None and v.name == "session_ryw", v
    with model_periods():
        minimized = minimize(
            "nodes2", trace, "session_ryw", session_unsafe=True
        )
        sched = schedule_dict(
            "nodes2", minimized, expect="session_ryw",
            note=v.detail, session_unsafe=True,
        )
        assert sched["session_unsafe"] is True
        assert len(minimized) < len(trace)
        replayed = replay_schedule(json.loads(json.dumps(sched)))
        assert replayed is not None and replayed.name == "session_ryw"
        # the correct rule survives the identical schedule
        safe = {k: v2 for k, v2 in sched.items() if k != "session_unsafe"}
        assert replay_schedule(safe) is None


def test_safe_session_rule_survives_the_directed_schedule():
    v, _trace = _drive_session_break(session_unsafe=False)
    assert v is None, v


def test_minimizer_shrinks_to_the_failing_core(monkeypatch):
    from scripts.jmodel import explore

    def fake_replay(data, budgets=None, runtime=None):
        acts = [tuple(a) for a in data["actions"]]
        if ("tick", "A") in acts and ("tick", "B") in acts:
            return Violation("fake", "both ticks present")
        return None

    monkeypatch.setattr(explore, "replay_schedule", fake_replay)
    out = minimize(
        "nodes2",
        [("tick", "A"), ("write", "A"), ("tick", "B"), ("write", "B")],
        "fake",
    )
    assert out == [("tick", "A"), ("tick", "B")]


def test_model_periods_patch_is_scoped():
    from jylis_tpu.cluster import cluster as cluster_mod

    before = cluster_mod.SYNC_PERIOD_TICKS
    with model_periods():
        assert cluster_mod.SYNC_PERIOD_TICKS == (
            MODEL_PERIODS["SYNC_PERIOD_TICKS"]
        )
    assert cluster_mod.SYNC_PERIOD_TICKS == before


# ---- model network semantics ----------------------------------------------


def test_virtual_clock_is_explorer_driven():
    clock = VirtualClock()
    t0 = clock.now_ms()
    assert clock.now_ms() == t0  # never advances on its own
    clock.advance(250)
    assert clock.now_ms() == t0 + 250
    assert clock.perf() < clock.perf()  # strictly increasing stamps


def test_link_kill_discards_in_flight_frames():
    net = Network()
    link = Link("t/fwd", net)
    link.write(b"frame1")
    link.deliver_one()
    link.write(b"frame2")
    link.kill()
    assert link.eof
    assert not link.outbox and not link.inbox  # torn-down socket = loss


# ---- the deep sweep (nightly) ----------------------------------------------


@pytest.mark.soak
@pytest.mark.slow  # nightly (`make soak`), not per-commit — every soak
# test carries both marks so tier-1's `-m 'not slow'` override (which
# replaces the addopts soak filter) still skips it; the v8 state space
# is ~2x the v7 one, which pushed these cells well past the tier-1 box
@pytest.mark.parametrize(
    "config,depth",
    [("nodes2", 8), ("nodes3", 6), ("regions3", 6)],
)
def test_soak_deep_exploration(config, depth):
    """Bigger budgets (two kills / dups / crashes), deeper frontier,
    denser quiescence sampling — bounded by max_states so the nightly
    stays finite."""
    with model_periods():
        result = Explorer(
            config,
            depth,
            budgets={"kills": 2, "dups": 2, "crashes": 2},
            quiesce_every=32,
            max_states=60_000,
        ).run()
    assert result.violation is None, result.violation
    assert result.states > 1_000


def test_bridge_tokens_verify_live_despite_interleaved_relays():
    """Review-find regression: a bridge's stream interleaves its own
    SeqPush with RelayPush frames; receivers must advance the bridge's
    OWN watermark on BOTH (contiguous transport application covers
    every own-write frame below), or one relayed frame parks the
    bridge's next own seq forever and its tokens go STALE on the LIVE
    path. Adoption masks the bug wherever a digest sync fires, so this
    test runs at PRODUCTION periods (no model_periods shrink): the
    whole window stays under one SYNC_PERIOD, the only adoption is the
    establishment-time sync (before the minted seqs exist), and the
    assertion exercises pure contiguous application."""
    w = World("regions3")
    try:
        def pump(rounds: int):
            for _ in range(rounds):
                for key in sorted(w.instances):
                    if w.instances[key].alive:
                        w.apply(("tick", key))
                for _ in range(4):
                    for a in list(w.enabled_actions()):
                        if a[0] == "deliver":
                            w.apply(a)

        pump(8)  # establish + seed writes + relays flowing
        # bar's seed write has crossed foo's relay into baz by now;
        # foo's stream therefore carries RelayPush frames. Mint at
        # foo AFTER a fresh foo write: its token references foo
        # seqs ABOVE the relay frames.
        w.apply(("write", "foo"))
        w.apply(("mint", "foo"))
        g, vec, floor, _boot = w.tokens[-1]
        assert g == "foo"
        foo_srid = w.instances["foo"].cluster._srid
        assert vec.get(foo_srid, 0) > 0, vec
        # foo relayed at least one foreign batch below the minted seq
        assert w.instances["foo"].cluster._stats["relays_sent"] > 0
        pump(8)  # live delivery only — total ticks < SYNC_PERIOD_TICKS
        for group in ("bar", "baz"):
            svec = w.dbs[group].sessions.vector()
            assert all(
                svec.get(r, 0) >= s for r, s in vec.items()
            ), (group, svec, vec)
    finally:
        w.close()


# ---- bridge failover (PR 15) ------------------------------------------------


def _drive_bridge_break(bridge_unsafe: bool):
    """Directed schedule for the broken-demotion demonstration: foo is
    region ra's bridge (foo+bar; baz is rb). Mesh up so bar holds
    received-frame evidence of foo, bkill foo (down and STAYS down —
    the new axis), then keep ticking bar: its evidence of foo ages
    past the model demotion bound while bar itself is a live
    successor. The broken rule (never demote — the pre-failover v10
    behavior) keeps electing the dead bridge, which the
    bridge_demotion invariant flags; the safe rule hands over to bar
    on the same schedule."""
    from scripts.jmodel.world import BRIDGE_DEMOTE_MODEL

    with model_periods():
        w = World("regions3", bridge_unsafe=bridge_unsafe)
        trace: list = []

        def do(a):
            trace.append(tuple(a))
            if w.apply(a):
                w.check_invariants()

        def pump():
            for _ in range(4):
                for a in list(w.enabled_actions()):
                    if a[0] == "deliver":
                        do(a)

        try:
            for _ in range(3):
                for key in ("foo", "bar", "baz"):
                    do(("tick", key))
                pump()
            # bar must hold direct evidence of foo before the kill, or
            # demotion has nothing to age out
            assert (
                str(w.instances["foo"].addr)
                in w.instances["bar"].cluster._seen_tick
            )
            do(("bkill", "foo"))
            for _ in range(BRIDGE_DEMOTE_MODEL + 3):
                do(("tick", "bar"))
            return None, trace
        except Violation as v:
            return v, trace
        finally:
            w.close()


def test_broken_demotion_rule_yields_minimized_counterexample():
    """Arm the DELIBERATELY broken bridge-demotion rule (an
    unreachable threshold — exactly the v10 single-WAN-path status
    quo) and the directed schedule must keep a provably-dead bridge
    elected past the bound with a live successor available
    (bridge_demotion); ddmin shrinks it to a standalone-replayable
    artifact, and the SAME schedule under the real liveness rule holds
    every invariant — bounded handover is exactly what the demotion
    threshold buys."""
    v, trace = _drive_bridge_break(bridge_unsafe=True)
    assert v is not None and v.name == "bridge_demotion", v
    with model_periods():
        minimized = minimize(
            "regions3", trace, "bridge_demotion", bridge_unsafe=True
        )
        sched = schedule_dict(
            "regions3", minimized, expect="bridge_demotion",
            note=v.detail, bridge_unsafe=True,
        )
        assert sched["bridge_unsafe"] is True
        assert len(minimized) < len(trace)
        replayed = replay_schedule(json.loads(json.dumps(sched)))
        assert replayed is not None and replayed.name == "bridge_demotion"
        # the liveness rule survives the identical schedule (and its
        # final auto-quiesce reboots the killed bridge and converges)
        safe = {k: v2 for k, v2 in sched.items() if k != "bridge_unsafe"}
        assert replay_schedule(safe) is None


def test_safe_demotion_rule_survives_the_directed_schedule():
    v, _trace = _drive_bridge_break(bridge_unsafe=False)
    assert v is None, v


def test_bkill_window_explores_and_quiesce_reboots():
    """The bkill/breboot axis end to end: kill the bridge, let the
    survivors churn through the succession window, reboot, and the
    world still quiesces to a digest match with every ladder law
    holding (zero whole-state dumps is the real cluster's gate; here
    the model's convergence + drain laws are the proof)."""
    with model_periods():
        w = World("regions3")
        try:
            def pump(rounds: int):
                for _ in range(rounds):
                    for key in sorted(w.instances):
                        if w.instances[key].alive:
                            w.apply(("tick", key))
                    for _ in range(4):
                        for a in list(w.enabled_actions()):
                            if a[0] == "deliver":
                                w.apply(a)
                    w.check_invariants()

            pump(4)
            # baseline BEFORE the kill: bootstrap already counted the
            # self -> foo reclassification on bar
            h0 = w.instances["bar"].cluster._stats["bridge_handovers"]
            assert w.apply(("bkill", "foo"))
            assert not w._group_alive("foo")
            w.check_invariants()
            pump(8)  # the succession window: bar takes over ra
            bar = w.instances["bar"].cluster
            assert bar._bridge_of("ra") == str(w.instances["bar"].addr)
            assert bar._stats["bridge_handovers"] > h0
            assert w.apply(("breboot", "foo"))
            pump(4)
            w.quiesce()  # digest match + drained ladders everywhere
        finally:
            w.close()
