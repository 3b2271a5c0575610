"""The cluster-aware client (jylis_tpu/client.py ClusterClient).

Two layers, matching docs/client.md's contract:

* Scripted-connection units: a FakeConn speaks the reply side of the
  protocol from a per-endpoint script, so the typed BUSY / STALE /
  BADTOKEN backoff paths, the jittered-exponential schedule, the
  failover + MTTR accounting, and the token-join monotonicity are all
  deterministic (injected sleep/clock/rng — no sockets, no timing).
* Spawned-node integration: REAL node processes for the parts a stub
  cannot vouch for — token monotonicity across a SIGKILL failover and
  topology re-discovery after a node leaves.
"""

import time

import pytest

from procutil import connect_client, free_port, spawn_node, stop_node

from jylis_tpu import sessions
from jylis_tpu.client import (
    Client,
    ClusterClient,
    ClusterError,
    ResponseError,
)

A = ("10.9.9.1", 1)
B = ("10.9.9.2", 2)


def _tok(vec):
    return sessions.encode_token(vec)


class FakeConn:
    """One endpoint's scripted reply stream. Script entries: a value
    (returned), or an Exception instance (raised)."""

    def __init__(self, ep, script):
        self.ep = ep
        self.script = script
        self.calls = []
        self.closed = False

    def execute_command(self, *args):
        self.calls.append(args)
        if not self.script:
            raise AssertionError(f"script exhausted on {self.ep}: {args}")
        r = self.script.pop(0)
        if isinstance(r, Exception):
            raise r
        return r


class _Clock:
    """Deterministic monotonic clock: every read advances a little, so
    MTTR spans are nonzero without real sleeping."""

    def __init__(self):
        self.t = 0.0

    def __call__(self):
        self.t += 0.01
        return self.t


class FakeCluster(ClusterClient):
    def __init__(self, scripts, **kw):
        self.sleeps = []
        self.fakes = {}
        self._scripts = scripts
        kw.setdefault("sleep_fn", self.sleeps.append)
        kw.setdefault("clock", _Clock())
        super().__init__(list(scripts), **kw)

    def discover(self):  # scripted units skip topology polling
        self.stats["rediscoveries"] += 1

    def _connect(self, ep):
        c = self.fakes.get(ep)
        if c is None:
            c = self.fakes[ep] = FakeConn(ep, self._scripts[ep])
        self._conn, self._ep = c, ep
        return c

    def close(self):
        self._conn = None
        self._ep = None


def _busy(hint=40):
    return ResponseError(
        f"BUSY (overload shed class=write retry-after-ms={hint}; "
        "node is shedding this class — back off and retry)"
    )


# ---- scripted units ---------------------------------------------------------


def test_busy_backoff_is_jittered_exponential():
    """Three typed BUSY refusals, then success: each wait honors the
    server's retry-after floor, doubles per attempt, and jitters in
    [0.5, 1.0) of the step — never in phase, never past the cap."""
    tok = _tok({"r1": 1})
    cc = FakeCluster(
        {A: [_busy(), _busy(), _busy(), [b"OK", tok]]},
        backoff_cap_ms=10_000.0,
    )
    assert cc.write("GCOUNT", "INC", "k", "1") == b"OK"
    assert cc.stats["busy_backoffs"] == 3
    assert len(cc.sleeps) == 3
    for n, s in enumerate(cc.sleeps):
        step = 0.040 * (2.0 ** n)  # hint 40ms doubling
        assert step * 0.5 <= s < step, (n, s)
    assert cc.token == tok


def test_busy_backoff_respects_cap():
    cc = FakeCluster(
        {A: [_busy(900), _busy(900), [b"OK", _tok({"r1": 1})]]},
        backoff_cap_ms=1000.0,
    )
    cc.write("GCOUNT", "INC", "k", "1")
    assert all(s < 1.0 for s in cc.sleeps)  # capped, pre-jitter, at 1s


def test_stale_read_fails_over_and_records_mttr():
    """The composite path: a write lands on A, A dies mid-read, the
    read fails over to B which first answers STALE (B hasn't caught up
    to the token), and the retry serves. MTTR spans first failure to
    first served reply; the STALE and the failover are both counted."""
    tok_a = _tok({"ra": 3})
    tok_b = _tok({"ra": 3, "rb": 1})
    stale = ResponseError("STALE (token not yet dominated here)")
    cc = FakeCluster(
        {
            A: [[b"OK", tok_a], OSError("connection reset")],
            B: [stale, [tok_b, 7]],
        }
    )
    assert cc.write("GCOUNT", "INC", "k", "3") == b"OK"
    assert cc.read("GCOUNT", "GET", "k") == 7
    assert cc.stats["failovers"] == 1
    assert cc.stats["stale_retries"] == 1
    assert cc.stats["last_mttr_s"] > 0.0
    # the token folded B's reply in and stayed monotone over A's mint
    vec = sessions.decode_token(cc.token)
    assert sessions.dominates(vec, {"ra": 3})
    assert vec == {"ra": 3, "rb": 1}
    # A saw exactly the write and the failed read — the STALE retry
    # never probed the dead-listed endpoint
    assert len(cc.fakes[A].calls) == 2


def test_badtoken_resets_session_and_retries_bare():
    tok = _tok({"ra": 5})
    cc = FakeCluster(
        {
            A: [
                [b"OK", tok],
                ResponseError("BADTOKEN (token crc mismatch)"),
                9,  # the bare retry: no SESSION framing, raw reply
            ]
        }
    )
    cc.write("GCOUNT", "INC", "k", "5")
    assert cc.token == tok
    assert cc.read("GCOUNT", "GET", "k") == 9
    assert cc.stats["badtoken_resets"] == 1
    assert cc.token is None  # the guarantee resets; next write re-mints
    conn = cc.fakes[A]
    assert conn.calls[1][:2] == ("SESSION", "READ")
    assert conn.calls[2] == ("GCOUNT", "GET", "k")  # retried WITHOUT token


def test_cluster_error_after_max_retries_carries_last():
    cc = FakeCluster({A: [_busy(), _busy(), _busy()]}, max_retries=2)
    with pytest.raises(ClusterError) as ei:
        cc.write("GCOUNT", "INC", "k", "1")
    assert isinstance(ei.value.last, ResponseError)
    assert "BUSY" in str(ei.value.last)


def test_token_join_is_monotone_not_replace():
    """A failover survivor can mint a token that does NOT dominate what
    the dead node already acked; the client's running token must JOIN,
    never regress (the read-your-writes half of the session contract
    belongs to the client across failovers)."""
    cc = FakeCluster({A: [[b"OK", _tok({"ra": 3, "rb": 7})]]})
    cc.token = _tok({"ra": 5})  # as if a prior write acked ra:5
    cc.write("GCOUNT", "INC", "k", "1")
    assert sessions.decode_token(cc.token) == {"ra": 5, "rb": 7}


def test_execute_routes_by_admission_class():
    """execute() uses the server's own classifier: read-shaped commands
    skip SESSION WRAP (and skip the token when none is held)."""
    cc = FakeCluster({A: [4, [b"OK", _tok({"r": 1})]]})
    assert cc.execute("GCOUNT", "GET", "k") == 4
    assert cc.execute("GCOUNT", "INC", "k", "1") == b"OK"
    conn = cc.fakes[A]
    assert conn.calls[0] == ("GCOUNT", "GET", "k")
    assert conn.calls[1][:2] == ("SESSION", "WRAP")


def test_inner_error_raises_after_token_merge():
    """A refused inner command must not strand the minted token: the
    reply token joins in BEFORE the inner error propagates."""
    cc = FakeCluster(
        {A: [[ResponseError("GCOUNT INC requires a count"), _tok({"r": 2})]]}
    )
    with pytest.raises(ResponseError):
        cc.write("GCOUNT", "INC", "k")
    assert sessions.decode_token(cc.token) == {"r": 2}


def test_region_preference_orders_routing():
    cc = FakeCluster({A: [], B: []}, region="emea")
    cc.nodes[B] = {"addr": "b", "region": "emea", "bridge": False,
                   "resp_port": 2}
    cc.nodes[A] = {"addr": "a", "region": "apac", "bridge": False,
                   "resp_port": 1}
    assert cc._preferred()[0] == B  # region match outranks list order
    cc._dead[B] = cc._clock() + 60  # a dead near replica routes last
    assert cc._preferred()[0] == A


# ---- spawned-node integration ----------------------------------------------


def _cluster_pair(region="ra"):
    pa, ca = free_port(), free_port()
    pb, cb = free_port(), free_port()
    fast = ("--heartbeat-time", "0.2", "--bridge-demote-ticks", "5",
            "--region", region)
    na = spawn_node(pa, ca, "aye", *fast)
    nb = spawn_node(pb, cb, "bee", *fast,
                    "--seed-addrs", f"127.0.0.1:{ca}:aye")
    return (pa, na), (pb, nb)


def test_token_monotone_across_forced_failover():
    """SIGKILL the node holding the session mid-stream: the client
    fails over, keeps writing, and its token's vector only ever grows —
    the read after failover serves the full pre-kill history."""
    (pa, na), (pb, nb) = _cluster_pair()
    cc = None
    try:
        connect_client(pa, proc=na).close()
        connect_client(pb, proc=nb).close()
        # generous retry budget: under a loaded CI box the survivor can
        # be slow to accept while the victim's port is still in limbo
        cc = ClusterClient(
            [("127.0.0.1", pa), ("127.0.0.1", pb)],
            timeout=15, max_retries=12,
        )
        assert cc.write("GCOUNT", "INC", "fk", "3") == b"OK"
        vec_before = sessions.decode_token(cc.token)
        # the victim is whichever node the client is actually stuck to
        victim = na if cc._ep[1] == pa else nb
        surv_port = pb if victim is na else pa
        # let the delta replicate so the survivor can serve the history
        deadline = time.time() + 30
        sb = Client("127.0.0.1", surv_port, timeout=10)
        while time.time() < deadline:
            if sb.execute_command("GCOUNT", "GET", "fk") == 3:
                break
            time.sleep(0.2)
        else:
            raise AssertionError("delta never replicated to the survivor")
        sb.close()
        victim.kill()  # SIGKILL: no goodbye frame, no clean close
        assert cc.write("GCOUNT", "INC", "fk", "4") == b"OK"
        assert cc.stats["failovers"] >= 1
        assert 0.0 < cc.stats["last_mttr_s"] < 30.0
        vec_after = sessions.decode_token(cc.token)
        assert sessions.dominates(vec_after, vec_before)
        assert cc.read("GCOUNT", "GET", "fk") == 7
    finally:
        if cc is not None:
            cc.close()
        stop_node(na)
        stop_node(nb)


def test_topology_rediscovery_after_node_leaves():
    """discover() reflects departure: after a SIGKILL the survivor's
    SYSTEM TOPOLOGY reports the dead peer live 0 (liveness is the
    bridge-election evidence: silence past --bridge-demote-ticks)."""
    (pa, na), (pb, nb) = _cluster_pair()
    cc = None
    try:
        connect_client(pa, proc=na).close()
        connect_client(pb, proc=nb).close()
        cc = ClusterClient([("127.0.0.1", pa)])
        deadline = time.time() + 30
        while time.time() < deadline:
            cc.discover()
            if len(cc.members) == 2:
                break
            time.sleep(0.2)
        else:
            raise AssertionError(f"never saw both members: {cc.members}")
        assert all(m["live"] for m in cc.members.values())
        nb.kill()
        bee = next(a for a in cc.members if a.endswith(":bee"))
        deadline = time.time() + 30
        while time.time() < deadline:
            cc.discover()
            if bee in cc.members and not cc.members[bee]["live"]:
                break
            time.sleep(0.3)
        else:
            raise AssertionError(f"bee never went dead: {cc.members}")
    finally:
        if cc is not None:
            cc.close()
        stop_node(na)
        stop_node(nb)
