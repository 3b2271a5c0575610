"""jtrace provenance spans (schema v11): wire robustness, fold
statistics, trace-ring bounds, sampling, and the regioned drill.

The drill at the bottom is the PR's acceptance cell: a 3-node 2-region
mesh where a sampled write on a NON-bridge r1 node must surface on the
r2 node as the full chain origin(bee) -> relay(aye, r1's bridge) ->
apply(sea) with per-hop latencies, queryable via ``SYSTEM TRACE
SPANS`` — the end-to-end path a convergence SLO is judged on.
"""

import asyncio
import threading

import pytest

from test_bridge_failover import _regioned_trio, _write_inc, _read_count
from test_cluster import Node, converge_wait, grab_ports, resp_call
from jylis_tpu.cluster import codec
from jylis_tpu.cluster.cluster import Cluster, check_frame
from jylis_tpu.cluster.framing import FrameReader
from jylis_tpu.cluster.msg import MsgRelayPush, MsgSeqPush
from jylis_tpu.obs import jtrace
from jylis_tpu.obs.jtrace import (
    HOP_APPLY,
    HOP_BUS,
    HOP_ORIGIN,
    HOP_RELAY,
    MAX_HOPS,
    SpanStats,
    append_hop,
    decode_span,
    format_chain,
)
from jylis_tpu.obs.trace import DETAIL_CAP, TraceRing
from jylis_tpu.utils.address import Address
from jylis_tpu.utils.config import Config
from jylis_tpu.utils.wire import WireError


# ---- wire format ------------------------------------------------------------


def _chain3() -> bytes:
    s = append_hop(b"", HOP_ORIGIN, "n1!1", "r1", 1000)
    s = append_hop(s, HOP_RELAY, "n2!1", "r1", 1003)
    return append_hop(s, HOP_APPLY, "n3!1", "r2", 1009)


def test_append_hop_roundtrips_and_is_append_only():
    one = append_hop(b"", HOP_ORIGIN, "n1!1", "r1", 1000)
    two = append_hop(one, HOP_BUS, "n1!1", "r1", 1001)
    assert two.startswith(one)  # append-only: the original is a prefix
    assert decode_span(one) == [(HOP_ORIGIN, "n1!1", "r1", 1000)]
    assert decode_span(two) == [
        (HOP_ORIGIN, "n1!1", "r1", 1000),
        (HOP_BUS, "n1!1", "r1", 1001),
    ]
    assert decode_span(b"") == []  # the unsampled-frame case


def test_format_chain_offsets_from_origin():
    chain = format_chain(decode_span(_chain3()))
    assert chain == (
        "origin@n1!1[r1]+0ms -> relay@n2!1[r1]+3ms -> apply@n3!1[r2]+9ms"
    )


def test_truncation_at_every_byte_never_invents_hops():
    """A truncated span either raises WireError or decodes to a strict
    PREFIX of the full hop list (truncation exactly at a hop boundary
    IS a valid shorter span) — never garbage hops, never a crash."""
    span = _chain3()
    full = decode_span(span)
    for i in range(len(span)):
        try:
            got = decode_span(span[:i])
        except WireError:
            continue
        assert got == full[: len(got)], (i, got)
        assert len(got) < len(full)


def test_ts_past_u64_is_wire_error():
    # hand-build a hop whose ts varint encodes 2^65: rid len 0,
    # region len 0, then the oversized varint
    payload = bytearray(b"\x00\x00")
    jtrace._w_varint(payload, 1 << 65)
    hop = bytearray()
    jtrace._w_varint(hop, HOP_ORIGIN)
    jtrace._w_varint(hop, len(payload))
    hop += payload
    with pytest.raises(WireError):
        decode_span(bytes(hop))


def test_unknown_hop_tags_are_skipped_via_length_prefix():
    s = append_hop(b"", HOP_ORIGIN, "n1!1", "r1", 5)
    # a hop kind from a newer node, with an opaque payload shape
    future = bytearray()
    jtrace._w_varint(future, 99)
    jtrace._w_varint(future, 4)
    future += b"\xff\xfe\xfd\xfc"
    s = bytes(s) + bytes(future)
    s = append_hop(s, HOP_APPLY, "n2!1", "r2", 9)
    assert decode_span(s) == [
        (HOP_ORIGIN, "n1!1", "r1", 5),
        (HOP_APPLY, "n2!1", "r2", 9),
    ]


def test_known_hop_with_trailing_payload_bytes_is_tolerated():
    """A newer node may EXTEND a known hop's payload; the length prefix
    already frames it, so extra bytes after ts must not be fatal."""
    payload = bytearray()
    jtrace._w_varint(payload, 2)
    payload += b"n1"
    jtrace._w_varint(payload, 2)
    payload += b"r1"
    jtrace._w_varint(payload, 7)
    payload += b"\x01\x02"  # the extension
    hop = bytearray()
    jtrace._w_varint(hop, HOP_ORIGIN)
    jtrace._w_varint(hop, len(payload))
    hop += payload
    assert decode_span(bytes(hop)) == [(HOP_ORIGIN, "n1", "r1", 7)]


def test_hop_count_bound():
    s = b""
    for i in range(MAX_HOPS):
        s = append_hop(s, HOP_RELAY, f"n{i}", "r", i)
    decode_span(s)  # exactly at the bound: fine
    with pytest.raises(WireError):
        decode_span(append_hop(s, HOP_APPLY, "x", "r", 99))


# ---- v11 codec carry --------------------------------------------------------


def test_codec_v11_span_roundtrip_fast_and_oracle():
    span = _chain3()
    batch = ((b"k1", {1: 10}),)
    for msg in (
        MsgSeqPush(9, 4, "GCOUNT", batch, span),
        MsgRelayPush(9, "h1:1:n!1", 4, "GCOUNT", batch, span),
        MsgSeqPush(9, 4, "GCOUNT", batch, b""),  # unsampled: empty span
    ):
        body = codec.encode(msg)
        assert codec.decode(body) == msg
        assert codec._encode_oracle(msg) == body
        assert codec._decode_oracle(body) == msg


# ---- SpanStats folding ------------------------------------------------------


def test_spanstats_folds_e2e_per_region_pair_and_slo():
    st = SpanStats(slo_ms=(50, 250))
    span = append_hop(b"", HOP_ORIGIN, "n1!1", "r1", 1000)
    span = append_hop(span, HOP_RELAY, "n2!1", "r1", 1030)
    st.ingest(span, "n3!1", "r2", 1040)  # e2e 40ms: under both
    st.ingest(span, "n3!1", "r2", 1100)  # e2e 100ms: under 250 only
    assert st.sampled == 2 and st.malformed == 0
    assert st.slo_ok == [1, 2]
    assert st.e2e_hists[("r1", "r2")].count == 2
    # per-transition histograms exist for each adjacent pair
    assert st.hop_hists[(HOP_ORIGIN, HOP_RELAY)].count == 2
    assert st.hop_hists[(HOP_RELAY, HOP_APPLY)].count == 2
    fr = {ms: (frac, ok) for ms, frac, ok in st.slo_fracs()}
    assert fr[50] == (0.5, 1) and fr[250] == (1.0, 2)
    lines = st.report_lines()
    assert any(line.startswith("e2e r1->r2 count 2") for line in lines)
    assert any(line.startswith("hop origin->relay") for line in lines)
    assert any(line.startswith("slo 50ms frac 0.5000 ok 1") for line in lines)


def test_spanstats_counts_malformed_and_originless():
    st = SpanStats()
    st.ingest(b"\xff\xff\xff", "n", "r", 10)  # truncated varint
    # decodes fine but the first hop is not an origin stamp
    st.ingest(append_hop(b"", HOP_RELAY, "n1", "r1", 5), "n", "r", 10)
    assert st.sampled == 0 and st.malformed == 2
    assert not st.e2e_hists and not st.worst


def test_spanstats_worst_reports_only_new_records():
    st = SpanStats()
    origin = append_hop(b"", HOP_ORIGIN, "n1", "r1", 0)
    assert st.ingest(origin, "n2", "r2", 50) is not None  # first = record
    assert st.ingest(origin, "n3", "r2", 30) is None  # not a record
    assert st.ingest(origin, "n4", "r2", 50) is None  # tie: no re-report
    chain = st.ingest(origin, "n5", "r2", 80)
    assert chain is not None and "+80ms" in chain
    assert st.worst[0][0] == 80 and len(st.worst) == 4


def test_spanstats_set_slo_sorts_and_resets():
    st = SpanStats()
    st.ingest(append_hop(b"", HOP_ORIGIN, "n", "r", 0), "m", "r", 10)
    st.set_slo_ms((5, 100, 9))
    assert st.slo_ms == (5, 9, 100)
    assert st.slo_ok == [0, 0, 0]


def test_spanstats_concurrent_ingest():
    st = SpanStats()
    span = append_hop(b"", HOP_ORIGIN, "n1", "r1", 0)

    def fold(k: int) -> None:
        for i in range(200):
            st.ingest(span, f"n{k}", "r2", i)

    threads = [threading.Thread(target=fold, args=(k,)) for k in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert st.sampled == 800
    assert st.e2e_hists[("r1", "r2")].count == 800


# ---- trace ring bounds ------------------------------------------------------


def test_trace_ring_wraps_at_cap_oldest_first():
    ring = TraceRing(512)
    for i in range(512 + 100):
        ring.push("t", f"e{i}")
    assert len(ring) == 512
    events = [e[2] for e in ring.dump()]
    assert events[0] == "e100" and events[-1] == "e611"


def test_trace_ring_concurrent_writers_stay_bounded():
    ring = TraceRing(512)
    stop = threading.Event()
    errors: list[Exception] = []

    def writer(k: int) -> None:
        try:
            for i in range(2000):
                ring.push(f"w{k}", f"e{i}", detail="x" * 300)
        except Exception as e:  # pragma: no cover - the assertion
            errors.append(e)

    def reader() -> None:
        try:
            while not stop.is_set():
                ring.dump(64)
                len(ring)
        except Exception as e:  # pragma: no cover - the assertion
            errors.append(e)

    threads = [threading.Thread(target=writer, args=(k,)) for k in range(4)]
    rd = threading.Thread(target=reader)
    rd.start()
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    stop.set()
    rd.join()
    assert not errors
    assert len(ring) == 512
    assert all(len(e[4]) <= DETAIL_CAP for e in ring.dump())


# ---- sampling + relay stamping (bare Cluster, no sockets) -------------------


def _mk_cluster(trace_sample: int) -> Cluster:
    cfg = Config()
    cfg.addr = Address("10.0.0.2", "7001", "bee")
    cfg.region = "r1"
    cfg.trace_sample = trace_sample

    class _Db:
        pass

    return Cluster(cfg, _Db())


def _last_logged_msg(c: Cluster):
    """Decode the newest delta-log frame back to its codec message."""
    _seq, data = c._delta_log[-1]
    fr = FrameReader()
    fr.append(data)
    bodies = list(fr)
    assert len(bodies) == 1
    checked = check_frame(bodies[0])
    assert checked is not None
    _origin_ms, payload = checked
    return codec.decode(payload)


def test_broadcast_mints_one_span_in_n():
    c = _mk_cluster(trace_sample=3)
    spans = []
    for _ in range(6):
        c.broadcast_deltas(("GCOUNT", [(b"k", {1: 1})]))
        spans.append(_last_logged_msg(c).span)
    assert [bool(s) for s in spans] == [False, False, True] * 2
    hops = decode_span(spans[2])
    assert len(hops) == 1
    assert hops[0][0] == HOP_ORIGIN and hops[0][2] == "r1"


def test_trace_sample_zero_never_mints():
    c = _mk_cluster(trace_sample=0)
    for _ in range(5):
        c.broadcast_deltas(("GCOUNT", [(b"k", {1: 1})]))
        assert _last_logged_msg(c).span == b""


def test_broadcast_wires_span_into_seq_push_frame():
    c = _mk_cluster(trace_sample=1)
    c.broadcast_deltas(("GCOUNT", [(b"k", {1: 1})]))
    msg = _last_logged_msg(c)
    assert isinstance(msg, MsgSeqPush)
    hops = decode_span(msg.span)
    assert [h[0] for h in hops] == [HOP_ORIGIN]
    assert hops[0][1] == c._srid and hops[0][2] == "r1"


def test_relay_appends_its_hop_to_the_chain():
    c = _mk_cluster(trace_sample=1)
    span = append_hop(b"", HOP_ORIGIN, "o!1", "r0", 7)
    c.relay_deltas("o!1", 1, ("GCOUNT", [(b"k", {1: 1})]), span)
    msg = _last_logged_msg(c)
    assert isinstance(msg, MsgRelayPush)
    hops = decode_span(msg.span)
    assert [h[0] for h in hops] == [HOP_ORIGIN, HOP_RELAY]
    assert hops[0] == (HOP_ORIGIN, "o!1", "r0", 7)  # original untouched
    assert hops[1][2] == "r1"  # this instance's stamp


def test_relay_leaves_unsampled_frames_unsampled():
    c = _mk_cluster(trace_sample=1)
    c.relay_deltas("o!1", 1, ("GCOUNT", [(b"k", {1: 1})]), b"")
    msg = _last_logged_msg(c)
    assert msg.span == b""  # no hop invented for an unsampled frame


# ---- the regioned drill (acceptance) ----------------------------------------


def _arm_tracing(node: Node) -> None:
    node.cluster._trace_sample = 1
    node.cluster._trace_n = 0


def test_regioned_span_chain_reaches_remote_region():
    """A sampled write on bee (r1, not the bridge) surfaces on sea (r2)
    as the full provenance chain origin(bee) -> relay(aye) -> apply —
    folded into the r1->r2 end-to-end histogram, counted in the SLO
    fractions, and rendered by SYSTEM TRACE SPANS."""

    async def main():
        a, b, c = await _regioned_trio(demote=8)
        try:
            for n in (a, b, c):
                _arm_tracing(n)
            await _write_inc(b, b"drill", 7)

            def sea_folded() -> bool:
                return ("r1", "r2") in c.database.metrics.spans.e2e_hists

            assert await converge_wait(sea_folded, ticks=600), \
                "sampled span never reached the remote region"
            assert await _read_count(c, b"drill") == 7
            st = c.database.metrics.spans
            assert st.sampled >= 1 and st.malformed == 0
            assert st.worst, "no worst exemplar retained"
            chains = " | ".join(chain for _ms, chain in st.worst)
            assert "origin@" in chains and "apply@" in chains
            assert "relay@" in chains
            assert "[r1]" in chains and "[r2]" in chains
            # per-hop transitions recorded, ending at the apply stamp
            assert any(k[1] == HOP_APPLY for k in st.hop_hists)
            # ... and the operator view renders it end to end
            out = await resp_call(
                c.server.port,
                b"*3\r\n$6\r\nSYSTEM\r\n$5\r\nTRACE\r\n$5\r\nSPANS\r\n",
            )
            text = out.decode(errors="replace")
            assert "spans sampled" in text
            assert "e2e r1->r2" in text
            assert "worst" in text and "origin@" in text
            # the bridge applies the frame before relaying onward, so
            # aye's own stats fold the shorter r1->r1 chain too
            assert a.database.metrics.spans.sampled >= 1
        finally:
            for n in (a, b, c):
                await n.stop()

    asyncio.run(main())


def test_system_observe_shows_slo_and_write_heat():
    """SYSTEM OBSERVE on a single node: write heat appears once a
    flushed batch is emitted, and the SLO lines render from config."""

    async def main():
        [p] = grab_ports(1)
        n = Node("obs", p)
        _arm_tracing(n)
        await n.start()
        try:
            await _write_inc(n, b"hk", 3)

            def heat_seen() -> bool:
                return "GCOUNT" in n.database.metrics.write_heat

            assert await converge_wait(heat_seen, ticks=400)
            heat = n.database.metrics.write_heat["GCOUNT"]
            assert sum(heat) >= 1 and len(heat) == 256
            out = await resp_call(
                n.server.port,
                b"*2\r\n$6\r\nSYSTEM\r\n$7\r\nOBSERVE\r\n",
            )
            text = out.decode(errors="replace")
            assert "converge sampled" in text
            assert "converge_slo ms 50" in text
            assert "write_heat GCOUNT total" in text
        finally:
            await n.stop()

    asyncio.run(main())
