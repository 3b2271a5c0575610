"""The reduction from trace to device numbers, on the small recorded trace
``data/tpu_like.xplane.pb`` (written by ``make_trace.py``, whose times are
the hand-worked cases below), and the roofline arithmetic."""

import os

import pytest

from benchmark.harness import readers, roofline, trace_reduce
from benchmark.tests.make_trace import MS, START_NS

PATH = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data", "tpu_like.xplane.pb")
W0, W1 = START_NS, START_NS + 1000 * MS


@pytest.fixture(scope="module")
def trace():
    return trace_reduce.Trace(PATH)


def test_times_are_on_the_epoch_clock_and_only_device_planes_count(trace):
    assert trace.start_ns == START_NS
    assert list(trace.devices) == ["/device:TPU:0"]
    assert [n for n, _a, _b in trace.host_spans] == ["drain_PNCOUNT", "drain_PNCOUNT"]
    assert any("XLA Ops | 5 events" in row for row in trace.summary)


def test_busy_is_the_union_of_op_intervals_clipped_to_the_window(trace):
    assert trace.busy_s(W0, W1) == pytest.approx(0.031)  # 4 (two ops overlap) + 6 + 20 + 1 ms
    assert trace.busy_s(START_NS + 302 * MS, START_NS + 510 * MS) == pytest.approx(0.014)
    assert trace.busy_s(START_NS + 2000 * MS, START_NS + 3000 * MS) == 0.0


def test_program_time_matches_module_names_without_their_run_ids(trace):
    assert trace.program_s(["jit__drain_pn"], W0, W1) == (pytest.approx(0.010), 2)
    assert trace.program_s(["jit__drain_pn_dense"], W0, W1) == (pytest.approx(0.020), 1)
    assert trace.program_s(["jit__drain_pn*"], W0, W1)[1] == 3
    secs, runs = trace.program_s(["jit__drain_pn"], START_NS + 302 * MS, START_NS + 510 * MS)
    assert (secs, runs) == (pytest.approx(0.004), 1)


def test_breakdown_names_top_ops_and_labels_idle_gaps_by_host_span(trace):
    b = trace.breakdown(W0, W1)
    assert [name for name, _s in b["device_ops"]] == ["fusion.9", "fusion.1", "copy.2", "fusion.3"]
    assert dict(b["device_ops"])["fusion.1"] == pytest.approx(0.009)
    gaps = dict(b["idle_gaps"])
    assert gaps["drain_PNCOUNT"] == pytest.approx(0.064)  # 10 + 14 + 20 + 20 ms of idle under drains
    assert gaps["(no host span)"] == pytest.approx(0.969 - 0.064)


@pytest.mark.parametrize("path,w0", [
    (os.path.join(os.path.dirname(PATH), "host_only.xplane.pb"), W0),  # no device plane
    (PATH, START_NS + 2000 * MS),  # a device plane, no op of it inside the window
])
def test_a_window_with_no_device_op_is_a_reading_not_a_crash(path, w0):
    """A node that served its window from the host (a new cell's PARENT):
    busy 0 s, no device op, the whole window idle, `device.idle_share` 100,
    no roofline; the traced run then prints its result line (PR 38 was
    refused over `ValueError: no device plane`)."""
    tr = trace_reduce.Trace(path)
    w1 = w0 + 1000 * MS
    assert tr.busy_s(w0, w1) == 0.0
    assert tr.program_s(["jit__drain_pn*"], w0, w1) == (0.0, 0)
    b = tr.breakdown(w0, w1)
    assert b["device_ops"] == []
    assert sum(s for _name, s in b["idle_gaps"]) == pytest.approx(1.0)
    rows = 'jylis_drain_total{type="PNCOUNT",kind="keys"}'
    ctx = readers.Context(None, {rows: 0.0}, {rows: 500.0}, w0, w1, _Node(), "", False, "")
    ctx._trace = tr
    assert readers.trace_idle(ctx, {}) == 100.0
    assert ctx.trace()["busy_s"] == 0.0 and ctx.trace()["window_s"] == pytest.approx(1.0)
    spec = {"type": "PNCOUNT", "bytes": "pncount", "programs": ["jit__drain_pn"],
            "dense_programs": ["jit__drain_pn_dense"], "rows": [rows]}
    assert readers.trace_roofline(ctx, spec) is None


def test_bytes_a_drain_must_move_and_the_share_of_the_roofline():
    assert roofline.pncount_sparse_bytes(1000, 64) == 1000 * (12 * 64 * 4 + 16)
    assert roofline.pncount_dense_bytes(1 << 20, 64) == (1 << 20) * (12 * 64 * 4 + 8)
    assert roofline.treg_sparse_bytes(4096) == 4096 * 81
    assert roofline.treg_dense_bytes(1 << 20) == (1 << 20) * 73
    share = roofline.share(3_088_000, 0.010, "TPU v5 lite")
    assert share == pytest.approx(100 * (3_088_000 / 819e9) / 0.010)
    with pytest.raises(KeyError):
        roofline.share(1, 1, "TPU v9 imaginary")


class _Node:
    lines = [(0.0, "(I) device state: PNCOUNT 1048576x64 over 1 device(s); TREG 1048576 over 1 device(s)")]

    def device(self):
        return {"kind": "TPU v5 lite"}


def test_roofline_reader_counts_rows_sparse_and_runs_dense(trace):
    rows = 'jylis_drain_total{type="PNCOUNT",kind="keys"}'
    spec = {"type": "PNCOUNT", "bytes": "pncount", "programs": ["jit__drain_pn"],
            "dense_programs": ["jit__drain_pn_dense"], "rows": [rows]}
    dense = roofline.pncount_dense_bytes(1 << 20, 64)
    # the recorded window holds one dense run: of its rows only those beyond
    # a whole keyspace are counted sparse, so none is counted twice
    for drained, sparse_rows in (((1 << 20) + 1000, 1000), (300_000, 0)):
        ctx = readers.Context(None, {rows: 100.0}, {rows: 100.0 + drained}, W0, W1, _Node(),
                              "", False, "")
        ctx._trace = trace
        needed = roofline.pncount_sparse_bytes(sparse_rows, 64) + dense
        assert readers.trace_roofline(ctx, spec) == pytest.approx(
            roofline.share(needed, 0.030, "TPU v5 lite"))
    assert readers.trace_idle(ctx, {}) == pytest.approx(100 * (1 - 0.031 / 1.0))
    nothing = dict(spec, programs=["jit_absent"], dense_programs=["jit_absent2"])
    assert readers.trace_roofline(ctx, nothing) is None


def test_counter_ratio_reads_window_differences_and_wildcards():
    before = {'s{seam="drain.A"}': 1.0, 's{seam="drain.B"}': 2.0, "n": 10.0}
    after = {'s{seam="drain.A"}': 1.5, 's{seam="drain.B"}': 4.0, "n": 20.0}
    ctx = readers.Context(None, before, after, 0, 2_000_000_000, None, "", False, "")
    assert readers.counter_ratio(ctx, {"num": ['s{seam="drain.*"}'], "den": ["n"], "scale": 2.0}) == 0.5
    assert readers.counter_ratio(ctx, {"num": ['s{seam="drain.*"}'], "den": "window_s", "scale": 100.0}) == 125.0
    assert readers.counter_ratio(ctx, {"num": ["absent"], "den": ["n"]}) is None
    assert readers.counter_ratio(ctx, {"num": ["n"], "den": ["absent"]}) is None
