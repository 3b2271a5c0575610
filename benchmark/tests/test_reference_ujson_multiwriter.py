"""The UJSON set reference under writers at three nodes, and the served
path against it: what the comparison that decides `correct` rests on in
`ycsb-ujson-1kx1k-r3.b`, where members join and leave at every replica.

First the reference alone: the same acknowledged INS and RM, split over
the logs as the harness holds them (one per load worker, whichever node it
wrote at), give the same `expected` in every order of the logs. Then the
system: three repos booted from the reference's snapshot with residency by
size, the logs' writes applied at the node that took them, every flush
shipped to the other two through the program's codec, and every node's
answer equal to the reference's. Last, what the cell's id rule avoids and
the lattice must still get right: a leave racing a join of the same id."""

import itertools

import numpy as np
import pytest

from benchmark.harness import check, gen, manifest, resp

UJ = manifest.load_module(manifest.BENCH + "/reference/UJSON.py")
RECIPE = {"keys": 10, "members": 80, "path": "members", "key_format": "doc%07d",
          "id_base": 10**18}
KEYS = list(range(RECIPE["keys"]))
MIN_LEAVES = 64



def feed(ref, logs):
    """`check.feed_reference` for logs of the one type UJSON."""
    for lg in logs:
        lg.setdefault("types", ["UJSON"] * len(lg["verbs"]))
    return check.feed_reference({"UJSON": ref}, logs)["UJSON"]

def reference(seed: int):
    return UJ.Reference(RECIPE, seed, 0, [1, 2], gen.hottest(10, 10))


def logs_of_three_nodes(seed: int, writes: int = 240) -> list[dict]:
    """Joins and leaves on a hot set of 5 documents as three workers would
    log them (worker w holds connections 2w and 2w+1: ids unique across
    nodes; one write in 9 was not acknowledged)."""
    rng = np.random.default_rng([seed, 0x554D])
    logs = []
    for w in range(3):
        conn = rng.integers(2 * w, 2 * w + 2, writes)
        when = np.sort(rng.random(writes)) * 3.0
        op = rng.integers(1, 3, writes).astype(np.uint8)  # 1 INS, 2 RM
        fresh = np.array([gen.make_ts(float(t), i + 1, int(c))
                          for i, (t, c) in enumerate(zip(when, conn))], np.uint64)
        base = rng.integers(10**18, 10**18 + 80, writes, dtype=np.uint64)
        logs.append({"kind": "open" if w else "closed", "op": op,
                     "key": rng.integers(0, 5, writes).astype(np.int64),
                     "a": np.where(op == 1, fresh, base), "b": np.zeros(writes, np.uint64),
                     "acked": rng.random(writes) > 1 / 9, "verbs": ["GET", "INS", "RM"],
                     "classes": ["read", "write", "write"]})
    return logs


@pytest.mark.parametrize("seed", [5, 2**31 + 39])
def test_every_order_of_the_three_nodes_logs_gives_the_same_expected(seed):
    logs = logs_of_three_nodes(seed)
    answers = []
    for order in itertools.permutations(range(3)):
        ref = reference(seed)
        written, doubtful = feed(ref, [logs[i] for i in order])
        answers.append((ref.expected(KEYS), written.tolist(), doubtful.tolist()))
    assert all(a == answers[0] for a in answers[1:])
    # and it is the plain rule: base less acknowledged leaves plus acknowledged joins
    for k in KEYS:
        want = set(range(10**18, 10**18 + 80))
        for lg in logs:
            mine = (lg["key"] == k) & lg["acked"]
            want -= {int(a) for a in lg["a"][mine & (lg["op"] == 2)]}
        for lg in logs:
            mine = (lg["key"] == k) & lg["acked"]
            want |= {int(a) for a in lg["a"][mine & (lg["op"] == 1)]}
        got = answers[0][0][k].decode().strip("[]").split(",")
        assert [int(x) for x in got] == sorted(want)
    assert len(answers[0][0][7]) == 80 * 20 + 1, "a document nobody wrote keeps its base set"


class Node:
    """One replica: the UJSON repo of a Database booted, as main.py boots
    it, from the reference's snapshot with residency by size."""

    def __init__(self, rid: int, ref):
        from jylis_tpu.models.database import Database

        self.db = Database(identity=rid)
        self.db.set_ujson_resident_min(MIN_LEAVES)
        self.repo = self.db._map[b"UJSON"].repo
        self.repo._mesh = None  # one device, as the benchmark's chip
        self.repo.load_state(self._through_codec(ref.snapshot_batch()))
        self.db.warm_drain_shapes()

    @staticmethod
    def _through_codec(batch):
        from jylis_tpu.cluster import codec
        from jylis_tpu.cluster.msg import MsgPushDeltas

        return codec.decode(codec.encode(MsgPushDeltas("UJSON", tuple(batch)))).batch

    def call(self, *words: bytes):
        from jylis_tpu.server.resp import Respond

        parser = resp.Parser()
        self.repo.apply(Respond(parser.feed), list(words))
        return parser.pop()

    def flush_to(self, others) -> None:
        self.repo.prepare_flush()
        batch = self._through_codec(self.repo.flush_deltas())
        for other in others:
            for key, delta in batch:
                other.repo.converge(key, delta)


@pytest.mark.parametrize("seed", [5, 2**31 + 39])
def test_three_nodes_that_take_the_logs_writes_answer_as_the_reference(seed):
    ref = reference(seed)
    nodes = [Node(101 + i, ref) for i in range(3)]
    logs = logs_of_three_nodes(seed)
    for lg in logs:
        lg["acked"][:] = True  # in process every write is applied, so acknowledged
    step = 0
    for i in range(len(logs[0]["op"])):  # interleaved: one write a node a round
        for w, lg in enumerate(logs):
            verb = lg["verbs"][int(lg["op"][i])].encode()
            key = ref.key(int(lg["key"][i]))
            assert nodes[w].call(verb, key, b"members", b"%d" % int(lg["a"][i])) == b"OK"
        step += 1
        if step % 16 == 0:  # a flush interval
            for w, node in enumerate(nodes):
                node.flush_to([n for n in nodes if n is not node])
        if step % 64 == 0:
            nodes[step // 64 % 3].repo.drain()
    for node in nodes:
        node.flush_to([n for n in nodes if n is not node])
    feed(ref, logs)
    expected = ref.expected(KEYS)
    for node in nodes:
        got = [node.call(*ref.read_command(k)[1:]) for k in KEYS]
        assert got == expected
        node.repo.drain()
        assert [node.call(*ref.read_command(k)[1:]) for k in KEYS] == expected
        tallies = node.db.metrics.tallies
        assert tallies["drain.UJSON.resident_rows"] == len(KEYS)
        assert tallies["drain.UJSON.demote_write"] == 0
        assert tallies["drain.UJSON.row_deltas"] > 100
        assert tallies["drain.UJSON.host_deltas"] > 0


def test_a_leave_racing_a_join_of_the_same_id_keeps_the_join_at_every_node():
    """Node A takes `RM g 1000000000000000007` while node B, which has not
    seen the leave, takes `INS g 1000000000000000007`: B's dot is new, A
    never observed it, so after both flushes the id is a member at all
    three nodes (add wins). A leave taken AFTER the join arrived removes
    it everywhere. The reference cannot replay this (its RM names base ids
    that nobody joins again); the lattice decides it."""
    ref = reference(3)
    a, b, c = (Node(201 + i, ref) for i in range(3))
    key, member = ref.key(2), b"1000000000000000007"
    assert a.call(b"RM", key, b"members", member) == b"OK"
    assert b.call(b"INS", key, b"members", member) == b"OK"  # concurrent: not seen at A
    a.flush_to([b, c])
    b.flush_to([a, c])
    for node in (a, b, c):
        assert member in node.call(b"GET", key, b"members")
        node.repo.drain()
        assert member in node.call(b"GET", key, b"members")
    assert c.call(b"RM", key, b"members", member) == b"OK"  # has seen both dots
    c.flush_to([a, b])
    for node in (a, b, c):
        assert member not in node.call(b"GET", key, b"members")
        assert node.repo._is_resident(key)
