"""The plain UJSON set reference: state from the seed, the snapshot's shape,
the replay that needs no order, the documented render, and the control."""

import os

import numpy as np
import pytest

from benchmark.harness import gen, manifest

UJSON = manifest.load_module(os.path.join(manifest.BENCH, "reference", "UJSON.py"))
RECIPE = {"keys": 8, "members": 30, "path": "members", "key_format": "doc%07d",
          "id_base": 10**18}


def ref(seed=7, **sizes):
    recipe = dict(RECIPE, **sizes)
    return UJSON.Reference(recipe, seed, 1, [2, 3], gen.hottest(recipe["keys"], recipe["keys"]))


def ids(rendered: bytes) -> list[int]:
    text = rendered.decode()
    if not text:
        return []
    return [int(x) for x in text.strip("[]").split(",")]


def test_base_ids_lie_below_every_id_a_client_can_make_and_have_nineteen_digits():
    assert UJSON.FIRST_CLIENT_ID == gen.TS_EPOCH_MS << gen.TS_SHIFT == gen.make_ts(0.0, 0, 0)
    r = ref()
    for got in r.expected(range(RECIPE["keys"])):
        members = ids(got)
        assert members == list(range(10**18, 10**18 + 30))
        assert all(len(str(m)) == 19 and m < UJSON.FIRST_CLIENT_ID for m in members)
    with pytest.raises(ValueError):
        ref(id_base=UJSON.FIRST_CLIENT_ID - 5)


def test_the_snapshot_is_the_base_state_in_the_programs_wire_shape_and_the_seed_moves_its_dots():
    from jylis_tpu.cluster import codec
    from jylis_tpu.cluster.msg import MsgPushDeltas

    r = ref(seed=11)
    batch = r.snapshot_batch()
    assert [k for k, _ in batch] == [b"doc%07d" % i for i in range(8)]
    # the program's own codec reads the reference's plain objects back
    msg = codec.decode(codec.encode(MsgPushDeltas("UJSON", tuple(batch))))
    for (key, doc), (_k, plain) in zip(msg.batch, batch):
        assert doc.render(("members",)).encode() == r.expected([int(key[3:])])[0]
        assert doc.ctx.vv == {r.loader_rid: 30} and not doc.ctx.cloud
        assert sorted(seq for _rid, seq in doc.entries) == list(range(1, 31))
        assert set(doc.entries) == set(plain.entries)
    other = ref(seed=12)
    assert other.loader_rid != r.loader_rid
    assert (other.seq_of != r.seq_of).any()
    assert other.expected(range(8)) == r.expected(range(8)), "the sets are the same on every seed"


@pytest.mark.parametrize("order", [(0, 1, 2), (2, 1, 0), (1, 2, 0)])
def test_the_order_of_acknowledged_writes_does_not_matter(order):
    r = ref()
    writes = [("INS", [0, 0, 3], [UJSON.FIRST_CLIENT_ID + 5, UJSON.FIRST_CLIENT_ID + 9,
                                  UJSON.FIRST_CLIENT_ID + 5]),
              ("RM", [0, 3, 3], [10**18 + 4, 10**18 + 4, 10**18 + 29]),
              ("RM", [0], [10**18 + 4])]  # a leave of an id that has left: a no-op
    for i in order:
        verb, keys, a = writes[i]
        r.apply(verb, np.array(keys), np.array(a, np.uint64), np.zeros(len(keys), np.uint64))
    got0, got3, got5 = (ids(x) for x in r.expected([0, 3, 5]))
    base = list(range(10**18, 10**18 + 30))
    assert got0 == sorted(set(base) - {10**18 + 4}) + [UJSON.FIRST_CLIENT_ID + 5,
                                                      UJSON.FIRST_CLIENT_ID + 9]
    assert got3 == sorted(set(base) - {10**18 + 4, 10**18 + 29}) + [UJSON.FIRST_CLIENT_ID + 5]
    assert got5 == base


def test_the_render_is_the_documented_one_bare_single_empty_string_and_sorted_tokens():
    r = ref(members=2)
    assert r.expected([1]) == [b"[1000000000000000000,1000000000000000001]"]
    r.apply("RM", np.array([1]), np.array([10**18], np.uint64), np.zeros(1, np.uint64))
    assert r.expected([1]) == [b"1000000000000000001"]  # one member renders bare
    r.apply("RM", np.array([1]), np.array([10**18 + 1], np.uint64), np.zeros(1, np.uint64))
    assert r.expected([1]) == [b""]  # an empty set is pruned
    assert r.read_command(1) == (b"UJSON", b"GET", b"doc0000001", b"members")


def test_writes_outside_the_id_rule_and_unknown_writes_are_refused():
    r = ref()
    one, zero = np.array([0]), np.zeros(1, np.uint64)
    with pytest.raises(ValueError):
        r.apply("INS", one, np.array([10**18 + 3], np.uint64), zero)  # a base id: not fresh
    # a leave of an id that is no member is a no-op, acknowledged ...
    r.apply("RM", one, np.array([UJSON.FIRST_CLIENT_ID + 1], np.uint64), zero)
    assert ids(r.expected([0])[0]) == list(range(10**18, 10**18 + 30))
    # ... unless a client joins under it: then the answer would need an order
    r.apply("INS", one, np.array([UJSON.FIRST_CLIENT_ID + 1], np.uint64), zero)
    with pytest.raises(ValueError):
        r.expected([0])
    r = ref()
    with pytest.raises(ValueError):
        r.apply("SET", one, zero, zero)


def test_the_lower_precision_control_collapses_neighbouring_ids():
    r = ref()
    exact, lower = r.expected([2]), r.expected_lower_precision([2])
    assert exact != lower
    assert len(ids(lower[0])) < len(ids(exact[0])) == 30


def test_the_reference_imports_nothing_of_the_program():
    src = open(os.path.join(manifest.BENCH, "reference", "UJSON.py")).read()
    assert "jylis_tpu" not in src.replace("Imports nothing of the program", "")
    assert "import numpy as np" in src
