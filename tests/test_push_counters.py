"""The six `push_*` counters of the CLUSTER section count the steady-state
delta traffic exactly: sequenced pushes first sent (per link written) and
decoded, as batches, keys and wire bytes. Two real node stacks on loopback
exchange a known set of writes; the sender's `sent` must move by exactly
the frames its retransmit window logged, the receiver's `recv` by the
same, and nothing else may move. The same numbers must appear on the
Prometheus scrape (`jylis_cluster{key=...}`) and in `SYSTEM METRICS`.
"""

import asyncio

import jylis_tpu  # noqa: F401
from jylis_tpu.client import pack_command
from jylis_tpu.obs import prom
from jylis_tpu.utils.address import Address
from test_cluster import Node, converge_wait, grab_ports, meshed, resp_call

PUSH_KEYS = ("push_batches_sent", "push_keys_sent", "push_bytes_sent",
             "push_batches_recv", "push_keys_recv", "push_bytes_recv")


def _push(node) -> dict[str, int]:
    totals = node.cluster.metrics_totals()
    return {k: totals[k] for k in PUSH_KEYS}


def _moved(node, before: dict[str, int]) -> dict[str, int]:
    return {k: v - before[k] for k, v in _push(node).items()}


def test_push_counters_move_by_exactly_the_exchange():
    async def main():
        p_foo, p_bar = grab_ports(2)
        foo = Node("foo", p_foo)
        bar = Node("bar", p_bar, seeds=[Address("127.0.0.1", str(p_foo), "foo")])
        await foo.start()
        await bar.start()
        try:
            assert await converge_wait(lambda: meshed(foo, bar), ticks=200)
            await asyncio.sleep(0.3)  # the join traffic (sync frames, no push) settles
            before_foo, before_bar = _push(foo), _push(bar)
            log_mark = len(foo.cluster._delta_log)

            # five client writes of 1 KB at foo: flushed in however many
            # batches the proactive flush and the heartbeat cut them into
            for i in range(5):
                got = await resp_call(
                    foo.server.port, pack_command("TREG", "SET", b"k%d" % i, b"%d" % i * 1000, 7 + i))
                assert got == b"+OK\r\n"
            assert await converge_wait(
                lambda: _moved(bar, before_bar)["push_keys_recv"] >= 5, ticks=200)
            # and one batch of three keys handed to the sink directly
            foo.cluster.broadcast_deltas(
                ("TREG", [(b"d%d" % i, (b"v" * 100, 99 + i)) for i in range(3)]))
            assert await converge_wait(
                lambda: _moved(bar, before_bar)["push_keys_recv"] >= 8, ticks=200)

            frames = [data for _seq, data in list(foo.cluster._delta_log)[log_mark:]]
            assert 2 <= len(frames) <= 6
            wire = sum(len(f) for f in frames)
            assert wire > 5 * 1000 + 3 * 100  # the values themselves, plus framing
            sent = {"push_batches_sent": len(frames), "push_keys_sent": 8,
                    "push_bytes_sent": wire}
            recv = {"push_batches_recv": len(frames), "push_keys_recv": 8,
                    "push_bytes_recv": wire}
            nothing = dict.fromkeys(PUSH_KEYS, 0)
            # one link each way: foo wrote every frame once, bar decoded each once,
            # and neither counted anything in the other direction
            assert _moved(foo, before_foo) == {**nothing, **sent}
            assert _moved(bar, before_bar) == {**nothing, **recv}
            assert foo.cluster._stats["deltas_reshipped"] == 0
            # what bar decoded is what it holds
            got = await resp_call(bar.server.port, pack_command("TREG", "GET", "d2"))
            assert got == b"*2\r\n$100\r\n" + b"v" * 100 + b"\r\n:101\r\n"

            # the scrape and SYSTEM METRICS carry the same numbers
            scrape = prom.render(foo.database)
            for key, n in _push(foo).items():
                assert f'jylis_cluster{{key="{key}"}} {n}\n' in scrape + "\n", key
            reader, writer = await asyncio.open_connection("127.0.0.1", bar.server.port)
            writer.write(pack_command("SYSTEM", "METRICS"))
            await writer.drain()
            text = b""
            while b"push_bytes_recv" not in text:
                chunk = await asyncio.wait_for(reader.read(1 << 16), timeout=5.0)
                assert chunk, "SYSTEM METRICS ended without the push counters"
                text += chunk
            writer.close()
            for key, n in _push(bar).items():
                assert b"CLUSTER %s %d" % (key.encode(), n) in text, key
        finally:
            await foo.stop()
            await bar.stop()

    asyncio.run(main())


def test_a_batch_held_for_want_of_a_peer_is_counted_when_it_ships():
    """No established peer: the batch is held, nothing is counted as sent;
    when a peer arrives the held frame ships and is counted once, with its
    keys."""

    async def main():
        p_foo, p_bar = grab_ports(2)
        foo = Node("foo", p_foo)
        await foo.start()
        bar = None
        try:
            foo.cluster.broadcast_deltas(
                ("TREG", [(b"h%d" % i, (b"held", 5 + i)) for i in range(4)]))
            assert len(foo.cluster._held) == 1
            assert _push(foo) == dict.fromkeys(PUSH_KEYS, 0)
            frame = foo.cluster._held[0][1]
            bar = Node("bar", p_bar, seeds=[Address("127.0.0.1", str(p_foo), "foo")])
            await bar.start()
            assert await converge_wait(lambda: meshed(foo, bar), ticks=200)
            assert await converge_wait(lambda: _push(bar)["push_keys_recv"] >= 4, ticks=200)
            assert foo.cluster._held == []
            assert _push(foo) == {**dict.fromkeys(PUSH_KEYS, 0), "push_batches_sent": 1,
                                  "push_keys_sent": 4, "push_bytes_sent": len(frame)}
            assert _push(bar) == {**dict.fromkeys(PUSH_KEYS, 0), "push_batches_recv": 1,
                                  "push_keys_recv": 4, "push_bytes_recv": len(frame)}
        finally:
            await foo.stop()
            if bar is not None:
                await bar.stop()

    asyncio.run(main())
