"""A connection that speaks several types: the seven commands of the
Retwis mix (benchmark/traffic/retwis-mix-r3.json) on ONE connection, at
depth 1 and pipelined at depth 8 with the types interleaved, through the
native engine (rounds that hold what they name, server.py
`_apply_native`) and through the Python path (``engine="python"``, the
oracle): the same reply bytes in command order and the same final state
of all three repos. And the engine's own half of the boundary: a run of
commands under a stated set of held types stops, nothing consumed of
it, before the first command of another type, and changes no table but
those of the types it holds.
"""

import asyncio
import random

import pytest

import jylis_tpu  # noqa: F401
from jylis_tpu.native.engine import ALL_TYPES, make_engine

from test_async_serving import make_server

USERS = 12
TYPES = ("GCOUNT", "PNCOUNT", "TREG", "TLOG", "UJSON", "MAP")  # the engine's order
# the mix's shares (TAPIR's Table 2 mapped onto three types)
MIX = (
    (0.5446, lambda r, u, n: b"TLOG GET list%d 10" % u),
    (0.1881, lambda r, u, n: b"TREG GET user%d" % r.randrange(4 * USERS)),
    (0.0891, lambda r, u, n: b"TREG SET user%d post-%d %d"
     % (r.randrange(4 * USERS), n, 1000 + n)),
    (0.0594, lambda r, u, n: b"UJSON GET set%d members" % u),
    (0.0594, lambda r, u, n: b"TLOG INS list%d post-%d %d" % (u, n, 1000 + n)),
    (0.0297, lambda r, u, n: b"UJSON INS set%d members %d" % (u, 10**18 + n)),
    (0.0297, lambda r, u, n: b"UJSON RM set%d members %d"
     % (u, 10**18 + r.randrange(max(n, 1)))),
)


def stream(seed: int, count: int) -> list[bytes]:
    """`count` commands of the mix from the seed; writes are three times
    the mix's share so that a short stream changes every type's state."""
    r = random.Random(seed)
    weights = [w * (1 if i in (0, 1, 3) else 3) for i, (w, _f) in enumerate(MIX)]
    out = []
    for n in range(count):
        (_w, make), = r.choices(MIX, weights)
        out.append(make(r, r.randrange(USERS), n))
    return out


def reply_length(data: bytes, at: int = 0) -> int | None:
    """Bytes of the one RESP reply at `data[at:]`, None while it is not
    all there."""
    eol = data.find(b"\r\n", at)
    if eol < 0:
        return None
    kind, head = data[at:at + 1], data[at + 1:eol]
    end = eol + 2
    if kind in (b"+", b"-", b":"):
        return end - at
    if kind == b"$":
        n = int(head)
        if n < 0:
            return end - at
        return end + n + 2 - at if len(data) >= end + n + 2 else None
    assert kind == b"*", data[at:at + 20]
    for _ in range(int(head)):
        n = reply_length(data, end)
        if n is None:
            return None
        end += n
    return end - at


async def drive(engine: str, cmds: list[bytes], depth: int):
    """The replies, one per command in order, and the canonical state of
    every key of the three types afterwards."""
    server, db = make_server(engine=engine)
    if engine == "auto" and db.native_engine is None:
        pytest.skip("no native engine on this host")
    await server.start()
    replies = []
    try:
        reader, writer = await asyncio.open_connection("127.0.0.1", server.port)
        data = b""
        for i in range(0, len(cmds), depth):
            chunk = cmds[i:i + depth]
            writer.write(b"".join(c + b"\r\n" for c in chunk))
            for _ in chunk:
                while (n := reply_length(data)) is None:
                    got = await asyncio.wait_for(reader.read(1 << 16), 10)
                    assert got, "the server closed the connection"
                    data += got
                replies.append(data[:n])
                data = data[n:]
        assert not data
        writer.close()
        state = {}
        for name in ("TREG", "TLOG", "UJSON"):
            mgr = db.manager(name)
            async with mgr.hold_sync():
                repo = mgr.repo
                prep = getattr(repo, "sync_prepare", None)
                if prep is not None:
                    prep()
                keys = sorted(k for k, _v in repo.dump_state())
                state[name] = {k: repo.sync_canon(k) for k in keys}
        return replies, state, db.serving_totals()
    finally:
        await server.dispose()


@pytest.mark.parametrize("depth", [1, 8])
@pytest.mark.parametrize("seed", [42, 2**31 + 7])
def test_the_mix_on_one_connection_is_the_python_paths_byte_for_byte(seed, depth):
    cmds = stream(seed, 400)

    async def main():
        want, want_state, _ = await drive("python", cmds, depth)
        got, got_state, serving = await drive("auto", cmds, depth)
        assert len(got) == len(want) == len(cmds)
        for i, (g, w) in enumerate(zip(got, want)):
            assert g == w, (i, cmds[i], g[:80], w[:80])
        assert got_state == want_state
        assert all(want_state[name] for name in ("TREG", "TLOG", "UJSON"))
        # served by the engine: nothing routed, the connection not demoted
        assert serving["busy_routed_cmds"] == 0 and serving["demotions"] == 0
        assert serving["native_cmds"] + serving["deferred_cmds"] == len(cmds)
        assert serving["native_cmds"] > 0.8 * len(cmds)
        if depth == 1:
            assert serving["burst_locks"] <= serving["native_bursts"]
        else:
            # a chunk of eight names two or three types: rounds of more
            # than one lock, and fewer rounds than commands
            assert serving["native_bursts"] < len(cmds) / 2
            assert serving["burst_locks"] > serving["native_bursts"]

    asyncio.run(asyncio.wait_for(main(), 120))


# ---- the engine's half: a run under a stated set of held types ---------------


def observed(eng) -> dict:
    """What each of the six types holds, read through the engine's own
    lookups (no apply): rows, pending writes, deltas, served counts."""
    return {
        "GCOUNT": (eng.rows(0), eng.pend_count(0), eng.dirty_count(0)),
        "PNCOUNT": (eng.rows(1), eng.pend_count(1), eng.dirty_count(1)),
        "TREG": (eng.treg_rows(), eng.treg_pend_count(), eng.treg_delta_count()),
        "TLOG": (eng.tlog_rows(), eng.tlog_pend_total(), eng.tlog_deltas_size()),
        "UJSON": (eng.uq_count(), eng.uj_memo_len(b"k0")),
        "MAP": (eng.map_rows(), eng.map_pend_count(), eng.map_dirty_count()),
    }


WRITES = {
    "GCOUNT": b"GCOUNT INC k%d 1\r\n",
    "PNCOUNT": b"PNCOUNT DEC k%d 2\r\n",
    "TREG": b"TREG SET k%d v 7\r\n",
    "TLOG": b"TLOG INS k%d v 7\r\n",
    "UJSON": b"UJSON INS k%d members 5\r\n",
    "MAP": b"MAP TREG SET k%d field3 v 7\r\n",
}


def write(name: str, n: int = 0) -> bytes:
    """One write of type `name` on a key of its own for every `n`."""
    return WRITES[name] % n


@pytest.mark.parametrize("held", TYPES)
def test_a_run_stops_before_the_first_command_of_a_type_that_is_not_held(held):
    """Under ONE held type the engine applies that type's commands and
    stops, nothing consumed of it, at the first command of any other of
    the six; `changed`, `served` and every other type's table stay as
    they were. A command of no engine type is handed back whatever is
    held."""
    eng = make_engine()
    if eng is None:
        pytest.skip("no native engine on this host")
    bit = TYPES.index(held)
    for k, other in enumerate(TYPES):
        if other == held:
            continue
        mine = write(held, 2 * k)
        buf = bytearray(mine + write(held, 2 * k + 1) + write(other, k) + mine)
        before, served = observed(eng), eng.served_counts()
        ahead = eng.types_ahead(buf)
        assert ahead & 63 == 1 << bit | 1 << TYPES.index(other) and ahead >> 8 == bit
        rc, consumed, n, unhandled, changed = eng.scan_apply(buf, 1 << bit)
        assert rc == 5 and consumed == len(buf) - len(write(other, k) + mine)
        assert unhandled is None
        assert eng.reply_bytes(n) == b"+OK\r\n" * 2
        assert [i for i, c in enumerate(changed) if c] == [bit] and changed[bit] == 2
        after = observed(eng)
        assert {t for t in TYPES if after[t] != before[t]} == {held}
        now = eng.served_counts()
        assert {t for t in now if now[t] != served.get(t, 0)} == {held}
        # the command it stopped at is the head of what is left
        del buf[:consumed]
        assert eng.types_ahead(buf) >> 8 == TYPES.index(other)
        assert eng.scan_apply(buf, 0)[:3] == (5, 0, 0)
    buf = bytearray(write(held) + b"SYSTEM VERSION\r\n" + write(held))
    assert eng.types_ahead(buf) == 1 << bit | bit << 8  # the run ends at SYSTEM
    rc, consumed, n, unhandled, _ = eng.scan_apply(buf, 1 << bit)
    assert rc == 1 and unhandled == [b"SYSTEM", b"VERSION"]
    assert consumed == len(buf) - len(write(held))


def test_types_ahead_reads_what_scan_apply_would_see():
    eng = make_engine()
    if eng is None:
        pytest.skip("no native engine on this host")
    none = 7 << 8
    assert eng.types_ahead(bytearray()) == none
    assert eng.types_ahead(bytearray(b"TLOG GE")) == none  # unfinished
    assert eng.types_ahead(bytearray(b"*x\r\n")) == none  # malformed
    assert eng.types_ahead(bytearray(b"\r\n\r\n")) == none  # blank lines
    # a first word that is no engine type ends the run before it
    assert eng.types_ahead(bytearray(b"TENSOR GET k\r\nTREG GET k\r\n")) == 6 << 8
    # MAP is the engine's sixth type, whatever inner type the command names
    buf = bytearray(b"MAP GCOUNT GET k f\r\nTREG GET k\r\n")
    assert eng.types_ahead(buf) == (1 << 5 | 1 << 2) | 5 << 8
    # blank inline lines are skipped, RESP arrays read like inline commands
    buf = bytearray(b"\r\n*3\r\n$4\r\nTREG\r\n$3\r\nGET\r\n$1\r\nk\r\nUJSON GET d\r\nTLOG GE")
    assert eng.types_ahead(buf) == (1 << 2 | 1 << 4) | 2 << 8
    rc, consumed, _n, _u, _c = eng.scan_apply(buf, ALL_TYPES)
    assert rc == 1 and buf[consumed:] == b"TLOG GE"  # UJSON GET d: a memo miss
    # it looks 64 commands ahead and no further: the TLOG command behind
    # them is met by `held` in scan_apply, a round later
    buf = bytearray(b"TREG GET k\r\n" * 64 + write("TLOG"))
    assert eng.types_ahead(buf) == 1 << 2 | 2 << 8
    assert eng.types_ahead(buf[12:]) == (1 << 2 | 1 << 3) | 2 << 8
    rc, consumed, _n, _u, _c = eng.scan_apply(buf, 1 << 2)
    assert rc == 5 and consumed == 64 * 12
    # a caller that holds everything never meets code 5
    buf = bytearray(b"".join(write(name) for name in TYPES))
    assert eng.scan_apply(buf)[:2] == (0, len(buf))
