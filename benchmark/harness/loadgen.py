"""Load worker: one process, one selector loop, no threads, no jax.

    python benchmark/harness/loadgen.py <config.json>

The parent (`run.py`) starts a few of these per cell and reads back one
``.npz`` log each. Three kinds, all driven by the stream's data:

``closed``  C connections, each keeps ``depth`` commands in flight and sends
            the next when a reply arrives (callers that wait);
``open``    commands go out on a fixed schedule whether or not replies have
            come; latency counts from the SCHEDULED time, and how late the
            sender ran is logged beside it (independent users);
``probe``   write at one target, then read at another every ``poll_ms``
            (more rarely once the probe is old) until the write shows: the
            lag from acknowledgement to visibility, as a client sees it. A
            probe fails only if it has not shown ``timeout_s`` later.

Every operation is logged (op, key, two integer arguments, scheduled time,
send time, latency, status), warm-up included: the reference needs every
acknowledged write, and the parent cuts the window out by time. Clocks are
``time.monotonic()``, which parent and workers share on one machine.
"""

from __future__ import annotations

import json
import os
import selectors
import socket
import sys
import time
from collections import deque

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))

from benchmark.harness import gen  # noqa: E402
from benchmark.harness.resp import Err, Parser  # noqa: E402

UNANSWERED, OK, ERROR, BUSY = 0, 1, 2, 3
STATUS_WORDS = ("unanswered", "ok", "error", "busy")
# After the window a worker waits this long, and no longer than needed, for
# replies still owed: a host that stalled must not turn answered operations
# into failed ones (the driver's check refused a set over 579 of 2.3M).
DRAIN_S = 60.0
# A probe is polled every ``poll_ms`` while younger than this, then every
# 1/20 of its age: the tail's resolution stays within 5% and a stall cannot
# turn the polls into a load of their own.
POLL_EXACT_S = 2.0
MAX_DRAWS = 1 << 21  # a closed-loop worker's draws (reused from the start if outlasted)


class Log:
    """Per-operation columns, preallocated and grown by doubling."""

    COLS = (("op", np.uint8), ("key", np.int64), ("a", np.uint64), ("b", np.uint64),
            ("conn", np.int32), ("sched", np.float64), ("sent", np.float64),
            ("lat", np.float32), ("status", np.uint8))

    def __init__(self, cap: int = 1 << 16):
        self.n = 0
        self.cap = cap
        for name, dtype in self.COLS:
            setattr(self, name, np.zeros(cap, dtype))

    def add(self, op: int, key: int, a: int, b: int, conn: int, sched: float,
            sent: float) -> int:
        i = self.n
        if i == self.cap:
            self.cap *= 2
            for name, dtype in self.COLS:
                setattr(self, name, np.concatenate([getattr(self, name), np.zeros(i, dtype)]))
        self.op[i], self.key[i], self.a[i], self.b[i] = op, key, a, b
        self.conn[i], self.sched[i], self.sent[i] = conn, sched, sent
        self.n = i + 1
        return i

    def done(self, i: int, now: float, reply) -> None:
        self.lat[i] = now - self.sched[i]
        if isinstance(reply, Err):
            self.status[i] = BUSY if reply.startswith("BUSY") else ERROR
        else:
            self.status[i] = OK

    def save(self, path: str, **extra) -> None:
        cols = {name: getattr(self, name)[: self.n] for name, _ in self.COLS}
        tmp = path + ".tmp.npz"
        np.savez(tmp, **cols, **extra)
        os.replace(tmp, path)


class Link:
    """One non-blocking connection with its FIFO of operations in flight."""

    def __init__(self, sel: selectors.BaseSelector, host: str, port: int, ident: int):
        self.sock = socket.create_connection((host, port), timeout=60)
        self.sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
        self.sock.setblocking(False)
        self.sel = sel
        self.ident = ident
        self.out = bytearray()
        self.parser = Parser()
        self.fifo: deque = deque()
        self.seq = 0
        self.closed = False
        sel.register(self.sock, selectors.EVENT_READ, self)

    def send(self, data: bytes) -> None:
        if self.out:
            self.out += data
            return
        try:
            n = self.sock.send(data)
        except BlockingIOError:
            n = 0
        if n < len(data):
            self.out += data[n:]
            self.sel.modify(self.sock, selectors.EVENT_READ | selectors.EVENT_WRITE, self)

    def on_writable(self) -> None:
        try:
            n = self.sock.send(self.out)
        except BlockingIOError:
            return
        del self.out[:n]
        if not self.out:
            self.sel.modify(self.sock, selectors.EVENT_READ, self)

    def replies(self):
        """Complete replies now readable (empty when the peer closed)."""
        try:
            chunk = self.sock.recv(1 << 18)
        except BlockingIOError:
            return
        if not chunk:
            self.closed = True
            self.sel.unregister(self.sock)
            return
        self.parser.feed(chunk)
        while True:
            reply = self.parser.pop()
            if reply is Parser.MORE:
                return
            yield reply


class Draws:
    """The stream's operations, keys and amounts, drawn once from the seed
    (and reused from the start if a run outlasts them)."""

    def __init__(self, cfg: dict, count: int):
        rng = np.random.default_rng([cfg["seed"], cfg["stream_index"], cfg["worker"]])
        self.templates, probs = gen.op_table(cfg["ops"])
        self.count = count
        self.ops = rng.choice(len(probs), count, p=probs).astype(np.uint8)
        # an op's key lies in ITS type's keyspace and takes its key format
        spaces = [cfg["keyspaces"][t.type_name] for t in self.templates]
        self.keys = gen.draw_keys(rng, cfg["keys"], [s["keys"] for s in spaces], self.ops)
        lo, hi = cfg.get("amount", [1, 1])
        self.amounts = rng.integers(lo, hi + 1, count, dtype=np.uint64)
        self.key_formats = [s["key_format"].encode() for s in spaces]
        self.values = gen.Values(cfg["seed"])
        self.i = 0
        self.t_begin = cfg["t_begin"]

    def next(self, link: Link, now: float) -> tuple[bytes, int, int, int, int]:
        """(command bytes, op, key, a, b) of the next operation on ``link``."""
        i = self.i % self.count
        self.i += 1
        op, key = int(self.ops[i]), int(self.keys[i])
        tpl = self.templates[op]
        a = b = 0
        value = b""
        if "amount" in tpl.fields:
            a = int(self.amounts[i])
        if "ts" in tpl.fields:
            link.seq += 1
            a = gen.make_ts(now - self.t_begin, link.seq, link.ident)
        if "value" in tpl.fields:
            b = (link.ident << 40) | link.seq
            value = self.values.make(b, tpl.value_size)
        return tpl.render(self.key_formats[op] % key, a, a, value), op, key, a, b


def sleep_until(t: float) -> None:
    while True:
        d = t - time.monotonic()
        if d <= 0:
            return
        time.sleep(min(d, 0.05))


def drain(sel, links, log: Log, deadline: float) -> None:
    """After the last send: collect replies still owed, until the deadline."""
    while any(lk.fifo and not lk.closed for lk in links) and time.monotonic() < deadline:
        for key, events in sel.select(0.05):
            lk = key.data
            if events & selectors.EVENT_WRITE:
                lk.on_writable()
            if events & selectors.EVENT_READ:
                for reply in lk.replies():
                    log.done(lk.fifo.popleft(), time.monotonic(), reply)


def run_closed(cfg: dict, log: Log) -> dict:
    sel = selectors.DefaultSelector()
    targets = cfg["targets"]
    links = [
        Link(sel, *targets[i % len(targets)], cfg["conn_base"] + i)
        for i in range(cfg["connections"])
    ]
    draws = Draws(cfg, MAX_DRAWS)
    t1 = cfg["t1"]
    sleep_until(cfg["t_begin"])

    def issue(lk: Link, now: float) -> None:
        data, op, key, a, b = draws.next(lk, now)
        lk.fifo.append(log.add(op, key, a, b, lk.ident, now, now))
        lk.send(data)

    now = time.monotonic()
    for lk in links:
        for _ in range(cfg["depth"]):
            issue(lk, now)
    cpu0 = time.process_time()
    while True:
        now = time.monotonic()
        if now >= t1:
            break
        for key, events in sel.select(min(0.05, t1 - now)):
            lk = key.data
            if events & selectors.EVENT_WRITE:
                lk.on_writable()
            if events & selectors.EVENT_READ:
                for reply in lk.replies():
                    now = time.monotonic()
                    log.done(lk.fifo.popleft(), now, reply)
                    if now < t1:
                        issue(lk, now)
        if all(lk.closed for lk in links):
            break
    busy = (time.process_time() - cpu0) / max(1e-9, time.monotonic() - cfg["t_begin"])
    drain(sel, links, log, t1 + DRAIN_S)
    return {"generator_cpu_share": busy}


def run_open(cfg: dict, log: Log) -> dict:
    """One connection per target; operation k is due at ``t_begin + k /
    rate`` and goes to target ``k mod targets``."""
    sel = selectors.DefaultSelector()
    links = [Link(sel, host, port, cfg["conn_base"] + i)
             for i, (host, port) in enumerate(cfg["targets"])]
    rate = float(cfg["rate_per_s"])
    t_begin, t1 = cfg["t_begin"], cfg["t1"]
    total = int((t1 - t_begin) * rate)
    draws = Draws(cfg, max(total, 1))
    sleep_until(t_begin)
    k = 0
    cpu0 = time.process_time()
    while k < total:
        now = time.monotonic()
        due = min(total, int((now - t_begin) * rate) + 1)
        batches: dict[int, list[bytes]] = {}
        while k < due:
            lk = links[k % len(links)]
            sched = t_begin + k / rate
            data, op, key, a, b = draws.next(lk, sched)
            lk.fifo.append(log.add(op, key, a, b, lk.ident, sched, now))
            batches.setdefault(k % len(links), []).append(data)
            k += 1
        for j, parts in batches.items():
            links[j].send(b"".join(parts))
        wait = max(0.0, t_begin + k / rate - time.monotonic()) if k < total else 0.0
        for key, events in sel.select(min(wait, 0.002)):
            lk = key.data
            if events & selectors.EVENT_WRITE:
                lk.on_writable()
            if events & selectors.EVENT_READ:
                for reply in lk.replies():
                    log.done(lk.fifo.popleft(), time.monotonic(), reply)
        if all(lk.closed for lk in links):
            break
    busy = (time.process_time() - cpu0) / max(1e-9, time.monotonic() - t_begin)
    drain(sel, links, log, t1 + DRAIN_S)
    return {"generator_cpu_share": busy}


def probe_draws(cfg: dict, count: int):
    """(key indices, amounts, write template, read template, key format)
    of the first ``count`` probes: the probes' type is their write
    template's, and its keyspace is the one they are drawn over."""
    p = cfg["probe"]
    rng = np.random.default_rng([cfg["seed"], cfg["stream_index"], cfg["worker"]])
    write_tpl, read_tpl = gen.Template(p["write"]), gen.Template(p["read"])
    space = cfg["keyspaces"][write_tpl.type_name]
    keys = gen.KeyDist(p["keys"], space["keys"]).draw(rng, count)
    lo, hi = p["amount"]
    amounts = rng.integers(lo, hi + 1, count, dtype=np.uint64)
    return keys, amounts, write_tpl, read_tpl, space["key_format"].encode()


def run_probe(cfg: dict, log: Log) -> dict:
    """Probe j is due at ``t_begin + j / rate``: GET at the reader target
    (the value before), write at writer target ``j mod writers``, then GET
    every ``poll_ms`` until ``visible``. Logged per probe: op 0, the key,
    a = the amount, sched = when the write was ACKNOWLEDGED, lat = from
    there to the first read that shows it."""
    p = cfg["probe"]
    sel = selectors.DefaultSelector()
    reader = Link(sel, *cfg["read_targets"][0], cfg["conn_base"])
    writers = [Link(sel, host, port, cfg["conn_base"] + 1 + i)
               for i, (host, port) in enumerate(cfg["write_targets"])]
    rate = float(p["rate_per_s"])
    t_begin, t1 = cfg["t_begin"], cfg["t1"]
    total = int((t1 - t_begin) * rate)
    keys, amounts, write_tpl, read_tpl, key_format = probe_draws(cfg, max(total, 1))
    threshold = int(p["visible_delta_at_least"])
    poll = p["poll_ms"] / 1000.0
    timeout = float(p["timeout_s"])
    sleep_until(t_begin)

    # per probe: [row, key bytes, before, t_ack, next poll]; states by where it waits
    polling: dict[int, list] = {}
    next_poll = t_begin
    j = 0
    while True:
        now = time.monotonic()
        if now >= t1 + timeout or (j >= total and not polling
                                    and not reader.fifo and not any(w.fifo for w in writers)):
            break
        while j < total and now < t1 and t_begin + j / rate <= now:
            kb = key_format % int(keys[j])
            row = log.add(0, int(keys[j]), int(amounts[j]), j % len(writers),
                          reader.ident, 0.0, now)
            reader.fifo.append(("before", [row, kb, 0, 0.0, 0.0]))
            reader.send(read_tpl.render(kb))
            j += 1
        if now >= next_poll:
            next_poll = now + poll
            for probe in list(polling.values()):
                age = now - probe[3]
                if age > timeout:
                    del polling[probe[0]]
                    continue
                if now < probe[4]:
                    continue
                probe[4] = now + age / 20 if age > POLL_EXACT_S else 0.0
                reader.fifo.append(("poll", probe))
                reader.send(read_tpl.render(probe[1]))
        for key, events in sel.select(0.002):
            lk = key.data
            if events & selectors.EVENT_WRITE:
                lk.on_writable()
            if not events & selectors.EVENT_READ:
                continue
            for reply in lk.replies():
                now = time.monotonic()
                what, probe = lk.fifo.popleft()
                if isinstance(reply, Err):
                    log.status[probe[0]] = ERROR
                    polling.pop(probe[0], None)
                elif what == "before":
                    probe[2] = int(reply)
                    w = writers[int(log.b[probe[0]])]
                    w.fifo.append(("ack", probe))
                    w.send(write_tpl.render(probe[1], int(log.a[probe[0]])))
                elif what == "ack":
                    probe[3] = now
                    log.sched[probe[0]] = now
                    polling[probe[0]] = probe
                elif probe[0] in polling:
                    delta = (int(reply) - probe[2] + (1 << 63)) % (1 << 64) - (1 << 63)
                    if delta >= threshold:
                        log.lat[probe[0]] = now - probe[3]
                        log.status[probe[0]] = OK
                        del polling[probe[0]]
    return {}


def main(path: str) -> int:
    with open(path) as f:
        cfg = json.load(f)
    log = Log()
    extra = {"closed": run_closed, "open": run_open, "probe": run_probe}[cfg["kind"]](cfg, log)
    log.save(cfg["out"], **{k: np.float64(v) for k, v in extra.items()})
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
