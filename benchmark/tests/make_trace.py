"""Writes ``data/tpu_like.xplane.pb`` and ``data/host_only.xplane.pb``
(the trace of a node that served its window from the host: no device
plane at all): a tiny trace in the profiler's file
format (XSpace, hand-encoded in protobuf wire format: no generated bindings
are installed here) with the planes and lines a TPU run writes. The times
are chosen so that the reduction's answers can be worked by hand; the file
is committed, and ``test_trace_reduce.py`` reads it with
``jax.profiler.ProfileData`` like any recorded trace.

    python benchmark/tests/make_trace.py
"""

import os
import struct

START_NS = 1_790_000_000_000_000_000  # profile_start_time (epoch ns)
MS = 1_000_000  # ns


def varint(n: int) -> bytes:
    out = bytearray()
    while True:
        b = n & 0x7F
        n >>= 7
        out.append(b | (0x80 if n else 0))
        if not n:
            return bytes(out)


def field(num: int, value) -> bytes:
    if isinstance(value, int):
        return varint(num << 3) + varint(value)
    if isinstance(value, float):
        return varint(num << 3 | 1) + struct.pack("<d", value)
    if isinstance(value, str):
        value = value.encode()
    return varint(num << 3 | 2) + varint(len(value)) + value


def plane(name: str, lines: dict, stats: dict | None = None) -> bytes:
    """lines: {line name: [(event name, start ns, duration ns)]}."""
    meta_ids: dict[str, int] = {}
    body = field(2, name)
    for li, (line_name, events) in enumerate(lines.items()):
        line = field(1, li + 1) + field(2, line_name) + field(3, 0)
        for ev_name, start_ns, dur_ns in events:
            mid = meta_ids.setdefault(ev_name, len(meta_ids) + 1)
            line += field(4, field(1, mid) + field(2, start_ns * 1000) + field(3, dur_ns * 1000))
        body += field(3, line)
    for ev_name, mid in meta_ids.items():
        body += field(4, field(1, mid) + field(2, field(1, mid) + field(2, ev_name)))
    for si, (stat_name, value) in enumerate((stats or {}).items()):
        body += field(5, field(1, si + 1) + field(2, field(1, si + 1) + field(2, stat_name)))
        body += field(6, field(1, si + 1) + field(3, value))
    return field(1, body)


def host_and_env() -> bytes:
    ms = lambda t: int(t * MS)
    host = plane("/host:CPU", {
        "python": [("drain_PNCOUNT", ms(290), ms(30)), ("drain_PNCOUNT", ms(480), ms(60)),
                   ("something_else", ms(0), ms(5))],
    })
    env = plane("Task Environment", {}, {"profile_start_time": START_NS,
                                         "profile_stop_time": START_NS + ms(1000)})
    return host + env


def build() -> bytes:
    ms = lambda t: int(t * MS)
    device = plane("/device:TPU:0", {
        "XLA Modules": [("jit__drain_pn(7)", ms(100), ms(4)), ("jit__drain_pn(7)", ms(300), ms(6)),
                        ("jit__drain_pn_dense(9)", ms(500), ms(20)), ("jit_other(3)", ms(900), ms(1))],
        "XLA Ops": [("fusion.1", ms(100), ms(3)), ("copy.2", ms(102), ms(2)),  # overlap: 4 ms busy
                    ("fusion.1", ms(300), ms(6)), ("fusion.9", ms(500), ms(20)),
                    ("fusion.3", ms(900), ms(1))],
        "Steps": [("0", ms(0), ms(1000))],
    })
    return device + host_and_env()


if __name__ == "__main__":
    data = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
    for name, blob in (("tpu_like", build()), ("host_only", host_and_env())):
        path = os.path.join(data, name + ".xplane.pb")
        with open(path, "wb") as f:
            f.write(blob)
        print(path, os.path.getsize(path))
