"""From the profiler's trace (``*.xplane.pb``) to device numbers, clipped to
the measured window. Read with ``jax.profiler.ProfileData`` and nothing
else; checked against a small recorded trace in ``benchmark/tests``.

Event times in the file count from the start of the trace; the plane
``Task Environment`` carries ``profile_start_time`` in epoch nanoseconds,
so an event starts at ``profile_start_time + start_ns`` on the same clock
as the harness's ``time.time_ns()`` at the window's edges.
"""

from __future__ import annotations

import fnmatch
import glob
import os
import re


_HLO = re.compile(r"^(%[\w.\-]+) = (\(?[a-z0-9]+\[[^\]]*\])\S* ([\w\-]+)\(")


def short(name: str) -> str:
    """An HLO instruction as the trace names it, cut to ``%name opcode
    result-shape``: the full text runs to hundreds of characters."""
    m = _HLO.match(name)
    return f"{m.group(1)} {m.group(3)} {m.group(2)}" if m else name[:80]


def find(trace_dir: str) -> str | None:
    files = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return files[-1] if files else None


def _union(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    out: list[list[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


class Trace:
    """Device and host events of one trace, in epoch nanoseconds."""

    DEVICE_PREFIXES = ("/device:TPU:",)
    OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"

    def __init__(self, path: str, host_as_device: bool = False):
        from jax.profiler import ProfileData

        data = ProfileData.from_file(path)
        start = 0
        for plane in data.planes:
            if plane.name == "Task Environment":
                start = dict(plane.stats).get("profile_start_time", 0)
        self.start_ns = int(start)
        # per device plane: {"ops": [(name, t0, t1)], "modules": [...]}
        self.devices: dict[str, dict[str, list]] = {}
        self.host_spans: list[tuple[str, float, float]] = []
        # what the file holds, for a reader of the run's log: one row per
        # (plane, line) with its event count and a few event names
        self.summary: list[str] = [f"{path}: {os.path.getsize(path)} bytes"]
        for plane in data.planes:
            for line in plane.lines:
                names, n = [], 0
                for e in line.events:
                    n += 1
                    if len(names) < 4 and e.name not in names:
                        names.append(e.name[:60])
                self.summary.append(f"{plane.name} | {line.name} | {n} events | {names}")
        for plane in data.planes:
            is_dev = plane.name.startswith(self.DEVICE_PREFIXES)
            if is_dev or (host_as_device and plane.name == "/host:CPU"):
                dev = self.devices.setdefault(plane.name, {"ops": [], "modules": []})
                for line in plane.lines:
                    if is_dev and line.name not in (self.OPS_LINE, self.MODULES_LINE):
                        continue
                    if not is_dev and not line.name.startswith("tf_XLAPjRtCpuClient"):
                        continue
                    which = "modules" if line.name == self.MODULES_LINE else "ops"
                    for e in line.events:
                        if e.duration_ns > 0:
                            t0 = self.start_ns + int(e.start_ns)  # ints: a float64
                            # of epoch nanoseconds is only good to 256 ns
                            dev[which].append((e.name, t0, t0 + int(e.duration_ns)))
            if plane.name == "/host:CPU":
                for line in plane.lines:
                    for e in line.events:
                        if e.name.startswith("drain_"):
                            t0 = self.start_ns + int(e.start_ns)
                            self.host_spans.append((e.name, t0, t0 + int(e.duration_ns)))
        # No device plane (a node that served its window from the host
        # writes none), or none with an op inside the window, is a READING:
        # busy 0 s, no device op, the whole window idle. The roofline
        # readers then find no program and leave their metrics out.

    def clip(self, events, w0: int, w1: int):
        return [(n, max(a, w0), min(b, w1)) for n, a, b in events if b > w0 and a < w1]

    def busy_s(self, w0: int, w1: int) -> float:
        """Seconds in which an operation ran, averaged over the devices."""
        total = 0.0
        for dev in self.devices.values():
            ops = self.clip(dev["ops"] or dev["modules"], w0, w1)
            total += sum(b - a for a, b in _union([(a, b) for _n, a, b in ops]))
        return total / max(1, len(self.devices)) / 1e9

    def program_s(self, patterns: list[str], w0: int, w1: int) -> tuple[float, int]:
        """(device seconds, runs) of the programs whose module name matches
        one of ``patterns``, summed over devices and clipped to the window."""
        secs, runs = 0.0, 0
        for dev in self.devices.values():
            for name, a, b in self.clip(dev["modules"] or dev["ops"], w0, w1):
                base = name.split("(")[0]
                if any(fnmatch.fnmatchcase(base, p) for p in patterns):
                    secs += (b - a) / 1e9
                    runs += 1
        return secs, runs

    def breakdown(self, w0: int, w1: int, top: int = 10) -> dict:
        by_op: dict[str, float] = {}
        busy: list[tuple[float, float]] = []
        for dev in self.devices.values():
            for name, a, b in self.clip(dev["ops"] or dev["modules"], w0, w1):
                by_op[short(name)] = by_op.get(short(name), 0.0) + (b - a) / 1e9
                busy.append((a, b))
        merged = _union(busy)
        edges = [w0] + [t for ab in merged for t in ab] + [w1]
        gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                if edges[i + 1] > edges[i]]
        by_host: dict[str, float] = {}
        for a, b in gaps:
            covered = 0.0
            for name, s0, s1 in self.host_spans:
                o = min(b, s1) - max(a, s0)
                if o > 0:
                    by_host[name] = by_host.get(name, 0.0) + o / 1e9
                    covered += o
            rest = (b - a) - covered
            if rest > 0:
                by_host["(no host span)"] = by_host.get("(no host span)", 0.0) + rest / 1e9
        order = lambda d: [[k, v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])[:top]]
        return {"device_ops": order(by_op), "idle_gaps": order(by_host)}
