"""Generic readers of per-layer metrics. A metric is a spec file under
``benchmark/layer_metrics/<name>.json`` naming one of these readers and
what it reads; a reader that finds nothing to read returns ``None`` and
the harness leaves the metric out of the line.
"""

from __future__ import annotations

import fnmatch
import os
import re

from . import roofline, trace_reduce

_STATE_RE = re.compile(r"(\w+) ([\dx]+) over (\d+) device")


class Context:
    """What a reader may look at: the node's counters at the window's two
    edges, the window itself, the node's log and the device trace."""

    def __init__(self, cell, before: dict, after: dict, wall0: int, wall1: int, node,
                 trace_dir: str, rehearse: bool, log_dir: str):
        self.cell, self.before, self.after = cell, before, after
        self.wall0, self.wall1 = wall0, wall1
        self.window_s = (wall1 - wall0) / 1e9
        self.node, self.trace_dir, self.rehearse = node, trace_dir, rehearse
        self.log_dir = log_dir
        self._trace = None
        self._reduced = None

    def delta(self, patterns: list[str]) -> float | None:
        """Sum over matching samples of (after - before); None when no
        sample matches."""
        names = [s for s in self.after if any(fnmatch.fnmatchcase(s, p) for p in patterns)]
        if not names:
            return None
        return sum(self.after[s] - self.before.get(s, 0.0) for s in names)

    def plane_shape(self, type_name: str) -> list[int] | None:
        for _t, line in self.node.lines:
            if "device state:" in line:
                for name, shape, _n in _STATE_RE.findall(line):
                    if name == type_name:
                        return [int(x) for x in shape.split("x")]
        return None

    def trace_obj(self):
        if self._trace is None:
            path = trace_reduce.find(self.trace_dir)
            if path is None:
                raise RuntimeError(f"the node wrote no trace under {self.trace_dir}")
            self._trace = trace_reduce.Trace(path, host_as_device=self.rehearse)
            os.makedirs(self.log_dir, exist_ok=True)
            with open(os.path.join(self.log_dir, "trace_summary.txt"), "w") as f:
                f.write("\n".join(self._trace.summary) + "\n")
        return self._trace

    def trace(self) -> dict:
        if self._reduced is None:
            tr = self.trace_obj()
            self._reduced = {"busy_s": tr.busy_s(self.wall0, self.wall1),
                             "window_s": self.window_s,
                             "breakdown": tr.breakdown(self.wall0, self.wall1)}
        return self._reduced


def counter_ratio(ctx: Context, spec: dict) -> float | None:
    """``scale * delta(num) / delta(den)`` over the window; ``den`` may be
    the literal ``"window_s"``."""
    num = ctx.delta(spec["num"])
    den = ctx.window_s if spec["den"] == "window_s" else ctx.delta(spec["den"])
    if num is None or not den:
        return None
    return spec.get("scale", 1.0) * num / den


def trace_idle(ctx: Context, spec: dict) -> float | None:
    t = ctx.trace()
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])


def trace_roofline(ctx: Context, spec: dict) -> float | None:
    """Needed bytes of the window's drains over the chip's peak, against
    the device time of the drain programs in the window."""
    tr = ctx.trace_obj()
    sparse_s, sparse_runs = tr.program_s(spec["programs"], ctx.wall0, ctx.wall1)
    dense_s, dense_runs = tr.program_s(spec["dense_programs"], ctx.wall0, ctx.wall1)
    rows = ctx.delta(spec["rows"])
    shape = ctx.plane_shape(spec["type"])
    if not (sparse_runs + dense_runs) or not rows or shape is None:
        return None
    sparse_bytes, dense_bytes = roofline.BYTES[spec["bytes"]]
    replicas = shape[1] if len(shape) > 1 else 1
    # The row counter does not say which program carried a row. A dense run
    # streams every plane whatever it carries (`dense_bytes`), and carries
    # at most the whole keyspace: only the rows beyond that are sure to have
    # gone through the sparse program, so no row is counted in both.
    sparse_rows = max(0, int(rows) - dense_runs * shape[0]) if sparse_runs else 0
    needed = dense_runs * dense_bytes(shape[0], replicas) + sparse_bytes(sparse_rows, replicas)
    if not needed:
        return None
    return roofline.share(needed, sparse_s + dense_s, ctx.node.device()["kind"])


READERS = {"counter_ratio": counter_ratio, "trace_idle": trace_idle,
           "trace_roofline": trace_roofline}


def read(ctx: Context, spec: dict) -> float | None:
    return READERS[spec["reader"]](ctx, spec)
