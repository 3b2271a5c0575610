"""From the workers' logs to the end-to-end metrics. The benchmark takes
these itself, on the host clock, from the client's side."""

from __future__ import annotations

import numpy as np

from .loadgen import OK, STATUS_WORDS
from .nodes import RunFailure


def percentile(values: np.ndarray, q: float) -> float:
    """Nearest-rank percentile of all the values given."""
    if len(values) == 0:
        raise RunFailure("a metric has no sample in the window: nothing was served")
    v = np.sort(values)
    return float(v[min(len(v) - 1, int(np.ceil(q * len(v))) - 1)])


class Window:
    """Logs of all workers of a run, cut to the measured window [t0, t1]."""

    def __init__(self, logs: list[dict], t0: float, t1: float):
        self.logs, self.t0, self.t1 = logs, t0, t1
        self.seconds = t1 - t0

    def _ops(self, counted_only: bool, cls: str | None = None):
        """(latencies in s with failures at the window's length, n failed)
        over operations SCHEDULED in the window."""
        lats, failed = [], 0
        for lg in self.logs:
            if lg["kind"] not in ("open", "closed") or (counted_only and not lg["counted"]):
                continue
            inside = (lg["sched"] >= self.t0) & (lg["sched"] < self.t1)
            if cls is not None:
                inside &= np.isin(lg["op"], [i for i, c in enumerate(lg["classes"]) if c == cls])
            ok = lg["status"][inside] == OK
            lat = lg["lat"][inside].astype(np.float64)
            lat[~ok] = self.seconds
            lats.append(lat)
            failed += int((~ok).sum())
        return (np.concatenate(lats) if lats else np.zeros(0)), failed

    def ops_per_s(self) -> float:
        """Operations COMPLETED inside the window by the counted streams."""
        done = 0
        for lg in self.logs:
            if lg["kind"] not in ("open", "closed") or not lg["counted"]:
                continue
            end = lg["sched"] + lg["lat"]
            done += int(((lg["status"] == OK) & (end >= self.t0) & (end < self.t1)).sum())
        return done / self.seconds

    def class_p95_ms(self, cls: str) -> tuple[float, int]:
        lat, _ = self._ops(True, cls)
        return percentile(lat, 0.95) * 1e3, len(lat)

    def probes(self):
        """(lags in s with failures at the timeout, n failed) over probes
        whose write was due in the window."""
        lags, failed = [], 0
        for lg in self.logs:
            if lg["kind"] != "probe":
                continue
            inside = (lg["sent"] >= self.t0) & (lg["sent"] < self.t1)
            ok = lg["status"][inside] == OK
            lag = lg["lat"][inside].astype(np.float64)
            lag[~ok] = lg["timeout_s"]
            lags.append(lag)
            failed += int((~ok).sum())
        return (np.concatenate(lags) if lags else np.zeros(0)), failed

    def slowest_probes(self, n: int) -> list[list[float]]:
        """[second of the window its write was acknowledged, writer, lag in
        s] of the ``n`` slowest probes that showed: WHEN the system was slow."""
        rows = []
        for lg in self.logs:
            if lg["kind"] != "probe":
                continue
            m = (lg["sent"] >= self.t0) & (lg["sent"] < self.t1) & (lg["status"] == OK)
            rows += [[round(float(t - self.t0), 2), int(w), round(float(lag), 3)]
                     for t, w, lag in zip(lg["sched"][m], lg["b"][m], lg["lat"][m])]
        return sorted(rows, key=lambda r: -r[2])[:n]

    def attempted_failed(self) -> tuple[int, int]:
        lat, failed = self._ops(False)
        lags, pfailed = self.probes()
        return len(lat) + len(lags), failed + pfailed

    def failures(self) -> dict[str, int]:
        """``{"<stream>.<unanswered|error|busy>": n}`` over what
        `attempted_failed` counts as failed: which stream, and how."""
        out: dict[str, int] = {}
        for lg in self.logs:
            if lg["kind"] not in ("open", "closed", "probe"):
                continue
            when = lg["sent"] if lg["kind"] == "probe" else lg["sched"]
            status = lg["status"][(when >= self.t0) & (when < self.t1)]
            for code, word in enumerate(STATUS_WORDS):
                n = int((status == code).sum())
                if n and code != OK:
                    key = f"{lg['stream']}.{word}"
                    out[key] = out.get(key, 0) + n
        return out

    def lateness_ms(self) -> dict | None:
        """How late the open-loop senders ran: sent minus scheduled."""
        late = [lg["sent"] - lg["sched"] for lg in self.logs if lg["kind"] == "open"]
        if not late:
            return None
        late = np.concatenate(late) * 1e3
        return {"p50": percentile(late, 0.5), "p99": percentile(late, 0.99),
                "max": float(late.max())}


def end_to_end(window: Window, names: list[str], setup_s: float) -> tuple[dict, dict]:
    """The cell's end-to-end metrics by name, and sample counts beside."""
    out, counts = {}, {}
    for name in names:
        if name == "setup_s":
            out[name] = setup_s
        elif name == "ops_per_s":
            out[name] = window.ops_per_s()
        elif name == "visible_lag_p95_ms":
            lags, _ = window.probes()
            out[name], counts[name] = percentile(lags, 0.95) * 1e3, len(lags)
        elif name.endswith("_p95_ms"):
            out[name], counts[name] = window.class_p95_ms(name[: -len("_p95_ms")])
        else:
            raise ValueError(f"no rule for the end-to-end metric {name!r}")
    return out, counts
