"""CI metrics-smoke: boot a real node with --metrics-port, scrape it,
validate the Prometheus text exposition, and assert every histogram,
gauge and counter declared in scripts/jlint/metrics_manifest.json is
present from boot (zero counts included — the observability surface must not depend
on traffic having happened).

Run via `make metrics-smoke` (part of `make ci`). Exit 0 = the scrape
is valid and complete, with non-trivial serving activity (the script
issues a few RESP commands first, so at least one seam has samples).
"""

from __future__ import annotations

import http.client
import json
import os
import re
import socket
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
MANIFEST = os.path.join(ROOT, "scripts", "jlint", "metrics_manifest.json")

# one exposition line: metric name, optional {labels}, a float value
SAMPLE_RE = re.compile(
    r"^[a-zA-Z_:][a-zA-Z0-9_:]*"
    r"(\{[a-zA-Z_][a-zA-Z0-9_]*=\"(?:[^\"\\]|\\.)*\""
    r"(,[a-zA-Z_][a-zA-Z0-9_]*=\"(?:[^\"\\]|\\.)*\")*\})?"
    r" -?[0-9.eE+-]+( [0-9]+)?$"
)

SPAWN = (
    "import jax; jax.config.update('jax_platforms','cpu'); "
    "import sys; from jylis_tpu.main import main; main(sys.argv[1:])"
)


def free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    p = s.getsockname()[1]
    s.close()
    return p


def scrape(port: int, timeout_s: float = 240.0) -> str:
    deadline = time.time() + timeout_s
    last: Exception | None = None
    while time.time() < deadline:
        try:
            conn = http.client.HTTPConnection("127.0.0.1", port, timeout=10)
            conn.request("GET", "/metrics")
            resp = conn.getresponse()
            body = resp.read().decode()
            ctype = resp.getheader("Content-Type", "")
            conn.close()
            if resp.status != 200:
                raise RuntimeError(f"HTTP {resp.status}")
            if "text/plain" not in ctype:
                raise RuntimeError(f"bad content type: {ctype}")
            return body
        except (OSError, RuntimeError) as e:
            last = e
            time.sleep(1.0)
    raise RuntimeError(f"metrics endpoint never came up: {last!r}")


def resp_traffic(port: int, timeout_s: float = 180.0) -> None:
    """A few real commands so the dispatch seams have samples."""
    deadline = time.time() + timeout_s
    while time.time() < deadline:
        try:
            s = socket.create_connection(("127.0.0.1", port), timeout=5)
            break
        except OSError:
            time.sleep(0.5)
    else:
        raise RuntimeError("RESP port never came up")
    s.sendall(
        b"GCOUNT INC smoke 3\r\nGCOUNT GET smoke\r\n"
        b"TLOG INS s x 1\r\nSYSTEM METRICS\r\n"
    )
    s.settimeout(30)
    got = b""
    while b"*" not in got:  # the METRICS array header arrived
        got += s.recv(1 << 16)
    s.close()


def _boot_and_scrape() -> str:
    resp_port = free_port()
    mport = free_port()
    args = [
        sys.executable, "-c", SPAWN,
        "--port", str(resp_port),
        "--addr", "127.0.0.1:0:metrics-smoke",
        "--metrics-port", str(mport),
        "--log-level", "warn",
    ]
    proc = subprocess.Popen(args, cwd=ROOT, stdout=subprocess.DEVNULL)
    try:
        resp_traffic(resp_port)
        return scrape(mport)
    finally:
        proc.terminate()
        try:
            proc.wait(timeout=60)
        except subprocess.TimeoutExpired:
            proc.kill()
            proc.wait(timeout=10)


_HIST_FAMILY = "jylis_seam_latency_log2_seconds"
_HIST_LINE_RE = re.compile(
    rf"^{_HIST_FAMILY}_(bucket|count)\{{(?P<labels>[^}}]*)\}} (?P<v>\d+)$"
)
_LE_RE = re.compile(r'(?:^|,)le="([^"]+)"')


def _check_histograms(body: str, failures: list, hists: list[str]) -> int:
    """Validate the real-histogram exposition grammar: every manifest
    seam exposes a `_bucket` series whose counts are CUMULATIVE in le
    order, ends at le="+Inf", and whose `_count` equals the +Inf bucket
    — the invariants histogram_quantile() silently miscomputes without.
    Returns the series count."""
    series: dict[str, list[tuple[float, int]]] = {}
    counts: dict[str, int] = {}
    for line in body.splitlines():
        m = _HIST_LINE_RE.match(line)
        if not m:
            continue
        labels, v = m.group("labels"), int(m.group("v"))
        if m.group(1) == "count":
            counts[labels] = v
            continue
        le = _LE_RE.search(labels)
        if le is None:
            failures.append(f"  _bucket without le: {line!r}")
            continue
        key = _LE_RE.sub("", labels)
        series.setdefault(key, []).append((float(le.group(1)), v))
    for key, pts in series.items():
        pts.sort()  # by le; float("+Inf") orders it last
        if pts[-1][0] != float("inf"):
            failures.append(f"  no le=\"+Inf\" bucket: {key}")
            continue
        vals = [v for _, v in pts]
        if any(b < a for a, b in zip(vals, vals[1:])):
            failures.append(
                f"  non-cumulative _bucket series: {key}"
            )
        if counts.get(key) != vals[-1]:
            failures.append(
                f"  _count != +Inf bucket for: {key}"
            )
    for name in hists:
        want = f'seam="{name}"'
        if not any(want in key for key in series):
            failures.append(
                f"  manifest seam has no _bucket series: {name}"
            )
    return len(series)


def _check_exposition(body: str, failures: list) -> int:
    n_samples = 0
    for line in body.splitlines():
        if not line or line.startswith("#"):
            continue
        if not SAMPLE_RE.match(line):
            failures.append(f"  bad exposition line: {line!r}")
        else:
            n_samples += 1
    return n_samples


def main() -> int:
    manifest = json.load(open(MANIFEST))["metrics"]
    hists = sorted(n[5:] for n in manifest if n.startswith("hist:"))
    gauges = sorted(n[6:] for n in manifest if n.startswith("gauge:"))
    counters = sorted(n[8:] for n in manifest if n.startswith("counter:"))
    serving = sorted(n[8:] for n in manifest if n.startswith("serving:"))

    body = _boot_and_scrape()

    failures = []
    n_samples = _check_exposition(body, failures)
    n_hist_series = _check_histograms(body, failures, hists)
    for name in hists:
        if f'seam="{name}"' not in body:
            failures.append(f"  manifest histogram absent from scrape: {name}")
    for name in gauges:
        if f'name="{name}"' not in body:
            failures.append(f"  manifest gauge absent from scrape: {name}")
    for name in counters:  # drain.<TYPE>.<kind>: a kind of the drain totals
        _, typ, kind = name.split(".")
        if f'jylis_drain_total{{type="{typ}",kind="{kind}"}}' not in body:
            failures.append(f"  manifest counter absent from scrape: {name}")
    for name in serving:  # a kind of the serving totals
        if f'jylis_serving_total{{kind="{name}"}}' not in body:
            failures.append(f"  manifest serving counter absent from scrape: {name}")
    # the traffic above must have armed the dispatch surface
    m = re.search(
        r'jylis_seam_latency_seconds_count\{seam="server\.(native_burst|'
        r'py_dispatch)"\} (\d+)',
        body,
    )
    counts = re.findall(
        r'jylis_seam_latency_seconds_count\{seam="server\.[a-z_]+"\} (\d+)',
        body,
    )
    if not m or not any(int(c) > 0 for c in counts):
        failures.append("  no dispatch-seam samples after RESP traffic")
    if "jylis_cmds_total" not in body:
        failures.append("  jylis_cmds_total family missing")

    if failures:
        print("metrics-smoke FAILED:")
        print("\n".join(failures))
        return 1
    print(
        f"metrics-smoke: {n_samples} valid samples; {len(hists)} histograms"
        f" + {len(gauges)} gauges + {len(counters) + len(serving)} counters all present; "
        f"{n_hist_series} cumulative _bucket series valid"
    )
    return 0


if __name__ == "__main__":
    sys.exit(main())
