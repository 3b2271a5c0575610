"""chip_smoke.py on the CPU test platform.

The on-chip gate itself needs a TPU (the driver runs it there). Here:
its refusal to produce a result on anything else, the plain reference it
compares the node against (checked with the repo's other oracle,
ops/hostref.py), and — marked slow, so `make test` runs it and the
time-boxed tier-1 does not — the whole path rehearsed at a tiny size,
which is how a builder debugs the smoke before spending chip time. Under
pytest the children inherit the 8-virtual-device platform, so the
rehearsal takes the mesh path a four-chip host takes.
"""

import dataclasses
import json
import os
import subprocess
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402
from jylis_tpu.ops import hostref  # noqa: E402


def _smoke(*args, timeout=900):
    return subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py"), *args],
        cwd=REPO, capture_output=True, text=True, timeout=timeout,
    )


def test_default_invocation_refuses_any_platform_but_tpu():
    # no --rehearse: a TPU is required (the size flag only keeps it quick)
    r = _smoke("--keys", "2000")
    assert r.returncode != 0
    # it got as far as spawning the node, from a parent that holds no
    # jax backend (a parent that touched jax would hold the chip) ...
    assert "no jax backend initialised; spawning the nodes" in r.stdout
    # ... names the platform the node found, and prints no result
    assert "came up on platform 'cpu'" in r.stderr
    assert '"ok"' not in r.stdout


def test_reference_agrees_with_hostref():
    """The smoke's expected answers are numpy arithmetic on what the seed
    wrote; replaying the same writes into ops/hostref.py's lattices must
    give the same values (two independent references agreeing)."""
    plan = dataclasses.replace(
        chip_smoke.Plan.tiny(), keys=500, foreign_keys=40, treg_peer_keys=200,
        live_keys=50, gcount_keys=30,
    )
    own, peer = 111, 222
    d = chip_smoke.Data(plan, seed=5, peer_rid=peer)
    d.late_applied = True
    batches = dict(d.peer_batches())

    pn = {i: hostref.PNCounter() for i in range(plan.keys)}
    for i in range(plan.keys):
        pn[i].increment(own, int(d.pn_inc[i]))
        if d.pn_dec[i]:
            pn[i].decrement(own, int(d.pn_dec[i]))
    index = {chip_smoke.pn_key(i): i for i in range(plan.keys)}
    for key, (dp, dn) in batches["PNCOUNT"]:
        other = hostref.PNCounter()
        other.p.counts, other.n.counts = dict(dp), dict(dn)
        pn[index[key]].converge(other)
    for idx, inc in ((d.live, d.live_inc), (d.late, d.late_inc)):
        for j, i in enumerate(idx):
            pn[int(i)].increment(peer, int(inc[j]))  # on top of its snapshot column
    every = np.arange(plan.keys)
    assert d.expect_pncount(every) == [pn[i].value() for i in every]
    assert any(abs(v) > 1 << 40 for v in d.expect_pncount(d.fk))  # hi planes matter
    # all 63 foreign columns are non-empty for some key, plus the own one
    assert len({r for _k, (dp, _dn) in batches["PNCOUNT"] for r in dp}) == 63

    gc = {}
    for i in range(plan.gcount_keys):
        g = gc[i] = hostref.GCounter()
        g.increment(own, int(d.gc_inc[i]))
    for n, (_key, cols) in enumerate(batches["GCOUNT"]):
        other = hostref.GCounter()
        other.counts = dict(cols)
        gc[n].converge(other)
    assert d.expect_gcount(range(plan.gcount_keys)) == [
        gc[i].value() for i in range(plan.gcount_keys)
    ]

    tr = {}
    for i in range(plan.keys):
        tr[i] = hostref.TReg()
        tr[i].write(d.tr_value(d.tr_tag[i]), int(d.tr_ts[i]))
    tindex = {chip_smoke.tr_key(i): i for i in range(plan.keys)}
    ties = 0
    for key, (value, ts) in batches["TREG"]:
        i = tindex[key]
        ties += ts == int(d.tr_ts[i])
        tr[i].write(value, ts)
    assert d.expect_treg(every) == [list(tr[i].read()) for i in every]
    assert ties > 0  # equal-timestamp conflicts (the prefix-rank tie) occur

    log = d.expect_tlog(0)
    assert log.size() == plan.tlog_trim and log.cutoff > 1 << 40  # wide timestamps
    assert d.expect_tlog(1).cutoff < 1 << 32  # ... and a narrow-layout key


@pytest.mark.slow
def test_rehearsal_passes_every_leg():
    r = _smoke("--rehearse")
    assert r.returncode == 0, r.stdout[-4000:] + r.stderr[-4000:]
    summary, verdict = r.stdout.strip().splitlines()[-2:]
    out = json.loads(summary)
    # the last line is the verdict a driver reads: these keys and no other
    verdict = json.loads(verdict)
    assert verdict == {"ok": True, "device": out["device"]}
    assert list(verdict["device"]) == ["platform", "kind", "count"]
    assert isinstance(verdict["device"]["count"], int)
    assert out["ok"] is True and out["rehearsal"] is True
    assert list(out)[-1] == "claim" and out["claim"] is None
    assert out["device"]["platform"] == "cpu"
    for name in chip_smoke.DRAIN_TYPES:
        assert out["per_type"][name]["drains"] > 0, out["per_type"]
    assert out["serving"]["native_cmds"] > 0
    assert out["compile_cache"]["entries_before_second_boot"] > 0
    assert out["boot_second"]["warmup_s"] > 0
    n_dev = out["device"]["count"]
    for name in ("GCOUNT", "PNCOUNT", "TREG", "TLOG"):
        assert out["device_state"][name]["devices"] == n_dev
