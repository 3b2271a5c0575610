"""Each cell end to end at a tiny size on the CPU (``--rehearse``), the
refusals a measuring run must make, and `correct` coming out false when
the timed path is broken underneath."""

import argparse
import json
import os
import shutil
import subprocess
import sys

import pytest

from benchmark.harness import manifest

ROOT = manifest.ROOT
CELLS = [w["name"] for w in manifest.load_json(os.path.join(ROOT, "BENCHMARK.json"))["workloads"]]
RESULT_KEYS = {"correct", "attempted", "failed", "metrics", "device"}


def run_py(*argv, cwd=ROOT, env=None, timeout=600):
    return subprocess.run([sys.executable, os.path.join(cwd, "benchmark", "run.py"), *argv],
                          cwd=cwd, env=env, capture_output=True, text=True, timeout=timeout)


@pytest.mark.parametrize("workload", CELLS)
@pytest.mark.parametrize("trace", [0, 1])
def test_rehearsal_of_each_cell(workload, trace):
    p = run_py("--workload", workload, "--seed", str(2**31 + 12345 + trace), "--seconds", "3",
               "--trace", str(trace), "--rehearse")
    assert p.returncode == 0, p.stderr[-3000:]
    result = json.loads(p.stdout.strip().splitlines()[-1])
    assert RESULT_KEYS <= set(result)
    assert result["rehearsal"] is True, "a CPU result must say it is no chip result"
    assert result["correct"] is True and result["failed"] == 0 and result["attempted"] > 100
    assert result["compiles_in_window"] == 0
    assert result["device"]["platform"] == "cpu" and result["device"]["count"] >= 1
    cell = manifest.Cell(workload)
    if trace:
        assert result["device"]["window_s"] > 2.9
        if workload.endswith(".fanin"):  # the only tiny window sure to hold a drain
            assert result["device"]["busy_s"] > 0
            assert result["breakdown"]["device_ops"]
        assert set(result["metrics"]) <= {m["name"] for m in cell.per_layer}
        assert "server.dispatch_us_per_cmd" in result["metrics"]
        assert len(result["breakdown"]["device_ops"]) <= 10
    else:
        assert set(result["metrics"]) == {m["name"] for m in cell.end_to_end}
        assert all(m["value"] > 0 for m in result["metrics"].values())


def test_a_traced_run_with_no_device_plane_prints_its_line(monkeypatch, capsys):
    """What a new cell's PARENT meets on the chip: a node that serves its
    window from the host writes a trace with no device plane. Here the CPU
    rehearsal's trace is read as a chip run's would be (its host plane not
    taken for a device): busy 0 s, `device.idle_share` 100, no roofline,
    the result line printed, exit code 0 (PR 38 was refused over the raise)."""
    from benchmark import run as bench

    real = bench.readers.Context
    monkeypatch.setattr(bench.readers, "Context",
                        lambda *a: real(*a[:7], False, *a[8:]))  # rehearse=False: no host-as-device
    rc = bench.main(["--workload", "ycsb-treg-1m.a", "--seed", str(2**31 + 4141), "--seconds", "3",
                     "--trace", "1", "--rehearse"])
    assert rc == 0
    result = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert result["correct"] is True and result["failed"] == 0
    assert result["device"]["busy_s"] == 0.0 and result["device"]["window_s"] > 2.9
    assert result["metrics"]["device.idle_share"]["value"] == 100.0
    assert "kernel.treg_drain_roofline" not in result["metrics"]
    assert result["breakdown"]["device_ops"] == []
    assert sum(s for _n, s in result["breakdown"]["idle_gaps"]) == pytest.approx(
        result["device"]["window_s"], rel=1e-6)
    assert "server.dispatch_us_per_cmd" in result["metrics"]


def test_a_measuring_run_without_a_tpu_names_the_platform_and_prints_no_result(tmp_path):
    """Without ``--rehearse`` the CPU platform is refused. A copy of the
    benchmark with the state cut down keeps the test short; the program is
    found through PYTHONPATH."""
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "benchmark"), tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("out", "__pycache__", ".pytest_cache"))
    path = tmp_path / "benchmark" / "configs" / "ycsb-treg-1m.json"
    cfg = json.loads(path.read_text())
    cfg["state"]["keys"] = 2000
    path.write_text(json.dumps(cfg))
    env = dict(os.environ, PYTHONPATH=ROOT, JAX_PLATFORMS="cpu")
    p = run_py("--workload", "ycsb-treg-1m.a", "--seed", "5", "--seconds", "2", "--trace", "0",
               cwd=str(tmp_path), env=env)
    assert p.returncode != 0
    assert "platform 'cpu'" in p.stderr and "no accelerator, no result" in p.stderr
    assert not p.stdout.strip() or not p.stdout.strip().splitlines()[-1].startswith("{")


def test_a_directory_with_only_the_benchmark_gives_no_result(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "benchmark"), tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("out", "__pycache__", ".pytest_cache"))
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    p = run_py("--workload", CELLS[0], "--seed", "5", "--seconds", "2", "--trace", "0",
               "--rehearse", cwd=str(tmp_path), env=env)
    assert p.returncode != 0 and "{" not in p.stdout


class TamperingProxy:
    """Stands between the load and one node and alters one write in 50
    on its way in (an INC/DEC amount grows by one; a SET value's last byte
    flips a bit; hot keys are soon overwritten, so one write would not
    do): the node acknowledges what it got, the reference follows what was
    sent, so answers are wrong where they are produced. ``only_type``
    leaves the writes of every other data type alone."""

    def __init__(self, port: int, only_type: bytes | None = None):
        import socket
        import threading

        self.port_to = port
        self.only_type = only_type
        self.tampered = self.writes = 0
        self.lock = threading.Lock()
        self.listener = socket.socket()
        self.listener.bind(("127.0.0.1", 0))
        self.listener.listen(128)
        self.port = self.listener.getsockname()[1]
        self.threading, self.socket = threading, socket
        threading.Thread(target=self._accept, daemon=True).start()

    def _accept(self):
        while True:
            try:
                client, _ = self.listener.accept()
            except OSError:
                return
            upstream = self.socket.create_connection(("127.0.0.1", self.port_to))
            self.threading.Thread(target=self._up, args=(client, upstream), daemon=True).start()
            self.threading.Thread(target=self._down, args=(upstream, client), daemon=True).start()

    def _up(self, client, upstream):
        from benchmark.harness import resp

        parser = resp.Parser()
        try:
            while chunk := client.recv(1 << 16):
                parser.feed(chunk)
                while (cmd := parser.pop()) is not resp.Parser.MORE:
                    with self.lock:
                        write = cmd[1] in (b"INC", b"DEC", b"SET") and (
                            self.only_type in (None, cmd[0]))
                        self.writes += write
                        if write and self.writes % 50 == 1:
                            self.tampered += 1
                            if cmd[1] == b"SET":
                                cmd[3] = cmd[3][:-1] + bytes([cmd[3][-1] ^ 1])
                            else:
                                cmd[3] = b"%d" % (int(cmd[3]) + 1)
                    upstream.sendall(resp.pack(*cmd))
        except OSError:
            pass
        finally:
            upstream.close()

    def _down(self, upstream, client):
        try:
            while chunk := upstream.recv(1 << 16):
                client.sendall(chunk)
        except OSError:
            pass
        finally:
            client.close()


@pytest.mark.parametrize("workload,victim", [
    ("pncount-1m-r64.fanin", "bench-peer1"),  # a write altered on its way to a peer
    ("ycsb-treg-1m.a", "bench-node"),
])
def test_correct_is_false_when_a_write_is_altered_under_the_timed_path(workload, victim):
    """Skips the harness's look for a chip (a rehearsal) and drives the rest
    of a run with the timed path broken underneath: `correct` must be false."""
    from benchmark import run as bench

    args = argparse.Namespace(workload=workload, seed=77, seconds=3.0, trace=0, rehearse=True)
    run = bench.Run(args)
    run.config["check"]["settle_seconds"] = 3
    try:
        run.boot()
        target = next(n for n in run.everyone if n.name == victim)
        proxy = TamperingProxy(target.port)
        run.load_ports[victim] = proxy.port
        run.drive(run.traffic, args.seconds)
        assert proxy.tampered >= 5
        assert run.verify() is False
        proxy.listener.close()
    finally:
        run.close(False)


def test_a_node_that_stalls_inside_the_window_fails_no_operation():
    """A host that stalls (here: the node stopped for 12 s, longer than any
    heartbeat) makes operations slow, not failed: every one is answered and
    every probe shows once the node runs again, so `failed` stays 0 and
    `correct` true. The driver's check refused a benchmark whose probes gave
    up after 10 s (PERF.md, PR 23)."""
    import signal
    import threading

    from benchmark import run as bench

    args = argparse.Namespace(workload="pncount-1m-r64.fanin", seed=78, seconds=16.0, trace=0,
                              rehearse=True)
    run = bench.Run(args)
    try:
        run.boot()
        lead = bench.LEAD_S + run.traffic["warm_seconds"] + 2.0
        stop = threading.Timer(lead, lambda: run.node.proc.send_signal(signal.SIGSTOP))
        cont = threading.Timer(lead + 12.0, lambda: run.node.proc.send_signal(signal.SIGCONT))
        stop.start()
        cont.start()
        win = run.drive(run.traffic, args.seconds)["window"]
        attempted, failed = win.attempted_failed()
        lags, _ = win.probes()
        assert attempted > 1000 and failed == 0, win.failures()
        assert lags.max() > 10.0, "the stall must show in the lag, not in `failed`"
        assert run.verify() is True
    finally:
        run.close(False)
