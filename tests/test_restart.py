"""Graceful restart: SIGUSR2 spawns a replacement that overlap-binds via
SO_REUSEPORT; the old process drains and exits only after the
replacement answers /healthcheck/ready (reference einhorn handoff,
server.go:1404, README.md:170-178)."""

from __future__ import annotations

import os
import re
import signal
import socket
import subprocess
import sys
import time
import urllib.request

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def ready_pid(port: int):
    """Returns the answering pid, or None when not ready."""
    try:
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/healthcheck/ready",
                timeout=2) as r:
            if r.status == 200:
                return int(r.headers.get("X-Veneur-Pid", "0"))
    except Exception:
        return None
    return None


class TestInstallContract:
    """restart.install's explicit (shutdown, http_address) contract —
    the seam both CLIs (server and proxy) depend on."""

    def test_ready_handoff_calls_shutdown(self, monkeypatch):
        from veneur_tpu.core import restart

        calls = []

        class FakeChild:
            pid = 4242

            def poll(self):
                return None

        monkeypatch.setattr(restart.subprocess, "Popen",
                            lambda cmd, env=None: FakeChild())
        monkeypatch.setattr(restart, "_wait_ready",
                            lambda addr, child, timeout=0, ready_file="": (
                                calls.append(("ready", addr)) or True))
        restart._restart(lambda: calls.append(("shutdown",)),
                         "127.0.0.1:9999", ["prog"])
        assert ("ready", "127.0.0.1:9999") in calls
        assert ("shutdown",) in calls

    def test_unready_replacement_keeps_the_old_process(self, monkeypatch):
        from veneur_tpu.core import restart

        calls = []

        class FakeChild:
            pid = 4242
            returncode = 1

            def poll(self):
                return 1  # replacement died

        monkeypatch.setattr(restart.subprocess, "Popen",
                            lambda cmd, env=None: FakeChild())
        restart._restart(lambda: calls.append("shutdown"),
                        "127.0.0.1:9999", ["prog"])
        assert calls == []  # old process keeps serving

    def test_no_http_uses_ready_file_handshake(self, tmp_path):
        """Without a readiness endpoint the handoff waits for the
        replacement to write its pid once its listeners are bound — a
        merely-alive child (wedged in startup) must NOT win, and a dead
        child loses immediately."""
        from veneur_tpu.core import restart

        class DeadChild:
            pid = 1111

            def poll(self):
                return 1

        class LiveChild:
            pid = 2222

            def poll(self):
                return None

        rf = str(tmp_path / "ready")
        assert restart._wait_ready("", DeadChild(), timeout=0.3,
                                   ready_file=rf) is False
        # alive but never binds: refused
        assert restart._wait_ready("", LiveChild(), timeout=0.5,
                                   ready_file=rf) is False
        # bound (pid written): handoff proceeds
        with open(rf, "w") as f:
            f.write("2222")
        assert restart._wait_ready("", LiveChild(), timeout=2.0,
                                   ready_file=rf) is True
        # a stale file from some OTHER pid does not count
        with open(rf, "w") as f:
            f.write("9999")
        assert restart._wait_ready("", LiveChild(), timeout=0.5,
                                   ready_file=rf) is False

    def test_restart_ready_file_wedged_child_loses(self, monkeypatch,
                                                   tmp_path):
        """Full _restart coverage of the SIGUSR2 ready-file handoff: a
        child that stays alive but never reports its listeners bound
        (wedged in startup) must NOT win — shutdown is never called, the
        old process keeps serving, and the handshake file is cleaned
        up."""
        from veneur_tpu.core import restart

        spawned = {}

        class WedgedChild:
            pid = 7777

            def poll(self):
                return None  # alive forever, never writes the file

        def fake_popen(cmd, env=None):
            spawned["cmd"], spawned["env"] = cmd, env
            return WedgedChild()

        monkeypatch.setattr(restart.subprocess, "Popen", fake_popen)
        # _restart passes no timeout; bound the real _wait_ready so the
        # wedged child times out in test time, not 60 s
        real_wait = restart._wait_ready
        monkeypatch.setattr(
            restart, "_wait_ready",
            lambda addr, child, ready_file="": real_wait(
                addr, child, timeout=0.6, ready_file=ready_file))
        calls = []
        restart._restart(lambda: calls.append("shutdown"), "", ["prog"])
        assert calls == []  # the old process keeps serving
        # the handshake went through the environment, single-use file
        env = spawned["env"]
        ready_file = env[restart.READY_FILE_ENV]
        assert ready_file.startswith("/") and not os.path.exists(ready_file)

    def test_restart_ready_file_bound_child_wins(self, monkeypatch):
        """The complementary path: a child that writes its pid (its
        Server.start() completed, listeners bound) wins the handoff —
        shutdown runs and the handshake file is removed."""
        from veneur_tpu.core import restart

        spawned = {}

        class BoundChild:
            pid = 8888

            def poll(self):
                # "bind the listeners": write our pid the first time the
                # parent polls us, like Server.start()'s mark_ready()
                rf = spawned["env"][restart.READY_FILE_ENV]
                with open(rf, "w") as f:
                    f.write(str(self.pid))
                return None

        def fake_popen(cmd, env=None):
            spawned["env"] = env
            return BoundChild()

        monkeypatch.setattr(restart.subprocess, "Popen", fake_popen)
        real_wait = restart._wait_ready
        monkeypatch.setattr(
            restart, "_wait_ready",
            lambda addr, child, ready_file="": real_wait(
                addr, child, timeout=5.0, ready_file=ready_file))
        calls = []
        restart._restart(lambda: calls.append("shutdown"), "", ["prog"])
        assert calls == ["shutdown"]
        assert not os.path.exists(spawned["env"][restart.READY_FILE_ENV])

    def test_mark_ready_is_single_use(self, tmp_path, monkeypatch):
        """mark_ready pops the env var: descendants must never inherit
        the handshake path and re-create it later (TOCTOU guard)."""
        from veneur_tpu.core import restart

        rf = tmp_path / "ready"
        monkeypatch.setenv(restart.READY_FILE_ENV, str(rf))
        restart.mark_ready()
        assert rf.read_text() == str(os.getpid())
        assert restart.READY_FILE_ENV not in os.environ
        rf.unlink()
        restart.mark_ready()  # second call: env popped, no-op
        assert not rf.exists()

    def test_server_start_writes_ready_file(self, tmp_path, monkeypatch):
        from veneur_tpu.config import Config
        from veneur_tpu.core.server import Server
        from veneur_tpu.sinks.channel import ChannelMetricSink

        rf = str(tmp_path / "ready")
        monkeypatch.setenv("VENEUR_TPU_READY_FILE", rf)
        cfg = Config()
        cfg.interval = 3600
        cfg.statsd_listen_addresses = ["udp://127.0.0.1:0"]
        cfg.apply_defaults()
        server = Server(cfg, extra_metric_sinks=[ChannelMetricSink()])
        server.start()
        try:
            with open(rf) as f:
                assert f.read().strip() == str(os.getpid())
        finally:
            server.shutdown()


@pytest.mark.skipif(not hasattr(socket, "SO_REUSEPORT"),
                    reason="needs SO_REUSEPORT")
def test_sigusr2_hands_off_without_dropping_the_listener(tmp_path):
    udp_port, http_port = free_port(), free_port()
    cfg = tmp_path / "veneur.yaml"
    cfg.write_text(
        "statsd_listen_addresses:\n"
        f"  - udp://127.0.0.1:{udp_port}\n"
        f"http_address: \"127.0.0.1:{http_port}\"\n"
        "interval: 1.0\n"
        "flush_on_shutdown: true\n"
        "stats_address: \"\"\n"
        "metric_sinks:\n"
        "  - kind: blackhole\n"
        "    name: blackhole\n")
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    env["XLA_FLAGS"] = re.sub(
        r"--xla_force_host_platform_device_count=\d+", "",
        env.get("XLA_FLAGS", "")).strip()
    if not env["XLA_FLAGS"]:
        del env["XLA_FLAGS"]
    old = subprocess.Popen(
        [sys.executable, "-m", "veneur_tpu.cmd.veneur", "-f", str(cfg)],
        cwd=REPO, env=env,
        stdout=subprocess.DEVNULL, stderr=subprocess.PIPE, text=True)
    new_pid = None
    try:
        deadline = time.time() + 90
        while time.time() < deadline and ready_pid(http_port) != old.pid:
            assert old.poll() is None, old.stderr.read()[-3000:]
            time.sleep(0.5)
        assert ready_pid(http_port) == old.pid, "server never became ready"

        old.send_signal(signal.SIGUSR2)
        # the replacement must answer ready from a different pid
        deadline = time.time() + 120
        while time.time() < deadline:
            pid = ready_pid(http_port)
            if pid and pid != old.pid:
                new_pid = pid
                break
            time.sleep(0.5)
        assert new_pid, "replacement never became ready"
        # old process drains and exits on its own
        assert old.wait(timeout=60) == 0
        # the port is still served throughout — no listening gap
        deadline = time.time() + 10
        pid = None
        while time.time() < deadline:
            pid = ready_pid(http_port)
            if pid:
                break
            time.sleep(0.2)
        assert pid == new_pid
        # and the UDP listener answers to the new process too: send a
        # packet, then confirm the replacement is still healthy
        with socket.socket(socket.AF_INET, socket.SOCK_DGRAM) as s:
            s.sendto(b"restart.probe:1|c", ("127.0.0.1", udp_port))
        assert ready_pid(http_port) == new_pid
    finally:
        for pid in {new_pid, old.pid if old.poll() is None else None}:
            if pid:
                try:
                    os.kill(pid, signal.SIGTERM)
                except OSError:
                    pass
        try:
            old.wait(timeout=10)
        except Exception:
            old.kill()
