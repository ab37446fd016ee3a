"""Auxiliary-subsystem coverage (SURVEY §5): flush watchdog, ConsumePanic
crash reporting, and runtime diagnostics self-metrics."""

import os
import subprocess
import sys
import time

import pytest

from veneur_tpu.core import diagnostics
from veneur_tpu.util import crash

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class TestConsumePanic:
    """Core report-and-reraise / thread / logging-hook coverage lives in
    tests/test_ops.py TestCrash; only behavior not pinned there is
    added here."""

    def teardown_method(self):
        crash.clear_reporters()

    def test_broken_reporter_does_not_mask_panic(self):
        crash.register_reporter(lambda exc, tb: 1 / 0)
        with pytest.raises(ValueError):
            crash.guarded(lambda: (_ for _ in ()).throw(ValueError("x")))()


class TestDiagnostics:
    def test_collect_emits_runtime_gauges(self):
        calls = []

        class FakeStatsd:
            def gauge(self, name, value, tags=None):
                calls.append((name, value))

            def count(self, name, value, tags=None):
                calls.append((name, value))

        diagnostics.collect(FakeStatsd(), time.time() - 5.0,
                            include_device=False)
        names = {c[0] for c in calls}
        assert {"mem.rss_bytes", "cpu.user_seconds", "threads.count",
                "gc.collections_total", "uptime_ms"} <= names
        by = dict(calls)
        assert by["mem.rss_bytes"] > 0
        assert by["uptime_ms"] >= 5000


class TestFlushWatchdog:
    def test_watchdog_kills_stalled_process(self):
        """Reference server.go:877-919: missed flushes crash the process
        (crash = recovery under a supervisor). Run in a subprocess: a
        flush that hangs forever must lead to os._exit(2)."""
        code = """
import threading, time
from veneur_tpu.config import Config
from veneur_tpu.core.server import Server

cfg = Config()
cfg.interval = 0.3
cfg.flush_watchdog_missed_flushes = 2
cfg.synchronize_with_interval = False
cfg.tpu.counter_capacity = 32
cfg.tpu.gauge_capacity = 32
cfg.tpu.histo_capacity = 32
cfg.tpu.set_capacity = 16
cfg.tpu.batch_cap = 32
cfg.apply_defaults()
server = Server(cfg)
server._flush_locked = lambda: time.sleep(3600)  # simulated stall
server.last_flush_unix = time.time()
server.start()
time.sleep(30)  # watchdog must fire long before this
print("WATCHDOG NEVER FIRED")
"""
        env = {**os.environ, "JAX_PLATFORMS": "cpu"}
        proc = subprocess.run([sys.executable, "-c", code],
                              capture_output=True, text=True, timeout=120,
                              env=env, cwd=REPO)
        assert proc.returncode == 2, (proc.returncode, proc.stderr[-1500:])
        assert "WATCHDOG NEVER FIRED" not in proc.stdout
        # the watchdog dumps tracebacks before exiting (faulthandler)
        assert "watchdog" in proc.stderr.lower()
