"""The benchmark artifact contract: once a backend is up, `python
bench.py` prints exactly one JSON line with the driver-required keys and
exits 0 — on success and on deadline expiry (partial result). These
tests run it with JAX_PLATFORMS=cpu named explicitly; without that, a
host where JAX finds only the CPU makes bench.py exit non-zero (no
fallback number)."""

import json
import os
import re
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_bench(*args, timeout=180):
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    # conftest pins an 8-virtual-device mesh for the in-process suite;
    # the bench subprocess must see the topology the driver's standalone
    # `python bench.py` run sees
    flags = re.sub(r"--xla_force_host_platform_device_count=\d+", "",
                   env.get("XLA_FLAGS", "")).strip()
    if flags:
        env["XLA_FLAGS"] = flags
    else:
        env.pop("XLA_FLAGS", None)
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "bench.py"), *args],
        capture_output=True, text=True, timeout=timeout, env=env, cwd=REPO)
    return proc


def last_json_line(stdout: str) -> dict:
    lines = [ln for ln in stdout.strip().splitlines() if ln.startswith("{")]
    assert len(lines) == 1, f"expected exactly one JSON line, got: {lines}"
    return json.loads(lines[0])


@pytest.fixture(scope="module")
def single_proc():
    return run_bench("--scenario", "single", "--duration", "1",
                     "--keys", "500", "--deadline", "150")


class TestBenchContract:
    def test_single_scenario_emits_contract_keys(self, single_proc):
        proc = single_proc
        assert proc.returncode == 0, proc.stderr[-2000:]
        obj = last_json_line(proc.stdout)
        for key in ("metric", "value", "unit", "vs_baseline"):
            assert key in obj, key
        assert obj["metric"] == "dogstatsd_samples_per_sec"
        assert obj["value"] > 0
        assert obj["unit"] == "samples/s"

    def test_deadline_emits_partial_json_rc0(self):
        """A too-tight budget must still land a parseable line with
        truncated=true and exit 0 — never a silent driver timeout."""
        proc = run_bench("--scenario", "single", "--duration", "60",
                         "--keys", "2000", "--deadline", "12", timeout=90)
        assert proc.returncode == 0, proc.stderr[-2000:]
        obj = last_json_line(proc.stdout)
        assert obj.get("truncated") is True
        assert "metric" in obj and "vs_baseline" in obj

    def test_progress_lines_on_stderr(self, single_proc):
        """Timestamped stage lines make a driver-side timeout tail
        diagnosable."""
        proc = single_proc
        assert "bench[" in proc.stderr
        assert "backend=" in proc.stderr

    def test_llhist_scenario_smoke(self):
        """The llhist BASELINE config must run and emit its contract
        line (the log-linear family rides the Python parse path, so
        this also smoke-tests `|l` ingest end to end)."""
        proc = run_bench("--scenario", "llhist", "--duration", "1",
                         "--keys", "200", "--deadline", "150")
        assert proc.returncode == 0, proc.stderr[-2000:]
        obj = last_json_line(proc.stdout)
        assert obj["metric"] == "llhist_samples_per_sec"
        assert obj["value"] > 0
        assert obj["unit"] == "samples/s"
