"""Whole runs of a tiny cell on the CPU backend (`sut.REQUIRED_PLATFORM`
steered to "cpu" by tests/drive.py), a process per run, ~15 s each.

The cell, its deployment, its traffic mix and one more per-layer metric
are added as new files in a copy of the benchmark's data directories and
as entries in a copy of BENCHMARK.json: no file that exists is edited,
and run.py finds each by name.
"""

import json
import os
import shutil
import subprocess
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
KEYS = {"counter": 120, "gauge": 60, "timer": 90, "set": 20, "llhist": 10}


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    root = str(tmp_path_factory.mktemp("bench_root"))
    for sub in ("configs", "traffic", "layer_metrics"):
        shutil.copytree(os.path.join(BENCH, sub),
                        os.path.join(root, "benchmark", sub))
    before = {os.path.join(d, f): open(os.path.join(d, f)).read()
              for d, _, files in os.walk(root) for f in files}

    def add(sub, name, obj):
        path = os.path.join(root, "benchmark", sub, name + ".json")
        assert not os.path.exists(path)
        with open(path, "w") as f:
            json.dump(obj, f)

    add("configs", "tiny", {
        "name": "tiny", "interval_s": 3.0, "percentiles": [0.5, 0.9, 0.99],
        "keys": KEYS, "overrides": {
            "synchronize_with_interval": True, "num_readers": 2,
            # a small batch_cap: the hot timer keys then overflow their
            # staging slots over several applies and force `compact`, as
            # 100k keys do at the shipped 16,384
            "tpu": {"counter_capacity": 256, "gauge_capacity": 128,
                    "histo_capacity": 128, "set_capacity": 32,
                    "llhist_capacity": 16, "batch_cap": 256}}})
    add("traffic", "tiny-each-key", {
        "kind": "per_interval", "lines_per_datagram": 10, "lead_s": 0.3,
        "send_window": 0.5, "warmup_send_s": 0.8, "sender_processes": 1,
        "per_interval": {"keys": KEYS, "samples": {
            "timer": [[2, 400], [8, 33], [None, 3]], "set_members": 16,
            "llhist": 6}},
        "check": {"timer_first": 20, "timers": 40}})
    add("traffic", "tiny-replay", {
        "kind": "replay", "lines_per_datagram": 13, "lead_s": 0.3,
        "send_window": 0.5, "warmup_send_s": 0.8, "sender_processes": 2,
        "datagrams_per_s": 3000,
        "corpus": {"keys": {"timer": 50},
                   "samples": {"timer": [[None, 104]]}},
        "once": {"first_key": {"timer": 50}, "keys": {"timer": 8},
                 "samples": {"timer": [[None, 3]]}}})
    add("layer_metrics", "ingest.parse_errors", {
        "reader": {"kind": "prometheus",
                   "row": "veneur_ingest_parse_errors_total"}})

    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    manifest["configs"].append({
        "name": "tiny", "source": "test", "reduced": [], "why": "test",
        "file": "benchmark/configs/tiny.json"})
    cells = {"global100k-interval": "tiny-each-key",
             "timers1k-replay": "tiny-replay"}
    for like, name in cells.items():
        manifest["workloads"].append({
            "name": name, "config": "tiny", "traffic": name, "chips": 1,
            "why": "test"})
        for metric in manifest["end_to_end"] + manifest["per_layer"]:
            if like in metric.get("workloads", ()):
                metric["workloads"].append(name)
    manifest["per_layer"].append({
        "name": "ingest.parse_errors", "unit": "count", "better": "lower",
        "source": "program_counter", "layer": "socket read + parse",
        "moves": "lines_per_s", "workloads": list(cells.values())})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(manifest, f)
    for path, text in before.items():
        assert open(path).read() == text, f"{path} was edited"
    return root


def drive(root, workload, fault="none", seed=2_147_484_321, trace=0,
          extra=()):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    done = subprocess.run(
        [sys.executable, os.path.join(BENCH, "tests", "drive.py"),
         "--root", root, "--workload", workload, "--seed", str(seed),
         "--seconds", "6", "--trace", str(trace), "--fault", fault, *extra],
        capture_output=True, text=True, timeout=600, env=env, cwd=ROOT)
    assert done.returncode == 0, done.stdout[-3000:] + done.stderr[-3000:]
    result = json.loads(done.stdout.strip().splitlines()[-1])
    return result, done.stdout


def numbers(result):
    return {k: v["value"] for k, v in result["compared"].items()}


def test_added_cell_is_found_by_name_and_proves_correct(root):
    result, out = drive(root, "tiny-each-key")
    assert result["correct"] is True, out[-3000:]
    assert result["failed"] == 0 and result["attempted"] == 2 * 1864
    assert set(result["metrics"]) == {"flush_ms", "lines_per_s",
                                      "cpu_us_per_line", "setup_s"}
    assert result["metrics"]["lines_per_s"]["value"] == 2 * 1864 / 6.0
    assert list(result)[-1] == "compared"
    assert result["device"]["platform"] == "cpu"   # never a device number


def test_traced_replay_cell_reports_its_layer_metrics(root):
    result, out = drive(root, "tiny-replay", trace=1)
    assert result["correct"] is True, out[-3000:]
    got = set(result["metrics"])
    assert {"ingest.kernel_drops", "ingest.ring_stalls",
            "ingest.lines_per_batch", "harness.compiles_in_window",
            "flush.mean_ms.replay", "device.idle_pct",
            "ingest.parse_errors"} == got
    assert result["device"]["busy_s"] > 0
    assert 0 < result["device"]["window_s"] < 6
    assert len(result["breakdown"]["device_ops"]) <= 10
    assert len(result["breakdown"]["idle_gaps"]) <= 10


def test_withheld_datagram_is_failed_and_correct_stays_true(root):
    result, out = drive(root, "tiny-each-key", "skip")
    assert result["correct"] is True, out[-3000:]
    assert result["failed"] == 10          # that datagram's lines
    assert result["attempted"] == 2 * 1864
    assert result["metrics"]["lines_per_s"]["value"] == (2 * 1864 - 10) / 6.0
    assert "1 datagrams taken as lost" in out


def test_burst_of_withheld_datagrams_is_failed_and_compared_bounded(root):
    """440 of interval 0's 4,402 datagrams never arrive: more than the
    counts of 50 keys can name one by one."""
    result, out = drive(root, "tiny-replay", "burst")
    assert result["correct"] is True, out[-3000:]
    assert result["failed"] == 440 * 13
    assert result["attempted"] - result["failed"] == round(
        result["metrics"]["lines_per_s"]["value"] * 6.0)
    assert "1 part(s) compared bounded" in out
    assert numbers(result)["read_not_aggregated"] == 0


@pytest.mark.parametrize("at,seconds", [(1.0, 0.7), (1.6, 1.7)])
def test_host_that_stands_still_leaves_correct_true(root, at, seconds):
    """Every process of the run stopped in the middle of interval 0's
    send, and from before its end until past the next tick: whatever the
    server then loses is `failed`; what the senders had left when the
    quiet time was half over is withheld, not sent into interval 1."""
    result, out = drive(root, "tiny-replay", "freeze",
                        extra=["--freeze-at", str(at),
                               "--freeze-s", str(seconds)])
    assert result["correct"] is True, out[-4000:]
    assert numbers(result)["read_not_aggregated"] == 0
    if seconds > 1.5:
        assert result["failed"] > 0 and "(0 of them withheld)" not in \
            [ln for ln in out.splitlines() if "interval 0:" in ln][0]


@pytest.mark.parametrize("fault,number", [
    ("remove", "scalar_keys_wrong"),     # a read line absent from the bodies
    ("alter", "timer_stats_wrong"),      # an answer altered
    ("drop_rows", "scalar_keys_wrong"),  # half of each batch left out
])
def test_read_line_lost_or_altered_is_not_correct(root, fault, number):
    result, out = drive(root, "tiny-each-key", fault)
    assert result["correct"] is False, out[-3000:]
    assert numbers(result)[number] >= 1
    assert result["failed"] == 0
    if fault != "alter":
        assert numbers(result)["read_not_aggregated"] >= 1


@pytest.mark.parametrize("workload", ["tiny-each-key", "tiny-replay"])
def test_lowered_precision_control_is_not_correct(root, workload):
    result, out = drive(root, workload, "control")
    assert result["correct"] is False, out[-3000:]
    got = numbers(result)
    assert got["cold_timer_rel_gap"] > result["compared"][
        "cold_timer_rel_gap"]["limit"]
    # and it is the only number the control fails
    assert all(v["value"] <= v["limit"] for k, v in
               result["compared"].items() if k != "cold_timer_rel_gap")


def test_no_accelerator_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    done = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload",
         "timers1k-replay", "--seed", "1", "--seconds", "10", "--trace", "0"],
        capture_output=True, text=True, timeout=300, env=env, cwd=ROOT)
    assert done.returncode != 0
    assert not [ln for ln in done.stdout.splitlines() if ln.startswith("{")]
    assert "not 'tpu'" in done.stderr
