"""The 100k-set deployment's rehearsal: `drive.py`'s tiny cell with
nothing but set keys, end to end on the CPU, a process per run (~30 s).

`sets100k`, its cell `sets100k-interval`, its traffic
`each-set-per-interval` and the three metrics that came with them are
files and manifest entries only: `run.load_cell` finds each by name, and
the tiny cell below is listed wherever the real one is, in a copy of the
manifest. Its bank climbs the slot ladder 256 -> 2,048 in the harness's
first warm-up round, on programs the server's own warm-up compiled.
"""

import json
import os
import shutil
import subprocess
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
CELL = "sets100k-interval"
NEW_METRICS = {"flush.set_fold_dispatches", "flush.set_device_rows",
               "flush.set_chip_ms"}
PACING = ("kind", "lines_per_datagram", "lead_s", "send_window",
          "warmup_send_s", "sender_processes")
KEYS = {"set": 1500}
MEMBERS = 16
LINES = 1500 * MEMBERS   # per interval
FLUSHES = 2


def test_the_cell_its_config_its_traffic_and_its_metric_files_are_found():
    import run as bench
    from harness.traffic import Traffic

    loaded = bench.load_cell(CELL)
    sibling = bench.load_cell("global100k-interval")
    assert loaded["cell"]["chips"] == 1
    assert loaded["cell"]["traffic"] == "each-set-per-interval"
    config = loaded["config"]
    assert config["keys"] == {"set": 100000}
    tpu = config["overrides"]["tpu"]
    assert tpu["set_capacity"] == tpu["set_max_dev_slots"] == 131072
    assert tpu["set_promote_samples"] == 16 and tpu["shards"] == 1
    assert config["reduced"] == ["offered_rate"]
    assert len(config["source"]) <= 200
    # the pacing is each-key-per-interval's, letter for letter; the rate
    # follows from keys x members
    traffic, other = loaded["traffic"], sibling["traffic"]
    for key in PACING:
        assert traffic[key] == other[key], key
    assert traffic["per_interval"]["samples"] == {"set_members": 16}
    assert traffic["check"] == {"sets": 20000}   # drawn from the seed
    assert Traffic(traffic, config, 1).lines_of(0) == 1_600_000
    names = {m["name"] for m in loaded["per_layer"]}
    assert NEW_METRICS <= names
    assert not NEW_METRICS & {m["name"] for m in sibling["per_layer"]}
    for m in loaded["per_layer"]:
        if m["name"] in NEW_METRICS:
            assert m["layer"] == "readout kernels + sync"
            assert m["moves"] == "flush_ms" and m["workloads"] == [CELL]
    # no timer, llhist, route, merge or compact metric: nothing to read
    assert not {n for n in names if "timers" in n or "llhist" in n
                or "route" in n or "merge" in n or "compact" in n}
    assert {m["name"] for m in loaded["end_to_end"]} == {
        "flush_ms", "lines_per_s", "cpu_us_per_line", "setup_s"}


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    """A copy of the benchmark's data with one tiny sets-only cell added,
    listed wherever `sets100k-interval` is."""
    root = str(tmp_path_factory.mktemp("bench_root_sets"))
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

    add("configs", "tiny-sets", {
        "name": "tiny-sets", "interval_s": 3.0,
        "percentiles": [0.5, 0.9, 0.99], "keys": KEYS, "overrides": {
            "synchronize_with_interval": True, "num_readers": 2,
            "tpu": {"counter_capacity": 64, "gauge_capacity": 64,
                    "histo_capacity": 64, "set_capacity": 2048,
                    "set_max_dev_slots": 2048, "set_promote_samples": 16,
                    "llhist_capacity": 16, "batch_cap": 512, "shards": 1}}})
    add("traffic", "tiny-each-set", {
        "kind": "per_interval", "lines_per_datagram": 40, "lead_s": 0.3,
        "send_window": 0.5, "warmup_send_s": 0.8, "sender_processes": 1,
        "per_interval": {"keys": KEYS,
                         "samples": {"set_members": MEMBERS}},
        "check": {}})
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    manifest["configs"].append({
        "name": "tiny-sets", "source": "test", "reduced": [],
        "why": "test", "file": "benchmark/configs/tiny-sets.json"})
    manifest["workloads"].append({
        "name": "tiny-sets-interval", "config": "tiny-sets",
        "traffic": "tiny-each-set", "chips": 1, "why": "test"})
    for metric in manifest["end_to_end"] + manifest["per_layer"]:
        if CELL in metric.get("workloads", ()):
            metric["workloads"].append("tiny-sets-interval")
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(manifest, f)
    for path, text in before.items():
        assert open(path).read() == text, f"{path} was edited"
    return root


def drive(root, trace, seed=2_147_484_141):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    done = subprocess.run(
        [sys.executable, os.path.join(BENCH, "tests", "drive.py"),
         "--root", root, "--workload", "tiny-sets-interval",
         "--seed", str(seed), "--seconds", str(3 * FLUSHES),
         "--trace", str(trace)],
        capture_output=True, text=True, timeout=600, env=env, cwd=ROOT)
    assert done.returncode == 0, done.stdout[-3000:] + done.stderr[-3000:]
    return json.loads(done.stdout.strip().splitlines()[-1]), done.stdout


def test_sets_cell_proves_correct_on_the_flush_loops_own_ticks(root):
    result, out = drive(root, trace=0)
    assert result["correct"] is True, out[-3000:]
    assert result["failed"] == 0 and result["attempted"] == FLUSHES * LINES
    assert set(result["metrics"]) == {"flush_ms", "lines_per_s",
                                      "cpu_us_per_line", "setup_s"}
    assert result["device"]["platform"] == "cpu"   # never a device number
    assert result["compared"]["set_keys_wrong"]["value"] == 0
    # nothing compiled after the server's own warm-up: the harness's
    # rounds, the climb to 2,048 slots included, found every program there
    warmups = [line for line in out.splitlines()
               if "benchmark: warm-up " in line and "compiled or loaded" in line]
    assert len(warmups) == 2
    for line in warmups:
        assert line.rstrip().endswith("compiled or loaded []"), line


def test_traced_sets_cell_reports_the_fold_and_the_device_rows(root):
    result, out = drive(root, trace=1, seed=2_147_484_142)
    assert result["correct"] is True, out[-3000:]
    got = {name: m["value"] for name, m in result["metrics"].items()}
    assert NEW_METRICS <= set(got), sorted(got)
    assert got["harness.compiles_in_window"] == 0, out[-3000:]
    assert got["flush.late"] == 0
    # every key is a device row in every flush of the window; the
    # backlog of their first 15 members folds in several batches
    assert got["flush.set_device_rows"] == KEYS["set"] * FLUSHES
    assert got["flush.set_fold_dispatches"] > FLUSHES
    assert got["flush.set_chip_ms"] >= 0
    assert got["flush.set_wait_ms"] >= 0 and got["flush.chip_wait_ms"] >= 0
