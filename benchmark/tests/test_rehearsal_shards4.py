"""The four-shard deployment's rehearsal: `drive.py`'s tiny cell with
`tpu.shards: 4` on four virtual CPU devices, end to end, a process per
run (~20 s).

`global100k-shards4`, its cell `global100k-interval-4chip` and the four
per-layer metrics that came with them are files and manifest entries
only: `run.load_cell` finds each by name, and the tiny cell below is
listed wherever the real one is, in a copy of the manifest.
"""

import json
import os
import shutil
import subprocess
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
CELL = "global100k-interval-4chip"
MESH_METRICS = {"flush.merge_ms", "flush.shard_sync_ms",
                "ingest.shard_route_s", "mesh.merge_rounds"}
KEYS = {"counter": 120, "gauge": 60, "timer": 90, "set": 20, "llhist": 10}
LINES = 1864   # what KEYS and the samples below come to, per interval
FAMILIES = 5


def test_the_cell_its_config_and_its_metric_files_are_found_by_name():
    import run as bench

    loaded = bench.load_cell(CELL)
    sibling = bench.load_cell("global100k-interval")
    assert loaded["cell"]["chips"] == 4
    assert loaded["cell"]["traffic"] == "each-key-per-interval"
    assert loaded["traffic"] == sibling["traffic"]
    config, other = loaded["config"], sibling["config"]
    assert config["overrides"]["tpu"].pop("shards") == 4
    assert other["overrides"]["tpu"].pop("shards") == 1
    for key in ("keys", "overrides", "percentiles", "guarantees", "limits",
                "interval_s", "reduced"):
        assert config[key] == other[key], key
    assert len(config["source"]) < 200
    names = {m["name"] for m in loaded["per_layer"]}
    assert MESH_METRICS <= names
    # everything its one-chip sibling reports, it reports
    assert {m["name"] for m in sibling["per_layer"]} <= names
    assert not MESH_METRICS & {m["name"] for m in sibling["per_layer"]}
    assert {m["name"] for m in loaded["end_to_end"]} == {
        "flush_ms", "lines_per_s", "cpu_us_per_line", "setup_s"}
    for m in loaded["per_layer"]:
        if m["name"] in MESH_METRICS:
            assert m["reader"]["kind"] in ("flush_phase", "prometheus")


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    """A copy of the benchmark's data with one tiny four-shard cell
    added, listed wherever `global100k-interval-4chip` is."""
    root = str(tmp_path_factory.mktemp("bench_root_shards4"))
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

    add("configs", "tiny-shards4", {
        "name": "tiny-shards4", "interval_s": 3.0,
        "percentiles": [0.5, 0.9, 0.99], "keys": KEYS, "overrides": {
            "synchronize_with_interval": True, "num_readers": 2,
            "tpu": {"counter_capacity": 256, "gauge_capacity": 128,
                    "histo_capacity": 128, "set_capacity": 32,
                    "llhist_capacity": 16, "batch_cap": 256, "shards": 4}}})
    add("traffic", "tiny-each-key", {
        "kind": "per_interval", "lines_per_datagram": 10, "lead_s": 0.3,
        "send_window": 0.5, "warmup_send_s": 0.8, "sender_processes": 1,
        "per_interval": {"keys": KEYS, "samples": {
            "timer": [[2, 400], [8, 33], [None, 3]], "set_members": 16,
            "llhist": 6}},
        "check": {"timer_first": 20, "timers": 40}})
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    manifest["configs"].append({
        "name": "tiny-shards4", "source": "test", "reduced": [],
        "why": "test", "file": "benchmark/configs/tiny-shards4.json"})
    manifest["workloads"].append({
        "name": "tiny-4chip", "config": "tiny-shards4",
        "traffic": "tiny-each-key", "chips": 4, "why": "test"})
    for metric in manifest["end_to_end"] + manifest["per_layer"]:
        if CELL in metric.get("workloads", ()):
            metric["workloads"].append("tiny-4chip")
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(manifest, f)
    for path, text in before.items():
        assert open(path).read() == text, f"{path} was edited"
    return root


def drive(root, trace, seed=2_147_484_329):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    done = subprocess.run(
        [sys.executable, os.path.join(BENCH, "tests", "drive.py"),
         "--root", root, "--workload", "tiny-4chip", "--seed", str(seed),
         "--seconds", "6", "--trace", str(trace)],
        capture_output=True, text=True, timeout=600, env=env, cwd=ROOT)
    assert done.returncode == 0, done.stdout[-3000:] + done.stderr[-3000:]
    return json.loads(done.stdout.strip().splitlines()[-1]), done.stdout


def test_four_shard_cell_proves_correct_on_the_flush_loops_own_ticks(root):
    result, out = drive(root, trace=0)
    assert result["correct"] is True, out[-3000:]
    assert result["failed"] == 0 and result["attempted"] == 2 * LINES
    assert set(result["metrics"]) == {"flush_ms", "lines_per_s",
                                      "cpu_us_per_line", "setup_s"}
    assert result["metrics"]["lines_per_s"]["value"] == 2 * LINES / 6.0
    assert result["device"]["count"] == 4
    assert result["device"]["platform"] == "cpu"   # never a device number


def test_traced_four_shard_cell_reports_the_mesh_metrics(root):
    result, out = drive(root, trace=1)
    assert result["correct"] is True, out[-3000:]
    got = {name: m["value"] for name, m in result["metrics"].items()}
    assert MESH_METRICS <= set(got), sorted(got)
    assert got["harness.compiles_in_window"] == 0, out[-3000:]
    assert got["flush.late"] == 0
    # one collective merge per family per flush: the two flushes of the
    # window; the flush of the opening tick merges what the server's own
    # self-metrics touched (it is inside the counters' two scrapes)
    merges = got["mesh.merge_rounds"]
    assert merges == int(merges) and merges >= 2 * FAMILIES, merges
    assert got["flush.merge_ms"] > 0 and got["flush.shard_sync_ms"] >= 0
    assert got["ingest.shard_route_s"] > 0
    assert result["device"]["count"] == 4
