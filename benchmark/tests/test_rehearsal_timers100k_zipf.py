"""The Zipf-keyed 100k-timer deployment's rehearsal: `drive.py`'s tiny
cell with Zipf-skewed timer keys, end to end on the CPU, a process per
run (~30 s).

`timers100k-zipf`, its cell `timers100k-zipf-interval`, its traffic
`zipf-timers-per-interval` and the two metrics that came with them
(`ingest.tdigest_dense_keys`, `ingest.tdigest_compact_chip_s`) are
files and manifest entries only: `run.load_cell` finds each by name, and
the tiny cell below is listed wherever the real one is, in a copy of the
manifest. Its hottest key passes 128 samples in every batch of 1,024
lines, so the dense k-bucket fold and a whole-table compact run batch
after batch, as they do at 100k keys on the chip.
"""

import json
import os
import shutil
import subprocess
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
CELL = "timers100k-zipf-interval"
SIBLING = "timers100k-interval"
NEW_METRICS = {"ingest.tdigest_dense_keys", "ingest.tdigest_compact_chip_s"}
PACING = ("kind", "lines_per_datagram", "lead_s", "send_window",
          "warmup_send_s", "sender_processes")
ZIPF = [[1, 117387], [1, 59102], [2, 34660], [6, 16868], [20, 6441],
        [70, 2082], [200, 678], [700, 216], [2000, 70], [7000, 23],
        [20000, 8], [None, 3]]
KEYS = {"timer": 400}
# a key of rank r sends ~r^-1 of the hottest's, as the real tiers do
TIERS = [[1, 1200], [1, 600], [2, 350], [6, 170], [20, 65], [70, 21],
         [200, 7], [None, 3]]
LINES = 1200 + 600 + 2 * 350 + 6 * 170 + 20 * 65 + 70 * 21 + 200 * 7 \
    + 100 * 3   # per interval
FLUSHES = 2


def test_the_cell_its_config_its_traffic_and_its_metric_files_are_found():
    import run as bench
    from harness.traffic import Traffic

    loaded = bench.load_cell(CELL)
    sibling = bench.load_cell(SIBLING)
    assert loaded["cell"]["chips"] == 1
    assert loaded["cell"]["traffic"] == "zipf-timers-per-interval"
    config, other = loaded["config"], sibling["config"]
    # timers100k's deployment, bar the skew of its keys
    for key in ("interval_s", "percentiles", "keys", "overrides",
                "guarantees", "limits", "reduced", "device_tables"):
        assert config[key] == other[key], key
    assert len(config["source"]) <= 200
    assert "key_distribution" in config["assumed"]
    traffic = loaded["traffic"]
    for key in PACING:
        assert traffic[key] == sibling["traffic"][key], key
    assert traffic["per_interval"]["samples"]["timer"] == ZIPF
    assert traffic["check"] == {"timer_first": 1000, "timers": 2000,
                                "cold_timer_max_samples": 8}
    assert Traffic(traffic, config, 1).lines_of(0) == 1_579_377
    assert Traffic(traffic, config, 1).fresh.datagrams == 39_485
    # reported wherever the sibling's are, and the two new metrics in
    # both cells
    names = {m["name"] for m in loaded["per_layer"]}
    sibling_names = {m["name"] for m in sibling["per_layer"]}
    assert NEW_METRICS <= names == sibling_names
    for m in loaded["per_layer"]:
        if m["name"] in NEW_METRICS:
            assert m["reader"]["kind"] == "prometheus"
            assert m["layer"] == "column-store apply"
            assert m["workloads"] == [CELL, SIBLING]
    moves = {m["name"]: m["moves"] for m in loaded["per_layer"]}
    assert moves["ingest.tdigest_dense_keys"] == "cpu_us_per_line"
    assert moves["ingest.tdigest_compact_chip_s"] == "flush_ms"
    assert {m["name"] for m in loaded["end_to_end"]} == {
        "flush_ms", "lines_per_s", "cpu_us_per_line", "setup_s"}


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    """A copy of the benchmark's data with one tiny Zipf-timers cell
    added, listed wherever `timers100k-zipf-interval` is."""
    root = str(tmp_path_factory.mktemp("bench_root_zipf"))
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

    add("configs", "tiny-zipf", {
        "name": "tiny-zipf", "interval_s": 3.0,
        "percentiles": [0.5, 0.9, 0.99], "keys": KEYS, "overrides": {
            "synchronize_with_interval": True, "num_readers": 2,
            "tpu": {"counter_capacity": 64, "gauge_capacity": 64,
                    "histo_capacity": 512, "set_capacity": 32,
                    "llhist_capacity": 16, "batch_cap": 1024,
                    "shards": 1}}})
    add("traffic", "tiny-zipf-timers", {
        "kind": "per_interval", "lines_per_datagram": 20, "lead_s": 0.3,
        "send_window": 0.5, "warmup_send_s": 0.8, "sender_processes": 1,
        "per_interval": {"keys": KEYS, "samples": {"timer": TIERS}},
        "check": {"timer_first": 30, "timers": 60,
                  "cold_timer_max_samples": 8}})
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    manifest["configs"].append({
        "name": "tiny-zipf", "source": "test", "reduced": [],
        "why": "test", "file": "benchmark/configs/tiny-zipf.json"})
    manifest["workloads"].append({
        "name": "tiny-zipf-interval", "config": "tiny-zipf",
        "traffic": "tiny-zipf-timers", "chips": 1, "why": "test"})
    for metric in manifest["end_to_end"] + manifest["per_layer"]:
        if CELL in metric.get("workloads", ()):
            metric["workloads"].append("tiny-zipf-interval")
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(manifest, f)
    for path, text in before.items():
        assert open(path).read() == text, f"{path} was edited"
    return root


def drive(root, trace, seed):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    done = subprocess.run(
        [sys.executable, os.path.join(BENCH, "tests", "drive.py"),
         "--root", root, "--workload", "tiny-zipf-interval",
         "--seed", str(seed), "--seconds", str(3 * FLUSHES),
         "--trace", str(trace)],
        capture_output=True, text=True, timeout=600, env=env, cwd=ROOT)
    assert done.returncode == 0, done.stdout[-3000:] + done.stderr[-3000:]
    return json.loads(done.stdout.strip().splitlines()[-1]), done.stdout


def test_zipf_cell_proves_correct_with_dense_keys(root):
    result, out = drive(root, trace=0, seed=3_147_484_545)
    assert result["correct"] is True, out[-3000:]
    assert result["failed"] == 0
    assert result["attempted"] == FLUSHES * LINES
    assert set(result["metrics"]) == {"flush_ms", "lines_per_s",
                                      "cpu_us_per_line", "setup_s"}
    assert result["device"]["platform"] == "cpu"   # never a device number
    compared = result["compared"]
    assert compared["timer_stats_wrong"]["value"] == 0
    assert 0 < compared["timer_rank_gap"]["value"] <= 0.02
    assert compared["cold_timer_rel_gap"]["value"] <= 1.5e-6
    for line in out.splitlines():
        if "benchmark: warm-up " in line and "compiled or loaded" in line:
            assert line.rstrip().endswith("compiled or loaded []"), line


def test_traced_zipf_cell_reports_the_dense_keys_and_the_compacts_chip(root):
    result, out = drive(root, trace=1, seed=3_147_484_546)
    assert result["correct"] is True, out[-3000:]
    got = {name: m["value"] for name, m in result["metrics"].items()}
    assert NEW_METRICS <= set(got), sorted(got)
    assert got["harness.compiles_in_window"] == 0, out[-3000:]
    # the hottest key passes 128 samples in every full batch of 1,024
    # lines: dense there, so the next batch compacts the whole table
    batches = FLUSHES * LINES // 1024
    assert got["ingest.tdigest_dense_keys"] >= batches - FLUSHES
    assert got["ingest.tdigest_compacts"] >= batches - 2 * FLUSHES
    # stamped off the dispatcher's thread (a CPU number: not the chip's)
    assert got["ingest.tdigest_compact_chip_s"] > 0
