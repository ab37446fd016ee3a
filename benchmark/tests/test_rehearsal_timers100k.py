"""The 100k-timer deployment's rehearsal: `drive.py`'s tiny cell with
nothing but timer keys, end to end on the CPU, a process per run (~20 s).

`timers100k`, its cell `timers100k-interval`, its traffic
`each-timer-per-interval` and the compaction metric that came with them
are files and manifest entries only: `run.load_cell` finds each by
name, and the tiny cell below is listed wherever the real one is, in a
copy of the manifest.
"""

import json
import os
import shutil
import subprocess
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
CELL = "timers100k-interval"
COMPACT_METRIC = "ingest.tdigest_compacts"
# what a deployment without llhists, routing or a mesh leaves out
NOT_HERE = {"flush.assembly_llhist_ms", "flush.llhist_nonzero_bins",
            "flush.route_ms", "flush.materialize_ms", "flush.egress_select_ms",
            "flush.routed_rows", "flush.unrouted_rows",
            "flush.route_evaluated_rows", "flush.merge_ms",
            "ingest.shard_route_s",
            "mesh.merge_rounds", "flush.mean_ms.replay"}
KEYS = {"timer": 300}
TIERS = [[2, 400], [8, 33], [None, 3]]
LINES = 2 * 400 + 8 * 33 + 290 * 3   # per interval
SERIES = 300 * 6   # a timer's min, max, count and three percentiles


def test_the_cell_its_config_its_traffic_and_its_metric_files_are_found():
    import run as bench
    from harness.traffic import Traffic

    loaded = bench.load_cell(CELL)
    sibling = bench.load_cell("global100k-interval")
    small = bench.load_cell("timers1k-replay")["config"]
    assert loaded["cell"]["chips"] == 1
    assert loaded["cell"]["traffic"] == "each-timer-per-interval"
    config = loaded["config"]
    assert config["keys"] == {"timer": 100000}
    assert config["overrides"]["tpu"] == {
        **small["overrides"]["tpu"], "histo_capacity": 131072}
    for key in ("interval_s", "percentiles", "guarantees", "limits",
                "synchronize_with_interval_why"):
        assert config[key] == small[key], key
    assert config["reduced"] == ["offered_rate"]
    assert len(config["source"]) <= 200
    # the pacing is each-key-per-interval's, letter for letter; the lines
    # of an interval are as many as that traffic sends at 100k keys
    traffic, other = loaded["traffic"], sibling["traffic"]
    for key in ("kind", "lines_per_datagram", "lead_s", "send_window",
                "warmup_send_s", "sender_processes", "check"):
        assert traffic[key] == other[key], key
    assert (traffic["per_interval"]["samples"]["timer"]
            == other["per_interval"]["samples"]["timer"])
    assert Traffic(traffic, config, 1).lines_of(0) == Traffic(
        other, sibling["config"], 1).lines_of(0) == 332936
    names = {m["name"] for m in loaded["per_layer"]}
    sibling_names = {m["name"] for m in sibling["per_layer"]}
    assert COMPACT_METRIC in names and COMPACT_METRIC in sibling_names
    # the flush's wait for the chip is this cell's own: `sync_s`, which
    # reads ~0 on one device wherever the set family's estimate hides it
    assert names == (sibling_names - NOT_HERE) | {"flush.shard_sync_ms"}
    assert {m["name"] for m in loaded["end_to_end"]} == {
        "flush_ms", "lines_per_s", "cpu_us_per_line", "setup_s"}
    for m in loaded["per_layer"]:
        if m["name"] == COMPACT_METRIC:
            assert m["reader"]["kind"] == "prometheus"
            assert m["layer"] == "column-store apply"
            assert m["moves"] == "cpu_us_per_line"
            assert m["workloads"] == [CELL, "global100k-interval",
                                      "timers1k-replay"]


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    """A copy of the benchmark's data with one tiny timers-only cell
    added, listed wherever `timers100k-interval` is."""
    root = str(tmp_path_factory.mktemp("bench_root_timers"))
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

    add("configs", "tiny-timers", {
        "name": "tiny-timers", "interval_s": 3.0,
        "percentiles": [0.5, 0.9, 0.99], "keys": KEYS, "overrides": {
            "synchronize_with_interval": True, "num_readers": 2,
            "tpu": {"counter_capacity": 64, "gauge_capacity": 64,
                    "histo_capacity": 512, "set_capacity": 32,
                    "llhist_capacity": 16, "batch_cap": 256, "shards": 1}}})
    add("traffic", "tiny-each-timer", {
        "kind": "per_interval", "lines_per_datagram": 10, "lead_s": 0.3,
        "send_window": 0.5, "warmup_send_s": 0.8, "sender_processes": 1,
        "per_interval": {"keys": KEYS, "samples": {"timer": TIERS}},
        "check": {"timer_first": 20, "timers": 40,
                  "cold_timer_max_samples": 8}})
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    manifest["configs"].append({
        "name": "tiny-timers", "source": "test", "reduced": [],
        "why": "test", "file": "benchmark/configs/tiny-timers.json"})
    manifest["workloads"].append({
        "name": "tiny-timers-interval", "config": "tiny-timers",
        "traffic": "tiny-each-timer", "chips": 1, "why": "test"})
    for metric in manifest["end_to_end"] + manifest["per_layer"]:
        if CELL in metric.get("workloads", ()):
            metric["workloads"].append("tiny-timers-interval")
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(manifest, f)
    for path, text in before.items():
        assert open(path).read() == text, f"{path} was edited"
    return root


def drive(root, trace, seed=2_147_484_536):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    done = subprocess.run(
        [sys.executable, os.path.join(BENCH, "tests", "drive.py"),
         "--root", root, "--workload", "tiny-timers-interval",
         "--seed", str(seed), "--seconds", "6", "--trace", str(trace)],
        capture_output=True, text=True, timeout=600, env=env, cwd=ROOT)
    assert done.returncode == 0, done.stdout[-3000:] + done.stderr[-3000:]
    return json.loads(done.stdout.strip().splitlines()[-1]), done.stdout


def test_timers_cell_proves_correct_on_the_flush_loops_own_ticks(root):
    result, out = drive(root, trace=0)
    assert result["correct"] is True, out[-3000:]
    assert result["failed"] == 0 and result["attempted"] == 2 * LINES
    assert set(result["metrics"]) == {"flush_ms", "lines_per_s",
                                      "cpu_us_per_line", "setup_s"}
    assert result["metrics"]["lines_per_s"]["value"] == 2 * LINES / 6.0
    assert result["device"]["platform"] == "cpu"   # never a device number
    compared = result["compared"]
    assert compared["timer_stats_wrong"]["value"] == 0
    assert 0 < compared["timer_rank_gap"]["value"] <= 0.02
    assert compared["cold_timer_rel_gap"]["value"] <= 1.5e-6
    # nothing compiled after the server's own warm-up: the harness's
    # rounds, first overflow included, found every program there
    for line in out.splitlines():
        if "benchmark: warm-up " in line and "compiled or loaded" in line:
            assert line.rstrip().endswith("compiled or loaded []"), line


def test_traced_timers_cell_reports_the_compaction_metrics(root):
    result, out = drive(root, trace=1)
    assert result["correct"] is True, out[-3000:]
    got = {name: m["value"] for name, m in result["metrics"].items()}
    assert COMPACT_METRIC in got, sorted(got)
    assert not NOT_HERE & set(got), sorted(got)
    assert got["harness.compiles_in_window"] == 0, out[-3000:]
    assert got["flush.late"] == 0
    # the two very hot keys pass their 128 staging slots several times
    # an interval; every such batch compacts the whole table once
    compacts = got["ingest.tdigest_compacts"]
    assert compacts == int(compacts) and 2 <= compacts <= 40, compacts
    assert got["flush.shard_sync_ms"] >= 0
    # every series of the window's two flushes left by the native encoder
    assert got["flush.egress_native_rows"] >= 2 * SERIES
    assert got["flush.assembly_timers_ms"] > 0
    assert got["flush.egress_ms"] >= got["flush.egress_post_wall_ms"] > 0
