"""The routed deployment's rehearsal: `drive.py`'s tiny cell with
`features.enable_metric_sink_routing` and the three rules of
`configs/global100k-routed.json`, end to end on the CPU, a process per
run (~20 s).

`global100k-routed`, its cell `global100k-routed-interval` and the five
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
CELL = "global100k-routed-interval"
ROUTE_METRICS = {"flush.route_ms", "flush.materialize_ms",
                 "flush.egress_select_ms", "flush.routed_rows",
                 "flush.unrouted_rows"}
# what only the columnar side of the gate has: the native encoder's
# counters and the pipelined send's tail
COLUMNAR_ONLY = {"flush.egress_native_rows", "flush.egress_prefix_renders",
                 "flush.egress_post_tail_ms"}
# the legacy flush runs the columnar one's spans, by name
SHARED_EGRESS = {"flush.egress_ms", "flush.egress_encode_ms",
                 "flush.egress_gzip_ms", "flush.egress_http_ms",
                 "flush.egress_post_wall_ms"}
ROUTING_KEYS = ("features", "metric_sink_routing")
KEYS = {"counter": 120, "gauge": 60, "timer": 90, "set": 20, "llhist": 10}
LINES = 1864   # what KEYS and the samples below come to, per interval
# series a flush of KEYS holds at the least: a counter, a gauge and a
# set one each, a timer min, max, count and three percentiles
SERIES = 120 + 60 + 20 + 90 * 6


def test_the_cell_its_config_and_its_metric_files_are_found_by_name():
    import run as bench

    loaded = bench.load_cell(CELL)
    sibling = bench.load_cell("global100k-interval")
    assert loaded["cell"]["chips"] == 1
    assert loaded["cell"]["traffic"] == "each-key-per-interval"
    assert loaded["traffic"] == sibling["traffic"]
    config, other = loaded["config"], sibling["config"]
    overrides = dict(config["overrides"])
    assert overrides.pop("features") == {"enable_metric_sink_routing": True}
    rules = overrides.pop("metric_sink_routing")
    assert [r["name"] for r in rules] == [
        "timers-and-histograms", "zones-0-3", "not-canary"]
    # every rule sends what it matches to the harness's one sink
    assert {(tuple(r["sinks"]["matched"]), tuple(r["sinks"]["not_matched"]))
            for r in rules} == {(("datadog",), ())}
    assert overrides == other["overrides"]
    guarantees = dict(config["guarantees"])
    assert "datadog" in guarantees.pop("routing")
    assert guarantees == other["guarantees"]
    for key in ("keys", "percentiles", "limits", "interval_s",
                "device_tables", "synchronize_with_interval_why"):
        assert config[key] == other[key], key
    assert set(config) == set(other)
    assert config["reduced"] == other["reduced"] + ["sinks"]
    assert "flush_async" not in config["defaults_kept"]
    assert len(config["source"]) < 200
    names = {m["name"] for m in loaded["per_layer"]}
    assert ROUTE_METRICS <= names
    # everything its unrouted sibling reports, bar what only the
    # columnar side has, and the five that only this side has
    sibling_names = {m["name"] for m in sibling["per_layer"]}
    assert names == (sibling_names - COLUMNAR_ONLY) | ROUTE_METRICS
    assert COLUMNAR_ONLY <= sibling_names and SHARED_EGRESS <= names
    assert not ROUTE_METRICS & sibling_names
    assert {m["name"] for m in loaded["end_to_end"]} == {
        "flush_ms", "lines_per_s", "cpu_us_per_line", "setup_s"}
    for m in loaded["per_layer"]:
        if m["name"] in ROUTE_METRICS:
            assert m["reader"]["kind"] in ("flush_phase", "prometheus")
            assert m["layer"] == "sink routing" and m["moves"] == "flush_ms"


def test_the_rules_route_every_key_of_the_traffic_to_the_one_sink():
    """By the plain reference, over the generator's own names and tags:
    no rule alone covers the keys, their union covers every one."""
    import run as bench
    from harness.traffic import key_name, key_tags
    from veneur_tpu.util import matcher_ref

    rules = bench.load_cell(CELL)["config"]["overrides"][
        "metric_sink_routing"]
    keys = [(key_name(fam, i), key_tags(i).split(","))
            for fam in KEYS for i in range(0, 40)]
    for name, tags in keys:
        assert matcher_ref.route(rules, name, tags) == {"datadog"}
    alone = [sum(bool(matcher_ref.rule_sinks(rule, name, tags))
                 for name, tags in keys) for rule in rules]
    assert alone == [2 * 40, len(keys) // 2, len(keys)]


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    """A copy of the benchmark's data with one tiny routed cell added,
    listed wherever `global100k-routed-interval` is."""
    import run as bench

    root = str(tmp_path_factory.mktemp("bench_root_routed"))
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

    real = bench.load_cell(CELL)["config"]["overrides"]
    add("configs", "tiny-routed", {
        "name": "tiny-routed", "interval_s": 3.0,
        "percentiles": [0.5, 0.9, 0.99], "keys": KEYS, "overrides": {
            "synchronize_with_interval": True, "num_readers": 2,
            "tpu": {"counter_capacity": 256, "gauge_capacity": 128,
                    "histo_capacity": 128, "set_capacity": 32,
                    "llhist_capacity": 16, "batch_cap": 256, "shards": 1},
            **{key: real[key] for key in ROUTING_KEYS}}})
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
        "name": "tiny-routed", "source": "test", "reduced": [],
        "why": "test", "file": "benchmark/configs/tiny-routed.json"})
    manifest["workloads"].append({
        "name": "tiny-routed-interval", "config": "tiny-routed",
        "traffic": "tiny-each-key", "chips": 1, "why": "test"})
    for metric in manifest["end_to_end"] + manifest["per_layer"]:
        if CELL in metric.get("workloads", ()):
            metric["workloads"].append("tiny-routed-interval")
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(manifest, f)
    for path, text in before.items():
        assert open(path).read() == text, f"{path} was edited"
    return root


def drive(root, trace, seed=2_147_484_411):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    done = subprocess.run(
        [sys.executable, os.path.join(BENCH, "tests", "drive.py"),
         "--root", root, "--workload", "tiny-routed-interval",
         "--seed", str(seed), "--seconds", "6", "--trace", str(trace)],
        capture_output=True, text=True, timeout=600, env=env, cwd=ROOT)
    assert done.returncode == 0, done.stdout[-3000:] + done.stderr[-3000:]
    return json.loads(done.stdout.strip().splitlines()[-1]), done.stdout


def test_routed_cell_proves_correct_on_the_flush_loops_own_ticks(root):
    result, out = drive(root, trace=0)
    assert result["correct"] is True, out[-3000:]
    assert result["failed"] == 0 and result["attempted"] == 2 * LINES
    assert set(result["metrics"]) == {"flush_ms", "lines_per_s",
                                      "cpu_us_per_line", "setup_s"}
    assert result["metrics"]["lines_per_s"]["value"] == 2 * LINES / 6.0
    assert result["device"]["platform"] == "cpu"   # never a device number
    # the phases the harness prints are the routed side's
    phases = json.loads(out.split("phases of the last flush ")[1]
                        .splitlines()[0])
    assert {"route_s", "materialize_s", "route_match_s", "egress_select_s",
            "egress_encode_s", "egress_join_s", "egress_post_wall_s",
            "egress_gzip_s", "egress_http_s"} <= set(phases)
    assert "egress_post_tail_s" not in phases


def test_traced_routed_cell_reports_the_routing_metrics(root):
    result, out = drive(root, trace=1)
    assert result["correct"] is True, out[-3000:]
    got = {name: m["value"] for name, m in result["metrics"].items()}
    assert ROUTE_METRICS | SHARED_EGRESS <= set(got), sorted(got)
    assert not COLUMNAR_ONLY & set(got), sorted(got)
    assert got["harness.compiles_in_window"] == 0, out[-3000:]
    assert got["flush.late"] == 0
    # every series of the window's two flushes was routed, to one sink
    routed = got["flush.routed_rows"]
    assert routed == int(routed) and routed >= 2 * SERIES, routed
    assert got["flush.unrouted_rows"] == 0
    assert got["flush.route_ms"] >= got["flush.materialize_ms"] > 0
    assert got["flush.egress_select_ms"] > 0
    assert got["flush.egress_encode_ms"] > 0
    assert got["flush.egress_ms"] >= got["flush.egress_post_wall_ms"] > 0
