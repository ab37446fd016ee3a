"""The flush round's one span source (core/telemetry.FlushRound) and the
ingest path's busy / wait / CPU rows, end to end: one server with a
Datadog sink posting to a loopback stub intake, native-pump UDP traffic
where the native library builds, warm flushes, then one flush read back
through /debug/flush, one under a jax.profiler capture, and two scrapes
of /metrics around more traffic.

Pins the vocabulary too: every `/debug/flush` phase key and `/metrics`
row that a file under benchmark/layer_metrics/ names is produced by the
program, so renaming a span fails here instead of silently dropping a
metric from the benchmark's line.
"""

import glob
import json
import os
import socket
import subprocess
import sys
import threading
import time
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

import numpy as np
import pytest

from veneur_tpu import native
from veneur_tpu.core.server import Server
from veneur_tpu.core.telemetry import FlushRound
from veneur_tpu.sinks.datadog import DatadogMetricSink
from veneur_tpu.util import http as vhttp

from test_server import generate_config

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
LAYER_METRICS = sorted(glob.glob(
    os.path.join(ROOT, "benchmark", "layer_metrics", "*.json")))
INTAKE_DELAY_S = 0.2   # the sink's wait dwarfs a thread switch
SWITCH_S = 0.002       # what an identity may miss by on a loaded host

# ISSUE 27's table of new phase keys, and the keys /debug/flush had
NEW_PHASES = (
    "sync_s", "transfer_s", "assembly_scalar_s", "assembly_histogram_s",
    "assembly_set_s", "assembly_llhist_s", "recycle_s", "egress_start_s",
    "egress_encode_s", "egress_join_s", "egress_post_wall_s",
    "egress_gzip_s", "egress_http_s", "flush_cpu_s")
# ISSUE 28: what of the send is left once the encode has ended
NEW_PHASES += ("egress_post_tail_s",)
KEPT_PHASES = (
    "swap_s", "preflush_s", "store_flush_s", "dispatch_s",
    "device_sync_s", "assembly_s", "sink_join_s")
# ISSUE 31: what left with the readout that ran a tick ahead
GONE_PHASES = ("join_s", "critical_path_s")
INGEST_ROWS = (
    "veneur_ingest_reader_cpu_seconds_total",
    "veneur_ingest_reader_stall_seconds_total",
    "veneur_ingest_dispatch_cpu_seconds_total",
    "veneur_ingest_dispatch_wait_seconds_total",
    "veneur_ingest_apply_seconds_total",
    "veneur_ingest_apply_lock_wait_seconds_total")
# rows that only the native pump feeds
PUMP_ROWS = INGEST_ROWS[:4] + ("veneur_ingest_ring_stalls_total",)
# metrics of the four-shard cell that only a `tpu.shards` > 1 server
# feeds: tests/test_sharded_flush_loop.py pins them on such a server;
# here, on one shard, what they read must be absent (not 0)
MESH_ONLY = ("flush.merge_ms", "ingest.shard_route_s", "mesh.merge_rounds")
# metrics of the routed cell that only a server with
# `features.enable_metric_sink_routing` feeds: tests/test_routed_flush.py
# pins them on such a server; here, with routing off, absent (not 0)
ROUTED_ONLY = ("flush.route_ms", "flush.materialize_ms",
               "flush.egress_select_ms", "flush.routed_rows",
               "flush.unrouted_rows", "flush.route_evaluated_rows")
# ISSUE 39: `dispatch{set}` in its four parts, the flush thread's whole
# wait for the chip, and the readout's device time from completion stamps
SET_CHILDREN = ("set_fold", "set_wait", "set_transfer", "set_host_estimate")
NEW_PHASES += tuple(name + "_s" for name in SET_CHILDREN) + (
    "chip_wait_s", "chip_busy_s")
WATCHED = ("histogram", "counter", "gauge", "llhist", "set")  # as dispatched
HOT_SETS = 3           # set keys that pass the promotion threshold a round
PROMOTE_SAMPLES = 4    # the table's own is 2,048 on the CPU backend


class _Intake(BaseHTTPRequestHandler):
    def do_POST(self):
        self.rfile.read(int(self.headers.get("Content-Length", 0)))
        time.sleep(INTAKE_DELAY_S)
        self.send_response(202)
        self.send_header("Content-Length", "2")
        self.end_headers()
        self.wfile.write(b"{}")

    def log_message(self, *args):
        pass


def _lines(round_no: int) -> list:
    lines = []
    for i in range(300):
        lines += [b"sp.c%d:1|c" % i, b"sp.t%d:%d|ms" % (i, i + round_no),
                  b"sp.t%d:%d|ms" % (i, 2 * i + 1)]
    for i in range(60):
        lines += [b"sp.g%d:2|g" % i, b"sp.s%d:m%d|s" % (i, round_no),
                  b"sp.l%d:%d|l" % (i, i + 1)]
    # sets past the promotion threshold: their rows live on the device,
    # so the readout waits for the estimate (`set_wait`); the 60 above
    # stay on the host (`set_host_estimate`)
    for i in range(HOT_SETS):
        lines += [b"sp.hot%d:m%d.%d|s" % (i, round_no, j)
                  for j in range(3 * PROMOTE_SAMPLES)]
    return lines


def _prom(base: str) -> dict:
    rows: dict = {}
    for line in vhttp.get(base + "/metrics")[1].decode().splitlines():
        if line and not line.startswith("#"):
            head, _, value = line.rpartition(" ")
            name = head.split("{", 1)[0]
            rows[name] = rows.get(name, 0.0) + float(value)
    return rows


@pytest.fixture(scope="module")
def flushed(tmp_path_factory):
    import jax

    httpd = ThreadingHTTPServer(("127.0.0.1", 0), _Intake)
    httpd.daemon_threads = True
    threading.Thread(target=httpd.serve_forever, daemon=True,
                     name="stub-intake").start()
    sink = DatadogMetricSink(
        "datadog", "key", f"http://127.0.0.1:{httpd.server_port}", "me",
        10.0, flush_max_per_body=400, num_workers=4)
    cfg = generate_config(
        statsd_listen_addresses=["udp://127.0.0.1:0"],
        http_address="127.0.0.1:0", interval=60.0, num_readers=2)
    cfg.tpu.counter_capacity = cfg.tpu.histo_capacity = 512
    server = Server(cfg, extra_metric_sinks=[sink])
    server.store.sets._promote_samples = PROMOTE_SAMPLES
    server.start()
    pumped = getattr(server._listeners[0], "pump", None) is not None
    sock = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
    address = tuple(server.local_addr("udp")[:2])

    def send(round_no: int) -> None:
        lines = _lines(round_no)
        # `processed` is stamped after a batch has been applied to the
        # store; `packets_received` when a reader has parsed it
        want = server.store.processed + len(lines)
        for k in range(0, len(lines), 20):
            sock.sendto(b"\n".join(lines[k:k + 20]), address)
        deadline = time.time() + 10.0
        while server.store.processed < want and time.time() < deadline:
            time.sleep(0.02)
        assert server.store.processed >= want
        server.store.apply_all_pending()

    base = "http://%s:%d" % tuple(server.http_api.address[:2])
    try:
        for round_no in range(2):   # compile, then recycle once
            send(round_no)
            server.flush()
        send(2)
        server.flush()
        measured = json.loads(
            vhttp.get(base + "/debug/flush?n=1")[1])["rounds"][-1]
        scrape_1 = _prom(base)
        trace_dir = str(tmp_path_factory.mktemp("profile"))
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 1
        jax.profiler.start_trace(trace_dir, profiler_options=options)
        try:
            send(3)
            server.flush()
        finally:
            jax.profiler.stop_trace()
        scrape_2 = _prom(base)
        rounds = json.loads(
            vhttp.get(base + "/debug/flush?n=4")[1])["rounds"]
        yield {"round": measured, "scrapes": (scrape_1, scrape_2),
               "rounds": rounds, "trace_dir": trace_dir, "pumped": pumped}
    finally:
        sock.close()
        server.shutdown()
        httpd.shutdown()
        httpd.server_close()


# -- (a) the phase keys and the three identities ---------------------------

@pytest.mark.parametrize("key", NEW_PHASES + KEPT_PHASES)
def test_debug_flush_has_phase(flushed, key):
    phases = flushed["round"]["phases"]
    assert key in phases, sorted(phases)
    assert phases[key] >= 0.0


@pytest.mark.parametrize("key", GONE_PHASES)
def test_debug_flush_lost_phase(flushed, key):
    assert key not in flushed["round"]["phases"]
    assert key[:-len("_s")] not in {
        s["name"] for s in flushed["round"]["spans"]}


def test_sync_and_transfer_make_up_device_sync(flushed):
    p = flushed["round"]["phases"]
    assert p["device_sync_s"] > 0
    assert p["sync_s"] + p["transfer_s"] <= p["device_sync_s"]
    # within 2 %, or 2 ms where a loaded host takes the thread away
    # between two spans
    assert p["device_sync_s"] - p["sync_s"] - p["transfer_s"] <= max(
        0.02 * p["device_sync_s"], SWITCH_S), p


def test_family_blocks_make_up_assembly(flushed):
    p = flushed["round"]["phases"]
    parts = sum(p[k] for k in ("assembly_scalar_s", "assembly_histogram_s",
                               "assembly_set_s", "assembly_llhist_s",
                               "recycle_s"))
    assert parts <= p["assembly_s"]
    assert p["assembly_s"] - parts <= max(0.10 * p["assembly_s"],
                                          SWITCH_S), p


def test_egress_spans_explain_sink_join(flushed):
    """The sends run beside the encode, so what adds up on the sink
    thread is start + encode (+ a join there: a one-body flush's) + the
    tail, not the post wall."""
    p = flushed["round"]["phases"]
    spans = flushed["round"]["spans"]
    [sink] = [s for s in spans if s.get("sink") == "metric:datadog"
              and s["name"] == "sink"]
    joined_here = sum(s["wall_s"] for s in spans
                      if s["name"] == "egress_join"
                      and s["thread"] == sink["thread"])
    parts = (p["egress_start_s"] + p["egress_encode_s"] + joined_here
             + p["egress_post_tail_s"])
    assert p["sink_join_s"] >= INTAKE_DELAY_S
    assert p["egress_post_tail_s"] <= p["egress_post_wall_s"]
    assert abs(parts - p["sink_join_s"]) <= max(0.05 * p["sink_join_s"],
                                                SWITCH_S), p


def test_gzip_and_http_run_inside_the_post_wall(flushed):
    rnd = flushed["round"]
    spans = rnd["spans"]
    [wall] = [s for s in spans if s["name"] == "egress_post_wall"]
    bodies = [s for s in spans if s["name"] == "egress_http"]
    assert len(bodies) == wall["bodies"] >= 2
    # more than one worker posted, so the summed http time passes the wall
    assert len({s["thread"] for s in bodies}) >= 2
    assert rnd["phases"]["egress_http_s"] >= len(bodies) * INTAKE_DELAY_S
    sent = rnd["sinks"]["metric:datadog"]
    assert sent["bodies"] == wall["bodies"]
    # each answer takes 0.2 s, the encode of a body far less: every
    # hand-off but the first few finds all workers busy, up to the cap
    assert sent["workers"] == wall["workers"] == wall["peak_in_flight"] \
        == min(4, wall["bodies"])
    # bodies whose gzip began while the encoder ran: never the last one
    [encode] = [s for s in spans if s["name"] == "egress_encode"]
    assert sent["bodies_overlapped"] == wall["bodies_overlapped"] == sum(
        s["start_s"] < encode["start_s"] + encode["wall_s"]
        for s in spans if s["name"] == "egress_gzip") < wall["bodies"]
    assert 0 < sent["gzip_bytes"] < sent["bytes"]
    assert sent["gzip_bytes"] == sum(
        s["bytes"] for s in spans if s["name"] == "egress_gzip")


def test_flush_cpu_counts_each_thread_once(flushed):
    rnd = flushed["round"]
    by_name = {}
    for s in rnd["spans"]:
        by_name.setdefault(s["name"], []).append(s)
    [root] = by_name["flush"]
    [sink] = [s for s in by_name["sink"]
              if s.get("sink") == "metric:datadog"]
    workers = {s["thread"] for s in by_name["egress_http"]} - {
        sink["thread"]}
    floor = root["cpu_s"] + sink["cpu_s"] + sum(
        s["cpu_s"] for s in by_name["egress_http"] + by_name["egress_gzip"]
        + by_name["egress_join"] if s["thread"] in workers)
    cpu = rnd["phases"]["flush_cpu_s"]
    assert cpu == pytest.approx(floor, rel=0.02, abs=2e-4)
    # a thread's CPU seconds cannot pass the wall it was alive for
    assert cpu <= root["wall_s"] * (2 + len(workers))


@pytest.mark.parametrize("field", ["llhist_rows", "llhist_nonzero_bins"])
def test_debug_flush_counts_the_llhists_nonzero_bins(flushed, field):
    """The 60 llhist keys take one sample a round each: 60 rows leave
    the readout as 60 nonzero registers (of 270,060), and the counter
    on /metrics adds the same up with every readout delivered."""
    assert flushed["round"][field] == 60
    row = "veneur_flush_llhist_nonzero_bins_total"
    first, second = flushed["scrapes"]
    assert first[row] == 3 * 60
    assert second[row] == first[row] + 60


def _section_series(rnd: dict) -> int:
    """Series of a round that come from `batch.sections`: all but the
    llhists' bucket lines (a line per nonzero bin and `le:+Inf`)."""
    return (rnd["metrics_flushed"] - rnd["llhist_nonzero_bins"]
            - rnd["llhist_rows"])


@pytest.mark.parametrize("field", ["encoder", "native_rows",
                                   "prefix_renders", "count_mismatch"])
def test_debug_flush_names_the_series_encoder(flushed, field):
    """Every round reports the same keys, so the measured one (the
    third) finds each section's prefix arena as the second left it:
    every section row went through the native encoder, none through
    `_frag` (but `ssf.names_unique`, the server's own, in the round it
    happens to land in)."""
    if native.load_series() is None:
        pytest.skip("the native series encoder did not build")
    sent = flushed["round"]["sinks"]["metric:datadog"]
    [encode] = [s for s in flushed["round"]["spans"]
                if s["name"] == "egress_encode"]
    assert encode[field] == sent[field]
    if field == "prefix_renders":
        assert sent[field] <= 1
    elif field == "count_mismatch":
        # the flush checked its own count: every row is in a body
        assert sent[field] == 0
    else:
        assert sent[field] == {
            "encoder": "native",
            "native_rows": _section_series(flushed["round"])}[field]


@pytest.mark.parametrize("row, field", [
    ("veneur_sink_datadog_encode_native_rows_total", "native_rows"),
    ("veneur_sink_datadog_encode_prefix_renders_total", "prefix_renders"),
    ("veneur_sink_datadog_encode_count_mismatch_total", "count_mismatch"),
    ("veneur_sink_datadog_post_workers_total", "workers")])
def test_metrics_count_the_series_encoders_rows(flushed, row, field):
    """The counters add up what each round's `sinks.<key>` says:
    `native_rows` a flush's section series, `prefix_renders` those of
    the first flush and next to nothing since, `workers` the POST
    workers each flush started."""
    if native.load_series() is None:
        pytest.skip("the native series encoder did not build")
    rounds = flushed["rounds"]
    assert len(rounds) == 4 and rounds[2]["flush"] == flushed["round"]["flush"]
    per_round = [r["sinks"]["metric:datadog"][field] for r in rounds]
    first, second = flushed["scrapes"]
    assert first[row] == sum(per_round[:3])
    assert second[row] == sum(per_round)
    series = [_section_series(r) for r in rounds]
    if field == "native_rows":
        assert per_round == series
    elif field == "count_mismatch":
        assert per_round == [0, 0, 0, 0]
    elif field == "workers":
        assert per_round == [4, 4, 4, 4]
    else:
        assert per_round[0] == series[0] >= 2520
        assert max(per_round[1:]) <= 1


# -- (a2) the wait for the chip, where it happens (ISSUE 39) ---------------

def _spans(rnd: dict, name: str, **tags) -> list:
    return [s for s in rnd["spans"] if s["name"] == name
            and all(s.get(k) == v for k, v in tags.items())]


def _end(span: dict) -> float:
    return span["start_s"] + span["wall_s"]


@pytest.mark.parametrize("name", SET_CHILDREN)
def test_dispatch_set_has_the_child(flushed, name):
    """The round's set table held promoted rows and sparse ones, so all
    four parts of the sets' readout are there, on the flush thread: the
    fold inside `dispatch{set}` (a second time where a last pending
    batch was applied), the wait, the copy and the host estimate inside
    `assembly_set`, where the estimate is collected (ISSUE 40)."""
    rnd = flushed["round"]
    [dispatch] = _spans(rnd, "dispatch", family="set")
    parent = "dispatch" if name == "set_fold" else "assembly_set"
    [outer] = _spans(rnd, parent, **({"family": "set"}
                                     if parent == "dispatch" else {}))
    children = _spans(rnd, name)
    assert 1 <= len(children) <= 1 + (name == "set_fold")
    for child in children:
        assert child["parent"] == parent and child["family"] == "set"
        assert child["thread"] == outer["thread"] == dispatch["thread"]
        assert outer["start_s"] - 1e-6 <= child["start_s"]
        assert _end(child) <= _end(outer) + 1e-6


def test_set_children_follow_each_other_and_make_up_dispatch_set(flushed):
    """The four parts follow each other; the fold makes up
    `dispatch{set}` (the estimate's dispatch is its last step)."""
    rnd = flushed["round"]
    [outer] = _spans(rnd, "dispatch", family="set")
    parts = sorted((s for name in SET_CHILDREN for s in _spans(rnd, name)),
                   key=lambda s: s["start_s"])
    assert [s["name"] for s in parts][-4:] == list(SET_CHILDREN)
    for before, after in zip(parts, parts[1:]):
        assert _end(before) <= after["start_s"] + 1e-6
    assert sum(s["wall_s"] for s in parts) == pytest.approx(
        sum(rnd["phases"][name + "_s"] for name in SET_CHILDREN), abs=1e-5)
    folds = sum(s["wall_s"] for s in _spans(rnd, "set_fold"))
    assert folds <= outer["wall_s"] + 1e-6
    assert outer["wall_s"] - folds <= SWITCH_S, (outer, parts)


def test_dispatch_set_waits_for_nothing_and_the_join_follows_the_sets(
        flushed):
    """No `set_wait` lies inside `dispatch{set}`: the estimate is
    collected in `assembly_set`, after the scalars' and histograms'
    assembly; the watcher is joined after that, before the drained
    generations are recycled (the last span of the assembly)."""
    rnd = flushed["round"]
    [dispatch] = _spans(rnd, "dispatch", family="set")
    [wait] = _spans(rnd, "set_wait")
    [assembly] = _spans(rnd, "assembly")
    [collected] = _spans(rnd, "assembly_set")
    [recycle] = _spans(rnd, "recycle")
    assert wait["start_s"] >= _end(dispatch)
    for before in ("assembly_scalar", "assembly_histogram"):
        [block] = _spans(rnd, before)
        assert _end(block) <= collected["start_s"] + 1e-6
    blocks = [s for s in rnd["spans"] if s["parent"] == "assembly"]
    assert max(blocks, key=lambda s: s["start_s"]) is recycle
    assert _end(recycle) <= _end(assembly) + 1e-6
    for busy in _spans(rnd, "chip_busy"):
        assert _end(busy) <= recycle["start_s"] + 1e-6, busy


def test_chip_wait_is_every_sync_and_the_sets_wait(flushed):
    p = flushed["round"]["phases"]
    assert p["set_wait_s"] > 0
    assert p["chip_wait_s"] == pytest.approx(p["sync_s"] + p["set_wait_s"],
                                             abs=2e-6)


@pytest.mark.parametrize("family", WATCHED)
def test_one_chip_busy_span_per_family_and_device(flushed, family):
    """One device here, so one completion stamp a family, the sets'
    too, from the watcher: inside `readout`, not before the family was
    dispatched, closed before the readout ends."""
    rnd = flushed["round"]
    [busy] = _spans(rnd, "chip_busy", family=family)
    [readout] = _spans(rnd, "readout")
    [dispatch] = _spans(rnd, "dispatch", family=family)
    assert busy["parent"] == "readout" and busy["device"].startswith("cpu:")
    assert busy["wall_s"] >= 0.0 and busy["cpu_s"] == 0.0
    assert readout["start_s"] <= dispatch["start_s"] - 1e-6 <= busy["start_s"]
    assert _end(busy) <= _end(readout) + 1e-6


def test_chip_busy_spans_of_a_device_follow_each_other(flushed):
    rnd = flushed["round"]
    busy = _spans(rnd, "chip_busy")
    assert [s["family"] for s in busy] == list(WATCHED)   # as dispatched
    assert len({s["device"] for s in busy}) == 1
    for before, after in zip(busy, busy[1:]):
        assert _end(before) <= after["start_s"] + 1e-9
    assert rnd["phases"]["chip_busy_s"] == pytest.approx(
        sum(s["wall_s"] for s in busy), abs=1e-5)
    # device seconds of one device cannot pass the wall they lie in
    [readout] = _spans(rnd, "readout")
    assert rnd["phases"]["chip_busy_s"] <= readout["wall_s"]


def test_readout_kernel_row_is_fed_by_the_stamps(flushed):
    """`device.kernel.readout_s` counts one time a family, device and
    round (four rounds by the second scrape), `dispatches` one a family
    and round, as it did."""
    _first, second = flushed["scrapes"]
    assert second["veneur_device_kernel_readout_s_count_total"] == \
        4 * len(WATCHED)


def _quiet_round(observatory: bool) -> dict:
    """The last of three flushes of a server that nobody sends a set,
    through `handle_metric_packet` (no listener, no sink), with the set
    table's count of deferred estimates beside it."""
    cfg = generate_config(interval=60.0, device_observatory=observatory)
    cfg.tpu.counter_capacity = cfg.tpu.histo_capacity = 512
    server = Server(cfg)
    try:
        for round_no in range(3):
            for i in range(40):
                for line in (b"q.c%d:1|c", b"q.g%d:2|g", b"q.t%d:3|ms",
                             b"q.l%d:4|l"):
                    server.handle_metric_packet(line % i)
            server.store.apply_all_pending()
            server.flush()
        return dict(server.telemetry.flushes.snapshot()[-1],
                    deferred_estimates=(
                        server.store.sets.deferred_estimates_total))
    finally:
        server.shutdown()


@pytest.fixture(scope="module")
def quiet():
    return {on: _quiet_round(on) for on in (True, False)}


def test_idle_set_table_waits_in_sync_alone(quiet):
    """No set key: the table closes none of its spans, and the flush
    thread's wait for the chip is the `sync` spans'."""
    rnd = quiet[True]
    assert not [s for s in rnd["spans"] if s["name"] in SET_CHILDREN]
    assert not {name + "_s" for name in SET_CHILDREN} & set(rnd["phases"])
    assert rnd["phases"]["chip_wait_s"] == pytest.approx(
        rnd["phases"]["sync_s"], abs=2e-6)
    assert [s["family"] for s in _spans(rnd, "chip_busy")] == list(
        WATCHED[:-1])


def test_deferred_estimates_count_the_flushes_with_device_set_rows(
        flushed, quiet):
    """`flush.set.deferred_estimates_total`: one a flush whose set table
    held promoted rows (every one of the four here), none where nobody
    sends a set."""
    row = "veneur_flush_set_deferred_estimates_total"
    first, second = flushed["scrapes"]
    assert (first[row], second[row]) == (3, 4)
    assert quiet[True]["deferred_estimates"] == 0
    assert quiet[False]["deferred_estimates"] == 0


def test_set_chip_share_and_set_counters_read_what_the_rounds_did(
        flushed, quiet):
    """`set_chip_busy_s` is the sets' `chip_busy` spans, a
    share of `chip_busy_s`; absent where nobody sends a set. At
    /metrics, the three promoted keys of every round are its device
    rows, the 60 one-member keys (and at most the server's own
    `ssf.names_unique`) its host rows, the bank never climbs, and the
    fold carries no more than the promoted keys' backlog."""
    rnd = flushed["round"]
    busy = _spans(rnd, "chip_busy", family="set")
    assert rnd["phases"]["set_chip_busy_s"] == pytest.approx(
        sum(s["wall_s"] for s in busy), abs=1e-5)
    assert 0 <= rnd["phases"]["set_chip_busy_s"] <= \
        rnd["phases"]["chip_busy_s"]
    assert "set_chip_busy_s" not in quiet[True]["phases"]
    first, second = flushed["scrapes"]

    def grew(row):
        return first["veneur_" + row], second["veneur_" + row]

    assert grew("flush_set_device_rows_total") == (3 * HOT_SETS,
                                                    4 * HOT_SETS)
    host = grew("flush_set_host_rows_total")
    assert 3 * 60 <= host[0] <= 3 * 61 and 60 <= host[1] - host[0] <= 61
    dispatches = grew("flush_set_fold_dispatches_total")
    entries = grew("flush_set_fold_entries_total")
    assert 0 <= dispatches[1] - dispatches[0] <= 1
    assert 0 <= entries[1] - entries[0] <= HOT_SETS * (PROMOTE_SAMPLES - 1)
    assert grew("set_slot_ladder_climbs_total") == (0, 0)
    assert second["veneur_set_device_slots"] == 64   # the table's rows


def test_set_fold_counters_count_the_rounds_dispatches_and_entries():
    """Through the columnar flush, six keys promoted at their fourth
    member leave 3 backlog entries each; they fold in one dispatch of
    the bank's fold size (64 slots x 3 entries, rounded up to a window
    of 1,024 pairs), after the last pending batch's, though batch_cap
    is 8; their estimates and six two-member keys' are computed on the
    device and the host."""
    from veneur_tpu.core.columnstore import ColumnStore
    from veneur_tpu.core.flusher import flush_columnstore_batch
    from veneur_tpu.ops import hll_ref
    from veneur_tpu.samplers.metrics import HistogramAggregates
    from veneur_tpu.samplers.parser import Parser

    store = ColumnStore(counter_capacity=64, gauge_capacity=64,
                        histo_capacity=64, set_capacity=64, batch_cap=8)
    sets = store.sets
    sets._promote_samples = PROMOTE_SAMPLES
    parser = Parser()
    for i in range(6):
        for j in range(3 * PROMOTE_SAMPLES + i):
            parser.parse_metric_fast(b"hot.%d:m%d|s" % (i, j), store.process)
        for j in range(2):
            parser.parse_metric_fast(b"cold.%d:m%d|s" % (i, j), store.process)
    pending = sets._n
    batch, _fwd = flush_columnstore_batch(
        store, False, (0.5,), HistogramAggregates.from_names(["count"]),
        timing=FlushRound())
    backlog = 6 * (PROMOTE_SAMPLES - 1)
    assert sets.fold_entries_total == backlog + pending
    assert sets._fold_size(64) == 1024
    assert sets.fold_dispatches_total == -(
        -backlog // sets._fold_size(64)) + (pending > 0)
    assert sets.fold_dispatches_total == 1 + (pending > 0)
    assert (sets.device_rows_total, sets.host_rows_total) == (6, 6)
    got = {str(n): v for s in batch.sections for n, v in zip(s.names,
                                                              s.values)}
    for name, n in (("hot.5", 3 * PROMOTE_SAMPLES + 5), ("cold.0", 2)):
        ref = hll_ref.HLL()
        for j in range(n):
            ref.insert(b"m%d" % j)
        assert got[name] == ref.estimate(), name


def test_no_observatory_no_chip_busy_and_the_same_flush(quiet):
    on, off = quiet[True], quiet[False]
    assert not _spans(off, "chip_busy")
    assert "chip_busy_s" not in off["phases"]
    assert "chip_wait_s" in off["phases"]

    def shape(rnd):
        return sorted((s["name"], s["parent"], s.get("family"),
                       s.get("device")) for s in rnd["spans"]
                      if s["name"] != "chip_busy")

    assert shape(on) == shape(off)
    assert on["metrics_flushed"] == off["metrics_flushed"] > 0
    assert set(on["phases"]) - set(off["phases"]) == {"chip_busy_s"}


def _set_round_store():
    """A store whose one round holds promoted set rows (past the
    promotion threshold, with a pre-promotion backlog to fold) and
    sparse ones, of both scopes, beside the other families: a local
    server emits the local-only sets' estimates and forwards the
    others' registers."""
    from veneur_tpu.core.columnstore import ColumnStore
    from veneur_tpu.samplers.parser import Parser

    store = ColumnStore(counter_capacity=64, gauge_capacity=64,
                        histo_capacity=64, set_capacity=64, batch_cap=128)
    store.sets._promote_samples = PROMOTE_SAMPLES
    lines = []
    for i in range(6):
        scope = b"" if i % 2 else b"|#veneurlocalonly"
        lines += [b"hot.%d:m%d|s%s" % (i, j, scope)
                  for j in range(3 * PROMOTE_SAMPLES + i)]
        lines += [b"cold.%d:m%d|s%s" % (i, j, scope) for j in range(2)]
        lines += [b"c.%d:1|c" % i, b"t.%d:%d|ms" % (i, i), b"l.%d:2|l" % i]
    parser = Parser()
    for line in lines:
        parser.parse_metric_fast(line, store.process)
    store.apply_all_pending()
    return store


def test_deferred_set_readout_flushes_what_the_eager_one_does(monkeypatch):
    """The columnar flush collects the sets' estimate in `assembly_set`;
    with `SetTable.readout` forced to collect at once (as every snapshot
    and live read does) the same round gives the same FlushBatch:
    sections in the same order, the same names, values, tags and types,
    and the same forwarded registers."""
    from veneur_tpu.core.flusher import flush_columnstore_batch
    from veneur_tpu.samplers.metrics import HistogramAggregates

    aggs = HistogramAggregates.from_names(["min", "max", "count"])
    deferred, eager = _set_round_store(), _set_round_store()
    plain = eager.sets.readout
    monkeypatch.setattr(eager.sets, "readout",
                        lambda snap, timing=None, collect=True:
                        plain(snap, timing))
    rounds = (FlushRound(), FlushRound())
    (got, got_fwd), (want, want_fwd) = (
        flush_columnstore_batch(store, True, (0.5, 0.99), aggs,
                                collect_forward=True, timing=rnd)
        for store, rnd in zip((deferred, eager), rounds))
    # both paths ran, and waited for promoted rows' estimate
    assert [s["parent"] for s in rounds[0].spans
            if s["name"] == "set_wait"] == ["assembly_set"]
    assert [s["parent"] for s in rounds[1].spans
            if s["name"] == "set_wait"] == ["dispatch"]
    assert (deferred.sets.deferred_estimates_total,
            eager.sets.deferred_estimates_total) == (1, 0)
    assert len(got.sections) == len(want.sections) > 0
    for a, b in zip(got.sections, want.sections):
        assert a.mtype == b.mtype
        np.testing.assert_array_equal(a.names, b.names)
        np.testing.assert_array_equal(a.values, b.values)
        assert list(a.tags) == list(b.tags)
    estimates = [v for a in got.sections for n, v in zip(a.names, a.values)
                 if str(n).startswith(("hot.", "cold."))]
    assert len(estimates) == 6 and min(estimates) >= 2
    assert [m.name for m, _ in got_fwd.sets] == [
        m.name for m, _ in want_fwd.sets]
    assert len(got_fwd.sets) == 6
    for (_, a), (_, b) in zip(got_fwd.sets, want_fwd.sets):
        np.testing.assert_array_equal(a, b)
        assert a.any()


def test_an_assembly_that_raises_still_recycles_every_family(monkeypatch):
    """The drained generations go back as spares even where the
    assembly fails, after the sets' collect would have run: the
    recycle span closes and every family's snap is recycled once."""
    from veneur_tpu.core.flusher import flush_columnstore_batch
    from veneur_tpu.samplers.metrics import HistogramAggregates

    store = _set_round_store()
    recycled = []
    for family in ("counters", "gauges", "histos", "llhists", "sets"):
        table = getattr(store, family)
        monkeypatch.setattr(table, "recycle",
                            lambda snap, f=family: recycled.append(f))

    def broken(snap, *args, **kwargs):
        raise RuntimeError("assembly failed")

    monkeypatch.setattr(store.sets, "collect", broken)
    rnd = FlushRound()
    with pytest.raises(RuntimeError, match="assembly failed"):
        flush_columnstore_batch(
            store, True, (0.5,), HistogramAggregates.from_names(["max"]),
            timing=rnd)
    assert recycled == ["counters", "gauges", "histos", "llhists", "sets"]
    assert [s["parent"] for s in rnd.spans
            if s["name"] == "recycle"] == ["assembly"]


# -- (b) the spans form a tree on one clock --------------------------------

def test_every_span_has_a_parent_that_exists(flushed):
    spans = flushed["round"]["spans"]
    names = {s["name"] for s in spans}
    assert {s["parent"] for s in spans} - {None} <= names
    assert [s["name"] for s in spans if s["parent"] is None] == ["flush"]
    for s in spans:
        assert set(s) >= {"name", "parent", "thread", "start_s", "wall_s",
                          "cpu_s"}, s


def test_a_child_lies_inside_its_parent(flushed):
    spans = flushed["round"]["spans"]
    slack = 1e-4   # /debug/flush rounds nothing, clocks are read in turn
    for s in spans:
        if s["parent"] is None:
            continue
        parents = [p for p in spans if p["name"] == s["parent"]]
        assert any(p["start_s"] - slack <= s["start_s"]
                   and s["start_s"] + s["wall_s"]
                   <= p["start_s"] + p["wall_s"] + slack
                   for p in parents), (s, parents)


# -- (c) the ingest rows ---------------------------------------------------

@pytest.mark.parametrize("row", INGEST_ROWS)
def test_ingest_row_is_there_and_monotonic(flushed, row):
    if row in PUMP_ROWS and not flushed["pumped"]:
        pytest.skip(f"no native pump: {native.unavailable_reason()}")
    first, second = flushed["scrapes"]
    assert row in first and row in second
    assert second[row] >= first[row] >= 0.0


def test_reader_and_dispatcher_cpu_is_counted(flushed):
    if not flushed["pumped"]:
        pytest.skip(f"no native pump: {native.unavailable_reason()}")
    first, second = flushed["scrapes"]
    assert first["veneur_ingest_reader_cpu_seconds_total"] > 0
    assert (second["veneur_ingest_dispatch_cpu_seconds_total"]
            > first["veneur_ingest_dispatch_cpu_seconds_total"] > 0)
    assert (second["veneur_ingest_apply_seconds_total"]
            > first["veneur_ingest_apply_seconds_total"] > 0)
    # the dispatcher idles between the rounds' bursts
    assert second["veneur_ingest_dispatch_wait_seconds_total"] > 0.1


# -- (d) the same spans on the profiler's clock ----------------------------

@pytest.mark.parametrize("event", ["veneur/flush", "veneur/egress_encode",
                                   "veneur/apply.histogram",
                                   "veneur/egress_http"])
def test_profiler_capture_holds_the_spans(flushed, event):
    from jax.profiler import ProfileData

    [path] = glob.glob(os.path.join(flushed["trace_dir"], "plugins",
                                    "profile", "*", "*.xplane.pb"))
    profile = ProfileData.from_file(path)
    found = [plane.name for plane in profile.planes
             for line in plane.lines for e in line.events
             if e.name == event]
    assert found and all(name.startswith("/host:") for name in found), found


# -- (e) names on the device -----------------------------------------------

def test_apply_and_readout_kernels_carry_their_scope():
    from veneur_tpu.ops import batch_tdigest

    state = batch_tdigest.init_state(16)
    applied = batch_tdigest._apply_batch_jit.lower(
        state, np.zeros(8, np.int32), np.zeros(8, np.float32),
        np.ones(8, np.float32), np.zeros(8, np.int32))
    read_out = batch_tdigest.flush_quantiles_packed.lower(
        state, (0.5, 0.99), True)
    for lowered, scope in ((applied, "veneur/apply/histogram"),
                           (read_out, "veneur/readout/histogram")):
        # the optimized module, as the device runs it: its fusions keep
        # the scope in their metadata
        text = lowered.compile().as_text()
        names = [ln for ln in text.splitlines() if "op_name=" in ln]
        assert names and any(scope in ln.split("op_name=", 1)[1]
                             for ln in names), text[:2000]


# -- (f) the benchmark's vocabulary ----------------------------------------

@pytest.mark.parametrize("path", LAYER_METRICS, ids=[
    os.path.basename(p)[:-len(".json")] for p in LAYER_METRICS])
def test_layer_metric_reads_something_the_program_produces(flushed, path):
    with open(path) as f:
        reader = json.load(f)["reader"]
    if os.path.basename(path)[:-len(".json")] in MESH_ONLY + ROUTED_ONLY:
        assert not set(reader.get("keys", ())) & set(
            flushed["round"]["phases"])
        assert reader.get("row") not in flushed["scrapes"][1]
    elif reader["kind"] == "flush_phase":
        phases = flushed["round"]["phases"]
        assert set(reader["keys"]) <= set(phases), sorted(phases)
    elif reader["kind"] == "prometheus":
        if reader["row"] in PUMP_ROWS and not flushed["pumped"]:
            pytest.skip(f"no native pump: {native.unavailable_reason()}")
        assert reader["row"] in flushed["scrapes"][1]
    else:
        pytest.skip(f"a {reader['kind']} reader reads the harness, not "
                    "the program")


# -- the helper itself -----------------------------------------------------

def test_round_times_a_readout_on_its_own_clock():
    rnd = FlushRound()
    with rnd.phase("flush"):
        time.sleep(0.002)
        with rnd.phase("readout", parent="flush"):
            with rnd.phase("dispatch", parent="readout", family="set"):
                time.sleep(0.002)
    by_name = {s["name"]: s for s in rnd.spans}
    # the readout ran inside this round: its spans start after 0
    assert (0 <= by_name["flush"]["start_s"] < by_name["readout"]["start_s"]
            <= by_name["dispatch"]["start_s"])
    assert by_name["dispatch"]["family"] == "set"
    assert rnd.phases["dispatch_s"] == by_name["dispatch"]["wall_s"] > 0.001
    # nested on one thread: the outer span's CPU is the thread's
    assert rnd.cpu_s() == pytest.approx(by_name["flush"]["cpu_s"])


def test_handoff_phase_ends_on_another_thread():
    rnd = FlushRound()
    starting = rnd.phase("egress_start", parent="flush").start(handoff=True)
    worker = threading.Thread(target=starting.stop, name="sink-thread")
    worker.start()
    worker.join(5.0)
    assert not worker.is_alive()
    [span] = rnd.spans
    assert span["cpu_s"] == 0.0 and span["wall_s"] > 0
    assert span["thread"] == threading.current_thread().name


def test_phase_imports_no_jax_into_a_process_without_it():
    code = (
        "import sys\n"
        "from veneur_tpu.core.telemetry import FlushRound\n"
        "rnd = FlushRound()\n"
        "with rnd.phase('flush'):\n"
        "    pass\n"
        "assert rnd.phases['flush_s'] >= 0\n"
        "assert 'jax' not in sys.modules, 'jax was imported'\n")
    done = subprocess.run([sys.executable, "-c", code], cwd=ROOT,
                          capture_output=True, text=True, timeout=120)
    assert done.returncode == 0, done.stderr[-2000:]


# -- loss the sockets' own rows cannot see ---------------------------------

def test_snmp_rcvbuf_errors_parse():
    from veneur_tpu.core.overload import KernelDropMonitor

    text = ("Tcp: RtoAlgorithm RtoMin\nTcp: 1 200\n"
            "Udp: InDatagrams NoPorts InErrors OutDatagrams RcvbufErrors "
            "SndbufErrors\nUdp: 56 0 19944 20000 19943 0\n"
            "UdpLite: InDatagrams\nUdpLite: 0\n")
    assert KernelDropMonitor.parse_proc_snmp(text) == 19943
    assert KernelDropMonitor.parse_proc_snmp("Udp: InDatagrams\nUdp: 1\n") \
        is None
    assert KernelDropMonitor.parse_proc_snmp("") is None


def test_overflowed_socket_shows_in_udp_rcvbuf_errors():
    """A datagram dropped at a full receive buffer moves the host-wide
    row, whatever /proc/net/udp says of the socket."""
    from veneur_tpu.core.overload import KernelDropMonitor

    if not os.path.exists(KernelDropMonitor.SNMP_FILE):
        pytest.skip("no /proc/net/snmp here")
    monitor = KernelDropMonitor()
    with socket.socket(socket.AF_INET, socket.SOCK_DGRAM) as rx, \
            socket.socket(socket.AF_INET, socket.SOCK_DGRAM) as tx:
        rx.bind(("127.0.0.1", 0))
        rx.setsockopt(socket.SOL_SOCKET, socket.SO_RCVBUF, 4096)
        monitor.watch_socket(rx, "test")
        monitor.poll()   # the baseline: earlier loss is not this one's
        assert monitor.rcvbuf_errors == 0
        tx.setblocking(False)
        sent = 0
        for _ in range(2000):
            try:
                tx.sendto(b"x" * 1000, rx.getsockname())
                sent += 1
            except BlockingIOError:
                break
        time.sleep(0.1)
        monitor.poll()
        rx.setblocking(False)
        got = 0
        try:
            while True:
                rx.recv(2048)
                got += 1
        except BlockingIOError:
            pass
    if got == sent:
        pytest.skip("this network stack buffered every datagram")
    # host-wide: other sockets may add to it, never take away
    assert monitor.rcvbuf_errors >= sent - got > 0
