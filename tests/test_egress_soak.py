"""Soaks of the Datadog egress that hold a whole server to conservation
while its flushes run beside live ingest (ISSUE 33, after PR 32's
refusal: 29 keys of one window absent or short, with every line read).

(a) a started `Server` (native pump where it builds, `ledger_strict`,
    its own `veneur.*` self-metrics in the same tables, one Datadog sink
    posting to a loopback intake, 1 s clock-aligned ticks) takes UDP
    traffic ACROSS its ticks for a dozen flushes. What was sent is then
    what was posted: counters, timer and llhist counts add up over the
    flushes, a quiet last interval gives every gauge and set exactly,
    no series is posted twice in a flush, the ledger balanced.
(b) in the same run every posted body is, byte for byte, the parts the
    Python loop (`encode`) gives for that flush's batch.

Both encoders run it, so a failure on both names the program and not
the encoder; the native one runs it once more with as many POST workers
as this host's cores allow, where a flush's bodies arrive in any order
and must still be the same bodies, each once. (c), the arena under
churn, is in test_egress.py's idiom at the end of this file.
"""

import gzip
import json
import socket
import threading
import time
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

import numpy as np
import pytest

from veneur_tpu import native
from veneur_tpu.core.egress import DatadogColumnarEncoder
from veneur_tpu.core.server import Server
from veneur_tpu.ops import hll_ref
from veneur_tpu.sinks.datadog import DatadogMetricSink

from test_server import generate_config

KEYS = {"c": 900, "g": 500, "t": 450, "s": 100, "l": 50}   # 2,000
SET_MEMBERS, TIMER_SAMPLES, LLHIST_SAMPLES = 16, 2, 4
PER_DATAGRAM = 40
PER_BODY = 1000
SOAK_FLUSHES = 12
PASS_S = 0.45     # one pass of every key's lines leaves across this long


def _pass_lines(n: int, rng) -> list:
    """Every key's lines of pass `n`, shuffled: what a pass adds to a
    counter and sets a gauge to depends on `n`, a set's members do not."""
    lines = [b"bench.c%d:%d|c|#env:soak,z:%d" % (i, 1 + (i + n) % 7, i % 4)
             for i in range(KEYS["c"])]
    lines += [b"bench.g%d:%d.25|g|#env:soak" % (i, n * 1000 + i)
              for i in range(KEYS["g"])]
    for i in range(KEYS["t"]):
        lines += [b"bench.t%d:%d|ms|#env:soak" % (i, 5 + (i * 7 + n + k) % 90)
                  for k in range(TIMER_SAMPLES)]
    for i in range(KEYS["s"]):
        lines += [b"bench.s%d:u%d|s|#env:soak" % (i, i * 100 + m)
                  for m in range(SET_MEMBERS)]
    for i in range(KEYS["l"]):
        lines += [b"bench.l%d:%d|l|#env:soak" % (i, 1 + (i + k) % 50)
                  for k in range(LLHIST_SAMPLES)]
    order = rng.permutation(len(lines))
    return [lines[j] for j in order.tolist()]


LINES_PER_PASS = (KEYS["c"] + KEYS["g"] + KEYS["t"] * TIMER_SAMPLES
                  + KEYS["s"] * SET_MEMBERS + KEYS["l"] * LLHIST_SAMPLES)


class _Intake:
    """A loopback Datadog intake that keeps every series body, inflated,
    in the order its last byte arrived."""

    def __init__(self):
        self.bodies: list = []
        lock = threading.Lock()
        bodies = self.bodies

        class Handler(BaseHTTPRequestHandler):
            protocol_version = "HTTP/1.1"

            def do_POST(self):
                raw = self.rfile.read(
                    int(self.headers.get("Content-Length", 0)))
                if self.path.startswith("/api/v1/series"):
                    if self.headers.get("Content-Encoding") == "gzip":
                        raw = gzip.decompress(raw)
                    with lock:
                        bodies.append(raw)
                self.send_response(202)
                self.send_header("Content-Length", "2")
                self.end_headers()
                self.wfile.write(b"{}")

            def log_message(self, *args):
                pass

        self.httpd = ThreadingHTTPServer(("127.0.0.1", 0), Handler)
        self.httpd.daemon_threads = True
        threading.Thread(target=self.httpd.serve_forever, daemon=True,
                         name="soak-intake").start()

    @property
    def url(self) -> str:
        return f"http://127.0.0.1:{self.httpd.server_port}"

    def close(self) -> None:
        self.httpd.shutdown()
        self.httpd.server_close()


def _sleep_until(t: float) -> None:
    while True:
        left = t - time.time()
        if left <= 0:
            return
        time.sleep(min(left, 0.05))


def _send_pass(sock, address, lines, seconds: float) -> None:
    datagrams = [b"\n".join(lines[k:k + PER_DATAGRAM])
                 for k in range(0, len(lines), PER_DATAGRAM)]
    t0 = time.monotonic()
    for n, datagram in enumerate(datagrams):
        sock.sendto(datagram, address)
        behind = t0 + (n + 1) * seconds / len(datagrams) - time.monotonic()
        if behind > 0:
            time.sleep(behind)


def _soak(encoder: str, num_workers) -> dict:
    """The run: -> what was sent, and per flush what was posted and what
    the Python loop makes of its batch. One POST worker: bodies arrive
    in the order they were cut; `None`: the cap a sink derives from the
    host's cores, as a deployment that sets no `datadog_num_workers`."""
    intake = _Intake()
    sink = DatadogMetricSink("datadog", "key", intake.url, "soak-host",
                             1.0, flush_max_per_body=PER_BODY,
                             num_workers=num_workers)
    if encoder == "python":
        sink._encoder._lib = None
    assert sink._encoder.name == encoder
    reference = DatadogColumnarEncoder(sink)
    reference._lib = None
    flushes: list = []
    real_flush = sink.flush_columnar

    def recording_flush(batch):
        before = len(intake.bodies)
        real_flush(batch)   # returns after its last body was answered
        flushes.append({"posted": list(intake.bodies[before:]),
                        "parts": reference.encode(batch)[0],
                        "rows": len(batch),
                        "workers": max(
                            s["workers"] for s in list(batch.timing.spans)
                            if s["name"] == "egress_post_wall")})

    sink.flush_columnar = recording_flush
    cfg = generate_config(
        statsd_listen_addresses=["udp://127.0.0.1:0"],
        http_address="127.0.0.1:0", interval=1.0, num_readers=2,
        synchronize_with_interval=True, stats_address="internal",
        ledger_strict=True)
    cfg.tpu.counter_capacity = cfg.tpu.gauge_capacity = 2048
    cfg.tpu.histo_capacity = 1024
    cfg.tpu.set_capacity = 256
    cfg.tpu.llhist_capacity = 128
    server = Server(cfg, extra_metric_sinks=[sink])
    server.start()
    pumped = getattr(server._listeners[0], "pump", None) is not None
    sock = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
    sock.setsockopt(socket.SOL_SOCKET, socket.SO_SNDBUF, 1 << 20)
    address = tuple(server.local_addr("udp")[:2])
    rng = np.random.default_rng(33)
    passes = 0

    def send(seconds: float) -> None:
        nonlocal passes
        _send_pass(sock, address, _pass_lines(passes, rng), seconds)
        passes += 1

    def wait_read() -> bool:
        """Until the server has read every line sent so far."""
        deadline = time.time() + 15.0
        while time.time() < deadline:
            if server.stats["packets_received"] >= passes * LINES_PER_PASS:
                return True
            time.sleep(0.01)
        return False

    try:
        # every key once, and two flushes, before the soak: the kernels
        # compile and the keys are interned outside it
        for _ in range(2):
            send(0.2)
            assert wait_read()
            time.sleep(0.3)
            server.flush()
        # the soak: passes leave back to back, across every tick
        first = len(flushes)
        end = time.time() + SOAK_FLUSHES + 0.5
        while time.time() < end:
            send(PASS_S)
        assert wait_read()
        # a tick passes with nothing sent; then one pass well inside an
        # interval, whose flush holds that pass and nothing else
        tick = float(int(time.time()) + 1)
        _sleep_until(tick + 0.3)
        if time.time() > tick + 0.45:    # a host that stood still
            tick = float(int(time.time()) + 1)
            _sleep_until(tick + 0.3)
        send(0.15)
        assert wait_read()
        in_one_interval = time.time() < tick + 0.8
        quiet = len(flushes)
        deadline = tick + 11.0
        while time.time() < deadline and not any(
                f["rows"] > sum(KEYS.values()) for f in flushes[quiet:]):
            time.sleep(0.05)
        time.sleep(0.2)
        ledger = server.ledger.report()
    finally:
        sock.close()
        server.shutdown()
        intake.close()
    return {"flushes": flushes, "first": first, "passes": passes,
            "pumped": pumped, "in_one_interval": in_one_interval,
            "ledger": ledger, "worker_cap": sink.num_workers}


def _series_of(flush: dict) -> list:
    out = []
    for body in flush["posted"]:
        out += json.loads(body)["series"]
    return out


def _name_of(series: dict) -> tuple:
    """A series' identity at the intake: its name and its tags."""
    return (series["metric"], series.get("host"), tuple(series["tags"]))


@pytest.fixture(scope="module", params=[
    ("native", 1), ("python", 1), ("native", None)],
    ids=["native", "python", "native-host_workers"])
def soaked(request):
    encoder, num_workers = request.param
    if encoder == "native" and native.load_series() is None:
        pytest.skip("the native series encoder did not build")
    return _soak(encoder, num_workers)


def test_soak_crosses_a_dozen_ticks_with_traffic(soaked):
    """The soak is what it says: a dozen flushes or more, each with
    series of the traffic in it, and the server's own beside them."""
    flushes = soaked["flushes"][soaked["first"]:]
    busy = [f for f in flushes if f["rows"] > sum(KEYS.values())]
    assert len(busy) >= SOAK_FLUSHES
    assert soaked["passes"] >= 2 * SOAK_FLUSHES
    names = {s["metric"] for f in busy for s in _series_of(f)}
    # the server's own, through the same counter, gauge and timer tables
    assert {"flush.metrics_total", "flush.total_duration_ns",
            "flush.phase_duration.count",
            "sink.datadog.encode.count_mismatch"} <= names
    assert any(n.startswith("bench.c") for n in names)


def test_soak_posts_no_series_twice_in_a_flush(soaked):
    for flush in soaked["flushes"]:
        names = [_name_of(s) for s in _series_of(flush)]
        assert len(names) == len(set(names)) == flush["rows"]


def test_soak_conserves_what_was_sent(soaked):
    """Counters, and the counts of timers and llhists, add up over all
    the flushes to what was sent, key by key."""
    got: dict = {}
    for flush in soaked["flushes"]:
        for s in _series_of(flush):
            if s["metric"].startswith("bench.") and s["type"] == "rate" \
                    and not s["metric"].endswith(".bucket"):
                got[s["metric"]] = (got.get(s["metric"], 0.0)
                                    + s["points"][0][1] * s["interval"])
    n = soaked["passes"]
    want = {f"bench.c{i}": float(sum(1 + (i + k) % 7 for k in range(n)))
            for i in range(KEYS["c"])}
    want.update({f"bench.t{i}.count": float(n * TIMER_SAMPLES)
                 for i in range(KEYS["t"])})
    want.update({f"bench.l{i}.count": float(n * LLHIST_SAMPLES)
                 for i in range(KEYS["l"])})
    off = {k: (got.get(k), v) for k, v in want.items() if got.get(k) != v}
    assert not off, (len(off), sorted(off.items())[:10])


def test_soak_last_interval_is_exact(soaked):
    """The pass sent alone inside the last interval: every gauge's
    value and every set's estimate, exactly; no set of any flush holds
    more than its members."""
    assert soaked["in_one_interval"], "the host stood still"
    ref = hll_ref.HLL()
    for m in range(SET_MEMBERS):
        ref.insert(b"u%d" % m)
    busy = [f for f in soaked["flushes"] if f["rows"] > sum(KEYS.values())]
    last = {s["metric"]: s["points"][0][1] for s in _series_of(busy[-1])}
    n = soaked["passes"] - 1
    for i in range(KEYS["g"]):
        assert last[f"bench.g{i}"] == n * 1000 + i + 0.25
    for i in range(KEYS["c"]):
        assert last[f"bench.c{i}"] == float(1 + (i + n) % 7)
    for i in range(KEYS["s"]):
        want = hll_ref.HLL()
        for m in range(SET_MEMBERS):
            want.insert(b"u%d" % (i * 100 + m))
        assert last[f"bench.s{i}"] == want.estimate()
    for flush in busy:
        for s in _series_of(flush):
            if s["metric"].startswith("bench.s"):
                assert 1 <= s["points"][0][1] <= SET_MEMBERS + 1


def test_soak_ledger_balances(soaked):
    """`ingest.admitted == agg.applied + agg.rejected` over the run, and
    in every interval but one that closed while a chunk was between its
    two stamps (`_ingest` stamps a chunk's counters and gauges admitted
    when it takes the chunk, each table stamps them applied as they
    land): the next interval is then off by as much the other way."""
    ledger = soaked["ledger"]
    assert ledger["strict"] and ledger["intervals_closed"] >= SOAK_FLUSHES
    assert ledger["identities"]["ingest"]["imbalance_net"] == 0.0
    off = [rec["imbalance"]["ingest"] for rec in ledger["intervals"]]
    carried = 0.0
    for imbalance in off:
        assert carried == 0.0 or imbalance == -carried
        carried = imbalance if carried == 0.0 else 0.0
    assert carried == 0.0
    assert not ledger["stage_totals"].get("agg.rejected")
    applied = sum(ledger["stage_totals"]["agg.applied"].values())
    assert applied >= soaked["passes"] * LINES_PER_PASS


def test_soak_flushes_count_their_own_series(soaked):
    """The count guard read 0 in every flush: it is a self-metric, so
    the flushes themselves carry its value, one interval on."""
    values = [s["points"][0][1] for f in soaked["flushes"]
              for s in _series_of(f)
              if s["metric"] == "sink.datadog.encode.count_mismatch"]
    assert len(values) >= SOAK_FLUSHES and not any(values)


def test_soak_posted_bodies_are_the_python_loops_parts(soaked):
    """(b): every flush's bodies are `encode(batch)`'s parts cut every
    `flush_max_per_body` and joined: in order behind one POST worker,
    each once in whatever order behind several."""
    for flush in soaked["flushes"]:
        parts = flush["parts"]
        want = [b'{"series":[' + b",".join(parts[k:k + PER_BODY]) + b"]}"
                for k in range(0, len(parts), PER_BODY)]
        if soaked["worker_cap"] == 1:
            assert flush["posted"] == want
        else:
            assert sorted(flush["posted"]) == sorted(want)


def test_soak_ran_the_post_workers_its_cap_allows(soaked):
    """A flush of several bodies behind the native encoder keeps more
    than one worker busy wherever the cap allows a second."""
    started = [f["workers"] for f in soaked["flushes"]]
    assert max(started) <= soaked["worker_cap"]
    if soaked["worker_cap"] > 1:
        assert max(started) >= 2
    else:   # 0: a quiet interval's one body, sent by the sink thread
        assert set(started) <= {0, 1}


# -- (c) the prefix arena under churn ---------------------------------------


def _churn_lines(rng, flush_no: int, absent: float, minted: list) -> list:
    """One flush's lines: every key of every family absent with
    probability `absent`, a key of a low row that reports every third
    flush, and now and then a key never seen before (it takes a row that
    reclamation freed, or a new one)."""
    lines = []
    if flush_no % 3 == 0:
        lines += [b"churn.low:1|c|#env:t", b"churn.low.g:2|g",
                  b"churn.low.s:m%d|s" % flush_no]
    for i in range(40):
        if rng.random() >= absent:
            lines.append(b"k.c%d:%d|c|#env:t,i:%d" % (i, 1 + i % 5, i))
    for i in range(20):
        if rng.random() >= absent:
            lines.append(b"k.g%d:%d.5|g|#env:t" % (i, flush_no + i))
        if rng.random() >= absent:
            lines += [b"k.t%d:%d|ms|#env:t" % (i, 10 + i + flush_no % 7),
                      b"k.t%d:%d|ms|#env:t" % (i, 30 + i)]
    for i in range(12):
        if rng.random() >= absent:
            lines += [b"k.s%d:u%d|s" % (i, m) for m in range(1 + i % 3)]
    for i in range(6):
        if rng.random() >= absent:
            lines.append(b"k.l%d:%d|l|#svc:x" % (i, 1 + (i + flush_no) % 9))
    if rng.random() < 0.3:
        minted.append(len(minted))
        lines += [b"k.new%d:7|c|#env:t" % minted[-1],
                  b"k.newg%d:1.5|g" % minted[-1]]
    lines += [b"hosted:4|c|#host:other,device:sda,env:t",
              b"dropme.x:1|c|#env:t"]
    return lines


@pytest.mark.parametrize("per_body", [3, 7, 50, 1000])
def test_arena_under_churn_posts_the_python_loops_bodies(per_body):
    """Fifty flushes of one store a case (200 in all), keys of every
    family coming and going at random (3 % to 40 % absent), idle rows
    reclaimed and recycled to new keys: the native encoder's bodies are
    the Python loop's in every one, and the count guard reads 0."""
    from test_egress import (AGGS, PCTS, _bodies, _cut_bodies, _dd_encoder,
                             _dd_sink)
    from veneur_tpu.core.columnstore import ColumnStore
    from veneur_tpu.core.flusher import flush_columnstore_batch
    from veneur_tpu.samplers.parser import Parser

    sink = _dd_sink()
    enc = _dd_encoder(sink, "native")
    loop = _dd_encoder(sink, "python")
    store = ColumnStore(counter_capacity=128, gauge_capacity=64,
                        histo_capacity=64, set_capacity=32, batch_cap=256)
    tables = (store.counters, store.gauges, store.histos, store.sets,
              store.llhists)
    parser = Parser()
    rng = np.random.default_rng([33, per_body])
    minted: list = []
    rebuilt = 0
    for flush_no in range(50):
        absent = (0.03, 0.1, 0.4)[int(rng.integers(3))]
        for line in _churn_lines(rng, flush_no, absent, minted):
            parser.parse_metric_fast(line, store.process)
        store.apply_all_pending()
        batch, _fwd = flush_columnstore_batch(store, False, PCTS, AGGS)
        for table in tables:
            table.reclaim_idle(2)
        want = _bodies(loop.encode(batch)[0], per_body)
        assert _cut_bodies(enc, batch, per_body) == want, flush_no
        assert enc.series_written + enc.series_skipped == len(batch)
        assert enc.series_written == loop.series_written
        rebuilt += enc.prefix_renders > 0
    recycled = sum(t.recycled_total for t in tables)
    # the churn is real: most flushes rebuilt part of an arena, and
    # rows were recycled to other keys
    assert rebuilt >= 25 and recycled >= 3
