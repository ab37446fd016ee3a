"""The routed flush (`features.enable_metric_sink_routing` +
`metric_sink_routing`): the side of `Server._flush_sink_safe`'s gate that
materialises one `InterMetric` per series, runs every rule over each and
hands every sink the list it selected.

Held to two things that share no code with it: the plain reference of the
routing semantics (`util/matcher_ref.py`), and the columns of the
`FlushBatch` that the same traffic delivers with routing off, walked here
by hand. (a) seeded keys of all five families and a few service checks
through servers with three recording sinks; (b) the matcher against the
reference on seeded names, tags and rules; (c) the Datadog sink's legacy
bodies against the columnar encode of the same batch; (d) the spans and
counters that cover the routed side; (e) a failed sink's spill.
"""

import json
import random
import threading
import time
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

import pytest

from veneur_tpu.config import read_config
from veneur_tpu.core.egress import DatadogColumnarEncoder
from veneur_tpu.core.flusher import le_tags
from veneur_tpu.core.server import Server
from veneur_tpu.samplers.metrics import MetricType
from veneur_tpu.sinks import MetricSink
from veneur_tpu.sinks.datadog import DatadogMetricSink
from veneur_tpu.util import matcher_ref
from veneur_tpu.util.matcher import SinkRoutingMatcher

SEED = 2_147_483_777
SINKS = ("a", "b", "c")
FAMILIES = {"counter": 200, "gauge": 120, "timer": 160, "set": 80,
            "llhist": 40}
SERVICES = ("api", "db", "web")
OWN = "rf."     # the server's own series (`ssf.names_unique`) are not ours

# overlap (timers in zones 0-3 reach `a` twice over), a `not_matched`
# branch, a regex, an `unset`, and a rule that routes nowhere
OVERLAP = [
    {"name": "timers-and-llhists",
     "match": [{"name": {"kind": "prefix", "value": "rf.api.timer."}},
               {"name": {"kind": "regex", "value": r"^rf\.[a-z]+\.llhist\."}}],
     "sinks": {"matched": ["a"], "not_matched": ["b"]}},
    {"name": "zones-0-3",
     "match": [{"name": {"kind": "any"},
                "tags": [{"kind": "regex", "value": "^zone:z[0-3]$"}]}],
     "sinks": {"matched": ["a", "c"], "not_matched": []}},
    {"name": "not-canary",
     "match": [{"name": {"kind": "any"},
                "tags": [{"kind": "exact", "value": "env:canary",
                          "unset": True}]}],
     "sinks": {"matched": ["c"], "not_matched": []}},
    {"name": "nowhere",
     "match": [{"name": {"kind": "prefix", "value": "rf.db."}}],
     "sinks": {"matched": [], "not_matched": []}},
]
# most series reach no sink at all, and `c` none
SPARSE = [
    {"name": "counters-of-zone-1x",
     "match": [{"name": {"kind": "regex", "value": r"\.counter\."},
                "tags": [{"kind": "prefix", "value": "zone:z1"},
                         {"kind": "prefix", "value": "team:",
                          "unset": True}]}],
     "sinks": {"matched": ["a"], "not_matched": []}},
    {"name": "canary",
     "match": [{"tags": [{"kind": "exact", "value": "env:canary"}]},
               {"name": {"kind": "exact", "value": "rf.api.svc.0"}}],
     "sinks": {"matched": ["b"], "not_matched": []}},
]
RULES = {"overlap": OVERLAP, "sparse": SPARSE}


def _lines(seed: int = SEED, round_no: int = 0) -> list:
    """One interval's DogStatsD lines: every key of every family, its
    tags (none to three) drawn from the seed, and three service checks."""
    rng = random.Random(seed)
    lines = []
    for fam, n in FAMILIES.items():
        for i in range(n):
            name = f"rf.{rng.choice(SERVICES)}.{fam}.{i:04d}"
            tags = []
            if rng.random() < 0.9:
                tags.append("env:" + rng.choice(("prod", "canary", "dev")))
            if rng.random() < 0.9:
                tags.append(f"zone:z{rng.randrange(16)}")
            if rng.random() < 0.3:
                tags.append("team:" + rng.choice(("core", "edge")))
            sfx = "|#" + ",".join(tags) if tags else ""
            if fam == "counter":
                lines.append(f"{name}:{rng.randrange(1, 50)}|c{sfx}")
            elif fam == "gauge":
                lines.append(f"{name}:{rng.random() * 100 + round_no}|g{sfx}")
            elif fam == "timer":
                lines += [f"{name}:{rng.random() * 900 + 1}|ms{sfx}"
                          for _ in range(rng.randrange(1, 8))]
            elif fam == "set":
                lines += [f"{name}:m{rng.randrange(40)}|s{sfx}"
                          for _ in range(rng.randrange(1, 6))]
            else:
                lines += [f"{name}:{rng.random() * 10 ** rng.randrange(4)}"
                          f"|l{sfx}" for _ in range(rng.randrange(1, 9))]
    for i, status in enumerate((0, 1, 2)):
        lines.append(f"_sc|rf.api.svc.{i}|{status}|h:host{i}"
                     f"|#env:prod,zone:z{i}|m:status {status}")
    return [ln.encode() for ln in lines]


class _Recorder(MetricSink):
    """Keeps every list it is handed."""

    def __init__(self, name: str):
        self._name = name
        self.flushes: list = []

    def name(self) -> str:
        return self._name

    def kind(self) -> str:
        return "recorder"

    def flush(self, metrics) -> None:
        self.flushes.append(list(metrics))


class _Tap(_Recorder):
    """A columnar sink: keeps the `FlushBatch` itself."""

    def flush_batch(self, batch) -> None:
        self.flushes.append(batch)


def _config(rules=None, **overrides):
    raw = {"interval": "60s", "hostname": "test-host",
           "statsd_listen_addresses": [], "percentiles": [0.5, 0.99],
           "tpu": {"counter_capacity": 512, "gauge_capacity": 256,
                   "histo_capacity": 256, "set_capacity": 128,
                   "llhist_capacity": 64, "batch_cap": 1024}}
    if rules is not None:
        raw.update(features={"enable_metric_sink_routing": True},
                   metric_sink_routing=rules)
    raw.update(overrides)
    return read_config(overrides=raw, env={})


def _flush(server, lines, chaos=None) -> dict:
    """One interval of `lines` through `server`, flushed under `chaos`;
    its round."""
    for line in lines:
        server.handle_metric_packet(line)
    server.chaos = chaos
    server.flush()
    server.chaos = None
    return server.telemetry.flushes.snapshot(1)[-1]


def _exact(m) -> tuple:
    return (m.name, tuple(m.tags), m.type, m.value, m.message, m.hostname)


def _columns(batch) -> list:
    """Every series of a `FlushBatch`, read off its columns: the
    sections row by row, the llhists' CSR entry by entry and then
    `le:+Inf`, the extras as they are. No `materialize()`, no `rows()`."""
    out = []
    for sec in batch.sections:
        for i in range(sec.names.shape[0]):
            out.append((sec.names[i], tuple(sec.tags[i]), sec.mtype,
                        float(sec.values[i]), "", ""))
    les = le_tags()
    for bs in batch.bucket_sections:
        for i in range(bs.names.shape[0]):
            base = tuple(bs.tags[i])
            for k in range(int(bs.indptr[i]), int(bs.indptr[i + 1])):
                out.append((bs.names[i], base + (les[int(bs.le_idx[k])],),
                            MetricType.COUNTER, float(bs.cum[k]), "", ""))
            out.append((bs.names[i], base + ("le:+Inf",),
                        MetricType.COUNTER, float(bs.total[i]), "", ""))
    out.extend(_exact(m) for m in batch.extras)
    return out


def _ours(series) -> list:
    return sorted((s for s in series if s[0].startswith(OWN)),
                  key=lambda s: (s[0], s[1]))


@pytest.fixture(scope="module")
def unrouted():
    """What the traffic delivers with routing off: the columnar
    `flush_batch` of a tap sink, as rows."""
    tap = _Tap("tap")
    server = Server(_config(), extra_metric_sinks=[tap])
    try:
        rnd = _flush(server, _lines())
    finally:
        server.shutdown()
    [batch] = tap.flushes
    return {"series": _columns(batch), "round": rnd,
            "metrics": server.telemetry.registry.render_prometheus()}


@pytest.fixture(scope="module", params=sorted(RULES))
def routed(request):
    sinks = [_Recorder(name) for name in SINKS]
    server = Server(_config(RULES[request.param]), extra_metric_sinks=sinks)
    try:
        rnd = _flush(server, _lines())
    finally:
        server.shutdown()
    return {"rules": RULES[request.param], "round": rnd,
            "received": {s.name(): s.flushes for s in sinks},
            "metrics": server.telemetry.registry.render_prometheus()}


# -- (a) each sink's share, against the reference and the columns -----------

def test_the_unrouted_flush_holds_every_family(unrouted):
    names = {s[0] for s in unrouted["series"]}
    for fam, n in FAMILIES.items():
        assert sum(f".{fam}." in name for name in names) >= n, fam
    assert {s[2] for s in unrouted["series"]} == {
        MetricType.COUNTER, MetricType.GAUGE, MetricType.STATUS}
    assert sum(s[1][-1:] == ("le:+Inf",) for s in unrouted["series"]) == \
        FAMILIES["llhist"]
    assert [s[4] for s in _ours(unrouted["series"]) if s[4]] == [
        "status 0", "status 1", "status 2"]


@pytest.mark.parametrize("sink", SINKS)
def test_sink_receives_the_references_set_with_the_columns_values(
        unrouted, routed, sink):
    """Name, tags, type, value, message and hostname of every series
    the reference routes to this sink, and of no other."""
    want = [s for s in unrouted["series"]
            if sink in matcher_ref.route(routed["rules"], s[0], s[1])]
    [got] = routed["received"][sink]
    assert _ours(map(_exact, got)) == _ours(want)
    if routed["rules"] is SPARSE and sink == "c":
        assert got == []
    else:
        assert len(_ours(want)) > 50


def test_overlapping_rules_deliver_a_series_once(routed):
    for sink, (got,) in routed["received"].items():
        keys = [(m.name, tuple(m.tags)) for m in got]
        assert len(keys) == len(set(keys)), sink


def test_round_counts_what_was_routed(unrouted, routed):
    routing = routed["round"]["routing"]
    assert routing["rules"] == len(routed["rules"])
    assert routing["materialized"] == routed["round"]["metrics_flushed"]
    # a sink the rules name is counted, whether a series reached it
    assert routing["routed"] == {
        sink: len(got) for sink, (got,) in routed["received"].items()
        if got}
    ours = _ours(unrouted["series"])
    nowhere = sum(not matcher_ref.route(routed["rules"], s[0], s[1])
                  for s in ours)
    others = routing["materialized"] - len(ours)
    assert 0 <= others <= 2      # the server's own `ssf.names_unique`
    assert 0 <= routing["unrouted"] - nowhere <= others
    assert (nowhere > 1000) == (routed["rules"] is SPARSE)


# -- (b) the matcher against the reference ----------------------------------

def _pairs(rng, n: int) -> list:
    out = []
    for _ in range(n):
        name = ".".join(rng.choice(("rf", "api", "db", "web", "x1", "timer",
                                    "99percentile", "count", ""))
                        for _ in range(rng.randrange(1, 5)))
        tags = [rng.choice(("env", "zone", "team", "le", "h")) + ":"
                + rng.choice(("prod", "canary", "z1", "z12", "+Inf", "",
                              "a.b"))
                for _ in range(rng.randrange(0, 4))]
        if rng.random() < 0.1:
            tags.append("bare")
        out.append((name, tags))
    return out


def _rule(rng, pairs) -> dict:
    def value_of(text: str, kind: str) -> str:
        if kind == "prefix":
            return text[:rng.randrange(0, len(text) + 1)]
        if kind == "regex":
            cut = text[:rng.randrange(0, len(text) + 1)]
            return rng.choice(("^", "")) + "".join(
                "\\" + ch if ch in ".+" else ch for ch in cut) + rng.choice(
                    ("", "$", "[0-9]+", ".*canary"))
        return text

    matchers = []
    for _ in range(rng.randrange(0, 4)):
        matcher = {}
        if rng.random() < 0.8:
            kind = rng.choice(("any", "exact", "prefix", "regex"))
            matcher["name"] = {"kind": kind, "value": value_of(
                rng.choice(pairs)[0], kind)}
        tests = []
        for _ in range(rng.randrange(0, 3)):
            kind = rng.choice(("exact", "prefix", "regex"))
            tags = rng.choice(pairs)[1] or ["env:prod"]
            tests.append({"kind": kind, "value": value_of(
                rng.choice(tags), kind), "unset": rng.random() < 0.4})
        if tests or rng.random() < 0.5:
            matcher["tags"] = tests
        matchers.append(matcher)
    return {"name": "r", "match": matchers, "sinks": {
        "matched": rng.sample(SINKS, rng.randrange(0, 4)),
        "not_matched": rng.sample(SINKS, rng.randrange(0, 3))}}


@pytest.mark.parametrize("group", range(5))
def test_matcher_agrees_with_the_reference(group):
    """2,000 seeded (name, tags) under 50 seeded rules, ten a case:
    rule by rule the sinks, in order, and the union over the ten."""
    pairs = _pairs(random.Random(SEED), 2000)
    rng = random.Random(SEED + 1 + group)
    rules = [_rule(rng, pairs) for _ in range(10)]
    compiled = [SinkRoutingMatcher(rc) for rc in _config(
        rules).metric_sink_routing]
    outcomes = set()
    for name, tags in pairs:
        union = set()
        for rule, matcher in zip(rules, compiled):
            got = matcher.route(name, tags)
            assert got == matcher_ref.rule_sinks(rule, name, tags), (
                rule, name, tags)
            outcomes.add((id(rule), got is matcher.matched))
            union.update(got)
        assert union == matcher_ref.route(rules, name, tags)
    # the draw takes both branches of several of the ten rules
    assert len(outcomes) >= 14


def test_reference_reads_an_unknown_kind_as_an_error():
    with pytest.raises(ValueError):
        matcher_ref.route([{"match": [{"name": {"kind": "glob",
                                                "value": "a*"}}],
                            "sinks": {"matched": ["a"]}}], "abc", [])


# -- (c), (d): the Datadog sink on the routed side ---------------------------

TO_DATADOG = [
    {"name": "timers", "match": [{"name": {"kind": "regex",
                                           "value": r"\.timer\."}}],
     "sinks": {"matched": ["datadog"], "not_matched": []}},
    {"name": "the-rest", "match": [{"name": {"kind": "regex",
                                             "value": r"\.timer\."}}],
     "sinks": {"matched": [], "not_matched": ["datadog"]}},
]
PER_BODY = 700
NEW_PHASES = ("route_s", "materialize_s", "route_match_s", "egress_select_s")
SHARED_PHASES = ("egress_encode_s", "egress_join_s", "egress_post_wall_s",
                 "egress_gzip_s", "egress_http_s")
ROWS = ("veneur_flush_route_materialized_rows_total",
        "veneur_flush_route_routed_rows_total",
        "veneur_flush_route_unrouted_rows_total")
INTAKE_DELAY_S = 0.15   # the sends dwarf the three check_runs after them
SWITCH_S = 0.004   # what an identity may miss by on a loaded host


class _Intake(BaseHTTPRequestHandler):
    def do_POST(self):
        import gzip

        body = self.rfile.read(int(self.headers.get("Content-Length", 0)))
        self.server.bodies.append(json.loads(gzip.decompress(body)))
        if "/series" in self.path:    # a check_run runs under no span
            time.sleep(INTAKE_DELAY_S)
        self.send_response(202)
        self.send_header("Content-Length", "2")
        self.end_headers()
        self.wfile.write(b"{}")

    def log_message(self, *args):
        pass


def _prom(text: str) -> dict:
    rows: dict = {}
    for line in text.splitlines():
        if line and not line.startswith("#"):
            head, _, value = line.rpartition(" ")
            name = head.split("{", 1)[0]
            rows[name] = rows.get(name, 0.0) + float(value)
    return rows


@pytest.fixture(scope="module")
def datadog_routed():
    """Two routed flushes to a Datadog sink that posts to a loopback
    intake; the batch of the second, kept aside."""
    httpd = ThreadingHTTPServer(("127.0.0.1", 0), _Intake)
    httpd.daemon_threads = True
    httpd.bodies = []
    threading.Thread(target=httpd.serve_forever, daemon=True,
                     name="stub-intake").start()
    sink = DatadogMetricSink(
        "datadog", "key", f"http://127.0.0.1:{httpd.server_port}",
        "test-host", 10.0, flush_max_per_body=PER_BODY, num_workers=1)
    server = Server(_config(TO_DATADOG), extra_metric_sinks=[sink])
    batches = []
    flush_sink = server._flush_sink_safe

    def keep_batch(key, sink, batch, *rest):
        batches.append(batch)
        return flush_sink(key, sink, batch, *rest)

    server._flush_sink_safe = keep_batch
    try:
        _flush(server, _lines())
        del httpd.bodies[:]
        rnd = _flush(server, _lines(round_no=1))
    finally:
        server.shutdown()
        httpd.shutdown()
        httpd.server_close()
    return {"round": rnd, "batch": batches[1], "sink": sink,
            "bodies": list(httpd.bodies),
            "metrics": _prom(server.telemetry.registry.render_prometheus())}


def test_legacy_bodies_hold_the_columnar_encodes_series(datadog_routed):
    """tests/test_egress.py's normalisation: both sides decoded, the
    same objects in the same order, cut every `flush_max_per_body`."""
    batch, sink = datadog_routed["batch"], datadog_routed["sink"]
    parts, checks = DatadogColumnarEncoder(sink).encode(batch)
    columnar = [json.loads(p) for p in parts]
    legacy = [body["series"] for body in datadog_routed["bodies"]
              if "series" in body]
    assert [len(b) for b in legacy] == [
        min(PER_BODY, len(columnar) - i)
        for i in range(0, len(columnar), PER_BODY)]
    assert [s for body in legacy for s in body] == columnar
    assert len(columnar) + len(checks) == len(batch) > 2 * PER_BODY
    assert sorted(b["check"] for b in datadog_routed["bodies"]
                  if "check" in b) == sorted(c.name for c in checks)


@pytest.mark.parametrize("key", NEW_PHASES + SHARED_PHASES)
def test_routed_round_has_phase(datadog_routed, key):
    assert datadog_routed["round"]["phases"][key] > 0.0


def test_route_is_materialize_and_the_rule_loop(datadog_routed):
    p = datadog_routed["round"]["phases"]
    parts = p["materialize_s"] + p["route_match_s"]
    assert parts <= p["route_s"]
    assert p["route_s"] - parts <= max(0.05 * p["route_s"], SWITCH_S), p
    spans = {s["name"]: s for s in datadog_routed["round"]["spans"]}
    assert spans["route"]["parent"] == "flush"
    assert spans["materialize"]["parent"] == "route"
    assert spans["route_match"]["parent"] == "route"
    # before any sink thread was started
    assert (spans["route"]["start_s"] + spans["route"]["wall_s"]
            <= spans["egress_select"]["start_s"])


def test_select_encode_join_and_post_wall_explain_the_sink(datadog_routed):
    rnd = datadog_routed["round"]
    p = rnd["phases"]
    [sink] = [s for s in rnd["spans"] if s["name"] == "sink"
              and s["sink"] == "metric:datadog"]
    for s in rnd["spans"]:
        if s["name"].startswith("egress_") and s["name"] != "egress_start":
            assert s["thread"] == sink["thread"], s   # num_workers 1
    parts = (p["egress_select_s"] + p["egress_encode_s"] + p["egress_join_s"]
             + p["egress_post_wall_s"])
    assert parts <= sink["wall_s"]
    assert sink["wall_s"] - parts <= max(0.05 * sink["wall_s"], SWITCH_S), p
    bodies = len(datadog_routed["bodies"]) - 3    # the three check_runs
    assert p["egress_http_s"] >= bodies * INTAKE_DELAY_S
    assert p["egress_gzip_s"] + p["egress_http_s"] <= p["egress_post_wall_s"]
    assert sum(s["name"] == "egress_join" for s in rnd["spans"]) == bodies
    assert "egress_post_tail_s" not in p       # nothing is pipelined here


def test_round_names_the_legacy_encoder_and_its_bodies(datadog_routed):
    rnd = datadog_routed["round"]
    sent = rnd["sinks"]["metric:datadog"]
    legacy = [b for b in datadog_routed["bodies"] if "series" in b]
    assert sent["encoder"] == "legacy" and sent["status"] == "ok"
    assert sent["bodies"] == len(legacy) >= 3
    assert sent["bodies_overlapped"] == sent["native_rows"] == 0
    assert 0 < sent["gzip_bytes"] < sent["bytes"]
    assert rnd["routing"] == {
        "rules": 2, "materialized": rnd["metrics_flushed"],
        "routed": {"datadog": rnd["metrics_flushed"]}, "unrouted": 0}


@pytest.mark.parametrize("row", ROWS)
def test_metrics_count_the_routed_rows(datadog_routed, row):
    rows = datadog_routed["metrics"]
    flushed = rows["veneur_flush_metrics_total"]
    assert rows[row] == (0 if "unrouted" in row else flushed)
    assert flushed >= 2 * datadog_routed["round"]["metrics_flushed"] - 1


@pytest.mark.parametrize("key", NEW_PHASES + ("routing",) + ROWS)
def test_nothing_of_it_appears_with_routing_off(unrouted, key):
    rnd = unrouted["round"]
    assert key not in rnd["phases"] and key not in rnd
    assert key.rsplit("_s", 1)[0] not in {s["name"] for s in rnd["spans"]}
    assert key not in _prom(unrouted["metrics"])


# -- (e) a failed sink's spill ----------------------------------------------

class _FailsOnce(_Recorder):
    def flush(self, metrics) -> None:
        super().flush(metrics)
        if len(self.flushes) == 1:
            raise RuntimeError("boom")


class _SeamFailsOnce:
    """`Server.chaos`: the `sink_flush` seam, before a sink's share was
    selected, fails the first flush of sink `a`."""

    def __init__(self):
        self.failed = False

    def inject(self, seam: str) -> None:
        if (seam == "sink_flush" and not self.failed
                and threading.current_thread().name == "flush-metric:a"):
            self.failed = True
            raise RuntimeError("boom")


@pytest.mark.parametrize("how", ["flush_raises", "fails_before_selection"])
def test_failed_sink_spills_its_routed_share_and_gets_it_once_more(how):
    a = _FailsOnce("a") if how == "flush_raises" else _Recorder("a")
    b, c = _Recorder("b"), _Recorder("c")
    server = Server(_config(OVERLAP), extra_metric_sinks=[a, b, c])
    seam = _SeamFailsOnce() if how == "fails_before_selection" else None
    try:
        _flush(server, _lines(), chaos=seam)
        spilled = server._sink_spill["metric:a"]
        assert list(server._sink_spill) == ["metric:a"]
        second = _flush(server, _lines(SEED + 9, round_no=1))
    finally:
        server.shutdown()
    # what spilled is a's share of the first interval and nothing else
    assert spilled and all("a" in m.sinks for m in spilled)
    if how == "flush_raises":
        assert a.flushes[0] == spilled
    # the second flush: the spill first, once, then the interval's share
    retried = a.flushes[-1]
    assert retried[:len(spilled)] == spilled
    fresh = retried[len(spilled):]
    assert fresh and all("a" in m.sinks for m in fresh)
    assert not {id(m) for m in fresh} & {id(m) for m in spilled}
    assert not server._sink_spill
    # the other sinks saw each interval once and nothing of the spill
    for sink in (b, c):
        assert len(sink.flushes) == 2
        assert not {id(m) for m in sink.flushes[1]} & {
            id(m) for m in spilled}
    assert second["sinks"]["metric:a"]["status"] == "ok"
    assert second["routing"]["routed"]["a"] == len(fresh)
