"""The routed flush (`features.enable_metric_sink_routing` +
`metric_sink_routing`): routes are row masks over the `FlushBatch`
(`core/routing.py`), kept between flushes, and a sink's share is a
`FlushBatch.select` of the batch that takes the columnar egress; objects
are built only for a filtered sink, a spill, or a sink with no
`flush_batch` of its own.

Held to two things that share no code with it: the plain reference of the
routing semantics (`util/matcher_ref.py`), and the columns of the
`FlushBatch` that the same traffic delivers with routing off, walked here
by hand. (a) seeded keys of all five families and a few service checks
through servers with three recording sinks, and again with three columnar
sinks, under rules that overlap, that leave most series out, and that
take an `le:` tag (bucket lines routed one by one); (b) the matcher
against the reference on seeded names, tags and rules; (c) the Datadog
sink's legacy bodies (a filtered sink's) against the columnar encode of
the same share; (d) the spans and counters of the mask route; (e) a failed
sink's spill; (f) `FlushBatch.select`; (g) kept routes.
"""

import json
import random
import threading
import time
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

import pytest

import numpy as np

from veneur_tpu.config import SinkConfig, read_config
from veneur_tpu.core.egress import DatadogColumnarEncoder
from veneur_tpu.core.flusher import (
    BucketSection, FlushBatch, FlushSection, le_tags)
from veneur_tpu.core.routing import ColumnRouter
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
# a tag test that takes `le:` tags: a bucket row's lines go different
# ways (its `le:+Inf` never to `a`), so they are routed one by one
LE = [
    {"name": "low-bounds",
     "match": [{"tags": [{"kind": "regex", "value": "^le:[1-4]"}]}],
     "sinks": {"matched": ["a"], "not_matched": ["b"]}},
    {"name": "zone-1x",
     "match": [{"tags": [{"kind": "prefix", "value": "zone:z1"}]}],
     "sinks": {"matched": ["c"], "not_matched": []}},
]
RULES = {"overlap": OVERLAP, "sparse": SPARSE, "le": LE}


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


def _series(flush) -> list:
    """What a sink was handed, as rows: a `FlushBatch` off its columns,
    a list object by object."""
    if isinstance(flush, FlushBatch):
        return _columns(flush)
    return [_exact(m) for m in flush]


@pytest.fixture(scope="module", ids="-".join, params=[
    (rules, kind) for rules in sorted(RULES)
    for kind in ("objects", "columns")])
def routed(request):
    """The traffic under a rule list, to three sinks that take lists
    (`MetricSink.flush_batch` materialises their share) or three that
    take the `FlushBatch` share itself."""
    rules, kind = request.param
    sinks = [(_Recorder if kind == "objects" else _Tap)(name)
             for name in SINKS]
    server = Server(_config(RULES[rules]), extra_metric_sinks=sinks)
    try:
        rnd = _flush(server, _lines())
    finally:
        server.shutdown()
    return {"rules": RULES[rules], "kind": kind, "round": rnd,
            "router": server._routing,
            "received": {s.name(): [_series(f) for f in s.flushes]
                         for s in sinks},
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
    the reference routes to this sink, and of no other: partial masks
    over every family, the llhists' CSR re-sliced by row, or cut line by
    line under the rule that takes `le:` tags."""
    want = [s for s in unrouted["series"]
            if sink in matcher_ref.route(routed["rules"], s[0], s[1])]
    [got] = routed["received"][sink]
    assert _ours(got) == _ours(want)
    if routed["rules"] is SPARSE and sink == "c":
        assert got == []
    else:
        assert 50 < len(_ours(want)) < len(_ours(unrouted["series"]))


def test_overlapping_rules_deliver_a_series_once(routed):
    for sink, (got,) in routed["received"].items():
        keys = [(s[0], s[1]) for s in got]
        assert len(keys) == len(set(keys)), sink


def test_round_counts_what_was_routed(unrouted, routed):
    routing = routed["round"]["routing"]
    assert routing["rules"] == len(routed["rules"])
    # a sink the rules name is counted, whether a series reached it
    assert routing["routed"] == {
        sink: len(got) for sink, (got,) in routed["received"].items()
        if got}
    # objects are built for sinks that take lists, and for no other
    assert routing["materialized"] == (
        sum(routing["routed"].values()) if routed["kind"] == "objects"
        else 0)
    ours = _ours(unrouted["series"])
    nowhere = sum(not matcher_ref.route(routed["rules"], s[0], s[1])
                  for s in ours)
    others = routed["round"]["metrics_flushed"] - len(ours)
    assert 0 <= others <= 2      # the server's own `ssf.names_unique`
    assert 0 <= routing["unrouted"] - nowhere <= others
    assert (nowhere > 1000) == (routed["rules"] is SPARSE)
    # a first flush: every rule ran over every row (a bucket row once,
    # or once a line where its lines go different ways)
    assert routing["cached"] == 0
    assert routed["router"].le_sensitive == (routed["rules"] is LE)
    lines = sum(s[1][-1:] != () and s[1][-1].startswith("le:")
                for s in unrouted["series"])
    assert routing["evaluated"] == routed["round"]["metrics_flushed"] - (
        0 if routed["rules"] is LE else lines - FAMILIES["llhist"])


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
# the timers to Datadog, everything else to `b`
SPLIT = [
    {"name": "timers", "match": [{"name": {"kind": "regex",
                                           "value": r"\.timer\."}}],
     "sinks": {"matched": ["datadog"], "not_matched": ["b"]}},
]
PER_BODY = 700
NEW_PHASES = ("route_s", "route_match_s", "egress_select_s")
SHARED_PHASES = ("egress_encode_s", "egress_join_s", "egress_post_wall_s",
                 "egress_gzip_s", "egress_http_s")
ROWS = ("veneur_flush_route_materialized_rows_total",
        "veneur_flush_route_routed_rows_total",
        "veneur_flush_route_unrouted_rows_total",
        "veneur_flush_route_evaluated_rows_total",
        "veneur_flush_route_cached_rows_total")
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


def _datadog_rounds(rules, filtered: bool = False, others=()) -> dict:
    """Two routed flushes of the same keys to a Datadog sink that posts
    to a loopback intake (with `filtered`, behind a sink filter that
    drops nothing), and to `others`; the second flush's batch kept
    aside, and what the intake got of it."""
    httpd = ThreadingHTTPServer(("127.0.0.1", 0), _Intake)
    httpd.daemon_threads = True
    httpd.bodies = []
    threading.Thread(target=httpd.serve_forever, daemon=True,
                     name="stub-intake").start()
    sink = DatadogMetricSink(
        "datadog", "key", f"http://127.0.0.1:{httpd.server_port}",
        "test-host", 10.0, flush_max_per_body=PER_BODY, num_workers=1)
    server = Server(_config(rules), extra_metric_sinks=[sink, *others])
    if filtered:
        server._sink_filters["datadog"] = SinkConfig(
            kind="datadog", name="datadog", max_name_length=10_000)
    batches = []
    flush_sink = server._flush_sink_safe

    def keep_batch(key, sink, batch, *rest):
        batches.append(batch)
        return flush_sink(key, sink, batch, *rest)

    server._flush_sink_safe = keep_batch
    try:
        first = _flush(server, _lines())
        del httpd.bodies[:]
        rnd = _flush(server, _lines(round_no=1))
    finally:
        server.shutdown()
        httpd.shutdown()
        httpd.server_close()
    return {"round": rnd, "first": first, "batch": batches[-1],
            "sink": sink, "bodies": list(httpd.bodies),
            "metrics": _prom(server.telemetry.registry.render_prometheus())}


@pytest.fixture(scope="module")
def datadog_routed():
    return _datadog_rounds(TO_DATADOG)


@pytest.fixture(scope="module")
def datadog_filtered():
    return _datadog_rounds(TO_DATADOG, filtered=True)


def test_legacy_bodies_hold_the_columnar_encodes_series(datadog_filtered):
    """A filtered sink still gets objects, through the legacy flush:
    tests/test_egress.py's normalisation, both sides decoded, the same
    objects in the same order, cut every `flush_max_per_body`."""
    batch, sink = datadog_filtered["batch"], datadog_filtered["sink"]
    parts, checks = DatadogColumnarEncoder(sink).encode(batch)
    columnar = [json.loads(p) for p in parts]
    legacy = [body["series"] for body in datadog_filtered["bodies"]
              if "series" in body]
    assert [len(b) for b in legacy] == [
        min(PER_BODY, len(columnar) - i)
        for i in range(0, len(columnar), PER_BODY)]
    assert [s for body in legacy for s in body] == columnar
    assert len(columnar) + len(checks) == len(batch) > 2 * PER_BODY
    assert sorted(b["check"] for b in datadog_filtered["bodies"]
                  if "check" in b) == sorted(c.name for c in checks)
    rnd = datadog_filtered["round"]
    sent = rnd["sinks"]["metric:datadog"]
    assert sent["encoder"] == "legacy" and sent["status"] == "ok"
    assert sent["bodies"] == len(legacy)
    assert sent["bodies_overlapped"] == sent["native_rows"] == 0
    assert rnd["routing"]["materialized"] == len(batch)
    assert "egress_post_tail_s" not in rnd["phases"]   # nothing pipelined
    # objects are built on the sink's thread, inside its selection
    spans = {s["name"]: s for s in rnd["spans"]}
    assert spans["materialize"]["parent"] == "egress_select"
    assert spans["materialize"]["thread"] == spans["egress_select"]["thread"]
    assert 0.0 < rnd["phases"]["materialize_s"] <= \
        rnd["phases"]["egress_select_s"]


def test_routed_and_filtered_sinks_post_the_same_series(
        datadog_routed, datadog_filtered):
    def posted(run) -> list:
        return sorted(
            (s["metric"], tuple(s["tags"]), s["type"], s["points"][0][1])
            for body in run["bodies"] for s in body.get("series", ())
            if s["metric"].startswith(OWN))

    assert posted(datadog_routed) == posted(datadog_filtered)
    assert len(posted(datadog_routed)) > 2 * PER_BODY


@pytest.mark.parametrize(
    "key", NEW_PHASES + SHARED_PHASES + ("egress_post_tail_s",))
def test_routed_round_has_phase(datadog_routed, key):
    assert datadog_routed["round"]["phases"][key] > 0.0


def test_route_holds_the_rule_loop_and_builds_no_object(datadog_routed):
    for rnd in (datadog_routed["first"], datadog_routed["round"]):
        p = rnd["phases"]
        assert 0.0 < p["route_match_s"] <= p["route_s"]
        assert "materialize_s" not in p
        spans = {s["name"]: s for s in rnd["spans"]}
        assert "materialize" not in spans
        assert spans["route"]["parent"] == "flush"
        assert spans["route_match"]["parent"] == "route"
        assert spans["egress_select"]["parent"] == "sink"
        # before any sink thread was started
        assert (spans["route"]["start_s"] + spans["route"]["wall_s"]
                <= spans["egress_select"]["start_s"])
        assert rnd["routing"]["materialized"] == 0
    # the first flush ran the rules over every row, the second over the
    # three service checks (new objects every flush)
    first, second = (datadog_routed[k]["routing"]
                     for k in ("first", "round"))
    assert first["cached"] == 0 and first["evaluated"] > 1000
    assert 3 <= second["evaluated"] <= 4    # + `ssf.names_unique`
    assert second["cached"] >= first["evaluated"] - 3


def test_select_encode_and_post_tail_explain_the_sink(datadog_routed):
    rnd = datadog_routed["round"]
    p = rnd["phases"]
    [sink] = [s for s in rnd["spans"] if s["name"] == "sink"
              and s["sink"] == "metric:datadog"]
    parts = (p["egress_select_s"] + p["egress_encode_s"]
             + p["egress_post_tail_s"])
    assert parts <= sink["wall_s"]
    assert sink["wall_s"] - parts <= max(0.05 * sink["wall_s"], SWITCH_S), p
    bodies = len(datadog_routed["bodies"]) - 3    # the three check_runs
    assert p["egress_http_s"] >= bodies * INTAKE_DELAY_S
    assert sum(s["name"] == "egress_join" for s in rnd["spans"]) == bodies


def test_round_names_the_columnar_encoder_and_its_bodies(datadog_routed):
    rnd, batch = datadog_routed["round"], datadog_routed["batch"]
    sent = rnd["sinks"]["metric:datadog"]
    posted = [b for b in datadog_routed["bodies"] if "series" in b]
    assert sent["encoder"] in ("native", "python")
    assert sent["status"] == "ok" and sent["count_mismatch"] == 0
    assert sent["bodies"] == len(posted) >= 3
    assert sent["bodies_overlapped"] >= 1
    assert 0 < sent["gzip_bytes"] < sent["bytes"]
    rows = sum(sec.names.shape[0] for sec in batch.sections)
    if sent["encoder"] == "native":
        # every series reaches the sink, so its share is the batch's
        # own sections: the encoder finds last flush's arenas
        assert sent["native_rows"] == rows
        assert sent["prefix_renders"] <= 1
    routing = dict(rnd["routing"])
    assert routing.pop("evaluated") + routing.pop("cached") == len(
        batch.extras) + rows + sum(
            bs.names.shape[0] for bs in batch.bucket_sections)
    assert routing == {
        "rules": 2, "materialized": 0,
        "routed": {"datadog": rnd["metrics_flushed"]}, "unrouted": 0}


@pytest.mark.parametrize("row", ROWS)
def test_metrics_count_the_routed_rows(datadog_routed, row):
    rows = datadog_routed["metrics"]
    flushed = rows["veneur_flush_metrics_total"]
    assert flushed >= 2 * datadog_routed["round"]["metrics_flushed"] - 1
    key = row[len("veneur_flush_route_"):-len("_rows_total")]
    if key == "routed":
        assert rows[row] == flushed
    elif key in ("materialized", "unrouted"):
        assert rows[row] == 0
    else:
        assert rows[row] == sum(datadog_routed[k]["routing"][key]
                                for k in ("first", "round")) > 0


@pytest.mark.parametrize("metric", [
    "flush.route_ms", "flush.egress_select_ms", "flush.routed_rows",
    "flush.unrouted_rows", "flush.route_evaluated_rows",
    "flush.materialize_ms"])
def test_routed_layer_metric_reads_what_the_round_produces(
        datadog_routed, metric):
    """The benchmark's data files of the `sink routing` layer, against
    a routed round; `flush.materialize_ms` reads nothing in a round
    whose sinks took columns (its span exists only where objects are
    built: `datadog_filtered`'s rounds have it)."""
    import os

    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmark", "layer_metrics",
        metric + ".json")
    with open(path) as f:
        spec = json.load(f)
    assert spec["layer"] == "sink routing"
    reader = spec["reader"]
    phases = datadog_routed["round"]["phases"]
    if metric == "flush.materialize_ms":
        assert not set(reader["keys"]) & set(phases)
    elif reader["kind"] == "flush_phase":
        assert set(reader["keys"]) <= set(phases)
    else:
        assert reader["row"] in datadog_routed["metrics"]


@pytest.mark.parametrize(
    "key", NEW_PHASES + ("materialize_s", "routing") + ROWS)
def test_nothing_of_it_appears_with_routing_off(unrouted, key):
    rnd = unrouted["round"]
    assert key not in rnd["phases"] and key not in rnd
    assert key.rsplit("_s", 1)[0] not in {s["name"] for s in rnd["spans"]}
    assert key not in _prom(unrouted["metrics"])


def test_datadog_sink_under_rules_that_split_posts_exactly_its_share(
        unrouted):
    b = _Recorder("b")
    run = _datadog_rounds(SPLIT, others=[b])
    posted = sorted(
        (s["metric"], tuple(s["tags"])) for body in run["bodies"]
        for s in body.get("series", ()) if s["metric"].startswith(OWN))
    want = {sink: sorted(
        (s[0], s[1]) for s in _ours(unrouted["series"])
        if s[2] != MetricType.STATUS
        and sink in matcher_ref.route(SPLIT, s[0], s[1]))
        for sink in ("datadog", "b")}
    assert posted == want["datadog"]
    assert sorted((m.name, tuple(m.tags)) for m in b.flushes[-1]
                  if m.name.startswith(OWN)
                  and m.type != MetricType.STATUS) == want["b"]
    assert len(posted) > PER_BODY and len(want["b"]) > PER_BODY
    assert all(".timer." in name for name, _ in posted)
    rnd = run["round"]
    assert rnd["sinks"]["metric:datadog"]["encoder"] in ("native", "python")
    assert rnd["sinks"]["metric:datadog"]["count_mismatch"] == 0
    # objects only for the sink that takes a list
    assert rnd["routing"]["materialized"] == len(b.flushes[-1])
    assert rnd["routing"]["routed"] == {
        "datadog": len(posted), "b": len(b.flushes[-1])}


# -- (e) a failed sink's spill ----------------------------------------------

class _FailsOnce(_Recorder):
    def flush(self, metrics) -> None:
        super().flush(metrics)
        if len(self.flushes) == 1:
            raise RuntimeError("boom")


class _TapFailsOnce(_Tap):
    def flush_batch(self, batch) -> None:
        super().flush_batch(batch)
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


@pytest.mark.parametrize("how", ["flush_raises", "flush_batch_raises",
                                 "fails_before_selection"])
def test_failed_sink_spills_its_routed_share_and_gets_it_once_more(how):
    a = {"flush_raises": _FailsOnce, "flush_batch_raises": _TapFailsOnce,
         "fails_before_selection": _Recorder}[how]("a")
    b, c = _Recorder("b"), _Recorder("c")
    server = Server(_config(OVERLAP), extra_metric_sinks=[a, b, c])
    seam = _SeamFailsOnce() if how == "fails_before_selection" else None
    try:
        first = _flush(server, _lines(), chaos=seam)
        spilled = server._sink_spill["metric:a"]
        assert list(server._sink_spill) == ["metric:a"]
        second = _flush(server, _lines(SEED + 9, round_no=1))
    finally:
        server.shutdown()

    def routed_to_a(metrics) -> bool:
        return all("a" in matcher_ref.route(OVERLAP, m.name, m.tags)
                   for m in metrics)

    # what spilled is a's share of the first interval and nothing else:
    # objects, also where the sink had been handed columns
    assert len(spilled) == first["routing"]["routed"]["a"]
    assert routed_to_a(spilled)
    if how != "fails_before_selection":
        assert _series(a.flushes[0]) == [_exact(m) for m in spilled]
    # the second flush: the spill first, once, then the interval's
    # share, as a list (a spill is prepended to objects)
    retried = a.flushes[-1]
    assert retried[:len(spilled)] == spilled
    fresh = retried[len(spilled):]
    assert fresh and routed_to_a(fresh)
    assert not {id(m) for m in fresh} & {id(m) for m in spilled}
    assert not server._sink_spill
    # the other sinks saw each interval once and nothing of the spill
    for sink in (b, c):
        assert len(sink.flushes) == 2
        assert not {id(m) for m in sink.flushes[1]} & {
            id(m) for m in spilled}
    assert second["sinks"]["metric:a"]["status"] == "ok"
    assert second["routing"]["routed"]["a"] == len(fresh)


# -- (f) FlushBatch.select ---------------------------------------------------

def _obj(items) -> np.ndarray:
    out = np.empty(len(items), object)
    for i, item in enumerate(items):
        out[i] = item
    return out


def _handmade() -> FlushBatch:
    """Two sections of five rows, three llhists with 2, 0 and 3 nonzero
    bins, and two status checks."""
    tags = _obj([["env:prod"], [], ["zone:z1", "env:dev"], ["zone:z2"],
                 ["team:core"]])
    gauges = FlushSection(_obj([f"rf.g.{i}" for i in range(5)]),
                          np.arange(5, dtype=np.float64), tags,
                          MetricType.GAUGE)
    counters = FlushSection(_obj([f"rf.c.{i}" for i in range(5)]),
                            np.arange(5, dtype=np.float64) * 2, tags,
                            MetricType.COUNTER)
    buckets = BucketSection(
        _obj([f"rf.l.{i}.bucket" for i in range(3)]), tags[:3],
        np.array([0, 2, 2, 5]), np.array([2300, 2400, 2250, 2300, 2500]),
        np.array([1.0, 4.0, 2.0, 3.0, 9.0]), np.array([4.0, 0.0, 9.0]))
    from veneur_tpu.samplers.metrics import InterMetric
    checks = [InterMetric(name=f"rf.svc.{i}", timestamp=7, value=float(i),
                          tags=["env:prod"], type=MetricType.STATUS,
                          message=f"status {i}") for i in range(2)]
    return FlushBatch(7, [gauges, counters], checks, [buckets])


def _mask(*bits) -> np.ndarray:
    return np.array(bits, bool)


def test_a_share_that_takes_everything_is_the_batch_itself():
    batch = _handmade()
    share = batch.select([_mask(1, 1, 1, 1, 1)] * 2, [_mask(1, 1, 1)],
                         _mask(1, 1))
    assert share is batch
    by_line = batch.select([_mask(1, 1, 1, 1, 1)] * 2, [np.ones(8, bool)],
                           _mask(1, 1), bucket_lines=True)
    assert by_line is batch


def test_select_by_rows_keeps_whole_sections_and_reslices_the_csr():
    batch = _handmade()
    share = batch.select(
        [_mask(1, 1, 1, 1, 1), _mask(0, 1, 0, 0, 1)], [_mask(1, 0, 1)],
        _mask(0, 1))
    # picked whole: the same object; picked in part: a section of its
    # rows; the CSR: rows 0 and 2 with their entries
    assert share.sections[0] is batch.sections[0]
    assert share.sections[1].names.tolist() == ["rf.c.1", "rf.c.4"]
    [bs] = share.bucket_sections
    assert bs.indptr.tolist() == [0, 2, 5] and bs.total.tolist() == [4, 9]
    assert share.timestamp == 7 and share.timing is batch.timing
    whole = _columns(batch)
    want = whole[:5] + [whole[6], whole[9]] + whole[10:13] \
        + whole[14:18] + whole[19:]
    assert _columns(share) == want
    assert len(share) == len(want) == 15
    assert [_exact(m) for m in share.materialize()] == want
    assert share.materialized_rows == 15 and batch.materialized_rows == 0
    # picked empty: left out
    none = batch.select([_mask(0, 0, 0, 0, 0)] * 2, [_mask(0, 0, 0)],
                        _mask(0, 0))
    assert len(none) == 0 and none.materialize() == []
    assert none.sections == none.bucket_sections == none.extras == []


def test_select_by_lines_cuts_a_bucket_rows_lines_apart():
    batch = _handmade()
    whole = _columns(batch)
    lines = whole[10:18]     # 2 + Inf, Inf, 3 + Inf
    assert [s[1][-1] for s in lines].count("le:+Inf") == 3
    # row 0 without its le:+Inf, row 1 (only le:+Inf), one entry of row 2
    pick = _mask(1, 1, 0, 1, 0, 1, 0, 0)
    share = batch.select([_mask(0, 0, 0, 0, 0)] * 2, [pick], _mask(0, 0),
                         bucket_lines=True)
    assert share.bucket_sections == []
    [sec] = share.sections
    assert sec.mtype == MetricType.COUNTER
    want = [s for s, keep in zip(lines, pick) if keep]
    assert _columns(share) == want
    assert [_exact(m) for m in share.materialize()] == want
    assert len(share) == 4
    # the base tag lists were copied, not grown
    assert batch.bucket_sections[0].tags[0] == ["env:prod"]


# -- (g) kept routes ----------------------------------------------------------

def _router(rules) -> ColumnRouter:
    return ColumnRouter([SinkRoutingMatcher(rc) for rc in _config(
        rules).metric_sink_routing])


def _keyed(n: int):
    rng = random.Random(SEED + 3)
    names = [f"rf.{rng.choice(SERVICES)}.timer.{i:04d}.max"
             for i in range(n)]
    tags = [[f"zone:z{rng.randrange(16)}"]
            + (["env:canary"] if rng.random() < 0.3 else [])
            for _ in range(n)]
    return names, tags


def _gauges(names, tags) -> FlushBatch:
    return FlushBatch(7, [FlushSection(
        _obj(names), np.zeros(len(names)), _obj(tags), MetricType.GAUGE)],
        [], [])


@pytest.mark.parametrize("change", [
    "none", "equal_copies", "new_row", "tags_changed", "name_changed",
    "shrank", "first_row_gone"])
def test_router_evaluates_only_rows_that_differ_from_the_kept(change):
    """The second flush of a section runs the rules over the rows whose
    (name, tags) changed or are new, and every sink's share is the
    reference's whatever was kept."""
    router = _router(OVERLAP)
    names, tags = _keyed(60)
    whole = _gauges(names, tags)
    first = router.route(whole)
    assert (first.evaluated, first.cached) == (60, 0)
    names, tags = list(names), list(tags)
    evaluated = 0
    if change == "equal_copies":
        # other objects of the same value, past the first row (which the
        # kept section is found by): a route depends on values only
        names[1:] = [str(bytes(n, "ascii"), "ascii") for n in names[1:]]
        tags[1:] = [list(t) for t in tags[1:]]
    elif change == "new_row":
        names.append("rf.api.timer.new.max")
        tags.append(["zone:z2"])
        evaluated = 1
    elif change == "tags_changed":
        row = next(i for i, t in enumerate(tags) if i and t == ["zone:z1"])
        tags[row] = ["zone:z9", "env:canary"]
        evaluated = 1
    elif change == "name_changed":
        names[7] = "rf.db.gauge.0007"
        evaluated = 1
    elif change == "shrank":
        del names[40:], tags[40:]
    elif change == "first_row_gone":
        del names[0], tags[0]
        evaluated = 59      # found by its first row: all of it anew
    batch = _gauges(names, tags)
    second = router.route(batch)
    assert second.evaluated == evaluated
    assert second.cached == len(names) - evaluated
    for sink in SINKS:
        got = [(s[0], s[1]) for s in _columns(second.share(sink))]
        assert got == [(n, tuple(t)) for n, t in zip(names, tags)
                       if sink in matcher_ref.route(OVERLAP, n, t)], sink
    routed, unrouted = second.counts()
    assert unrouted == sum(not matcher_ref.route(OVERLAP, n, t)
                           for n, t in zip(names, tags))
    if change == "shrank":
        # the longer kept section stayed: the keys at its end report again
        assert router.route(whole).evaluated == 0


def test_kept_routes_age_out():
    from veneur_tpu.core.routing import KEPT_IDLE_FLUSHES

    router = _router(OVERLAP)
    names, tags = _keyed(10)
    batch, other = _gauges(names, tags), _gauges(*_keyed(5))
    assert router.route(batch).evaluated == 10
    for _ in range(KEPT_IDLE_FLUSHES):
        router.route(other)
    assert router.route(batch).evaluated == 0     # still kept
    for _ in range(KEPT_IDLE_FLUSHES + 1):
        router.route(other)
    assert router.route(batch).evaluated == 10    # gone, routed anew


def test_started_flushes_evaluate_only_the_keys_that_are_new(unrouted):
    """Through a server: the column store hands a live key the same
    name and tags objects every flush, so from the second flush on the
    rules run over the service checks and over a key that is new."""
    taps = [_Tap(name) for name in SINKS]
    server = Server(_config(OVERLAP), extra_metric_sinks=taps)
    try:
        first = _flush(server, _lines())["routing"]
        second = _flush(server, _lines(round_no=1))["routing"]
        third = _flush(server, _lines(round_no=2) + [
            b"rf.api.counter.new:1|c|#zone:z2"])["routing"]
    finally:
        server.shutdown()
    rows = first["evaluated"]
    assert first["cached"] == 0 and rows > 1000
    assert 3 <= second["evaluated"] <= 4        # + `ssf.names_unique`
    assert second["evaluated"] + second["cached"] in (rows, rows + 1)
    assert 4 <= third["evaluated"] <= 5
    assert third["evaluated"] + third["cached"] in (rows + 1, rows + 2)
    # and what was kept is still the reference's answer (gauges carry
    # their round number: compare names and tags)
    for tap in taps:
        want = sorted((s[0], s[1]) for s in _ours(unrouted["series"])
                      if tap.name() in matcher_ref.route(
                          OVERLAP, s[0], s[1]))
        for share in tap.flushes[:2]:
            assert sorted((s[0], s[1]) for s in _ours(
                _columns(share))) == want
        assert sorted((s[0], s[1]) for s in _ours(_columns(
            tap.flushes[2]))) == sorted(want + [
                ("rf.api.counter.new", ("zone:z2",))])
