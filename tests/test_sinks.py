"""Sink tests against local HTTP/UDP fakes — the reference's
httptest.Server pattern (e.g. sinks/datadog/datadog_test.go:496,
sinks/cortex/cortex_test.go:764)."""

import gzip
import json
import os
import socket
import threading
import time
import urllib.parse
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

import pytest

from veneur_tpu.samplers.metrics import InterMetric, MetricType
from veneur_tpu.ssf.protos import ssf_pb2
from veneur_tpu.util import http as vhttp


class CapturingHTTPServer:
    """Records every request (path, headers, body) and returns 200,
    after holding it for `delay_s`; `most_in_flight` is the most
    requests it ever held at once."""

    def __init__(self, delay_s=0.0):
        outer = self
        self.requests = []
        self.event = threading.Event()
        self.lock = threading.Lock()
        self.in_flight = self.most_in_flight = 0

        class Handler(BaseHTTPRequestHandler):
            def log_message(self, fmt, *args):
                pass

            def do_POST(self):  # noqa: N802
                length = int(self.headers.get("Content-Length", 0))
                body = self.rfile.read(length)
                if self.headers.get("Content-Encoding") == "gzip":
                    body = gzip.decompress(body)
                with outer.lock:
                    outer.requests.append(
                        (self.path, dict(self.headers), body))
                    outer.in_flight += 1
                    outer.most_in_flight = max(outer.most_in_flight,
                                               outer.in_flight)
                outer.event.set()
                time.sleep(delay_s)
                with outer.lock:
                    outer.in_flight -= 1
                self.send_response(200)
                self.send_header("Content-Length", "2")
                self.end_headers()
                self.wfile.write(b"{}")

            do_GET = do_POST  # noqa: N815
            do_PUT = do_POST  # noqa: N815

        self.httpd = ThreadingHTTPServer(("127.0.0.1", 0), Handler)
        threading.Thread(target=self.httpd.serve_forever,
                         daemon=True).start()

    @property
    def url(self):
        host, port = self.httpd.server_address
        return f"http://{host}:{port}"

    def close(self):
        self.httpd.shutdown()
        self.httpd.server_close()


@pytest.fixture
def fake():
    server = CapturingHTTPServer()
    yield server
    server.close()


def im(name="a.b.c", value=1.0, mtype=MetricType.COUNTER, tags=(),
       ts=1_700_000_000, hostname="h1", message=""):
    return InterMetric(name=name, timestamp=ts, value=value,
                       tags=list(tags), type=mtype, message=message,
                       hostname=hostname)


def make_span(trace_id=1, span_id=2, parent_id=0, name="op",
              service="svc", error=False, indicator=False, tags=None):
    s = ssf_pb2.SSFSpan()
    s.trace_id = trace_id
    s.id = span_id
    s.parent_id = parent_id
    s.name = name
    s.service = service
    s.error = error
    s.indicator = indicator
    s.start_timestamp = 1_700_000_000_000_000_000
    s.end_timestamp = 1_700_000_001_000_000_000
    for k, v in (tags or {}).items():
        s.tags[k] = v
    return s


class TestDatadog:
    def _sink(self, fake, **kw):
        from veneur_tpu.sinks.datadog import DatadogMetricSink
        return DatadogMetricSink("datadog", api_key="k", api_url=fake.url,
                                 hostname="dh", interval=10.0, **kw)

    def test_counter_rate_conversion_and_tags(self, fake):
        sink = self._sink(fake)
        sink.flush([im(value=50.0, tags=["a:b", "host:other", "device:sda"]),
                    im("g1", 7.0, MetricType.GAUGE)])
        path, _, body = fake.requests[0]
        assert path.startswith("/api/v1/series")
        assert "api_key=k" in path
        series = json.loads(body)["series"]
        counter = next(s for s in series if s["metric"] == "a.b.c")
        assert counter["type"] == "rate"
        assert counter["points"][0][1] == pytest.approx(5.0)  # 50/10s
        assert counter["host"] == "other"
        assert counter["device"] == "sda"
        assert "a:b" in counter["tags"]
        assert not any(t.startswith("host:") for t in counter["tags"])
        gauge = next(s for s in series if s["metric"] == "g1")
        assert gauge["type"] == "gauge"
        assert gauge["points"][0][1] == 7.0

    def test_chunking(self, fake):
        sink = self._sink(fake, flush_max_per_body=2)
        sink.flush([im(f"m{i}") for i in range(5)])
        assert len(fake.requests) == 3
        total = sum(len(json.loads(b)["series"]) for _, _, b in fake.requests)
        assert total == 5

    def test_metric_name_prefix_drops(self, fake):
        sink = self._sink(fake, metric_name_prefix_drops=["veneur."])
        sink.flush([im("veneur.flush.total"), im("app.reqs")])
        series = json.loads(fake.requests[0][2])["series"]
        assert [s["metric"] for s in series] == ["app.reqs"]

    def test_tag_exclusion_by_metric_prefix(self, fake):
        sink = self._sink(
            fake, excluded_tag_prefixes=["noisy"],
            exclude_tags_prefix_by_prefix_metric={"db.": ["shard"]})
        sink.flush([
            im("db.queries", tags=["shard:3", "env:prod", "noisy:x"]),
            im("web.hits", tags=["shard:3", "noisy:x"])])
        series = {s["metric"]: s for s in
                  json.loads(fake.requests[0][2])["series"]}
        assert series["db.queries"]["tags"] == ["env:prod"]
        assert series["web.hits"]["tags"] == ["shard:3"]

    def test_service_checks(self, fake):
        sink = self._sink(fake)
        sink.flush([im("check.up", 2.0, MetricType.STATUS,
                       message="oh no")])
        path, _, body = fake.requests[0]
        assert path.startswith("/api/v1/check_run")
        payload = json.loads(body)
        assert payload["check"] == "check.up"
        assert payload["status"] == 2
        assert payload["message"] == "oh no"

    def test_events(self, fake):
        from veneur_tpu.samplers.parser import Event
        sink = self._sink(fake)
        sink.flush_other_samples([Event(
            name="deploy", message="v2 shipped", timestamp=123,
            tags={"alert_type": "warning", "env": "prod"})])
        path, _, body = fake.requests[0]
        assert path.startswith("/intake")
        events = json.loads(body)["events"]["datadog"]
        assert events[0]["title"] == "deploy"
        assert events[0]["alert_type"] == "warning"
        assert "env:prod" in events[0]["tags"]

    def test_span_sink(self, fake):
        from veneur_tpu.sinks.datadog import DatadogSpanSink
        sink = DatadogSpanSink("datadog", trace_api_url=fake.url,
                               hostname="dh")
        sink.ingest(make_span(trace_id=5, span_id=6,
                              tags={"resource": "GET /"}))
        sink.ingest(make_span(trace_id=5, span_id=7, parent_id=6))
        sink.ingest(make_span(trace_id=0))  # no trace id -> dropped
        sink.flush()
        _, _, body = fake.requests[0]
        traces = json.loads(body)
        assert len(traces) == 1
        assert len(traces[0]) == 2
        assert traces[0][0]["resource"] == "GET /"
        # second flush with nothing buffered: no POST
        sink.flush()
        assert len(fake.requests) == 1


class TestDatadogPipeline:
    """flush_columnar as a pipeline (sinks/datadog.py `_BodyPosts`):
    judged on the round's spans and on what the intake saw."""

    def _flush(self, url, per_body, num_workers, encoder=None):
        from test_egress import _mk_batch
        from veneur_tpu.sinks.datadog import DatadogMetricSink

        batch, _ = _mk_batch()
        sink = DatadogMetricSink("datadog", api_key="k", api_url=url,
                                 hostname="dh", interval=10.0,
                                 flush_max_per_body=per_body,
                                 num_workers=num_workers)
        if encoder is not None:
            sink._encoder = encoder(sink, batch.timing)
        with batch.timing.phase("sink", parent=None):
            sink.flush_columnar(batch)
        by_name = {}
        for span in batch.timing.spans:
            by_name.setdefault(span["name"], []).append(span)
        return by_name

    @staticmethod
    def _waiting_encoder(sink, rnd):
        """An encoder slowed per body: after each hand-off it waits
        until that body's gzip has run (its span is in the round)."""
        from veneur_tpu.core.egress import DatadogColumnarEncoder

        class Waiting(DatadogColumnarEncoder):
            def encode_bodies(self, batch, per_body, emit):
                handed = []

                def emit_and_wait(parts):
                    emit(parts)
                    handed.append(parts)
                    deadline = time.time() + 10.0
                    while (sum(s["name"] == "egress_gzip"
                               for s in list(rnd.spans)) < len(handed)
                           and time.time() < deadline):
                        time.sleep(0.001)

                return super().encode_bodies(batch, per_body,
                                             emit_and_wait)

        return Waiting(sink)

    def test_bodies_are_sent_while_the_encoder_runs(self, fake):
        spans = self._flush(fake.url, 20, 1, self._waiting_encoder)
        [encode], [wall] = spans["egress_encode"], spans["egress_post_wall"]
        [tail], [sink] = spans["egress_post_tail"], spans["sink"]
        encode_end = encode["start_s"] + encode["wall_s"]
        gzips = sorted(spans["egress_gzip"], key=lambda s: s["start_s"])
        assert len(gzips) == wall["bodies"] == len(fake.requests) >= 3
        # every body but the last was compressed while the encoder ran
        assert gzips[0]["start_s"] < encode_end
        assert [g["start_s"] < encode_end for g in gzips] == (
            [True] * (len(gzips) - 1) + [False])
        assert wall["bodies_overlapped"] == wall["bodies"] - 1
        # ... on the one worker, not on the encoding thread
        senders = {s["thread"] for s in gzips + spans["egress_http"]
                   + spans["egress_join"]}
        assert senders == {"datadog-post-0"} != {encode["thread"]}
        # the wall spans the sends, the tail only what follows the encode
        assert wall["start_s"] < encode_end <= tail["start_s"]
        ends = [s["start_s"] + s["wall_s"] for s in spans["egress_http"]]
        assert wall["start_s"] + wall["wall_s"] >= max(ends)
        assert tail["start_s"] + tail["wall_s"] >= max(ends)
        assert tail["wall_s"] < wall["wall_s"]
        assert wall["thread"] == tail["thread"] == sink["thread"]
        assert wall["bytes"] == sum(len(b) for _, _, b in fake.requests)

    def test_a_one_body_flush_posts_from_the_sink_thread(self, fake,
                                                         monkeypatch):
        started = []
        start = threading.Thread.start
        monkeypatch.setattr(
            threading.Thread, "start",
            lambda thread: (started.append(thread.name), start(thread))[1])
        spans = self._flush(fake.url, 25_000, 4)
        assert not [name for name in started if "-post-" in name]
        me = threading.current_thread().name
        sending = [s for name in ("egress_join", "egress_gzip",
                                  "egress_http", "egress_post_wall",
                                  "egress_post_tail")
                   for s in spans[name]]
        assert len(sending) == 5 and {s["thread"] for s in sending} == {me}
        [wall], [tail] = spans["egress_post_wall"], spans["egress_post_tail"]
        assert wall["bodies"] == 1 and wall["bodies_overlapped"] == 0
        assert len(fake.requests) == 1
        # in turn on one thread: encode, join, then the tail around the post
        [encode], [join] = spans["egress_encode"], spans["egress_join"]
        assert (encode["start_s"] + encode["wall_s"] <= join["start_s"]
                and join["start_s"] + join["wall_s"] <= tail["start_s"]
                <= wall["start_s"])

    @pytest.mark.parametrize("num_workers", [1, 2, 4])
    def test_no_more_than_num_workers_requests_in_flight(self, num_workers):
        intake = CapturingHTTPServer(delay_s=0.03)
        try:
            spans = self._flush(intake.url, 8, num_workers)
        finally:
            intake.close()
        [wall] = spans["egress_post_wall"]
        assert len(intake.requests) == wall["bodies"] >= 8
        assert intake.most_in_flight <= num_workers
        if num_workers > 1:   # and the cap is used, not only kept
            assert intake.most_in_flight >= 2
        assert len({s["thread"] for s in spans["egress_http"]}) \
            <= num_workers
        assert intake.most_in_flight <= wall["peak_in_flight"] \
            <= wall["workers"] <= num_workers

    @pytest.mark.parametrize("cores", [3, 8])
    def test_a_sink_of_no_stated_cap_fills_the_hosts(self, monkeypatch,
                                                     cores):
        """No `num_workers`: the cores this process may run on, less
        one, cap the POSTs in flight, and a slow intake fills the cap's
        first places (here each body waits 30 ms for its answer while
        the encode takes well under one)."""
        monkeypatch.setattr(os, "sched_getaffinity",
                            lambda pid: set(range(cores)), raising=False)
        intake = CapturingHTTPServer(delay_s=0.03)
        try:
            spans = self._flush(intake.url, 8, None)
        finally:
            intake.close()
        [wall] = spans["egress_post_wall"]
        assert len(intake.requests) == wall["bodies"] >= 8
        assert 1 < intake.most_in_flight <= wall["peak_in_flight"] \
            <= wall["workers"] <= cores - 1
        assert len({s["thread"] for s in spans["egress_http"]}) \
            == wall["workers"]

    def test_one_worker_serves_an_encoder_slower_than_the_send(
            self, monkeypatch):
        """An instant fake POST and an encoder that takes 20 ms more
        after each body it hands off: every hand-off finds the one
        worker waiting, so no second one starts under a cap of 8."""
        from test_egress import _capture_posts
        from veneur_tpu.core.egress import DatadogColumnarEncoder

        posted = _capture_posts(monkeypatch)

        class Slow(DatadogColumnarEncoder):
            def encode_bodies(self, batch, per_body, emit):
                handed = []

                def emit_and_dawdle(parts):
                    emit(parts)
                    handed.append(parts)
                    deadline = time.time() + 10.0
                    while len(posted) < len(handed) \
                            and time.time() < deadline:
                        time.sleep(0.001)
                    time.sleep(0.02)

                return super().encode_bodies(batch, per_body,
                                             emit_and_dawdle)

        spans = self._flush("http://unused.invalid", 20, 8,
                            lambda sink, rnd: Slow(sink))
        [wall] = spans["egress_post_wall"]
        assert wall["bodies"] == len(posted) >= 3
        assert wall["workers"] == wall["peak_in_flight"] == 1
        assert {thread for *_, thread in posted} == {"datadog-post-0"}

    def test_two_failing_workers_give_one_error_naming_both(self, fake):
        """Two workers each fail on a body: the flush ends once every
        worker has, with one `SeriesPartlySent` that names both, and
        the bodies that did leave left once."""
        from test_egress import _mk_batch
        from veneur_tpu.sinks.datadog import (DatadogMetricSink,
                                              SeriesPartlySent)

        batch, _ = _mk_batch()
        sink = DatadogMetricSink("datadog", api_key="k", api_url=fake.url,
                                 hostname="dh", interval=10.0,
                                 flush_max_per_body=8, num_workers=2)
        post, failed = sink._post_series_body_safe, []
        both = threading.Barrier(2, timeout=10.0)

        def post_or_fail(body, phase=None):
            # each worker's first body: held until the other worker has
            # one too, then lost
            me = threading.current_thread().name
            if me.startswith("datadog-post-") and me not in failed:
                failed.append(me)
                both.wait()
                raise RuntimeError(f"boom on {me}")
            post(body, phase)

        sink._post_series_body_safe = post_or_fail
        with pytest.raises(SeriesPartlySent) as raised:
            sink.flush_batch(batch)
        assert sorted(failed) == ["datadog-post-0", "datadog-post-1"]
        assert "2 of " in str(raised.value)
        assert all(name in str(raised.value) for name in failed)
        [wall] = [s for s in batch.timing.spans
                  if s["name"] == "egress_post_wall"]
        assert wall["workers"] == 2
        assert len(fake.requests) == wall["bodies"] - 2 >= 1
        bodies = [body for _, _, body in fake.requests]
        assert len(set(bodies)) == len(bodies)
        assert not any(t.name.startswith("datadog-post-")
                       for t in threading.enumerate())

    @pytest.mark.parametrize("per_body, workers", [(25_000, 0), (8, None)])
    def test_the_flush_counts_the_workers_it_started(self, fake, per_body,
                                                     workers):
        """`sink.datadog.post.workers` and the wall's `workers`: what
        the flush started, 0 where the sink thread sent the one body."""
        from test_egress import _mk_batch
        from veneur_tpu.sinks.datadog import DatadogMetricSink

        counts = []

        class Statsd:
            def count(self, name, value, tags=()):
                counts.append((name, value, list(tags)))

        batch, _ = _mk_batch()
        sink = DatadogMetricSink("dd-a", api_key="k", api_url=fake.url,
                                 hostname="dh", interval=10.0,
                                 flush_max_per_body=per_body, num_workers=3)
        sink._statsd = Statsd()
        sink.flush_columnar(batch)
        [wall] = [s for s in batch.timing.spans
                  if s["name"] == "egress_post_wall"]
        [(value, tags)] = [(v, t) for name, v, t in counts
                           if name == "sink.datadog.post.workers"]
        assert tags == ["sink:dd-a"] and value == wall["workers"]
        if workers == 0:
            assert value == 0 and wall["peak_in_flight"] == 1
        else:
            assert 1 <= value <= 3
            assert 1 <= wall["peak_in_flight"] <= value


class TestDatadogPostWorkerCap:
    """What caps a flush's POST workers (`host_post_workers`, the
    factory): the sink's own `datadog_num_workers`, else the host."""

    @staticmethod
    def _from_config(sink_keys, **server_keys):
        from veneur_tpu.config import Config, SinkConfig
        from veneur_tpu.sinks import MetricSinkTypes, register_builtin_sinks

        register_builtin_sinks()
        cfg = Config(**server_keys)
        cfg.apply_defaults()
        return MetricSinkTypes["datadog"](
            SinkConfig(kind="datadog", name="datadog", config={
                "datadog_api_key": "k", **sink_keys}), cfg)

    @pytest.mark.parametrize("cores, cap", [(1, 1), (2, 1), (8, 7),
                                            (112, 111)])
    def test_the_cores_the_process_may_run_on_less_one(self, monkeypatch,
                                                       cores, cap):
        monkeypatch.setattr(os, "sched_getaffinity",
                            lambda pid: set(range(cores)), raising=False)
        monkeypatch.setattr(os, "cpu_count", lambda: 64)
        # `Config.num_workers` counts aggregation workers, not these
        for server_keys in ({}, {"num_workers": 96}):
            sink = self._from_config({}, **server_keys)
            assert sink.num_workers == cap

    def test_the_hosts_cores_where_no_affinity_is_to_be_had(self,
                                                            monkeypatch):
        monkeypatch.delattr(os, "sched_getaffinity", raising=False)
        monkeypatch.setattr(os, "cpu_count", lambda: 6)
        assert self._from_config({}).num_workers == 5
        monkeypatch.setattr(os, "cpu_count", lambda: None)
        assert self._from_config({}).num_workers == 1

    @pytest.mark.parametrize("key, cap", [(1, 1), (3, 3), ("24", 24)])
    def test_the_sinks_own_key_wins(self, monkeypatch, key, cap):
        monkeypatch.setattr(os, "sched_getaffinity",
                            lambda pid: set(range(8)), raising=False)
        sink = self._from_config({"datadog_num_workers": key},
                                 num_workers=96)
        assert sink.num_workers == cap


class TestCortex:
    def test_remote_write_roundtrip(self, fake):
        from veneur_tpu.sinks.cortex import (
            CortexMetricSink, decode_write_request)
        sink = CortexMetricSink("cortex", url=fake.url, hostname="ch",
                                auth_token="tok")
        sink.flush([im("http.requests", 3.5, MetricType.GAUGE,
                       tags=["region:us", "bad-label:x"])])
        _, headers, body = fake.requests[0]
        assert headers["Content-Encoding"] == "snappy"
        assert headers["X-Prometheus-Remote-Write-Version"] == "0.1.0"
        assert headers["Authorization"] == "Bearer tok"
        series = decode_write_request(vhttp.snappy_decode(body))
        labels, value, ts = series[0]
        assert labels["__name__"] == "http.requests".replace(".", "_") \
            or labels["__name__"] == "http.requests"
        assert labels["region"] == "us"
        assert labels["bad_label"] == "x"
        assert labels["host"] == "h1"  # metric hostname wins
        assert value == 3.5
        assert ts == 1_700_000_000_000

    def test_name_sanitization(self):
        from veneur_tpu.sinks.cortex import sanitize_label, sanitize_name
        assert sanitize_name("a.b-c/d") == "a_b_c_d"
        assert sanitize_name("9lives") == "_9lives"
        assert sanitize_name("ok:name_1") == "ok:name_1"
        assert sanitize_label("a:b") == "a_b"

    def test_batching(self, fake):
        from veneur_tpu.sinks.cortex import CortexMetricSink
        sink = CortexMetricSink("cortex", url=fake.url, hostname="ch",
                                batch_write_size=2)
        sink.flush([im(f"m{i}", i, MetricType.GAUGE) for i in range(5)])
        assert len(fake.requests) == 3


class TestPrometheus:
    def test_exposition(self):
        from veneur_tpu.sinks.prometheus import render_exposition
        text = render_exposition([
            im("req.count", 5, MetricType.COUNTER, tags=["code:200"]),
            im("check", 0, MetricType.STATUS)])
        assert 'req_count{code="200"} 5' in text
        assert "check" not in text

    def test_expose_endpoint_and_repeater(self):
        from veneur_tpu.sinks.prometheus import PrometheusMetricSink
        recv = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
        recv.bind(("127.0.0.1", 0))
        recv.settimeout(5.0)
        port = recv.getsockname()[1]
        sink = PrometheusMetricSink(
            "prometheus", repeater_address=f"127.0.0.1:{port}",
            expose_address="127.0.0.1:0")
        sink.start(None)
        try:
            sink.flush([im("up", 1, MetricType.GAUGE, tags=["a:b"])])
            data, _ = recv.recvfrom(65536)
            assert data == b"up:1|g|#a:b"
            status, body = vhttp.get(
                f"http://127.0.0.1:{sink.expose_port}/metrics")
            assert status == 200
            assert b"up{" in body
        finally:
            sink.stop()
            recv.close()


class TestSignalFx:
    def test_datapoints_and_token_routing(self, fake):
        from veneur_tpu.sinks.signalfx import SignalFxMetricSink
        sink = SignalFxMetricSink(
            "signalfx", api_key="default-tok", endpoint=fake.url,
            hostname="sh", vary_key_by="customer",
            per_tag_tokens={"acme": "acme-tok"})
        sink.flush([
            im("c1", 2, MetricType.COUNTER, tags=["customer:acme"]),
            im("g1", 3, MetricType.GAUGE)])
        assert len(fake.requests) == 2
        # urllib normalizes header casing; match case-insensitively
        by_token = {
            next(v for k, v in h.items() if k.lower() == "x-sf-token"):
            json.loads(b) for _, h, b in fake.requests}
        assert by_token["acme-tok"]["counter"][0]["metric"] == "c1"
        assert by_token["acme-tok"]["counter"][0]["dimensions"][
            "customer"] == "acme"
        assert by_token["default-tok"]["gauge"][0]["metric"] == "g1"
        assert by_token["default-tok"]["gauge"][0]["dimensions"][
            "host"] == "h1"  # metric hostname wins over sink hostname

    def test_status_checks_emit_as_gauges(self, fake):
        from veneur_tpu.sinks.signalfx import SignalFxMetricSink
        sink = SignalFxMetricSink("signalfx", api_key="t",
                                  endpoint=fake.url, hostname="sh")
        sink.flush([im("svc.up", 2, MetricType.STATUS)])
        payload = json.loads(fake.requests[0][2])
        assert payload["gauge"][0]["metric"] == "svc.up"
        assert payload["gauge"][0]["value"] == 2

    def test_drop_host_with_tag_key(self, fake):
        from veneur_tpu.sinks.signalfx import SignalFxMetricSink
        sink = SignalFxMetricSink(
            "signalfx", api_key="t", endpoint=fake.url, hostname="sh",
            drop_host_with_tag_key="multihost")
        sink.flush([
            im("c1", 1, MetricType.COUNTER, tags=["multihost:yes"]),
            im("c2", 1, MetricType.COUNTER),
            im("g1", 1, MetricType.GAUGE, tags=["multihost:yes"])])
        payload = json.loads(fake.requests[0][2])
        dims = {p["metric"]: p["dimensions"]
                for kind in payload.values() for p in kind}
        assert "host" not in dims["c1"]  # counter with the tag: dropped
        assert dims["c2"]["host"] == "h1"  # counter without: kept
        assert dims["g1"]["host"] == "h1"  # gauges never drop

    def test_event_flush(self, fake):
        from veneur_tpu.samplers.parser import Event
        from veneur_tpu.samplers.parser import EVENT_IDENTIFIER_KEY
        from veneur_tpu.sinks.signalfx import SignalFxMetricSink
        sink = SignalFxMetricSink("signalfx", api_key="t",
                                  endpoint=fake.url, hostname="sh")
        ev = Event(name="deploy", message="%%% \nrolled out\n %%%",
                   timestamp=1000,
                   tags={EVENT_IDENTIFIER_KEY: "", "env": "prod"})
        not_event = Event(name="no", message="x", timestamp=1,
                          tags={"env": "prod"})
        sink.flush_other_samples([ev, not_event])
        path, _, body = fake.requests[0]
        assert path == "/v2/event"
        events = json.loads(body)
        assert len(events) == 1  # non-event sample ignored
        assert events[0]["eventType"] == "deploy"
        assert events[0]["properties"]["description"] == "rolled out"
        assert events[0]["dimensions"]["env"] == "prod"
        assert EVENT_IDENTIFIER_KEY not in events[0]["dimensions"]

    def test_event_truncation(self, fake):
        from veneur_tpu.samplers.parser import Event
        from veneur_tpu.samplers.parser import EVENT_IDENTIFIER_KEY
        from veneur_tpu.sinks.signalfx import SignalFxMetricSink
        sink = SignalFxMetricSink("signalfx", api_key="t",
                                  endpoint=fake.url, hostname="sh")
        ev = Event(name="n" * 400, message="m" * 400, timestamp=1,
                   tags={EVENT_IDENTIFIER_KEY: ""})
        sink.flush_other_samples([ev])
        events = json.loads(fake.requests[0][2])
        assert len(events[0]["eventType"]) == 256
        assert len(events[0]["properties"]["description"]) == 256

    def test_flush_max_per_body_chunks(self, fake):
        from veneur_tpu.sinks.signalfx import SignalFxMetricSink
        sink = SignalFxMetricSink("signalfx", api_key="t",
                                  endpoint=fake.url, hostname="sh",
                                  flush_max_per_body=3)
        sink.flush([im(f"m{i}", i, MetricType.GAUGE) for i in range(8)])
        assert len(fake.requests) == 3  # ceil(8/3)
        total = sum(len(json.loads(b).get("gauge", []))
                    for _, _, b in fake.requests)
        assert total == 8


class TestKafka:
    def test_metric_sink(self):
        from veneur_tpu.sinks.kafka import InMemoryProducer, KafkaMetricSink
        producer = InMemoryProducer()
        sink = KafkaMetricSink("kafka", producer, metric_topic="metrics")
        sink.flush([im("k1", 9, tags=["x:y"])])
        topic, key, value = producer.messages[0]
        assert topic == "metrics"
        assert key == b"k1"
        decoded = json.loads(value)
        assert decoded["value"] == 9
        assert decoded["tags"] == ["x:y"]

    def test_span_sink_sampling(self):
        from veneur_tpu.sinks.kafka import InMemoryProducer, KafkaSpanSink
        producer = InMemoryProducer()
        sink = KafkaSpanSink("kafka", producer, span_topic="spans",
                             encoding="json", sample_rate_percent=50.0)
        for tid in range(1, 101):
            sink.ingest(make_span(trace_id=tid))
        sink.flush()
        kept = len(producer.messages)
        assert 0 < kept < 100  # deterministic by trace id, roughly half
        # identical ingest keeps/drops the same traces
        decoded = json.loads(producer.messages[0][2])
        assert "trace_id" in decoded

    def test_span_protobuf_encoding(self):
        from veneur_tpu.sinks.kafka import InMemoryProducer, KafkaSpanSink
        producer = InMemoryProducer()
        sink = KafkaSpanSink("kafka", producer, span_topic="spans")
        sink.ingest(make_span(trace_id=42))
        parsed = ssf_pb2.SSFSpan()
        parsed.ParseFromString(producer.messages[0][2])
        assert parsed.trace_id == 42


class TestS3:
    def test_tsv_upload(self):
        from veneur_tpu.sinks.s3 import InMemoryUploader, S3MetricSink
        uploader = InMemoryUploader()
        sink = S3MetricSink("s3", uploader, bucket="b", hostname="s3h",
                            interval=10.0)
        sink.flush([im("s.m", 4.5, MetricType.GAUGE, tags=["t:1"])])
        bucket, key, body = uploader.objects[0]
        assert bucket == "b"
        assert key.startswith("s3h/")
        row = gzip.decompress(body).decode().strip().split("\t")
        assert row[0] == "s.m"
        assert row[1] == "t:1"
        assert row[2] == "gauge"
        assert float(row[5]) == 4.5


class TestCloudWatch:
    def test_put_metric_data(self, fake):
        from veneur_tpu.sinks.cloudwatch import CloudWatchMetricSink
        sink = CloudWatchMetricSink("cloudwatch", endpoint=fake.url + "/",
                                    namespace="ns")
        sink.flush([im("cw.m", 2.5, MetricType.GAUGE, tags=["az:us-1a"])])
        _, _, body = fake.requests[0]
        params = dict(urllib.parse.parse_qsl(body.decode()))
        assert params["Action"] == "PutMetricData"
        assert params["Namespace"] == "ns"
        assert params["MetricData.member.1.MetricName"] == "cw.m"
        assert float(params["MetricData.member.1.Value"]) == 2.5
        assert params["MetricData.member.1.Dimensions.member.1.Name"] == "az"

    def test_chunking_and_signing(self, fake):
        from veneur_tpu.sinks.cloudwatch import CloudWatchMetricSink
        sink = CloudWatchMetricSink(
            "cloudwatch", endpoint=fake.url + "/", namespace="ns",
            region="us-east-1", credentials=("AKID", "SECRET"))
        sink.flush([im(f"m{i}") for i in range(25)])
        assert len(fake.requests) == 2
        _, headers, _ = fake.requests[0]
        assert headers["Authorization"].startswith(
            "AWS4-HMAC-SHA256 Credential=AKID/")
        assert "X-Amz-Date" in headers


class TestSplunk:
    def test_hec_events(self, fake):
        from veneur_tpu.sinks.splunk import SplunkSpanSink
        sink = SplunkSpanSink("splunk", hec_address=fake.url, token="tok",
                              hostname="sph", index="idx")
        sink.ingest(make_span(trace_id=10, tags={"k": "v"}))
        sink.ingest(make_span(trace_id=11, error=True))
        sink.flush()
        _, headers, body = fake.requests[0]
        assert headers["Authorization"] == "Splunk tok"
        events = [json.loads(line) for line in body.splitlines()]
        assert len(events) == 2
        assert events[0]["index"] == "idx"
        assert events[0]["event"]["tags"] == {"k": "v"}
        assert events[1]["event"]["error"] is True

    def test_sampling_keeps_indicators(self, fake):
        from veneur_tpu.sinks.splunk import SplunkSpanSink
        sink = SplunkSpanSink("splunk", hec_address=fake.url, token="t",
                              hostname="h", sample_rate=10)
        for tid in range(1, 101):
            sink.ingest(make_span(trace_id=tid))
        sink.ingest(make_span(trace_id=7, indicator=True))
        sink.flush()
        _, _, body = fake.requests[0]
        events = [json.loads(line) for line in body.splitlines()]
        # 10 sampled (trace_id % 10 == 0) + 1 indicator
        assert len(events) == 11


class TestXRay:
    def test_segments_over_udp(self):
        from veneur_tpu.sinks.xray import XRaySpanSink
        recv = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
        recv.bind(("127.0.0.1", 0))
        recv.settimeout(5.0)
        port = recv.getsockname()[1]
        sink = XRaySpanSink("xray", daemon_address=f"127.0.0.1:{port}",
                            annotation_tags=["env"])
        sink.start(None)
        try:
            sink.ingest(make_span(trace_id=99, span_id=100, parent_id=1,
                                  tags={"env": "prod", "other": "x"}))
            data, _ = recv.recvfrom(65536)
            header, payload = data.split(b"\n", 1)
            assert json.loads(header)["format"] == "json"
            seg = json.loads(payload)
            assert seg["trace_id"].startswith("1-")
            assert seg["annotations"] == {"env": "prod"}
            assert seg["type"] == "subsegment"
            assert sink.spans_handled == 1
        finally:
            sink.stop()
            recv.close()


class TestFalconerLightstepNewrelic:
    def test_falconer_sender(self):
        from veneur_tpu.sinks.falconer import FalconerSpanSink
        sent = []
        sink = FalconerSpanSink("falconer", sender=sent.append)
        sink.ingest(make_span(trace_id=3))
        assert sink.spans_handled == 1
        assert sent[0].trace_id == 3

    def test_lightstep(self, fake):
        from veneur_tpu.sinks.lightstep import LightStepSpanSink
        sink = LightStepSpanSink("lightstep", access_token="at",
                                 collector_url=fake.url, num_clients=2)
        sink.ingest(make_span(trace_id=1))
        sink.ingest(make_span(trace_id=2))
        sink.flush()
        assert len(fake.requests) == 2  # one OTLP request per stripe
        path, headers, body = fake.requests[0]
        assert path.endswith("/v1/traces")
        lower = {k.lower(): v for k, v in headers.items()}
        assert lower["lightstep-access-token"] == "at"
        payload = json.loads(body)
        spans = payload["resourceSpans"][0]["scopeSpans"][0]["spans"]
        assert len(spans) == 1

    def test_newrelic_metrics(self, fake):
        from veneur_tpu.sinks.newrelic import NewRelicMetricSink
        sink = NewRelicMetricSink(
            "newrelic", insert_key="ik", hostname="nh", interval=10.0,
            metric_url=fake.url + "/metric/v1")
        sink.flush([im("nr.c", 5, MetricType.COUNTER),
                    im("nr.g", 6, MetricType.GAUGE)])
        _, headers, body = fake.requests[0]
        assert headers["Api-Key"] == "ik"
        metrics = json.loads(body)[0]["metrics"]
        count = next(m for m in metrics if m["name"] == "nr.c")
        assert count["type"] == "count"
        assert count["interval.ms"] == 10_000
        gauge = next(m for m in metrics if m["name"] == "nr.g")
        assert gauge["type"] == "gauge"

    def test_newrelic_spans(self, fake):
        from veneur_tpu.sinks.newrelic import NewRelicSpanSink
        sink = NewRelicSpanSink("newrelic", insert_key="ik",
                                trace_url=fake.url + "/trace/v1")
        sink.ingest(make_span(trace_id=8, span_id=9, parent_id=4))
        sink.flush()
        _, _, body = fake.requests[0]
        spans = json.loads(body)[0]["spans"]
        assert spans[0]["trace.id"] == "8"
        assert spans[0]["attributes"]["parent.id"] == "4"
        assert spans[0]["attributes"]["duration.ms"] == pytest.approx(1000.0)


class TestRegistry:
    def test_all_kinds_registered(self):
        from veneur_tpu import sinks as sinks_mod
        sinks_mod.register_builtin_sinks()
        for kind in ("datadog", "signalfx", "cortex", "kafka", "s3",
                     "cloudwatch", "prometheus", "newrelic", "blackhole",
                     "debug", "localfile", "channel"):
            assert kind in sinks_mod.MetricSinkTypes, kind
        for kind in ("datadog", "kafka", "splunk", "xray", "falconer",
                     "lightstep", "newrelic"):
            assert kind in sinks_mod.SpanSinkTypes, kind


class TestDatadogSpanDepth:
    """Reference datadog.go:453-660 span-path semantics."""

    def test_ring_overflow_accounting(self):
        from veneur_tpu.sinks.datadog import DatadogSpanSink
        sink = DatadogSpanSink("datadog", trace_api_url="http://x",
                               hostname="dh", buffer_size=4)
        for i in range(7):
            sink.ingest(make_span(trace_id=1, span_id=i + 1))
        assert len(sink.buffer) == 4  # oldest overwritten, never blocks
        assert sink.overwritten_total == 3
        ids = [s.id for s in sink.buffer]
        assert ids == [4, 5, 6, 7]

    def test_dd_span_shape(self, fake):
        from veneur_tpu.sinks.datadog import DatadogSpanSink
        sink = DatadogSpanSink("datadog", trace_api_url=fake.url,
                               hostname="dh")
        root = make_span(trace_id=9, span_id=1, parent_id=-1,
                         tags={"resource": "GET /x", "env": "t"})
        root.error = True
        sink.ingest(root)
        child = make_span(trace_id=9, span_id=2, parent_id=1)
        child.name = ""
        sink.ingest(child)
        sink.flush()
        path, headers, body = fake.requests[0]
        assert path == "/v0.3/traces"
        # the traces endpoint takes an uncompressed PUT
        assert headers.get("Content-Encoding") is None
        traces = json.loads(body)
        assert len(traces) == 1
        by_id = {s["span_id"]: s for s in traces[0]}
        assert by_id[1]["parent_id"] == 0        # root clamps to 0
        assert by_id[1]["resource"] == "GET /x"  # promoted out of meta
        assert "resource" not in by_id[1]["meta"]
        assert by_id[1]["error"] == 2
        assert by_id[1]["type"] == "web"
        assert by_id[2]["name"] == "unknown"
        assert by_id[2]["resource"] == "unknown"

    def test_flush_self_metrics_per_service(self, fake):
        from veneur_tpu.sinks.datadog import DatadogSpanSink
        calls = []

        class FakeStatsd:
            def count(self, name, value, tags=None):
                calls.append((name, value, tuple(tags or ())))

            def gauge(self, name, value, tags=None):
                calls.append((name, value, tuple(tags or ())))

        class FakeServer:
            statsd = FakeStatsd()

        sink = DatadogSpanSink("datadog", trace_api_url=fake.url,
                               hostname="dh")
        sink.start(FakeServer())
        s1 = make_span(trace_id=1, span_id=1)
        s1.service = "api"
        s2 = make_span(trace_id=2, span_id=2)
        s2.service = "api"
        s3 = make_span(trace_id=3, span_id=3)
        s3.service = "db"
        for s in (s1, s2, s3):
            sink.ingest(s)
        sink.flush()
        flushed = {c for c in calls if c[0] == "sink.spans_flushed_total"}
        assert ("sink.spans_flushed_total", 2,
                ("sink:datadog", "service:api")) in flushed
        assert ("sink.spans_flushed_total", 1,
                ("sink:datadog", "service:db")) in flushed
        assert any(c[0] == "sink.span_flush_total_duration_ns"
                   for c in calls)


class TestKafkaBackpressure:
    def test_span_buffer_bound_drops_and_counts(self):
        from veneur_tpu.sinks.kafka import InMemoryProducer, KafkaSpanSink
        prod = InMemoryProducer()
        sink = KafkaSpanSink("kafka", prod, span_topic="spans",
                             max_buffered=3)
        for i in range(5):
            sink.ingest(make_span(trace_id=i + 1, span_id=1))
        assert len(prod.messages) == 3
        assert sink.dropped_total == 2
        sink.flush()  # resets the per-interval bound
        sink.ingest(make_span(trace_id=9, span_id=1))
        assert len(prod.messages) == 4


class TestFalconerDepth:
    def test_validates_and_counts(self):
        from veneur_tpu.sinks.falconer import FalconerSpanSink
        sent = []
        sink = FalconerSpanSink("falconer", sender=sent.append)
        sink.ingest(make_span(trace_id=1, span_id=2))
        sink.ingest(make_span(trace_id=0, span_id=2))  # invalid: no trace
        sink.ingest(make_span(trace_id=3, span_id=0))  # invalid: no id
        assert len(sent) == 1
        assert sink.spans_handled == 1

        def boom(span):
            raise RuntimeError("conn reset")
        sink.sender = boom
        sink.ingest(make_span(trace_id=5, span_id=6))
        assert sink.errors == 1

    def test_grpc_route_parity(self):
        from veneur_tpu.sinks.falconer import GrpcSpanSender
        # reference generated client invokes /falconer.SpanSink/SendSpan
        # (sinks/falconer/grpc_sink.pb.go:108)
        assert GrpcSpanSender.METHOD == "/falconer.SpanSink/SendSpan"


class TestNewRelicBackpressure:
    def test_span_buffer_bound(self):
        from veneur_tpu.sinks.newrelic import NewRelicSpanSink
        sink = NewRelicSpanSink("nr", insert_key="k",
                                trace_url="http://x", max_buffered=2)
        for i in range(4):
            sink.ingest(make_span(trace_id=i + 1, span_id=1))
        assert len(sink._spans) == 2
        assert sink.dropped_total == 2


class TestSpanFlushSelfMetrics:
    """Uniform span-sink flush self-metrics (reference sinks.go:58-67)."""

    class FakeStatsd:
        def __init__(self):
            self.calls = []

        def count(self, name, value, tags=None):
            self.calls.append((name, value, tuple(tags or ())))

        def gauge(self, name, value, tags=None):
            self.calls.append((name, value, tuple(tags or ())))

    class FakeServer:
        def __init__(self, statsd):
            self.statsd = statsd

    def test_splunk_emits_flush_keys(self, fake):
        from veneur_tpu.sinks.splunk import SplunkSpanSink
        statsd = self.FakeStatsd()
        sink = SplunkSpanSink("splunk", hec_address=fake.url, token="t",
                              hostname="h", max_buffer=2)
        sink.start(self.FakeServer(statsd))
        for i in range(4):
            sink.ingest(make_span(trace_id=i + 1, span_id=1))
        sink.flush()
        names = {c[0] for c in statsd.calls}
        assert "sink.spans_flushed_total" in names
        assert "sink.spans_dropped_total" in names
        assert "sink.span_flush_total_duration_ns" in names
        by = {c[0]: c for c in statsd.calls}
        assert by["sink.spans_flushed_total"][1] == 2
        assert by["sink.spans_dropped_total"][1] == 2
        assert by["sink.spans_flushed_total"][2] == ("sink:splunk",)

    def test_lightstep_emits_flush_keys(self, fake):
        from veneur_tpu.sinks.lightstep import LightStepSpanSink
        statsd = self.FakeStatsd()
        sink = LightStepSpanSink("lightstep", collector_url=fake.url,
                                 access_token="t")
        sink.start(self.FakeServer(statsd))
        sink.ingest(make_span(trace_id=1, span_id=1))
        sink.ingest(make_span(trace_id=2, span_id=2))
        sink.flush()
        by = {c[0]: c for c in statsd.calls}
        assert by["sink.spans_flushed_total"][1] == 2


class TestXRayTraceId:
    def test_same_trace_same_id_across_seconds(self):
        """Without root_start_timestamp, spans of one trace agree via the
        256 s bucket of their own starts (reference xray.go:290-306 —
        probabilistic: only spans within one bucket agree, so the test
        places both starts inside a single bucket)."""
        from veneur_tpu.sinks.xray import xray_trace_id
        a = make_span(trace_id=77, span_id=1)
        b = make_span(trace_id=77, span_id=2)
        base = 1_700_000_000 * 10**9  # 256-aligned epoch: bucket start
        a.start_timestamp = base
        b.start_timestamp = base + 5 * 10**9  # 5 s later, same bucket
        assert xray_trace_id(a) == xray_trace_id(b)
        # straddling a bucket boundary splits (documented reference
        # behavior); root_start_timestamp is the robust path
        c = make_span(trace_id=77, span_id=3)
        c.start_timestamp = base - 10**9
        assert xray_trace_id(c) != xray_trace_id(a)

    def test_root_timestamp_preferred(self):
        from veneur_tpu.sinks.xray import xray_trace_id
        s = make_span(trace_id=5, span_id=1)
        s.start_timestamp = 1_700_000_999 * 10**9
        s.root_start_timestamp = 1_700_000_000 * 10**9
        assert xray_trace_id(s).split("-")[1] == f"{1_700_000_000:08x}"


class TestSignalFxRoutingExtras:
    def test_metric_tag_prefix_drops(self, fake):
        from veneur_tpu.sinks.signalfx import SignalFxMetricSink
        sink = SignalFxMetricSink(
            "signalfx", api_key="t", endpoint=fake.url, hostname="sh",
            metric_tag_prefix_drops=["internal."])
        sink.flush([
            im("kept", 1, MetricType.GAUGE, tags=["env:prod"]),
            im("dropped", 1, MetricType.GAUGE,
               tags=["internal.debug:yes"])])
        payload = json.loads(fake.requests[0][2])
        names = {p["metric"] for kind in payload.values() for p in kind}
        assert names == {"kept"}
        assert sink.skipped_total == 1

    def test_preferred_vary_key_beats_vary_key(self, fake):
        from veneur_tpu.sinks.signalfx import SignalFxMetricSink
        sink = SignalFxMetricSink(
            "signalfx", api_key="default-tok", endpoint=fake.url,
            hostname="sh", vary_key_by="customer",
            preferred_vary_key_by="team",
            per_tag_tokens={"acme": "acme-tok", "infra": "infra-tok"})
        sink.flush([im("m1", 1, MetricType.GAUGE,
                       tags=["customer:acme", "team:infra"])])
        tok = next(v for k, v in fake.requests[0][1].items()
                   if k.lower() == "x-sf-token")
        assert tok == "infra-tok"

    def test_excluded_tag_still_routes_token(self, fake):
        """Token selection sees the full dimension set; excluded tags are
        removed only afterwards (signalfx.go:534-564)."""
        from veneur_tpu.sinks.signalfx import SignalFxMetricSink
        sink = SignalFxMetricSink(
            "signalfx", api_key="default-tok", endpoint=fake.url,
            hostname="sh", vary_key_by="customer",
            excluded_tags=["customer"],
            per_tag_tokens={"acme": "acme-tok"})
        sink.flush([im("m1", 1, MetricType.GAUGE, tags=["customer:acme"])])
        _, headers, body = fake.requests[0]
        tok = next(v for k, v in headers.items()
                   if k.lower() == "x-sf-token")
        assert tok == "acme-tok"
        dims = json.loads(body)["gauge"][0]["dimensions"]
        assert "customer" not in dims

    def test_fetch_api_keys_paginates(self):
        from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
        from urllib.parse import parse_qs, urlparse

        from veneur_tpu.sinks.signalfx import fetch_api_keys

        pages = {
            0: [{"name": "a", "secret": "s-a"},
                {"name": "b", "secret": "s-b"}],
            200: [{"name": "c", "secret": "s-c"}],
            400: [],
        }
        seen_tokens = []

        class Handler(BaseHTTPRequestHandler):
            def log_message(self, fmt, *args):
                pass

            def do_GET(self):  # noqa: N802
                q = parse_qs(urlparse(self.path).query)
                seen_tokens.append(self.headers.get("X-SF-Token"))
                body = json.dumps(
                    {"results": pages[int(q["offset"][0])]}).encode()
                self.send_response(200)
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)

        httpd = ThreadingHTTPServer(("127.0.0.1", 0), Handler)
        threading.Thread(target=httpd.serve_forever, daemon=True).start()
        try:
            url = f"http://127.0.0.1:{httpd.server_address[1]}"
            tokens = fetch_api_keys(url, "api-tok")
            assert tokens == {"a": "s-a", "b": "s-b", "c": "s-c"}
            assert set(seen_tokens) == {"api-tok"}
        finally:
            httpd.shutdown()

    def test_dynamic_keys_require_refresh_period(self):
        from veneur_tpu.config import Config, SinkConfig
        from veneur_tpu.sinks import MetricSinkTypes, register_builtin_sinks
        register_builtin_sinks()
        cfg = Config()
        cfg.apply_defaults()
        sc = SinkConfig(kind="signalfx", name="sfx", config={
            "dynamic_per_tag_api_keys_enable": True})
        with pytest.raises(ValueError, match="refresh period is unset"):
            MetricSinkTypes["signalfx"](sc, cfg)


class TestKafkaProducerConfig:
    def test_ack_and_partitioner_mapping(self):
        from veneur_tpu.sinks.kafka import ProducerConfig
        kw = ProducerConfig(require_acks="local").kafka_python_kwargs()
        assert kw["acks"] == 1
        kw = ProducerConfig(require_acks="none").kafka_python_kwargs()
        assert kw["acks"] == 0
        # unknown ack level falls back to all (kafka.go:155-158)
        kw = ProducerConfig(require_acks="bogus").kafka_python_kwargs()
        assert kw["acks"] == "all"
        kw = ProducerConfig(partitioner="random").kafka_python_kwargs()
        assert callable(kw["partitioner"])
        assert kw["partitioner"](b"k", [0, 1, 2], [1, 2]) in (1, 2)

    def test_from_config_reads_reference_keys(self):
        from veneur_tpu.sinks.kafka import ProducerConfig
        pc = ProducerConfig.from_config({
            "metric_require_acks": "local",
            "partitioner": "random",
            "retry_max": 7,
            "metric_buffer_bytes": 1024,
            "metric_buffer_messages": 50,
            "metric_buffer_frequency": "500ms",
        }, "metric")
        assert pc.require_acks == "local"
        assert pc.partitioner == "random"
        assert pc.retry_max == 7
        kw = pc.kafka_python_kwargs()
        assert kw["batch_size"] == 1024
        assert kw["linger_ms"] == 500
        assert kw["retries"] == 7
        # the reference misspells span_buffer_mesages; both spellings work
        pc2 = ProducerConfig.from_config({"span_buffer_mesages": 9}, "span")
        assert pc2.buffer_messages == 9


class TestCortexMonotonic:
    def test_counters_accumulate_across_flushes(self, fake):
        from veneur_tpu.sinks.cortex import (
            CortexMetricSink, decode_write_request)
        sink = CortexMetricSink("cortex", url=fake.url, hostname="ch",
                                convert_counters_to_monotonic=True)
        sink.flush([im("req", 3, MetricType.COUNTER, tags=["a:b"]),
                    im("g", 1, MetricType.GAUGE)])
        sink.flush([im("req", 4, MetricType.COUNTER, tags=["a:b"])])
        first = decode_write_request(
            vhttp.snappy_decode(fake.requests[0][2]))
        second = decode_write_request(
            vhttp.snappy_decode(fake.requests[1][2]))
        by_name_1 = {l["__name__"]: v for l, v, _ in first}
        by_name_2 = {l["__name__"]: v for l, v, _ in second}
        assert by_name_1["req"] == 3  # running total after first flush
        assert by_name_1["g"] == 1  # gauges pass through untouched
        assert by_name_2["req"] == 7  # 3 + 4: monotonic, not per-interval


class TestCloudWatchUnitTag:
    def test_unit_tag_sets_unit_and_drops_dimension(self, fake):
        from veneur_tpu.sinks.cloudwatch import CloudWatchMetricSink
        sink = CloudWatchMetricSink("cloudwatch", endpoint=fake.url + "/",
                                    namespace="ns")
        sink.flush([im("cw.t", 1.0, MetricType.GAUGE,
                       tags=["cloudwatch_standard_unit:Seconds",
                             "az:us-1a", "illegal-no-colon"])])
        params = dict(urllib.parse.parse_qsl(fake.requests[0][2].decode()))
        assert params["MetricData.member.1.Unit"] == "Seconds"
        dims = {v for k, v in params.items() if "Dimensions" in k}
        assert "cloudwatch_standard_unit" not in dims
        assert "illegal-no-colon" not in dims
        assert params["MetricData.member.1.Dimensions.member.1.Name"] == "az"


class TestSplunkBatching:
    def test_batch_size_splits_bodies(self, fake):
        from veneur_tpu.sinks.splunk import SplunkSpanSink
        sink = SplunkSpanSink("splunk", hec_address=fake.url, token="t",
                              hostname="h", batch_size=2,
                              submission_workers=3)
        for tid in range(1, 6):
            sink.ingest(make_span(trace_id=tid))
        sink.flush()
        assert len(fake.requests) == 3  # ceil(5/2)
        total = sum(len(b.splitlines()) for _, _, b in fake.requests)
        assert total == 5


class TestLightstepMaxSpans:
    def test_maximum_spans_bounds_buffer(self, fake):
        from veneur_tpu.sinks.lightstep import LightStepSpanSink
        sink = LightStepSpanSink("ls", access_token="t",
                                 collector_url=fake.url,
                                 maximum_spans=3)
        for sid in range(10):
            sink.ingest(make_span(trace_id=1, span_id=sid + 1))
        assert sink.dropped_total == 7
        sink.flush()
        payload = json.loads(fake.requests[0][2])
        spans = payload["resourceSpans"][0]["scopeSpans"][0]["spans"]
        assert len(spans) == 3


class TestNewRelicEvents:
    def test_service_checks_become_custom_events(self, fake):
        from veneur_tpu.sinks.newrelic import NewRelicMetricSink
        sink = NewRelicMetricSink(
            "nr", insert_key="k", hostname="nh", interval=10.0,
            metric_url=fake.url + "/metric", account_id=42,
            event_url=fake.url + "/events")
        sink.flush([im("svc.up", 2, MetricType.STATUS, tags=["env:prod"]),
                    im("g", 1, MetricType.GAUGE)])
        by_path = {p: json.loads(b) for p, _, b in fake.requests}
        events = by_path["/events"]
        assert events[0]["eventType"] == "veneurCheck"
        assert events[0]["status"] == "CRITICAL"
        assert events[0]["statusCode"] == 2
        assert events[0]["env"] == "prod"
        metrics = by_path["/metric"][0]["metrics"]
        assert [m["name"] for m in metrics] == ["g"]

    def test_dogstatsd_events_flush_with_event_type(self, fake):
        from veneur_tpu.samplers.parser import Event
        from veneur_tpu.sinks.newrelic import NewRelicMetricSink
        sink = NewRelicMetricSink(
            "nr", insert_key="k", hostname="nh", interval=10.0,
            metric_url=fake.url + "/metric", event_type="myEvents",
            event_url=fake.url + "/events")
        sink.flush_other_samples([
            Event(name="deploy", message="done", timestamp=5,
                  tags={"env": "prod"})])
        events = json.loads(fake.requests[0][2])
        assert events[0]["eventType"] == "myEvents"
        assert events[0]["name"] == "deploy"
        assert events[0]["env"] == "prod"

    def test_events_dropped_without_account(self, fake):
        from veneur_tpu.sinks.newrelic import NewRelicMetricSink
        sink = NewRelicMetricSink(
            "nr", insert_key="k", hostname="nh", interval=10.0,
            metric_url=fake.url + "/metric")
        sink.flush([im("svc.up", 0, MetricType.STATUS)])
        # no event endpoint configured: nothing POSTed anywhere
        assert fake.requests == []
