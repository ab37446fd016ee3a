"""Live query plane + alert engine (the `query` marker).

The consistency contract under pin: a `/query` taken between flushes
returns values BIT-IDENTICAL to evaluating the same readout kernels on
the subsequent flush's captured generation restricted to the same rows
— single-device AND mesh, across a capacity-resize boundary, and with
concurrent ingest to other rows.
`ledger_strict` stays green throughout (a query moves no samples, so it
must not perturb conservation).

The alert engine's state machines (idle -> pending -> firing ->
resolved with `for:` hold-down), flight-recorder `alert_transition`
events, log rate limiting, and SIGHUP-shaped hot reload are pinned
here too, plus the HTTP surface (/query, /alerts, ?kind= event
filtering, http.route.* rows).
"""

from __future__ import annotations

import json
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

from veneur_tpu.config import Config
from veneur_tpu.core.query import QueryError, QuerySpec, parse_tags
from veneur_tpu.core.server import Server
from veneur_tpu.sinks.channel import ChannelMetricSink

pytestmark = pytest.mark.query


def corpus(round_no: int = 0):
    lines = []
    for i in range(8):
        lines.append(b"c.%d:%d|c|#env:t" % (i, i + 1 + round_no))
        lines.append(b"g.%d:%.2f|g" % (i, i * 1.5 + round_no))
        lines.append(b"t.%d:%.2f|ms" % (i, 10.0 + i + round_no))
        lines.append(b"t.%d:%.2f|ms" % (i, 40.0 + i))
        lines.append(b"s.%d:m%d|s" % (i, i))
        lines.append(b"s.%d:m%d|s" % (i, i + 50 + round_no))
        lines.append(b"ll.%d:%.2f|l" % (i, 3.0 + i + round_no))
    return lines


def mk_server(**kw):
    cfg = Config()
    cfg.interval = 60.0
    cfg.hostname = "test"
    cfg.statsd_listen_addresses = []
    cfg.tpu.counter_capacity = 128
    cfg.tpu.gauge_capacity = 128
    cfg.tpu.histo_capacity = 128
    cfg.tpu.set_capacity = 64
    cfg.tpu.llhist_capacity = 64
    cfg.tpu.batch_cap = 512
    cfg.ledger_strict = True
    for k, v in kw.items():
        if "." in k:
            ns, field = k.split(".", 1)
            setattr(getattr(cfg, ns), field, v)
        else:
            setattr(cfg, k, v)
    cfg.apply_defaults()
    obs = ChannelMetricSink()
    return Server(cfg, extra_metric_sinks=[obs]), obs


def _feed(server, lines):
    for line in lines:
        server.handle_metric_packet(line)
    server.store.apply_all_pending()


def _q(server, metric, kind, **kw):
    return server.query_plane.query(
        QuerySpec.build(metric=metric, kind=kind, **kw))


def _flushed(metrics):
    """{(name, sorted tags): value} for exact-equality lookups."""
    return {(m.name, tuple(sorted(m.tags))): float(m.value)
            for m in metrics}


def _assert_queries_match_flush(queries: dict, flushed: dict):
    """The pin itself: every pre-flush query value equals (==, not
    approx — the kernels are the same, so the floats must be the same
    bits) the next flush's reading of the same row."""
    for label, (fname, ftags, qval) in queries.items():
        assert (fname, ftags) in flushed, \
            f"{label}: {fname}{ftags} missing from flush output"
        got = flushed[(fname, ftags)]
        assert qval == got, f"{label}: query {qval!r} != flush {got!r}"


def _query_all(server):
    """One query per family against the fixed corpus; returns
    {label: (flush_name, flush_tags, query_value)} for the pin."""
    return {
        "t50": ("t.0.50percentile", (),
                _q(server, "t.0", "quantile", q=0.5)["value"]),
        "t99": ("t.0.99percentile", (),
                _q(server, "t.0", "quantile", q=0.99)["value"]),
        "ll50": ("ll.0.50percentile", (),
                 _q(server, "ll.0", "quantile", q=0.5)["value"]),
        "count": ("c.0", ("env:t",),
                  _q(server, "c.0", "count",
                     tags=parse_tags("env:t"))["value"]),
        "gauge": ("g.0", (), _q(server, "g.0", "value")["value"]),
        "card": ("s.0", (), _q(server, "s.0", "cardinality")["value"]),
    }


class TestQueryConsistency:
    def test_query_matches_next_flush_single_device(self):
        """The base pin: queries between flushes == the next flush's
        readout of the same generation, all five families, exact."""
        server, obs = mk_server()
        try:
            _feed(server, corpus())
            queries = _query_all(server)
            # staleness is surfaced, and zero once pending is applied
            r = _q(server, "c.0", "count", tags=parse_tags("env:t"))
            assert r["stale_pending_samples"] == 0
            assert r["matched_rows"] == 1
            server.flush()  # ledger_strict: raises on any perturbation
            _assert_queries_match_flush(queries, _flushed(obs.drain()))
        finally:
            server.config.flush_on_shutdown = False
            server.shutdown()

    @pytest.mark.mesh
    def test_query_matches_next_flush_on_mesh(self):
        """Same pin over the sharded mesh store: the query path runs
        the NON-reset collective merges, which must reduce with the
        exact same expressions as the flush's fused donating merges."""
        server, obs = mk_server(**{"tpu.shards": 2})
        assert server.store.shard_plane is not None, "virtual mesh missing"
        try:
            _feed(server, corpus())
            queries = _query_all(server)
            server.flush()
            _assert_queries_match_flush(queries, _flushed(obs.drain()))
            # and the query left the live mesh state intact: a second
            # interval ingests + flushes cleanly (ledger_strict)
            _feed(server, corpus(round_no=3))
            _query_all(server)
            server.flush()
            assert obs.drain()
        finally:
            server.config.flush_on_shutdown = False
            server.shutdown()

    def test_query_across_resize_boundary(self):
        """Growing a family past its capacity rung mid-interval must
        leave the query plane consistent: queries after the resize
        match the next flush over the resized generation."""
        server, obs = mk_server(**{"tpu.histo_capacity": 32})
        try:
            _feed(server, corpus())
            before = _q(server, "t.0", "quantile", q=0.5)["value"]
            # blow through the 32-row rung with distinct histo keys
            _feed(server, [b"resize.%d:%d|ms" % (i, i)
                           for i in range(64)])
            assert server.store.histos.capacity > 32
            after = _q(server, "t.0", "quantile", q=0.5)
            # t.0 saw no new samples: the resize itself must not move it
            assert after["value"] == before
            queries = _query_all(server)
            server.flush()
            _assert_queries_match_flush(queries, _flushed(obs.drain()))
        finally:
            server.config.flush_on_shutdown = False
            server.shutdown()

    def test_query_with_concurrent_ingest(self):
        """Readers race ingest to OTHER rows: queries stay exact for
        the rows they match (the capture is consistent), and the final
        pre-flush values still equal the flush readout."""
        server, obs = mk_server()
        try:
            _feed(server, corpus())
            stop = threading.Event()
            errors = []

            def _ingest():
                i = 0
                while not stop.is_set():
                    server.handle_metric_packet(
                        b"other.%d:1|c" % (i % 16))
                    i += 1

            def _read():
                while not stop.is_set():
                    try:
                        _q(server, "t.0", "quantile", q=0.5)
                        _q(server, "c.0", "count",
                           tags=parse_tags("env:t"))
                    except Exception as e:  # pragma: no cover
                        errors.append(e)
                        return

            threads = [threading.Thread(target=_ingest)] + \
                [threading.Thread(target=_read) for _ in range(3)]
            for t in threads:
                t.start()
            time.sleep(0.8)
            stop.set()
            for t in threads:
                t.join(5.0)
                assert not t.is_alive()
            assert not errors
            server.store.apply_all_pending()
            queries = _query_all(server)
            server.flush()  # ledger_strict: concurrent reads cost nothing
            _assert_queries_match_flush(queries, _flushed(obs.drain()))
        finally:
            server.config.flush_on_shutdown = False
            server.shutdown()

    def test_tag_filter_and_errors(self):
        server, obs = mk_server()
        try:
            _feed(server, [b"m:1|c|#env:prod,svc:a", b"m:2|c|#env:dev"])
            prod = _q(server, "m", "count", tags=parse_tags("env:prod"))
            assert prod["matched_rows"] == 1 and prod["value"] == 1.0
            both = _q(server, "m", "count")
            assert both["matched_rows"] == 2 and both["value"] == 3.0
            with pytest.raises(QueryError):
                QuerySpec.build(metric="", kind="count")
            with pytest.raises(QueryError):
                QuerySpec.build(metric="m", kind="nope")
            with pytest.raises(QueryError):
                QuerySpec.build(metric="m", kind="quantile")  # no q
            with pytest.raises(QueryError):
                QuerySpec.build(metric="m", kind="bin_occupancy",
                                lo=2.0, hi=1.0)
        finally:
            server.config.flush_on_shutdown = False
            server.shutdown()


class TestAlertEngine:
    def test_lifecycle_pending_firing_resolved(self):
        """The full state machine with a `for:` hold-down, plus the
        flight-recorder trail: every transition is an alert_transition
        event stamped with the interval trace id."""
        server, obs = mk_server()
        try:
            _feed(server, corpus())
            server.alerts.configure([
                {"id": "hits", "metric": "c.0", "kind": "count",
                 "op": ">", "threshold": 0.5, "for": "0.2s",
                 "tags": "env:t"},
            ])
            now = time.time()
            trs = server.alerts.evaluate_once(now=now)
            assert [(t["from_state"], t["to_state"]) for t in trs] == \
                [("idle", "pending")]
            # hold-down not yet satisfied
            assert server.alerts.evaluate_once(now=now + 0.1) == []
            trs = server.alerts.evaluate_once(now=now + 0.3)
            assert [(t["from_state"], t["to_state"]) for t in trs] == \
                [("pending", "firing")]
            rep = server.alerts.report()
            assert rep["rules"][0]["state"] == "firing"
            assert rep["rules"][0]["value"] == 1.0
            server.flush()  # resets the counter generation
            trs = server.alerts.evaluate_once(now=now + 0.5)
            assert [(t["from_state"], t["to_state"]) for t in trs] == \
                [("firing", "resolved")]
            events = server.telemetry.events.snapshot(
                kind="alert_transition")
            assert [e["to_state"] for e in events] == \
                ["pending", "firing", "resolved"]
            assert all(e["rule"] == "hits" for e in events)
            assert all(e.get("trace_id") for e in events)
            # state machine rows export
            rows = {r[0] for r in server.alerts.telemetry_rows()}
            assert {"alert.rules", "alert.state", "alert.firing",
                    "alert.evals_total",
                    "alert.transitions_total"} <= rows
        finally:
            server.config.flush_on_shutdown = False
            server.shutdown()

    def test_hot_reload_preserves_surviving_state(self):
        server, obs = mk_server()
        try:
            _feed(server, corpus())
            server.alerts.configure([
                {"id": "a", "metric": "c.0", "kind": "count",
                 "op": ">", "threshold": 0.0, "tags": "env:t"},
                {"id": "b", "metric": "g.0", "kind": "value",
                 "op": ">", "threshold": 1e9},
            ])
            server.alerts.evaluate_once()
            assert server.alerts.report()["rules"][0]["state"] == "firing"
            # reload: keep `a`, drop `b`, add `c` — a's firing survives
            n = server.alerts.configure([
                {"id": "a", "metric": "c.0", "kind": "count",
                 "op": ">", "threshold": 0.0, "tags": "env:t"},
                {"id": "c", "metric": "s.0", "kind": "cardinality",
                 "op": ">=", "threshold": 1.0},
            ])
            assert n == 2
            rep = {r["id"]: r for r in server.alerts.report()["rules"]}
            assert rep["a"]["state"] == "firing"
            assert rep["c"]["state"] == "idle"
            assert "b" not in rep
            # a bad reload raises and keeps the table
            with pytest.raises(QueryError):
                server.alerts.configure([{"id": "x", "metric": "m",
                                          "kind": "count", "op": "~",
                                          "threshold": 1}])
            assert {r["id"] for r in
                    server.alerts.report()["rules"]} == {"a", "c"}
            # the server-level reload path records the event
            server.reload_alerts()
            assert server.telemetry.events.snapshot(kind="alerts_reload")
        finally:
            server.config.flush_on_shutdown = False
            server.shutdown()

    def test_transition_log_rate_limit(self):
        """First transition per rule per flush interval is logged, the
        rest within the same interval only count (events still land)."""
        server, obs = mk_server()
        try:
            _feed(server, corpus())
            server.alerts.configure([
                {"id": "flap", "metric": "c.0", "kind": "count",
                 "op": ">", "threshold": 0.5, "tags": "env:t"},
            ])
            now = time.time()
            server.alerts.evaluate_once(now=now)        # -> firing
            # force a clear without a flush: flap the threshold via a
            # reload (state survives, threshold now unreachable)
            server.alerts.configure([
                {"id": "flap", "metric": "c.0", "kind": "count",
                 "op": ">", "threshold": 1e9, "tags": "env:t"},
            ])
            server.alerts.evaluate_once(now=now + 0.1)  # -> resolved
            assert server.alerts.suppressed_logs_total == 1
            events = server.telemetry.events.snapshot(
                kind="alert_transition")
            assert len(events) == 2  # the recorder is never suppressed
        finally:
            server.config.flush_on_shutdown = False
            server.shutdown()

    def test_config_block_and_rule_validation(self):
        from veneur_tpu.config import AlertsConfig
        cfg = AlertsConfig(interval="500ms", rules=[
            {"id": "r1", "metric": "m", "kind": "quantile", "q": 0.99,
             "op": ">", "threshold": 100, "for": "30s"}])
        assert cfg.interval == 0.5
        server, obs = mk_server()
        try:
            n = server.alerts.configure(cfg.rules, interval_s=cfg.interval)
            assert n == 1 and server.alerts.interval_s == 0.5
            rule = server.alerts.report()["rules"][0]
            assert rule["for_s"] == 30.0 and rule["q"] == 0.99
            with pytest.raises(QueryError):  # duplicate ids
                server.alerts.configure([
                    {"id": "d", "metric": "m", "kind": "count",
                     "threshold": 1},
                    {"id": "d", "metric": "m2", "kind": "count",
                     "threshold": 1}])
        finally:
            server.config.flush_on_shutdown = False
            server.shutdown()


class TestHTTPSurface:
    def test_query_alerts_routes_and_route_latency(self):
        from veneur_tpu.core.httpapi import HTTPApi
        server, obs = mk_server()
        api = None
        try:
            _feed(server, corpus())
            server.alerts.configure([
                {"id": "hits", "metric": "c.0", "kind": "count",
                 "op": ">", "threshold": 0.5, "tags": "env:t"}])
            server.alerts.evaluate_once()
            api = HTTPApi(server.config, server=server,
                          address="127.0.0.1:0")
            api.start()
            host, port = api.address

            def get(path):
                try:
                    with urllib.request.urlopen(
                            f"http://{host}:{port}{path}", timeout=10) as r:
                        return r.status, r.read()
                except urllib.error.HTTPError as e:
                    return e.code, e.read()

            status, body = get("/query?metric=c.0&kind=count&tags=env:t")
            assert status == 200
            payload = json.loads(body)
            assert payload["value"] == 1.0
            assert payload["kind"] == "count"
            status, body = get(
                "/query?metric=t.0&kind=percentile&q=0.5")
            assert status == 200 and json.loads(body)["value"] is not None
            status, body = get("/query?kind=count")  # no metric
            assert status == 400 and b"metric" in body
            status, body = get("/alerts")
            assert status == 200
            rep = json.loads(body)
            assert rep["rules"][0]["id"] == "hits"
            assert rep["rules"][0]["state"] == "firing"
            # ?kind= filtering on the flight recorder
            status, body = get("/debug/events?kind=alert_transition")
            assert status == 200
            events = json.loads(body)["events"]
            assert events and all(e["kind"] == "alert_transition"
                                  for e in events)
            # every route above landed in the per-route llhists
            status, body = get("/metrics")
            assert status == 200
            text = body.decode()  # prometheus-mangled names
            assert "veneur_http_route_count_total" in text
            assert 'path="/query"' in text
            assert "veneur_query_requests_total" in text
            assert "veneur_alert_rules" in text
        finally:
            if api is not None:
                api.stop()
            server.config.flush_on_shutdown = False
            server.shutdown()


@pytest.mark.slow
class TestOverheadSoak:
    def test_alert_and_reader_overhead_bounded(self):
        """The acceptance soak: a 1 Hz alert evaluation over 64 rules
        plus 8 concurrent /query readers must cost <2% of flush wall
        time and leave the rounds' `duration_s` p99 unmoved."""
        server, obs = mk_server()
        try:
            rules = []
            for i in range(8):
                rules += [
                    {"id": f"c{i}", "metric": f"c.{i}", "kind": "count",
                     "op": ">", "threshold": 1e9, "tags": "env:t"},
                    {"id": f"r{i}", "metric": f"c.{i}", "kind": "rate",
                     "op": ">", "threshold": 1e9, "tags": "env:t"},
                    {"id": f"g{i}", "metric": f"g.{i}", "kind": "value",
                     "op": ">", "threshold": 1e9},
                    {"id": f"t{i}", "metric": f"t.{i}",
                     "kind": "quantile", "q": 0.99, "op": ">",
                     "threshold": 1e9},
                    {"id": f"l{i}", "metric": f"ll.{i}",
                     "kind": "quantile", "q": 0.5, "op": ">",
                     "threshold": 1e9},
                    {"id": f"s{i}", "metric": f"s.{i}",
                     "kind": "cardinality", "op": ">", "threshold": 1e9},
                    {"id": f"b{i}", "metric": f"ll.{i}",
                     "kind": "bin_occupancy", "lo": 0.0, "hi": 100.0,
                     "op": ">", "threshold": 2.0},
                    {"id": f"q{i}", "metric": f"t.{i}",
                     "kind": "quantile", "q": 0.5, "op": ">",
                     "threshold": 1e9},
                ]
            assert len(rules) == 64
            server.alerts.configure(rules, interval_s=1.0)

            def flush_round(n, round0):
                walls = []
                for k in range(n):
                    _feed(server, corpus(round_no=round0 + k))
                    t0 = time.perf_counter()
                    server.flush()
                    walls.append(time.perf_counter() - t0)
                rounds = server.telemetry.flushes.snapshot()[-n:]
                return walls, [float(ri["duration_s"]) for ri in rounds]

            # warmup (kernel compiles must not pollute either side)
            flush_round(2, 0)
            base_walls, base_durs = flush_round(6, 10)

            stop = threading.Event()
            errors = []

            def _reader():
                while not stop.is_set():
                    try:
                        _q(server, "t.0", "quantile", q=0.5)
                    except QueryError:
                        pass  # post-swap empty generation: fine
                    except Exception as e:  # pragma: no cover
                        errors.append(e)
                        return
                    time.sleep(0.01)

            def _alert_tick():
                while not stop.is_set():
                    try:
                        server.alerts.evaluate_once()
                    except Exception:
                        pass
                    stop.wait(1.0)  # the 1 Hz cadence under test

            threads = [threading.Thread(target=_reader)
                       for _ in range(8)]
            threads.append(threading.Thread(target=_alert_tick))
            for t in threads:
                t.start()
            try:
                loaded_walls, loaded_durs = flush_round(6, 30)
            finally:
                stop.set()
                for t in threads:
                    t.join(10.0)
                    assert not t.is_alive()
            assert not errors

            base = float(np.mean(base_walls))
            loaded = float(np.mean(loaded_walls))
            # <2% of flush wall, with an absolute floor for CI jitter
            assert loaded - base <= 0.02 * base + 0.25, \
                f"flush wall moved: base={base:.3f}s loaded={loaded:.3f}s"
            bp99 = float(np.percentile(base_durs, 99))
            lp99 = float(np.percentile(loaded_durs, 99))
            assert lp99 <= bp99 * 1.02 + 0.25, \
                f"duration_s p99 moved: {bp99:.3f} -> {lp99:.3f}"
        finally:
            server.config.flush_on_shutdown = False
            server.shutdown()
