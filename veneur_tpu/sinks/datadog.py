"""Datadog sink: metrics, events, service checks, and APM spans.

Behavioral parity with reference sinks/datadog/datadog.go (660 LoC):
- InterMetrics serialize to DDMetric JSON; counters convert to Datadog
  "rate" (value/interval) (datadog.go DDMetric conversion), gauges stay
  gauges, status checks go to /api/v1/check_run.
- A flush is chunked across `flush_max_per_body` and POSTed in parallel
  (reference: a goroutine a chunk, datadog.go:182-207), by as many POST
  workers as the flush keeps busy, up to `datadog_num_workers` or, where
  that is not set, the cores this process may run on less one
  (`host_post_workers`). `Config.num_workers` has no say here.
- `device:` / `host:` magic tags move into dedicated DDMetric fields.
- Events (from flush_other_samples) post to the events intake.
- Spans buffer in a bounded ring (2^14, reference datadog.go spanBuffer)
  and flush to the APM traces endpoint.
"""

from __future__ import annotations

import collections
import json
import logging
import os
import queue
import threading
import time
from typing import Any, Dict, List, Optional, Sequence

from veneur_tpu.core.telemetry import FlushRound, current_round
from veneur_tpu.samplers.metrics import InterMetric, MetricType
from veneur_tpu.sinks import (
    MetricSink, SpanSink, register_metric_sink, register_span_sink,
)
from veneur_tpu.util import http as vhttp

logger = logging.getLogger("veneur_tpu.sinks.datadog")

DATADOG_SPAN_BUFFER_CAP = 1 << 14  # reference datadog.go datadogSpanBufferSize


def host_post_workers() -> int:
    """The most POST workers a flush runs where `datadog_num_workers`
    does not say: the cores this process may run on, less one for the
    sink thread that encodes beside them, at least one (a one- or
    two-core sidecar gets one worker)."""
    try:
        cores = len(os.sched_getaffinity(0))
    except (AttributeError, OSError):   # not on this platform
        cores = os.cpu_count() or 1
    return max(cores - 1, 1)


class DatadogMetricSink(MetricSink):
    def __init__(self, name: str, api_key: str, api_url: str, hostname: str,
                 interval: float, flush_max_per_body: int = 25_000,
                 num_workers: Optional[int] = None,
                 tags: Sequence[str] = (),
                 metric_name_prefix_drops: Sequence[str] = (),
                 excluded_tag_prefixes: Sequence[str] = (),
                 exclude_tags_prefix_by_prefix_metric: Dict[str, Sequence[str]] = None,
                 timeout: float = 10.0):
        self._name = name
        self.api_key = api_key
        self.api_url = api_url.rstrip("/")
        self.hostname = hostname
        self.interval = max(interval, 1e-9)
        self.flush_max_per_body = flush_max_per_body
        # the cap on a flush's POST workers; how many start is the
        # flush's own matter (`_BodyPosts.hand_off`)
        self.num_workers = (max(int(num_workers), 1) if num_workers
                            else host_post_workers())
        self.tags = list(tags)
        # reference datadog.go:313-317: drop whole metrics by name prefix
        self.metric_name_prefix_drops = list(metric_name_prefix_drops)
        # reference datadog.go:345-352: drop tags by prefix, globally
        self.excluded_tag_prefixes = list(excluded_tag_prefixes)
        # reference datadog.go:323-331: per-metric-prefix tag exclusion
        self.exclude_tags_prefix_by_prefix_metric = dict(
            exclude_tags_prefix_by_prefix_metric or {})
        self.timeout = timeout
        # built here, with the native library it loads (and compiles on
        # a fresh tree): before the server is ready, never in a flush
        from veneur_tpu.core.egress import DatadogColumnarEncoder
        self._encoder = DatadogColumnarEncoder(self)

    def name(self) -> str:
        return self._name

    def kind(self) -> str:
        return "datadog"

    # -- serialization ----------------------------------------------------

    def _dd_metric(self, m: InterMetric) -> Dict[str, Any]:
        tags = list(self.tags)
        host = m.hostname or self.hostname
        device = ""
        per_metric_excludes: Sequence[str] = ()
        for prefix, excludes in self.exclude_tags_prefix_by_prefix_metric.items():
            if m.name.startswith(prefix):
                per_metric_excludes = excludes
                break
        for t in m.tags:
            if t.startswith("host:"):
                host = t[5:]
            elif t.startswith("device:"):
                device = t[7:]
            elif (any(t.startswith(p) for p in self.excluded_tag_prefixes)
                  or any(t.startswith(p) for p in per_metric_excludes)):
                continue
            else:
                tags.append(t)
        if m.type == MetricType.COUNTER:
            # Datadog rate: counts divide by the flush interval
            dd_type, value = "rate", m.value / self.interval
        else:
            dd_type, value = "gauge", m.value
        out = {
            "metric": m.name,
            "points": [[m.timestamp, value]],
            "type": dd_type,
            "host": host,
            "interval": int(self.interval) or 1,
            "tags": tags,
        }
        if device:
            out["device"] = device
        return out

    # -- flush ------------------------------------------------------------

    def flush(self, metrics: List[InterMetric]) -> None:
        """The legacy flush, of materialised `InterMetric`s: what a
        routed, filtered or spilled interval takes, and `flush_batch`'s
        fallback. Nothing overlaps: this thread scans the list into
        series (`egress_encode`, `encoder=legacy`), dumps every
        `flush_max_per_body` of them into a body (`egress_join`), and
        only then are the bodies gzipped and posted (`egress_post_wall`
        around `_post_parallel`; per body `egress_gzip`, `egress_http`),
        by up to `self.num_workers` threads, this one among them. The spans
        are the columnar flush's, by name, timed into the round whose
        sink thread this is (`telemetry.current_round`)."""
        rnd = current_round.get() or FlushRound()
        t0 = time.perf_counter()
        # single encode pass: name-prefix drop, status split, and
        # series conversion fold into one scan of the metric list
        drops = self.metric_name_prefix_drops
        checks: List[InterMetric] = []
        series: List[dict] = []
        with rnd.phase("egress_encode", parent="sink",
                       encoder="legacy") as encode:
            for m in metrics:
                if drops and any(m.name.startswith(p) for p in drops):
                    continue
                if m.type == MetricType.STATUS:
                    checks.append(m)
                else:
                    series.append(self._dd_metric(m))
        bodies: List[bytes] = []
        for i in range(0, len(series), self.flush_max_per_body):
            with rnd.phase("egress_join", parent="sink"):
                bodies.append(json.dumps(
                    {"series": series[i:i + self.flush_max_per_body]},
                    separators=(",", ":")).encode())
        t1 = time.perf_counter()
        if bodies:
            posts = _BodyPosts(self, rnd)
            posts.open_wall()
            self._post_parallel(bodies, posts.post)
            posts.close_wall(encode)
        self._post_checks(checks)
        self.note_egress(t1 - t0, time.perf_counter() - t1,
                         encoder="legacy")

    def flush_batch(self, batch) -> None:
        """`flush_columnar`, with the legacy `flush(materialize())` as
        the fallback while that is safe: only if the columnar flush
        failed before it handed a body to a POST worker. After a
        hand-off (`SeriesPartlySent`) a fallback would post those series
        twice in one flush, so the error is raised instead and the flush
        counts as failed: the sink's breaker, and the server's spill,
        which offers a failed interval once more with the next one (the
        bodies that did leave then arrive again, same timestamps and
        values)."""
        try:
            self.flush_columnar(batch)
        except SeriesPartlySent:
            raise
        except Exception:
            logger.exception("datadog columnar flush failed; "
                             "falling back to materialize()")
            self.flush(batch.materialize())

    def flush_columnar(self, batch) -> None:
        """Columnar fast path: pre-encoded JSON series parts straight
        from the FlushBatch arrays (core/egress.py), gzip-POSTed as raw
        bodies — no per-InterMetric dicts, no json.dumps of the flush.

        A pipeline: this thread encodes, and each time
        `flush_max_per_body` parts are ready and more are to come they
        go to a POST worker, which joins, gzips and sends that body
        while this thread encodes the next. A worker starts only when a
        body is ready and every running worker has one, so the flush
        runs as many as gzip and POST take beside the encode, and never
        more than `self.num_workers`: `datadog_num_workers`, or else
        the host's cores less one (`host_post_workers`). A flush of one
        body starts no thread and sends it from here. Returns after the
        last body was answered (or failed and was logged) and the checks
        were posted; no worker outlives it.

        An error before any hand-off is raised as it is (`flush_batch`
        falls back); after one, the workers are waited for and
        `SeriesPartlySent` is raised: no series is posted twice.

        The encode is the native encoder's where its library could be
        had (`encoder` in the round's `sinks.<key>`, beside the series
        it wrote and the rows it had to look up in Python), and the
        hand-off then carries a body's few chunks, not a part a series.
        Either way the series put into bodies are counted against the
        batch's rows (`count_mismatch`, logged as an error when not 0).

        Timed into the round that delivers the batch (`batch.timing`):
        `egress_encode` here; per body `egress_join`, `egress_gzip` and
        `egress_http` on whichever thread sends it; `egress_post_wall`
        from the first hand-off to the last answer (it overlaps the
        encode; `bodies_overlapped` counts the bodies whose gzip began
        before the encode ended, `workers` the POST workers started and
        `peak_in_flight` the most bodies in gzip or POST at once);
        `egress_post_tail` from the end of the encode to the last
        answer, the part of the send that this thread still waits for.
        With more than one worker the per-body spans overlap: their sum
        is what the send cost, not how long it took."""
        rnd = batch.timing
        enc = self._encoder
        posts = _BodyPosts(self, rnd)
        try:
            with rnd.phase("egress_encode", parent="sink",
                           encoder=enc.name) as encode:
                rest, checks = enc.encode_bodies(
                    batch, self.flush_max_per_body, posts.hand_off)
                # the flush checks its own count: every row of the
                # batch was put into a body or renders to none
                mismatch = abs(len(batch) - enc.series_skipped
                               - enc.series_written)
                encode.update(native_rows=enc.native_rows,
                              prefix_renders=enc.prefix_renders,
                              count_mismatch=mismatch)
            if mismatch:
                logger.error(
                    "datadog encode wrote %d series of a batch of %d "
                    "(%d of them render to none)", enc.series_written,
                    len(batch), enc.series_skipped)
        except Exception as e:
            if not posts.workers:
                raise
            posts.finish(encode, [])
            raise SeriesPartlySent(
                f"encode failed after {len(posts.sent)} bodies "
                "were handed to the POST workers") from e
        tail = posts.finish(encode, rest)
        if posts.errors:
            raise SeriesPartlySent(
                f"{len(posts.errors)} of {len(posts.sent)} bodies failed "
                "on POST workers " + ", ".join(
                    sorted({worker for worker, _ in posts.errors}))
            ) from posts.errors[0][1]
        self._post_checks(checks)
        statsd = getattr(self, "_statsd", None)
        if statsd is not None:
            tags = [f"sink:{self._name}"]
            statsd.count("sink.datadog.encode.native_rows",
                         enc.native_rows, tags=tags)
            statsd.count("sink.datadog.encode.prefix_renders",
                         enc.prefix_renders, tags=tags)
            statsd.count("sink.datadog.encode.count_mismatch",
                         mismatch, tags=tags)
            statsd.count("sink.datadog.post.workers", len(posts.workers),
                         tags=tags)
        self.note_egress(encode["wall_s"], tail, encoder=enc.name)

    def _post_parallel(self, chunks, post_one) -> None:
        """The legacy flush's send: every body exists before the first
        leaves, and nothing here overlaps the encode. Concurrency capped
        at `self.num_workers` POSTs, this thread one of them (reference
        datadog.go:182-207: a goroutine a chunk)."""
        it = iter(chunks)

        def worker():
            while True:
                try:
                    chunk = next(it)
                except StopIteration:
                    return
                post_one(chunk)

        threads = [threading.Thread(target=worker, daemon=True)
                   for _ in range(min(self.num_workers, len(chunks)) - 1)]
        for t in threads:
            t.start()
        worker()
        for t in threads:
            t.join()

    def _post_checks(self, checks: List[InterMetric]) -> None:
        for check in checks:
            self._post_safe("/api/v1/check_run", {
                "check": check.name,
                "host_name": check.hostname or self.hostname,
                "status": int(check.value),
                "message": check.message,
                "timestamp": check.timestamp,
                "tags": list(self.tags) + list(check.tags),
            })

    def _post_series_body_safe(self, body: bytes, phase=None) -> None:
        url = f"{self.api_url}/api/v1/series?api_key={self.api_key}"
        try:
            vhttp.post(url, body, compress="gzip", timeout=self.timeout,
                       phase=phase)
        except Exception as e:
            logger.error("datadog POST /api/v1/series failed: %s", e)

    def _post_safe(self, path: str, payload: dict) -> None:
        url = f"{self.api_url}{path}?api_key={self.api_key}"
        try:
            vhttp.post_json(url, payload, compress="gzip",
                            timeout=self.timeout)
        except Exception as e:
            logger.error("datadog POST %s failed: %s", path, e)

    # -- events / service checks -----------------------------------------

    def flush_other_samples(self, samples: Sequence[Any]) -> None:
        """DogStatsD events -> the nonpublic events intake (reference
        datadog.go FlushOtherSamples)."""
        events = []
        for s in samples:
            tags = dict(getattr(s, "tags", {}) or {})
            events.append({
                "title": getattr(s, "name", ""),
                "text": getattr(s, "message", ""),
                "date_happened": getattr(s, "timestamp", 0),
                "hostname": tags.pop("host", self.hostname),
                "aggregation_key": tags.pop("aggregation_key", ""),
                "priority": tags.pop("priority", "normal"),
                "source_type_name": tags.pop("source_type_name", ""),
                "alert_type": tags.pop("alert_type", "info"),
                "tags": [f"{k}:{v}" if v else k for k, v in tags.items()]
                + list(self.tags),
            })
        if events:
            self._post_safe("/intake", {"events": {self._name: events}})


class SeriesPartlySent(Exception):
    """A columnar flush failed after some of its bodies had gone to the
    POST workers: posting the batch again would post their series
    twice."""


class _BodyPosts:
    """The sending half of one `flush_columnar`: bodies handed off as
    lists of parts, each joined, gzipped and posted by one of up to
    `sink.num_workers` POST workers beside the encoding thread, or,
    when the flush has one body, by the encoding thread itself. The
    legacy `flush` uses its wall and `post` alone, for bodies that all
    exist before the first leaves."""

    def __init__(self, sink: DatadogMetricSink, rnd):
        self.sink = sink
        self.rnd = rnd
        self.queue: "queue.SimpleQueue" = queue.SimpleQueue()
        self.workers: List[threading.Thread] = []
        self.sent: List[dict] = []    # per body: bytes, gzip (its span)
        self.errors: List[tuple] = []  # (worker's name, what it raised)
        self.wall = None              # the egress_post_wall phase
        self._lock = threading.Lock()  # of the three counts below
        self.unanswered = 0           # bodies handed off, not yet answered
        self.in_flight = 0            # bodies in gzip or POST right now
        self.peak_in_flight = 0

    def hand_off(self, parts: List[bytes]) -> None:
        """A body for the POST workers (the encoder's `emit`, on the
        encoding thread): queue it, and start one more worker if every
        running one has a body to send and fewer than
        `sink.num_workers` run. So the workers number what gzip and
        POST take beside the encode: several behind the native encoder,
        one behind an encoder slower than a send. Never waits."""
        self.open_wall()
        with self._lock:
            self.unanswered += 1
            all_busy = self.unanswered > len(self.workers)
        self.queue.put(parts)
        if all_busy and len(self.workers) < self.sink.num_workers:
            worker = threading.Thread(
                target=self._work, daemon=True,
                name=f"{self.sink.name()}-post-{len(self.workers)}")
            worker.start()
            self.workers.append(worker)

    def finish(self, encode: dict, rest: List[bytes]) -> float:
        """After the encode (its closed span): send the last body,
        through the workers if any run and else from this thread, wait
        for every answer and close the spans. -> the tail's seconds."""
        body = self._join(rest) if rest and not self.workers else None
        with self.rnd.phase("egress_post_tail", parent="sink") as tail:
            if body is not None:
                self.open_wall()
                self.post(body)
            else:
                if rest:
                    self.hand_off(rest)
                for _ in self.workers:
                    self.queue.put(None)
                for worker in self.workers:
                    worker.join()
        self.close_wall(encode)
        return tail["wall_s"]

    def open_wall(self) -> None:
        if self.wall is None:
            # ends after spans of this thread that began inside it
            self.wall = self.rnd.phase(
                "egress_post_wall", parent="sink").start(handoff=True)

    def close_wall(self, encode: dict) -> None:
        """After the last answer: the wall's span, with what was sent
        inside it."""
        if self.wall is None:
            return
        encode_end_s = encode["start_s"] + encode["wall_s"]
        gzips = [sent["gzip"] for sent in self.sent if "gzip" in sent]
        self.wall.stop().update(
            bodies=len(self.sent),
            bytes=sum(sent["bytes"] for sent in self.sent),
            gzip_bytes=sum(g.get("bytes", 0) for g in gzips),
            bodies_overlapped=sum(
                1 for g in gzips if g["start_s"] < encode_end_s),
            workers=len(self.workers),
            peak_in_flight=self.peak_in_flight)

    def _work(self) -> None:
        for parts in iter(self.queue.get, None):
            try:
                self.post(self._join(parts))
            except Exception as e:
                logger.exception("datadog POST worker failed on a body")
                self.errors.append((threading.current_thread().name, e))
            finally:
                with self._lock:
                    self.unanswered -= 1

    def _join(self, parts: List[bytes]) -> bytes:
        """The body of these parts, copied once (a native part is
        megabytes long)."""
        with self.rnd.phase("egress_join", parent="sink"):
            pieces = [b'{"series":[']
            for part in parts:
                pieces.append(part)
                pieces.append(b",")
            pieces[-1] = b"]}"
            return b"".join(pieces)

    def post(self, body: bytes) -> None:
        sent = {"bytes": len(body)}
        self.sent.append(sent)

        def timed(name: str):
            """vhttp.post's "gzip" and "http", as spans of the round."""
            phase = self.rnd.phase("egress_" + name,
                                   parent="egress_post_wall")
            sent[name] = phase.rec
            return phase

        with self._lock:
            self.in_flight += 1
            self.peak_in_flight = max(self.peak_in_flight, self.in_flight)
        try:
            self.sink._post_series_body_safe(body, timed)
        finally:
            with self._lock:
                self.in_flight -= 1


# timestamp plausibility window, adapted to this pipeline's nanosecond
# span timestamps (the reference's constants at datadog.go:536-538 target
# second-scale values): spans outside 2001..2100 count as scale errors
_SPAN_TS_TOO_EARLY = 978_307_200 * 10**9
_SPAN_TS_TOO_LATE = 4_102_444_800 * 10**9

_DD_SPAN_TYPE = "web"  # reference datadog.go:31 datadogSpanType
_DD_RESOURCE_KEY = "resource"  # datadog.go:27


class DatadogSpanSink(SpanSink):
    """Bounded span ring -> Datadog APM traces (reference datadog.go
    span path, :453-660): the ring overwrites its oldest entry when full
    (overflow is counted, not blocked on), flush converts each span to
    the DD trace-span shape — resource tag promoted out of meta with an
    "unknown" default, root spans get parent_id 0, errors map to code 2,
    span type "web" — groups spans by trace id, and PUTs the
    two-dimensional trace array uncompressed (the traces endpoint does
    not accept compressed bodies). Flush self-metrics match the
    reference sink keys: sink.spans_flushed_total (tagged per service)
    and sink.span_flush_total_duration_ns."""

    def __init__(self, name: str, trace_api_url: str, hostname: str,
                 buffer_size: int = DATADOG_SPAN_BUFFER_CAP,
                 timeout: float = 10.0):
        self._name = name
        self.trace_api_url = trace_api_url.rstrip("/")
        self.hostname = hostname
        self.buffer: "collections.deque" = collections.deque(maxlen=buffer_size)
        self.timeout = timeout
        self._lock = threading.Lock()
        self.overwritten_total = 0  # ring overflow accounting
        self.timestamp_errors = 0

    def name(self) -> str:
        return self._name

    def kind(self) -> str:
        return "datadog"

    def ingest(self, span) -> None:
        if not span.trace_id:
            return
        with self._lock:
            if len(self.buffer) == self.buffer.maxlen:
                # ring semantics: the append below evicts the oldest
                self.overwritten_total += 1
            self.buffer.append(span)

    def ingest_many(self, spans) -> None:
        good = [s for s in spans if s.trace_id]
        if not good:
            return
        with self._lock:
            room = self.buffer.maxlen - len(self.buffer)
            if len(good) > room:
                self.overwritten_total += len(good) - room
            self.buffer.extend(good)

    def _to_dd_span(self, s) -> dict:
        meta = dict(s.tags)
        resource = meta.pop(_DD_RESOURCE_KEY, "") or "unknown"
        if (s.start_timestamp < _SPAN_TS_TOO_EARLY
                or s.start_timestamp > _SPAN_TS_TOO_LATE):
            self.timestamp_errors += 1
        return {
            "trace_id": s.trace_id,
            "span_id": s.id,
            "parent_id": max(s.parent_id, 0),  # root spans -> 0
            "service": s.service,
            "name": s.name or "unknown",
            "resource": resource,
            "start": s.start_timestamp,
            "duration": max(s.end_timestamp - s.start_timestamp, 0),
            "type": _DD_SPAN_TYPE,
            "error": 2 if s.error else 0,
            "meta": meta,
            # numeric span tags; always present in the DD wire shape
            # (reference DatadogTraceSpan.Metrics, datadog.go:434)
            "metrics": {},
        }

    def flush(self) -> None:
        import time as _time

        flush_start = _time.perf_counter()
        with self._lock:
            spans, self.buffer = list(self.buffer), collections.deque(
                maxlen=self.buffer.maxlen)
        if not spans:
            return
        traces: Dict[int, List[dict]] = {}
        service_counts: Dict[str, int] = {}
        for s in spans:
            traces.setdefault(s.trace_id, []).append(self._to_dd_span(s))
            service_counts[s.service] = service_counts.get(s.service, 0) + 1
        try:
            vhttp.put_json(f"{self.trace_api_url}/v0.3/traces",
                           list(traces.values()), timeout=self.timeout)
        except Exception as e:
            logger.error("datadog trace PUT failed: %s", e)
            return
        statsd = getattr(self, "_statsd", None)
        if statsd is not None:
            # per-service flushed counts are datadog-specific (reference
            # datadog.go:654); duration + ring-overwrite drops go through
            # the shared helper
            for service, count in service_counts.items():
                statsd.count(
                    "sink.spans_flushed_total", count,
                    tags=[f"sink:{self._name}", f"service:{service}"])
            ts_errors, self.timestamp_errors = self.timestamp_errors, 0
            if ts_errors:
                statsd.count(
                    "worker.trace.sink.timestamp_error", ts_errors,
                    tags=[f"sink:{self._name}"])
            dropped, self.overwritten_total = self.overwritten_total, 0
            if dropped:
                statsd.count("sink.spans_dropped_total", dropped,
                             tags=[f"sink:{self._name}"])
            statsd.gauge(
                "sink.span_flush_total_duration_ns",
                int((_time.perf_counter() - flush_start) * 1e9),
                tags=[f"sink:{self._name}"])


@register_metric_sink("datadog")
def _metric_factory(sink_config, server_config):
    c = sink_config.config
    return DatadogMetricSink(
        sink_config.name or "datadog",
        api_key=str(c.get("datadog_api_key", c.get("api_key", ""))),
        api_url=c.get("datadog_api_hostname",
                      c.get("api_hostname",
                            "https://app.datadoghq.com")),
        hostname=server_config.hostname,
        interval=server_config.interval,
        flush_max_per_body=int(c.get("datadog_flush_max_per_body", 25_000)),
        # unset (or 0): the host's cores decide, not `Config.num_workers`
        num_workers=c.get("datadog_num_workers"),
        tags=c.get("tags", []) or [],
        metric_name_prefix_drops=c.get(
            "datadog_metric_name_prefix_drops", []) or [],
        excluded_tag_prefixes=c.get("datadog_excluded_tags", []) or [],
        exclude_tags_prefix_by_prefix_metric={
            str(e.get("metric_prefix", "")): list(e.get("tags", []) or [])
            for e in (c.get(
                "datadog_exclude_tags_prefix_by_prefix_metric", []) or [])})


@register_span_sink("datadog")
def _span_factory(sink_config, server_config):
    c = sink_config.config
    return DatadogSpanSink(
        sink_config.name or "datadog",
        trace_api_url=c.get("datadog_trace_api_address",
                            "http://127.0.0.1:8126"),
        hostname=server_config.hostname,
        buffer_size=int(c.get("datadog_span_buffer_size",
                              DATADOG_SPAN_BUFFER_CAP)))
