"""The veneur-tpu server: wires config -> column store -> sources -> sinks.

Structural parity with reference server.go (NewFromConfig:462, Start:711,
Flush ticker:837-875, HandleMetricPacket:949, Shutdown:1424) with the
worker pool replaced by the device column store. Ingest threads parse
packets and append samples to batch buffers; the flush ticker runs the
device flush kernels and fans InterMetrics out to sinks in parallel.
"""

from __future__ import annotations

import logging
import os
import queue
import socket
import threading
import time
from typing import Callable, Dict, List, Optional

from veneur_tpu import sinks as sinks_mod
from veneur_tpu.config import Config, SinkConfig
from veneur_tpu.core import networking
from veneur_tpu.core.columnstore import ColumnStore, WarmupFailed
from veneur_tpu.core.latency import family_tree
from veneur_tpu.core.telemetry import FlushRound, current_round
from veneur_tpu.core.routing import BatchRoutes, ColumnRouter
from veneur_tpu.core.flusher import (
    FlushBatch, ForwardableState, readout_columnstore, swap_columnstore)
from veneur_tpu.samplers import metrics as m
from veneur_tpu.samplers.metrics import (
    HistogramAggregates, InterMetric, MetricScope, UDPMetric,
)
from veneur_tpu.samplers.parser import ParseError, Parser
from veneur_tpu.util import compilecache
from veneur_tpu.util.matcher import SinkRoutingMatcher

logger = logging.getLogger("veneur_tpu.server")

# wire type -> overload shed class (the priority ladder's middle rung;
# counter/gauge/status samples never appear here — they are always kept)
from veneur_tpu.core import overload as overload_mod  # noqa: E402

_SHED_CLASS = {
    m.HISTOGRAM: overload_mod.CLASS_HISTOGRAM,
    m.TIMER: overload_mod.CLASS_HISTOGRAM,
    m.LLHIST: overload_mod.CLASS_HISTOGRAM,
    m.SET: overload_mod.CLASS_SET,
}


class RawSpan:
    """A span still in wire form: the native SSF path already extracted
    its metrics, so decoding (for external span sinks) happens lazily in
    the span worker instead of on the ingest path."""

    __slots__ = ("data",)

    def __init__(self, data: bytes):
        self.data = data


class _SpanSinkWorker:
    """Per-sink span ingest isolation: each external span sink gets a
    bounded buffer and one dedicated thread, so a slow or hung sink drops
    its own spans instead of stalling the shared span workers — the
    TPU-build equivalent of the reference's 9 s per-sink ingest timeout
    (reference worker.go:588-656). Internal sinks (metric extraction) are
    called inline by the span workers and bypass this.

    Spans move in CHUNKS: the span workers submit whole decoded batches
    and this thread swaps the pending list out in one lock window, so
    per-span cost on the shared path is one list-append — at bench rate
    (>100k spans/s) per-span Queue put/get was itself the bottleneck and
    shed half the stream (BENCH_r04: 137,896 drops in 5.7 s). Capacity
    counts SPANS, not chunks, and a chunk that would overflow is dropped
    whole (accounted per-sink)."""

    def __init__(self, sink, capacity: int, observatory=None):
        from veneur_tpu.sinks import SpanSink
        self.sink = sink
        # duck-typed sinks (tests, plugins) may predate the batch API;
        # bind the base default for them (per-span isolate-and-log) so
        # the loop has exactly one delivery path
        self._ingest_many = getattr(
            sink, "ingest_many",
            lambda chunk: SpanSink.ingest_many(sink, chunk))
        self.capacity = max(16, capacity)
        self._pending: list = []  # list of (enqueue_t, chunk) pairs
        self._pending_spans = 0
        # queue-dwell telemetry: per-chunk enqueue->drain latency plus a
        # scrape-time depth gauge (None when the observatory is off)
        self._dwell = None
        if observatory is not None and observatory.enabled:
            qname = f"span_sink:{sink.name()}"
            self._dwell = observatory.queue_hist(qname)
            observatory.register_queue(
                qname, lambda: self._pending_spans, self.capacity)
        self._lock = threading.Lock()
        self._ready = threading.Condition(self._lock)
        self.dropped = 0
        self.ingested = 0
        self._stop = threading.Event()
        self.thread: Optional[threading.Thread] = None

    def start(self) -> None:
        from veneur_tpu.util.crash import guarded
        self.thread = threading.Thread(
            target=guarded(self._loop),
            name=f"span-sink-{self.sink.name()}", daemon=True)
        self.thread.start()

    def submit(self, span) -> None:
        self.submit_many((span,))

    def submit_many(self, spans) -> None:
        n = len(spans)
        if n == 0:
            return
        with self._lock:
            # overflow drops whole chunks, but an empty buffer always
            # accepts one — otherwise a configured capacity below the
            # worker batch size (256) would starve the sink forever
            if self._pending and self._pending_spans + n > self.capacity:
                self.dropped += n
                return
            self._pending.append((time.monotonic(), spans))
            self._pending_spans += n
            self._ready.notify()

    def _loop(self) -> None:
        while True:
            with self._lock:
                while not self._pending:
                    if self._stop.is_set():
                        return
                    self._ready.wait(timeout=0.5)
                chunks, self._pending = self._pending, []
                self._pending_spans = 0
            dwell = self._dwell
            now = time.monotonic() if dwell is not None else 0.0
            for enqueued_t, chunk in chunks:
                if dwell is not None:
                    dwell.observe(now - enqueued_t)
                try:
                    self._ingest_many(chunk)
                    self.ingested += len(chunk)
                except Exception:
                    logger.exception(
                        "span sink %s ingest failed", self.sink.name())

    def stop(self, timeout: float = 2.0) -> None:
        """Signal, then join: the loop drains whatever was already
        submitted before it sees the stop flag on its next empty wait."""
        self._stop.set()
        with self._lock:
            self._ready.notify()
        if self.thread is not None:
            self.thread.join(timeout)


class Server:
    def __init__(self, config: Config,
                 extra_metric_sinks: Optional[List] = None,
                 extra_span_sinks: Optional[List] = None):
        self.config = config
        self.interval = config.interval
        # forward_only: metrics that don't declare a scope become
        # global-only, so a local server aggregates nothing itself and
        # forwards everything (reference server.go:547-552)
        self.parser = Parser(
            extend_tags=config.extend_tags,
            default_scope=(MetricScope.GLOBAL_ONLY if config.forward_only
                           else MetricScope.MIXED))
        self.store = ColumnStore(
            counter_capacity=config.tpu.counter_capacity,
            gauge_capacity=config.tpu.gauge_capacity,
            histo_capacity=config.tpu.histo_capacity,
            set_capacity=config.tpu.set_capacity,
            batch_cap=config.tpu.batch_cap,
            shard_devices=config.tpu.shards,
            max_rows=config.tpu.max_rows_per_family,
            set_promote_samples=config.tpu.set_promote_samples,
            set_max_dev_slots=config.tpu.set_max_dev_slots,
            llhist_capacity=config.tpu.llhist_capacity,
            histogram_encoding=config.histogram_encoding,
            shard_routing=config.tpu.shard_routing)
        self._keys_dropped_reported = 0
        self.aggregates = HistogramAggregates.from_names(config.aggregates)
        self.percentiles = tuple(config.percentiles)

        sinks_mod.register_builtin_sinks()
        self.metric_sinks: List = list(extra_metric_sinks or [])
        for sc in config.metric_sinks:
            factory = sinks_mod.MetricSinkTypes.get(sc.kind)
            if factory is None:
                raise ValueError(f"unknown metric sink kind: {sc.kind}")
            self.metric_sinks.append(factory(sc, config))
        self.span_sinks: List = list(extra_span_sinks or [])
        for sc in config.span_sinks:
            factory = sinks_mod.SpanSinkTypes.get(sc.kind)
            if factory is None:
                raise ValueError(f"unknown span sink kind: {sc.kind}")
            self.span_sinks.append(factory(sc, config))
        # per-sink tag/name filtering config — only sinks with ACTIVE
        # filters, so unfiltered config-declared sinks still take the
        # columnar fast path in _flush_sink_safe (an entry here forces
        # per-metric object materialization)
        self._sink_filters = {
            sc.name or sc.kind: sc for sc in config.metric_sinks
            if (sc.strip_tags or sc.add_tags or sc.max_name_length
                or sc.max_tag_length or sc.max_tags)}

        from veneur_tpu import sources as sources_mod
        sources_mod.register_builtin_sources()
        self.sources: List = []
        for src_cfg in config.sources:
            factory = sources_mod.SourceTypes.get(src_cfg.kind)
            if factory is None:
                raise ValueError(f"unknown source kind: {src_cfg.kind}")
            self.sources.append(factory(src_cfg, config))
        self._source_threads: List[threading.Thread] = []

        self._routing: Optional[ColumnRouter] = None
        if config.features.enable_metric_sink_routing:
            self._routing = ColumnRouter(
                [SinkRoutingMatcher(rc)
                 for rc in config.metric_sink_routing])

        # events & service-check samples buffered between flushes
        self._other_samples: List = []
        self._other_lock = threading.Lock()

        # latency observatory (core/latency.py): flush dispatch
        # attribution, per-plane sample-age watermarks, and queue
        # dwell/depth telemetry. Created before any bounded hand-off so
        # the queues below can be instrumented at construction.
        from veneur_tpu.core.latency import LatencyObservatory
        self.latency = LatencyObservatory(
            enabled=config.latency_observatory)

        # span pipeline: bounded channel + worker pool (reference
        # server.go:728-736, worker.go:547-686); the metric-extraction
        # sink is always attached (server.go:654-664)
        from veneur_tpu.sinks.ssfmetrics import MetricExtractionSink
        self.metric_extraction = MetricExtractionSink(
            self.ingest_metric, self.parser,
            indicator_timer_name=config.indicator_span_timer_name,
            objective_timer_name=config.objective_span_timer_name)
        self.span_sinks.append(self.metric_extraction)
        self.span_chan: "queue.Queue" = self.latency.instrument_queue(
            "span_channel", maxsize=config.span_channel_capacity)
        self._span_workers: List[threading.Thread] = []
        self._span_sink_workers: List[_SpanSinkWorker] = []
        self.spans_dropped = 0

        self.forwarder: Optional[Callable[[ForwardableState], None]] = None
        self.forward_client = None  # set in start() when forward_address
        self.import_server = None  # set in start() when grpc_address
        self.grpc_ingest_servers: List = []  # per grpc_listen_addresses
        # timestamp-faithful backfill (forward/backfill.py): imports
        # stamped with an interval older than backfill_after_s bucket
        # by ORIGINAL interval and flush with original timestamps.
        # Constructed below once the ledger exists.
        self.backfill = None
        self.backfill_after_s = 0.0
        # the running interval's start (the previous flush boundary):
        # WAL appends stamp it onto every forwardable snapshot
        self._interval_start_unix = time.time()

        # pull-side telemetry: every statsd emission below tees into this
        # registry, and the HTTP API serves it (/metrics, /debug/events,
        # /debug/flush) — the expvar/flight-recorder side of the loop
        from veneur_tpu.core import telemetry as telemetry_mod
        self.telemetry = telemetry_mod.Telemetry()
        self.telemetry.registry.add_collector(self._live_telemetry_rows)
        self.telemetry.registry.add_collector(self._ring_telemetry_rows)
        self.telemetry.registry.add_collector(self._ingest_time_rows)
        self._warmup_seconds: Dict[str, float] = {}
        self.telemetry.registry.add_collector(self._warmup_rows)
        self.telemetry.registry.add_collector(
            telemetry_mod.device_memory_rows)

        # cross-tier self-trace plane (trace/store.py): the bounded
        # trace store behind /debug/traces, the pre-minted per-interval
        # trace id that exemplar capture and the flush span share, and
        # the sampling decision bounding all of it. Every flight-
        # recorder event and ledger interval is stamped with the active
        # interval's trace id, and /metrics exposition lines pick up
        # OpenMetrics exemplars from the plane.
        from veneur_tpu.trace.store import SelfTracePlane
        self.trace_plane = SelfTracePlane(
            service="veneur-tpu",
            sample_rate=config.trace_self_sample_rate,
            max_traces=config.trace_store_traces,
            max_spans=config.trace_store_spans,
            exemplar_names=config.trace_exemplar_names)
        self.telemetry.registry.add_collector(
            self.trace_plane.telemetry_rows)
        self.telemetry.trace_source = self.trace_plane.active_trace_hex
        self.telemetry.registry.exemplar_source = \
            self.trace_plane.exemplar_for
        # a GLOBAL's next flush adopts the originating local's interval
        # trace (latest fresh import wins); see adopt_flush_trace
        self._adopted_trace = None

        # flow ledger (core/ledger.py): per-interval conservation
        # accounting from socket to sink ack. Declared here so every
        # crossing below (ingest, store, forward, spool) can stamp it;
        # the interval closes at the end of each flush.
        from veneur_tpu.core.ledger import FlowLedger
        self.ledger = FlowLedger(
            enabled=config.ledger_enabled, strict=config.ledger_strict,
            history=config.ledger_history,
            on_event=self.telemetry.record_event)
        # ingested = aggregated + rejected: a sample admitted past
        # admission control must land in a family table or be rejected
        # at the mint gate — anything else is a silent drop
        self.ledger.declare(
            "ingest", inputs=("ingest.admitted",),
            outputs=("agg.applied", "agg.rejected"),
            # migrating digest-range rows captured out of the old
            # topology but not yet merged into the new one (always 0
            # at close — the cutover runs under _flush_lock — so a
            # nonzero closing level is itself a conservation break)
            stocks=("reshard_inflight",))
        # snapshotted = acked + merged-away + shed, with the carryover,
        # the durable spool, and the in-flight send as inventory stocks
        self.ledger.declare(
            "forward", inputs=("forward.snapshot",),
            outputs=("forward.acked", "forward.merged_away",
                     "forward.shed"),
            stocks=("forward_carryover", "forward_spool",
                    "forward_inflight", "spool_quarantine"))
        # backfill plane (forward/backfill.py, receivers only): every
        # metric merged into a historical bucket is retired when its
        # bucket closes, with the open buckets as inventory — WAL
        # replay must not be able to lose state silently either
        self.ledger.declare(
            "backfill", inputs=("backfill.merged",),
            outputs=("backfill.closed",), stocks=("backfill_open",))
        if config.backfill_max_open_intervals > 0:
            # built here (not start()) so a manually-wired ImportServer
            # — the in-process test topology — finds the plane too
            from veneur_tpu.forward.backfill import BackfillPlane
            self.backfill = BackfillPlane(
                percentiles=self.percentiles,
                max_open=config.backfill_max_open_intervals,
                ledger=(self.ledger if self.ledger.enabled else None),
                on_event=self.telemetry.record_event)
            self.backfill_after_s = (config.wal_stale_after_intervals
                                     * self.interval)
            bf = self.backfill
            self.ledger.stock("backfill_open", lambda: bf.open_metrics)
            self.telemetry.registry.add_collector(bf.telemetry_rows)
        # cross-tier reconciliation: what this local acked against what
        # the receiver reports it received/merged (FlowCounts responses)
        self.ledger.declare(
            "forward_tier", inputs=("forward.acked_reported",),
            outputs=("forward.remote_merged", "forward.remote_rejected",
                     "forward.remote_deduped"))
        self.latency.ledger = self.ledger if self.ledger.enabled else None
        self.ledger.trace_source = self.trace_plane.active_trace_hex
        self.telemetry.registry.add_collector(self.ledger.telemetry_rows)

        # self-metrics: UDP to stats_address, or internal loopback so they
        # re-enter this server's own pipeline (reference scopedstatsd +
        # NewChannelClient server.go:518-524)
        from veneur_tpu.util.scopedstatsd import NullClient, ScopedClient
        if config.stats_address == "internal":
            # explicit loopback: self-metrics re-enter this server
            self.statsd = ScopedClient(
                packet_cb=self._self_packet,
                scopes=config.veneur_metrics_scopes,
                additional_tags=config.veneur_metrics_additional_tags,
                registry=self.telemetry.registry)
        elif config.stats_address:
            self.statsd = ScopedClient(
                address=config.stats_address,
                scopes=config.veneur_metrics_scopes,
                additional_tags=config.veneur_metrics_additional_tags,
                registry=self.telemetry.registry)
        else:
            self.statsd = NullClient(registry=self.telemetry.registry)

        # self-tracing: every flush is a span through the internal channel
        # client into our own span pipeline (reference flusher.go:27-28);
        # its bounded buffer is instrumented like every other hand-off
        from veneur_tpu import trace as trace_mod
        self.trace_client = trace_mod.Client(
            trace_mod.ChannelBackend(self.ingest_span),
            capacity=config.span_channel_capacity,
            buffer=self.latency.instrument_queue(
                "trace_client", maxsize=config.span_channel_capacity),
            # every self-span also lands (synchronously, when its trace
            # is sampled) in the bounded trace store behind /debug/traces
            tee=self.trace_plane.record_proto)
        self.telemetry.registry.add_collector(self.latency.telemetry_rows)

        self.diagnostics = None
        if config.features.diagnostics_metrics_enabled:
            from veneur_tpu.core.diagnostics import DiagnosticsLoop
            self.diagnostics = DiagnosticsLoop(self.statsd, config.interval)

        # native batch ingest engine (None -> numpy columnar fallback)
        from veneur_tpu.core.ingest import BatchIngester, PyBatchIngester
        self._ingester = (None if config.tpu.disable_native_parser
                          else BatchIngester.create(self))
        # the numpy columnar decoder (core/batchdecode.py): same batch
        # pipeline — intern-table columnar parse, per-family add_batch,
        # batch admission — with the parse step in pure Python, so the
        # ingest speedup survives hosts without the C++ extension
        self._py_ingester = (PyBatchIngester(self)
                             if self._ingester is None else None)

        self.http_api = None  # set in start() when http_address
        self.profiler = None  # set in start() when enable_profiling
        self._warmup_thread = None  # set in start()
        self._listeners: List[networking.Listener] = []
        self._flush_lock = threading.Lock()
        # the query plane's readout worker (core/flushexec.py), created
        # by the first live read
        self._flush_executor = None
        self.prewarmer = None  # set in start() when prewarm_ladder
        # last flush thread per sink: a sink whose previous flush is still
        # running gets skipped — the hard cap is ONE concurrent flush
        # thread per sink, so a permanently hung sink costs one thread,
        # not one per interval
        self._sink_flush_threads: Dict[str, threading.Thread] = {}
        # consecutive skipped intervals per sink (the pileup depth a hung
        # sink would have caused without the cap); logged and exported
        self._sink_skip_depth: Dict[str, int] = {}
        # egress resilience: per-sink circuit breakers (shared
        # util/resilience.py implementation, same knobs as the forward
        # breaker) and the bounded one-interval spill of a failed metric
        # sink's InterMetric batch
        from veneur_tpu.util import chaos as chaos_mod
        from veneur_tpu.util.resilience import CircuitBreaker
        self._breaker_cls = CircuitBreaker
        self._sink_breakers: Dict[str, CircuitBreaker] = {}
        self._sink_spill: Dict[str, List[InterMetric]] = {}
        self.chaos = chaos_mod.Chaos.from_config(config)
        # ingest-side resilience: admission buckets, the ok/degraded/
        # shedding watermark ladder, kernel-drop polling, and the
        # pipeline supervisor (core/overload.py — PR 2's egress layer
        # mirrored onto ingest)
        from veneur_tpu.core.overload import OverloadManager
        self.overload = OverloadManager(
            config, chaos=self.chaos,
            on_transition=self._overload_transition,
            on_stall=self._supervisor_stall)
        self.telemetry.registry.add_collector(self.overload.telemetry_rows)
        # cardinality observatory (core/cardinality.py): heavy-hitter
        # series accounting fed from the column store's interning path,
        # per-tag-key HLL diagnosis of top offenders, and the
        # cardinality rung of the shed ladder (rejected mints land in
        # ingest.shed_total via overload.shed, reason:cardinality*)
        from veneur_tpu.core.cardinality import CardinalityAccountant
        self.cardinality = CardinalityAccountant(
            soft_limit=config.cardinality_soft_limit,
            hard_limit=config.cardinality_hard_limit,
            degraded_keep=config.cardinality_degraded_keep,
            top_k=config.cardinality_top_k,
            hll_names=config.cardinality_hll_names,
            hll_min_mints=config.cardinality_hll_min_mints,
            on_shed=self.overload.shed,
            on_event=self.telemetry.record_event)
        self.store.attach_cardinality(self.cardinality)
        # device observatory (core/deviceobs.py): HBM generation ledger,
        # kernel dispatch/compile registry, shard-balance scrape —
        # served at /debug/device, feeding the overload ladder's device
        # watermark rung and the shard_skew alert rule kind
        from veneur_tpu.core.deviceobs import DeviceObservatory
        self.deviceobs = DeviceObservatory(
            enabled=bool(getattr(config, "device_observatory", True)))
        if self.deviceobs.enabled:
            self.store.attach_deviceobs(self.deviceobs)
            self.telemetry.registry.add_collector(
                self.deviceobs.telemetry_rows)
            self.overload.attach_device_source(self.deviceobs.total_bytes)
        # persistent-compilation-cache probe state: entry counts
        # snapshotted at resize time, compared after the recompile
        self._cache_entries_at_resize: Dict[str, int] = {}
        self.store.attach_resize_hook(self._store_resize)
        self.telemetry.registry.add_collector(self.store.telemetry_rows)
        self.telemetry.registry.add_collector(
            self.cardinality.telemetry_rows)
        # live query plane (core/query.py): consistent read-only
        # captures of the live device generation, served between
        # flushes by GET /query and evaluated every tick by the alert
        # engine (core/alerts.py). Built here, not start(), so
        # in-process test topologies can query without an HTTP listener.
        from veneur_tpu.core.alerts import AlertEngine
        from veneur_tpu.core.query import LiveQueryPlane
        self.query_plane = LiveQueryPlane(self)
        self.telemetry.registry.add_collector(
            self.query_plane.telemetry_rows)
        self.alerts = AlertEngine(self, self.query_plane,
                                  interval_s=config.alerts.interval)
        try:
            self.alerts.configure(config.alerts.rules)
        except Exception:
            # a bad rule table must not keep the server down: start
            # with an empty table, loudly — SIGHUP reloads it once fixed
            logger.exception("invalid alerts.rules; starting with an "
                             "empty rule table")
        self.telemetry.registry.add_collector(self.alerts.telemetry_rows)
        # elastic reshard controller (parallel/reshard.py): live
        # digest-range migration N->M with a WAL-backed exactly-once
        # cutover. Built here (not start()) so in-process topologies
        # can drive begin()/recover() directly.
        from veneur_tpu.parallel.reshard import ReshardController
        self.reshard = ReshardController(self)
        self.ledger.stock("reshard_inflight",
                          self.reshard.inflight_metrics)
        self.telemetry.registry.add_collector(self.reshard.telemetry_rows)
        self._flush_thread: Optional[threading.Thread] = None
        self._watchdog_thread: Optional[threading.Thread] = None
        self._shutdown = threading.Event()
        # set once shutdown() completes, so a CLI embedding this server
        # can exit when /quitquitquit triggered the shutdown internally
        self.shutdown_complete = threading.Event()
        self.last_flush_unix = time.time()
        self.flush_count = 0
        # locked counters: increments arrive from many reader threads
        from veneur_tpu.util.stats import StatCounters
        self.stats = StatCounters(
            "packets_received", "parse_errors", "metrics_flushed",
            "tcp_overlong_dropped", "ssf_undecodable_dropped",
            "batches_dispatched")
        # ledger feeds from counters that already exist: parse errors
        # and the overload shed table surface as informational ingress
        # stages in /debug/ledger (per-interval deltas, folded at close)
        self.store.attach_ledger(self.ledger if self.ledger.enabled
                                 else None)
        self.ledger.probe("ingress.parse_errors",
                          lambda: self.stats["parse_errors"])
        self.ledger.probe_map("ingress.shed", self.overload.shed_snapshot)

    # -- identity --------------------------------------------------------

    @property
    def is_local(self) -> bool:
        return self.config.is_local

    # -- ingest ----------------------------------------------------------

    def handle_packet_batch(self, datagrams) -> None:
        """Fast path: parse a batch of datagrams through the columnar
        batch decoder (native C++, or the numpy fallback) straight into
        the column store. Chaos ingest faults (drop/truncate/duplicate)
        apply here; admission control gates the parsed BATCH — one
        token-bucket take whose cost is the batch's sample count, inside
        the ingester's apply path — and an over-limit batch still parses
        columnar, in essential-only mode (histogram/llhist/set columns
        shed with exact per-class counts, counter/gauge deltas kept)."""
        chaos = self.chaos
        if chaos is not None and chaos.ingest_faults_planned:
            datagrams = chaos.mangle_packets(datagrams)
        # sample-age stamp at the socket-read boundary, one per batch
        self.latency.note_arrival("dogstatsd", len(datagrams))
        ingester = self._ingester or self._py_ingester
        good = []
        for dgram in datagrams:
            if len(dgram) > self.config.metric_max_length:
                self.stats.inc("parse_errors")
            else:
                good.append(dgram)
        if good:
            ingester.ingest_buffer(b"\n".join(good))

    def handle_metric_packet(self, packet: bytes,
                             shed_nonessential: bool = False) -> None:
        """Dispatch one datagram/line (reference server.go:949-1000).
        With `shed_nonessential` (over-limit packet) histogram/set
        samples are shed; counter/gauge deltas are always kept."""
        self.stats.inc("packets_received")
        cb = (self._ingest_metric_essential if shed_nonessential
              else self.ingest_metric)
        try:
            if packet.startswith(b"_sc"):
                metric = self.parser.parse_service_check(packet)
                self.ingest_metric(metric)
            elif packet.startswith(b"_e{"):
                event = self.parser.parse_event(packet)
                with self._other_lock:
                    self._other_samples.append(event)
            else:
                self.parser.parse_metric_fast(packet, cb)
        except ParseError as e:
            self.stats.inc("parse_errors")
            logger.debug("could not parse packet %r: %s", packet[:100], e)

    def handle_packet_buffer(self, buf: bytes,
                             shed_nonessential: bool = False) -> None:
        """Newline-split a multi-metric datagram (server.go:1116-1140)."""
        if len(buf) > self.config.metric_max_length:
            self.stats.inc("parse_errors")
            return
        if not shed_nonessential and not self.overload.admit_statsd_packet():
            shed_nonessential = True
        for line in buf.split(b"\n"):
            if line:
                self.handle_metric_packet(
                    line, shed_nonessential=shed_nonessential)

    def ingest_metric(self, metric: UDPMetric) -> None:
        """The single Python-path chokepoint into the column store: the
        overload shed ladder applies here (histogram/set samples are
        shed under memory pressure; counter/gauge deltas never are).
        Samples that pass admission stamp the flow ledger's
        ingest.admitted — the in-side of the conservation identity the
        column store's applied/rejected stamps must balance. The
        chaos_ledger_leak seam sits between the stamp and the store:
        the deliberate silent drop the ledger must catch."""
        cls = _SHED_CLASS.get(metric.key.type)
        if cls is not None and not self.overload.admit_sample(cls):
            return
        led = self.ledger
        if led.enabled:
            led.note("ingest.admitted", 1, key="python")
            chaos = self.chaos
            if chaos is not None and chaos.leak_sample():
                return  # the drill: vanish with no accounting at all
        # exemplar capture: first sample per heavy-hitter/llhist name
        # per interval, stamped with the pre-minted interval trace id
        # (two set lookups when the name isn't interesting)
        if metric.value is not None:
            self.trace_plane.maybe_capture(
                metric.key.name, metric.value,
                always=metric.key.type == m.LLHIST)
        self.store.process(metric)

    def _ingest_metric_essential(self, metric: UDPMetric) -> None:
        """Essential-only intake for over-limit packets: histogram/set
        samples are shed (counted), counter/gauge deltas admitted."""
        cls = _SHED_CLASS.get(metric.key.type)
        if cls is not None and not self.overload.admit_sample(
                cls, over_limit=True):
            return
        led = self.ledger
        if led.enabled:
            led.note("ingest.admitted", 1, key="python")
        self.store.process(metric)

    def _self_packet(self, packet: bytes) -> None:
        """Loop a self-metric packet straight back into the parse path."""
        try:
            self.parser.parse_metric_fast(packet, self.ingest_metric)
        except ParseError:
            pass

    def _live_telemetry_rows(self):
        """Scrape-time /metrics rows for live counters the registry does
        not own: the locked ingest counters (which otherwise surface only
        as per-flush gauges) and span-pipeline drop totals."""
        rows = [(key if key.startswith("ingest") else f"ingest.{key}",
                 "counter", float(value), ())
                for key, value in self.stats.items()]
        rows.append(("ingest.spans_dropped", "counter",
                     float(self.spans_dropped), ()))
        # the trace CLIENT's silent drops (bounded buffer + buffered
        # backend), distinct from the span channel's ingest-side drops
        rows.append(("trace.spans_dropped", "counter",
                     float(self.trace_client.spans_dropped), ()))
        for worker in self._span_sink_workers:
            tags = [f"sink:{worker.sink.name()}"]
            rows.append(("ingest.span_sink_dropped", "counter",
                         float(worker.dropped), tags))
            rows.append(("ingest.span_sink_ingested", "counter",
                         float(worker.ingested), tags))
        rows.append(("flush.rounds", "counter", float(self.flush_count), ()))
        rows.append(("flush.last_unix_seconds", "gauge",
                     self.last_flush_unix, ()))
        # egress resilience: per-sink breaker state (0 closed / 1 open /
        # 2 half-open), pileup depth behind the 1-thread cap, and the
        # pending spill size
        for key, breaker in list(self._sink_breakers.items()):
            tags = [f"target:{key}"]
            rows.append(("resilience.breaker_state", "gauge",
                         float(breaker.state_code), tags))
            rows.append(("resilience.breaker_opens", "counter",
                         float(breaker.open_total), tags))
        for key, depth in list(self._sink_skip_depth.items()):
            rows.append(("flush.sink_pileup_depth", "gauge", float(depth),
                         [f"sink:{key}"]))
        for key, spill in list(self._sink_spill.items()):
            rows.append(("flush.spill_pending", "gauge", float(len(spill)),
                         [f"sink:{key}"]))
        return rows

    def _ring_telemetry_rows(self):
        """Scrape-time /metrics rows for the ingest SPSC rings: per
        reader, the ready-ring depth/capacity gauges plus sealed-chunk
        and reader-stall counters. (Ring dwell rides the observatory's
        queue.dwell llhists under the same ingest_ring names.)"""
        from veneur_tpu.core.ingest import addr_label
        rows = []
        for listener, pump in self._pumps():
            try:
                depths, caps, sealed, stalls = pump.ring_stats()
            except Exception:
                continue
            for i in range(len(depths)):
                tags = [f"ring:{addr_label(listener.address)}:{i}"]
                rows.append(("ingest.ring.depth", "gauge",
                             float(depths[i]), tags))
                rows.append(("ingest.ring.capacity", "gauge",
                             float(caps[i]), tags))
                rows.append(("ingest.ring.sealed_total", "counter",
                             float(sealed[i]), tags))
                rows.append(("ingest.ring.stalls_total", "counter",
                             float(stalls[i]), tags))
        return rows

    def _ingest_time_rows(self):
        """Scrape-time /metrics rows for the time the native ingest
        path's threads spend: per reader thread its CPU seconds and the
        seconds it was blocked on a full ring; over the dispatcher
        threads, their CPU seconds and the seconds they waited for a
        chunk (`core/ingest.py` books those on the pump, per chunk)."""
        from veneur_tpu.core.ingest import addr_label
        rows = []
        pumps = self._pumps()
        for listener, pump in pumps:
            try:
                cpu_s, stall_s = pump.reader_times()
            except Exception:
                continue
            for i in range(len(cpu_s)):
                tags = [f"reader:{addr_label(listener.address)}:{i}"]
                rows.append(("ingest.reader.cpu_seconds_total", "counter",
                             float(cpu_s[i]), tags))
                rows.append(("ingest.reader.stall_seconds_total",
                             "counter", float(stall_s[i]), tags))
        if pumps:
            rows.append(("ingest.dispatch.cpu_seconds_total", "counter",
                         sum(p.dispatch_cpu_s for _l, p in pumps), ()))
            rows.append(("ingest.dispatch.wait_seconds_total", "counter",
                         sum(p.dispatch_wait_s for _l, p in pumps), ()))
        return rows

    def _pumps(self) -> list:
        """(listener, its native pump) for every listener that has one."""
        return [(listener, listener.pump)
                for listener in list(getattr(self, "_listeners", ()) or ())
                if getattr(listener, "pump", None) is not None]

    # -- spans -----------------------------------------------------------

    def handle_ssf_packet(self, packet: bytes) -> None:
        """One unframed SSF datagram (reference server.go:1053-1100)."""
        self.latency.note_arrival("ssf")
        self._handle_ssf_packet_stamped(packet)

    def _handle_ssf_packet_stamped(self, packet: bytes) -> None:
        """handle_ssf_packet minus the arrival stamp — the buffer path
        below stamps once per batch and must not re-stamp per packet."""
        from veneur_tpu import protocol
        self.stats.inc("packets_received")
        try:
            span = protocol.parse_ssf(packet)
        except Exception:
            self.stats.inc("parse_errors")
            logger.debug("could not parse SSF packet (%d bytes)", len(packet))
            return
        self.ingest_span(span)

    def handle_ssf_batch(self, packets) -> None:
        """A batch of unframed SSF datagrams; delegates to
        handle_ssf_buffer over their concatenation."""
        import numpy as np
        n = len(packets)
        if not n:
            return
        lens = np.fromiter((len(p) for p in packets), np.int64, n)
        offs = np.zeros(n, np.int64)
        if n > 1:
            np.cumsum(lens[:-1], out=offs[1:])
        self.handle_ssf_buffer(b"".join(packets), offs, lens)

    def handle_ssf_buffer(self, buf, offs, lens) -> None:
        """A batch of unframed SSF datagrams as a contiguous buffer with
        per-packet (offset, length) — the shape the native UDP reader
        produces. With the native library the spans decode and their
        metrics extract in C++ (SURVEY §2 native-components item 6); the
        span objects external sinks need are decoded lazily at worker
        pace (RawSpan), so sink-side decode cost rides the existing
        bounded-queue drop semantics instead of the ingest path."""
        self.latency.note_arrival("ssf", len(offs))
        ing = getattr(self, "_ingester", None)
        if ing is not None and not os.environ.get(
                "VENEUR_TPU_DISABLE_PUMP"):
            try:
                decoded = ing.ingest_ssf_buffer(buf, offs, lens)
            except Exception:
                # the native path may already have applied part of the
                # batch; replaying it through the Python path would
                # double-count, so the remainder is dropped (UDP
                # semantics) and the failure is loud
                logger.exception(
                    "native SSF buffer failed; dropping the batch "
                    "remainder to avoid double-counting")
                self.stats.inc("parse_errors", len(offs))
                return
            if self._span_sink_workers:
                # batch admission decides the span-OBJECT handoff only:
                # the native extraction above already ran, so the
                # counter/gauge deltas embedded in SSF samples are never
                # lost (extraction precedes the span channel on this
                # path, exactly as before admission control existed).
                # Admitting AFTER decode — and only when span sinks
                # exist — keeps tokens and shed counts tied to spans
                # that would actually have been handed off.
                import numpy as np
                idxs = np.nonzero(decoded)[0]
                if len(idxs) and self.overload.admit_spans(len(idxs)):
                    for i in idxs:
                        start = int(offs[i])
                        self.ingest_span(
                            RawSpan(buf[start:start + int(lens[i])]),
                            preadmitted=True)
            return
        for off, ln in zip(offs, lens):
            # already stamped above, once for the whole batch
            self._handle_ssf_packet_stamped(buf[int(off):int(off) + int(ln)])

    def ingest_span(self, span, preadmitted: bool = False) -> None:
        """Enqueue a span for the worker pool; drops (and counts) when the
        channel is saturated rather than blocking ingest. Spans are the
        FIRST rung of the overload shed ladder: any degradation state
        (or an exhausted span-plane token bucket) sheds them here —
        `preadmitted` spans already passed batch admission upstream."""
        if not preadmitted and not self.overload.admit_span():
            return
        try:
            self.span_chan.put_nowait(span)
        except queue.Full:
            self.spans_dropped += 1

    def _span_worker_loop(self) -> None:
        """Fan spans out to every span sink (worker.go:587-662): metric
        extraction runs inline (internal, cannot hang); external sinks
        receive spans through their isolation buffers so one hung sink
        can't stall the pipeline. Spans are drained and fanned out in
        batches — one submit_many per sink per batch instead of per-span
        queue traffic. On shutdown, drains queued spans (which sit ahead
        of the None sentinels) before exiting; the timed get covers the
        case where a full channel swallowed the sentinels."""
        from veneur_tpu import protocol
        beat = self.overload.supervisor.beat
        name = threading.current_thread().name
        while True:
            beat(name)
            try:
                first = self.span_chan.get(timeout=0.5)
            except queue.Empty:
                if self._shutdown.is_set():
                    return
                continue
            if first is None:
                return
            batch = [first]
            done = False
            try:
                while len(batch) < 256:
                    nxt = self.span_chan.get_nowait()
                    if nxt is None:  # consume at most ONE sentinel so
                        done = True  # sibling workers still get theirs
                        break
                    batch.append(nxt)
            except queue.Empty:
                pass
            out = []
            for span in batch:
                if isinstance(span, RawSpan):
                    # metrics were already extracted natively; only
                    # external sinks need the decoded object
                    try:
                        out.append(protocol.parse_ssf(span.data))
                    except Exception:
                        pass  # native decode succeeded; should not happen
                else:
                    try:
                        self.metric_extraction.ingest(span)
                    except Exception:
                        logger.exception("span metric extraction failed")
                    out.append(span)
            if out:
                for worker in self._span_sink_workers:
                    worker.submit_many(out)
            if done:
                return

    # -- lifecycle -------------------------------------------------------

    def enable_compilation_cache(self) -> bool:
        """Turn on JAX's persistent compilation cache under the one
        directory rule of util/compilecache.py: a cold start, a
        crash-restart-replay cycle (SIGUSR2 handoff, WAL recovery)
        comes up with warm kernels from disk instead of paying the
        full retrace tax. Returns True when enabled."""
        try:
            cache_dir = compilecache.enable(
                self.config.jax_compilation_cache_dir)
        except OSError:
            logger.exception("could not create the persistent JAX "
                             "compilation cache directory")
            return False
        if not cache_dir:
            return False
        self.telemetry.record_event(
            "compilation_cache_enabled", directory=cache_dir,
            entries=max(0, compilecache.entries()))
        return True

    def start(self) -> None:
        from veneur_tpu.util.crash import guarded
        self.enable_compilation_cache()
        for sink in self.metric_sinks + self.span_sinks:
            sink.start(self)
        for sink in self.span_sinks:
            if sink is self.metric_extraction:
                continue
            worker = _SpanSinkWorker(
                sink, self.config.span_sink_queue_capacity,
                observatory=self.latency)
            worker.start()
            self._span_sink_workers.append(worker)
        for i in range(max(1, self.config.num_span_workers)):
            t = threading.Thread(target=guarded(self._span_worker_loop),
                                 name=f"span-worker-{i}", daemon=True)
            self.overload.supervisor.register(t.name)
            t.start()
            self._span_workers.append(t)
        for addr in self.config.statsd_listen_addresses:
            self._listeners.extend(networking.start_statsd(
                addr, self, num_readers=self.config.num_readers,
                rcvbuf=self.config.read_buffer_size_bytes))
        for addr in self.config.ssf_listen_addresses:
            self._listeners.extend(networking.start_ssf(
                addr, self, rcvbuf=self.config.read_buffer_size_bytes))
        if self.config.forward_address and self.forwarder is None:
            from veneur_tpu.forward.client import ForwardClient
            from veneur_tpu.util.grpctls import GrpcTLS
            from veneur_tpu.util.resilience import (Carryover, RetryPolicy)
            fwd_tls = GrpcTLS(
                certificate=self.config.forward_tls_certificate,
                key=(self.config.forward_tls_key.reveal()
                     if self.config.forward_tls_key else ""),
                authority=self.config.forward_tls_authority_certificate)
            cfg = self.config
            # durable carryover spill: with a spool dir configured,
            # carryover past its bound serializes to disk instead of
            # shedding; segments left by a previous process (crash or
            # SIGUSR2 handoff mid-outage) are re-scanned here and drain
            # after the first successful forward
            spool = None
            ledger = self.ledger if self.ledger.enabled else None
            if cfg.carryover_spool_dir:
                from veneur_tpu.util.spool import CarryoverSpool
                spool = CarryoverSpool(
                    cfg.carryover_spool_dir,
                    max_bytes=cfg.carryover_spool_max_bytes,
                    max_segments=cfg.carryover_spool_max_segments,
                    quarantine_max_bytes=(
                        cfg.carryover_spool_quarantine_max_bytes),
                    quarantine_max_segments=(
                        cfg.carryover_spool_quarantine_max_segments),
                    dwell_hist=self.latency.queue_hist("forward_spool"),
                    ledger=ledger)
                self.latency.register_queue(
                    "forward_spool", lambda: spool.depth,
                    cfg.carryover_spool_max_segments)
                self.telemetry.record_event(
                    "spool_attached", directory=cfg.carryover_spool_dir,
                    wal=cfg.forward_wal,
                    replayed_segments=spool.replayed_total)
            replay_limiter = None
            if cfg.forward_wal and cfg.wal_replay_rate_limit > 0:
                from veneur_tpu.core.overload import TokenBucket
                replay_limiter = TokenBucket(
                    cfg.wal_replay_rate_limit,
                    cfg.wal_replay_rate_limit * cfg.wal_replay_burst)
            self.forward_client = ForwardClient(
                cfg.forward_address, deadline=self.interval,
                tls=fwd_tls or None,
                retry=RetryPolicy(
                    max_attempts=cfg.forward_retry_max_attempts,
                    base_delay=cfg.forward_retry_base,
                    max_delay=cfg.forward_retry_max),
                breaker=self._breaker_cls(
                    failure_threshold=cfg.circuit_breaker_failure_threshold,
                    recovery_time=cfg.circuit_breaker_recovery,
                    name="forward", on_transition=self._breaker_transition),
                carryover=Carryover(cfg.carryover_max_intervals,
                                    ledger=ledger),
                chaos=self.chaos, spool=spool, ledger=ledger,
                trace_plane=self.trace_plane,
                wal=cfg.forward_wal, replay_limiter=replay_limiter,
                replay_stale_after=(cfg.wal_stale_after_intervals
                                    * self.interval),
                shards=(self.store.shard_plane.n
                        if self.store.shard_plane is not None else 0))
            self.forwarder = self.forward_client.forward
            self.telemetry.registry.add_collector(
                self.forward_client.telemetry_rows)
            # the forward plane's bounded hand-off: failed intervals
            # queue in the carryover (depth in intervals, not items)
            self.latency.register_queue(
                "forward_carryover",
                lambda: self.forward_client.carryover.depth,
                cfg.carryover_max_intervals)
            # ledger inventory stocks: metrics held in the carryover,
            # on disk in the spool (incl. segments replayed from a dead
            # process — opening stock, not unexplained inflow), and
            # in flight inside a send — so a close landing mid-outage
            # (or mid-send) still balances
            fc = self.forward_client
            self.ledger.stock("forward_carryover",
                              lambda: fc.carryover.pending_metrics)
            self.ledger.stock("forward_inflight",
                              lambda: fc.inflight_metrics)
            if spool is not None:
                self.ledger.stock("forward_spool",
                                  lambda: spool.pending_metrics)
                # quarantined segments are set ASIDE, not shed: the
                # metrics stay booked as inventory until the quarantine
                # bound purges them (explained shed at that point)
                self.ledger.stock("spool_quarantine",
                                  lambda: spool.quarantined_metrics)
        if self.chaos is not None:
            # make the plan visible to the object-less seams (http_post)
            from veneur_tpu.util import chaos as chaos_mod
            chaos_mod.install(self.chaos)
            self.telemetry.registry.add_collector(self.chaos.telemetry_rows)
            self.telemetry.record_event(
                "chaos_enabled", error_rate=self.chaos.error_rate,
                delay_rate=self.chaos.delay_rate,
                seams=sorted(self.chaos.seams))
        for addr in self.config.grpc_listen_addresses:
            from veneur_tpu.core.grpc_ingest import GrpcIngestServer
            gi = GrpcIngestServer(self, addr)
            gi.start()
            self.grpc_ingest_servers.append(gi)
        if self.config.grpc_address:
            from veneur_tpu.forward.server import ImportServer
            from veneur_tpu.util.grpctls import GrpcTLS
            from veneur_tpu.util.matcher import TagMatcher
            ignored = [TagMatcher(kind="prefix", value=t)
                       for t in self.config.tags_exclude]
            grpc_tls = GrpcTLS(
                certificate=self.config.grpc_tls_certificate,
                key=(self.config.grpc_tls_key.reveal()
                     if self.config.grpc_tls_key else ""),
                authority=self.config.grpc_tls_authority_certificate)
            self.import_server = ImportServer(
                self, self.config.grpc_address, ignored_tags=ignored,
                tls=grpc_tls or None)
            # hedge/retry duplicate drops surface in /metrics
            self.telemetry.registry.add_collector(
                self.import_server.telemetry_rows)
            imp = self.import_server
            self.ledger.probe("import.deduped",
                              lambda: imp.duplicates_dropped_total,
                              key="forward")
            self.import_server.start()
        for source in self.sources:
            t = threading.Thread(target=source.start, args=(self,),
                                 name=f"source-{source.name()}", daemon=True)
            t.start()
            self._source_threads.append(t)
        if self.config.http_address:
            from veneur_tpu.core.httpapi import HTTPApi
            self.http_api = HTTPApi(
                self.config, server=self, address=self.config.http_address,
                http_quit=self.config.http_quit, on_quit=self.shutdown)
            self.http_api.start()
        if self.config.enable_profiling:
            # continuous all-threads CPU sampler from startup (reference
            # server.go:1382-1390), readable at /debug/profile/cpu
            from veneur_tpu.core.profiling import StackSampler
            self.profiler = StackSampler()
            self.profiler.start()
        if self.config.profile_server_port:
            from veneur_tpu.core.profiling import start_profile_server
            start_profile_server(self.config.profile_server_port)
        if self.config.block_profile_rate or self.config.mutex_profile_fraction:
            logger.warning(
                "block_profile_rate/mutex_profile_fraction are Go-runtime "
                "knobs with no Python analog; accepted for config compat "
                "only — use /debug/pprof and enable_profiling instead")
        # pre-compile the flush kernels off the ticker path so the first
        # real flush isn't delayed by XLA compilation (~20-40s on TPU);
        # kept as an attribute so callers that pre-load the store (bench,
        # tests) can join it before measuring
        self._warmup_thread = threading.Thread(
            target=self._warmup, name="kernel-warmup", daemon=True)
        self._warmup_thread.start()
        if self.config.prewarm_ladder:
            # shape-ladder prewarmer (core/flushexec.py): compile each
            # family's NEXT capacity rung in the background so resizes
            # never retrace on the hot path; fed by the resize hook
            from veneur_tpu.core.flushexec import ShapeLadderPrewarmer
            self.prewarmer = ShapeLadderPrewarmer(
                self.store, percentiles=self.percentiles,
                need_export=(self.is_local and self.forwarder is not None),
                on_event=self.telemetry.record_event)
            self.telemetry.registry.add_collector(
                self.prewarmer.telemetry_rows)
            self.prewarmer.start()
            self.prewarmer.prewarm_initial()
        if self.diagnostics is not None:
            self.diagnostics.start()
        # replay range segments an interrupted reshard cutover left
        # behind — before the flush loop starts, so the recovered rows
        # land in the first interval and the ledger books them cleanly
        try:
            self.reshard.recover()
        except Exception:
            logger.exception("reshard recovery failed; segments left "
                             "in place for the next start")
        self._flush_thread = threading.Thread(
            target=guarded(self._flush_loop), name="flush-ticker",
            daemon=True)
        # the flush loop beats once per interval, so its deadline must
        # clear the interval no matter how tight the global deadline is
        # — and floors at 60s because a first flush legitimately blocks
        # on XLA compilation for tens of seconds (the flush watchdog and
        # the readiness ladder are the tight-bound wedge detectors for
        # this component; the supervisor is its long-stop)
        self.overload.supervisor.register(
            "flush-loop", deadline=max(
                self.overload.supervisor.deadline, 2.5 * self.interval,
                60.0))
        self._flush_thread.start()
        if self.config.alerts.enabled:
            # alert evaluation loop: supervised like every pipeline
            # thread, with a generous deadline — one tick's capture
            # rides the shared readout executor and can queue behind a
            # seconds-long flush readout
            self.overload.supervisor.register(
                "alert-loop", deadline=max(
                    self.overload.supervisor.deadline,
                    10 * self.alerts.interval_s, 60.0))
            self.alerts.start()
        self.overload.start()
        if self.config.flush_watchdog_missed_flushes > 0:
            self._watchdog_thread = threading.Thread(
                target=self._flush_watchdog, name="flush-watchdog", daemon=True)
            self._watchdog_thread.start()
        # graceful-restart handshake: a parent mid-SIGUSR2 waits for the
        # ready file before it drains — written only now, with every
        # listener bound, so a wedged startup never wins a handoff
        from veneur_tpu.core import restart
        restart.mark_ready()
        import jax
        devices = jax.devices()
        # where this server runs, so no run is ambiguous about it
        self.device_info = {"platform": devices[0].platform,
                            "device_kind": devices[0].device_kind,
                            "device_count": len(devices)}
        startup = {"pid": os.getpid(),
                   "mode": "local" if self.is_local else "global",
                   **self.device_info}
        if self.store.shard_plane is not None:
            # mesh topology in the flight recorder: which devices this
            # store partitioned over, under which routing policy
            startup["mesh"] = self.store.shard_plane.describe()
        self.telemetry.record_event("startup", **startup)

    def local_addr(self, scheme: str = "udp"):
        for listener in self._listeners:
            if listener.scheme == scheme:
                return listener.address
        return None

    def _breaker_transition(self, name: str, old: str, new: str) -> None:
        """Flight-recorder hook for every breaker edge (forward + sinks)."""
        self.telemetry.record_event(
            "breaker_transition", target=name, old=old, new=new)

    def _sink_breaker(self, key: str):
        """Get-or-create the per-sink breaker (same knobs as forward)."""
        breaker = self._sink_breakers.get(key)
        if breaker is None:
            breaker = self._sink_breakers[key] = self._breaker_cls(
                failure_threshold=
                self.config.circuit_breaker_failure_threshold,
                recovery_time=self.config.circuit_breaker_recovery,
                name=key, on_transition=self._breaker_transition)
        return breaker

    def _overload_transition(self, old: str, new: str, rss: int) -> None:
        """Flight-recorder + log hook for every watermark ladder edge."""
        self.telemetry.record_event(
            "overload_state", old=old, new=new, rss_bytes=rss)

    def _supervisor_stall(self, component: str, age: float) -> None:
        """Flight-recorder hook for every freshly-detected stall."""
        self.telemetry.record_event(
            "pipeline_stall", component=component,
            heartbeat_age_s=round(age, 3))

    def _store_resize(self, family: str, old_cap: int, new_cap: int,
                      seconds: float, kind: str = "resize",
                      prewarmed: bool = False) -> None:
        """Flight-recorder hook for every column-store capacity doubling
        (kind=resize: the array re-layout, fired under the table's
        buffer lock — event recording only, never statsd) and for the
        first post-resize batch apply (kind=recompile: the jit retrace
        the new capacity forces, the TPU-specific cost — or, when the
        shape-ladder prewarmer compiled this rung ahead of time, a warm
        dispatch tagged `prewarmed`)."""
        cache = None
        if kind == "resize":
            self._cache_entries_at_resize[family] = \
                compilecache.entries()
            if self.prewarmer is not None:
                # queue the rung AFTER the one just reached, so the
                # next doubling is already compiled when it lands
                self.prewarmer.note_resize(family, new_cap)
        elif kind == "recompile":
            before = self._cache_entries_at_resize.pop(family, -1)
            after = compilecache.entries()
            if before >= 0 and after >= 0:
                cache = "miss" if after > before else "hit"
            if prewarmed and cache != "hit":
                # the shape ladder compiled this rung ahead of the
                # resize: the timed "recompile" window was a warm
                # dispatch, not a retrace
                cache = "prewarmed"
        self.telemetry.record_event(
            f"columnstore_{kind}", family=family, old_capacity=old_cap,
            new_capacity=new_cap, duration_s=round(seconds, 6),
            **({"compile_cache": cache} if cache else {}),
            **({"prewarmed": True} if prewarmed else {}))
        if kind == "recompile":
            # tag the next flush round's waterfall: recompile cost must
            # be separable from steady-state execute cost (and, with
            # the persistent cache on, whether disk served it)
            self.latency.note_retrace(family, seconds, cache=cache)

    def device_report(self) -> dict:
        """The /debug/device payload: the HBM generation ledger (by
        family / lifecycle state, with forecast and backend
        reconciliation), the kernel dispatch/compile registry, the
        shard-balance observatory, and the overload ladder's device
        watermark rung."""
        out = self.deviceobs.report()
        dw = self.overload.device_watermarks
        out["watermarks"] = {
            "state": dw.state,
            "soft_bytes": dw.soft_bytes,
            "hard_bytes": dw.hard_bytes,
            "last_bytes": dw.last_rss,
            "transitions": dw.transitions,
        }
        return out

    def adopt_flush_trace(self, trace_id: int, parent_span_id: int) -> None:
        """Called by the import server when a fresh (non-duplicate)
        forwarded payload carries trace metadata: this GLOBAL's next
        flush span parents under the originating local's interval trace
        (latest import wins — hedged duplicates were already deduped by
        token before reaching here, so exactly one import per payload
        adopts). Only the latch is written here: the flush itself calls
        set_active() when it consumes the adoption, so an import landing
        DURING a flush can't retarget the trace id that flush's ledger
        close and event stamps are about to read."""
        self._adopted_trace = (int(trace_id), int(parent_span_id))

    def cardinality_report(self, top: int = 20, name: str = "") -> dict:
        """The /debug/cardinality payload. With `name`, a single-name
        drill-down (exact per-family rows + tag-key HLL estimates);
        otherwise the top-N names by live series, per-table capacity/
        churn stats, and the watermark state. The per-name scan is
        capacity-proportional — operator-triggered only."""
        if name:
            detail = self.cardinality.name_report(name)
            exact = self.store.live_rows_by_name().get(name)
            if exact is not None:
                detail.update(exact)
            else:
                detail.setdefault("live_rows", 0)
            return detail
        per_name = self.store.live_rows_by_name()
        tracked = {r["name"]: r for r in self.cardinality.top(top)}
        # candidates = top names by exact live rows UNION the tracker's
        # top by mint activity: a hard-capped storm offender has few
        # ADMITTED rows (the cap is working), but its mint rate is the
        # very thing the operator came to see — ranking by live rows
        # alone would hide it behind any large steady keyset
        by_rows = sorted(
            per_name, key=lambda nm: (per_name[nm]["live_rows"],
                                      per_name[nm]["touched_rows"]),
            reverse=True)[:max(0, top)]
        top_list = []
        for nm in set(by_rows) | set(tracked):
            row = {"name": nm}
            row.update(per_name.get(
                nm, {"live_rows": 0, "touched_rows": 0, "families": {}}))
            rec = tracked.get(nm)
            if rec is not None:
                for field in ("mints_interval", "mints_last_interval",
                              "mint_rate_per_s", "shed_total"):
                    row[field] = rec[field]
            tag_report = self.cardinality.tag_report(nm)
            if tag_report is not None:
                row["tags"] = tag_report
            top_list.append(row)
        top_list.sort(
            key=lambda r: (r["live_rows"] + r.get("mints_interval", 0)
                           + r.get("mints_last_interval", 0)),
            reverse=True)
        del top_list[max(0, top):]
        return {
            "generated_unix": round(time.time(), 3),
            "interval_s": round(self.cardinality.interval_s, 3),
            "total_names": len(per_name),
            "tables": self.store.capacity_report(),
            "top": top_list,
            "limits": self.cardinality.limits_report(),
        }

    def ready_state(self):
        """(ready, reason) for /healthcheck/ready: not ready while the
        overload ladder is shedding, or while the flush watchdog's
        budget is blown (a wedged flush loop means this instance is
        about to abort — orchestrators should stop routing to it)."""
        if self.overload.state == overload_mod.SHEDDING:
            return False, (f"overload state {overload_mod.SHEDDING} "
                           f"(rss {self.overload.watermarks.last_rss} bytes)")
        if self.reshard.past_deadline():
            # a cutover past its deadline means the topology swap is
            # wedged (prewarm hung, device link down) — stop routing
            # to this instance until it completes or is abandoned
            return False, (f"reshard past deadline: state "
                           f"{self.reshard.state}, deadline "
                           f"{self.reshard.deadline_unix:.0f}")
        if self.config.flush_watchdog_missed_flushes > 0:
            allowed = self.config.flush_watchdog_missed_flushes * self.interval
            since = time.time() - self.last_flush_unix
            if since > allowed:
                return False, (f"flush watchdog tripped: no flush for "
                               f"{since:.1f}s (allowed {allowed:.1f}s)")
        return True, ""

    def reload_alerts(self, config_path: Optional[str] = None) -> int:
        """SIGHUP hot-reload of the `alerts:` block: re-read the config
        file (when the process has one), swap the rule table in place —
        in-flight state machines survive for rule ids present in both
        tables — and record the reload in the flight recorder. Returns
        the new rule count; raises (keeping the old table) on a bad
        rule, so a fat-fingered reload can't silence a firing alert."""
        rules = self.config.alerts.rules
        interval_s = self.config.alerts.interval
        if config_path:
            from veneur_tpu.config import read_config
            fresh = read_config(config_path)
            rules = fresh.alerts.rules
            interval_s = fresh.alerts.interval
            self.config.alerts = fresh.alerts
        n = self.alerts.configure(rules, interval_s=interval_s)
        self.telemetry.record_event("alerts_reload", rules=n,
                                    interval_s=round(interval_s, 3))
        logger.info("alerts reloaded: %d rule(s), interval %.3fs",
                    n, interval_s)
        return n

    def shutdown(self) -> None:
        self.telemetry.record_event("shutdown", pid=os.getpid())
        self._shutdown.set()
        # stop supervision first: pipeline threads exiting on the
        # shutdown path must not be flagged (or escalated) as stalls
        self.overload.stop()
        # stop the alert loop before anything drains: its captures ride
        # the shared readout executor the flush path stops below
        self.alerts.stop()
        if self.chaos is not None:
            # only clear the global seam if WE installed this plan (two
            # servers in one test process chaos independently)
            from veneur_tpu.util import chaos as chaos_mod
            if chaos_mod.active() is self.chaos:
                chaos_mod.install(None)
        # stop pull sources first (bound-join) so an in-flight scrape
        # can't ingest after the final flush below
        for source in self.sources:
            source.stop()
        for t in self._source_threads:
            t.join(timeout=2.0)
        # close listeners BEFORE the final flush so everything received
        # up to the moment of shutdown is aggregated and flushed: close()
        # joins the native pump readers, and the bounded thread joins
        # below let the pump dispatcher / Python readers drain their
        # in-flight buffers into the column store
        for listener in self._listeners:
            listener.close()
        for listener in self._listeners:
            for t in listener._threads:
                # generous bound: a pump-dispatcher drain can hit a cold
                # XLA compile; normal exit is well under a second
                t.join(timeout=15.0)
        # sentinels wake idle workers promptly; a full channel is fine —
        # workers also poll the shutdown event every 0.5s
        for _ in self._span_workers:
            try:
                self.span_chan.put_nowait(None)
            except queue.Full:
                break
        # let workers drain in-flight spans before the final flush
        for t in self._span_workers:
            t.join(timeout=2.0)
        for worker in self._span_sink_workers:
            worker.stop()
        if self.config.flush_on_shutdown:
            # final flush: the open partial interval delivers before
            # exit (a tick still running holds _flush_lock until its
            # own interval is delivered, so this one queues behind it)
            self.flush()
        if self._flush_executor is not None:
            self._flush_executor.stop()
        # the last flush is done: the readout's completion watcher goes
        self.deviceobs.close()
        if self.prewarmer is not None:
            self.prewarmer.stop()
        if self.import_server is not None:
            self.import_server.stop()
        for gi in self.grpc_ingest_servers:
            gi.stop()
        if self.http_api is not None:
            self.http_api.stop()
            self.http_api = None
        if self.profiler is not None:
            self.profiler.stop()
        if self.forward_client is not None:
            self.forward_client.close()
            # retire the forward plane's observatory queues with their
            # owner so /debug/latency reflects only live hand-offs
            self.latency.unregister_queue("forward_carryover")
            self.ledger.unstock("forward_carryover")
            self.ledger.unstock("forward_inflight")
            if self.forward_client.spool is not None:
                self.latency.unregister_queue("forward_spool")
                self.ledger.unstock("forward_spool")
                self.ledger.unstock("spool_quarantine")
        if self.backfill is not None:
            self.ledger.unstock("backfill_open")
        if self.diagnostics is not None:
            self.diagnostics.stop()
        self.trace_client.close()
        self.statsd.close()
        for sink in self.metric_sinks + self.span_sinks:
            sink.stop()
        self.shutdown_complete.set()

    # -- flush -----------------------------------------------------------

    def _tick_delay(self) -> float:
        """Clock-aligned tick (reference server.go:1458 CalculateTickDelay)."""
        interval = self.interval
        now = time.time()
        return interval - (now % interval)

    def _flush_loop(self) -> None:
        beat = self.overload.supervisor.beat
        # the first interval starts when the kernel warm-up ends: a
        # flush that races it compiles the same programs a second time,
        # and a cold compile at 100k keys is minutes, not seconds
        while self._warmup_thread.is_alive():
            beat("flush-loop")
            if self._shutdown.wait(0.2):
                return
        while not self._shutdown.is_set():
            delay = (self._tick_delay() if self.config.synchronize_with_interval
                     else self.interval)
            if self._shutdown.wait(delay):
                return
            beat("flush-loop")
            try:
                self.flush()
            except Exception:
                logger.exception("flush failed")
            # beat on completion too: a slow-but-finishing flush (cold
            # compile) clears its staleness the moment it lands
            beat("flush-loop")

    def _flush_watchdog(self) -> None:
        """Die loudly if flushes stall (reference server.go:877-919)."""
        allowed = self.config.flush_watchdog_missed_flushes * self.interval
        while not self._shutdown.wait(self.interval):
            if self._warmup_thread.is_alive():
                continue  # compiling, not stalled; _warmup restarts the clock
            since = time.time() - self.last_flush_unix
            self.telemetry.record_event(
                "watchdog_tick", since_last_flush_s=round(since, 3),
                allowed_s=allowed)
            if since > allowed:
                logger.critical(
                    "flush watchdog: no flush for %ds; aborting", allowed)
                self.telemetry.record_event(
                    "watchdog_abort", since_last_flush_s=round(since, 3))
                import faulthandler
                import os
                faulthandler.dump_traceback(all_threads=True)
                os._exit(2)

    def _warmup(self) -> None:
        """Compile, off the ingest and flush threads and before the flush
        watchdog's clock starts, every device program the configured
        capacities imply: each family's own list (`warm_programs`: batch
        apply, the digest family's `compact`, the readout with the live
        flush's percentiles and `need_export`, the zeroing of the spare;
        a sharded table's per-device applies and collective merges), run
        by `prewarm_rung` against throwaway state, never the live one.
        Cold, `compact` or a t-digest merge alone compiles for longer
        than the watchdog allows a flush, and the first hot key (or the
        first flush with data) would otherwise do it under `apply_lock`
        or the flush lock. One `warmup` event lists every program with
        its seconds and what the persistent cache served or missed; a
        program that raises is a `warmup_failed` event, logged once, and
        the remaining families are still warmed."""
        t0 = time.perf_counter()
        programs = []

        def report(family, program, seconds, cache_hits, cache_misses):
            programs.append({"family": family, "program": program,
                             "seconds": round(seconds, 6),
                             "cache_hits": cache_hits,
                             "cache_misses": cache_misses})
            self._warmup_seconds[family] = (
                self._warmup_seconds.get(family, 0.0) + seconds)

        # percentiles and need_export must match the live flush's
        # (`_swap_columnstore`): they are static arguments of the
        # readout programs, and warming another specialization would
        # leave the first real flush paying the full compile
        full_ps = tuple(self.percentiles)
        all_ps = tuple(sorted(set(full_ps) | {0.5}))
        need_export = self.is_local and self.forwarder is not None
        try:
            for family, table in self.store.tables():
                try:
                    table.prewarm_rung(
                        table.capacity,
                        all_ps if family == "histogram" else full_ps,
                        need_export=need_export, report=report)
                except Exception as e:
                    program = (e.program if isinstance(e, WarmupFailed)
                               else "list")
                    cause = e.__cause__ or e
                    logger.error("kernel warm-up failed at %s.%s",
                                 family, program, exc_info=cause)
                    self.telemetry.record_event(
                        "warmup_failed", family=family, program=program,
                        error=f"{type(cause).__name__}: {cause}")
        finally:
            self.telemetry.record_event(
                "warmup", seconds=round(time.perf_counter() - t0, 6),
                programs=programs)
            # the flush watchdog and the readiness check count from here
            self.last_flush_unix = time.time()

    def _warmup_rows(self) -> list:
        """`warmup.seconds_total{family}`: what start-up spent compiling
        (or loading from the persistent cache) each family's programs."""
        return [("warmup.seconds_total", "counter", seconds,
                 [f"family:{family}"])
                for family, seconds in sorted(self._warmup_seconds.items())]

    def flush(self) -> None:
        """One flush pass (reference flusher.go:26-122)."""
        with self._flush_lock:
            self._flush_locked()

    def _flush_locked(self) -> None:
        from veneur_tpu import trace as trace_mod
        from veneur_tpu.trace.store import trace_id_hex
        # the round's one span source: phases, spans and the profiler's
        # annotations all come from rnd.phase(...)
        rnd = FlushRound()
        flush_phase = rnd.phase("flush").start()
        preflush = rnd.phase("preflush", parent="flush").start()
        self.last_flush_unix = rnd.start_unix
        # the interval this flush's snapshot covers began at the
        # previous flush boundary: the WAL stamps it onto the
        # forwardable snapshot so a replay lands under THIS interval
        interval_start = self._interval_start_unix
        self._interval_start_unix = self.last_flush_unix
        self.flush_count += 1
        # the flush span IS the interval trace root: a local roots it on
        # the plane's pre-minted interval trace id (the same id ingest-
        # time exemplars stamped all interval), a global parents it
        # under the originating local's trace when a fresh import
        # adopted one this interval — that is what makes local flush ->
        # proxy.route -> import.merge -> global sink ack ONE trace
        plane = self.trace_plane
        adopted, self._adopted_trace = self._adopted_trace, None
        tags = {"mode": "local" if self.is_local else "global",
                "interval": str(self.flush_count)}
        if adopted and not self.is_local:
            flush_span = trace_mod.Span(
                self.trace_client, "flush", "veneur-tpu",
                trace_id=adopted[0], parent_id=adopted[1], tags=tags)
        else:
            flush_span = trace_mod.Span(
                self.trace_client, "flush", "veneur-tpu",
                trace_id=plane.interval_trace_id, tags=tags)
        traced = plane.is_sampled(flush_span.trace_id)
        plane.set_active(flush_span.trace_id if traced else 0)

        if self.config.count_unique_timeseries:
            # exact count of timeseries touched this interval (reference
            # flusher.go:43 flush.unique_timeseries_total)
            self.statsd.count(
                "flush.unique_timeseries_total",
                self.store.unique_timeseries(),
                tags=[f"global_veneur:{str(not self.is_local).lower()}"])

        with self._other_lock:
            samples, self._other_samples = self._other_samples, []
        # events/service checks are delivered inside each sink's bounded
        # flush thread below — flush_other_samples is a vendor network
        # call (e.g. datadog events POST) and used to run inline here,
        # where one hung endpoint stalled the whole flush loop

        # every per-sink flush (span and metric) runs in its own thread and
        # the whole pass is bounded by one interval — the reference's
        # context deadline (server.go:869, flusher.go:553-566). A sink
        # whose previous flush is still running is skipped this interval,
        # so a hung sink costs its own data, never the flush loop or
        # another sink's. Each sink's outcome (duration, error, skipped,
        # timed-out) lands in this round's flight-recorder entry.
        threads: List[threading.Thread] = []
        round_info = {
            "flush": self.flush_count,
            "start_unix": self.last_flush_unix,
            "mode": "local" if self.is_local else "global",
            "sinks": {},
            # the live list: a straggler's spans land after the round
            "spans": rnd.spans,
        }
        if traced:
            # cross-link: /debug/flush (and its waterfall view) point at
            # the interval's /debug/traces entry
            round_info["trace_id"] = trace_id_hex(flush_span.trace_id)

        def _start_sink_thread(key: str, target, *args) -> bool:
            """Dispatch one sink flush thread; returns False when the
            interval was NOT dispatched (skip or open breaker) so the
            forward path can stash its state into carryover instead of
            dropping it."""
            prev = self._sink_flush_threads.get(key)
            if prev is not None and prev.is_alive():
                # hard cap: one concurrent flush thread per sink. The
                # depth counts what the pileup WOULD be if each interval
                # re-created a thread against the hung sink.
                depth = self._sink_skip_depth.get(key, 0) + 1
                self._sink_skip_depth[key] = depth
                logger.warning(
                    "sink %s: previous flush still running; skipping "
                    "(pileup depth %d, capped at 1 thread)", key, depth)
                self.statsd.count("flush.sink_skipped_total", 1,
                                  tags=[f"sink:{key}"])
                round_info["sinks"][key] = {"status": "skipped",
                                            "duration_s": 0.0,
                                            "pileup_depth": depth}
                # every skipped interval is a delivery failure the hung
                # thread will never report; feeding the breaker here is
                # what takes a permanently-down sink to OPEN. The
                # forward path is exempt: ForwardClient owns its own
                # breaker (which stashes to carryover instead of
                # dropping), and two breakers on one series would fight
                # over the /metrics gauge.
                if key != "forward":
                    self._sink_breaker(key).record_failure()
                self.telemetry.record_event(
                    "sink_skipped", sink=key, flush=round_info["flush"],
                    pileup_depth=depth)
                return False
            self._sink_skip_depth.pop(key, None)
            if key != "forward" and not self._sink_breaker(key).allow():
                # open breaker: don't even spawn the thread — a sick
                # sink's interval is dropped (counted) until the
                # half-open probe closes it again
                self.statsd.count("flush.sink_breaker_open_total", 1,
                                  tags=[f"sink:{key}"])
                round_info["sinks"][key] = {"status": "breaker_open",
                                            "duration_s": 0.0}
                self.telemetry.record_event(
                    "sink_breaker_open", sink=key,
                    flush=round_info["flush"])
                return False
            t = threading.Thread(
                target=self._timed_sink_flush,
                args=(key, flush_span, traced, round_info, rnd,
                      target) + args,
                daemon=True, name=f"flush-{key}")
            t.start()
            self._sink_flush_threads[key] = t
            threads.append(t)
            return True

        for sink in self.span_sinks:
            _start_sink_thread(
                f"span:{sink.name()}", self._flush_span_sink_safe, sink)

        phases = rnd.phases
        # sample-age watermarks roll at the same boundary the column
        # store snapshots: everything stamped before this flush's
        # snapshot is aged through to sink ack below
        watermarks = self.latency.take_watermarks()
        preflush.stop()
        # the interval boundary is a generation swap (O(1) per table):
        # ingest continues into the fresh generation while this thread
        # reads the captured one out
        with rnd.phase("store_flush", parent="flush"):
            swap = swap_columnstore(
                self.store, self.is_local, self.percentiles,
                collect_forward=self.forwarder is not None,
                timing=rnd)
            with rnd.phase("readout", parent="store_flush"):
                batch, fwd = self._run_readout(swap, rnd)
        # every family was synced on its own (attribute): the
        # waterfall's per-family segment tree
        families = family_tree(rnd) if self.latency.enabled else None

        # deliver: fan the readout out to the forward plane and the
        # metric sinks, which time their encode and sends into this round
        batch.timing = rnd
        if batch.bucket_sections:
            # llhist registers leave the readout as their nonzero
            # bins: how many rows, and how many entries for them
            ll_bins = sum(b.le_idx.shape[0] for b in batch.bucket_sections)
            self.statsd.count("flush.llhist.nonzero_bins", ll_bins)
            round_info["llhist_rows"] = sum(
                b.names.shape[0] for b in batch.bucket_sections)
            round_info["llhist_nonzero_bins"] = ll_bins
        self.stats.inc("metrics_flushed", len(batch))
        # flush-stage ledger rows (informational): what the interval's
        # snapshot produced
        self.ledger.note("flush.emitted", len(batch))
        self.ledger.note("flush.forward_rows", len(fwd))

        # dispatch even with an empty snapshot when a previous
        # interval's failed state is pending (in carryover OR the
        # durable spool) — otherwise a quiet interval would strand
        # it until new traffic arrives
        pending_carryover = (
            self.forward_client is not None
            and (self.forward_client.carryover.depth > 0
                 or (self.forward_client.spool is not None
                     and self.forward_client.spool.depth > 0)))
        if self.is_local and self.forwarder is not None and (
                len(fwd) or pending_carryover):
            # flow ledger: everything snapshotted for the forward
            # plane is owed an outcome (ack / merge-away / shed /
            # inventory)
            self.ledger.note("forward.snapshot", len(fwd))
            if not _start_sink_thread(
                    "forward", self._forward_safe, fwd, interval_start) \
                    and self.forward_client is not None and len(fwd):
                # undispatched interval (previous forward still
                # hung): the snapshot is mergeable state, so it
                # carries over exactly like a failed send instead
                # of being dropped
                self.forward_client.carryover.stash(fwd)
                self.statsd.count("flush.forward_undispatched_total", 1)

        routes: Optional[BatchRoutes] = None
        if self._routing is not None:
            # a route is a function of (name, tags), which the batch
            # holds as columns: every row gets a route id here, kept
            # from the last flush where the row is unchanged, and each
            # sink thread masks its own share out of the batch
            with rnd.phase("route", parent="flush"):
                routes = self._routing.route(batch)
                routed, unrouted = routes.counts()
            for to, n in routed.items():
                self.statsd.count("flush.route.routed_rows", n,
                                  tags=[f"sink:{to}"])
            self.statsd.count("flush.route.unrouted_rows", unrouted)
            self.statsd.count("flush.route.evaluated_rows", routes.evaluated)
            self.statsd.count("flush.route.cached_rows", routes.cached)
            round_info["routing"] = {
                "rules": len(self._routing.rules), "routed": routed,
                "unrouted": unrouted, "evaluated": routes.evaluated,
                "cached": routes.cached}

        for sink in self.metric_sinks:
            key = f"metric:{sink.name()}"
            # per-sink gate: another sink's pending spill must not
            # dispatch this one — a no-op flush would still
            # thread-spawn and (worse) count as a probe against
            # this sink's breaker
            if len(batch) or samples or key in self._sink_spill:
                # from here to the sink's own flush call: thread
                # start, events, spill, routing
                starting = rnd.phase("egress_start", parent="flush",
                                     sink=key).start(handoff=True)
                _start_sink_thread(
                    key, self._flush_sink_safe, key, sink, batch,
                    samples, starting, routes)

        # bounded wait: one interval from flush start, minus time already
        # spent; stragglers keep running on their daemon threads and are
        # skipped next interval if still alive. The shutdown flush gets a
        # generous grace instead, so the final interval's metrics are
        # delivered before daemon threads die with the process.
        grace = (max(self.interval, 30.0) if self._shutdown.is_set()
                 else self.interval)
        deadline = rnd.t0 + grace
        with rnd.phase("sink_join", parent="flush"):
            for t in threads:
                remaining = deadline - time.perf_counter()
                if remaining <= 0:
                    break
                t.join(remaining)
        stuck = [t.name for t in threads if t.is_alive()]
        if stuck:
            logger.error(
                "flush exceeded the %.1fs interval; still running: %s",
                self.interval, ", ".join(stuck))
            self.statsd.count("flush.timeout_total", len(stuck))
            for name in stuck:
                key = name[len("flush-"):]
                # the sink thread holds the same outcome dict: if it
                # lands after this round is recorded, its final status
                # overwrites timed_out (flagged `late`)
                entry = round_info["sinks"].setdefault(key, {})
                entry.setdefault("status", "timed_out")
                # a hang is a failure the sink thread will never report
                # itself: feed the breaker here so a permanently-down
                # sink ends at ONE live thread + an OPEN breaker instead
                # of silent per-interval skips (forward exempt: the
                # client's breaker + carryover own that path)
                if key != "forward":
                    self._sink_breaker(key).record_failure()
                self.telemetry.record_event(
                    "sink_timeout", sink=key, flush=round_info["flush"])

        if routes is not None:
            # objects exist only where a sink could not take its share
            # by columns (a filter, a spill, a sink without flush_batch)
            built = routes.materialized_rows()
            self.statsd.count("flush.route.materialized_rows", built)
            round_info["routing"]["materialized"] = built
        if self.import_server is not None:
            # per-RPC latency/error aggregates (reference proxy/grpcstats)
            self.import_server.rpc_stats.emit(self.statsd, prefix="import.rpc")
        # sink joins are the ack point: everything dispatched this round
        # has been delivered (or timed out, recorded above) — the moment
        # the interval's samples stop aging
        ack_unix = time.time()
        self.latency.observe_sample_age(watermarks, ack_unix)
        if traced and watermarks:
            # anchor the interval's worst-case staleness to its trace:
            # the pipeline.sample_age rows in /metrics carry an
            # OpenMetrics exemplar pointing at this flush
            oldest = min(mark[0] for mark in watermarks.values())
            self.trace_plane.exemplars.capture(
                "pipeline.sample_age", max(0.0, ack_unix - oldest),
                flush_span.trace_id, ts=ack_unix)
        # retrace tags drain once per tick, onto the families tree of
        # the interval the pending recompile preceded
        retraces = self.latency.drain_retraces()
        if families:
            for family, (secs, cache) in retraces.items():
                frec = families.get(family)
                if frec is not None:
                    frec["retrace"] = True
                    frec["recompile_s"] = round(secs, 6)
                    if cache:
                        frec["compile_cache"] = cache
            self._record_family_spans(flush_span, families)
        flush_span.finish()
        duration = flush_phase.stop()["wall_s"]
        phases["flush_cpu_s"] = rnd.cpu_s()
        # every span in which the flush thread stood still for the chip:
        # the `sync` spans and, where the sets' estimate ran, `set_wait`
        phases["chip_wait_s"] = (phases.get("sync_s", 0.0)
                                 + phases.get("set_wait_s", 0.0))
        # the set family's share of `chip_busy_s`: device time up to the
        # estimate's completion stamp, where the round has one
        set_busy = rnd.spans_of("chip_busy", family="set")
        if set_busy:
            phases["set_chip_busy_s"] = sum(s["wall_s"] for s in set_busy)
        self.statsd.gauge("flush.total_duration_ns", int(duration * 1e9))
        self.statsd.timing("flush.total_duration", duration)
        for phase, secs in phases.items():
            self.statsd.timing("flush.phase_duration", secs,
                               tags=[f"phase:{phase}"])
        self.statsd.count("flush.metrics_total", len(batch))
        round_info["duration_s"] = round(duration, 6)
        round_info["metrics_flushed"] = len(batch)
        round_info["phases"] = {k: round(v, 6) for k, v in phases.items()}
        if families:
            round_info["families"] = _round_family_tree(families)
        self.telemetry.flushes.record(round_info)
        self.telemetry.record_event(
            "flush", flush=round_info["flush"],
            duration_s=round_info["duration_s"],
            metrics=len(batch),
            phases=round_info["phases"],
            sinks={k: v.get("status", "running")
                   for k, v in round_info["sinks"].items()})
        # cumulative process counters emit as gauges (they never reset)
        self.statsd.gauge("worker.metrics_processed_total",
                          int(self.stats["packets_received"]))
        span_sink_drops = 0
        for w in self._span_sink_workers:
            span_sink_drops += w.dropped
            if w.dropped or w.ingested:
                # per-sink shed visibility: drop RATE is the signal that
                # a sink's buffer is undersized for the offered load
                self.statsd.gauge("worker.ssf.sink.dropped_total",
                                  w.dropped,
                                  tags=[f"sink:{w.sink.name()}"])
                self.statsd.gauge("worker.ssf.sink.ingested_total",
                                  w.ingested,
                                  tags=[f"sink:{w.sink.name()}"])
        if self.spans_dropped or span_sink_drops:
            self.statsd.gauge("worker.ssf.spans_dropped_total",
                              self.spans_dropped + span_sink_drops)
        self._reclaim_idle_rows()
        # interval close for the flow ledger: fold the probe deltas,
        # read the inventory stocks, run every conservation check. In
        # strict mode (tests) an imbalance raises out of flush(); in
        # production it exports ledger.imbalance and records an event.
        if self.ledger.enabled:
            ledger_record = self.ledger.close_interval()
            round_info["ledger"] = ledger_record.get("imbalance", {})
        # interval-trace rollover LAST (the ledger close above stamps
        # this interval's trace id): mint the next interval's id, reset
        # the exemplar capture budget, and refresh the watched
        # heavy-hitter names from the cardinality observatory
        self.trace_plane.roll(
            [rec["name"] for rec in self.cardinality.top(16)])

    def _run_readout(self, swap: dict, rnd: FlushRound):
        """The readout half of one flush, on the flush thread: drain
        the swapped generations (kernel dispatch, device sync, transfer,
        assembly) plus the backfill drain, whose metrics carry their
        ORIGINAL timestamps, and pre-encode the forward payload."""
        batch, fwd = readout_columnstore(
            self.store, swap, self.is_local, self.aggregates,
            collect_forward=self.forwarder is not None,
            timing=rnd, attribute=self.latency.enabled)
        if self.backfill is not None:
            # closed historical buckets flush alongside the live
            # interval, each series timestamped at its ORIGINAL
            # interval start — backfilled history, not a traffic spike
            backfilled = self.backfill.drain()
            if backfilled:
                batch.extras.extend(backfilled)
                self.statsd.count("flush.backfilled_series_total",
                                  len(backfilled))
        if self.is_local and self.forwarder is not None and len(fwd):
            # wire-encode the forward payload HERE, on the flush
            # thread, under the round's own span — the forward thread
            # finds fwd.wire pre-built and skips straight to the POST.
            # Carryover merges invalidate it.
            from veneur_tpu.forward.convert import forwardable_to_wire
            with rnd.phase("forward_encode", parent="readout"):
                try:
                    fwd.wire = forwardable_to_wire(fwd)
                except Exception:
                    fwd.wire = None  # forward thread re-encodes
                    logger.exception("forward pre-encode failed")
        return batch, fwd

    def _readout_executor(self):
        """Get-or-create the worker the query plane's live reads run
        their readouts on (core/query.py), supervised as `flush-readout`
        like the flush loop itself — a wedged readout (hung device link
        mid-transfer) trips the same stall ladder."""
        if self._flush_executor is None:
            from veneur_tpu.core.flushexec import FlushReadoutExecutor
            self.overload.supervisor.register(
                "flush-readout", deadline=max(
                    self.overload.supervisor.deadline,
                    2.5 * self.interval, 60.0))
            self._flush_executor = FlushReadoutExecutor(
                beat=self.overload.supervisor.beat)
        return self._flush_executor

    def _reclaim_idle_rows(self) -> None:
        """Idle-key reclamation + intern-table self-metrics, once per
        flush: tombstoned rows lose their native intern mappings
        immediately; their ids are recycled by the tables one flush later
        (columnstore._BaseTable.reclaim_idle). Bounds host memory under
        key churn (the reference instead resets ALL sampler state every
        interval, worker.go:470-489)."""
        from veneur_tpu import native

        idle = self.config.tpu.idle_key_intervals
        store = self.store
        tables = (
            (store.counters, native.FAM_COUNTER),
            (store.gauges, native.FAM_GAUGE),
            (store.histos, native.FAM_HISTO),
            (store.sets, native.FAM_SET),
            (store.llhists, native.FAM_LLHIST),
            (store.statuses, None),  # never registered natively
        )
        # intern-table sweep target: the C++ engine, or the numpy
        # fallback decoder (same unregister_rows_multi contract)
        engine = (self._ingester._engine
                  if getattr(self, "_ingester", None) is not None
                  else getattr(self, "_py_ingester", None))
        if idle > 0:
            pairs = []
            for table, family in tables:
                try:
                    evicted = table.reclaim_idle(idle)
                except Exception:
                    logger.exception("idle-row reclamation failed")
                    continue
                if evicted and family is not None:
                    pairs.extend((family, row) for row in evicted)
            if pairs and engine is not None:
                # one combined intern-table sweep per flush: the pump
                # readers block on the shared lock once, not per family
                engine.unregister_rows_multi(pairs)
        self.statsd.gauge(
            "intern.rows_total",
            sum(len(t.rows) for t, _f in tables))
        if engine is not None:
            self.statsd.gauge("intern.native_table_size", engine.size())
        dropped = sum(t.keys_dropped for t, _f in tables)
        if dropped > self._keys_dropped_reported:
            self.statsd.count("intern.keys_dropped_total",
                              dropped - self._keys_dropped_reported)
            self._keys_dropped_reported = dropped
        # interval rollover AFTER reclaim so eviction-driven live-count
        # decrements land in the interval they happened in; this resets
        # the per-name mint budgets (the shed rung's immediate recovery)
        self.cardinality.roll_interval()

    def _record_family_spans(self, flush_span, families: dict) -> None:
        """Matching child spans under the flush span, one per family of
        the round's `families` tree (`latency.family_tree`, built from
        the readout's spans): from the start of the family's dispatch to
        the end of its transfer, on the READOUT's wall clock — an async
        readout runs after its interval's flush span finished, and
        stamping it off this tick's flush time would both misplace the
        segments and parent them under the wrong interval's trace."""
        for family, rec in families.items():
            tags = {"family": family,
                    "dispatch_s": f"{rec.get('dispatch_s', 0.0):.6f}",
                    "transfer_s": f"{rec.get('transfer_s', 0.0):.6f}"}
            for dev, seg in rec.get("devices", {}).items():
                tags[f"sync_s.{dev}"] = f"{seg.get('sync_s', 0.0):.6f}"
            if rec.get("retrace"):
                tags["retrace"] = "true"
                tags["recompile_s"] = f"{rec.get('recompile_s', 0.0):.6f}"
                if rec.get("compile_cache"):
                    tags["compile_cache"] = rec["compile_cache"]
            child = flush_span.child("flush.family", tags=tags)
            child.proto.start_timestamp = int(rec["start_unix"] * 1e9)
            child.finish(end_time=rec["end_unix"])

    def _timed_sink_flush(self, key: str, parent_span, span_traced,
                          round_info: dict, rnd: FlushRound, target,
                          *args) -> None:
        """Body of one per-sink flush thread: a `sink` span of the
        round, a child span under the DELIVERED interval's flush span
        stamped from it (an async round delivers the previous interval's
        readout — its sink spans parent there), the sink-outcome row
        shared with the flight recorder, and the per-sink duration
        self-metric."""
        outcome = round_info["sinks"].setdefault(key, {})
        child = parent_span.child("flush.sink", tags={"sink": key})
        # make this sink's span the ambient parent for the duration of
        # the flush call (each sink thread has its own context): the
        # forward client reads it to inject (trace_id, span_id) gRPC
        # metadata, which is how the interval trace crosses the tier.
        # Gated on the delivered round being traced so unsampled
        # intervals add no metadata downstream. The round itself is
        # ambient too, for a sink's legacy flush(list) to time into.
        ctx_token = None
        if span_traced:
            from veneur_tpu.trace import context as trace_ctx
            ctx_token = trace_ctx._current_span.set(child)
        round_token = current_round.set(rnd)
        try:
            with rnd.phase("sink", parent="flush", sink=key) as sink_span:
                ok = target(*args)
        finally:
            current_round.reset(round_token)
            if ctx_token is not None:
                from veneur_tpu.trace import context as trace_ctx
                trace_ctx._current_span.reset(ctx_token)
        duration = sink_span["wall_s"]
        was_timed_out = outcome.get("status") == "timed_out"
        breaker = self._sink_breakers.get(key)
        # ok is None when the sink was never exercised (nothing to
        # deliver): feeding the breaker then would let a quiet interval
        # reset a sick sink's failure streak or close its half-open
        # breaker without a real probe. A hung flush that finally fails
        # also stays silent — the deadline sweep already counted that
        # delivery failure, and counting it twice would open the breaker
        # after ~threshold/2 sick intervals.
        if breaker is not None and ok is not None:
            if ok:
                # a late success after a timed_out round still closes
                # the breaker — the sink proved it can deliver again
                breaker.record_success()
            elif not was_timed_out:
                breaker.record_failure()
        if ok is False:
            child.error()
        started_unix = rnd.start_unix + sink_span["start_s"]
        child.proto.start_timestamp = int(started_unix * 1e9)
        child.finish(end_time=started_unix + duration)
        # what the sink encoded and posted, where it times them into the
        # round (its egress_encode and egress_post_wall spans: this
        # thread's, inside this call)
        for span in list(rnd.spans):
            if (span["thread"] != sink_span["thread"]
                    or span["start_s"] < sink_span["start_s"]):
                continue
            if span["name"] == "egress_post_wall":
                fields = ("bodies", "bytes", "gzip_bytes",
                          "bodies_overlapped", "workers")
            elif span["name"] == "egress_encode" and "encoder" in span:
                outcome["encoder"] = span["encoder"]
                fields = ("native_rows", "prefix_renders",
                          "count_mismatch")
            else:
                continue
            for field in fields:
                outcome[field] = outcome.get(field, 0) + span.get(field, 0)
        if was_timed_out:
            # finished after its round was declared over — keep that
            # visible while still landing the real outcome
            outcome["late"] = True
        outcome["status"] = "error" if ok is False else "ok"
        outcome["duration_s"] = round(duration, 6)
        self.statsd.timing(
            "flush.sink_duration", duration,
            tags=[f"sink:{key}", f"status:{outcome['status']}"])
        if ok is False:
            self.telemetry.record_event(
                "sink_error", sink=key, flush=round_info["flush"],
                duration_s=outcome["duration_s"])
        if key == "forward":
            self.telemetry.record_event(
                "forward", status=outcome["status"],
                flush=round_info["flush"],
                duration_s=outcome["duration_s"])

    def _forward_safe(self, fwd: ForwardableState,
                      interval_start: float = 0.0) -> bool:
        try:
            if self._forwarder_takes_interval():
                self.forwarder(fwd, interval_start)
            else:
                # duck-typed forwarder predating the interval stamp
                self.forwarder(fwd)
            return True
        except Exception:
            logger.exception("forward failed")
            return False

    def _forwarder_takes_interval(self) -> bool:
        """Signature-based capability check (NOT a TypeError catch: a
        TypeError from inside the forwarder must never re-invoke it —
        in WAL mode a second call would append the same snapshot under
        a second token and double-merge)."""
        import inspect
        try:
            sig = inspect.signature(self.forwarder)
            params = list(sig.parameters.values())
        except (TypeError, ValueError):
            return True  # builtins/partials: assume the full contract
        if any(p.kind == p.VAR_POSITIONAL for p in params):
            return True
        positional = [p for p in params
                      if p.kind in (p.POSITIONAL_ONLY,
                                    p.POSITIONAL_OR_KEYWORD)]
        return len(positional) >= 2

    def _flush_span_sink_safe(self, sink) -> bool:
        try:
            sink.flush()
            return True
        except Exception:
            logger.exception("span sink %s flush failed", sink.name())
            return False

    def _flush_sink_safe(self, key: str, sink, batch: FlushBatch,
                         other_samples=(), starting=None,
                         routes: Optional[BatchRoutes] = None
                         ) -> Optional[bool]:
        """Returns True/False for a delivery attempt, None when the sink
        was never exercised (nothing to flush) — None must not feed the
        sink's breaker. `starting` is the round's `egress_start` phase,
        begun where the flush loop dispatched this thread; it ends where
        the sink's own flush is called. `routes` are the flush's routes
        where routing is on: the sink gets its share of the batch."""
        ok = True
        if other_samples:
            try:
                sink.flush_other_samples(other_samples)
            except Exception:
                logger.exception("sink %s flush_other_samples failed",
                                 sink.name())
                ok = False
        # bounded retry spill: a batch that failed LAST interval gets
        # exactly one more delivery attempt, prepended to this one
        spill = self._sink_spill.pop(key, None)
        if spill:
            self.statsd.count("flush.spill_retry_total", len(spill),
                              tags=[f"sink:{key}"])
        if not len(batch) and not spill:
            return ok if other_samples else None
        name = sink.name()
        sc = self._sink_filters.get(name)
        # columnar fast path: no per-sink filtering and no spill to
        # prepend, so the sink sees its share as a FlushBatch (the
        # default flush_batch materializes; blackhole and friends never
        # do); else the share's objects, filtered
        columnar = sc is None and not spill

        def objects(parent: str) -> List[InterMetric]:
            share = batch if routes is None else routes.share(name)
            with batch.timing.phase("materialize", parent=parent, sink=key):
                built = share.materialize()
            return built if sc is None else _apply_sink_filters(built, sc)

        current: Optional[List[InterMetric]] = None
        try:
            if self.chaos is not None:
                self.chaos.inject("sink_flush")
            share = batch
            if routes is not None or not columnar:
                with batch.timing.phase("egress_select", parent="sink",
                                        sink=key):
                    if columnar:
                        share = routes.share(name)
                    else:
                        current = objects("egress_select")
            if starting is not None:
                starting.stop()
            if not columnar:
                sink.flush(spill + current if spill else current)
                self.ledger.note("egress.acked",
                                 len(current) + len(spill or ()), key=name)
                return ok
            # getattr: duck-typed sinks that only implement flush()
            # still work
            fb = getattr(sink, "flush_batch", None)
            if fb is not None:
                fb(share)
            else:
                sink.flush(share.materialize())
            self.ledger.note("egress.acked", len(share), key=name)
            return ok
        except Exception:
            logger.exception("sink %s flush failed", sink.name())
            # keep THIS interval's metrics for one retry next interval;
            # a spill that just failed its retry is shed (loudly) so the
            # buffer never exceeds one interval of data
            if spill:
                self.statsd.count("flush.spill_shed_total", len(spill),
                                  tags=[f"sink:{key}"])
                self.ledger.note("egress.shed", len(spill), key=key)
                logger.error(
                    "sink %s: shedding %d spilled metrics after a failed "
                    "retry (one-interval spill bound)", key, len(spill))
            if current is None:
                # spill only this sink's routed+filtered share, or the
                # next interval would deliver it metrics that routing
                # excluded — and double-deliver them elsewhere. Only
                # here does a columnar flush build objects
                try:
                    current = objects("sink")
                except Exception:
                    logger.exception(
                        "sink %s: selection failed while spilling; "
                        "shedding the interval", key)
                    current = []
            if current:
                self._sink_spill[key] = current
                self.ledger.note("egress.spilled", len(current), key=key)
            return False


def _round_family_tree(families: dict) -> dict:
    """Round the flusher's per-family segment tree for the flight
    recorder / waterfall JSON (floats to µs precision, structure kept)."""
    out = {}
    for family, rec in families.items():
        entry = {k: (round(v, 6) if isinstance(v, float) else v)
                 for k, v in rec.items() if k != "devices"}
        entry["devices"] = {
            dev: {k: round(v, 6) for k, v in seg.items()}
            for dev, seg in rec.get("devices", {}).items()}
        out[family] = entry
    return out


def _apply_sink_filters(metrics: List[InterMetric], sc: SinkConfig
                        ) -> List[InterMetric]:
    """Per-sink filtering: max name/tag limits, strip/add tags
    (reference flusher.go:138-213)."""
    from veneur_tpu.util.matcher import TagMatcher
    strip = [TagMatcher.from_config(t) for t in sc.strip_tags]
    out = []
    for metric in metrics:
        if sc.max_name_length and len(metric.name) > sc.max_name_length:
            continue
        tags = metric.tags
        if strip:
            tags = [t for t in tags
                    if not any(sm.match(t) for sm in strip)]
        if sc.add_tags:
            tags = sorted(set(tags) | {
                f"{k}:{v}" if v else k for k, v in sc.add_tags.items()})
        if sc.max_tag_length and any(len(t) > sc.max_tag_length for t in tags):
            continue
        if sc.max_tags and len(tags) > sc.max_tags:
            continue
        if tags is not metric.tags:
            metric = InterMetric(
                name=metric.name, timestamp=metric.timestamp,
                value=metric.value, tags=tags, type=metric.type,
                message=metric.message, hostname=metric.hostname,
                backfilled=metric.backfilled)
        out.append(metric)
    return out
