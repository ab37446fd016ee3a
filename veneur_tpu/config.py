"""Configuration: YAML file + VENEUR_* environment overlay.

Field parity with reference config.go:12-135 (same yaml keys, same
defaults: interval 10s, metric_max_length 4096, read buffer 2 MiB,
aggregates min/max/count), plus a `tpu` block for the device column store
(capacities, batch size). Durations accept Go-style strings ("10s",
"500ms") or numbers of seconds. Environment variables VENEUR_<UPPERFIELD>
override file values (reference README.md:236-247 envconfig behavior).
"""

from __future__ import annotations

import os
import re
import socket
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional

import yaml

from veneur_tpu.util.secret import StringSecret

_DURATION_RE = re.compile(r"(\d+(?:\.\d+)?)(ns|us|µs|ms|s|m|h)")
_DURATION_UNITS = {"ns": 1e-9, "us": 1e-6, "µs": 1e-6, "ms": 1e-3,
                   "s": 1.0, "m": 60.0, "h": 3600.0}


def parse_duration(v: Any) -> float:
    """Go-style duration to seconds."""
    if v is None:
        return 0.0
    if isinstance(v, (int, float)):
        return float(v)
    s = str(v).strip()
    if not s:
        return 0.0
    matches = _DURATION_RE.findall(s)
    if not matches or "".join(f"{n}{u}" for n, u in matches) != s:
        raise ValueError(f"invalid duration: {v!r}")
    return sum(float(n) * _DURATION_UNITS[u] for n, u in matches)


@dataclass
class SinkConfig:
    kind: str = ""
    name: str = ""
    config: Dict[str, Any] = field(default_factory=dict)
    max_name_length: int = 0
    max_tag_length: int = 0
    max_tags: int = 0
    strip_tags: List[Dict[str, Any]] = field(default_factory=list)
    add_tags: Dict[str, str] = field(default_factory=dict)


@dataclass
class SourceConfig:
    kind: str = ""
    name: str = ""
    config: Dict[str, Any] = field(default_factory=dict)
    tags: List[str] = field(default_factory=list)


@dataclass
class SinkRoutingConfig:
    name: str = ""
    match: List[Dict[str, Any]] = field(default_factory=list)
    matched: List[str] = field(default_factory=list)
    not_matched: List[str] = field(default_factory=list)


@dataclass
class Features:
    diagnostics_metrics_enabled: bool = False
    enable_metric_sink_routing: bool = False


@dataclass
class TpuConfig:
    """Device column-store sizing (no reference equivalent; this is the
    TPU-native replacement for num_workers map sharding)."""

    counter_capacity: int = 4096
    gauge_capacity: int = 4096
    histo_capacity: int = 4096
    set_capacity: int = 1024
    # log-linear histogram rows (each is ~18 KB of int32 bins on
    # device); size to the llhist-keyed cardinality, not total keys
    llhist_capacity: int = 1024
    batch_cap: int = 8192
    # local devices to shard the column store across: every family's
    # interval state partitions over this many devices (digest-home
    # routing, collective interval merges — core.sharded_tables /
    # parallel.collectives). 0/1 = single-device tables.
    shards: int = 1
    # shard routing policy: "digest" (default — a key's 64-bit digest
    # picks its home shard at mint time; all five families shard and
    # the merged flush is bit-identical to single-device) or
    # "roundrobin" (legacy A/B escape hatch — batches rotate across
    # shards; only the histogram/set families shard, because rotation
    # destroys the per-key ordering gauges need and the key-range
    # invariant failover re-homing relies on)
    shard_routing: str = "digest"
    # force the pure-Python per-packet parser (the C++ batch parser is
    # used whenever it compiles; this is the escape hatch)
    disable_native_parser: bool = False
    # idle-row reclamation: a key idle for this many flushes is evicted
    # (dict entry + native intern mapping removed, row id recycled one
    # flush later), bounding host memory under key churn the way the
    # reference's per-interval map swap does (worker.go:470-489).
    # 0 disables eviction.
    idle_key_intervals: int = 5
    # hard per-family cardinality cap: new keys beyond it are dropped
    # (and counted) until eviction frees rows. 0 = unlimited.
    max_rows_per_family: int = 2_000_000
    # set-family tier crossover: a set key's samples accumulate as
    # host-side sparse COO until the key sees this many samples within
    # one interval, then the key promotes to a dense device row and its
    # stream rides the scatter-max kernel. 0 = auto: 16 on a real
    # accelerator (at sustained rates the host tier's per-flush sort is
    # the cost, and a promoted row is 16 KB of HBM — cheap until
    # cardinality is huge, see set_max_dev_slots), 2048 on the CPU
    # backend where the "device" is the same host core and promoting
    # buys nothing.
    set_promote_samples: int = 0
    # hard cap on promoted device rows (HBM guard: slots are 16 KiB
    # each; 65536 = 1 GiB a generation). Keys past the cap stay on the
    # host tier. Raising it, budget for the rung the bank climbs to
    # (256, 2,048, 16,384, 131,072: 8x a rung, capped here) times the
    # generations held at once: the live bank and the one a flush
    # captured, 2 x 2 GiB at 131,072 slots (a climb briefly holds the
    # old rung beside the new). At 131,072 the v5e compiler keeps no
    # whole-bank temporary in the scatter or the estimate; at 16,384
    # the scatter keeps one (256 MiB). The flush's backlog fold
    # (`batch_hll.fold_backlog`) keeps none at either.
    set_max_dev_slots: int = 65536


@dataclass
class AlertsConfig:
    """Declarative alert rule table (core/alerts.py). Each rule is a
    mapping — {id, metric, kind, op, threshold, q, for, tags, lo, hi} —
    validated at engine load, not here, so a SIGHUP reload of a bad
    table reports the offending rule instead of failing config parse."""

    enabled: bool = True
    interval: float = 1.0  # duration between evaluation rounds
    rules: List[Dict[str, Any]] = field(default_factory=list)

    def __post_init__(self) -> None:
        self.interval = parse_duration(self.interval) or 1.0


@dataclass
class Config:
    aggregates: List[str] = field(default_factory=lambda: ["min", "max", "count"])
    count_unique_timeseries: bool = False
    debug: bool = False
    enable_profiling: bool = False
    # when set, jax.profiler.start_server(port) for live
    # TensorBoard capture of device profiles
    profile_server_port: int = 0
    # Go-runtime profiler rates (reference config.go:14,35), accepted so
    # a reference config stays valid under validate-config-strict; the
    # Python runtime has no block/mutex profiler — the /debug/pprof
    # endpoints (core/profiling.py) are this rebuild's analog
    block_profile_rate: int = 0
    mutex_profile_fraction: int = 0
    extend_tags: List[str] = field(default_factory=list)
    features: Features = field(default_factory=Features)
    flush_on_shutdown: bool = False
    flush_watchdog_missed_flushes: int = 0
    forward_address: str = ""
    forward_only: bool = False
    # which sketch family aggregates DogStatsD histogram/timer samples:
    # "tdigest" (reference parity: approximate percentiles, compressed
    # merges) or "circllhist" (log-linear bins: globally-EXACT
    # percentiles through the forward tier, one-bin-width quantile
    # error). Explicit `|l` samples and OTLP exponential histograms
    # always use the circllhist family regardless of this switch.
    histogram_encoding: str = "tdigest"
    # -- egress resilience (util/resilience.py) -------------------------
    # forward retry: jittered exponential backoff, total spend bounded by
    # the flush interval (a retry storm can never blow the flush budget)
    forward_retry_max_attempts: int = 3
    forward_retry_base: float = 0.2    # duration; first backoff cap
    forward_retry_max: float = 2.0     # duration; per-retry backoff cap
    # per-destination/per-sink circuit breakers: consecutive failures to
    # open, and how long to stay open before the single half-open probe
    circuit_breaker_failure_threshold: int = 3
    circuit_breaker_recovery: float = 30.0  # duration
    # failed forward intervals merge (losslessly — counters sum, digests
    # recompress, HLL registers max) into the next snapshot, for at most
    # this many consecutive intervals; beyond it the state is shed loudly.
    # 0 disables carryover (fail-and-forget, the pre-resilience behavior).
    carryover_max_intervals: int = 3
    # -- durable carryover spill (util/spool.py) ------------------------
    # when set, carryover past the bound is serialized (metricpb wire,
    # the same bytes a forward send carries) into this directory instead
    # of shed, drained oldest-first when the destination recovers, and
    # replayed on process restart. Empty = shed at the bound (above).
    carryover_spool_dir: str = ""
    carryover_spool_max_bytes: int = 256 * 1024 * 1024
    carryover_spool_max_segments: int = 1024
    # quarantine bound: undeliverable segments move to
    # <spool_dir>/quarantine (an inventory stock the flow ledger books,
    # carryover.spool.quarantined) instead of dying in place; past
    # these bounds the OLDEST quarantined segments are purged and their
    # metrics booked as explained shed
    carryover_spool_quarantine_max_bytes: int = 64 * 1024 * 1024
    carryover_spool_quarantine_max_segments: int = 256
    # -- durable interval WAL (util/spool.py + forward/backfill.py) -----
    # forward_wal: with a spool dir configured, EVERY forwardable
    # interval snapshot is appended to the spool — stamped with its
    # interval-start timestamp, fsync'd — BEFORE the send attempt, and
    # the oldest-first drain is the only send path. kill -9 anywhere
    # between append and ack replays the interval at restart,
    # exactly-once via per-segment idempotency tokens (stable across
    # restarts). Off = the PR-7 behavior (spool only past the
    # carryover bound).
    forward_wal: bool = False
    # -- elastic resharding (parallel/reshard.py) -----------------------
    # range-segment WAL for live N->M cutovers: the captured per-range
    # state is appended here (one segment per migrating digest range,
    # fsync'd) BEFORE any state moves, so a SIGKILL anywhere mid-reshard
    # replays exactly-once at restart. Empty falls back to
    # <carryover_spool_dir>/reshard when that is set; with neither, a
    # cutover still works but loses its crash-replay guarantee (logged
    # loudly, flagged in /debug/reshard).
    reshard_spool_dir: str = ""
    # a plan (prewarm) + cutover that has not completed this long after
    # begin() flips /healthcheck/ready to 503 with a JSON reason
    reshard_deadline: float = 30.0  # duration
    # segments whose interval stamp is older than this many flush
    # intervals are BACKFILL: the local drains them behind fresh
    # segments under the replay token bucket below, and the receiving
    # global buckets them by original interval (bounded open buckets,
    # original-timestamp emission) instead of the live flush
    wal_stale_after_intervals: float = 2.0
    # replay throttle (core/overload.py TokenBucket, metrics/second;
    # 0 = full speed): bounds how fast an hours-stale backlog drains so
    # live forward traffic is never starved of the flush budget. Each
    # drain always moves at least one segment (progress + breaker
    # probes stay live).
    wal_replay_rate_limit: float = 0.0
    wal_replay_burst: float = 2.0  # seconds of rate headroom
    # bounded open historical buckets on the receiving tier (0 disables
    # the backfill plane: stale imports merge into the live interval,
    # the pre-WAL behavior)
    backfill_max_open_intervals: int = 8
    # persistent JAX compilation cache directory: a crash-restart-
    # replay cycle (and any cold start) reuses compiled flush/ingest
    # kernels from disk instead of paying the full retrace mid-
    # recovery. Empty = in-memory compilation only.
    jax_compilation_cache_dir: str = ""
    # (hedged forwards are a proxy-tier knob — `hedge_after` in the
    # proxy yaml; the local forward client has one upstream and gets
    # duplicate-safety from its per-interval idempotency token alone)
    # -- flow ledger (core/ledger.py) -----------------------------------
    # per-interval conservation accounting from socket to sink ack:
    # stage counters stamped at every pipeline crossing, reconciled at
    # flush close (ingested = aggregated + rejected; snapshotted =
    # acked + merged-away + shed, with carryover/spool/in-flight as
    # inventory). Nonzero unexplained imbalance exports
    # ledger.imbalance{identity:} and records a flight-recorder event;
    # ledger_strict additionally makes it RAISE at interval close (for
    # tests/soaks — never on in production, where a transient mid-send
    # close can show a one-interval blip that nets out).
    ledger_enabled: bool = True
    ledger_strict: bool = False
    ledger_history: int = 32
    # -- cross-tier self-tracing (trace/store.py) -----------------------
    # fraction of flush intervals whose self-trace is recorded AND
    # propagated across the forward tier (1.0 = every interval; a
    # deterministic 1-in-N below that). Unsampled intervals still get a
    # flush span through the SSF pipeline, but nothing lands in the
    # bounded trace store and no trace metadata rides the forward RPCs,
    # so downstream tiers do zero tracing work for them.
    trace_self_sample_rate: float = 1.0
    # bounded /debug/traces store: traces kept (LRU) and spans per trace
    trace_store_traces: int = 128
    trace_store_spans: int = 256
    # exemplars: per-series (trace_id, value, timestamp) captured at
    # ingest for heavy-hitter + llhist series, merged latest-wins across
    # the forward tier, rendered in OpenMetrics exemplar syntax by
    # /metrics and the Prometheus/Cortex sinks. Bounds the name set.
    trace_exemplar_names: int = 64
    # -- latency observatory (core/latency.py) --------------------------
    # per-family×device flush dispatch attribution, per-plane end-to-end
    # sample-age llhists, and queue dwell/depth telemetry. On by default
    # (total cost is pinned under 2% of flush wall time by a soak);
    # false hands out plain queues and skips all attribution.
    latency_observatory: bool = True
    # -- shape ladder (core/flushexec.py) -------------------------------
    # prewarm_ladder compiles each family's NEXT capacity rung's
    # kernels (apply + readout + zeroing) in a background thread — at
    # startup and again on every resize event — so a capacity doubling
    # never retraces on the hot path: the post-resize round's retrace
    # tag reads prewarmed:true (or compile_cache:hit when the
    # persistent cache served it). Off by default to keep short-lived
    # processes (tests, CLIs) from paying the extra compiles.
    prewarm_ladder: bool = False
    # -- ingest admission control (core/overload.py) --------------------
    # per-plane token-bucket rate limits (0 = unlimited). The statsd
    # batch plane meters SAMPLES/second — admission gates each parsed
    # batch with one bucket take costing its sample count — while the
    # TCP line path and the span plane still meter per intake unit. An
    # over-limit statsd batch is parsed in essential-only mode
    # (histogram/llhist/set columns shed with exact per-class counts,
    # counter/gauge deltas kept); an over-limit span is dropped and
    # counted.
    ingest_rate_limit_statsd: float = 0.0
    ingest_rate_limit_spans: float = 0.0
    # bucket capacity = rate * this many seconds of burst headroom
    ingest_rate_limit_burst: float = 1.0
    # -- batch ingest pipeline (core/ingest.py, native/dogstatsd.cc) ----
    # samples per sealed pump chunk: readers seal a chunk when any
    # family column fills, so this bounds both the hand-off batch size
    # and the per-chunk native memory (~52 B/sample across the columns)
    ingest_batch_max_samples: int = 65536
    # SPSC ring slots PER READER thread (chunks cycling through each
    # reader's free/ready rings; min 3). A full ring blocks its reader
    # — backpressure into the kernel socket buffer, never a silent
    # in-process drop — and every such wait is a counted stall
    # (ingest.ring.stalls_total).
    ingest_ring_slots: int = 4
    # -- cardinality watermarks (core/cardinality.py) -------------------
    # per-NAME new-key mint budgets per flush interval (0 = disabled).
    # Past soft, further mints for that name are admitted 1-in-N
    # (cardinality_degraded_keep); past hard, they are rejected and
    # counted in ingest.shed_total{reason:cardinality}. Existing rows
    # always keep updating — only new keys are gated; budgets reset
    # every flush, so recovery after a storm is immediate.
    cardinality_soft_limit: int = 0
    cardinality_hard_limit: int = 0
    cardinality_degraded_keep: float = 0.1
    # heavy-hitter tracker capacity (bounded memory: names tracked for
    # /debug/cardinality and the mint budgets)
    cardinality_top_k: int = 512
    # per-tag-key HLL tracking: at most this many offender names get
    # per-tag-key distinct-value estimates (16 KB per tag key, <= 16
    # tag keys per name), started once a name mints this many keys in
    # one interval
    cardinality_hll_names: int = 8
    cardinality_hll_min_mints: int = 64
    # -- memory watermarks (core/overload.py) ---------------------------
    # RSS thresholds stepping the server ok -> degraded -> shedding
    # (0 = disabled). Degraded pauses span ingest and keeps only
    # `degraded_keep` of histogram/set samples; shedding drops all
    # histogram/set samples. Counter/gauge deltas are never shed.
    overload_watermark_soft_bytes: int = 0
    overload_watermark_hard_bytes: int = 0
    overload_watermark_poll: float = 1.0   # duration between RSS polls
    overload_watermark_degraded_keep: float = 0.25
    # device watermark rung (core/deviceobs.py HBM ledger bytes): same
    # ladder semantics as the RSS rung, thresholds on device-resident
    # generation bytes instead of host RSS (0 = disabled). The combined
    # overload state is the severity max of the two rungs.
    overload_device_soft_bytes: int = 0
    overload_device_hard_bytes: int = 0
    # -- device observatory (core/deviceobs.py) -------------------------
    # HBM generation ledger + kernel dispatch/compile registry + shard
    # balance scrape, served at /debug/device. Off, every hook is one
    # attribute read (the <2% overhead soak's off switch).
    device_observatory: bool = True
    # -- pipeline supervision (core/overload.py) ------------------------
    # a pipeline thread (ingest pump dispatch, span workers, flush loop)
    # whose heartbeat goes stale past supervisor_deadline is flagged
    # (ERROR log + supervisor.stalls_total); one stalled past
    # supervisor_escalation_deadline aborts the process so the external
    # supervisor restarts it (0 disables each behavior).
    supervisor_deadline: float = 0.0       # duration; 0 = supervision off
    supervisor_poll: float = 1.0           # duration between checks
    supervisor_escalation_deadline: float = 0.0  # duration; 0 = never abort
    # -- fault injection (util/chaos.py) --------------------------------
    # deterministic (seeded) probabilistic faults at the egress seams
    # (forward_send, sink_flush, http_post); VENEUR_CHAOS_* env overlay
    # reaches every field, so a soak can be driven without a config file
    chaos_enabled: bool = False
    chaos_error_rate: float = 0.0
    chaos_delay_rate: float = 0.0
    chaos_delay: float = 0.0           # duration per injected delay
    chaos_seams: List[str] = field(default_factory=list)  # empty = all
    chaos_seed: int = 0
    # ingest-side chaos: per-packet drop/truncate/duplicate rolls applied
    # by the server's packet intake, and simulated memory pressure added
    # to real RSS by the overload watermark monitor
    # deterministic slow-destination injection: every forward_send seam
    # crossing (local forward client AND proxy destination senders)
    # sleeps this long — makes hedging budgets and health-probe timeouts
    # testable without probabilistic rolls
    chaos_forward_latency_ms: float = 0.0
    # deterministic SILENT drop seam for the flow ledger's acceptance
    # drill: every Nth sample admitted past admission control vanishes
    # WITHOUT any accounting (0 = off). The ledger must catch it as a
    # nonzero ingest imbalance within one flush interval — this knob
    # exists so that detection is testable.
    chaos_ledger_leak: int = 0
    chaos_ingest_drop_rate: float = 0.0
    chaos_ingest_truncate_rate: float = 0.0
    chaos_ingest_duplicate_rate: float = 0.0
    chaos_ingest_rss_bytes: int = 0
    # reshard crossings (all deterministic — see util/chaos.py): plan-
    # thread prewarm delay, every-Nth faulted range-segment append, and
    # the durable-segments->merge-back kill window the soak SIGKILLs in
    chaos_reshard_prewarm_delay_s: float = 0.0
    chaos_reshard_append_fault_nth: int = 0
    chaos_reshard_cutover_delay_s: float = 0.0
    grpc_address: str = ""
    grpc_listen_addresses: List[str] = field(default_factory=list)
    hostname: str = ""
    http_address: str = ""
    http_quit: bool = False
    indicator_span_timer_name: str = ""
    interval: float = 10.0
    metric_max_length: int = 4096
    metric_sink_routing: List[SinkRoutingConfig] = field(default_factory=list)
    metric_sinks: List[SinkConfig] = field(default_factory=list)
    num_readers: int = 1
    num_span_workers: int = 1
    num_workers: int = 1
    objective_span_timer_name: str = ""
    omit_empty_hostname: bool = False
    percentiles: List[float] = field(default_factory=lambda: [0.5, 0.75, 0.99])
    read_buffer_size_bytes: int = 2 * 1024 * 1024
    sentry_dsn: StringSecret = field(default_factory=StringSecret)
    sources: List[SourceConfig] = field(default_factory=list)
    span_channel_capacity: int = 100
    # per-sink isolation buffer, counted in spans; 0 = auto-size to
    # max(4096, 8x span_channel_capacity). Unlike span_channel_capacity
    # (reference-pinned default) this one must absorb offered-rate x
    # sink-latency bursts, so it defaults much larger.
    span_sink_queue_capacity: int = 0
    span_sinks: List[SinkConfig] = field(default_factory=list)
    ssf_listen_addresses: List[str] = field(default_factory=list)
    stats_address: str = ""
    statsd_listen_addresses: List[str] = field(default_factory=list)
    synchronize_with_interval: bool = False
    tags_exclude: List[str] = field(default_factory=list)
    tls_authority_certificate: str = ""
    tls_certificate: str = ""
    tls_key: StringSecret = field(default_factory=StringSecret)
    # mTLS for the gRPC forward plane: grpc_tls_* terminate TLS on the
    # import server (grpc_address); forward_tls_* are the client
    # credentials used when dialing forward_address. Values are inline
    # PEM or file paths, like the TCP tls_* fields.
    grpc_tls_certificate: str = ""
    grpc_tls_key: StringSecret = field(default_factory=StringSecret)
    grpc_tls_authority_certificate: str = ""
    forward_tls_certificate: str = ""
    forward_tls_key: StringSecret = field(default_factory=StringSecret)
    forward_tls_authority_certificate: str = ""
    trace_max_length_bytes: int = 16 * 1024 * 1024
    veneur_metrics_additional_tags: List[str] = field(default_factory=list)
    veneur_metrics_scopes: Dict[str, str] = field(default_factory=dict)
    tpu: TpuConfig = field(default_factory=TpuConfig)
    alerts: AlertsConfig = field(default_factory=AlertsConfig)

    def apply_defaults(self) -> "Config":
        if not self.aggregates:
            self.aggregates = ["min", "max", "count"]
        if not self.hostname and not self.omit_empty_hostname:
            self.hostname = socket.gethostname()
        if self.interval <= 0:
            self.interval = 10.0
        if self.metric_max_length <= 0:
            self.metric_max_length = 4096
        if self.read_buffer_size_bytes <= 0:
            self.read_buffer_size_bytes = 2 * 1024 * 1024
        if self.span_channel_capacity <= 0:
            self.span_channel_capacity = 100
        if self.span_sink_queue_capacity <= 0:
            self.span_sink_queue_capacity = max(
                4096, 8 * self.span_channel_capacity)
        if self.trace_max_length_bytes <= 0:
            self.trace_max_length_bytes = 16 * 1024 * 1024
        return self

    @property
    def is_local(self) -> bool:
        """A server is local iff it forwards (reference server.go:1447)."""
        return self.forward_address != ""


_SUBSECTION_TYPES = {
    "features": Features,
    "tpu": TpuConfig,
    "alerts": AlertsConfig,
}
_LIST_TYPES = {
    "metric_sinks": SinkConfig,
    "span_sinks": SinkConfig,
    "sources": SourceConfig,
}
_SECRET_FIELDS = {"sentry_dsn", "tls_key"}
_DURATION_FIELDS = {"interval", "forward_retry_base", "forward_retry_max",
                    "circuit_breaker_recovery", "chaos_delay",
                    "ingest_rate_limit_burst", "overload_watermark_poll",
                    "supervisor_deadline", "supervisor_poll",
                    "supervisor_escalation_deadline", "reshard_deadline"}


def _coerce(name: str, value: Any) -> Any:
    if name in _DURATION_FIELDS:
        return parse_duration(value)
    if name in _SECRET_FIELDS:
        return StringSecret(str(value) if value is not None else "")
    if name in _SUBSECTION_TYPES and isinstance(value, dict):
        cls = _SUBSECTION_TYPES[name]
        allowed = set(cls.__dataclass_fields__)
        return cls(**{k: v for k, v in value.items() if k in allowed})
    if name in _LIST_TYPES and isinstance(value, list):
        cls = _LIST_TYPES[name]
        allowed = set(cls.__dataclass_fields__)
        out = []
        for item in value or []:
            item = dict(item or {})
            if cls is SinkConfig:
                item.setdefault("config", {})
            out.append(cls(**{k: v for k, v in item.items() if k in allowed}))
        return out
    if name == "metric_sink_routing" and isinstance(value, list):
        out = []
        for item in value or []:
            sinks = (item or {}).get("sinks", {}) or {}
            out.append(SinkRoutingConfig(
                name=item.get("name", ""), match=item.get("match", []) or [],
                matched=sinks.get("matched", []) or [],
                not_matched=sinks.get("not_matched", []) or []))
        return out
    return value


def read_config(path: Optional[str] = None, overrides: Optional[dict] = None,
                env: Optional[dict] = None, strict: bool = False) -> Config:
    """Load YAML config, overlay VENEUR_* env vars, apply defaults."""
    raw: Dict[str, Any] = {}
    if path:
        with open(path) as f:
            raw = yaml.safe_load(f) or {}
    if overrides:
        raw.update(overrides)

    cfg = Config()
    known = set(cfg.__dataclass_fields__)
    for key, value in raw.items():
        if key not in known:
            if strict:
                raise ValueError(f"unknown config field: {key}")
            continue
        setattr(cfg, key, _coerce(key, value))

    def _env_value(raw: str, current: Any, key: str) -> Any:
        """Coerce an env string by the type of the current value."""
        if isinstance(current, bool):
            return str(raw).lower() in ("1", "true", "yes", "on")
        if isinstance(current, int):
            return int(raw)
        if isinstance(current, float) and key not in _DURATION_FIELDS:
            return float(raw)
        if isinstance(current, list):
            vals = [s for s in str(raw).split(",") if s]
            if key == "percentiles":
                return [float(x) for x in vals]
            return vals
        return raw

    env = os.environ if env is None else env
    for key in known:
        env_key = "VENEUR_" + key.upper().replace(".", "_")
        if env_key in env:
            v = _env_value(env[env_key], getattr(cfg, key), key)
            setattr(cfg, key, _coerce(key, v))

    # an empty/omitted `tpu:` YAML section must still take env overrides
    if not isinstance(cfg.tpu, TpuConfig):
        cfg.tpu = TpuConfig()
    # nested device-sizing fields: VENEUR_TPU_<FIELD> (e.g.
    # VENEUR_TPU_HISTO_CAPACITY) overlays cfg.tpu.<field>
    for key in TpuConfig.__dataclass_fields__:
        env_key = "VENEUR_TPU_" + key.upper()
        if env_key in env:
            setattr(cfg.tpu, key, _env_value(
                env[env_key], getattr(cfg.tpu, key), key))

    return cfg.apply_defaults()
