"""Forward client: streams the flush's mergeable state to the global tier.

Parity with reference flusher.go:516-591 (forward/forwardGrpc) — one
SendMetricsV2 client-stream per flush, deadline-bounded by the interval —
hardened with the shared resilience layer (util/resilience.py):

* transient failures (UNAVAILABLE, DEADLINE_EXCEEDED, injected chaos)
  retry with jittered backoff inside the flush-interval budget;
* a circuit breaker stops hammering a down global tier (single half-open
  probe per recovery window);
* a FAILED interval's state is not dropped: counters are deltas, so a
  dropped forward is permanently lost counts. Because every forwarded
  family merges associatively, the failed snapshot is carried over and
  merged into the next interval's snapshot (bounded, loud shedding
  beyond the bound).
"""

from __future__ import annotations

import logging
import time
import uuid
from typing import Dict, Optional

import grpc

from veneur_tpu.core.flusher import ForwardableState
from veneur_tpu.forward.convert import forwardable_to_wire
from veneur_tpu.forward.wire import (_frame_v1, _serialize_metric,
                                     combine_metadata, decode_flow_counts,
                                     interval_metadata, send_batch,
                                     shards_metadata, stamp_interval_wire,
                                     token_metadata, trace_metadata)
from veneur_tpu.util import chaos as chaos_mod
from veneur_tpu.util.chaos import ChaosError
from veneur_tpu.util.grpctls import GrpcTLS, secure_or_insecure_channel
from veneur_tpu.util.resilience import Carryover, CircuitBreaker, RetryPolicy
from veneur_tpu.util.spool import CarryoverSpool

logger = logging.getLogger("veneur_tpu.forward.client")

_EMPTY_DESERIALIZER = lambda b: b  # google.protobuf.Empty carries nothing

# transient transport states worth another attempt inside the budget;
# anything else (UNIMPLEMENTED, INVALID_ARGUMENT, ...) is structural and
# fails fast
_RETRYABLE_CODES = (grpc.StatusCode.UNAVAILABLE,
                    grpc.StatusCode.DEADLINE_EXCEEDED)


class ForwardClient:
    """gRPC client for /forwardrpc.Forward, built on the generic channel
    API (no generated stubs needed)."""

    # drain attempts (while the destination is demonstrably up) before a
    # spool segment is declared undeliverable and quarantined
    SEGMENT_ATTEMPTS_MAX = 10

    def __init__(self, address: str, deadline: float = 10.0,
                 channel: Optional[grpc.Channel] = None,
                 tls: Optional[GrpcTLS] = None,
                 retry: Optional[RetryPolicy] = None,
                 breaker: Optional[CircuitBreaker] = None,
                 carryover: Optional[Carryover] = None,
                 chaos: Optional[chaos_mod.Chaos] = None,
                 spool: Optional[CarryoverSpool] = None,
                 ledger=None, trace_plane=None,
                 wal: bool = False, replay_limiter=None,
                 replay_stale_after: float = 0.0,
                 shards: int = 0):
        self.address = address
        self.deadline = deadline
        # the owning server's mesh width, stamped as x-veneur-shards on
        # every attempt so the receiving tier can export it
        self.shards = max(0, int(shards))
        # resilience: callers that want fail-and-forget (veneur-emit's
        # one-shot send) pass retry/carryover explicitly disabled via
        # RetryPolicy(max_attempts=1) / Carryover(0); the server wires
        # these from its forward_retry_* / circuit_breaker_* /
        # carryover_max_intervals config
        self.retry = retry or RetryPolicy()
        self.breaker = breaker or CircuitBreaker(name=f"forward:{address}")
        self.carryover = carryover or Carryover()
        # durable spill: carryover past its age bound serializes into
        # the spool (instead of shedding) and drains oldest-first after
        # the next successful send; segments left by a dead process were
        # already re-scanned by the spool's constructor
        self.spool = spool
        if spool is not None and self.carryover.spill is None:
            self.carryover.spill = self._spill
        # durable WAL mode (`forward_wal: true`): EVERY interval
        # snapshot is serialized and appended to the spool — stamped
        # with its interval-start timestamp — BEFORE the send attempt,
        # and the drain loop IS the send path. A kill -9 anywhere
        # between the append's fsync and the receiver's ack replays the
        # interval at restart, exactly-once via the per-segment token
        # (derived from the on-disk name, stable across restarts).
        self.wal = bool(wal) and spool is not None
        # backfill throttle: a drain of segments older than
        # `replay_stale_after` seconds (an hours-stale spool restored
        # from a dead peer, a long-outage backlog) pays metric tokens
        # from `replay_limiter` (a core.overload.TokenBucket) — so a
        # bulk replay can never starve live forward traffic of the
        # flush budget or the receiver of cycles. Fresh segments (the
        # live WAL write of the current interval) are never throttled.
        self.replay_limiter = replay_limiter
        self.replay_stale_after = float(replay_stale_after)
        self.wal_appended_metrics = 0
        self.wal_acked_metrics = 0
        self.wal_replay_throttled = 0
        self.chaos = chaos
        # flow ledger (core/ledger.py): acked/shed stamps plus the
        # in-flight inventory stock, so a close landing mid-send still
        # balances; the receiver's FlowCounts response feeds the
        # forward_tier reconciliation (sent vs merged across the tier)
        self.ledger = ledger
        if ledger is not None and self.carryover.ledger is None:
            self.carryover.ledger = ledger
        # self-trace plane (trace/store.py): when the owning server's
        # flush runs under a sampled interval trace, the forward sink
        # thread's ambient span is injected as gRPC metadata on EVERY
        # attempt (V1 body, V2 fallback, retries, spool drains), and the
        # interval's exemplars ride alongside so the global's merge
        # keeps them latest-wins
        self.trace_plane = trace_plane
        self.inflight_metrics = 0
        # interval+shard idempotency token: every forward() call mints
        # one token that rides ALL its attempts (V1 body, V2 fallback,
        # every retry) as gRPC metadata — the import server merges the
        # payload once no matter how many attempts land. The uuid is the
        # shard identity (one per client/process), the sequence the
        # interval identity.
        self._token_id = uuid.uuid4().hex[:12]
        self._token_seq = 0
        # per-segment drain attempts: a segment whose send fails
        # DETERMINISTICALLY (server-side merge error, not an outage)
        # would otherwise wedge the whole drain at the head of the
        # queue forever; past the cap it is quarantined (*.corrupt)
        self._segment_attempts: Dict[str, int] = {}
        from veneur_tpu.util.grpctls import RECONNECT_BACKOFF_OPTIONS
        self._channel = channel or secure_or_insecure_channel(
            address, tls,
            # the V1 bulk body scales with key count (~36 MB at 50k
            # keys); the shared backoff cap keeps a freshly-restored
            # global dialable within a flush interval so the carryover/
            # spool drain isn't stalled by grpc's post-outage backoff
            options=[("grpc.max_send_message_length", 256 << 20),
                     *RECONNECT_BACKOFF_OPTIONS])
        self._send_v2 = self._channel.stream_unary(
            "/forwardrpc.Forward/SendMetricsV2",
            request_serializer=_serialize_metric,
            response_deserializer=_EMPTY_DESERIALIZER)
        # V1 body is assembled by hand from the already-serialized
        # metrics (MetricList = repeated field-1 Metric), so the
        # serializer is identity
        self._send_v1 = self._channel.unary_unary(
            "/forwardrpc.Forward/SendMetrics",
            request_serializer=lambda b: b,
            response_deserializer=_EMPTY_DESERIALIZER)
        # a reference-style importer rejects V1 (UNIMPLEMENTED,
        # sources/proxy/server.go:138-142) and an un-upgraded receiver
        # may bounce the body (RESOURCE_EXHAUSTED); either pins the
        # client to V2 streams
        self._v1_ok = True
        self.stats: Dict[str, int] = {
            "forwarded_total": 0, "errors_deadline": 0,
            "errors_unavailable": 0, "errors_send": 0,
            "retries_total": 0, "breaker_refused_total": 0,
        }

    def _inject_chaos(self) -> None:
        c = self.chaos or chaos_mod.active()
        if c is not None:
            c.inject("forward_send")

    def _trace_sidecar(self):
        """Trace + exemplar metadata for this send: the ambient span
        (the flush's `flush.sink` child, set by the owning server's
        sink thread — None on unsampled intervals and for standalone
        clients) and the interval's exemplar blob."""
        from veneur_tpu.trace import context as trace_ctx
        parts = []
        shard_md = shards_metadata(self.shards)
        if shard_md:
            parts.append(shard_md)
        parent = trace_ctx.current_span()
        if parent is not None:
            parts.append(trace_metadata(parent.trace_id, parent.id))
        plane = self.trace_plane
        if plane is not None and parent is not None:
            from veneur_tpu.trace.store import EXEMPLAR_KEY
            blob = plane.exemplar_wire()
            if blob:
                parts.append(((EXEMPLAR_KEY, blob),))
        return combine_metadata(*parts)

    def forward(self, fwd: ForwardableState,
                interval_start: float = 0.0) -> int:
        """Serialize and send one flush's state; returns count sent.
        `interval_start` is the unix timestamp the snapshot's interval
        began at (0 = unstamped): the WAL stamps it into the segment
        header and every send carries it as x-veneur-interval metadata,
        so a replayed interval lands under its ORIGINAL interval on the
        receiving tier.

        Any pending carryover from failed intervals is first merged into
        `fwd` (counters sum, digests recompress, HLL registers max), so a
        success delivers everything owed. On final failure the MERGED
        state is stashed back (legacy mode) or already durable on disk
        (WAL mode); nothing is lost until the spool bound sheds it.

        Serialization goes through the native digest encoder
        (convert.forwardable_to_wire) — the per-centroid Python proto
        loop capped the plane at 883 keys/s (BENCH_r04). Transport
        prefers one unary SendMetrics (MetricList) — per-message stream
        overhead at 50k keys costs seconds — falling back to the V2
        stream for importers that reject V1."""
        self.inflight_metrics = len(fwd)
        try:
            return self._forward_inner(fwd, interval_start)
        finally:
            # an unexpected exception past this point loses the state
            # with no outcome stamped — clearing the in-flight stock
            # here makes that loss VISIBLE as ledger imbalance instead
            # of hiding it behind a stuck inventory level
            self.inflight_metrics = 0

    def _note(self, stage: str, n: int, key: str = "") -> None:
        led = self.ledger
        if led is not None and n:
            led.note(stage, n, key=key)

    def _note_tier(self, sent: int, resp) -> None:
        """Reconcile one acked send against the receiver's FlowCounts
        response (None/empty = an un-upgraded peer; skipped)."""
        counts = decode_flow_counts(resp)
        if counts is None or not sent:
            return
        self._note("forward.acked_reported", sent)
        if counts["duplicate"]:
            # whole payload dropped by the receiver's token dedupe: a
            # previous attempt already merged it
            self._note("forward.remote_deduped", sent)
            return
        merged = int(counts["merged"])
        received = int(counts["received"])
        self._note("forward.remote_merged", merged)
        # receiver-side accounted drops (unknown families, undecodable
        # payloads): explained by the receiver, distinct from the
        # unexplained residual (sent != received = wire-level loss)
        if received > merged:
            self._note("forward.remote_rejected", received - merged)

    def _forward_inner(self, fwd: ForwardableState,
                       interval_start: float = 0.0) -> int:
        fwd = self.carryover.drain_into(fwd)
        self.inflight_metrics = len(fwd)
        if self.wal:
            return self._forward_wal(fwd, interval_start)
        spool_pending = self.spool is not None and self.spool.depth > 0
        if not len(fwd) and not spool_pending:
            return 0
        if not self.breaker.allow():
            self.stats["breaker_refused_total"] += 1
            if len(fwd):
                self.carryover.stash(fwd)
                logger.warning(
                    "forward breaker %s to %s: carrying %d metrics over",
                    self.breaker.state, self.address, len(fwd))
            return 0
        # prefer the frames the flush's readout pre-encoded; carryover
        # merges invalidate the cache, so a non-None wire is always
        # current
        if len(fwd):
            protos = (fwd.wire if fwd.wire is not None
                      else forwardable_to_wire(fwd))
        else:
            protos = []
        if not protos and not spool_pending:
            # nonempty state that serialized to nothing leaves the
            # pipeline here — explained as a convert shed
            self._note("forward.shed", len(fwd), key="convert")
            return 0
        deadline_ts = time.monotonic() + self.deadline
        resp = None
        sidecar = self._trace_sidecar()
        if protos:
            # one token per interval payload, stable across every retry
            # and the V1->V2 fallback of THIS call — an attempt that
            # landed but errored client-side can't merge twice
            self._token_seq += 1
            token = f"fwd:{self._token_id}:{self._token_seq}"
            delays = self.retry.delays(self.deadline)
            while True:
                try:
                    self._inject_chaos()
                    # per-attempt timeout is the REMAINING budget: a slow
                    # first attempt leaves correspondingly less for retries
                    timeout = max(0.05, deadline_ts - time.monotonic())
                    # a single flush body scales with key count (~36 MB at
                    # 50k keys), so RESOURCE_EXHAUSTED here is structural,
                    # not transient — both codes pin the client to V2
                    self._v1_ok, resp = send_batch(
                        self._send_v1, self._send_v2, protos, timeout,
                        self._v1_ok,
                        pin_codes=(grpc.StatusCode.UNIMPLEMENTED,
                                   grpc.StatusCode.RESOURCE_EXHAUSTED),
                        metadata=combine_metadata(
                            token_metadata(token), sidecar))
                    break
                except (grpc.RpcError, ChaosError) as e:
                    code = e.code() if hasattr(e, "code") else None
                    retryable = (isinstance(e, ChaosError)
                                 or code in _RETRYABLE_CODES)
                    delay = next(delays, None) if retryable else None
                    if delay is None:
                        self._record_failure(code, fwd, len(protos))
                        return 0
                    self.stats["retries_total"] += 1
                    logger.info(
                        "forward to %s failed (%s); retrying in %.2fs",
                        self.address, code or e, delay)
                    if delay > 0:
                        time.sleep(delay)
        else:
            # nothing fresh to send, but the spool holds spilled state:
            # probe the destination with the drain itself below
            pass
        drained, drain_err, attempted = self._drain_spool(
            deadline_ts, destination_up=bool(protos), sidecar=sidecar)
        if not protos and drained == 0:
            if drain_err is not None:
                # the spool-only probe failed: destination still down
                self._record_failure(
                    drain_err.code() if hasattr(drain_err, "code")
                    else None, fwd, 0)
                return 0
            if not attempted:
                # nothing sendable was found (every segment quarantined
                # on read): there is NO network evidence the peer is up,
                # so don't close a half-open breaker on it — release the
                # probe pessimistically instead
                self.breaker.record_failure()
                # fwd can only be nonempty here when it serialized to
                # zero protos — unconvertible state that leaves the
                # pipeline now, explained as a convert shed
                self._note("forward.shed", len(fwd), key="convert")
                return 0
        self.breaker.record_success()
        self.carryover.clear_age()
        self.stats["forwarded_total"] += len(protos)
        if protos:
            self._note("forward.acked", len(protos))
            self._note_tier(len(protos), resp)
        if len(fwd) > len(protos):
            # rows the wire conversion dropped, accounted only on
            # success (a failed send stashes the FULL state back);
            # outside the `if protos` guard so a spool-drain-only
            # success with a fully-unconvertible snapshot still
            # explains where that snapshot went
            self._note("forward.shed", len(fwd) - len(protos),
                       key="convert")
        logger.debug("forwarded %d metrics to %s", len(protos), self.address)
        return len(protos) + drained

    def _record_failure(self, code, fwd: ForwardableState,
                        n_protos: int) -> None:
        if code == grpc.StatusCode.DEADLINE_EXCEEDED:
            self.stats["errors_deadline"] += 1
        elif code == grpc.StatusCode.UNAVAILABLE:
            self.stats["errors_unavailable"] += 1
        else:
            self.stats["errors_send"] += 1
        self.breaker.record_failure()
        if len(fwd):
            self.carryover.stash(fwd)
        logger.warning(
            "could not forward %d metrics to %s: %s (carryover depth %d)",
            n_protos, self.address, code, self.carryover.depth)

    # -- durable WAL -----------------------------------------------------

    def _forward_wal(self, fwd: ForwardableState,
                     interval_start: float) -> int:
        """WAL-mode forward: append the interval to disk FIRST (fsync'd,
        stamped with its interval-start), then drain the log oldest-
        first. The drain is the only send path, so ordering across
        crashes is the on-disk segment order and the breaker/budget
        logic has exactly one seam. Returns metrics delivered."""
        if len(fwd):
            protos = (fwd.wire if fwd.wire is not None
                      else forwardable_to_wire(fwd))
            if len(fwd) > len(protos):
                # rows the wire conversion dropped leave the pipeline at
                # the append boundary (the WAL only ever holds sendable
                # bytes), explained as a convert shed
                self._note("forward.shed", len(fwd) - len(protos),
                           key="convert")
            if protos:
                stamp = interval_start or time.time()
                self.spool.append(
                    [stamp_interval_wire(p, stamp) for p in protos],
                    interval_unix=stamp)
                self.wal_appended_metrics += len(protos)
        # durable now: the spool stock carries the state, so the
        # in-flight stock must stop double-counting it
        self.inflight_metrics = 0
        if self.spool.depth == 0:
            return 0
        if not self.breaker.allow():
            self.stats["breaker_refused_total"] += 1
            return 0
        deadline_ts = time.monotonic() + self.deadline
        sidecar = self._trace_sidecar()
        drained, err, attempted = self._drain_spool(
            deadline_ts, destination_up=False, sidecar=sidecar)
        if drained:
            self.breaker.record_success()
            self.carryover.clear_age()
            self.stats["forwarded_total"] += drained
            self.wal_acked_metrics += drained
        elif err is not None:
            code = err.code() if hasattr(err, "code") else None
            self._record_failure(code, ForwardableState(), 0)
        else:
            # no RPC evidence the peer is up (every segment quarantined
            # on read): release a half-open probe pessimistically
            # rather than close the breaker on a no-op
            self.breaker.record_failure()
        return drained

    def _spill(self, fwd: ForwardableState) -> int:
        """Carryover's overflow hook: serialize the shed-bound state to
        the on-disk spool (same wire bytes a send would carry)."""
        return self.spool.append(forwardable_to_wire(fwd))

    def _drain_spool(self, deadline_ts: float, destination_up: bool,
                     sidecar=None):
        """After a successful send (the destination is demonstrably up),
        deliver spilled segments oldest-first until the spool is empty,
        the flush budget runs out, or a send fails (the segment stays
        for the next interval). Returns (metrics_drained, last_error,
        attempted) — `attempted` is False when no RPC was even made
        (empty spool, budget gone, or every segment quarantined on
        read), so the caller can't mistake a no-op for a live peer.

        Each segment send carries its own idempotency token, stable for
        the segment's lifetime (derived from its path), so a segment
        whose send landed but errored client-side is dropped by the
        import server when re-sent next interval.

        `destination_up` gates the quarantine counter: a head-segment
        failure right after a SUCCESSFUL main send points at the
        segment, but a failure on the spool-only probe path is
        indistinguishable from the outage continuing — counting those
        would quarantine a perfectly good segment after a long quiet
        outage."""
        if self.spool is None:
            return 0, None, False
        drained = 0
        err = None
        attempted = False
        sent_any = False
        now = time.time()
        stale_after = self.replay_stale_after if self.wal else 0.0
        ordered = self.spool.segments()
        if stale_after > 0:
            # WAL backfill isolation: fresh segments (the live interval,
            # a short outage's backlog) drain first at full speed; an
            # hours-stale backlog (a restored peer's disk) drains BEHIND
            # them under the replay token bucket — ordering across
            # buckets is free because every family merges commutatively
            # and the receiver buckets by the segment's interval stamp,
            # not arrival order
            fresh = [s for s in ordered
                     if not s.interval_unix
                     or now - s.interval_unix <= stale_after]
            fresh_set = set(id(s) for s in fresh)
            ordered = fresh + [s for s in ordered
                               if id(s) not in fresh_set]
        for seg in ordered:
            remaining = deadline_ts - time.monotonic()
            if remaining <= 0.05:
                break
            is_stale = (stale_after > 0 and seg.interval_unix
                        and now - seg.interval_unix > stale_after)
            if (is_stale and sent_any and self.replay_limiter is not None
                    and not self.replay_limiter.admit(seg.count)):
                # out of replay tokens: everything after this segment is
                # at least as stale (fresh-first ordering), so stop the
                # drain here and let the backlog trickle next interval.
                # `sent_any` exempts the first segment — every drain
                # makes progress and resolves a half-open breaker probe.
                self.wal_replay_throttled += 1
                logger.info(
                    "WAL replay throttled at %s (%d segments remain)",
                    seg.path, self.spool.depth)
                break
            try:
                metrics = seg.read_metrics()
            except (OSError, ValueError) as e:
                logger.error("undeliverable spool segment %s: %s",
                             seg.path, e)
                self.spool.discard(seg)
                self._segment_attempts.pop(seg.path, None)
                continue
            token = "spool:" + seg.path.rsplit("/", 1)[-1]
            try:
                attempted = True
                self._inject_chaos()
                self._v1_ok, resp = send_batch(
                    self._send_v1, self._send_v2, metrics, remaining,
                    self._v1_ok,
                    pin_codes=(grpc.StatusCode.UNIMPLEMENTED,
                               grpc.StatusCode.RESOURCE_EXHAUSTED),
                    # spilled segments drain inside the CURRENT flush's
                    # trace (the spans show replay work where it costs)
                    # and carry their ORIGINAL interval stamp, so the
                    # receiver backfills them into the right interval
                    metadata=combine_metadata(
                        token_metadata(token),
                        interval_metadata(seg.interval_unix), sidecar))
            except (grpc.RpcError, ChaosError) as e:
                err = e
                code = e.code() if hasattr(e, "code") else None
                attempts = self._segment_attempts.get(seg.path, 0)
                # count toward quarantine only failures that indict the
                # SEGMENT: the peer answered (destination_up, or an
                # earlier segment landed this drain) with a
                # non-transient error. DEADLINE_EXCEEDED is usually a
                # near-exhausted flush budget after a slow main send,
                # UNAVAILABLE the node dying mid-drain, chaos an
                # injected transport fault — quarantining a deliverable
                # interval on those would BE the loss the spool
                # prevents.
                if (destination_up or sent_any) \
                        and not isinstance(e, ChaosError) \
                        and code not in (
                            grpc.StatusCode.DEADLINE_EXCEEDED,
                            grpc.StatusCode.UNAVAILABLE):
                    attempts += 1
                    self._segment_attempts[seg.path] = attempts
                if attempts >= self.SEGMENT_ATTEMPTS_MAX:
                    # not an outage (the main send just succeeded, or
                    # this has now failed across many recovered
                    # intervals): the segment itself is undeliverable —
                    # quarantine it so it can't wedge everything behind
                    logger.error(
                        "spool segment %s failed %d drain attempts; "
                        "quarantining", seg.path, attempts)
                    self.spool.discard(seg)
                    self._segment_attempts.pop(seg.path, None)
                    continue
                logger.warning(
                    "spool drain to %s stopped at %s: %s (%d segments "
                    "remain)", self.address, seg.path, e, self.spool.depth)
                break
            self.spool.pop(seg)
            sent_any = True
            self._segment_attempts.pop(seg.path, None)
            # the popped segment's stock delta is seg.count; ack the
            # same figure so a header/body count drift surfaces as
            # imbalance instead of silently canceling
            self._note("forward.acked", seg.count, key="spool")
            self._note_tier(len(metrics), resp)
            drained += len(metrics)
        if drained:
            logger.info("drained %d spilled metrics to %s (%d segments "
                        "remain)", drained, self.address, self.spool.depth)
        if len(self._segment_attempts) > 64:
            # segments can also leave via the spool's own bound shed,
            # which this client never sees — prune to live paths so the
            # attempt map can't grow without bound
            live = self.spool.live_paths()
            self._segment_attempts = {p: n for p, n
                                      in self._segment_attempts.items()
                                      if p in live}
        return drained, err, attempted

    def telemetry_rows(self):
        """(name, kind, value, tags) rows for the /metrics registry: the
        send/error counters that used to be a private dict, plus breaker
        and carryover state."""
        rows = [(f"forward.{key}", "counter", float(value), ())
                for key, value in self.stats.items()]
        rows.append(("resilience.breaker_state", "gauge",
                     float(self.breaker.state_code), ["target:forward"]))
        rows.append(("resilience.breaker_opens", "counter",
                     float(self.breaker.open_total), ["target:forward"]))
        rows.append(("resilience.carryover_depth", "gauge",
                     float(self.carryover.depth), ()))
        rows.append(("resilience.carryover_merged", "counter",
                     float(self.carryover.merged_total), ()))
        rows.append(("resilience.carryover_shed", "counter",
                     float(self.carryover.shed_total), ()))
        rows.append(("resilience.carryover_spilled", "counter",
                     float(self.carryover.spilled_total), ()))
        if self.spool is not None:
            rows.extend(self.spool.telemetry_rows())
        if self.wal:
            rows.append(("wal.appended", "counter",
                         float(self.wal_appended_metrics), ()))
            rows.append(("wal.acked", "counter",
                         float(self.wal_acked_metrics), ()))
            rows.append(("wal.replay_throttled", "counter",
                         float(self.wal_replay_throttled), ()))
            rows.append(("wal.pending", "gauge",
                         float(self.spool.pending_metrics), ()))
        return rows

    def send_protos(self, protos) -> int:
        """Stream pre-built metricpb Metrics (veneur-emit's grpc mode)."""
        protos = list(protos)
        if protos:
            self._send_v2(iter(protos), timeout=self.deadline)
        return len(protos)

    def close(self) -> None:
        self._channel.close()
