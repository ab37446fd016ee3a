"""Batched ingest: the native parser wired to the device column store.

This is the framework's hot ingest loop (the TPU-build replacement for the
reference's ReadMetricSocket -> ParseMetric -> Worker.ProcessMetric chain,
reference server.go:1103-1140, samplers/parser.go:349, worker.go:350):
packet buffers are parsed by the C++ batch parser into per-family COO
columns, which append straight into the column store's pending buffers —
one lock acquisition and one memcpy per family per buffer instead of one
object, one dict lookup, and one lock per sample.

Slow-path contract: lines the native parser defers (unknown keys, events,
service checks, malformed packets, non-ASCII set members) are replayed
through Server.handle_metric_packet, which preserves exact parse/error
semantics; metric lines that intern a new key are then registered with the
native table, so each unique timeseries pays the Python path exactly once.
"""

from __future__ import annotations

import logging
import random
import threading
import time
from typing import Optional

import numpy as np

from veneur_tpu import native
from veneur_tpu.core import batchdecode
from veneur_tpu.core.telemetry import annotate
from veneur_tpu.samplers import metrics as m

logger = logging.getLogger("veneur_tpu.ingest")

_FAMILY_BY_TYPE = {
    m.COUNTER: native.FAM_COUNTER,
    m.GAUGE: native.FAM_GAUGE,
    m.HISTOGRAM: native.FAM_HISTO,
    m.TIMER: native.FAM_HISTO,
    m.SET: native.FAM_SET,
    m.LLHIST: native.FAM_LLHIST,
}

# SSF metric enum -> DogStatsD family char (dogstatsd.cc kFamilyChar)
_SSF_TC = {0: b"c", 1: b"g", 2: b"h", 3: b"s"}


def addr_label(address) -> str:
    """Human-readable listener address for ring/queue names:
    ('127.0.0.1', 8126) -> '127.0.0.1:8126'."""
    if isinstance(address, (tuple, list)):
        return ":".join(str(part) for part in address)
    return str(address)


def ssf_meta_key(sample) -> Optional[bytes]:
    """Canonical intern key for an SSF sample, byte-identical to
    dogstatsd.cc ssf_key: DogStatsD line-key form with sorted tag keys,
    a "|@rate" chunk when the rate is not 1, and a "|$N" suffix for an
    enum-forced scope. Identical identities unify with rows interned by
    the DogStatsD plane."""
    tc = _SSF_TC.get(sample.metric)
    if tc is None:
        return None
    parts = [sample.name.encode(), b"|", tc]
    rate = sample.sample_rate or 1.0
    if rate != 1.0:
        parts.append(b"|@%g" % rate)
    if sample.tags:
        kv = ",".join(f"{k}:{sample.tags[k]}" for k in sorted(sample.tags))
        parts.append(b"|#" + kv.encode())
    if sample.scope in (1, 2):
        parts.append(b"|$%d" % sample.scope)
    return b"".join(parts)


class _ColumnarIngesterBase:
    """Shared columnar apply path: parsed per-family COO columns (from
    the C++ parser, a pump chunk, or the numpy fallback decoder — all
    the same duck type) land in the column store as batch applies, with
    batch-granular admission, the shed ladder in column form, ordered
    gauge replay-merge, and the slow-path deferral contract.

    Subclasses provide the parse step and the intern-table registration
    hook (`_register_entry`)."""

    # flow-ledger key stamped on admitted batch columns (tells the
    # /debug/ledger reader which parse plane took the sample)
    LEDGER_KEY = "native"

    server = None
    store = None
    parser = None  # the scalar (Python) parser, for the slow path

    def _table_for_family(self, family: int):
        return {
            native.FAM_COUNTER: self.store.counters,
            native.FAM_GAUGE: self.store.gauges,
            native.FAM_HISTO: self.store.histos,
            native.FAM_SET: self.store.sets,
            native.FAM_LLHIST: self.store.llhists,
        }[family]

    def _register_entry(self, meta_key: bytes, family: int, row: int,
                        rate: float) -> None:
        raise NotImplementedError

    def _ingest(self, res, shed_nonessential: bool = False) -> int:
        store = self.store
        server = self.server
        # batch admission (PR-3's token bucket, re-pointed at batches):
        # ONE bucket take per parsed batch, token cost = the batch's
        # sample count. An over-limit batch still rides the columnar
        # fast path — shedding must not cost more CPU than admitting —
        # but its histogram/set/llhist columns shed with exact per-class
        # counts below, and only counter/gauge columns land.
        overload = getattr(server, "overload", None)
        # token cost = the batch's sample count; deferred lines count
        # one each (they are load the slow path still has to parse)
        n_ask = res.samples + len(res.unknown)
        if (not shed_nonessential and overload is not None and n_ask
                and not overload.admit_statsd_batch(n_ask)):
            shed_nonessential = True
        # columnar lines count as received; unknown lines are counted in
        # the replay loop below. The processed counter is stamped at the
        # END of this method, after every column landed in a pending
        # buffer — a waiter that observes the count and flushes must see
        # the samples in that flush, not the next one.
        server.stats.inc("packets_received", res.lines - len(res.unknown))
        server.stats.inc("batches_dispatched")
        # flow ledger: the counter/gauge columns are admitted here
        # (histogram/set/llhist columns stamp in _add_histo_set, where
        # the shed ladder decides what actually reaches the store)
        ledger = getattr(server, "ledger", None)
        if ledger is not None:
            n = len(res.c_rows) + len(res.g_rows)
            if n:
                ledger.note("ingest.admitted", n, key=self.LEDGER_KEY)
        unknown = res.unknown

        # Counters/histograms/sets merge commutatively, so replay order
        # vs. native-column order is irrelevant for them. Gauges are
        # last-write-wins: a deferred line can fall anywhere relative to
        # the native lines of the same row, so replayed gauge samples are
        # captured (not applied) and merged with the native gauge columns
        # by line index before one ordered add_batch.
        if unknown:
            gauge_rows: list = []
            gauge_vals: list = []
            gauge_lines: list = []
            line_no = 0

            essential_cb = (server._ingest_metric_essential
                            if shed_nonessential else server.ingest_metric)

            def capture(metric):
                if metric.key.type == m.GAUGE:
                    # admitted BEFORE the intern: a mint rejection
                    # stamps agg.rejected inside row_for, so the
                    # ledger's ingest identity stays balanced
                    if ledger is not None:
                        ledger.note("ingest.admitted", 1, key="python")
                    row = store.gauges.intern(metric)
                    if row < 0:  # cardinality cap: drop, already counted
                        return
                    gauge_rows.append(row)
                    gauge_vals.append(metric.value)
                    gauge_lines.append(line_no)
                else:
                    essential_cb(metric)

            from veneur_tpu.samplers.parser import ParseError
            for line, line_no in zip(unknown, res.unknown_lines):
                if line.startswith(b"_e{") or line.startswith(b"_sc"):
                    server.handle_metric_packet(line)
                    continue
                server.stats.inc("packets_received")
                try:
                    self.parser.parse_metric_fast(line, capture)
                except ParseError as e:
                    server.stats.inc("parse_errors")
                    logger.debug("could not parse line %r: %s",
                                 line[:100], e)
                    continue
                self._register_line(line)
        else:
            gauge_rows = None

        if len(res.c_rows):
            store.counters.add_batch(res.c_rows, res.c_vals, res.c_rates)
        if gauge_rows:
            all_rows = np.concatenate(
                [res.g_rows, np.asarray(gauge_rows, np.int32)])
            all_vals = np.concatenate(
                [res.g_vals, np.asarray(gauge_vals, np.float32)])
            all_lines = np.concatenate(
                [res.g_lines, np.asarray(gauge_lines, np.int32)])
            # stable sort: a line is either native or deferred, never
            # both, and multi-value samples share a line index, so append
            # order breaks ties correctly
            order = np.argsort(all_lines, kind="stable")
            store.gauges.add_batch(all_rows[order], all_vals[order])
        elif len(res.g_rows):
            store.gauges.add_batch(res.g_rows, res.g_vals)
        self._add_histo_set(res, shed_nonessential)
        # processed stamp LAST (see above): columns are in pending
        # buffers now, so a flush racing this count still emits them
        store.count_processed(res.samples +
                              (len(gauge_rows) if gauge_rows else 0))
        return res.samples

    def _add_histo_set(self, res, shed_nonessential: bool = False) -> None:
        """Append the histogram/llhist/set columns, applying the
        overload shed ladder in batch form: shedding (or an over-limit
        batch) drops the columns whole, degraded stride-subsamples them
        (precision shed, counters untouched — the SALSA ladder). Every
        shed sample is counted with its exact per-class count straight
        off the batch's own type-code columns — a rejected batch books
        len(h)/len(l)/len(s) sample counts, never packet counts."""
        store = self.store
        overload = getattr(self.server, "overload", None)
        ledger = getattr(self.server, "ledger", None)

        def admit(n):
            if ledger is not None and n:
                ledger.note("ingest.admitted", n, key=self.LEDGER_KEY)

        if shed_nonessential and overload is not None:
            keep = 0.0
        else:
            keep = overload.histo_set_keep() if overload is not None else 1.0
        if keep >= 1.0:
            if len(res.h_rows):
                admit(len(res.h_rows))
                store.histos.add_batch(res.h_rows, res.h_vals, res.h_wts)
            if len(res.l_rows):
                admit(len(res.l_rows))
                store.llhists.add_batch_binned(
                    res.l_rows, res.l_bins, res.l_wts, res.l_clamped)
            if len(res.s_rows):
                admit(len(res.s_rows))
                store.sets.add_batch(res.s_rows, res.s_idx, res.s_rho)
            return
        from veneur_tpu.core import overload as overload_mod
        stride = max(1, round(1.0 / keep)) if keep > 0 else 0
        shed_reason = "rate_limit" if shed_nonessential else "overload"
        groups = (
            (overload_mod.CLASS_HISTOGRAM, res.h_rows,
             lambda k, s: store.histos.add_batch(
                 k, res.h_vals[::s], res.h_wts[::s])),
            # llhist shares the histogram shed class (it loses precision,
            # not truth); truly-subsampled batches skip the clamped
            # credit (the aggregate can't be attributed to surviving
            # samples), but stride 1 keeps every sample and the credit
            (overload_mod.CLASS_HISTOGRAM, res.l_rows,
             lambda k, s: store.llhists.add_batch_binned(
                 k, res.l_bins[::s], res.l_wts[::s],
                 res.l_clamped if s == 1 else 0)),
            (overload_mod.CLASS_SET, res.s_rows,
             lambda k, s: store.sets.add_batch(
                 k, res.s_idx[::s], res.s_rho[::s])),
        )
        for cls, rows, apply_fn in groups:
            n = len(rows)
            if not n:
                continue
            if stride == 0:
                overload.shed(cls, n, reason=shed_reason)
                continue
            kept = rows[::stride]
            overload.shed(cls, n - len(kept), reason="degraded")
            admit(len(kept))
            apply_fn(kept, stride)

    def _register_line(self, line: bytes) -> None:
        """After the slow path interned a metric line's key, teach the
        intern table its (family, row, rate) so the next occurrence
        stays on the columnar fast path."""
        type_start = line.find(b"|")
        if type_start < 0:
            return
        value_start = line.find(b":", 0, type_start)
        if value_start < 0:
            return
        meta_key = line[:value_start] + line[type_start:]
        cached = self.parser._meta_cache.get(meta_key)
        if cached is None:
            return  # line never parsed cleanly; stays on the slow path
        key, _h32, h64, rate, _tags, scope = cached
        family = _FAMILY_BY_TYPE.get(key.type)
        if family is None:
            return
        table = self._table_for_family(family)
        dict_key = (h64 << 2) | int(scope)
        row = table.rows.get(dict_key)
        if row is None:
            return
        self._register_entry(meta_key, family, row, rate)


class PyBatchIngester(_ColumnarIngesterBase):
    """The numpy columnar fallback: same batch pipeline as the native
    ingester — intern-table columnar parse, one add_batch per family,
    batch admission, slow-path deferral — with the parse step in pure
    Python (core/batchdecode.py). Hosts without a compiler keep the
    batched shape of the speedup instead of dropping all the way to the
    per-sample object path."""

    LEDGER_KEY = "columnar"

    def __init__(self, server):
        self.server = server
        self.store = server.store
        self.parser = server.parser
        self.decoder = batchdecode.ColumnarDecoder()

    def ingest_buffer(self, buf: bytes,
                      shed_nonessential: bool = False) -> int:
        """Parse and aggregate one newline-joined packet buffer; same
        contract as BatchIngester.ingest_buffer."""
        return self._ingest(self.decoder.parse(buf), shed_nonessential)

    def _register_entry(self, meta_key: bytes, family: int, row: int,
                        rate: float) -> None:
        self.decoder.register(meta_key, family, row, rate)

    def unregister_rows_multi(self, pairs) -> None:
        """Idle-row reclamation hook (same contract as
        native.Engine.unregister_rows_multi)."""
        self.decoder.unregister_rows(
            {(int(f), int(r)) for f, r in pairs})

    def size(self) -> int:
        """Intern-table size (native.Engine duck type, for the
        intern.native_table_size gauge)."""
        return self.decoder.size()

    @property
    def interned_keys(self) -> int:
        return self.decoder.size()


class BatchIngester(_ColumnarIngesterBase):
    """One native intern table + parse buffers per server.

    Falls back to None from `create` when the native library is
    unavailable; callers then use PyBatchIngester's numpy columnar
    decoder instead.
    """

    def __init__(self, server):
        self.server = server
        self.store = server.store
        self.parser = server.parser
        self._engine = native.Engine()  # shared intern table
        self._tls = threading.local()   # per-thread parse buffers

    @classmethod
    def create(cls, server) -> Optional["BatchIngester"]:
        if not native.available():
            return None
        try:
            return cls(server)
        except Exception:
            logger.exception("native batch ingester unavailable")
            return None

    def _parser(self) -> native.NativeParser:
        p = getattr(self._tls, "parser", None)
        if p is None:
            p = native.NativeParser(engine=self._engine)
            self._tls.parser = p
        return p

    def ingest_buffer(self, buf: bytes,
                      shed_nonessential: bool = False) -> int:
        """Parse and aggregate one newline-joined packet buffer; returns
        the number of samples taken (native + slow path not counted).
        `shed_nonessential` is the over-limit (rate-limited) intake
        mode: the buffer still rides the columnar fast path — shedding
        load must not COST more CPU per packet than admitting it — but
        its histogram/set/llhist columns are dropped (counted) and only
        the counter/gauge columns land."""
        parser = self._parser()
        return self._ingest(parser.parse(buf), shed_nonessential)

    def ingest_ptr(self, ptr, length: int) -> int:
        """Zero-copy variant over a native reader's joined buffer."""
        parser = self._parser()
        return self._ingest(parser.parse_ptr(ptr, length))

    def _register_entry(self, meta_key: bytes, family: int, row: int,
                        rate: float) -> None:
        self._engine.register(meta_key, family, row, rate)

    @property
    def interned_keys(self) -> int:
        return self._engine.size()

    # ---- SSF fast path ----------------------------------------------------

    def ingest_ssf_batch(self, packets) -> np.ndarray:
        """List-of-packets convenience wrapper over
        ingest_ssf_buffer."""
        n = len(packets)
        buf = b"".join(packets)
        lens = np.fromiter((len(p) for p in packets), np.int64, n)
        offs = np.zeros(n, np.int64)
        if n > 1:
            np.cumsum(lens[:-1], out=offs[1:])
        return self.ingest_ssf_buffer(buf, offs, lens)

    def ingest_ssf_buffer(self, buf, offs, lens) -> np.ndarray:
        """Native SSF span decode + metric extraction (reference
        protocol/wire.go:108-186 + sinks/ssfmetrics/metrics.go:89-146
        semantics): spans decode and their samples extract in C++ through
        the shared intern table; samples the native path defers (unknown
        keys, STATUS, non-ASCII members, malformed) replay through the
        Python SSF converter, which also registers their canonical keys.
        Returns the per-packet decoded mask (True = span parsed OK, for
        the span-sink handoff)."""
        from veneur_tpu import protocol, ssf
        from veneur_tpu.samplers.parser import ParseError

        server = self.server
        store = self.store
        cfg = server.config
        ledger = getattr(server, "ledger", None)
        ext = server.metric_extraction
        parser_nat = self._parser()
        n = len(offs)
        indicator_enabled = bool(cfg.indicator_span_timer_name
                                 or cfg.objective_span_timer_name)
        uniq_rate = getattr(ext, "_uniqueness_rate", 0.01)
        res = parser_nat.parse_ssf(
            buf, offs, lens, indicator_enabled, uniq_rate,
            rng_seed=random.getrandbits(63) | 1)
        server.stats.inc("packets_received", n)
        flags = res.flags
        bad = int(((flags & native.SSF_BAD) != 0).sum())
        if bad:
            server.stats.inc("parse_errors", bad)
        # processed is stamped after the batch applies (same flush-race
        # rule as _ingest)

        spans_cache: dict = {}

        def get_span(idx: int):
            span = spans_cache.get(idx)
            if span is None:
                start = int(offs[idx])
                span = protocol.parse_ssf(buf[start:start + int(lens[idx])])
                spans_cache[idx] = span
            return span

        replayed = 0
        gauge_rows: list = []
        gauge_vals: list = []
        gauge_lines: list = []
        for pkt_idx, raw, line_no in res.deferred:
            sample = ssf.SSFSample()
            try:
                sample.ParseFromString(raw)
            except Exception:
                logger.debug("undecodable SSF sample (%d bytes)", len(raw))
                continue
            try:
                metric = server.parser.parse_metric_ssf(sample)
            except ParseError:
                continue  # invalid sample (reference parser.go:154-171)
            if not metric.name or metric.value is None:
                continue
            if metric.key.type == m.GAUGE:
                # captured, not applied: merged with the native gauge
                # columns by line index so last-write-wins holds
                # (admitted stamp precedes the intern, like _ingest's)
                if ledger is not None:
                    ledger.note("ingest.admitted", 1, key="python")
                row = store.gauges.intern(metric)
                if row >= 0:
                    gauge_rows.append(row)
                    gauge_vals.append(metric.value)
                    gauge_lines.append(line_no)
            else:
                server.ingest_metric(metric)  # process() counts it
            replayed += 1
            self._register_ssf_sample(sample, metric)

        if ledger is not None:
            n = len(res.c_rows) + len(res.g_rows)
            if n:
                ledger.note("ingest.admitted", n, key="native")
        if len(res.c_rows):
            store.counters.add_batch(res.c_rows, res.c_vals, res.c_rates)
        if gauge_rows:
            all_rows = np.concatenate(
                [res.g_rows, np.asarray(gauge_rows, np.int32)])
            all_vals = np.concatenate(
                [res.g_vals, np.asarray(gauge_vals, np.float32)])
            all_lines = np.concatenate(
                [res.g_lines, np.asarray(gauge_lines, np.int32)])
            order = np.argsort(all_lines, kind="stable")
            store.gauges.add_batch(all_rows[order], all_vals[order])
        elif len(res.g_rows):
            store.gauges.add_batch(res.g_rows, res.g_vals)
        self._add_histo_set(res)
        store.count_processed(res.samples + len(gauge_rows))

        # derived-metric replays the native path owed us
        for idx in np.nonzero((flags & native.SSF_NEEDS_UNIQ) != 0)[0]:
            span = get_span(int(idx))
            sample = ssf.set_sample("ssf.names_unique", span.name, {
                "indicator": "true" if span.indicator else "false",
                "service": span.service,
                "root_span": ("true" if span.id == span.trace_id
                              else "false")})
            # the keep/drop roll already happened in C++; only the
            # rate-scaling half of ssf.randomly_sample applies here
            if 0 < uniq_rate <= 1:
                sample.sample_rate = uniq_rate
            try:
                metric = server.parser.parse_metric_ssf(sample)
            except ParseError:
                continue
            server.ingest_metric(metric)  # process() counts it
            replayed += 1
            self._register_ssf_sample(sample, metric)
        if indicator_enabled:
            for idx in np.nonzero(
                    (flags & native.SSF_NEEDS_INDICATOR) != 0)[0]:
                span = get_span(int(idx))
                for metric in server.parser.convert_indicator_metrics(
                        span, cfg.indicator_span_timer_name,
                        cfg.objective_span_timer_name):
                    server.ingest_metric(metric)  # process() counts it
                    replayed += 1

        decoded_mask = (flags & native.SSF_DECODED) != 0
        with ext._lock:
            ext.spans_processed += int(decoded_mask.sum())
            ext.metrics_generated += res.samples + replayed
        return decoded_mask

    def _register_ssf_sample(self, sample, metric) -> None:
        """Bind an SSF sample's canonical key to the row the Python path
        just interned, so its next occurrence never leaves C++."""
        key = ssf_meta_key(sample)
        if key is None:
            return
        family = _FAMILY_BY_TYPE.get(metric.key.type)
        if family is None:
            return
        table = {
            native.FAM_COUNTER: self.store.counters,
            native.FAM_GAUGE: self.store.gauges,
            native.FAM_HISTO: self.store.histos,
            native.FAM_SET: self.store.sets,
        }[family]
        dict_key = (metric.digest64 << 2) | int(metric.scope)
        row = table.rows.get(dict_key)
        if row is None:
            return
        self._engine.register(key, family, row,
                              metric.sample_rate or 1.0)

    # ---- C++-resident pump ------------------------------------------------

    def start_pump(self, socks) -> Optional["native.Pump"]:
        """Build a native pump over the listener's sockets: the whole
        socket->parse->accumulate loop runs in C++ reader threads (one per
        socket, GIL-free) behind per-reader SPSC ring buffers, and Python
        touches a chunk of ~tens of thousands of samples at a time
        instead of one 512-datagram buffer. Returns None when the native
        pump cannot start."""
        try:
            cfg = self.server.config
            max_len = cfg.metric_max_length
            return native.Pump(
                self._engine, [s.fileno() for s in socks],
                max_dgram=max_len + 1, max_len=max_len,
                chunk_cap=max(1024, int(getattr(
                    cfg, "ingest_batch_max_samples", 65536))),
                ring_slots=max(3, int(getattr(
                    cfg, "ingest_ring_slots", 4))))
        except Exception:
            logger.exception("native pump unavailable")
            return None

    def run_pump_dispatch(self, pump, listener) -> None:
        """Dispatcher thread body: drain sealed chunks into the column
        store until the listener closes, then stop the readers and flush
        whatever they sealed on the way out. Heartbeats the pipeline
        supervisor every loop (the 200 ms chunk wait bounds the beat
        interval) and registers the native stall counter as a probe."""
        server = self.server
        supervisor = None
        # per-listener component name: two listeners run two pumps, and
        # one wedged dispatcher must not hide behind the other's beats
        sup_name = f"ingest-pump:{listener.address}"
        overload = getattr(server, "overload", None)
        if overload is not None:
            supervisor = overload.supervisor
            supervisor.register(sup_name)
            supervisor.add_probe(sup_name, pump.stalls)
        # ring observability: each reader's ready ring registers as an
        # `ingest_ring:<reader>` queue in the latency observatory (depth
        # gauge at scrape, dwell llhist fed per chunk below), so ring
        # pressure shows up in /debug/latency next to every other
        # bounded hand-off
        latency = getattr(server, "latency", None)
        ring_names = []
        ring_hists = []
        if latency is not None and getattr(latency, "enabled", False):
            _d, caps, _s, _st = pump.ring_stats()
            for i in range(pump.nreaders):
                name = f"ingest_ring:{addr_label(listener.address)}:{i}"
                ring_names.append(name)
                ring_hists.append(latency.queue_hist(name))

                def depth_of(idx=i):
                    return int(pump.ring_stats()[0][idx])

                latency.register_queue(name, depth_of, int(caps[i]))
        while not listener.closed:
            if supervisor is not None:
                supervisor.beat(sup_name)
            self._dispatch_one(pump, server, timeout_ms=200,
                               ring_hists=ring_hists)
        # readers may be blocked waiting for a free chunk: keep draining
        # while they wind down so their partial chunks (and the samples in
        # them) make it into the store before the final flush
        pump.signal_stop()
        while pump.live_readers() > 0:
            self._dispatch_one(pump, server, timeout_ms=50)
        pump.stop()  # join (Listener.close may be doing the same)
        while self._dispatch_one(pump, server, timeout_ms=0):
            pass
        lost = pump.lost_lines()
        if lost:
            logger.warning("pump discarded %d in-flight lines at shutdown",
                           lost)
            server.stats.inc("parse_errors", lost)
        if supervisor is not None:
            # a deliberately-closed listener is not a stall
            supervisor.unregister(sup_name)
        if latency is not None:
            for name in ring_names:
                latency.unregister_queue(name)
        # native memory is freed by Pump.__del__ once the listener drops
        # its reference: freeing here would race Listener.close()'s own
        # concurrent stop() call

    def _dispatch_one(self, pump, server, timeout_ms: int,
                      ring_hists=None) -> bool:
        """One sealed chunk into the store. Books the dispatcher's
        account on the pump, once per chunk: the wall it waited in
        `pump.next` and this thread's CPU (its busy wall is the window
        less the wait), the `ingest.dispatch.*` rows of /metrics."""
        cpu0, t0 = time.thread_time(), time.perf_counter()
        chunk = pump.next(timeout_ms)
        pump.dispatch_wait_s += time.perf_counter() - t0
        if chunk is None:
            return False
        with annotate("ingest.dispatch"):
            self._dispatch_chunk(pump, server, chunk, ring_hists)
        pump.dispatch_cpu_s += time.thread_time() - cpu0
        return True

    def _dispatch_chunk(self, pump, server, chunk, ring_hists) -> None:
        # sample-age stamp: the closest Python point to the C++ socket
        # read (readers seal within seal_age_ms of the first sample)
        server.latency.note_arrival("dogstatsd",
                                    getattr(chunk, "samples", 0) or 1)
        # ring dwell: seal -> dispatch, measured on the C++ monotonic
        # clock (both stamps native-side, so no cross-clock skew)
        if ring_hists:
            try:
                ring_hists[chunk.reader].observe(chunk.dwell_ms / 1000.0)
            except IndexError:
                pass
        try:
            if chunk.dropped:
                # oversized datagrams, dropped in C++ (metric_max_length
                # parity with handle_packet_buffer)
                server.stats.inc("parse_errors", chunk.dropped)
            self._ingest(chunk)
        except Exception:
            logger.exception("pump chunk dispatch failed")
        finally:
            pump.release(chunk)
        # surface reader backpressure (kernel-buffer loss risk) as a
        # self-metric so operators can tell it apart from network loss
        stalls = pump.stalls()
        seen = getattr(pump, "_stalls_seen", 0)
        if stalls != seen:
            server.stats.inc("ingest_pump_stalls", stalls - seen)
            pump._stalls_seen = stalls
