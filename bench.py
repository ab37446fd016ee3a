#!/usr/bin/env python
"""veneur-tpu benchmark: aggregated DogStatsD samples/sec.

Drives the full in-process pipeline — packet bytes -> parse -> key intern ->
device batch apply -> flush — over a mixed workload (counters, gauges,
timers, sets across many unique keys), and prints ONE JSON line.

Baseline: the reference's published sustained UDP throughput of 60,000
packets/sec (reference README.md:361-364); see BASELINE.md.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import threading
import time
import traceback

BASELINE_SAMPLES_PER_SEC = 60_000.0
_T0 = time.monotonic()

# one authoritative name per scenario, shared by the success and the
# error-path JSON so harnesses can key records by metric name
METRIC_NAMES = {
    "mixed": "dogstatsd_samples_per_sec",
    "counter": "counter_samples_per_sec",
    "timers": "timer_samples_per_sec",
    "hll": "hll_samples_per_sec",
    "forward": "forwarded_digest_keys_per_sec",
    "llhist": "llhist_samples_per_sec",
    "ssf": "ssf_extracted_samples_per_sec",
    "device": "device_samples_per_sec",
    "sustained": "sustained_samples_per_sec",
    "tdigest": "tdigest_samples_per_sec",
    "mesh": "mesh_samples_per_sec",
    "mesh-worker": "mesh_samples_per_sec",
    "resize_storm": "resize_storm_flush_p99_ratio",
    "query": "query_reads_per_sec",
    "reshard": "reshard_flush_p99_ratio",
    "reshard-worker": "reshard_flush_p99_ratio",
    "egress": "egress_encode_rate",
}

# accumulates fields as stages complete, so the deadline guard can emit a
# partial-but-valid JSON line if a stage (usually an XLA compile on a cold
# cache) runs long
RESULT: dict = {}
LAST_SSF_STATS: dict = {}  # side-channel detail for the configs record
_EMIT_LOCK = threading.Lock()
_EMITTED = False

# device batch size by platform: larger batches amortize the
# per-dispatch transfer overhead on TPU; on the CPU fallback the kernels
# compete with the host pipeline for the same core, so smaller batches
# keep latency sane. Set once by main() after backend detection; module
# importers (tests) get the CPU value.
BATCH_CAP = [16384]


def set_batch_cap_for(platform: str) -> None:
    env = os.environ.get("BENCH_BATCH_CAP")
    if env:  # manual tuning knob
        try:
            cap = int(env)
        except ValueError:
            cap = 0
        if cap > 0:
            BATCH_CAP[0] = cap
            return
        log(f"ignoring invalid BENCH_BATCH_CAP={env!r}")
    BATCH_CAP[0] = 32768 if not platform.startswith("cpu") else 16384


def log(msg: str) -> None:
    """Timestamped progress line to stderr — makes a driver-side timeout
    tail diagnosable (which stage was running, how long it had been)."""
    print(f"bench[{time.monotonic() - _T0:7.1f}s] {msg}",
          file=sys.stderr, flush=True)


def finalize() -> None:
    """Emit THE one benchmark JSON line exactly once (normal completion
    and the deadline guard race to call this)."""
    global _EMITTED
    with _EMIT_LOCK:
        if _EMITTED:
            return
        _EMITTED = True
        obj = dict(RESULT)
        obj.setdefault("metric", "dogstatsd_samples_per_sec")
        obj.setdefault("value", 0.0)
        obj.setdefault("unit", "samples/s")
        obj["vs_baseline"] = round(
            float(obj["value"]) / BASELINE_SAMPLES_PER_SEC, 3)
        print(json.dumps(obj), flush=True)


_DEADLINE_AT = [float("inf")]


def arm_deadline(seconds: float) -> None:
    """Hard wall-clock budget: when it fires, whatever stages completed
    are emitted (truncated=true) and the process exits 0 — a partial
    number always beats a driver-side timeout with no number."""
    _DEADLINE_AT[0] = time.monotonic() + seconds

    def fire():
        log(f"deadline ({seconds:.0f}s) reached; emitting partial result")
        RESULT["truncated"] = True
        finalize()
        os._exit(0)

    t = threading.Timer(seconds, fire)
    t.daemon = True
    t.start()


def time_left() -> float:
    """Seconds until the hard deadline (inf when none armed). Stages use
    this to skip gracefully instead of being killed mid-flight."""
    return _DEADLINE_AT[0] - time.monotonic()


def initialize_backend() -> str:
    """Bring up the JAX backend before constructing any pipeline object so
    a backend failure is visible up front. A benchmark number comes from
    an accelerator: when JAX finds only the CPU the run stops here,
    unless the caller asked for the CPU by name (JAX_PLATFORMS=cpu, how
    tests/test_bench.py smoke-runs the scenarios)."""
    import jax

    devs = jax.devices()
    platform = devs[0].platform
    if (platform == "cpu"
            and not os.environ.get("JAX_PLATFORMS", "").startswith("cpu")):
        raise SystemExit(
            f"bench: no accelerator: JAX found platform {platform!r} "
            f"({len(devs)} device(s)); set JAX_PLATFORMS=cpu to run the "
            "scenarios on the CPU on purpose")
    # one persistent compile cache, shared with the server and
    # chip_smoke.py (util/compilecache.py has the directory rule)
    from veneur_tpu.util import compilecache
    log(f"backend={platform} devices={devs} "
        f"compile_cache={compilecache.enable() or 'off'}")
    return platform


def make_datagrams(packets, per: int = 40):
    """Batch packets into datagram-sized buffers (~`per` metrics each,
    like a client pipelining into 1400-byte datagrams)."""
    return [b"\n".join(packets[i:i + per])
            for i in range(0, len(packets), per)]


def make_packets(num_keys: int, values_per_packet: int = 8):
    """Pre-render a packet corpus: multi-value timers, counters, gauges and
    sets across num_keys unique keys (veneur-emit-style load)."""
    import numpy as np
    rng = np.random.default_rng(42)
    packets = []
    samples = 0
    for i in range(num_keys):
        kind = i % 4
        tag = b"#shard:%d,env:bench" % (i % 100)
        if kind == 0:
            packets.append(b"bench.counter.%d:%d|c|%s" % (i, rng.integers(1, 100), tag))
            samples += 1
        elif kind == 1:
            packets.append(b"bench.gauge.%d:%.3f|g|%s" % (i, rng.random() * 100, tag))
            samples += 1
        elif kind == 2:
            vals = b":".join(b"%.2f" % v for v in rng.normal(100, 15, values_per_packet))
            packets.append(b"bench.timer.%d:%s|ms|%s" % (i, vals, tag))
            samples += values_per_packet
        else:
            packets.append(b"bench.set.%d:user%d|s|%s" % (i, rng.integers(0, 10000), tag))
            samples += 1
    return packets, samples


class UdpRig:
    """A live UDP server plus the native blaster pointed at it: the
    benchmark's end-to-end rig (C++ sendmmsg senders -> kernel loopback ->
    C++ pump readers -> Python chunk dispatch -> device column store).
    This replaces the old in-process handle_packet_batch drive: load
    generation and ingest both run GIL-free, so the measurement reflects
    the server's pipeline, not the Python emitter's."""

    def __init__(self, num_keys: int, datagrams, samples_per_dgram: float,
                 families: int = 1, **cfg_overrides):
        import socket

        from veneur_tpu import native

        # blaster first: if the native lib is unavailable this raises
        # before a server (ticker thread, sockets) exists to leak
        self.blaster = native.Blaster(datagrams)
        self.spd = samples_per_dgram
        self.datagrams = datagrams
        self.server = _mk_server(
            num_keys, families=families,
            statsd_listen_addresses=["udp://127.0.0.1:0"],
            **cfg_overrides)
        self.server.start()
        addr = self.server.local_addr("udp")
        self.pump = self.server._listeners[0].pump
        self.sock = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
        self.sock.connect(addr)
        self.sock.setsockopt(socket.SOL_SOCKET, socket.SO_SNDBUF, 4 << 20)

    def warmup(self, join_warmup_thread: bool = True):
        """Intern every key (slow path) + compile every kernel path."""
        import numpy as np

        server = self.server
        server.handle_packet_batch(self.datagrams)
        # promote-early set policy (tpu.set_promote_samples): the live
        # window would otherwise climb the device slot ladder and pay
        # each dev-cap shape's scatter/estimate compile mid-measurement.
        # Promote every interned set row now; _dev_cap persists across
        # the flush below, so steady-state intervals never compile.
        sets = server.store.sets
        import jax
        if jax.default_backend() not in ("cpu",):
            if sets.prewarm_dense():
                # one dense-tier sample so apply_batch compiles at the
                # settled dev cap (row 0 is promoted by prewarm; the
                # warmup interval's flush is discarded anyway)
                sets.add_batch(np.zeros(1, np.int32), np.zeros(1, np.int32),
                               np.ones(1, np.int32))
        server.store.apply_all_pending()
        server.flush()
        if join_warmup_thread and server._warmup_thread is not None:
            server._warmup_thread.join(timeout=120)
        with server._flush_lock:  # let an in-flight ticker flush drain
            pass

    def blast(self, seconds: float, offered_samples_per_s: float = 0.0,
              senders: int = 1, drain_s: float = 2.0):
        """Offer load for `seconds`; returns (offered_rate, processed_rate,
        elapsed). offered==0 blasts flat out. drain_s bounds the
        post-window wait for in-flight chunks to settle."""
        blaster, server = self.blaster, self.server
        blaster.reset()
        pace = (offered_samples_per_s / self.spd / senders
                if offered_samples_per_s else 0.0)
        sent = [0] * senders
        fd = self.sock.fileno()

        def run(slot):
            sent[slot] = blaster.run(fd, burst=64, pace_pps=pace,
                                     phase=slot * 997)

        ts = [threading.Thread(target=run, args=(i,), daemon=True)
              for i in range(senders)]
        p0 = server.store.processed
        t0 = time.perf_counter()
        for t in ts:
            t.start()
        time.sleep(seconds)
        blaster.stop()
        for t in ts:
            t.join(timeout=30)
        # drain until the processed counter stabilizes so one window's
        # in-flight chunks don't bleed into the next measurement
        last = server.store.processed
        drain_deadline = time.perf_counter() + drain_s
        while time.perf_counter() < drain_deadline:
            time.sleep(0.15)
            cur = server.store.processed
            if cur == last:
                break
            last = cur
        elapsed = time.perf_counter() - t0
        processed = server.store.processed - p0
        return (sum(sent) * self.spd / elapsed, processed / elapsed,
                elapsed)

    def close(self):
        self.server.shutdown()
        try:
            self.sock.close()
        except OSError:
            pass


# offered-load ladder for the knee search, in samples/s (0 = unpaced).
# The 2M->4M->6M rungs bracket the BENCH_r05 knee (1.33M -> 330k
# processed when offered doubled to 4M): the batch-pipeline acceptance
# is processed rate monotonically non-decreasing through 5M offered.
LADDER = (2e6, 4e6, 6e6, 8e6, 16e6, 0)


def run_pipeline_mt(duration_s: float, num_keys: int, rig: UdpRig = None,
                    ladder=LADDER, scale_senders: bool = False):
    """The headline scenario: end-to-end UDP at increasing offered load.
    On a small host an unpaced sender starves the pipeline of CPU, so the
    ladder sweeps offered rates and reports the knee (best processed
    rate). Returns (best_rate, {offered_label: processed_rate})."""
    from veneur_tpu import native

    if not native.available():
        return _run_pipeline_inproc(duration_s, num_keys)
    own_rig = rig is None
    if own_rig:
        packets, samples = make_packets(num_keys)
        datagrams = make_datagrams(packets)
        rig = UdpRig(num_keys, datagrams, samples / len(datagrams),
                     families=4, interval=3600.0)
        log(f"mixed: warmup (intern {num_keys} keys + compile kernels)")
        rig.warmup()
        log("mixed: warmup done")
    per = max(1.2, duration_s / max(1, len(ladder)))
    sweep = {}
    offers = {}  # label -> numeric offered rate (0 = unpaced)
    batch_sizes = {}  # label -> avg samples per dispatched batch
    zero_rungs = 0
    try:
        for offered in ladder:
            if time_left() < per + 8:
                log("mixed: ladder truncated by deadline")
                break
            b0 = rig.server.stats["batches_dispatched"]
            p0 = rig.server.store.processed
            off_rate, rate, _ = rig.blast(per, offered)
            label = "unpaced" if not offered else f"{offered / 1e6:g}M"
            sweep[label] = round(rate, 1)
            offers[label] = offered
            # per-stage batch size: how many samples each sealed chunk
            # carried into the column store this rung (the number that
            # explains WHERE on the ladder batching amortization lives)
            batches = rig.server.stats["batches_dispatched"] - b0
            if batches > 0:
                batch_sizes[label] = round(
                    (rig.server.store.processed - p0) / batches, 1)
            log(f"mixed: offered {off_rate:,.0f}/s -> processed "
                f"{rate:,.0f} samples/s "
                f"(avg batch {batch_sizes.get(label, 0):,.0f})")
            best_so_far = max(sweep.values())
            if best_so_far and 0 < rate < 0.5 * best_so_far:
                # past the knee: on a small host higher offered load only
                # starves the pipeline; further rungs waste budget. A
                # ZERO rung is a measurement artifact (one long
                # synchronous apply swallowed the window), not a knee —
                # keep climbing in that case, but two in a row means the
                # senders are starving the dispatcher outright and every
                # higher rung will too.
                log("mixed: past the knee; stopping ladder")
                break
            zero_rungs = zero_rungs + 1 if not rate else 0
            if zero_rungs >= 2:
                log("mixed: dispatcher starved two rungs; stopping ladder")
                break
        # the headline/knee comes from the single-sender ladder only:
        # the sustained stage paces a single sender against it
        best = max(sweep.values()) if sweep else 0.0
        # sender-scaling row (only meaningful with cores to spare, and
        # only for the headline caller — the sustained knee probe would
        # discard it): the C++ senders and pump readers are GIL-free, so
        # on multi-core hosts a second sender demonstrates
        # reader-parallel scaling
        if (scale_senders and (os.cpu_count() or 1) > 1 and sweep
                and time_left() > per + 8):
            best_offered = max(sweep, key=sweep.get)
            _off2, rate2, _ = rig.blast(per, offers[best_offered],
                                        senders=2)
            sweep[f"{best_offered}x2senders"] = round(rate2, 1)
            log(f"mixed: 2 senders at {best_offered} -> "
                f"{rate2:,.0f} samples/s")
    finally:
        if own_rig:
            rig.close()
    if batch_sizes:
        RESULT["ingest_batch_sizes"] = batch_sizes
    return best, sweep


def _run_pipeline_inproc(duration_s: float, num_keys: int):
    """Fallback when the native library is unavailable: the in-process
    drive through handle_packet_batch (now the numpy columnar decoder,
    so even compiler-less hosts measure the batched pipeline)."""
    server = _mk_server(num_keys, families=4)
    packets, samples_per_round = make_packets(num_keys)
    datagrams = make_datagrams(packets)
    server.handle_packet_batch(datagrams)
    server.store.apply_all_pending()
    server.flush()
    t0 = time.perf_counter()
    rounds = 0
    while time.perf_counter() - t0 < duration_s:
        server.handle_packet_batch(datagrams)
        rounds += 1
    server.store.apply_all_pending()
    elapsed = time.perf_counter() - t0
    server.flush()
    return rounds * samples_per_round / elapsed, {"inproc": True}


def run_scenario_sustained(num_keys: int = 100_000, interval_s: float = 10.0,
                           intervals: int = 3, rig: UdpRig = None,
                           offered: float = None, ladder_s: float = 6.0):
    """The north-star gate at the reference's production shape: a live
    server with a real flush ticker (interval_s, >= `intervals` flushes)
    under sustained UDP load; reports per-interval flush wall time (must
    stay under the interval — reference flusher.go:26-122's one-interval
    deadline, config.go:109's 10s default) and the sustained processed
    rate. Load is offered at ~85% of the measured knee so the number
    reflects steady aggregation, not drop handling."""
    from veneur_tpu import native

    if not native.available():
        raise RuntimeError(
            f"sustained gate needs the native rig: "
            f"{native.unavailable_reason()}")
    own_rig = rig is None
    if own_rig:
        packets, samples = make_packets(num_keys)
        datagrams = make_datagrams(packets)
        # flush_async: the overlapped flush is the production shape this
        # gate now measures — the swap is the only store work on the
        # tick, readouts drain on the background executor, and the
        # overlap acceptance below compares ingest rate during flush
        # windows against the between-flush rate
        rig = UdpRig(num_keys, datagrams, samples / len(datagrams),
                     families=4, interval=interval_s,
                     synchronize_with_interval=False, flush_async=True)
        log(f"sustained: warmup ({num_keys} keys)")
        rig.warmup()
        log("sustained: warmup done")
    server = rig.server
    flush_times = []
    flush_windows = []  # (start, end) perf_counter stamps per flush tick
    flush_phases = []  # per-flush attribution (server.flush_phase_timings)
    # per-flush self-tracing cost counters (trace/store.py): spans
    # recorded + exemplars captured per flush, so the next BENCH round
    # measures what the cross-tier trace plane costs under load
    trace_marks = []
    orig_flush_locked = server._flush_locked

    def _trace_mark():
        plane = getattr(server, "trace_plane", None)
        if plane is None:
            return (0, 0)
        return (plane.store.spans_recorded,
                plane.exemplars.captured_total)

    def timed_flush():
        t0 = time.perf_counter()
        mark = _trace_mark()
        orig_flush_locked()
        end = time.perf_counter()
        flush_times.append(end - t0)
        flush_windows.append((t0, end))
        flush_phases.append(dict(getattr(server, "flush_phase_timings", {})))
        after = _trace_mark()
        trace_marks.append((after[0] - mark[0], after[1] - mark[1]))

    server._flush_locked = timed_flush
    try:
        if offered is None:
            # short knee probe to pick the sustained offered rate
            best, _ = run_pipeline_mt(ladder_s, num_keys, rig=rig,
                                      ladder=(4e6, 12e6, 0))
            offered = max(best * 0.85, 2e5)
        log(f"sustained: offering {offered:,.0f} samples/s for "
            f"{intervals}x{interval_s:g}s")
        flush_times.clear()
        flush_windows.clear()
        # overlap acceptance sampler: the processed counter every 25ms,
        # classified against the flush windows afterwards — with
        # flush_async the during-flush ingest rate must track the
        # between-flush rate (it used to stall behind ~1.7s of dispatch)
        ingest_samples = []
        sampler_stop = threading.Event()

        def _sample_ingest():
            while not sampler_stop.is_set():
                ingest_samples.append(
                    (time.perf_counter(), server.store.processed))
                sampler_stop.wait(0.025)

        sampler = threading.Thread(target=_sample_ingest, daemon=True)
        sampler.start()
        off_rate, rate, elapsed = rig.blast(
            intervals * interval_s + 0.5, offered)
        sampler_stop.set()
        sampler.join(timeout=2)
        # let an in-flight ticker flush finish so its wall time lands
        wait_deadline = time.perf_counter() + interval_s * 2
        while (len(flush_times) < intervals
               and time.perf_counter() < wait_deadline
               and time_left() > 10):
            time.sleep(0.1)
        drain_t0 = time.perf_counter()
        server.store.apply_all_pending()
        import jax
        jax.block_until_ready(server.store.counters.state)
        drain_s = time.perf_counter() - drain_t0
        ticker_flushes = len(flush_times)
        # a final timed flush guarantees at least one measurement of a
        # full-table flush under post-load state
        server.flush()
    finally:
        server._flush_locked = orig_flush_locked
        if own_rig:
            rig.close()
    times = sorted(flush_times) or [0.0]
    p50 = times[len(times) // 2]
    p99 = times[min(len(times) - 1, int(len(times) * 0.99))]
    log(f"sustained: {rate:,.0f} samples/s over {elapsed:.1f}s "
        f"(offered {off_rate:,.0f}), {len(times)} flushes, "
        f"p50={p50:.3f}s p99={p99:.3f}s drain={drain_s:.2f}s")
    extra = {
        "flush_p50_s": round(p50, 4),
        "flush_p99_s": round(p99, 4),
        "flush_count": ticker_flushes,
        "queue_drain_s": round(drain_s, 3),
        "interval_s": interval_s,
        "offered_samples_per_sec": round(off_rate, 1),
        "sustained_keys": num_keys,
        "flush_async": bool(server.config.flush_async),
    }
    # capacity headroom columns (PR-20 device observatory): peak HBM
    # held by registered generations over the run, and the end-of-run
    # shard balance (None on single-device stores)
    devobs = getattr(server, "deviceobs", None)
    if devobs is not None and devobs.enabled:
        extra["device_mem_peak_bytes"] = int(devobs.peak_bytes)
        skew = devobs.shard_skew()
        extra["shard_skew"] = round(skew, 4) if skew is not None else None
    # overlap acceptance: ingest processed-rate inside flush windows vs
    # between them (PR-15's pin — was gated behind the dispatch stall)
    if len(ingest_samples) >= 3 and flush_windows:
        def _in_flush(a, b):
            return any(a < fe and b > fs for fs, fe in flush_windows)

        dur_n = dur_t = btw_n = btw_t = 0.0
        for (ta, pa), (tb, pb) in zip(ingest_samples, ingest_samples[1:]):
            if _in_flush(ta, tb):
                dur_n += pb - pa
                dur_t += tb - ta
            else:
                btw_n += pb - pa
                btw_t += tb - ta
        if dur_t > 0 and btw_t > 0:
            r_during = dur_n / dur_t
            r_between = btw_n / btw_t
            extra["ingest_rate_during_flush"] = round(r_during, 1)
            extra["ingest_rate_between_flush"] = round(r_between, 1)
            extra["ingest_overlap_ratio"] = round(
                r_during / r_between, 4) if r_between else None
    if trace_marks:
        extra["trace_spans_per_flush"] = {
            "max": max(s for s, _e in trace_marks),
            "total": sum(s for s, _e in trace_marks)}
        extra["exemplars_per_flush"] = {
            "max": max(e for _s, e in trace_marks),
            "total": sum(e for _s, e in trace_marks)}
    if flush_phases:
        scalar = [{k: v for k, v in p.items()
                   if isinstance(v, (int, float))} for p in flush_phases]
        keys = sorted(set().union(*(p.keys() for p in scalar)))

        def series(vals):
            vals = sorted(vals)
            return {"p50": round(vals[len(vals) // 2], 4),
                    "p99": round(vals[min(len(vals) - 1,
                                          int(len(vals) * 0.99))], 4),
                    "max": round(vals[-1], 4)}

        # attribution: worst flush per phase (the p99 driver) — kept for
        # trajectory continuity with earlier BENCH rounds
        extra["flush_phases_max_s"] = {
            k: round(max(p.get(k, 0.0) for p in scalar), 4) for k in keys}
        # the full per-flush series, p50/p99/max per phase — so the perf
        # trajectory captures the phase distribution, not one outlier
        extra["flush_phase_series"] = {
            k: series([p.get(k, 0.0) for p in scalar]) for k in keys}
        # the PR-15 acceptance row pulled out by name: join-only wall
        # time per flush tick (excludes dispatch/sync/transfer when the
        # readout ran on the background executor)
        if "critical_path_s" in extra["flush_phase_series"]:
            extra["flush_critical_path"] = \
                extra["flush_phase_series"]["critical_path_s"]
        # per-family dispatch attribution (core/latency.py observatory):
        # per family, host dispatch vs summed per-device sync vs host
        # transfer, aggregated across the measured flushes
        fams = [p["families"] for p in flush_phases
                if isinstance(p.get("families"), dict)]
        if fams:
            agg: dict = {}
            for ftree in fams:
                for fam, rec in ftree.items():
                    segs = agg.setdefault(
                        fam, {"dispatch_s": [], "sync_s": [],
                              "transfer_s": []})
                    segs["dispatch_s"].append(rec.get("dispatch_s", 0.0))
                    segs["transfer_s"].append(rec.get("transfer_s", 0.0))
                    segs["sync_s"].append(sum(
                        d.get("sync_s", 0.0)
                        for d in rec.get("devices", {}).values()))
            extra["flush_family_breakdown"] = {
                fam: {seg: series(vals) for seg, vals in segs.items()}
                for fam, segs in agg.items()}
    return rate, extra


def run_pipeline(duration_s: float, num_keys: int):
    """Single-threaded host pipeline (kept for comparison runs)."""
    server = _mk_server(num_keys, families=4)
    packets, samples_per_round = make_packets(num_keys)
    datagrams = make_datagrams(packets)
    server.handle_packet_batch(datagrams)
    server.store.apply_all_pending()
    server.flush()

    t0 = time.perf_counter()
    total_samples = 0
    while True:
        server.handle_packet_batch(datagrams)
        total_samples += samples_per_round
        if time.perf_counter() - t0 >= duration_s:
            break
    server.store.apply_all_pending()
    server.flush()
    elapsed = time.perf_counter() - t0
    return total_samples / elapsed, elapsed


def _mk_server(num_keys: int, extra_span_sinks=None, families: int = 1,
               **cfg_overrides):
    from veneur_tpu.config import Config
    from veneur_tpu.core.server import Server
    from veneur_tpu.sinks.blackhole import BlackholeMetricSink

    cfg = Config()
    cfg.interval = 10.0
    # families: how many sampler families the caller's corpus spreads
    # num_keys across (make_packets: 4 via i % 4; single-family
    # scenarios keep the exact legacy sizing). Flush kernels are
    # capacity-proportional — the t-digest flush sorts every row, live
    # or not — so sizing every family at num_keys for a mixed corpus
    # quadrupled the flush's device work for nothing. Margin covers
    # self-metrics and slack.
    if families > 1:
        fam = max(4096, num_keys // families + num_keys // 16 + 256)
        cfg.tpu.counter_capacity = fam
        cfg.tpu.gauge_capacity = fam
        cfg.tpu.histo_capacity = fam
        cfg.tpu.set_capacity = max(1024, fam)
    else:
        cfg.tpu.counter_capacity = max(4096, num_keys)
        cfg.tpu.gauge_capacity = max(4096, num_keys)
        cfg.tpu.histo_capacity = max(4096, num_keys)
        cfg.tpu.set_capacity = max(1024, num_keys // 2)
    cfg.tpu.batch_cap = BATCH_CAP[0]
    for k, v in cfg_overrides.items():
        setattr(cfg, k, v)
    cfg.apply_defaults()
    return Server(cfg, extra_metric_sinks=[BlackholeMetricSink()],
                  extra_span_sinks=extra_span_sinks)


def _run_udp_scenario(duration_s: float, packets, samples: int,
                      num_keys: int, offered: float = 0.0,
                      per_datagram: int = 40):
    """Shared driver for the UDP config scenarios: warmup, then offer
    load (unpaced knee by default, or an exact paced rate) and report the
    processed rate. per_datagram=1 sends each packet as its own datagram
    (the veneur-emit shape); the default batches ~40 per datagram like a
    pipelining client."""
    from veneur_tpu import native

    datagrams = make_datagrams(packets, per=per_datagram)
    if not native.available():
        server = _mk_server(num_keys)
        server.handle_packet_batch(datagrams)
        server.store.apply_all_pending()
        server.flush()
        t0 = time.perf_counter()
        rounds = 0
        while time.perf_counter() - t0 < duration_s:
            server.handle_packet_batch(datagrams)
            rounds += 1
        server.store.apply_all_pending()
        elapsed = time.perf_counter() - t0
        server.flush()
        return rounds * samples / elapsed
    rig = UdpRig(num_keys, datagrams, samples / len(datagrams),
                 interval=3600.0)
    try:
        rig.warmup(join_warmup_thread=False)
        if offered:
            _off, rate, _el = rig.blast(duration_s, offered)
        else:
            # two-rung mini-ladder: paced near capacity beats unpaced on
            # small hosts where the sender competes for the core
            per = max(1.0, duration_s / 2)
            _off, r1, _ = rig.blast(per, 0.0)
            _off, r2, _ = rig.blast(per, max(r1 * 2.0, 1e6))
            rate = max(r1, r2)
    finally:
        rig.close()
    return rate


def run_scenario_counter(duration_s: float):
    """BASELINE config 1: one counter key, single-metric datagrams (the
    veneur-emit shape — one metric per send, unlike the other
    scenarios' 40-metric pipelined datagrams) into a blackhole sink.
    Unpaced since BENCH_r06: the original 10k/s offered pace (matching
    the paper's emit rate) CAPPED the measurement once the pipeline
    outran it — the knee is what the config tracks now."""
    packets = [b"bench.one:1|c"] * 512
    return _run_udp_scenario(duration_s, packets, len(packets), 16,
                             per_datagram=1)


def run_scenario_timers(duration_s: float, num_keys: int = 1000):
    """BASELINE config 2: t-digest stress, multi-value timer packets
    replayed over UDP."""
    import numpy as np
    rng = np.random.default_rng(1)
    packets = []
    for i in range(num_keys):
        vals = b":".join(b"%.2f" % v for v in rng.normal(100, 15, 8))
        packets.append(b"bench.timer.%d:%s|ms" % (i, vals))
    return _run_udp_scenario(duration_s, packets, num_keys * 8,
                             num_keys * 2)


def run_scenario_forward(duration_s: float, num_keys: int = 50_000):
    """BASELINE config 4: local->global t-digest merge over forwardrpc."""
    import numpy as np
    server_global = _mk_server(num_keys, grpc_address="127.0.0.1:0")
    from veneur_tpu.forward.server import ImportServer
    imp = ImportServer(server_global, "127.0.0.1:0")
    imp.start()
    local = _mk_server(num_keys, forward_address=imp.address)
    from veneur_tpu.forward.client import ForwardClient
    client = ForwardClient(imp.address, deadline=30.0)
    local.forwarder = client.forward

    rng = np.random.default_rng(2)
    packets = [b"bench.fwd.%d:%s|ms" % (
        i, b":".join(b"%.2f" % v for v in rng.normal(50, 9, 4)))
        for i in range(num_keys)]
    datagrams = make_datagrams(packets)
    local.handle_packet_batch(datagrams)
    local.store.apply_all_pending()
    # warmup flush: compiles the fused flush+export kernel outside the
    # timed window (a cold TPU compile would eat the whole budget)
    local.flush()
    t0 = time.perf_counter()
    rounds = 0
    while time.perf_counter() - t0 < duration_s:
        local.handle_packet_batch(datagrams)
        local.flush()  # flush forwards the digests and resets state
        rounds += 1
    elapsed = time.perf_counter() - t0
    server_global.flush()
    client.close()
    imp.stop()
    # merged keys per second through the full forward+import+merge plane
    return rounds * num_keys / elapsed


def run_scenario_ssf(duration_s: float, num_keys: int = 10_000):
    """BASELINE config 5 (scaled): SSF spans with attached samples ->
    native extraction -> aggregation, plus span-sink fanout (TWO
    blackhole span sinks stand in for the datadog+kafka pair: each gets
    its own isolation queue and worker, so the measured path is the
    real two-sink fanout — lazy RawSpan decode, per-sink submit, queue
    overflow drops — without vendor HTTP noise)."""
    from veneur_tpu import ssf
    from veneur_tpu.sinks.blackhole import BlackholeSpanSink
    server = _mk_server(
        num_keys, interval=3600.0, span_channel_capacity=8192,
        extra_span_sinks=[BlackholeSpanSink("datadog-standin"),
                          BlackholeSpanSink("kafka-standin")])
    server.start()  # span workers drain the channel
    spans = []
    for i in range(2000):
        span = ssf.SSFSpan(
            id=i + 1, trace_id=i + 1, name=f"op{i % 50}",
            service="bench", start_timestamp=1, end_timestamp=2)
        span.metrics.append(ssf.count(f"bench.span.c{i % num_keys}", 2))
        span.metrics.append(
            ssf.timing(f"bench.span.t{i % num_keys}", 0.01, 1e-3))
        spans.append(span.SerializeToString())
    # warmup interns every sample key (slow path once per key), so the
    # measured window runs the native C++ span-decode path over the
    # pre-joined buffer (the shape the native UDP reader produces)
    import numpy as np
    joined = b"".join(spans)
    lens = np.fromiter((len(s) for s in spans), np.int64, len(spans))
    offs = np.zeros(len(spans), np.int64)
    np.cumsum(lens[:-1], out=offs[1:])
    server.handle_ssf_batch(spans[:100])
    server.handle_ssf_buffer(joined, offs, lens)
    server.flush()
    p0 = server.store.processed
    d0 = server.spans_dropped
    w0 = sum(w.dropped for w in server._span_sink_workers)
    dl0 = sum(w.ingested for w in server._span_sink_workers)
    t0 = time.perf_counter()
    sent = 0
    while time.perf_counter() - t0 < duration_s:
        server.handle_ssf_buffer(joined, offs, lens)
        sent += len(spans)
    elapsed = time.perf_counter() - t0  # before the settle wait: idle
    # tail time would deflate the rate
    server.store.apply_all_pending()
    # native extraction counts processed synchronously in this thread;
    # the non-native fallback extracts in span workers, so wait for the
    # counter to settle before reading it (bounded)
    settle_deadline = time.perf_counter() + 10
    last = -1
    while time.perf_counter() < settle_deadline:
        cur = server.store.processed
        if cur == last:
            break
        last = cur
        time.sleep(0.15)
    # extraction throughput is what aggregates; span-SINK delivery is
    # best-effort by design (bounded isolation queues, drops counted)
    extracted = server.store.processed - p0
    # two distinct shed points: the shared span channel (producer
    # outruns the decode workers — expected under flat-out offered load
    # on few cores) vs the per-sink isolation buffers (a sink falling
    # behind its fan-out — should be ~0 since chunked submission)
    chan_drops = server.spans_dropped - d0
    sink_drops = sum(w.dropped for w in server._span_sink_workers) - w0
    delivered = sum(w.ingested for w in server._span_sink_workers) - dl0
    log(f"ssf: {sent / elapsed:,.0f} spans/s ingested, "
        f"{extracted / elapsed:,.0f} samples/s extracted, "
        f"{delivered} sink-delivered, {sink_drops} sink-plane drops, "
        f"{chan_drops} span-channel sheds")
    LAST_SSF_STATS.clear()
    LAST_SSF_STATS.update(
        spans_per_sec=round(sent / elapsed, 1),
        sink_delivered=delivered, sink_drops=sink_drops,
        span_channel_sheds=chan_drops)
    server.flush()
    server.shutdown()
    return extracted / elapsed


def run_scenario_device(duration_s: float, num_keys: int = 100_000,
                        batch: int = 65_536):
    """Device-only throughput: samples/s through the batched apply kernels
    plus one flush pass, with pre-staged on-device COO arrays — separates
    device kernel throughput from host parse/intern overhead."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from veneur_tpu.ops import batch_hll, batch_tdigest, scalars

    percentiles = (0.5, 0.9, 0.99)
    quarter = batch // 4
    rng = np.random.default_rng(7)
    f32 = np.float32
    b = {
        "c_rows": rng.integers(0, num_keys, quarter).astype(np.int32),
        "c_vals": (rng.random(quarter) * 10).astype(f32),
        "c_rates": np.ones(quarter, f32),
        "g_rows": rng.integers(0, num_keys, quarter).astype(np.int32),
        "g_vals": rng.random(quarter).astype(f32),
        "h_rows": (h_rows := rng.integers(0, num_keys, quarter).astype(
            np.int32)),
        "h_vals": rng.normal(100, 15, quarter).astype(f32),
        "h_wts": np.ones(quarter, f32),
        "h_slots": batch_tdigest.host_ranks(h_rows),
        "s_rows": rng.integers(0, max(1, num_keys // 8), quarter).astype(
            np.int32),
        "s_idx": rng.integers(0, batch_hll.M, quarter).astype(np.int32),
        "s_rho": rng.integers(1, 30, quarter).astype(np.int32),
    }
    b = jax.device_put(b)

    @jax.jit
    def apply_step(counters, gauges, histos, sets, data):
        counters = scalars.apply_counters(
            counters, data["c_rows"], data["c_vals"], data["c_rates"])
        gauges = scalars.apply_gauges(gauges, data["g_rows"], data["g_vals"])
        histos = batch_tdigest.apply_batch(
            histos, data["h_rows"], data["h_vals"], data["h_wts"],
            data["h_slots"])
        sets = batch_hll.apply_batch(
            sets, data["s_rows"], data["s_idx"], data["s_rho"])
        return counters, gauges, histos, sets

    @jax.jit
    def flush_step(counters, histos, sets):
        return (scalars.counter_values(counters),
                batch_tdigest.flush_quantiles(histos, percentiles),
                batch_hll.estimate(sets))

    state = (scalars.init_counters(num_keys),
             scalars.init_gauges(num_keys),
             batch_tdigest.init_state(num_keys),
             batch_hll.init_state(max(1, num_keys // 8)))
    # warmup/compile
    state = apply_step(*state, b)
    jax.block_until_ready(flush_step(state[0], state[2], state[3]))

    t0 = time.perf_counter()
    applies = 0
    while time.perf_counter() - t0 < duration_s:
        for _ in range(20):
            state = apply_step(*state, b)
        applies += 20
    jax.block_until_ready(state)
    apply_elapsed = time.perf_counter() - t0

    tf = time.perf_counter()
    out = flush_step(state[0], state[2], state[3])
    jax.block_until_ready(out)
    flush_latency = time.perf_counter() - tf

    rate = applies * batch / apply_elapsed
    return rate, flush_latency


def _time_flush(fn, reps: int = 3) -> float:
    """Median wall time of a flush callable (first call compiles)."""
    import jax
    jax.block_until_ready(fn())
    times = []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        times.append(time.perf_counter() - t0)
    return sorted(times)[len(times) // 2]


def run_scenario_tdigest(duration_s: float, num_keys: int = 100_000,
                         batch: int = 16_384):
    """Histogram-family steady state through the real table: COO batches
    ingest via HistoTable.add_batch (host slot computation + adaptive
    compaction included), sparse-key regime at `num_keys`. The
    round-2 verdict's t-digest gate: >= 5M histo samples/s at 100k keys."""
    import numpy as np

    from veneur_tpu.core.columnstore import HistoTable

    table = HistoTable(num_keys, batch)
    rng = np.random.default_rng(11)
    batches = []
    for _ in range(16):
        rows = rng.integers(0, num_keys, batch).astype(np.int32)
        vals = rng.normal(100, 15, batch).astype(np.float32)
        wts = np.ones(batch, np.float32)
        batches.append((rows, vals, wts))
    # warmup: compile apply + compact + the exact flush being timed
    # (the percentile tuple is a static jit arg: a different tuple would
    # compile a separate executable inside the timed window)
    table.add_batch(*batches[0])
    table.apply_pending()
    table.snapshot_and_reset((0.5, 0.9, 0.99))
    log(f"tdigest: warmup done ({num_keys} keys, batch {batch})")

    t0 = time.perf_counter()
    total = 0
    i = 0
    while time.perf_counter() - t0 < duration_s:
        table.add_batch(*batches[i % 16])
        total += batch
        i += 1
    table.apply_pending()
    import jax
    jax.block_until_ready(table.state)
    elapsed = time.perf_counter() - t0
    tq = time.perf_counter()
    table.snapshot_and_reset((0.5, 0.9, 0.99))
    flush_s = time.perf_counter() - tq
    return total / elapsed, {"flush_latency_s": round(flush_s, 4),
                             "tdigest_keys": num_keys}


def run_scenario_llhist(duration_s: float, num_keys: int = 1000):
    """BASELINE config 6: Circllhist stress — multi-value `|l` packets
    (the exact-merge log-linear family). The batch decoders (native C++
    and the numpy fallback) now parse and BIN the `l` type in-column,
    so this measures the same columnar fast path as the other families:
    batch parse + pre-binned register scatter-add. (Before this rung
    rode the per-packet Python path — the gap the old BASELINE row
    measured.)"""
    import numpy as np
    rng = np.random.default_rng(6)
    packets = []
    for i in range(num_keys):
        vals = b":".join(b"%.3f" % v for v in rng.lognormal(3, 1, 8))
        packets.append(b"bench.llh.%d:%s|l" % (i, vals))
    return _run_udp_scenario(duration_s, packets, num_keys * 8,
                             num_keys * 2)


def run_scenario_mesh(duration_s: float, num_keys: int = 2000):
    """BASELINE config 7: mesh scaling — per-shard sustained throughput
    of the partitioned column store on 1/2/4 virtual CPU devices
    (xla_force_host_platform_device_count). Device count must be fixed
    before the backend initializes, so each rung runs in a fresh
    subprocess (run_scenario_mesh_worker); this parent collects the
    ladder and reports the widest rung's rate, with per-rung rates and
    scaling ratios (rate_N / rate_1) in the extra fields. On real TPU
    hardware the same scenario runs over the local chips instead
    (ROADMAP item 2's acceptance: >= 0.7*N scaling, bit-identical
    global percentiles — the exactness half is pinned by
    tests/test_mesh_plane.py)."""
    import subprocess

    ladder = {}
    for n in (1, 2, 4):
        if time_left() < 30:
            log(f"mesh rung {n} skipped: {time_left():.0f}s left")
            break
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        env["XLA_FLAGS"] = (
            f"--xla_force_host_platform_device_count={max(n, 2)}")
        env["VENEUR_TPU_MESH_N"] = str(n)
        cmd = [sys.executable, os.path.abspath(__file__),
               "--scenario", "mesh-worker",
               "--duration", str(max(2.0, duration_s / 3)),
               "--keys", str(num_keys), "--deadline", "0"]
        try:
            proc = subprocess.run(cmd, env=env, capture_output=True,
                                  timeout=max(60, time_left() - 5))
            line = proc.stdout.decode().strip().splitlines()[-1]
            ladder[str(n)] = json.loads(line)
        except Exception as e:
            ladder[str(n)] = {"error": f"{type(e).__name__}: {e}"}
            log(f"mesh rung {n} failed: {e}")
        else:
            log(f"mesh rung {n}: "
                f"{ladder[str(n)].get('value', 0):,.0f} samples/s")
    rates = {n: r.get("value", 0.0) for n, r in ladder.items()
             if isinstance(r, dict) and r.get("value")}
    base = rates.get("1", 0.0)
    RESULT["mesh_ladder"] = ladder
    if base > 0:
        RESULT["mesh_scaling"] = {
            n: round(rates[n] / base, 3) for n in rates}
    # capacity-headroom columns from the widest rung (each rung also
    # carries its own in the ladder)
    widest = max(rates, key=int, default=None)
    if widest is not None and isinstance(ladder.get(widest), dict):
        for col in ("device_mem_peak_bytes", "shard_skew"):
            if col in ladder[widest]:
                RESULT[col] = ladder[widest][col]
    best = max(rates.values()) if rates else 0.0
    return best


def run_scenario_mesh_worker(duration_s: float, num_keys: int) -> float:
    """One mesh rung (fresh process): drive the partitioned column
    store's batch fast path — pre-interned keys, digest-home routed
    dispatches across all four batched families, one columnar flush per
    ~second — and report aggregated samples/s. VENEUR_TPU_MESH_N picks
    the shard count (1 = single-device control, the exactness
    baseline)."""
    import numpy as np

    from veneur_tpu.core.columnstore import ColumnStore
    from veneur_tpu.core.flusher import flush_columnstore_batch
    from veneur_tpu.samplers.metrics import HistogramAggregates
    from veneur_tpu.samplers.parser import Parser

    shards = int(os.environ.get("VENEUR_TPU_MESH_N", "1"))
    cap = max(256, 1 << (num_keys - 1).bit_length())
    store = ColumnStore(
        counter_capacity=cap, gauge_capacity=cap, histo_capacity=cap,
        set_capacity=cap, llhist_capacity=cap, batch_cap=BATCH_CAP[0],
        shard_devices=shards if shards > 1 else 0)
    RESULT["mesh_shards"] = (store.shard_plane.n
                             if store.shard_plane is not None else 1)
    # standalone device observatory: the capacity-headroom columns the
    # BASELINE trajectory records beside the rates
    from veneur_tpu.core.deviceobs import DeviceObservatory
    devobs = DeviceObservatory()
    store.attach_deviceobs(devobs)
    parser = Parser()
    for i in range(num_keys):
        parser.parse_metric_fast(b"mesh.c.%d:1|c" % i, store.process)
        parser.parse_metric_fast(b"mesh.t.%d:5|ms" % i, store.process)
        parser.parse_metric_fast(b"mesh.l.%d:5|l" % i, store.process)
        parser.parse_metric_fast(b"mesh.s.%d:x|s" % i, store.process)
    store.apply_all_pending()

    aggs = HistogramAggregates.from_names(["min", "max", "count"])
    ps = (0.5, 0.99)

    def flush():
        return flush_columnstore_batch(store, False, ps, aggs,
                                       collect_forward=False)

    flush()  # compile the flush kernels off the timed window

    rng = np.random.default_rng(13)
    b = BATCH_CAP[0]
    rows = rng.integers(0, num_keys, b).astype(np.int32)
    vals = rng.normal(100, 15, b).astype(np.float32)
    ones = np.ones(b, np.float32)
    from veneur_tpu.ops import batch_hll
    s_idx = rng.integers(0, batch_hll.M, b).astype(np.int32)
    s_rho = rng.integers(1, 30, b).astype(np.int32)

    samples = 0
    t0 = time.perf_counter()
    next_flush = t0 + 1.0
    while time.perf_counter() - t0 < duration_s:
        store.counters.add_batch(rows, vals, ones)
        store.histos.add_batch(rows, vals, ones)
        store.llhists.add_batch(rows, vals, ones)
        store.sets.add_batch(rows, s_idx, s_rho)
        samples += 4 * b
        if time.perf_counter() >= next_flush:
            flush()
            next_flush = time.perf_counter() + 1.0
    batch, _fwd = flush()  # final flush inside the measurement contract
    elapsed = time.perf_counter() - t0
    RESULT["mesh_flush_metrics"] = len(batch)
    RESULT["device_mem_peak_bytes"] = int(devobs.peak_bytes)
    skew = devobs.shard_skew()
    RESULT["shard_skew"] = round(skew, 4) if skew is not None else None
    return samples / max(elapsed, 1e-9)


def run_scenario_resize_storm(duration_s: float = 0.0,
                              interval_s: float = 1.5,
                              intervals: int = 3):
    """PR-15 acceptance gate: flush-latency FLATNESS across capacity
    doublings. A live ticker server with deliberately small family
    capacities (1024 rows), the overlapped flush, and the shape-ladder
    prewarmer takes a steady baseline (keys below capacity), then a
    cardinality storm (scripts/cardinality_storm.py's driver, pointed
    at the server's own UDP port) mints enough counter series to force
    TWO capacity doublings (1024 -> 2048 -> 4096), then the baseline
    runs again. Reports flush p99 before/during/after the storm (the
    acceptance: during <= 1.25x pre), plus every post-resize round's
    retrace tag — each must read prewarmed/cache-hit, never a bare
    hot-path retrace. Returns the during/pre p99 ratio."""
    import sys as _sys

    storm_dir = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                             "scripts")
    if storm_dir not in _sys.path:
        _sys.path.insert(0, storm_dir)
    import cardinality_storm

    from veneur_tpu.config import Config
    from veneur_tpu.core.server import Server
    from veneur_tpu.sinks.blackhole import BlackholeMetricSink

    # built directly (not _mk_server, which floors capacities at 4096):
    # the storm needs small rungs it can actually climb twice
    cfg = Config()
    cfg.interval = interval_s
    cfg.statsd_listen_addresses = ["udp://127.0.0.1:0"]
    cfg.flush_async = True
    cfg.prewarm_ladder = True
    cfg.tpu.counter_capacity = 1024
    cfg.tpu.gauge_capacity = 1024
    cfg.tpu.histo_capacity = 1024
    cfg.tpu.set_capacity = 512
    cfg.tpu.batch_cap = BATCH_CAP[0]
    cfg.apply_defaults()
    server = Server(cfg, extra_metric_sinks=[BlackholeMetricSink()])
    server.start()
    host, port = server.local_addr("udp")

    flush_times = []
    orig = server._flush_locked

    def timed():
        t0 = time.perf_counter()
        orig()
        flush_times.append(time.perf_counter() - t0)

    server._flush_locked = timed

    def storm(keys, pps, duration):
        cardinality_storm.main([
            "--hostport", f"udp://{host}:{port}",
            "--name", "storm.resize", "--tag-key", "rid",
            "--keys", str(keys), "--pps", str(pps),
            "--duration", str(duration), "--type", "c"])

    def phase(keys, label):
        flush_times.clear()
        storm(keys, 20_000, intervals * interval_s)
        deadline = time.perf_counter() + interval_s * 2
        while len(flush_times) < intervals and \
                time.perf_counter() < deadline and time_left() > 10:
            time.sleep(0.1)
        times = sorted(flush_times) or [0.0]
        p99 = times[min(len(times) - 1, int(len(times) * 0.99))]
        log(f"resize_storm {label}: {len(times)} flushes, "
            f"p99={p99:.3f}s")
        return p99

    try:
        if server._warmup_thread is not None:
            server._warmup_thread.join(timeout=120)
        # let the initial prewarm rungs land before the baseline
        deadline = time.perf_counter() + 60
        while (server.prewarmer is not None
               and server.prewarmer.compiled_total < 4
               and time.perf_counter() < deadline and time_left() > 30):
            time.sleep(0.2)
        pre_p99 = phase(800, "pre-storm")       # below capacity: no resize
        cap0 = server.store.counters.capacity
        during_p99 = phase(3600, "storm")       # forces two doublings
        cap1 = server.store.counters.capacity
        post_p99 = phase(800, "post-storm")
    finally:
        server._flush_locked = orig
        server.config.flush_on_shutdown = False
        server.shutdown()

    doublings = 0
    c = cap0
    while c < cap1:
        c *= 2
        doublings += 1
    # every post-resize round's retrace tag, straight off the recorder
    retrace_tags = []
    for r in server.telemetry.flushes.snapshot():
        for fam, rec in (r.get("families") or {}).items():
            if rec.get("retrace"):
                retrace_tags.append({
                    "family": fam,
                    "recompile_s": rec.get("recompile_s"),
                    "compile_cache": rec.get("compile_cache")})
    prewarmed_ok = bool(retrace_tags) and all(
        t["compile_cache"] in ("prewarmed", "hit")
        for t in retrace_tags)
    ratio = during_p99 / pre_p99 if pre_p99 > 0 else 0.0
    RESULT.update(
        resize_storm_flush_p99_pre_s=round(pre_p99, 4),
        resize_storm_flush_p99_during_s=round(during_p99, 4),
        resize_storm_flush_p99_post_s=round(post_p99, 4),
        resize_storm_capacity=f"{cap0}->{cap1}",
        resize_storm_doublings=doublings,
        resize_storm_retrace_tags=retrace_tags,
        resize_storm_prewarmed_ok=prewarmed_ok,
        resize_storm_flat=bool(pre_p99 and ratio <= 1.25))
    log(f"resize_storm: capacity {cap0}->{cap1} ({doublings} doublings), "
        f"p99 pre={pre_p99:.3f}s during={during_p99:.3f}s "
        f"post={post_p99:.3f}s ratio={ratio:.2f} "
        f"prewarmed_ok={prewarmed_ok}")
    return ratio


def run_scenario_reshard(duration_s: float = 0.0):
    """PR-18 acceptance gate: flush-latency FLATNESS through a live
    elastic reshard. The mesh needs its virtual device count fixed
    before the backend initializes (same constraint as the mesh
    ladder), so the measurement runs in a fresh reshard-worker
    subprocess; this parent relays its result fields."""
    import subprocess

    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    cmd = [sys.executable, os.path.abspath(__file__),
           "--scenario", "reshard-worker",
           "--duration", str(duration_s), "--deadline", "0"]
    budget = time_left()
    try:
        proc = subprocess.run(
            cmd, env=env, capture_output=True,
            timeout=None if budget == float("inf")
            else max(120, budget - 5))
        line = proc.stdout.decode().strip().splitlines()[-1]
        obj = json.loads(line)
    except Exception as e:
        RESULT["reshard_error"] = f"{type(e).__name__}: {e}"
        log(f"reshard worker failed: {e}")
        return 0.0
    for key, val in obj.items():
        if key.startswith("reshard_"):
            RESULT[key] = val
    ratio = float(obj.get("value") or 0.0)
    log(f"reshard: p99 ratio={ratio:.2f} "
        f"cutover={obj.get('reshard_cutover_s')}s "
        f"segments={obj.get('reshard_segments')} "
        f"flat={obj.get('reshard_flat')}")
    return ratio


def run_scenario_reshard_worker(duration_s: float = 0.0,
                                interval_s: float = 1.5,
                                intervals: int = 3):
    """One fresh-process reshard measurement: a live ticker mesh server
    (2 shards) under steady mixed UDP load takes a flush-p99 baseline,
    then a live 2 -> 3 elastic reshard (parallel/reshard.py) runs —
    plan, prewarm, WAL-backed cutover — while the load keeps flowing,
    then the baseline runs again. Reports flush p99 before/during/after
    (the acceptance, mirroring resize_storm: during <= 1.25x pre — the
    plan/prewarm phases must not crater the flush loop; the cutover
    itself happens under the flush lock, between ticks), plus the
    cutover duration and WAL segment count. Returns the during/pre p99
    ratio."""
    import socket
    import tempfile
    import threading

    from veneur_tpu.config import Config
    from veneur_tpu.core.server import Server
    from veneur_tpu.sinks.blackhole import BlackholeMetricSink

    cfg = Config()
    cfg.interval = interval_s
    cfg.statsd_listen_addresses = ["udp://127.0.0.1:0"]
    cfg.tpu.shards = 2
    cfg.reshard_spool_dir = tempfile.mkdtemp(prefix="bench-reshard-")
    cfg.tpu.counter_capacity = 2048
    cfg.tpu.gauge_capacity = 2048
    cfg.tpu.histo_capacity = 2048
    cfg.tpu.set_capacity = 1024
    cfg.tpu.llhist_capacity = 1024
    cfg.tpu.batch_cap = BATCH_CAP[0]
    cfg.apply_defaults()
    server = Server(cfg, extra_metric_sinks=[BlackholeMetricSink()])
    server.start()
    if server.store.shard_plane is None:
        RESULT["reshard_error"] = "no serving plane (device count)"
        server.shutdown()
        return 0.0
    host, port = server.local_addr("udp")

    flush_times = []
    orig = server._flush_locked

    def timed():
        t0 = time.perf_counter()
        orig()
        flush_times.append(time.perf_counter() - t0)

    server._flush_locked = timed

    stop = threading.Event()

    def sender():
        # steady mixed load, keys well below capacity (no resize rungs
        # — this scenario isolates the reshard's cost)
        sock = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
        packets = []
        for i in range(1000):
            packets.append(b"bench.rs.c.%d:1|c" % i)
            packets.append(b"bench.rs.t.%d:%d|ms" % (i, i % 97))
        i = 0
        while not stop.is_set():
            sock.sendto(packets[i % len(packets)], (host, port))
            i += 1
            if i % 50 == 0:
                time.sleep(0.01)  # ~5k pps offered: steady load, not a
                # saturation probe — the scenario isolates the
                # reshard's cost, so the baseline must have headroom

    feeder = threading.Thread(target=sender, daemon=True)
    feeder.start()

    def p99_of(times):
        times = sorted(times) or [0.0]
        return times[min(len(times) - 1, int(len(times) * 0.99))]

    def settle(label, min_flushes=intervals):
        flush_times.clear()
        deadline = time.perf_counter() + interval_s * (min_flushes + 3)
        while len(flush_times) < min_flushes and \
                time.perf_counter() < deadline and time_left() > 10:
            time.sleep(0.1)
        p99 = p99_of(flush_times)
        log(f"reshard {label}: {len(flush_times)} flushes, "
            f"p99={p99:.3f}s")
        return p99

    try:
        if server._warmup_thread is not None:
            server._warmup_thread.join(timeout=120)
        settle("warmup")  # compile the steady-state kernels off-window
        pre_p99 = settle("pre")
        flush_times.clear()
        ctl = server.reshard
        ctl.begin(shards=3, deadline_s=600.0)
        deadline = time.perf_counter() + 600
        while (ctl.state != "idle" or ctl.epoch == 0) and \
                time.perf_counter() < deadline and time_left() > 10:
            time.sleep(0.1)
        while len(flush_times) < intervals and time_left() > 10:
            time.sleep(0.1)
        during_p99 = p99_of(flush_times)
        log(f"reshard during: {len(flush_times)} flushes, "
            f"p99={during_p99:.3f}s (cutover "
            f"{ctl.last_cutover_seconds:.3f}s)")
        post_p99 = settle("post")
    finally:
        stop.set()
        feeder.join(timeout=5)
        server.config.flush_on_shutdown = False
        server.shutdown()

    ratio = during_p99 / pre_p99 if pre_p99 > 0 else 0.0
    RESULT.update(
        reshard_flush_p99_pre_s=round(pre_p99, 4),
        reshard_flush_p99_during_s=round(during_p99, 4),
        reshard_flush_p99_post_s=round(post_p99, 4),
        reshard_shards="2->3",
        reshard_epoch=ctl.epoch,
        reshard_cutover_s=round(ctl.last_cutover_seconds, 4),
        reshard_segments=ctl.segments_written,
        reshard_last_error=ctl.last_error,
        reshard_flat=bool(pre_p99 and ratio <= 1.25))
    log(f"reshard: 2->3, p99 pre={pre_p99:.3f}s during={during_p99:.3f}s "
        f"post={post_p99:.3f}s ratio={ratio:.2f} "
        f"cutover={ctl.last_cutover_seconds:.3f}s")
    return ratio


def run_scenario_query(duration_s: float, num_keys: int = 2000):
    """Live query plane read-path (PR 16): query throughput and read
    latency under sustained ingest at 1, 8, and 64 concurrent readers.
    Readers rotate the four dashboard kinds (quantile / count /
    cardinality / value) against a live server while an ingest thread
    keeps the pending fold busy — every query takes a consistent
    read-only capture and syncs on the shared flush executor, so the
    rungs measure real capture/readout contention, not a cached value.
    Headline: reads/s at 8 readers; per-rung reads/s and p50/p99 read
    latency ride along in the result record."""
    from veneur_tpu.core.query import QuerySpec

    server = _mk_server(num_keys, families=4, interval=3600.0)
    packets, _samples = make_packets(num_keys)
    datagrams = make_datagrams(packets)
    server.handle_packet_batch(datagrams)
    server.store.apply_all_pending()

    specs = [
        QuerySpec.build("bench.timer.2", "quantile", q=0.99),
        QuerySpec.build("bench.counter.0", "count"),
        QuerySpec.build("bench.set.3", "cardinality"),
        QuerySpec.build("bench.gauge.1", "value"),
    ]
    # first pass compiles/warms every family's capture + readout path
    for s in specs:
        server.query_plane.query(s)

    stop_ingest = threading.Event()

    def ingest():
        while not stop_ingest.is_set():
            server.handle_packet_batch(datagrams)
            time.sleep(0.001)

    def reader(lat: list, stop_rung: threading.Event):
        i = 0
        while not stop_rung.is_set():
            t0 = time.perf_counter()
            server.query_plane.query(specs[i % len(specs)])
            lat.append(time.perf_counter() - t0)
            i += 1

    rung_s = max(2.0, duration_s / 3)
    rungs = {}
    feeder = threading.Thread(target=ingest, daemon=True)
    feeder.start()
    try:
        for readers in (1, 8, 64):
            if time_left() < rung_s + 10:
                log(f"query rung {readers} skipped: "
                    f"{time_left():.0f}s left")
                break
            stop_rung = threading.Event()
            lats = [[] for _ in range(readers)]
            threads = [threading.Thread(target=reader,
                                        args=(lats[i], stop_rung),
                                        daemon=True)
                       for i in range(readers)]
            t0 = time.perf_counter()
            for t in threads:
                t.start()
            time.sleep(rung_s)
            stop_rung.set()
            for t in threads:
                t.join(timeout=30)
            elapsed = time.perf_counter() - t0
            merged = sorted(x for l in lats for x in l)
            n = len(merged)
            rungs[readers] = {
                "reads_per_sec": round(n / elapsed, 1),
                "read_p50_ms": round(merged[n // 2] * 1e3, 3) if n else None,
                "read_p99_ms": round(merged[min(n - 1, int(n * 0.99))]
                                     * 1e3, 3) if n else None,
            }
            log(f"query rung {readers} readers: "
                f"{rungs[readers]['reads_per_sec']}/s "
                f"p50={rungs[readers]['read_p50_ms']}ms "
                f"p99={rungs[readers]['read_p99_ms']}ms")
    finally:
        stop_ingest.set()
        feeder.join(timeout=10)
        server.config.flush_on_shutdown = False
        server.shutdown()

    for readers, r in rungs.items():
        RESULT[f"query_reads_per_sec_{readers}"] = r["reads_per_sec"]
        RESULT[f"query_read_p50_ms_{readers}"] = r["read_p50_ms"]
        RESULT[f"query_read_p99_ms_{readers}"] = r["read_p99_ms"]
    headline = rungs.get(8) or (rungs[max(rungs)] if rungs else None)
    return headline["reads_per_sec"] if headline else 0.0


def run_scenario_egress(duration_s: float, num_keys: int = 100_000):
    """Columnar egress encode throughput per wire format off a synthetic
    100k-key FlushBatch (no UDP, no HTTP — pure encode). The first
    encode per format warms the fragment caches (cold cost is one flush
    by design); the timed loop measures the steady-state regime. The
    headline `egress_encode_rate` is the SLOWEST format's lines/s — the
    bound a multi-sink deployment actually feels. Returns
    (headline, per_format_rates)."""
    import numpy as np
    from veneur_tpu.core.columnstore import RowMeta
    from veneur_tpu.core.egress import (
        CortexColumnarEncoder, DatadogColumnarEncoder,
        PrometheusColumnarRenderer,
    )
    from veneur_tpu.core.flusher import (
        BucketSection, FlushBatch, FlushSection, ForwardableState,
    )
    from veneur_tpu.forward.convert import forwardable_to_wire
    from veneur_tpu.ops import llhist_ref
    from veneur_tpu.samplers.metrics import MetricScope, MetricType
    from veneur_tpu.sinks.cortex import CortexMetricSink
    from veneur_tpu.sinks.datadog import DatadogMetricSink

    rng = np.random.default_rng(7)
    n_half = num_keys // 2

    def section(prefix, n, mtype):
        names = np.empty(n, object)
        tags = np.empty(n, object)
        for i in range(n):
            names[i] = f"bench.{prefix}.{i}"
            tags[i] = [f"env:prod", f"shard:{i % 64}"]
        vals = rng.uniform(0.5, 5000.0, n)
        return FlushSection(names, vals, tags, mtype)

    sec_c = section("c", n_half, MetricType.COUNTER)
    sec_g = section("g", num_keys - n_half, MetricType.GAUGE)
    # llhist buckets: 2% of keys are histograms, 16 occupied bins each
    # — the CSR entries the encoders splice `le:` rows from
    n_hist = max(num_keys // 50, 1)
    bins = len(llhist_ref.UPPER_SORTED)
    occ = np.stack([rng.choice(bins, size=16, replace=False)
                    for _ in range(n_hist)])
    indptr, le_idx, cum, total = llhist_ref.cumulative_entries(
        np.repeat(np.arange(n_hist), 16), occ.ravel(),
        rng.integers(1, 50, size=n_hist * 16), n_hist)
    bnames = np.empty(n_hist, object)
    btags = np.empty(n_hist, object)
    for i in range(n_hist):
        bnames[i] = f"bench.ll.{i}.bucket"
        btags[i] = [f"env:prod", f"shard:{i % 64}"]
    bucket = BucketSection(bnames, btags, indptr, le_idx,
                           cum.astype(np.float64), total.astype(np.float64))
    batch = FlushBatch(int(time.time()), [sec_c, sec_g], [], [bucket])
    lines = len(batch)

    # forward wire: same key population as mergeable state — scalar
    # frames hand-packed, llhist registers through the native encoder
    fwd = ForwardableState()
    for i in range(n_half):
        meta = RowMeta(f"bench.c.{i}", sec_c.tags[i],
                       ",".join(sec_c.tags[i]), 0, MetricScope.MIXED,
                       "counter")
        fwd.counters.append((meta, float(i + 1)))
    for i in range(num_keys - n_half):
        meta = RowMeta(f"bench.g.{i}", sec_g.tags[i],
                       ",".join(sec_g.tags[i]), 0, MetricScope.MIXED,
                       "gauge")
        fwd.gauges.append((meta, float(sec_g.values[i])))
    ll_bins = np.zeros(bins, np.int64)
    ll_bins[::300] = 7
    for i in range(n_hist):
        meta = RowMeta(f"bench.ll.{i}", btags[i], ",".join(btags[i]),
                       0, MetricScope.MIXED, "timer")
        fwd.llhists.append((meta, ll_bins))

    dd = DatadogMetricSink("datadog", "key", "https://dd.invalid",
                           "bench", 10.0)
    cx = CortexMetricSink("cortex", "http://cx.invalid/api", "bench")
    encoders = {
        "datadog": (DatadogColumnarEncoder(dd).encode, lines),
        "prometheus": (PrometheusColumnarRenderer().render, lines),
        "cortex": (CortexColumnarEncoder(cx).encode, lines),
        "metricpb": (forwardable_to_wire, len(fwd)),
    }
    budget = max(duration_s / len(encoders), 1.0)
    rates = {}
    for fmt, (encode, units) in encoders.items():
        arg = fwd if fmt == "metricpb" else batch
        encode(arg)  # warm the fragment caches / pb frames
        t0 = time.perf_counter()
        done = 0
        while time.perf_counter() - t0 < budget:
            encode(arg)
            done += units
        rates[fmt] = round(done / (time.perf_counter() - t0), 1)
        log(f"egress encode {fmt}: {rates[fmt]:,.0f} lines/s")
    return min(rates.values()), rates


def run_scenario_hll(duration_s: float, num_keys: int = 10_000,
                     cardinality: int = 100):
    """BASELINE config 3: mixed keys at tag cardinality 100 — HLL stress
    (each base key fans out to `cardinality` distinct tag combinations)."""
    import numpy as np
    rng = np.random.default_rng(3)
    base = max(1, num_keys // cardinality)
    packets = []
    for i in range(base):
        for t in range(cardinality):
            packets.append(
                b"bench.hll.%d:user%d|s|#card:%d,env:bench"
                % (i, rng.integers(0, 100_000), t))
    return _run_udp_scenario(duration_s, packets, len(packets),
                             num_keys * 2)


SCENARIOS = ["default", "mixed", "single", "counter", "timers", "hll",
             "llhist", "forward", "ssf", "device", "sustained", "tdigest",
             "mesh", "mesh-worker", "resize_storm", "query",
             "reshard", "reshard-worker", "egress"]


def clamp_keys(keys: int, on_tpu: bool) -> int:
    """Key-regime policy for the heavy scenarios: the full 100k-key
    north-star shape on TPU, a tractable 10k on the CPU fallback."""
    return max(keys, 100_000) if on_tpu else min(keys, 10_000)


def run_one(scenario: str, duration: float, keys: int, on_tpu: bool = True):
    """Returns (metric_name, rate, extra_fields)."""
    extra = {}
    metric = METRIC_NAMES.get(scenario, METRIC_NAMES["mixed"])
    if scenario == "mixed":
        rate, scaling = run_pipeline_mt(duration, keys, scale_senders=True)
        extra["threads"] = scaling
    elif scenario == "single":
        metric = METRIC_NAMES["mixed"]
        rate, _ = run_pipeline(duration, keys)
    elif scenario == "counter":
        rate = run_scenario_counter(duration)
    elif scenario == "timers":
        rate = run_scenario_timers(duration, min(keys, 1000))
    elif scenario == "hll":
        rate = run_scenario_hll(duration, keys)
    elif scenario == "llhist":
        rate = run_scenario_llhist(duration, min(keys, 1000))
    elif scenario == "forward":
        rate = run_scenario_forward(duration, keys)
    elif scenario == "egress":
        # pure host-side encode — no device in the loop, so the 100k
        # north-star snapshot shape holds on the CPU fallback too
        rate, per_format = run_scenario_egress(duration,
                                               max(keys, 100_000))
        extra["egress_encode_rates"] = per_format
        # the egress acceptance pins BASELINE configs 1 and 4: re-run
        # them alongside so one record carries all three measurements
        if time_left() >= 60:
            extra["counter_samples_per_sec"] = round(
                run_scenario_counter(min(duration, 6.0)), 1)
        if time_left() >= 90:
            extra["forwarded_digest_keys_per_sec"] = round(
                run_scenario_forward(min(duration, 6.0), 50_000), 1)
    elif scenario == "device":
        if on_tpu and os.environ.get("BENCH_DEVICE_SWEEP") == "1":
            # opt-in batch-size ladder (manual captures only: each shape
            # is a fresh compile, too slow for the driver's budget). The
            # 64k default can be dispatch-overhead-bound — the sweep
            # shows where the knee really is.
            sweep = {}
            rate, dflush = 0.0, None
            for b in (65_536, 262_144, 1_048_576):
                if time_left() < 30:
                    log("device sweep truncated by deadline")
                    break
                try:
                    r, fl = run_scenario_device(
                        max(2.0, duration / 2), clamp_keys(keys, on_tpu),
                        batch=b)
                except Exception as e:  # e.g. the largest shape OOMs —
                    # keep the measurements already collected
                    sweep[str(b)] = f"error: {type(e).__name__}: {e}"
                    continue
                sweep[str(b)] = round(r, 1)
                if r > rate:
                    rate, dflush = r, fl
            if rate == 0.0 and time_left() >= 30:
                log("device sweep pre-empted entirely; single fallback run")
                rate, dflush = run_scenario_device(
                    2.0, clamp_keys(keys, on_tpu))
            elif rate == 0.0:
                log(f"device fallback skipped: {time_left():.0f}s left")
            extra["device_batch_sweep"] = sweep
        else:
            rate, dflush = run_scenario_device(
                duration, clamp_keys(keys, on_tpu))
        extra["flush_latency_s"] = round(dflush, 4) if dflush else None
    elif scenario == "sustained":
        rate, extra = run_scenario_sustained(
            clamp_keys(keys, on_tpu), interval_s=10.0 if on_tpu else 2.0)
    elif scenario == "tdigest":
        rate, extra = run_scenario_tdigest(duration, clamp_keys(keys, on_tpu))
    elif scenario == "mesh":
        rate = run_scenario_mesh(duration, min(keys, 2000))
    elif scenario == "mesh-worker":
        rate = run_scenario_mesh_worker(duration, min(keys, 2000))
    elif scenario == "resize_storm":
        rate = run_scenario_resize_storm(duration)
    elif scenario == "reshard":
        rate = run_scenario_reshard(duration)
    elif scenario == "reshard-worker":
        rate = run_scenario_reshard_worker(duration)
    elif scenario == "query":
        rate = run_scenario_query(duration, min(keys, 2000))
    else:
        rate = run_scenario_ssf(duration, keys)
    return metric, rate, extra


def run_default(args, on_tpu: bool) -> None:
    """The driver's default artifact: one rig runs the mixed offered-load
    ladder and the sustained flush-latency gate at the production shape
    (100k keys / 10s interval on TPU — BASELINE.md's north star; budget-
    adaptive on the CPU fallback), then the device-kernel stage and a
    short run of each of the five BASELINE configs."""
    from veneur_tpu import native

    if on_tpu:
        # >= 5 flushes when the budget allows: p50/p99 quoted off 2-3
        # samples is not a latency claim (VERDICT r04); the time_left
        # guard below still protects the device/config stages
        keys, interval_s = 100_000, 10.0
        intervals = 5 if time_left() > 150 else 3
    elif time_left() > 130:
        keys, interval_s, intervals = 50_000, 5.0, 3
    else:  # late start (probe retries ate the budget): keep stages landing
        keys, interval_s, intervals = 10_000, 2.0, 2

    log(f"stage 1/3: pipeline rig ({keys} keys, {interval_s:g}s interval)")
    rig = None
    try:
        if native.available():
            packets, samples = make_packets(keys)
            datagrams = make_datagrams(packets)
            rig = UdpRig(keys, datagrams, samples / len(datagrams),
                         families=4, interval=interval_s,
                         synchronize_with_interval=False,
                         flush_async=True)
            log(f"pipeline: warmup (intern {keys} keys + compile)")
            rig.warmup()
            log("pipeline: warmup done; ticker live")
        rate, sweep = run_pipeline_mt(args.duration, keys, rig=rig,
                                      scale_senders=True)
        RESULT.update(metric=METRIC_NAMES["mixed"], value=round(rate, 1),
                      unit="samples/s", offered_sweep=sweep,
                      pipeline_keys=keys)
        if time_left() < intervals * interval_s + 25:
            log(f"sustained skipped: {time_left():.0f}s of budget left")
            RESULT["sustained_skipped"] = True
        else:
            try:
                srate, sextra = run_scenario_sustained(
                    keys, interval_s=interval_s, intervals=intervals,
                    rig=rig, offered=max(rate * 0.85, 2e5))
                RESULT["sustained_samples_per_sec"] = round(srate, 1)
                RESULT.update(sextra)
            except Exception as e:
                traceback.print_exc()
                RESULT["sustained_error"] = f"{type(e).__name__}: {e}"
    finally:
        if rig is not None:
            rig.close()

    log("stage 2/3: device-only kernel throughput")
    if time_left() < 25:
        log(f"device stage skipped: {time_left():.0f}s of budget left")
        RESULT["device_skipped"] = True
    else:
        try:
            _m, drate, dextra = run_one(
                "device", 3.0 if on_tpu else 2.0, args.keys, on_tpu)
            RESULT["device_samples_per_sec"] = round(drate, 1)
            RESULT["device_flush_latency_s"] = dextra.get("flush_latency_s")
            if "device_batch_sweep" in dextra:
                RESULT["device_batch_sweep"] = dextra["device_batch_sweep"]
        except Exception as e:
            traceback.print_exc()
            RESULT["device_error"] = f"{type(e).__name__}: {e}"

    # the five BASELINE configs, cheapest first so a tight budget still
    # lands most of the table (BASELINE.json `configs`)
    log("stage 3/3: BASELINE config suite")
    configs = {}
    RESULT["configs"] = configs
    config_runs = [
        ("counter", lambda d: run_scenario_counter(d), 20),
        ("timers", lambda d: run_scenario_timers(d, 1000), 20),
        ("hll", lambda d: run_scenario_hll(d, 10_000), 25),
        ("llhist", lambda d: run_scenario_llhist(d, 1000), 25),
        ("ssf", lambda d: run_scenario_ssf(d, 10_000), 30),
        ("forward", lambda d: run_scenario_forward(
            d, 50_000 if on_tpu else 10_000), 35),
    ]
    for name, fn, reserve in config_runs:
        if time_left() < reserve:
            configs[name] = {"skipped": True}
            log(f"config {name} skipped: {time_left():.0f}s left")
            continue
        dur = min(4.0, max(2.0, (time_left() - reserve + 15) / 6))
        try:
            t0 = time.perf_counter()
            r = fn(dur)
            configs[name] = {
                "samples_per_sec": round(r, 1),
                "wall_s": round(time.perf_counter() - t0, 1)}
            if name == "ssf" and LAST_SSF_STATS:
                configs[name].update(LAST_SSF_STATS)
            log(f"config {name}: {r:,.0f} samples/s")
        except Exception as e:
            traceback.print_exc()
            configs[name] = {"error": f"{type(e).__name__}: {e}"}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--duration", type=float, default=8.0)
    ap.add_argument("--keys", type=int, default=10_000)
    ap.add_argument("--scenario", default="default", choices=SCENARIOS,
                    help="default = mixed (multi-threaded headline) + "
                         "sustained (live-ticker flush-latency gate); the "
                         "rest mirror the BASELINE.json config suite")
    ap.add_argument("--deadline", type=float,
                    default=float(os.environ.get("BENCH_DEADLINE_S", 170)),
                    help="hard wall-clock budget; partial JSON on expiry")
    args = ap.parse_args()

    if args.deadline > 0:
        arm_deadline(args.deadline)

    RESULT["metric"] = METRIC_NAMES.get(
        "mixed" if args.scenario == "default" else args.scenario,
        METRIC_NAMES["mixed"])
    try:
        platform = initialize_backend()
    except Exception as e:
        RESULT["error"] = f"backend init failed: {type(e).__name__}: {e}"
        finalize()
        return 1
    RESULT["platform"] = platform
    RESULT["host_cpus"] = os.cpu_count()
    on_tpu = not platform.startswith("cpu")
    set_batch_cap_for(platform)

    try:
        if args.scenario == "default":
            run_default(args, on_tpu)
        else:
            metric, rate, extra = run_one(
                args.scenario, args.duration, args.keys, on_tpu)
            RESULT.update(metric=metric, value=round(rate, 1),
                          unit="samples/s", **extra)
    except Exception as e:
        traceback.print_exc()
        RESULT["error"] = f"{type(e).__name__}: {e}"
        finalize()
        return 1

    finalize()
    return 0


if __name__ == "__main__":
    rc = main()
    # hard exit: daemon load threads and accelerator-client teardown can
    # abort the interpreter after the JSON line is already out; the
    # driver only needs the line and the return code
    sys.stdout.flush()
    sys.stderr.flush()
    os._exit(rc)
