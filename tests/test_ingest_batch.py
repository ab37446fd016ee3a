"""Batch ingest pipeline tests (the `ingest` marker): llhist wire-type
parity on a fuzz corpus (native C++ and numpy fallback vs the scalar
parser), batch-granular admission/shedding with exact per-class counts
under a strict flow ledger, SPSC ring backpressure (a full ring blocks
the reader — no silent drop), supervisor coverage of a wedged pump
dispatcher, kernel-drop inode watching after the listener rebuild, and
the ingest_ring observability surface.
"""

from __future__ import annotations

import os
import socket
import time

import numpy as np
import pytest

from veneur_tpu import native
from veneur_tpu.config import Config
from veneur_tpu.core.server import Server
from veneur_tpu.sinks.channel import ChannelMetricSink

pytestmark = pytest.mark.ingest

needs_native = pytest.mark.skipif(
    not native.available(),
    reason=f"native parser unavailable: {native.unavailable_reason()}")


def make_server(disable_native: bool = False, **overrides):
    cfg = Config()
    cfg.interval = 3600.0
    cfg.tpu.disable_native_parser = disable_native
    for key, value in overrides.items():
        setattr(cfg, key, value)
    cfg.apply_defaults()
    ch = ChannelMetricSink()
    return Server(cfg, extra_metric_sinks=[ch]), ch


def llhist_state(server) -> np.ndarray:
    server.store.llhists.apply_pending()
    return np.asarray(server.store.llhists.state)


# ---------------------------------------------------------------------------
# llhist wire type in the batch decoders


def _llhist_fuzz_corpus():
    """Multi-value `l` lines spanning the whole bin window plus both
    clamp edges, bin-boundary magnitudes, negatives, rates, and junk —
    the corpus that pins the C++ binning against llhist_ref."""
    rng = np.random.default_rng(1234)
    lines = []
    # random magnitudes across (and beyond) the representable window
    mags = 10.0 ** rng.uniform(-12, 18, 120)
    signs = rng.choice([-1.0, 1.0], 120)
    vals = mags * signs
    for i in range(0, 120, 4):
        chunk = b":".join(b"%r" % v for v in vals[i:i + 4])
        lines.append(b"fz.%d:%s|l" % (i % 7, chunk))
    # exact bin edges: m * 10^(e-1) and the window/clamp boundaries
    edges = [1e-9, 9.9e-9, 1e16, 9.9e15, 1.0, 10.0, 99.0, 0.0, -0.0,
             1e-10, -1e17, 5.5, -5.5, 2.5e-5, 12.0, 12.0000001]
    for i, v in enumerate(edges):
        lines.append(b"edge.%d:%r|l" % (i % 3, v))
    # rates (integral and rounding-edge weights) + multi-value
    lines.append(b"rated:3.7:42|l|@0.5")
    lines.append(b"rated2:3.7|l|@0.4")    # 1/0.4 = 2.5 -> banker's 2
    lines.append(b"rated3:1000|l|@0.125")
    # absurd-but-valid rate: 1/1e-10 saturates at INT32_MAX in every
    # decoder (scalar, numpy, C++) instead of wrapping/raising
    lines.append(b"rated4:7|l|@0.0000000001")
    # slow-path material: junk values, NaN/Inf, unknown-but-llhist
    lines.append(b"fz.0:nan|l")
    lines.append(b"fz.0:inf|l")
    lines.append(b"fz.0:1_0|l")
    lines.append(b"fz.0:|l")
    lines.append(b"fz.0:1:|l")
    lines.append(b"fz.0::1|l")
    return lines


class TestLLHistWireType:
    def _run_batch(self, disable_native: bool):
        """Corpus through the batch path (native or numpy columnar):
        pass 1 interns via the slow path, passes 2-3 ride the columns."""
        server, ch = make_server(disable_native)
        try:
            lines = _llhist_fuzz_corpus()
            for _ in range(3):
                server.handle_packet_batch(lines)
            ing = server._ingester or server._py_ingester
            assert ing.interned_keys > 0  # fast path actually engaged
            return (llhist_state(server).copy(),
                    server.store.llhists.samples_total,
                    server.store.llhists.clamped_total,
                    dict(server.stats))
        finally:
            server.shutdown()

    def _run_scalar(self):
        """Same corpus through the per-packet scalar parser path."""
        server, ch = make_server(disable_native=True)
        try:
            lines = _llhist_fuzz_corpus()
            for _ in range(3):
                for line in lines:
                    server.handle_packet_buffer(line)
            return (llhist_state(server).copy(),
                    server.store.llhists.samples_total,
                    server.store.llhists.clamped_total,
                    dict(server.stats))
        finally:
            server.shutdown()

    @needs_native
    def test_native_binning_matches_scalar_parser(self):
        state_n, samples_n, clamped_n, stats_n = self._run_batch(False)
        state_s, samples_s, clamped_s, stats_s = self._run_scalar()
        assert np.array_equal(state_n, state_s)  # registers bit-identical
        assert samples_n == samples_s
        assert clamped_n == clamped_s
        assert stats_n["parse_errors"] == stats_s["parse_errors"]

    def test_numpy_fallback_matches_scalar_parser(self):
        state_p, samples_p, clamped_p, stats_p = self._run_batch(True)
        state_s, samples_s, clamped_s, stats_s = self._run_scalar()
        assert np.array_equal(state_p, state_s)
        assert samples_p == samples_s
        assert clamped_p == clamped_s
        assert stats_p["parse_errors"] == stats_s["parse_errors"]

    @needs_native
    def test_native_and_fallback_agree(self):
        state_n, samples_n, clamped_n, _ = self._run_batch(False)
        state_p, samples_p, clamped_p, _ = self._run_batch(True)
        assert np.array_equal(state_n, state_p)
        assert (samples_n, clamped_n) == (samples_p, clamped_p)


# ---------------------------------------------------------------------------
# numpy columnar fallback: full-grammar parity with the scalar path


FULL_CORPUS = [
    b"c1:5|c|#a:b", b"c1:2|c|@0.5|#a:b", b"g1:2.5|g", b"g1:7|g",
    b"t1:1:2:3:4|ms|@0.5|#x:y", b"h1:0.25|h", b"d1:9|d",
    b"s1:u1|s\ns1:u2|s\ns1:u1|s", b"ll1:5:50:500|l",
    b"bad packet", b"nopipe:1", b"novalue|c", b":1|c",
    b"x:|c", b"x:1:|c", b"x::1|c",
    b"weird:1e999|c", b"tiny:1e-999|g", b"neg:-12.5|g", b"plus:+3|c",
    b"exp:2.5e2|ms", b"dot:.5|g", b"dotted:5.|g",
    b"under:1_0|c", b"space: 1|c", b"nan:nan|g", b"inf:inf|g",
    b"hex:0x10|c", b"_sc|check|9", b"_e{2,2}:ab|cd|t:error",
    b"setnonascii:caf\xc3\xa9|s", b"s1:\xff\xfe|s",
    b"multi:1:2:3|c|#m:n", b"glob:1|c|#veneurglobalonly",
]


class TestNumpyFallbackParity:
    def test_corpus_matches_scalar_path(self):
        """The numpy columnar decoder must be observably identical to
        the per-packet scalar path across the whole grammar."""
        outs = []
        for batched in (True, False):
            server, ch = make_server(disable_native=True)
            try:
                for _ in range(2):
                    if batched:
                        server.handle_packet_batch(FULL_CORPUS)
                    else:
                        for dgram in FULL_CORPUS:
                            server.handle_packet_buffer(dgram)
                server.flush()
                rows = sorted(
                    (m.name, m.type.name, round(float(m.value), 4),
                     tuple(m.tags))
                    for m in ch.wait_flush())
                stats = dict(server.stats)
                stats.pop("batches_dispatched")  # batch-path only
                outs.append((rows, stats))
            finally:
                server.shutdown()
        assert outs[0][0] == outs[1][0]
        assert outs[0][1] == outs[1][1]

    def test_decoder_interns_after_slow_path(self):
        server, _ch = make_server(disable_native=True)
        try:
            assert server._py_ingester is not None
            server.handle_packet_batch([b"pyk:1|c", b"pyl:2|l"])
            assert server._py_ingester.interned_keys >= 2
            # second pass rides the columns: no new slow-path registers
            before = dict(server._py_ingester.decoder.table)
            server.handle_packet_batch([b"pyk:1|c", b"pyl:2|l"])
            assert dict(server._py_ingester.decoder.table) == before
        finally:
            server.shutdown()


# ---------------------------------------------------------------------------
# batch-granular admission + exact per-class shed accounting


class TestBatchShedLedger:
    def test_shed_books_exact_sample_counts_at_30pct(self):
        """The 30%-shed ledger drill: 3 of 10 batches rejected; the
        shed table must book the exact per-class sample counts from the
        batches' type-code columns, and the strict flow ledger must
        close the interval with zero unexplained imbalance."""
        server, _ch = make_server(disable_native=False,
                                  ledger_strict=True)
        try:
            ing = server._ingester or server._py_ingester
            # each batch: 4 counter + 1 gauge + 3 histo + 2 llhist + 1 set
            batch = b"\n".join([
                b"bc:1:2:3:4|c", b"bg:7|g", b"bh:1:2:3|ms",
                b"bl:5:50|l", b"bs:member|s"])
            ing.ingest_buffer(batch)  # intern pass (slow path, admitted)
            for i in range(10):
                ing.ingest_buffer(batch, shed_nonessential=(i < 3))
            shed = server.overload.shed_snapshot()
            # histo(3) + llhist(2) per rejected batch; set(1) each
            assert shed.get("histogram|rate_limit") == 3 * (3 + 2)
            assert shed.get("set|rate_limit") == 3 * 1
            # flush closes the ledger interval; strict mode raises on
            # any conservation imbalance
            server.flush()
            assert server.ledger.history_imbalances()[-1]["ingest"] == 0.0
        finally:
            server.shutdown()

    def test_over_limit_batches_keep_counters_end_to_end(self):
        """Token-bucket batch admission end to end: counter deltas from
        over-limit batches still land; histogram/llhist columns shed."""
        server, ch = make_server(disable_native=False,
                                 ingest_rate_limit_statsd=1.0,
                                 ingest_rate_limit_burst=1.0)
        try:
            for _ in range(4):
                server.handle_packet_batch([b"ol.c:1|c\nol.l:5|l"])
            server.flush()
            got = {m.name: m for m in ch.wait_flush()}
            assert got["ol.c"].value == 4.0  # every delta kept
            shed = server.overload.shed_snapshot()
            assert shed.get("histogram|rate_limit", 0) >= 1
        finally:
            server.shutdown()


# ---------------------------------------------------------------------------
# SPSC ring backpressure & crash coverage


@needs_native
class TestRingBackpressure:
    def test_full_ring_blocks_reader_no_silent_drop(self):
        """With no dispatcher draining, the reader fills its ring and
        BLOCKS (counted stalls); once draining starts, every line the
        readers accepted is accounted — nothing vanishes in-process."""
        eng = native.Engine()
        eng.register(b"rb|c", native.FAM_COUNTER, 0, 1.0)
        recv = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
        recv.setsockopt(socket.SOL_SOCKET, socket.SO_RCVBUF, 4 << 20)
        recv.bind(("127.0.0.1", 0))
        addr = recv.getsockname()
        send = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
        pump = native.Pump(eng, [recv.fileno()], max_dgram=2048,
                           max_len=2047, chunk_cap=512, ring_slots=3,
                           seal_age_ms=20)
        try:
            dgram = b"\n".join([b"rb:1|c"] * 100)
            n_dgrams = 60  # 6000 samples >> 3 rings * 512 samples
            for _ in range(n_dgrams):
                send.sendto(dgram, addr)
            deadline = time.time() + 5.0
            while time.time() < deadline and pump.stalls() == 0:
                time.sleep(0.05)
            assert pump.stalls() > 0  # ring filled; reader blocked
            depths, caps, sealed, stalls = pump.ring_stats()
            assert depths[0] == caps[0]  # ready ring is full
            assert sealed[0] >= caps[0]
            assert stalls[0] > 0
            # now drain: every accepted line must surface in a chunk
            got = 0
            idle = 0
            while got < n_dgrams * 100 and idle < 40:
                chunk = pump.next(100)
                if chunk is None:
                    idle += 1
                    continue
                idle = 0
                got += chunk.samples + len(chunk.unknown)
                pump.release(chunk)
            assert got == n_dgrams * 100
        finally:
            pump.stop()
            pump.close()
            recv.close()
            send.close()

    def test_dead_dispatcher_caught_by_supervisor(self):
        """A wedged pump dispatcher stops heartbeating; the PR-3
        supervisor flags the ingest-pump component."""
        from veneur_tpu.core.ingest import BatchIngester
        server, _ch = make_server(supervisor_deadline=0.4,
                                  statsd_listen_addresses=[
                                      "udp://127.0.0.1:0"])
        try:
            server.start()
            sup = server.overload.supervisor
            comps = [c for c in sup._beats if c.startswith("ingest-pump:")]
            assert comps  # dispatcher registered itself
            orig = BatchIngester._dispatch_one
            # wedge: the dispatcher loop re-resolves the method each
            # iteration, so the class patch takes effect immediately;
            # one call outlasts the deadline, so the next beat is late
            BatchIngester._dispatch_one = (
                lambda self, *a, **k: time.sleep(1.0) or False)
            try:
                deadline = time.time() + 5.0
                flagged = []
                while time.time() < deadline and not flagged:
                    time.sleep(0.2)
                    flagged = [c for c in sup.check()
                               if c.startswith("ingest-pump:")]
                    flagged += [c for c in sup.stalled_components()
                                if c.startswith("ingest-pump:")]
                assert flagged
            finally:
                BatchIngester._dispatch_one = orig
        finally:
            server.shutdown()

    def test_kernel_drop_monitor_watches_listener_inodes(self):
        """After the ring rebuild the kernel-drop monitor must still
        poll the pump's actual socket inodes (/proc/net/udp rows)."""
        server, _ch = make_server(
            statsd_listen_addresses=["udp://127.0.0.1:0"], num_readers=2)
        try:
            server.start()
            listener = server._listeners[0]
            want = {os.fstat(s.fileno()).st_ino for s in listener._socks}
            with server.overload.kernel_drops._lock:
                watched = set(server.overload.kernel_drops._watched)
            assert want <= watched
            server.overload.kernel_drops.poll()  # must not raise
        finally:
            server.shutdown()


@needs_native
class TestSwapIngestRace:
    """PR-15 generation-swap pin: the overlapped flush swaps a table's
    pending columns + device generation at the interval boundary while
    ingest threads keep hammering add_batch. A swap must never drop a
    pending chunk (every sample lands in exactly one interval) and the
    strict-ledger ingest identity must stay clean through the overlap."""

    def test_counter_swap_add_batch_hammer_conserves_every_sample(self):
        import threading

        from veneur_tpu.core.columnstore import CounterTable
        from veneur_tpu.samplers.parser import Parser

        table = CounterTable(capacity=256, batch_cap=64)
        table.family = "counter"
        n_keys = 32
        parser = Parser()
        for i in range(n_keys):  # intern the rows once, slow path
            parser.parse_metric_fast(b"hammer.%d:0|c" % i, table.add)
        table.apply_pending()
        table.snapshot_and_reset()  # discard the zero-sample warmup

        writers = 4
        rounds = 200
        wrote = [0] * writers

        def writer(w):
            rows = np.arange(n_keys, dtype=np.int32)
            vals = np.ones(n_keys, np.float32)
            rates = np.ones(n_keys, np.float32)
            for _ in range(rounds):
                table.add_batch(rows, vals, rates)
                wrote[w] += n_keys

        threads = [threading.Thread(target=writer, args=(w,))
                   for w in range(writers)]
        for t in threads:
            t.start()
        # hammer generation swaps (the overlapped flush's critical-path
        # half + background readout) against the live writers
        total_seen = 0.0
        while any(t.is_alive() for t in threads):
            snap = table.readout(table.swap_out())
            vals, _touched, _meta = table.snapshot_finish(snap)
            table.recycle(snap)
            total_seen += float(vals[:n_keys].sum())
        for t in threads:
            t.join()
        # final interval drains whatever the last swap raced past
        table.apply_pending()
        vals, _t, _m = table.snapshot_and_reset()
        total_seen += float(vals[:n_keys].sum())
        assert total_seen == float(sum(wrote))

    def test_server_flush_hammer_strict_ledger_clean(self):
        """Whole-pipeline hammer under ledger_strict: python-path
        ingest races the flush thread's swaps and readouts; counters
        conserve exactly across every delivered interval and no flush
        raises a conservation imbalance."""
        import threading

        server, ch = make_server(ledger_strict=True)
        try:
            writers = 3
            per_writer = 400
            keys = 16

            def writer(w):
                for i in range(per_writer):
                    server.handle_metric_packet(
                        b"flood.%d:1|c" % (i % keys))

            threads = [threading.Thread(target=writer, args=(w,))
                       for w in range(writers)]
            for t in threads:
                t.start()
            while any(t.is_alive() for t in threads):
                server.flush()  # strict ledger: raises on any leak
                time.sleep(0.01)
            for t in threads:
                t.join()
            server.store.apply_all_pending()
            server.flush()  # the tail interval, delivered this tick
            total = sum(m.value for m in ch.drain()
                        if m.name.startswith("flood."))
            assert total == float(writers * per_writer)
        finally:
            server.config.flush_on_shutdown = False
            server.shutdown()


class TestRingObservability:
    def test_ring_rows_and_latency_queues(self):
        server, _ch = make_server(
            statsd_listen_addresses=["udp://127.0.0.1:0"], num_readers=2)
        try:
            server.start()
            send = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
            addr = server.local_addr("udp")
            for _ in range(3):
                send.sendto(b"ring.obs:1|c", addr)
            deadline = time.time() + 5.0
            while time.time() < deadline and server.store.processed < 1:
                time.sleep(0.05)
            send.close()
            rows = {name for name, _k, _v, _t
                    in server._ring_telemetry_rows()}
            assert rows == {"ingest.ring.depth", "ingest.ring.capacity",
                            "ingest.ring.sealed_total",
                            "ingest.ring.stalls_total"}
            report = server.latency.report()
            ring_queues = [q for q in report["queues"]
                           if q.startswith("ingest_ring:")]
            assert len(ring_queues) == 2  # one per reader
            # dwell llhist observed at least one sealed chunk
            assert any(
                report["queues"][q].get("dwell", {}).get("count", 0) > 0
                for q in ring_queues)
        finally:
            server.shutdown()
