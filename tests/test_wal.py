"""Durable interval WAL & timestamp-faithful backfill replay tests:
interval-stamped segments, write-ahead-of-send ordering, exactly-once
crash replay via stable per-segment tokens, quarantine bounding and
accounting, the backfill plane's interval buckets and original-
timestamp emission, replay rate-limit isolation, and the in-process
crash drill the acceptance criteria pin (kill mid-flush, restart,
replay — zero counter loss, llhist registers bit-identical to an
unfaulted control, zero unexplained ledger imbalance under
ledger_strict)."""

import os
import shutil
import time

import numpy as np
import pytest

from veneur_tpu.config import Config
from veneur_tpu.forward.backfill import BackfillPlane
from veneur_tpu.forward.protos import metric_pb2
from veneur_tpu.forward.wire import (INTERVAL_KEY, IDEMPOTENCY_KEY,
                                     stamp_interval_wire)
from veneur_tpu.samplers.metrics import MetricType
from veneur_tpu.testing.forwardtest import ForwardTestServer
from veneur_tpu.util.spool import QUARANTINE_DIR, CarryoverSpool

pytestmark = pytest.mark.wal


def wait_until(fn, timeout=10.0, step=0.02):
    deadline = time.time() + timeout
    while time.time() < deadline:
        if fn():
            return True
        time.sleep(step)
    return False


def mkmetric(name, value=1, tags=(), interval=0):
    pbm = metric_pb2.Metric(name=name, type=metric_pb2.Counter,
                            scope=metric_pb2.Global)
    pbm.tags.extend(tags)
    pbm.counter.value = value
    if interval:
        pbm.interval = int(interval)
    return pbm


def mk_server(**kw):
    """The in-process Server pattern (no listeners, manual flush)."""
    from veneur_tpu.core.server import Server
    from veneur_tpu.sinks.channel import ChannelMetricSink

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
    for k, v in kw.items():
        setattr(cfg, k, v)
    cfg.apply_defaults()
    obs = ChannelMetricSink()
    return Server(cfg, extra_metric_sinks=[obs]), obs


class _LedgerSpy:
    """Minimal ledger double recording note() calls."""

    def __init__(self):
        self.notes = []

    def note(self, stage, n, key=""):
        self.notes.append((stage, n, key))


# -------------------------------------------------------------------------
# WAL segment format: interval stamps, restart survival
# -------------------------------------------------------------------------


class TestWalSegments:
    def test_interval_stamp_survives_restart(self, tmp_path):
        spool = CarryoverSpool(str(tmp_path))
        spool.append([b"m1"], interval_unix=1700000123.5)
        spool.append([b"m2"])  # unstamped legacy append still works
        seg = spool.oldest()
        assert seg.interval_unix == pytest.approx(1700000123.5)

        replayed = CarryoverSpool(str(tmp_path))
        assert replayed.replayed_total == 2
        assert replayed.oldest().interval_unix == \
            pytest.approx(1700000123.5)
        assert replayed.segments()[1].interval_unix == 0.0

    def test_three_restart_ordering_with_corrupt_head(self, tmp_path):
        """Satellite pin: the seq reseed must hold across THREE
        restarts with interleaved appends, and a corrupt-HEAD segment
        must quarantine (accounted) instead of wedging the order."""
        a = CarryoverSpool(str(tmp_path))
        a.append([b"s1a", b"s1b"], interval_unix=100.0)
        a.append([b"s2"], interval_unix=110.0)

        b = CarryoverSpool(str(tmp_path))            # restart 1
        assert b.replayed_total == 2
        b.append([b"s3"], interval_unix=120.0)

        # corrupt the HEAD segment's body on disk (header intact, so
        # the next scan still admits it — the corruption surfaces at
        # read_metrics time, like a torn sector would)
        head = b.oldest()
        with open(head.path, "r+b") as f:
            f.readline()
            f.write(b"\xff\xff\xff\xff")

        c = CarryoverSpool(str(tmp_path))            # restart 2
        assert c.replayed_total == 3
        c.append([b"s4"], interval_unix=130.0)
        names = sorted(os.path.basename(s.path) for s in c.segments())
        seqs = [int(n.split("-")[1]) for n in names]
        assert seqs == sorted(seqs) and len(set(seqs)) == 4
        assert seqs[-1] >= 4  # never reused a predecessor's sequence

        # drain: the corrupt head quarantines, the rest read in order
        drained = []
        for seg in c.segments():
            try:
                drained.append(seg.read_metrics())
            except ValueError:
                c.discard(seg)
        assert drained == [[b"s2"], [b"s3"], [b"s4"]]
        assert c.quarantine_depth == 1
        assert c.quarantined_metrics == 2  # s1a + s1b, still inventoried
        assert c.quarantined_bytes > 0
        qdir = os.path.join(str(tmp_path), QUARANTINE_DIR)
        assert len([f for f in os.listdir(qdir)
                    if f.endswith(".vspool")]) == 1

        # restart 3: quarantine accounting (and the seq floor) survive
        d = CarryoverSpool(str(tmp_path))
        assert d.quarantine_depth == 1
        assert d.quarantined_metrics == 2
        d.append([b"s5"])
        assert int(os.path.basename(
            d.segments()[-1].path).split("-")[1]) > seqs[-1]

    def test_unreadable_at_scan_quarantines(self, tmp_path):
        bad = tmp_path / "spill-00000001-junk.vspool"
        bad.write_bytes(b"not a header\n\xff")
        spool = CarryoverSpool(str(tmp_path))
        assert spool.depth == 0
        assert spool.quarantine_depth == 1
        # count unknown: never entered the books, stock contribution 0
        assert spool.quarantined_metrics == 0

    def test_quarantine_bound_purges_oldest(self, tmp_path):
        ledger = _LedgerSpy()
        spool = CarryoverSpool(str(tmp_path), quarantine_max_segments=2,
                               ledger=ledger)
        for i in range(3):
            spool.append([b"x%d" % i, b"y%d" % i])
        for seg in spool.segments():
            spool.discard(seg)
        assert spool.quarantine_depth == 2
        assert spool.quarantine_purged_total == 1
        assert spool.quarantine_purged_metrics_total == 2
        # the purge is explained shed; the quarantine moves are NOT
        sheds = [n for n in ledger.notes if n[0] == "forward.shed"]
        assert sheds == [("forward.shed", 2, "quarantine_purged")]

    def test_quarantine_byte_bound(self, tmp_path):
        spool = CarryoverSpool(str(tmp_path), quarantine_max_bytes=150,
                               quarantine_max_segments=100)
        for i in range(3):
            spool.append([b"z" * 100])
        for seg in spool.segments():
            spool.discard(seg)
        assert spool.quarantined_bytes <= 150
        assert spool.quarantine_purged_total >= 1

    def test_telemetry_rows_include_quarantine(self, tmp_path):
        spool = CarryoverSpool(str(tmp_path))
        spool.append([b"q"])
        spool.discard(spool.oldest())
        rows = {name: value for name, _k, value, _t
                in spool.telemetry_rows()}
        assert rows["carryover.spool.quarantined"] == 1.0
        assert rows["carryover.spool.quarantined_bytes"] > 0
        assert rows["carryover.spool.quarantine_purged"] == 0.0


# -------------------------------------------------------------------------
# WAL-mode forward client
# -------------------------------------------------------------------------


def mk_client(address, spool, **kw):
    from veneur_tpu.forward.client import ForwardClient
    from veneur_tpu.util.resilience import CircuitBreaker, RetryPolicy

    kw.setdefault("retry", RetryPolicy(max_attempts=1))
    kw.setdefault("breaker",
                  CircuitBreaker(failure_threshold=10_000, name="t"))
    return ForwardClient(address, deadline=3.0, spool=spool, wal=True,
                         **kw)


def one_counter(name="wal.cnt", value=1.0):
    from veneur_tpu.core.columnstore import RowMeta
    from veneur_tpu.core.flusher import ForwardableState
    from veneur_tpu.samplers.metrics import MetricScope

    meta = RowMeta(name=name, tags=[], joined_tags="", digest32=1,
                   scope=MetricScope.GLOBAL_ONLY, wire_type="counter")
    return ForwardableState(counters=[(meta, value)])


class TestForwardWal:
    def test_append_rides_ahead_of_send(self, tmp_path):
        """WAL mode: the interval reaches disk before any RPC, every
        send carries the interval stamp + a spool-derived token, and a
        delivered segment leaves the log."""
        received = []
        ft = ForwardTestServer(received.extend)
        ft.start()
        spool = CarryoverSpool(str(tmp_path))
        client = mk_client(ft.address, spool)
        try:
            t0 = 1700000000.0
            got = client.forward(one_counter(value=7.0), interval_start=t0)
            assert got == 1
            assert client.wal_appended_metrics == 1
            assert client.wal_acked_metrics == 1
            assert spool.depth == 0
            assert [p.counter.value for p in received] == [7]
            # the segment bytes were field-11 stamped too
            assert received[0].interval == int(t0)
            md = ft.call_metadata[-1]
            assert md[INTERVAL_KEY] == f"{t0:.3f}"
            assert md[IDEMPOTENCY_KEY].startswith("spool:")
        finally:
            client.close()
            ft.stop()

    def test_crash_before_send_replays_on_restart(self, tmp_path):
        """Process dies after the append, before the send: a fresh
        client over the same directory delivers the interval."""
        spool = CarryoverSpool(str(tmp_path))
        client = mk_client("127.0.0.1:1", spool)  # dead upstream
        t0 = time.time() - 5.0
        assert client.forward(one_counter(value=3.0),
                              interval_start=t0) == 0
        assert spool.depth == 1  # durable, undelivered
        client.close()  # "kill -9"

        received = []
        ft = ForwardTestServer(received.extend, address="127.0.0.1:0")
        ft.start()
        spool2 = CarryoverSpool(str(tmp_path))
        assert spool2.replayed_total == 1
        client2 = mk_client(ft.address, spool2)
        try:
            from veneur_tpu.core.flusher import ForwardableState
            assert client2.forward(ForwardableState()) == 1
            assert spool2.depth == 0
            assert [p.counter.value for p in received] == [3]
            assert received[0].interval == int(t0)
            md = ft.call_metadata[-1]
            assert md[INTERVAL_KEY] == f"{t0:.3f}"
        finally:
            client2.close()
            ft.stop()

    def test_replay_is_exactly_once_via_stable_token(self, tmp_path):
        """A segment whose send landed but whose ack was lost (crash
        between send and pop) re-sends with the SAME token after
        restart, and the receiver's dedupe drops it: at-least-once
        replay, exactly-once merge."""
        from veneur_tpu.core.flusher import ForwardableState
        from veneur_tpu.forward.server import ImportServer

        glob, gobs = mk_server()
        imp = ImportServer(glob, "127.0.0.1:0")
        imp.start()
        try:
            # ack-lost simulation: append, copy the segment aside (its
            # name IS the token), drain, restore the copy = the crash
            # wiped the ack but not the log — then restart and re-drain
            spool = CarryoverSpool(str(tmp_path))
            client = mk_client(imp.address, spool)
            client.forward(one_counter("wal.once", 9.0),
                           interval_start=time.time())
            # appended-but-undrained? no: live WAL drains in the same
            # call, so re-append one undelivered interval by hand
            assert spool.depth == 0
            client.forward(one_counter("wal.once", 9.0),
                           interval_start=time.time())
            client.close()

            spool2 = CarryoverSpool(str(tmp_path / "d2"))
            client2 = mk_client(imp.address, spool2)
            client2.forward(one_counter("wal.twice", 4.0),
                            interval_start=time.time())
            client2.close()
            assert spool2.depth == 0

            # now the real scenario end-to-end in one directory
            spool3 = CarryoverSpool(str(tmp_path / "d3"))
            client3 = mk_client("127.0.0.1:1", spool3)  # dead upstream
            client3.forward(one_counter("wal.exact", 6.0),
                            interval_start=time.time())
            client3.close()
            assert spool3.depth == 1
            seg = spool3.oldest()
            saved = seg.path + ".saved"
            shutil.copyfile(seg.path, saved)

            spool4 = CarryoverSpool(str(tmp_path / "d3"))
            client4 = mk_client(imp.address, spool4)
            assert client4.forward(ForwardableState()) == 1  # delivered
            client4.close()
            os.replace(saved, seg.path)  # the ack never reached disk

            spool5 = CarryoverSpool(str(tmp_path / "d3"))
            assert spool5.replayed_total == 1
            client5 = mk_client(imp.address, spool5)
            before = imp.duplicates_dropped_total
            client5.forward(ForwardableState())
            assert imp.duplicates_dropped_total == before + 1
            assert spool5.depth == 0  # acked (as duplicate) and removed
            client5.close()

            glob.store.apply_all_pending()
            glob.flush()
            got = {m.name: m.value for m in gobs.wait_flush()}
            assert got["wal.exact"] == 6.0  # merged exactly once
            assert got["wal.once"] == 18.0  # two separate intervals
        finally:
            imp.stop()

    def test_stale_replay_throttled_fresh_first(self, tmp_path):
        """Backfill isolation: a stale backlog drains BEHIND the live
        interval and under the replay token bucket, while fresh
        forwards sustain full rate."""
        from veneur_tpu.core.overload import TokenBucket

        received = []
        ft = ForwardTestServer(received.extend)
        ft.start()
        spool = CarryoverSpool(str(tmp_path))
        now = time.time()
        # a 6-interval-stale backlog (1 metric per segment)
        for i in range(6):
            stamp = now - 3600 + i * 10
            spool.append(
                [stamp_interval_wire(
                    mkmetric(f"stale.{i}", 1).SerializeToString(), stamp)],
                interval_unix=stamp)
        limiter = TokenBucket(1.0, 1.0)  # ~1 stale metric/second
        client = mk_client(ft.address, spool, replay_limiter=limiter,
                           replay_stale_after=60.0)
        try:
            got = client.forward(one_counter("live.cnt", 2.0),
                                 interval_start=now)
            # the live interval landed despite the backlog, plus the
            # first stale segment (progress guarantee) and whatever the
            # bucket's initial burst admitted
            names = [p.name for p in received]
            assert "live.cnt" in names
            assert got >= 2
            assert spool.depth >= 3  # most of the backlog deferred
            assert client.wal_replay_throttled >= 1
            # fresh-first: the live segment beat every stale one out
            assert names[0] == "live.cnt"

            # the backlog trickles out across later intervals
            from veneur_tpu.core.flusher import ForwardableState
            deadline = time.time() + 30.0
            while spool.depth and time.time() < deadline:
                client.forward(ForwardableState())
                time.sleep(0.5)
            assert spool.depth == 0
            assert sorted(p.name for p in received if p.name != "live.cnt") \
                == sorted(f"stale.{i}" for i in range(6))
        finally:
            client.close()
            ft.stop()


# -------------------------------------------------------------------------
# Backfill plane: interval buckets, original-timestamp emission
# -------------------------------------------------------------------------


class TestBackfillPlane:
    def test_counters_sum_gauges_lww_per_interval(self):
        plane = BackfillPlane(percentiles=(0.5,))
        ledger = _LedgerSpy()
        plane.ledger = ledger
        t1, t2 = 1700000000, 1700000060
        assert plane.merge_proto(mkmetric("bf.c", 3), t1)
        assert plane.merge_proto(mkmetric("bf.c", 4), t1)
        assert plane.merge_proto(mkmetric("bf.c", 9), t2)
        g = metric_pb2.Metric(name="bf.g", type=metric_pb2.Gauge)
        g.gauge.value = 1.5
        assert plane.merge_proto(g, t1)
        g2 = metric_pb2.Metric(name="bf.g", type=metric_pb2.Gauge)
        g2.gauge.value = 2.5
        assert plane.merge_proto(g2, t1)
        assert plane.open_intervals == 2
        assert plane.open_metrics == 5

        plane.drain()              # generation roll: nothing closes yet
        out = plane.drain()        # now both buckets are idle -> close
        assert plane.open_intervals == 0
        by = {(m.name, m.timestamp): m for m in out}
        assert by[("bf.c", t1)].value == 7.0
        assert by[("bf.c", t1)].type == MetricType.COUNTER
        assert by[("bf.c", t1)].backfilled is True
        assert by[("bf.c", t2)].value == 9.0
        assert by[("bf.g", t1)].value == 2.5
        # conservation notes: merged == closed
        merged = sum(n for s, n, _k in ledger.notes
                     if s == "backfill.merged")
        closed = sum(n for s, n, _k in ledger.notes
                     if s == "backfill.closed")
        assert merged == closed == 5

    def test_per_metric_field11_beats_rpc_stamp(self):
        plane = BackfillPlane()
        t_rpc, t_field = 1700000000, 1700000300
        assert plane.merge_proto(
            mkmetric("bf.f11", 2, interval=t_field), t_rpc)
        plane.drain()
        out = plane.drain()
        assert out[0].timestamp == t_field

    def test_llhist_register_add_is_exact(self):
        from veneur_tpu.forward import llhistwire
        from veneur_tpu.ops import llhist_ref

        plane = BackfillPlane(percentiles=(0.5,))
        t = 1700000000
        bins_a = np.zeros(llhist_ref.BINS, np.int64)
        bins_b = np.zeros(llhist_ref.BINS, np.int64)
        bins_a[llhist_ref.bin_index(np.array([12.0]))[0]] = 5
        bins_b[llhist_ref.bin_index(np.array([12.0]))[0]] = 2
        bins_b[llhist_ref.bin_index(np.array([120.0]))[0]] = 1
        for bins in (bins_a, bins_b):
            pbm = metric_pb2.Metric(name="bf.ll", type=metric_pb2.LLHist)
            pbm.llhist.bins = llhistwire.marshal(bins)
            assert plane.merge_proto(pbm, t)
        plane.drain()
        out = plane.drain()
        by_name = {}
        for m in out:
            by_name.setdefault(m.name, []).append(m)
        assert by_name["bf.ll.count"][0].value == 8.0
        assert by_name["bf.ll.count"][0].timestamp == t
        # cumulative buckets: le:+Inf equals the exact register count
        inf = [m for m in by_name["bf.ll.bucket"]
               if "le:+Inf" in m.tags]
        assert inf[0].value == 8.0

    def test_bound_closes_oldest_first(self):
        plane = BackfillPlane(max_open=2)
        stamps = [1700000000 + 60 * i for i in range(3)]
        for i, t in enumerate(stamps):
            plane.merge_proto(mkmetric(f"bf.b{i}", 1), t)
        assert plane.open_intervals == 2
        assert plane.bound_closed_total == 1
        out = plane.drain()  # pending (bound-forced) emission delivers
        assert [m.timestamp for m in out] == [stamps[0]]

    def test_older_than_every_bucket_still_emits(self):
        """Regression: a stamp older than ALL open buckets at the bound
        creates the bucket that is itself the eviction victim — the
        metric must still emit (a one-metric interval) and the books
        must balance, never orphan."""
        ledger = _LedgerSpy()
        plane = BackfillPlane(max_open=2, ledger=ledger)
        plane.merge_proto(mkmetric("bf.new1", 1), 1700001000)
        plane.merge_proto(mkmetric("bf.new2", 1), 1700002000)
        plane.merge_proto(mkmetric("bf.ancient", 1), 1700000500)
        assert plane.open_intervals == 2
        out = plane.drain() + plane.drain() + plane.drain(force=True)
        assert sorted(m.name for m in out) == \
            ["bf.ancient", "bf.new1", "bf.new2"]
        merged = sum(n for s, n, _k in ledger.notes
                     if s == "backfill.merged")
        closed = sum(n for s, n, _k in ledger.notes
                     if s == "backfill.closed")
        assert merged == closed == 3
        assert plane.open_metrics == 0

    def test_unstamped_and_junk_rejected(self):
        plane = BackfillPlane()
        assert not plane.merge_proto(mkmetric("bf.u", 1), 0)
        novalue = metric_pb2.Metric(name="bf.nv")
        assert not plane.merge_proto(novalue, 1700000000)
        assert plane.rejected_total == 2


# -------------------------------------------------------------------------
# End-to-end backfill drill: stale spool -> import -> original timestamps
# -------------------------------------------------------------------------


class TestBackfillEndToEnd:
    def test_stale_spool_replays_with_original_timestamps(self, tmp_path):
        """The acceptance backfill drill (in-process shape): a
        6-interval-stale spool directory replays through the real gRPC
        import plane; the global buckets by ORIGINAL interval and its
        flush emits series timestamped at those intervals, visible in
        Cortex remote-write sample timestamps and Prometheus exposition
        lines; the books close clean under ledger_strict."""
        from veneur_tpu.core.flusher import ForwardableState
        from veneur_tpu.forward.server import ImportServer
        from veneur_tpu.sinks.prometheus import render_exposition

        glob, gobs = mk_server(ledger_strict=True)
        assert glob.backfill is not None
        imp = ImportServer(glob, "127.0.0.1:0")
        imp.start()

        # a dead peer's spool directory: 6 intervals, hours stale
        now = time.time()
        stamps = [int(now - 7200 + 60 * i) for i in range(6)]
        spool = CarryoverSpool(str(tmp_path))
        for i, t in enumerate(stamps):
            metrics = [stamp_interval_wire(
                mkmetric("restore.cnt", 10 + i).SerializeToString(), t)]
            spool.append(metrics, interval_unix=t)
        del spool

        restored = CarryoverSpool(str(tmp_path))
        assert restored.replayed_total == 6
        client = mk_client(imp.address, restored)
        try:
            assert client.forward(ForwardableState()) == 6
            assert restored.depth == 0
            assert glob.backfill.open_intervals == 6
            assert glob.backfill.open_metrics == 6

            glob.flush()  # generation roll
            glob.flush()  # idle buckets close -> backfilled emission
            flushed = gobs.drain()
            backfilled = [m for m in flushed if m.backfilled]
            got = {m.timestamp: m.value for m in backfilled
                   if m.name == "restore.cnt"}
            assert got == {t: float(10 + i)
                           for i, t in enumerate(stamps)}

            # Cortex remote-write: per-sample timestamps are the
            # ORIGINAL interval starts (milliseconds)
            from veneur_tpu.sinks.cortex import CortexMetricSink
            cortex = CortexMetricSink("cortex", "http://unused/", "host")
            series = [cortex._series(m) for m in backfilled
                      if m.name == "restore.cnt"]
            assert sorted(ts for _l, _v, ts in series) == \
                [t * 1000 for t in stamps]

            # Prometheus exposition: backfilled lines carry explicit
            # millisecond timestamps; live lines stay bare
            text = render_exposition(backfilled)
            for t in stamps:
                assert f" {t * 1000}" in text
            live = render_exposition(
                [m for m in flushed if not m.backfilled][:5])
            for t in stamps:
                assert f" {t * 1000}" not in live
            # OpenMetrics negotiation stamps SECONDS, not milliseconds
            om = render_exposition(backfilled, openmetrics=True)
            for t in stamps:
                assert f" {t}" in om
                assert f" {t * 1000}" not in om
        finally:
            client.close()
            imp.stop()


# -------------------------------------------------------------------------
# Crash drill: kill mid-flush, restart, replay — exactness pinned
# -------------------------------------------------------------------------


class TestCrashDrill:
    def test_crash_restart_replay_is_exact(self, tmp_path):
        """In-process acceptance drill: three rounds of append-then-die
        (the send never completes), each followed by a restart+replay;
        final global state must equal an unfaulted control's — counter
        sums exact, llhist registers bit-identical — and every ledger
        interval closes with zero unexplained imbalance (strict)."""
        from veneur_tpu.forward.server import ImportServer

        faulted, _fobs = mk_server(ledger_strict=True)
        control, _cobs = mk_server(ledger_strict=True)
        f_imp = ImportServer(faulted, "127.0.0.1:0")
        f_imp.start()
        c_imp = ImportServer(control, "127.0.0.1:0")
        c_imp.start()

        def mk_local(forward_to):
            local, _ = mk_server(forward_address="127.0.0.1:1")
            return local

        f_local = mk_local(f_imp.address)
        c_local = mk_local(c_imp.address)
        c_client = mk_client(c_imp.address,
                             CarryoverSpool(str(tmp_path / "control")))
        c_local.forwarder = c_client.forward
        wal_dir = str(tmp_path / "wal")

        def feed(server, round_no):
            for i in range(30):
                server.handle_metric_packet(
                    b"drill.cnt.%d:3|c|#veneurglobalonly" % (i % 5))
                server.handle_metric_packet(
                    b"drill.llh.%d:%d|l" % (i % 3, (round_no * 13 + i) % 87))
            server.store.apply_all_pending()

        try:
            for round_no in range(3):
                feed(f_local, round_no)
                feed(c_local, round_no)
                c_local.flush()

                # faulted path: forward to a dead port — the WAL append
                # lands, the send cannot; then the "process" dies
                dead_spool = CarryoverSpool(wal_dir)
                dead_client = mk_client("127.0.0.1:1", dead_spool)
                f_local.forwarder = dead_client.forward
                f_local.forward_client = dead_client
                f_local.flush()
                assert dead_spool.depth >= 1
                dead_client.close()  # kill -9

                # restart: fresh objects over the same WAL directory
                re_spool = CarryoverSpool(wal_dir)
                assert re_spool.replayed_total >= 1
                re_client = mk_client(f_imp.address, re_spool)
                f_local.forwarder = re_client.forward
                # forward_client drives the empty-snapshot dispatch:
                # pending WAL segments alone must trigger the drain
                f_local.forward_client = re_client
                f_local.flush()  # empty snapshot still drains the WAL
                assert re_spool.depth == 0
                re_client.close()

            # the diff: counters exact, llhist registers bit-identical
            for server in (faulted, control):
                server.store.apply_all_pending()

            def counter_sums(server):
                vals, touched, meta = \
                    server.store.counters.snapshot_and_reset()
                return {meta[r].name: float(np.asarray(vals)[r])
                        for r in np.flatnonzero(np.asarray(touched)).tolist()
                        if meta[r] is not None}

            def llhist_bins(server):
                _out, bins, touched, meta = \
                    server.store.llhists.snapshot_and_reset((0.5,))
                rows = np.flatnonzero(np.asarray(touched)).tolist()
                return {meta[row].name: np.asarray(bins)[i]
                        for i, row in enumerate(rows)
                        if meta[row] is not None}

            f_sums, c_sums = counter_sums(faulted), counter_sums(control)
            assert f_sums == c_sums and f_sums  # zero counter loss
            f_bins, c_bins = llhist_bins(faulted), llhist_bins(control)
            assert set(f_bins) == set(c_bins) and f_bins
            for name in f_bins:
                assert np.array_equal(f_bins[name], c_bins[name]), name

            # strict close on both receivers: zero unexplained imbalance
            faulted.ledger.close_interval()
            control.ledger.close_interval()
        finally:
            c_client.close()
            f_imp.stop()
            c_imp.stop()


# -------------------------------------------------------------------------
# Satellites: compilation cache, retrace cache tags
# -------------------------------------------------------------------------


@pytest.mark.usefixtures("jax_cache_config")
class TestCompilationCache:
    """The directory rule of util/compilecache.py, driven through the
    server."""

    def test_knob_points_jax_at_directory(self, tmp_path):
        import jax

        cache_dir = str(tmp_path / "jit-cache")
        server, _ = mk_server(jax_compilation_cache_dir=cache_dir)
        assert server.enable_compilation_cache() is True
        assert jax.config.jax_compilation_cache_dir == cache_dir
        assert os.path.isdir(cache_dir)
        assert server.telemetry.events.snapshot(
            kind="compilation_cache_enabled")

    def test_disabled_without_directory(self):
        # CPU backend, nothing configured: the <checkout>/.jax_cache
        # default is for accelerators only
        server, _ = mk_server()
        assert server.enable_compilation_cache() is False

    def test_environment_directory_wins(self, tmp_path, monkeypatch):
        """JAX_COMPILATION_CACHE_DIR set: the server sets no directory
        in code, even with one configured."""
        import jax

        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR",
                           str(tmp_path / "from-env"))
        before = jax.config.jax_compilation_cache_dir
        server, _ = mk_server(
            jax_compilation_cache_dir=str(tmp_path / "from-config"))
        assert server.enable_compilation_cache() is True
        assert jax.config.jax_compilation_cache_dir == before
        assert not (tmp_path / "from-config").exists()
        event = server.telemetry.events.snapshot(
            kind="compilation_cache_enabled")[-1]
        assert event["directory"] == str(tmp_path / "from-env")

    def test_retrace_tags_carry_cache_outcome(self, tmp_path):
        cache_dir = tmp_path / "jit-cache"
        server, _ = mk_server(jax_compilation_cache_dir=str(cache_dir))
        assert server.enable_compilation_cache() is True
        # miss: the recompile ADDED a cache entry
        server._store_resize("counter", 64, 128, 0.01, kind="resize")
        (cache_dir / "jit_x-abc-cache").write_bytes(b"x")
        server._store_resize("counter", 64, 128, 0.5, kind="recompile")
        # hit: no new entries appeared during the recompile
        server._store_resize("gauge", 64, 128, 0.01, kind="resize")
        server._store_resize("gauge", 64, 128, 0.02, kind="recompile")
        drained = server.latency.drain_retraces()
        assert drained["counter"][1] == "miss"
        assert drained["gauge"][1] == "hit"


# -------------------------------------------------------------------------
# SIGKILL soak: the real kill -9 mid-flush loop (slow)
# -------------------------------------------------------------------------


@pytest.mark.slow
class TestCrashReplaySoak:
    def test_sigkill_soak_zero_loss(self):
        """Drive scripts/crash_replay_soak.py: SIGKILL a real local
        child mid-flush (fresh WAL segment on disk, send hanging in
        the chaos seam) twice, restart, replay — final global state
        diffs clean against the unfaulted control."""
        import importlib.util
        spec = importlib.util.spec_from_file_location(
            "crash_replay_soak",
            os.path.join(os.path.dirname(__file__), os.pardir,
                         "scripts", "crash_replay_soak.py"))
        soak = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(soak)
        report = soak.run_soak(kills=2, counters_per_round=20)
        assert report["kills"] == 2 and report["restarts"] == 2
        assert report["counters"]  # nonempty and already diffed exact


if __name__ == "__main__":
    import sys
    sys.exit(pytest.main([__file__, "-v"] + sys.argv[1:]))
