"""The flush pipeline & shape ladder (the `flushperf` marker).

A tick swaps each family's device generation at the interval boundary
(O(1)), reads the captured generation out on the flush thread while
ingest continues into the fresh one, and delivers that interval the
same tick. These tests pin the contract:

- exactness: a readout that runs on one thread while another ingests
  the next interval is bit-identical to swap + readout in one call for
  all five families (values, tags, llhist bins, HLL registers),
  single-device AND on the virtual mesh;
- the recycled (donated, re-initialized) spare generation is
  indistinguishable from a fresh allocation — interval N+1 over the
  recycled buffers equals interval N over fresh ones, including the
  t-digest ±inf min/max re-init;
- a tick delivers its own interval under the strict ledger, and
  nothing is carried to the next tick: shutdown has only the open
  interval to flush, and in WAL mode that flush reaches disk before
  the process exits;
- the waterfall has one lane, `flush.family` spans parent under their
  own tick's flush trace, a readout that raises fails its tick and
  frees the next, and a config that still sets the removed option
  loads and delivers in the same tick;
- a prewarmed capacity rung's post-resize round tags
  `prewarmed`/`compile_cache` instead of paying a hot-path retrace,
  and the cold (un-prewarmed) fallback stays correct.
"""

from __future__ import annotations

import threading
import time

import numpy as np
import pytest

from veneur_tpu.config import Config
from veneur_tpu.core.columnstore import ColumnStore
from veneur_tpu.core.flusher import (flush_columnstore_batch,
                                     readout_columnstore,
                                     swap_columnstore)
from veneur_tpu.core.server import Server
from veneur_tpu.samplers.metrics import HistogramAggregates
from veneur_tpu.samplers.parser import Parser
from veneur_tpu.sinks.channel import ChannelMetricSink

pytestmark = pytest.mark.flushperf

PCTS = (0.5, 0.99)
AGGS = HistogramAggregates.from_names(
    ["min", "max", "median", "avg", "count", "sum"])


def wait_until(fn, timeout=10.0, step=0.02):
    deadline = time.time() + timeout
    while time.time() < deadline:
        if fn():
            return True
        time.sleep(step)
    return False


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
    lines.append(b"sc.ok:0|sc")
    return lines


def _mk_store(**kw):
    kw.setdefault("counter_capacity", 64)
    kw.setdefault("gauge_capacity", 64)
    kw.setdefault("histo_capacity", 64)
    kw.setdefault("set_capacity", 32)
    kw.setdefault("llhist_capacity", 64)
    kw.setdefault("batch_cap", 128)
    return ColumnStore(**kw)


def _feed(store, lines):
    p = Parser()
    for line in lines:
        p.parse_metric_fast(line, store.process)
    store.apply_all_pending()


def _batch_keys(batch):
    return sorted(
        (m.name, float(m.value), tuple(sorted(m.tags)), int(m.type))
        for m in batch.materialize())


def _fwd_keys(fwd):
    """Bit-level ForwardableState fingerprint: scalar values exact,
    llhist bins and HLL registers compared register-for-register."""
    return {
        "counters": sorted((m.name, v) for m, v in fwd.counters),
        "gauges": sorted((m.name, v) for m, v in fwd.gauges),
        "histos": sorted(
            (m.name, means.tobytes(), weights.tobytes(), lo, hi, recip)
            for m, means, weights, lo, hi, recip in fwd.histograms),
        "sets": sorted((m.name, np.asarray(regs).tobytes())
                       for m, regs in fwd.sets),
        "llhists": sorted((m.name, np.asarray(bins).tobytes())
                          for m, bins in fwd.llhists),
    }


def _overlapped_flush(store, is_local, collect_forward=True):
    """Swap on this thread (the interval boundary), read out on another
    while this one keeps ingesting — what the server's flush thread and
    its ingest threads do."""
    swap = swap_columnstore(store, is_local, PCTS,
                            collect_forward=collect_forward)
    result = {}

    def _readout():
        result["out"] = readout_columnstore(
            store, swap, is_local, AGGS,
            collect_forward=collect_forward)

    t = threading.Thread(target=_readout)
    t.start()
    # ingest the NEXT interval concurrently with the readout
    _feed(store, corpus(round_no=7))
    t.join(30.0)
    assert not t.is_alive()
    return result["out"]


class TestOverlapExactness:
    @pytest.mark.parametrize("is_local", [False, True])
    def test_overlapped_bit_identical_single_device(self, is_local):
        """Overlapped readout == swap + readout in one call, all five
        families, for both server modes — AND the recycled spare generation's
        second interval equals a fresh store's."""
        plain_store, over_store = _mk_store(), _mk_store()
        _feed(plain_store, corpus())
        _feed(over_store, corpus())
        plain_batch, plain_fwd = flush_columnstore_batch(
            plain_store, is_local, PCTS, AGGS)
        over_batch, over_fwd = _overlapped_flush(over_store, is_local)
        assert _batch_keys(over_batch) == _batch_keys(plain_batch)
        assert _fwd_keys(over_fwd) == _fwd_keys(plain_fwd)
        # interval 2: the overlapped store now flushes over RECYCLED
        # (donated, re-initialized) generations; feed the plain store
        # the same second-interval corpus and compare again
        _feed(plain_store, corpus(round_no=7))
        plain2, pfwd2 = flush_columnstore_batch(
            plain_store, is_local, PCTS, AGGS)
        over2, ofwd2 = flush_columnstore_batch(
            over_store, is_local, PCTS, AGGS)
        assert _batch_keys(over2) == _batch_keys(plain2)
        assert _fwd_keys(ofwd2) == _fwd_keys(pfwd2)

    @pytest.mark.mesh
    def test_overlapped_bit_identical_on_mesh(self):
        """The overlapped readout over the sharded mesh store (stacked
        donated merges) matches the single-device swap + readout
        bit-for-bit — the PR-11 exactness pin survives the overlap."""
        single = _mk_store()
        mesh_store = _mk_store(shard_devices=2)
        assert mesh_store.shard_plane is not None, "virtual mesh missing"
        _feed(single, corpus())
        _feed(mesh_store, corpus())
        plain_batch, plain_fwd = flush_columnstore_batch(
            single, True, PCTS, AGGS)
        over_batch, over_fwd = _overlapped_flush(mesh_store, True)
        assert _batch_keys(over_batch) == _batch_keys(plain_batch)
        assert _fwd_keys(over_fwd) == _fwd_keys(plain_fwd)
        # second interval over the recycled stacked generations
        _feed(single, corpus(round_no=7))
        plain2, pfwd2 = flush_columnstore_batch(single, True, PCTS, AGGS)
        over2, ofwd2 = flush_columnstore_batch(mesh_store, True, PCTS,
                                               AGGS)
        assert _batch_keys(over2) == _batch_keys(plain2)
        assert _fwd_keys(ofwd2) == _fwd_keys(pfwd2)


# -------------------------------------------------------------------------
# Server pipeline: same-tick delivery, ledger, waterfall, shutdown
# -------------------------------------------------------------------------


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


def _server_feed(server, lines):
    for line in lines:
        server.handle_metric_packet(line)
    server.store.apply_all_pending()


def _obs_keys(metrics):
    return sorted((m.name, float(m.value), tuple(sorted(m.tags)),
                   int(m.type)) for m in metrics)


class TestServerPipeline:
    @pytest.mark.parametrize("shards", [
        0, pytest.param(2, marks=pytest.mark.mesh)])
    def test_tick_delivers_its_own_interval_strict_ledger(self, shards):
        """The first flush() after a feed yields that corpus, the same
        tick, what a one-device column store's own swap + readout
        yields for it; the next tick yields the next interval's and
        nothing of the first's; every ledger identity closes at zero,
        and the round carries nothing of a readout that ran a tick
        ahead. The same on two shards."""
        server, obs = mk_server(**{"tpu.shards": shards})
        assert (server.store.shard_plane is not None) == bool(shards)
        store = _mk_store(counter_capacity=128, gauge_capacity=128,
                          histo_capacity=128, set_capacity=64)
        try:
            for tick, round_no in enumerate((0, 5), start=1):
                _server_feed(server, corpus(round_no))
                server.flush()
                _feed(store, corpus(round_no))
                want, _fwd = flush_columnstore_batch(
                    store, False, server.percentiles, server.aggregates)
                got = obs.drain()
                assert _obs_keys(got) == _obs_keys(want.materialize())
                ri = server.telemetry.flushes.snapshot()[-1]
                assert ri["flush"] == tick
                assert ri["metrics_flushed"] == len(got) > 0
                assert not {"async", "delivered_flush"} & set(ri)
                assert not {"critical_path_s", "join_s"} & set(ri["phases"])
                assert all(v == 0.0 for v in ri["ledger"].values())
            for interval in server.ledger.history_imbalances():
                assert all(v == 0.0 for v in interval.values()), interval
        finally:
            server.config.flush_on_shutdown = False
            server.shutdown()

    @pytest.mark.parametrize("flush_on_shutdown", [True, False])
    def test_shutdown_flush(self, flush_on_shutdown):
        """`flush_on_shutdown` delivers the open interval before exit;
        without it shutdown delivers nothing new, and what a tick
        already delivered stayed delivered: no tick leaves anything
        behind for shutdown to hand over."""
        server, obs = mk_server(flush_on_shutdown=flush_on_shutdown)
        ref, ref_obs = mk_server()
        try:
            _server_feed(server, corpus())
            server.flush()
            ticked = _obs_keys(obs.drain())
            _server_feed(ref, corpus())
            ref.flush()
            assert ticked == _obs_keys(ref_obs.drain()) != []
            _server_feed(server, corpus(round_no=3))
            _server_feed(ref, corpus(round_no=3))
            ref.flush()
            open_interval = _obs_keys(ref_obs.drain())
        finally:
            ref.config.flush_on_shutdown = False
            ref.shutdown()
            server.shutdown()
        got = _obs_keys(obs.drain())
        assert got == (open_interval if flush_on_shutdown else [])
        assert server.flush_count == (2 if flush_on_shutdown else 1)

    def test_shutdown_flush_reaches_wal(self, tmp_path):
        """WAL mode + dead upstream: the shutdown flush appends its
        forward snapshot to the on-disk WAL before exiting — a crash
        after shutdown loses nothing (PR-10's replay picks it up)."""
        from veneur_tpu.forward.client import ForwardClient
        from veneur_tpu.util.resilience import CircuitBreaker, RetryPolicy
        from veneur_tpu.util.spool import CarryoverSpool

        server, obs = mk_server(forward_only=True, flush_on_shutdown=True,
                                forward_address="127.0.0.1:1")
        spool = CarryoverSpool(str(tmp_path))
        client = ForwardClient(  # dead upstream: WAL append still lands
            "127.0.0.1:1", deadline=3.0, spool=spool, wal=True,
            retry=RetryPolicy(max_attempts=1),
            breaker=CircuitBreaker(failure_threshold=10_000, name="t"))
        server.forwarder = client.forward
        server.forward_client = client
        # the stocks start() would have registered: the strict forward
        # identity must see WAL-spooled metrics as inventory
        server.ledger.stock("forward_carryover",
                            lambda: client.carryover.pending_metrics)
        server.ledger.stock("forward_inflight",
                            lambda: client.inflight_metrics)
        server.ledger.stock("forward_spool",
                            lambda: spool.pending_metrics)
        server.ledger.stock("spool_quarantine",
                            lambda: spool.quarantined_metrics)
        try:
            _server_feed(server, corpus())
            assert client.wal_appended_metrics == 0 and spool.depth == 0
        finally:
            server.shutdown()  # final flush: forward -> WAL append
            client.close()
        assert client.wal_appended_metrics > 0
        assert spool.depth >= 1  # durable, awaiting replay

    def test_waterfall_has_one_lane(self):
        server, obs = mk_server()
        try:
            from veneur_tpu.core.latency import waterfall_rounds
            _server_feed(server, corpus())
            server.flush()
            [tree] = waterfall_rounds(server.telemetry.flushes.snapshot())
            assert tree["flush"] == 1
            assert not {"async_readout", "delivered_flush",
                        "critical_path_s"} & set(tree)
            assert set(tree["families"]) == {
                "counter", "gauge", "histogram", "set", "llhist", "status"}
            for rec in tree["families"].values():
                assert "lane" not in rec
            # every family segment is one of the round's own spans
            assert 0 < tree["segments_sum_s"] <= tree["device_total_s"] * 1.10
        finally:
            server.config.flush_on_shutdown = False
            server.shutdown()

    def test_family_spans_parent_under_this_ticks_flush(self):
        """PR-9 single-root pin: each tick's flush.family spans land in
        that tick's own interval trace, parented under its flush span,
        stamped inside the round's wall-clock window."""
        server, obs = mk_server()
        try:
            for tick in (1, 2):
                _server_feed(server, corpus(round_no=tick))
                server.flush()
                ri = server.telemetry.flushes.snapshot()[-1]
                trace = server.trace_plane.store.get(int(ri["trace_id"], 16))
                spans = trace["spans"]
                assert len(trace["roots"]) == 1  # PR-9 single-root pin
                root = next(s for s in spans
                            if s["span_id"] == trace["roots"][0])
                assert root["name"] == "flush"
                assert root["tags"]["interval"] == str(tick)
                fam_spans = [s for s in spans
                             if s["name"] == "flush.family"]
                assert len(fam_spans) == 6, fam_spans
                for s in fam_spans:
                    assert s["parent_id"] == root["span_id"]
                    # on this round's own wall clock
                    assert (root["start_ns"] <= s["start_ns"]
                            <= s["end_ns"] <= root["end_ns"]), (s, root)
        finally:
            server.config.flush_on_shutdown = False
            server.shutdown()

    @pytest.mark.parametrize("strict", [False, True])
    def test_removed_option_flush_async(self, tmp_path, strict):
        """A YAML that still carries the removed `flush_async: true`
        loads (the key is ignored like any unknown one) and delivers in
        the same tick; validate-config-strict names the field."""
        from veneur_tpu.config import read_config

        path = tmp_path / "veneur.yaml"
        path.write_text("interval: 60s\nhostname: test\n"
                        "statsd_listen_addresses: []\n"
                        "flush_async: true\n")
        if strict:
            with pytest.raises(ValueError, match="flush_async"):
                read_config(str(path), strict=True)
            return
        cfg = read_config(str(path), env={})
        assert not hasattr(cfg, "flush_async")
        obs = ChannelMetricSink()
        server = Server(cfg, extra_metric_sinks=[obs])
        try:
            _server_feed(server, corpus())
            server.flush()
            assert {m.name for m in obs.drain()} >= {"c.0", "g.0", "s.0"}
        finally:
            server.config.flush_on_shutdown = False
            server.shutdown()

    def test_failed_readout_fails_its_tick_and_frees_the_next(
            self, monkeypatch):
        """A readout that raises fails its tick loudly (flush() raises)
        and releases the flush lock; the next tick flushes the next
        interval, with the ledger closed on both."""
        from veneur_tpu.core import server as server_mod

        server, obs = mk_server()
        real = server_mod.readout_columnstore
        calls = []

        def failing_once(*args, **kw):
            calls.append(1)
            if len(calls) == 1:
                raise RuntimeError("device link down")
            return real(*args, **kw)

        monkeypatch.setattr(server_mod, "readout_columnstore", failing_once)
        try:
            _server_feed(server, corpus())
            with pytest.raises(RuntimeError, match="device link down"):
                server.flush()
            assert not server._flush_lock.locked()
            assert obs.drain() == []
            _server_feed(server, corpus(round_no=4))
            server.flush()
            ref, ref_obs = mk_server()
            try:
                _server_feed(ref, corpus(round_no=4))
                ref.flush()
                assert _obs_keys(obs.drain()) == _obs_keys(ref_obs.drain())
            finally:
                ref.config.flush_on_shutdown = False
                ref.shutdown()
            assert server.flush_count == 2
        finally:
            server.config.flush_on_shutdown = False
            server.shutdown()

    def test_forward_payload_is_encoded_on_the_flush_thread(self):
        """A local's readout pre-encodes the forward payload under the
        round's own `forward_encode` span, child of `readout`, on the
        thread that flushes: the forward thread finds `fwd.wire` built
        and current."""
        from veneur_tpu.forward.convert import forwardable_to_wire

        # the stub forwarder acknowledges nothing to the ledger
        server, obs = mk_server(forward_address="127.0.0.1:1",
                                ledger_strict=False)
        assert server.is_local
        sent = []
        server.forwarder = lambda fwd, interval_start: sent.append(
            (fwd, list(fwd.wire), threading.current_thread().name))
        try:
            _server_feed(server, corpus())
            server.flush()
            [(fwd, wire, forward_thread)] = sent
            assert len(fwd) > 0
            assert wire == forwardable_to_wire(fwd) != []
            spans = server.telemetry.flushes.snapshot()[-1]["spans"]
            [encode] = [s for s in spans if s["name"] == "forward_encode"]
            [readout] = [s for s in spans if s["name"] == "readout"]
            assert encode["parent"] == "readout"
            assert readout["parent"] == "store_flush"
            me = threading.current_thread().name
            assert encode["thread"] == readout["thread"] == me
            assert forward_thread == "flush-forward"
            assert (readout["start_s"] <= encode["start_s"]
                    and encode["start_s"] + encode["wall_s"]
                    <= readout["start_s"] + readout["wall_s"] + 1e-6)
        finally:
            server.config.flush_on_shutdown = False
            server.shutdown()

    def test_flush_readout_worker_waits_for_the_first_live_read(self):
        """The flush reads out on its own thread: no `flush-readout`
        worker exists, and the supervisor watches none, until a live
        read (core/query.py) first needs one."""
        from veneur_tpu.core.query import QuerySpec

        server, obs = mk_server()
        beats = server.overload.supervisor._beats
        try:
            _server_feed(server, corpus())
            server.flush()
            assert obs.drain()
            assert server._flush_executor is None
            assert "flush-readout" not in beats
            _server_feed(server, corpus(round_no=2))
            got = server.query_plane.query(
                QuerySpec.build(metric="g.0", kind="value"))
            assert got["value"] == 2.0
            assert server._flush_executor is not None
            assert "flush-readout" in beats
            readers = [t for t in threading.enumerate()
                       if t.name == "flush-readout"]
            assert len(readers) == 1
        finally:
            server.config.flush_on_shutdown = False
            server.shutdown()
        readers[0].join(5.0)
        assert not readers[0].is_alive()


# -------------------------------------------------------------------------
# Shape-ladder prewarm
# -------------------------------------------------------------------------


class TestShapeLadder:
    def _force_resize(self, table, parser, n=80):
        for i in range(n):
            parser.parse_metric_fast(b"pw.%d:1|c" % i, table.add)
        table.apply_pending()

    def test_prewarmed_resize_tags_and_stays_correct(self):
        """A prewarmed rung's post-resize apply reports prewarmed=True
        through the resize hook (the waterfall tag), and the values
        coming out of the resized table are exact."""
        store = _mk_store(counter_capacity=64)
        table = store.counters
        events = []
        table.on_resize = lambda *a, **kw: events.append((a, kw))
        assert table.prewarm_rung(128, PCTS)
        assert 128 in table._prewarmed_caps
        self._force_resize(table, Parser())
        recompiles = [kw for a, kw in events
                      if kw.get("kind") == "recompile"]
        assert recompiles and recompiles[0]["prewarmed"] is True
        vals, touched, meta = table.snapshot_and_reset()
        got = {meta[r].name: vals[r] for r in np.flatnonzero(touched)}
        assert got == {f"pw.{i}": 1.0 for i in range(80)}

    def test_cold_resize_fallback_still_correct(self):
        """Without prewarm the resize retraces on the hot path (the
        pre-ladder behavior): tagged prewarmed=False, values exact."""
        store = _mk_store(counter_capacity=64)
        table = store.counters
        events = []
        table.on_resize = lambda *a, **kw: events.append((a, kw))
        self._force_resize(table, Parser())
        recompiles = [kw for a, kw in events
                      if kw.get("kind") == "recompile"]
        assert recompiles and recompiles[0]["prewarmed"] is False
        vals, touched, meta = table.snapshot_and_reset()
        assert len(np.flatnonzero(touched)) == 80

    def test_prewarmer_thread_compiles_queued_rungs(self):
        """ShapeLadderPrewarmer end to end: initial prewarm queues 2x
        rungs for every device family; a resize event queues the rung
        after; every compile lands in the table's prewarmed set."""
        from veneur_tpu.core.flushexec import ShapeLadderPrewarmer

        store = _mk_store()
        events = []
        pw = ShapeLadderPrewarmer(
            store, percentiles=PCTS, need_export=True,
            on_event=lambda kind, **kw: events.append((kind, kw)))
        pw.start()
        try:
            pw.prewarm_initial()
            assert wait_until(
                lambda: 128 in store.counters._prewarmed_caps
                and 128 in store.gauges._prewarmed_caps
                and 128 in store.histos._prewarmed_caps
                and 128 in store.llhists._prewarmed_caps, timeout=60.0)
            # the sparse set table's rung prewarm is a documented no-op
            assert not store.sets._prewarmed_caps
            pw.note_resize("counter", 128)
            assert wait_until(
                lambda: 256 in store.counters._prewarmed_caps,
                timeout=60.0)
            assert pw.compiled_total >= 5
            rows = {name: v for name, _k, v, _t in pw.telemetry_rows()}
            assert rows["prewarm.compiled_total"] >= 5
        finally:
            pw.stop()

    def test_server_recompile_event_reads_prewarmed(self):
        """Server-side tag plumbing: a prewarmed recompile lands in the
        flight recorder + retrace cache as prewarmed (the waterfall's
        `compile_cache: prewarmed` tag the acceptance reads)."""
        server, obs = mk_server()
        try:
            server._store_resize("counter", 64, 128, 0.01, kind="resize")
            server._store_resize("counter", 64, 128, 0.002,
                                 kind="recompile", prewarmed=True)
            events = [e for e in server.telemetry.events.snapshot()
                      if e["kind"] == "columnstore_recompile"]
            assert events and events[-1]["prewarmed"] is True
            assert events[-1].get("compile_cache") in ("prewarmed", "hit")
            drained = server.latency.drain_retraces()
            secs, cache = drained["counter"]
            assert cache in ("prewarmed", "hit")
        finally:
            server.config.flush_on_shutdown = False
            server.shutdown()


class TestReadoutExecutor:
    def test_join_reraises_and_survives(self):
        from veneur_tpu.core.flushexec import FlushReadoutExecutor

        beats = []
        ex = FlushReadoutExecutor(beat=beats.append)
        try:
            boom = ex.submit(lambda: 1 / 0)
            with pytest.raises(ZeroDivisionError):
                boom.result(5.0)
            ok = ex.submit(lambda: 42)
            assert ok.result(5.0) == 42
            assert beats  # supervisor heartbeats flowed
        finally:
            ex.stop()
