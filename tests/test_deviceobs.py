"""Device capacity & shard-balance observatory (the `deviceobs` marker).

The HBM ledger's contract is *conservation*: `total_bytes()` equals the
exact sum of every registered generation's nbytes — live generations
plus parked spares at quiescence, plus the in-flight snapshot mid
overlap — at every step of the lifecycle the column store can drive:

- generation swap while the flush reads out beside a thread that
  ingests, including the recycled-spare reuse on the following interval;
- a capacity resize (the grow drops and re-registers the live
  generation at the new rung);
- a prewarm-rung compile (the throwaway state is booked `prewarm` and
  dropped before the call returns);
- a live 2 -> 3 reshard (capture buffers ride `reshard_capture` into
  the snapshot and are dropped at cutover merge).

The shard-balance plane is pinned by a hot-key storm: rejection-sampled
names homed onto one shard drive `device.shard.skew` over threshold and
a `shard_skew` alert rule through idle -> pending -> firing with
trace-stamped alert_transition events. A `slow`-marked soak holds the
enabled-vs-disabled flush overhead under the same 2% bar as the
latency/query observatories.
"""

from __future__ import annotations

import json
import threading
import time
import urllib.request

import numpy as np
import pytest

import jax

from veneur_tpu.config import Config
from veneur_tpu.core.columnstore import ColumnStore
from veneur_tpu.core.deviceobs import (DeviceObservatory, HIST_ROWS,
                                       KERNEL_KINDS)
from veneur_tpu.core.flusher import (flush_columnstore_batch,
                                     readout_columnstore,
                                     swap_columnstore)
from veneur_tpu.core.server import Server
from veneur_tpu.core.telemetry import FlushRound
from veneur_tpu.samplers.metrics import HistogramAggregates
from veneur_tpu.samplers.parser import Parser
from veneur_tpu.sinks.channel import ChannelMetricSink

pytestmark = pytest.mark.deviceobs

PCTS = (0.5, 0.99)
AGGS = HistogramAggregates.from_names(
    ["min", "max", "median", "avg", "count", "sum"])


def corpus(round_no: int = 0):
    lines = []
    for i in range(8):
        lines.append(b"c.%d:%d|c|#env:t" % (i, i + 1 + round_no))
        lines.append(b"g.%d:%.2f|g" % (i, i * 1.5 + round_no))
        lines.append(b"t.%d:%.2f|ms" % (i, 10.0 + i + round_no))
        lines.append(b"s.%d:m%d|s" % (i, i))
        lines.append(b"ll.%d:%.2f|l" % (i, 3.0 + i + round_no))
    return lines


def _mk_store(**kw):
    kw.setdefault("counter_capacity", 64)
    kw.setdefault("gauge_capacity", 64)
    kw.setdefault("histo_capacity", 64)
    kw.setdefault("set_capacity", 32)
    kw.setdefault("llhist_capacity", 64)
    kw.setdefault("batch_cap", 128)
    return ColumnStore(**kw)


def _feed_store(store, lines):
    p = Parser()
    for line in lines:
        p.parse_metric_fast(line, store.process)
    store.apply_all_pending()


def mk_server(**kw):
    cfg = Config()
    cfg.interval = 3600.0
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


def _feed(server, lines, apply=True):
    for line in lines:
        server.handle_metric_packet(line)
    if apply:
        server.store.apply_all_pending()


def expected_bytes(store) -> int:
    """Ground truth the ledger must match at quiescence: the exact
    nbytes sum over every table's live device state plus its parked
    spare. (Mid-overlap the in-flight snapshot is extra — the overlap
    test accounts for it separately.)"""
    total = 0
    for _family, t in store.tables():
        state_of = getattr(t, "_devobs_state", None)
        if state_of is None:
            continue
        for tree in (state_of(), getattr(t, "_spare", None)):
            if tree is None:
                continue
            for leaf in jax.tree_util.tree_leaves(tree):
                total += int(getattr(leaf, "nbytes", 0))
    return total


def _inflight_bytes(obs) -> int:
    led = obs.ledger()
    return sum(states.get("inflight", 0) + states.get("reshard_capture", 0)
               for states in led["by_family"].values())


def _skewed_names(n_shards: int, shard: int, count: int, salt: str = "skew"):
    """Rejection-sample metric names whose digest64 homes onto `shard`
    under the (digest * n) >> 64 routing."""
    p = Parser()
    grabbed = []
    names, i = [], 0
    while len(names) < count:
        line = b"%s.%d:1|c" % (salt.encode(), i)
        i += 1
        del grabbed[:]
        p.parse_metric_fast(line, grabbed.append)
        d = grabbed[-1].digest64 & 0xFFFFFFFFFFFFFFFF
        if (d * n_shards) >> 64 == shard:
            names.append(line)
        assert i < 100_000, "rejection sampling runaway"
    return names


# -------------------------------------------------------------------------
# HBM ledger conservation
# -------------------------------------------------------------------------


class TestLedgerConservation:
    def test_attach_registers_exact(self):
        store = _mk_store()
        obs = DeviceObservatory()
        _feed_store(store, corpus())
        store.attach_deviceobs(obs)
        assert obs.total_bytes() == expected_bytes(store) > 0
        led = obs.ledger()
        assert led["live_bytes"] == led["total_bytes"]
        assert led["forecast_next_resize_bytes"] == 2 * led["live_bytes"]

    @pytest.mark.parametrize("is_local", [False, True])
    def test_swap_under_overlapped_flush(self, is_local):
        """The flush's shape: swap, then a readout on one thread while
        another keeps ingesting. Mid-overlap the
        old generation is booked `inflight`; after the join/recycle it
        is the parked spare and the ledger is exact again — and the
        next interval's spare REUSE conserves bytes too."""
        store = _mk_store()
        obs = DeviceObservatory()
        store.attach_deviceobs(obs)
        _feed_store(store, corpus())
        swap = swap_columnstore(store, is_local, PCTS)
        # old generations in flight, fresh ones live: exact, with the
        # in-flight bytes on top of live+spare
        inflight = _inflight_bytes(obs)
        assert inflight > 0
        assert obs.total_bytes() == expected_bytes(store) + inflight

        result = {}

        def _readout():
            result["out"] = readout_columnstore(store, swap, is_local,
                                                AGGS)

        t = threading.Thread(target=_readout)
        t.start()
        _feed_store(store, corpus(round_no=7))
        t.join(30.0)
        assert not t.is_alive()
        # quiescent: snapshots recycled into spares, ledger exact
        assert _inflight_bytes(obs) == 0
        assert obs.total_bytes() == expected_bytes(store) > 0
        led = obs.ledger()
        spares = sum(s.get("spare", 0) for s in led["by_family"].values())
        assert spares > 0
        # interval 2 swaps INTO the recycled spares (retag, not fresh
        # registration) — still exact at quiescence
        flush_columnstore_batch(store, is_local, PCTS, AGGS)
        assert obs.total_bytes() == expected_bytes(store)

    def test_resize_grow_rebooks_live_generation(self):
        store = _mk_store(counter_capacity=64)
        obs = DeviceObservatory()
        store.attach_deviceobs(obs)
        before = obs.total_bytes()
        # mint past capacity to force the grow
        _feed_store(store, [b"rz.%d:1|c" % i for i in range(100)])
        assert store.counters.capacity > 64
        after = obs.total_bytes()
        assert after > before
        assert after == expected_bytes(store)
        # grown table survives a flush round with conservation intact
        flush_columnstore_batch(store, True, PCTS, AGGS)
        assert obs.total_bytes() == expected_bytes(store)

    def test_prewarm_rung_token_is_transient(self):
        store = _mk_store(counter_capacity=64)
        obs = DeviceObservatory()
        store.attach_deviceobs(obs)
        before = obs.total_bytes()
        assert store.counters.prewarm_rung(128, PCTS)
        # the throwaway rung state was booked `prewarm` and dropped
        assert obs.total_bytes() == before == expected_bytes(store)
        rep = obs.kernel_report()
        kinds = {(k["kind"], k["family"]) for k in rep["kernels"]}
        assert ("prewarm", "counter") in kinds
        assert rep["compiles"].get("counter", 0) >= 1

    def test_live_reshard_2_to_3_conserves(self, tmp_path):
        """The full migration: capture buffers ride `reshard_capture`
        through the WAL'd merge and are dropped at cutover; the
        re-topologized 3-shard generations register fresh. Exact at
        every quiescent point."""
        server, _obs = mk_server(**{"tpu.shards": 2},
                                 reshard_spool_dir=str(tmp_path / "wal"))
        try:
            obs = server.deviceobs
            assert obs is not None and obs.enabled
            _feed(server, corpus())
            assert obs.total_bytes() == expected_bytes(server.store)
            server.flush()
            assert obs.total_bytes() == expected_bytes(server.store)
            server.reshard.begin(shards=3, block=True)
            assert _inflight_bytes(obs) == 0
            assert obs.total_bytes() == expected_bytes(server.store) > 0
            # post-reshard interval still conserves
            _feed(server, corpus(round_no=3))
            server.flush()
            assert obs.total_bytes() == expected_bytes(server.store)
            bal = obs.shard_balance()
            assert bal is not None and bal["n_shards"] == 3
        finally:
            server.config.flush_on_shutdown = False
            server.shutdown()

    def test_disabled_observatory_is_inert(self):
        server, _obs = mk_server(device_observatory=False)
        try:
            _feed(server, corpus())
            server.flush()
            assert server.deviceobs.total_bytes() == 0
            assert server.deviceobs.telemetry_rows() == []
            rep = server.device_report()
            assert rep["enabled"] is False
        finally:
            server.config.flush_on_shutdown = False
            server.shutdown()


# -------------------------------------------------------------------------
# Kernel registry & telemetry export
# -------------------------------------------------------------------------


class TestKernelRegistry:
    def test_flush_populates_dispatches_and_hists(self):
        store = _mk_store()
        obs = DeviceObservatory()
        store.attach_deviceobs(obs)
        _feed_store(store, corpus())
        flush_columnstore_batch(store, True, PCTS, AGGS)
        _feed_store(store, corpus(round_no=1))
        flush_columnstore_batch(store, True, PCTS, AGGS)
        rep = obs.kernel_report()
        kinds = {(k["kind"], k["family"]) for k in rep["kernels"]}
        assert ("apply", "counter") in kinds
        assert ("readout", "counter") in kinds
        assert ("reset", "counter") in kinds  # spare re-init on recycle
        # compiles are counted on the retrace paths: force a resize
        _feed_store(store, [b"kr.%d:1|c" % i for i in range(100)])
        rep = obs.kernel_report()
        assert rep["compiles"].get("counter", 0) >= 1
        timed = [k for k in rep["kernels"] if k.get("wall")]
        assert timed and all(k["wall"]["count"] >= 1 for k in timed)
        rows = {r[0] for r in obs.telemetry_rows()}
        assert {"device.mem.total_bytes", "device.mem.peak_bytes",
                "device.mem.generations", "device.mem.bytes",
                "device.kernel.dispatches",
                "device.compile.count"} <= rows
        # every exported hist row expands from the linted HIST_ROWS set
        hist_rows = {r for r in rows if ".kernel." in r
                     and r != "device.kernel.dispatches"}
        bases = {r.rsplit(".", 1)[0] for r in hist_rows}
        assert bases <= set(HIST_ROWS)
        assert set(KERNEL_KINDS) == {
            b.split(".")[-1][:-2] for b in HIST_ROWS}


    def test_readout_row_holds_the_completion_stamps(self):
        """`device.kernel.readout_s{family}` is the chip's time: one
        observation a `chip_busy{family,device}` span of the round, of
        that span's wall, and nothing of the dispatch's host wall. The
        sets here stay on the host (no promoted row, no estimate), so
        the family has no stamp and no row."""
        store = _mk_store()
        obs = DeviceObservatory()
        store.attach_deviceobs(obs)
        rounds = []
        for round_no in range(3):
            _feed_store(store, corpus(round_no))
            rounds.append(FlushRound())
            flush_columnstore_batch(store, True, PCTS, AGGS,
                                    timing=rounds[-1])
        busy = [s for rnd in rounds for s in rnd.spans
                if s["name"] == "chip_busy"]
        families = ("counter", "gauge", "histogram", "llhist")
        assert sorted(s["family"] for s in busy) == sorted(families * 3)
        rows = {k["family"]: k for k in obs.kernel_report()["kernels"]
                if k["kind"] == "readout"}
        assert set(rows) == set(families)
        for family, row in rows.items():
            mine = [s["wall_s"] for s in busy if s["family"] == family]
            assert row["dispatches"] == row["wall"]["count"] == 3
            assert row["wall"]["sum"] == pytest.approx(sum(mine), abs=2e-6)
        # the watcher is one thread, gone with `close`; a later flush
        # would start another
        watcher = obs.readout_watcher()
        assert watcher.thread.is_alive()
        obs.close()
        watcher.thread.join(5.0)
        assert not watcher.thread.is_alive()
        assert obs.readout_watcher() is not watcher
        obs.close()

    def test_watcher_under_many_flush_threads(self):
        """More threads than cores hand rounds to the one watcher under
        a short switch interval, a compact's handle after each readout:
        no hand-off is lost or stamped twice, `join` returns only once
        all readouts are stamped, every compact is booked once, and the
        spans of the one device never overlap, whoever handed them
        over."""
        import sys

        import jax.numpy as jnp

        obs = DeviceObservatory()
        watcher = obs.readout_watcher()
        threads, per_thread, errors, booked = 24, 40, [], []
        rounds = [FlushRound() for _ in range(threads)]
        handle = jnp.zeros(4)
        [device] = [f"{d.platform}:{d.id}" for d in handle.devices()]

        def flush_thread(rnd):
            try:
                for i in range(per_thread):
                    watcher.watch(rnd, f"f{i % 4}", {device: [handle + i]},
                                  time.perf_counter())
                    watcher.watch_compact(handle - i, time.perf_counter(),
                                          booked.append)
                    if i % 8 == 7 and not watcher.join():
                        errors.append("join timed out")
            except Exception as e:   # pragma: no cover - the assertion
                errors.append(repr(e))

        old = sys.getswitchinterval()
        sys.setswitchinterval(1e-5)
        try:
            workers = [threading.Thread(target=flush_thread, args=(rnd,))
                       for rnd in rounds]
            for t in workers:
                t.start()
            for t in workers:
                t.join(60.0)
            assert not any(t.is_alive() for t in workers)
        finally:
            sys.setswitchinterval(old)
        assert watcher.join() and not errors, errors
        # a last readout: the compacts handed over before it are booked
        # once it is stamped
        last = FlushRound()
        watcher.watch(last, "f0", {device: [handle]}, time.perf_counter())
        assert watcher.join() and len(last.spans) == 1
        assert watcher._open == 0 and not watcher._handed
        assert len(booked) == threads * per_thread
        assert all(s >= 0 for s in booked)
        spans = [s for rnd in rounds for s in rnd.spans]
        assert [len(rnd.spans) for rnd in rounds] == [per_thread] * threads
        assert {s["device"] for s in spans} == {device}
        ends = []   # on one clock: each round counts from its own t0
        for rnd in rounds:
            ends += [(rnd.t0 + s["start_s"], rnd.t0 + s["start_s"]
                      + s["wall_s"]) for s in rnd.spans]
        ends.sort(key=lambda se: se[1])
        for (_s0, e0), (s1, _e1) in zip(ends, ends[1:]):
            assert e0 <= s1 + 1e-9
        counted = {k["family"]: k for k in obs.kernel_report()["kernels"]}
        stamped = len(spans) + 1   # and the last readout's
        assert sum(k["wall"]["count"] for k in counted.values()) == stamped
        assert sum(k["dispatches"] for k in counted.values()) == stamped
        obs.close()

    def test_compact_stamps_are_booked_once_off_the_dispatchers_thread(
            self):
        """A key past its 128 staging slots within one batch (dense)
        and the whole-table compact its next batch forces, applied on a
        thread of its own as the dispatcher applies them: each compact's
        `done` handle is stamped once, on the watcher's thread, and its
        seconds booked to `compact_chip_seconds_total[path]`; the
        flush's readout-path compact is booked under `readout`. The
        flush's join covers every compact handed over before its
        readout."""
        store = _mk_store(histo_capacity=64, batch_cap=256)
        obs = DeviceObservatory()
        store.attach_deviceobs(obs)
        histos = store.histos
        booked = []
        book = histos._book_compact_chip

        def recording(path, seconds):
            booked.append((path, seconds, threading.current_thread().name))
            book(path, seconds)

        histos._book_compact_chip = recording
        hot = [b"hot:%d.5|ms" % i for i in range(200)]

        def dispatcher():
            for _ in range(3):   # dense, then dense after a compact, twice
                _feed_store(store, hot)

        t = threading.Thread(target=dispatcher, name="ingest-dispatch")
        t.start()
        t.join(60.0)
        # one more dense batch stays pending: it compacts on the flush
        for line in hot:
            Parser().parse_metric_fast(line, store.process)
        flush_columnstore_batch(store, True, PCTS, AGGS)
        assert histos.compacts_total == {"live": 2, "readout": 1}
        assert histos.dense_keys_total == {"live": 3, "readout": 1}
        assert sorted(p for p, _s, _n in booked) == ["live", "live",
                                                     "readout"]
        assert {n for _p, _s, n in booked} == {"readout-watcher"}
        for path in ("live", "readout"):
            mine = [s for p, s, _n in booked if p == path]
            assert all(s >= 0 for s in mine)
            assert histos.compact_chip_seconds_total[path] == sum(mine)
        rows = {(r[0], tuple(r[3])): r[2] for r in store.telemetry_rows()
                if r[0].startswith("ingest.tdigest.")}
        assert rows[("ingest.tdigest.compact_chip_seconds_total",
                     ("path:live",))] == histos.compact_chip_seconds_total[
                         "live"]
        assert rows[("ingest.tdigest.dense_keys_total",
                     ("path:readout",))] == 1
        obs.close()

    def test_compact_hand_off_never_waits_and_join_waits_for_readouts(self):
        """A compact still on the chip holds the watcher, not the thread
        that hands the next ones over; the flush's `join` returns once
        its readouts are stamped, whatever compacts queue behind them;
        each compact is booked once, in hand-over order, when the chip
        has done it."""
        import jax.numpy as jnp

        obs = DeviceObservatory()
        watcher = obs.readout_watcher()
        release = threading.Event()
        booked = []
        handle = jnp.zeros(4)
        [dev] = handle.devices()

        class OnTheChip:
            """A compact's `done` handle whose program has not ended."""

            def devices(self):
                return {dev}

            def block_until_ready(self):
                release.wait(30.0)
                return self

        rnd = FlushRound()
        watcher.watch(rnd, "histogram",
                      {f"{dev.platform}:{dev.id}": [handle]},
                      time.perf_counter())
        t0 = time.perf_counter()
        for i in range(50):
            watcher.watch_compact(
                OnTheChip(), time.perf_counter(),
                lambda s, i=i: booked.append(
                    (i, s, threading.current_thread().name)))
        handed_s = time.perf_counter() - t0
        assert watcher.join()
        assert [s["name"] for s in rnd.spans] == ["chip_busy"]
        assert not booked and handed_s < 1.0
        release.set()
        deadline = time.monotonic() + 30.0
        while len(booked) < 50 and time.monotonic() < deadline:
            time.sleep(0.01)
        assert [i for i, _s, _n in booked] == list(range(50))
        assert {n for _i, _s, n in booked} == {"readout-watcher"}
        assert all(s >= 0 for _i, s, _n in booked)
        assert watcher.join() and watcher._open == 0
        obs.close()

    def test_disabled_observatory_stamps_no_compact(self):
        """Under `device_observatory: false` the compacts are counted and
        the dense keys too, but nothing stamps them: no watcher, no
        chip seconds, no row."""
        server, _obs = mk_server(device_observatory=False)
        try:
            hot = [b"hot:%d.5|ms" % i for i in range(200)]
            _feed(server, hot)
            _feed(server, hot)
            server.flush()
            histos = server.store.histos
            assert histos.compacts_total["live"] == 1
            assert histos.dense_keys_total["live"] == 2
            assert histos.compact_chip_seconds_total == {"live": 0.0,
                                                         "readout": 0.0}
            assert server.deviceobs._watcher is None
            names = {r[0] for r in server.store.telemetry_rows()}
            assert "ingest.tdigest.dense_keys_total" in names
            assert "ingest.tdigest.compact_chip_seconds_total" not in names
        finally:
            server.config.flush_on_shutdown = False
            server.shutdown()

    def test_disabled_observatory_has_no_watcher(self):
        obs = DeviceObservatory(enabled=False)
        assert obs.readout_watcher() is None
        obs.close()


# -------------------------------------------------------------------------
# Shard balance, skew alert, HTTP surface
# -------------------------------------------------------------------------


class TestShardBalance:
    def test_hot_key_storm_fires_shard_skew_rule(self):
        """Hot-key storm: names homed onto shard 0 drive the skew over
        threshold; a `shard_skew` rule walks idle -> pending -> firing
        with trace-stamped alert_transition events."""
        server, _obs = mk_server(**{"tpu.shards": 2})
        try:
            hot = _skewed_names(2, 0, 30)
            cold = _skewed_names(2, 1, 5, salt="cold")
            _feed(server, hot + cold)
            obs = server.deviceobs
            skew = obs.shard_skew()
            assert skew is not None and skew > 1.5
            server.alerts.configure([
                {"id": "skew", "kind": "shard_skew", "op": ">",
                 "threshold": 1.5, "for": "0.2s"},
            ])
            now = time.time()
            trs = server.alerts.evaluate_once(now=now)
            assert [(t["from_state"], t["to_state"]) for t in trs] == \
                [("idle", "pending")]
            assert server.alerts.evaluate_once(now=now + 0.1) == []
            trs = server.alerts.evaluate_once(now=now + 0.3)
            assert [(t["from_state"], t["to_state"]) for t in trs] == \
                [("pending", "firing")]
            rep = server.alerts.report()
            assert rep["rules"][0]["state"] == "firing"
            assert rep["rules"][0]["value"] == pytest.approx(skew,
                                                             rel=1e-6)
            events = server.telemetry.events.snapshot(
                kind="alert_transition")
            assert [e["to_state"] for e in events] == ["pending",
                                                       "firing"]
            assert all(e["rule"] == "skew" for e in events)
            assert all(e.get("trace_id") for e in events)
            # the gauge the rule watches is exported
            rows = {r[0]: r[2] for r in obs.telemetry_rows()}
            assert rows["device.shard.skew"] == pytest.approx(skew)
        finally:
            server.config.flush_on_shutdown = False
            server.shutdown()

    def test_hot_shard_detection_and_reshard_plan(self):
        """All rows on one of four shards: skew 4.0, shard 0 flagged
        hot, and the planner recommends a rebalancing target priced in
        migration cells."""
        server, _obs = mk_server(**{"tpu.shards": 4})
        try:
            _feed(server, _skewed_names(4, 0, 24))
            bal = server.deviceobs.shard_balance()
            assert bal is not None
            assert bal["n_shards"] == 4
            assert sum(bal["rows_per_shard"]) == 24
            assert bal["rows_per_shard"][0] == 24
            assert bal["skew"] == pytest.approx(4.0)
            assert bal["hot_shards"] == [0]
            assert sum(bal["digest_occupancy"]) == 24
            plan = bal.get("reshard_plan")
            assert plan is not None
            assert plan["from_shards"] == 4
            assert plan["to_shards"] != 4
            assert plan["rows_moved"] >= 0
            assert plan["migration_cells"] is None or \
                plan["migration_cells"] >= 1
        finally:
            server.config.flush_on_shutdown = False
            server.shutdown()

    def test_unsharded_store_has_no_balance(self):
        store = _mk_store()
        obs = DeviceObservatory()
        store.attach_deviceobs(obs)
        _feed_store(store, corpus())
        assert obs.shard_balance() is None
        assert obs.shard_skew() is None

    def test_debug_device_http_surface(self):
        from veneur_tpu.core.httpapi import HTTPApi
        server, _obs = mk_server(**{"tpu.shards": 2})
        api = None
        try:
            _feed(server, corpus())
            server.flush()
            api = HTTPApi(server.config, server=server,
                          address="127.0.0.1:0")
            api.start()
            host, port = api.address
            with urllib.request.urlopen(
                    f"http://{host}:{port}/debug/device",
                    timeout=10) as r:
                assert r.status == 200
                body = json.loads(r.read())
            assert body["enabled"] is True
            assert body["ledger"]["total_bytes"] == \
                expected_bytes(server.store)
            assert body["kernels"]
            assert body["shard_balance"]["n_shards"] == 2
            assert "watermarks" in body
        finally:
            if api is not None:
                api.stop()
            server.config.flush_on_shutdown = False
            server.shutdown()


# -------------------------------------------------------------------------
# Overhead soak
# -------------------------------------------------------------------------


@pytest.mark.slow
class TestOverheadSoak:
    def test_observatory_overhead_bounded(self):
        """The acceptance soak: observatory enabled vs disabled, same
        corpus, same flush cadence — flush wall and the rounds'
        `duration_s` p99 within 2% (plus the same absolute CI-jitter
        floor the query-plane soak uses)."""
        def soak(enabled):
            server, _obs = mk_server(device_observatory=enabled)
            try:
                walls = []
                for k in range(2):  # warmup: compiles off both sides
                    _feed(server, corpus(round_no=k))
                    server.flush()
                for k in range(8):
                    _feed(server, corpus(round_no=10 + k))
                    t0 = time.perf_counter()
                    server.flush()
                    walls.append(time.perf_counter() - t0)
                rounds = server.telemetry.flushes.snapshot()[-8:]
                return walls, [float(ri["duration_s"]) for ri in rounds]
            finally:
                server.config.flush_on_shutdown = False
                server.shutdown()

        base_walls, base_durs = soak(enabled=False)
        on_walls, on_durs = soak(enabled=True)
        base = float(np.mean(base_walls))
        loaded = float(np.mean(on_walls))
        assert loaded - base <= 0.02 * base + 0.25, \
            f"flush wall moved: off={base:.3f}s on={loaded:.3f}s"
        bp99 = float(np.percentile(base_durs, 99))
        lp99 = float(np.percentile(on_durs, 99))
        assert lp99 <= bp99 * 1.02 + 0.25, \
            f"duration_s p99 moved: {bp99:.3f} -> {lp99:.3f}"
