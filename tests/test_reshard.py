"""Elastic resharding tests (the `reshard` marker).

The contract under pin (parallel/reshard.py): a live N -> M reshard
under sustained ingest produces a post-cutover flush BIT-IDENTICAL to a
never-resharded control — all five families; counters exact through the
int64 wire; llhist/HLL registers bit-for-bit; t-digest percentile rows
within re-compression tolerance (pack_centroids_many re-packs the
captured centroids once, statistically identical but not bitwise) — and
`ledger_strict` stays green through every interval including the
cutover one.

Crash coverage: a process death anywhere mid-cutover leaves WAL range
segments behind; a fresh server (ANY topology) replays them
exactly-once and its next flush matches the control. A WAL append fault
degrades only the faulted cell to in-memory merge — still zero loss
absent a crash.

The proxy tier's half: ShardGroupRing.regroup G -> G' keeps every
non-migrating key's owner EXACTLY, converges with a freshly-started
ring at G', and a clean regroup routes zero keys off-range
(`proxy.ring.group_spill` stays 0).
"""

from __future__ import annotations

import json
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

from veneur_tpu.config import Config
from veneur_tpu.core.query import QueryError, QuerySpec, ReshardRetry, \
    parse_tags
from veneur_tpu.core.server import Server
from veneur_tpu.parallel.reshard import ReshardError, migration_cells
from veneur_tpu.proxy.ring import ShardGroupRing
from veneur_tpu.sinks.channel import ChannelMetricSink

pytestmark = pytest.mark.reshard

_FULL = 1 << 64


def wait_until(fn, timeout=120.0, step=0.02):
    deadline = time.time() + timeout
    while time.time() < deadline:
        if fn():
            return True
        time.sleep(step)
    return False


def corpus(round_no: int = 0):
    """All five families, enough distinct names to land rows on every
    shard of a small mesh."""
    lines = []
    for i in range(12):
        lines.append(b"rs.c.%d:%d|c|#env:t" % (i, i + 1 + round_no))
        lines.append(b"rs.g.%d:%.2f|g" % (i, i * 1.5 + round_no))
        lines.append(b"rs.t.%d:%.2f|ms" % (i, 10.0 + i + round_no))
        lines.append(b"rs.t.%d:%.2f|ms" % (i, 40.0 + i))
        lines.append(b"rs.s.%d:m%d|s" % (i, i))
        lines.append(b"rs.s.%d:m%d|s" % (i, i + 50 + round_no))
        lines.append(b"rs.ll.%d:%.2f|l" % (i, 3.0 + i + round_no))
    return lines


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


def _flushed(metrics):
    return {(m.name, tuple(sorted(m.tags))): float(m.value)
            for m in metrics}


def _assert_bit_identical(resharded: dict, control: dict):
    """Exact equality row for row, except t-digest percentile rows
    (captured centroids are re-compressed ONCE by the migration, so the
    quantile estimate may differ in the last ulps — rtol pins it)."""
    assert set(resharded) == set(control), (
        sorted(set(control) - set(resharded)),
        sorted(set(resharded) - set(control)))
    for key, want in control.items():
        got = resharded[key]
        if key[0].endswith("percentile"):
            assert np.isclose(got, want, rtol=1e-6), (key, got, want)
        else:
            assert got == want, (key, got, want)


def _assert_ledger_clean(server):
    for interval in server.ledger.history_imbalances():
        assert all(v == 0.0 for v in interval.values()), interval
    assert all(v == 0.0 for v in server.ledger.imbalance_net.values())


def _shutdown(server):
    server.config.flush_on_shutdown = False
    server.shutdown()


# ---------------------------------------------------------------------------
# plan geometry
# ---------------------------------------------------------------------------


class TestMigrationCells:
    @pytest.mark.parametrize("n_old,n_new", [
        (2, 3), (3, 2), (2, 4), (4, 2), (3, 5), (8, 3), (1, 2), (5, 5)])
    def test_cells_partition_the_digest_space(self, n_old, n_new):
        """Cells are contiguous, cover [0, 2^64) exactly, number at
        most N+M-1, and every digest inside a cell routes to the cell's
        single old_home / new_home."""
        cells = migration_cells(n_old, n_new)
        assert len(cells) <= n_old + n_new - 1
        assert cells[0]["lo"] == 0
        assert cells[-1]["hi"] == _FULL
        for prev, cur in zip(cells, cells[1:]):
            assert prev["hi"] == cur["lo"]
        rng = np.random.RandomState(7)
        for cell in cells:
            width = cell["hi"] - cell["lo"]
            probes = {cell["lo"], cell["hi"] - 1} | {
                cell["lo"] + int(rng.randint(0, min(width, 1 << 62)))
                for _ in range(8)}
            for d in probes:
                assert (d * n_old) >> 64 == cell["old_home"], (cell, d)
                assert (d * n_new) >> 64 == cell["new_home"], (cell, d)

    def test_identity_reshard_has_no_moving_cells(self):
        for cell in migration_cells(4, 4):
            # same partition on both sides: homes can only agree
            assert cell["old_home"] == cell["new_home"]


# ---------------------------------------------------------------------------
# the cutover itself
# ---------------------------------------------------------------------------


class TestElasticCutover:
    @pytest.mark.parametrize("tick_first", [False, True])
    def test_live_split_bit_identity_vs_control(self, tmp_path,
                                                tick_first):
        """2 -> 3 under sustained ingest: rows fed before, DURING, and
        after the reshard all land; the post-cutover flush is
        bit-identical to a never-resharded 2-shard control; strict
        ledger green end to end. With `tick_first` the reshard begins
        right after a flush tick: the tick delivered its interval and
        released the flush lock, so the cutover finds recycled
        generations and nothing of that interval to wait for."""
        server, obs = mk_server(**{"tpu.shards": 2},
                                reshard_spool_dir=str(tmp_path / "wal"))
        control, cobs = mk_server(**{"tpu.shards": 2})
        assert server.store.shard_plane is not None, "virtual mesh missing"
        try:
            if tick_first:
                _feed(server, corpus(4))
                _feed(control, corpus(4))
                server.flush()
                control.flush()
                _assert_bit_identical(_flushed(obs.drain()),
                                      _flushed(cobs.drain()))
            _feed(server, corpus(0))
            _feed(control, corpus(0))

            ctl = server.reshard
            ctl.begin(shards=3)
            # sustained ingest while the plan thread prewarms + cuts
            # over: packets keep being admitted (they stage in the
            # ingest ring; the apply below lands them on whichever
            # topology is live — commutative merges make the order
            # immaterial, and the gauge rows' last write is round 1 on
            # both pipelines)
            mid = corpus(1)
            fed = 0
            deadline = time.time() + 300.0
            while ctl.state != "idle" or ctl.epoch == 0:
                assert not ctl.last_error, ctl.last_error
                assert time.time() < deadline, "reshard never finished"
                if fed < len(mid):
                    server.handle_metric_packet(mid[fed])
                    fed += 1
                else:
                    time.sleep(0.01)
            _feed(server, mid[fed:])
            server.store.apply_all_pending()
            _feed(control, mid)

            assert ctl.epoch == 1 and ctl.cutovers == 1
            assert ctl.last_error == ""
            assert ctl.segments_written > 0, "cutover wrote no WAL"
            assert ctl.inflight_metrics() == 0
            assert server.store.shard_plane.n == 3

            # post-split ingest keeps landing on the new plane
            _feed(server, corpus(2))
            _feed(control, corpus(2))

            # the live query plane survived the swap: same answer as
            # the never-resharded control, pre-flush
            spec = QuerySpec.build(metric="rs.c.0", kind="count",
                                   tags=parse_tags("env:t"))
            assert (server.query_plane.query(spec)["value"]
                    == control.query_plane.query(spec)["value"])

            server.flush()
            control.flush()
            _assert_bit_identical(_flushed(obs.drain()),
                                  _flushed(cobs.drain()))
            _assert_ledger_clean(server)
            _assert_ledger_clean(control)
        finally:
            _shutdown(server)
            _shutdown(control)

    def test_crash_mid_cutover_replays_exactly_once(self, tmp_path):
        """Kill the merge after every range segment is durable (the
        widest crash window): a FRESH server — restarted at the OLD
        shard count, not the mid-flight target — replays the segments
        exactly-once and flushes identically to the control."""
        spool_dir = str(tmp_path / "wal")
        server, obs = mk_server(**{"tpu.shards": 2},
                                reshard_spool_dir=spool_dir)
        control, cobs = mk_server(**{"tpu.shards": 2})
        try:
            _feed(server, corpus(0))
            _feed(control, corpus(0))
            ctl = server.reshard

            def die(batch):
                raise RuntimeError("simulated SIGKILL mid-merge")
            ctl._merge_decoded = die

            with pytest.raises(ReshardError, match="SIGKILL"):
                ctl.begin(shards=3, block=True)
            written = ctl.segments_written
            assert written > 0
            assert list((tmp_path / "wal").iterdir()), \
                "no durable segments on disk after the crash"
        finally:
            _shutdown(server)
            del obs

        # restart on the same spool; 2 shards again — recovery must be
        # correct into a topology that differs from the crashed target
        server2, obs2 = mk_server(**{"tpu.shards": 2},
                                  reshard_spool_dir=spool_dir)
        try:
            replayed = server2.reshard.recover()
            assert replayed == written
            assert server2.reshard.replayed_segments == written
            # exactly-once: a second recover finds nothing
            assert server2.reshard.recover() == 0
            server2.flush()
            control.flush()
            _assert_bit_identical(_flushed(obs2.drain()),
                                  _flushed(cobs.drain()))
            _assert_ledger_clean(server2)
        finally:
            _shutdown(server2)
            _shutdown(control)

    @pytest.mark.chaos
    def test_append_fault_degrades_without_loss(self, tmp_path):
        """Every WAL append faulted (chaos seam): the cutover degrades
        to in-memory merge per cell — still zero loss, still
        bit-identical, and the fault is counted loudly."""
        server, obs = mk_server(**{"tpu.shards": 2},
                                reshard_spool_dir=str(tmp_path / "wal"),
                                chaos_enabled=True,
                                chaos_reshard_append_fault_nth=1)
        control, cobs = mk_server(**{"tpu.shards": 2})
        try:
            _feed(server, corpus(0))
            _feed(control, corpus(0))
            server.reshard.begin(shards=3, block=True)
            assert server.reshard.append_faults > 0
            assert server.reshard.segments_written == 0
            assert server.reshard.epoch == 1
            _feed(server, corpus(1))
            _feed(control, corpus(1))
            server.flush()
            control.flush()
            _assert_bit_identical(_flushed(obs.drain()),
                                  _flushed(cobs.drain()))
            _assert_ledger_clean(server)
        finally:
            _shutdown(server)
            _shutdown(control)


# ---------------------------------------------------------------------------
# ready semantics, request validation, query retry
# ---------------------------------------------------------------------------


class TestReadyAndQuerySemantics:
    def test_begin_refuses_unsharded_and_busy(self, tmp_path):
        server, _ = mk_server()  # no mesh
        try:
            with pytest.raises(ReshardError, match="not sharded"):
                server.reshard.begin(shards=2)
        finally:
            _shutdown(server)
        server, _ = mk_server(**{"tpu.shards": 2})
        try:
            with pytest.raises(ReshardError, match=">= 1"):
                server.reshard.begin(shards=0)
            server.reshard.state = "planning"
            try:
                with pytest.raises(ReshardError, match="in progress"):
                    server.reshard.begin(shards=3)
            finally:
                server.reshard.state = "idle"
        finally:
            _shutdown(server)

    def test_ready_degrades_past_deadline(self):
        """/healthcheck/ready flips to 503 + reason while a cutover is
        past its deadline, and recovers the moment the state machine
        returns to idle."""
        server, _ = mk_server(**{"tpu.shards": 2})
        try:
            ok, _reason = server.ready_state()
            assert ok
            server.reshard.state = "cutover"
            server.reshard.deadline_unix = time.time() - 5.0
            ok, reason = server.ready_state()
            assert not ok and "reshard" in reason
            server.reshard.state = "idle"
            server.reshard.deadline_unix = 0.0
            ok, _reason = server.ready_state()
            assert ok
        finally:
            _shutdown(server)

    def test_query_mid_cutover_raises_typed_retry(self):
        """capture() during a cutover returns the typed retry — never a
        shape error from half-swapped generations — and the alert
        engine's per-tick QueryError catch covers it (ReshardRetry IS a
        QueryError, so a topology swap can't crash the alert loop)."""
        assert issubclass(ReshardRetry, QueryError)
        server, _ = mk_server(**{"tpu.shards": 2})
        try:
            _feed(server, corpus(0))
            spec = QuerySpec.build(metric="rs.c.0", kind="count",
                                   tags=parse_tags("env:t"))
            server.reshard.state = "cutover"
            with pytest.raises(ReshardRetry):
                server.query_plane.query(spec)
            # the alert engine path: a tick mid-cutover raises the
            # typed retry, which the loop's `except QueryError` catch
            # swallows (pinned by the issubclass assert above) — the
            # alert loop cannot be crashed by a topology swap
            server.alerts.configure([
                {"id": "r", "metric": "rs.c.0", "kind": "count",
                 "op": ">", "threshold": 0.5, "tags": "env:t"}])
            with pytest.raises(ReshardRetry):
                server.alerts.evaluate_once()
            server.reshard.state = "idle"
            assert server.alerts.evaluate_once() is not None
            assert server.query_plane.query(spec)["value"] is not None
        finally:
            _shutdown(server)

    def test_http_surface(self, tmp_path):
        """POST /reshard kicks a live split (202), /debug/reshard
        reports the state machine, and /query answers 503 + retry while
        a cutover is in flight."""
        from veneur_tpu.core.httpapi import HTTPApi
        server, obs = mk_server(**{"tpu.shards": 2},
                                reshard_spool_dir=str(tmp_path / "wal"))
        api = None
        try:
            _feed(server, corpus(0))
            api = HTTPApi(server.config, server=server,
                          address="127.0.0.1:0")
            api.start()
            host, port = api.address

            def get(path):
                try:
                    with urllib.request.urlopen(
                            f"http://{host}:{port}{path}", timeout=10) as r:
                        return r.status, r.read()
                except urllib.error.HTTPError as e:
                    return e.code, e.read()

            def post(path, payload):
                req = urllib.request.Request(
                    f"http://{host}:{port}{path}",
                    data=json.dumps(payload).encode(),
                    headers={"Content-Type": "application/json"})
                try:
                    with urllib.request.urlopen(req, timeout=10) as r:
                        return r.status, r.read()
                except urllib.error.HTTPError as e:
                    return e.code, e.read()

            status, body = get("/debug/reshard")
            assert status == 200
            assert json.loads(body)["state"] == "idle"

            # typed retry through HTTP while a cutover is in flight
            server.reshard.state = "cutover"
            status, body = get("/query?metric=rs.c.0&kind=count&tags=env:t")
            assert status == 503
            payload = json.loads(body)
            assert payload["retry"] is True
            server.reshard.state = "idle"

            status, body = post("/reshard", {"shards": 3})
            assert status == 202, body
            assert json.loads(body)["target_shards"] == 3
            # a second request while one is running is refused
            status, body = post("/reshard", {"shards": 4})
            assert status == 409, body
            assert wait_until(lambda: server.reshard.epoch == 1
                              and server.reshard.state == "idle")
            assert server.store.shard_plane.n == 3
            status, body = get("/debug/reshard")
            assert json.loads(body)["cutovers"] == 1

            status, body = post("/reshard", {"shards": "bogus"})
            assert status == 400
        finally:
            if api is not None:
                api.stop()
            _shutdown(server)

    def test_telemetry_rows_inventory(self):
        """Every reshard.* self-metric in the README inventory is
        emitted by the collector (names drift-pinned here; the
        inventory lint pins the docs side)."""
        server, _ = mk_server(**{"tpu.shards": 2})
        try:
            names = {row[0] for row in server.reshard.telemetry_rows()}
            assert names == {
                "reshard.state", "reshard.epoch", "reshard.cutovers",
                "reshard.last_cutover_seconds",
                "reshard.segments_written", "reshard.replayed_segments",
                "reshard.append_faults", "reshard.capture_failures",
                "reshard.device_losses", "reshard.inflight_metrics"}
        finally:
            _shutdown(server)


# ---------------------------------------------------------------------------
# proxy tier: ShardGroupRing regroup
# ---------------------------------------------------------------------------


def _keys(n=10_000):
    return [f"svc.metric.{i}|host:h{i % 97}" for i in range(n)]


class TestShardGroupRegroup:
    def _ring(self, groups, members, pins=()):
        ring = ShardGroupRing(groups)
        for member, group in pins:
            ring.assign(member, group)
        for member in members:
            ring.add(member)
        return ring

    def test_identity_roundtrip_after_churn(self):
        """G -> G regroup is the identity — even after ejection /
        readmission churn — for pinned AND hash-assigned members."""
        members = [f"10.0.0.{i}:8128" for i in range(9)]
        pins = [(members[i], i % 3) for i in range(4)]
        ring = self._ring(3, members, pins)
        ring.remove(members[2])
        ring.add(members[2])
        before = {k: ring.get(k) for k in _keys()}
        assert ring.regroup(3) == 0
        assert {k: ring.get(k) for k in _keys()} == before

    def test_regroup_converges_with_fresh_ring(self):
        """A regrouped proxy and a freshly-started proxy at G' must
        agree on every key — the fleet regroups without coordination,
        so both derivations of (address -> group) must match."""
        members = [f"10.0.1.{i}:8128" for i in range(10)]
        pins = [(members[0], 2), (members[1], 5)]
        ring = self._ring(3, members, pins)
        moved = ring.regroup(5)
        fresh = self._ring(5, members, pins)
        assert moved >= 0
        keys = _keys()
        assert [ring.get(k) for k in keys] == [fresh.get(k) for k in keys]

    def test_nonmigrating_keys_keep_owner_exactly(self):
        """The sticky-assignment pin: across G=3 -> G'=4, every key
        whose new group's member set equals its old group's member set
        keeps its owner EXACTLY (ring points are a pure function of
        group membership). Members are pinned to groups 0..2, which
        survive the widening unchanged — so the property provably
        bites on the whole first quarter of the digest space."""
        members = [f"10.0.2.{i}:8128" for i in range(12)]
        ring = self._ring(3, members,
                          pins=[(m, i % 3)
                                for i, m in enumerate(members)])
        old_sets = {g: set(ms) for g, ms in
                    enumerate(ring.group_members())}
        keys = _keys()
        before = {}
        for k in keys:
            p = ring.point_of(k)
            before[k] = (ring.group_of_point(p), ring.get_at(p))
        ring.regroup(4)
        new_sets = {g: set(ms) for g, ms in
                    enumerate(ring.group_members())}
        checked = 0
        for k in keys:
            p = ring.point_of(k)
            old_group, old_owner = before[k]
            if new_sets[ring.group_of_point(p)] == old_sets[old_group]:
                assert ring.get_at(p) == old_owner, k
                checked += 1
        # the property must actually bite on a real fraction of keys
        assert checked > len(keys) // 20, checked

    def test_clean_regroup_is_spill_free(self):
        """After a regroup that leaves every group populated, no key
        routes off-range: the pool's group_spill counter stays 0 over
        10k routed points."""
        from veneur_tpu.proxy.destinations import Destinations
        pool = Destinations(shard_groups=3)
        members = [f"10.0.3.{i}:8128" for i in range(12)]
        for m in members:
            pool.ring.add(m)
        moved = pool.regroup(4)
        assert pool.shard_groups == 4 and pool.ring.groups == 4
        assert all(pool.ring.group_members()), \
            "regroup left an empty group; spill check would be vacuous"
        for k in _keys():
            point = pool.ring.point_of(k)
            with pool._lock:
                pool._note_group_spill(point, pool.ring.get_at(point))
        assert pool.group_spill_total == 0
        assert moved >= 0

    def test_regroup_refuses_flat_ring(self):
        from veneur_tpu.proxy.destinations import Destinations
        pool = Destinations(shard_groups=0)  # plain ConsistentRing
        with pytest.raises(ValueError):
            pool.regroup(4)


# ---------------------------------------------------------------------------
# SIGKILL soak: the real kill -9 mid-cutover loop (slow)
# ---------------------------------------------------------------------------


@pytest.mark.slow
class TestReshardSoak:
    def test_sigkill_mid_cutover_soak(self):
        """Drive scripts/reshard_soak.py: SIGKILL a real mesh child
        mid-cutover (range segments durable, merge held open in the
        chaos seam), restart at the OLD shard count, replay — the
        flush diffs clean against the never-resharded control."""
        import importlib.util
        import os
        spec = importlib.util.spec_from_file_location(
            "reshard_soak",
            os.path.join(os.path.dirname(__file__), os.pardir,
                         "scripts", "reshard_soak.py"))
        soak = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(soak)
        report = soak.run_soak(kills=1)
        assert report["kills"] == 1 and report["restarts"] == 1
        # nonempty and already diffed bit-identical inside run_soak
        assert all(r["rows"] > 0 for r in report["rounds"])
