"""The server's one warm-up rule (`Server._warmup`, `prewarm_rung`,
`warm_programs`) and the compaction counters of the digest table.

(a) once the warm-up thread has ended, the first batch, the first
    staging overflow (one key past 128 samples: `compact`), a key dense
    within one batch (the k-bucket fold), the first flush with data and
    the first flush over recycled generations compile nothing, by JAX's
    own compile events (what the benchmark's `harness/sut.py`
    `CompileMeter` counts), on one device and on four;
(b) a timers-only server fed over UDP, whole-table compacts included,
    against `ops/tdigest_ref.py`, and `ingest.tdigest.compacts_total`
    equal to the overflows the traffic forced;
(c) the `warmup` event, `warmup.seconds_total{family}` and the
    compaction rows at `/metrics` (the dense keys' and the compacts'
    chip seconds with (a)'s dense key);
(d) a program of the list that raises is a `warmup_failed` event, and
    the other families are still warmed.

Capacities here are ones no other test file uses, so that a program
another test left in this worker's jit cache cannot make (a) pass.
"""

import socket
import time

import jax
import jax.monitoring
import numpy as np
import pytest

from veneur_tpu.core import columnstore
from veneur_tpu.core.server import Server
from veneur_tpu.ops import tdigest_ref
from veneur_tpu.sinks.channel import ChannelMetricSink
from veneur_tpu.util import http as vhttp

from test_server import generate_config

PERCENTILES = (0.5, 0.9, 0.99)
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
# the two limits of benchmark/configs/timers100k.json that are not exact
TIMER_RANK_GAP = 0.02
COLD_TIMER_REL_GAP = 1.5e-6


class Compiles:
    """Backend compiles since `__enter__`, from JAX's monitoring events."""

    def __init__(self):
        self.count = 0

    def _note(self, event, duration, **_kw):
        if event == COMPILE_EVENT:
            self.count += 1

    def __enter__(self):
        jax.monitoring.register_event_duration_secs_listener(self._note)
        return self

    def __exit__(self, *exc):
        jax.monitoring.unregister_event_duration_listener(self._note)


def config(shards: int, caps, **overrides):
    cfg = generate_config(**{
        "interval": 60.0, "percentiles": list(PERCENTILES),
        "http_address": "127.0.0.1:0", **overrides})
    tpu = cfg.tpu
    tpu.shards = shards
    (tpu.counter_capacity, tpu.gauge_capacity, tpu.histo_capacity,
     tpu.set_capacity, tpu.llhist_capacity, tpu.batch_cap) = caps
    return cfg


def started(cfg):
    sink = ChannelMetricSink()
    server = Server(cfg, extra_metric_sinks=[sink])
    server.start()
    server._warmup_thread.join(300)
    assert not server._warmup_thread.is_alive()
    return server, sink


def stop(server):
    server.config.flush_on_shutdown = False
    server.shutdown()


def events(server, kind):
    return [e for e in server.telemetry.events.snapshot()
            if e["kind"] == kind]


def metric_rows(server, prefix):
    """{row with its labels: value} of the /metrics rows under `prefix`."""
    body = vhttp.get("http://%s:%d/metrics"
                     % tuple(server.http_api.address[:2]))[1].decode()
    return {line.rsplit(" ", 1)[0]: float(line.rsplit(" ", 1)[1])
            for line in body.splitlines() if line.startswith(prefix)}


def feed(server, lines):
    for line in lines:
        server.handle_metric_packet(line)
    server.store.apply_all_pending()


def one_interval(k: int):
    """Counters, gauges, a set, cold timers and one timer key of 100
    samples: sent twice in an interval, the second batch finds 100
    staged slots and overflows the key's 128."""
    lines = [b"w.c.%d:%d|c" % (i, k + i) for i in range(20)]
    lines += [b"w.g.%d:%d.25|g" % (i, k + i) for i in range(10)]
    lines += [b"w.s:m%d|s" % i for i in range(5)]
    lines += [b"w.cold.%d:%d.5|ms" % (i, 3 * i + j)
              for i in range(30) for j in range(3)]
    lines += [b"w.hot:%d.125|ms" % (k * 100 + j) for j in range(100)]
    return lines


# -- (a) ---------------------------------------------------------------------

@pytest.mark.parametrize("shards,caps", [
    (1, (96, 80, 160, 48, 24, 352)),
    pytest.param(4, (112, 72, 176, 40, 24, 368), marks=pytest.mark.mesh)])
def test_nothing_compiles_after_the_warm_up(shards, caps):
    with Compiles() as warming:
        server, sink = started(config(shards, caps))
    try:
        # the warm-up itself compiled: the shapes are this test's own
        [warmup] = events(server, "warmup")
        assert warming.count >= len(warmup["programs"]) >= 13
        assert not events(server, "warmup_failed")
        histos = server.store.histos
        with Compiles() as after:
            for k in range(3):
                # first batch; then the first overflow, on the live path
                feed(server, one_interval(k))
                assert sum(histos.compacts_total.values()) == 2 * k  # none
                feed(server, one_interval(k))
                assert histos.compacts_total["live"] == k + 1
                # and once more under the flush: the last pending batch
                # overflows on the captured generation
                for line in one_interval(k)[-100:]:
                    server.handle_metric_packet(line)
                # first flush with data (k = 0), first over recycled
                # generations (k = 1), one more
                server.flush()
                assert histos.compacts_total == {"live": k + 1,
                                                 "readout": k + 1}
                got = {m.name: m.value for m in sink.wait_flush(30.0)}
                assert got["w.hot.count"] == 300.0
                assert got["w.hot.max"] == k * 100 + 99.125
                assert got["w.c.3"] == 2.0 * (k + 3)
                assert got["w.g.9"] == k + 9.25
                assert got["w.s"] == 5.0
                assert got["w.cold.7.min"] == 21.5
        assert after.count == 0
    finally:
        stop(server)


@pytest.mark.parametrize("shards,caps", [
    (1, (184, 168, 144, 44, 36, 328)),
    pytest.param(4, (192, 160, 152, 52, 44, 312), marks=pytest.mark.mesh)])
def test_a_dense_key_compiles_nothing_and_its_counters_reach_metrics(
        shards, caps):
    """(a) for a key dense within one batch (200 samples of one timer, past
    its 128 staging slots: `host_slots` folds them into batch-local
    k-buckets and marks the row full), the compact its next batch forces,
    and one more under the flush: nothing compiles, and both counters of
    the digest table are at `/metrics` with the observatory on, by path."""
    server, sink = started(config(shards, caps))
    try:
        histos = server.store.histos
        dense = [b"w.dense:%d.25|ms" % j for j in range(200)]
        with Compiles() as after:
            feed(server, dense)
            feed(server, dense)
            for line in dense:
                server.handle_metric_packet(line)
            server.flush()
            got = {m.name: m.value for m in sink.wait_flush(30.0)}
        assert after.count == 0
        assert got["w.dense.count"] == 600.0
        assert got["w.dense.max"] == 199.25 and got["w.dense.min"] == 0.25
        assert histos.dense_keys_total == {"live": 2, "readout": 1}
        assert histos.compacts_total == {"live": 1, "readout": 1}
        rows = metric_rows(server, "veneur_ingest_tdigest_")
        for path, n in (("live", 2), ("readout", 1)):
            assert rows['veneur_ingest_tdigest_dense_keys_total'
                        '{path="%s"}' % path] == n
            # the flush's join covered the compacts handed over before
            # its readout: both are stamped
            assert rows['veneur_ingest_tdigest_compact_chip_seconds_total'
                        '{path="%s"}' % path] > 0
    finally:
        stop(server)


def test_the_set_banks_slot_ladder_is_warmed_up_to_its_cap():
    """(a) for the set bank's slot ladder: with `set_max_dev_slots` past
    the first rung, the warm-up compiles every rung promotions can
    reach (the climb, the apply, the backlog's fold, the next swap's
    fresh generation, the estimate; the first rung's fold too), and
    2,100 keys promoted in one interval, twice, compile
    nothing: the first interval climbs 256 -> 2,048 -> 2,100 on the
    live path, the second starts at the top rung."""
    cfg = config(1, (136, 104, 200, 2100, 28, 392))
    cfg.tpu.set_max_dev_slots = 2100
    cfg.tpu.set_promote_samples = 2
    server, sink = started(cfg)
    try:
        [warmup] = events(server, "warmup")
        assert [p["program"] for p in warmup["programs"]
                if p["family"] == "set"] == [
            "apply", "fold@256", "readout"] + [
            f"{program}@{rung}" for rung in (2048, 2100)
            for program in ("climb", "apply", "fold", "fresh", "readout")]
        sets = server.store.sets
        with Compiles() as after:
            for k in range(2):
                feed(server, [b"w.ladder.%d:m%d|s" % (i, 2 * k + j)
                              for i in range(2100) for j in range(2)])
                server.flush()
                got = {m.name: m.value for m in sink.wait_flush(30.0)}
                assert got["w.ladder.0"] == got["w.ladder.2099"] == 2.0
                assert sets._dev_cap == 2100
        assert after.count == 0
        assert sets.slot_ladder_climbs_total == 2
        assert sets.device_rows_total == 2 * 2100
    finally:
        stop(server)


# -- (b) ---------------------------------------------------------------------

def test_timers_only_server_over_udp_against_the_reference():
    """Three tiers, as `each-timer-per-interval` has them: two keys
    that pass their 128 staging slots several times an interval (every
    time, the whole table compacts), 30 warm keys of 33 samples, 200
    cold keys of 3. Sent in four phases of 100 samples a hot key; each
    phase after the first finds 72 to 100 of a hot key's slots staged,
    so exactly one batch of it overflows, however the pump cut it."""
    rng = np.random.default_rng(2_147_484_736)
    names = ([f"t.hot.{i}" for i in range(2)]
             + [f"t.warm.{i}" for i in range(30)]
             + [f"t.cold.{i}" for i in range(200)])
    per_phase = [100] * 2 + [0] * 230
    first_phase = [100] * 2 + [33] * 30 + [3] * 200
    server, sink = started(config(
        1, (64, 64, 288, 32, 16, 2048),
        statsd_listen_addresses=["udp://127.0.0.1:0"], num_readers=2))
    sock = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
    truth = {name: [] for name in names}
    try:
        address = tuple(server.local_addr("udp")[:2])
        histos = server.store.histos

        def send(counts):
            lines = []
            for name, n in zip(names, counts):
                vals = np.round(rng.lognormal(3.0, 1.0, n), 3)
                truth[name].extend(vals.tolist())
                lines += [f"{name}:{v:.3f}|ms|#env:t".encode()
                          for v in vals.tolist()]
            order = rng.permutation(len(lines))
            want = server.store.processed + len(lines)
            for at in range(0, len(lines), 20):
                sock.sendto(b"\n".join(lines[j] for j in order[at:at + 20]),
                            address)
                time.sleep(0.001)
            deadline = time.monotonic() + 30.0
            while server.store.processed < want:
                assert time.monotonic() < deadline, "datagrams lost"
                time.sleep(0.005)

        for phase in range(3):
            send(first_phase if phase == 0 else per_phase)
            server.store.apply_all_pending()
            assert histos.compacts_total == {"live": phase, "readout": 0}
        # the fourth phase stays pending: it overflows under the flush
        send(per_phase)
        server.flush()
        assert histos.compacts_total == {"live": 2, "readout": 1}
        assert histos.compact_seconds_total["live"] > 0
        assert histos.compact_seconds_total["readout"] > 0
        got = {m.name: m.value for m in sink.wait_flush(30.0)}
        rank_gap = 0.0
        for name in names:
            vals = np.asarray(truth[name])
            f32 = vals.astype(np.float32)
            # exact: float32 min/max select a sample, the count is a
            # whole number of unit weights
            assert got[f"{name}.min"] == float(f32.min()), name
            assert got[f"{name}.max"] == float(f32.max()), name
            assert got[f"{name}.count"] == float(vals.size), name
            ref = tdigest_ref.MergingDigest(100.0)
            for v in vals.tolist():
                ref.add(v)
            for p in PERCENTILES:
                have = got[f"{name}.{int(p * 100)}percentile"]
                if vals.size <= 8:
                    # a cold key's samples are centroids of their own:
                    # value for value, through the compacts
                    q = ref.quantile(p)
                    assert abs(have - q) <= COLD_TIMER_REL_GAP * abs(q), (
                        name, p, have, q)
                else:
                    rank_gap = max(rank_gap, abs(ref.cdf(have) - p))
        assert 0 < rank_gap <= TIMER_RANK_GAP
        # the compacts at /metrics, by path
        rows = metric_rows(server, "veneur_ingest_tdigest_")
        assert rows['veneur_ingest_tdigest_compacts_total{path="live"}'] == 2
        assert rows[
            'veneur_ingest_tdigest_compacts_total{path="readout"}'] == 1
        assert rows[
            'veneur_ingest_tdigest_compact_seconds_total{path="live"}'] > 0
    finally:
        sock.close()
        stop(server)


# -- (c) ---------------------------------------------------------------------

def test_the_warmup_event_lists_every_program_and_metrics_carry_its_seconds():
    server, _sink = started(config(1, (104, 88, 168, 56, 24, 336)))
    try:
        [warmup] = events(server, "warmup")
        listed = [(p["family"], p["program"]) for p in warmup["programs"]]
        assert listed == [
            (family, wp.program)
            for family, table in server.store.tables()
            for wp in table.warm_programs(PERCENTILES, False)]
        assert listed == [
            ("counter", "apply"), ("counter", "reset"),
            ("gauge", "apply"), ("gauge", "reset"),
            ("histogram", "apply"), ("histogram", "compact"),
            ("histogram", "readout"), ("histogram", "reset"),
            ("llhist", "apply"), ("llhist", "readout"), ("llhist", "reset"),
            ("set", "apply"), ("set", "fold@56"), ("set", "readout")]
        for p in warmup["programs"]:
            assert p["seconds"] > 0
            # the CPU backend keeps no persistent cache (compilecache.py)
            assert (p["cache_hits"], p["cache_misses"]) == (0, 0)
        assert warmup["seconds"] >= sum(p["seconds"]
                                        for p in warmup["programs"])
        rows = metric_rows(server, "veneur_warmup_seconds_total")
        for family in ("counter", "gauge", "histogram", "llhist", "set"):
            want = sum(p["seconds"] for p in warmup["programs"]
                       if p["family"] == family)
            row = rows['veneur_warmup_seconds_total{family="%s"}' % family]
            assert row == pytest.approx(want, abs=1e-4)
        # the deviceobs registry got a compile per program
        compiles = server.deviceobs.kernel_report()["compiles"]
        assert compiles["histogram"] >= 4 and compiles["set"] >= 2
        # the watchdog's clock starts where the warm-up ended
        assert server.last_flush_unix >= warmup["ts"] - 1.0
    finally:
        stop(server)


def test_cache_events_count_this_threads_hits_and_misses(
        jax_cache_config, tmp_path):
    """What the `warmup` event's `cache_hits` / `cache_misses` are taken
    from: a compile the persistent cache did not hold is a miss, the same
    program compiled anew from disk a hit, and another thread's compile
    is not counted here."""
    import threading

    import jax.numpy as jnp

    from veneur_tpu.util import compilecache

    assert compilecache.enable(str(tmp_path / "jit-cache"))
    x = jnp.arange(211, dtype=jnp.float32)

    def compile_once():
        # a fresh jit wrapper each time: the process's jit cache cannot
        # serve it, the persistent one can
        jax.jit(lambda v: (v * 3.0 + 211.0).sum())(x).block_until_ready()

    hits, misses = compilecache.cache_events()
    compile_once()
    assert compilecache.cache_events() == (hits, misses + 1)
    compile_once()
    assert compilecache.cache_events() == (hits + 1, misses + 1)
    other = threading.Thread(target=compile_once)
    other.start()
    other.join()
    assert compilecache.cache_events() == (hits + 1, misses + 1)


# -- (d) ---------------------------------------------------------------------

def test_a_failed_warm_up_is_an_event_and_the_rest_is_still_warmed(
        monkeypatch, caplog):
    def refuse(*args):
        raise RuntimeError("the compiler refused")

    real = columnstore.HistoTable.warm_programs

    def programs(self, ps, need_export):
        return [wp._replace(fn=refuse) if wp.program == "compact" else wp
                for wp in real(self, ps, need_export)]

    monkeypatch.setattr(columnstore.HistoTable, "warm_programs", programs)
    with caplog.at_level("ERROR", logger="veneur_tpu.core.server"):
        server, _sink = started(config(1, (120, 88, 184, 56, 24, 344)))
    try:
        [failed] = events(server, "warmup_failed")
        assert (failed["family"], failed["program"]) == ("histogram",
                                                         "compact")
        assert "the compiler refused" in failed["error"]
        logged = [r for r in caplog.records
                  if "warm-up failed" in r.getMessage()]
        assert len(logged) == 1 and logged[0].exc_info
        [warmup] = events(server, "warmup")
        listed = [(p["family"], p["program"]) for p in warmup["programs"]]
        # the digest family stops at the program that failed; the
        # families after it are warmed all the same
        assert ("histogram", "apply") in listed
        assert ("histogram", "compact") not in listed
        assert ("histogram", "readout") not in listed
        assert ("llhist", "readout") in listed and ("set", "apply") in listed
        # and the server serves: the first overflow compiles late
        feed(server, one_interval(0))
        feed(server, one_interval(0))
        assert server.store.histos.compacts_total["live"] == 1
    finally:
        stop(server)
