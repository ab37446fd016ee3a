"""The four-shard deployment (`tpu.shards: 4`, benchmark config
`global100k-shards4`) at a small size, on four of the eight virtual CPU
devices `conftest.py` sets up.

Three things, each against the plain references (`ops/tdigest_ref.py`,
`ops/hll_ref.py`, `ops/llhist_ref.py`, exact sums), never against
another server:

(a) a started `Server` fed DogStatsD over UDP and flushed by its own
    clock-aligned loop, four consecutive flushes (the second is the first
    over a recycled spare generation), series for series;
(b) the share tied to the whole: each family's four per-shard partial
    states read one by one, every key on its home shard alone, and the
    parts combined by the family's rule equal to the reference;
(c) the spans and counters the mesh adds (`merge{family}`,
    `ingest.shard.route_seconds_total`, `mesh.merge_rounds`), present on
    the sharded server and absent on a one-shard one.

The traffic is `chip_smoke.Workload`'s seeded mix (what the chip runs at
100k keys). Every tolerance is written beside its comparison; (d) runs
(a)'s comparisons over a server whose t-digest centroid sums are
computed in bfloat16 and sees them fail.
"""

import json
import os
import socket
import sys
import time

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for path in (REPO, os.path.join(REPO, "benchmark")):
    if path not in sys.path:
        sys.path.insert(0, path)

import chip_smoke  # noqa: E402
from veneur_tpu.core.server import Server  # noqa: E402
from veneur_tpu.ops import (batch_tdigest, hll_ref, llhist_ref,  # noqa: E402
                            tdigest_ref)
from veneur_tpu.parallel import collectives  # noqa: E402
from veneur_tpu.sinks.channel import ChannelMetricSink  # noqa: E402
from veneur_tpu.util import http as vhttp  # noqa: E402

from test_flush_spans import (SET_CHILDREN, SWITCH_S, WATCHED,  # noqa: E402
                              _end)
from test_server import generate_config  # noqa: E402

SHARDS = 4
SIZES = {"counter": 400, "gauge": 200, "timer": 300, "set": 90, "llhist": 10}
PERCENTILES = chip_smoke.PERCENTILES
INTERVAL_S = 2.0
SEND_S = 0.7            # lines leave in the first 0.7 s after a tick
FLUSHES = 4
SEED = 2_147_484_653
FAMILIES = ("counter", "gauge", "histogram", "llhist", "set")
# the configuration's two limits that are not exact
# (benchmark/configs/global100k-shards4.json "limits")
TIMER_RANK_GAP = 0.02
COLD_TIMER_REL_GAP = 1.5e-6


def config(shards: int, **overrides):
    cfg = generate_config(**{
        "statsd_listen_addresses": ["udp://127.0.0.1:0"],
        "http_address": "127.0.0.1:0", "interval": INTERVAL_S,
        "num_readers": 2, "synchronize_with_interval": True,
        "percentiles": list(PERCENTILES), **overrides})
    tpu = cfg.tpu
    tpu.shards = shards
    tpu.counter_capacity, tpu.gauge_capacity = 512, 256
    tpu.histo_capacity, tpu.set_capacity, tpu.llhist_capacity = 512, 128, 16
    # a small batch: the seven very hot timer keys then overflow their
    # 128 staging slots over several applies and force `compact` on
    # their shard, as 100k keys do at the shipped 16,384
    tpu.batch_cap = 1024
    return cfg


class _TimedSink(ChannelMetricSink):
    """The channel sink, each flush stamped with its arrival: a flush
    belongs to the tick it arrived after."""

    def flush(self, metrics) -> None:
        self.queue.put((time.time(), list(metrics)))

    def flush_after(self, tick: float, timeout: float = 30.0) -> dict:
        """{name: value} of the first flush that arrives after `tick`."""
        deadline = time.monotonic() + timeout
        while True:
            arrived, metrics = self.queue.get(
                timeout=max(0.01, deadline - time.monotonic()))
            if arrived >= tick:
                return chip_smoke.flushed_values(metrics)


def run_server(shards: int, flushes: int, work) -> dict:
    """A started server, `flushes` intervals of `work` sent over UDP
    right after a tick each, flushed by the server's own loop. Returns
    each interval's truth and flushed values, the rounds of
    /debug/flush and two scrapes of /metrics around the traffic."""
    sink = _TimedSink()
    server = Server(config(shards), extra_metric_sinks=[sink])
    server.start()
    sock = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
    out = {"truth": [], "got": []}
    try:
        assert server._warmup_thread is not None
        server._warmup_thread.join(300)
        address = tuple(server.local_addr("udp")[:2])
        base = "http://%s:%d" % tuple(server.http_api.address[:2])
        plane = server.store.shard_plane
        assert (plane.n if plane is not None else 1) == shards

        def send(k: int, warm: bool = True) -> dict:
            truth = work.interval(k)
            datagrams = truth.pop("datagrams")
            # the flush of the tick just passed has swapped: these lines
            # belong to the interval that the next tick closes. Straight
            # on from the flush before where that came early enough, so
            # that consecutive ticks flush consecutive rounds
            if not 0.1 <= time.time() % INTERVAL_S <= 0.4:
                time.sleep(INTERVAL_S - time.time() % INTERVAL_S + 0.15)
            want = server.stats["packets_received"] + truth["lines"]
            t0 = time.monotonic()
            for i, d in enumerate(datagrams):
                sock.sendto(d, address)
                if i % 16 == 15:
                    time.sleep(SEND_S * 16 / len(datagrams))
            # a warm-up round compiles as it goes and may take (or lose)
            # what it likes; a compared one is read before its tick
            patience = INTERVAL_S - 0.6 if warm else 30.0
            while (server.stats["packets_received"] < want
                   and time.monotonic() - t0 < patience):
                time.sleep(0.005)
            truth["whole"] = (server.stats["packets_received"] == want
                              and time.monotonic() - t0 < INTERVAL_S - 0.4)
            assert truth["whole"] or not warm, (
                "datagrams lost on loopback, or read late", k,
                server.stats["packets_received"] - want)
            # the tick that closes the interval these lines were read in
            truth["tick"] = (time.time() // INTERVAL_S + 1) * INTERVAL_S
            return truth

        # warm-up: every key minted, every program compiled, until a
        # round is read whole inside its interval (not compared)
        for attempt in range(6):
            warmed = send(1000 + attempt, warm=False)
            if warmed["whole"]:
                break
        else:
            pytest.fail("no warm-up round was read whole")
        assert sink.flush_after(warmed["tick"], 60.0)
        out["scrapes"] = [chip_smoke.Api(server).get("/metrics").decode()]
        out["scraped_at"] = [time.time()]
        for k in range(flushes):
            truth = send(k)
            out["truth"].append(truth)
            out["got"].append(sink.flush_after(truth["tick"]))
        out["scrapes"].append(
            chip_smoke.Api(server).get("/metrics").decode())
        out["scraped_at"].append(time.time())
        rounds = json.loads(vhttp.get(
            base + "/debug/flush?n=64")[1])["rounds"]
        # every round between the two scrapes, and of those the rounds
        # of the ticks that closed a compared interval
        out["all_rounds"] = [r for r in rounds if out["scraped_at"][0]
                             <= r["start_unix"] <= out["scraped_at"][1]]
        out["rounds"] = [r for r in rounds if any(
            abs(r["start_unix"] - t["tick"]) < INTERVAL_S / 2
            for t in out["truth"])]
        out["store"] = server.store
    finally:
        sock.close()
        server.shutdown()
    return out


def compare_interval(work, truth: dict, got: dict) -> list:
    """One flushed interval against the references. Returns what
    disagrees (empty = the flush is right)."""
    wrong = []
    names = work.names

    # counters and gauges: exact. Digest-home routing puts every sample
    # of a key on one shard and the merge selects it (sums three zeros),
    # counters are whole numbers under 2**24, gauges quarter-integers
    for fam, want in (("counter", truth["counters"]),
                      ("gauge", truth["gauges"])):
        for name, w in zip(names[fam], want.tolist()):
            if got.get(name) != float(w):
                wrong.append((name, got.get(name), float(w)))

    for i, vals in truth["timers"].items():
        name = names["timer"][i]
        f32 = vals.astype(np.float32)
        # min, max, count: exact (float32 min/max select a sample, the
        # count is a whole number of unit weights)
        for stat, w in (("min", float(f32.min())), ("max", float(f32.max())),
                        ("count", float(vals.size))):
            if got.get(f"{name}.{stat}") != w:
                wrong.append((f"{name}.{stat}", got.get(f"{name}.{stat}"), w))
        ref = tdigest_ref.MergingDigest(100.0)
        for v in vals.tolist():
            ref.add(v)
        for p in PERCENTILES:
            series = f"{name}.{int(p * 100)}percentile"
            have = got.get(series)
            if have is None:
                wrong.append((series, None, ref.quantile(p)))
            elif vals.size <= 8:
                # up to 8 samples the digest holds every sample as its
                # own centroid, on one shard; the cross-shard merge must
                # hand them through unchanged, so the value itself is
                # held to the reference's: 1.5e-6 relative, the
                # configuration's `cold_timer_rel_gap` (float32 centroid
                # sums at Precision.HIGHEST read ~2e-7; one bfloat16
                # pass reads ~4e-3 and fails, see (d))
                q = ref.quantile(p)
                if abs(have - q) > COLD_TIMER_REL_GAP * abs(q):
                    wrong.append((series, have, q))
            # beyond, two correct digests agree in rank, not in value
            # (a hot key's shard compacts on a schedule of its own):
            # 0.02 in rank, the configuration's `timer_rank_gap`, which
            # is what tests/test_tdigest.py holds a digest to
            elif abs(ref.cdf(have) - p) > TIMER_RANK_GAP:
                wrong.append((series, have, ref.quantile(p)))

    # sets: the HLL registers merge by elementwise max over shards, of
    # which three hold zeros: the estimate equals the reference's. It
    # is a float32 sum floored to a whole number on the device, so it
    # may differ by one exactly where the reference's own pre-floor
    # value lies within 1e-3 of a whole number
    for i, members in truth["sets"].items():
        ref = hll_ref.HLL()
        for m in members:
            ref.insert(m.encode())
        want, have = ref.estimate(), got.get(names["set"][i])
        if have != want:
            regs = np.asarray(ref.regs)
            ez = float(np.count_nonzero(regs == 0))
            raw = (hll_ref._ALPHA * hll_ref.M * (hll_ref.M - ez)
                   / (hll_ref.beta14(ez)
                      + float(np.sum(np.exp2(-regs.astype(np.float64))))))
            if (have is None or abs(have - want) != 1.0
                    or abs(raw - round(raw)) >= 1e-3):
                wrong.append((names["set"][i], have, want))

    # llhists: int32 registers added over shards, exact; so the count
    # and the +Inf bucket are exact, the quantiles follow from the
    # registers (rtol 1e-5: float32 bin edges, tests/test_llhist.py's),
    # and `.sum` is a float64 dot over the registers on the host whose
    # order of summation follows the array's layout: 1e-12 relative,
    # the benchmark's tolerance (ROADMAP D16: not bit for bit)
    for i, vals in truth["llhists"].items():
        name = names["llhist"][i]
        ref = llhist_ref.LLHist()
        ref.insert_many(vals)
        for series in (f"{name}.count", f"{name}.bucket|le:+Inf"):
            if got.get(series) != float(ref.count()):
                wrong.append((series, got.get(series), float(ref.count())))
        have = got.get(f"{name}.sum")
        if have is None or not np.isclose(have, ref.sum(), rtol=1e-12,
                                          atol=0.0):
            wrong.append((f"{name}.sum", have, ref.sum()))
        for p, want in zip(PERCENTILES, ref.quantiles(PERCENTILES)):
            series = f"{name}.{int(p * 100)}percentile"
            have = got.get(series)
            if have is None or not np.isclose(have, want, rtol=1e-5,
                                              atol=0.0):
                wrong.append((series, have, float(want)))
    return wrong


@pytest.fixture(scope="module")
def work():
    work = chip_smoke.Workload(SEED, SIZES)
    # a few keys past the 128 staging slots, a few hot, the rest cold
    assert 4 <= int((work.timer_samples > batch_tdigest.C).sum()) <= 8
    assert int((work.timer_samples <= 8).sum()) > 250
    # every timer, set and llhist key has its reference
    assert work.timer_check.size == SIZES["timer"]
    assert work.set_check.size == SIZES["set"]
    assert work.llhist_check.size == SIZES["llhist"]
    return work


@pytest.fixture(scope="module")
def mesh(work):
    return run_server(SHARDS, FLUSHES, work)


# -- (a) the server's own flush loop against the references ----------------

@pytest.mark.parametrize("k", range(FLUSHES))
def test_flush_of_the_servers_own_loop_meets_the_references(work, mesh, k):
    truth, got = mesh["truth"][k], mesh["got"][k]
    expected = (SIZES["counter"] + SIZES["gauge"] + SIZES["set"]
                + SIZES["timer"] * (3 + len(PERCENTILES)))
    assert len(got) >= expected
    wrong = compare_interval(work, truth, got)
    assert not wrong, (len(wrong), wrong[:5])


def test_every_flush_ran_on_a_tick_over_recycled_spares(mesh):
    import jax

    rounds = mesh["rounds"]
    assert len(rounds) == FLUSHES
    for r in rounds:
        # the loop's own tick, not a hand-called flush: within a fifth
        # of a second of a multiple of the interval on a loaded host
        late = r["start_unix"] % INTERVAL_S
        assert min(late, INTERVAL_S - late) < 0.2, late
    store = mesh["store"]
    for family, table in store.tables():
        if family == "status":
            continue
        # the generation ladder is live / spare: after four flushes the
        # next swap still finds a recycled spare of the right shape
        assert table._spare is not None, family
        assert table._spare_cap == table._state_capacity(), family
        # and both generations still lie one shard to a device (a
        # stacked spare that came back replicated would put all four
        # shards' rows, and their scatters, on every device)
        devices = list(store.shard_plane.devices)
        for generation in (table._devobs_state(), table._spare):
            for leaf in jax.tree.leaves(generation):
                if len(leaf.sharding.device_set) == 1:   # per-device list
                    continue
                assert [s.device for s in leaf.addressable_shards] == devices
                assert all(s.data.shape[0] == 1
                           for s in leaf.addressable_shards), (family, leaf)
        per_device = getattr(table, "states", None)
        if per_device is not None:
            assert [next(iter(jax.tree.leaves(s)[0].devices()))
                    for s in per_device] == devices, family


# -- (b) the shares tied to the whole --------------------------------------

@pytest.fixture(scope="module")
def parts(work):
    """A four-shard server that is never started or flushed: one
    interval's lines handed to it, pending batches applied, and each
    shard's partial state brought to the host."""
    import jax

    server = Server(config(SHARDS, statsd_listen_addresses=[],
                           http_address=""))
    truth = work.interval(7)
    for datagram in truth.pop("datagrams"):
        server.handle_packet_buffer(datagram)
    store = server.store
    out = {"truth": truth, "rows": {}, "home": {}}
    try:
        for family, table in store.tables():
            if family == "status":
                continue
            with table.lock:
                while table._n:
                    table._dispatch_pending_locked()
            rows = {table.meta[row].name: row
                    for row in table.rows.values()}
            digests = np.array(
                [(table._dict_key_of[row] >> 2) & (2**64 - 1)
                 for row in range(len(table.meta))], np.uint64)
            # the configuration's rule, computed here from the digests
            home = collectives.home_shards(digests, SHARDS)
            assert (table._shard_of[:home.size] == home).all(), family
            out["rows"][family], out["home"][family] = rows, home
            state = table.state if table.state is not None else None
            if state is not None:     # stacked (4, K, ...) leaves
                out[family] = jax.tree.map(np.asarray, state)
            else:                     # one state per device
                assert [next(iter(jax.tree.leaves(s)[0].devices()))
                        for s in table.states] == list(
                            store.shard_plane.devices)
                out[family] = [jax.tree.map(np.asarray, s)
                               for s in table.states]
    finally:
        server.shutdown()
    return out


def _on_home_alone(present: np.ndarray, home: np.ndarray, family: str):
    """`present[s, row]`: shard s holds something for the row. Every
    live row is on its home shard, and on no other."""
    shards, rows = np.nonzero(present[:, :home.size])
    assert set(rows.tolist()) == set(range(home.size)), family
    assert (shards == home[rows]).all(), family
    # and the key space really is spread: no shard is empty (ten
    # llhist keys may miss one)
    assert len(set(home.tolist())) >= min(SHARDS, home.size // 4), family


def test_counter_parts_sum_to_the_exact_totals(work, parts):
    state, home = parts["counter"], parts["home"]["counter"]
    _on_home_alone(state["sum"] != 0, home, "counter")
    # the family's rule: sum over shards (the Kahan pair's f64 readout)
    whole = (state["sum"].astype(np.float64).sum(0)
             - state["comp"].astype(np.float64).sum(0))
    for name, want in zip(work.names["counter"],
                          parts["truth"]["counters"].tolist()):
        assert whole[parts["rows"]["counter"][name]] == float(want)  # exact


def test_gauge_parts_select_the_last_write(work, parts):
    state, home = parts["gauge"], parts["home"]["gauge"]
    _on_home_alone(state["set"], home, "gauge")
    # the family's rule: selection of the one shard whose mask is set
    whole = np.where(state["set"], state["value"], 0.0).sum(0)
    for name, want in zip(work.names["gauge"],
                          parts["truth"]["gauges"].tolist()):
        assert whole[parts["rows"]["gauge"][name]] == want  # exact


def test_set_parts_max_to_the_reference_registers(work, parts):
    banks, home = np.stack(parts["set"]), parts["home"]["set"]
    _on_home_alone(banks.any(axis=2), home, "set")
    whole = banks.max(axis=0)       # the family's rule: register max
    for i, members in parts["truth"]["sets"].items():
        ref = hll_ref.HLL()
        for m in members:
            ref.insert(m.encode())
        row = parts["rows"]["set"][work.names["set"][i]]
        assert (whole[row] == np.asarray(ref.regs)).all()  # registers exact


def test_llhist_parts_add_to_the_reference_registers(work, parts):
    regs, home = parts["llhist"], parts["home"]["llhist"]
    _on_home_alone(regs.any(axis=2), home, "llhist")
    whole = regs.sum(axis=0)        # the family's rule: register add
    for i, vals in parts["truth"]["llhists"].items():
        ref = llhist_ref.LLHist()
        ref.insert_many(vals)
        row = parts["rows"]["llhist"][work.names["llhist"][i]]
        assert (whole[row, :llhist_ref.BINS] == ref.bins).all()  # exact
        assert not whole[row, llhist_ref.BINS:].any()


def test_tdigest_parts_concatenate_and_recompress_to_the_reference(
        work, parts):
    states, home = parts["histogram"], parts["home"]["histogram"]
    weight = np.stack([s["weights"].sum(1) + s["sweights"].sum(1)
                       for s in states])
    _on_home_alone(weight > 0, home, "histogram")
    for i, vals in parts["truth"]["timers"].items():
        row = parts["rows"]["histogram"][work.names["timer"][i]]
        # the family's rule: every shard's centroids (compacted and
        # staged) concatenated, then recompressed: here by the scalar
        # reference digest, fed the centroids in order of their means
        means, weights = [], []
        for s in states:
            for wv, w in ((s["wv"][row], s["weights"][row]),
                          (s["swv"][row], s["sweights"][row])):
                keep = w > 0
                means += (wv[keep] / w[keep]).tolist()
                weights += w[keep].tolist()
        # unit weights are whole numbers in float32: the count is exact
        assert sum(weights) == float(vals.size)
        assert min(s["dmin"][row] for s in states) == np.float32(vals.min())
        assert max(s["dmax"][row] for s in states) == np.float32(vals.max())
        whole = tdigest_ref.MergingDigest(100.0)
        for m, w in sorted(zip(means, weights)):
            whole.add(m, w)
        ref = tdigest_ref.MergingDigest(100.0)
        for v in vals.tolist():
            ref.add(v)
        for p in PERCENTILES:
            if vals.size <= 8:
                # every sample its own centroid, a float32 each
                assert abs(whole.quantile(p) - ref.quantile(p)) <= \
                    COLD_TIMER_REL_GAP * abs(ref.quantile(p))
            else:
                # 0.02 in rank: the configuration's digest bound
                assert abs(ref.cdf(whole.quantile(p)) - p) <= TIMER_RANK_GAP


# -- (c) the mesh's spans and counters -------------------------------------

def _rows(scrape: str, name: str) -> dict:
    return {line.rsplit(" ", 1)[0]: float(line.rsplit(" ", 1)[1])
            for line in scrape.splitlines()
            if line.split("{", 1)[0].split(" ", 1)[0] == name}


def test_each_family_has_one_merge_span_under_its_dispatch(mesh):
    for r in mesh["rounds"]:
        merges = [s for s in r["spans"] if s["name"] == "merge"]
        assert sorted(s["family"] for s in merges) == sorted(FAMILIES)
        assert all(s["parent"] == "dispatch" for s in merges)
        assert r["phases"]["merge_s"] == pytest.approx(
            sum(s["wall_s"] for s in merges), rel=1e-6, abs=1e-6)
        dispatch = {s["family"]: s for s in r["spans"]
                    if s["name"] == "dispatch"}
        for s in merges:     # inside its family's dispatch span
            outer = dispatch[s["family"]]
            assert outer["start_s"] <= s["start_s"] + 1e-6
            assert (s["start_s"] + s["wall_s"]
                    <= outer["start_s"] + outer["wall_s"] + 1e-6)
        assert r["phases"]["merge_s"] <= r["phases"]["dispatch_s"]
        # one sync span per family per device that holds a handle
        syncs = [s for s in r["spans"] if s["name"] == "sync"]
        assert {s["family"] for s in syncs} == {
            "counter", "gauge", "histogram", "llhist"}


# ISSUE 39: the readout's completion stamps and `dispatch{set}` in parts
# (the one-device server's are tests/test_flush_spans.py's)

@pytest.mark.parametrize("shard", range(SHARDS))
def test_each_device_has_a_stamp_a_family_in_dispatch_order(mesh, shard):
    """20 completion stamps a round: the four merged families' handles
    are replicated, and so is the estimate of the merged register bank
    (every device computes it), so the sets' one wait stamps all four.
    On each device the spans follow each other in the order the
    families were dispatched, inside `readout`, closed before the
    drained generations are recycled (ISSUE 40: the watcher is joined
    once the sets' estimate has been collected in `assembly_set`)."""
    for r in mesh["rounds"]:
        busy = [s for s in r["spans"] if s["name"] == "chip_busy"]
        assert len(busy) == len(WATCHED) * SHARDS
        devices = sorted({s["device"] for s in busy})
        assert len(devices) == SHARDS
        mine = [s for s in busy if s["device"] == devices[shard]]
        assert [s["family"] for s in mine] == list(WATCHED)
        [readout] = [s for s in r["spans"] if s["name"] == "readout"]
        [recycle] = [s for s in r["spans"] if s["name"] == "recycle"]
        assert all(s["parent"] == "readout" for s in mine)
        assert readout["start_s"] <= mine[0]["start_s"]
        for before, after in zip(mine, mine[1:]):
            assert _end(before) <= after["start_s"] + 1e-9
        assert _end(mine[-1]) <= recycle["start_s"] + 1e-6
        assert r["phases"]["chip_busy_s"] == pytest.approx(
            sum(s["wall_s"] for s in busy), abs=1e-5)
        # device seconds, summed over the devices
        assert r["phases"]["chip_busy_s"] <= SHARDS * readout["wall_s"]


def test_dispatch_set_is_its_four_parts_and_the_merge(mesh):
    """`dispatch{set}` is the routed fold, the merge and the fold's last
    step, the merged bank's estimate dispatched; the wait for that
    estimate, its copy and the provider follow in `assembly_set`, and
    only then are the per-device states, the merge's inputs, recycled
    (ISSUE 40)."""
    for r in mesh["rounds"]:
        [outer] = [s for s in r["spans"] if s["name"] == "dispatch"
                   and s["family"] == "set"]
        [collected] = [s for s in r["spans"] if s["name"] == "assembly_set"]
        [recycle] = [s for s in r["spans"] if s["name"] == "recycle"]
        parts = sorted((s for s in r["spans"]
                        if s["name"] in SET_CHILDREN + ("merge",)
                        and s["family"] == "set"),
                       key=lambda s: s["start_s"])
        # the last pending batch routed and applied, the merge's own
        # span, the estimate's dispatch, then the wait for it
        assert [s["name"] for s in parts] == [
            "set_fold", "merge", "set_fold", "set_wait", "set_transfer",
            "set_host_estimate"]
        assert [s["parent"] for s in parts] == [
            "dispatch", "dispatch", "dispatch", "assembly_set",
            "assembly_set", "assembly_set"]
        assert outer["start_s"] <= parts[0]["start_s"] + 1e-6
        assert _end(parts[2]) <= _end(outer) + 1e-6
        assert collected["start_s"] <= parts[3]["start_s"] + 1e-6
        assert _end(parts[-1]) <= _end(collected) + 1e-6
        assert _end(parts[-1]) <= recycle["start_s"] + 1e-6
        for before, after in zip(parts, parts[1:]):
            assert _end(before) <= after["start_s"] + 1e-6
        covered = sum(s["wall_s"] for s in parts[:3])
        assert outer["wall_s"] - covered <= SWITCH_S, (outer, parts)
        p = r["phases"]
        assert p["chip_wait_s"] == pytest.approx(
            p["sync_s"] + p["set_wait_s"], abs=2e-6)


def test_sharded_set_table_counts_its_fold_and_rows_as_one_device_does(
        mesh):
    """The set counters on the dense sharded bank: the fold is the
    last pending batch's apply, one dispatch a shard it reaches; every
    touched row is estimated on the device, none on the host; the bank
    never climbs; `set_chip_busy_s` sums the sets' stamps of every
    device."""
    sets = mesh["store"].sets
    assert sets.host_rows_total == 0 and sets.slot_ladder_climbs_total == 0
    assert sets.device_rows_total >= SIZES["set"] * FLUSHES
    # one readout a flush of an interval with set keys
    assert 0 < sets.fold_dispatches_total <= (
        SHARDS * sets.deferred_estimates_total)
    assert sets.fold_entries_total >= sets.fold_dispatches_total
    for r in mesh["rounds"]:
        busy = [s["wall_s"] for s in r["spans"]
                if s["name"] == "chip_busy" and s["family"] == "set"]
        assert len(busy) == SHARDS
        assert r["phases"]["set_chip_busy_s"] == pytest.approx(sum(busy),
                                                               abs=1e-5)


def test_readout_kernel_row_counts_a_stamp_a_device(mesh):
    before, after = mesh["scrapes"]
    stamps0 = _rows(before, "veneur_device_kernel_readout_s_count_total")
    stamps1 = _rows(after, "veneur_device_kernel_readout_s_count_total")
    assert {k.split('family="')[1].split('"')[0] for k in stamps1} == set(
        WATCHED)
    per_family = {}
    for r in mesh["all_rounds"]:
        for s in r["spans"]:
            if s["name"] == "chip_busy":
                per_family[s["family"]] = per_family.get(s["family"], 0) + 1
    for key, value in stamps1.items():
        family = key.split('family="')[1].split('"')[0]
        assert value - stamps0.get(key, 0.0) == per_family[family] \
            >= SHARDS * FLUSHES, key


def test_mesh_rows_are_on_metrics_and_rise(mesh):
    before, after = mesh["scrapes"]
    route0 = _rows(before, "veneur_ingest_shard_route_seconds_total")
    route1 = _rows(after, "veneur_ingest_shard_route_seconds_total")
    assert {k.split('family="')[1].split('"')[0] for k in route1} == set(
        FAMILIES)
    for key, value in route1.items():
        assert value > route0.get(key, 0.0) >= 0.0, key
    [(_, rounds0)] = _rows(before, "veneur_mesh_merge_rounds_total").items()
    [(_, rounds1)] = _rows(after, "veneur_mesh_merge_rounds_total").items()
    # one merge per family per flush of an interval that touched it (a
    # tick between two compared rounds flushes the server's own
    # self-metrics: fewer families)
    assert rounds1 - rounds0 == sum(
        1 for r in mesh["all_rounds"] for s in r["spans"]
        if s["name"] == "merge") >= len(FAMILIES) * FLUSHES
    routed = _rows(after, "veneur_shard_samples_routed_total")
    assert len(routed) == len(FAMILIES) * SHARDS
    sent = sum(t["lines"] for t in mesh["truth"])
    assert (sum(routed.values()) - sum(_rows(
        before, "veneur_shard_samples_routed_total").values())) >= sent
    # the merge's kernel-registry row is fed from the same spans
    merged = _rows(after, "veneur_device_kernel_merge_s_count_total")
    assert merged and all(v >= FLUSHES for v in merged.values())


def test_one_shard_server_has_no_merge_span_and_no_route_row(work):
    one = run_server(1, 1, work)
    assert not compare_interval(work, one["truth"][0], one["got"][0])
    [r] = one["rounds"]
    assert "merge_s" not in r["phases"]
    assert not [s for s in r["spans"] if s["name"] == "merge"]
    for scrape in one["scrapes"]:
        for row in ("veneur_ingest_shard_route_seconds_total",
                    "veneur_mesh_merge_rounds_total",
                    "veneur_shard_samples_routed_total"):
            assert not _rows(scrape, row), row


@pytest.mark.parametrize("metric", [
    "flush.merge_ms", "flush.shard_sync_ms", "ingest.shard_route_s",
    "mesh.merge_rounds", "flush.chip_wait_ms", "flush.set_wait_ms",
    "flush.set_host_ms", "flush.set_transfer_ms", "flush.readout_chip_ms"])
def test_mesh_layer_metric_reads_something_the_program_produces(
        mesh, metric):
    """The benchmark's vocabulary for the four-shard cell, as
    tests/test_flush_spans.py pins the one-shard cells'."""
    with open(os.path.join(REPO, "benchmark", "layer_metrics",
                           metric + ".json")) as f:
        reader = json.load(f)["reader"]
    if reader["kind"] == "flush_phase":
        for r in mesh["rounds"]:
            assert set(reader["keys"]) <= set(r["phases"])
    else:
        assert reader["kind"] == "prometheus"
        assert _rows(mesh["scrapes"][1], reader["row"])


# -- (d) the control: centroid sums one precision down ---------------------

def test_bfloat16_centroid_sums_fail_the_comparison(work, monkeypatch):
    """`benchmark/harness/control.py`'s control over the sharded server:
    the merge's recompress with its segment sums in bfloat16 must not
    pass (a) (it fails `cold_timer_rel_gap`, as on one chip)."""
    import jax
    from harness import control

    # what the control patches, put back afterwards; programs traced
    # under it are dropped on both sides
    monkeypatch.setattr(batch_tdigest, "jnp", batch_tdigest.jnp)
    monkeypatch.setattr(batch_tdigest, "_segment_reduce_gather",
                        batch_tdigest._segment_reduce_gather)
    jax.clear_caches()
    try:
        control.lower_tdigest_precision("bf16", force_matmul=True)
        lowered = run_server(SHARDS, 1, work)
    finally:
        monkeypatch.undo()
        jax.clear_caches()
    wrong = compare_interval(work, lowered["truth"][0], lowered["got"][0])
    assert wrong
    # and only the t-digest's values: every exact family still holds
    assert all(name.startswith("smoke.timer.") and name.endswith(
        "percentile") for name, _have, _want in wrong), wrong[:5]
