#!/usr/bin/env python
"""chip_smoke.py — the server's main path on one TPU, checked end to end.

One process, and only this process touches the chip. It writes a YAML
config from the defaults of examples/example.yaml, loads it the way
`python -m veneur_tpu.cmd.veneur -f` does, starts `Server`, and sends
real DogStatsD datagrams to the server's own UDP listener: 100,000 live
keys over five families (40k counters, 20k gauges, 30k t-digest timers,
9k sets, 1k llhists), ~330k lines per 10 s interval, paced under a
thousand datagrams a second. After warm-up intervals (which compile),
three intervals are checked: the flushed InterMetrics against plain
references fed the same lines, every flush inside its interval, the HTTP
API answering while loaded, and no recompile once warm.

The generator is quiet from the moment an interval's lines are all in
until that interval's flush has closed: a line's interval must be known
for the comparison to be exact.

    python chip_smoke.py             one chip (what the driver runs)
    python chip_smoke.py --chips 4   only the sharded phase and its control

The timer-driven sharded flush (`tpu.shards: 4` under the flush loop's
own ticks, a Datadog sink, compared with the references at 100k keys) is
the benchmark's business since PR 29: cell `global100k-interval-4chip`
(`python3 benchmark/run.py --workload global100k-interval-4chip ...`).
`--chips 4` keeps what that cell does not do: each shard's arrays on a
device of its own, and the mesh against a one-shard control fed the same
lines in one process, flushes called by hand.

It exits non-zero on any failed phase, when JAX finds no TPU, and where
the rest of the repo is missing. On success its last line is
`{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}`.
"""

from __future__ import annotations

import argparse
import json
import os
import socket
import sys
import threading
import time
import traceback

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
OUT_DIR = os.path.join(HERE, "chiprun_out", "chip_smoke")

# what JAX must report; tests/test_chip_smoke.py steers this to "cpu"
REQUIRED_PLATFORM = "tpu"

SIZES = {"counter": 40_000, "gauge": 20_000, "timer": 30_000,
         "set": 9_000, "llhist": 1_000}
PERCENTILES = (0.5, 0.9, 0.99)
INTERVAL_S = 10.0
LINES_PER_DATAGRAM = 40
# share of the interval the datagrams are paced across; the rest is for
# the rings to drain, the /query read, and the flush tick itself
SEND_WINDOW = 0.84
SET_MEMBERS = 16          # tpu.set_promote_samples' accelerator default
TIMER_BASE, TIMER_HOT, TIMER_VERY_HOT = 3, 33, 400   # samples per key
LLHIST_SAMPLES = 6
MAX_WARMUP, CHECKED = 3, 3
MESH_MAX_WARMUP = 5       # two servers share the host while they intern
TIMER_CHECK, SET_CHECK, LLHIST_CHECK = 600, 200, 200


def log(msg: str) -> None:
    print(f"chip_smoke: {msg}", flush=True)


def fail(msg: str):
    raise SystemExit(f"chip_smoke: FAILED: {msg}")


def check(ok, msg: str) -> None:
    if not ok:
        fail(msg)


# ---------------------------------------------------------------------------
# device
# ---------------------------------------------------------------------------


def require_devices(count: int):
    """The devices JAX reports, or exit: a smoke run means nothing on a
    platform other than the one asked for."""
    import jax

    devices = jax.devices()
    platform = devices[0].platform
    if platform != REQUIRED_PLATFORM:
        fail(f"JAX found platform {platform!r} ({len(devices)} device(s)), "
             f"not {REQUIRED_PLATFORM!r}")
    if len(devices) < count:
        fail(f"{count} {platform} device(s) needed, JAX found "
             f"{len(devices)}")
    return devices


def devices_of(tree) -> set:
    """Every device that holds an array of `tree` (a family table's live
    generation is `table._devobs_state()`)."""
    import jax

    out = set()
    for leaf in jax.tree.leaves(tree):
        out |= set(leaf.devices())
    return out


class CompileMeter:
    """Backend compile seconds and persistent-cache hits/misses, from
    JAX's own monitoring events."""

    def __init__(self):
        import jax.monitoring as mon

        self.seconds = 0.0
        self.compiles = 0
        self.hits = 0
        self.misses = 0
        mon.register_event_duration_secs_listener(self._duration)
        mon.register_event_listener(self._event)

    def _duration(self, event, duration, **_kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration
            self.compiles += 1

    def _event(self, event, **_kw):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def snapshot(self) -> dict:
        return {"compile_s": round(self.seconds, 3),
                "compiles": self.compiles,
                "cache_hits": self.hits, "cache_misses": self.misses}


# ---------------------------------------------------------------------------
# workload: seeded lines + the plain truth they encode
# ---------------------------------------------------------------------------


class Workload:
    """Deterministic DogStatsD traffic over `sizes` keys. interval(k)
    renders interval k's datagrams and keeps what the references need:
    the same parsed values, per key."""

    def __init__(self, seed: int, sizes: dict):
        self.seed = seed
        self.sizes = dict(sizes)
        self.names = {
            fam: [f"smoke.{fam}.{i:05d}" for i in range(n)]
            for fam, n in sizes.items()}
        self.tags = {fam: [f"env:smoke,zone:z{i % 8}" for i in range(n)]
                     for fam, n in sizes.items()}
        n_timer = sizes["timer"]
        per_key = np.full(n_timer, TIMER_BASE, np.int64)
        per_key[:max(1, n_timer // 30)] = TIMER_HOT
        per_key[:min(8, max(1, n_timer // 40))] = TIMER_VERY_HOT
        self.timer_samples = per_key
        rng = np.random.default_rng([seed, 9999])
        pick = lambda n, k: np.sort(rng.choice(n, min(n, k), replace=False))
        # the very hot keys (which force a staging compact) and a share
        # of the hot ones are always among the checked timers
        hot = np.arange(min(n_timer, 100))
        self.timer_check = np.unique(np.concatenate(
            [hot, pick(n_timer, TIMER_CHECK)]))
        self.set_check = pick(sizes["set"], SET_CHECK)
        self.llhist_check = pick(sizes["llhist"], LLHIST_CHECK)

    def interval(self, k: int) -> dict:
        rng = np.random.default_rng([self.seed, k])
        sizes, names, tags = self.sizes, self.names, self.tags
        lines = []

        counters = rng.integers(1, 1000, sizes["counter"])
        lines += [f"{n}:{v}|c|#{t}" for n, v, t in
                  zip(names["counter"], counters.tolist(), tags["counter"])]

        # quarter-integers: exact in float32, so "exactly" is well defined
        gauges = rng.integers(0, 1 << 20, sizes["gauge"]) / 4.0
        lines += [f"{n}:{v}|g|#{t}" for n, v, t in
                  zip(names["gauge"], gauges.tolist(), tags["gauge"])]

        key = np.repeat(np.arange(sizes["timer"]), self.timer_samples)
        text = [f"{v:.3f}" for v in
                rng.lognormal(3.0, 1.0, key.size).tolist()]
        lines += [f"{names['timer'][i]}:{v}|ms|#{tags['timer'][i]}"
                  for i, v in zip(key.tolist(), text)]
        timers = {}
        values = np.array([float(v) for v in text])
        bounds = np.concatenate([[0], np.cumsum(self.timer_samples)])
        for i in self.timer_check.tolist():
            timers[i] = values[bounds[i]:bounds[i + 1]]

        base = (rng.integers(0, 1 << 40, sizes["set"]) * SET_MEMBERS).tolist()
        members = lambda i: [f"u{base[i] + j}" for j in range(SET_MEMBERS)]
        for i in range(sizes["set"]):
            lines += [f"{names['set'][i]}:{m}|s|#{tags['set'][i]}"
                      for m in members(i)]
        sets = {i: members(i) for i in self.set_check.tolist()}

        ll_text = [[f"{v:.4g}" for v in row] for row in rng.lognormal(
            1.0, 2.0, (sizes["llhist"], LLHIST_SAMPLES)).tolist()]
        for i, row in enumerate(ll_text):
            lines += [f"{names['llhist'][i]}:{v}|l|#{tags['llhist'][i]}"
                      for v in row]
        llhists = {i: np.array([float(v) for v in ll_text[i]])
                   for i in self.llhist_check.tolist()}

        order = rng.permutation(len(lines)).tolist()
        per = LINES_PER_DATAGRAM
        datagrams = ["\n".join(lines[j] for j in order[i:i + per]).encode()
                     for i in range(0, len(order), per)]
        return {"k": k, "lines": len(lines), "datagrams": datagrams,
                "counters": counters, "gauges": gauges, "timers": timers,
                "sets": sets, "llhists": llhists}


def send_paced(datagrams, address, window_s: float, result: dict) -> None:
    """Send `datagrams` evenly across `window_s` from four client
    sockets (SO_REUSEPORT spreads sources over the readers). Records how
    late the worst datagram left."""
    socks = [socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
             for _ in range(4)]
    try:
        n, sent, late = len(datagrams), 0, 0.0
        t0 = time.monotonic()
        while sent < n:
            now = time.monotonic() - t0
            due = min(n, int(now / window_s * n) + 1)
            while sent < due:
                socks[sent % 4].sendto(datagrams[sent], address)
                sent += 1
            late = max(late, time.monotonic() - t0 - (sent - 1) * window_s / n)
            time.sleep(0.002)
        result.update(sent=sent, seconds=time.monotonic() - t0,
                      max_late_s=late)
    finally:
        for s in socks:
            s.close()


# ---------------------------------------------------------------------------
# server
# ---------------------------------------------------------------------------


def pow2_at_least(n: int) -> int:
    return 1 << max(0, int(n - 1).bit_length())


def write_config(name: str, sizes: dict, interval_s: float,
                 shards: int = 1) -> str:
    """examples/example.yaml with loopback port-0 listeners, a channel
    sink to observe flushes, and capacities that hold every key without
    a resize. Everything else stays at its shipped default
    (prewarm_ladder off, native parser on, ledger on)."""
    import yaml

    with open(os.path.join(HERE, "examples", "example.yaml")) as f:
        raw = yaml.safe_load(f)
    raw.update(
        interval=f"{interval_s}s",
        hostname="chip-smoke",
        statsd_listen_addresses=["udp://127.0.0.1:0"],
        ssf_listen_addresses=[],
        grpc_address="",
        http_address="127.0.0.1:0",
        http_quit=False,
        percentiles=list(PERCENTILES),
        metric_sinks=[{"kind": "channel", "name": "channel"}],
    )
    raw["tpu"].update(
        counter_capacity=pow2_at_least(sizes["counter"]),
        gauge_capacity=pow2_at_least(sizes["gauge"]),
        histo_capacity=pow2_at_least(sizes["timer"]),
        set_capacity=pow2_at_least(sizes["set"]),
        llhist_capacity=pow2_at_least(sizes["llhist"]),
        shards=shards,
    )
    os.makedirs(OUT_DIR, exist_ok=True)
    path = os.path.join(OUT_DIR, f"{name}.yaml")
    with open(path, "w") as f:
        yaml.safe_dump(raw, f, sort_keys=False)
    return path


def start_server(config_path: str):
    from veneur_tpu import native
    from veneur_tpu.config import read_config
    from veneur_tpu.core.server import Server

    check(native.available(),
          f"native parser unavailable: {native.unavailable_reason()}")
    server = Server(read_config(config_path))
    server.start()
    udp = server._listeners[0]
    check(getattr(udp, "pump", None) is not None,
          "the UDP listener did not start the native pump rung")
    [sink] = [s for s in server.metric_sinks if s.kind() == "channel"]
    return server, sink


class Api:
    """The server's HTTP API, as a client sees it."""

    def __init__(self, server):
        self.base = "http://%s:%d" % tuple(server.http_api.address[:2])

    def get(self, path: str) -> bytes:
        from veneur_tpu.util import http

        status, body = http.get(self.base + path, timeout=60.0)
        check(status == 200, f"GET {path} answered {status}")
        return body

    def json(self, path: str):
        return json.loads(self.get(path))

    def kernel_drops(self) -> float:
        total = 0.0
        for line in self.get("/metrics").decode().splitlines():
            if line.startswith("veneur_ingest_kernel_drops"):
                total += float(line.rsplit(" ", 1)[1])
        return total


def wait_until(predicate, timeout_s: float) -> bool:
    deadline = time.monotonic() + timeout_s
    while not predicate():
        if time.monotonic() > deadline:
            return False
        time.sleep(0.01)
    return True


def lines_received(server) -> int:
    """DogStatsD lines read off the listeners so far (self-metrics loop
    back below this counter and are not in it)."""
    return int(server.stats["packets_received"])


def admitted(stages: dict) -> int:
    """Lines of one ledger interval that the native batch path admitted
    (self-metrics enter on the "python" key and are not ours)."""
    return int(stages.get("ingest.admitted", {}).get("native", 0))


def device_stamp() -> dict:
    import jax

    devices = jax.devices()
    return {"platform": devices[0].platform,
            "kind": devices[0].device_kind, "count": len(devices)}


def check_placement(server, devices) -> None:
    """Every family table's live arrays sit on exactly `devices`, which
    are of the required platform."""
    check(all(d.platform == REQUIRED_PLATFORM for d in devices),
          f"devices {devices} are not {REQUIRED_PLATFORM}")
    for family, table in server.store.tables():
        if family == "status":  # host-only
            continue
        on = devices_of(table._devobs_state())
        check(on == set(devices),
              f"{family} table's arrays live on {sorted(d.id for d in on)}"
              f", expected {sorted(d.id for d in devices)}")


# ---------------------------------------------------------------------------
# comparison with the plain references
# ---------------------------------------------------------------------------


def flushed_values(metrics) -> dict:
    """{name: value}, bucket series keyed with their le tag."""
    out = {}
    for m in metrics:
        if not m.name.startswith("smoke."):
            continue
        if m.name.endswith(".bucket"):
            le = next(t for t in m.tags if t.startswith("le:"))
            out[f"{m.name}|{le}"] = m.value
        else:
            out[m.name] = m.value
    return out


def reference_digest(values):
    from veneur_tpu.ops.tdigest_ref import MergingDigest

    ref = MergingDigest(100.0)
    for v in values.tolist():
        ref.add(v)
    return ref


def compare_interval(work: Workload, truth: dict, got: dict) -> dict:
    """Flushed values of one interval against the references. Returns
    the counts of what was compared; exits on the first mismatch."""
    from veneur_tpu.ops import hll_ref, llhist_ref

    names, k = work.names, truth["k"]

    def value(name):
        check(name in got, f"interval {k}: {name} missing from the flush")
        return got[name]

    for fam, want in (("counter", truth["counters"]),
                      ("gauge", truth["gauges"])):
        have = np.array([value(n) for n in names[fam]], np.float64)
        bad = np.flatnonzero(have != want.astype(np.float64))
        if bad.size:
            fail(f"interval {k}: {bad.size} {fam} keys differ, first "
                 f"{names[fam][bad[0]]}: got {have[bad[0]]}, want "
                 f"{want[bad[0]]}")

    # t-digest timers against ops/tdigest_ref.py, at the tolerance
    # tests/test_tdigest.py holds a digest to: 0.02 in rank. The flushed
    # quantile is located in the reference digest's CDF, because two
    # correct digests of a long-tailed key agree in rank, not in value
    for i, vals in truth["timers"].items():
        name = names["timer"][i]
        ref = reference_digest(vals)
        for p in PERCENTILES:
            have = value(f"{name}.{int(p * 100)}percentile")
            check(abs(ref.cdf(have) - p) <= 0.02,
                  f"interval {k}: {name} p{p}: got {have}, which the "
                  f"reference digest ranks at {ref.cdf(have)} (its own "
                  f"quantile is {ref.quantile(p)})")
        f32 = vals.astype(np.float32)
        check(value(f"{name}.min") == float(f32.min())
              and value(f"{name}.max") == float(f32.max())
              and value(f"{name}.count") == float(vals.size),
              f"interval {k}: {name} min/max/count differ")

    # sets against ops/hll_ref.py: the whole-number estimate, exactly —
    # off by one only where the reference's own value sits on a rounding
    # boundary that float32 device arithmetic can cross
    off_by_one = 0
    for i, members in truth["sets"].items():
        name = names["set"][i]
        ref = hll_ref.HLL()
        for m in members:
            ref.insert(m.encode())
        want, have = ref.estimate(), value(name)
        if have != want:
            regs = np.asarray(ref.regs)
            ez = float(np.count_nonzero(regs == 0))
            raw = (hll_ref._ALPHA * hll_ref.M * (hll_ref.M - ez)
                   / (hll_ref.beta14(ez)
                      + float(np.sum(np.exp2(-regs.astype(np.float64))))))
            check(abs(have - want) == 1.0
                  and abs(raw - round(raw)) < 1e-3,
                  f"interval {k}: {name}: estimate {have}, want {want} "
                  f"(pre-floor {raw})")
            off_by_one += 1

    # llhist against ops/llhist_ref.py: registers are integers, so the
    # count and the +Inf bucket are exact, and so is the midpoint sum
    # (one definition, llhist_ref.entry_sums, on both sides); quantiles
    # at tests/test_llhist.py's rtol
    for i, vals in truth["llhists"].items():
        name = names["llhist"][i]
        ref = llhist_ref.LLHist()
        ref.insert_many(vals)
        check(value(f"{name}.count") == float(ref.count())
              and value(f"{name}.bucket|le:+Inf") == float(ref.count()),
              f"interval {k}: {name} count differs")
        check(value(f"{name}.sum") == ref.sum(),
              f"interval {k}: {name} sum differs")
        for p, want in zip(PERCENTILES, ref.quantiles(PERCENTILES)):
            have = value(f"{name}.{int(p * 100)}percentile")
            check(np.isclose(have, want, rtol=1e-5),
                  f"interval {k}: {name} p{p}: got {have}, want {want}")

    return {"counters": len(names["counter"]), "gauges": len(names["gauge"]),
            "timers": len(truth["timers"]), "sets": len(truth["sets"]),
            "sets_off_by_one": off_by_one, "llhists": len(truth["llhists"])}


# ---------------------------------------------------------------------------
# the one-chip run
# ---------------------------------------------------------------------------


def check_http_while_loaded(api: Api, device) -> None:
    check(api.get("/healthcheck").strip() == b"ok",
          "GET /healthcheck did not answer ok")
    metrics = api.get("/metrics").decode()
    if device.memory_stats() is not None:
        row = (f'veneur_device_bytes_in_use{{device="0",'
               f'platform="{device.platform}"}}')
        check(row in metrics, f"/metrics carries no {row} row")
    else:
        check(device.platform != "tpu", "the TPU reports no memory_stats()")
    ledger = api.json("/debug/device")["ledger"]
    by_table = sum(t["bytes"] for t in ledger["by_table"].values())
    check(ledger["total_bytes"] > 0 and ledger["total_bytes"] == by_table,
          f"/debug/device: HBM ledger total {ledger['total_bytes']} != "
          f"sum of registered generations {by_table}")
    log(f"http while loaded: /healthcheck ok, /metrics "
        f"{len(metrics.splitlines())} rows, /debug/device ledger "
        f"{ledger['total_bytes']} bytes in {ledger['generations']} "
        "generations")


def run_one_chip(seed: int, sizes: dict = SIZES,
                 interval_s: float = INTERVAL_S) -> dict:
    """Warm-up, then three checked intervals through a started server's
    own flush loop. Returns the device stamp for the result line."""
    import jax

    from veneur_tpu.util import compilecache

    device = require_devices(1)[0]
    meter = CompileMeter()
    t_run = time.monotonic()
    work = Workload(seed, sizes)
    # rendered before the server starts: an interval's send window opens
    # the moment the flush before it closes
    intervals = [work.interval(k) for k in range(MAX_WARMUP + CHECKED)]
    log(f"device {device.platform} {device.device_kind!r}; "
        f"{sum(sizes.values())} keys {sizes}; seed {seed}; "
        f"interval {interval_s}s; {intervals[0]['lines']} lines per "
        f"interval rendered in {time.monotonic() - t_run:.1f}s")

    config_path = write_config("one_chip", sizes, interval_s)
    server, sink = start_server(config_path)
    try:
        cache_dir = jax.config.jax_compilation_cache_dir
        entries_before = compilecache.entries()
        log(f"config {config_path}; compile cache {cache_dir}, "
            f"{entries_before} entries before "
            f"({'cold' if entries_before <= 0 else 'warm'})")
        check(entries_before >= 0,
              f"the server keeps no compile cache (directory {cache_dir})")
        check(server.device_info["platform"] == REQUIRED_PLATFORM,
              f"the server started on {server.device_info}")
        check_placement(server, [device])
        api = Api(server)
        address = server.local_addr("udp")
        probe = work.names["timer"][int(work.timer_check[0])]
        closed = server.ledger.intervals_closed
        drops = api.kernel_drops()
        to_compare = None
        checked, warmups, warm = [], 0, False
        checked_from_unix = None
        k = 0
        while len(checked) < CHECKED:
            checking = warm
            if checking and not checked:
                checked_from_unix = time.time()
            truth = intervals[k]
            datagrams = truth.pop("datagrams")
            compiles_before = meter.compiles
            rx_before = lines_received(server)
            status: dict = {}
            sender = threading.Thread(
                target=send_paced, name="smoke-sender", daemon=True,
                args=(datagrams, address, SEND_WINDOW * interval_s, status))
            sender.start()
            if to_compare is not None:
                log(f"interval {to_compare[0]['k']}: references agree: "
                    f"{compare_interval(work, *to_compare)}")
                to_compare = None
            if checking and not checked:
                check_http_while_loaded(api, device)
            sender.join()
            check(status.get("sent") == len(datagrams),
                  f"interval {k}: the sender stopped early: {status}")

            # once every line is in, one live read the flush must equal
            t_grace = time.monotonic() + 0.1 * interval_s
            all_in = lambda: (lines_received(server) - rx_before
                              >= truth["lines"])
            while (not all_in() and time.monotonic() < t_grace
                   and server.ledger.intervals_closed == closed):
                time.sleep(0.01)
            query = None
            if (checking and all_in()
                    and server.ledger.intervals_closed == closed):
                query = api.json(
                    f"/query?metric={probe}&kind=quantile&q=0.5")

            check(wait_until(
                lambda: server.ledger.intervals_closed > closed, 900.0),
                f"interval {k}: no flush closed in 900s")
            report = api.json("/debug/ledger?intervals=1")
            check(report["intervals_closed"] == closed + 1,
                  f"interval {k}: a second flush closed before its lines "
                  "were read back")
            closed += 1
            received = admitted(report["intervals"][-1]["stages"])
            round_ = api.json("/debug/flush?n=1")["rounds"][-1]
            now_drops = api.kernel_drops()
            metrics = sink.drain()
            log(f"interval {k} ({'checked' if checking else 'warm-up'}): "
                f"sent {truth['lines']} lines / {len(datagrams)} datagrams "
                f"in {status['seconds']:.2f}s (latest "
                f"{status['max_late_s'] * 1e3:.0f}ms late), ingest ledger "
                f"received {received}, kernel drops "
                f"{now_drops - drops:.0f}; flush {round_['duration_s']:.3f}s "
                f"of {interval_s}s, {round_['metrics_flushed']} metrics, "
                f"phases {json.dumps(round_['phases'])}; "
                f"{json.dumps(meter.snapshot())}")
            inside = round_["duration_s"] < interval_s

            if checking:
                check(received == truth["lines"] and now_drops == drops,
                      f"interval {k}: datagrams lost: sent "
                      f"{truth['lines']} lines, the ingest ledger stage "
                      f"received {received}, ingest.kernel_drops rose by "
                      f"{now_drops - drops:.0f}")
                check(inside, f"interval {k}: the flush took "
                      f"{round_['duration_s']}s, past its {interval_s}s "
                      "interval")
                check(meter.compiles == compiles_before,
                      f"interval {k}: {meter.compiles - compiles_before} "
                      "programs compiled inside a checked interval")
                got = flushed_values(metrics)
                # the read counts when its capture (stamped as it
                # begins) clearly preceded the flush's swap
                if (query is not None and query["as_of_unix"]
                        + 0.02 * interval_s < round_["start_unix"]):
                    flushed = got[f"{probe}.50percentile"]
                    check(query["stale_pending_samples"] == 0
                          and query["value"] == flushed,
                          f"interval {k}: GET /query {probe} q=0.5 gave "
                          f"{query['value']} with "
                          f"{query['stale_pending_samples']} samples "
                          f"pending; the flush gave {flushed}")
                    log(f"interval {k}: /query {probe} q=0.5 = "
                        f"{query['value']} == the flush "
                        f"(eval {query['eval_s']}s)")
                else:
                    query = None
                to_compare = (truth, got)
                checked.append({"flush_s": round_["duration_s"],
                                "queried": query is not None})
            else:
                warmups += 1
                # two intervals at least: the second flush is the first
                # to run over recycled generations
                warm = warmups >= 2 and inside
                check(warm or warmups < MAX_WARMUP,
                      "no flush finished inside its interval in "
                      f"{MAX_WARMUP} warm-up intervals")
                if warm:
                    log(f"warm after {warmups} intervals "
                        f"({time.monotonic() - t_run:.1f}s): "
                        f"{json.dumps(meter.snapshot())}")
            drops = now_drops
            k += 1

        log(f"interval {to_compare[0]['k']}: references agree: "
            f"{compare_interval(work, *to_compare)}")
        check(any(c["queried"] for c in checked),
              "no checked interval left room for the /query read")

        # and again at the end: where it all ran, and that it stayed warm
        check_placement(server, [device])
        retraces = [
            e for kind in ("columnstore_recompile", "columnstore_resize")
            for e in api.json(f"/debug/events?kind={kind}")["events"]
            if e["ts"] >= checked_from_unix]
        check(not retraces, f"resize/recompile events fired inside the "
              f"checked intervals: {retraces}")
        peak = (device.memory_stats() or {}).get("peak_bytes_in_use")
        log(f"passed in {time.monotonic() - t_run:.1f}s on "
            f"{device.device_kind!r}: {sum(sizes.values())} live keys, "
            f"{len(checked)} checked intervals, flush wall "
            f"{[c['flush_s'] for c in checked]}s; "
            f"{json.dumps(meter.snapshot())}; compile cache {cache_dir} "
            f"{entries_before} -> {compilecache.entries()} entries; peak "
            f"HBM {peak if peak is not None else 'not reported'} bytes")
        return device_stamp()
    finally:
        server.shutdown()


# ---------------------------------------------------------------------------
# --chips 4: the sharded store against its single-device control
# ---------------------------------------------------------------------------


def run_four_chips(seed: int, sizes: dict = SIZES) -> dict:
    """The same lines into a `tpu.shards: 4` server and a `shards: 1`
    control in this process: flushed series identical (the PR 11 pin;
    t-digest percentiles to float32 rounding), each shard's arrays on a
    device of its own. Flushes are called by hand here: the sharded
    flush under the loop's own ticks is the benchmark cell
    `global100k-interval-4chip`'s business."""
    from veneur_tpu.ops import batch_tdigest

    devices = require_devices(4)[:4]
    meter = CompileMeter()
    t_run = time.monotonic()
    work = Workload(seed, sizes)
    log(f"{len(devices)} x {devices[0].platform} "
        f"{devices[0].device_kind!r}; {sum(sizes.values())} keys {sizes}; "
        f"seed {seed}; placement and mesh against control, flushes by "
        "hand (the timer-driven sharded flush is measured by the benchmark "
        "cell global100k-interval-4chip)")
    servers = []
    try:
        for name, shards in (("mesh", 4), ("control", 1)):
            servers.append(start_server(
                write_config(name, sizes, 3600.0, shards=shards)))
        (mesh, mesh_sink), (control, control_sink) = servers
        check(mesh.store.shard_plane is not None
              and mesh.store.shard_plane.n == 4,
              "the tpu.shards: 4 server built no four-shard plane")
        check_placement(mesh, devices)
        check_placement(control, devices[:1])
        for family, table in mesh.store.tables():
            states = getattr(table, "states", None)
            if states is not None:  # per-device lists: one device each
                per_shard = [devices_of(s) for s in states]
                check(len({d.id for ds in per_shard for d in ds}) == 4
                      and all(len(ds) == 1 for ds in per_shard),
                      f"{family}: per-shard states live on {per_shard}")
        balance = Api(mesh).json("/debug/device").get("shard_balance") or {}
        check(balance.get("n_shards") == 4,
              f"/debug/device shows {balance.get('n_shards')} shards")

        addresses = [s.local_addr("udp") for s, _ in servers]
        warm, warmups, compared, k = False, 0, 0, 0
        while compared < 2:
            truth = work.interval(k)
            datagrams = truth.pop("datagrams")
            before = [lines_received(s) for s, _ in servers]
            senders = [threading.Thread(
                target=send_paced, daemon=True,
                args=(datagrams, addr, SEND_WINDOW * INTERVAL_S, {}))
                for addr in addresses]
            for t in senders:
                t.start()
            for t in senders:
                t.join()
            got_lines = lambda: [lines_received(s) - base
                                 for (s, _), base in zip(servers, before)]
            arrived = wait_until(
                lambda: min(got_lines()) >= truth["lines"], 10.0)
            lines = got_lines()
            flushed = []
            for server, sink in servers:
                t0 = time.monotonic()
                server.flush()
                flushed.append((flushed_values(sink.drain()),
                                time.monotonic() - t0))
            (got_mesh, s_mesh), (got_control, s_control) = flushed
            log(f"round {k} ({'compared' if warm else 'warm-up'}): "
                f"{truth['lines']} lines to both, received {lines}; flush "
                f"wall mesh {s_mesh:.3f}s, control {s_control:.3f}s; "
                f"{json.dumps(meter.snapshot())}")
            k += 1
            if not warm:
                # first sight of 100k keys takes the Python slow path and
                # every program compiles: a round may lose datagrams
                warmups += 1
                warm = arrived
                check(warm or warmups < MESH_MAX_WARMUP,
                      f"datagrams still lost after {MESH_MAX_WARMUP} "
                      "warm-up rounds")
                continue
            check(arrived, f"round {k - 1}: datagrams lost: sent "
                  f"{truth['lines']} lines, received {lines}")
            # counters, gauges, sets and llhists merge by selection or
            # integer addition: bit for bit (an llhist's .sum too: it
            # is llhist_ref.entry_sums over those registers, in an
            # order that no layout changes). A
            # t-digest's cross-shard merge re-compresses, so its
            # percentiles are held to a float32 tolerance
            # (tests/test_reshard.py's kind of pin) — except the keys hot
            # enough to overflow their staging slots, which compact on a
            # schedule of their shard's own: two valid digests, each
            # held to the reference in rank
            check(got_mesh.keys() == got_control.keys()
                  and len(got_mesh) > sum(sizes.values()),
                  f"round {k - 1}: {len(got_mesh)} series from shards:4, "
                  f"{len(got_control)} from shards:1")
            compacting = {
                f"{work.names['timer'][i]}.{int(p * 100)}percentile": (i, p)
                for i in np.flatnonzero(
                    work.timer_samples > batch_tdigest.C).tolist()
                for p in PERCENTILES}
            differ = []
            for n, v in got_control.items():
                if n in compacting:
                    i, p = compacting[n]
                    ref = reference_digest(truth["timers"][i])
                    same = all(abs(ref.cdf(x) - p) <= 0.02
                               for x in (got_mesh[n], v))
                elif (n.startswith("smoke.timer.")
                      and n.endswith("percentile")):
                    same = np.isclose(got_mesh[n], v, rtol=1e-5, atol=0.0)
                else:
                    same = got_mesh[n] == v
                if not same:
                    differ.append((n, got_mesh[n], v))
            check(not differ,
                  f"round {k - 1}: {len(differ)} series differ between "
                  f"shards:4 and shards:1, first {sorted(differ)[:5]}")
            log(f"round {k - 1}: {len(got_mesh)} series agree "
                f"({len(compacting)} of them as digests of compacting keys)")
            compared += 1
        check_placement(mesh, devices)
        log(f"passed in {time.monotonic() - t_run:.1f}s on "
            f"{len(devices)} x {devices[0].device_kind!r}")
        return device_stamp()
    finally:
        for server, _ in servers:
            server.shutdown()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)
    if args.chips == 4:
        stamp = run_four_chips(args.seed)
    else:
        stamp = run_one_chip(args.seed)
    print(json.dumps({"ok": True, "device": stamp}), flush=True)
    return 0


if __name__ == "__main__":
    try:
        code = main()
    except BaseException as e:  # any failure is a non-zero exit, no result
        if isinstance(e, SystemExit) and not isinstance(e.code, int):
            print(e.code, file=sys.stderr)
        else:
            traceback.print_exc()
        code = e.code if isinstance(getattr(e, "code", None), int) else 1
    sys.stdout.flush()
    sys.stderr.flush()
    # no interpreter teardown after the verdict: daemon server threads
    # and the accelerator client must not turn it into a crash or a hang
    os._exit(code)
