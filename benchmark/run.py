#!/usr/bin/env python3
"""benchmark/run.py — one cell of BENCHMARK.json on the chip, one line out.

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Looks the cell up in BENCHMARK.json, loads `configs/<config>.json`,
`traffic/<traffic>.json` and the `layer_metrics/<metric>.json` the
manifest lists for the cell, starts the intake and the load generator as
child processes (neither imports JAX), builds a `Server` from a YAML file
the way `python -m veneur_tpu.cmd.veneur -f` does, warms it up, and then
measures `N = max(1, seconds // interval)` whole intervals: flush latency
and lines/s from what reached the intake, CPU from the server process's
own clock. After the window it frees the server and compares every flush
of the window with the plain references.

The last line of standard output is the result object. It exits
non-zero, with no result, when JAX finds no TPU (harness/sut.py's
REQUIRED_PLATFORM steers the CPU rehearsal) or the repo is missing.
README.md documents every earlier line of a run.
"""

from __future__ import annotations

import time

T_START = time.time()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import subprocess  # noqa: E402
import sys  # noqa: E402
import urllib.request  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
OUT_DIR = os.path.join(HERE, "_out")
sys.path.insert(0, HERE)
sys.path.insert(0, ROOT)

from harness import intake, metrics, sut, trace  # noqa: E402
from harness.compare import Comparer, not_aggregated, series_values  # noqa: E402
from harness.traffic import NAME_PREFIX, WARMUP_BASE, Traffic, load_json  # noqa: E402

WARMUP_ROUNDS = 2
SAMPLE_S = 0.08    # the server's counters are read this long before a tick
STOOD_STILL_S = 0.25   # later than this, the host stood still
# every number `correct` rests on, beside its limit; a configuration
# file's "limits" may state the two that are not exact
LIMITS = {"scalar_keys_wrong": 0, "timer_stats_wrong": 0,
          "timer_rank_gap": 0.02, "cold_timer_rel_gap": 1.5e-06,
          "set_keys_wrong": 0, "llhist_keys_wrong": 0,
          "unexpected_series": 0, "read_not_aggregated": 0,
          "flushes_missing": 0, "unparsed_bodies": 0}


def log(msg: str) -> None:
    print(f"benchmark: {msg}", flush=True)


def fail(msg: str):
    raise SystemExit(f"benchmark: FAILED: {msg}")


def load_cell(workload: str, root: str = ROOT) -> dict:
    """The cell and every file it names, found by name."""
    manifest = load_json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in manifest["workloads"]}
    if workload not in cells:
        fail(f"no workload {workload!r} in BENCHMARK.json (has "
             f"{sorted(cells)})")
    cell = cells[workload]
    [config_entry] = [c for c in manifest["configs"]
                      if c["name"] == cell["config"]]
    config_path = os.path.join(root, config_entry["file"])
    bench_dir = os.path.join(root, manifest["paths"][0])
    traffic_path = os.path.join(bench_dir, "traffic",
                                cell["traffic"] + ".json")

    def applies(metric: dict) -> bool:
        return workload in metric.get("workloads", [workload])

    per_layer = []
    for m in manifest["per_layer"]:
        if applies(m):
            spec = load_json(os.path.join(bench_dir, "layer_metrics",
                                          m["name"] + ".json"))
            per_layer.append({**m, "reader": spec["reader"]})
    return {"cell": cell, "config": load_json(config_path),
            "traffic": load_json(traffic_path), "config_path": config_path,
            "traffic_path": traffic_path, "per_layer": per_layer,
            "end_to_end": [m for m in manifest["end_to_end"] if applies(m)]}


class Child:
    """A child process spoken to in JSON lines. Its stderr is ours."""

    def __init__(self, argv):
        env = {k: v for k, v in os.environ.items() if k != "BENCH_RUN"}
        self.argv = argv
        self.proc = subprocess.Popen(
            [sys.executable, *argv], stdin=subprocess.PIPE,
            stdout=subprocess.PIPE, text=True, env=env, cwd=ROOT)

    def read(self) -> dict:
        line = self.proc.stdout.readline()
        if not line:
            fail(f"child {self.argv[0]} ended early (code "
                 f"{self.proc.poll()})")
        return json.loads(line)

    def write(self, obj: dict) -> None:
        self.proc.stdin.write(json.dumps(obj) + "\n")
        self.proc.stdin.flush()

    def stop(self) -> None:
        try:
            self.proc.stdin.close()
        except OSError:
            pass
        try:
            self.proc.wait(10)
        except subprocess.TimeoutExpired:
            self.proc.kill()
            self.proc.wait()


def http_get(url: str) -> bytes:
    with urllib.request.urlopen(url, timeout=120.0) as r:
        return r.read()


def wait_until(predicate, timeout_s: float, step_s: float = 0.02) -> bool:
    deadline = time.monotonic() + timeout_s
    while not predicate():
        if time.monotonic() > deadline:
            return False
        time.sleep(step_s)
    return True


def wait_quiet(count, want: int, quiet_s: float = 0.4,
               timeout_s: float = 10.0) -> bool:
    """Until `count()` reaches `want`, or has stood still for `quiet_s`
    (a warm-up round may lose datagrams while keys are first seen)."""
    deadline = time.monotonic() + timeout_s
    last, since = count(), time.monotonic()
    while last < want and time.monotonic() < deadline:
        time.sleep(0.02)
        now = count()
        if now != last:
            last, since = now, time.monotonic()
        elif time.monotonic() - since > quiet_s:
            break
    return last >= want


def sleep_until_unix(t: float) -> None:
    while True:
        left = t - time.time()
        if left <= 0:
            return
        time.sleep(min(left, 0.2))


def next_tick(interval_s: float, margin_s: float) -> float:
    """The server's next clock-aligned tick at least `margin_s` away."""
    now = time.time()
    tick = (now // interval_s + 1) * interval_s
    return tick if tick - now >= margin_s else tick + interval_s


def send_round(loadgens, k: int, at: float, address: str) -> dict:
    """One interval's lines from every sender; their worst lateness."""
    for child in loadgens:
        child.write({"send": k, "at": at, "address": address})
    return collect_round([child.read() for child in loadgens])


def collect_round(answers) -> dict:
    return {"k": answers[0]["k"],
            "sent": sum(a["sent"] for a in answers),
            "lines": sum(a["lines"] for a in answers),
            "withheld": sum(a["withheld"] for a in answers),
            "seconds": max(a["seconds"] for a in answers),
            "start_late_s": max(a["start_late_s"] for a in answers),
            "max_late_s": max(a["max_late_s"] for a in answers)}


class GcMeter:
    """Full (generation 2) collections of this process's Python heap and
    the seconds they took: a flush of 100k keys allocates enough to bring
    them on, and each stops every Python thread."""

    def __init__(self) -> None:
        self.count, self.seconds, self._t0 = 0, 0.0, 0.0
        gc.callbacks.append(self._note)

    def _note(self, phase: str, info: dict) -> None:
        if info["generation"] < 2:
            return
        if phase == "start":
            self._t0 = time.perf_counter()
        else:
            self.count += 1
            self.seconds += time.perf_counter() - self._t0

    def close(self) -> None:
        gc.callbacks.remove(self._note)


def lines_read(server) -> int:
    """DogStatsD lines the server has read off its listeners so far."""
    return int(server.stats["packets_received"])


def kernel_drops(server) -> int:
    """Datagrams the kernel dropped at the server's sockets so far."""
    server.overload.kernel_drops.poll()
    return int(sum(server.overload.kernel_drops.totals().values()))


def start(args, loaded: dict, traffic: Traffic, n_intervals: int,
          children: list) -> dict:
    """The intake and the senders (they render while JAX loads), then JAX
    and the server. No chip, no run: `require_devices` exits before
    anything is built."""
    cell, config = loaded["cell"], loaded["config"]
    chips = int(cell["chips"])
    harness = os.path.join(HERE, "harness")
    intake_child = Child([os.path.join(harness, "intake.py")])
    children.append(intake_child)
    port = intake_child.read()["port"]
    loadgens = []
    for i in range(traffic.senders):
        loadgens.append(Child([
            os.path.join(harness, "loadgen.py"),
            "--config", loaded["config_path"],
            "--traffic", loaded["traffic_path"], "--seed", str(args.seed),
            "--index", str(i), "--of", str(traffic.senders),
            "--warmups", str(WARMUP_ROUNDS),
            "--intervals", str(n_intervals)]))
        children.append(loadgens[-1])

    devices = sut.require_devices(chips)
    meter = sut.CompileMeter()
    config_path = sut.write_config(ROOT, OUT_DIR, cell["name"], config, port)
    server = sut.start_server(config_path)
    ctx = {"server": server, "meter": meter, "devices": devices,
           "chips": chips, "intake_url": f"http://127.0.0.1:{port}",
           "loadgens": loadgens, "api": sut.Api(server),
           "address": "%s:%d" % tuple(server.local_addr("udp")[:2]),
           "tick_wait_s": 0.0}
    if server.device_info["platform"] != sut.REQUIRED_PLATFORM:
        fail(f"the server started on {server.device_info}")
    if not wait_until(lambda: not server._warmup_thread.is_alive(), 1100.0,
                      0.05):
        fail("the server's kernel warm-up did not end in 1100s")
    import jax

    ready = [child.read() for child in loadgens]
    log(f"server up on {devices[0].device_kind!r}, compile cache "
        f"{jax.config.jax_compilation_cache_dir!r}, SO_RCVBUF "
        f"{sut.rcvbuf_bytes(server)} bytes effective; generators ready "
        f"(render {max(r['render_s'] for r in ready):.2f}s) at "
        f"{time.time() - T_START:.1f}s: {json.dumps(meter.snapshot())}")
    return ctx


def warm_up(ctx: dict, traffic: Traffic) -> None:
    """Every key and every shape of the cell's traffic: two rounds, each
    closed by a hand-called flush (the second is the first over recycled
    generations), then the flush of an interval with no line in it, as
    the flush of the opening tick is. A round waits for the server's
    tick where that would otherwise fall inside its send."""
    server, meter = ctx["server"], ctx["meter"]
    for r in range(WARMUP_ROUNDS):
        k = WARMUP_BASE + r
        interval_s = traffic.interval_s
        left = interval_s - time.time() % interval_s
        waited = 0.0
        if left < traffic.warmup_send_s + 0.5:
            waited = left + 0.05
            time.sleep(waited)
            ctx["tick_wait_s"] += waited
        before, seen = lines_read(server), len(meter.names)
        status = send_round(ctx["loadgens"], k, time.time() + 0.02,
                            ctx["address"])
        whole = wait_quiet(lambda: lines_read(server) - before,
                           status["lines"])
        t0 = time.monotonic()
        server.flush()
        log(f"warm-up {r}: {status['sent']} datagrams in "
            f"{status['seconds']:.2f}s after {waited:.2f}s of waiting for a "
            f"tick, {lines_read(server) - before} of {status['lines']} lines "
            f"read{'' if whole else ' (short)'}, hand-called flush "
            f"{time.monotonic() - t0:.2f}s: {json.dumps(meter.snapshot())}; "
            f"compiled or loaded {meter.names[seen:]}")
    t0 = time.monotonic()
    server.flush()
    log(f"warm-up: hand-called flush of an empty interval "
        f"{time.monotonic() - t0:.2f}s at {time.time() - T_START:.1f}s: "
        f"{json.dumps(meter.snapshot())}")


def measure(ctx: dict, args, cell: dict, traffic: Traffic,
            n_intervals: int, skip=None) -> dict:
    """The window: from the opening tick to the arrival of the flush of
    tick `n_intervals`. Returns what was read while it was open."""
    server, meter, api = ctx["server"], ctx["meter"], ctx["api"]
    interval_s = traffic.interval_s
    ready = time.time()
    setup_s = ready - T_START - ctx["tick_wait_s"]
    tick0 = next_tick(interval_s, 0.7)
    # what a sender has not sent half way through the quiet time before
    # the next tick, it withholds (after a host that stood still)
    grace_s = (interval_s - traffic.lead_s - traffic.send_s - SAMPLE_S) / 2
    for k in range(n_intervals):
        at = tick0 + k * interval_s + traffic.lead_s
        for i, child in enumerate(ctx["loadgens"]):
            child.write({"send": k, "at": at, "address": ctx["address"],
                         "until": at + traffic.send_s + grace_s,
                         "skip": (skip or {}).get((k, i), [])})
    log(f"window opens at tick {tick0:.0f}, {tick0 - T_START:.2f}s after "
        f"process start: set-up {setup_s:.2f}s and "
        f"{ctx['tick_wait_s'] + tick0 - ready:.2f}s of waiting for the "
        "server's ticks")

    sleep_until_unix(tick0 - 0.15)
    prom_before = api.prometheus()
    closed0 = server.ledger.intervals_closed
    read_at = [lines_read(server)]
    drops_at = [kernel_drops(server)]
    sleep_until_unix(tick0)
    cpu0, compiles0, seen = time.process_time(), meter.compiles, len(
        meter.names)
    cpu_at = [cpu0]
    gc_meter = GcMeter()
    # one interval is traced: from 0.15 of an interval after the opening
    # tick, so that the trace holds one whole flush and one whole send
    trace_dir, trace_window, tracing_from = None, None, None
    actions = [(tick0 + j * interval_s - SAMPLE_S, "sample")
               for j in range(1, n_intervals + 1)]
    sampled_late = []
    if args.trace:
        import jax

        trace_dir = os.path.join(OUT_DIR, f"trace-{cell['name']}")
        shutil.rmtree(trace_dir, ignore_errors=True)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 1
        actions.append((tick0 + 0.15 * interval_s, "trace"))
        if n_intervals > 1:
            actions.append((tick0 + 1.15 * interval_s, "stop"))
    for at, action in sorted(actions):
        sleep_until_unix(at)
        if action == "sample":
            # the generator has been quiet for a second: every line of
            # the interval that will ever be read has been
            sampled_late.append(round((time.time() - at) * 1e3, 1))
            read_at.append(lines_read(server))
            drops_at.append(kernel_drops(server))
            cpu_at.append(time.process_time())
        elif action == "trace":
            # collection runs from the return of start_trace to the call
            # of stop_trace; both calls take seconds themselves
            jax.profiler.start_trace(trace_dir, profiler_options=options)
            tracing_from = time.time()
        else:
            trace_window = (tracing_from, time.time())
            jax.profiler.stop_trace()
    want = closed0 + n_intervals + 1
    if not wait_until(lambda: server.ledger.intervals_closed >= want,
                      3 * interval_s, 0.005):
        log(f"no flush closed within {3 * interval_s}s of tick "
            f"{tick0 + n_intervals * interval_s:.0f}")
    cpu_s = time.process_time() - cpu0
    gc_meter.close()
    closed_late_s = time.time() - (tick0 + n_intervals * interval_s)
    if args.trace and trace_window is None:
        trace_window = (tracing_from, time.time())
        jax.profiler.stop_trace()

    rounds = [collect_round([child.read() for child in ctx["loadgens"]])
              for _ in range(n_intervals)]
    for r in rounds:
        log(f"interval {r['k']}: {r['sent']} datagrams ({r['withheld']} of "
            f"them withheld), {r['lines']} lines "
            f"in {r['seconds']:.2f}s from {traffic.lead_s}s after its tick, "
            f"started {r['start_late_s'] * 1e3:.1f}ms late, worst "
            f"{r['max_late_s'] * 1e3:.1f}ms late")
    prom_after = api.prometheus()
    flush_rounds = api.json(f"/debug/flush?n={n_intervals}")["rounds"]
    retraces = [e for kind in ("columnstore_recompile", "columnstore_resize")
                for e in api.json(f"/debug/events?kind={kind}")["events"]
                if e["ts"] >= tick0]
    facts = {
        "tick0": tick0, "setup_s": setup_s, "cpu_s": cpu_s,
        "interval_s": interval_s, "closed_late_s": closed_late_s,
        "prom_before": prom_before, "prom_after": prom_after,
        "flush_rounds": flush_rounds, "rounds": rounds,
        "read": [b - a for a, b in zip(read_at, read_at[1:])],
        "kernel_drops": [b - a for a, b in zip(drops_at, drops_at[1:])],
        "trace_dir": trace_dir, "trace_window": trace_window,
        "device": {**sut.device_stamp(ctx["devices"]),
                   "memory_peak_bytes": sut.memory_peak_bytes(ctx["devices"])},
        "harness": {"compiles_in_window": meter.compiles - compiles0,
                    "resize_events": len(retraces),
                    "sampled_late_ms": sampled_late,
                    "flush_began_ms": [
                        round(((r["start_unix"] - tick0 + interval_s / 2)
                               % interval_s - interval_s / 2) * 1e3, 1)
                        for r in flush_rounds],
                    "max_late_s": max(r["max_late_s"] for r in rounds),
                    "max_start_late_s": max(r["start_late_s"]
                                            for r in rounds)},
    }
    log(f"window closed {closed_late_s:.2f}s after its last tick: cpu "
        f"{cpu_s:.2f}s (tick to tick "
        f"{[round(b - a, 2) for a, b in zip(cpu_at, cpu_at[1:])]}, the "
        f"last flush {cpu0 + cpu_s - cpu_at[-1]:.2f}; {gc_meter.count} full "
        f"collections of the Python heap took {gc_meter.seconds:.2f}s); "
        "generator worst "
        f"{facts['harness']['max_late_s'] * 1e3:.1f}ms late; the server's "
        f"counters were read {sampled_late}ms after {SAMPLE_S}s before each "
        "tick; "
        f"{json.dumps(meter.snapshot())}; compiled or loaded inside the "
        f"window {meter.names[seen:]}; resize/recompile events {retraces}; "
        "the server began its flushes "
        f"{facts['harness']['flush_began_ms']}"
        "ms after their ticks; assembly_s by flush "
        f"{[r['phases'].get('assembly_s') for r in flush_rounds]}, "
        f"sink_join_s {[r['phases'].get('sink_join_s') for r in flush_rounds]}"
        "; phases of the last flush "
        f"{json.dumps(flush_rounds[-1]['phases']) if flush_rounds else None}")
    return facts


def flushes_at_intake(bodies, tick0: float, interval_s: float,
                      n_intervals: int):
    """The window's flushes as the intake saw them: {tick index: last
    arrival, series lists, bodies}, and the bodies it could not parse.
    A flush's bodies are those that carry the deployment's own series; a
    body of the server's veneur.* self-metrics alone is not the flush a
    user waits for. A body belongs to the tick it arrived after."""
    per_tick, unparsed, other = {}, 0, 0
    for arrival, path, encoding, body in bodies:
        j = int((arrival - tick0) // interval_s)
        if not 1 <= j <= n_intervals:
            continue
        if path != "/api/v1/series":
            other += 1
            continue
        series = intake.decode_series(encoding, body)
        if series is None:
            unparsed += 1
            continue
        own = sum(1 for s in series if s["metric"].startswith(NAME_PREFIX))
        if not own:
            continue
        slot = per_tick.setdefault(j, {"last": arrival, "series": [],
                                       "bodies": 0, "arrivals": []})
        slot["last"] = max(slot["last"], arrival)
        slot["series"].append(series)
        slot["bodies"] += 1
        slot["arrivals"].append((
            round((arrival - tick0 - j * interval_s) * 1000.0, 1),
            len(body), len(series), own))
    return per_tick, unparsed, other


def compare_window(per_tick: dict, traffic: Traffic, config: dict,
                   facts: dict, n_intervals: int) -> dict:
    """Every flush of the window against the references. Returns the
    numbers compared (sums, and the widest gaps), the line account, and
    each flush's latency."""
    comparer = Comparer(traffic, config["percentiles"],
                        traffic.spec.get("check", {}))
    sums = ("scalar_keys_wrong", "timer_stats_wrong", "set_keys_wrong",
            "llhist_keys_wrong", "unexpected_series", "lines_aggregated",
            "lines_slack", "lines_carried")
    out = {name: 0 for name in sums}
    out.update(timer_rank_gap=0.0, cold_timer_rel_gap=0.0, flushes_missing=0,
               flushes=[])
    tick0, interval_s = facts["tick0"], facts["interval_s"]
    out["lines_sent"] = sum(r["lines"] for r in facts["rounds"])
    out["lines_read"] = sum(facts["read"])
    # delivery is judged over the window: a reading of the server's
    # counter taken late (a host that stood still) moves lines between
    # two intervals' `read`, never into or out of the window
    out["lines_failed"] = max(0, out["lines_sent"] - out["lines_read"])
    # a window in which delivery lost lines, or the host stood still (a
    # flush begun late, a counter read late, a sender behind), may hold
    # keys short of lines that never arrived, or over by lines aggregated
    # after a late swap: compare.py excuses those, and only those
    harness = facts["harness"]
    disturbed = bool(
        out["lines_failed"] or harness["max_late_s"] > STOOD_STILL_S
        or harness["max_start_late_s"] > STOOD_STILL_S
        or any(ms > STOOD_STILL_S * 1e3 for ms in harness["sampled_late_ms"]
               + harness["flush_began_ms"]))
    log(f"window disturbed: {disturbed} (failed {out['lines_failed']} "
        f"lines; flushes began {harness['flush_began_ms']}ms after their "
        f"ticks, counters read {harness['sampled_late_ms']}ms late, senders "
        f"up to {harness['max_late_s'] * 1e3:.1f}ms behind; "
        f"{STOOD_STILL_S}s counts as standing still)")
    for j in range(1, n_intervals + 1):
        k = j - 1
        sent, read = facts["rounds"][k]["lines"], facts["read"][k]
        slot = per_tick.get(j)
        if slot is None:
            out["flushes_missing"] += 1
            log(f"flush {j}: nothing reached the intake in its interval; "
                f"account: sent {sent}, read {read}, kernel-dropped "
                f"{facts['kernel_drops'][k]}, aggregated 0")
            continue
        latency_ms = (slot["last"] - tick0 - j * interval_s) * 1000.0
        out["flushes"].append({"tick": j, "latency_ms": latency_ms,
                               "bodies": slot["bodies"]})
        res = comparer.compare_interval(
            k, series_values(slot["series"], interval_s), sent, read,
            lossy=disturbed)
        for name in sums:
            out[name] += res[name]
        for name in ("timer_rank_gap", "cold_timer_rel_gap"):
            out[name] = max(out[name], res[name])
        log(f"flush {j}: {latency_ms:.1f}ms after its tick in "
            f"{slot['bodies']} bodies (ms, bytes, series, own: "
            f"{sorted(slot['arrivals'])}); account: sent {sent}, read "
            f"{read}, kernel-dropped {facts['kernel_drops'][k]}, aggregated "
            f"{res['lines_aggregated']} ({res['datagrams_lost']} datagrams "
            f"taken as lost; {res['bounded_parts']} part(s) compared "
            f"bounded, their keys {res['lines_short']} lines short and "
            f"{res['lines_carried']} over from the interval before, "
            f"{res['lines_slack']} lines without a count); compared "
            f"{json.dumps(res['compared'])}; rank "
            f"gap {res['timer_rank_gap']:.4g}, cold gap "
            f"{res['cold_timer_rel_gap']:.4g}"
            + (f"; WRONG: {res['first_wrong']}" if res["first_wrong"]
               else ""))
        for name in ("timer_rank_gap_at", "cold_timer_rel_gap_at"):
            if name in res:
                log(f"flush {j}: {name}: {res[name]}")
    # every line read is aggregated once: over the window, as delivery is
    out["read_not_aggregated"] = not_aggregated(
        out["lines_read"], out["lines_aggregated"], out["lines_slack"])
    # lines without a count (gauges and sets of a bounded part) are taken
    # as aggregated as far as the server says it read them
    out["lines_aggregated"] += min(out["lines_slack"], max(
        0, out["lines_read"] - out["lines_aggregated"]))
    return out


def reduce_trace(facts: dict):
    """The traced interval, reduced; None where nothing was traced."""
    if not facts["trace_dir"]:
        return None
    from jax.profiler import ProfileData

    path = trace.newest_xplane(facts["trace_dir"])
    if path is None:
        fail(f"the profiler wrote no trace under {facts['trace_dir']}")
    t0, t1 = facts["trace_window"]
    profile = ProfileData.from_file(path)
    # /debug/flush's phases as spans on the host's clock; they name a gap
    # only where the trace's own clock is the Unix epoch too
    phases = []
    for r in facts["flush_rounds"]:
        at = r["start_unix"]
        for name, seconds in r["phases"].items():
            if name in ("store_flush_s", "critical_path_s"):
                continue
            phases.append((name[:-2], int(at * 1e9),
                           int((at + seconds) * 1e9)))
    reduced = trace.reduce_profile(
        profile, t1 - t0, host_as_device=sut.REQUIRED_PLATFORM == "cpu",
        phases=phases)
    log(f"trace {path}: {os.path.getsize(path)} bytes: busy "
        f"{reduced['busy_s']:.4f}s of {reduced['window_s']:.2f}s on "
        f"{reduced['planes']} plane(s)")
    shutil.rmtree(facts["trace_dir"], ignore_errors=True)
    return reduced


def run(args, children: list, skip=None, tamper=None,
        root: str = ROOT) -> dict:
    """One run of one cell; returns the result object. `skip` withholds
    datagrams at the senders and `tamper` edits the intake's bodies
    before they are compared: the tests' faults, never set by `main`."""
    loaded = load_cell(args.workload, root)
    cell, config = loaded["cell"], loaded["config"]
    traffic = Traffic(loaded["traffic"], config, args.seed)
    n_intervals = max(1, int(args.seconds // traffic.interval_s))
    log(f"cell {cell['name']}: config {cell['config']}, traffic "
        f"{cell['traffic']} ({traffic.kind}), seed {args.seed}, "
        f"{n_intervals} interval(s) of {traffic.interval_s}s, "
        f"{traffic.lines_of(0)} lines per interval"
        + (f" in {traffic.cycles} cycles" if traffic.cycles else "")
        + f" sent across {traffic.send_s:.2f}s from {traffic.lead_s}s after "
        "each tick")
    ctx = start(args, loaded, traffic, n_intervals, children)
    server = ctx["server"]
    try:
        warm_up(ctx, traffic)
        facts = measure(ctx, args, cell, traffic, n_intervals, skip)
    finally:
        server.shutdown()
        ctx["meter"].close()
    facts["trace"] = reduce_trace(facts)
    # the program's state is freed; now the references
    t0 = time.monotonic()
    bodies = intake.parse_dump(http_get(ctx["intake_url"] + "/dump"))
    if tamper is not None:
        bodies = tamper(bodies, facts)
    per_tick, unparsed, other = flushes_at_intake(
        bodies, facts["tick0"], traffic.interval_s, n_intervals)
    cmp = compare_window(per_tick, traffic, config, facts, n_intervals)
    cmp["unparsed_bodies"] = unparsed
    facts.update(flushes=cmp["flushes"], lines_read=cmp["lines_read"])
    log(f"compared in {time.monotonic() - t0:.1f}s; the intake could not "
        f"parse {unparsed} bodies of {len(bodies)} ({other} posts in the "
        "window were not series)")
    log(f"over the window: sent {cmp['lines_sent']}, read "
        f"{cmp['lines_read']}, kernel-dropped {sum(facts['kernel_drops'])} "
        f"(/metrics delta "
        f"{metrics.read_prometheus({'row': 'veneur_ingest_kernel_drops_total'}, facts)}"
        f"), aggregated {cmp['lines_aggregated']}; failed "
        f"{cmp['lines_failed']}")

    window_s = n_intervals * traffic.interval_s
    aggregated = cmp["lines_aggregated"]
    flushes = cmp["flushes"]
    end_to_end = {
        "flush_ms": (sum(f["latency_ms"] for f in flushes) / len(flushes)
                     if flushes else None),
        "lines_per_s": aggregated / window_s,
        "cpu_us_per_line": (facts["cpu_s"] * 1e6 / aggregated
                            if aggregated else None),
        "setup_s": facts["setup_s"]}
    limits = {**LIMITS, **config.get("limits", {})}
    compared = {name: {"value": cmp[name], "limit": limits[name]}
                for name in LIMITS}
    correct = all(c["value"] <= c["limit"] for c in compared.values())
    if args.trace:
        values = {m["name"]: metrics.read(m, facts)
                  for m in loaded["per_layer"]}
        units = {m["name"]: m["unit"] for m in loaded["per_layer"]}
    else:
        values = {m["name"]: end_to_end.get(m["name"])
                  for m in loaded["end_to_end"]}
        units = {m["name"]: m["unit"] for m in loaded["end_to_end"]}
    log(f"end to end: {json.dumps(end_to_end)}; window {window_s}s closed "
        f"{facts['closed_late_s']:.2f}s after its last tick; generator "
        f"worst lateness {facts['harness']['max_late_s']:.4f}s, latest "
        f"start {facts['harness']['max_start_late_s']:.4f}s; events (not "
        f"part of `correct`): {json.dumps({k: facts['harness'][k] for k in ('compiles_in_window', 'resize_events')})}")
    result = {
        "correct": bool(correct), "attempted": cmp["lines_sent"],
        "failed": cmp["lines_failed"],
        "metrics": {name: {"value": v, "unit": units[name]}
                    for name, v in values.items() if v is not None},
        "device": dict(facts["device"])}
    if args.trace and facts["trace"]:
        result["device"].update(busy_s=facts["trace"]["busy_s"],
                                window_s=facts["trace"]["window_s"])
        result["breakdown"] = {"device_ops": facts["trace"]["device_ops"],
                               "idle_gaps": facts["trace"]["idle_gaps"]}
    result["compared"] = compared
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if not os.path.isdir(os.path.join(ROOT, "veneur_tpu")):
        fail(f"no veneur_tpu package beside {HERE}: nothing to measure")
    children: list = []
    try:
        result = run(args, children)
    finally:
        for child in children:
            child.stop()
    for name, c in result["compared"].items():
        print(f"benchmark: compared {name} {c['value']} (limit "
              f"{c['limit']})", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    try:
        code = main()
    except BaseException as e:  # any failure is a non-zero exit, no result
        import traceback

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
