#!/usr/bin/env python
"""Reshard SIGKILL soak: kill a mesh server mid-cutover — after the
range segments are durable but before the merge-back ran — restart it,
replay the WAL, and diff its flush against a never-resharded control.

What it exercises (parallel/reshard.py, "Elastic resharding: live
digest-range migration with WAL-backed exactly-once cutover"):

- the cutover WAL-appends every migrating digest-range cell's captured
  state (metricpb wire, one spool segment per cell) BEFORE any state
  moves onto the new plane;
- a `kill -9` landing between the append and the merge-back loses
  nothing: the restarted process replays the range segments at startup
  — into whatever topology the restart config builds, which this soak
  makes DIFFERENT from the mid-flight target on purpose (the child
  restarts at the old shard count);
- segments are popped only after their merge lands, so the replay is
  exactly-once: a second scan finds an empty spool.

The kill is made deterministic the honest way: the child runs with
`chaos_reshard_cutover_delay_s` high enough that the cutover sleeps
between the appends and the merges, the driver waits until the range
segments are on disk (the appends happened; the merge provably has
not), and THEN delivers SIGKILL. The restarted child runs with chaos
off and replays at start().

The invariant pinned is EXACTNESS: after N kill/restart rounds the
faulted pipeline's flush must match an unfaulted control fed the
identical stream — every family; counters/gauges/llhist/HLL rows
bit-equal; t-digest percentile rows within re-compression tolerance
(the migration re-packs captured centroids once). `ledger_strict` is
on in both children, so any conservation break raises out of flush()
and "FLUSHED" never prints.

Runnable standalone:

    JAX_PLATFORMS=cpu python scripts/reshard_soak.py --kills 2

and from the `reshard`+`slow`-marked soak test (tests/test_reshard.py),
which drives `run_soak()` directly and asserts the report's invariants.
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

CHILD_ENV_FLAG = "RESHARD_SOAK_CHILD"
SHARDS_OLD = 2
SHARDS_NEW = 3


def wait_until(pred, timeout=120.0, step=0.05):
    deadline = time.time() + timeout
    while time.time() < deadline:
        if pred():
            return True
        time.sleep(step)
    return False


# ---------------------------------------------------------------------------
# child: one mesh server, reshard WAL on, feed protocol over stdin
# ---------------------------------------------------------------------------


def run_child() -> None:
    """Child-process entry: a real mesh Server (strict ledger). Feed
    protocol: metric lines apply on `APPLY`; `RESHARD <n>` starts a
    live reshard (chaos holds the cutover open mid-WAL so the parent
    can SIGKILL provably inside the crash window); `FLUSH` flushes and
    prints the flushed rows as JSON; EOF exits."""
    from veneur_tpu.config import Config
    from veneur_tpu.core.server import Server
    from veneur_tpu.sinks.channel import ChannelMetricSink

    cfg = Config()
    cfg.interval = 3600.0  # flushes are driven by the feed protocol
    cfg.hostname = "reshard-soak"
    cfg.statsd_listen_addresses = []
    cfg.tpu.shards = SHARDS_OLD
    cfg.reshard_spool_dir = os.environ["SOAK_RESHARD_WAL"]
    # acceptance pin: zero unexplained imbalance through the
    # kill/replay cycle — strict raises out of flush(), so "FLUSHED"
    # never prints and the soak fails loudly
    cfg.ledger_strict = True
    delay_s = float(os.environ.get("SOAK_CUTOVER_DELAY_S", "0"))
    if delay_s:
        cfg.chaos_enabled = True
        cfg.chaos_reshard_cutover_delay_s = delay_s
    cfg.tpu.counter_capacity = 128
    cfg.tpu.gauge_capacity = 128
    cfg.tpu.histo_capacity = 128
    cfg.tpu.set_capacity = 64
    cfg.tpu.llhist_capacity = 64
    cfg.tpu.batch_cap = 512
    cfg.apply_defaults()
    obs = ChannelMetricSink()
    server = Server(cfg, extra_metric_sinks=[obs])
    server.start()  # replays any range segments a killed round left
    print("READY", flush=True)
    for line in sys.stdin:
        line = line.strip()
        if not line:
            continue
        if line == "APPLY":
            server.store.apply_all_pending()
            print("APPLIED", flush=True)
        elif line.startswith("RESHARD "):
            server.reshard.begin(shards=int(line.split()[1]),
                                 deadline_s=600.0)
            print("RESHARD_STARTED", flush=True)
        elif line == "FLUSH":
            server.store.apply_all_pending()
            server.flush()
            rows = {f"{m.name}|{','.join(sorted(m.tags))}": float(m.value)
                    for m in obs.drain()}
            print("FLUSHED " + json.dumps(rows, sort_keys=True),
                  flush=True)
        else:
            server.handle_metric_packet(line.encode())
    server.config.flush_on_shutdown = False
    server.shutdown()
    print("DONE", flush=True)


# ---------------------------------------------------------------------------
# parent: the kill loop
# ---------------------------------------------------------------------------


def _spawn_child(wal_dir: str, cutover_delay_s: float
                 ) -> subprocess.Popen:
    env = dict(os.environ)
    env.update({
        CHILD_ENV_FLAG: "1",
        "JAX_PLATFORMS": os.environ.get("JAX_PLATFORMS", "cpu"),
        "XLA_FLAGS": (env.get("XLA_FLAGS", "")
                      + " --xla_force_host_platform_device_count=8"),
        "SOAK_RESHARD_WAL": wal_dir,
        "SOAK_CUTOVER_DELAY_S": str(cutover_delay_s),
    })
    proc = subprocess.Popen(
        [sys.executable, os.path.abspath(__file__)],
        stdin=subprocess.PIPE, stdout=subprocess.PIPE,
        env=env, text=True, bufsize=1)
    assert wait_until(lambda: proc.stdout.readline().strip() == "READY",
                      timeout=300.0), "child never came up"
    return proc


def _feed(proc: subprocess.Popen, lines) -> None:
    for line in lines:
        proc.stdin.write(line + "\n")
    proc.stdin.flush()


def _await(proc: subprocess.Popen, prefix: str, timeout=300.0) -> str:
    box = []

    def got():
        line = proc.stdout.readline().strip()
        if line.startswith(prefix):
            box.append(line)
            return True
        return False
    assert wait_until(got, timeout=timeout), f"no {prefix!r} from child"
    return box[0]


def _flush(proc: subprocess.Popen) -> dict:
    _feed(proc, ["FLUSH"])
    return json.loads(_await(proc, "FLUSHED ")[len("FLUSHED "):])


def _wal_segments(wal_dir: str):
    try:
        return sorted(f for f in os.listdir(wal_dir)
                      if f.endswith(".vspool"))
    except OSError:
        return []


def _compare(faulted: dict, control: dict) -> int:
    """Exact row-for-row equality except t-digest percentile rows
    (re-compressed once by the migration; rtol pins them)."""
    assert set(faulted) == set(control), (
        sorted(set(control) - set(faulted))[:5],
        sorted(set(faulted) - set(control))[:5])
    checked = 0
    for key, want in control.items():
        got = faulted[key]
        if key.split("|", 1)[0].endswith("percentile"):
            assert abs(got - want) <= 1e-6 * max(abs(want), 1e-12), (
                key, got, want)
        else:
            assert got == want, (key, got, want)
        checked += 1
    return checked


def lines_for(round_no: int):
    out = []
    for i in range(16):
        out.append(f"soak.rs.c.{i}:{i + 1 + round_no}|c|#env:soak")
        out.append(f"soak.rs.t.{i}:{10.0 + i + round_no:.1f}|ms")
        out.append(f"soak.rs.ll.{i}:{(round_no * 17 + i) % 91}|l")
        out.append(f"soak.rs.s.{i}:m{(round_no * 7 + i) % 23}|s")
        out.append(f"soak.rs.g.{i}:{i * 1.5 + round_no:.2f}|g")
    return out


def run_soak(kills: int = 2, cutover_delay_s: float = 120.0,
             verbose: bool = False) -> dict:
    """`kills` rounds of feed -> reshard -> SIGKILL-mid-WAL ->
    restart -> replay -> flush-and-diff against an unfaulted control.
    Returns the comparison report; raises AssertionError when an
    invariant breaks."""
    tmp = tempfile.mkdtemp(prefix="reshard-soak-")
    wal_dir = os.path.join(tmp, "reshard-wal")
    report = {"kills": 0, "restarts": 0, "rounds": []}

    child = None
    ctl = _spawn_child(os.path.join(tmp, "ctl-wal"), 0.0)
    try:
        for round_no in range(kills):
            if child is not None:
                # the previous round's replay child ran chaos-free;
                # each kill round needs the hold-open seam back
                child.kill()
                child.wait()
            child = _spawn_child(wal_dir, cutover_delay_s)
            lines = lines_for(round_no)
            _feed(child, lines + ["APPLY"])
            _await(child, "APPLIED")
            _feed(ctl, lines + ["APPLY"])
            _await(ctl, "APPLIED")
            before = set(_wal_segments(wal_dir))
            _feed(child, [f"RESHARD {SHARDS_NEW}"])
            _await(child, "RESHARD_STARTED")
            # the WAL appends land, then chaos holds the cutover open:
            # the moment fresh segments are on disk the merge provably
            # has not run — kill -9 now, inside the crash window
            assert wait_until(
                lambda: set(_wal_segments(wal_dir)) - before,
                timeout=600.0), "range segments never appeared"
            child.kill()
            child.wait()
            report["kills"] += 1
            # restart with chaos OFF at the OLD shard count: start()
            # replays the log into a topology that differs from the
            # killed cutover's target on purpose
            child = _spawn_child(wal_dir, 0.0)
            report["restarts"] += 1
            assert wait_until(lambda: not _wal_segments(wal_dir),
                              timeout=30.0), "reshard WAL did not drain"
            # post-restart ingest keeps landing, then the diff
            post = lines_for(round_no + 100)
            _feed(child, post + ["APPLY"])
            _await(child, "APPLIED")
            _feed(ctl, post + ["APPLY"])
            _await(ctl, "APPLIED")
            rows = _compare(_flush(child), _flush(ctl))
            if verbose:
                print(f"round {round_no}: killed mid-WAL, replayed, "
                      f"{rows} flush rows match")
            report["rounds"].append({"round": round_no, "rows": rows})
    finally:
        for proc in (child, ctl):
            try:
                proc.kill()
            except (OSError, AttributeError):
                pass
    return report


def main(argv=None) -> int:
    if os.environ.get(CHILD_ENV_FLAG):
        run_child()
        return 0
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--kills", type=int, default=2)
    ap.add_argument("--cutover-delay-s", type=float, default=120.0)
    ap.add_argument("--verbose", action="store_true")
    args = ap.parse_args(argv)
    report = run_soak(kills=args.kills,
                      cutover_delay_s=args.cutover_delay_s,
                      verbose=args.verbose)
    print(json.dumps(report, indent=2))
    print(f"ok: {report['kills']} kill(s), {report['restarts']} "
          f"restart(s), zero loss, flush bit-identical to control")
    return 0


if __name__ == "__main__":
    sys.exit(main())
