#!/usr/bin/env python
"""Crash/replay soak driver: SIGKILL a local mid-flush, restart it,
replay the WAL, and diff the global's final state against an unfaulted
control.

What it exercises (the durable interval WAL, "Durable interval WAL &
timestamp-faithful backfill replay"):

- `forward_wal: true` appends every forwardable interval snapshot to
  disk (fsync'd, interval-stamped) BEFORE the send attempt;
- a `kill -9` landing between the append and the receiver's ack loses
  nothing: the restarted process re-scans the spool and replays the
  unacked interval;
- per-segment idempotency tokens (derived from the on-disk name,
  stable across restarts) make the replay exactly-once — a segment
  whose send landed but whose ack was lost is deduped, not re-merged.

The kill is made deterministic the honest way: the child local runs
with `chaos_forward_latency_ms` high enough that every forward send
hangs mid-flight, the driver waits until a fresh WAL segment appears on
disk (the append happened; the flush is mid-send), and THEN delivers
SIGKILL. The restarted child runs with chaos off and drains the log.

The invariant pinned is EXACTNESS, not accounting: after N kill/restart
rounds the faulted pipeline's global must hold the same counter sums as
an unfaulted control fed the identical stream, and the llhist family's
registers must match BIT FOR BIT (register-add merges are exact
regardless of arrival order — the Circllhist property the WAL's replay
correctness rests on).

Runnable standalone:

    JAX_PLATFORMS=cpu python scripts/crash_replay_soak.py \
        --kills 3 --counters-per-round 40 --value 3

and from the `wal`+`slow`-marked soak test (tests/test_wal.py), which
drives `run_soak()` directly and asserts the report's invariants.
"""

from __future__ import annotations

import argparse
import json
import os
import signal
import subprocess
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

CHILD_ENV_FLAG = "CRASH_REPLAY_SOAK_CHILD"


def wait_until(pred, timeout=30.0, step=0.05):
    deadline = time.time() + timeout
    while time.time() < deadline:
        if pred():
            return True
        time.sleep(step)
    return False


# ---------------------------------------------------------------------------
# child: one local server, WAL on, forwarding to the parent's global
# ---------------------------------------------------------------------------


def run_child() -> None:
    """Child-process entry: a real local Server with the WAL enabled,
    reading DogStatsD lines from stdin ("feed" protocol: one line per
    metric packet, `FLUSH\\n` triggers a flush, EOF exits after a final
    flush). Forward sends hang for CHAOS_MS, so the parent can SIGKILL
    this process provably mid-flight."""
    from veneur_tpu.config import Config
    from veneur_tpu.core.server import Server

    cfg = Config()
    cfg.interval = 3600.0  # flushes are driven by the feed protocol
    cfg.hostname = "soak-local"
    cfg.forward_address = os.environ["SOAK_FORWARD_ADDRESS"]
    cfg.carryover_spool_dir = os.environ["SOAK_WAL_DIR"]
    cfg.forward_wal = True
    cfg.forward_retry_max_attempts = 1
    cfg.circuit_breaker_failure_threshold = 10_000
    # acceptance pin: every interval's books must close with zero
    # unexplained imbalance THROUGH the kill/replay cycle — strict
    # raises out of flush(), so "FLUSHED" never prints and the soak
    # fails loudly
    cfg.ledger_strict = True
    chaos_ms = float(os.environ.get("SOAK_CHAOS_MS", "0"))
    if chaos_ms:
        cfg.chaos_enabled = True
        cfg.chaos_forward_latency_ms = chaos_ms
    cfg.tpu.counter_capacity = 128
    cfg.tpu.gauge_capacity = 128
    cfg.tpu.histo_capacity = 128
    cfg.tpu.set_capacity = 64
    cfg.tpu.llhist_capacity = 64
    cfg.tpu.batch_cap = 512
    cfg.apply_defaults()
    server = Server(cfg)
    server.start()
    print("READY", flush=True)
    for line in sys.stdin:
        line = line.strip()
        if not line:
            continue
        if line == "FLUSH":
            server.flush()
            print("FLUSHED", flush=True)
            continue
        server.handle_metric_packet(line.encode())
    server.store.apply_all_pending()
    server.flush()
    print("DONE", flush=True)


# ---------------------------------------------------------------------------
# parent: two in-process globals (faulted path + control), the kill loop
# ---------------------------------------------------------------------------


def _mk_global():
    from veneur_tpu.config import Config
    from veneur_tpu.core.server import Server
    from veneur_tpu.forward.server import ImportServer
    from veneur_tpu.sinks.channel import ChannelMetricSink

    cfg = Config()
    cfg.interval = 3600.0
    cfg.hostname = "soak-global"
    cfg.statsd_listen_addresses = []
    cfg.ledger_strict = True
    cfg.tpu.counter_capacity = 128
    cfg.tpu.gauge_capacity = 128
    cfg.tpu.histo_capacity = 128
    cfg.tpu.set_capacity = 64
    cfg.tpu.llhist_capacity = 64
    cfg.tpu.batch_cap = 512
    cfg.apply_defaults()
    obs = ChannelMetricSink()
    server = Server(cfg, extra_metric_sinks=[obs])
    imp = ImportServer(server, "127.0.0.1:0")
    imp.start()
    return server, imp, obs


def _spawn_child(wal_dir: str, forward_address: str, chaos_ms: float
                 ) -> subprocess.Popen:
    env = dict(os.environ)
    env.update({
        CHILD_ENV_FLAG: "1",
        "JAX_PLATFORMS": os.environ.get("JAX_PLATFORMS", "cpu"),
        "SOAK_FORWARD_ADDRESS": forward_address,
        "SOAK_WAL_DIR": wal_dir,
        "SOAK_CHAOS_MS": str(chaos_ms),
    })
    proc = subprocess.Popen(
        [sys.executable, os.path.abspath(__file__)],
        stdin=subprocess.PIPE, stdout=subprocess.PIPE,
        env=env, text=True, bufsize=1)
    assert wait_until(lambda: proc.stdout.readline().strip() == "READY",
                      timeout=120.0), "child never came up"
    return proc


def _feed(proc: subprocess.Popen, lines) -> None:
    for line in lines:
        proc.stdin.write(line + "\n")
    proc.stdin.flush()


def _wal_segments(wal_dir: str):
    try:
        return sorted(f for f in os.listdir(wal_dir)
                      if f.endswith(".vspool"))
    except OSError:
        return []


def run_soak(kills: int = 3, counters_per_round: int = 40,
             value: int = 3, chaos_ms: float = 20_000.0,
             verbose: bool = False) -> dict:
    """`kills` rounds of feed -> flush -> SIGKILL-mid-send -> restart ->
    replay, then a clean final round. Returns the comparison report;
    raises AssertionError when an invariant breaks."""
    import numpy as np

    faulted, f_imp, _ = _mk_global()
    control, c_imp, _ = _mk_global()
    tmp = tempfile.mkdtemp(prefix="crash-replay-soak-")
    wal_dir = os.path.join(tmp, "wal")
    ctl_wal_dir = os.path.join(tmp, "wal-control")
    report = {"kills": 0, "restarts": 0, "rounds": []}

    def lines_for(round_no: int):
        # counters ride the magic global-scope tag so a LOCAL forwards
        # them (mixed-scope counters flush locally); llhist samples are
        # mixed-scope and forward their registers by default
        out = []
        for i in range(counters_per_round):
            out.append(f"soak.cnt.{i % 8}:{value}|c"
                       f"|#veneurglobalonly")
            out.append(f"soak.llh.{i % 4}:{(round_no * 17 + i) % 91}|l")
        return out

    child = None
    ctl = _spawn_child(ctl_wal_dir, c_imp.address, 0.0)
    try:
        for round_no in range(kills):
            if child is not None:
                # the previous round's replay child ran chaos-free (its
                # WAL is drained); each kill round needs the hang seam
                # back, so respawn with chaos on
                child.kill()
                child.wait()
            child = _spawn_child(wal_dir, f_imp.address, chaos_ms)
            lines = lines_for(round_no)
            _feed(child, lines)
            _feed(ctl, lines + ["FLUSH"])
            assert wait_until(
                lambda: ctl.stdout.readline().strip() == "FLUSHED",
                timeout=60.0)
            before = set(_wal_segments(wal_dir))
            _feed(child, ["FLUSH"])
            # the WAL append lands BEFORE the (chaos-delayed) send:
            # the moment a fresh segment is on disk the flush is
            # provably mid-send — kill -9 now
            assert wait_until(
                lambda: set(_wal_segments(wal_dir)) - before,
                timeout=60.0), "WAL segment never appeared pre-ack"
            child.kill()
            child.wait()
            report["kills"] += 1
            # restart with chaos OFF: the re-scan replays the log
            child = _spawn_child(wal_dir, f_imp.address, 0.0)
            report["restarts"] += 1
            _feed(child, ["FLUSH"])  # drains the replayed segments
            assert wait_until(
                lambda: child.stdout.readline().strip() == "FLUSHED",
                timeout=60.0)
            assert wait_until(lambda: not _wal_segments(wal_dir),
                              timeout=30.0), "WAL did not drain"
            if verbose:
                print(f"round {round_no}: killed + replayed")
            report["rounds"].append(round_no)
        # clean final round on both pipelines
        lines = lines_for(kills)
        _feed(child, lines + ["FLUSH"])
        assert wait_until(
            lambda: child.stdout.readline().strip() == "FLUSHED",
            timeout=60.0)
        _feed(ctl, lines + ["FLUSH"])
        assert wait_until(
            lambda: ctl.stdout.readline().strip() == "FLUSHED",
            timeout=60.0)
    finally:
        for proc in (child, ctl):
            try:
                proc.kill()
            except OSError:
                pass

    # -- the diff: zero counter loss, llhist registers bit-identical ----
    def counter_sums(server):
        table = server.store.counters
        server.store.apply_all_pending()
        vals, touched, meta = table.snapshot_and_reset()
        out = {}
        for row in np.flatnonzero(np.asarray(touched)).tolist():
            if meta[row] is not None:
                out[meta[row].name] = float(np.asarray(vals)[row])
        return out

    def llhist_bins(server):
        table = server.store.llhists
        ps = (0.5,)
        _out, bins, touched, meta = table.snapshot_and_reset(ps)
        out = {}
        for i, row in enumerate(np.flatnonzero(np.asarray(touched)).tolist()):
            if meta[row] is not None:
                out[meta[row].name] = np.asarray(bins)[i]
        return out

    f_counters = counter_sums(faulted)
    c_counters = counter_sums(control)
    assert f_counters == c_counters, (
        f"counter loss: faulted {f_counters} != control {c_counters}")
    f_bins = llhist_bins(faulted)
    c_bins = llhist_bins(control)
    assert set(f_bins) == set(c_bins), (set(f_bins), set(c_bins))
    for name in f_bins:
        assert np.array_equal(f_bins[name], c_bins[name]), (
            f"llhist registers diverge for {name}")
    # conservation: zero unexplained imbalance on the receiving tier
    faulted.ledger.close_interval()
    control.ledger.close_interval()
    report["counters"] = f_counters
    report["llhist_names"] = sorted(f_bins)
    report["dedupe_drops"] = f_imp.duplicates_dropped_total
    f_imp.stop()
    c_imp.stop()
    return report


def main(argv=None) -> int:
    if os.environ.get(CHILD_ENV_FLAG):
        run_child()
        return 0
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--kills", type=int, default=3)
    ap.add_argument("--counters-per-round", type=int, default=40)
    ap.add_argument("--value", type=int, default=3)
    ap.add_argument("--chaos-ms", type=float, default=20_000.0)
    ap.add_argument("--verbose", action="store_true")
    args = ap.parse_args(argv)
    report = run_soak(kills=args.kills,
                      counters_per_round=args.counters_per_round,
                      value=args.value, chaos_ms=args.chaos_ms,
                      verbose=args.verbose)
    print(json.dumps(report, indent=2, default=str))
    print(f"ok: {report['kills']} kill(s), {report['restarts']} "
          f"restart(s), zero loss, llhist bit-exact")
    return 0


if __name__ == "__main__":
    sys.exit(main())
