"""One whole run of a cell with a fault planted: what test_rehearsal.py
starts on the CPU backend, a process per run (the control has to be in
place before any program is traced, and a server owns its process), and
what reads the control and the faults on the chip at a cell's own size
(`--platform tpu`, through the builder's chip tool; PERF.md has the
readings).

    python benchmark/tests/drive.py --workload <cell> --seed <n>
        --seconds <s> [--trace <0|1>] [--fault <name>] [--mode <m>]
        [--platform cpu|tpu] [--root <dir with BENCHMARK.json>]

Faults: `none`; `skip` (one datagram withheld at the sender: delivery);
`burst` (a fifth of sender 0's datagrams of interval 0 withheld in one
run: more than the counts can name one by one, so the flush is compared
bounded); `freeze` (every process of the run, this one too, stopped for
`--freeze-s` seconds from `--freeze-at` seconds after the opening tick, as
a host that stands still: the senders then send what they are behind at
once, and the server loses what it cannot take);
`remove` and `alter` (a series of the first flush taken out of, or
changed in, the intake's bodies: an answer altered where it is
produced); `drop_rows` (the column store leaves out half of every
counter batch: the timed path broken underneath); `control` (t-digest
centroid sums one precision below the configuration's: `--mode high`
on a TPU; `bf16`, the default, on the CPU, whose `high` is exact).
"""

import argparse
import gzip
import json
import os
import subprocess
import sys
import threading
import time

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [os.path.dirname(BENCH), BENCH]

import run as bench  # noqa: E402
from harness import control, intake, sut  # noqa: E402


def edit_first_flush(edit):
    """A `tamper` for `run`: `edit(series)` on the first body of the
    window that carries the benchmark's own series."""
    def tamper(bodies, facts):
        out, done = [], False
        for arrival, path, encoding, body in bodies:
            series = (None if done or arrival < facts["tick0"]
                      + facts["interval_s"] else
                      intake.decode_series(encoding, body))
            if series and any(s["metric"].startswith("bench.")
                              for s in series):
                edit(series)
                body = gzip.compress(json.dumps({"series": series}).encode())
                encoding, done = "gzip", True
            out.append((arrival, path, encoding, body))
        assert done, "no body of the window carried a bench. series"
        return out
    return tamper


def remove(series):
    at = next(i for i, s in enumerate(series)
              if s["metric"].startswith("bench.counter."))
    del series[at]


def alter(series):
    s = next(s for s in series if s["metric"].endswith(".max"))
    s["points"][0][1] *= 1.0001


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--root", default=bench.ROOT)
    ap.add_argument("--platform", default="cpu")
    ap.add_argument("--mode", default="bf16")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, default=0)
    ap.add_argument("--fault", default="none")
    ap.add_argument("--freeze-at", type=float, default=3.0)
    ap.add_argument("--freeze-s", type=float, default=1.0)
    args = ap.parse_args()
    sut.REQUIRED_PLATFORM = args.platform   # the rehearsal's one constant
    if args.root != bench.ROOT:
        bench.OUT_DIR = os.path.join(args.root, "_out")
    skip = tamper = None
    children: list = []
    if args.fault == "skip":
        skip = {(0, 0): [2]}           # interval 0, sender 0, its datagram 2
    elif args.fault == "burst":
        loaded = bench.load_cell(args.workload, args.root)
        traffic = bench.Traffic(loaded["traffic"], loaded["config"],
                                args.seed)
        mine = -(-(traffic.fresh.datagrams + traffic.cycles * (
            traffic.corpus.datagrams if traffic.corpus else 0))
            // traffic.senders)
        skip = {(0, 0): list(range(mine // 3, mine // 3 + mine // 5))}
    elif args.fault == "freeze":
        next_tick = bench.next_tick

        def freezing(interval_s, margin_s):
            tick0 = next_tick(interval_s, margin_s)
            pids = " ".join(str(p) for p in [os.getpid()] + [
                c.proc.pid for c in children])
            script = (f"kill -STOP {pids}; sleep {args.freeze_s}; "
                      f"kill -CONT {pids}")
            threading.Timer(
                tick0 + args.freeze_at - time.time(),
                lambda: subprocess.run(["sh", "-c", script])).start()
            return tick0

        bench.next_tick = freezing
    elif args.fault in ("remove", "alter"):
        tamper = edit_first_flush({"remove": remove, "alter": alter}
                                  [args.fault])
    elif args.fault == "control":
        control.lower_tdigest_precision(
            args.mode, force_matmul=args.platform == "cpu")
    elif args.fault == "drop_rows":
        start_server = sut.start_server

        def faulty(config_path):
            server = start_server(config_path)
            add_batch = server.store.counters.add_batch

            def half(rows, *columns):
                n = (len(rows) + 1) // 2
                return add_batch(rows[:n], *[c[:n] for c in columns])

            server.store.counters.add_batch = half
            return server

        sut.start_server = faulty
    try:
        result = bench.run(args, children, skip=skip, tamper=tamper,
                           root=args.root)
    finally:
        for child in children:
            child.stop()
    print(json.dumps(result), flush=True)
    sys.stdout.flush()
    os._exit(0)


if __name__ == "__main__":
    main()
