"""The rate sweep of a replay cell, on the chip host: the cell's traffic
file copied with each `datagrams_per_s` of the list, one run of
benchmark/tests/drive.py each (a process each), and one line per rate.
The highest rate with nothing failed is the knee; the cell's own file
takes 0.8 of it. Run once, by hand, through the builder's chip tool:

    python benchmark/tests/chip_sweep.py --workload timers1k-replay \\
        --rates 20000,30000,40000 --seconds 20 --out chiprun_out/sweep
"""

import argparse
import json
import os
import shutil
import subprocess
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--seed", type=int, default=2_147_485_000)
    ap.add_argument("--out", required=True)
    ap.add_argument("--platform", default="tpu")
    ap.add_argument("--senders", type=int, default=0,
                    help="sender processes, where not the file's own")
    args = ap.parse_args()
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    [cell] = [w for w in manifest["workloads"] if w["name"] == args.workload]
    root = os.path.join(BENCH, "_out", "sweep")
    shutil.rmtree(root, ignore_errors=True)
    for sub in ("configs", "traffic", "layer_metrics"):
        shutil.copytree(os.path.join(BENCH, sub),
                        os.path.join(root, "benchmark", sub))
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(manifest, f)
    path = os.path.join(root, "benchmark", "traffic",
                        cell["traffic"] + ".json")
    with open(path) as f:
        traffic = json.load(f)
    if args.senders:
        traffic["sender_processes"] = args.senders
    os.makedirs(args.out, exist_ok=True)
    for i, rate in enumerate(int(r) for r in args.rates.split(",")):
        traffic["datagrams_per_s"] = rate
        with open(path, "w") as f:
            json.dump(traffic, f)
        log = os.path.join(args.out, f"rate_{rate}")
        with open(log + ".out", "w") as out, open(log + ".err", "w") as err:
            done = subprocess.run(
                [sys.executable, os.path.join(BENCH, "tests", "drive.py"),
                 "--root", root, "--platform", args.platform, "--workload",
                 args.workload, "--seed", str(args.seed + i), "--seconds",
                 str(args.seconds)], stdout=out, stderr=err, cwd=ROOT)
        with open(log + ".out") as f:
            lines = f.read().splitlines()
        if done.returncode:
            print(f"SWEEP rate {rate}: exit {done.returncode}", flush=True)
            continue
        result = json.loads(lines[-1])
        late = [ln for ln in lines if "generator worst lateness" in ln]
        print("SWEEP " + json.dumps({
            "datagrams_per_s": rate, "correct": result["correct"],
            "attempted": result["attempted"], "failed": result["failed"],
            "metrics": {k: v["value"] for k, v in result["metrics"].items()},
            "late": late[-1].split("generator worst lateness")[1][:40]
            if late else None}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
