"""The load generator: a child process that never imports JAX.

Renders its datagrams from `--seed` and the traffic file, prints
`{"ready": true, "render_s": ...}`, then obeys one JSON command per line
on stdin and answers one JSON line on stdout:

    {"send": k, "at": unix, "address": "host:port"}
        sleep until `at`, then send interval k's datagrams (this
        process's share: every `--of`-th, from `--index`) evenly across
        the traffic file's send window. The schedule does not slow when
        the server does (open loop). `"skip": [i, ...]` withholds those
        of this process's datagrams and counts them as sent (the tests'
        datagram lost on the way). `"until": unix`: a datagram whose
        turn comes after that time is withheld and counted as sent too:
        after a host that stood still, what is left of an interval is not
        sent into the next one. Answers {"k", "sent", "lines",
        "withheld", "seconds", "start_late_s", "max_late_s"}.

It exits when stdin closes. Intervals at or above WARMUP_BASE are
warm-up rounds: `warmup_cycles` corpus cycles (replay) or one interval's
lines, sent across `warmup_send_s`.
"""

from __future__ import annotations

import argparse
import json
import os
import socket
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from harness.traffic import WARMUP_BASE, Traffic, load_json  # noqa: E402

SOCKETS = 4   # SO_REUSEPORT spreads sources over the server's readers


def send_paced(fresh, corpus, cycles: int, address, window_s: float,
               skip=(), until=None) -> dict:
    """`fresh`, then `cycles` times `corpus`, evenly across `window_s`.
    Records how late the worst datagram left, and how many were withheld
    (`skip`, or their turn came after `until`)."""
    socks = [socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
             for _ in range(SOCKETS)]
    skip = set(skip)
    n_fresh, n_corpus = len(fresh), len(corpus)
    n = n_fresh + cycles * n_corpus
    sent, late, lines, withheld = 0, 0.0, 0, 0
    try:
        t0 = time.monotonic()
        while sent < n:
            now = time.monotonic() - t0
            due = min(n, int(now / window_s * n) + 1)
            expired = until is not None and time.time() > until
            while sent < due:
                d = (fresh[sent] if sent < n_fresh
                     else corpus[(sent - n_fresh) % n_corpus])
                # a withheld datagram is one the network lost: it counts
                # as sent, and nobody reads it
                if expired or sent in skip:
                    withheld += 1
                else:
                    socks[sent % SOCKETS].sendto(d, address)
                lines += d.count(b"\n") + 1
                sent += 1
            late = max(late,
                       time.monotonic() - t0 - (sent - 1) * window_s / n)
            time.sleep(0.001)
        return {"sent": sent, "lines": lines, "withheld": withheld,
                "seconds": time.monotonic() - t0, "max_late_s": late}
    finally:
        for s in socks:
            s.close()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", required=True)
    ap.add_argument("--traffic", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--index", type=int, default=0)
    ap.add_argument("--of", type=int, default=1)
    ap.add_argument("--warmups", type=int, default=2)
    ap.add_argument("--intervals", type=int, default=1)
    args = ap.parse_args(argv)

    t0 = time.monotonic()
    traffic = Traffic(load_json(args.traffic), load_json(args.config),
                      args.seed)
    share = slice(args.index, None, args.of)
    corpus = traffic.render_corpus()[share]
    rounds = ([WARMUP_BASE + r for r in range(args.warmups)]
              + list(range(args.intervals)))
    fresh = {k: traffic.render_fresh(k)[share] for k in rounds}
    print(json.dumps({"ready": True, "render_s": time.monotonic() - t0,
                      "corpus_datagrams": len(corpus)}), flush=True)

    for line in sys.stdin:
        cmd = json.loads(line)
        k = int(cmd["send"])
        host, port = cmd["address"].rsplit(":", 1)
        late = max(0.0, time.time() - cmd["at"])
        if not late:
            time.sleep(max(0.0, cmd["at"] - time.time()))
        window_s = (traffic.warmup_send_s if k >= WARMUP_BASE
                    else traffic.send_s)
        out = send_paced(fresh.pop(k), corpus, traffic.cycles_of(k),
                         (host, int(port)), window_s, cmd.get("skip", ()),
                         cmd.get("until"))
        out.update(k=k, start_late_s=late)
        print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
