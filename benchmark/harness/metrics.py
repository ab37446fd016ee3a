"""Readers of the per-layer metrics: five kinds, implemented once.

A file under `benchmark/layer_metrics/` names its metric, `layer`,
`unit`, `better`, `moves`, and a `reader` of one of these kinds:

  prometheus    {"row": R}: the delta of /metrics row R (labels summed)
                over the window; with "lines_per": true, the lines read
                in the window over that delta.
  flush_phase   {"keys": [...]}: those `/debug/flush` phase keys summed,
                mean over the window's flush rounds, in milliseconds.
  intake        {"stat": "max_ms" | "mean_ms" | "late" |
                "bodies_per_flush"}: a statistic of the window's flushes
                as the intake saw them.
  trace         {"stat": "idle_pct"} or {"stat": "device_time_ms",
                "match": regex}, or {"stat": "roofline_pct", "match":
                regex, "flops": F, "bytes": B} (per call) against
                peaks.json: from the reduced profiler trace.
  harness       {"key": K}: a count the harness itself keeps over the
                window (`compiles_in_window`: programs JAX compiled or
                loaded from its cache, by its monitoring events;
                `resize_events`).

A reader that finds nothing to read returns None and the harness leaves
the metric out of the line. It never returns 0 for a share of a roofline.
"""

from __future__ import annotations

import re

from harness import peaks

def read_prometheus(reader: dict, facts: dict):
    before, after = facts.get("prom_before"), facts.get("prom_after")
    row = reader["row"]
    if before is None or after is None or row not in after:
        return None
    delta = after[row] - before.get(row, 0.0)
    if reader.get("lines_per"):
        return facts["lines_read"] / delta if delta > 0 else None
    return delta


def read_flush_phase(reader: dict, facts: dict):
    rounds = [r for r in facts.get("flush_rounds") or ()
              if all(k in r.get("phases", {}) for k in reader["keys"])]
    if not rounds:
        return None
    return 1000.0 * sum(sum(r["phases"][k] for k in reader["keys"])
                        for r in rounds) / len(rounds)


def read_intake(reader: dict, facts: dict):
    flushes = facts.get("flushes")
    if not flushes:
        return None
    stat = reader["stat"]
    if stat == "max_ms":
        return max(f["latency_ms"] for f in flushes)
    if stat == "mean_ms":
        return sum(f["latency_ms"] for f in flushes) / len(flushes)
    if stat == "late":
        return float(sum(1 for f in flushes
                         if f["latency_ms"] > facts["interval_s"] * 1000.0))
    if stat == "bodies_per_flush":
        return sum(f["bodies"] for f in flushes) / len(flushes)
    raise ValueError(f"unknown intake stat {stat!r}")


def read_trace(reader: dict, facts: dict):
    trace = facts.get("trace")
    if not trace or not trace["planes"]:
        return None
    stat = reader["stat"]
    if stat == "idle_pct":
        return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])
    match = re.compile(reader["match"])
    seconds = sum(s for n, s in trace["by_name"].items() if match.search(n))
    if seconds <= 0:
        return None
    if stat == "device_time_ms":
        return 1000.0 * seconds
    if stat == "roofline_pct":
        peak = peaks.peaks_of(facts["device"]["kind"])
        n = sum(c for name, c in trace["calls"].items() if match.search(name))
        least = n * max(reader.get("flops", 0) / peak["bf16_flops_per_s"],
                        reader.get("bytes", 0) / peak["hbm_bytes_per_s"])
        return 100.0 * least / seconds if least > 0 else None
    raise ValueError(f"unknown trace stat {stat!r}")


def read_harness(reader: dict, facts: dict):
    value = facts.get("harness", {}).get(reader["key"])
    return None if value is None else float(value)


READERS = {"prometheus": read_prometheus, "flush_phase": read_flush_phase,
           "intake": read_intake, "trace": read_trace,
           "harness": read_harness}


def read(metric: dict, facts: dict):
    reader = metric["reader"]
    if reader["kind"] not in READERS:
        raise ValueError(f"unknown reader kind {reader['kind']!r} (have "
                         f"{sorted(READERS)})")
    return READERS[reader["kind"]](reader, facts)
