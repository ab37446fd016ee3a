"""Reduction of a profiler trace to device busy time, idle gaps and the
device operations that took most time.

Works on anything shaped like `jax.profiler.ProfileData`: `.planes`, each
with `.name` and `.lines`, each line with `.name` and `.events`, each
event with `.name`, `.start_ns` and `.duration_ns`. The tests feed it a
small synthetic one with a known idle share.
"""

from __future__ import annotations

import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):\d+")
# the line of a device plane that holds one event per executed operation
OPS_LINE = "XLA Ops"
TOP = 10


def device_planes(profile, host_as_device: bool = False) -> list:
    """The planes of the devices; on a backend with none (the CPU
    rehearsal) the host planes stand in when `host_as_device`."""
    planes = [p for p in profile.planes if DEVICE_PLANE.match(p.name)]
    if not planes and host_as_device:
        planes = [p for p in profile.planes if p.name.startswith("/host:")]
    return planes


def short_name(name: str) -> str:
    """A device event is named by its whole HLO instruction; the part
    before " = " (`%fusion.23`) names it, the rest is its signature."""
    return name.split(" = ", 1)[0].lstrip("%")[:96]


def union_s(intervals) -> float:
    """Seconds covered by the union of (start_ns, end_ns) intervals."""
    total, end = 0, None
    for a, b in sorted(intervals):
        if end is None or a > end:
            total += b - a
            end = b
        elif b > end:
            total += b - end
            end = b
    return total / 1e9


def gaps(intervals, span) -> list:
    """[(gap_s, start_ns, end_ns)] between the merged busy intervals
    inside span = (start_ns, end_ns), longest first."""
    out, at = [], span[0]
    for a, b in sorted(intervals):
        if a > at:
            out.append(((a - at) / 1e9, at, a))
        at = max(at, b)
    if span[1] > at:
        out.append(((span[1] - at) / 1e9, at, span[1]))
    return sorted(out, reverse=True)


def name_gap(start_ns: int, end_ns: int, ended_by: str, phases) -> str:
    """What the host was doing in an idle gap: the phase of `phases`
    ([(name, start_ns, end_ns)] on the trace's clock) that covers most
    of it, else the operation that ended it."""
    best, cover = None, 0
    for name, a, b in phases or ():
        c = min(end_ns, b) - max(start_ns, a)
        if c > cover:
            best, cover = name, c
    if best is not None and cover * 2 >= end_ns - start_ns:
        return f"host:{best}"
    return f"before:{ended_by}"


def reduce_profile(profile, window_s: float, host_as_device: bool = False,
                   phases=None) -> dict:
    """busy_s averaged over the device planes, the ten device operations
    with most summed time, the ten longest idle gaps (named by the host
    phase that covers them, else by the operation that ended each), and
    the events by name for the `trace` layer metrics that match a regex."""
    planes = device_planes(profile, host_as_device)
    busy, by_name, calls, idle = [], {}, {}, []
    for plane in planes:
        lines = [ln for ln in plane.lines if ln.name == OPS_LINE] \
            or list(plane.lines)
        events = [(e.start_ns, e.start_ns + e.duration_ns, short_name(e.name))
                  for ln in lines for e in ln.events if e.duration_ns > 0]
        if not events:
            continue
        busy.append(union_s((a, b) for a, b, _ in events))
        for a, b, name in events:
            by_name[name] = by_name.get(name, 0.0) + (b - a) / 1e9
            calls[name] = calls.get(name, 0) + 1
        first = min(a for a, _, _ in events)
        span = (first, max(first + int(window_s * 1e9),
                           max(b for _, b, _ in events)))
        starts = sorted((a, name) for a, _, name in events)
        for gap_s, a, b in gaps(((a, b) for a, b, _ in events), span)[:TOP]:
            ended_by = next((n for s, n in starts if s >= b), "end_of_trace")
            idle.append((name_gap(a, b, ended_by, phases), gap_s))
    ranked = sorted(by_name.items(), key=lambda kv: kv[1], reverse=True)
    return {"planes": len(busy),
            "busy_s": sum(busy) / len(busy) if busy else 0.0,
            "window_s": window_s,
            "device_ops": [[n, s] for n, s in ranked[:TOP]],
            "idle_gaps": [[n, s] for n, s in
                          sorted(idle, key=lambda g: g[1], reverse=True)[:TOP]],
            "by_name": by_name, "calls": calls}


def newest_xplane(directory: str):
    found = glob.glob(os.path.join(directory, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    return max(found, key=os.path.getmtime) if found else None
