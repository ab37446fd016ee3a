"""The trace reduction on a synthetic profile with a known idle share."""

from types import SimpleNamespace as NS

import pytest

from harness import metrics, trace


def event(name, start_us, dur_us):
    return NS(name=name, start_ns=start_us * 1000, duration_ns=dur_us * 1000)


def profile():
    ops = NS(name="XLA Ops", events=[
        event("%fusion.1 = f32[8]{0} fusion(...)", 0, 100_000),
        event("%copy-start.2 = (f32[8]) copy-start(...)", 50_000, 100_000),
        event("%fusion.1 = f32[8]{0} fusion(...)", 600_000, 50_000),
    ])
    steps = NS(name="Steps", events=[event("step", 0, 1_000_000)])
    host = NS(name="/host:CPU", lines=[NS(name="python", events=[
        event("noise", 0, 900_000)])])
    return NS(planes=[NS(name="/device:TPU:0", lines=[steps, ops]), host])


def test_known_idle_share():
    reduced = trace.reduce_profile(profile(), window_s=1.0)
    # busy: [0, 150 ms] united + [600, 650 ms] = 200 ms of a 1 s window
    assert reduced["planes"] == 1
    assert reduced["busy_s"] == pytest.approx(0.2)
    idle = metrics.read({"reader": {"kind": "trace", "stat": "idle_pct"}},
                        {"trace": reduced})
    assert idle == pytest.approx(80.0)
    assert reduced["device_ops"][0] == ["fusion.1", pytest.approx(0.15)]
    assert reduced["device_ops"][1] == ["copy-start.2", pytest.approx(0.1)]
    gaps = reduced["idle_gaps"]
    assert gaps[0] == ["before:fusion.1", pytest.approx(0.45)]
    assert gaps[1] == ["before:end_of_trace", pytest.approx(0.35)]


def test_gap_named_by_host_phase():
    phases = [("assembly", 200_000_000, 590_000_000)]
    reduced = trace.reduce_profile(profile(), 1.0, phases=phases)
    assert reduced["idle_gaps"][0][0] == "host:assembly"


def test_no_device_plane_reads_nothing():
    host_only = NS(planes=[profile().planes[1]])
    reduced = trace.reduce_profile(host_only, 1.0)
    assert reduced["planes"] == 0
    assert metrics.read({"reader": {"kind": "trace", "stat": "idle_pct"}},
                        {"trace": reduced}) is None
    # and the host planes stand in only where the rehearsal asks
    assert trace.reduce_profile(host_only, 1.0,
                                host_as_device=True)["planes"] == 1


def test_trace_metric_by_regex_and_unknown_device():
    reduced = trace.reduce_profile(profile(), 1.0)
    facts = {"trace": reduced, "device": {"kind": "TPU v5 lite"}}
    ms = metrics.read({"reader": {"kind": "trace", "stat": "device_time_ms",
                                  "match": "^fusion"}}, facts)
    assert ms == pytest.approx(150.0)
    assert metrics.read({"reader": {"kind": "trace", "stat":
                                    "device_time_ms", "match": "^nothing"}},
                        facts) is None
    share = metrics.read({"reader": {
        "kind": "trace", "stat": "roofline_pct", "match": "^copy-start",
        "bytes": 8.19e9}}, facts)
    assert share == pytest.approx(10.0)   # 10 ms at 819 GB/s of 100 ms
    with pytest.raises(KeyError):
        metrics.read({"reader": {"kind": "trace", "stat": "roofline_pct",
                                 "match": "^fusion", "flops": 1e9}},
                     {"trace": reduced, "device": {"kind": "TPU v9"}})
