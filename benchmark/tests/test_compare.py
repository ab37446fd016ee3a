"""The comparison, with the plain references standing in for the server
(tests/ideal.py): a datagram that never arrived is `failed` and nothing
else; a line the server read that is missing from, or altered in, the
flush is wrong."""

import numpy as np
import pytest

from harness.compare import Comparer, series_values
from harness.traffic import Traffic
from ideal import PERCENTILES, ideal_flush
from test_traffic import CONFIG, PER_INTERVAL, REPLAY

EXACT = ("scalar_keys_wrong", "timer_stats_wrong", "set_keys_wrong",
         "llhist_keys_wrong", "unexpected_series", "read_not_aggregated")
KINDS = {"per_interval": PER_INTERVAL, "replay": REPLAY}


def clean(res) -> bool:
    return (all(res[name] == 0 for name in EXACT)
            and res["timer_rank_gap"] <= 0.02
            and res["cold_timer_rel_gap"] <= 1.5e-6)


def cell(kind, seed=2_147_483_900):
    traffic = Traffic(KINDS[kind], CONFIG, seed)
    return traffic, Comparer(traffic, PERCENTILES, {"timers": 30, "sets": 5})


def without_datagrams(parts, drop):
    """Weights with `drop` = {(part, datagram): times} never arrived."""
    weights = [np.full(len(lines), copies, np.int64)
               for lines, copies in parts]
    lost = 0
    for (part, d), times in drop.items():
        at = parts[part][0].datagram == d
        weights[part][at] -= times
        lost += times * int(at.sum())
    return weights, lost


@pytest.mark.parametrize("kind", ["per_interval", "replay"])
def test_intact_flush_is_clean(kind):
    traffic, comparer = cell(kind)
    for k in (0, 1):
        sent = traffic.lines_of(k)
        res = comparer.compare_interval(k, ideal_flush(traffic.truth(k)),
                                        sent, sent)
        assert clean(res), res
        assert res["lines_aggregated"] == sent
        assert res["lines_failed"] == 0 and res["datagrams_lost"] == 0
        assert res["compared"]["timers"] + res["compared"]["cold_timers"] > 0


@pytest.mark.parametrize("kind,drop", [
    ("per_interval", {(0, 3): 1}),
    ("per_interval", {(0, 0): 1, (0, 17): 1, (0, 40): 1}),
    ("replay", {(1, 5): 1}),
    ("replay", {(1, 5): 2, (1, 60): 1, (0, 1): 1}),
])
def test_unread_datagram_is_failed_not_wrong(kind, drop):
    traffic, comparer = cell(kind)
    parts = traffic.truth(0)
    weights, lost = without_datagrams(parts, drop)
    sent = traffic.lines_of(0)
    res = comparer.compare_interval(0, ideal_flush(parts, weights), sent,
                                    sent - lost)
    assert clean(res), res
    assert res["lines_failed"] == lost
    assert res["lines_aggregated"] == sent - lost
    assert res["datagrams_lost"] == sum(drop.values())


@pytest.mark.parametrize("kind", ["per_interval", "replay"])
def test_lost_lines_are_excused_only_as_far_as_the_server_read_fewer(kind):
    """The same short flush with the server saying it read every line:
    nothing is taken as lost, and the flush is wrong."""
    traffic, comparer = cell(kind)
    parts = traffic.truth(0)
    weights, lost = without_datagrams(parts, {(len(parts) - 1, 3): 1})
    sent = traffic.lines_of(0)
    res = comparer.compare_interval(0, ideal_flush(parts, weights), sent,
                                    sent)
    assert not clean(res)
    # (a set key off its estimate accounts for none of its lines)
    assert res["read_not_aggregated"] >= lost and res["lines_failed"] == 0


def heavy_loss(parts, seed=5, share=0.3, whole_cycles=2):
    """Weights after a host that stood still: `whole_cycles` copies of
    every corpus datagram gone, and a `share` of the rest at random: far
    more datagrams than the counts of the keys can name one by one."""
    rng = np.random.default_rng(seed)
    weights, lost = [], 0
    for lines, copies in parts:
        gone = np.zeros(lines.n_datagrams, np.int64)
        if copies > 1:
            gone += whole_cycles + rng.binomial(copies - whole_cycles, share,
                                                lines.n_datagrams)
        weights.append(copies - gone[lines.datagram])
        lost += int(gone[lines.datagram].sum())
    return weights, lost


def test_loss_that_cannot_be_named_is_failed_and_compared_bounded():
    traffic, comparer = cell("replay")
    parts = traffic.truth(0)
    weights, lost = heavy_loss(parts)
    sent = traffic.lines_of(0)
    got = ideal_flush(parts, weights)
    res = comparer.compare_interval(0, got, sent, sent - lost)
    assert clean(res), res
    assert res["bounded_parts"] == 1 and res["lines_short"] == lost
    assert res["lines_failed"] == lost
    assert res["lines_aggregated"] == sent - lost
    assert res["compared"]["short_timers"] > 0
    # the window lost lines, this interval's own reading says it did not
    # (a reading taken late): the same, where the caller says so
    res = comparer.compare_interval(0, got, sent, sent, lossy=True)
    assert res["bounded_parts"] == 1 and res["timer_stats_wrong"] == 0
    assert res["read_not_aggregated"] == lost


@pytest.mark.parametrize("series,change,number", [
    # a line the server read and lost, beside those that never arrived
    ("bench.timer.000007.count", lambda v: v - 1, "read_not_aggregated"),
    ("bench.timer.000007.count", lambda v: 10_000.0, "timer_stats_wrong"),
    ("bench.timer.000007.count", lambda v: v - 0.5, "timer_stats_wrong"),
    ("bench.timer.000007.max", lambda v: v * 1.001, "timer_stats_wrong"),
    ("bench.timer.000007.min", lambda v: 0.0, "timer_stats_wrong"),
    ("bench.timer.000003.50percentile", lambda v: v * 3, "timer_rank_gap"),
])
def test_bounded_comparison_still_holds_the_server(series, change, number):
    traffic, comparer = cell("replay")
    comparer.check = {}
    parts = traffic.truth(0)
    weights, lost = heavy_loss(parts, share=0.1, whole_cycles=0)
    sent = traffic.lines_of(0)
    got = ideal_flush(parts, weights)
    got[series] = change(got[series])
    res = comparer.compare_interval(0, got, sent, sent - lost)
    assert res["bounded_parts"] == 1 and not clean(res)
    assert res[number] > (0.02 if number == "timer_rank_gap" else 0)


@pytest.mark.parametrize("step,named", [(9, True), (2, False)])
def test_many_datagrams_sent_once_and_lost(step, named):
    """A tenth of an interval's datagrams are still named one by one and
    the rest compared as strictly as ever; at a third (of datagrams of 7
    lines) one that arrived has every key short too, and the part is
    compared bounded."""
    traffic, comparer = cell("per_interval")
    parts = traffic.truth(0)
    drop = {(0, d): 1 for d in range(5, 60, step)}
    weights, lost = without_datagrams(parts, drop)
    sent = traffic.lines_of(0)
    res = comparer.compare_interval(0, ideal_flush(parts, weights), sent,
                                    sent - lost)
    assert clean(res), res
    assert res["lines_failed"] == lost
    if named:
        assert res["datagrams_lost"] == len(drop)
        assert not res["bounded_parts"]
        assert res["lines_aggregated"] == sent - lost
    else:
        assert res["bounded_parts"] == 1
        assert res["lines_aggregated"] <= sent - lost \
            <= res["lines_aggregated"] + res["lines_slack"]


@pytest.mark.parametrize("kind", ["per_interval", "replay"])
def test_lines_aggregated_after_a_late_swap_are_carried_not_wrong(kind):
    """A host that stood still across tick 1: some of interval 0's
    datagrams never arrive, some are aggregated after the late swap,
    into flush 2. Both flushes are clean and every line read is found;
    the same two flushes are wrong where nothing says the window was
    disturbed, or where flush 2 holds more than flush 1 was short of."""
    traffic, comparer = cell(kind)
    comparer.check = {}
    first, second = traffic.truth(0), traffic.truth(1)
    lines, copies = first[-1]
    late = np.zeros(lines.n_datagrams, np.int64)
    late[[3, 9, 10]] = 1 if copies == 1 else 2
    gone = np.zeros(lines.n_datagrams, np.int64)
    gone[[5, 20]] = 1
    if copies > 1:
        gone += np.random.default_rng(3).binomial(2, 0.3, gone.size)
    weights = [np.full(len(ls), c, np.int64) for ls, c in first]
    weights[-1] = copies - (late + gone)[lines.datagram]
    lost = int(gone[lines.datagram].sum())
    moved = int(late[lines.datagram].sum())
    sent = traffic.lines_of(0)
    got0 = ideal_flush(first, weights)
    got1 = ideal_flush(second + [(lines, 0)],
                       [np.full(len(ls), c, np.int64) for ls, c in second]
                       + [late[lines.datagram]])
    res0 = comparer.compare_interval(0, got0, sent, sent - lost - moved,
                                     lossy=True)
    res1 = comparer.compare_interval(1, got1, sent, sent + moved, lossy=True)
    assert clean({**res0, "read_not_aggregated": 0}), res0
    assert clean({**res1, "read_not_aggregated": 0}), res1
    found = res0["lines_aggregated"] + res1["lines_aggregated"]
    slack = res0["lines_slack"] + res1["lines_slack"]
    assert found <= 2 * sent - lost <= found + slack
    assert res1["lines_carried"] > 0 and res1["bounded_parts"] == 1
    if kind == "replay":
        assert slack == 0 and res1["lines_carried"] == moved

    strict = Comparer(traffic, PERCENTILES, {})
    strict.compare_interval(0, got0, sent, sent - lost - moved, lossy=True)
    assert not clean(strict.compare_interval(1, got1, sent, sent + moved))
    over = Comparer(traffic, PERCENTILES, {})
    over.compare_interval(0, got0, sent, sent - lost - moved, lossy=True)
    name = next(n for n in got1 if n.endswith(".count")
                and n.startswith("bench.timer."))
    got1[name] += 10_000.0
    assert over.compare_interval(1, got1, sent, sent + moved,
                                 lossy=True)["timer_stats_wrong"] >= 1


def drop_one_line(parts, fam, clear_of=None):
    """Weights with one line of family `fam` taken out: a line the
    server read and lost. Its key has no line in datagram `clear_of`."""
    weights = [np.full(len(lines), copies, np.int64)
               for lines, copies in parts]
    part = len(parts) - 1
    lines = parts[part][0]
    there = set(lines.key[(lines.fam == fam)
                          & (lines.datagram == clear_of)].tolist())
    at = int(next(i for i in np.flatnonzero(lines.fam == fam)
                  if lines.key[i] not in there))
    weights[part][at] -= 1
    return weights


@pytest.mark.parametrize("kind,fam,number", [
    ("per_interval", 0, "scalar_keys_wrong"),
    ("per_interval", 1, "scalar_keys_wrong"),
    ("per_interval", 2, "timer_stats_wrong"),
    ("per_interval", 3, "set_keys_wrong"),
    ("per_interval", 4, "llhist_keys_wrong"),
    ("replay", 2, "timer_stats_wrong"),
])
def test_read_line_missing_from_the_flush_is_wrong(kind, fam, number):
    traffic, comparer = cell(kind)
    comparer.check = {}          # every key meets its reference
    parts = traffic.truth(0)
    sent = traffic.lines_of(0)
    got = ideal_flush(parts, drop_one_line(parts, fam))
    res = comparer.compare_interval(0, got, sent, sent)
    assert res[number] >= 1 and not clean(res)
    # and with a datagram truly lost beside it, still wrong
    weights, lost = without_datagrams(parts, {(len(parts) - 1, 2): 1})
    gone = drop_one_line(parts, fam, clear_of=2)
    weights[-1] = np.minimum(weights[-1], gone[-1])
    res = comparer.compare_interval(0, ideal_flush(parts, weights), sent,
                                    sent - lost)
    assert not clean(res)


@pytest.mark.parametrize("series,change", [
    ("bench.counter.000007", lambda v: v + 1),
    ("bench.gauge.000003", lambda v: v + 0.25),
    ("bench.timer.000001.max", lambda v: v * 1.001),
    ("bench.timer.000069.50percentile", lambda v: v * (1 + 1e-5)),
    ("bench.timer.000000.50percentile", lambda v: v * 1.2),
    ("bench.set.000002", lambda v: v + 2),
    ("bench.llhist.000001.sum", lambda v: v * (1 + 1e-9)),
    ("bench.llhist.000001.99percentile", lambda v: v * 1.001),
])
def test_altered_value_is_wrong(series, change):
    traffic, comparer = cell("per_interval")
    comparer.check = {}
    sent = traffic.lines_of(0)
    got = ideal_flush(traffic.truth(0))
    got[series] = change(got[series])
    assert not clean(comparer.compare_interval(0, got, sent, sent))


def test_series_nobody_sent_and_series_sent_twice_are_wrong():
    traffic, comparer = cell("per_interval")
    sent = traffic.lines_of(0)
    got = ideal_flush(traffic.truth(0))
    got["bench.counter.999999"] = 1.0
    res = comparer.compare_interval(0, got, sent, sent)
    assert res["unexpected_series"] == 1
    body = [{"metric": "bench.counter.000001", "points": [[0, 1.2]],
             "type": "rate", "tags": []}]
    assert series_values([body], 10.0) == {"bench.counter.000001": 12.0}
    assert series_values([body, body], 10.0) == {"bench.counter.000001": 24.0}
    assert series_values([[{"metric": "veneur.flush", "points": [[0, 1]],
                            "type": "gauge", "tags": []}]], 10.0) == {}
