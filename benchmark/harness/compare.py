"""What one flush delivered to the intake against the plain references.

The references (`harness/refs/`) are copies of the repo's scalar
t-digest, HyperLogLog and log-linear histogram: plain Python, fed the
same parsed values the lines carried. Nothing here imports the program.

Three kinds of number are kept apart (README.md, "the line account"):

  delivery   lines sent and never read by the server are `failed`, not
             wrong. When the server read fewer lines than were sent, the
             datagrams that never arrived are inferred (every counted key
             of such a datagram is short by that datagram's lines) and
             taken out of the truth; everything is then compared again,
             as strictly as before. Where that cannot be done (a host
             that stood still loses thousands of datagrams of a replayed
             corpus: 1,000 counts cannot name 10,000 datagrams), the part
             is compared `bounded`: a key may be short, never over; what
             a short key's lines said is held to what was sent (below);
             and over the window the counts still have to add up to the
             lines the server read, so a read line that goes missing is
             wrong in either mode. A host that stands still across a tick
             also makes the server aggregate, after its late swap, lines
             it was sent before the tick: what one interval was left
             short of, key by key, the next may be over by (`carried`),
             and is compared bounded likewise.
  values     a line the server read that is absent from, or altered in,
             the flush is wrong: a key off its reference, or lines read
             and not aggregated (`read_not_aggregated`).
  events     compiles, late flushes, resizes: not here at all.

`compare_interval` returns numbers, never a verdict: `run.py` sets each
beside its limit.
"""

from __future__ import annotations

import numpy as np

from harness.refs import hll_ref, llhist_ref
from harness.refs.tdigest_ref import MergingDigest
from harness.traffic import FAMILIES, NAME_PREFIX, key_name

COUNTER, GAUGE, TIMER, SET, LLHIST = range(5)
TIMER_AGGREGATES = ("count", "min", "max")
# the counts of keys off their reference, and what is summed over parts
WRONG = ("scalar_keys_wrong", "timer_stats_wrong", "llhist_keys_wrong",
         "set_keys_wrong")
SUMMED = WRONG + ("lines_aggregated", "lines_slack", "lines_short",
                  "lines_carried", "datagrams_lost")


def not_aggregated(read: int, aggregated: int, slack: int = 0) -> int:
    """Lines the server read that the flushes do not account for, or
    account for twice. `slack`: lines of a bounded part's gauges and sets,
    which carry no count and may or may not have arrived."""
    gap = int(read) - int(aggregated)
    return -gap if gap < 0 else max(0, gap - int(slack))


def series_values(series_lists, interval_s: float) -> dict:
    """{name: value} of the benchmark's own series in one flush's bodies,
    bucket series keyed with their le tag. A Datadog `rate` is undone to
    the count it was (value * interval; counts here are whole numbers)."""
    out = {}
    for series in series_lists:
        for s in series:
            name = s["metric"]
            if not name.startswith(NAME_PREFIX):
                continue
            value = s["points"][0][1]
            if s.get("type") == "rate":
                value = value * interval_s
                if abs(value - round(value)) < 1e-9 * max(1.0, abs(value)):
                    value = float(round(value))
            if name.endswith(".bucket"):
                le = next(t for t in s["tags"] if t.startswith("le:"))
                name = f"{name}|{le}"
            # a series sent twice is counted twice: the sum is then wrong
            out[name] = out[name] + value if name in out else value
    return out


def pct_name(name: str, p: float) -> str:
    return f"{name}.{int(p * 100)}percentile"


class Comparer:
    """Compares flushes of one cell."""

    def __init__(self, traffic, percentiles, check: dict):
        self.traffic = traffic
        self.percentiles = tuple(percentiles)
        self.check = check
        self.cold_max = int(check.get("cold_timer_max_samples", 8))
        self._chosen_memo: dict = {}
        # what the interval compared last was left short of, for the next:
        # (k, {part index: {(family, key): (lines, their values)}})
        self._left = (None, {})

    def _chosen(self, part_index: int, fam: str, ids: np.ndarray):
        """Which of a part's keys of `fam` meet their reference: drawn
        once from the seed, the same in every interval; all of them
        where the traffic file's `check` names no number."""
        memo = (part_index, fam)
        if memo not in self._chosen_memo:
            want = self.check.get({"timer": "timers", "set": "sets",
                                   "llhist": "llhists"}[fam])
            if want is None or want >= ids.size:
                chosen = set(ids.tolist())
            else:
                rng = np.random.default_rng(
                    [self.traffic.seed, 9999, part_index, FAMILIES.index(fam)])
                first = int(self.check.get("timer_first", 100)) \
                    if fam == "timer" else 0
                chosen = set(ids[:first].tolist()) | set(
                    rng.choice(ids, min(ids.size, int(want)),
                               replace=False).tolist())
            self._chosen_memo[memo] = chosen
        return self._chosen_memo[memo]

    # -- one interval -------------------------------------------------------

    def compare_interval(self, k: int, got: dict, sent: int, read: int,
                         lossy=None) -> dict:
        """Interval k's truth against one flush's values. `sent` and
        `read` are the generator's and the server's line counts of the
        interval; `lossy` says whether keys may be short of lines that
        never arrived, or over by lines the flush before was short of (by
        default: where this interval's `read` is under `sent`; `run.py`
        passes whether the window was disturbed, because a reading of
        `read` taken late moves lines between two intervals' counts).
        Intervals are compared in their order. Returns counts of what
        disagreed, the widest gaps, the lines the flush accounts for and
        how many keys were compared."""
        lossy = sent > read if lossy is None else bool(lossy)
        carried = self._left[1] if lossy and self._left[0] == k - 1 else {}
        done, left = [], {}
        for index, (lines, copies) in enumerate(self.traffic.truth(k)):
            w = used = np.full(len(lines), copies, np.int64)
            part = self._compare_part(index, lines, w, got)
            if lossy and not self._settled(part):
                bases, named = [], None
                if part["deficit"]:
                    trial = w.copy()
                    lost = self._take_out_lost(lines, trial,
                                               dict(part["deficit"]))
                    if lost:
                        named = self._compare_part(index, lines, trial, got)
                        named["datagrams_lost"] = lost
                        # a datagram sent once is named by its keys, unless
                        # so many were lost that one which arrived has
                        # every key short too; one of many copies is not
                        if copies == 1:
                            bases.append((trial, lost))
                if named is not None and self._settled(named):
                    part, used = named, trial
                else:
                    for used, lost in bases + [(w, 0)]:
                        part = self._compare_part(
                            index, lines, used, got, bounded=True,
                            carried=carried.get(index))
                        part["datagrams_lost"] = lost
                        if not any(part[name] for name in WRONG):
                            break
            left[index] = self._left_short(lines, w - used, part)
            done.append(part)
        self._left = (k, left)
        res = self._merge(done, got)
        res["lines_sent"], res["lines_read"] = int(sent), int(read)
        res["lines_failed"] = max(0, int(sent) - int(read))
        res["read_not_aggregated"] = not_aggregated(
            read, res["lines_aggregated"], res["lines_slack"])
        return res

    @staticmethod
    def _settled(part: dict) -> bool:
        """No counted key short or off (a set key carries no count of its
        lines: one that is off is never taken for delivery's loss)."""
        return not part["deficit"] and not any(
            part[name] for name in WRONG if name != "set_keys_wrong")

    @staticmethod
    def _left_short(lines, gone: np.ndarray, part: dict) -> dict:
        """{(family, key): (lines, values)}: what this part's flush was
        left short of (the lines of the datagrams named as lost, and what
        a bounded part's keys were short), with all the values the key
        was sent: the next flush may hold that many of them."""
        short = dict(part["deficit"]) if part["bounded"] else {}
        for name, n in part["sets_short"].items():
            short[name] = short.get(name, 0) + n
        at = np.flatnonzero(gone > 0)
        for f, key, n in zip(lines.fam[at].tolist(), lines.key[at].tolist(),
                             gone[at].tolist()):
            short[(f, key)] = short.get((f, key), 0) + n
        if not short:
            return {}
        code = lines.fam.astype(np.int64) * (1 << 40) + lines.key
        order = np.argsort(code, kind="stable")
        sorted_code = code[order]
        out = {}
        for (f, key), n in short.items():
            c = f * (1 << 40) + key
            a, b = np.searchsorted(sorted_code, [c, c + 1])
            out[(f, key)] = (int(n), lines.value[order[a:b]])
        return out

    @staticmethod
    def _merge(done: list, got: dict) -> dict:
        res = {name: sum(p[name] for p in done) for name in SUMMED}
        res["compared"] = {name: sum(p["compared"][name] for p in done)
                           for name in done[0]["compared"]}
        res["first_wrong"] = [t for p in done for t in p["first_wrong"]][:5]
        res["bounded_parts"] = sum(1 for p in done if p["bounded"])
        for gap in ("timer_rank_gap", "cold_timer_rel_gap"):
            widest = max(done, key=lambda p: p[gap])
            res[gap] = widest[gap]
            if gap + "_at" in widest:
                res[gap + "_at"] = widest[gap + "_at"]
        own = sum(1 for name in got
                  if not name.startswith(NAME_PREFIX + "llhist."))
        res["unexpected_series"] = max(
            0, own - sum(p["expected"] for p in done))
        return res

    @staticmethod
    def _take_out_lost(lines, w: np.ndarray, deficit: dict) -> int:
        """Lower `w` by the datagrams that never arrived: a datagram was
        lost x times where every counted key in it (timers and llhists
        by their count, counters and gauges by their absence) is short by
        x times its lines there. Returns the datagrams taken out."""
        if not deficit:
            return 0
        short = np.array([deficit.get((f, key), 0) for f, key in
                          zip(lines.fam.tolist(), lines.key.tolist())])
        counted = lines.fam != SET
        candidates = np.setdiff1d(
            np.unique(lines.datagram[counted & (short > 0)]),
            np.unique(lines.datagram[counted & (short <= 0)]))
        order = np.argsort(lines.datagram, kind="stable")
        first = np.searchsorted(lines.datagram[order], candidates)
        last = np.searchsorted(lines.datagram[order], candidates, "right")
        lost = 0
        for a, b in zip(first.tolist(), last.tolist()):
            whole = order[a:b]
            at = whole[counted[whole]]
            keys = list(zip(lines.fam[at].tolist(), lines.key[at].tolist()))
            mult = {key: keys.count(key) for key in set(keys)}
            x = min(deficit.get(key, 0) // m for key, m in mult.items())
            x = int(min(x, w[at].min()))
            if x > 0:
                for key, m in mult.items():
                    deficit[key] -= x * m
                w[whole] -= x
                lost += x
        return lost

    def _compare_part(self, index: int, lines, w: np.ndarray, got: dict,
                      bounded: bool = False, carried=None) -> dict:
        """One part's lines, each sent `w` times, against the flush.
        `deficit` = {(family, key): lines short} of the counted keys.
        `carried` (bounded only): what the flush before was left short
        of, which this one may hold: `_left_short`'s."""
        carried = carried or {}
        res = {"scalar_keys_wrong": 0, "timer_stats_wrong": 0,
               "timer_rank_gap": 0.0, "cold_timer_rel_gap": 0.0,
               "set_keys_wrong": 0, "llhist_keys_wrong": 0,
               "lines_aggregated": 0, "lines_slack": 0, "lines_short": 0,
               "lines_carried": 0, "sets_short": {},
               "datagrams_lost": 0, "first_wrong": [], "deficit": {},
               "bounded": bounded, "expected": 0,
               "compared": {"scalars": 0, "timers": 0, "cold_timers": 0,
                            "short_timers": 0, "sets": 0,
                            "sets_off_by_one": 0, "llhists": 0}}
        deficit = res["deficit"]

        def wrong(kind: str, text: str) -> None:
            res[kind] += 1
            if len(res["first_wrong"]) < 5:
                res["first_wrong"].append(text)

        def of(code):
            at = np.flatnonzero(lines.fam == code)
            return lines.key[at], lines.value[at], w[at]

        res["expected"] += self._scalars(of(COUNTER), "counter", True, got,
                                         res, wrong, deficit, bounded,
                                         carried)
        res["expected"] += self._scalars(of(GAUGE), "gauge", False, got,
                                         res, wrong, deficit, bounded,
                                         carried)
        res["expected"] += self._timers(index, of(TIMER), got, res, wrong,
                                        deficit, bounded, carried)
        res["expected"] += self._sets(index, of(SET), got, res, wrong,
                                      bounded, carried)
        self._llhists(index, of(LLHIST), got, res, wrong, deficit, bounded,
                      carried)
        res["lines_short"] = int(sum(deficit.values())) if bounded else 0
        return res

    # -- families -----------------------------------------------------------

    def _scalars(self, part, fam, additive, got, res, wrong, deficit,
                 bounded, carried) -> int:
        ids, values, w = part
        present = 0
        code = COUNTER if additive else GAUGE
        for i, v, n in zip(ids.tolist(), values.tolist(), w.tolist()):
            name = key_name(fam, i)
            have = got.get(name)
            before = carried.get((code, i))
            if n <= 0 and not (before and have is not None):
                if have is not None:
                    wrong("scalar_keys_wrong",
                          f"{name}: got {have}, want no series")
                continue
            res["compared"]["scalars"] += 1
            want = v * n if additive else v
            if have is None:
                deficit[(code, i)] = n
                if not bounded:
                    wrong("scalar_keys_wrong",
                          f"{name}: got nothing, want {want}")
                continue
            present += 1
            if not bounded or (n == 1 and not before):
                res["lines_aggregated"] += n
                if have != want:
                    wrong("scalar_keys_wrong",
                          f"{name}: got {have}, want {want}")
            elif additive:
                # m of its n copies and c of the lines the flush before
                # was left short of (one value each): whole numbers
                c_max, p = (before[0], float(before[1][0])) if before \
                    else (0, 0.0)
                fits = [(m, c) for m in range(max(n, 0) + 1)
                        for c in range(c_max + 1)
                        if m + c and v * m + p * c == have]
                if not fits:
                    wrong("scalar_keys_wrong", f"{name}: got {have}, want "
                          f"{v} times a whole number up to {n}"
                          + (f" and {p} up to {c_max} times" if before
                             else ""))
                    fits = [(n, 0)]
                m, c = fits[0]
                res["lines_aggregated"] += m + c
                res["lines_carried"] += c
                if m < n:
                    deficit[(code, i)] = n - m
            else:
                # a gauge says nothing of how many of its lines arrived
                c_max = before[0] if before else 0
                res["lines_aggregated"] += 1
                res["lines_slack"] += max(n, 0) - 1 + c_max
                if have != want and not (
                        before and have in before[1].tolist()):
                    wrong("scalar_keys_wrong",
                          f"{name}: got {have}, want {want}")
        return present

    @staticmethod
    def _by_key(ids, values, w):
        """Lines grouped by key: [(key, values, weights)], weight > 0."""
        keep = w > 0
        ids, values, w = ids[keep], values[keep], w[keep]
        order = np.argsort(ids, kind="stable")
        ids, values, w = ids[order], values[order], w[order]
        uniq, start = np.unique(ids, return_index=True)
        stop = np.append(start[1:], ids.size)
        return uniq, start, stop, values, w

    def _timers(self, index, part, got, res, wrong, deficit, bounded,
                carried) -> int:
        all_ids = np.unique(part[0])
        uniq, start, stop, values, w = self._by_key(*part)
        chosen = self._chosen(index, "timer", all_ids)
        live = set(uniq.tolist())
        present = 0
        for i in all_ids.tolist():
            if i not in live and key_name("timer", i) + ".count" in got \
                    and (TIMER, i) not in carried:
                wrong("timer_stats_wrong",
                      f"{key_name('timer', i)}: series of a key that sent "
                      "nothing")
        for i, a, b in zip(uniq.tolist(), start.tolist(), stop.tolist()):
            name = key_name("timer", i)
            vals, wts = values[a:b], w[a:b]
            n = int(wts.sum())
            f32 = vals.astype(np.float32)
            want = (float(n), float(f32.min()), float(f32.max()))
            have = tuple(got.get(f"{name}.{agg}") for agg in TIMER_AGGREGATES)
            if have[0] is None:
                deficit[(TIMER, i)] = n
                if not bounded:
                    wrong("timer_stats_wrong",
                          f"{name}: count/min/max got nothing, want {want}")
                continue
            present += sum(1 for h in have if h is not None) + sum(
                1 for p in self.percentiles if pct_name(name, p) in got)
            res["lines_aggregated"] += int(have[0])
            short = more = 0
            if have != want:
                # bounded: some of its lines never arrived, or some that
                # the flush before was left short of arrived here: its
                # count is a whole number, over by no more than those, and
                # its extremes are two of the values it was sent
                before = carried.get((TIMER, i))
                more = before[0] if before else 0
                net = have[0] - n
                if net < 0:
                    deficit[(TIMER, i)] = int(-net)
                if not (bounded and have[0] == int(have[0]) >= 1
                        and (net < 0 or more) and net <= more
                        and None not in have[1:] and have[1] <= have[2]
                        and np.isin(have[1:], f32 if not before else
                                    np.append(f32, before[1].astype(
                                        np.float32))).all()):
                    wrong("timer_stats_wrong",
                          f"{name}: count/min/max got {have}, want {want}"
                          + (f" and up to {more} lines more" if more else ""))
                    continue
                short = int(more - net)
                res["lines_carried"] += int(max(net, 0))
            if i in chosen:
                self._percentiles(name, vals, wts, n, got, res, wrong,
                                  short, more, int(have[0]))
        return present

    def _percentiles(self, name, vals, wts, n, got, res, wrong,
                     short: int = 0, more: int = 0, total=None) -> None:
        """A timer key's flushed percentiles. Up to `cold_max` samples
        the digest holds every sample, and the value itself is compared
        with the reference digest's. Beyond, the value is ranked in the
        data themselves, as tests/test_tdigest.py holds a digest: each
        distinct value sits at the centre of its own weight in the exact
        CDF, and a flushed value between two of them may stand for any
        rank between their centres (with n samples a rank is only known
        to 1/n: interpolation rules differ by that much). The gap is the
        distance from p to that bracket.

        A key that may be `short` of that many of its n lines and hold up
        to `more` lines of the interval before (bounded mode), `total` by
        its count, is ranked in what was sent, with the bracket widened
        by what those lines can move a centre: with cumulative weight a
        in the n sent it lies in [(a - short) / total, (a + more) /
        total] of those read. Such a key of up to `cold_max` samples is
        not compared: which of its values arrived is not known."""
        cold = n <= self.cold_max
        if short or more:
            res["compared"]["short_timers"] += 1
            if cold:
                return
        res["compared"]["cold_timers" if cold else "timers"] += 1
        if cold:
            ref = MergingDigest(100.0)
            for v, wt in zip(vals.tolist(), wts.tolist()):
                ref.add(v, float(wt))
        else:
            values, inverse = np.unique(vals, return_inverse=True)
            weight = np.bincount(inverse, weights=wts)
            centre = (np.cumsum(weight) - weight / 2.0) / weight.sum()
        for p in self.percentiles:
            series = pct_name(name, p)
            value = got.get(series)
            if value is None:
                wrong("timer_stats_wrong", f"{series}: got nothing")
            elif cold:
                q = ref.quantile(p)
                gap = abs(value - q) / max(abs(q), 1e-30)
                if gap > res["cold_timer_rel_gap"]:
                    res["cold_timer_rel_gap"] = gap
                    res["cold_timer_rel_gap_at"] = (
                        f"{series}: got {value}, reference {q}")
            else:
                # the neighbours of the flushed value, a float32's
                # rounding apart or more
                slack = abs(value) * 1e-6
                below = int(np.searchsorted(values, value - slack)) - 1
                above = int(np.searchsorted(values, value + slack, "right"))
                lo = centre[below] if below >= 0 else 0.0
                hi = centre[above] if above < values.size else 1.0
                if short or more:
                    lo = max(0.0, (lo * n - short) / total)
                    hi = min(1.0, (hi * n + more) / total)
                gap = max(0.0, lo - p, p - hi)
                if gap > res["timer_rank_gap"]:
                    res["timer_rank_gap"] = gap
                    res["timer_rank_gap_at"] = (
                        f"{series}: got {value}, which the data rank "
                        f"between {lo} and {hi}")

    def _sets(self, index, part, got, res, wrong, bounded, carried) -> int:
        all_ids = np.unique(part[0])
        uniq, start, stop, values, w = self._by_key(*part)
        chosen = self._chosen(index, "set", all_ids)
        present = 0
        for i, a, b in zip(uniq.tolist(), start.tolist(), stop.tolist()):
            name = key_name("set", i)
            have = got.get(name)
            if have is None:
                if not bounded:
                    wrong("set_keys_wrong", f"{name}: got nothing")
                continue
            present += 1
            # an estimate carries no count of lines: a set key accounts
            # for its lines where its estimate is the reference's (or it
            # is not among the keys chosen to meet their reference); in a
            # bounded part only a whole estimate says that each member
            # arrived, once at the least
            credit = "lines_slack" if bounded else "lines_aggregated"
            if i not in chosen:
                res[credit] += int(w[a:b].sum())
                continue
            res["compared"]["sets"] += 1
            ref = hll_ref.HLL()
            for member in values[a:b].tolist():
                ref.insert(f"u{int(member)}".encode())
            want = ref.estimate()
            if have == want:
                res["lines_aggregated"] += (b - a) if bounded \
                    else int(w[a:b].sum())
                res["lines_slack"] += int(w[a:b].sum()) - (b - a) \
                    if bounded else 0
                continue
            if bounded and have < want:
                res["lines_slack"] += int(w[a:b].sum())
                res["sets_short"][(SET, i)] = int(w[a:b].sum())
                continue
            if bounded and (SET, i) in carried:
                # members of the interval before, and maybe not all its own
                res["lines_slack"] += int(w[a:b].sum()) + carried[(SET, i)][0]
                continue
            # off by one only where the reference's own value sits on a
            # rounding boundary that float32 device arithmetic can cross
            regs = np.asarray(ref.regs)
            ez = float(np.count_nonzero(regs == 0))
            raw = (hll_ref._ALPHA * hll_ref.M * (hll_ref.M - ez)
                   / (hll_ref.beta14(ez)
                      + float(np.sum(np.exp2(-regs.astype(np.float64))))))
            if abs(have - want) == 1.0 and abs(raw - round(raw)) < 1e-3:
                res["compared"]["sets_off_by_one"] += 1
                res[credit] += int(w[a:b].sum())
            else:
                wrong("set_keys_wrong", f"{name}: estimate {have}, want "
                      f"{want} (pre-floor {raw})")
        return present

    def _llhists(self, index, part, got, res, wrong, deficit, bounded,
                 carried) -> None:
        uniq, start, stop, values, w = self._by_key(*part)
        for i, a, b in zip(uniq.tolist(), start.tolist(), stop.tolist()):
            name = key_name("llhist", i)
            have = got.get(name + ".count")
            n = int(w[a:b].sum())
            if have is None:
                deficit[(LLHIST, i)] = n
                if not bounded:
                    wrong("llhist_keys_wrong", f"{name}: got nothing")
                continue
            res["lines_aggregated"] += int(have)
            res["compared"]["llhists"] += 1
            more = carried[(LLHIST, i)][0] if (LLHIST, i) in carried else 0
            if bounded and have != n and 1 <= have <= n + more and got.get(
                    name + ".bucket|le:+Inf") == have == int(have):
                # short of some lines, or over by some of the interval
                # before: which, its count cannot say
                if have < n:
                    deficit[(LLHIST, i)] = n - int(have)
                res["lines_carried"] += int(max(have - n, 0))
                continue
            ref = llhist_ref.LLHist()
            ref.insert_many(values[a:b], w[a:b])
            ok = (have == float(ref.count())
                  and got.get(name + ".bucket|le:+Inf") == float(ref.count())
                  and np.isclose(got.get(name + ".sum", np.nan), ref.sum(),
                                 rtol=1e-12))
            for p, want in zip(self.percentiles,
                               ref.quantiles(self.percentiles)):
                ok = ok and np.isclose(got.get(pct_name(name, p), np.nan),
                                       want, rtol=1e-5)
            if not ok:
                if have < n:
                    deficit[(LLHIST, i)] = n - int(have)
                wrong("llhist_keys_wrong",
                      f"{name}: count {have}, want {ref.count()}; sum "
                      f"{got.get(name + '.sum')}, want {ref.sum()}")
