"""Sink routing over a FlushBatch's columns (`metric_sink_routing`).

A series' route, the sinks its rules name, is a function of its
`(name, tags)`, and a `FlushBatch` holds exactly those as columns. So a
routed flush builds no `InterMetric`: `ColumnRouter.route` gives every
row of every section a route id, from which each sink's share of the
batch is one boolean mask per section (`BatchRoutes.share`), handed to
the sink as a `FlushBatch` like an unrouted flush's.

Routes are kept between flushes. The column store hands a live key the
same `str` and the same tags list flush after flush, so a section is
mostly last flush's, row for row: the router keeps the `names` and
`tags` arrays it last routed for each section and their route ids,
compares the new arrays against them by value, and runs the rules
(`util/matcher.py`, unchanged) only over rows that differ or are new.
"""

from __future__ import annotations

from typing import Dict, List, Sequence, Tuple

import numpy as np

from veneur_tpu.core.flusher import FlushBatch, le_tags
from veneur_tpu.util.matcher import Matcher, SinkRoutingMatcher

# a section's kept routes go when this many flushes did not meet it
KEPT_IDLE_FLUSHES = 3


class _KeptRoutes:
    """What the router keeps of one section between flushes: the arrays
    it routed, each row's route id, and the flush that last met it."""

    __slots__ = ("names", "tags", "ids", "used")

    def __init__(self, names: np.ndarray, tags: np.ndarray,
                 ids: np.ndarray, used: int):
        self.names = names
        self.tags = tags
        self.ids = ids
        self.used = used


class ColumnRouter:
    """`rules` over columns. A route id indexes `routes`, the distinct
    sets of sinks met so far (few: at most one per outcome of the
    rules)."""

    def __init__(self, rules: Sequence[SinkRoutingMatcher]):
        self.rules = list(rules)
        # every rule's matchers in one list; rule r owns those at
        # _owned[r], so a tags list is put to each matcher once
        self._matchers: List[Matcher] = []
        self._owned: List[range] = []
        for rule in self.rules:
            at = len(self._matchers)
            self._matchers.extend(rule.matchers)
            self._owned.append(range(at, len(self._matchers)))
        self.routes: List[frozenset] = []
        self._ids: Dict[Tuple[bool, ...], int] = {}  # rules' outcome -> id
        # a section's kept routes, found again by the identity of its
        # first row (the kept arrays pin both objects)
        self._kept: Dict[Tuple[int, int], _KeptRoutes] = {}
        self._flushes = 0
        # a bucket row materialises as lines tagged `base + [le:<b>]`.
        # Where no tag matcher takes any `le:` tag (any rule list an
        # operator writes), the extra tag decides nothing and a row's
        # lines share the route of (name, base); else they are routed
        # one by one
        les = le_tags()
        self.le_sensitive = any(
            tm.match(le) for mt in self._matchers for tm in mt.tags
            for le in les)

    def _tag_halves(self, tags: Sequence[str]) -> Tuple[bool, ...]:
        return tuple(mt.match_tags(tags) for mt in self._matchers)

    def _route_id(self, name: str, halves: Tuple[bool, ...]) -> int:
        """`SinkRoutingMatcher.route` of every rule, united, as an id;
        `halves` are the matchers' answers to the row's tags."""
        matchers = self._matchers
        outcome = tuple(
            any(halves[j] and matchers[j].name.match(name) for j in owned)
            for owned in self._owned)
        rid = self._ids.get(outcome)
        if rid is None:
            sinks = frozenset().union(*(
                rule.matched if hit else rule.not_matched
                for rule, hit in zip(self.rules, outcome)))
            rid = self._ids[outcome] = len(self.routes)
            self.routes.append(sinks)
        return rid

    def route(self, batch: FlushBatch) -> "BatchRoutes":
        """Route ids for every row of `batch`; rows whose rules have to
        run do so under one `route_match` span of `batch.timing`."""
        self._flushes += 1
        misses = []  # (names, tags, rows to evaluate, ids to fill)
        cached = 0
        by_line = self.le_sensitive and bool(batch.bucket_sections)
        columns = [(sec.names, sec.tags) for sec in batch.sections]
        if not by_line:
            columns += [(bs.names, bs.tags) for bs in batch.bucket_sections]
        ids: List[np.ndarray] = []
        for names, tags in columns:
            sec_ids, miss = self._kept_ids(names, tags)
            ids.append(sec_ids)
            cached += names.shape[0] - len(miss)
            if len(miss):
                misses.append((names, tags, miss, sec_ids))
        evaluated = sum(len(miss) for _, _, miss, _ in misses)
        line_ids: List[np.ndarray] = []
        extra_ids = np.empty(len(batch.extras), np.intp)
        if misses or by_line or batch.extras:
            with batch.timing.phase("route_match", parent="route"):
                # a timer's series share one tags list: asked once a
                # flush (the batch pins the lists, so an id is one list)
                halves: Dict[int, Tuple[bool, ...]] = {}
                for names, tags, miss, sec_ids in misses:
                    picked = np.asarray(miss, np.intp)
                    for i, name, row_tags in zip(miss, names[picked].tolist(),
                                                 tags[picked].tolist()):
                        half = halves.get(id(row_tags))
                        if half is None:
                            half = halves[id(row_tags)] = \
                                self._tag_halves(row_tags)
                        sec_ids[i] = self._route_id(name, half)
                    # kept only now that every row has its id
                    self._kept[id(names[0]), id(tags[0])] = _KeptRoutes(
                        names, tags, sec_ids, self._flushes)
                if by_line:
                    line_ids = [self._line_ids(bs)
                                for bs in batch.bucket_sections]
                    evaluated += sum(x.shape[0] for x in line_ids)
                # statuses and backfilled series are new objects every
                # flush: nothing to keep
                for i, metric in enumerate(batch.extras):
                    extra_ids[i] = self._route_id(
                        metric.name, self._tag_halves(metric.tags))
                evaluated += len(batch.extras)
        for key in [key for key, kept in self._kept.items()
                    if kept.used + KEPT_IDLE_FLUSHES < self._flushes]:
            del self._kept[key]
        n = len(batch.sections)
        return BatchRoutes(self, batch, ids[:n],
                           line_ids if by_line else ids[n:],
                           extra_ids, by_line, evaluated, cached)

    def _kept_ids(self, names: np.ndarray,
                  tags: np.ndarray) -> Tuple[np.ndarray, Sequence[int]]:
        """-> (a section's route ids, the rows of it still to evaluate).
        Rows equal to the kept section's keep their id; with none to
        evaluate the kept section stays, also where this one is only its
        first rows (keys at its end did not report)."""
        n = names.shape[0]
        if not n:
            return np.empty(0, np.intp), ()
        kept = self._kept.get((id(names[0]), id(tags[0])))
        if kept is None:
            return np.empty(n, np.intp), range(n)
        same = min(n, kept.names.shape[0])
        differ = np.flatnonzero(
            ~(np.equal(names[:same], kept.names[:same])
              & np.equal(tags[:same], kept.tags[:same])))
        if not differ.size and n == same:
            kept.used = self._flushes
            return kept.ids[:n], ()
        ids = np.empty(n, np.intp)
        ids[:same] = kept.ids[:same]
        return ids, differ.tolist() + list(range(same, n))

    def _line_ids(self, bs) -> np.ndarray:
        """A bucket section's route ids line by line, over
        `base + [le:<bound>]`: the side where a rule takes an `le:` tag.
        Which bins are nonzero changes with every flush, so nothing is
        kept."""
        row, le, _values = bs.lines()
        les = le_tags()
        names, bases = bs.names.tolist(), bs.tags.tolist()
        ids = np.empty(row.shape[0], np.intp)
        for i, (r, k) in enumerate(zip(row.tolist(), le.tolist())):
            ids[i] = self._route_id(
                names[r], self._tag_halves(bases[r] + [les[k]]))
        return ids


class BatchRoutes:
    """One flush's routes: per section, per bucket section (by row, or
    by line with `bucket_lines`) and for `batch.extras`, the route id of
    every row."""

    def __init__(self, router: ColumnRouter, batch: FlushBatch,
                 sections: List[np.ndarray], buckets: List[np.ndarray],
                 extras: np.ndarray, bucket_lines: bool,
                 evaluated: int, cached: int):
        self._router = router
        self.batch = batch
        self.sections = sections
        self.buckets = buckets
        self.extras = extras
        self.bucket_lines = bucket_lines
        self.evaluated = evaluated  # rows whose rules ran this flush
        self.cached = cached        # rows that kept their route
        self._shares: Dict[str, FlushBatch] = {}

    def counts(self) -> Tuple[Dict[str, int], int]:
        """-> (series routed to each sink that got any, series routed
        nowhere); a bucket row counts its lines."""
        routes = self._router.routes
        per_route = np.zeros(len(routes), np.int64)
        for ids in self.sections + [self.extras]:
            per_route += np.bincount(ids, minlength=len(routes))
        for ids, bs in zip(self.buckets, self.batch.bucket_sections):
            weights = None if self.bucket_lines else np.diff(bs.indptr) + 1
            per_route += np.bincount(
                ids, weights, minlength=len(routes)).astype(np.int64)
        routed: Dict[str, int] = {}
        unrouted = 0
        for sinks, n in zip(routes, per_route.tolist()):
            if not n:
                continue
            if not sinks:
                unrouted += n
            for sink in sinks:
                routed[sink] = routed.get(sink, 0) + n
        return routed, unrouted

    def share(self, sink: str) -> FlushBatch:
        """The series routed to `sink`, as a `FlushBatch.select` of the
        batch (built once a sink)."""
        share = self._shares.get(sink)
        if share is None:
            to_sink = np.fromiter((sink in sinks
                                   for sinks in self._router.routes), bool)
            share = self._shares[sink] = self.batch.select(
                [to_sink[ids] for ids in self.sections],
                [to_sink[ids] for ids in self.buckets],
                to_sink[self.extras], bucket_lines=self.bucket_lines)
        return share

    def materialized_rows(self) -> int:
        """`InterMetric`s built so far for the shares handed out: 0
        while every sink took its share by columns."""
        distinct = {id(share): share for share in self._shares.values()}
        return sum(share.materialized_rows for share in distinct.values())
