"""Flush generation: device snapshots -> InterMetrics + forwardable state.

Semantic parity with reference flusher.go:26-122 and samplers.go:359-514:

* A local server (forward_address set) emits only histogram *aggregates*
  for mixed-scope histograms/timers (no percentiles) and forwards their
  digests; a global server emits *percentiles* (no aggregates) for
  mixed-scope rows merged from its locals.
* Local-only rows always flush in their entirety (full percentiles +
  aggregates) on whichever server owns them.
* Global-only rows emit nothing on a local server (forward only) and
  flush with digest-derived ("global") aggregate values on a global one.
* Sets emit their HLL estimate as a gauge, on global servers only, except
  local-only sets which flush locally.
* Counters/gauges: mixed+local rows flush locally; global-only rows flush
  only on the global server.
"""

from __future__ import annotations

import math
import threading
import time
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from veneur_tpu.core.columnstore import ColumnStore, RowMeta
from veneur_tpu.core.telemetry import FlushRound
from veneur_tpu.samplers import metrics as m
from veneur_tpu.samplers.metrics import (
    Aggregate, HistogramAggregates, InterMetric, MetricScope, MetricType,
)


@dataclass
class ForwardableState:
    """Host-side snapshot of mergeable state bound for the global tier
    (the equivalent of reference worker.go:180-217 ForwardableMetrics)."""

    counters: List[Tuple[RowMeta, float]] = field(default_factory=list)
    gauges: List[Tuple[RowMeta, float]] = field(default_factory=list)
    # (meta, means, weights, min, max, reciprocal_sum)
    histograms: List[Tuple[RowMeta, np.ndarray, np.ndarray, float, float, float]] = \
        field(default_factory=list)
    # (meta, registers)
    sets: List[Tuple[RowMeta, np.ndarray]] = field(default_factory=list)
    # (meta, llhist bins int64) — exact-merge family: registers ADD
    llhists: List[Tuple[RowMeta, np.ndarray]] = field(default_factory=list)
    # pre-serialized metricpb frames (forward/convert.forwardable_to_wire),
    # populated by the readout on the flush thread, so the forward
    # thread goes straight to the POST; MUST be dropped whenever the
    # state lists mutate (carryover stash/drain call invalidate_wire)
    wire: Optional[List[bytes]] = None

    def __len__(self):
        return (len(self.counters) + len(self.gauges) + len(self.histograms)
                + len(self.sets) + len(self.llhists))

    def invalidate_wire(self) -> None:
        self.wire = None


def _percentile_name(name: str, p: float) -> str:
    # reference naming truncates: 0.999 -> "99percentile" (samplers.go:498)
    return f"{name}.{int(p * 100)}percentile"


def _fmt_le(bound: float) -> str:
    return "+Inf" if math.isinf(bound) else format(bound, ".12g")


def _flush_llhist_family(store, is_local: bool, percentiles, now: int,
                         final: List[InterMetric],
                         fwd: "ForwardableState",
                         collect_forward: bool, finished=None) -> None:
    """Snapshot + emit the llhist family (shared verbatim by the legacy
    and columnar flush paths, so they cannot diverge). The columnar
    path passes the already-finished snapshot (`finished`) so the
    family's device dispatch/sync ride the shared flush phases and get
    attributed like every other family; the legacy path snapshots
    inline.

    Scoping mirrors the t-digest family: a local server forwards the
    bins of mixed/global rows (no local emission — the global tier owns
    the exact distribution) and fully flushes local-only rows; a global
    server fully flushes everything it holds. A full flush emits the
    configured percentiles, the midpoint sum, the exact count, and the
    Prometheus-histogram-shaped cumulative buckets
    (`<name>.bucket{le:...}` + `+Inf`), which the Prometheus and Cortex
    sinks render as `_bucket`/`_sum`/`_count` series."""
    from veneur_tpu.ops import llhist_ref

    table = store.llhists
    ps = tuple(percentiles)
    need_export = is_local and collect_forward
    # bins are needed for forwarding AND for bucket emission; only a
    # local server with forwarding disabled could skip them, and that
    # configuration still emits local-only rows' buckets — so always on
    if finished is None:
        out, bins, touched, meta_list = table.snapshot_and_reset(ps)
    else:
        out, bins, touched, meta_list = finished
    rows = np.flatnonzero(touched)
    if rows.size == 0:
        return
    quants = out["quantiles"][rows]
    order = llhist_ref.ORDER
    upper = llhist_ref.UPPER_SORTED
    for i, row in enumerate(rows.tolist()):
        meta = meta_list[row]
        if meta is None:  # recycled mid-interval (reclaim straggler)
            continue
        regs = bins[i].astype(np.int64)  # the transferred row is int32
        scope = meta.scope
        if is_local and scope != MetricScope.LOCAL_ONLY:
            if need_export:
                fwd.llhists.append((meta, regs))
            continue
        # count and sum are derived from the HOST-side registers, not
        # the device readout: the count must equal the le:+Inf bucket
        # exactly (both are the same registers), and f64 midpoint math
        # keeps the sum consistent with what a downstream
        # re-aggregation would get
        count = int(regs.sum())
        total = llhist_ref.approx_sum(regs)
        names = meta.flush_names
        if names is None:
            names = meta.flush_names = {}
        tags = list(meta.tags)
        for j, p in enumerate(ps):
            nm = names.get(p)
            if nm is None:
                nm = names[p] = _percentile_name(meta.name, p)
            final.append(InterMetric(
                name=nm, timestamp=now, value=float(quants[i, j]),
                tags=list(tags), type=MetricType.GAUGE))
        for suffix, value, mtype in (
                ("sum", total, MetricType.GAUGE),
                ("count", float(count), MetricType.COUNTER)):
            nm = names.get(suffix)
            if nm is None:
                nm = names[suffix] = f"{meta.name}.{suffix}"
            final.append(InterMetric(
                name=nm, timestamp=now, value=value,
                tags=list(tags), type=mtype))
        bname = names.get("bucket")
        if bname is None:
            bname = names["bucket"] = f"{meta.name}.bucket"
        c_sorted = regs[order]
        csum = np.cumsum(c_sorted)
        for k in np.flatnonzero(c_sorted).tolist():
            final.append(InterMetric(
                name=bname, timestamp=now, value=float(csum[k]),
                tags=tags + [f"le:{_fmt_le(upper[k])}"],
                type=MetricType.COUNTER))
        final.append(InterMetric(
            name=bname, timestamp=now, value=float(csum[-1]),
            tags=tags + ["le:+Inf"], type=MetricType.COUNTER))


def flush_columnstore(
    store: ColumnStore,
    is_local: bool,
    percentiles: Sequence[float],
    aggregates: HistogramAggregates,
    collect_forward: bool = True,
) -> Tuple[List[InterMetric], ForwardableState]:
    """Snapshot+reset every table and generate final metrics plus the
    forwardable snapshot (empty unless is_local and collect_forward)."""
    now = int(time.time())
    final: List[InterMetric] = []
    fwd = ForwardableState()

    # ---- counters & gauges --------------------------------------------
    # hot-loop shape: bulk-convert the touched rows of each device
    # snapshot to Python lists once (numpy scalar indexing and enum
    # bit-ops per row are what made a 100k-key flush burn seconds of
    # GIL time)
    def _flush_scalar_rows(vals, touched, meta_list, fwd_list, mtype):
        rows = np.flatnonzero(touched)
        vlist = np.asarray(vals, np.float64)[rows].tolist()
        for i, row in enumerate(rows.tolist()):
            meta = meta_list[row]
            if meta is None:  # recycled mid-interval (reclaim straggler)
                continue
            if meta.scope == MetricScope.GLOBAL_ONLY and is_local:
                if collect_forward:
                    fwd_list.append((meta, vlist[i]))
                continue
            final.append(InterMetric(
                name=meta.name, timestamp=now, value=vlist[i],
                tags=list(meta.tags), type=mtype))

    c_vals, c_touched, c_meta = store.counters.snapshot_and_reset()
    _flush_scalar_rows(c_vals, c_touched, c_meta, fwd.counters,
                       MetricType.COUNTER)
    g_vals, g_touched, g_meta = store.gauges.snapshot_and_reset()
    _flush_scalar_rows(g_vals, g_touched, g_meta, fwd.gauges,
                       MetricType.GAUGE)

    # ---- histograms & timers ------------------------------------------
    # full percentile list is always used for local-only rows
    # (flusher.go:383-404); the server-level list applies to mixed rows.
    # Aggregates are always the configured set (generateInterMetrics passes
    # s.HistogramAggregates unconditionally, flusher.go:360-371) — on a
    # global server the Local* guards suppress everything except median.
    full_ps = tuple(percentiles)
    server_ps = () if is_local else full_ps
    server_aggs = aggregates
    all_ps = tuple(sorted(set(full_ps) | {0.5}))  # median always computable
    need_export = is_local and collect_forward
    out, export, h_touched, h_meta = store.histos.snapshot_and_reset(
        all_ps, need_export=need_export)
    ps_index = {p: i for i, p in enumerate(all_ps)}
    if export is not None:
        exp_means, exp_weights, exp_min, exp_max, exp_recip = export

    h_rows = np.flatnonzero(h_touched)
    cols = {k: np.asarray(out[k], np.float64)[h_rows].tolist()
            for k in ("lmin", "lmax", "lsum", "lweight", "lrecip",
                      "min", "max", "sum", "count", "hmean")}
    quants = np.asarray(out["quantiles"], np.float64)[h_rows].tolist()
    server_agg_bits = int(server_aggs.value)
    full_agg_bits = int(aggregates.value)

    for i, row in enumerate(h_rows.tolist()):
        meta = h_meta[row]
        if meta is None:  # recycled mid-interval (reclaim straggler)
            continue
        scope = meta.scope
        if scope == MetricScope.MIXED:
            ps, agg_bits, use_global = server_ps, server_agg_bits, False
        elif scope == MetricScope.LOCAL_ONLY:
            ps, agg_bits, use_global = full_ps, full_agg_bits, False
        else:  # GLOBAL_ONLY
            if is_local:
                ps, agg_bits, use_global = (), 0, False
            else:
                ps, agg_bits, use_global = full_ps, full_agg_bits, True
        if need_export and scope != MetricScope.LOCAL_ONLY:
            fwd.histograms.append((
                meta, exp_means[row].copy(), exp_weights[row].copy(),
                float(exp_min[row]), float(exp_max[row]),
                float(exp_recip[row])))
        final.extend(_flush_histo_row(
            meta, i, cols, quants[i], ps_index, now, ps, agg_bits,
            use_global))

    # ---- log-linear histograms ----------------------------------------
    _flush_llhist_family(store, is_local, percentiles, now, final, fwd,
                         collect_forward)

    # ---- sets ----------------------------------------------------------
    estimates, registers, s_touched, s_meta = store.sets.snapshot_and_reset()
    s_rows = np.flatnonzero(s_touched)
    e_list = np.asarray(estimates, np.float64)[s_rows].tolist()
    for i, row in enumerate(s_rows.tolist()):
        meta = s_meta[row]
        if meta is None:  # recycled mid-interval (reclaim straggler)
            continue
        if meta.scope == MetricScope.LOCAL_ONLY:
            final.append(InterMetric(
                name=meta.name, timestamp=now, value=e_list[i],
                tags=list(meta.tags), type=MetricType.GAUGE))
            continue
        if is_local:
            if collect_forward:
                fwd.sets.append((meta, registers[row].copy()))
            continue
        final.append(InterMetric(
            name=meta.name, timestamp=now, value=e_list[i],
            tags=list(meta.tags), type=MetricType.GAUGE))

    # ---- status checks -------------------------------------------------
    st_vals, st_touched, st_meta = store.statuses.snapshot_and_reset()
    for row in np.flatnonzero(st_touched).tolist():
        meta = st_meta[row]
        if meta is None:  # recycled mid-interval (reclaim straggler)
            continue
        entry = st_vals[row]
        final.append(InterMetric(
            name=meta.name, timestamp=now, value=entry.value,
            tags=list(meta.tags), type=MetricType.STATUS,
            message=entry.message, hostname=entry.hostname))

    return final, fwd


# plain-int aggregate masks: IntFlag's __and__ allocates an enum member
# per test, which at 100k keys x 7 aggregates is real GIL time
_A_MIN = int(Aggregate.MIN)
_A_MAX = int(Aggregate.MAX)
_A_MEDIAN = int(Aggregate.MEDIAN)
_A_AVERAGE = int(Aggregate.AVERAGE)
_A_COUNT = int(Aggregate.COUNT)
_A_SUM = int(Aggregate.SUM)
_A_HMEAN = int(Aggregate.HARMONIC_MEAN)


def _flush_histo_row(
    meta: RowMeta, row: int, cols: Dict[str, list], qrow: list,
    ps_index: Dict[float, int], now: int,
    percentiles: Sequence[float], agg_bits: int,
    use_global: bool,
) -> List[InterMetric]:
    """Emit aggregate + percentile metrics for one histogram row; condition
    and value-selection parity with reference samplers.go:359-514."""
    ms: List[InterMetric] = []
    a = agg_bits
    lmin, lmax = cols["lmin"][row], cols["lmax"][row]
    lsum, lweight = cols["lsum"][row], cols["lweight"][row]
    lrecip = cols["lrecip"][row]
    dmin, dmax = cols["min"][row], cols["max"][row]
    dsum, dcount = cols["sum"][row], cols["count"][row]
    drecip_hmean = cols["hmean"][row]

    names = meta.flush_names
    if names is None:
        names = meta.flush_names = {}

    def emit(suffix, value, mtype=MetricType.GAUGE):
        nm = names.get(suffix)
        if nm is None:
            nm = names[suffix] = f"{meta.name}.{suffix}"
        ms.append(InterMetric(
            name=nm, timestamp=now, value=value,
            tags=list(meta.tags), type=mtype))

    if (a & _A_MAX) and (not math.isinf(lmax) or use_global):
        emit("max", dmax if use_global else lmax)
    if (a & _A_MIN) and (not math.isinf(lmin) or use_global):
        emit("min", dmin if use_global else lmin)
    if (a & _A_SUM) and (lsum != 0 or use_global):
        emit("sum", dsum if use_global else lsum)
    if (a & _A_AVERAGE) and (use_global or (lsum != 0 and lweight != 0)):
        emit("avg", (dsum / dcount) if use_global else (lsum / lweight))
    if (a & _A_COUNT) and (lweight != 0 or use_global):
        emit("count", dcount if use_global else lweight, MetricType.COUNTER)
    if a & _A_MEDIAN:
        emit("median", qrow[ps_index[0.5]])
    if (a & _A_HMEAN) and (
            use_global or (lrecip != 0 and lweight != 0)):
        emit("hmean", drecip_hmean if use_global else (lweight / lrecip))

    for p in percentiles:
        nm = names.get(p)
        if nm is None:
            nm = names[p] = _percentile_name(meta.name, p)
        ms.append(InterMetric(
            name=nm, timestamp=now, value=qrow[ps_index[p]],
            tags=list(meta.tags), type=MetricType.GAUGE))
    return ms


# --------------------------------------------------------------------------
# Columnar flush: the TPU-first production path.
#
# flush_columnstore above is the readable per-row spec (kept as the parity
# oracle — tests pin the two paths equal); flush_columnstore_batch is what
# the server runs. It differs in shape, not semantics:
#
#   * every table's device flush is DISPATCHED first, then synced once —
#     over the device link the per-table snapshot sync was a
#     serialized queue-drain each;
#   * per-row value selection and emission guards become numpy mask math
#     over the touched rows;
#   * the result is a FlushBatch of columnar sections. Sinks that don't
#     care about per-metric objects (blackhole, and any sink that can
#     serialize columns directly) never materialize them; everything
#     else gets the exact legacy List[InterMetric] via materialize(),
#     built once and shared across sink threads.
#
# At 100k keys the legacy loop built ~325k InterMetrics per flush inside
# the GIL while ingest threads competed for the same core — the dominant
# term in the sustained flush-latency gate (BENCH_r05_manual: p50 10.7s
# against a 10s interval). The columnar path assembles the same flush in
# milliseconds of numpy.
# --------------------------------------------------------------------------


@dataclass
class FlushSection:
    """One homogeneous column group: parallel names/values/tags arrays
    sharing a metric type. `tags` entries are per-row list refs shared
    with RowMeta — consumers must copy before mutating (materialize
    does). `rows`: the table rows the series come from, ascending, where
    the section has them (an encoder finds a row's state again by it)."""

    names: np.ndarray   # object ndarray of str
    values: np.ndarray  # float64
    tags: np.ndarray    # object ndarray of List[str] (shared refs)
    mtype: MetricType
    rows: Optional[np.ndarray] = None  # int64, parallel to names

    def select(self, mask: np.ndarray) -> Optional["FlushSection"]:
        """The rows a boolean mask picks: this very section where it
        picks all (a consumer that keeps state per section finds it
        again, and nothing is copied), None where it picks none."""
        if mask.all():
            return self
        if not mask.any():
            return None
        return FlushSection(self.names[mask], self.values[mask],
                            self.tags[mask], self.mtype,
                            None if self.rows is None else self.rows[mask])


_LE_TAGS: Optional[List[str]] = None


def le_tags() -> List[str]:
    """`le:<bound>` tag strings for every sorted llhist bin plus the
    final `le:+Inf`, indexed by BucketSection.le_idx."""
    global _LE_TAGS
    if _LE_TAGS is None:
        from veneur_tpu.ops import llhist_ref
        _LE_TAGS = [f"le:{_fmt_le(u)}" for u in llhist_ref.UPPER_SORTED]
        _LE_TAGS.append("le:+Inf")
    return _LE_TAGS


@dataclass
class BucketSection:
    """Cumulative llhist buckets, CSR over the emitted llhists: row `i`
    owns the entries `indptr[i]:indptr[i + 1]`, one per NONZERO
    register in value-ascending order (`llhist_ref.cumulative_entries`).
    A row materializes as COUNTER `<name>` lines tagged
    `le_tags()[le_idx[k]]` with value `cum[k]` for each of its entries,
    plus an unconditional `le:+Inf` line carrying `total[i]` — exactly
    `_flush_llhist_family`'s per-row loop. `tags` rows are base
    tag-list refs (copy before mutating)."""

    names: np.ndarray   # object ndarray of str ("<base>.bucket")
    tags: np.ndarray    # object ndarray of List[str] (base tags, no le:)
    indptr: np.ndarray  # (rows + 1,) int64 entry offsets
    le_idx: np.ndarray  # (nnz,) position of the bin in value order
    cum: np.ndarray     # (nnz,) float64 cumulative count at that bin
    total: np.ndarray   # (rows,) float64 the row's count (le:+Inf)

    def line_count(self) -> int:
        return self.le_idx.shape[0] + self.names.shape[0]

    def select(self, mask: np.ndarray) -> Optional["BucketSection"]:
        """The rows a boolean mask picks, each with all its lines, as
        CSR again; this very section where it picks all, None where it
        picks none."""
        if mask.all():
            return self
        if not mask.any():
            return None
        counts = np.diff(self.indptr)
        indptr = np.zeros(int(mask.sum()) + 1, np.int64)
        np.cumsum(counts[mask], out=indptr[1:])
        entries = np.repeat(mask, counts)
        return BucketSection(self.names[mask], self.tags[mask], indptr,
                             self.le_idx[entries], self.cum[entries],
                             self.total[mask])

    def lines(self) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
        """Line by line, in `materialize()`'s order (a row's entries,
        then its `le:+Inf`): the line's row, its `le_tags()` index and
        its value."""
        rows = self.names.shape[0]
        row = np.repeat(np.arange(rows), np.diff(self.indptr) + 1)
        is_inf = np.zeros(row.shape[0], bool)
        is_inf[self.indptr[1:] + np.arange(rows)] = True
        le = np.empty(row.shape[0], np.int64)
        le[is_inf] = len(le_tags()) - 1
        le[~is_inf] = self.le_idx
        values = np.empty(row.shape[0], np.float64)
        values[is_inf] = self.total
        values[~is_inf] = self.cum
        return row, le, values

    def select_lines(self, mask: np.ndarray):
        """The lines a boolean mask over `lines()` picks, for lines of
        one row that go different ways: this very section where it picks
        all, None where it picks none, else a COUNTER `FlushSection` of
        the picked lines, each tagged `base + [le:<bound>]` as
        `materialize()` tags it (the CSR cannot leave a row's `le:+Inf`
        out)."""
        if mask.all():
            return self
        if not mask.any():
            return None
        row, le, values = (col[mask] for col in self.lines())
        les = le_tags()
        tags = np.empty(row.shape[0], object)
        for i, (base, k) in enumerate(zip(self.tags[row].tolist(),
                                          le.tolist())):
            tags[i] = base + [les[k]]
        return FlushSection(self.names[row], values, tags,
                            MetricType.COUNTER)

    def rows(self, scale: float = 1.0):
        """Row by row: (name, base tags, `le_tags()` indices, values),
        the row's entries and then -1 (`le:+Inf`) with its total; the
        values divided by `scale` (a sink's interval)."""
        indptr, le_idx = self.indptr.tolist(), self.le_idx.tolist()
        cum = (self.cum / scale).tolist()
        totals = (self.total / scale).tolist()
        for i, (name, tags) in enumerate(zip(self.names.tolist(),
                                             self.tags.tolist())):
            lo, hi = indptr[i], indptr[i + 1]
            yield name, tags, le_idx[lo:hi] + [-1], cum[lo:hi] + [totals[i]]


class FlushBatch:
    """Columnar flush result. len() counts metrics; materialize() yields
    the legacy List[InterMetric] (cached, thread-safe — sink flush
    threads share one materialization)."""

    def __init__(self, timestamp: int, sections: List[FlushSection],
                 extras: List[InterMetric],
                 bucket_sections: Optional[List[BucketSection]] = None):
        self.timestamp = timestamp
        self.sections = sections
        self.bucket_sections: List[BucketSection] = bucket_sections or []
        self.extras = extras  # statuses: carry message/hostname fields
        # the span source of the round that delivers this batch: the
        # server sets its own before the sink threads start, and a sink
        # times its encode and sends into it
        self.timing = FlushRound()
        self._materialized: Optional[List[InterMetric]] = None
        self._mat_lock = threading.Lock()

    def __len__(self) -> int:
        return (sum(s.names.shape[0] for s in self.sections)
                + sum(b.line_count() for b in self.bucket_sections)
                + len(self.extras))

    def select(self, sections: Sequence[np.ndarray],
               buckets: Sequence[np.ndarray], extras: np.ndarray,
               bucket_lines: bool = False) -> "FlushBatch":
        """The share of this batch that boolean masks pick: one per
        section and one per bucket section, over its rows, and one over
        `extras`; with `bucket_lines` a bucket section's mask is over
        its `lines()`. Shares `timestamp` and `timing`; a section picked
        whole is the same object in the share, one picked empty is left
        out, and a batch picked whole is this batch (so sinks that take
        all of it share one `materialize()`). `len()` and
        `materialize()` of the share are those of the picked series."""
        secs = [sec.select(mask)
                for sec, mask in zip(self.sections, sections)]
        bsecs = [bs.select_lines(mask) if bucket_lines else bs.select(mask)
                 for bs, mask in zip(self.bucket_sections, buckets)]
        if extras.all() and all(
                a is b for a, b in zip(
                    secs + bsecs, self.sections + self.bucket_sections)):
            return self
        share = FlushBatch(
            self.timestamp,
            [sec for sec in secs + bsecs if isinstance(sec, FlushSection)],
            [m for m, keep in zip(self.extras, extras.tolist()) if keep],
            [bs for bs in bsecs if isinstance(bs, BucketSection)])
        share.timing = self.timing
        return share

    @property
    def materialized_rows(self) -> int:
        """How many `InterMetric`s `materialize()` has built: 0 for a
        batch that only columnar consumers have seen."""
        built = self._materialized
        return 0 if built is None else len(built)

    def materialize(self) -> List[InterMetric]:
        with self._mat_lock:
            if self._materialized is None:
                ts = self.timestamp
                out: List[InterMetric] = []
                for sec in self.sections:
                    tp = sec.mtype
                    out.extend(
                        InterMetric(name=n, timestamp=ts, value=v,
                                    tags=list(t), type=tp)
                        for n, v, t in zip(sec.names.tolist(),
                                           sec.values.tolist(),
                                           sec.tags.tolist()))
                les = le_tags()
                for bs in self.bucket_sections:
                    for nm, base, idxs, values in bs.rows():
                        out.extend(
                            InterMetric(name=nm, timestamp=ts, value=v,
                                        tags=base + [les[k]],
                                        type=MetricType.COUNTER)
                            for k, v in zip(idxs, values))
                out.extend(self.extras)
                self._materialized = out
            return self._materialized


def _valid_rows(touched: np.ndarray, meta_list) -> np.ndarray:
    """Touched rows whose snapshot meta is live (reclaim stragglers have
    meta None — legacy skips them row by row)."""
    rows = np.flatnonzero(touched)
    if rows.size == 0:
        return rows
    keep = np.fromiter((meta_list[r] is not None for r in rows.tolist()),
                       bool, rows.size)
    return rows[keep] if not keep.all() else rows


def _handles_by_device(handles) -> Dict[str, list]:
    """Group a family's device handles by the device that owns them
    ("platform:id"), splitting sharded arrays into their addressable
    per-device shards — so a per-device `block_until_ready` attributes
    sync stall to the device actually causing it. Host-side arrays
    (numpy) land under "host"."""
    import jax

    groups: Dict[str, list] = {}
    for leaf in jax.tree_util.tree_leaves(handles):
        shards = getattr(leaf, "addressable_shards", None)
        if shards:
            for sh in shards:
                d = sh.device
                groups.setdefault(f"{d.platform}:{d.id}", []).append(sh.data)
        else:
            groups.setdefault("host", []).append(leaf)
    return groups


def swap_columnstore(
    store: ColumnStore,
    is_local: bool,
    percentiles: Sequence[float],
    collect_forward: bool = True,
    timing: Optional[FlushRound] = None,
) -> dict:
    """Critical-path half of the columnar flush: swap every family's
    pending columns and device-state generation out at ONE interval
    boundary, with no device readout work at all (each table's swap_out
    is O(1) under its locks — see columnstore._BaseTable). Ingest
    continues into the fresh generations the moment this returns; the
    swapped snapshot is private to the readout and can be drained on a
    background executor (`readout_columnstore`). The host-dominant
    families (statuses) snapshot in full here so every family shares
    the same boundary. `timing`, when given, takes the `swap` span."""
    with (timing or FlushRound()).phase("swap", parent="store_flush"):
        return _swap_columnstore(store, is_local, percentiles,
                                 collect_forward)


def _swap_columnstore(store: ColumnStore, is_local: bool,
                      percentiles: Sequence[float],
                      collect_forward: bool) -> dict:
    full_ps = tuple(percentiles)
    all_ps = tuple(sorted(set(full_ps) | {0.5}))
    need_export = is_local and collect_forward
    swap = {
        "now": int(time.time()),
        "full_ps": full_ps,
        "all_ps": all_ps,
        "histogram": store.histos.swap_out(ps=all_ps,
                                           need_export=need_export),
        "counter": store.counters.swap_out(),
        "gauge": store.gauges.swap_out(),
        # llhist bins always on: forwarding and bucket emission both
        # need them — see _flush_llhist_family
        "llhist": store.llhists.swap_out(ps=full_ps, need_bins=True),
        "set": store.sets.swap_out(),
        "status": store.statuses.snapshot_and_reset(),
    }
    return swap


def readout_columnstore(
    store: ColumnStore,
    swap: dict,
    is_local: bool,
    aggregates: HistogramAggregates,
    collect_forward: bool = True,
    timing: Optional[FlushRound] = None,
    attribute: bool = False,
) -> Tuple[FlushBatch, ForwardableState]:
    """Background half of the columnar flush: dispatch every swapped
    generation's readout kernels, sync, transfer, and assemble the
    FlushBatch + ForwardableState. Same snapshot semantics and emission
    rules as the legacy path (the docstring at module top); touches no
    live table state (beyond telemetry counters and the donated-buffer
    recycle), so it runs concurrently with ingest and with the next
    interval's accumulation. `timing`, when given, receives the spans
    (all under a `readout` parent): one `dispatch` per family, back to
    back, so their sum is `dispatch_s`; `device_sync` around the `sync`
    and `transfer` spans; `assembly` around one `assembly_<family>` per
    family block and `recycle`. With `attribute` every family
    is synced on its own, device by device, so a sync stall is booked
    to the family and device that caused it: that is the DEFAULT path of
    a server (`latency_observatory: true`, `Server._run_readout`), and
    `latency.family_tree` builds the round's `families` tree from its
    spans. Without it everything still on the device is drained in one
    `sync`: the path no benchmark cell has measured.

    Where the chip's time shows. Every family's dispatch is
    asynchronous. The sets need their estimate on the host to fill in
    the rows the device does not hold, so `dispatch{set}` dispatches it
    (`SetTable.readout(collect=False)`) and `assembly_set` collects it
    (`SetTable.collect`: `set_wait`, `set_transfer`, `set_host_estimate`),
    after the other families' syncs, transfers and scalar and histogram
    assembly: the chip runs the sets' programs meanwhile, and a copy of
    a ready output does not queue behind them. The flush thread waits
    for the chip in the `sync` spans and in `set_wait`; `chip_wait_s`
    (`Server._flush_locked`) is the two together. With a device
    observatory each family's output handles go to its completion
    watcher at the end of the family's dispatch; it closes one
    `chip_busy{family,device}` span a family and device (what the device
    ran up to that family's completion: `deviceobs._ReadoutWatcher` says
    what such a span books to whom) and is joined once the sets are
    collected, before the drained generations are recycled (the
    counters' and gauges' outputs are their captured states)."""
    import jax

    timing = timing or FlushRound()
    now = swap["now"]
    fwd = ForwardableState()
    sections: List[FlushSection] = []
    full_ps = swap["full_ps"]
    all_ps = swap["all_ps"]
    ps_index = {p: i for i, p in enumerate(all_ps)}
    need_export = is_local and collect_forward
    full_bits = int(aggregates.value)
    local_code = int(MetricScope.LOCAL_ONLY)
    global_code = int(MetricScope.GLOBAL_ONLY)
    deviceobs = getattr(store, "deviceobs", None)
    watcher = deviceobs.readout_watcher() if deviceobs is not None else None
    # family -> its output handles by device, grouped once: what the
    # watcher waits for and what the attributed path syncs
    by_device: Dict[str, Dict[str, list]] = {}

    def dispatching(family: str):
        """One family's dispatch span."""
        return timing.phase("dispatch", parent="readout", family=family)

    def dispatched(family: str, span: dict, handles: list) -> list:
        """The outputs of a family whose dispatch span has closed, on
        their way to the completion watcher."""
        if watcher is not None or attribute:
            by_device[family] = _handles_by_device(handles)
        if watcher is not None and by_device[family]:
            watcher.watch(timing, family, by_device[family],
                          timing.t0 + span["start_s"])
        return handles

    # ---- phase 1: dispatch every device flush, sync nothing ------------
    # (the per-family dispatch spans are back-to-back, so their sum IS
    # the dispatch_s total)
    with dispatching("histogram") as span:
        h_snap = store.histos.readout(swap["histogram"], timing)
    h_handles = dispatched("histogram", span, [
        h for h in (h_snap["packed"], h_snap["export_packed"])
        if h is not None])
    with dispatching("counter") as span:
        c_snap = store.counters.readout(swap["counter"], timing)
    c_handles = dispatched("counter", span, list(c_snap["dev"]))
    with dispatching("gauge") as span:
        g_snap = store.gauges.readout(swap["gauge"], timing)
    g_handles = dispatched("gauge", span, [g_snap["dev"]])
    with dispatching("llhist") as span:
        ll_snap = store.llhists.readout(swap["llhist"], timing)
    ll_handles = dispatched("llhist", span, [
        h for h in (ll_snap["packed"], ll_snap["bins_dev"])
        if h is not None])
    # the estimate is dispatched here and collected in `assembly_set`
    with dispatching("set") as span:
        set_snap = store.sets.readout(swap["set"], timing, collect=False)
    estimate = set_snap.get("_estimate", {}).get("dev")
    dispatched("set", span, [] if estimate is None else [estimate])
    with dispatching("status"):
        st_vals, st_touched, st_meta = swap["status"]

    # ---- phase 2: drain the device queue, then transfer ----------------
    family_finishes = (
        ("counter", c_handles,
         lambda: store.counters.snapshot_finish(c_snap)),
        ("gauge", g_handles,
         lambda: store.gauges.snapshot_finish(g_snap)),
        ("histogram", h_handles,
         lambda: store.histos.snapshot_finish(h_snap)),
        ("llhist", ll_handles,
         lambda: store.llhists.snapshot_finish(ll_snap)),
    )
    finished = {}
    with timing.phase("device_sync", parent="readout"):
        if not attribute:
            # one queue drain for everything still on device
            with timing.phase("sync", parent="device_sync"):
                jax.block_until_ready([h for _f, hs, _fn in family_finishes
                                       for h in hs])
        for family, _handles, finish in family_finishes:
            if attribute:
                for dev, dev_handles in by_device[family].items():
                    with timing.phase("sync", parent="device_sync",
                                      family=family, device=dev):
                        jax.block_until_ready(dev_handles)
            with timing.phase("transfer", parent="device_sync",
                              family=family):
                finished[family] = finish()
    c_vals, c_touched, c_meta = finished["counter"]
    g_vals, g_touched, g_meta = finished["gauge"]
    out, export, h_touched, h_meta = finished["histogram"]
    assembly = timing.phase("assembly", parent="readout").start()
    try:
        # ---- counters & gauges -----------------------------------------
        def scalar_family(table, vals, touched, meta_list, mtype, fwd_list):
            rows = _valid_rows(touched, meta_list)
            if rows.size == 0:
                return
            vals_sel = np.asarray(vals, np.float64)[rows]
            if is_local:
                fwd_mask = table.scope_code[rows] == global_code
                if fwd_mask.any():
                    if collect_forward:
                        fwd_list.extend(
                            (meta_list[r], v)
                            for r, v in zip(rows[fwd_mask].tolist(),
                                            vals_sel[fwd_mask].tolist()))
                    keep = ~fwd_mask
                    rows, vals_sel = rows[keep], vals_sel[keep]
            if rows.size:
                sections.append(FlushSection(
                    table.flush_names("", rows, meta_list, lambda m: m.name),
                    vals_sel, table.flush_tags(rows, meta_list), mtype,
                    rows))

        with timing.phase("assembly_scalar", parent="assembly"):
            scalar_family(store.counters, c_vals, c_touched, c_meta,
                          MetricType.COUNTER, fwd.counters)
            scalar_family(store.gauges, g_vals, g_touched, g_meta,
                          MetricType.GAUGE, fwd.gauges)

        # ---- histograms & timers -------------------------------------------
        with timing.phase("assembly_histogram", parent="assembly"):
            hr = _valid_rows(h_touched, h_meta)
            if hr.size:
                htab = store.histos
                scope = htab.scope_code[hr]
                local_only = scope == local_code
                global_only = scope == global_code
                # server_aggs == aggregates (flusher.go:360-371 passes the
                # configured set unconditionally), so the only per-scope
                # bits variation is global-only rows emitting nothing on a
                # local server
                a_on = np.where(global_only & is_local, 0, full_bits)
                use_global = global_only & (not is_local)
                emit_ps = local_only | (not is_local)

                cols = {k: np.asarray(out[k], np.float64)[hr]
                        for k in ("lmin", "lmax", "lsum", "lweight", "lrecip",
                                  "min", "max", "sum", "count", "hmean")}
                quants = np.asarray(out["quantiles"], np.float64)[hr]
                # one tag-cache pass for every histo section; sections slice it
                tags_hr = htab.flush_tags(hr, h_meta)

                def agg_section(suffix, mask, values, mtype=MetricType.GAUGE):
                    if not mask.any():
                        return
                    sections.append(FlushSection(
                        htab.flush_names(
                            suffix, hr[mask], h_meta,
                            lambda m, s=suffix: f"{m.name}.{s}"),
                        values[mask], tags_hr[mask], mtype, hr[mask]))

                lmin, lmax = cols["lmin"], cols["lmax"]
                lsum, lweight = cols["lsum"], cols["lweight"]
                lrecip = cols["lrecip"]
                dmin, dmax = cols["min"], cols["max"]
                dsum, dcount = cols["sum"], cols["count"]
                with np.errstate(divide="ignore", invalid="ignore"):
                    avg = np.where(use_global,
                                   dsum / np.where(dcount, dcount, 1.0),
                                   lsum / np.where(lweight, lweight, 1.0))
                    hmean = np.where(use_global, cols["hmean"],
                                     lweight / np.where(lrecip, lrecip, 1.0))
                agg_section("max", ((a_on & _A_MAX) != 0)
                            & (~np.isinf(lmax) | use_global),
                            np.where(use_global, dmax, lmax))
                agg_section("min", ((a_on & _A_MIN) != 0)
                            & (~np.isinf(lmin) | use_global),
                            np.where(use_global, dmin, lmin))
                agg_section("sum", ((a_on & _A_SUM) != 0)
                            & ((lsum != 0) | use_global),
                            np.where(use_global, dsum, lsum))
                agg_section("avg", ((a_on & _A_AVERAGE) != 0)
                            & (use_global
                               | ((lsum != 0) & (lweight != 0))), avg)
                agg_section("count", ((a_on & _A_COUNT) != 0)
                            & ((lweight != 0) | use_global),
                            np.where(use_global, dcount, lweight),
                            MetricType.COUNTER)
                agg_section("median", (a_on & _A_MEDIAN) != 0,
                            quants[:, ps_index[0.5]])
                agg_section("hmean", ((a_on & _A_HMEAN) != 0)
                            & (use_global | ((lrecip != 0) & (lweight != 0))),
                            hmean)

                if full_ps and emit_ps.any():
                    pr = hr[emit_ps]
                    pq = quants[emit_ps]
                    ptags = tags_hr[emit_ps]
                    for p in full_ps:
                        sections.append(FlushSection(
                            htab.flush_names(
                                p, pr, h_meta,
                                lambda m, p=p: _percentile_name(m.name, p)),
                            pq[:, ps_index[p]], ptags, MetricType.GAUGE,
                            pr))

                if need_export:
                    (exp_means, exp_weights, exp_min, exp_max,
                     exp_recip) = export
                    fr = hr[~local_only]
                    if fr.size:
                        # one bulk fancy-index copy into a COMPACT matrix,
                        # then row views into it: per-row .copy() was pure
                        # overhead on the forward config's flush path, but
                        # views into the full (K, 2C+3) export would pin
                        # ~capacity-sized memory for the lifetime of the
                        # async forward send
                        cm, cw = exp_means[fr], exp_weights[fr]
                        cmin, cmax = exp_min[fr], exp_max[fr]
                        crecip = exp_recip[fr]
                        for j, row in enumerate(fr.tolist()):
                            fwd.histograms.append((
                                h_meta[row], cm[j], cw[j], float(cmin[j]),
                                float(cmax[j]), float(crecip[j])))

        # ---- sets -----------------------------------------------------------
        with timing.phase("assembly_set", parent="assembly"):
            # the estimate dispatched in `dispatch{set}`, collected where it
            # is first needed (see above)
            store.sets.collect(set_snap, timing, parent="assembly_set")
            estimates, registers, s_touched, s_meta = \
                store.sets.snapshot_finish(set_snap)
            sr = _valid_rows(s_touched, s_meta)
            if sr.size:
                stab = store.sets
                s_local = stab.scope_code[sr] == local_code
                if is_local:
                    if collect_forward:
                        for row in sr[~s_local].tolist():
                            fwd.sets.append((s_meta[row],
                                             registers[row].copy()))
                    er = sr[s_local]
                else:
                    er = sr
                if er.size:
                    sections.append(FlushSection(
                        stab.flush_names("", er, s_meta, lambda m: m.name),
                        np.asarray(estimates, np.float64)[er],
                        stab.flush_tags(er, s_meta), MetricType.GAUGE, er))

        # ---- log-linear histograms ------------------------------------------
        # percentiles/sum/count columnarize like every other family; the
        # variable-length cumulative buckets become a BucketSection. The
        # transferred register table is mostly zeros (six samples a key
        # leave 6 of 4,501 registers live), so it is scanned ONCE for its
        # nonzero (row, bin, count) entries and everything is derived from
        # those: nothing here allocates a (rows, BINS) array. Exploded per
        # row only by materialize() and the legacy `_flush_llhist_family`
        # oracle (parity pinned by tests)
        with timing.phase("assembly_llhist", parent="assembly"):
            extras: List[InterMetric] = []
            bucket_sections: List[BucketSection] = []
            ll_out, ll_bins, ll_touched, ll_meta = finished["llhist"]
            llr = np.flatnonzero(ll_touched)
            if llr.size:
                from veneur_tpu.ops import llhist_ref

                lltab = store.llhists
                # ll_bins is compact over the touched rows in `llr` order;
                # `emit` marks the compact rows this server emits: no
                # reclaim stragglers and, on a local, no row it forwards
                emit = np.fromiter(
                    (ll_meta[r] is not None for r in llr.tolist()),
                    bool, llr.size)
                if is_local:
                    fwd_mask = emit & (lltab.scope_code[llr] != local_code)
                    if fwd_mask.any():
                        if need_export:
                            # the global merges whole rows: widen the ones
                            # that leave (a compact copy, so the forward
                            # send does not pin the transferred table)
                            fwd_bins = ll_bins[fwd_mask].astype(np.int64)
                            fwd.llhists.extend(
                                (ll_meta[row], fwd_bins[j]) for j, row
                                in enumerate(llr[fwd_mask].tolist()))
                        emit &= ~fwd_mask
                er = llr[emit]
                if er.size:
                    quants = np.asarray(ll_out["quantiles"], np.float64)[er]
                    tags_er = lltab.flush_tags(er, ll_meta)
                    for j, p in enumerate(full_ps):
                        sections.append(FlushSection(
                            lltab.flush_names(
                                p, er, ll_meta,
                                lambda m, p=p: _percentile_name(m.name, p)),
                            quants[:, j], tags_er, MetricType.GAUGE, er))
                    e_rows, e_bins, e_counts = \
                        llhist_ref.nonzero_entries(ll_bins)
                    kept = emit[e_rows]
                    e_bins, e_counts = e_bins[kept], e_counts[kept]
                    # compact row -> emitted row
                    e_rows = (np.cumsum(emit) - 1)[e_rows[kept]]
                    # count and sum from the HOST-side registers (see the
                    # legacy helper: count must equal the le:+Inf bucket)
                    sections.append(FlushSection(
                        lltab.flush_names("sum", er, ll_meta,
                                          lambda m: f"{m.name}.sum"),
                        llhist_ref.entry_sums(e_rows, e_bins, e_counts,
                                              er.size),
                        tags_er, MetricType.GAUGE, er))
                    indptr, le_idx, cum, total = llhist_ref.cumulative_entries(
                        e_rows, e_bins, e_counts, er.size)
                    total = total.astype(np.float64)
                    sections.append(FlushSection(
                        lltab.flush_names("count", er, ll_meta,
                                          lambda m: f"{m.name}.count"),
                        total, tags_er, MetricType.COUNTER, er))
                    bucket_sections.append(BucketSection(
                        lltab.flush_names("bucket", er, ll_meta,
                                          lambda m: f"{m.name}.bucket"),
                        tags_er, indptr, le_idx, cum.astype(np.float64),
                        total))

        # ---- status checks --------------------------------------------------
        for row in np.flatnonzero(st_touched).tolist():
            meta = st_meta[row]
            if meta is None:  # recycled mid-interval (reclaim straggler)
                continue
            entry = st_vals[row]
            extras.append(InterMetric(
                name=meta.name, timestamp=now, value=entry.value,
                tags=list(meta.tags), type=MetricType.STATUS,
                message=entry.message, hostname=entry.hostname))
    finally:
        if watcher is not None:
            # every handle is ready: a wake-up, after which the round holds
            # its `chip_busy` spans
            watcher.join()
        # donate the drained generations back as the next interval's spares
        # (the second buffer of each family's double-buffer; no-op for snaps
        # whose state escaped — sparse sets): after the transfers, the sets'
        # collect (a sharded table's per-device states are the inputs of the
        # merge its estimate reads) and the join (the watcher may hold the
        # counters' and gauges' states). Booked in the assembly phase: the
        # zeroing dispatches are async and off the segment-attribution pin.
        with timing.phase("recycle", parent="assembly"):
            store.counters.recycle(c_snap)
            store.gauges.recycle(g_snap)
            store.histos.recycle(h_snap)
            store.llhists.recycle(ll_snap)
            store.sets.recycle(set_snap)
    batch = FlushBatch(now, sections, extras, bucket_sections)
    assembly.stop()
    return batch, fwd


def flush_columnstore_batch(
    store: ColumnStore,
    is_local: bool,
    percentiles: Sequence[float],
    aggregates: HistogramAggregates,
    collect_forward: bool = True,
    timing: Optional[FlushRound] = None,
    attribute: bool = False,
) -> Tuple[FlushBatch, ForwardableState]:
    """Columnar flush: swap + readout in one call (the server calls the
    two halves itself, each under its own span). Semantics identical to
    the legacy flush_columnstore — the parity tests pin the two
    equal."""
    swap = swap_columnstore(store, is_local, percentiles,
                            collect_forward=collect_forward,
                            timing=timing)
    return readout_columnstore(store, swap, is_local, aggregates,
                               collect_forward=collect_forward,
                               timing=timing, attribute=attribute)
