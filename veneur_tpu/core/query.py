"""Sub-interval live query plane: serve reads between flushes.

The flush interval used to be the only consistent read point — every
row's value materialized once per interval, at swap. PR 15's
double-buffered generation swap made a read-only capture of the live
device generation an O(1) operation, and both sketch families were
chosen for exactly this kind of online interrogation: t-digests give
mergeable accuracy-bounded quantiles at any moment, Circllhist bins a
one-pass quantile/count readout with a fixed error bound. This module
turns that into a serving surface: `GET /query` answers percentile /
count / rate / cardinality / bin-occupancy lookups for a metric name +
tag filter with sub-interval latency, against the LIVE generation.

Mechanics (core/columnstore.py owns the capture protocol):

  capture   `_BaseTable.capture_readonly()` — fold the pending columns
            into the live state through the normal dispatch path, then
            capture touched/meta/extras and the live device arrays BY
            REFERENCE under the table locks. No swap, no reset, no
            generation advance; residual pending samples after the
            bounded fold are the query's reported staleness.
  readout   `query_readout()` on the server's supervised flush executor
            (core/flushexec.py) — the same single worker the background
            flush readout runs on, so a query can never collide with an
            in-flight readout's donated buffers. Sharded tables
            dispatch the NON-reset collective merges here; results are
            bit-identical to the flush readout over the same rows.
  finish    the family's ordinary `snapshot_finish` transfer + host
            assembly, then host-side row matching (name + tag subset).

Consistency contract (pinned by tests/test_query.py): a query taken
between flushes returns values bit-identical to evaluating the same
readout kernels on the subsequent flush's captured generation
restricted to the same rows — the capture IS the generation the next
swap_out hands to the flush, absent further ingest on those rows.
Queries never touch the ledger (conservation is about samples, and a
query moves none) and never recycle device state.
"""

from __future__ import annotations

import logging
import time
from dataclasses import dataclass
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from veneur_tpu.core.latency import LatencyHist
from veneur_tpu.ops import llhist_ref

logger = logging.getLogger("veneur_tpu.core.query")

# llhist series exported by the plane: query.eval renders
# .p50/.p99/.max gauges + .count counter (scripts/check_metric_names.py
# expands HIST_ROWS tuples against the README inventory)
HIST_ROWS = ("query.eval",)

# canonical kinds; "percentile" is accepted as an alias for "quantile"
QUERY_KINDS = ("quantile", "count", "rate", "cardinality", "value",
               "bin_occupancy")

# kind -> the families searched, in order (quantile falls through the
# t-digest family to llhist so `histogram_encoding: circllhist` stores
# answer transparently)
_KIND_FAMILIES = {
    "quantile": ("histogram", "llhist"),
    "count": ("counter",),
    "rate": ("counter",),
    "cardinality": ("set",),
    "value": ("gauge",),
    "bin_occupancy": ("llhist",),
}


class QueryError(ValueError):
    """A malformed or unanswerable query (surfaced as HTTP 400)."""


class ReshardRetry(QueryError):
    """A reshard cutover is swapping the serving topology under this
    capture — retry once it settles (surfaced as HTTP 503 + retry:
    true, never a shape error). Subclasses QueryError so existing
    catch-alls (the alert engine's tick guard) stay safe."""


def parse_tags(raw: Optional[str]) -> Tuple[str, ...]:
    """'env:prod,region:us' -> a sorted tag tuple (empty for None)."""
    if not raw:
        return ()
    return tuple(sorted(t.strip() for t in raw.split(",") if t.strip()))


@dataclass(frozen=True)
class QuerySpec:
    """One validated query: metric name, kind, and kind parameters."""

    metric: str
    kind: str
    q: Optional[float] = None
    tags: Tuple[str, ...] = ()
    lo: Optional[float] = None
    hi: Optional[float] = None

    @classmethod
    def build(cls, metric: str, kind: str, q=None, tags=(),
              lo=None, hi=None) -> "QuerySpec":
        if not metric:
            raise QueryError("metric is required")
        kind = {"percentile": "quantile"}.get(kind or "", kind)
        if kind not in _KIND_FAMILIES:
            raise QueryError(
                f"unknown kind {kind!r} (expected one of {QUERY_KINDS})")
        if kind == "quantile":
            if q is None:
                raise QueryError("quantile queries require q=")
            # 4-decimal rounding bounds the jit trace cache: the packed
            # flush kernels take the percentile tuple as a STATIC arg,
            # so every distinct q is one compile
            q = round(float(q), 4)
            if not 0.0 <= q <= 1.0:
                raise QueryError(f"q must be in [0, 1], got {q}")
        else:
            q = None
        if kind == "bin_occupancy":
            if lo is None or hi is None:
                raise QueryError("bin_occupancy queries require lo= and hi=")
            lo, hi = float(lo), float(hi)
            if not hi > lo:
                raise QueryError(f"need hi > lo, got [{lo}, {hi})")
        else:
            lo = hi = None
        return cls(metric=metric, kind=kind, q=q,
                   tags=tuple(sorted(tags or ())), lo=lo, hi=hi)


def match_rows(meta: Sequence, touched: np.ndarray, name: str,
               tags: Tuple[str, ...]) -> List[int]:
    """Touched rows whose meta matches `name` and carries every
    requested tag (subset match, the standard dashboard filter)."""
    want = set(tags)
    rows: List[int] = []
    for row, rm in enumerate(meta):
        if rm is None or rm.name != name:
            continue
        if want and not want.issubset(rm.tags or ()):
            continue
        if row < touched.shape[0] and touched[row]:
            rows.append(row)
    return rows


class LiveQueryPlane:
    """The server's live read surface: consistent read-only captures of
    the device families, evaluated with the flush readout kernels, on
    demand. One instance per server; thread-safe (captures serialize on
    the table locks, readouts on the shared flush executor)."""

    def __init__(self, server, timeout_s: float = 30.0):
        self._server = server
        self._timeout_s = timeout_s
        # monotonic counters (GIL point increments, scrape reads race-
        # free enough — a torn read is one scrape stale, never corrupt)
        self.queries_total = 0
        self.errors_total = 0
        self._eval_hist = LatencyHist("query.eval")

    # -- capture ---------------------------------------------------------

    def _tables(self) -> Dict[str, object]:
        store = self._server.store
        return {"counter": store.counters, "gauge": store.gauges,
                "histogram": store.histos, "llhist": store.llhists,
                "set": store.sets}

    def capture(self, families: Sequence[str], ps: Tuple[float, ...] = (),
                need_bins: bool = False) -> dict:
        """One consistent read-only snapshot per requested family,
        readout dispatched through the server's supervised flush
        executor, finished into host arrays. Returns
        {family: {values/flush/..., touched, meta, stale_pending}}."""
        if self._server._shutdown.is_set():
            raise QueryError("server is shutting down")
        reshard = getattr(self._server, "reshard", None)
        if reshard is not None and reshard.state == "cutover":
            # the topology swap is in flight: captures taken now could
            # straddle generations (family A on the new plane, family B
            # still on the old) — typed retry, never a shape error
            raise ReshardRetry("reshard cutover in progress")
        tables = self._tables()
        bundle: dict = {"as_of_unix": time.time()}
        epochs = set()
        for family in families:
            table = tables[family]
            if family == "histogram":
                snap = table.capture_readonly(ps=ps, need_export=False)
            elif family == "llhist":
                snap = table.capture_readonly(ps=ps, need_bins=need_bins)
            else:
                snap = table.capture_readonly()
            epoch = snap.get("topo_epoch")
            if epoch is not None:
                epochs.add(epoch)
            fut = self._server._readout_executor().submit(
                lambda t=table, s=snap: t.query_readout(s))
            snap = fut.result(timeout=self._timeout_s)
            bundle[family] = self._finish(family, table, snap)
        if len(epochs) > 1 or (reshard is not None
                               and reshard.state == "cutover"):
            # a cutover began mid-capture: the bundle mixes topology
            # generations (sharded captures stamp their table's
            # topo_epoch) — retry against the settled plane
            raise ReshardRetry("reshard cutover landed mid-capture")
        return bundle

    @staticmethod
    def _finish(family: str, table, snap: dict) -> dict:
        stale = int(snap.get("stale_pending", 0))
        if family in ("counter", "gauge"):
            values, touched, meta = table.snapshot_finish(snap)
            fam = {"values": values}
        elif family == "histogram":
            flush, _export, touched, meta = table.snapshot_finish(snap)
            fam = {"flush": flush}
        elif family == "llhist":
            flush, bins, touched, meta = table.snapshot_finish(snap)
            fam = {"flush": flush, "bins": bins}
        elif family == "set":
            estimates, _regs, touched, meta = table.snapshot_finish(snap)
            fam = {"values": estimates}
        else:  # pragma: no cover - guarded by _KIND_FAMILIES
            raise QueryError(f"unqueryable family {family!r}")
        fam.update(touched=touched, meta=meta, stale_pending=stale)
        return fam

    # -- evaluation (pure host work over a finished bundle) --------------

    def evaluate(self, bundle: dict, spec: QuerySpec,
                 ps: Tuple[float, ...] = ()) -> dict:
        """Evaluate one spec against a capture bundle. Usable for many
        specs over ONE bundle (the alert engine's path)."""
        matched_family = None
        rows: List[int] = []
        fam: Optional[dict] = None
        for family in _KIND_FAMILIES[spec.kind]:
            fam = bundle.get(family)
            if fam is None:
                continue
            rows = match_rows(fam["meta"], fam["touched"], spec.metric,
                              spec.tags)
            matched_family = family
            if rows:
                break
        out_rows, agg = (self._values_for(matched_family, fam, rows,
                                          spec, ps)
                         if rows else ([], None))
        result = {
            "metric": spec.metric,
            "kind": spec.kind,
            "family": matched_family,
            "matched_rows": len(rows),
            "rows": out_rows,
            "value": agg,
            "as_of_unix": round(bundle["as_of_unix"], 3),
            "stale_pending_samples": int(fam["stale_pending"]) if fam
            else 0,
        }
        if spec.kind == "quantile":
            result["q"] = spec.q
        if spec.kind == "bin_occupancy":
            result["lo"], result["hi"] = spec.lo, spec.hi
        if spec.tags:
            result["tags"] = list(spec.tags)
        return result

    def _values_for(self, family: str, fam: dict, rows: List[int],
                    spec: QuerySpec, ps: Tuple[float, ...]):
        out: List[dict] = []

        def row_entry(row: int, value: float) -> dict:
            rm = fam["meta"][row]
            return {"tags": list(rm.tags or ()), "value": value}

        if spec.kind in ("count", "rate"):
            values = fam["values"]
            elapsed = max(
                time.time() - self._server._interval_start_unix, 1e-9)
            for row in rows:
                v = float(values[row])
                if spec.kind == "rate":
                    v = v / elapsed
                out.append(row_entry(row, v))
            return out, float(sum(e["value"] for e in out))

        if spec.kind in ("value", "cardinality"):
            values = fam["values"]
            for row in rows:
                out.append(row_entry(row, float(values[row])))
            if spec.kind == "cardinality":
                # per-series estimates sum (series are distinct keys;
                # their member streams are reported per tag-set)
                return out, float(sum(e["value"] for e in out))
            return out, max(e["value"] for e in out)

        if spec.kind == "quantile":
            flush = fam["flush"]
            quant = flush.get("quantiles")
            if quant is None or spec.q not in ps:  # idle llhist capture
                return [], None
            qi = ps.index(spec.q)
            for row in rows:
                out.append(row_entry(row, float(quant[row, qi])))
            finite = [e["value"] for e in out
                      if not np.isnan(e["value"])]
            return out, (max(finite) if finite else None)

        if spec.kind == "bin_occupancy":
            bins = fam.get("bins")
            if bins is None or not bins.shape[0]:
                return [], None
            tpos = {int(r): i for i, r in
                    enumerate(np.flatnonzero(fam["touched"]))}
            mids = llhist_ref.BIN_MID
            mask = (mids >= spec.lo) & (mids < spec.hi)
            in_total = 0.0
            all_total = 0.0
            for row in rows:
                i = tpos.get(row)
                if i is None:
                    continue
                regs = bins[i].astype(np.int64)  # transferred as int32
                total = float(regs.sum())
                in_range = float(regs[mask].sum())
                frac = in_range / total if total > 0 else 0.0
                out.append(row_entry(row, frac))
                in_total += in_range
                all_total += total
            agg = in_total / all_total if all_total > 0 else 0.0
            return out, agg

        raise QueryError(f"unknown kind {spec.kind!r}")

    # -- the one-shot path (/query) --------------------------------------

    def ps_for(self, specs: Sequence[QuerySpec]) -> Tuple[float, ...]:
        """The percentile tuple one capture dispatches for a set of
        specs: the server's configured percentiles when they cover every
        requested q (the flush kernels are then textually identical to
        the flush's — the bit-identity pin), extended otherwise."""
        server_ps = tuple(self._server.config.percentiles)
        want = {s.q for s in specs if s.kind == "quantile"}
        if want <= set(server_ps):
            return server_ps
        return tuple(sorted(set(server_ps) | want))

    def query(self, spec: QuerySpec) -> dict:
        t0 = time.perf_counter()
        self.queries_total += 1
        try:
            ps = self.ps_for((spec,))
            bundle = self.capture(
                _KIND_FAMILIES[spec.kind], ps=ps,
                need_bins=(spec.kind == "bin_occupancy"))
            result = self.evaluate(bundle, spec, ps)
        except Exception:
            self.errors_total += 1
            raise
        result["eval_s"] = round(time.perf_counter() - t0, 6)
        self._eval_hist.observe(result["eval_s"])
        return result

    # -- export ----------------------------------------------------------

    def telemetry_rows(self) -> List[tuple]:
        rows: List[tuple] = [
            ("query.requests_total", "counter",
             float(self.queries_total), ()),
            ("query.errors_total", "counter",
             float(self.errors_total), ()),
        ]
        snap = self._eval_hist.snapshot()
        for label in ("p50", "p99", "max"):
            rows.append((f"query.eval.{label}", "gauge", snap[label], ()))
        rows.append(("query.eval.count", "counter",
                     float(snap["count"]), ()))
        return rows


class ProxyQueryView:
    """The proxy-side aggregate query surface. A proxy holds no column
    store — its queryable state is the per-destination routing plane:
    forwarded-key HLL cardinalities, queue depths, and forward volume.
    `GET /query` on a proxy therefore serves aggregate views
    (kind=cardinality over forwarded key digests, kind=count over
    forwarded metrics) rather than per-metric values."""

    def __init__(self, proxy):
        self._proxy = proxy
        self._started_unix = time.time()
        self.queries_total = 0
        self.errors_total = 0

    def query(self, spec: QuerySpec) -> dict:
        self.queries_total += 1
        if spec.kind not in ("cardinality", "count", "rate"):
            self.errors_total += 1
            raise QueryError(
                "a proxy serves aggregate views only: kind must be "
                "cardinality, count, or rate")
        try:
            report = self._proxy.cardinality_report(top=4096)
        except Exception:
            self.errors_total += 1
            raise
        rows = []
        total = 0.0
        for entry in report.get("destinations", ()):
            if spec.kind == "cardinality":
                v = float(entry.get("forwarded_keys_estimate", 0))
            else:
                v = float(entry.get("sent_total", 0))
            rows.append({"tags": [f"destination:{entry.get('address')}"],
                         "value": v})
            total += v
        if spec.kind == "rate":
            # cumulative counters over the proxy's lifetime -> mean rate
            # since this view came up alongside the proxy
            elapsed = max(time.time() - self._started_unix, 1e-9)
            for e in rows:
                e["value"] = e["value"] / elapsed
            total = sum(e["value"] for e in rows)
        return {
            "metric": spec.metric or "forward.keys",
            "kind": spec.kind,
            "family": "proxy",
            "matched_rows": len(rows),
            "rows": rows,
            "value": total,
            "as_of_unix": round(time.time(), 3),
        }

    def telemetry_rows(self) -> List[tuple]:
        return [
            ("query.requests_total", "counter",
             float(self.queries_total), ()),
            ("query.errors_total", "counter",
             float(self.errors_total), ()),
        ]
