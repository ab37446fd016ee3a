"""Device capacity & shard-balance observatory.

Six observability layers watch the host side (telemetry, cardinality,
latency waterfall, flow ledger, tracing, live queries) but the device
plane — where the column store keeps live generations, recycled donated
spares, flush-inflight snapshots, prewarm-rung throwaways, and reshard
capture buffers — was a black box. This module is the accounting layer
for it, three planes wired through the existing registries:

- **HBM ledger** — every `_BaseTable` generation registers its arrays'
  nbytes as a *token* tagged family / table / shard / lifecycle state
  (``live`` / ``spare`` / ``inflight`` / ``prewarm`` /
  ``reshard_capture``). Lifecycle transitions *retag* the token (a
  recycled spare is shape-identical to the generation it was captured
  from, so nbytes is conserved) and every exit path — donation failure,
  capacity mismatch, topology-epoch mismatch, cutover merge — *drops*
  it. The invariant the conservation tests pin: ``total_bytes()`` equals
  the exact sum of registered generation nbytes at every step of
  swap / resize / prewarm / reshard. The total is reconciled against
  ``jax.device_memory_stats`` where the backend provides it (TPU/GPU;
  the CPU backend reports nothing) and feeds the overload ladder's
  device watermark rung (`overload_device_soft_bytes` /
  `_hard_bytes`) beside the RSS rung.
- **Kernel registry** — the jitted apply / readout / merge / reset /
  prewarm kernels register dispatch counts and a time into
  per-(kind, family) LatencyHist rows (`device.kernel.*`), plus
  compile/retrace counts generalizing the PR-10/15 compile-cache probe
  beyond the resize hook: prewarm-rung compiles and post-resize
  retraces land in the same `device.compile.*` counters. Which time:
  `device.kernel.readout_s` holds the CHIP's, from the completion
  stamps of `_ReadoutWatcher` (one `chip_busy{family,device}` span a
  family and device in the flush round); `apply_s`, `merge_s`,
  `reset_s` and `prewarm_s` hold the HOST wall of an asynchronous
  dispatch (`merge_s`: of the `merge{family}` span, stacking
  included), which says nothing of how long the chip took. The same
  watcher stamps every t-digest `compact` (the table books its
  seconds: `ingest.tdigest.compact_chip_seconds_total`).
- **Shard-balance observatory** — computed at scrape time from the
  attached store's digest-routed tables: per-shard live rows and
  samples-routed, a digest-space occupancy histogram, the skew ratio
  ``device.shard.skew = max/mean`` that a `shard_skew` alert rule can
  watch, hot-shard detection (> `HOT_SHARD_FACTOR` x mean), and a
  recommended reshard plan that projects live digests onto candidate
  shard counts and prices the best one in `migration_cells` moved rows.

Everything is scrape-time or O(1)-under-a-lock on the hot path, and the
whole observatory is gated by the `device_observatory` config knob (a
`slow`-marked soak pins total cost under 2% of flush wall time, the
same bar as the latency/cardinality observatories). The full ledger +
kernel table + balance report is served at ``GET /debug/device``.
"""

from __future__ import annotations

import logging
import threading
import time
import weakref
from collections import deque
from typing import Any, Callable, Dict, List, Optional, Tuple

import numpy as np

from veneur_tpu.core.latency import LatencyHist

logger = logging.getLogger("veneur_tpu.deviceobs")

# lifecycle states a generation token may carry
STATE_LIVE = "live"
STATE_SPARE = "spare"
STATE_INFLIGHT = "inflight"
STATE_PREWARM = "prewarm"
STATE_RESHARD = "reshard_capture"

_STATES = (STATE_LIVE, STATE_SPARE, STATE_INFLIGHT, STATE_PREWARM,
           STATE_RESHARD)

# kernel kinds the registry tracks; each timed kind renders a
# `device.kernel.<kind>_s` llhist series (p50/p99/max gauges + count
# counter). Listed literally so scripts/check_metric_names.py can lint
# the expanded names against the README inventory. `readout_s` is the
# chip's time per family and device, from completion stamps
# (`_ReadoutWatcher`); the others are host walls of a dispatch.
KERNEL_KINDS = ("apply", "readout", "merge", "reset", "prewarm")
HIST_ROWS = ("device.kernel.apply_s", "device.kernel.readout_s",
             "device.kernel.merge_s", "device.kernel.reset_s",
             "device.kernel.prewarm_s")

# a shard is "hot" above this multiple of the mean live-row count
HOT_SHARD_FACTOR = 2.0

# digest-space occupancy histogram resolution (bins over [0, 2^64))
DIGEST_BINS = 16

_U64 = np.uint64


def _nbytes_of(arrays: Any) -> int:
    """Sum of nbytes over all array leaves of a state pytree. Works on
    jax.Arrays, numpy arrays, and the dataclass/tuple states the tables
    use; non-array leaves (ints, None) contribute nothing."""
    try:
        import jax
        leaves = jax.tree_util.tree_leaves(arrays)
    except Exception:  # pragma: no cover - jax always importable here
        leaves = [arrays]
    total = 0
    for leaf in leaves:
        n = getattr(leaf, "nbytes", None)
        if n is not None:
            total += int(n)
    return total


def backend_memory_stats() -> List[dict]:
    """Per-device allocator stats where the backend exposes them
    (TPU/GPU `memory_stats()`; CPU returns None). Used to reconcile the
    ledger against what the runtime actually holds."""
    rows: List[dict] = []
    try:
        import jax
        devices = jax.devices()
    except Exception:  # pragma: no cover
        return rows
    for d in devices:
        try:
            stats = d.memory_stats()
        except Exception:
            stats = None
        if not stats:
            continue
        rows.append({
            "device": getattr(d, "id", None),
            "platform": getattr(d, "platform", ""),
            "bytes_in_use": int(stats.get("bytes_in_use", 0)),
            "bytes_limit": int(stats.get("bytes_limit", 0)),
            "peak_bytes_in_use": int(stats.get("peak_bytes_in_use", 0)),
        })
    return rows


class _Token:
    __slots__ = ("family", "table", "state", "nbytes", "shard")

    def __init__(self, family: str, table: str, state: str, nbytes: int,
                 shard: Optional[int]):
        self.family = family
        self.table = table
        self.state = state
        self.nbytes = nbytes
        self.shard = shard


class _ReadoutWatcher:
    """Completion stamps for a flush's readout programs: the kernel
    registry's device half. The flush thread hands over each family's
    OUTPUT handles, grouped by device, as soon as it has dispatched the
    family (`flusher.readout_columnstore`); this one daemon thread takes
    them in that order, blocks until a device's handles are ready (the
    GIL is released meanwhile), reads `perf_counter()` and closes a
    `chip_busy{family,device}` span (parent `readout`) in the round:
    from the later of that device's previous stamp and the family's
    dispatch start, to the stamp. The span's wall also feeds
    `device.kernel.readout_s{family}`.

    The t-digest table hands over each `compact`'s `done` handle the
    same way (`watch_compact`, from the dispatcher's thread under
    `apply_lock` or from the flush thread): stamped in its turn with the
    readouts, its seconds go to the table's `book` callback and to no
    span, and it moves the device's last stamp, so a readout's span no
    longer holds a compact that finished before it. `join` waits only
    for readouts: a compact dispatched after them is not the flush's.

    What a stamp can and cannot say. A chip runs its stream in order, so
    a span holds everything the device ran between two watched
    completions: programs nobody watches (the dispatcher's live applies
    during the flush, the merges, resets) are booked to the next
    watched program of that device; a compact handed over after a
    readout it ran ahead of reads 0. One watcher waits for the
    devices of a family in turn, so a device that finished before the
    one ahead of it reads late by the difference; and a stamp read while
    the flush thread holds the GIL is late by up to the interpreter's
    switch interval. Only outputs are held, never a state that a later
    program donates before `join` has returned."""

    JOIN_S = 5.0   # a wedged device: the flush goes on without stamps

    def __init__(self, obs: "DeviceObservatory"):
        self._obs = weakref.ref(obs)
        self._cv = threading.Condition()
        self._handed: deque = deque()
        self._open = 0        # readouts handed over and not yet stamped
        self._closed = False
        self._last: Dict[str, float] = {}   # device -> its last stamp
        self.thread = threading.Thread(
            target=self._run, name="readout-watcher", daemon=True)
        self.thread.start()

    def watch(self, rnd, family: str, by_device: Dict[str, list],
              dispatched_at: float) -> None:
        with self._cv:
            self._handed.append((rnd, family, by_device, dispatched_at,
                                 None))
            self._open += 1
            self._cv.notify_all()

    def watch_compact(self, done, dispatched_at: float,
                      book: Callable[[float], None]) -> None:
        """A compact's `done` handle (`batch_tdigest.compact`); `book`
        gets its device seconds on the watcher's thread. Never waits."""
        [d] = done.devices()
        by_device = {f"{d.platform}:{d.id}": [done]}
        with self._cv:
            self._handed.append((None, "histogram", by_device,
                                 dispatched_at, book))
            self._cv.notify_all()

    def join(self) -> bool:
        """Until every readout handed over is stamped: a wake-up once
        the flush thread has synced the same handles itself."""
        with self._cv:
            return self._cv.wait_for(lambda: not self._open, self.JOIN_S)

    def close(self) -> None:
        with self._cv:
            self._closed = True
            self._cv.notify_all()

    def _run(self) -> None:
        while True:
            with self._cv:
                self._cv.wait_for(lambda: self._handed or self._closed)
                if not self._handed:
                    return
                item = self._handed.popleft()
            readout = item[0] is not None
            try:
                self._stamp(*item)
            except Exception:
                logger.exception("completion stamp failed")
            finally:
                del item   # the handles: nothing outlives its stamp
                if readout:
                    with self._cv:
                        self._open -= 1
                        self._cv.notify_all()

    def _stamp(self, rnd, family, by_device, dispatched_at, book):
        import jax

        obs = self._obs()
        first = True
        for device, handles in by_device.items():
            if device == "host":
                continue
            jax.block_until_ready(handles)
            done = time.perf_counter()
            start = max(self._last.get(device, 0.0), dispatched_at)
            done = self._last[device] = max(done, start)
            if book is not None:   # a compact: no span, no registry row
                book(done - start)
                continue
            rnd.stamped("chip_busy", "readout", start, done,
                        family=family, device=device)
            if obs is not None:
                # one dispatch a family and round, one time a device
                obs.note_kernel("readout", family, done - start,
                                n=int(first))
            first = False


class DeviceObservatory:
    """One server's (or standalone store's) device observatory.

    Disabled, every note_* call is a cheap early return and
    `note_generation` hands back None (retag/drop tolerate None), so
    the hook sites in the column store cost one attribute read — the
    <2% overhead guard's off switch."""

    def __init__(self, enabled: bool = True):
        self.enabled = enabled
        self._lock = threading.Lock()
        self._tokens: Dict[int, _Token] = {}
        self._next_token = 1
        self._total = 0           # running sum of registered nbytes
        self.peak_bytes = 0       # high-water mark of _total
        # kernel registry: (kind, family) -> dispatch count / hist
        self._dispatch: Dict[Tuple[str, str], int] = {}
        self._kernel_hists: Dict[Tuple[str, str], LatencyHist] = {}
        # compile/retrace counts + last compile wall per family
        self._compiles: Dict[str, int] = {}
        self._compile_seconds: Dict[str, float] = {}
        # shard-balance plane reads the attached store at scrape time
        self._store = None
        self._resize_events = 0
        self._watcher: Optional[_ReadoutWatcher] = None

    # ------------------------------------------------------------------
    # HBM ledger
    # ------------------------------------------------------------------

    def note_generation(self, family: str, state: str, arrays: Any,
                        table: Optional[str] = None,
                        shard: Optional[int] = None) -> Optional[int]:
        """Register one device generation; returns an opaque token used
        to retag/drop it across lifecycle transitions, or None when the
        observatory is disabled or the state holds no arrays."""
        if not self.enabled or arrays is None:
            return None
        nbytes = _nbytes_of(arrays)
        if nbytes <= 0:
            return None
        with self._lock:
            tok = self._next_token
            self._next_token += 1
            self._tokens[tok] = _Token(family, table or family, state,
                                       nbytes, shard)
            self._total += nbytes
            if self._total > self.peak_bytes:
                self.peak_bytes = self._total
        return tok

    def retag(self, token: Optional[int], new_state: str) -> None:
        """Move a registered generation to a new lifecycle state. The
        bytes stay registered — a retag conserves the ledger total."""
        if token is None:
            return
        with self._lock:
            t = self._tokens.get(token)
            if t is not None:
                t.state = new_state

    def drop(self, token: Optional[int]) -> None:
        """Unregister a generation (donated away, freed, or merged)."""
        if token is None:
            return
        with self._lock:
            t = self._tokens.pop(token, None)
            if t is not None:
                self._total -= t.nbytes

    def total_bytes(self) -> int:
        with self._lock:
            return self._total

    def note_resize(self) -> None:
        if not self.enabled:
            return
        with self._lock:
            self._resize_events += 1

    def ledger(self) -> dict:
        """Full ledger breakdown: per-family per-state bytes, per-table
        rows, totals, peak, and a forecast-to-next-resize row (a grow
        doubles the live generation, so next-resize demand is live
        bytes x2 for the growing family — the report forecasts the
        worst case: every family doubling at once)."""
        with self._lock:
            toks = [(t.family, t.table, t.state, t.nbytes, t.shard)
                    for t in self._tokens.values()]
            total, peak = self._total, self.peak_bytes
        by_family: Dict[str, Dict[str, int]] = {}
        by_table: Dict[str, dict] = {}
        live_total = 0
        for family, table, state, nbytes, shard in toks:
            fam = by_family.setdefault(
                family, {s: 0 for s in _STATES})
            fam[state] = fam.get(state, 0) + nbytes
            row = by_table.setdefault(
                table, {"family": family, "bytes": 0, "states": {}})
            row["bytes"] += nbytes
            row["states"][state] = row["states"].get(state, 0) + nbytes
            if shard is not None:
                row["shard"] = shard
            if state == STATE_LIVE:
                live_total += nbytes
        return {
            "total_bytes": total,
            "peak_bytes": peak,
            "live_bytes": live_total,
            # worst-case demand at the next capacity rung: every live
            # generation doubles (grow policy) while the old one is
            # still resident for the copy
            "forecast_next_resize_bytes": live_total * 2,
            "generations": len(toks),
            "by_family": by_family,
            "by_table": by_table,
        }

    # ------------------------------------------------------------------
    # Kernel registry
    # ------------------------------------------------------------------

    def note_kernel(self, kind: str, family: str,
                    seconds: Optional[float] = None, n: int = 1) -> None:
        """Record `n` dispatches of a jitted kernel; `seconds` feeds the
        `device.kernel.<kind>_s` llhist for that family: the chip's
        time from a completion stamp for `readout`, the caller's host
        wall of the dispatch for every other kind."""
        if not self.enabled:
            return
        key = (kind, family)
        with self._lock:
            self._dispatch[key] = self._dispatch.get(key, 0) + n
            if seconds is not None:
                hist = self._kernel_hists.get(key)
                if hist is None:
                    hist = self._kernel_hists[key] = LatencyHist(
                        f"device.kernel.{kind}_s")
        if seconds is not None:
            hist.observe(seconds)

    def readout_watcher(self) -> Optional[_ReadoutWatcher]:
        """The completion watcher of the flush's readout programs,
        started with the first flush that asks; None when disabled."""
        if not self.enabled:
            return None
        with self._lock:
            if self._watcher is None:
                self._watcher = _ReadoutWatcher(self)
                weakref.finalize(self, self._watcher.close)
            return self._watcher

    def close(self) -> None:
        """Stop the watcher's thread (`Server.shutdown`); the next
        flush, if one comes, starts another."""
        with self._lock:
            watcher, self._watcher = self._watcher, None
        if watcher is not None:
            watcher.close()

    def note_compile(self, family: str,
                     seconds: Optional[float] = None) -> None:
        """Record one XLA compile/retrace for `family` (prewarm-rung
        compile, post-resize retrace, or first-dispatch trace)."""
        if not self.enabled:
            return
        with self._lock:
            self._compiles[family] = self._compiles.get(family, 0) + 1
            if seconds is not None:
                self._compile_seconds[family] = float(seconds)

    def kernel_report(self) -> dict:
        with self._lock:
            dispatch = dict(self._dispatch)
            hists = dict(self._kernel_hists)
            compiles = dict(self._compiles)
            compile_s = dict(self._compile_seconds)
        kernels: List[dict] = []
        for (kind, family), count in sorted(dispatch.items()):
            row = {"kind": kind, "family": family, "dispatches": count}
            hist = hists.get((kind, family))
            if hist is not None:
                row["wall"] = hist.snapshot()
            kernels.append(row)
        return {
            "kernels": kernels,
            "compiles": compiles,
            "last_compile_seconds": compile_s,
        }

    # ------------------------------------------------------------------
    # Shard-balance observatory
    # ------------------------------------------------------------------

    def attach_store(self, store) -> None:
        self._store = store

    def _sharded_tables(self) -> List[Tuple[str, Any]]:
        store = self._store
        if store is None:
            return []
        out = []
        for family, table in store.tables():
            if getattr(table, "_shard_of", None) is not None \
                    and getattr(table, "_n_shards", 0) > 1:
                out.append((family, table))
        return out

    def shard_balance(self) -> Optional[dict]:
        """Per-shard live rows / samples-routed / digest occupancy for
        the attached store's digest-routed tables; None when the store
        isn't sharded. Reads host-side routing arrays only — no device
        sync."""
        tables = self._sharded_tables()
        if not tables:
            return None
        store = self._store
        plane = getattr(store, "shard_plane", None)
        n_shards = tables[0][1]._n_shards
        rows = np.zeros(n_shards, np.int64)
        digest_hist = np.zeros(DIGEST_BINS, np.int64)
        per_family: Dict[str, list] = {}
        digests_all: List[np.ndarray] = []
        shift = _U64(64 - (DIGEST_BINS.bit_length() - 1))
        for family, table in tables:
            with table.lock:
                n = len(table.meta)
                shard_of = np.asarray(table._shard_of[:n])
                live = np.asarray(table._has_meta[:n], bool)
                # dict keys are (digest64 << 2) | scope — wider than 64
                # bits as Python ints, so mask before the uint64 cast
                dig_list = [(dk >> 2) & 0xFFFFFFFFFFFFFFFF
                            for row, dk in enumerate(table._dict_key_of)
                            if row < n and live[row]]
            fam_rows = np.bincount(shard_of[live].astype(np.int64),
                                   minlength=n_shards)[:n_shards]
            rows += fam_rows
            per_family[family] = [int(x) for x in fam_rows]
            if dig_list:
                digests = np.asarray(dig_list, np.uint64)
                digests_all.append(digests)
                digest_hist += np.bincount(
                    (digests >> shift).astype(np.int64),
                    minlength=DIGEST_BINS)[:DIGEST_BINS]
        mean = float(rows.mean()) if rows.size else 0.0
        skew = float(rows.max() / mean) if mean > 0 else None
        hot = [int(i) for i in np.nonzero(
            rows > HOT_SHARD_FACTOR * mean)[0]] if mean > 0 else []
        samples: Dict[str, list] = {}
        if plane is not None:
            for family, acc in getattr(plane, "_samples", {}).items():
                samples[family] = [int(x) for x in acc]
        out = {
            "n_shards": int(n_shards),
            "rows_per_shard": [int(x) for x in rows],
            "rows_per_shard_by_family": per_family,
            "samples_routed": samples,
            "digest_occupancy": [int(x) for x in digest_hist],
            "skew": skew,
            "hot_shards": hot,
        }
        plan = self._reshard_plan(digests_all, int(n_shards), rows)
        if plan is not None:
            out["reshard_plan"] = plan
        return out

    def _reshard_plan(self, digests_all: List[np.ndarray], n_old: int,
                      rows: np.ndarray) -> Optional[dict]:
        """Project live digests onto candidate shard counts and price
        the best one: projected skew + migration_cells cost in moved
        rows. Only a recommendation — the reshard controller cuts over."""
        if not digests_all:
            return None
        try:
            import jax
            max_m = len(jax.devices())
        except Exception:  # pragma: no cover
            max_m = n_old
        digests = np.concatenate(digests_all)
        if digests.size == 0 or max_m < 2:
            return None
        # digest-home routing: home = (digest * M) >> 64, computed via
        # the 128-bit object path (numpy has no u128)
        dig_obj = digests.astype(object)
        old_home = np.asarray([(int(d) * n_old) >> 64 for d in dig_obj],
                              np.int64)
        best = None
        for m in range(2, max_m + 1):
            if m == n_old:
                continue
            new_home = np.asarray([(int(d) * m) >> 64 for d in dig_obj],
                                  np.int64)
            proj = np.bincount(new_home, minlength=m)[:m]
            mean = float(proj.mean())
            if mean <= 0:
                continue
            proj_skew = float(proj.max() / mean)
            moved = int(np.count_nonzero(old_home != new_home))
            cand = (proj_skew, moved, m)
            if best is None or cand < best:
                best = cand
        if best is None:
            return None
        proj_skew, moved, m = best
        try:
            from veneur_tpu.parallel.reshard import migration_cells
            cells = len(migration_cells(n_old, m))
        except Exception:
            cells = None
        return {
            "from_shards": n_old,
            "to_shards": m,
            "projected_skew": proj_skew,
            "rows_moved": moved,
            "migration_cells": cells,
        }

    def shard_skew(self) -> Optional[float]:
        """max/mean live-row ratio across shards; None when the store
        isn't sharded or holds no rows — the `shard_skew` alert rule's
        and `device.shard.skew` gauge's source."""
        tables = self._sharded_tables()
        if not tables:
            return None
        n_shards = tables[0][1]._n_shards
        rows = np.zeros(n_shards, np.int64)
        for _family, table in tables:
            with table.lock:
                n = len(table.meta)
                shard_of = np.asarray(table._shard_of[:n])
                live = np.asarray(table._has_meta[:n], bool)
            rows += np.bincount(shard_of[live].astype(np.int64),
                                minlength=n_shards)[:n_shards]
        mean = float(rows.mean()) if rows.size else 0.0
        if mean <= 0:
            return None
        return float(rows.max() / mean)

    # ------------------------------------------------------------------
    # Export
    # ------------------------------------------------------------------

    def telemetry_rows(self) -> List[tuple]:
        if not self.enabled:
            return []
        rows: List[tuple] = []
        led = self.ledger()
        rows.append(("device.mem.total_bytes", "gauge",
                     float(led["total_bytes"]), ()))
        rows.append(("device.mem.peak_bytes", "gauge",
                     float(led["peak_bytes"]), ()))
        rows.append(("device.mem.forecast_next_resize_bytes", "gauge",
                     float(led["forecast_next_resize_bytes"]), ()))
        rows.append(("device.mem.generations", "gauge",
                     float(led["generations"]), ()))
        for family, states in sorted(led["by_family"].items()):
            for state, nbytes in sorted(states.items()):
                if nbytes:
                    rows.append(("device.mem.bytes", "gauge",
                                 float(nbytes),
                                 (f"family:{family}", f"state:{state}")))
        with self._lock:
            dispatch = dict(self._dispatch)
            hists = dict(self._kernel_hists)
            compiles = dict(self._compiles)
        for (kind, family), count in sorted(dispatch.items()):
            rows.append(("device.kernel.dispatches", "counter",
                         float(count),
                         (f"kind:{kind}", f"family:{family}")))
        for (kind, family), hist in sorted(hists.items()):
            snap = hist.snapshot()
            tags = (f"family:{family}",)
            base = f"device.kernel.{kind}_s"
            for label in ("p50", "p99", "max"):
                rows.append((f"{base}.{label}", "gauge", snap[label],
                             tags))
            rows.append((f"{base}.count", "counter",
                         float(snap["count"]), tags))
        for family, count in sorted(compiles.items()):
            rows.append(("device.compile.count", "counter", float(count),
                         (f"family:{family}",)))
        skew = self.shard_skew()
        if skew is not None:
            rows.append(("device.shard.skew", "gauge", skew, ()))
        return rows

    def report(self) -> dict:
        """The `/debug/device` payload: ledger + backend reconciliation
        + kernel table + shard balance."""
        led = self.ledger()
        backend = backend_memory_stats()
        recon = None
        if backend:
            in_use = sum(r["bytes_in_use"] for r in backend)
            recon = {
                "backend_bytes_in_use": in_use,
                "ledger_bytes": led["total_bytes"],
                # allocator slack: runtime-held bytes the ledger doesn't
                # model (XLA scratch, executables, donation slop)
                "unaccounted_bytes": in_use - led["total_bytes"],
            }
        out = {
            "generated_unix": time.time(),
            "enabled": self.enabled,
            "ledger": led,
            "backend_devices": backend,
            "reconciliation": recon,
            **self.kernel_report(),
        }
        balance = self.shard_balance()
        if balance is not None:
            out["shard_balance"] = balance
        return out
