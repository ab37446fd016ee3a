"""Elastic resharding: live digest-range migration with WAL-backed
exactly-once cutover.

Takes the serving plane from N shards to M while ingest keeps flowing,
with zero loss provable by the strict flow ledger. Three phases:

**plan** — compute the new digest-range -> home assignment (contiguous
range partition: the only rows that change home are the ones in cells
whose range boundary moved) and background-compile the M-shard
apply/readout/merge kernels through the shape-ladder prewarmer
(core/flushexec.py) against throwaway M-shard tables, so the cutover
never pays a cold XLA retrace.

**cutover** — at a flush boundary (under the server's flush lock, which
no flush's readout outlives): atomically
`reshard_swap` each family's old generation, capture the merged
per-row state, WAL-append it as metricpb wire — one spool segment per
migrating digest-range cell — *before* any state moves, then merge the
captured rows back through the exact decode+merge path crash recovery
uses. Replay-as-the-only-path is what makes the cutover exactly-once:
merged device state is volatile until the segments are popped, and the
segments are popped before the flush lock is released, so a crash at
ANY point either replays a segment whose merge died with the process
or finds no segment because the merge already flushed. Post-reshard
flush output is bit-identical to a never-resharded control (counters
exact through the int64 wire, llhist/HLL registers bit-for-bit,
t-digest centroids re-compressed once — same count, quantiles within
compression tolerance).

**recover** — a crash (SIGKILL) anywhere mid-reshard leaves range
segments in the reshard spool; the next start replays them exactly
once into whatever topology the new process builds. A device-loss
event is a forced scale-down through the same machinery
(`device_loss(shard)`).

Degraded mode: with neither `reshard_spool_dir` nor
`carryover_spool_dir` configured there is no WAL — the cutover merges
from memory (zero loss absent a crash, no crash coverage) and logs
loudly. An append fault (disk error / chaos seam) degrades only the
faulted cell the same way.
"""

from __future__ import annotations

import logging
import os
import threading
import time
from typing import Dict, List, Optional

import numpy as np

from veneur_tpu.forward import rangewire
from veneur_tpu.parallel import collectives
from veneur_tpu.parallel.sharded_server import (ShardedServingPlane,
                                                local_shard_devices)
from veneur_tpu.util import chaos as chaos_mod
from veneur_tpu.util.spool import CarryoverSpool

logger = logging.getLogger("veneur_tpu.reshard")

_FULL = 1 << 64

# state machine: idle -> planning -> ready -> cutover -> idle
_STATE_IDS = {"idle": 0.0, "planning": 1.0, "ready": 2.0, "cutover": 3.0}

# fixed family encode order; per-cell l-stat sidecars are aligned with
# the cell's histogram frames, so the order must be deterministic
_FAMILY_ORDER = ("counter", "gauge", "histogram", "llhist", "set")


class ReshardError(Exception):
    """Invalid reshard request (not sharded, bad target, in progress)."""


def migration_cells(n_old: int, n_new: int) -> List[dict]:
    """The digest-range cells of an N->M reshard: the union of both
    partitions' range boundaries splits [0, 2^64) into at most N+M-1
    contiguous cells, each with ONE old home and ONE new home."""
    bounds = sorted(set(collectives.range_bounds(n_old))
                    | set(collectives.range_bounds(n_new)))
    cells = []
    for i, lo in enumerate(bounds):
        hi = bounds[i + 1] if i + 1 < len(bounds) else _FULL
        cells.append({
            "lo": lo, "hi": hi,
            "old_home": (lo * n_old) >> 64,
            "new_home": (lo * n_new) >> 64,
        })
    return cells


class _PlanStore:
    """Shim store for the plan-phase prewarmer: throwaway M-shard
    tables at the live capacities (the prewarmer only calls
    .tables())."""

    def __init__(self, tables):
        self._tables = tuple(tables)

    def tables(self):
        return self._tables


class ReshardController:
    """Owns the reshard state machine for one server. Thread-safe:
    `begin` spawns the plan thread; `cutover` runs under the server's
    flush lock; telemetry/describe are lock-free point reads."""

    def __init__(self, server):
        self._server = server
        self._lock = threading.Lock()
        self.state = "idle"
        self.epoch = 0
        self.target_shards = 0
        self.deadline_unix = 0.0
        self.last_error = ""
        self.last_cutover_seconds = 0.0
        self.segments_written = 0
        self.replayed_segments = 0
        self.append_faults = 0
        self.capture_failures = 0
        self.device_losses = 0
        self.cutovers = 0
        self._inflight = 0  # metrics captured but not yet merged back
        self._thread: Optional[threading.Thread] = None
        self._spool_obj: Optional[CarryoverSpool] = None
        cfg = getattr(server, "config", None)
        self._deadline_default = float(
            getattr(cfg, "reshard_deadline", 30.0) or 30.0)
        d = getattr(cfg, "reshard_spool_dir", "") or ""
        if not d:
            carry = getattr(cfg, "carryover_spool_dir", "") or ""
            if carry:
                d = os.path.join(carry, "reshard")
        self._spool_dir = d
        if not d:
            logger.warning(
                "reshard: no spool directory configured "
                "(reshard_spool_dir / carryover_spool_dir both empty) — "
                "cutovers will run WITHOUT a WAL: zero loss absent a "
                "crash, but a crash mid-cutover loses the migrating "
                "interval")

    # -- wiring ----------------------------------------------------------

    def _spool(self) -> Optional[CarryoverSpool]:
        if self._spool_obj is None and self._spool_dir:
            # generous bounds: a range segment holds one interval's
            # migrating rows; shedding one would be silent loss, which
            # is the one thing the reshard WAL exists to prevent
            self._spool_obj = CarryoverSpool(
                self._spool_dir, max_bytes=2 * 1024 * 1024 * 1024,
                max_segments=65536, ledger=None)
        return self._spool_obj

    def _ledger(self):
        led = getattr(self._server, "ledger", None)
        return led if (led is not None and led.enabled) else None

    def inflight_metrics(self) -> int:
        """Ledger stock `reshard_inflight`: rows captured out of the
        old generation but not yet merged into the new one. Always 0 at
        interval close — the whole cutover runs under the flush lock —
        so any nonzero closing level is itself a conservation break."""
        return self._inflight

    # -- public API ------------------------------------------------------

    def begin(self, shards: Optional[int] = None, devices=None,
              deadline_s: Optional[float] = None,
              block: bool = False) -> dict:
        """Start an elastic reshard to `shards` (or an explicit device
        list). Plans + prewarms on a background thread, then cuts over
        at the next flush boundary it can take. `block=True` joins."""
        store = self._server.store
        if store.shard_plane is None:
            raise ReshardError("store is not sharded (no serving plane)")
        if devices is None:
            if shards is None or int(shards) < 1:
                raise ReshardError("target shards must be >= 1")
            try:
                devices = local_shard_devices(int(shards))
            except ValueError as e:
                raise ReshardError(str(e)) from e
        devices = list(devices)
        if not devices:
            raise ReshardError("no devices available for target plane")
        with self._lock:
            if self.state != "idle":
                raise ReshardError(
                    f"reshard already in progress (state={self.state})")
            self.state = "planning"
            self.target_shards = len(devices)
            self.last_error = ""
            dl = (float(deadline_s) if deadline_s is not None
                  else self._deadline_default)
            self.deadline_unix = time.time() + dl
        from veneur_tpu.util.crash import guarded
        self._thread = threading.Thread(
            target=guarded(self._run), args=(devices,),
            name="reshard-plan", daemon=True)
        self._thread.start()
        if block:
            self._thread.join()
            if self.last_error:
                raise ReshardError(self.last_error)
        return self.describe()

    def device_loss(self, shard_index: int,
                    deadline_s: Optional[float] = None,
                    block: bool = False) -> dict:
        """Forced scale-down after losing one device: reshard onto the
        surviving devices through the normal plan/cutover machinery.
        The lost shard's un-flushed interval state is gone with the
        device — what this saves is every OTHER shard's state plus the
        routing: no row keeps a dead home."""
        plane = self._server.store.shard_plane
        if plane is None:
            raise ReshardError("store is not sharded (no serving plane)")
        survivors = [d for i, d in enumerate(plane.devices)
                     if i != int(shard_index)]
        if not survivors:
            raise ReshardError("no surviving devices")
        self.device_losses += 1
        logger.error(
            "device loss on shard %d/%d: forcing scale-down to %d "
            "shards", shard_index, plane.n, len(survivors))
        return self.begin(devices=survivors, deadline_s=deadline_s,
                          block=block)

    def past_deadline(self) -> bool:
        return (self.state != "idle" and self.deadline_unix > 0
                and time.time() > self.deadline_unix)

    def describe(self) -> dict:
        plane = self._server.store.shard_plane
        return {
            "state": self.state,
            "epoch": self.epoch,
            "shards": plane.n if plane is not None else 0,
            "target_shards": self.target_shards,
            "deadline_unix": round(self.deadline_unix, 3),
            "past_deadline": self.past_deadline(),
            "durable": bool(self._spool_dir),
            "spool_dir": self._spool_dir,
            "cutovers": self.cutovers,
            "last_cutover_seconds": round(self.last_cutover_seconds, 6),
            "segments_written": self.segments_written,
            "replayed_segments": self.replayed_segments,
            "append_faults": self.append_faults,
            "capture_failures": self.capture_failures,
            "device_losses": self.device_losses,
            "inflight_metrics": self._inflight,
            "last_error": self.last_error,
        }

    def telemetry_rows(self) -> List[tuple]:
        return [
            ("reshard.state", "gauge", _STATE_IDS.get(self.state, -1.0),
             (f"state:{self.state}",)),
            ("reshard.epoch", "counter", float(self.epoch), ()),
            ("reshard.cutovers", "counter", float(self.cutovers), ()),
            ("reshard.last_cutover_seconds", "gauge",
             self.last_cutover_seconds, ()),
            ("reshard.segments_written", "counter",
             float(self.segments_written), ()),
            ("reshard.replayed_segments", "counter",
             float(self.replayed_segments), ()),
            ("reshard.append_faults", "counter",
             float(self.append_faults), ()),
            ("reshard.capture_failures", "counter",
             float(self.capture_failures), ()),
            ("reshard.device_losses", "counter",
             float(self.device_losses), ()),
            ("reshard.inflight_metrics", "gauge",
             float(self._inflight), ()),
        ]

    # -- plan ------------------------------------------------------------

    def _run(self, devices) -> None:
        try:
            chaos = getattr(self._server, "chaos", None)
            if chaos is not None:
                chaos.reshard_prewarm_delay()
            plane = ShardedServingPlane(devices)
            self._prewarm(plane)
            with self._lock:
                self.state = "ready"
            self.cutover(plane)
        except Exception as e:
            logger.exception("reshard to %d shards failed", len(devices))
            self.last_error = f"{type(e).__name__}: {e}"
            with self._lock:
                self.state = "idle"
        finally:
            self.deadline_unix = 0.0
            self.target_shards = 0

    def _prewarm(self, plane: ShardedServingPlane) -> None:
        """Compile the M-shard apply/readout/merge kernels against
        throwaway tables at the LIVE capacities, so the retopo'd real
        tables hit the process-global jit cache on their first batch.
        Best-effort: a prewarm failure costs a hot retrace, not the
        reshard."""
        from veneur_tpu.core import sharded_tables as st
        from veneur_tpu.core.flushexec import ShapeLadderPrewarmer
        classes = {
            "counter": st.ShardedCounterTable,
            "gauge": st.ShardedGaugeTable,
            "histogram": st.ShardedHistoTable,
            "llhist": st.ShardedLLHistTable,
            "set": st.ShardedSetTable,
        }
        server = self._server
        shim_tables = []
        for family, table in server.store.tables():
            cls = classes.get(family)
            if cls is None:
                continue
            try:
                shim_tables.append(
                    (family, cls(capacity=table.capacity, plane=plane)))
            except Exception:
                logger.exception(
                    "reshard plan: throwaway %s table build failed "
                    "(cutover will pay the retrace)", family)
        if not shim_tables:
            return
        pw = ShapeLadderPrewarmer(
            _PlanStore(shim_tables),
            percentiles=getattr(server, "percentiles", ()),
            need_export=(getattr(server, "is_local", False)
                         and getattr(server, "forwarder", None)
                         is not None),
            on_event=server.telemetry.record_event)
        pw.start()
        for family, table in shim_tables:
            pw._enqueue(family, table.capacity)
        remaining = max(1.0, self.deadline_unix - time.time())
        # stop() appends the queue sentinel AFTER the enqueued rungs,
        # so every rung compiles before the thread exits (or the
        # deadline expires and the daemon thread is abandoned)
        pw.stop(timeout=remaining)

    # -- cutover ---------------------------------------------------------

    def cutover(self, plane: ShardedServingPlane) -> None:
        """The atomic topology swap. Everything — swap, capture, WAL
        append, merge-back, segment pop — happens under the server's
        flush lock (no readout outlives it), so no flush can deliver half-migrated
        state downstream and the popped-segment invariant holds (see
        module docstring)."""
        server = self._server
        chaos = getattr(server, "chaos", None)
        t0 = time.perf_counter()
        with self._lock:
            self.state = "cutover"
        try:
            with server._flush_lock:
                store = server.store
                n_old = store.shard_plane.n
                n_new = plane.n
                snaps: Dict[str, dict] = {}
                for family, table in store.tables():
                    if not hasattr(table, "reshard_swap"):
                        continue  # host-only families (statuses)
                    try:
                        snaps[family] = table.reshard_swap(plane)
                    except Exception:
                        self.capture_failures += 1
                        logger.exception(
                            "reshard: %s capture failed — family "
                            "restarts empty on the new plane (its "
                            "un-flushed interval state is lost)",
                            family)
                store.shard_plane = plane
                cells = self._encode_cells(snaps, n_old, n_new)
                self._wal_and_merge(cells, chaos)
                # the merged old-mesh capture generations are dead: the
                # HBM-ledger tokens that rode each family's snap as
                # `reshard_capture` unregister here
                for family, table in store.tables():
                    snap = snaps.get(family)
                    obs = getattr(table, "_deviceobs", None)
                    if snap is not None and obs is not None:
                        obs.drop(snap.pop("_devobs", None))
                self.epoch += 1
                self.cutovers += 1
        finally:
            self.last_cutover_seconds = time.perf_counter() - t0
            with self._lock:
                self.state = "idle"
        logger.info(
            "reshard cutover complete: %d -> %d shards, epoch %d, "
            "%.3fs", n_old, n_new, self.epoch, self.last_cutover_seconds)
        try:
            server.telemetry.record_event(
                "reshard_cutover", shards_old=n_old, shards_new=n_new,
                epoch=self.epoch,
                duration_s=round(self.last_cutover_seconds, 6))
        except Exception:
            pass

    # -- capture encode --------------------------------------------------

    def _encode_cells(self, snaps: Dict[str, dict], n_old: int,
                      n_new: int) -> List[dict]:
        """Serialize every touched captured row into its digest-range
        cell's frame list. ALL touched rows are encoded — even
        zero-total counters — because touched rows emit at flush, and
        bit-identity with a never-resharded control requires the
        post-cutover flush to see the same row set."""
        cells = migration_cells(n_old, n_new)
        for cell in cells:
            cell["frames"] = []
            cell["histo_l"] = {k: [] for k in rangewire.LSTAT_FIELDS}
            cell["count"] = 0
        bounds = np.array([c["lo"] for c in cells], np.uint64)

        def rows_and_cells(snap):
            touched = snap["touched"]
            meta = snap["meta"]
            limit = min(touched.shape[0], len(meta))
            rows = np.flatnonzero(touched[:limit])
            idx = np.searchsorted(bounds, snap["digest64"][rows],
                                  side="right") - 1
            return rows.tolist(), idx.tolist(), meta

        for family in _FAMILY_ORDER:
            snap = snaps.get(family)
            if snap is None:
                continue
            if family == "counter" and "dev" in snap:
                values = (np.asarray(snap["dev"][0], np.float64)
                          - np.asarray(snap["dev"][1], np.float64))
                acc = snap.get("import_acc")
                if acc is not None:
                    values[:acc.shape[0]] += acc
                rows, idx, meta = rows_and_cells(snap)
                for row, c in zip(rows, idx):
                    cells[c]["frames"].append(rangewire.counter_to_wire(
                        meta[row], values[row]))
                    cells[c]["count"] += 1
            elif family == "gauge" and "dev" in snap:
                values = np.asarray(snap["dev"], np.float64)
                rows, idx, meta = rows_and_cells(snap)
                for row, c in zip(rows, idx):
                    cells[c]["frames"].append(rangewire.gauge_to_wire(
                        meta[row], values[row]))
                    cells[c]["count"] += 1
            elif family == "histogram" and "hstate" in snap:
                h = {k: np.asarray(v) for k, v in snap["hstate"].items()}
                weights = h["weights"]
                means = np.divide(h["wv"], weights,
                                  out=np.zeros_like(weights),
                                  where=weights > 0)
                rows, idx, meta = rows_and_cells(snap)
                for row, c in zip(rows, idx):
                    cells[c]["frames"].append(
                        rangewire.histogram_to_wire(
                            meta[row], means[row], weights[row],
                            h["dmin"][row], h["dmax"][row],
                            h["drecip"][row]))
                    cells[c]["count"] += 1
                    for k in rangewire.LSTAT_FIELDS:
                        cells[c]["histo_l"][k].append(float(h[k][row]))
            elif family == "llhist" and "bins" in snap:
                bins = np.asarray(snap["bins"])
                rows, idx, meta = rows_and_cells(snap)
                for row, c in zip(rows, idx):
                    cells[c]["frames"].append(rangewire.llhist_to_wire(
                        meta[row], bins[row]))
                    cells[c]["count"] += 1
            elif family == "set" and "regs" in snap:
                regs = np.asarray(snap["regs"])
                rows, idx, meta = rows_and_cells(snap)
                for row, c in zip(rows, idx):
                    cells[c]["frames"].append(rangewire.set_to_wire(
                        meta[row], regs[row]))
                    cells[c]["count"] += 1
        out = []
        for cell in cells:
            if not cell["frames"]:
                continue
            if cell["histo_l"]["lsum"]:
                cell["frames"].append(
                    rangewire.lstat_sidecar(cell["histo_l"]))
            out.append(cell)
        return out

    # -- WAL + merge-back ------------------------------------------------

    def _wal_and_merge(self, cells: List[dict], chaos) -> None:
        spool = self._spool()
        token = f"reshard-{self.epoch + 1:06d}"
        self._inflight = sum(cell["count"] for cell in cells)
        mem_cells: List[List[bytes]] = []
        for i, cell in enumerate(cells):
            if spool is None:
                mem_cells.append(cell["frames"])
                continue
            try:
                if chaos is not None:
                    chaos.reshard_append_seam()
                spool.append(cell["frames"], extra={
                    "kind": "reshard", "token": token, "cell": i,
                    "lo": str(cell["lo"]), "hi": str(cell["hi"]),
                    "old_home": cell["old_home"],
                    "new_home": cell["new_home"],
                    "count": cell["count"]})
                self.segments_written += 1
            except (chaos_mod.ChaosError, OSError) as e:
                self.append_faults += 1
                logger.error(
                    "reshard: range segment append failed (%s); cell "
                    "%d merges from memory — zero loss absent a "
                    "crash, but this cell has no crash coverage", e, i)
                mem_cells.append(cell["frames"])
        # the SIGKILL window the soak targets: every durable cell is on
        # disk, the retopo'd tables are empty — a kill here must replay
        # to exactly the same state the merge below produces
        if chaos is not None:
            chaos.reshard_cutover_delay()
        if spool is not None:
            for seg in spool.segments():
                extra = seg.extra or {}
                if extra.get("kind") != "reshard":
                    continue
                batch = rangewire.decode_segment(seg.read_metrics())
                self._merge_decoded(batch)
                spool.pop(seg)
        for frames in mem_cells:
            self._merge_decoded(rangewire.decode_segment(frames))
        self._inflight = 0

    def _merge_decoded(self, batch: rangewire.DecodedBatch) -> int:
        """Merge one decoded range segment into the live tables — the
        single replay path shared by cutover merge-back and crash
        recovery. Ledger: each family batch books ingest.admitted
        (key=reshard); merge_batch books agg.applied (and agg.rejected
        for cardinality-capped rows), so the ingest identity balances
        within the interval."""
        store = self._server.store
        led = self._ledger()

        def admit(n: int) -> None:
            if led is not None and n:
                led.note("ingest.admitted", n, key="reshard")

        merged = 0
        if batch.counter_stubs:
            admit(len(batch.counter_stubs))
            store.counters.merge_batch(batch.counter_stubs,
                                       batch.counter_values)
            merged += len(batch.counter_stubs)
        if batch.gauge_stubs:
            admit(len(batch.gauge_stubs))
            store.gauges.merge_batch(batch.gauge_stubs,
                                     batch.gauge_values)
            merged += len(batch.gauge_stubs)
        if batch.histo_stubs:
            from veneur_tpu.ops import batch_tdigest
            admit(len(batch.histo_stubs))
            pm, pw = batch_tdigest.pack_centroids_many(
                batch.histo_means, batch.histo_weights)
            store.histos.merge_batch(
                batch.histo_stubs, pm, pw, batch.histo_mins,
                batch.histo_maxs, batch.histo_recips)
            if batch.lstats is not None:
                if hasattr(store.histos, "merge_local_stats"):
                    store.histos.merge_local_stats(
                        batch.histo_stubs,
                        *(batch.lstats[k]
                          for k in rangewire.LSTAT_FIELDS))
                else:
                    logger.warning(
                        "reshard replay: store has no sharded "
                        "histogram table; migrated local-sample "
                        "stats (min/max/sum) dropped")
            merged += len(batch.histo_stubs)
        if batch.llhist_stubs:
            admit(len(batch.llhist_stubs))
            store.llhists.merge_batch(batch.llhist_stubs,
                                      np.stack(batch.llhist_bins))
            merged += len(batch.llhist_stubs)
        if batch.set_stubs:
            admit(len(batch.set_stubs))
            store.sets.merge_batch(batch.set_stubs,
                                   np.stack(batch.set_regs))
            merged += len(batch.set_stubs)
        if batch.parse_errors:
            logger.error("reshard replay: %d unparseable frames "
                         "dropped", batch.parse_errors)
        return merged

    # -- recovery --------------------------------------------------------

    def recover(self) -> int:
        """Replay range segments a killed predecessor left behind.
        Runs at startup before listeners: the rows merge into whatever
        topology THIS process built (the WAL stores rows, not shard
        assignments — routing is recomputed by merge_batch), so
        recovery is correct even when the restart config differs from
        the mid-flight target plane."""
        spool = self._spool()
        if spool is None:
            return 0
        replayed = 0
        for seg in spool.segments():
            extra = seg.extra or {}
            if extra.get("kind") != "reshard":
                continue
            try:
                batch = rangewire.decode_segment(seg.read_metrics())
                self._merge_decoded(batch)
            except Exception:
                logger.exception(
                    "reshard recovery: segment %s replay failed; "
                    "left in place", seg.path)
                continue
            spool.pop(seg)
            replayed += 1
            self.replayed_segments += 1
        if replayed:
            logger.warning(
                "reshard recovery: replayed %d range segment(s) from "
                "an interrupted cutover", replayed)
            try:
                self._server.telemetry.record_event(
                    "reshard_replay", segments=replayed)
            except Exception:
                pass
        return replayed
