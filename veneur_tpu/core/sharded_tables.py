"""Live multi-device sharding: the column store as a partitioned mesh.

The reference scales its hot path by sharding metric keys across worker
goroutines and re-merging forwarded state on a global instance (reference
server.go:1016, worker.go:410-467, flusher.go:516-591). On a multi-chip
host the TPU-native equivalent keeps ONE host intern table but
PARTITIONS the interval state of every device family across the local
mesh (parallel/collectives.py owns the kernels and the
`Mesh`/`NamedSharding` layout):

  counters    (n, K) Kahan pairs      merge = psum (selection)
  gauges      (n, K) LWW + set mask   merge = home-shard selection
  histograms  per-shard slot grids    merge = centroid re-insertion
  sets        per-shard registers     merge = elementwise max
  llhists     (n, K, BINS) int32      merge = register ADD (bit-exact)

Routing is **digest-home** by default: a key's 64-bit fnv1a digest picks
its home shard at mint time (parallel/sharded_server.py), and every
sample, batch chunk, and import merge for that key lands on that shard.
That single invariant is what makes the whole plane exact:

  * gauges keep last-write-wins ordering (all of a key's writes serialize
    on one shard — the reason the round-robin era could not shard them);
  * counter Kahan pairs and llhist/HLL registers merge by selection
    (summing n-1 zeros), so flush output is bit-identical to a
    single-device table over the same stream;
  * a dead chip's blast radius is exactly its key range — the failover
    tier (proxy shard groups) re-homes only those keys.

Ingest dispatches keep their compiled shapes: the pending buffer is
masked per shard (non-home rows -> PAD_ROW, dropped by the scatter
kernels) instead of split, so kernels never retrace on data-dependent
sub-batch lengths. `shard_routing: roundrobin` keeps the legacy
round-robin behavior for the histogram/set families (A/B escape hatch);
the scalar and llhist families require digest routing and stay
single-device under round-robin.

Enable with config `tpu.shards: N` (0/1 = single-device tables).
"""

from __future__ import annotations

import contextlib
import logging
import time
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from veneur_tpu.core.columnstore import (CounterTable, GaugeTable,
                                         HistoTable, LLHistTable, PAD_ROW,
                                         SetTable, WarmProgram, _result,
                                         _state_only, _zeros_like_spare)
from veneur_tpu.core.telemetry import FlushRound
from veneur_tpu.ops import batch_hll, batch_llhist, batch_tdigest, scalars
from veneur_tpu.parallel import collectives
from veneur_tpu.parallel.collectives import SHARD_AXIS
from veneur_tpu.parallel.sharded_server import (ROUTING_DIGEST,
                                                ROUTING_ROUNDROBIN,
                                                ShardedServingPlane,
                                                local_shard_devices)

logger = logging.getLogger("veneur_tpu.sharded")

__all__ = [
    "ShardedCounterTable", "ShardedGaugeTable", "ShardedHistoTable",
    "ShardedLLHistTable", "ShardedSetTable", "local_shard_devices",
    "SHARD_AXIS",
]


# kept as aliases so pre-mesh callers (tests, notebooks) keep working;
# the implementations moved to parallel/collectives.py
_stack_on_mesh = collectives.stack_on_mesh
_merge_hll_stacked = collectives.merge_hll_stacked
_merge_histo_stacked = collectives.merge_histo_stacked


class _DigestRouted:
    """Mixin: per-row home-shard assignment + batch masking, shared by
    every sharded family table. Initialized BEFORE _BaseTable.__init__
    (whose _init_arrays builds device state on the mesh)."""

    def _routing_init(self, capacity: int, devices: Optional[List],
                      plane: Optional[ShardedServingPlane]) -> None:
        if plane is None:
            plane = ShardedServingPlane(
                devices or local_shard_devices(2))
        self._plane = plane
        self._devices = plane.devices
        self._mesh = plane.mesh
        self._n_shards = plane.n
        self._shard_sharding = collectives.shard_sharding(plane.mesh)
        # row -> home shard, stamped at mint time (see _note_minted);
        # int8 bounds the mesh at 128 shards, far past any host
        self._shard_of = np.zeros(capacity, np.int8)
        self._rr_next = 0  # roundrobin mode's rotation cursor
        # bumped by every live reshard (_retopo_locked): snapshots carry
        # the epoch they were swapped under, so a readout that crossed a
        # cutover can never donate old-mesh buffers back as a spare
        self._topo_epoch = 0

    @property
    def _digest_routed(self) -> bool:
        return self._plane.routing == ROUTING_DIGEST

    def _note_minted(self, row: int, metric) -> None:
        if row < self._shard_of.shape[0]:
            self._shard_of[row] = self._plane.home(metric.digest64)

    def _grow_shard_of(self, new_cap: int) -> None:
        grown = np.zeros(new_cap, np.int8)
        grown[: self._shard_of.shape[0]] = self._shard_of
        self._shard_of = grown

    def _home_of(self, rows: np.ndarray) -> np.ndarray:
        """(batch,) rows -> home shard per sample, -1 for padding."""
        cap = self._shard_of.shape[0]
        safe = np.minimum(rows, cap - 1)
        return np.where(rows < cap, self._shard_of[safe],
                        np.int8(-1)).astype(np.int32)

    def _shard_counts_of(self, home: np.ndarray) -> np.ndarray:
        return np.bincount(home[home >= 0],
                           minlength=self._n_shards).astype(np.int64)

    def _put_sharded(self, host_arr: np.ndarray):
        return jax.device_put(host_arr, self._shard_sharding)

    def _warm_apply(self) -> WarmProgram:
        """The stacked families' masked apply, as a warm-up entry. Rung
        compiles must not inflate the serving plane's routed/dispatch
        accounting — the batch is all-PAD throwaway."""
        return WarmProgram(
            "apply",
            lambda state, cols: self._apply_cols_state(
                state, cols, note=False),
            lambda state, cols: (state, cols), then=_result)

    def _stacked_batch(self, rows: np.ndarray, value_cols: Tuple,
                       note: bool = True) -> Tuple:
        """Masked (n, batch) row column + tiled value columns for one
        fixed-shape stacked dispatch. With `note` the plane books the
        per-shard sample counts and the wall of this routing (mask,
        tile, device_put): `ingest.shard.route_seconds_total`."""
        t0 = time.perf_counter()
        home = self._home_of(np.asarray(rows))
        srows = collectives.mask_batch_for_shards(
            home, self._n_shards, np.asarray(rows))
        tiled = tuple(
            np.ascontiguousarray(
                collectives.tile_batch(self._n_shards, np.asarray(c)))
            for c in value_cols)
        out = (self._put_sharded(srows),
               tuple(self._put_sharded(t) for t in tiled))
        if note:
            self._plane.note_routed(self.family, self._shard_counts_of(home),
                                    time.perf_counter() - t0)
        return out

    @contextlib.contextmanager
    def _merging(self, snap: dict):
        """One collective merge of a flush readout: a `merge{family}`
        span of the round the readout runs for, child of its
        `dispatch{family}`; the `device.kernel.merge_s` row is fed from
        the span's own clock, so it holds a HOST wall (stacking the
        per-device states and dispatching the merge), not the chip's
        time. A readout nobody times (a hand-called
        `snapshot_and_reset`) gets a round of its own."""
        timing = snap.get("_timing") or FlushRound()
        with timing.phase("merge", parent="dispatch",
                          family=self.family) as span:
            yield
        obs = self._deviceobs
        if obs is not None:
            obs.note_kernel("merge", self.family, span["wall_s"])
        self._plane.note_merge_round()

    # -- elastic resharding (parallel/reshard.py) ------------------------

    def swap_out(self, **kw) -> dict:
        snap = super().swap_out(**kw)
        snap["_topo_epoch"] = self._topo_epoch
        return snap

    def capture_readonly(self, **kw) -> dict:
        snap = super().capture_readonly(**kw)
        snap["topo_epoch"] = self._topo_epoch
        return snap

    def recycle(self, snap: dict) -> None:
        if snap.pop("_topo_epoch", self._topo_epoch) != self._topo_epoch:
            # the snapshot was swapped out under the OLD mesh and its
            # readout finished after a cutover retopologized this table:
            # its spare/recycle buffers are shaped (N_old, ...) and must
            # never be installed into the (M, ...) generation ladder
            for key in ("cap", "_spare", "_recycle"):
                snap.pop(key, None)
            tok = snap.pop("_devobs", None)
            obs = self._deviceobs
            if obs is not None:
                obs.drop(tok)
            return
        super().recycle(snap)

    def reshard_swap(self, new_plane: ShardedServingPlane, **kw) -> dict:
        """The per-family cutover primitive: ONE critical section that
        (a) swaps the current interval's generation out exactly like a
        flush boundary (pending columns folded, extras captured), (b)
        reduces the captured per-shard state to a single merged copy on
        the OLD mesh (`_reshard_capture_device` — the same selection /
        reduction expressions the flush merge uses, so the migrated
        values are the values a flush would have emitted), and (c)
        rebinds the table to `new_plane` (`_retopo_locked`). Ingest that
        lands after the locks release accumulates directly in the new
        topology; everything before is in the returned snap, which the
        reshard controller serializes to the range-segment WAL and
        merges back through the family's own merge_batch path.

        Atomic because the table locks are plain (non-reentrant) Locks:
        holding them across an external WAL write would deadlock every
        concurrent ingest dispatch, and releasing between swap and
        retopo would let a sample land in a generation nobody drains."""
        snap = dict(kw)
        with self.lock:
            idle = self._idle_swap_locked(snap)
            if not idle:
                snap["cols"] = self._swap_locked()
            with self.apply_lock:
                if not idle:
                    self._note_generation_locked()
                    snap["touched"] = self.touched.copy()
                    snap["meta"] = list(self.meta)
                    # per-row 64-bit key digests, for the range-cell
                    # partition of the migrating rows (dict key is
                    # (digest64 << 2) | scope)
                    digests = np.zeros(self.touched.shape[0], np.uint64)
                    for row, dict_key in enumerate(self._dict_key_of):
                        if row < digests.shape[0]:
                            digests[row] = np.uint64(
                                (dict_key >> 2) & 0xFFFFFFFFFFFFFFFF)
                    snap["digest64"] = digests
                    self.touched[:] = False
                    self._swap_extras_locked(snap)
                    state = self._swap_device_locked()
                    cols = snap.pop("cols", None)
                    if cols is not None:
                        # folds the final pending columns on the OLD
                        # topology (the routing attrs are still bound)
                        state = self._readout_apply(state, cols, snap)
                    snap.pop("staged", None)
                    self._reshard_capture_device(state, snap)
                    # the captured old-mesh generation stays resident
                    # until the controller's WAL+merge completes: its
                    # ledger token rides the snap as `reshard_capture`
                    obs = self._deviceobs
                    if obs is not None:
                        tok = self._devobs_inflight
                        self._devobs_inflight = None
                        obs.retag(tok, "reshard_capture")
                        snap["_devobs"] = tok
                self._retopo_locked(new_plane)
        snap["_topo_epoch"] = self._topo_epoch
        return snap

    def _reshard_capture_device(self, state, snap: dict) -> None:
        """Family hook: reduce the captured per-shard generation to one
        merged, NON-donated copy the controller can serialize (runs on
        the old mesh, inside the cutover critical section)."""
        raise NotImplementedError

    def _retopo_locked(self, plane: ShardedServingPlane) -> None:
        """Rebind this table to a new serving plane (caller holds
        ``lock`` + ``apply_lock``): new mesh/sharding, every live row's
        home recomputed under the new range assignment, fresh device
        state, and all old-mesh spares/prewarm records invalidated."""
        self._plane = plane
        self._devices = plane.devices
        self._mesh = plane.mesh
        self._n_shards = plane.n
        self._shard_sharding = collectives.shard_sharding(plane.mesh)
        self._rr_next = 0
        shard_of = np.zeros(self._shard_of.shape[0], np.int8)
        for dict_key, row in self.rows.items():
            if row < shard_of.shape[0]:
                shard_of[row] = plane.home(dict_key >> 2)
        self._shard_of = shard_of
        # old-mesh buffers can never serve the new topology
        self._spare = None
        self._spare_cap = -1
        obs = self._deviceobs
        if obs is not None:
            # the parked spare is discarded with the old mesh, and the
            # live generation is about to be rebound to a fresh one —
            # on the IDLE cutover path no swap ran, so the original
            # live token is still held here and dies now
            obs.drop(self._devobs_spare)
            self._devobs_spare = None
            obs.drop(self._devobs_live)
            self._devobs_live = None
        self._prewarmed_caps = set()
        self._topo_epoch += 1
        self._retopo_device_locked()
        if obs is not None:
            self._devobs_live = obs.note_generation(
                self.family, "live", self._devobs_state())

    def _retopo_device_locked(self) -> None:
        # stacked families: a fresh (M, K) zero generation on the new
        # mesh (per-device families override)
        self.state = self._fresh_state()


# ---------------------------------------------------------------------------
# Scalar families: stacked (n, K) state under one NamedSharding, one
# jitted vmapped scatter per dispatch, collective selection at flush.
# ---------------------------------------------------------------------------


class ShardedCounterTable(_DigestRouted, CounterTable):
    """CounterTable partitioned across the mesh: each key's deltas
    accumulate in its home shard's Kahan pair; flush merges by psum
    (pure selection under digest routing, so the f64 host readout is
    bit-identical to single-device)."""

    def __init__(self, capacity: int = 1024, batch_cap: int = 8192,
                 devices: Optional[List] = None, max_rows: int = 0,
                 plane: Optional[ShardedServingPlane] = None):
        self._routing_init(capacity, devices, plane)
        super().__init__(capacity, batch_cap, max_rows=max_rows)

    def _init_arrays(self):
        super()._init_arrays()
        self.state = collectives.init_stacked(
            self._mesh, scalars.init_counters, self.capacity)

    def _grow_arrays(self, new_cap):
        self._grow_shard_of(new_cap)
        self.state = collectives.grow_stacked(self._mesh, self.state,
                                              new_cap)

    def _fresh_state_at(self, capacity: int):
        return collectives.init_stacked(
            self._mesh, scalars.init_counters, capacity)

    def _apply_cols_state(self, state, cols, note: bool = True):
        rows, vals, rates = cols
        srows, (svals, srates) = self._stacked_batch(
            rows, (vals, rates), note)
        return collectives.apply_counters_sharded(
            state, srows, svals, srates)

    def _readout_device(self, state, snap) -> None:
        """Fused donated collective merge: the drained stacked
        generation's buffers come back as the next interval's spare."""
        with self._merging(snap):
            snap["dev"], snap["_spare"] = \
                collectives.merge_counters_stacked_reset(
                    state, self._shard_sharding)

    def _query_readout_device(self, state, snap) -> None:
        # read-only merge over the LIVE stacked generation: the fused
        # reset variant would donate (and zero) the live buffers. Same
        # reduction expression, so query results stay bit-identical to
        # the flush readout under digest routing.
        snap["dev"] = collectives.merge_counters_stacked(state)
        self._plane.note_merge_round()

    def warm_programs(self, ps, need_export):
        # the fused merge zeroes the generation it drains
        return [self._warm_apply(),
                WarmProgram("merge",
                            collectives.merge_counters_stacked_reset,
                            lambda state, cols: (state,
                                                 self._shard_sharding))]

    def _reshard_capture_device(self, state, snap: dict) -> None:
        # psum selection, non-donating: (sum, comp) per row, the exact
        # pair snapshot_finish differences (counter totals are integral
        # by the apply kernel's trunc contract, so the f64 host total
        # survives the metricpb int64 wire bit-exactly)
        snap["dev"] = collectives.merge_counters_stacked(state)
        self._plane.note_merge_round()


class ShardedGaugeTable(_DigestRouted, GaugeTable):
    """GaugeTable partitioned across the mesh. Digest-home routing is
    load-bearing here: every write for a key serializes on its home
    shard, so last-write-wins ordering survives sharding (the property
    the round-robin split destroyed, which is why gauges stayed
    single-device until this plane)."""

    def __init__(self, capacity: int = 1024, batch_cap: int = 8192,
                 devices: Optional[List] = None, max_rows: int = 0,
                 plane: Optional[ShardedServingPlane] = None):
        self._routing_init(capacity, devices, plane)
        super().__init__(capacity, batch_cap, max_rows=max_rows)

    def _init_arrays(self):
        super()._init_arrays()
        self.state = collectives.init_stacked(
            self._mesh, scalars.init_gauges, self.capacity)

    def _grow_arrays(self, new_cap):
        self._grow_shard_of(new_cap)
        self.state = collectives.grow_stacked(self._mesh, self.state,
                                              new_cap)

    def _fresh_state_at(self, capacity: int):
        return collectives.init_stacked(
            self._mesh, scalars.init_gauges, capacity)

    def _apply_cols_state(self, state, cols, note: bool = True):
        rows, vals = cols
        srows, (svals,) = self._stacked_batch(rows, (vals,), note)
        return collectives.apply_gauges_sharded(state, srows, svals)

    def merge_batch(self, stubs, values) -> None:
        """Import-path overwrite, routed to each row's home shard (the
        same masked-batch shape as ingest, so ordering semantics
        match)."""
        with self.lock:
            rows = np.fromiter(
                (self.row_for(s) for s in stubs), np.int32, len(stubs))
            ok = rows >= 0  # cardinality-capped stubs drop out
            rows = rows[ok]
            self.touched[rows] = True
            self._note_applied(int(rows.size))
            self.apply_lock.acquire()
        try:
            if rows.size:
                srows, (svals,) = self._stacked_batch(
                    rows, (np.asarray(values, np.float32)[ok],),
                    note=False)
                self.state = collectives.merge_gauges_sharded(
                    self.state, srows, svals)
        finally:
            self.apply_lock.release()

    def _readout_device(self, state, snap) -> None:
        with self._merging(snap):
            (dev, _set), snap["_spare"] = \
                collectives.merge_gauges_stacked_reset(
                    state, self._shard_sharding)
        snap["dev"] = dev

    def _query_readout_device(self, state, snap) -> None:
        # non-donating LWW merge (see ShardedCounterTable note)
        dev, _set = collectives.merge_gauges_stacked(state)
        snap["dev"] = dev
        self._plane.note_merge_round()

    def warm_programs(self, ps, need_export):
        return [self._warm_apply(),
                WarmProgram("merge", collectives.merge_gauges_stacked_reset,
                            lambda state, cols: (state,
                                                 self._shard_sharding))]

    def _reshard_capture_device(self, state, snap: dict) -> None:
        # home-shard LWW selection, non-donating; the set mask rides
        # along so untouched rows are distinguishable from value 0.0
        dev, set_mask = collectives.merge_gauges_stacked(state)
        snap["dev"] = dev
        snap["set"] = set_mask
        self._plane.note_merge_round()


class ShardedLLHistTable(_DigestRouted, LLHistTable):
    """LLHistTable partitioned across the mesh: a (n, K, BINS_PAD) int32
    register bank sharded on the leading axis; ingest scatter-adds into
    each key's home shard, flush merges with one register-ADD reduction.
    Integer addition is associative and commutative, so the merged
    registers — and therefore every percentile, count, sum, and bucket
    the flusher emits, and every forwarded bin payload — are
    BIT-IDENTICAL to a single-device table (the PR-5 exactness pin,
    generalized to the mesh)."""

    def __init__(self, capacity: int = 1024, batch_cap: int = 8192,
                 devices: Optional[List] = None, max_rows: int = 0,
                 plane: Optional[ShardedServingPlane] = None):
        self._routing_init(capacity, devices, plane)
        super().__init__(capacity, batch_cap, max_rows=max_rows)

    def _init_arrays(self):
        super()._init_arrays()
        self.state = collectives.init_stacked(
            self._mesh, batch_llhist.init_state, self.capacity)

    def _grow_arrays(self, new_cap):
        self._grow_shard_of(new_cap)
        self.state = collectives.grow_stacked(self._mesh, self.state,
                                              new_cap)

    def _fresh_state_at(self, capacity: int):
        return collectives.init_stacked(
            self._mesh, batch_llhist.init_state, capacity)

    def _apply_cols_state(self, state, cols, note: bool = True):
        rows, bins, wts = cols
        srows, (sbins, swts) = self._stacked_batch(
            rows, (bins, wts), note)
        return collectives.apply_llhist_sharded(state, srows, sbins, swts)

    def merge_batch(self, stubs, in_bins) -> None:
        """Import-path register ADD, each incoming row landed on its
        home shard (exact under any routing — addition commutes — but
        home routing keeps the shard-is-the-key-range invariant that
        failover re-homing relies on)."""
        with self.lock:
            rows = np.fromiter(
                (self.row_for(s) for s in stubs), np.int32, len(stubs))
            ok = rows >= 0  # cardinality-capped stubs drop out
            rows = rows[ok]
            self.touched[rows] = True
            self._note_applied(int(rows.size))
            padded = batch_llhist.pad_rows_to_device(
                np.asarray(in_bins)[ok])
            self.samples_total += int(padded.sum())
            home = self._home_of(rows)
            self.apply_lock.acquire()
        try:
            if rows.size:
                self.state = collectives.merge_llhist_rows_at(
                    self.state, jnp.asarray(home), jnp.asarray(rows),
                    jnp.asarray(padded))
        finally:
            self.apply_lock.release()

    def _readout_device(self, state, snap) -> None:
        with self._merging(snap):
            merged, snap["_spare"] = \
                collectives.merge_llhist_stacked_reset(
                    state, self._shard_sharding)
        packed = batch_llhist.flush_packed(merged, snap["ps"])
        rows = np.flatnonzero(snap["touched"])
        bins_dev = None
        if snap.pop("need_bins") and rows.size:
            bins_dev = jnp.take(merged, jnp.asarray(rows, jnp.int32),
                                axis=0)
        snap["packed"] = packed
        snap["bins_dev"] = bins_dev

    def _query_readout_device(self, state, snap) -> None:
        # non-donating register-ADD merge over the live stacked bank
        # (integer addition: bit-identical to the fused reset merge)
        merged = collectives.merge_llhist_stacked(state)
        self._plane.note_merge_round()
        packed = batch_llhist.flush_packed(merged, snap["ps"])
        rows = np.flatnonzero(snap["touched"])
        bins_dev = None
        if snap.pop("need_bins") and rows.size:
            bins_dev = jnp.take(merged, jnp.asarray(rows, jnp.int32),
                                axis=0)
        snap["packed"] = packed
        snap["bins_dev"] = bins_dev

    def warm_programs(self, ps, need_export):
        # the fused merge returns (merged, fresh): the readout reads the
        # first and hands the second on
        return [self._warm_apply(),
                WarmProgram("merge", collectives.merge_llhist_stacked_reset,
                            lambda state, cols: (state,
                                                 self._shard_sharding),
                            then=_result),
                WarmProgram("readout", batch_llhist.flush_packed,
                            lambda carry, cols: (carry[0],), static=(ps,),
                            then=lambda carry, packed: carry[1])]

    def _reshard_capture_device(self, state, snap: dict) -> None:
        # register ADD, non-donating: the merged (K, BINS_PAD) bank is
        # bit-identical to what a flush would have reduced, and integer
        # addition keeps the replay merge bit-exact too
        snap["bins"] = collectives.merge_llhist_stacked(state)
        self._plane.note_merge_round()


# ---------------------------------------------------------------------------
# Sketch families with per-shard grids (histograms, sets): per-device
# states, digest-home masked dispatch, stacked collective flush merge.
# ---------------------------------------------------------------------------


class _PerDeviceStates:
    """Generation swap over the per-device `states` list (the histo/set
    sharded families keep one committed state per device rather than a
    stacked array; `self.state` stays None)."""

    def _swap_device_locked(self):
        captured = self.states
        spare, self._spare = self._spare, None
        used_spare = (spare is not None
                      and self._spare_cap == self._state_capacity())
        if used_spare:
            self.states = spare
        else:
            self.states = self._fresh_state()
        self._devobs_swap_locked(used_spare)
        return captured

    def _capture_device_locked(self):
        # shallow list copy under apply_lock: a consistent point-in-time
        # set of per-device array refs (ingest rebinds list entries)
        return list(self.states)

    def _retopo_device_locked(self) -> None:
        self.states = self._fresh_state()
        self.state = None


class ShardedHistoTable(_PerDeviceStates, _DigestRouted, HistoTable):
    """HistoTable whose interval state lives across N local devices;
    ingest routes each key's samples to its home shard (digest mode) or
    round-robins whole batches (legacy mode); flush merges across the
    device axis with collectives."""

    def __init__(self, capacity: int = 1024, batch_cap: int = 8192,
                 devices: Optional[List] = None, max_rows: int = 0,
                 plane: Optional[ShardedServingPlane] = None):
        self._routing_init(capacity, devices, plane)
        super().__init__(capacity, batch_cap, max_rows=max_rows)

    def _init_arrays(self):
        self._init_pending()
        self.states = [
            jax.device_put(batch_tdigest.init_state(self.capacity), d)
            for d in self._devices]
        self._shard_counts = [np.zeros(self.capacity, np.int32)
                              for _ in self._devices]
        self.state = None  # unused; all device state lives in .states

    def _grow_arrays(self, new_cap):
        self._grow_shard_of(new_cap)
        grown = []
        for dev, st in zip(self._devices, self.states):
            new = batch_tdigest.init_state(new_cap)
            g = {k: jax.lax.dynamic_update_slice(
                    new[k], st[k], (0,) * new[k].ndim) for k in new}
            grown.append(jax.device_put(g, dev))
        self.states = grown
        extended = []
        for counts in self._shard_counts:
            e = np.zeros(new_cap, np.int32)
            e[: counts.shape[0]] = counts
            extended.append(e)
        self._shard_counts = extended

    def _fresh_state_at(self, capacity: int):
        return [jax.device_put(batch_tdigest.init_state(capacity), d)
                for d in self._devices]

    def _apply_to_shard(self, states, shard_counts, i: int, rows, vals,
                        wts, path: str = "live") -> float:
        """One shard's masked fixed-shape batch apply over an explicit
        (states, staging-occupancy) generation — the live path passes
        the table's own, the flush readout the captured one; handles
        the per-shard staging compact. Returns the wall of placing the
        batch on the shard's device (routing, not apply)."""
        dev = self._devices[i]
        states[i], slots = self._slots(states[i], (rows, vals, wts),
                                       shard_counts[i], path)
        t0 = time.perf_counter()
        placed = [jax.device_put(c, dev) for c in (rows, vals, wts, slots)]
        route_s = time.perf_counter() - t0
        states[i] = batch_tdigest.apply_batch(states[i], *placed)
        return route_s

    def _apply_cols_states(self, states, shard_counts, cols,
                           path: str = "live") -> None:
        rows, vals, wts = cols
        if not self._digest_routed:
            # legacy round-robin: whole batch to the next shard
            i = self._rr_next
            self._rr_next = (i + 1) % self._n_shards
            self._apply_to_shard(states, shard_counts, i, rows, vals, wts,
                                 path)
            return
        t0 = time.perf_counter()
        home = self._home_of(rows)
        counts = self._shard_counts_of(home)
        route_s = time.perf_counter() - t0
        for i in np.flatnonzero(counts).tolist():
            # masked, not split: the kernels' compiled (batch_cap,)
            # shape is preserved; non-home rows scatter-drop
            t0 = time.perf_counter()
            rows_i = np.where(home == i, rows, PAD_ROW)
            route_s += time.perf_counter() - t0
            route_s += self._apply_to_shard(states, shard_counts, i,
                                            rows_i, vals, wts, path)
        self._plane.note_routed(self.family, counts, route_s)

    def _apply_cols(self, cols):
        self._apply_cols_states(self.states, self._shard_counts, cols)
        self._applies += 1

    def merge_batch(self, stubs, in_means, in_weights, in_min, in_max,
                    in_recip) -> None:
        """Import-path digest merge, routed per home shard (digest mode;
        digest merge is commutative across shards, so the legacy mode's
        single-shard landing stays correct too)."""
        with self.lock:
            rows = np.fromiter(
                (self.row_for(s) for s in stubs), np.int32, len(stubs))
            # cardinality-capped/rejected stubs drop out: scattering a
            # -1 row would negative-index the LAST device row
            ok = rows >= 0
            rows = rows[ok]
            self.touched[rows] = True
            self._note_applied(int(rows.size))
            home = (self._home_of(rows) if self._digest_routed
                    else np.full(rows.shape, self._rr_next, np.int32))
            if not self._digest_routed:
                self._rr_next = (self._rr_next + 1) % self._n_shards
            self.apply_lock.acquire()
        try:
            sel_arrs = tuple(np.asarray(a, np.float32)[ok]
                             for a in (in_means, in_weights, in_min,
                                       in_max, in_recip))
            for i in np.unique(home[home >= 0]).tolist():
                sel = home == i
                dev = self._devices[i]
                put = lambda a: jax.device_put(a, dev)  # noqa: E731
                self.states[i] = batch_tdigest.merge_centroid_rows(
                    self.states[i], put(rows[sel]),
                    *(put(a[sel]) for a in sel_arrs))
                # merge_centroid_rows folds every staged row on this
                # shard
                self._shard_counts[i][:] = 0
        finally:
            self.apply_lock.release()

    def _merged_state(self, states, note: bool = True
                      ) -> Dict[str, jnp.ndarray]:
        stacked = {
            k: collectives.stack_on_mesh(
                self._mesh, [st[k] for st in states])
            for k in states[0]}
        if note:
            self._plane.note_merge_round()
        return collectives.merge_histo_stacked(stacked)

    def _swap_extras_locked(self, snap: dict) -> None:
        snap["staged"] = self._shard_counts
        self._shard_counts = [np.zeros(self.capacity, np.int32)
                              for _ in self._devices]
        self._applies = 0

    def _readout_apply(self, states, cols, snap: dict):
        self._apply_cols_states(states, snap.pop("staged"), cols,
                                path="readout")
        return states

    def _readout_device(self, states, snap: dict) -> None:
        with self._merging(snap):
            merged = self._merged_state(states, note=False)
        ps = snap["ps"]
        if snap.pop("need_export"):
            # fused flush+export: one dispatch, two transfers (the
            # merged state's staging is already folded, so the fold
            # inside the fused op is a no-op concat of zeros).
            packed, export_packed = batch_tdigest.flush_export_packed(
                merged, ps)
        else:
            packed = batch_tdigest.flush_quantiles_packed(
                merged, ps, fold_staging=False)
            export_packed = None
        snap["packed"] = packed
        snap["export_packed"] = export_packed
        snap["_recycle"] = states

    def warm_programs(self, ps, need_export):
        """Per shard the apply and the staging compact (each device has
        an executable of its own), then the collective merge, the
        readout of what it returned (the carry is (states, merged)
        between the two), then the per-device zeroing."""

        def apply(states, cols):
            rows, vals, wts = cols
            counts = [np.zeros(st["wv"].shape[0], np.int32)
                      for st in states]
            for i in range(self._n_shards):
                self._apply_to_shard(states, counts, i, rows, vals, wts)
            return states

        def compact(states):
            return [batch_tdigest.compact(
                st, np.zeros(st["wv"].shape[0], bool))[0] for st in states]

        def readout(merged):
            if need_export:
                return batch_tdigest.flush_export_packed(merged, ps)
            return batch_tdigest.flush_quantiles_packed(
                merged, ps, fold_staging=False)

        return [
            WarmProgram("apply", apply, lambda states, cols: (states, cols),
                        then=_result),
            WarmProgram("compact", compact, _state_only, then=_result),
            WarmProgram("merge",
                        lambda states: self._merged_state(states,
                                                          note=False),
                        _state_only,
                        then=lambda states, merged: (states, merged)),
            WarmProgram("readout", readout,
                        lambda carry, cols: (carry[1],),
                        then=lambda carry, out: carry[0]),
            WarmProgram("reset", self._reset_state_donated, _state_only,
                        then=_result)]

    def _retopo_device_locked(self) -> None:
        super()._retopo_device_locked()
        self._shard_counts = [np.zeros(self.capacity, np.int32)
                              for _ in self._devices]
        self._applies = 0

    def _reshard_capture_device(self, states, snap: dict) -> None:
        # concat + recompress across shards (staging already folded by
        # the readout apply above); the merged dict carries BOTH the
        # digest-side d* stats and the local-sample l* stats — the wire
        # encodes d* into MergingDigestData and the controller sidecars
        # l*, because merge_centroid_rows deliberately never touches l*
        snap["hstate"] = self._merged_state(states)

    def merge_local_stats(self, stubs, lmin, lmax, lsum, lweight,
                          lrecip) -> None:
        """Re-attach migrated LOCAL sample stats to their (new) home
        shards. The import merge path (merge_batch above) carries only
        the digest-side state — by design: a forwarded digest is remote
        data, its receiver has no local samples. A reshard migration is
        the one caller for which the l* stats ARE local history, so the
        controller replays them here right after the centroid merge
        (same stub batch, rows already interned and ledger-booked — no
        _note_applied)."""
        with self.lock:
            rows = np.fromiter(
                (self.row_for(s) for s in stubs), np.int32, len(stubs))
            ok = rows >= 0
            rows = rows[ok]
            home = self._home_of(rows)
            self.apply_lock.acquire()
        try:
            arrs = tuple(np.asarray(a, np.float32)[ok]
                         for a in (lmin, lmax, lsum, lweight, lrecip))
            for i in np.unique(home[home >= 0]).tolist():
                sel = home == i
                dev = self._devices[i]
                put = lambda a: jax.device_put(a, dev)  # noqa: E731
                rsel = put(rows[sel])
                st = dict(self.states[i])
                st["lmin"] = st["lmin"].at[rsel].min(put(arrs[0][sel]))
                st["lmax"] = st["lmax"].at[rsel].max(put(arrs[1][sel]))
                st["lsum"] = st["lsum"].at[rsel].add(put(arrs[2][sel]))
                st["lweight"] = st["lweight"].at[rsel].add(
                    put(arrs[3][sel]))
                st["lrecip"] = st["lrecip"].at[rsel].add(put(arrs[4][sel]))
                self.states[i] = st
        finally:
            self.apply_lock.release()


class ShardedSetTable(_PerDeviceStates, _DigestRouted, SetTable):
    """SetTable whose HLL register banks live across N local devices;
    ingest routes each key's stream to its home shard, flush merges
    registers with an all-reduce max (exact under any routing — max
    commutes — with digest routing keeping the key-range invariant)."""

    def __init__(self, capacity: int = 256, batch_cap: int = 8192,
                 devices: Optional[List] = None, max_rows: int = 0,
                 plane: Optional[ShardedServingPlane] = None):
        self._routing_init(capacity, devices, plane)
        # dense path: sharding already spreads register memory across
        # devices, and the collective merge needs uniform dense rows
        super().__init__(capacity, batch_cap, sparse=False,
                         max_rows=max_rows)

    def _init_arrays(self):
        self._init_pending()
        self.states = [
            jax.device_put(batch_hll.init_state(self.capacity), d)
            for d in self._devices]
        self.state = None

    def _grow_arrays(self, new_cap):
        self._grow_shard_of(new_cap)
        self.states = [
            jax.device_put(
                jnp.pad(st, [(0, new_cap - st.shape[0]), (0, 0)]), dev)
            for dev, st in zip(self._devices, self.states)]

    def _state_capacity(self) -> int:
        # dense per-device banks track row capacity (no slot ladder)
        return self.capacity

    def _fresh_state_at(self, capacity: int):
        return [jax.device_put(batch_hll.init_state(capacity), d)
                for d in self._devices]

    def _apply_cols_states(self, states, cols) -> Tuple[int, int]:
        """Apply one batch to the per-device banks it reaches; returns
        the apply_batch dispatches and the entries they carried."""
        rows, idxs, rhos = cols
        if not self._digest_routed:
            i = self._rr_next
            self._rr_next = (i + 1) % self._n_shards
            dev = self._devices[i]
            r, ix, rh = (jax.device_put(c, dev) for c in cols)
            states[i] = batch_hll.apply_batch(states[i], r, ix, rh)
            return 1, int(np.count_nonzero(rows != PAD_ROW))
        t0 = time.perf_counter()
        home = self._home_of(rows)
        counts = self._shard_counts_of(home)
        route_s = time.perf_counter() - t0
        for i in np.flatnonzero(counts).tolist():
            dev = self._devices[i]
            t0 = time.perf_counter()
            placed = [jax.device_put(c, dev) for c in
                      (np.where(home == i, rows, PAD_ROW), idxs, rhos)]
            route_s += time.perf_counter() - t0
            states[i] = batch_hll.apply_batch(states[i], *placed)
        self._plane.note_routed(self.family, counts, route_s)
        return int(np.count_nonzero(counts)), int(counts.sum())

    def _apply_cols(self, cols):
        self._apply_cols_states(self.states, cols)

    def _readout_apply(self, states, cols, snap: dict):
        with self._set_phase(snap, "set_fold"):
            self._note_fold(snap, *self._apply_cols_states(states, cols))
        return states

    def merge_batch(self, stubs, in_regs) -> None:
        with self.lock:
            rows = np.fromiter(
                (self.row_for(s) for s in stubs), np.int32, len(stubs))
            # cardinality-capped/rejected stubs drop out: scattering a
            # -1 row would negative-index the LAST device row
            ok = rows >= 0
            rows = rows[ok]
            self.touched[rows] = True
            self._note_applied(int(rows.size))
            home = (self._home_of(rows) if self._digest_routed
                    else np.full(rows.shape, self._rr_next, np.int32))
            if not self._digest_routed:
                self._rr_next = (self._rr_next + 1) % self._n_shards
            self.apply_lock.acquire()
        try:
            regs_sel = np.asarray(in_regs, np.int8)[ok]
            for i in np.unique(home[home >= 0]).tolist():
                sel = home == i
                dev = self._devices[i]
                self.states[i] = batch_hll.merge_rows(
                    self.states[i], jax.device_put(rows[sel], dev),
                    jax.device_put(regs_sel[sel], dev))
        finally:
            self.apply_lock.release()

    def _merged_state(self, states, note: bool = True) -> jnp.ndarray:
        stacked = collectives.stack_on_mesh(self._mesh, states)
        if note:
            self._plane.note_merge_round()
        return collectives.merge_hll_stacked(stacked)

    def _readout_device(self, states, snap: dict) -> None:
        """The dispatch half of `SetTable`'s readout, the merge's own
        span between two `set_fold`s: the last pending batch, routed and
        applied per shard, before it; the estimate of the merged bank,
        which every device computes, dispatched after it and left for
        `collect`. The lazy per-row provider (columnstore._SetRegisters)
        references the MERGED bank, so the drained per-device
        generations are recyclable, once the estimate has been
        collected: they are the merge's inputs."""
        with self._merging(snap):
            merged = self._merged_state(states, note=False)
        self._dispatch_estimate(merged, snap)
        snap["_recycle"] = states

    def warm_programs(self, ps, need_export):
        """Unlike the sparse table's, the dense per-device banks DO
        track row capacity, so a resize retraces: every rung is warmed
        (`SetTable.prewarm_rung`)."""

        def apply(states, cols):
            return [batch_hll.apply_batch(
                st, *(jax.device_put(c, dev) for c in cols))
                for dev, st in zip(self._devices, states)]

        def readout(states):
            return batch_hll.estimate(
                self._merged_state(states, note=False))

        return [
            WarmProgram("apply", apply, lambda states, cols: (states, cols),
                        then=_result),
            WarmProgram("readout", readout, _state_only),
            WarmProgram("reset", _zeros_like_spare, _state_only,
                        then=_result)]

    def _reshard_capture_device(self, states, snap: dict) -> None:
        # elementwise register max, non-donating — bit-exact under
        # migration (max is idempotent and commutative)
        snap["regs"] = self._merged_state(states)
