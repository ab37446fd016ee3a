"""The device column store: metric keys are rows, samples are batches.

This replaces the reference's per-worker map-of-samplers hot path
(reference worker.go:59-176, WorkerMetrics.Upsert and the per-type maps)
with four device-resident tables:

  counters  (K,)      f32 accumulators
  gauges    (K,)      f32 last-write-wins + set mask
  histos    (K, C)    t-digest centroid grids + per-key stats
  sets      (K, 16k)  HLL registers

A host dictionary interns MetricKey (by 64-bit fnv1a digest) to a row id;
names/tags/scopes never leave the host. Samples append into pinned numpy
batch buffers and are applied to device arrays in fixed-size padded batches
(one scatter/sort kernel per batch), so the device sees a few large
dispatches per second instead of one per packet.

State is interval-scoped: flush snapshots the device arrays and zeroes them
(the map-swap trick of reference worker.go:470-489); the key dictionary
persists so steady-state ingest never re-interns.

Capacity management: row capacity doubles on demand (device arrays are
padded and the jitted kernels recompile once per capacity, amortized to
zero); batch buffers are fixed-size so kernels compile once per (capacity,
batch) shape.
"""

from __future__ import annotations

import logging
import threading
import time
from dataclasses import dataclass, field
from functools import lru_cache, partial
from typing import Callable, Dict, List, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from veneur_tpu.core.telemetry import FlushRound, annotate
from veneur_tpu.ops import (batch_hll, batch_llhist, batch_tdigest,
                            device_scope, hll_ref, llhist_ref, scalars)
from veneur_tpu.samplers import metrics as m
from veneur_tpu.samplers.metrics import MetricScope, UDPMetric
from veneur_tpu.util import compilecache

logger = logging.getLogger("veneur_tpu.core.columnstore")

# pending-buffer padding marker: any out-of-range row is dropped by the
# scatter kernels (mode="drop"), independent of table capacity
PAD_ROW = np.int32(2**31 - 1)


@partial(jax.jit, donate_argnums=0)
@device_scope("reset", "any")
def _zeros_like_donated(tree):
    """Zero a drained interval generation IN PLACE (buffer donation —
    the SNIPPETS pjit donation vectors): the returned fresh generation
    aliases the donated input's buffers, so the double-buffered flush
    ping-pongs two device allocations per family instead of allocating
    a new interval state every flush."""
    return jax.tree.map(jnp.zeros_like, tree)


def _state_device(tree):
    return next(iter(jax.tree.leaves(tree)[0].devices()))


@lru_cache(maxsize=None)
def _zeros_like_donated_on(device):
    """Per-device reset variant for the sharded histo/set spare lists.
    The reset's output carries no data dependence on the donated input,
    so without an explicit out_sharding XLA commits it to the DEFAULT
    device — every entry of a per-device spare list would silently land
    on device 0 and the next flush's cross-shard stack would reject the
    duplicate placement."""
    return jax.jit(
        device_scope("reset", "any")(
            lambda tree: jax.tree.map(jnp.zeros_like, tree)),
        donate_argnums=0,
        out_shardings=jax.sharding.SingleDeviceSharding(device))


def _zeros_like_spare(captured):
    """Donate-and-zero one captured generation — a state pytree, or a
    per-device list of them (the sharded histo/set tables), which must
    zero per device because one jit call cannot mix committed devices."""
    if isinstance(captured, list):
        return [_zeros_like_donated_on(_state_device(st))(st)
                for st in captured]
    return _zeros_like_donated(captured)


@partial(jax.jit, donate_argnums=0)
@device_scope("reset", "histogram")
def _reset_tdigest_donated(state):
    """Donated t-digest generation reset: rebuilds init_state's values
    (±inf min/max, zero grids) in the donated buffers."""
    return batch_tdigest.init_state(state["wv"].shape[0])


@lru_cache(maxsize=None)
def _reset_tdigest_donated_on(device):
    # same device pin as _zeros_like_donated_on: init_state's values
    # are constants, so the output needs an explicit placement
    return jax.jit(
        device_scope("reset", "histogram")(
            lambda st: batch_tdigest.init_state(st["wv"].shape[0])),
        donate_argnums=0,
        out_shardings=jax.sharding.SingleDeviceSharding(device))


def _reset_tdigest_spare(captured):
    if isinstance(captured, list):
        return [_reset_tdigest_donated_on(_state_device(st))(st)
                for st in captured]
    return _reset_tdigest_donated(captured)


@dataclass
class RowMeta:
    """Host-side identity of a row (never touches the device)."""

    name: str
    tags: List[str]
    joined_tags: str
    digest32: int
    scope: MetricScope
    wire_type: str  # counter/gauge/histogram/timer/set/status
    # per-row cache of rendered flush-metric names ("x.max",
    # "x.99percentile", ...): metas persist across intervals, so the
    # flusher's hot loop renders each name once per key lifetime instead
    # of once per flush
    flush_names: dict = None
    # per-row cache of the metricpb wire prefix/suffix (serialized
    # fields 1-3 and field 9) used by the native forward encoder —
    # identity-only, so it too lives for the row's lifetime
    pb_frame: tuple = None


class WarmProgram(NamedTuple):
    """One device program of a table's warm-up list (`warm_programs`):
    `fn(*args(carry, cols), *static)` is the call the live path makes,
    on throwaway state and an all-padding batch `cols`. On a one-device
    table `fn` is the jitted program itself, so
    tests/test_chip_compile.py lowers the same entries for a described
    v5e (bar the set bank's climbs and fresh generations above its
    first rung: eager calls, as the live path makes them). `carry` is
    what the list threads from entry to entry: the
    throwaway generation to begin with, then `then(carry, result)` of
    each entry (`None` = the program donates nothing and the next entry
    gets the same carry; `_result` = it donates the state and returns
    the next one; a sharded list keeps the merged state beside the
    per-device ones for its readout)."""

    program: str
    fn: Callable
    args: Callable
    static: tuple = ()
    then: Optional[Callable] = None


def _state_only(state, cols):
    return (state,)


def _state_and_cols(state, cols):
    return (state, *cols)


def _no_args(state, cols):
    return ()


def _result(carry, result):
    return result


def _state_and_fold(state, cols):
    """A compact's arguments: the state and a fold mask of its rows."""
    return (state, np.zeros(state["wv"].shape[0], bool))


def _result_state(carry, result):
    """The state of a (state, done) pair (`batch_tdigest.compact`)."""
    return result[0]


class WarmupFailed(Exception):
    """A program of the warm-up list raised: which one, for the
    `warmup_failed` event."""

    def __init__(self, family: str, program: str):
        super().__init__(f"warm-up of {family}.{program} failed")
        self.family = family
        self.program = program


class _BaseTable:
    """Row interning + touched tracking + capacity doubling, shared by all
    device families.

    Lock discipline (double-buffered hot path — the device-side analog of
    the reference's map-swap, worker.go:470-489):

      * ``lock`` (buffer lock) protects the pending sample columns, the
        row dictionary, meta, and touched masks. Reader threads hold it
        only for memcpy-scale work.
      * ``apply_lock`` protects the device-resident ``state``. It is
        always acquired while still holding ``lock`` (which fixes batch
        application order to buffer-swap order — load-bearing for gauge
        last-write-wins) but is held WITHOUT ``lock`` during the actual
        kernel dispatch, so readers filling the fresh buffer never block
        on a device call.
      * Order: ``lock`` then ``apply_lock``; never the reverse.

    Invariant: a row's touched flag may only be set in the same ``lock``
    hold that makes its value visible to a flush (appended to a pending
    buffer, or applied to state while ``apply_lock`` was acquired under
    ``lock``). Setting it earlier lets a concurrent snapshot clear the
    flag before the value exists (the value is later reset un-emitted);
    setting it later lets a snapshot emit a touched-but-valueless row.
    """

    # family label for self-telemetry rows and the cardinality
    # accountant's shed classes; overwritten per instance by ColumnStore
    family = "unknown"

    def __init__(self, capacity: int = 1024, batch_cap: int = 8192,
                 max_rows: int = 0):
        self.capacity = capacity
        self.batch_cap = batch_cap
        self.max_rows = max_rows  # hard cardinality cap (0 = unlimited)
        self.rows: Dict[int, int] = {}  # digest64 -> row
        self.meta: List[RowMeta] = []
        self.touched = np.zeros(capacity, bool)
        self.lock = threading.Lock()
        self.apply_lock = threading.Lock()
        # cardinality observatory (core/cardinality.py): duck-typed
        # accountant consulted on every mint (admit_mint/note_mint) and
        # fed evictions; None = unlimited, account nothing
        self.cardinality = None
        # flow ledger (core/ledger.py): every sample this table accepts
        # stamps agg.applied, every mint-gate rejection agg.rejected —
        # the out-side of the ingest conservation identity. The ledger
        # lock is a leaf, so stamping under this table's locks is safe.
        self.ledger = None
        # capacity/churn accounting, exported by ColumnStore.telemetry_rows
        # and /debug/cardinality: every counter below is monotonic and
        # mutated only under `lock` (resize/recompile under apply rules
        # documented at the mutation sites)
        self.minted_total = 0
        self.tombstoned_total = 0
        self.recycled_total = 0
        self.dispatch_total = 0
        # the batch apply's wall (jit dispatch; on a backend that runs
        # it inline, the kernel too) and the wait for apply_lock before
        # it: the flush's swap and readout dispatch hold that lock
        self.apply_seconds_total = 0.0
        self.apply_lock_wait_seconds_total = 0.0
        self.resize_total = 0
        self.resize_seconds_total = 0.0
        self.resize_last_seconds = 0.0
        self.recompile_seconds_total = 0.0
        self.recompile_last_seconds = 0.0
        self._recompile_pending = False
        # on_resize(family, old_capacity, new_capacity, seconds) — the
        # server's flight-recorder hook. Fired while holding the buffer
        # lock, so it must not emit statsd (an internal-loopback
        # self-metric would re-enter this very table's lock); recording
        # a telemetry event (its own lock only) is safe.
        self.on_resize = None
        # idle-row reclamation state (the TPU build's answer to the
        # reference's per-interval map swap, worker.go:470-489: row
        # IDENTITY persists here for fast-path reuse, so under key churn
        # it must be reclaimed or host memory grows without bound).
        # Rows are tombstoned (dict entry + native intern mapping
        # removed) once idle for N flushes, then recycled one further
        # flush later so in-flight native chunks can no longer reference
        # them.
        self._generation = 0
        self._last_touched = np.zeros(capacity, np.int64)
        self._tombstone_gen = np.full(capacity, -1, np.int64)
        self._has_meta = np.zeros(capacity, bool)
        self._dict_key_of: List[int] = []  # row -> rows-dict key
        self._free_rows: List[int] = []
        self.keys_dropped = 0
        # vectorized-flush row caches (core/flusher.py batch assembly):
        # per-row scope code for mask math, and per-row rendered flush
        # names / tag-list refs so steady keysets format strings once per
        # row lifetime, not once per flush. Entries are invalidated when
        # a recycled row is re-interned (row_for) — safe against in-flush
        # races because recycling a row emitted by flush N cannot happen
        # before flush N+1 (reclaim's two-phase contract above), and
        # flushes are serialized by the server's flush lock.
        self.scope_code = np.full(capacity, -1, np.int8)
        self._tags_cache = np.empty(capacity, object)
        self._flush_name_cache: Dict[object, np.ndarray] = {}
        # double-buffered flush: the recycled (already-zeroed) device
        # generation the next swap_out installs, and the capacity it was
        # shaped for (a resize in between invalidates it). Guarded by
        # apply_lock.
        self._spare = None
        self._spare_cap = -1
        # capacities whose kernels the shape-ladder prewarmer has
        # already compiled (core/flushexec.py): the post-resize
        # recompile probe reads this to tag the round prewarmed
        self._prewarmed_caps = set()
        # device observatory (core/deviceobs.py): duck-typed HBM-ledger
        # + kernel-registry sink, None = unregistered. The three token
        # slots track this table's generations through the double-buffer
        # lifecycle (live -> inflight -> spare -> live ...); all three
        # are guarded by apply_lock.
        self._deviceobs = None
        self._devobs_live = None
        self._devobs_spare = None
        self._devobs_inflight = None
        self._init_arrays()

    # subclasses define _init_arrays / _grow_arrays / _apply_cols / reset

    def _swap_locked(self):
        """Copy out and reset the pending columns (caller holds ``lock``).
        Returns the column copies, or None when nothing is pending. The
        whole buffer is copied; rows beyond the fill point are PAD_ROW and
        dropped by the scatter kernels."""
        if self._n == 0:
            return None
        cols = tuple(c.copy() for c in self._pcols)
        self._prow[: self._n] = PAD_ROW
        self._n = 0
        return cols

    def intern(self, metric: UDPMetric) -> int:
        """Intern a metric's row WITHOUT marking it touched — used by
        callers that batch values themselves (ordered gauge replay-merge
        in core.ingest). Touched must only be set once the value is in a
        pending buffer or the state, else a concurrent flush would emit a
        touched-but-valueless row (a fabricated 0.0)."""
        with self.lock:
            return self.row_for(metric)

    def _dispatch_pending_locked(self):
        """Swap the pending buffer out under ``lock`` and apply it to the
        device state with ``lock`` released (``apply_lock`` held). Caller
        holds ``lock`` on entry and on return."""
        cols = self._swap_locked()
        if cols is None:
            return
        t_wait = time.perf_counter()
        self.apply_lock.acquire()
        t0 = time.perf_counter()
        self.apply_lock_wait_seconds_total += t0 - t_wait
        self.lock.release()
        try:
            # first batch apply after a capacity doubling: the jit
            # kernels retrace+recompile for the new shape here. Time it
            # (block once — compile is the cost being measured) so the
            # TPU-specific resize tax is attributable.
            recompile = self._recompile_pending
            self._recompile_pending = False
            with annotate("apply." + self.family):
                self._apply_cols(cols)
                if recompile:
                    # sharded tables keep per-device state in `states`
                    dev_state = getattr(self, "state",
                                        getattr(self, "states", None))
                    if dev_state is not None:
                        try:
                            jax.block_until_ready(
                                jax.tree.leaves(dev_state))
                        except Exception:
                            logger.exception(
                                "post-resize recompile sync failed")
            elapsed = time.perf_counter() - t0
            self.apply_seconds_total += elapsed
            obs = self._deviceobs
            if obs is not None:
                obs.note_kernel("apply", self.family, elapsed)
            if recompile:
                self.recompile_last_seconds = elapsed
                self.recompile_seconds_total += elapsed
                if obs is not None:
                    obs.note_compile(self.family, elapsed)
                hook = self.on_resize
                if hook is not None:
                    try:
                        hook(self.family, self.capacity, self.capacity,
                             elapsed, kind="recompile",
                             prewarmed=self.capacity in self._prewarmed_caps)
                    except Exception:
                        logger.exception("resize hook failed")
            self.dispatch_total += 1
        finally:
            self.apply_lock.release()
            self.lock.acquire()

    # -- two-phase flush: generation swap / readout -----------------------
    #
    # The interval boundary is a pure generation swap, so ingest applies
    # never wait on apply_lock for the readout's dispatch window:
    #
    #   swap_out()   O(1) under the table locks: swap the pending
    #                columns out, capture touched/meta, capture the live
    #                device generation and install a fresh one (the
    #                recycled spare when capacity still matches). NO
    #                device dispatch — ingest continues into the fresh
    #                generation the moment the locks drop.
    #   readout()    lock-free on the CAPTURED generation (it is private
    #                to the snapshot): apply the final pending columns,
    #                dispatch the readout kernels. Runs on the flush
    #                thread while ingest applies to the fresh generation.
    #   snapshot_finish()  transfer + host assembly (unchanged).
    #   recycle()    after the transfer: donate the drained generation
    #                to the zeroing kernel and park it as the spare —
    #                the second buffer of the double-buffer.

    def swap_out(self, **kw) -> dict:
        """Critical-path flush half: swap this table's interval out with
        no device work. Extra kwargs ride into the snap (family readout
        parameters: ps, need_export, need_bins)."""
        snap = dict(kw)
        with self.lock:
            if self._idle_swap_locked(snap):
                return snap
            snap["cols"] = self._swap_locked()
            with self.apply_lock:
                self._note_generation_locked()
                snap["touched"] = self.touched.copy()
                snap["meta"] = list(self.meta)
                self.touched[:] = False
                self._swap_extras_locked(snap)
                snap["state"] = self._swap_device_locked()
                snap["cap"] = self._state_capacity()
                # flush-inflight ledger token rides the snap; recycle()
                # retags it spare or drops it when the generation dies
                snap["_devobs"] = self._devobs_inflight
                self._devobs_inflight = None
        return snap

    def _idle_swap_locked(self, snap: dict) -> bool:
        """Family-specific idle fast path (caller holds ``lock``):
        return True to skip the generation swap entirely (the llhist
        table skips its capacity-proportional readout when untouched)."""
        return False

    def _swap_extras_locked(self, snap: dict) -> None:
        """Capture family-specific host-side interval state into the
        snap and reset it (caller holds ``lock`` + ``apply_lock``)."""

    def _swap_device_locked(self):
        """Capture the live device generation and install a fresh one
        (caller holds ``apply_lock``). The recycled spare is used when
        its capacity still matches — a resize in between falls back to
        a fresh allocation."""
        captured = self.state
        spare, self._spare = self._spare, None
        used_spare = (spare is not None
                      and self._spare_cap == self._state_capacity())
        if used_spare:
            self.state = spare
        else:
            self.state = self._fresh_state()
        self._devobs_swap_locked(used_spare)
        return captured

    def _devobs_state(self):
        """The live device generation pytree for HBM-ledger
        registration. Sharded per-device tables keep it in `states`;
        the host-only status table has neither and registers nothing."""
        state = getattr(self, "state", None)
        if state is None:
            state = getattr(self, "states", None)
        return state

    def _devobs_swap_locked(self, used_spare: bool) -> None:
        """HBM-ledger bookkeeping for a generation swap (caller holds
        ``apply_lock``; the new live state is already bound): the old
        live token goes flush-inflight, and the spare token — when its
        generation was the one installed — becomes the new live token
        (conserving its bytes); otherwise the fresh allocation registers
        anew and any stale spare token (capacity mismatch dropped its
        generation) is unregistered."""
        obs = self._deviceobs
        if obs is None:
            return
        tok, self._devobs_live = self._devobs_live, None
        if tok is not None:
            obs.retag(tok, "inflight")
            self._devobs_inflight = tok
        spare_tok, self._devobs_spare = self._devobs_spare, None
        if used_spare and spare_tok is not None:
            obs.retag(spare_tok, "live")
            self._devobs_live = spare_tok
        else:
            obs.drop(spare_tok)
            self._devobs_live = obs.note_generation(
                self.family, "live", self._devobs_state())

    def _state_capacity(self) -> int:
        """Key-axis capacity the device state is shaped for (the set
        table's dense bank rides its own slot ladder)."""
        return self.capacity

    def _reset_state_donated(self, captured):
        """Donate the drained generation into a kernel that rewrites its
        buffers to the family's INIT values. Zeros for most families;
        the t-digest table overrides (its min/max fields initialize to
        ±inf, which zeros would corrupt into fabricated 0.0 extrema)."""
        return _zeros_like_spare(captured)

    def _fresh_state(self):
        return self._fresh_state_at(self._state_capacity())

    def _fresh_state_at(self, capacity: int):
        raise NotImplementedError

    def readout(self, snap: dict, timing=None) -> dict:
        """Background flush half: apply the snap's final pending columns
        to the captured generation and dispatch its readout kernels.
        Touches no live table state beyond monotonic telemetry counters,
        so it needs no locks and may run concurrently with ingest.
        `timing` is the flush round whose `dispatch{family}` span the
        call runs under, in the snap as `_timing` while the call lasts:
        a sharded table hangs its `merge` span there
        (core/sharded_tables.py), the set tables their `set_*` spans."""
        if "state" not in snap:
            return snap  # idle fast path: nothing was swapped
        if timing is not None:
            snap["_timing"] = timing
        state = snap.pop("state")
        cols = snap.pop("cols")
        if cols is not None:
            state = self._readout_apply(state, cols, snap)
        self._readout_device(state, snap)
        snap.pop("_timing", None)
        return snap

    def _readout_apply(self, state, cols, snap: dict):
        return self._apply_cols_state(state, cols)

    def _readout_device(self, state, snap: dict) -> None:
        raise NotImplementedError

    def _finish_and_recycle(self, snap: dict):
        """snapshot_finish + recycle in the order the donation protocol
        requires (transfer first, then donate the drained generation) —
        the one place the invariant lives for every snapshot_and_reset."""
        out = self.snapshot_finish(snap)
        self.recycle(snap)
        return out

    def recycle(self, snap: dict) -> None:
        """Donate the drained snapshot's device generation back as the
        next spare (call only after snapshot_finish — the zeroing kernel
        consumes the buffers the transfer just read). Sharded merges
        produce an already-zeroed generation (`_spare`) from their fused
        merge+reset kernel; everything else zero-donates the captured
        state (`_recycle`)."""
        cap = snap.pop("cap", -1)
        spare = snap.pop("_spare", None)
        captured = snap.pop("_recycle", None)
        tok = snap.pop("_devobs", None)
        obs = self._deviceobs
        if spare is None and captured is not None:
            t0 = time.perf_counter()
            try:
                spare = self._reset_state_donated(captured)
            except Exception:
                logger.exception("%s generation recycle failed",
                                 self.family)
                if obs is not None:
                    obs.drop(tok)
                return
            if obs is not None:
                obs.note_kernel("reset", self.family,
                                time.perf_counter() - t0)
        if spare is None:
            # generation not recyclable (sparse set readout consumed
            # it): its ledger token dies with it
            if obs is not None:
                obs.drop(tok)
            return
        with self.apply_lock:
            if cap == self._state_capacity() and self._spare is None:
                self._spare = spare
                self._spare_cap = cap
                if obs is not None:
                    obs.retag(tok, "spare")
                    self._devobs_spare = tok
                    tok = None
        # resized-under-flush or spare slot already occupied: the
        # zeroed generation is discarded, unregister its bytes
        if obs is not None and tok is not None:
            obs.drop(tok)

    # -- live-query capture: read-only snapshot between flushes ----------
    #
    # The query plane (core/query.py) reads the LIVE generation without
    # swapping it: no reset, no generation advance, no recycle. Safety
    # rests on two invariants the flush path already establishes:
    #
    #   * jax arrays are immutable — capturing `self.state` by reference
    #     under apply_lock yields a consistent point-in-time view even
    #     while ingest keeps rebinding the live attribute to new arrays;
    #   * every DONATING kernel on the readout path is either avoided
    #     (sharded tables override _query_readout_device with the
    #     non-reset collective merges) or fed a private copy (the sparse
    #     set table's hot-COO fold).
    #
    # Pending columns fold into the live state first through the normal
    # dispatch path (donation-safe: the donated input is the OLD live
    # buffer, replaced by the kernel's output), so absent further ingest
    # the captured generation is exactly what the next swap_out would
    # capture — the bit-identity the consistency pin asserts. Under
    # sustained ingest the fold retries a bounded number of rounds;
    # anything still pending after that is the query's (bounded)
    # staleness, one batch_cap of samples at most per round lost.

    _CAPTURE_FOLD_ROUNDS = 8

    def capture_readonly(self, **kw) -> dict:
        """Read-only counterpart of swap_out: capture the live device
        generation plus touched/meta/extras WITHOUT swapping or
        resetting anything, and dispatch the readout kernels over it.
        Extra kwargs ride into the snap exactly as for swap_out (ps,
        need_export, need_bins).

        The readout DISPATCH happens here, under apply_lock, and that
        placement is load-bearing: the next pending apply DONATES the
        live buffers, deleting the captured references — a dispatch
        after the lock releases would race that deletion. Dispatch is
        asynchronous (no device sync under the lock); its result
        buffers are fresh, so later donation cannot touch them. The
        sync itself happens in query_readout(), off the table locks."""
        snap = dict(kw)
        with self.lock:
            if self._idle_capture_locked(snap):
                return snap
            for _ in range(self._CAPTURE_FOLD_ROUNDS):
                if self._n == 0:
                    break
                self._dispatch_pending_locked()  # may release/reacquire
            # residual pending samples after the bounded fold ARE the
            # query's staleness — surfaced to the caller, never lost
            # (they fold into the live state on the next dispatch)
            snap["stale_pending"] = self._n
            with self.apply_lock:
                snap["touched"] = self.touched.copy()
                snap["meta"] = list(self.meta)
                self._capture_extras_locked(snap)
                self._query_readout_device(
                    self._capture_device_locked(), snap)
                # the snap must NEVER reach recycle(): the state it read
                # IS the live generation
                for key in ("_recycle", "_spare", "cap"):
                    snap.pop(key, None)
        return snap

    def _idle_capture_locked(self, snap: dict) -> bool:
        """Family-specific idle fast path for queries (caller holds
        ``lock``): mirrors _idle_swap_locked but advances nothing."""
        return False

    def _capture_extras_locked(self, snap: dict) -> None:
        """Read-only counterpart of _swap_extras_locked: COPY family
        host-side interval state into the snap without resetting it
        (caller holds ``lock`` + ``apply_lock``)."""

    def _capture_device_locked(self):
        """Reference to the live device generation (caller holds
        ``apply_lock``). A reference, not a copy: the arrays are
        immutable, and later applies rebind the live attribute without
        touching the captured value."""
        return self.state

    def query_readout(self, snap: dict) -> dict:
        """The device-sync half of a query: wait for the result buffers
        capture_readonly dispatched. Runs lock-free on the server's
        supervised flush executor, so query syncs serialize with the
        in-flight flush readout instead of colliding with it."""
        import jax
        jax.block_until_ready(
            {k: v for k, v in snap.items() if k != "meta"})
        return snap

    def _query_readout_device(self, state, snap: dict) -> None:
        """Family hook for the query readout. The default is safe only
        when the flush readout stores nothing but fresh kernel outputs
        into the snap (histogram/llhist). Families whose flush readout
        captures the state by reference (counter/gauge transfer rows),
        donates it (the sharded fused merge+reset kernels), or writes
        into it (the sparse set fold) override this — a query reads the
        LIVE generation, which stays exposed to later donating applies."""
        self._readout_device(state, snap)

    # -- shape-ladder prewarm --------------------------------------------

    def prewarm_rung(self, capacity: int, percentiles=(),
                     need_export: bool = True, report=None) -> bool:
        """Compile every program of this family's warm-up list
        (`warm_programs`) for `capacity` rows against a throwaway state:
        the server's start-up warm-up at the configured capacity
        (`Server._warmup`) and the shape ladder's next rung
        (core/flushexec.py) are this one call. Runs on a background
        thread and never touches live state or locks. The jit caches —
        and the persistent compilation cache — are process-global, so
        the first live dispatch at this capacity finds them warm instead
        of compiling under `apply_lock` or the flush lock. `report`,
        when given, gets `(family, program, seconds, cache_hits,
        cache_misses)` after each program: what the persistent cache
        served and what it had to compile (both 0 = already in this
        process's jit cache, or the cache is off). Returns True when the
        rung was compiled; a program that raises ends the rung with
        `WarmupFailed`."""
        programs = self.warm_programs(tuple(percentiles), need_export)
        if not programs:
            return False
        cols = self._prewarm_cols()
        obs = self._deviceobs
        t_rung = time.perf_counter()
        state = self._warm_state(capacity)
        # the throwaway rung state is real HBM while the compile runs;
        # ledger it as a transient `prewarm` generation
        tok = obs.note_generation(self.family, "prewarm", state) \
            if obs is not None else None
        try:
            for wp in programs:
                t0 = time.perf_counter()
                hits, misses = compilecache.cache_events()
                try:
                    with annotate(f"warmup.{self.family}.{wp.program}"):
                        out = wp.fn(*wp.args(state, cols), *wp.static)
                        jax.block_until_ready(
                            [leaf for leaf in jax.tree.leaves(out)
                             if leaf is not None])
                except Exception as e:
                    raise WarmupFailed(self.family, wp.program) from e
                if wp.then is not None:
                    state = wp.then(state, out)
                seconds = time.perf_counter() - t0
                if obs is not None:
                    obs.note_compile(self.family, seconds)
                if report is not None:
                    now = compilecache.cache_events()
                    report(self.family, wp.program, seconds,
                           now[0] - hits, now[1] - misses)
        finally:
            if obs is not None:
                obs.drop(tok)
        self._prewarmed_caps.add(capacity)
        if obs is not None:
            obs.note_kernel("prewarm", self.family,
                            time.perf_counter() - t_rung)
        return True

    def warm_programs(self, ps: tuple, need_export: bool
                      ) -> List[WarmProgram]:
        """The device programs this family's configured capacity
        implies, in the order the live path meets them: the batch
        apply, the readout with the live flush's percentiles and
        `need_export`, the zeroing of the spare generation (the digest
        family adds `compact`). The ONE list: the warm-up runs it, and
        tests/test_chip_compile.py compiles the one-device entries for
        a described v5e. Empty = a host-only family."""
        return []

    def _warm_state(self, capacity: int):
        """The throwaway generation a rung's programs run on."""
        return self._fresh_state_at(capacity)

    def _prewarm_cols(self):
        """An all-padding pending batch with the live buffer dtypes
        (None = family has no batch apply to prewarm)."""
        pcols = getattr(self, "_pcols", None)
        if not pcols:
            return None
        return (np.full(self.batch_cap, PAD_ROW, np.int32),) + tuple(
            np.zeros(self.batch_cap, c.dtype) for c in pcols[1:])

    def _apply_cols_state(self, state, cols):
        """Pure batch apply: fold one swapped pending-column batch into
        `state` and return it. The live path (`_apply_cols`) targets
        self.state; the flush readout targets the captured generation."""
        raise NotImplementedError

    def _apply_cols(self, cols):
        self.state = self._apply_cols_state(self.state, cols)

    def row_for(self, metric: UDPMetric) -> int:
        # scope is part of row identity: the reference keeps separate maps
        # per scope variant (worker.go:59-102), so one MetricKey may hold
        # state in two scopes at once
        dict_key = (metric.digest64 << 2) | int(metric.scope)
        row = self.rows.get(dict_key)
        if row is None:
            # cardinality watermark rung: a NEW key consults the
            # accountant's per-name mint budget before any allocation.
            # Existing rows never come through here, so a storm can only
            # starve its own new keys — pre-existing series keep
            # updating. The accountant counts every rejection
            # (ingest.shed_total reason:cardinality*).
            card = self.cardinality
            if card is not None and not card.admit_mint(
                    self.family, metric.key.name, metric.tags):
                if self.ledger is not None:
                    self.ledger.note("agg.rejected", 1, key=self.family)
                return -1
            meta = RowMeta(
                name=metric.key.name, tags=list(metric.tags),
                joined_tags=metric.key.joined_tags, digest32=metric.digest,
                scope=metric.scope, wire_type=metric.key.type)
            if self._free_rows:
                row = self._free_rows.pop()
                self.meta[row] = meta
                self._dict_key_of[row] = dict_key
                self._last_touched[row] = self._generation
                self._has_meta[row] = True
                # recycled row: drop the previous occupant's cached
                # flush names/tags before the new key's first flush
                self._tags_cache[row] = None
                for arr in self._flush_name_cache.values():
                    arr[row] = None
            elif self.max_rows and len(self.rows) >= self.max_rows:
                # hard cardinality cap: protects host memory during a
                # within-interval key flood; the sample is dropped and
                # counted (keys_dropped self-metric)
                self.keys_dropped += 1
                if self.ledger is not None:
                    self.ledger.note("agg.rejected", 1, key=self.family)
                return -1
            else:
                row = len(self.meta)
                if row >= self.capacity:
                    self._grow()
                self.meta.append(meta)
                self._dict_key_of.append(dict_key)
                self._has_meta[row] = True
                # stamp creation as activity: without this a row interned
                # (but not yet touched) late in life would read as idle
                # since generation 0 and tombstone on its first flush
                self._last_touched[row] = self._generation
            self.scope_code[row] = int(metric.scope)
            self._note_minted(row, metric)
            self.rows[dict_key] = row
            self.minted_total += 1
            if card is not None:
                card.note_mint(self.family, metric.key.name)
        return row

    def _note_minted(self, row: int, metric: UDPMetric) -> None:
        """Mint hook, fired once per fresh/recycled row assignment under
        the buffer lock. The sharded tables (core/sharded_tables.py)
        record the row's digest-derived home shard here; the base table
        does nothing."""

    def _note_applied(self, n: int) -> None:
        """Stamp n samples accepted into this family (flow ledger)."""
        led = self.ledger
        if led is not None and n:
            led.note("agg.applied", n, key=self.family)

    def _note_generation_locked(self) -> None:
        """Advance the flush generation and stamp rows touched this
        interval (caller holds ``lock``, before clearing ``touched``)."""
        self._generation += 1
        self._last_touched[self.touched] = self._generation

    def reclaim_idle(self, idle_intervals: int):
        """Two-phase idle-row reclamation, run after each flush.

        Phase 1 (tombstone): rows idle for >= idle_intervals flushes
        lose their rows-dict entry now; the caller must also erase their
        native intern mappings (the returned rows) so no NEW native
        samples can reference them.

        Phase 2 (recycle): rows tombstoned at least one flush ago and
        untouched since go to the free list. A tombstoned row that was
        touched in the gap (an in-flight chunk straggler, emitted
        normally) has its tombstone re-stamped and waits another flush.

        Returns the list of rows tombstoned in this call."""
        if idle_intervals <= 0:
            return []
        evicted_names: List[str] = []
        with self.lock:
            gen = self._generation
            n = len(self.meta)
            if n == 0:
                return []
            last = self._last_touched[:n]
            tomb = self._tombstone_gen[:n]
            # phase 2. A currently-set touched flag counts as activity
            # even though _last_touched is only stamped at snapshot time:
            # a straggler chunk landing between snapshot_and_reset and
            # this call has touched[row]=True and its value in the NEW
            # pending buffer — recycling now would orphan that value (or
            # credit it to whatever key re-interns the row).
            rearm = (tomb >= 0) & ((last > tomb) | self.touched[:n])
            if rearm.any():
                tomb[rearm] = gen
            recycle = (tomb >= 0) & (gen > tomb) & (last <= tomb)
            for row in np.nonzero(recycle)[0]:
                row = int(row)
                tomb[row] = -1
                self.meta[row] = None
                self._has_meta[row] = False
                self._free_rows.append(row)
                self.recycled_total += 1
            # phase 1
            cand = ((tomb < 0) & (gen - last >= idle_intervals)
                    & self._has_meta[:n])
            evicted = [int(r) for r in np.nonzero(cand)[0]]
            for row in evicted:
                self.rows.pop(self._dict_key_of[row], None)
                tomb[row] = gen
                meta = self.meta[row]
                if meta is not None:
                    evicted_names.append(meta.name)
            self.tombstoned_total += len(evicted)
        # live-row accounting outside the buffer lock: the eviction list
        # can be large under churn, and the accountant only needs names
        if evicted_names and self.cardinality is not None:
            self.cardinality.note_evicted(self.family, evicted_names)
        return evicted

    def flush_names(self, key, rows: np.ndarray, meta_list,
                    render) -> np.ndarray:
        """Rendered flush-name object array for `rows` (row ids), cached
        for the row's lifetime under `key` (a suffix string or percentile).
        Misses render via `render(meta)` against the caller's SNAPSHOT
        meta list, so a concurrent re-intern can never leak another key's
        name into this flush.

        Cache-dict mutation (new key, grow-replacement) happens under the
        buffer lock: row_for iterates .values() to invalidate recycled
        rows and _grow re-lays-out every entry, both under that lock.
        Element fills stay lock-free — a fill can only target a row that
        is live in this snapshot, which the two-phase reclaim contract
        keeps un-recyclable until the next flush, so the worst concurrent
        outcome is a fill landing in an orphaned (pre-grow) array: a lost
        cache entry, re-rendered next flush."""
        with self.lock:
            arr = self._flush_name_cache.get(key)
            if arr is None:
                arr = self._flush_name_cache[key] = np.empty(
                    max(self.capacity, len(self.meta)), object)
            elif arr.shape[0] < len(self.meta):
                grown = np.empty(self.capacity, object)
                grown[: arr.shape[0]] = arr
                arr = self._flush_name_cache[key] = grown
        sel = arr[rows]
        miss = np.flatnonzero(np.equal(sel, None))
        for j in miss.tolist():
            row = int(rows[j])
            sel[j] = arr[row] = render(meta_list[row])
        return sel

    def flush_tags(self, rows: np.ndarray, meta_list) -> np.ndarray:
        """Per-row tag-list refs for `rows`, cached like flush_names.
        Consumers must copy before mutating (InterMetric materialization
        does)."""
        with self.lock:  # a concurrent _grow replaces the array
            arr = self._tags_cache
        sel = arr[rows]
        miss = np.flatnonzero(np.equal(sel, None))
        for j in miss.tolist():
            row = int(rows[j])
            sel[j] = arr[row] = meta_list[row].tags
        return sel

    def _grow(self):
        t0 = time.perf_counter()
        new_cap = self.capacity * 2
        pad = new_cap - self.capacity
        self.touched = np.concatenate(
            [self.touched, np.zeros(pad, bool)])
        self._last_touched = np.concatenate(
            [self._last_touched, np.zeros(pad, np.int64)])
        self._tombstone_gen = np.concatenate(
            [self._tombstone_gen, np.full(pad, -1, np.int64)])
        self._has_meta = np.concatenate(
            [self._has_meta, np.zeros(pad, bool)])
        self.scope_code = np.concatenate(
            [self.scope_code, np.full(pad, -1, np.int8)])
        self._tags_cache = np.concatenate(
            [self._tags_cache, np.empty(pad, object)])
        for key, arr in self._flush_name_cache.items():
            self._flush_name_cache[key] = np.concatenate(
                [arr, np.empty(pad, object)])
        # _grow_arrays re-lays-out the device state, so it needs the state
        # lock; caller already holds the buffer lock (correct lock order)
        with self.apply_lock:
            # the recycled spare generation is shaped for the OLD
            # capacity; drop it rather than let a stale swap install it
            self._spare = None
            self._spare_cap = -1
            obs = self._deviceobs
            if obs is not None:
                obs.drop(self._devobs_spare)
                self._devobs_spare = None
            self._grow_arrays(new_cap)
            # the live generation was re-laid-out at the new capacity:
            # re-register its (doubled) footprint
            if obs is not None:
                obs.drop(self._devobs_live)
                self._devobs_live = obs.note_generation(
                    self.family, "live", self._devobs_state())
                obs.note_resize()
        old_cap, self.capacity = self.capacity, new_cap
        # capacity doublings are permanent HBM growth AND a pending jit
        # recompile (every kernel specializes on capacity; the retrace
        # lands on the next batch apply, timed in _dispatch_pending_locked)
        elapsed = time.perf_counter() - t0
        self.resize_total += 1
        self.resize_last_seconds = elapsed
        self.resize_seconds_total += elapsed
        self._recompile_pending = True
        logger.info("%s table capacity %d -> %d (%.3fs relayout)",
                    self.family, old_cap, new_cap, elapsed)
        hook = self.on_resize
        if hook is not None:
            try:
                hook(self.family, old_cap, new_cap, elapsed, kind="resize")
            except Exception:
                logger.exception("resize hook failed")

    def _append_batch(self, columns, touch_rows=None) -> None:
        """Vectorized append of parallel sample columns into the typed
        pending buffers (the native-parser fast path), dispatching whenever
        full. Caller holds self.lock; rows must already be interned.

        Touched flags are set PER CHUNK, in the same lock hold that puts
        the chunk into the pending buffer. Marking all rows up front
        would race the dispatch below: it releases the lock while
        applying a full buffer, and a concurrent snapshot then clears
        the flags of samples not yet buffered — their values later land
        in the next interval's state untouched and are reset without
        ever being emitted (observed as lost samples under the
        concurrency stress suite). touch_rows defaults to the row
        column; tables whose buffers carry device slots (the set table)
        pass the table rows explicitly."""
        if touch_rows is None:
            touch_rows = columns[0]
        n = len(columns[0])
        i = 0
        while i < n:
            take = min(self.batch_cap - self._n, n - i)
            for buf, data in zip(self._pcols, columns):
                buf[self._n:self._n + take] = data[i:i + take]
            self.touched[touch_rows[i:i + take]] = True
            self._n += take
            i += take
            if self._n >= self.batch_cap:
                self._dispatch_pending_locked()

    @property
    def num_rows(self) -> int:
        return len(self.meta)


def _pad_cap(state_leaf, new_cap):
    pad = new_cap - state_leaf.shape[0]
    widths = [(0, pad)] + [(0, 0)] * (state_leaf.ndim - 1)
    return jnp.pad(state_leaf, widths)


class CounterTable(_BaseTable):
    def _init_arrays(self):
        self.state = scalars.init_counters(self.capacity)
        self._prow = np.full(self.batch_cap, PAD_ROW, np.int32)
        self._pval = np.zeros(self.batch_cap, np.float32)
        self._prate = np.ones(self.batch_cap, np.float32)
        self._pcols = (self._prow, self._pval, self._prate)
        self._n = 0
        self._import_acc = np.zeros(self.capacity, np.float64)

    def _grow_arrays(self, new_cap):
        self.state = jax.tree.map(lambda a: _pad_cap(a, new_cap), self.state)

    def add(self, metric: UDPMetric):
        with self.lock:
            row = self.row_for(metric)
            if row < 0:
                return
            self.touched[row] = True
            self._note_applied(1)
            n = self._n
            self._prow[n] = row
            self._pval[n] = metric.value
            self._prate[n] = max(metric.sample_rate, 1e-9)
            self._n = n + 1
            if self._n >= self.batch_cap:
                self._dispatch_pending_locked()

    def _apply_cols_state(self, state, cols):
        # cols are copies: execution is async and jax may alias numpy
        # buffers zero-copy, while the live buffers are refilled immediately
        rows, vals, rates = cols
        return scalars.apply_counters(state, rows, vals, rates)

    def _fresh_state_at(self, capacity: int):
        return scalars.init_counters(capacity)

    def warm_programs(self, ps, need_export):
        # the readout is a pure transfer of the Kahan pair: no program
        return [WarmProgram("apply", scalars.apply_counters,
                            _state_and_cols, then=_result),
                WarmProgram("reset", _zeros_like_donated, _state_only,
                            then=_result)]

    def apply_pending(self):
        with self.lock:
            self._dispatch_pending_locked()

    def add_batch(self, rows, vals, rates) -> None:
        """Native-parser fast path: pre-interned rows, parallel columns."""
        with self.lock:
            self._note_applied(len(rows))
            self._append_batch((rows, vals, rates))

    def merge_batch(self, stubs: List[UDPMetric], values) -> None:
        """Import-path merge: intern + touch + accumulate atomically, so a
        concurrent flush never sees touched-but-valueless rows. Values
        accumulate host-side in f64 because forwarded counters are exact
        int64 sums that f32 would quantize."""
        with self.lock:
            rows = []
            vals = []
            for stub, value in zip(stubs, values):
                row = self.row_for(stub)
                if row < 0:  # cardinality cap
                    continue
                self.touched[row] = True
                rows.append(row)
                vals.append(value)
            self._note_applied(len(rows))
            if self._import_acc.shape[0] < self.capacity:
                grown = np.zeros(self.capacity, np.float64)
                grown[: self._import_acc.shape[0]] = self._import_acc
                self._import_acc = grown
            np.add.at(self._import_acc, rows, np.asarray(vals, np.float64))

    def _swap_extras_locked(self, snap: dict) -> None:
        snap["import_acc"] = self._import_acc
        self._import_acc = np.zeros(self.capacity, np.float64)

    def _capture_extras_locked(self, snap: dict) -> None:
        # copy, not reference: merge_batch mutates the accumulator in
        # place (np.add.at), so a live reference could tear mid-read
        snap["import_acc"] = self._import_acc.copy()

    def _readout_device(self, state, snap: dict) -> None:
        """Counter readout is a pure transfer of the Kahan pair; the
        sharded table overrides this with the collective merge. The
        captured generation is recycled after the transfer."""
        snap["dev"] = (state["sum"], state["comp"])
        snap["_recycle"] = state

    def _query_readout_device(self, state, snap: dict) -> None:
        # the flush readout stores the Kahan pair BY REFERENCE — safe
        # there because the swapped-out generation is exclusive. A query
        # reads the LIVE pair, which the next pending apply DONATES, so
        # snapshot fresh buffers with an async copy kernel instead.
        snap["dev"] = (jnp.copy(state["sum"]), jnp.copy(state["comp"]))

    def snapshot_begin(self) -> dict:
        """Dispatch half of snapshot_and_reset: swap + readout, but do
        NOT transfer. The flusher begins every table first, then pays
        the device sync once for all of them (over a remote device link
        the per-table sync was a serialized round-trip each)."""
        return self.readout(self.swap_out())

    @staticmethod
    def snapshot_finish(snap: dict
                        ) -> Tuple[np.ndarray, np.ndarray, List[RowMeta]]:
        # f64 readout recovers the exact total from the Kahan pair
        values = (np.asarray(snap["dev"][0], np.float64)
                  - np.asarray(snap["dev"][1], np.float64))
        import_acc = snap["import_acc"]
        values[: import_acc.shape[0]] += import_acc
        return values, snap["touched"], snap["meta"]

    def snapshot_and_reset(self) -> Tuple[np.ndarray, np.ndarray, List[RowMeta]]:
        return self._finish_and_recycle(self.snapshot_begin())


class GaugeTable(_BaseTable):
    def _init_arrays(self):
        self.state = scalars.init_gauges(self.capacity)
        self._prow = np.full(self.batch_cap, PAD_ROW, np.int32)
        self._pval = np.zeros(self.batch_cap, np.float32)
        self._pcols = (self._prow, self._pval)
        self._n = 0

    def _grow_arrays(self, new_cap):
        self.state = jax.tree.map(lambda a: _pad_cap(a, new_cap), self.state)

    def add(self, metric: UDPMetric):
        with self.lock:
            row = self.row_for(metric)
            if row < 0:
                return
            self.touched[row] = True
            self._note_applied(1)
            n = self._n
            self._prow[n] = row
            self._pval[n] = metric.value
            self._n = n + 1
            if self._n >= self.batch_cap:
                self._dispatch_pending_locked()

    def _apply_cols_state(self, state, cols):
        rows, vals = cols
        return scalars.apply_gauges(state, rows, vals)

    def _fresh_state_at(self, capacity: int):
        return scalars.init_gauges(capacity)

    def warm_programs(self, ps, need_export):
        return [WarmProgram("apply", scalars.apply_gauges,
                            _state_and_cols, then=_result),
                WarmProgram("reset", _zeros_like_donated, _state_only,
                            then=_result)]

    def apply_pending(self):
        with self.lock:
            self._dispatch_pending_locked()

    def add_batch(self, rows, vals) -> None:
        """Native-parser fast path; buffer order preserves last-write-wins."""
        with self.lock:
            self._note_applied(len(rows))
            self._append_batch((rows, vals))

    def merge_batch(self, stubs: List[UDPMetric], values) -> None:
        """Import-path merge: overwrite. Interning is atomic under the
        buffer lock; the state update rides the apply ticket so it orders
        after any already-swapped local batches."""
        with self.lock:
            rows = np.fromiter(
                (self.row_for(s) for s in stubs), np.int32, len(stubs))
            ok = rows >= 0  # cardinality-capped stubs drop out
            rows = rows[ok]
            self.touched[rows] = True
            self._note_applied(int(rows.size))
            self.apply_lock.acquire()
        try:
            self.state = scalars.merge_gauges(
                self.state, rows, np.asarray(values, np.float32)[ok])
        finally:
            self.apply_lock.release()

    def _readout_device(self, state, snap: dict) -> None:
        """Gauge readout is a pure transfer of the LWW values; the
        sharded table overrides this with the collective merge."""
        snap["dev"] = state["value"]
        snap["_recycle"] = state

    def _query_readout_device(self, state, snap: dict) -> None:
        # see CounterTable: the live LWW column gets donated by the
        # next pending apply — a query must capture a fresh copy
        snap["dev"] = jnp.copy(state["value"])

    def snapshot_begin(self) -> dict:
        """Dispatch-only snapshot half; see CounterTable.snapshot_begin."""
        return self.readout(self.swap_out())

    @staticmethod
    def snapshot_finish(snap: dict):
        return np.asarray(snap["dev"]), snap["touched"], snap["meta"]

    def snapshot_and_reset(self):
        return self._finish_and_recycle(self.snapshot_begin())


class HistoTable(_BaseTable):
    """Histograms and timers, all scopes, one digest grid.

    Batches rank-park raw samples into the digest staging grid (O(batch)
    per apply, exact); the host tracks each key's staged count and runs
    the mean-sorted `compact` — the only capacity-proportional pass —
    over the rows that would overflow their C staging slots (and those
    half full), before the batch that would. This mirrors the reference's amortized temp-buffer
    merge (merging_digest.go:115-140): a sparse key stages until its own
    slots fill, however often a hot key compacts, and a dense key
    compacts about once per batch."""

    def _init_pending(self):
        self._prow = np.full(self.batch_cap, PAD_ROW, np.int32)
        self._pval = np.zeros(self.batch_cap, np.float32)
        self._pwt = np.zeros(self.batch_cap, np.float32)
        self._pcols = (self._prow, self._pval, self._pwt)
        self._n = 0
        self._applies = 0
        # exact per-key staging-slot occupancy since the last compact
        self._staged_counts = np.zeros(self.capacity, np.int32)
        # whole-table compacts a key past its C staging slots forced,
        # and their host wall, by the path that ran them: `live` under
        # apply_lock on the dispatcher's thread, `readout` on the flush
        # thread over the captured generation's last pending batch;
        # the keys dense within one batch (> C samples), which
        # `host_slots` folded into batch-local k-buckets; and the
        # compacts' device seconds from completion stamps, booked by
        # the device observatory's watcher thread (absent without one)
        self.compacts_total = {"live": 0, "readout": 0}
        self.compact_seconds_total = {"live": 0.0, "readout": 0.0}
        self.dense_keys_total = {"live": 0, "readout": 0}
        self.compact_chip_seconds_total = {"live": 0.0, "readout": 0.0}

    def _init_arrays(self):
        self._init_pending()
        self.state = batch_tdigest.init_state(self.capacity)

    def _grow_arrays(self, new_cap):
        old = self.state
        new = batch_tdigest.init_state(new_cap)
        grown = {}
        for k in new:
            grown[k] = jax.lax.dynamic_update_slice(
                new[k], old[k], (0,) * new[k].ndim)
        self.state = grown
        extended = np.zeros(new_cap, np.int32)
        extended[: self._staged_counts.shape[0]] = self._staged_counts
        self._staged_counts = extended

    def add(self, metric: UDPMetric):
        with self.lock:
            row = self.row_for(metric)
            if row < 0:
                return
            self.touched[row] = True
            self._note_applied(1)
            n = self._n
            self._prow[n] = row
            self._pval[n] = metric.value
            self._pwt[n] = 1.0 / max(metric.sample_rate, 1e-9)
            self._n = n + 1
            if self._n >= self.batch_cap:
                self._dispatch_pending_locked()

    def _apply_cols(self, cols):
        self.state = self._apply_cols_state(self.state, cols,
                                            self._staged_counts)
        self._applies += 1

    def _compact(self, state, fold, path: str):
        """One compact of the rows `fold` marks (a program over the
        whole table, or shard), counted and timed: the wall is the
        dispatch's; the kernel runs behind it. With a device observatory
        its `done` handle goes to the completion watcher, which books
        the device seconds to `compact_chip_seconds_total[path]` off
        this thread."""
        t0 = time.perf_counter()
        state, done = batch_tdigest.compact(state, fold)
        self.compacts_total[path] += 1
        self.compact_seconds_total[path] += time.perf_counter() - t0
        obs = self._deviceobs
        if obs is not None and obs.enabled:
            obs.readout_watcher().watch_compact(
                done, t0, partial(self._book_compact_chip, path))
        return state

    def _book_compact_chip(self, path: str, seconds: float) -> None:
        # the watcher thread is the only writer
        self.compact_chip_seconds_total[path] += seconds

    def _slots(self, state, cols, staged_counts, path: str):
        """`host_slots` for one batch, compacting first the rows whose
        staging would overflow; counts the keys it folded dense. The
        compact costs the same whatever it folds, so the rows at least
        half full (`batch_tdigest.FOLD_ALONG`) fold with them: they
        would force compacts of their own soon after."""
        rows, vals, wts = cols
        slots, overflow, dense = batch_tdigest.host_slots(
            rows, vals, wts, staged_counts)
        if overflow is not None:
            fold = overflow | (staged_counts >= batch_tdigest.FOLD_ALONG)
            state = self._compact(state, fold, path)
            staged_counts[fold] = 0
            slots, _, dense = batch_tdigest.host_slots(
                rows, vals, wts, staged_counts)
        self.dense_keys_total[path] += dense
        return state, slots

    def _apply_cols_state(self, state, cols, staged_counts,
                          path: str = "live"):
        """Pure batch apply over an explicit (state, staging-occupancy)
        pair: the live path passes the table's own, the flush readout
        passes the captured generation's."""
        state, slots = self._slots(state, cols, staged_counts, path)
        return batch_tdigest.apply_batch(state, *cols, slots)

    def _fresh_state_at(self, capacity: int):
        return batch_tdigest.init_state(capacity)

    def warm_programs(self, ps, need_export):
        """`compact` is on the list by name: no padding batch overflows
        a staging slot, so no faked apply would ever reach it, and cold
        at 131,072 rows it compiles for longer than the flush watchdog
        allows while a tick waits for `apply_lock`."""
        readout = (batch_tdigest.flush_export_packed if need_export
                   else batch_tdigest.flush_quantiles_packed)
        return [
            # rows, values, weights and the host-computed staging slots
            WarmProgram("apply", batch_tdigest._apply_batch_jit,
                        lambda state, cols: (
                            state, *cols,
                            np.zeros(cols[0].shape[0], np.int32)),
                        then=_result),
            WarmProgram("compact", batch_tdigest.compact, _state_and_fold,
                        then=_result_state),
            WarmProgram("readout", readout, _state_only, static=(ps,)),
            WarmProgram("reset", _reset_tdigest_donated, _state_only,
                        then=_result)]

    def apply_pending(self):
        with self.lock:
            self._dispatch_pending_locked()

    def add_batch(self, rows, vals, weights) -> None:
        """Native-parser fast path: weights are 1/sample_rate."""
        with self.lock:
            self._note_applied(len(rows))
            self._append_batch((rows, vals, weights))

    def merge_batch(self, stubs: List[UDPMetric], in_means, in_weights,
                    in_min, in_max, in_recip) -> None:
        """Import-path digest merge; interning atomic under the buffer
        lock, state update ordered via the apply ticket."""
        with self.lock:
            rows = np.fromiter(
                (self.row_for(s) for s in stubs), np.int32, len(stubs))
            ok = rows >= 0  # cardinality-capped stubs drop out
            rows = rows[ok]
            self.touched[rows] = True
            self._note_applied(int(rows.size))
            self.apply_lock.acquire()
        try:
            self.state = batch_tdigest.merge_centroid_rows(
                self.state, rows,
                np.asarray(in_means, np.float32)[ok],
                np.asarray(in_weights, np.float32)[ok],
                np.asarray(in_min, np.float32)[ok],
                np.asarray(in_max, np.float32)[ok],
                np.asarray(in_recip, np.float32)[ok])
            # the merge folds staging for every row with staged weight
            # (merge_centroid_rows touches staged rows too), so the whole
            # occupancy map resets
            self._staged_counts[:] = 0
        finally:
            self.apply_lock.release()

    def snapshot_and_reset(self, percentiles: Tuple[float, ...],
                           need_export: bool = True):
        """Returns (flush outputs dict of np arrays, centroid export,
        touched, meta).

        need_export=False (a global server: nothing downstream consumes
        the serialized digests) skips the centroid export entirely — the
        (K, C) weight/mean tables never cross the device link and the
        pre-export compact is elided (flush_quantiles folds staging
        itself); the flush then transfers a single packed (K, P+10)
        array instead of ~50 MB of centroids at K=100k."""
        return self._finish_and_recycle(
            self.snapshot_begin(percentiles, need_export))

    def _swap_extras_locked(self, snap: dict) -> None:
        snap["staged"] = self._staged_counts
        self._staged_counts = np.zeros(self.capacity, np.int32)
        self._applies = 0

    def _readout_apply(self, state, cols, snap: dict):
        return self._apply_cols_state(state, cols, snap.pop("staged"),
                                      path="readout")

    def _readout_device(self, state, snap: dict) -> None:
        ps = snap["ps"]
        if snap.pop("need_export"):
            # fused forwarding flush: one dispatch, one sort, and
            # two device->host transfers (the packed flush and the
            # packed export) instead of compact+flush+export
            packed, export_packed = batch_tdigest.flush_export_packed(
                state, ps)
        else:
            packed = batch_tdigest.flush_quantiles_packed(state, ps)
            export_packed = None
        snap["packed"] = packed
        snap["export_packed"] = export_packed
        snap["_recycle"] = state

    def _reset_state_donated(self, captured):
        return _reset_tdigest_spare(captured)

    def snapshot_begin(self, percentiles: Tuple[float, ...],
                       need_export: bool = True) -> dict:
        """Dispatch-only snapshot half; see CounterTable.snapshot_begin."""
        return self.readout(self.swap_out(
            ps=tuple(percentiles), need_export=need_export))

    @staticmethod
    def snapshot_finish(snap: dict):
        out = batch_tdigest.unpack_flush(snap["packed"], len(snap["ps"]))
        export = (batch_tdigest.unpack_export(snap["export_packed"])
                  if snap["export_packed"] is not None else None)
        return out, export, snap["touched"], snap["meta"]


class _SetRegisters:
    """Lazy per-row dense register view over the hybrid set state:
    promoted rows slice the (D, M) device readout; sparse rows
    materialize 16 KB only when a caller (the forward exporter) actually
    asks — the point of the sparse representation is that most rows
    never do both."""

    def __init__(self, dev_regs, slot_of, sparse_rows, sparse_idx,
                 sparse_rho):
        # (nslots, M) int8 — a DEVICE array, or None. Transferred to
        # host lazily on the first promoted-row access: a global server
        # never reads registers, and eagerly pulling the dense bank was
        # up to 16 KB x nslots per flush across the device link for
        # nothing.
        self._dev = dev_regs
        self._dev_np = None
        self._slot_of = slot_of
        # sparse COO sorted by row; boundaries found by searchsorted
        self._rows = sparse_rows
        self._idx = sparse_idx
        self._rho = sparse_rho

    @classmethod
    def dense(cls, state, capacity: int) -> "_SetRegisters":
        """All-dense provider: every row maps 1:1 to a device slot (the
        sparse tier is empty). Used by the non-sparse and sharded set
        tables."""
        empty = np.zeros(0, np.int32)
        return cls(state, np.arange(capacity, dtype=np.int32),
                   empty, empty, empty)

    def __getitem__(self, row: int) -> np.ndarray:
        slot = int(self._slot_of[row]) if row < self._slot_of.shape[0] else -1
        if slot >= 0 and self._dev is not None:
            if self._dev_np is None:
                self._dev_np = np.asarray(self._dev)
            return self._dev_np[slot]
        regs = np.zeros(batch_hll.M, np.int8)
        lo = np.searchsorted(self._rows, row, side="left")
        hi = np.searchsorted(self._rows, row, side="right")
        if hi > lo:
            np.maximum.at(regs, self._idx[lo:hi],
                          self._rho[lo:hi].astype(np.int8))
        return regs


class SetTable(_BaseTable):
    """Sets with a two-tier HLL representation (the reference's vendored
    hyperloglog likewise keeps small sets sparse, sparse.go): samples
    for a key accumulate as host-side COO (register, rho) pairs until
    the key crosses PROMOTE_SAMPLES within the interval, at which point
    it is promoted to a row of the dense (D, 16384) device table and its
    stream flows through the scatter-max kernel. At flush, promoted
    rows' early backlog folds into the device table, small rows estimate
    on host with the same LogLog-Beta math (vectorized over the sorted
    COO), and registers materialize per row only on demand. A 100k-key
    set workload with mostly small sets therefore costs megabytes of
    host COO instead of 1.6 GB of device registers.

    `sparse=False` (the sharded table) keeps the original all-dense
    device path: every row maps 1:1 to a device slot."""

    # HBM guard (`tpu.set_max_dev_slots`): 16 KiB a slot, 1 GiB a
    # generation at this default. The bank climbs 8x a rung up to it,
    # and a flush holds two generations at once: the live one and the
    # one it captured (its estimate and register provider read it).
    # What a raised cap costs is written at the option (config.py).
    MAX_DEV_SLOTS = 65536

    def __init__(self, capacity: int = 256, batch_cap: int = 8192,
                 sparse: bool = True, max_rows: int = 0,
                 promote_samples: int = 0, max_dev_slots: int = 0):
        self._sparse = sparse
        # 0 = auto, resolved lazily at the first promotion decision (the
        # backend probe must not run in the constructor: scratch stores
        # and tools build tables before — or without — a healthy device)
        self._promote_samples = promote_samples
        if max_dev_slots > 0:
            self.MAX_DEV_SLOTS = max_dev_slots
        # flush rounds whose estimate was left on the device for the
        # assembly to collect (`readout(collect=False)`)
        self.deferred_estimates_total = 0
        # what the flushes' readouts did (`readout`): the fold's
        # dispatches and the entries they carried, the rows
        # estimated on the device and on the host; and the bank's climbs
        # up its slot ladder (`_promote_locked`)
        self.fold_dispatches_total = 0
        self.fold_entries_total = 0
        self.device_rows_total = 0
        self.host_rows_total = 0
        self.slot_ladder_climbs_total = 0
        super().__init__(capacity, batch_cap, max_rows=max_rows)

    @property
    def PROMOTE_SAMPLES(self) -> int:
        """Tier-crossover threshold. Auto policy: on a real accelerator
        the dense scatter tier is the fast path, so promote early and
        let the host tier carry only the cold tail (the per-flush sparse
        sort is the sustained-gate cost). On the CPU backend the
        "device" is this same host core — promoting buys nothing and the
        dense estimate scan is slow, so stay sparse-biased."""
        t = self._promote_samples
        if t <= 0:
            t = self._promote_samples = (
                2048 if jax.default_backend() == "cpu" else 16)
        return t

    def _init_pending(self):
        self._prow = np.full(self.batch_cap, PAD_ROW, np.int32)
        self._pidx = np.zeros(self.batch_cap, np.int32)
        self._prho = np.zeros(self.batch_cap, np.int32)
        self._pcols = (self._prow, self._pidx, self._prho)
        self._n = 0

    def _init_arrays(self):
        self._init_pending()
        if self._sparse:
            self._dev_cap = min(256, self.capacity)
            self._slot_of = np.full(self.capacity, -1, np.int32)
            self._nslots = 0
            self._slot_row: List[int] = []
            self._counts = np.zeros(self.capacity, np.int32)
            self._coo: List[tuple] = []
            self._coo_scalar: tuple = ([], [], [])
        else:
            self._dev_cap = self.capacity
        self.state = batch_hll.init_state(self._dev_cap)

    def _grow_arrays(self, new_cap):
        if self._sparse:
            grown_slots = np.full(new_cap, -1, np.int32)
            grown_slots[: self._slot_of.shape[0]] = self._slot_of
            self._slot_of = grown_slots
            grown_counts = np.zeros(new_cap, np.int32)
            grown_counts[: self._counts.shape[0]] = self._counts
            self._counts = grown_counts
        else:
            self._dev_cap = new_cap
            self.state = _pad_cap(self.state, new_cap)

    @property
    def _slot_limit(self) -> int:
        """How many device slots may be ASSIGNED: the HBM guard clamped
        to the current row capacity (slots beyond the table's rows are
        unreachable). Shared by _promote_locked and the add_batch
        promotion-scan gate — they must agree or the scan skip would
        drop count accumulation while promotion is still possible."""
        return min(self.MAX_DEV_SLOTS, self.capacity)

    def _next_rung(self, slots: int) -> int:
        return min(slots * 8, self.MAX_DEV_SLOTS)

    def _ladder(self) -> List[int]:
        """The bank's slot ladder from its current rung up: every
        `_dev_cap` that promotions can climb to at this row capacity."""
        rungs = [self._dev_cap]
        while rungs[-1] < self._slot_limit:
            rungs.append(self._next_rung(rungs[-1]))
        return rungs

    def _promote_locked(self, row: int) -> None:
        """Assign a device slot (caller holds the buffer lock). A no-op
        at the slot limit — the key stays on the host tier (callers
        re-read _slot_of and route accordingly)."""
        if self._nslots >= self._slot_limit:
            return
        if self._nslots >= self._dev_cap:
            with self.apply_lock:
                # Device-cap growth stays ON THE 8x LADDER, bounded only
                # by the HBM guard — never clamped to capacity: sparse
                # _grow_arrays touches no device state, so a dev cap
                # tracking capacity doublings would pay a fresh
                # scatter/estimate shape compile per doubling on the
                # live ingest path (blocking under apply_lock). Ladder
                # shapes are <= 4 total; slots past the row capacity
                # simply idle (<= 8x overshoot, <= the guard). Every
                # rung's programs compile in the start-up warm-up
                # (`warm_programs`).
                self._dev_cap = self._next_rung(self._dev_cap)
                self.state = _pad_cap(self.state, self._dev_cap)
                self.slot_ladder_climbs_total += 1
        self._slot_of[row] = self._nslots
        self._slot_row.append(row)
        self._nslots += 1

    def add(self, metric: UDPMetric):
        member = metric.value if isinstance(metric.value, bytes) else str(
            metric.value).encode()
        h = hll_ref.hash_member(member)
        idx, rho = hll_ref.pos_val(h)
        with self.lock:
            row = self.row_for(metric)
            if row < 0:
                return
            self.touched[row] = True
            self._note_applied(1)
            if self._sparse:
                self._counts[row] += 1
                slot = self._slot_of[row]
                if slot < 0 and self._counts[row] >= self.PROMOTE_SAMPLES:
                    self._promote_locked(row)
                    slot = self._slot_of[row]
                if slot < 0:
                    # per-sample sparse path: cheap list appends, turned
                    # into COO arrays at snapshot
                    self._coo_scalar[0].append(row)
                    self._coo_scalar[1].append(idx)
                    self._coo_scalar[2].append(rho)
                    return
                row = int(slot)
            n = self._n
            self._prow[n] = row
            self._pidx[n] = idx
            self._prho[n] = rho
            self._n = n + 1
            if self._n >= self.batch_cap:
                self._dispatch_pending_locked()

    def _apply_cols_state(self, state, cols):
        rows, idxs, rhos = cols
        return batch_hll.apply_batch(state, rows, idxs, rhos)

    def _state_capacity(self) -> int:
        return self._dev_cap

    def _fresh_state_at(self, capacity: int):
        return batch_hll.init_state(capacity)

    def warm_programs(self, ps, need_export):
        """The bank's apply, backlog fold and estimate at its current
        rung, then, for every rung above it that promotions can reach
        (`_ladder`), what the live path meets there: the climb
        (`_pad_cap`, on the dispatcher's thread under `apply_lock`), the
        apply, the fold, the next swap's fresh generation and the
        estimate. A sparse table's captured bank escapes into the
        snapshot's register provider and is never zeroed; the sharded
        dense table has its own list (and no backlog)."""
        apply = WarmProgram("apply", batch_hll.apply_batch,
                            _state_and_cols, then=_result)
        readout = WarmProgram("readout", batch_hll.estimate, _state_only)
        if not self._sparse:
            return [apply, readout]
        first, *upper = self._ladder()
        programs = [apply, self._warm_fold(first), readout]
        for rung in upper:
            programs += [
                WarmProgram(f"climb@{rung}", _pad_cap, _state_only,
                            static=(rung,), then=_result),
                WarmProgram(f"apply@{rung}", batch_hll.apply_batch,
                            _state_and_cols, then=_result),
                self._warm_fold(rung),
                WarmProgram(f"fresh@{rung}", batch_hll.init_state, _no_args,
                            static=(rung,)),
                WarmProgram(f"readout@{rung}", batch_hll.estimate,
                            _state_only)]
        return programs

    def _warm_fold(self, rung: int) -> WarmProgram:
        """The backlog fold at `rung`, on all-padding arrays of the
        length the bank it is given takes (`batch_hll.fold_length`)."""
        def args(state, cols):
            length = batch_hll.fold_length(state.shape[0],
                                           self.PROMOTE_SAMPLES)
            return (state, np.full(length, batch_hll.FOLD_PAD, np.int32),
                    np.zeros(length, np.int32))
        return WarmProgram(f"fold@{rung}", batch_hll.fold_backlog, args,
                           then=_result)

    def _fold_size(self, rung: int) -> int:
        return batch_hll.fold_entries(rung, self.PROMOTE_SAMPLES)

    def _warm_state(self, capacity: int):
        # a sparse table's device bank rides its own 8x slot ladder
        # (`_dev_cap`), deliberately decoupled from row capacity — see
        # _promote_locked
        return (self._fresh_state() if self._sparse
                else self._fresh_state_at(capacity))

    def prewarm_rung(self, capacity: int, percentiles=(),
                     need_export: bool = True, report=None) -> bool:
        """The bank's slot ladder from its current rung up, for the
        table's own capacity; a no-op for any other: a capacity resize
        never retraces the set kernels."""
        if self._sparse and capacity != self.capacity:
            return False
        return super().prewarm_rung(capacity, percentiles, need_export,
                                    report)

    def apply_pending(self):
        with self.lock:
            self._dispatch_pending_locked()

    def add_batch(self, rows, reg_idx, rho) -> None:
        """Native-parser fast path: members already hashed to (idx, rho).
        Routes each sample to its key's tier (device slot or host COO)."""
        with self.lock:
            self._note_applied(len(rows))
            if not self._sparse:
                self._append_batch((rows, reg_idx, rho), touch_rows=rows)
                return
            # Route in buffer-sized chunks, re-deriving the slot map for
            # every chunk under the CURRENT lock hold: a dispatch below
            # releases the lock while applying, and a concurrent snapshot
            # resets the slot assignment — slot ids captured before that
            # window would write into the fresh interval's state at
            # stale positions (lost or cross-credited samples).
            start = 0
            total = rows.shape[0]
            while start < total:
                free = self.batch_cap - self._n
                if free <= 0:
                    self._dispatch_pending_locked()  # may release lock
                    continue
                sl = slice(start, start + free)
                r, ix, rh = rows[sl], reg_idx[sl], rho[sl]
                start += r.shape[0]
                slots = self._slot_of[r]
                cold = slots < 0
                if self._nslots < self._slot_limit:
                    # (at the slot cap the promotion scan is a
                    # guaranteed no-op; skip its per-chunk cost)
                    self._counts += np.bincount(
                        r, minlength=self._counts.shape[0]).astype(np.int32)
                    hot_rows = np.unique(
                        r[cold & (self._counts[r] >= self.PROMOTE_SAMPLES)])
                    for hr in hot_rows:
                        self._promote_locked(int(hr))
                    if hot_rows.size:
                        slots = self._slot_of[r]
                        cold = slots < 0
                # COO append + touched in the same hold, BEFORE the
                # dense append below can release the lock mid-dispatch
                if cold.any():
                    self.touched[r[cold]] = True
                    self._coo.append((r[cold].copy(), ix[cold].copy(),
                                      rh[cold].copy()))
                if (~cold).any():
                    # fits in the free space by construction, so the
                    # only possible dispatch happens after the chunk is
                    # fully buffered and touched
                    self._append_batch((slots[~cold], ix[~cold],
                                        rh[~cold]), touch_rows=r[~cold])

    def merge_batch(self, stubs: List[UDPMetric], in_regs) -> None:
        """Import-path HLL merge (register max); imported rows arrive
        dense, so they promote immediately in sparse mode. Rows the
        MAX_DEV_SLOTS cap refuses to promote fold into the host COO
        tier instead (nonzero registers -> (idx, rho) pairs) — scattering
        a -1 slot would corrupt the last device row."""
        with self.lock:
            rows = np.fromiter(
                (self.row_for(s) for s in stubs), np.int32, len(stubs))
            ok = rows >= 0  # cardinality-capped stubs drop out
            rows = rows[ok]
            self.touched[rows] = True
            self._note_applied(int(rows.size))
            regs_sel = np.asarray(in_regs, np.int8)[ok]
            if self._sparse:
                for r in rows:
                    if self._slot_of[r] < 0:
                        self._promote_locked(int(r))
                target = self._slot_of[rows]
                capped = target < 0
                if capped.any():
                    for j in np.flatnonzero(capped).tolist():
                        rowregs = regs_sel[j]
                        nz = np.flatnonzero(rowregs)
                        if nz.size:
                            self._coo.append((
                                np.full(nz.size, int(rows[j]), np.int32),
                                nz.astype(np.int32),
                                rowregs[nz].astype(np.int32)))
                    keep = ~capped
                    target, regs_sel = target[keep], regs_sel[keep]
            else:
                target = rows
            self.apply_lock.acquire()
        try:
            if target.size:
                self.state = batch_hll.merge_rows(
                    self.state, target, regs_sel)
        finally:
            self.apply_lock.release()

    def _host_estimates(self, rows, idx, rho):
        """Vectorized LogLog-Beta over row-grouped COO pairs; returns
        (unique_rows, estimates). Dedupe keeps the max rho per (row,
        register), matching the device scatter-max.

        Grouping sorts ONE fused 64-bit key ((row << 14) | register)
        instead of a 3-key lexsort — measured ~3x faster at the
        interval-scale COO volumes the sustained gate produces."""
        if rows.shape[0] == 0:
            return rows, np.zeros(0, np.float32)
        key = (rows.astype(np.int64) << hll_ref.P) | idx.astype(np.int64)
        order = np.argsort(key, kind="stable")
        k, q = key[order], rho[order]
        # max rho per (row, register) via reduceat over group boundaries
        starts = np.flatnonzero(np.r_[True, k[:-1] != k[1:]])
        qmax = np.maximum.reduceat(q, starts)
        kk = k[starts]
        r = (kk >> hll_ref.P).astype(rows.dtype)
        rb = np.flatnonzero(np.r_[True, r[:-1] != r[1:]])
        urows = r[rb]
        nnz = np.diff(np.r_[rb, r.shape[0]])
        pow_sum = np.add.reduceat(
            np.power(2.0, -qmax.astype(np.float64)), rb)
        ez = float(batch_hll.M) - nnz
        s = ez + pow_sum  # zero registers contribute 2^0 each
        # vectorized LogLog-Beta polynomial (hll_ref.beta14 per element)
        zl = np.log(ez + 1.0)
        beta = hll_ref._BETA14_EZ * ez
        for k, c in enumerate(hll_ref._BETA14):
            beta = beta + c * zl ** (k + 1)
        est = np.floor(
            hll_ref._ALPHA * batch_hll.M * (batch_hll.M - ez)
            / (beta + s) + 1.0)
        return urows, est.astype(np.float32)

    def _swap_extras_locked(self, snap: dict) -> None:
        """Capture the sparse tier's interval state (host COO backlog +
        the slot assignment) atomically with the device generation: the
        captured slot map is what makes the captured pending columns'
        slot ids meaningful."""
        if not self._sparse:
            return
        coo, self._coo = self._coo, []
        sc, self._coo_scalar = self._coo_scalar, ([], [], [])
        if sc[0]:
            coo.append((np.asarray(sc[0], np.int32),
                        np.asarray(sc[1], np.int32),
                        np.asarray(sc[2], np.int32)))
        snap["sparse"] = {"coo": coo, "slot_of": self._slot_of,
                          "slot_row": self._slot_row,
                          "nslots": self._nslots}
        self._slot_of = np.full(self.capacity, -1, np.int32)
        self._slot_row = []
        self._nslots = 0
        self._counts[:] = 0

    def _capture_extras_locked(self, snap: dict) -> None:
        """Read-only sparse-tier capture: the COO backlog and slot map
        copied WITHOUT the reset — the live tier keeps accumulating."""
        if not self._sparse:
            return
        coo = list(self._coo)  # entries are append-once, never mutated
        sc = self._coo_scalar
        if sc[0]:
            coo.append((np.asarray(sc[0], np.int32),
                        np.asarray(sc[1], np.int32),
                        np.asarray(sc[2], np.int32)))
        snap["sparse"] = {"coo": coo, "slot_of": self._slot_of.copy(),
                          "slot_row": list(self._slot_row),
                          "nslots": self._nslots}

    def _query_readout_device(self, state, snap: dict) -> None:
        # the sparse readout folds the hot-COO backlog through the
        # DONATING scatter-max kernel — feed it a private copy so the
        # live bank's buffers survive the query (single-device table,
        # so the default-device copy placement is the right one)
        if self._sparse:
            state = jnp.copy(state)
        self._readout_device(state, snap)
        self.collect(snap)
        snap.pop("_fold", None)   # a query is no flush: nothing counted

    # -- the readout in two halves ----------------------------------------
    #
    # The sets are the one family whose readout needs its device output
    # on the host before the FlushBatch can be built (the sparse rows'
    # estimates are scattered into the same array), so the readout is
    # split where it would wait for the chip:
    #   dispatch half  `_readout_device`, under `dispatch{set}`: the fold
    #                  and the estimate's dispatch; what the other half
    #                  needs stays in the snap (`_estimate`)
    #   collect half   `collect`: the wait, the copy and the host work
    # `readout()` runs both back to back, and with it every snapshot and
    # live read; only the columnar flush runs the collect half apart, in
    # `assembly_set`, so the chip's work runs beside the other families'
    # transfers and assembly (`flusher.readout_columnstore`). Spans,
    # `family="set"`, closed only where the table did the work (an idle
    # table closes none):
    #   set_fold           host, child of `dispatch`: the last pending
    #                      batch's apply, the COO concatenate, the slot
    #                      lookup, the backlog's pairs and its fold's
    #                      dispatch (`_fold_backlog`), the estimate's
    #   set_wait           THE FLUSH THREAD BLOCKED ON THE CHIP, until
    #                      the estimate is ready; a chip runs its stream
    #                      in order, so also until every program
    #                      dispatched before it has run
    #   set_transfer       the ready estimate's copy to the host
    #   set_host_estimate  host: the device rows' scatter, the sparse
    #                      rows' LogLog-Beta and argsort, the provider
    # the last three children of the collect half's `parent`

    @staticmethod
    def _set_phase(snap: dict, name: str, parent: str = "dispatch",
                   timing=None):
        # a readout nobody times (a hand-called snapshot_and_reset)
        # gets a round of its own, as a sharded table's merge does
        timing = timing or snap.get("_timing") or FlushRound()
        return timing.phase(name, parent=parent, family="set")

    def _dispatch_estimate(self, bank, snap: dict) -> None:
        """A dense bank's estimate dispatched, the last step of the
        fold (`set_fold`), left for `collect` with the bank the
        register provider reads. Every touched row is a device row."""
        with self._set_phase(snap, "set_fold"):
            snap["_estimate"] = {
                "dev": batch_hll.estimate(bank), "bank": bank,
                "device_rows": int(np.count_nonzero(snap["touched"])),
                "host_rows": 0}

    @staticmethod
    def _note_fold(snap: dict, dispatches: int, entries: int) -> None:
        """Dispatches of the readout's fold (the last pending batch's
        `apply_batch`, the backlog's `fold_backlog`) and the entries
        they carried, for `readout` to count."""
        fold = snap.setdefault("_fold", [0, 0])
        fold[0] += dispatches
        fold[1] += entries

    def _readout_apply(self, state, cols, snap: dict):
        with self._set_phase(snap, "set_fold"):
            self._note_fold(snap, 1, int(np.count_nonzero(cols[0] != PAD_ROW)))
            return self._apply_cols_state(state, cols)

    def _readout_device(self, state, snap: dict) -> None:
        """The dispatch half over the captured generation: the fold and
        the estimate's dispatch, leaving for `collect` the estimate's
        handle (`dev`, None where no row is on the device), the bank the
        register provider reads (lazy transfer, so the captured
        generation escapes into the snapshot and is NOT recycled) and
        the sparse tier's rows."""
        if not self._sparse:
            self._dispatch_estimate(state, snap)
            return
        sparse = snap.pop("sparse")
        coo = sparse["coo"]
        nslots = sparse["nslots"]
        if not coo and not nslots:
            # idle: no sparse sample, no promoted row, nothing to wait
            # for and no span
            snap["estimates"] = np.zeros(self.capacity, np.float32)
            snap["registers"] = _SetRegisters(
                None, sparse["slot_of"], *(np.zeros(0, np.int32),) * 3)
            return
        # fold promoted rows' pre-promotion backlog into the device
        # table; the rest of the COO is the sparse rows'
        with self._set_phase(snap, "set_fold"):
            if coo:
                rows_all = np.concatenate([c[0] for c in coo])
                idx_all = np.concatenate([c[1] for c in coo])
                rho_all = np.concatenate([c[2] for c in coo])
            else:
                rows_all = np.zeros(0, np.int32)
                idx_all = rho_all = rows_all
            pslots = sparse["slot_of"][rows_all] if rows_all.size else rows_all
            hot = pslots >= 0
            state = self._fold_backlog(state, pslots, hot, idx_all, rho_all,
                                       snap)
            snap["_estimate"] = {
                "dev": batch_hll.estimate(state) if nslots else None,
                "bank": state if nslots else None, "sparse": sparse,
                "coo": (rows_all, idx_all, rho_all), "hot": hot,
                # rows left on the host tier: touched, never promoted
                "device_rows": nslots, "host_rows": int(np.count_nonzero(
                    snap["touched"] & (sparse["slot_of"] < 0)))}

    def _fold_backlog(self, bank, slots, hot, idx, rho, snap: dict):
        """The promoted rows' backlog (the COO entries whose `slots` are
        `hot`) into the captured bank: one `batch_hll.fold_backlog` of
        the rung's size (`_fold_size`). Where the whole COO fits, it goes
        as it is, a host row's entries with slot -1, which the device
        skips: no copy of the hot entries on the host. Otherwise the hot
        entries alone go, in chunks of that size where they outgrow it
        (past FOLD_MAX_ENTRIES, or a threshold lowered inside the
        interval). An empty backlog dispatches nothing."""
        size = self._fold_size(bank.shape[0])
        entries = int(np.count_nonzero(hot))
        if slots.shape[0] > size:
            slots, idx, rho = slots[hot], idx[hot], rho[hot]
        n = slots.shape[0] if entries else 0
        self._note_fold(snap, -(-n // size), entries)
        length = size + batch_hll.FOLD_WINDOW
        for i in range(0, n, size):
            m = min(size, n - i)
            chunk_slots = np.empty(length, np.int32)
            chunk_pay = np.empty(length, np.int32)
            chunk_slots[:m] = slots[i:i + m]
            chunk_slots[m:] = batch_hll.FOLD_PAD
            np.left_shift(idx[i:i + m], 8, out=chunk_pay[:m])
            chunk_pay[:m] |= rho[i:i + m]
            chunk_pay[m:] = 0
            bank = batch_hll.fold_backlog(bank, chunk_slots, chunk_pay)
        return bank

    def readout(self, snap: dict, timing=None, collect: bool = True) -> dict:
        """Both halves back to back; with `collect=False` (the columnar
        flush alone) the dispatch half, the estimate left in the snap
        for `collect`. Such a round is counted where its estimate is
        on the device (`flush.set.deferred_estimates_total`). Every
        round counts its fold and its rows (`flush.set.*`)."""
        super().readout(snap, timing)
        dispatches, entries = snap.pop("_fold", (0, 0))
        self.fold_dispatches_total += dispatches
        self.fold_entries_total += entries
        pending = snap.get("_estimate", {})
        self.device_rows_total += pending.get("device_rows", 0)
        self.host_rows_total += pending.get("host_rows", 0)
        if collect:
            return self.collect(snap, timing)
        if pending.get("dev") is not None:
            self.deferred_estimates_total += 1
        return snap

    def collect(self, snap: dict, timing=None,
                parent: str = "dispatch") -> dict:
        """The collect half: wait for the estimate the dispatch half
        left in the snap, copy it and fill `estimates` and `registers`
        (the spans `set_wait`, `set_transfer`, `set_host_estimate`,
        children of `parent` in `timing`). A snap with nothing pending
        (an idle table) is returned as it is."""
        pending = snap.pop("_estimate", None)
        if pending is None:
            return snap
        dev = pending["dev"]
        if dev is not None:
            with self._set_phase(snap, "set_wait", parent, timing):
                jax.block_until_ready(dev)
            with self._set_phase(snap, "set_transfer", parent, timing):
                dev_est = np.asarray(dev)
        with self._set_phase(snap, "set_host_estimate", parent, timing):
            sparse = pending.get("sparse")
            if sparse is None:
                snap["estimates"] = dev_est
                snap["registers"] = _SetRegisters.dense(pending["bank"],
                                                        self.capacity)
                return snap
            estimates = np.zeros(self.capacity, np.float32)
            if dev is not None:
                estimates[np.asarray(sparse["slot_row"], np.int64)] = \
                    dev_est[:sparse["nslots"]]
            rows_all, idx_all, rho_all = pending["coo"]
            cold = ~pending["hot"]
            s_rows, s_idx, s_rho = rows_all[cold], idx_all[cold], rho_all[cold]
            if s_rows.size:
                urows, est = self._host_estimates(s_rows, s_idx, s_rho)
                estimates[urows] = est
                order = np.argsort(s_rows, kind="stable")
                s_rows, s_idx, s_rho = (s_rows[order], s_idx[order],
                                        s_rho[order])
            snap["estimates"] = estimates
            snap["registers"] = _SetRegisters(pending["bank"],
                                              sparse["slot_of"], s_rows,
                                              s_idx, s_rho)
        return snap

    def snapshot_begin(self) -> dict:
        """Swap + both halves of the readout: unlike every other
        family's, this one WAITS FOR THE CHIP (the `set_wait` span), so
        the snap holds the estimates on the host."""
        return self.readout(self.swap_out())

    @staticmethod
    def snapshot_finish(snap: dict):
        return (snap["estimates"], snap["registers"], snap["touched"],
                snap["meta"])

    def snapshot_and_reset(self):
        # recycle is a no-op for the sparse tier (its captured bank
        # escapes into the register provider) and real for the sharded
        # dense tier
        return self._finish_and_recycle(self.snapshot_begin())


class LLHistTable(_BaseTable):
    """Circllhist log-linear histograms: a dense (K, BINS) int32
    register table (veneur_tpu.ops.batch_llhist). The host bins values
    (ops/llhist_ref.bin_index — the same code the scalar reference
    runs, so the two can never disagree) into (row, bin, weight)
    triples; the device applies them as one scatter-add per batch.
    Merges — import, carryover, interval — are register additions,
    which is the family's whole point: the forward tier's global
    percentile is bit-identical to a single node that saw every sample.

    Weights are integral (1/sample_rate rounds to the nearest count);
    clamp accounting (values outside the representable magnitude
    window) is surfaced as the llhist.samples/llhist.clamped rows in
    ColumnStore.telemetry_rows."""

    def _init_arrays(self):
        self._prow = np.full(self.batch_cap, PAD_ROW, np.int32)
        self._pbin = np.zeros(self.batch_cap, np.int32)
        self._pwt = np.zeros(self.batch_cap, np.int32)
        self._pcols = (self._prow, self._pbin, self._pwt)
        self._n = 0
        self.state = batch_llhist.init_state(self.capacity)
        # monotonic sample/clamp accounting (mutated under `lock`)
        self.samples_total = 0
        self.clamped_total = 0

    def _grow_arrays(self, new_cap):
        self.state = _pad_cap(self.state, new_cap)

    def add(self, metric: UDPMetric):
        value = float(metric.value)
        bin_idx = int(llhist_ref.bin_index(value))
        # clamp into int32: registers are int32, and an absurd-but-valid
        # sample rate (@1e-10) must saturate, not overflow the buffer
        # assignment (same clamp as bin_batch_host and the C++ parser)
        weight = min(max(1, round(1.0 / max(metric.sample_rate, 1e-9))),
                     2**31 - 1)
        with self.lock:
            row = self.row_for(metric)
            if row < 0:
                return
            self.touched[row] = True
            self._note_applied(1)
            self.samples_total += weight
            if llhist_ref.clamped_mask(value):
                self.clamped_total += weight
            n = self._n
            self._prow[n] = row
            self._pbin[n] = bin_idx
            self._pwt[n] = weight
            self._n = n + 1
            if self._n >= self.batch_cap:
                self._dispatch_pending_locked()

    def _apply_cols_state(self, state, cols):
        rows, bins, wts = cols
        return batch_llhist.apply_batch(state, rows, bins, wts)

    def _fresh_state_at(self, capacity: int):
        return batch_llhist.init_state(capacity)

    def apply_pending(self):
        with self.lock:
            self._dispatch_pending_locked()

    def add_batch(self, rows, vals, weights) -> None:
        """Batch fast path: pre-interned rows, raw values (binned here),
        weights are 1/sample_rate floats."""
        bins, wts = batch_llhist.bin_batch_host(vals, weights)
        with self.lock:
            self._note_applied(len(rows))
            self.samples_total += int(wts.sum())
            self.clamped_total += int(
                wts[llhist_ref.clamped_mask(vals)].sum())
            self._append_batch((np.asarray(rows, np.int32), bins, wts))

    def add_batch_binned(self, rows, bins, wts, clamped: int = 0) -> None:
        """Batch fast path for ALREADY-binned samples — the native (C++)
        batch parser bins the `l` wire type itself (llhist_ref.bin_index
        parity pinned by the ingest fuzz corpus), so the hand-off is
        three int32 columns and no host float work at all. `clamped` is
        the parser's count of weight that fell outside the bin window
        (the accuracy-loss accounting bins alone can't reconstruct)."""
        with self.lock:
            self._note_applied(len(rows))
            self.samples_total += int(np.sum(wts))
            self.clamped_total += int(clamped)
            self._append_batch((np.asarray(rows, np.int32),
                                np.asarray(bins, np.int32),
                                np.asarray(wts, np.int32)))

    def merge_batch(self, stubs: List[UDPMetric], in_bins) -> None:
        """Import-path merge: register add. Interning atomic under the
        buffer lock; the state update rides the apply ticket so it
        orders after any already-swapped local batches."""
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
            self.apply_lock.acquire()
        try:
            if rows.size:
                self.state = batch_llhist.merge_rows(
                    self.state, rows, padded)
        finally:
            self.apply_lock.release()

    def _idle_swap_locked(self, snap: dict) -> bool:
        # idle-family fast path: every mutation path sets touched,
        # so no pending samples + no touched rows means the state
        # is still the all-zero array the last reset left — skip
        # the capacity-proportional readout dispatch, the register
        # gather, and the generation swap entirely. The generation
        # still advances so idle-row reclamation of a gone-quiet
        # keyset keeps working.
        if self._n == 0 and not self.touched.any():
            self._note_generation_locked()
            snap.update(packed=None, bins_dev=None,
                        touched=self.touched.copy(),
                        meta=list(self.meta))
            return True
        return False

    def _idle_capture_locked(self, snap: dict) -> bool:
        # same skip for queries — minus the generation advance (a
        # read-only capture must not perturb idle-row reclamation)
        if self._n == 0 and not self.touched.any():
            snap.update(packed=None, bins_dev=None,
                        touched=self.touched.copy(),
                        meta=list(self.meta))
            return True
        return False

    def snapshot_begin(self, percentiles: Tuple[float, ...],
                       need_bins: bool = True) -> dict:
        """Dispatch-only snapshot half (see CounterTable.snapshot_begin):
        swap+apply pending, dispatch the readout, capture the touched
        rows' raw bins (gathered on device, so only live rows cross the
        link — the full table at 100k keys would be ~2 GB), reset.
        `need_bins=False` (a server that neither forwards nor exports
        buckets) skips the register transfer entirely."""
        return self.readout(self.swap_out(
            ps=tuple(percentiles), need_bins=need_bins))

    def _readout_device(self, state, snap: dict) -> None:
        """Dispatch the readout + bins gather over the captured
        generation. The sharded table overrides this with the
        register-ADD collective merge before the same readout."""
        packed = batch_llhist.flush_packed(state, snap["ps"])
        rows = np.flatnonzero(snap["touched"])
        bins_dev = None
        if snap.pop("need_bins") and rows.size:
            bins_dev = jnp.take(state, jnp.asarray(rows, jnp.int32),
                                axis=0)
        snap["packed"] = packed
        snap["bins_dev"] = bins_dev
        snap["_recycle"] = state

    def warm_programs(self, ps, need_export):
        # the gather of the touched rows' bins is shaped by how many
        # rows an interval touched: not on a list that capacities fix
        return [WarmProgram("apply", batch_llhist.apply_batch,
                            _state_and_cols, then=_result),
                WarmProgram("readout", batch_llhist.flush_packed,
                            _state_only, static=(ps,)),
                WarmProgram("reset", _zeros_like_donated, _state_only,
                            then=_result)]

    @staticmethod
    def snapshot_finish(snap: dict):
        """Returns (readout dict of np arrays over all rows, bins
        (n_touched, BINS) aligned with the touched rows in ascending
        order, touched, meta). The bins are the device's int32
        registers as transferred (a view that leaves the padding out),
        mostly zeros: the flush reads their nonzero entries
        (`llhist_ref.nonzero_entries`), and a reader that sums or merges
        whole rows widens the rows it takes."""
        if snap["packed"] is None:  # idle-family fast path
            return ({}, np.zeros((0, llhist_ref.BINS), np.int32),
                    snap["touched"], snap["meta"])
        out = {k: np.asarray(v) for k, v in snap["packed"].items()}
        if snap["bins_dev"] is not None:
            bins = np.asarray(snap["bins_dev"])[:, :llhist_ref.BINS]
        else:
            bins = np.zeros((0, llhist_ref.BINS), np.int32)
        return out, bins, snap["touched"], snap["meta"]

    def snapshot_and_reset(self, percentiles: Tuple[float, ...],
                           need_bins: bool = True):
        return self._finish_and_recycle(
            self.snapshot_begin(percentiles, need_bins))


@dataclass
class StatusEntry:
    value: float = 0.0
    message: str = ""
    hostname: str = ""


class StatusTable(_BaseTable):
    """Service checks: last status + message; strings stay on host
    (reference samplers.go:210-231)."""

    def _init_arrays(self):
        self.values: List[StatusEntry] = []

    def _grow_arrays(self, new_cap):
        pass

    def add(self, metric: UDPMetric):
        with self.lock:
            row = self.row_for(metric)
            if row < 0:
                return
            while len(self.values) <= row:
                self.values.append(StatusEntry())
            self.touched[row] = True
            self._note_applied(1)
            self.values[row] = StatusEntry(
                value=float(metric.value), message=metric.message,
                hostname=metric.hostname)

    def apply_pending(self):
        pass

    def snapshot_and_reset(self):
        with self.lock:
            vals = list(self.values)
            self._note_generation_locked()
            touched = self.touched.copy()
            meta = list(self.meta)
            self.values = [StatusEntry() for _ in vals]
            self.touched[:] = False
        return vals, touched, meta


class ColumnStore:
    """All five device families plus host-side status checks.

    With shard_devices > 1 the store becomes a partitioned mesh
    (core/sharded_tables.py): every family's interval state spreads
    across that many local devices, keys routed to a digest-derived
    home shard and flushes merged with collectives. The legacy
    `shard_routing="roundrobin"` mode shards only the HBM-heavy
    histogram/set families (round-robin batches destroy the per-key
    ordering the scalar families need)."""

    def __init__(self, counter_capacity=1024, gauge_capacity=1024,
                 histo_capacity=1024, set_capacity=256, batch_cap=8192,
                 shard_devices=0, max_rows=0,
                 set_promote_samples=0, set_max_dev_slots=0,
                 llhist_capacity=1024, histogram_encoding="tdigest",
                 shard_routing="digest"):
        # histogram_encoding chooses the family DogStatsD histogram/timer
        # samples aggregate in: "tdigest" (reference parity, approximate
        # merges) or "circllhist" (log-linear bins, exact merges).
        # Explicit `|l` samples and OTLP exponential histograms always
        # land in the llhist family regardless.
        if histogram_encoding not in ("tdigest", "circllhist"):
            raise ValueError(
                f"unknown histogram_encoding: {histogram_encoding!r}")
        self.histogram_encoding = histogram_encoding
        self.shard_plane = None
        if shard_devices and shard_devices > 1:
            from veneur_tpu.parallel.sharded_server import build_plane
            self.shard_plane = build_plane(shard_devices, shard_routing)
        plane = self.shard_plane
        digest_routed = plane is not None and plane.routing == "digest"
        if digest_routed:
            from veneur_tpu.core.sharded_tables import (
                ShardedCounterTable, ShardedGaugeTable,
                ShardedLLHistTable)
            self.counters = ShardedCounterTable(
                counter_capacity, batch_cap, max_rows=max_rows,
                plane=plane)
            self.gauges = ShardedGaugeTable(
                gauge_capacity, batch_cap, max_rows=max_rows, plane=plane)
            self.llhists = ShardedLLHistTable(
                llhist_capacity, batch_cap, max_rows=max_rows,
                plane=plane)
        else:
            self.counters = CounterTable(counter_capacity, batch_cap,
                                         max_rows=max_rows)
            self.gauges = GaugeTable(gauge_capacity, batch_cap,
                                     max_rows=max_rows)
            self.llhists = LLHistTable(llhist_capacity, batch_cap,
                                       max_rows=max_rows)
        if plane is not None:
            from veneur_tpu.core.sharded_tables import (
                ShardedHistoTable, ShardedSetTable)
            self.histos = ShardedHistoTable(
                histo_capacity, batch_cap, max_rows=max_rows, plane=plane)
            self.sets = ShardedSetTable(set_capacity, batch_cap,
                                        max_rows=max_rows, plane=plane)
        else:
            self.histos = HistoTable(histo_capacity, batch_cap,
                                     max_rows=max_rows)
            self.sets = SetTable(set_capacity, batch_cap,
                                 max_rows=max_rows,
                                 promote_samples=set_promote_samples,
                                 max_dev_slots=set_max_dev_slots)
        self.statuses = StatusTable(max_rows=max_rows)
        for family, table in self.tables():
            table.family = family
        self.processed = 0
        self.ledger = None  # set by attach_ledger
        self.deviceobs = None  # set by attach_deviceobs
        self._processed_lock = threading.Lock()

    def tables(self):
        """(family, table) pairs, every device family plus statuses."""
        return (("counter", self.counters), ("gauge", self.gauges),
                ("histogram", self.histos), ("llhist", self.llhists),
                ("set", self.sets), ("status", self.statuses))

    def attach_cardinality(self, accountant) -> None:
        """Wire the cardinality accountant (core/cardinality.py) into
        every table's interning path."""
        for _family, table in self.tables():
            table.cardinality = accountant

    def attach_deviceobs(self, obs) -> None:
        """Wire the device observatory (core/deviceobs.py) into every
        table's generation lifecycle and kernel dispatch paths, register
        the current live generations (and any parked spares) in its HBM
        ledger, and hand it the store for shard-balance scrapes."""
        self.deviceobs = obs
        obs.attach_store(self)
        for family, table in self.tables():
            table._deviceobs = obs
            with table.apply_lock:
                state = table._devobs_state()
                if state is not None and table._devobs_live is None:
                    table._devobs_live = obs.note_generation(
                        family, "live", state)
                if table._spare is not None \
                        and table._devobs_spare is None:
                    table._devobs_spare = obs.note_generation(
                        family, "spare", table._spare)

    def attach_ledger(self, ledger) -> None:
        """Wire the flow ledger (core/ledger.py) into every table's
        apply/reject paths — the out-side of the ingest conservation
        identity (admitted == applied + rejected)."""
        self.ledger = ledger
        for _family, table in self.tables():
            table.ledger = ledger

    def attach_resize_hook(self, hook) -> None:
        """hook(family, old_cap, new_cap, seconds, kind=...) fires on
        every capacity doubling (kind="resize", under the buffer lock —
        see _BaseTable.on_resize for what the hook may safely do) and on
        the first post-resize batch apply (kind="recompile")."""
        for _family, table in self.tables():
            table.on_resize = hook

    def telemetry_rows(self) -> List[tuple]:
        """(name, kind, value, tags) scrape-time rows: per-family row
        capacity/occupancy, batch-buffer state, resize/recompile cost,
        and key-churn counters — the capacity picture that previously
        existed only as in-memory attributes. Reads are lock-free (GIL
        point reads of monotonic counters and gauges; a torn gauge is
        one scrape stale, never corrupt)."""
        rows: List[tuple] = []
        for family, t in self.tables():
            tags = [f"family:{family}"]
            rows.append(("columnstore.row_capacity", "gauge",
                         float(t.capacity), tags))
            rows.append(("columnstore.live_rows", "gauge",
                         float(len(t.rows)), tags))
            rows.append(("columnstore.free_rows", "gauge",
                         float(len(t._free_rows)), tags))
            rows.append(("columnstore.keys_minted_total", "counter",
                         float(t.minted_total), tags))
            rows.append(("columnstore.keys_tombstoned_total", "counter",
                         float(t.tombstoned_total), tags))
            rows.append(("columnstore.keys_recycled_total", "counter",
                         float(t.recycled_total), tags))
            rows.append(("columnstore.keys_dropped_total", "counter",
                         float(t.keys_dropped), tags))
            rows.append(("columnstore.resize_total", "counter",
                         float(t.resize_total), tags))
            rows.append(("columnstore.resize_seconds_total", "counter",
                         t.resize_seconds_total, tags))
            rows.append(("columnstore.resize_last_seconds", "gauge",
                         t.resize_last_seconds, tags))
            rows.append(("columnstore.recompile_seconds_total", "counter",
                         t.recompile_seconds_total, tags))
            rows.append(("columnstore.recompile_last_seconds", "gauge",
                         t.recompile_last_seconds, tags))
            rows.append(("columnstore.batch_dispatch_total", "counter",
                         float(t.dispatch_total), tags))
            rows.append(("ingest.apply.seconds_total", "counter",
                         t.apply_seconds_total, tags))
            rows.append(("ingest.apply.lock_wait_seconds_total", "counter",
                         t.apply_lock_wait_seconds_total, tags))
            for path, n in getattr(t, "compacts_total", {}).items():
                # the digest table only: compacts its keys forced, and
                # the keys dense within a batch
                rows.append(("ingest.tdigest.compacts_total", "counter",
                             float(n), [f"path:{path}"]))
                rows.append(("ingest.tdigest.compact_seconds_total",
                             "counter", t.compact_seconds_total[path],
                             [f"path:{path}"]))
                rows.append(("ingest.tdigest.dense_keys_total", "counter",
                             float(t.dense_keys_total[path]),
                             [f"path:{path}"]))
                if t._deviceobs is not None:
                    rows.append((
                        "ingest.tdigest.compact_chip_seconds_total",
                        "counter", t.compact_chip_seconds_total[path],
                        [f"path:{path}"]))
            pending = getattr(t, "_n", None)
            if pending is not None:  # statuses have no batch buffers
                rows.append(("columnstore.batch_cap", "gauge",
                             float(t.batch_cap), tags))
                rows.append(("columnstore.pending_samples", "gauge",
                             float(pending), tags))
            nslots = getattr(t, "_nslots", None)
            if nslots is not None:  # sparse set table: promoted HBM rows
                rows.append(("columnstore.set_dev_slots", "gauge",
                             float(nslots), tags))
            if isinstance(t, SetTable):
                rows += [
                    ("flush.set.deferred_estimates_total", "counter",
                     float(t.deferred_estimates_total), ()),
                    ("flush.set.fold_dispatches_total", "counter",
                     float(t.fold_dispatches_total), ()),
                    ("flush.set.fold_entries_total", "counter",
                     float(t.fold_entries_total), ()),
                    ("flush.set.device_rows_total", "counter",
                     float(t.device_rows_total), ()),
                    ("flush.set.host_rows_total", "counter",
                     float(t.host_rows_total), ()),
                    ("set.device_slots", "gauge",
                     float(t._state_capacity()), ()),
                    ("set.slot_ladder_climbs_total", "counter",
                     float(t.slot_ladder_climbs_total), ())]
        # llhist accuracy accounting: samples binned, and how many fell
        # outside the representable magnitude window (collapsed to the
        # zero bin / clamped into a top bin)
        rows.append(("llhist.samples_total", "counter",
                     float(self.llhists.samples_total), ()))
        rows.append(("llhist.clamped_total", "counter",
                     float(self.llhists.clamped_total), ()))
        # sharded serving plane: mesh topology + per-shard routed volume
        # (parallel/sharded_server.py), absent on single-device stores
        if self.shard_plane is not None:
            rows.extend(self.shard_plane.telemetry_rows())
        return rows

    def capacity_report(self) -> dict:
        """Per-family capacity/churn snapshot for /debug/cardinality."""
        out = {}
        for family, t in self.tables():
            out[family] = {
                "row_capacity": t.capacity,
                "live_rows": len(t.rows),
                "allocated_rows": len(t.meta),
                "free_rows": len(t._free_rows),
                "minted_total": t.minted_total,
                "tombstoned_total": t.tombstoned_total,
                "recycled_total": t.recycled_total,
                "keys_dropped_total": t.keys_dropped,
                "resize_total": t.resize_total,
                "resize_seconds_total": round(t.resize_seconds_total, 6),
                "resize_last_seconds": round(t.resize_last_seconds, 6),
                "recompile_seconds_total": round(
                    t.recompile_seconds_total, 6),
                "recompile_last_seconds": round(
                    t.recompile_last_seconds, 6),
                "batch_dispatch_total": t.dispatch_total,
            }
        return out

    def live_rows_by_name(self) -> Dict[str, dict]:
        """On-demand exact per-name series accounting: walks every
        table's meta under its buffer lock (pointer-copy only; the
        group-by runs outside the lock). Capacity-proportional — this is
        the /debug/cardinality drill-down path, never the hot path."""
        per_name: Dict[str, dict] = {}
        for family, t in self.tables():
            with t.lock:
                metas = list(t.meta)
                touched = t.touched.copy()
            for row, meta in enumerate(metas):
                if meta is None:
                    continue
                entry = per_name.setdefault(
                    meta.name, {"live_rows": 0, "touched_rows": 0,
                                "families": {}})
                entry["live_rows"] += 1
                entry["families"][family] = \
                    entry["families"].get(family, 0) + 1
                if row < touched.shape[0] and touched[row]:
                    entry["touched_rows"] += 1
        return per_name

    def count_processed(self, n: int) -> None:
        """Locked sample-count increment (readers race on += otherwise)."""
        with self._processed_lock:
            self.processed += n

    def process(self, metric: UDPMetric) -> None:
        """Route one parsed metric to its family table (the equivalent of
        reference worker.go:350-404 ProcessMetric)."""
        t = metric.key.type
        if t == m.COUNTER:
            self.counters.add(metric)
        elif t == m.GAUGE:
            self.gauges.add(metric)
        elif t in (m.HISTOGRAM, m.TIMER):
            if self.histogram_encoding == "circllhist":
                self.llhists.add(metric)
            else:
                self.histos.add(metric)
        elif t == m.LLHIST:
            self.llhists.add(metric)
        elif t == m.SET:
            self.sets.add(metric)
        elif t == m.STATUS:
            self.statuses.add(metric)
        else:
            # unknown wire type: the sample was counted admitted by the
            # caller, so its drop must be explained or the ledger's
            # ingest identity (rightly) flags it
            led = getattr(self, "ledger", None)
            if led is not None:
                led.note("agg.rejected", 1, key="unknown")
            return
        self.count_processed(1)

    def apply_all_pending(self):
        self.counters.apply_pending()
        self.gauges.apply_pending()
        self.histos.apply_pending()
        self.llhists.apply_pending()
        self.sets.apply_pending()

    def unique_timeseries(self) -> int:
        """Timeseries touched this interval. The reference approximates
        this with a per-worker HLL over key digests (worker.go:305-347);
        the column store's touched masks make it exact for free."""
        total = 0
        for table in (self.counters, self.gauges, self.histos,
                      self.llhists, self.sets, self.statuses):
            with table.lock:
                total += int(np.count_nonzero(table.touched))
        return total
