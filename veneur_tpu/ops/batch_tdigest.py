"""Batched t-digest over a (key x centroid) column store — the TPU kernel.

The reference maintains one merging t-digest per metric key and feeds it one
sample at a time (reference tdigest/merging_digest.go:115-255). Here the
whole table of digests is three dense device arrays (means, weights of shape
(K, C), plus per-key scalar stats) and ingestion is batched:

  1. Each sample RANK-PARKS into the per-key staging grid: its slot is
     the key's running staged-sample count plus its within-batch rank,
     so every staged sample keeps its exact (value, weight) — the device
     analog of the reference's raw temp buffer
     (merging_digest.go:115-140). Slots are computed on the HOST
     (host_ranks: one vectorized argsort per batch) because the host
     already tracks per-key staged counts for overflow control, and a
     16k-element 1-D segmented scan costs ~8 ms on the TPU VPU vs
     ~0.3 ms in numpy. The device apply is then pure O(B) scatters,
     independent of table capacity.
  2. Keys dense within one batch (> C samples) instead bucket by their
     batch-local weighted midpoint quantile (host_slots) — statistically
     sound at that density and identical to what a per-batch merge would
     do with them.
  3. When a key's staging would otherwise overflow its C slots — the
     host tracks exact per-key occupancy — `compact` folds the staging
     of the rows that would overflow (and of those at least half full,
     `FOLD_ALONG`, at no extra cost) into their main grid with the
     mean-sorted recompress: sort [main | staging] slots by mean, bucket
     by the arcsine k-scale of combined midpoint quantiles (parity with
     merging_digest.go:259-262), and segment-reduce the (sorted, hence
     contiguous) buckets with a chunked one-hot matmul on the MXU. The
     readouts and merges fold staging themselves.

Sparse keys (the 100k-key regime: ~1 sample/key/batch) therefore stage
EXACTLY until their own slots fill, however often a hot key forces a
compact (each recompress of a row's grid costs accuracy: a key of 70
samples recompressed once a batch strayed 0.021 in rank); dense keys
compact about once per batch, exactly like the reference's temp buffer
filling per ~5·compression samples. After every
compact each slot spans at most one k-unit of the combined distribution,
so quantile error stays in the sequential algorithm's class. Bucketing
by floor(k) bounds the store at `compression` centroids per key (the
reference's bound is ceil(pi*compression/2); ours is tighter but the
same order). Validated against veneur_tpu.ops.tdigest_ref by
statistical tests (tests/test_tdigest.py).
"""

from __future__ import annotations

import math
from functools import partial
from typing import Dict, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from veneur_tpu.ops import device_scope

COMPRESSION = 100.0  # parity with reference samplers/samplers.go:350
C = 128  # centroid slots per key; >= COMPRESSION buckets, lane-aligned
# a compact forced by some rows also folds every row with at least this
# many staged samples (a row folded with fewer would take a recompress
# it does not need, and each costs it accuracy)
FOLD_ALONG = C // 2

_INF = jnp.float32(jnp.inf)


def init_state(num_keys: int) -> Dict[str, jnp.ndarray]:
    """Fresh digest table. Per-key stats: d* follow the digest (updated by
    ingest and merge); l* follow only locally-ingested samples (reference
    samplers.go:316-343 Local{Weight,Min,Max,Sum,ReciprocalSum}).
    s* is the raw-sample staging grid (the host tracks per-key slot
    occupancy); `compact` folds it into wv/weights."""
    k = num_keys
    f = jnp.float32
    return {
        "wv": jnp.zeros((k, C), f),  # per-slot sum of weight*value
        "weights": jnp.zeros((k, C), f),
        "swv": jnp.zeros((k, C), f),  # staging: raw weight*value per slot
        "sweights": jnp.zeros((k, C), f),
        "dmin": jnp.full((k,), _INF, f),
        "dmax": jnp.full((k,), -_INF, f),
        "drecip": jnp.zeros((k,), f),
        "lmin": jnp.full((k,), _INF, f),
        "lmax": jnp.full((k,), -_INF, f),
        "lsum": jnp.zeros((k,), f),
        "lweight": jnp.zeros((k,), f),
        "lrecip": jnp.zeros((k,), f),
    }


def _k_scale(q: jnp.ndarray) -> jnp.ndarray:
    """Arcsine k-scale index (parity with merging_digest.go:259-262)."""
    q = jnp.clip(q, 0.0, 1.0)
    return COMPRESSION * (jnp.arcsin(2.0 * q - 1.0) / math.pi + 0.5)


def host_ranks(rows: np.ndarray) -> np.ndarray:
    """Within-batch ordinal of each sample among samples of the same row
    (host-side, vectorized: one stable argsort + grouped arange)."""
    order = np.argsort(rows, kind="stable")
    sr = rows[order]
    n = sr.shape[0]
    if n == 0:
        return np.zeros(0, np.int32)
    is_start = np.empty(n, bool)
    is_start[0] = True
    np.not_equal(sr[1:], sr[:-1], out=is_start[1:])
    starts = np.flatnonzero(is_start)
    seg = np.cumsum(is_start) - 1
    ranks_sorted = np.arange(n, dtype=np.int32) - starts[seg].astype(np.int32)
    ranks = np.empty(n, np.int32)
    ranks[order] = ranks_sorted
    return ranks


def host_slots(rows, values, weights, counts):
    """Staging slots for a COO batch (host-side; numpy throughout).

    Sparse keys (<= C samples in this batch) RANK-PARK: slot = the key's
    staged count so far (`counts`) + within-batch ordinal, keeping every
    staged sample exact. Keys dense within this batch (> C samples)
    fall back to batch-local weighted-midpoint-quantile k-buckets —
    statistically sound at that density — and are marked full so the
    next touch forces a compact.

    Returns (slots, overflow, dense): `dense` is how many keys took
    the k-bucket fold. `overflow` is None, or the (K,) mask of the rows
    whose staged count plus this batch would exceed C: the caller must
    `compact` those rows (zeroing their `counts`) and call again;
    `counts` is not mutated then.
    """
    cap = counts.shape[0]
    out = np.zeros(rows.shape[0], np.int32)
    valid = rows < cap
    r = rows[valid]
    n = r.shape[0]
    if n == 0:
        return out, None, 0
    g = np.bincount(r, minlength=cap).astype(np.int32)
    overflow = (counts > 0) & (counts + g > C)
    if overflow.any():
        return out, overflow, 0
    dense = g > C
    if not dense.any():
        out[valid] = counts[r] + host_ranks(r)
        counts += g
        return out, None, 0

    v = np.asarray(values)[valid]
    w = np.asarray(weights)[valid]
    order = np.lexsort((v, r))
    sr, sw = r[order], w[order]
    is_start = np.empty(n, bool)
    is_start[0] = True
    np.not_equal(sr[1:], sr[:-1], out=is_start[1:])
    starts = np.flatnonzero(is_start)
    ends = np.r_[starts[1:], n]
    seg = np.cumsum(is_start) - 1
    cw = np.cumsum(sw)
    gbase = np.where(starts > 0, cw[np.maximum(starts - 1, 0)], 0.0)
    gtot = cw[ends - 1] - gbase
    prefix = cw - sw - gbase[seg]
    q_mid = (prefix + 0.5 * sw) / np.maximum(gtot[seg], 1e-30)
    kq = COMPRESSION * (
        np.arcsin(np.clip(2.0 * q_mid - 1.0, -1.0, 1.0)) / math.pi + 0.5)
    qslot = np.clip(np.floor(kq).astype(np.int32), 0, C - 1)
    ranks_sorted = (np.arange(n, dtype=np.int32)
                    - starts[seg].astype(np.int32))
    park_sorted = counts[sr] + ranks_sorted
    slot_sorted = np.where(dense[sr], qslot, park_sorted)
    sl = np.empty(n, np.int32)
    sl[order] = slot_sorted
    out[valid] = sl
    counts += g
    counts[dense] = C  # full: next touch of a dense key forces a compact
    return out, None, int(np.count_nonzero(dense))


def batch_slots(rows, values, weights, num_keys):
    """Slots for a standalone single batch (fresh staging)."""
    counts = np.zeros(num_keys, np.int32)
    slots, _, _ = host_slots(np.asarray(rows), values, weights, counts)
    return slots


# one-hot workspace budget per lax.map chunk: 2^25 f32 elements = 128 MB.
# Rows per chunk derive from it, so a wide merge (J = shards x 2C) gets
# proportionally fewer rows per chunk instead of a multi-GB workspace.
_REDUCE_BUDGET_ELEMS = 1 << 25


def _segment_reduce_sorted(bucket, sw, swv):
    """Per-row segment sums of `sw`/`swv` grouped by `bucket` (K, J) into
    C buckets. Backend-adaptive at trace time: TPU uses a one-hot batched
    matmul (the MXU segment-reduce — per-row `take_along_axis` gathers
    measured ~100x slower there: 1.65 s vs ~20 ms for K=100k, J=256);
    CPU (the virtual validation mesh) uses a binary-search prefix-sum
    formulation, where the same matmul is ~50x slower than gathers."""
    import jax as _jax

    if _jax.default_backend() == "tpu":
        return _segment_reduce_matmul(bucket, sw, swv)
    return _segment_reduce_gather(bucket, sw, swv)


def _segment_reduce_gather(bucket, sw, swv):
    """Prefix sums + vectorized binary search for segment boundaries:
    bucket is non-decreasing along J, so each bucket's sum is a
    difference of prefix sums at its boundary. O(K·J) memory."""
    k_rows, j = bucket.shape
    cumw = jnp.cumsum(sw, axis=-1)
    cumwv = jnp.cumsum(swv, axis=-1)
    # lo converges to #{j : bucket[k, j] <= c}; answer space [0, j] has
    # j+1 candidates, and the lo<hi guard freezes converged lanes
    lo = jnp.zeros((k_rows, C), jnp.int32)
    hi = jnp.full((k_rows, C), j, jnp.int32)
    targets = jnp.arange(C, dtype=jnp.int32)[None, :]
    for _ in range(max(1, math.ceil(math.log2(j + 1)))):
        active = lo < hi
        mid = (lo + hi) >> 1
        b_mid = jnp.take_along_axis(bucket, jnp.minimum(mid, j - 1), axis=1)
        go_right = (b_mid <= targets) & active
        lo = jnp.where(go_right, mid + 1, lo)
        hi = jnp.where(go_right | ~active, hi, mid)
    gather_at = jnp.maximum(lo - 1, 0)
    gw = jnp.where(lo > 0,
                   jnp.take_along_axis(cumw, gather_at, axis=1), 0.0)
    gwv = jnp.where(lo > 0,
                    jnp.take_along_axis(cumwv, gather_at, axis=1), 0.0)
    zero_col = jnp.zeros((k_rows, 1), jnp.float32)
    new_w = gw - jnp.concatenate([zero_col, gw[:, :-1]], axis=-1)
    new_wv = gwv - jnp.concatenate([zero_col, gwv[:, :-1]], axis=-1)
    return new_w, new_wv


def _segment_reduce_matmul(bucket, sw, swv):
    """One-hot batched matmul, chunked under `lax.map` so the (chunk, J,
    C) one-hot workspace stays bounded at any table capacity."""
    k_rows, j = bucket.shape
    kc = max(1, min(k_rows, _REDUCE_BUDGET_ELEMS // (j * C)))
    pad = (-k_rows) % kc
    if pad:
        bucket = jnp.pad(bucket, ((0, pad), (0, 0)))
        sw = jnp.pad(sw, ((0, pad), (0, 0)))
        swv = jnp.pad(swv, ((0, pad), (0, 0)))
    nblocks = (k_rows + pad) // kc

    def one_chunk(args):
        b, w, wv = args
        onehot = (b[:, :, None] ==
                  jnp.arange(C, dtype=b.dtype)[None, None, :]
                  ).astype(jnp.float32)
        stacked = jnp.stack([w, wv], axis=0)  # (2, kc, J)
        # HIGHEST: the TPU's default for an f32 matmul is one bf16 pass,
        # which rounds every weight*value sum to 8 bits (measured on a
        # v5e: 3.9e-3 relative error, vs 8.5e-8 here, at the same speed)
        out = jnp.einsum("fkj,kjc->fkc", stacked, onehot,
                         preferred_element_type=jnp.float32,
                         precision=jax.lax.Precision.HIGHEST)
        return out[0], out[1]

    shaped = lambda a: a.reshape(nblocks, kc, j)
    new_w, new_wv = jax.lax.map(
        one_chunk, (shaped(bucket), shaped(sw), shaped(swv)))
    new_w = new_w.reshape(-1, C)[:k_rows]
    new_wv = new_wv.reshape(-1, C)[:k_rows]
    return new_w, new_wv


def _recompress_sorted(sm, sw, cum):
    """Recompress per-row mean-SORTED centroids into C k-buckets with the
    contiguous-segment prefix reduce. The single source of truth for the
    recompress math: compact() (via _recompress) and the fused
    forwarding flush both go through here, so their grids cannot
    diverge."""
    tot = cum[:, -1:]
    q_mid = (cum - sw * 0.5) / jnp.maximum(tot, 1e-30)
    bucket = jnp.clip(
        jnp.floor(_k_scale(q_mid)).astype(jnp.int32), 0, C - 1)
    new_w, new_wv = _segment_reduce_sorted(bucket, sw, sw * sm)
    new_w = jnp.maximum(new_w, 0.0)  # guard cumsum-difference round-off
    new_m = jnp.where(new_w > 0, new_wv / jnp.maximum(new_w, 1e-30), 0.0)
    return new_m, new_w


def _recompress(cat_means, cat_weights, num_keys):
    """Sort a (K, J) centroid set per row by mean and recompress to C
    k-buckets."""
    sort_key = jnp.where(cat_weights > 0, cat_means, _INF)
    _, sw, sm = jax.lax.sort(
        (sort_key, cat_weights, cat_means), num_keys=1, dimension=-1)
    cum = jnp.cumsum(sw, axis=-1)
    return _recompress_sorted(sm, sw, cum)


def apply_batch(state, rows, values, weights, slots=None):
    """Ingest a COO batch of histogram samples into the staging grid.

    rows: (B,) int32 — row index per sample; row == K (out of range) marks
      padding and is dropped by every scatter.
    values: (B,) f32 sample values; weights: (B,) f32 (1/sample_rate).
    slots: (B,) int32 staging slot per sample — the key's staged count
      before this batch plus the sample's within-batch rank (host_ranks);
      None defaults to ranks alone (single-batch callers).

    Cost is O(B) scatters regardless of table capacity; callers run
    `compact` before any key overflows C staged slots (the host tracks
    occupancy) and before any read, folding staging into the main grid.
    """
    if slots is None:
        slots = batch_slots(np.asarray(rows), np.asarray(values),
                            np.asarray(weights), state["wv"].shape[0])
    return _apply_batch_jit(state, rows, values, weights, slots)


@partial(jax.jit, donate_argnums=0)
@device_scope("apply", "histogram")
def _apply_batch_jit(state, rows, values, weights, slots):
    num_keys = state["wv"].shape[0]
    valid = rows < num_keys

    # scalar per-key stats (exact, not sketched)
    w_eff = jnp.where(valid, weights, 0.0)
    vmin = jnp.where(valid, values, _INF)
    vmax = jnp.where(valid, values, -_INF)
    add = lambda a, x: a.at[rows].add(x, mode="drop")
    state = dict(state)
    state["lweight"] = add(state["lweight"], w_eff)
    state["lsum"] = add(state["lsum"], w_eff * values)
    # zero values contribute +/-Inf, matching Go's 1/0 (samplers.go:341)
    recip = jnp.where(valid, weights / values, 0.0)
    state["lrecip"] = add(state["lrecip"], recip)
    state["drecip"] = add(state["drecip"], recip)
    state["lmin"] = state["lmin"].at[rows].min(vmin, mode="drop")
    state["lmax"] = state["lmax"].at[rows].max(vmax, mode="drop")
    state["dmin"] = state["dmin"].at[rows].min(vmin, mode="drop")
    state["dmax"] = state["dmax"].at[rows].max(vmax, mode="drop")

    # rank-park each sample into its own staging slot (host-computed:
    # the key's staged count before this batch + within-batch rank).
    # Every staged sample keeps its exact (value, weight) — the raw temp
    # buffer of the reference (merging_digest.go:115-140) — and
    # `compact` later merges [main | staging] with the mean-sorted
    # recompress. The host compacts before any key could exceed C staged
    # slots; the min() clamp is a correctness backstop (worst case:
    # overflow samples blend in the last slot) should a caller skip that
    # discipline.
    slot = jnp.minimum(slots, C - 1)
    state["sweights"] = state["sweights"].at[rows, slot].add(
        w_eff, mode="drop")
    state["swv"] = state["swv"].at[rows, slot].add(
        w_eff * values, mode="drop")
    return state


def _fold_grids(state):
    """[main | staging] mean/weight concatenation (K, 2C)."""
    main_w = state["weights"]
    main_m = jnp.where(
        main_w > 0, state["wv"] / jnp.maximum(main_w, 1e-30), 0.0)
    stage_w = state["sweights"]
    stage_m = jnp.where(
        stage_w > 0, state["swv"] / jnp.maximum(stage_w, 1e-30), 0.0)
    cat_m = jnp.concatenate([main_m, stage_m], axis=-1)
    cat_w = jnp.concatenate([main_w, stage_w], axis=-1)
    return cat_m, cat_w


@partial(jax.jit, donate_argnums=0)
@device_scope("compact", "histogram")
def compact(state, fold):
    """Fold the staging grid of the rows that the (K,) bool mask `fold`
    marks into their main grid with the mean-sorted recompress, leaving
    their staging empty; every other row keeps both grids as they are
    (a recompress costs a row accuracy, so no row takes one it does not
    need). Run when a row's staging would overflow (`host_slots`).

    Returns (state, done). `done` is a scalar of the recompressed grid
    that no later program donates: the handle a completion stamp may
    hold while the next apply consumes `state` (core/deviceobs.py)."""
    state = dict(state)
    cat_m, cat_w = _fold_grids(state)
    new_m, new_w = _recompress(cat_m, cat_w, state["wv"].shape[0])
    keep = ~fold[:, None]
    state["weights"] = jnp.where(keep, state["weights"], new_w)
    state["wv"] = jnp.where(keep, state["wv"], new_m * new_w)
    state["sweights"] = jnp.where(keep, state["sweights"], 0.0)
    state["swv"] = jnp.where(keep, state["swv"], 0.0)
    return state, new_w[-1, -1]


@jax.jit
@device_scope("compact", "histogram")
def recompress_state(state):
    """Re-tighten every row's slot grid (staging folded in): sort slots by
    mean and re-bucket by combined prefix weights. Exists for external
    callers merging raw grids (e.g. the mesh collective plane)."""
    state = dict(state)
    cat_m, cat_w = _fold_grids(state)
    new_m, new_w = _recompress(cat_m, cat_w, state["wv"].shape[0])
    state["wv"] = new_m * new_w
    state["weights"] = new_w
    state["sweights"] = jnp.zeros_like(new_w)
    state["swv"] = jnp.zeros_like(new_w)
    return state


@partial(jax.jit, donate_argnums=0)
@device_scope("merge", "histogram")
def merge_centroid_rows(state, rows, in_means, in_weights, in_min, in_max,
                        in_recip):
    """Merge externally-serialized digests into the table (the import path,
    parity with reference worker.go:444-457 / merging_digest.go:374-389).

    rows: (B,) int32 target row per incoming digest (row == K pads);
    in_means/in_weights: (B, C) centroid arrays; in_min/in_max/in_recip: (B,).
    """
    num_keys = state["wv"].shape[0]
    state = dict(state)
    state["dmin"] = state["dmin"].at[rows].min(in_min, mode="drop")
    state["dmax"] = state["dmax"].at[rows].max(in_max, mode="drop")
    state["drecip"] = state["drecip"].at[rows].add(in_recip, mode="drop")

    # overlay incoming digests on a per-key grid (same-row digests pre-blend
    # by bucket), then a full sort+recompress merges them with the store
    # (main + staging) — recompression here keeps skewed incoming digests
    # from blurring slots
    grid_w = jnp.zeros((num_keys, C), jnp.float32).at[rows].add(
        in_weights, mode="drop")
    grid_wv = jnp.zeros((num_keys, C), jnp.float32).at[rows].add(
        in_weights * in_means, mode="drop")
    grid_m = jnp.where(grid_w > 0, grid_wv / jnp.maximum(grid_w, 1e-30), 0.0)

    cat_m, cat_w = _fold_grids(state)
    cat_m = jnp.concatenate([cat_m, grid_m], axis=-1)
    cat_w = jnp.concatenate([cat_w, grid_w], axis=-1)
    new_m, new_w = _recompress(cat_m, cat_w, num_keys)
    # untouched rows keep their main/staging grids verbatim (recompressing
    # them too would be correct but would churn every row on every import)
    touched = ((jnp.sum(grid_w, axis=-1) > 0)
               | (jnp.sum(state["sweights"], axis=-1) > 0))[:, None]
    state["wv"] = jnp.where(touched, new_m * new_w, state["wv"])
    state["weights"] = jnp.where(touched, new_w, state["weights"])
    state["sweights"] = jnp.where(
        touched, jnp.zeros_like(new_w), state["sweights"])
    state["swv"] = jnp.where(touched, jnp.zeros_like(new_w), state["swv"])
    return state


def _quantiles_from_sorted(sm, sw, cum, state, percentiles):
    """Quantile interpolation over per-row mean-sorted centroids
    (parity with merging_digest.go:302-332: uniform within centroid,
    bounds at neighbor midpoints, min/max at the ends)."""
    num_keys = sm.shape[0]
    tot = cum[:, -1]
    n = jnp.sum(sw > 0, axis=-1)

    next_m = jnp.concatenate([sm[:, 1:], jnp.zeros((num_keys, 1))], axis=-1)
    idx = jnp.arange(sm.shape[-1])[None, :]
    ub = jnp.where(idx == (n - 1)[:, None], state["dmax"][:, None],
                   (next_m + sm) * 0.5)
    lb = jnp.concatenate([state["dmin"][:, None], ub[:, :-1]], axis=-1)

    ps = jnp.asarray(percentiles, jnp.float32)  # (P,)
    q_t = ps[None, :] * tot[:, None]  # (K, P)
    # first centroid index with cumw >= q_t
    i_star = jnp.sum(cum[:, None, :] < q_t[:, :, None], axis=-1)
    i_star = jnp.clip(i_star, 0, jnp.maximum(n - 1, 0)[:, None])
    g = lambda a: jnp.take_along_axis(a[:, None, :].repeat(ps.shape[0], 1),
                                      i_star[:, :, None], axis=-1)[:, :, 0]
    w_i = g(sw)
    cum_i = g(cum)
    lb_i, ub_i = g(lb), g(ub)
    proportion = (q_t - (cum_i - w_i)) / jnp.maximum(w_i, 1e-30)
    quant = lb_i + proportion * (ub_i - lb_i)
    return jnp.where((n > 0)[:, None], quant, jnp.nan)


def _flush_outputs(quant, sm, sw, cum, state):
    dcount = cum[:, -1]
    dsum = jnp.sum(sm * sw, axis=-1)
    hmean = jnp.where(state["drecip"] != 0, dcount / state["drecip"],
                      jnp.nan)
    return {
        "quantiles": quant,
        "count": dcount,
        "sum": dsum,
        "min": state["dmin"],
        "max": state["dmax"],
        "hmean": hmean,
        "lmin": state["lmin"],
        "lmax": state["lmax"],
        "lsum": state["lsum"],
        "lweight": state["lweight"],
        "lrecip": state["lrecip"],
    }


def _sorted_centroids(state, fold_staging: bool):
    """The shared flush preamble: (optionally) fold staging, then the
    per-row mean sort with weightless slots keyed to +inf. Every flush
    variant MUST go through this so the sort recipe cannot diverge
    between paths."""
    if fold_staging:
        means, weights = _fold_grids(state)
    else:
        weights = state["weights"]
        means = jnp.where(
            weights > 0, state["wv"] / jnp.maximum(weights, 1e-30), 0.0)
    sort_key = jnp.where(weights > 0, means, _INF)
    _, sw, sm = jax.lax.sort(
        (sort_key, weights, means), num_keys=1, dimension=-1)
    return sm, sw


def _pack_export(new_m, new_w, state):
    """The export layout: [means | weights | dmin dmax drecip]."""
    return jnp.concatenate(
        [new_m, new_w, state["dmin"][:, None], state["dmax"][:, None],
         state["drecip"][:, None]], axis=-1)


def _flush_quantiles_impl(state, percentiles: Sequence[float],
                          fold_staging: bool):
    sm, sw = _sorted_centroids(state, fold_staging)
    cum = jnp.cumsum(sw, axis=-1)
    quant = _quantiles_from_sorted(sm, sw, cum, state, percentiles)
    return _flush_outputs(quant, sm, sw, cum, state)


@partial(jax.jit, static_argnums=(1, 2))
@device_scope("readout", "histogram")
def flush_quantiles(state, percentiles: Sequence[float],
                    fold_staging: bool = True):
    """Compute per-key digest outputs: quantiles (K, P), plus digest count,
    sum, min, max, hmean. Interpolation parity with merging_digest.go:302-332
    (uniform within centroid, bounds at neighbor midpoints, min/max ends).
    By default staged-but-uncompacted slots are folded into the sort, so
    callers need not compact first (export_centroids does require it);
    callers that just compacted pass fold_staging=False to halve the sort
    width."""
    return _flush_quantiles_impl(state, percentiles, fold_staging)


# column order of the scalar tail in flush_quantiles_packed
FLUSH_SCALARS = ("count", "sum", "min", "max", "hmean",
                 "lmin", "lmax", "lsum", "lweight", "lrecip")


def _pack_flush(out):
    cols = [out["quantiles"]] + [out[k][:, None] for k in FLUSH_SCALARS]
    return jnp.concatenate(cols, axis=-1)


@partial(jax.jit, static_argnums=(1, 2))
@device_scope("readout", "histogram")
def flush_quantiles_packed(state, percentiles: Sequence[float],
                           fold_staging: bool = True):
    """flush_quantiles concatenated into one (K, P+10) float32 array.

    A flush over the device link pays a round-trip per array it pulls
    to host; packing the 11 outputs into a single device array makes the
    whole digest flush one transfer.
    Unpack host-side with unpack_flush."""
    return _pack_flush(_flush_quantiles_impl(state, percentiles,
                                             fold_staging))


def unpack_flush(packed, num_percentiles: int):
    """Host-side inverse of flush_quantiles_packed: one np.asarray transfer,
    then views. Returns the same dict shape flush_quantiles produces."""
    packed = np.asarray(packed)
    out = {"quantiles": packed[:, :num_percentiles]}
    for i, k in enumerate(FLUSH_SCALARS):
        out[k] = packed[:, num_percentiles + i]
    return out


@partial(jax.jit, static_argnums=(1,))
@device_scope("readout", "histogram")
def flush_export_packed(state, percentiles: Sequence[float]):
    """The forwarding flush, fused: fold staging, sort ONCE, interpolate
    quantiles from the sorted pre-merge centroids, and recompress the
    same sorted arrays into the <= C export grid — replacing the
    compact -> flush_quantiles_packed -> export_centroids sequence
    (three dispatches, two sorts, six device->host transfers) with one
    dispatch, one sort, and two transfers. Quantiles computed from the
    pre-merge centroids are at least as tight an approximation as the
    post-merge ones (finer grid, same invariant,
    merging_digest.go:140-224).

    Returns (flush_packed (K, P+10), export_packed (K, 2C+3):
    [means | weights | dmin dmax drecip]); unpack with unpack_flush /
    unpack_export."""
    sm, sw = _sorted_centroids(state, fold_staging=True)  # (K, 2C)
    cum = jnp.cumsum(sw, axis=-1)
    quant = _quantiles_from_sorted(sm, sw, cum, state, percentiles)
    flush_packed = _pack_flush(_flush_outputs(quant, sm, sw, cum, state))
    new_m, new_w = _recompress_sorted(sm, sw, cum)
    return flush_packed, _pack_export(new_m, new_w, state)


def unpack_export(export_packed):
    """Host-side inverse of flush_export_packed's export half: one
    np.asarray transfer, then views shaped like export_centroids'
    (means, weights, dmin, dmax, drecip)."""
    packed = np.asarray(export_packed)
    return (packed[:, :C], packed[:, C:2 * C], packed[:, 2 * C],
            packed[:, 2 * C + 1], packed[:, 2 * C + 2])


def pack_centroids(means, weights, cap: int = C):
    """Host-side: re-bucket an arbitrary centroid list into <= cap k-scale
    slots. Used to convert incoming serialized digests (which may carry up
    to ceil(pi*compression/2) ~ 158 centroids) into import-grid rows."""
    means = np.asarray(means, np.float64)
    weights = np.asarray(weights, np.float64)
    out_m = np.zeros((cap,), np.float32)
    out_w = np.zeros((cap,), np.float32)
    if means.size == 0 or weights.sum() <= 0:
        return out_m, out_w
    order = np.argsort(means, kind="stable")
    m, w = means[order], weights[order]
    tot = w.sum()
    q_mid = (np.cumsum(w) - w * 0.5) / tot
    k = COMPRESSION * (np.arcsin(np.clip(2 * q_mid - 1, -1, 1)) / math.pi + 0.5)
    bucket = np.clip(np.floor(k).astype(np.int64), 0, cap - 1)
    acc_w = np.zeros((cap,), np.float64)
    acc_wv = np.zeros((cap,), np.float64)
    np.add.at(acc_w, bucket, w)
    np.add.at(acc_wv, bucket, w * m)
    nz = acc_w > 0
    out_w[nz] = acc_w[nz]
    out_m[nz] = (acc_wv[nz] / acc_w[nz])
    return out_m, out_w


def pack_centroids_many(means_list, weights_list, cap: int = C):
    """Segmented pack_centroids over a whole import chunk: one lexsort +
    one scatter-add for every digest in the batch, replacing the per-key
    argsort/cumsum/add.at stack (which at 50k imported digests was ~3 s
    of host time per flush). Returns (K, cap) float32 means/weights.

    Bucketing is statistically identical to pack_centroids but not
    bit-identical: the within-segment cumsum (global cumsum minus an
    exclusive-prefix base) can round differently, flipping floor(k) at
    a bucket boundary for ~1% of digests — mass moves one adjacent
    k-scale slot, which the digest grid re-buckets on merge anyway.
    tests/test_tdigest.py pins total weight / weighted mean exactly and
    bounds the drift to adjacent slots."""
    K = len(means_list)
    out_m = np.zeros((K, cap), np.float32)
    out_w = np.zeros((K, cap), np.float32)
    if K == 0:
        return out_m, out_w
    lens = np.fromiter((len(x) for x in means_list), np.int64, K)
    if int(lens.sum()) == 0:
        return out_m, out_w
    m = np.concatenate([np.asarray(x, np.float64) for x in means_list])
    w = np.concatenate([np.asarray(x, np.float64) for x in weights_list])
    seg = np.repeat(np.arange(K), lens)
    # mean-order within each digest: stable sort by (segment, mean)
    order = np.lexsort((m, seg))
    m, w = m[order], w[order]
    tot = np.bincount(seg, weights=w, minlength=K)
    starts = np.zeros(K, np.int64)
    np.cumsum(lens[:-1], out=starts[1:])
    cw = np.cumsum(w)
    # within-segment inclusive cumsum via exclusive-prefix base; the
    # subtraction can round differently than a per-digest cumsum, which
    # may flip floor(k) at a bucket boundary — statistically identical,
    # and the digest grid re-buckets on merge anyway
    base = np.where(starts > 0, cw[starts - 1], 0.0)
    seg_cw = cw - np.repeat(base, lens)
    live = np.repeat(tot > 0, lens)
    q_mid = np.zeros_like(seg_cw)
    denom = np.repeat(np.where(tot > 0, tot, 1.0), lens)
    q_mid[live] = ((seg_cw - w * 0.5) / denom)[live]
    k = COMPRESSION * (np.arcsin(np.clip(2 * q_mid - 1, -1, 1)) / math.pi + 0.5)
    bucket = np.clip(np.floor(k).astype(np.int64), 0, cap - 1)
    flat = seg * cap + bucket
    acc_w = np.zeros(K * cap, np.float64)
    acc_wv = np.zeros(K * cap, np.float64)
    wl = np.where(live, w, 0.0)  # pack_centroids drops weightless digests
    np.add.at(acc_w, flat, wl)
    np.add.at(acc_wv, flat, wl * m)
    acc_w = acc_w.reshape(K, cap)
    acc_wv = acc_wv.reshape(K, cap)
    nz = acc_w > 0
    out_w[nz] = acc_w[nz]
    out_m[nz] = acc_wv[nz] / acc_w[nz]
    return out_m, out_w


def export_centroids(state):
    """Device->host view of the serializable digest state (forward plane).
    Caller must `compact` first so staging is folded into the main grid."""
    w = np.asarray(state["weights"])
    wv = np.asarray(state["wv"])
    means = np.divide(wv, w, out=np.zeros_like(wv), where=w > 0)
    return (means, w,
            np.asarray(state["dmin"]), np.asarray(state["dmax"]),
            np.asarray(state["drecip"]))
