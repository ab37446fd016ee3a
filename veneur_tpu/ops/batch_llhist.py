"""Batched log-linear histograms over a (key x bin) column store.

The Circllhist layout (veneur_tpu.ops.llhist_ref) makes the whole family
one dense (K, BINS) int32 device table: the host bins values (pure
numpy, the same code path the scalar reference uses, so device and
reference can never disagree on a bin id) into (row, bin, weight)
triples and the device applies them as one scatter-add. Merges — the
interval carryover, the forward-plane import, and the cross-shard
collective — are elementwise integer additions, which is what makes the
family's distributed story *exact* rather than approximate.

The flush readout (quantiles + count + midpoint sum) is one jitted pass:
gather the bins in value order, cumulative-sum, binary-search the rank
per (row, percentile), interpolate inside the located bin.

The device table is padded to a lane-aligned width (BINS_PAD, multiple
of 128); bins past llhist_ref.BINS are never written and every readout
indexes through the value-order gather, which only covers live bins.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from veneur_tpu.ops import device_scope, llhist_ref

BINS = llhist_ref.BINS
# lane-aligned device width (TPU last-dim tile is 128)
BINS_PAD = ((BINS + 127) // 128) * 128

_ORDER = jnp.asarray(llhist_ref.ORDER, jnp.int32)
_LEFT_SORTED = jnp.asarray(llhist_ref.LEFT_SORTED, jnp.float32)
_WIDTH_SORTED = jnp.asarray(llhist_ref.WIDTH_SORTED, jnp.float32)
_BIN_MID = jnp.asarray(llhist_ref.BIN_MID, jnp.float32)


def init_state(num_keys: int) -> jnp.ndarray:
    return jnp.zeros((num_keys, BINS_PAD), jnp.int32)


@partial(jax.jit, donate_argnums=0)
@device_scope("apply", "llhist")
def apply_batch(regs, rows, bin_idx, weight):
    """Scatter-add a batch of pre-binned samples. rows == PAD_ROW marks
    padding (dropped by mode="drop")."""
    return regs.at[rows, bin_idx].add(weight, mode="drop")


@jax.jit
@device_scope("merge", "llhist")
def merge(regs_a, regs_b):
    return regs_a + regs_b


@partial(jax.jit, donate_argnums=0)
@device_scope("merge", "llhist")
def merge_rows(regs, rows, in_regs):
    """Merge whole incoming bin rows (forward-import path): register
    add. Duplicate rows in one batch accumulate, matching the scalar
    merge semantics."""
    return regs.at[rows].add(in_regs, mode="drop")


@partial(jax.jit, static_argnums=1)
@device_scope("readout", "llhist")
def flush_packed(regs, ps: tuple):
    """One-pass readout: {quantiles (K, P), count (K,), sum (K,)}.

    The returned count IS the exact int32 cumulative sum (no float
    cast); ranks and the interpolation run in f32 (quantile error is
    bin-width-bounded, so f32 rank rounding past 2^24 samples is far
    below the representation error). An untouched row reads all
    zeros."""
    c = jnp.take(regs, _ORDER, axis=1)              # value-ascending bins
    csum = jnp.cumsum(c, axis=1)                    # int32, exact
    total = csum[:, -1]                             # int32, exact
    total_f = total.astype(jnp.float32)
    # HIGHEST: a default-precision f32 matmul on the TPU is one bf16 pass
    approx_sum = jnp.matmul(regs[:, :BINS].astype(jnp.float32), _BIN_MID,
                            precision=jax.lax.Precision.HIGHEST)

    if ps:
        p_arr = jnp.asarray(ps, jnp.float32)
        ranks = jnp.maximum(jnp.clip(p_arr, 0.0, 1.0)[None, :]
                            * total_f[:, None], 0.5)  # (K, P)
        find = jax.vmap(lambda cs, r: jnp.searchsorted(cs, r, side="left"))
        idx = jnp.minimum(find(csum.astype(jnp.float32), ranks),
                          BINS - 1)                 # (K, P)
        prev = jnp.where(idx > 0,
                         jnp.take_along_axis(
                             csum, jnp.maximum(idx - 1, 0), axis=1), 0)
        cnt = (jnp.take_along_axis(csum, idx, axis=1) - prev).astype(
            jnp.float32)
        frac = jnp.where(cnt > 0, (ranks - prev.astype(jnp.float32)) / cnt,
                         0.5)
        q = (_LEFT_SORTED[idx]
             + _WIDTH_SORTED[idx] * jnp.clip(frac, 0.0, 1.0))
        q = jnp.where(total[:, None] > 0, q, 0.0)
    else:
        q = jnp.zeros((regs.shape[0], 0), jnp.float32)
    return {"quantiles": q, "count": total,
            "sum": jnp.where(total > 0, approx_sum, 0.0)}


def bin_batch_host(values, weights=None):
    """Host-side binning for a value batch: (bin ids int32, integer
    weights int32). `weights` are 1/sample_rate floats from the parser;
    they round to the nearest integer count (floor 1) because llhist
    registers are integral — the property exact merges rest on."""
    idx = llhist_ref.bin_index(values)
    if weights is None:
        w = np.ones(idx.shape, np.int32)
    else:
        # clip BEFORE the cast: registers are int32, and 1/rate for an
        # absurd-but-valid rate (@1e-10) would otherwise wrap negative
        w = np.clip(np.rint(np.asarray(weights, np.float64)),
                    1.0, np.iinfo(np.int32).max).astype(np.int32)
    return idx, w


def pad_rows_to_device(in_bins) -> np.ndarray:
    """(n, BINS)-or-(n, BINS_PAD) host bins -> (n, BINS_PAD) int32 for
    merge_rows. Counts clip into int32 (a single interval cannot
    overflow it; carryover sums live in int64 host-side)."""
    arr = np.asarray(in_bins)
    arr = np.clip(arr, 0, np.iinfo(np.int32).max).astype(np.int32)
    if arr.shape[1] == BINS_PAD:
        return arr
    out = np.zeros((arr.shape[0], BINS_PAD), np.int32)
    out[:, :arr.shape[1]] = arr[:, :BINS_PAD]
    return out
