"""Batched HyperLogLog over a (key x register) column store — the TPU kernel.

The reference keeps one 2^14-register HLL per set key and inserts members
one at a time (vendored axiomhq/hyperloglog). Here the whole table is one
dense (K, 16384) int8 device array; the host hashes members (fnv1a-64 +
finalizer, veneur_tpu.ops.hll_ref.hash_member) into (row, register, rho)
triples and the device applies them as one scatter-max. A flush folds the
promoted keys' early members in one program (`fold_backlog`): sorted by
slot, then, on a TPU, a Pallas kernel over row blocks of the table. Merges —
both the cross-shard collective and the forward-plane import — are
elementwise maxima. Estimation is the LogLog-Beta formula as two row
reductions.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from veneur_tpu.ops import device_scope, hll_ref

M = hll_ref.M  # 16384 registers per key


def init_state(num_keys: int) -> jnp.ndarray:
    return jnp.zeros((num_keys, M), jnp.int8)


@partial(jax.jit, donate_argnums=0)
@device_scope("apply", "set")
def apply_batch(regs, rows, reg_idx, rho):
    """Scatter-max a batch of hashed members. rows == K marks padding."""
    return regs.at[rows, reg_idx].max(rho.astype(jnp.int8), mode="drop")


# -- the backlog fold --------------------------------------------------------
#
# A sparse table keeps a key's members on the host until the key is
# promoted at its T-th sample of the interval; at the flush those earlier
# members (its backlog, fewer than T a key) fold into the captured bank.
# `fold_backlog` takes a whole flush's backlog in one transfer, as
# (slot, register << 8 | rho) pairs, and sorts it by slot on the device.
# A pair whose slot is outside the bank is skipped: -1 (a key the host
# kept) or FOLD_PAD, which fills the arrays to their fixed length. On a
# TPU a Pallas kernel then walks the bank FOLD_ROWS rows at a time: each
# block is read into VMEM once, its run of the sorted pairs (offsets
# scalar-prefetched, the pairs copied into SMEM FOLD_WINDOW at a time) is
# max-ed into it pair by pair, and it is written back in place; a block no
# pair reaches is only copied. A duplicate (slot, register) needs no
# dedup: the pairs apply in turn, so the larger rho stays. Elsewhere (the
# CPU backend) the same pairs take one scatter-max.

FOLD_ROWS = 64
FOLD_WINDOW = 1024
FOLD_PAD = np.int32(2**31 - 1)
# pairs a dispatch carries at most: a larger backlog folds in chunks
FOLD_MAX_ENTRIES = 1 << 21


def fold_entries(rung: int, threshold: int) -> int:
    """Pairs one `fold_backlog` dispatch carries at a bank of `rung`
    slots: a whole flush's backlog, rung x (threshold - 1) (a key promotes
    at its threshold-th sample, so fewer wait), at most FOLD_MAX_ENTRIES,
    rounded up to FOLD_WINDOW. The arrays are one window longer
    (`fold_length`), always FOLD_PAD there, so the kernel's last copy
    stays inside them. A function of the rung and the threshold alone, so
    the warm-up compiles the one shape a rung's flushes use."""
    n = min(rung * max(threshold - 1, 1), FOLD_MAX_ENTRIES)
    return -(-n // FOLD_WINDOW) * FOLD_WINDOW


def fold_length(rung: int, threshold: int) -> int:
    return fold_entries(rung, threshold) + FOLD_WINDOW


def _fold_kernel(offs_ref, slot_hbm, pay_hbm, bank_in, bank_out,
                 sbuf, pbuf, wide, sem):
    rows = bank_out.shape[0]
    # the rows a pair's read-modify-write touches: one int32 vreg
    tile = 8 if rows % 8 == 0 else rows
    b = pl.program_id(0)
    start, end = offs_ref[b], offs_ref[b + 1]

    @pl.when(end == start)
    def _():
        bank_out[...] = bank_in[...]

    @pl.when(end > start)
    def _():
        # the block widened once, so that a pair touches one int32 vreg
        # (v5e's vector unit has no int8 max) and narrowed once
        wide[...] = bank_in[...].astype(jnp.int32)
        row0 = b * rows
        sub = lax.broadcasted_iota(jnp.int32, (tile, 128), 0)
        lane = lax.broadcasted_iota(jnp.int32, (tile, 128), 1)
        base = (start // FOLD_WINDOW) * FOLD_WINDOW

        def window(w, carry):
            w0 = pl.multiple_of(base + w * FOLD_WINDOW, FOLD_WINDOW)
            at = pl.ds(w0, FOLD_WINDOW)
            copies = (pltpu.make_async_copy(slot_hbm.at[at], sbuf, sem.at[0]),
                      pltpu.make_async_copy(pay_hbm.at[at], pbuf, sem.at[1]))
            for c in copies:
                c.start()
            for c in copies:
                c.wait()

            def pair(j, carry):
                # the loop is bound by its scalar work: shifts and masks
                r = sbuf[j] - row0
                pay = pbuf[j]
                reg = pay >> 8
                if tile == rows:
                    r0, r_in = 0, r
                else:
                    r0, r_in = pl.multiple_of(r & -8, 8), r & 7
                at = (pl.ds(r0, tile), pl.ds(pl.multiple_of(reg & -128, 128),
                                             128))
                t = wide[at]
                hit = (sub == r_in) & (lane == (reg & 127))
                wide[at] = jnp.where(hit, jnp.maximum(t, pay & 255), t)
                return carry

            return lax.fori_loop(jnp.maximum(start - w0, 0),
                                 jnp.minimum(end - w0, FOLD_WINDOW), pair,
                                 carry)

        lax.fori_loop(0, pl.cdiv(end - base, FOLD_WINDOW), window, 0)
        bank_out[...] = wide[...].astype(jnp.int8)


def _fold_rows(regs, slots, pay, interpret: bool = False):
    """The TPU pass over pairs sorted by slot: one Pallas kernel over
    blocks of FOLD_ROWS rows (the whole table where it has fewer), the
    table aliased in place."""
    k = regs.shape[0]
    rows = min(FOLD_ROWS, k)
    blocks = pl.cdiv(k, rows)
    # block b's pairs: from the first slot >= b * rows (a slot -1 comes
    # before block 0, FOLD_PAD after the last)
    offs = jnp.searchsorted(slots, lax.iota(jnp.int32, blocks + 1) * rows
                            ).astype(jnp.int32)
    block = pl.BlockSpec((rows, M), lambda b, offs: (b, 0))
    hbm = pl.BlockSpec(memory_space=pltpu.HBM)
    return pl.pallas_call(
        _fold_kernel,
        out_shape=jax.ShapeDtypeStruct(regs.shape, regs.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(blocks,),
            in_specs=[hbm, hbm, block], out_specs=block,
            scratch_shapes=[pltpu.SMEM((FOLD_WINDOW,), jnp.int32),
                            pltpu.SMEM((FOLD_WINDOW,), jnp.int32),
                            pltpu.VMEM((rows, M), jnp.int32),
                            pltpu.SemaphoreType.DMA((2,))]),
        input_output_aliases={3: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(offs, slots, pay, regs)


def _fold_sorted(regs, slots, pay, interpret: bool = False):
    """Sort the pairs by slot, then the TPU pass (`_fold_rows`)."""
    slots, pay = lax.sort((slots, pay), num_keys=1)
    return _fold_rows(regs, slots, pay, interpret)


@partial(jax.jit, donate_argnums=0)
@device_scope("apply", "set")
def fold_backlog(regs, slots, pay):
    """Fold (slot, register << 8 | rho) pairs into the table, skipping a
    slot outside it: one program, the table donated. The arrays are
    `fold_length` long and end in a window of FOLD_PAD. The pass is
    chosen at trace time, as `batch_tdigest` chooses its segment reduce:
    the Pallas kernel on a TPU, one scatter-max elsewhere."""
    if jax.default_backend() == "tpu":
        return _fold_sorted(regs, slots, pay)
    rows = jnp.where(slots >= 0, slots, regs.shape[0])
    return regs.at[rows, pay >> 8].max((pay & 255).astype(jnp.int8),
                                       mode="drop")


@jax.jit
@device_scope("merge", "set")
def merge(regs_a, regs_b):
    return jnp.maximum(regs_a, regs_b)


@partial(jax.jit, donate_argnums=0)
@device_scope("merge", "set")
def merge_rows(regs, rows, in_regs):
    """Merge whole incoming register rows (import path): per-key max."""
    num_keys = regs.shape[0]
    grid = jnp.zeros_like(regs).at[rows].max(in_regs, mode="drop")
    return jnp.maximum(regs, grid)


@jax.jit
@device_scope("readout", "set")
def estimate(regs):
    """Per-key LogLog-Beta estimate (parity with the reference's vendored
    estimator, hyperloglog.go:207-231 + utils.go:12-22), as two row
    reductions."""
    ez = jnp.sum(regs == 0, axis=-1).astype(jnp.float32)
    s = jnp.sum(jnp.exp2(-regs.astype(jnp.float32)), axis=-1)
    zl = jnp.log(ez + 1.0)
    beta = hll_ref._BETA14_EZ * ez
    for i, c in enumerate(hll_ref._BETA14):
        beta = beta + c * zl ** (i + 1)
    # parity: the reference adds 0.5 inside and rounds on return
    # (hyperloglog.go:225-231), so estimates are whole numbers
    est = jnp.floor(hll_ref._ALPHA * M * (M - ez) / (beta + s) + 1.0)
    # a key with no insertions estimates 0
    return jnp.where(ez >= M, 0.0, est)


def hash_members_host(members) -> np.ndarray:
    """Host-side member hashing: bytes -> (register index, rho) pairs."""
    out = np.empty((len(members), 2), np.int32)
    for i, member in enumerate(members):
        h = hll_ref.hash_member(member)
        idx, rho = hll_ref.pos_val(h)
        out[i, 0] = idx
        out[i, 1] = rho
    return out
