"""Batched HyperLogLog over a (key x register) column store — the TPU kernel.

The reference keeps one 2^14-register HLL per set key and inserts members
one at a time (vendored axiomhq/hyperloglog). Here the whole table is one
dense (K, 16384) int8 device array; the host hashes members (fnv1a-64 +
finalizer, veneur_tpu.ops.hll_ref.hash_member) into (row, register, rho)
triples and the device applies them as one scatter-max. Merges — both the
cross-shard collective and the forward-plane import — are elementwise
maxima. Estimation is the LogLog-Beta formula as two row reductions.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from veneur_tpu.ops import device_scope, hll_ref

M = hll_ref.M  # 16384 registers per key


def init_state(num_keys: int) -> jnp.ndarray:
    return jnp.zeros((num_keys, M), jnp.int8)


@partial(jax.jit, donate_argnums=0)
@device_scope("apply", "set")
def apply_batch(regs, rows, reg_idx, rho):
    """Scatter-max a batch of hashed members. rows == K marks padding."""
    return regs.at[rows, reg_idx].max(rho.astype(jnp.int8), mode="drop")


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
