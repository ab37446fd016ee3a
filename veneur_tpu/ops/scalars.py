"""Counter and gauge column kernels.

Counters accumulate trunc(value / rate) per sample (parity with reference
samplers/samplers.go:109-111, which truncates each contribution to int64);
merges add. Gauges are last-write-wins within and across batches (reference
samplers.go:160-162); merges overwrite.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from veneur_tpu.ops import device_scope


def init_counters(num_keys: int):
    """Kahan-compensated f32 accumulator pair: counters are exact integer
    counts in the reference (int64); compensated summation keeps the f32
    device accumulator exact past 2^24 samples per interval."""
    return {
        "sum": jnp.zeros((num_keys,), jnp.float32),
        "comp": jnp.zeros((num_keys,), jnp.float32),
    }


def _kahan_add(state, partial):
    y = partial - state["comp"]
    t = state["sum"] + y
    comp = (t - state["sum"]) - y
    return {"sum": t, "comp": comp}


@partial(jax.jit, donate_argnums=0)
@device_scope("apply", "counter")
def apply_counters(state, rows, values, rates):
    """rows == K marks padding; contribution is trunc(value/rate)."""
    num_keys = state["sum"].shape[0]
    contrib = jnp.trunc(values / rates)
    partial = jnp.zeros((num_keys,), jnp.float32).at[rows].add(
        contrib, mode="drop")
    return _kahan_add(state, partial)


def counter_values(state):
    return state["sum"] - state["comp"]


def init_gauges(num_keys: int):
    return {
        "value": jnp.zeros((num_keys,), jnp.float32),
        "set": jnp.zeros((num_keys,), bool),
    }


@partial(jax.jit, donate_argnums=0)
@device_scope("apply", "gauge")
def apply_gauges(state, rows, values):
    """Last-write-wins: for each row, keep the batch's last occurrence."""
    num_keys = state["value"].shape[0]
    order = jnp.arange(rows.shape[0], dtype=jnp.int32)
    last = jnp.full((num_keys,), -1, jnp.int32).at[rows].max(
        order, mode="drop")
    touched = last >= 0
    picked = values[jnp.clip(last, 0)]
    return {
        "value": jnp.where(touched, picked, state["value"]),
        "set": state["set"] | touched,
    }


@partial(jax.jit, donate_argnums=0)
@device_scope("merge", "gauge")
def merge_gauges(state, rows, in_values):
    """Import-path merge: overwrite (reference samplers.go:200-202). Within
    one import batch the last value wins, matching the reference's
    nondeterministic-order caveat (README.md:229)."""
    num_keys = state["value"].shape[0]
    order = jnp.arange(rows.shape[0], dtype=jnp.int32)
    last = jnp.full((num_keys,), -1, jnp.int32).at[rows].max(
        order, mode="drop")
    touched = last >= 0
    picked = in_values[jnp.clip(last, 0)]
    return {
        "value": jnp.where(touched, picked, state["value"]),
        "set": state["set"] | touched,
    }
