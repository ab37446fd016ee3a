"""Collective interval merges for the sharded serving plane.

The reference scales by forwarding mergeable sketch state up a two-tier
gRPC tree (local veneurs -> global veneur, flusher.go:516-591,
worker.go:410-467). On a device mesh the same tree collapses into
collectives: every shard aggregates its own slice of the key space into
a partitioned column store, and the per-interval global merge is one
reduction over the shard axis — psum for counters, masked-sum for
gauges (each key has exactly one home shard), register max for HLL,
register ADD for llhist, concat+recompress for t-digest centroids.
This module owns the jitted merge kernels and the mesh/`NamedSharding`
plumbing the live sharded tables (core/sharded_tables.py) run on; the
dryrun-shaped shard_map path lives next door in parallel/mesh.py.

Every kernel here operates on *stacked* state: a leading shard axis of
size n, laid out with `NamedSharding(mesh, P(SHARD_AXIS))` so XLA SPMD
partitions the apply (pure data parallelism, no communication) and
lowers the flush-time reductions to ICI collectives.

Exactness contract (the PR-5 llhist pin, generalized to the mesh):
with digest-home routing every row's samples land on exactly one
shard, so the counter Kahan pairs, the gauge last-write-wins value,
the llhist int32 registers, and the HLL registers merge by *selection*
— summing n-1 zeros — and the merged result is bit-identical to a
single-device table that saw the same stream.
"""

from __future__ import annotations

from functools import partial
from typing import Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from veneur_tpu.ops import device_scope

SHARD_AXIS = "shard"

# pending-buffer padding marker, shared with core/columnstore.py (kept
# numeric here to avoid a circular import; the scatter kernels drop any
# out-of-range row via mode="drop")
PAD_ROW = np.int32(2**31 - 1)


def local_mesh(devices: Sequence) -> Mesh:
    """A 1-D mesh over the given local devices, shard axis leading."""
    return Mesh(np.asarray(devices), (SHARD_AXIS,))


def shard_sharding(mesh: Mesh) -> NamedSharding:
    """Leading-axis partitioning: (n, ...) split one shard per device."""
    return NamedSharding(mesh, P(SHARD_AXIS))


def home_shards(digest64, n_shards: int) -> np.ndarray:
    """Key digest(s) -> home shard id(s). Pure function of the 64-bit
    fnv1a key digest, so every tier (ingest routing, import merges, the
    proxy's shard groups) that derives a home from the same digest
    agrees without coordination.

    Contiguous range partition — home = (digest * n) >> 64, the same
    top-bits split the proxy's ShardGroupRing uses — so each shard owns
    ONE digest range and an N->M reshard migrates at most N+M-1
    contiguous cells instead of rehashing the whole key space (the
    modulo it replaced moved ~every key on any N change). Computed in
    32-bit halves to stay exact in uint64."""
    d = np.asarray(digest64, np.uint64)
    n = np.uint64(n_shards)
    hi = d >> np.uint64(32)
    lo = d & np.uint64(0xFFFFFFFF)
    return ((hi * n + ((lo * n) >> np.uint64(32)))
            >> np.uint64(32)).astype(np.int32)


def range_bounds(n_shards: int) -> List[int]:
    """The digest-space lower bound of every shard's range under
    home_shards: shard i owns [bounds[i], bounds[i+1]) with an implicit
    final bound of 2**64. bounds[i] is the smallest digest with
    home == i (ceil(i * 2**64 / n))."""
    return [(i << 64) // n_shards + (1 if (i << 64) % n_shards else 0)
            for i in range(n_shards)]


def stack_on_mesh(mesh: Mesh, leaves: List[jnp.ndarray]) -> jnp.ndarray:
    """Assemble per-device arrays (one per mesh device, already
    resident) into a single (n, ...) jax.Array sharded on the leading
    axis — no host round-trip, no device copy."""
    n = len(leaves)
    global_shape = (n,) + leaves[0].shape
    sharding = shard_sharding(mesh)
    expanded = [leaf[None] for leaf in leaves]  # dispatched on-device
    return jax.make_array_from_single_device_arrays(
        global_shape, sharding, [x for x in expanded])


def init_stacked(mesh: Mesh, leaf_fn, num_keys: int):
    """Stacked per-shard state: `leaf_fn(num_keys)` broadcast to a
    leading shard axis and laid out across the mesh."""
    n = mesh.devices.size
    sharding = shard_sharding(mesh)

    def mk(leaf):
        return jax.device_put(
            jnp.broadcast_to(leaf[None], (n,) + leaf.shape), sharding)

    return jax.tree.map(mk, leaf_fn(num_keys))


def grow_stacked(mesh: Mesh, state, new_cap: int):
    """Pad the key axis (axis 1) of every stacked leaf to `new_cap`,
    keeping the shard-axis layout."""
    sharding = shard_sharding(mesh)

    def grow(leaf):
        pad = new_cap - leaf.shape[1]
        widths = [(0, 0), (0, pad)] + [(0, 0)] * (leaf.ndim - 2)
        return jax.device_put(jnp.pad(leaf, widths), sharding)

    return jax.tree.map(grow, state)


def mask_batch_for_shards(home: np.ndarray, n: int,
                          rows: np.ndarray) -> np.ndarray:
    """(batch,) interned rows + their home shard ids -> (n, batch) rows
    where shard i keeps only its own rows (everything else PAD_ROW, and
    therefore dropped by the scatter kernels). The stacked batch keeps
    the kernels' compiled shapes fixed — a variable-length split per
    shard would retrace on every dispatch — and under SPMD each device
    scatters only its slice, so the mask costs bandwidth, not a
    recompile."""
    mask = home[None, :] == np.arange(n, dtype=np.int32)[:, None]
    return np.where(mask, rows[None, :], PAD_ROW)


def tile_batch(n: int, col: np.ndarray) -> np.ndarray:
    """Value columns ride to every shard unchanged ((n, batch) tiles);
    the masked row column is what gates which shard applies them."""
    return np.broadcast_to(col, (n,) + col.shape)


# -- sharded apply kernels (vmap over the shard axis; SPMD partitions
# them into per-device scatters with zero communication) ---------------

@partial(jax.jit, donate_argnums=0)
@device_scope("apply", "counter")
def apply_counters_sharded(state, rows, values, rates):
    return jax.vmap(_counters_body)(state, rows, values, rates)


def _counters_body(state, rows, values, rates):
    # mirrors ops/scalars.apply_counters (Kahan-compensated scatter-add)
    # with the shard axis vmapped over it
    num_keys = state["sum"].shape[0]
    contrib = jnp.trunc(values / rates)
    part = jnp.zeros((num_keys,), jnp.float32).at[rows].add(
        contrib, mode="drop")
    y = part - state["comp"]
    t = state["sum"] + y
    comp = (t - state["sum"]) - y
    return {"sum": t, "comp": comp}


def _gauges_body(state, rows, values):
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
@device_scope("apply", "gauge")
def apply_gauges_sharded(state, rows, values):
    return jax.vmap(_gauges_body)(state, rows, values)


# import-path gauge merge: same LWW body, same masked-batch shape (the
# import path routes each stub to its home shard's batch row) — an
# alias, so the kernel compiles once for both call sites
merge_gauges_sharded = apply_gauges_sharded


@partial(jax.jit, donate_argnums=0)
@device_scope("apply", "llhist")
def apply_llhist_sharded(regs, rows, bin_idx, weight):
    """(n, K, BINS_PAD) int32 stacked registers += masked batch."""
    def body(r, rw, bi, w):
        return r.at[rw, bi].add(w, mode="drop")
    return jax.vmap(body)(regs, rows, bin_idx, weight)


@partial(jax.jit, donate_argnums=0)
@device_scope("merge", "llhist")
def merge_llhist_rows_at(regs, shard_ids, rows, in_rows):
    """Import-path whole-row register ADD over stacked state: incoming
    row i lands at (shard_ids[i], rows[i]). Indexed scatter rather than
    a masked tile — import batches are variable-length and each row
    carries ~BINS_PAD*4 bytes, so tiling them n-fold would swamp the
    link for nothing."""
    return regs.at[shard_ids, rows].add(in_rows, mode="drop")


# -- collective interval merges ----------------------------------------
#
# Two shapes per family: the read-only merge (kept for parity tests and
# any caller that wants the stacked state to survive), and the fused
# donated merge+reset the flush readout runs — `donate_argnums=0` lets
# XLA alias the drained interval's buffers for the returned fresh
# generation, so the double-buffered flush never allocates per interval
# and the merged readout leaves the swapped-out state's HBM in place.


def _zeros_tree(state, sharding: NamedSharding):
    """The fresh generation, laid out as the donated one was: one shard
    per device. Left to the compiler, a zero constant comes back
    replicated: every device would then hold and scatter all n shards
    from the second interval on, under a recompiled apply, and the
    interval merge would reduce nothing across devices."""
    return jax.tree.map(
        lambda leaf: jax.lax.with_sharding_constraint(
            jnp.zeros_like(leaf), sharding), state)


@partial(jax.jit, donate_argnums=0, static_argnums=1)
@device_scope("merge", "counter")
def merge_counters_stacked_reset(state, sharding: NamedSharding):
    """Fused donated interval merge: (merged Kahan pair, fresh zeroed
    stacked generation aliasing the donated input). `sharding` is the
    stacked layout (`shard_sharding(mesh)`), static."""
    merged = (jnp.sum(state["sum"], axis=0), jnp.sum(state["comp"], axis=0))
    return merged, _zeros_tree(state, sharding)


@partial(jax.jit, donate_argnums=0, static_argnums=1)
@device_scope("merge", "gauge")
def merge_gauges_stacked_reset(state, sharding: NamedSharding):
    """Fused donated LWW merge: ((value, set), fresh generation)."""
    value = jnp.sum(jnp.where(state["set"], state["value"], 0.0), axis=0)
    return ((value, jnp.any(state["set"], axis=0)),
            _zeros_tree(state, sharding))


@partial(jax.jit, donate_argnums=0, static_argnums=1)
@device_scope("merge", "llhist")
def merge_llhist_stacked_reset(stacked: jnp.ndarray,
                               sharding: NamedSharding):
    """Fused donated register-ADD merge: ((K, BINS_PAD) merged
    registers, fresh stacked generation)."""
    return jnp.sum(stacked, axis=0), _zeros_tree(stacked, sharding)

@jax.jit
@device_scope("merge", "counter")
def merge_counters_stacked(state) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """(n, K) Kahan pairs -> one (K,) pair. With digest-home routing
    exactly one shard holds nonzero state per row, so the sum is pure
    selection and the pair stays exact; the host readout recovers the
    exact total in f64 exactly like the single-device path."""
    return (jnp.sum(state["sum"], axis=0), jnp.sum(state["comp"], axis=0))


@jax.jit
@device_scope("merge", "gauge")
def merge_gauges_stacked(state) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """(n, K) LWW values + set masks -> merged (value, set). Each row
    has one home shard, so `where(set, value, 0)` summed over shards IS
    the home shard's last write."""
    value = jnp.sum(jnp.where(state["set"], state["value"], 0.0), axis=0)
    return value, jnp.any(state["set"], axis=0)


@jax.jit
@device_scope("merge", "llhist")
def merge_llhist_stacked(stacked: jnp.ndarray) -> jnp.ndarray:
    """(n, K, BINS_PAD) int32 -> (K, BINS_PAD): register ADD, the exact
    merge the family exists for (associative + commutative integer
    addition — bit-identical to any other shard assignment)."""
    return jnp.sum(stacked, axis=0)


@jax.jit
@device_scope("merge", "set")
def merge_hll_stacked(stacked: jnp.ndarray) -> jnp.ndarray:
    """(n, K, M) int8 -> (K, M) register max (all-reduce-max on SPMD)."""
    return jnp.max(stacked, axis=0)


@jax.jit
@device_scope("merge", "histogram")
def merge_histo_stacked(stacked: Dict[str, jnp.ndarray]
                        ) -> Dict[str, jnp.ndarray]:
    """Per-shard t-digest states stacked on axis 0 -> one merged state.
    Concatenate every shard's centroids per key and recompress once as
    a batched kernel (the global veneur's re-insertion, reference
    worker.go:455-457); scalar stats reduce with sum/min/max. With
    digest-home routing only one shard holds centroids per key, so the
    recompress degenerates to a self-compact of the home shard's grid."""
    from veneur_tpu.ops import batch_tdigest

    w = stacked["weights"]                      # (n, K, C)
    m = jnp.where(w > 0, stacked["wv"] / jnp.maximum(w, 1e-30), 0.0)
    sw = stacked["sweights"]                    # staged-but-uncompacted
    sm = jnp.where(sw > 0, stacked["swv"] / jnp.maximum(sw, 1e-30), 0.0)
    n, num_keys, c = w.shape
    cat_m = jnp.concatenate([m, sm], axis=-1)   # (n, K, 2C)
    cat_w = jnp.concatenate([w, sw], axis=-1)
    cat_m = jnp.moveaxis(cat_m, 0, 1).reshape(num_keys, n * 2 * c)
    cat_w = jnp.moveaxis(cat_w, 0, 1).reshape(num_keys, n * 2 * c)
    new_m, new_w = batch_tdigest._recompress(cat_m, cat_w, num_keys)
    return {
        "wv": new_m * new_w,
        "weights": new_w,
        "swv": jnp.zeros_like(new_w),
        "sweights": jnp.zeros_like(new_w),
        "dmin": jnp.min(stacked["dmin"], axis=0),
        "dmax": jnp.max(stacked["dmax"], axis=0),
        "drecip": jnp.sum(stacked["drecip"], axis=0),
        "lmin": jnp.min(stacked["lmin"], axis=0),
        "lmax": jnp.max(stacked["lmax"], axis=0),
        "lsum": jnp.sum(stacked["lsum"], axis=0),
        "lweight": jnp.sum(stacked["lweight"], axis=0),
        "lrecip": jnp.sum(stacked["lrecip"], axis=0),
    }
