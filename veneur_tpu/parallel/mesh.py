"""Multi-chip merge plane: the two-level aggregation tree on a device mesh.

The reference scales horizontally by forwarding mergeable state (t-digests,
HLLs, global counters/gauges) from local veneurs to a global veneur over
gRPC (reference flusher.go:516-591, worker.go:410-467). On a TPU pod the
same tree maps onto the mesh: every chip aggregates its own ingest shard
into a full-width column store, and the per-interval global merge is a set
of collectives over ICI:

  counters  -> psum            (merge = addition, samplers.go:143-145)
  gauges    -> last-set-wins   (merge = overwrite, samplers.go:200-202)
  HLL       -> pmax            (merge = register max, samplers.go:299-311)
  t-digest  -> all_to_all key-sharded recompress + all_gather
               (merge = centroid re-insertion, merging_digest.go:374-389;
               each chip recompresses only its K/n key block)

Cross-host (DCN) hops between tiers use the gRPC forward plane
(veneur_tpu.forward); this module covers the intra-mesh collective path.
"""

from __future__ import annotations

from functools import partial
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from veneur_tpu.ops import batch_hll, batch_tdigest, scalars

SHARD_AXIS = "shard"


def make_mesh(n_devices: int = 0) -> Mesh:
    devices = jax.devices()
    if n_devices and len(devices) < n_devices:
        raise ValueError(
            f"make_mesh: {n_devices} devices requested but only "
            f"{len(devices)} {devices[0].platform} device(s) exist")
    if n_devices:
        devices = devices[:n_devices]
    return Mesh(np.asarray(devices), (SHARD_AXIS,))


def init_sharded_state(mesh: Mesh, num_keys: int) -> Dict:
    """Per-shard column-store state, stacked on a leading shard axis and
    sharded across the mesh. Every shard holds the same key->row layout
    (the host dictionary is replicated by construction: row ids are
    assigned by the global tier's dictionary)."""
    n = mesh.devices.size
    shard = NamedSharding(mesh, P(SHARD_AXIS))

    def mk(leaf):
        stacked = jnp.broadcast_to(leaf[None], (n,) + leaf.shape)
        return jax.device_put(stacked, shard)

    return {
        "counters": jax.tree.map(mk, scalars.init_counters(num_keys)),
        "gauges": jax.tree.map(mk, scalars.init_gauges(num_keys)),
        "histos": jax.tree.map(mk, batch_tdigest.init_state(num_keys)),
        "sets": mk(batch_hll.init_state(num_keys)),
    }


def _merge_digest_keysharded(histo_state, n: int):
    """Inside shard_map: merge every shard's centroid grids, equivalent
    to the global veneur re-inserting each local digest's centroids
    (worker.go:455-457), done as one batched kernel.

    Layout: rather than all_gather-ing all n grids onto every device and
    recompressing all K rows redundantly on each (n*K*2C received and
    K-row sort per device), the key dimension is scattered with an
    all_to_all so each device receives only its K/n key block from every
    shard (K*2C received) and recompresses K/n rows; the compact results
    are then all_gather-ed back to the replicated view. Same collective
    bytes as a reduce_scatter+all_gather pair, n-fold less compute and
    peak memory per device."""
    num_keys = histo_state["wv"].shape[0]
    # fold each shard's staging grid into its slot list
    m, w = batch_tdigest._fold_grids(histo_state)  # (K, 2C)
    pad = (-num_keys) % n
    if pad:
        m = jnp.pad(m, ((0, pad), (0, 0)))
        w = jnp.pad(w, ((0, pad), (0, 0)))
    kp = m.shape[0] // n  # keys per device after scatter
    # (n, kp, 2C) blocks; all_to_all sends block j to device j, so the
    # leading axis afterwards indexes the SOURCE shard for THIS device's
    # key block
    m_all = jax.lax.all_to_all(m.reshape(n, kp, -1), SHARD_AXIS,
                               split_axis=0, concat_axis=0, tiled=False)
    w_all = jax.lax.all_to_all(w.reshape(n, kp, -1), SHARD_AXIS,
                               split_axis=0, concat_axis=0, tiled=False)
    cat_m = jnp.moveaxis(m_all, 0, 1).reshape(kp, -1)  # (kp, n*2C)
    cat_w = jnp.moveaxis(w_all, 0, 1).reshape(kp, -1)
    local_m, local_w = batch_tdigest._recompress(cat_m, cat_w, kp)
    # gather the compact per-block results back into the replicated view;
    # device order == key-block order by construction
    g_m = jax.lax.all_gather(local_m, SHARD_AXIS)  # (n, kp, C)
    g_w = jax.lax.all_gather(local_w, SHARD_AXIS)
    new_m = g_m.reshape(-1, g_m.shape[-1])[:num_keys]
    new_w = g_w.reshape(-1, g_w.shape[-1])[:num_keys]
    return {
        "wv": new_m * new_w,
        "weights": new_w,
        "swv": jnp.zeros_like(new_w),
        "sweights": jnp.zeros_like(new_w),
        "dmin": jax.lax.pmin(histo_state["dmin"], SHARD_AXIS),
        "dmax": jax.lax.pmax(histo_state["dmax"], SHARD_AXIS),
        "drecip": jax.lax.psum(histo_state["drecip"], SHARD_AXIS),
        "lmin": jax.lax.pmin(histo_state["lmin"], SHARD_AXIS),
        "lmax": jax.lax.pmax(histo_state["lmax"], SHARD_AXIS),
        "lsum": jax.lax.psum(histo_state["lsum"], SHARD_AXIS),
        "lweight": jax.lax.psum(histo_state["lweight"], SHARD_AXIS),
        "lrecip": jax.lax.psum(histo_state["lrecip"], SHARD_AXIS),
    }


def _merge_shards_local(state):
    """The shard_map body: collective merge of per-shard stores. Inputs
    arrive with a size-1 local shard axis, which we squeeze away."""
    state = jax.tree.map(lambda a: a[0], state)
    counters = jax.lax.psum(
        scalars.counter_values(state["counters"]), SHARD_AXIS)

    # last-set-wins across shards: highest-indexed shard that saw the gauge
    idx = jax.lax.axis_index(SHARD_AXIS)
    gset = state["gauges"]["set"]
    gval = state["gauges"]["value"]
    rank = jnp.where(gset, idx + 1, 0).astype(jnp.int32)
    best = jax.lax.pmax(rank, SHARD_AXIS)
    contrib = jnp.where(rank == jnp.maximum(best, 1), gval, 0.0)
    gauges_val = jax.lax.psum(contrib, SHARD_AXIS)
    gauges_set = best > 0

    sets = jax.lax.pmax(state["sets"].astype(jnp.int32), SHARD_AXIS).astype(
        jnp.int8)
    n = jax.lax.axis_size(SHARD_AXIS)
    histos = _merge_digest_keysharded(state["histos"], n)
    return {
        "counters": counters,
        "gauges": {"value": gauges_val, "set": gauges_set},
        "sets": sets,
        "histos": histos,
    }


def merge_shards(mesh: Mesh, state: Dict) -> Dict:
    """Merge every shard's interval state into the replicated global view.
    This is the flush-time 'forward + import' of the reference collapsed
    into ICI collectives."""
    spec_in = jax.tree.map(lambda _: P(SHARD_AXIS), state)
    out_specs = jax.tree.map(lambda _: P(), {
        "counters": 0, "gauges": {"value": 0, "set": 0}, "sets": 0,
        "histos": {k: 0 for k in batch_tdigest.init_state(1)}})
    # replication check off: outputs are replicated by construction
    # (derived from all_gather/psum results) but the tracker can't prove
    # it through sort
    fn = jax.shard_map(
        _merge_shards_local, mesh=mesh, in_specs=(spec_in,),
        out_specs=out_specs, check_vma=False)
    return fn(state)


def apply_shard_batches(state: Dict, batches: Dict) -> Dict:
    """Apply per-shard COO batches (leading axis = shard) to per-shard
    stores; pure data parallelism over the shard axis, no communication."""
    def one(cstate, gstate, hstate, sstate, b):
        c = scalars.apply_counters(
            cstate, b["c_rows"], b["c_vals"], b["c_rates"])
        g = scalars.apply_gauges(gstate, b["g_rows"], b["g_vals"])
        h = batch_tdigest.apply_batch(
            hstate, b["h_rows"], b["h_vals"], b["h_wts"], b["h_slots"])
        s = batch_hll.apply_batch(
            sstate, b["s_rows"], b["s_idx"], b["s_rho"])
        return c, g, h, s

    c, g, h, s = jax.vmap(one)(
        state["counters"], state["gauges"], state["histos"], state["sets"],
        batches)
    return {"counters": c, "gauges": g, "histos": h, "sets": s}


def make_shard_batches(n: int, num_keys: int, batch: int, seed: int = 0) -> Dict:
    """Synthetic per-shard sample batches (for dryrun/bench)."""
    rng = np.random.default_rng(seed)
    f32 = np.float32
    h_rows = rng.integers(0, num_keys, (n, batch)).astype(np.int32)
    h_vals = rng.normal(100, 15, (n, batch)).astype(f32)
    h_wts = np.ones((n, batch), f32)
    h_slots = np.stack(
        [batch_tdigest.batch_slots(h_rows[i], h_vals[i], h_wts[i], num_keys)
         for i in range(n)])
    return {
        "c_rows": rng.integers(0, num_keys, (n, batch)).astype(np.int32),
        "c_vals": rng.random((n, batch)).astype(f32) * 10,
        "c_rates": np.ones((n, batch), f32),
        "g_rows": rng.integers(0, num_keys, (n, batch)).astype(np.int32),
        "g_vals": rng.random((n, batch)).astype(f32),
        "h_rows": h_rows,
        "h_vals": h_vals,
        "h_wts": h_wts,
        "h_slots": h_slots,
        "s_rows": rng.integers(0, num_keys, (n, batch)).astype(np.int32),
        "s_idx": rng.integers(0, batch_hll.M, (n, batch)).astype(np.int32),
        "s_rho": rng.integers(1, 30, (n, batch)).astype(np.int32),
    }


def full_step(mesh: Mesh, state: Dict, batches: Dict) -> Tuple[Dict, Dict]:
    """One full sharded aggregation step: per-shard batch apply (data
    parallel) followed by the collective global merge — the computation
    `__graft_entry__.dryrun_multichip` compiles over the mesh."""
    state = apply_shard_batches(state, batches)
    merged = merge_shards(mesh, state)
    return state, merged
