#!/usr/bin/env python
"""Per-kernel microbenchmark: one JSON object with the hot-op timings
that explain the pipeline numbers (the round-3 manual artifact carried
an ad-hoc version of this table; this makes it reproducible).

Covers the device kernels (t-digest apply/compact/flush-export, HLL
apply/estimate — reference analogs tdigest/merging_digest.go Add/
Compress/Quantile and vendor axiomhq hyperloglog Estimate) at the
100k-key production shape, and the native forward-plane encoder
(reference analog: flusher.go:578-591's implicit Go protobuf
serialization).

Usage: python scripts/kernel_microbench.py [--keys 100000] [--out PATH]
The platform lands in the JSON; like bench.py it exits non-zero when
JAX finds only the CPU, unless JAX_PLATFORMS=cpu asks for it by name.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import threading
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


_EMIT_LOCK = threading.Lock()
_EMITTED = False


def _emit(out: dict, path: str | None) -> None:
    """Emit the JSON record exactly once (the success path and the
    deadline timer race to call this). dict(out) snapshots under the
    GIL before json.dumps walks it, so a concurrent key assignment in
    the other thread can't blow up the serialization."""
    global _EMITTED
    with _EMIT_LOCK:
        if _EMITTED:
            return
        _EMITTED = True
        line = json.dumps(dict(out))
        print(line, flush=True)
        if path:
            with open(path, "w") as f:
                f.write(line + "\n")


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--keys", type=int, default=100_000)
    ap.add_argument("--batch", type=int, default=16_384)
    ap.add_argument("--out", default=None,
                    help="also write the JSON object to this path")
    args = ap.parse_args()

    import bench  # repo-root harness: backend check + timing helpers

    out = {}
    # own deadline guard (NOT bench.arm_deadline: its expiry path emits
    # the pipeline-schema JSON line, which is the wrong schema here and
    # would discard the kernel timings already collected) — `out` fills
    # incrementally, so expiry flushes a truncated-but-real record
    deadline = float(os.environ.get("BENCH_DEADLINE_S", 600))

    def _expire():
        out["truncated"] = f"deadline ({deadline:.0f}s) reached"
        _emit(out, args.out)
        os._exit(3)

    timer = threading.Timer(deadline, _expire)
    timer.daemon = True
    timer.start()

    median_time = bench._time_flush  # one timing methodology for both
    platform = bench.initialize_backend()
    import jax
    import numpy as np

    from veneur_tpu.ops import batch_hll, batch_tdigest, scalars

    K, B = args.keys, args.batch
    rng = np.random.default_rng(11)
    out.update(platform=platform, keys=K, batch=B)

    # ---- t-digest ----
    state = batch_tdigest.init_state(K)
    rows = rng.integers(0, K, B).astype(np.int32)
    vals = rng.normal(100, 15, B).astype(np.float32)
    wts = np.ones(B, np.float32)
    slots = batch_tdigest.host_ranks(rows)
    dev = jax.device_put((rows, vals, wts, slots))
    apply_j = jax.jit(batch_tdigest.apply_batch)
    state = apply_j(state, *dev)  # populate + compile
    out["tdigest_apply_ms_per_batch"] = round(
        median_time(lambda: apply_j(state, *dev)) * 1e3, 3)

    compact_j = jax.jit(batch_tdigest.compact)
    state = compact_j(state)
    out["tdigest_compact_ms"] = round(
        median_time(lambda: compact_j(state)) * 1e3, 2)

    ps = (0.5, 0.9, 0.99)
    out["tdigest_flush_export_ms"] = round(median_time(
        lambda: batch_tdigest.flush_export_packed(state, ps)) * 1e3, 2)

    # ---- HLL ----
    hk = max(1, K // 8)
    regs = batch_hll.init_state(hk)
    s_rows = rng.integers(0, hk, B).astype(np.int32)
    s_idx = rng.integers(0, batch_hll.M, B).astype(np.int32)
    s_rho = rng.integers(1, 30, B).astype(np.int32)
    sdev = jax.device_put((s_rows, s_idx, s_rho))
    happly_j = jax.jit(batch_hll.apply_batch)
    regs = happly_j(regs, *sdev)
    out["hll_apply_ms_per_batch"] = round(
        median_time(lambda: happly_j(regs, *sdev)) * 1e3, 3)
    out["hll_keys"] = hk
    out["hll_estimate_ms"] = round(
        median_time(lambda: batch_hll.estimate(regs)) * 1e3, 2)

    # ---- scalar families ----
    counters = scalars.init_counters(K)
    c_rows = rng.integers(0, K, B).astype(np.int32)
    c_vals = (rng.random(B) * 10).astype(np.float32)
    c_rates = np.ones(B, np.float32)
    cdev = jax.device_put((c_rows, c_vals, c_rates))
    capply_j = jax.jit(scalars.apply_counters)
    counters = capply_j(counters, *cdev)
    out["counter_apply_ms_per_batch"] = round(
        median_time(lambda: capply_j(counters, *cdev)) * 1e3, 3)

    # ---- native forward-plane encoder (host-side, no device) ----
    try:
        from veneur_tpu.core.columnstore import MetricScope, RowMeta
        from veneur_tpu.core.flusher import ForwardableState
        from veneur_tpu.forward import convert as cv
        from veneur_tpu.forward.convert import forwardable_to_wire

        FK, C = 50_000, 128
        metas = [RowMeta(name=f"mb.fwd.{i}", tags=[f"h:{i % 100}"],
                         joined_tags=f"h:{i % 100}", digest32=i,
                         scope=MetricScope.MIXED,
                         wire_type=cv.m.TIMER)
                 for i in range(FK)]
        means = rng.normal(100, 15, (FK, C)).astype(np.float32)
        weights = rng.uniform(0, 50, (FK, C)).astype(np.float32)
        weights[:, C // 2:] = 0
        fwd = ForwardableState(histograms=[
            (metas[i], means[i], weights[i], 1.0, 200.0, 0.5)
            for i in range(FK)])
        forwardable_to_wire(fwd)  # warm the per-meta frame caches
        t0 = time.perf_counter()
        wire = forwardable_to_wire(fwd)
        dt = time.perf_counter() - t0
        out["forward_encode_keys_per_s"] = round(FK / dt, 1)
        out["forward_encode_keys"] = FK
        out["forward_wire_mb"] = round(sum(len(w) for w in wire) / 1e6, 1)
    except Exception as e:
        out["forward_encode_error"] = f"{type(e).__name__}: {e}"

    _emit(out, args.out)
    return 0


if __name__ == "__main__":
    rc = main()
    sys.stdout.flush()
    os._exit(rc)
