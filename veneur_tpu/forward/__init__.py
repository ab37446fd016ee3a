"""Forward plane: the local->global distribution tier over gRPC.

Wire- and route-compatible with the reference (forwardrpc/forward.proto,
samplers/metricpb/metric.proto): local servers stream mergeable state
(t-digests, HLL registers, global counters/gauges) to a global server via
/forwardrpc.Forward/SendMetricsV2; the global side merges into its device
column store with batched kernels (counter add, gauge overwrite, HLL
register max, digest recompress).

The package __init__ is lazy (PEP 562): convert/client/server pull jax
at import, and jax-free consumers — the proxy tier imports only
forward.protos and forward.wire — must not pay TPU-stack startup just
for touching a subpackage.
"""

_EXPORTS = {
    "forwardable_to_protos": "veneur_tpu.forward.convert",
    "metric_key_of_proto": "veneur_tpu.forward.convert",
    "ForwardClient": "veneur_tpu.forward.client",
    "ImportServer": "veneur_tpu.forward.server",
}

__all__ = list(_EXPORTS)


def __getattr__(name):
    target = _EXPORTS.get(name)
    if target is None:
        raise AttributeError(f"module 'veneur_tpu.forward' has no "
                             f"attribute {name!r}")
    import importlib

    return getattr(importlib.import_module(target), name)
