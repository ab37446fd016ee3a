"""The control of the comparison: the program with its t-digest centroid
sums computed one precision below what the configuration states.

The configuration states float32 at `Precision.HIGHEST` for
`ops/batch_tdigest._segment_reduce_matmul`; the control runs the same
program with that einsum at `high` (three bf16 passes: the nearest
precision below), `default` (one bf16 pass on a TPU: what the program
did before PR 22), or `bf16` (operands rounded to bfloat16 in code, for
backends such as the CPU whose DEFAULT is exact). A benchmark run never
calls this; the tests and `tests/chip_control.py` do, before any program
is traced.
"""

from __future__ import annotations


def lower_tdigest_precision(mode: str, force_matmul: bool = False) -> None:
    """Patch `batch_tdigest`'s view of `jnp.einsum`. `force_matmul` also
    sends the CPU side of the trace-time branch through the matmul
    formulation, so that a CPU test exercises the patched einsum."""
    import jax
    import jax.numpy as jnp

    from veneur_tpu.ops import batch_tdigest

    if mode == "highest":
        return
    if mode not in ("high", "default", "bf16"):
        raise ValueError(f"unknown control mode {mode!r}")

    def einsum(*args, **kwargs):
        if mode == "bf16":
            spec, *operands = args
            args = (spec, *[o.astype(jnp.bfloat16).astype(jnp.float32)
                            for o in operands])
        else:
            kwargs["precision"] = {
                "high": jax.lax.Precision.HIGH,
                "default": jax.lax.Precision.DEFAULT}[mode]
        return jnp.einsum(*args, **kwargs)

    class _Jnp:
        """`jax.numpy` as batch_tdigest sees it, einsum swapped."""

        def __getattr__(self, name):
            return einsum if name == "einsum" else getattr(jnp, name)

    batch_tdigest.jnp = _Jnp()
    if force_matmul:
        batch_tdigest._segment_reduce_gather = \
            batch_tdigest._segment_reduce_matmul
