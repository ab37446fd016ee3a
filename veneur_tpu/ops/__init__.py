"""Device kernels (jitted JAX) and their plain numpy references."""

import functools


def device_scope(kind: str, family: str):
    """Decorator for the body of a jitted kernel: traces it under
    `jax.named_scope("veneur/<kind>/<family>")`, so every operation it
    lowers to carries that prefix in its `op_name` metadata and a
    profiler trace can tell one kernel's fusions from another's. `kind`
    is one of `core/deviceobs.KERNEL_KINDS` or `compact`. Metadata
    only: the compiled code and (with JAX's default
    `jax_compilation_cache_include_metadata_in_key`, false) the
    persistent cache's keys are what they were."""
    def decorate(fn):
        @functools.wraps(fn)
        def scoped(*args, **kwargs):
            import jax
            with jax.named_scope(f"veneur/{kind}/{family}"):
                return fn(*args, **kwargs)
        return scoped
    return decorate
