"""Where JAX's persistent compilation cache lives — one rule for the
server, chip_smoke.py and the soak drivers.

The directory is part of every cache key's lookup, so it must not move
between runs: never a temporary name, a pid or a timestamp.

1. `JAX_COMPILATION_CACHE_DIR` set in the environment: JAX already uses
   it; no directory is set in code.
2. else the operator's `jax_compilation_cache_dir`, when configured;
3. else the fixed `<checkout>/.jax_cache` (git-ignored) — except on the
   CPU backend, where the cache then stays off: CPU entries embed the
   compiling host's machine features, and the test suite starts
   hundreds of CPU servers that have no use for a shared disk cache.

A cold start compiles for tens of seconds to minutes on a TPU v5e (by
table size; PERF.md has the readings) and a warm one for a second or
two, so every entry point goes through here.
"""

from __future__ import annotations

import os
import threading
from typing import Tuple

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def enable(configured: str = "") -> str:
    """Turn the persistent cache on under the rule above and return the
    directory in use ("" when it stays off). Thresholds are zeroed:
    restart warmth is the point, so every compile is worth caching."""
    import jax

    directory = os.environ.get(ENV_VAR, "")
    if not directory:
        if not configured and jax.default_backend() == "cpu":
            return ""
        directory = configured or DEFAULT_DIR
        os.makedirs(directory, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", directory)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return directory


def entries() -> int:
    """Entry count of the directory JAX actually uses (-1 = cache off or
    unreadable) — the hit/miss probe: a compile that ADDED entries was a
    miss, one that didn't was served from disk."""
    import jax

    directory = jax.config.jax_compilation_cache_dir
    if not directory:
        return -1
    try:
        return sum(1 for name in os.listdir(directory)
                   if name.endswith("-cache"))
    except OSError:
        return -1


_CACHE_EVENTS = {"/jax/compilation_cache/cache_hits": 0,
                 "/jax/compilation_cache/cache_misses": 1}
_counts = threading.local()
_listen_lock = threading.Lock()
_listening = False


def _on_event(event, **_kw) -> None:
    i = _CACHE_EVENTS.get(event)
    if i is not None:
        if not hasattr(_counts, "n"):
            _counts.n = [0, 0]
        _counts.n[i] += 1


def cache_events() -> Tuple[int, int]:
    """(hits, misses) of the persistent cache for the compiles THIS
    thread has asked for, from JAX's own monitoring events (the ones the
    benchmark's `CompileMeter` counts). JAX raises them on the compiling
    thread, so another thread's compile (the shape ladder's) is never
    counted here; a program already in the process's jit cache, or any
    program while the cache is off, raises neither. Take the difference
    of two calls around a dispatch."""
    global _listening
    if not _listening:
        with _listen_lock:
            if not _listening:
                import jax.monitoring

                jax.monitoring.register_event_listener(_on_event)
                _listening = True
    hits, misses = getattr(_counts, "n", (0, 0))
    return hits, misses
