"""The accelerator this process holds, and where its compiled programs
are cached.

A chip belongs to one process at a time, so there is no probing from
the side: the process that is to use the device calls ``acquire()``
once — on its main thread, before any worker thread dispatches a
kernel — and a backend that cannot initialise raises there.  Code on
the serving path asks ``held()``, which never imports JAX: a process
that did not acquire a device (every ``--processes`` shard) has none.
"""

from __future__ import annotations

import os
import threading
from typing import Optional

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_held: Optional[dict] = None

# What JAX reported since ``acquire()``: backend compilations, their
# seconds, and the persistent cache's hits and misses.  Compiles run on
# whichever thread first calls a shape, two shards' merges at once.
_listening = False
_compiles_lock = threading.Lock()
_compiles = {
    "compiles": 0,
    "compile_s": 0.0,
    "compile_cache_hits": 0,
    "compile_cache_misses": 0,
}


def compile_cache_dir() -> str:
    """Where JAX's persistent compilation cache lives: the directory
    ``JAX_COMPILATION_CACHE_DIR`` names, else ``<checkout>/.jax_cache``.
    The path is part of the cache key, so it is never derived from a
    pid, a time or a temporary directory."""
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
        _CHECKOUT, ".jax_cache"
    )


def place_compile_cache() -> str:
    """Point JAX at the compile cache before the first jit.  With
    ``JAX_COMPILATION_CACHE_DIR`` set JAX reads the variable itself and
    nothing is set in code.  Every program is cached (the merge network
    recompiles per (K, P) shape, 2-30 s each on a v5e)."""
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update(
            "jax_compilation_cache_dir", compile_cache_dir()
        )
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return compile_cache_dir()


def acquire() -> dict:
    """Initialise JAX in this process and record the device it holds
    as ``{"platform", "device_kind", "count"}``.  Raises whatever the
    backend raises when it cannot initialise — callers that need the
    device let that end the process."""
    global _held
    if _held is None:
        place_compile_cache()
        import jax

        _listen_for_compiles()
        devices = jax.devices()
        _held = {
            "platform": devices[0].platform,
            "device_kind": devices[0].device_kind,
            "count": len(devices),
        }
    return _held


def _listen_for_compiles() -> None:
    """Once per process: JAX keeps a listener for good."""
    global _listening
    if not _listening:
        import jax

        jax.monitoring.register_event_duration_secs_listener(
            _on_duration
        )
        jax.monitoring.register_event_listener(_on_event)
        _listening = True


def _on_duration(name: str, secs: float, **_kw) -> None:
    if name.endswith("backend_compile_duration"):
        with _compiles_lock:
            _compiles["compiles"] += 1
            _compiles["compile_s"] += secs


def _on_event(name: str, **_kw) -> None:
    if name.endswith("compilation_cache/cache_hits"):
        key = "compile_cache_hits"
    elif name.endswith("compilation_cache/cache_misses"):
        key = "compile_cache_misses"
    else:
        return
    with _compiles_lock:
        _compiles[key] += 1


def compile_counters() -> dict:
    """Compilations JAX has reported since ``acquire()``
    (``get_stats.compaction.compiles`` and beside it).  All zero in a
    process that holds no device.  Never touches JAX."""
    with _compiles_lock:
        return dict(_compiles)


def held() -> Optional[dict]:
    """What ``acquire()`` recorded, or None if this process never
    acquired a device.  Never touches JAX."""
    return _held
