"""Backend compilations as JAX reports them (after ``chip_smoke.Compiles``):
when each ended and how long it took, and the persistent cache's hits and
misses.  Only the process that holds the chip can listen."""

from __future__ import annotations

import time


class Compiles:
    def __init__(self) -> None:
        import jax

        self.events: list = []  # [monotonic time it ended, seconds]
        self.hits = 0
        self.misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_secs)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_secs(self, name, secs, **_kw) -> None:
        if name.endswith("backend_compile_duration"):
            self.events.append([time.monotonic(), secs])

    def _on_event(self, name, **_kw) -> None:
        if name.endswith("compilation_cache/cache_hits"):
            self.hits += 1
        elif name.endswith("compilation_cache/cache_misses"):
            self.misses += 1

    def take(self) -> dict:
        """What happened since the last take."""
        out = {
            "compile_s": [secs for _at, secs in self.events],
            "cache_hits": self.hits,
            "cache_misses": self.misses,
        }
        self.events, self.hits, self.misses = [], 0, 0
        return out
