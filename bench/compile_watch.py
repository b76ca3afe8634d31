"""Counts the backend compiles and persistent-cache hits that JAX reports
in this process, through ``jax.monitoring``."""
from __future__ import annotations

from typing import Tuple

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


class CompileWatch:
    """Backend compiles (count, seconds) and persistent compile-cache hits
    from construction on."""

    def __init__(self):
        import jax
        self.count, self.seconds, self.cache_hits = 0, 0.0, 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **kw):
        if event == COMPILE_EVENT:
            self.seconds += secs
            self.count += 1

    def _event(self, event, **kw):
        if event == CACHE_HIT_EVENT:
            self.cache_hits += 1

    def mark(self) -> Tuple[int, float, int]:
        return (self.count, self.seconds, self.cache_hits)

    def since(self, before: Tuple[int, float, int]) -> Tuple[int, float, int]:
        """(compiles, compile seconds, cache hits) since ``before``."""
        return tuple(n - b for n, b in zip(self.mark(), before))
