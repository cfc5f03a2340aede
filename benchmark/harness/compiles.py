"""Backend compilations and persistent-cache traffic, from
``jax.monitoring`` (the listener ``chip_smoke.py`` uses, copied so the
benchmark depends on no file of the program for it)."""

from __future__ import annotations

import time

import jax


class Compiles:
    def __init__(self):
        self.events = []          # (monotonic time at end, seconds)
        self.hits = self.misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._evt)

    def _dur(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.events.append((time.monotonic(), secs))

    def _evt(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def total_s(self) -> float:
        return sum(s for _, s in self.events)

    def between(self, t0: float, t1: float) -> int:
        """Compilations that ended inside [t0, t1)."""
        return sum(1 for t, _ in self.events if t0 <= t < t1)
