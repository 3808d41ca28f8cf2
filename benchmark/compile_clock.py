"""Counts XLA compiles (copy of ``chip_smoke.CompileClock``): jax's
backend-compile durations, a persistent-cache hit counted as the time it
took to load, and the cache hits."""

from __future__ import annotations


class CompileClock:
    def __init__(self):
        from jax import monitoring

        self.seconds = 0.0
        self.compiles = 0
        self.cache_hits = 0
        monitoring.register_event_duration_secs_listener(self._duration)
        monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += secs
            self.compiles += 1

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def mark(self):
        return (self.seconds, self.compiles, self.cache_hits)

    def since(self, mark):
        return {"compile_s": self.seconds - mark[0],
                "compiles": self.compiles - mark[1],
                "cache_hits": self.cache_hits - mark[2]}
