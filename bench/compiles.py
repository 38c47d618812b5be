"""Backend compiles and persistent-cache hits, from JAX's monitoring
events (the counter of ``chip_smoke.py``)."""
from __future__ import annotations


class CompileCounter:
    """Backend compiles (persistent-cache hits included) and cache hits."""

    def __init__(self):
        import jax

        self.compiles = 0
        self.compile_s = 0.0
        self.cache_hits = 0
        jax.monitoring.register_event_listener(self._event)
        jax.monitoring.register_event_duration_secs_listener(self._duration)

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def _duration(self, event, duration_secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1
            self.compile_s += duration_secs

    def mark(self):
        return self.compiles, self.compile_s, self.cache_hits

    def since(self, mark):
        compiles, compile_s, hits = mark
        return {"compiles": self.compiles - compiles,
                "compile_s": self.compile_s - compile_s,
                "cache_hits": self.cache_hits - hits}
