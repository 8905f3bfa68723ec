"""Backend compile seconds, compile count and persistent-cache hits, from
JAX's monitoring events (which fire in whichever thread compiles)."""
from __future__ import annotations

import threading


class CompileLog:
    COMPILE = "/jax/core/compile/backend_compile_duration"
    CACHE_HIT = "/jax/compilation_cache/cache_hits"

    def __init__(self):
        import jax

        self._lock = threading.Lock()
        self.compile_s = 0.0
        self.compiles = 0
        self.cache_hits = 0

        def on_duration(event, secs, **_):
            if event == self.COMPILE:
                with self._lock:
                    self.compile_s += secs
                    self.compiles += 1

        def on_event(event, **_):
            if event == self.CACHE_HIT:
                with self._lock:
                    self.cache_hits += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)

    def snapshot(self) -> dict:
        with self._lock:
            return {"compile_s": self.compile_s, "compiles": self.compiles,
                    "persistent_cache_hits": self.cache_hits}
