"""Backend compile time and persistent-cache hits, from JAX's own
monitoring events. A cache hit still records the compile event, with the
time it took to load the executable, so ``seconds`` is what set-up paid
for executables whether they were compiled or loaded.

Listeners stay registered for the life of the process; make one
``CompileStats`` per process.
"""
from __future__ import annotations

import jax


class CompileStats:
    def __init__(self):
        self.seconds, self.compiles, self.cache_hits = 0.0, 0, 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, secs: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += secs
            self.compiles += 1

    def _event(self, event: str, **_) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def snapshot(self) -> tuple:
        return (self.seconds, self.compiles, self.cache_hits)
