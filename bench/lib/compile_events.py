"""Counts compilations by JAX's own monitoring events.

``/jax/core/compile/backend_compile_duration`` fires once per XLA compile,
or per load of a program from the persistent cache.  Tracing and lowering
events nest (a jit inside a jit) and the cache's own events (time saved,
retrieval) would count a hit twice, so only this one is counted.
"""
from __future__ import annotations

import jax

EVENT = "/jax/core/compile/backend_compile_duration"


class CompileEvents:
    def __init__(self):
        self.count = 0
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, name, secs, **_):
        if name == EVENT:
            self.count += 1
            self.seconds += secs

    def snapshot(self):
        return self.count, self.seconds
