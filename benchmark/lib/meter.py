"""Compile seconds and persistent-cache hits and misses, read from JAX's
own monitoring events (copied from ``chip_smoke.py::CompileMeter``, which
PR 21 proved on the chip)."""

import jax.monitoring as mon


class CompileMeter:
    def __init__(self):
        self.seconds = 0.0
        self.compiles = 0
        self.hits = 0
        self.misses = 0
        mon.register_event_duration_secs_listener(self._on_duration)
        mon.register_event_listener(self._on_event)

    def _on_duration(self, event, seconds, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += seconds
            self.compiles += 1

    def _on_event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def read(self):
        """(compile seconds, backend compiles, cache hits, cache misses)
        so far; take two readings and subtract."""
        return self.seconds, self.compiles, self.hits, self.misses
