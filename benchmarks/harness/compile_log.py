"""Counts XLA compile requests (persistent-cache hits included) and cache
hits in this process, from JAX's own monitoring events. Copied from
`chip_smoke.CompileLog`."""
from __future__ import annotations


class CompileLog:
    def __init__(self):
        import jax
        self.requests = 0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._event)

    def _dur(self, name, _secs, **_kw):
        if name == "/jax/core/compile/backend_compile_duration":
            self.requests += 1

    def _event(self, name, **_kw):
        if name == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1
