"""Compiles and persistent-cache reads seen by this process.

Copied in spirit from ``chip_smoke.py``'s ``CompileMeter``: it listens to
JAX's monitoring events.  JAX times every program it builds as a backend
compile, whether it compiled it or read it from the persistent cache, so
a program built inside the measured window shows either way; the cache's
hits and misses say which.
"""
from __future__ import annotations


class CompileMeter:
    """Counts programs compiled (or read from the cache) while entered."""

    def __init__(self):
        self.backend_compiles = 0
        self.cache_hits = 0
        self.cache_misses = 0

    def _on_duration(self, event: str, secs: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.backend_compiles += 1

    def _on_event(self, event: str, **_) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.cache_misses += 1

    @property
    def compiles(self) -> int:
        """Programs built: compiled, or read back from the persistent cache."""
        return self.backend_compiles

    def __enter__(self) -> "CompileMeter":
        import jax.monitoring as mon

        mon.register_event_duration_secs_listener(self._on_duration)
        mon.register_event_listener(self._on_event)
        return self

    def __exit__(self, *exc_info) -> None:
        import jax.monitoring as mon

        mon.unregister_event_duration_listener(self._on_duration)
        mon.unregister_event_listener(self._on_event)
