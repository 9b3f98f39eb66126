"""Counting what compiles, as chip_smoke.py counts it: JAX's own
``backend_compile_duration`` events (a persistent-cache read shows as a short
one plus a ``cache_hits`` event)."""

from __future__ import annotations


class Compiles:
    def __init__(self):
        import jax

        self.events = []            # (fun_name, seconds)
        self.cache_hits = 0
        self.cache_misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def close(self) -> None:
        """Stop listening: a run leaves nothing registered in its process."""
        import jax

        jax.monitoring.unregister_event_duration_listener(self._duration)
        jax.monitoring.unregister_event_listener(self._event)

    def _duration(self, event, seconds, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.events.append((str(kw.get("fun_name", "?")), float(seconds)))

    def _event(self, event, **kw):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.cache_misses += 1

    @property
    def count(self) -> int:
        return len(self.events)

    @property
    def seconds(self) -> float:
        return sum(s for _, s in self.events)
