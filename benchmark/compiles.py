"""Counts compiles through ``jax.monitoring``, so a run can say how many
programs set-up compiled or loaded from the persistent cache, and show
that the measured window compiled nothing."""

from __future__ import annotations

EVENTS = {
    "/jax/compilation_cache/cache_hits": "cache_hits",
    "/jax/compilation_cache/cache_misses": "cache_misses",
}
BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"


class CompileCounter:
    def __init__(self):
        import jax.monitoring
        self.counts = {"cache_hits": 0, "cache_misses": 0,
                       "backend_compiles": 0}
        jax.monitoring.register_event_listener(self._on_event)
        jax.monitoring.register_event_duration_secs_listener(
            self._on_duration)

    def _on_event(self, event: str, **_) -> None:
        key = EVENTS.get(event)
        if key:
            self.counts[key] += 1

    def _on_duration(self, event: str, duration: float, **_) -> None:
        if event == BACKEND_COMPILE:
            self.counts["backend_compiles"] += 1

    def snapshot(self) -> dict:
        return dict(self.counts)

    @staticmethod
    def since(before: dict, after: dict) -> dict:
        return {k: after[k] - before[k] for k in after}
