"""Compile accounting from JAX's own monitoring events."""
from __future__ import annotations


class CompileClock:
    """Seconds JAX spends tracing, lowering and compiling (or loading from
    the persistent cache), the number of backend compiles, and how many of
    those the persistent cache answered."""

    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration")
    CACHE_HIT = "/jax/compilation_cache/cache_hits"

    def __init__(self):
        import jax

        self.seconds, self.compiles, self.cache_hits = 0.0, 0, 0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)
        jax.monitoring.register_event_listener(self._on_count)

    def _on_event(self, event: str, duration: float, **_) -> None:
        if event in self.EVENTS:
            self.seconds += duration
            self.compiles += event == self.EVENTS[-1]

    def _on_count(self, event: str, **_) -> None:
        self.cache_hits += event == self.CACHE_HIT

    def snapshot(self) -> dict:
        return {"compile_s": self.seconds, "compiles": self.compiles,
                "cache_hits": self.cache_hits}
