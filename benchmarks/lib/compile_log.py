"""Where the seconds before a first round go, from JAX's own monitoring
events: tracing, lowering, and the backend compile — which is a read of
the persistent cache when the program is there. Copied from
``chip_smoke.CompileLog``."""

from __future__ import annotations

EVENTS = {
    "/jax/core/compile/jaxpr_trace_duration": "trace_s",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower_s",
    "/jax/core/compile/backend_compile_duration": "backend_compile_s",
    "/jax/compilation_cache/cache_hits": "cache_hits",
    "/jax/compilation_cache/cache_misses": "cache_misses",
}


class CompileLog:
    def __init__(self):
        import jax

        self.seen = dict.fromkeys(EVENTS.values(), 0)
        jax.monitoring.register_event_duration_secs_listener(
            lambda event, secs, **_: self._add(event, secs))
        jax.monitoring.register_event_listener(
            lambda event, **_: self._add(event, 1))

    def _add(self, event, amount) -> None:
        if event in EVENTS:
            self.seen[EVENTS[event]] += amount

    def snapshot(self) -> dict:
        return dict(self.seen)
