"""Counts what JAX compiles, loads from its persistent cache and traces,
through ``jax.monitoring`` (copied in idea from chip_smoke.CacheCounter)."""

COMPILE_EVENTS = (
    "/jax/core/compile/jaxpr_trace_duration",
    "/jax/core/compile/jaxpr_to_mlir_module_duration",
    "/jax/core/compile/backend_compile_duration",
)
BACKEND_COMPILE = COMPILE_EVENTS[2]


class JaxMonitor:
    """Running totals; ``snapshot()`` copies them, ``since(snap)`` gives
    the difference — so set-up and the measured window are told apart."""

    def __init__(self):
        import jax

        self.totals = {"compile_s": 0.0, "backend_compiles": 0,
                       "cache_hits": 0, "cache_misses": 0}
        jax.monitoring.register_event_listener(self._on_event)
        jax.monitoring.register_event_duration_secs_listener(
            self._on_duration)

    def _on_event(self, event, **_kw):
        if event == "/jax/compilation_cache/cache_hits":
            self.totals["cache_hits"] += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.totals["cache_misses"] += 1

    def _on_duration(self, event, duration, **_kw):
        if event in COMPILE_EVENTS:
            self.totals["compile_s"] += duration
        if event == BACKEND_COMPILE:
            self.totals["backend_compiles"] += 1

    def snapshot(self):
        return dict(self.totals)

    def since(self, snap):
        return {k: self.totals[k] - snap[k] for k in snap}
