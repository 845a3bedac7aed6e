"""Sums jax's own monitoring events: seconds of tracing, lowering and backend
compilation, programs compiled and persistent-cache hits. A copy of
`chip_smoke.py`'s `CompileMeter` arithmetic (listed in PERF.md for a later PR
to fold together)."""

from __future__ import annotations

_DURATIONS = {
    "/jax/core/compile/jaxpr_trace_duration": "trace_s",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower_s",
    "/jax/core/compile/backend_compile_duration": "compile_s"}
_COUNTS = {"/jax/compilation_cache/cache_hits": "cache_hits",
           "/jax/compilation_cache/cache_misses": "cache_misses"}


class CompileMeter:
    def __init__(self):
        import jax.monitoring as mon

        self.totals = {"trace_s": 0.0, "lower_s": 0.0, "compile_s": 0.0,
                       "programs": 0, "cache_hits": 0, "cache_misses": 0}

        def on_duration(event, duration, **_):
            key = _DURATIONS.get(event)
            if key:
                self.totals[key] += duration
                if key == "compile_s":
                    self.totals["programs"] += 1

        def on_event(event, **_):
            key = _COUNTS.get(event)
            if key:
                self.totals[key] += 1

        mon.register_event_duration_secs_listener(on_duration)
        mon.register_event_listener(on_event)

    def snapshot(self) -> dict:
        return dict(self.totals)

    def since(self, before: dict) -> dict:
        return {k: self.totals[k] - before[k] for k in self.totals}
