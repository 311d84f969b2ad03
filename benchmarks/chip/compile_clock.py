"""Compile time and persistent-cache traffic, counted through
``jax.monitoring``: the sum of backend-compile durations (a cache hit
counts its retrieval), every backend compile, cache hits and misses, and
every trace and lowering to MLIR (a retrace whose program is already in
memory compiles nothing but still costs host time). ``compiles`` is what
``device.compiles_in_window`` reads."""
from __future__ import annotations

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
LOWER_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"


class CompileClock:
    def __init__(self):
        import jax
        self.secs, self.compiles, self.hits, self.misses = 0.0, 0, 0, 0
        self.traces, self.lowerings, self.lower_secs = 0, 0, 0.0
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._event)

    def _dur(self, event, duration_secs, **_):
        if event == COMPILE_EVENT:
            self.secs += duration_secs
            self.compiles += 1
        elif event == TRACE_EVENT:
            self.traces += 1
        elif event == LOWER_EVENT:
            self.lowerings += 1
            self.lower_secs += duration_secs

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def snapshot(self) -> dict:
        return {"compile_s": self.secs, "compiles": self.compiles,
                "cache_hits": self.hits, "cache_misses": self.misses,
                "traces": self.traces, "lowerings": self.lowerings,
                "lower_s": self.lower_secs}
