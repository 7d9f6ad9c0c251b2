"""Host-side instruments of a run: compile events and the benchmark's spans."""
from __future__ import annotations


class CompileMonitor:
    """Compile events and persistent-cache hits and misses, from JAX's
    monitoring events (process-wide listeners; register once per process).
    A compile event is a backend compile or a load from the persistent cache;
    the misses are what was compiled."""

    COMPILE = "/jax/core/compile/backend_compile_duration"
    HIT = "/jax/compilation_cache/cache_hits"
    MISS = "/jax/compilation_cache/cache_misses"

    def __init__(self):
        import jax

        self.compile_s = 0.0
        self.compiles = 0
        self.cache_hits = 0
        self.cache_misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event, secs, **_):
        if event == self.COMPILE:
            self.compile_s += secs
            self.compiles += 1

    def _on_event(self, event, **_):
        if event == self.HIT:
            self.cache_hits += 1
        elif event == self.MISS:
            self.cache_misses += 1

    def snapshot(self) -> dict:
        return dict(compiles=self.compiles, compile_s=self.compile_s,
                    cache_hits=self.cache_hits, cache_misses=self.cache_misses)

    @staticmethod
    def delta(before: dict, after: dict) -> dict:
        return {k: after[k] - before[k] for k in before}


class Spans:
    """The benchmark's own host spans, written into the profiler's trace as
    ``TraceAnnotation``\\ s (on the device events' clock), so that device idle
    gaps can be labelled by what the host was doing."""

    @staticmethod
    def span(name: str):
        import jax

        return jax.profiler.TraceAnnotation(name)
