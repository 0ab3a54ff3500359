"""Compilation seen through ``jax.monitoring`` (copied from
``chip_smoke.py _CompileMeter``, PR 21/22): seconds of lowering + backend
compile (a persistent-cache read counts as a compile event, with its
read time), how many backend compiles happened, and cache hits/writes.
Python tracing is left out: its events nest and would count twice."""
import threading

_DURATIONS = ("/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration")
_BACKEND = "/jax/core/compile/backend_compile_duration"


class CompileMeter:
    def __init__(self):
        import jax.monitoring

        self._lock = threading.Lock()
        self.compile_s = 0.0
        self.compiles = 0
        self.cache_hits = 0
        self.cache_writes = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_dur)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_dur(self, event, duration, **_):
        if event in _DURATIONS:
            with self._lock:
                self.compile_s += duration
                if event == _BACKEND:
                    self.compiles += 1

    def _on_event(self, event, **_):
        with self._lock:
            if event == "/jax/compilation_cache/cache_hits":
                self.cache_hits += 1
            elif event == "/jax/compilation_cache/cache_misses":
                # recorded when an executable is WRITTEN to the cache
                self.cache_writes += 1

    def snapshot(self):
        with self._lock:
            return {"compile_s": self.compile_s, "compiles": self.compiles,
                    "cache_hits": self.cache_hits,
                    "cache_writes": self.cache_writes}
