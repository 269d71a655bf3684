"""Counts taken from OUTSIDE the engine. By ``jax.monitoring``: compile
requests (an in-process jit miss, served by a compile or by a read of
the persistent cache), persistent-cache hits, and the seconds of both.
By ``gc.callbacks``: the interpreter's garbage collections and the time
each held the process (every thread waits: the GIL is held).
"""

import gc
import time


class JaxCounters:
    COMPILE = "/jax/core/compile/backend_compile_duration"
    CACHE_HIT = "/jax/compilation_cache/cache_hits"
    CACHE_READ = "/jax/compilation_cache/cache_retrieval_time_sec"

    def __init__(self):
        import jax
        self.compile_requests = 0
        self.compile_s = 0.0
        self.persistent_hits = 0
        self.cache_read_s = 0.0

        def on_duration(event, secs, **_kw):
            if event == self.COMPILE:
                self.compile_requests += 1
                self.compile_s += float(secs)
            elif event == self.CACHE_READ:
                self.cache_read_s += float(secs)

        def on_event(event, **_kw):
            if event == self.CACHE_HIT:
                self.persistent_hits += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)

    def snapshot(self) -> dict:
        return {"compile_requests": self.compile_requests,
                "compile_s": self.compile_s,
                "persistent_hits": self.persistent_hits,
                "cache_read_s": self.cache_read_s}


class GcCounters:
    def __init__(self):
        self.collections = 0
        self.full_collections = 0
        self.pause_s = 0.0
        self.longest_s = 0.0
        self._t = None
        gc.callbacks.append(self._on)

    def _on(self, phase, info):
        if phase == "start":
            self._t = time.perf_counter()
        elif self._t is not None:
            took = time.perf_counter() - self._t
            self.collections += 1
            self.full_collections += info.get("generation") == 2
            self.pause_s += took
            self.longest_s = max(self.longest_s, took)

    def snapshot(self) -> dict:
        return {"collections": self.collections,
                "full_collections": self.full_collections,
                "pause_s": self.pause_s, "longest_s": self.longest_s}
