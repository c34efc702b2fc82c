"""Backend compile seconds and persistent-cache hits, from JAX's
monitoring events (a cache hit is timed as its retrieval). Copied from
``chip_smoke.CompileClock`` (PR 21 tree), with a count of programs
(compiled or read from the cache), so that one inside the measured window
shows."""

from __future__ import annotations


class CompileClock:
    def __init__(self):
        import jax

        self.compile_s = 0.0
        self.programs = 0
        self.cache_hits = 0
        self.names: list[str] = []

        def on_duration(event, duration, fun_name="?", **_):
            if event == "/jax/core/compile/backend_compile_duration":
                self.compile_s += duration
                self.programs += 1
                self.names.append(fun_name)

        def on_event(event, **_):
            if event == "/jax/compilation_cache/cache_hits":
                self.cache_hits += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)

    def mark(self) -> tuple[float, int, int]:
        return self.compile_s, self.programs, self.cache_hits
