"""Counts XLA compile requests and persistent-cache hits through JAX's own
monitoring events (a copy of ``chip_smoke.py``'s ``CompileCounter``, kept
here so that the program cannot move it). ``compiled`` is what went to the
compiler; inside a measured window it has to stay 0."""

from __future__ import annotations


class CompileCounter:
    def __init__(self):
        import jax
        self.requests = 0
        self.hits = 0
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.requests += 1
            self.seconds += secs

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1

    def snapshot(self) -> dict:
        return {"requests": self.requests, "cache_hits": self.hits,
                "compiled": self.requests - self.hits,
                "compile_s": round(self.seconds, 3)}

    @staticmethod
    def delta(after: dict, before: dict) -> dict:
        return {k: round(after[k] - before[k], 3) for k in after}
