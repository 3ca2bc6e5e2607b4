"""Counts what reaches the XLA compiler, from ``jax.monitoring``.

Copied from ``chip_smoke.py::CompileMeter`` (PR 21), which later PRs
may change. Every jit-cache miss is one compile REQUEST (the
backend-compile event, which wraps the persistent-cache lookup);
``hits`` of them were served from the persistent cache; ``seconds`` is
what the requests took on this run's clock; ``saved`` is what the hits
would have cost cold — the compile time JAX stored with each entry when
the entry was WRITTEN, one sample per checkout, so nothing read from it
is an end-to-end metric (PERF.md section 2).
"""
from __future__ import annotations

FIELDS = ("requests", "hits", "seconds", "saved")


class CompileMeter:
    """Listens from ``__enter__`` to ``__exit__``."""

    def __init__(self):
        self.requests = self.hits = 0
        self.seconds = self.saved = 0.0

    def __enter__(self):
        import jax.monitoring
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._event)
        return self

    def __exit__(self, *exc):
        import jax.monitoring
        jax.monitoring.unregister_event_duration_listener(self._dur)
        jax.monitoring.unregister_event_listener(self._event)

    def _dur(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.requests += 1
            self.seconds += secs
        elif event == "/jax/compilation_cache/compile_time_saved_sec":
            self.saved += max(0.0, secs)

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1

    def snapshot(self) -> dict:
        return {f: getattr(self, f) for f in FIELDS}

    def since(self, before: dict) -> dict:
        return {f: getattr(self, f) - before[f] for f in FIELDS}
