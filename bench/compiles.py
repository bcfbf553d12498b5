"""Count XLA compiles from JAX's own monitoring events (copied from the
program's ``chip_smoke.CompileLog``): the compile event spans the
persistent-cache lookup, so a warm cache shows as fewer seconds."""
from __future__ import annotations


class CompileLog:
    """Compiles and persistent-cache hits since ``register``; ``mark()``
    starts a new count, e.g. at the start of the measured window."""

    def __init__(self):
        self.secs = 0.0
        self.n = 0
        self.cache_hits = 0
        self.n_at_mark = 0

    def register(self) -> "CompileLog":
        from jax import monitoring
        monitoring.register_event_duration_secs_listener(self._on_duration)
        monitoring.register_event_listener(self._on_event)
        return self

    def _on_duration(self, name, secs, **_):
        if name == "/jax/core/compile/backend_compile_duration":
            self.secs += secs
            self.n += 1

    def _on_event(self, name, **_):
        if name == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def mark(self) -> None:
        self.n_at_mark = self.n

    @property
    def since_mark(self) -> int:
        return self.n - self.n_at_mark
