"""Counts XLA compilations through ``jax.monitoring`` listeners (a copy of
``chip_smoke.CompileLog``, kept here so that the yardstick does not move
with the program).

Every backend compile request fires one duration event that carries the
jitted function's name, also when the persistent cache serves it; in that
case a ``cache_hits`` event fires too, and the duration is that of loading
the executable."""

from __future__ import annotations

from collections import Counter


class CompileLog:
    def __init__(self):
        import jax.monitoring

        self.programs: Counter = Counter()
        self.events: Counter = Counter()
        self.compile_seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, seconds: float, **kwargs) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.programs[kwargs.get("fun_name", "?")] += 1
            self.compile_seconds += seconds

    def _event(self, event: str, **kwargs) -> None:
        if event.startswith("/jax/compilation_cache/cache_"):
            self.events[event.rsplit("/", 1)[1]] += 1

    def mark(self) -> dict:
        """The totals so far; subtract two marks for what lies between."""
        return {
            "programs": sum(self.programs.values()),
            "compile_seconds": self.compile_seconds,
            "cache_hits": self.events["cache_hits"],
            "cache_misses": self.events["cache_misses"],
        }

    def close(self) -> None:
        import jax.monitoring

        jax.monitoring.unregister_event_duration_listener(self._duration)
        jax.monitoring.unregister_event_listener(self._event)


def between(before: dict, after: dict) -> dict:
    return {k: after[k] - before[k] for k in after}
