"""Compile counts and device memory, read from JAX itself."""
from __future__ import annotations

import jax

__all__ = ["CompileMeter", "peak_bytes"]


class CompileMeter:
    """Backend compiles (each one a compile request: a persistent-cache hit
    or a fresh compile) and their seconds, from ``jax.monitoring`` events."""

    def __init__(self):
        self.compiles = 0
        self.seconds = 0.0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1
            self.seconds += duration

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def mark(self):
        return self.compiles, self.seconds, self.cache_hits

    def since(self, mark) -> dict:
        return dict(compiles=self.compiles - mark[0],
                    compile_s=self.seconds - mark[1],
                    cache_hits=self.cache_hits - mark[2])


def peak_bytes(devices) -> int:
    """The highest ``peak_bytes_in_use`` over ``devices`` (0 where unknown)."""
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devices]
    return int(max(peaks, default=0))
