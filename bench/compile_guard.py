"""Compile accounting from JAX's own monitoring events.

Copied from ``chip_smoke.py:_CompileLog``. A persistent-cache hit is
counted in the compile seconds too, as the time it took to load, so
``setup_s`` carries cache loads. :meth:`CompileLog.guard` refuses a timed
window in which anything compiled or was loaded from the cache.
"""
from __future__ import annotations

import contextlib

import jax


class CompileError(RuntimeError):
    """A program compiled (or was loaded) inside the timed window."""


class CompileLog:
    def __init__(self):
        self.seconds = 0.0
        self.compiles = 0
        self.hits = 0
        self.misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration
            self.compiles += 1

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def snapshot(self) -> tuple[int, int, int]:
        return self.compiles, self.hits, self.misses

    @contextlib.contextmanager
    def guard(self, what: str):
        """Raise :class:`CompileError` if the block compiled anything."""
        before = self.snapshot()
        yield
        after = self.snapshot()
        if after != before:
            raise CompileError(
                f"{what}: {after[0] - before[0]} compiles, "
                f"{after[1] - before[1]} cache hits, {after[2] - before[2]} "
                f"cache misses inside the timed window")
