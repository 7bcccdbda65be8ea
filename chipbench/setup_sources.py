"""Where set-up numbers come from: the persistent compilation cache rule
and a listener that sums what JAX reports about compilation. Copies of
``bench.enable_compile_cache`` and ``chip_smoke.CompileMeter`` (PR 21),
kept here so that the yardstick does not change when those scripts do."""

from __future__ import annotations

import os

CHECKOUT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its
    directory. Where ``JAX_COMPILATION_CACHE_DIR`` is set JAX reads it
    itself and no directory is set here; otherwise the cache goes to one
    fixed path inside the checkout (the path is part of the cache's key,
    so a directory that moves never hits). Every program is kept,
    however quickly it compiled: each run is a new process and pays for
    whatever is not there."""
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    path = os.path.join(CHECKOUT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def directory_bytes(path: str) -> int:
    total = 0
    for root, _, files in os.walk(path):
        for name in files:
            try:
                total += os.path.getsize(os.path.join(root, name))
            except OSError:     # an entry evicted while we walk
                pass
    return total


class CompileMeter:
    """Sums compilation as JAX reports it: seconds inside
    ``backend_compile`` (which wraps either the compiler or the cache
    read that replaced it), how many programs that was, and the
    persistent cache's hits and misses."""

    def __init__(self):
        import jax.monitoring as monitoring

        self.seconds = 0.0
        self.programs = 0
        self.hits = 0
        self.misses = 0
        monitoring.register_event_duration_secs_listener(self._duration)
        monitoring.register_event_listener(self._event)

    def _duration(self, event, seconds, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += seconds
            self.programs += 1

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1
