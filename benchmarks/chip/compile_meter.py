"""Backend compiles and persistent-cache loads, read from JAX's monitoring
events.

``/jax/core/compile/backend_compile_duration`` fires once for every
program that JAX hands to the backend, whether the backend compiles it or
JAX loads it from the persistent cache: the event times
``compile_or_get_cached``.  A load also fires
``/jax/compilation_cache/cache_hits`` first, in the same thread, so a
duration event that follows a hit in its thread is a load, and any other
is a compile."""
from __future__ import annotations

import threading
from typing import Tuple

import jax

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


class CompileMeter:
    """Counts from the moment it is made; ``reading()`` gives the totals:
    programs compiled, the seconds they took, and programs loaded from the
    persistent cache."""

    def __init__(self):
        self._lock = threading.Lock()
        self._hit = threading.local()
        self.compiles = 0
        self.compile_s = 0.0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, secs: float, **_) -> None:
        if event != COMPILE_EVENT:
            return
        loaded = getattr(self._hit, "pending", False)
        self._hit.pending = False
        if not loaded:
            with self._lock:
                self.compiles += 1
                self.compile_s += secs

    def _event(self, event: str, **_) -> None:
        if event == CACHE_HIT_EVENT:
            self._hit.pending = True
            with self._lock:
                self.cache_hits += 1

    def reading(self) -> Tuple[int, float, int]:
        with self._lock:
            return self.compiles, self.compile_s, self.cache_hits
