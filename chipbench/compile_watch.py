"""Counts programs built (compiled, or loaded from the persistent cache).

``jax.monitoring`` reports a ``backend_compile_duration`` for every
executable JAX builds, whether XLA compiled it or the persistent cache
served it; either one stalls the caller, so either one inside a measured
window makes the run incorrect. Cache hits and misses are counted beside it
so that set-up can say how warm the cache was.
"""

from __future__ import annotations

import threading
from typing import Dict

_BUILD = "/jax/core/compile/backend_compile_duration"
_EVENTS = {"/jax/compilation_cache/cache_hits": "cache_hits",
           "/jax/compilation_cache/cache_misses": "cache_misses"}


class CompileWatch:
    def __init__(self):
        import jax.monitoring
        self._lock = threading.Lock()
        self._counts = {"programs": 0, "cache_hits": 0, "cache_misses": 0}
        self._build_s = 0.0
        self._names = []
        jax.monitoring.register_event_duration_secs_listener(self._on_secs)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_secs(self, event: str, secs: float, **kw) -> None:
        if event == _BUILD:
            with self._lock:
                self._counts["programs"] += 1
                self._build_s += secs
                self._names.append(str(kw.get("fun_name", "?")))

    def _on_event(self, event: str, **_kw) -> None:
        key = _EVENTS.get(event)
        if key is not None:
            with self._lock:
                self._counts[key] += 1

    def snapshot(self) -> Dict[str, float]:
        with self._lock:
            return dict(self._counts, build_s=self._build_s)

    def since(self, before: Dict[str, float]) -> Dict[str, float]:
        now = self.snapshot()
        return {k: now[k] - before.get(k, 0) for k in now}

    def programs(self) -> int:
        with self._lock:
            return self._counts["programs"]

    def names_since(self, programs_before: int) -> list:
        """Names of the programs built after the first ``programs_before``."""
        with self._lock:
            return list(self._names[programs_before:])
