"""What the process is running on, and where its compiled programs go.

Two questions every device-facing module asks, answered once:

``on_chip()`` — is the default JAX backend a TPU? Pallas kernels compile
through Mosaic there and run in the interpreter on the CPU test mesh;
``"auto"`` implementation choices (models/gpt.py, kernels_bench.py)
resolve on the same answer. A backend that is neither ``tpu`` nor ``cpu``
is an error: nothing in this repo was written for it, and guessing would
send kernels through the interpreter on a device nobody meant.

``enable_compile_cache()`` — the persistent compilation cache, placed from
outside. Where ``JAX_COMPILATION_CACHE_DIR`` is set JAX already reads it
and nothing here sets another directory; where it is not, the cache lives
at a FIXED path inside the checkout (the path is part of the cache key, so
a directory that moves never hits).
"""

from __future__ import annotations

import os
from typing import Dict

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
DEFAULT_CACHE_DIR = os.path.join(_REPO_ROOT, ".jax_compile_cache")


def on_chip() -> bool:
    """True on a TPU backend, False on the CPU backend, error otherwise."""
    import jax
    backend = jax.default_backend()
    if backend == "tpu":
        return True
    if backend == "cpu":
        return False
    raise RuntimeError(
        f"unsupported JAX backend {backend!r}: deepspeed_tpu runs on 'tpu' "
        f"(Pallas kernels compiled by Mosaic) or on 'cpu' (tests; kernels "
        f"in the Pallas interpreter) and refuses to guess for anything else")


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on before the first compile.
    Returns the directory in use."""
    import jax
    env_dir = os.environ.get(CACHE_ENV)
    if env_dir:
        return env_dir          # JAX reads the variable itself
    jax.config.update("jax_compilation_cache_dir", DEFAULT_CACHE_DIR)
    return DEFAULT_CACHE_DIR


class CompileCacheCounter:
    """Counts persistent-cache traffic through ``jax.monitoring``:
    ``requests`` (compiles that consulted the cache), ``hits`` (served
    from it) and ``writes`` (entries stored). Compiles under JAX's
    minimum-compile-time threshold are requests that neither hit nor
    write."""

    _EVENTS = {
        "/jax/compilation_cache/compile_requests_use_cache": "requests",
        "/jax/compilation_cache/cache_hits": "hits",
        "/jax/compilation_cache/cache_misses": "writes",
    }

    def __init__(self):
        import jax.monitoring
        self.counts: Dict[str, int] = {"requests": 0, "hits": 0, "writes": 0}
        jax.monitoring.register_event_listener(self._on_event)

    def _on_event(self, event: str, **_kw) -> None:
        key = self._EVENTS.get(event)
        if key is not None:
            self.counts[key] += 1

    def snapshot(self) -> Dict[str, int]:
        return dict(self.counts)

    def since(self, before: Dict[str, int]) -> Dict[str, int]:
        return {k: v - before.get(k, 0) for k, v in self.counts.items()}
