"""Where compiled XLA programs are kept between processes.

Every process that compiles for the chip — a worker that binds a TPU
lease, the GPT-2 bench — calls :func:`configure` once.  The directory
can be placed from outside: if ``JAX_COMPILATION_CACHE_DIR`` is set,
JAX reads it itself and nothing is set in code.  Otherwise the cache is
``<checkout>/.jax_cache``, computed from this package's own location:
the path is part of what a cache entry is found by, so it must be the
same for every process and every run of one checkout — never under
``/tmp``, a session directory, a pid or a temporary name.
"""

from __future__ import annotations

import os

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"


def default_dir() -> str:
    """``<checkout>/.jax_cache`` (the directory that holds ``ray_tpu/``)."""
    pkg = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    return os.path.join(os.path.dirname(pkg), ".jax_cache")


def configure() -> str:
    """Point JAX's persistent compilation cache at its directory and
    return that directory.  Safe to call again; must run before the
    first compile it is meant to catch."""
    placed = os.environ.get(ENV_VAR)
    if placed:
        return placed
    import jax

    path = default_dir()
    jax.config.update("jax_compilation_cache_dir", path)
    return path


class CompileLog:
    """Counts the XLA backend compiles of this process from the moment
    it is made: how many, how long they took together (a program found
    in the persistent cache counts with the time it took to load), and
    how many of them the cache answered."""

    _COMPILE = "/jax/core/compile/backend_compile_duration"
    _CACHE_HIT = "/jax/compilation_cache/cache_hits"

    def __init__(self):
        import jax.monitoring

        self.count = 0
        self.seconds = 0.0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, seconds: float, **_kw) -> None:
        if event == self._COMPILE:
            self.count += 1
            self.seconds += seconds

    def _event(self, event: str, **_kw) -> None:
        if event == self._CACHE_HIT:
            self.cache_hits += 1

    def snapshot(self) -> dict:
        return {
            "count": self.count,
            "seconds": round(self.seconds, 3),
            "cache_hits": self.cache_hits,
        }
