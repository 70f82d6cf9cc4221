"""JAX persistent compilation cache at a fixed, outside-placeable path.

``JAX_COMPILATION_CACHE_DIR``, when set, is read by JAX itself and this
module sets no other directory. Otherwise the cache lives at ``.jax_cache``
in the checkout root: a fixed path, because the path is part of every
cache key — a directory that moved between runs would never hit.

``CacheEvents`` counts JAX's own persistent-cache hit/miss events, so a
driver can report whether a second run of the same programs loaded them
from the cache instead of compiling.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"

_HIT = "/jax/compilation_cache/cache_hits"
_MISS = "/jax/compilation_cache/cache_misses"


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns the directory in use."""
    if os.environ.get(ENV_VAR):
        # JAX reads the variable into jax_compilation_cache_dir itself
        return str(jax.config.jax_compilation_cache_dir)
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)


class CacheEvents:
    """Counts persistent-cache hits and misses (written entries) from the
    moment it is constructed. JAX offers no way to unregister a listener,
    so make one per process."""

    def __init__(self):
        self.hits = 0
        self.misses = 0
        jax.monitoring.register_event_listener(self._on_event)

    def _on_event(self, event: str, **_) -> None:
        if event == _HIT:
            self.hits += 1
        elif event == _MISS:
            self.misses += 1
