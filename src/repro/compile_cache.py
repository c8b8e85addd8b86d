"""JAX's persistent compilation cache for the repo's entry points.

``enable()`` is called by ``chip_smoke.py``, ``benchmarks/run.py`` and
the ``examples/`` scripts; importing ``repro`` never turns the cache on.

* ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself and ``enable``
  sets nothing.
* Not set: the cache lives at the fixed ``<repo>/.jax_cache`` (listed
  in ``.gitignore``). The path is never derived from a temporary name,
  a PID or the time, so a later process finds what an earlier one
  wrote.

``CacheCounter`` counts the cache's hits and writes from its creation
on.
"""
from __future__ import annotations

import os

import jax

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
DEFAULT_DIR = os.path.join(REPO_ROOT, ".jax_cache")


def enable() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if path:
        return path
    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR


class CacheCounter:
    """Persistent-cache hits and writes JAX reports while this lives."""

    _EVENTS = {"/jax/compilation_cache/cache_hits": "hits",
               "/jax/compilation_cache/cache_misses": "writes"}

    def __init__(self):
        self.hits = 0
        self.writes = 0
        jax.monitoring.register_event_listener(self._on_event)

    def _on_event(self, event: str, **_) -> None:
        name = self._EVENTS.get(event)
        if name is not None:
            setattr(self, name, getattr(self, name) + 1)
