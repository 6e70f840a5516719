"""Where JAX keeps its persistent compilation cache, and whether it hit.

Entry points that compile at full size (``chip_smoke.py``,
``benchmarks/run.py``) call :func:`enable_compile_cache` once at start-up;
importing ``repro`` never touches the cache.  A deployment that sets
``JAX_COMPILATION_CACHE_DIR`` owns the location: JAX reads the variable
itself and this module sets no directory.  Otherwise the cache lives in
one fixed directory inside the checkout (git-ignored).  The path is part
of what makes a later run find its entries, so it is never built from a
temporary name, a process id or the time.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

__all__ = ["CACHE_ENV", "DEFAULT_CACHE_DIR", "CompileCache",
           "enable_compile_cache"]

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"

_HIT = "/jax/compilation_cache/cache_hits"
_WRITE = "/jax/compilation_cache/cache_misses"


class CompileCache:
    """The cache's directory plus hit/write counts since it was enabled.

    ``hits`` counts executables JAX loaded from the cache, ``writes`` the
    ones it compiled and stored.  :meth:`close` stops the counting.
    """

    def __init__(self, directory: str):
        self.directory = directory
        self.hits = 0
        self.writes = 0
        jax.monitoring.register_event_listener(self._on_event)

    def _on_event(self, event: str, **_) -> None:
        if event == _HIT:
            self.hits += 1
        elif event == _WRITE:
            self.writes += 1

    def close(self) -> None:
        jax.monitoring.unregister_event_listener(self._on_event)

    def summary(self) -> str:
        return (f"compile cache {self.directory}: hits={self.hits} "
                f"writes={self.writes}")


def enable_compile_cache() -> CompileCache:
    """Place JAX's persistent compilation cache and start counting hits.

    With ``JAX_COMPILATION_CACHE_DIR`` set, JAX's own setting stands and
    nothing is configured here; otherwise the cache goes to
    :data:`DEFAULT_CACHE_DIR`.
    """
    directory = os.environ.get(CACHE_ENV)
    if not directory:
        directory = str(DEFAULT_CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", directory)
    return CompileCache(directory)
