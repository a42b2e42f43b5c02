"""JAX's persistent compilation cache for the entry points.

At full width, compiling the fused generation programs is most of a cold
run, so every entry point (``chip_smoke.py``, ``examples/``,
``benchmarks/``) calls ``enable_compile_cache()`` before its first
compile.  Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it
itself and nothing is set here; otherwise the cache goes to
``<checkout>/.jax_cache`` (git-ignored).  The path is fixed on purpose:
it is part of what lets a later run find the entries again.
"""
from __future__ import annotations

import os
import pathlib

import jax
from jax import monitoring

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"

# JAX records a hit when an executable is read back from the cache and a
# miss when a freshly compiled one is written to it
_EVENTS = {"/jax/compilation_cache/cache_hits": "hits",
           "/jax/compilation_cache/cache_misses": "misses"}
_counts = {"hits": 0, "misses": 0}
_listening = False


def _count(event: str, **_) -> None:
    name = _EVENTS.get(event)
    if name is not None:
        _counts[name] += 1


def enable_compile_cache() -> str:
    """Turn the persistent cache on (call before the first compile) and
    start counting its hits and misses; returns the directory:
    ``$JAX_COMPILATION_CACHE_DIR`` when set, else the in-checkout
    default."""
    global _listening
    path = os.environ.get(ENV_VAR)
    if not path:
        path = str(DEFAULT_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    if not _listening:
        monitoring.register_event_listener(_count)
        _listening = True
    return path


def cache_stats() -> dict:
    """Cache hits and misses (entries written) since the listener began."""
    return dict(_counts)
