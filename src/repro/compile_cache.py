"""Where this checkout's entry points keep JAX's persistent compilation
cache.

A cold process compiles every ``(C, T, p)`` step shape it meets; the
persistent cache lets the next process on the same machine load them
instead.  An entry's key includes the cache directory, so the directory
must not move between runs: it is either the one the environment names or
a fixed path inside the checkout — never a temporary directory, a pid or a
timestamp.
"""
from __future__ import annotations

import os
from pathlib import Path

CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache for this process and return
    its directory.  Called at the start of the entry points (``main()`` of
    the scripts and of ``repro.net.host``), never at import and never from
    the tests.

    If ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    nothing is set here; otherwise the cache goes to ``<checkout>/.jax_cache``
    (listed in ``.gitignore``)."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
