"""Where JAX's persistent compilation cache lives.

The directory is part of the cache's key, so one that moves never hits:
it is either what `JAX_COMPILATION_CACHE_DIR` says or one fixed path
under the checkout, never a temporary name, a process id or the time.
"""

from __future__ import annotations

import os

CACHE_DIR_ENV = "JAX_COMPILATION_CACHE_DIR"


def default_cache_dir() -> str:
    """`<checkout>/.cache/jax`, from this package's location."""
    checkout = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    return os.path.join(checkout, ".cache", "jax")


def place_compile_cache() -> str:
    """Called by a process about to compile for the chip, before or after
    it imports JAX, and without initialising a backend. Returns the
    directory in use.

    With the variable set this does nothing: JAX reads it, and workers
    inherit it from the raylet's environment. Without it the cache goes
    to `default_cache_dir()`."""
    placed = os.environ.get(CACHE_DIR_ENV)
    if placed:
        return placed
    path = default_cache_dir()
    os.makedirs(path, exist_ok=True)
    import jax

    jax.config.update("jax_compilation_cache_dir", path)
    return path
