"""Where JAX's persistent compilation cache lives, and how often this
process has compiled.

The directory is part of the cache's key, so one that moves never hits:
it is either what `JAX_COMPILATION_CACHE_DIR` says or one fixed path
under the checkout, never a temporary name, a process id or the time.
"""

from __future__ import annotations

import os
import threading

CACHE_DIR_ENV = "JAX_COMPILATION_CACHE_DIR"
# JAX records this around every backend compilation, the small eager
# programs and persistent-cache hits included (jax/_src/dispatch.py).
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"

_compile_lock = threading.Lock()
_compiles = {"listening": False, "n": 0}


def _on_event_duration(event: str, _seconds: float, **_kw) -> None:
    if event == _COMPILE_EVENT:
        with _compile_lock:
            _compiles["n"] += 1


def compile_events() -> int:
    """Backend compilations in this process, from JAX's own events: the
    one counter the engine's `compiles` and the StepProfiler's
    `rec["compiles"]` are differences of. The first call registers the
    listener, so only differences mean anything."""
    with _compile_lock:
        if not _compiles["listening"]:
            import jax.monitoring

            jax.monitoring.register_event_duration_secs_listener(
                _on_event_duration)
            _compiles["listening"] = True
        return _compiles["n"]


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
