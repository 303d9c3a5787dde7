"""Compilations counted from JAX's own events, in the process that holds
the chip. `engine._compile_count()` counts jit-cache entries and misses the
small eager programs (PERF.md section 7); this counts every backend
compilation, cache hit or not, that ends while the window is open."""

from __future__ import annotations

import threading

_EVENT = "/jax/core/compile/backend_compile_duration"
_lock = threading.Lock()
_state = {"installed": False, "open": False, "in_window": 0, "total": 0,
          "seconds_total": 0.0}


def install() -> None:
    """Register the listener once, before the first compilation."""
    with _lock:
        if _state["installed"]:
            return
        _state["installed"] = True
    import jax.monitoring

    def on_duration(event: str, seconds: float, **_kw) -> None:
        if event != _EVENT:
            return
        with _lock:
            _state["total"] += 1
            _state["seconds_total"] += seconds
            if _state["open"]:
                _state["in_window"] += 1

    jax.monitoring.register_event_duration_secs_listener(on_duration)


def open_window() -> None:
    with _lock:
        _state["open"] = True
        _state["in_window"] = 0


def close_window() -> dict:
    with _lock:
        _state["open"] = False
        return {"compiles_in_window": _state["in_window"],
                "compiles_total": _state["total"],
                "compile_seconds_total": _state["seconds_total"]}
