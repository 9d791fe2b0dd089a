"""Programs compiled in this process, counted by one ``jax.monitoring`` listener.

JAX reports ``/jax/core/compile/backend_compile_duration`` around every
executable it produces, whether XLA compiled it or the persistent
compilation cache loaded it.  One listener, registered on the first
:func:`read` and never removed (``jax.monitoring`` has no public way to
remove one), keeps the process-wide count and seconds of those events.
A caller takes a :func:`read` before its work and :func:`since` after,
and keeps the difference: the programs its work compiled and the seconds
that took, apart from the tracing and the execution around them.
"""

from __future__ import annotations

import threading

BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"

_lock = threading.Lock()
_count = 0
_seconds = 0.0
_registered = False


def _on_event(event: str, duration: float, **_) -> None:
    global _count, _seconds
    if event == BACKEND_COMPILE:
        with _lock:
            _count += 1
            _seconds += duration


def read() -> tuple[int, float]:
    """``(programs, seconds)`` compiled or loaded so far in this process."""

    global _registered
    if not _registered:
        with _lock:
            if not _registered:
                import jax

                jax.monitoring.register_event_duration_secs_listener(_on_event)
                _registered = True
    return _count, _seconds


def since(mark: tuple[int, float]) -> tuple[int, float]:
    """``(programs, seconds)`` compiled or loaded since ``mark = read()``."""

    n, s = read()
    return n - mark[0], s - mark[1]


__all__ = ["BACKEND_COMPILE", "read", "since"]
