"""Process-wide compile accounting from JAX's own monitoring events.

The benchmark's copy of the pattern in ``parallel/prewarm.py`` (which
counts per thread): here every thread's events land in one counter, so a
compile on ANY thread inside the measured window is seen.
"""

from __future__ import annotations

import threading

_HIT = "/jax/compilation_cache/cache_hits"
_MISS = "/jax/compilation_cache/cache_misses"
_BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"

_lock = threading.Lock()
# ``compile_requests``: programs that reached the backend's
# compile-or-load-from-cache step at all (JAX fires the duration event on
# a persistent-cache hit too); a window that compiles nothing counts zero.
# ``persistent_misses``: those the persistent cache did not hold, i.e. the
# fresh compiles.
_counts = {"persistent_hits": 0, "persistent_misses": 0,
           "compile_requests": 0, "compile_request_s": 0.0}
_installed = False


def install() -> None:
    global _installed
    with _lock:
        if _installed:
            return
        import jax.monitoring

        def on_event(event, **kw):
            if event == _HIT:
                with _lock:
                    _counts["persistent_hits"] += 1
            elif event == _MISS:
                with _lock:
                    _counts["persistent_misses"] += 1

        def on_duration(event, duration, **kw):
            if event == _BACKEND_COMPILE:
                with _lock:
                    _counts["compile_requests"] += 1
                    _counts["compile_request_s"] += float(duration)

        jax.monitoring.register_event_listener(on_event)
        jax.monitoring.register_event_duration_secs_listener(on_duration)
        _installed = True


def snapshot() -> dict:
    with _lock:
        return dict(_counts)


def delta(before: dict, after: dict) -> dict:
    return {k: after[k] - before[k] for k in after}
