"""Engine prewarm + persistent compile cache + compile-time attribution.

Three pieces of the wavefront throughput round (docs/perf.md), all about
the same unattributed cost center — XLA engine compiles:

 - :class:`EnginePrewarmer` — a single background worker thread that
   compiles the growth ladder's NEXT capacity rungs ahead of time
   (``jax.jit(...).lower(avals).compile()``), so a growth boundary swaps
   in a ready executable instead of blocking the run on a cold compile.
   The predicted rungs are cheap to enumerate (capacities only ever
   double; see ``TpuChecker._schedule_prewarm``), and a wrong prediction
   costs one wasted background compile, never correctness: the prewarmed
   executable is the SAME program, compiled earlier.

 - :func:`enable_persistent_compile_cache` — wiring of JAX's persistent
   compilation cache, so repeated CLI/bench/smoke invocations skip engine
   compiles entirely.  WHERE the cache lives is decided by
   :func:`resolve_compile_cache_dir` alone: ``JAX_COMPILATION_CACHE_DIR``
   when the environment sets it (JAX reads it itself; this module then
   never re-points the cache), else an explicit request, else — for the
   chip entry points — one fixed directory inside the checkout.
   Thresholds are zeroed: the default min-compile-time gate would skip
   caching the small helper programs whose re-trace still costs host
   time.

 - :class:`CompileWatch` — compile-time attribution via JAX's monitoring
   events (``/jax/core/compile/backend_compile_duration``, which holds a
   cache retrieval inside it, the lowering and retrieval durations beside
   it, and the compilation-cache hit/miss events).  The engines' run
   loops snapshot it around device calls to split lowering and the
   backend's compile-or-load step out of the call without adding any ops
   to the compiled programs.  Counters are
   PER-THREAD (jax fires the events on the compiling thread), so the run
   loop's watch never absorbs the prewarm worker's background compiles —
   each watcher sees exactly its own.
"""

from __future__ import annotations

import os
import sys
import threading
import time
from typing import Callable, Optional

# -- compile-event accounting (jax monitoring) --------------------------------

_listener_lock = threading.Lock()
_listener_installed = False
# PER-THREAD accumulators: jax's monitoring events fire synchronously on
# the thread performing the compile, so thread-local counters give each
# watcher exactly its own compiles — the run loop's watch never sees the
# prewarm worker's background compiles and vice versa (a process-global
# counter attributed whoever compiled anywhere to whoever was watching).
_tls = threading.local()

# JAX's duration events this module reads (jax 0.9.0).  The backend event
# WRAPS the retrieval one: ``pxla._cached_compilation`` times
# ``compiler.compile_or_get_cached`` under it, and on a persistent-cache
# hit that call records the retrieval from inside — a hit fires both, a
# miss one.  So the two are never summed into one counter.
BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
RETRIEVAL_EVENT = "/jax/compilation_cache/cache_retrieval_time_sec"
LOWER_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"
_TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
_HIT_EVENT = "/jax/compilation_cache/cache_hits"
_MISS_EVENT = "/jax/compilation_cache/cache_misses"


def _tls_counts() -> dict:
    counts = getattr(_tls, "counts", None)
    if counts is None:
        counts = {
            # a program's whole compile-or-load step: cache key, then a
            # retrieval and deserialisation or a fresh XLA compile
            "backend_compile_secs": 0.0,
            "retrieval_secs": 0.0,  # the retrievals inside the above
            "lower_secs": 0.0,  # jaxpr -> MLIR module
            # a COUNT: an inner jit's trace lies inside the outer's, so
            # the trace durations nest and their sum is not a time
            "jaxprs_traced": 0,
            "persistent_cache_hits": 0,
            "persistent_cache_misses": 0,
        }
        _tls.counts = counts
        # open only between a CompileWatch() and its delta(): the
        # (event, end on time.monotonic, duration, retrieval inside it or
        # None) of each lower and each backend event, for the spans
        _tls.events = None
        _tls.retrieved = None  # a retrieval whose backend event is due
    return counts


def _install_listener() -> None:
    """Register the jax monitoring listeners, once."""
    global _listener_installed
    with _listener_lock:
        if _listener_installed:
            return
        import jax.monitoring

        def on_event(event, **kw):
            if event == _HIT_EVENT:
                _tls_counts()["persistent_cache_hits"] += 1
            elif event == _MISS_EVENT:
                _tls_counts()["persistent_cache_misses"] += 1

        def on_duration(event, duration, **kw):
            if event == _TRACE_EVENT:
                _tls_counts()["jaxprs_traced"] += 1
                return
            if event not in (BACKEND_COMPILE_EVENT, RETRIEVAL_EVENT,
                             LOWER_EVENT):
                return
            end = time.monotonic()
            counts = _tls_counts()
            secs = max(float(duration), 0.0)
            if event == RETRIEVAL_EVENT:
                counts["retrieval_secs"] += secs
                _tls.retrieved = secs
                return
            retrieved = None
            if event == LOWER_EVENT:
                counts["lower_secs"] += secs
            else:
                counts["backend_compile_secs"] += secs
                retrieved, _tls.retrieved = _tls.retrieved, None
            if _tls.events is not None:
                _tls.events.append((event, end, secs, retrieved))

        jax.monitoring.register_event_listener(on_event)
        jax.monitoring.register_event_duration_secs_listener(on_duration)
        _listener_installed = True


def compile_counters() -> dict:
    """Snapshot of the CALLING THREAD's compile accounting (installs the
    monitoring listener on first call)."""
    _install_listener()
    return dict(_tls_counts())


class CompileWatch:
    """Delta view over :func:`compile_counters`: ``delta()`` yields what
    the CURRENT THREAD did since the watch was made (or ``start()``-ed):
    ``compile_secs`` (the backend's compile-or-load steps, a retrieval
    counted once, inside its step), ``retrieval_secs``, ``lower_secs``,
    ``jaxprs_traced``, the persistent-cache hits and misses, and
    ``events`` — one ``(event, end, duration, retrieved)`` per lower and
    per backend step, ``end`` on ``time.monotonic``, so that each can be
    laid down as a span with a real start.  The event list is kept only
    while a watch is open; ``delta()`` hands it over and clears it (see
    module docstring)."""

    def __init__(self):
        self.start()

    def start(self) -> "CompileWatch":
        self._base = compile_counters()
        _tls.events = []
        return self

    def delta(self) -> dict:
        now = compile_counters()
        base = self._base
        events, _tls.events = _tls.events or [], None
        return {
            "compile_secs": round(
                now["backend_compile_secs"] - base["backend_compile_secs"], 6
            ),
            "retrieval_secs": round(
                now["retrieval_secs"] - base["retrieval_secs"], 6
            ),
            "lower_secs": round(now["lower_secs"] - base["lower_secs"], 6),
            "jaxprs_traced": now["jaxprs_traced"] - base["jaxprs_traced"],
            "persistent_hits": (
                now["persistent_cache_hits"] - base["persistent_cache_hits"]
            ),
            "persistent_misses": (
                now["persistent_cache_misses"]
                - base["persistent_cache_misses"]
            ),
            "events": events,
        }


# -- persistent compilation cache ---------------------------------------------

ENV_JAX_CACHE_DIR = "JAX_COMPILATION_CACHE_DIR"  # JAX's own variable
# the chip entry points' cache when the environment names none: a FIXED
# path (the directory is part of JAX's cache key story — a temp name, pid
# or timestamp never hits), inside the checkout, git-ignored
CHECKOUT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)
    ))),
    ".jax_cache",
)
ENV_PREWARM = "STATERIGHT_TPU_PREWARM"
ENV_PREDEDUP = "STATERIGHT_TPU_PREDEDUP"
ENV_POR = "STATERIGHT_TPU_POR"
ENV_SPILL = "STATERIGHT_TPU_SPILL"

_cache_lock = threading.Lock()
_cache_dir: Optional[str] = None  # what enable_persistent_compile_cache turned on


def resolve_compile_cache_dir(
    requested: Optional[str] = None, *, entry_point: bool = False
) -> Optional[str]:
    """THE answer to "where does the persistent compile cache live":

     - ``JAX_COMPILATION_CACHE_DIR`` set -> that directory, always.  A
       ``requested`` dir (``CheckerBuilder.compile_cache()`` /
       ``--compile-cache=``) that disagrees is ignored with one stderr
       line — whoever launched the process placed the cache.
     - unset -> ``requested`` if given; else :data:`CHECKOUT_CACHE_DIR`
       for the chip entry points (``entry_point=True``: chip_smoke.py,
       bench.py's device child, the CLI device verbs on an accelerator);
       else None — a plain library ``spawn_tpu()`` keeps no cache."""
    env = os.environ.get(ENV_JAX_CACHE_DIR)
    if env:
        if requested and os.path.abspath(requested) != os.path.abspath(env):
            print(
                f"stateright-tpu: {ENV_JAX_CACHE_DIR}={env} is set; "
                f"ignoring the requested compile cache dir {requested}",
                file=sys.stderr,
            )
        return env
    if requested:
        return str(requested)
    return CHECKOUT_CACHE_DIR if entry_point else None


def _point_jax_cache(path: Optional[str]) -> None:
    """The ONE place this package sets ``jax_compilation_cache_dir`` —
    and only when the environment did not (JAX already holds the
    ``JAX_COMPILATION_CACHE_DIR`` value then).  jax binds its cache
    object to the first directory it initializes with, so RE-pointing
    (or switching off) needs the cache reset; merely enabling after the
    process's first compile does not (checked on jax 0.9.0)."""
    if os.environ.get(ENV_JAX_CACHE_DIR):
        return
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    jax.config.update("jax_compilation_cache_dir", path)
    if _cache_dir is not None:
        compilation_cache.reset_cache()


def enable_persistent_compile_cache(
    path: Optional[str] = None, *, entry_point: bool = False
) -> Optional[str]:
    """Turn on JAX's persistent compilation cache at the directory
    :func:`resolve_compile_cache_dir` picks (no-op returning None when it
    picks none).  Idempotent.  Also zeroes the cache's size/compile-time
    admission thresholds so every engine program is cached, and installs
    the hit/miss listener so the flight recorder can tell a disk hit
    from a fresh compile."""
    global _cache_dir
    path = resolve_compile_cache_dir(path, entry_point=entry_point)
    if not path:
        return None
    with _cache_lock:
        if _cache_dir == path:
            return path
        import jax

        os.makedirs(path, exist_ok=True)
        _point_jax_cache(path)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        _cache_dir = path
    _install_listener()
    return path


def disable_persistent_compile_cache() -> None:
    """Undo :func:`enable_persistent_compile_cache` (tests restore global
    state; a long-lived process keeps the cache on once enabled).  Under
    ``JAX_COMPILATION_CACHE_DIR`` the directory stays JAX's."""
    global _cache_dir
    with _cache_lock:
        if _cache_dir is not None:
            _point_jax_cache(None)
            _cache_dir = None


def resolve_flag(mode: Optional[bool], env: str) -> bool:
    """Builder-flag resolution shared by the engines: an explicit builder
    setting wins; otherwise the env knob (``=1``) decides."""
    if mode is not None:
        return bool(mode)
    return os.environ.get(env, "") == "1"


# -- ahead-of-time engine prewarm ---------------------------------------------

PREWARM_THREAD_NAME = "stateright-prewarm"

# Interpreter-teardown guard: killing a daemon thread in the middle of an
# XLA compile aborts the process ("terminate called without an active
# exception"), so an atexit hook drops every queued job and waits out the
# in-flight one before Python starts tearing down C++ state.
_live_prewarmers: "weakref.WeakSet" = None  # type: ignore[assignment]
_atexit_lock = threading.Lock()


def _drain_prewarmers_at_exit() -> None:
    for p in list(_live_prewarmers or ()):
        try:
            p.close()
            p.wait_idle(120.0)
        except Exception:  # noqa: BLE001 - exit path must never raise
            pass


def _register_prewarmer(p: "EnginePrewarmer") -> None:
    global _live_prewarmers
    with _atexit_lock:
        if _live_prewarmers is None:
            import atexit
            import weakref

            _live_prewarmers = weakref.WeakSet()
            atexit.register(_drain_prewarmers_at_exit)
        _live_prewarmers.add(p)


class _Job:
    __slots__ = ("key", "build", "done", "result", "error", "compile_secs",
                 "persistent_hit", "started_t", "finished_t")

    def __init__(self, key, build):
        self.key = key
        self.build = build
        self.done = threading.Event()
        self.result = None
        self.error: Optional[BaseException] = None
        self.compile_secs = 0.0
        self.persistent_hit = False
        self.started_t: Optional[float] = None
        self.finished_t: Optional[float] = None


class EnginePrewarmer:
    """One background worker compiling predicted engine rungs in schedule
    order.  ``schedule(key, build)`` enqueues ``build()`` (idempotent per
    key); ``take(key)`` returns ``(result, waited_secs, was_ready)`` for a
    scheduled key — waiting out an in-flight compile if the boundary
    arrived first (still strictly better than compiling cold: the compile
    started earlier) — or ``None`` when the key was never scheduled.
    ``build`` runs on the worker thread and should return the fully
    compiled engine; exceptions are captured and re-raised at ``take``
    (the caller then falls back to its cold path)."""

    def __init__(self, name: str = PREWARM_THREAD_NAME):
        self._jobs: dict = {}
        self._queue: list = []
        self._lock = threading.Lock()
        self._wake = threading.Event()
        self._idle = threading.Event()
        self._idle.set()
        self._closed = False
        self._thread = threading.Thread(
            target=self._work, name=name, daemon=True
        )
        _register_prewarmer(self)
        self._thread.start()

    # -- worker --------------------------------------------------------------

    def _work(self) -> None:
        while True:
            self._wake.wait()
            with self._lock:
                if self._closed and not self._queue:
                    self._idle.set()
                    return
                job = self._queue.pop(0) if self._queue else None
                if not self._queue and not self._closed:
                    self._wake.clear()
                if job is not None:
                    self._idle.clear()
            if job is None:
                continue
            job.started_t = time.monotonic()
            watch = CompileWatch()
            try:
                job.result = job.build()
            except BaseException as e:  # noqa: BLE001 - surfaced at take()
                job.error = e
            d = watch.delta()
            job.compile_secs = d["compile_secs"]
            job.persistent_hit = d["persistent_hits"] > 0
            job.finished_t = time.monotonic()
            job.done.set()
            with self._lock:
                if not self._queue:
                    self._idle.set()

    # -- caller surface ------------------------------------------------------

    def schedule(self, key, build: Callable[[], object]) -> bool:
        """Enqueue ``build()`` for ``key`` unless already scheduled;
        True when a new job was queued."""
        with self._lock:
            if self._closed or key in self._jobs:
                return False
            job = _Job(key, build)
            self._jobs[key] = job
            self._queue.append(job)
            self._wake.set()
            return True

    def scheduled(self, key) -> bool:
        with self._lock:
            return key in self._jobs

    def ready(self, key) -> bool:
        """True when ``key``'s background compile has finished (the rung
        would swap in with ~zero wait)."""
        with self._lock:
            job = self._jobs.get(key)
        return job is not None and job.done.is_set()

    def take(self, key, timeout: Optional[float] = None):
        """Consume the job for ``key``: ``(result, waited_secs, was_ready)``
        or None when never scheduled.  A job that is DONE is returned
        instantly; an IN-FLIGHT compile is waited out (bounded by
        ``timeout``; the compile started earlier, so waiting beats
        duplicating it).  A job still sitting in the queue is CANCELLED and
        None returned — the caller's inline cold build starts immediately
        instead of queueing behind unrelated background compiles."""
        with self._lock:
            job = self._jobs.get(key)
            if job is None:
                return None
            if job in self._queue:  # scheduled but never started: cancel
                self._queue.remove(job)
                self._jobs.pop(key, None)
                return None
        was_ready = job.done.is_set()
        t0 = time.monotonic()
        if not job.done.wait(timeout):
            return None
        waited = time.monotonic() - t0
        with self._lock:
            self._jobs.pop(key, None)
        if job.error is not None:
            raise job.error
        return job.result, waited, was_ready, job

    def pending(self) -> int:
        with self._lock:
            return len(self._jobs)

    def prune(self, keep) -> int:
        """Drop jobs whose key is not in ``keep``: queued ones are
        cancelled outright, finished ones release their executables
        (their rung can no longer be consumed once capacities moved past
        it — holding the compiled program is pure memory waste, and a
        stale queued job would delay the NEXT useful compile on the
        single worker).  The in-flight job is left alone.  Returns the
        number of jobs dropped."""
        keep = set(keep)
        dropped = 0
        with self._lock:
            for job in list(self._queue):
                if job.key not in keep:
                    self._queue.remove(job)
                    self._jobs.pop(job.key, None)
                    job.error = RuntimeError("prewarm prediction superseded")
                    job.done.set()
                    dropped += 1
            for key, job in list(self._jobs.items()):
                if key not in keep and job.done.is_set():
                    self._jobs.pop(key, None)
                    dropped += 1
        return dropped

    def close(self) -> None:
        """Stop accepting work and DROP queued (not yet started) jobs —
        their predicted rungs will never be consumed once the run is over.
        The in-flight compile (if any) runs to completion on the worker;
        :func:`wait_idle` (and the atexit drain) waits it out so the
        interpreter never tears down under a live XLA compile."""
        with self._lock:
            self._closed = True
            for job in self._queue:
                job.error = RuntimeError("prewarmer closed")
                job.done.set()
                self._jobs.pop(job.key, None)
            self._queue.clear()
            self._wake.set()

    def wait_idle(self, timeout: Optional[float] = None) -> bool:
        """True once no compile is in flight (the queue is already empty
        or dropped by :func:`close`)."""
        return self._idle.wait(timeout)
