"""Span-structured tracing: the live half of the telemetry stack.

Dapper-style hierarchical spans over the work that hops scheduler →
supervisor → engine: a **trace** is one fleet run (or one standalone
check), and each unit of work inside it is a **span** with a fresh
``span_id`` and its parent's ``span_id`` as ``parent_id``:

    fleet ──┬── job (one scheduling episode on a slot)
            │     └── attempt (one supervised spawn+join)
            │           └── engine_run (one engine's whole run)
            │                 ├── step blocks (the existing ``step``
            │                 │   records — the engine binds its run
            │                 │   span to the recorder, so every step
            │                 │   carries ``span=<engine span id>``)
            │                 └── host seams: ``engine_acquire``,
            │                     ``device_call`` (``dispatch``, ``wait``),
            │                     ``grow`` (``grow.pull`` / ``.rehash`` /
            │                     ``.queue`` / ``.push``), ``autosave``,
            │                     ``checkpoint.pull``, ``spill_drain``;
            │                     where a program was acquired, ``dispatch``
            │                     (or an ahead-of-time ``engine_acquire``)
            │                     holds ``program.lower`` / ``program.load``
            └── job ...

(Two seams run before an engine's run span opens and are parentless in
its trace: ``fingerprint_bridge`` and ``twin_compile``, the actor
compiler's closure + tabulation, which runs before any checker — and so
any recorder — exists: it closes with no recorder and keeps its
:attr:`span.fields` on the twin, and the first checker that adopts the
twin records them.)

Span ids are minted where the work is minted — the fleet scheduler
roots the trace, ``supervise()`` opens one span per attempt, the
engines one per run — and the context propagates DOWN via the builder
(``builder._span_ctx``), never through globals.  A span closes by
recording one ``span`` record into the flight recorder's ring
(``kind="span"``: name, trace/span/parent ids, ``start``, ``dur``; both
on the recorder's clock, ``start`` the open-time read itself, and the
record's ``t`` the close time).  The Chrome-trace
exporter (:func:`telemetry.export.to_chrome_trace`) turns the records
into nested duration events — one Perfetto load shows the whole fleet
timeline.

The same names also exist on the PROFILER's clock.  :class:`span`
enters a ``jax.profiler.TraceAnnotation("sr/<name>")`` for its block, so
a ``jax.profiler`` trace shows the host seam beside the device
operations it waited for; and the device step program wraps its stages
in ``jax.named_scope`` under the ``sr.<stage>`` names below, which XLA
carries into every operation's metadata (``tf_op`` in the trace's event
metadata).  One list of names, here, for both.

Overhead contract (the telemetry discipline): spans are host-side
bookkeeping at seams that already exist — one ``uuid`` and two
``time.monotonic()`` calls per span, one dict per close, and one
TraceMe (a flag test while no profiler session is active).  No recorder
→ no ring record; the step jaxpr is untouched either way (a named scope
is debug metadata, not an operation).
"""

from __future__ import annotations

import time
import uuid
from typing import Optional

# span record schema version (tests/test_telemetry_schema.py pins it)
SPAN_V = 2  # 2: a record carries ``start``

# The device step program's stages, in program order: the
# ``jax.named_scope`` names ``parallel/wavefront.py:_build_engine`` wraps
# them in (the first ``sr.`` component of an operation's scope path names
# its stage).  The last two are the tail of ``step`` and the packed stats
# vector, which have a name and no benchmark metric.
STAGE_POP = "sr.pop"
STAGE_PROPS = "sr.props"
STAGE_EXPAND = "sr.expand"
STAGE_HASH = "sr.hash"
STAGE_INSERT = "sr.insert"
STAGE_APPEND = "sr.append"
STAGE_BOOKKEEP = "sr.bookkeep"
STAGE_STATS = "sr.stats"
STAGES = (STAGE_POP, STAGE_PROPS, STAGE_EXPAND, STAGE_HASH, STAGE_INSERT,
          STAGE_APPEND, STAGE_BOOKKEEP, STAGE_STATS)

# The growth programs' scope (``ops/buckets.bucket_split``, and
# ``parallel/wavefront.py``'s queue slide): no stage of the step program
# and so no member of ``STAGES``, but a stage of the trace all the same -
# the device time of a growth event reads ``sr.grow``, not ``unnamed``.
STAGE_GROW = "sr.grow"

# Sub-scopes of ``sr.expand`` that a compiled actor twin's ``step_rows``
# opens (``parallel/actor_compiler.py``; ``twin.net`` lives in
# ``parallel/actor_tensor.py``'s slot kernels, which hand-written twins
# share).  They do NOT start with ``sr.``: an operation's stage stays the
# first ``sr.<stage>`` of its scope path, and these split that stage.
TWIN_TABLE = "twin.table"  # (state, envelope) look-ups, effect decoding
TWIN_NET = "twin.net"  # slot deliver / send / canonicalise
TWIN_HISTORY = "twin.history"  # the linearizability history fields
TWIN_SCOPES = (TWIN_TABLE, TWIN_NET, TWIN_HISTORY)

# Sub-scope of ``sr.expand`` around the Drop columns of a compiled actor
# twin under ``lossy_network(True)``: the second successor block (every
# occupied slot consumed without a delivery) and its canonicalisation.
# Opened by a lossy twin alone, so it is no member of ``TWIN_SCOPES``
# (what every compiled twin opens); a Drop operation's scope path reads
# ``sr.expand/.../twin.drop/...`` whatever kernel it calls inside.
TWIN_DROP = "twin.drop"

# Sub-scope of ``sr.bookkeep`` around the step's test of the popped rows'
# poison bit (``poison_rows``: a compiled twin's crossed compile-time bound,
# ``PaxosTensor``'s network slot overflow), opened only where the twin has
# one: the test's operations carry ``sr.bookkeep/twin.poison``, a named
# stage, so it adds nothing to the share of busy time that has no name.
TWIN_POISON = "twin.poison"

# Sub-scope of ``sr.hash`` around the twin's ``representative_rows``, opened
# only by a step program built under ``.symmetry()``: the canonicaliser's
# operations carry ``sr.hash/sym.canon`` (their stage stays ``sr.hash``).
SYM_CANON = "sym.canon"

# Sub-scope of ``sr.props`` around the linearizability verdict of a twin
# whose state holds a history (the compiled actor twin's and
# ``PaxosTensor``'s ``property_masks``): the history fields' decoding and
# the closure / table verdict carry ``sr.props/props.lin``; the stage's
# other properties (``value chosen``'s slot scan) do not.
PROPS_LIN = "props.lin"

# a host span ``name`` is ``sr/<name>`` in the profiler's trace
ANNOTATION_PREFIX = "sr/"

# Children of the span that acquired a program (``dispatch`` on the lazy
# path, ``engine_acquire`` ahead of time), laid down after the fact from
# JAX's own monitoring events (``parallel/prewarm.py:CompileWatch``): one
# per module lowered to MLIR, one per program that reached the backend's
# compile-or-load step (cache key, then a retrieval and deserialisation or
# a fresh XLA compile; ``hit``, and ``retrieved_s`` for the retrieval
# inside it).  The parent's self time is the Python tracing and the enqueue.
PROGRAM_LOWER = "program.lower"
PROGRAM_LOAD = "program.load"


def new_id() -> str:
    """A fresh 64-bit id (hex) for traces and spans alike."""
    return uuid.uuid4().hex[:16]


class SpanContext:
    """The (trace_id, span_id) pair a child span parents under.  Flows
    down the spawn path as ``builder._span_ctx``; immutable in use."""

    __slots__ = ("trace_id", "span_id")

    def __init__(self, trace_id: Optional[str] = None,
                 span_id: Optional[str] = None):
        self.trace_id = trace_id or new_id()
        self.span_id = span_id or new_id()

    def __repr__(self) -> str:  # debugging/log lines only
        return f"SpanContext(trace={self.trace_id}, span={self.span_id})"


class SpanHandle:
    """An open span: created by :func:`start_span`, closed by
    :meth:`end` (which records the ``span`` record).  ``.ctx`` is what
    children parent under.  ``end`` is idempotent — a double close
    records nothing twice."""

    __slots__ = ("name", "ctx", "parent_id", "fields", "_t0", "_closed")

    def __init__(self, name: str, parent: Optional[SpanContext] = None,
                 trace_id: Optional[str] = None):
        self.name = str(name)
        # ``trace_id`` joins a parentless span to a trace that is already
        # there (a seam that runs after its run span closed)
        self.ctx = SpanContext(
            trace_id=parent.trace_id if parent is not None else trace_id
        )
        self.parent_id = parent.span_id if parent is not None else None
        # the closed span: its record, or with no recorder its fields alone
        self.fields: Optional[dict] = None
        self._t0 = time.monotonic()
        self._closed = False

    def end(self, recorder, **attrs) -> Optional[dict]:
        """Close the span and record it into ``recorder`` (None → the
        span is dropped, by the no-recorder-no-telemetry rule; its
        ``fields`` stay on the handle for a seam that closes before its
        recorder exists — with ``start`` still the raw ``time.monotonic()``
        read, there being no recorder's clock to put it on:
        :func:`adopt_span` does that).  Extra ``attrs`` ride the record
        (they must stay within the golden schema's optional set).  One
        clock read closes the span: ``dur`` and the record's ``t`` both
        come from it.  Returns the stored record (or None)."""
        if self._closed:
            return None
        return self._close(recorder, time.monotonic(), attrs)

    def _close(self, recorder, t1: float, attrs: dict) -> Optional[dict]:
        self._closed = True
        fields = {
            "v": SPAN_V,
            "name": self.name,
            "trace_id": self.ctx.trace_id,
            "span_id": self.ctx.span_id,
            "start": self._t0,
            "dur": round(t1 - self._t0, 6),
        }
        if self.parent_id is not None:
            fields["parent_id"] = self.parent_id
        fields.update({k: v for k, v in attrs.items() if v is not None})
        if recorder is not None:
            fields = _record(recorder, fields, self._t0, t1)
        self.fields = fields
        return fields if recorder is not None else None


def _record(recorder, fields: dict, t0: float, t1: float) -> dict:
    """One ``span`` record from two ``time.monotonic()`` reads: ``start``
    and ``t`` are the reads on the recorder's clock, rounded to the µs as
    every record is, and ``dur`` is their difference, so
    ``start + dur == t`` to the float."""
    start = round(recorder.rel(t0), 6)
    t = round(recorder.rel(t1), 6)
    return recorder.record(
        "span", t=t, **{**fields, "start": start, "dur": round(t - start, 6)}
    )


def record_span(recorder, name: str, *, parent: Optional[SpanContext],
                start: float, dur: float, **attrs) -> dict:
    """Record a span whose interval was learnt AFTER the fact: ``start``
    is a ``time.monotonic()`` stamp, ``dur`` seconds.  Such a span was
    never a block of this program, so it has no ``sr/`` annotation in the
    profiler's trace: it exists on the recorder's clock only."""
    handle = SpanHandle(name, parent)
    handle._t0 = start
    return handle._close(recorder, start + max(float(dur), 0.0), attrs)


def adopt_span(recorder, fields: dict, trace_id: str) -> dict:
    """Record the ``fields`` a span kept when it closed with no recorder
    (:meth:`SpanHandle.end`) into one that exists now, in ``trace_id``:
    where it was on the clock, which may be before the recorder's origin."""
    t0 = fields["start"]
    return _record(
        recorder, {**fields, "trace_id": trace_id}, t0, t0 + fields["dur"]
    )


def start_span(name: str, parent: Optional[SpanContext] = None) -> SpanHandle:
    """Open a span (child of ``parent``; a fresh trace root without
    one).  Close it with :meth:`SpanHandle.end`."""
    return SpanHandle(name, parent)


class span:
    """Context-manager form for block-shaped seams::

        with span("autosave", rec, parent=self._span_ctx, gen=3):
            ...write the generation...

    The record lands on exit — exception or not (the seam's duration is
    real either way); the original exception always propagates.  The
    block is also one ``sr/<name>`` event in a ``jax.profiler`` trace,
    recorder or not.  :meth:`set` adds an attribute that is only known
    inside the block (it rides the ring record)."""

    def __init__(self, name: str, recorder, *,
                 parent: Optional[SpanContext] = None,
                 trace_id: Optional[str] = None, **attrs):
        self._handle = SpanHandle(name, parent, trace_id)
        self._recorder = recorder
        self._attrs = attrs
        self._annotation = None

    @property
    def ctx(self) -> SpanContext:
        return self._handle.ctx

    @property
    def fields(self) -> Optional[dict]:
        """The closed span's record, or with no recorder its fields alone
        (None while open)."""
        return self._handle.fields

    def set(self, **attrs) -> None:
        self._attrs.update(attrs)
        if self._annotation is not None:
            self._annotation.set_metadata(**attrs)

    def __enter__(self) -> "span":
        # imported here: the package's host-only paths never load jax
        from jax.profiler import TraceAnnotation

        self._annotation = TraceAnnotation(
            ANNOTATION_PREFIX + self._handle.name,
            **{k: v for k, v in self._attrs.items() if v is not None},
        )
        self._annotation.__enter__()
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        self._annotation.__exit__(exc_type, exc, tb)
        attrs = dict(self._attrs)
        if exc_type is not None:
            attrs.setdefault("error", exc_type.__name__)
        self._handle.end(self._recorder, **attrs)
        return False  # never swallow the block's exception
