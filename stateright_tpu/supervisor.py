"""Supervised runs: retry/backoff around the device engines, with
autosave-based resume and graceful OOM degradation
(``docs/robustness.md``).

``supervise(builder, autosave_dir=...)`` runs the check under a
supervisor loop that

 1. **arms periodic autosave** (``stateright_tpu/checkpoint.py``) so the
    run always has a recent durable generation to fall back to;
 2. **classifies failures** (:func:`classify_failure`): SIGTERM/SIGINT
    preemption and injected kills are ``preemption``; an
    ``XlaRuntimeError`` carrying ``RESOURCE_EXHAUSTED`` (or the injected
    equivalent) is ``oom``; ``OSError`` family is ``io``; anything else
    is ``fatal`` and re-raises immediately — a model bug must never be
    retried into a silently wrong answer;
 3. **resumes transient failures from the latest autosave generation**
    with bounded exponential backoff + deterministic jitter and a
    restart budget — each resumed attempt links ``parent_run_id`` so the
    run registry's lineage gate (``_cli compare parent child --expect``)
    verifies exactly-once recovery end to end;
 4. **degrades gracefully on device OOM at a growth boundary**: when the
    spill tier applies (single-device wavefront, no POR), the supervisor
    arms ``CheckerBuilder.spill()`` — the next growth boundary EVICTS to
    the host tier instead of growing (pinning a device-byte budget from
    the snapshot's recorded footprint when none is known); when spill
    cannot apply, it shrinks the expansion batch once (halving the
    per-step candidate/queue transients) before giving up.

Cross-process resume: ``supervise`` looks for an existing latest
generation in ``autosave_dir`` FIRST, so re-running the same supervised
command after a SIGKILL continues the dead run — and when a run registry
is configured, the dead parent's last manifest is archived as a stub
report (``checkpoint.stub_report_doc``) so the lineage chain stays
auditable even though the parent never reached ``join()``.
"""

from __future__ import annotations

import os
import random
import sys
import time
from dataclasses import dataclass, field
from typing import Callable, Optional

from .checkpoint import (
    DEFAULT_EVERY_SECS,
    DEFAULT_KEEP,
    latest_generation,
    stub_report_doc,
)

SUPERVISE_V = 1

#: failure classes (classify_failure); ``fatal`` re-raises, the rest are
#: transient and resume from the latest autosave generation
PREEMPTION, OOM, IO, FATAL = "preemption", "oom", "io", "fatal"


def classify_failure(exc: BaseException) -> str:
    """Map one run failure onto the supervision taxonomy
    (docs/robustness.md "Fault taxonomy").  Matching is structural
    (type/name + message), never by import identity: a real
    ``jaxlib``-minted ``XlaRuntimeError`` and the fault layer's injected
    equivalent classify identically."""
    from .testing.faults import InjectedKill, InjectedOOM

    if isinstance(exc, InjectedOOM):
        return OOM
    if isinstance(exc, (InjectedKill, KeyboardInterrupt)):
        return PREEMPTION
    if "RESOURCE_EXHAUSTED" in str(exc):
        # the XLA device-OOM shape (a real jaxlib XlaRuntimeError or the
        # injected equivalent).  An XlaRuntimeError WITHOUT it
        # (INVALID_ARGUMENT, INTERNAL, ...) is a codegen/model bug and
        # falls through to fatal — retrying it cannot help
        return OOM
    if isinstance(exc, OSError):
        return IO
    if isinstance(exc, SystemExit):
        # a SIGTERM handler converting to exit is preemption-shaped
        return PREEMPTION
    return FATAL


@dataclass
class Attempt:
    """One supervised attempt's outcome (the result's audit trail)."""

    n: int
    outcome: str  # "completed" | a failure class
    error: Optional[str] = None
    resumed_from_gen: Optional[int] = None
    backoff_secs: Optional[float] = None
    degradation: Optional[str] = None


@dataclass
class SupervisedRun:
    """What ``supervise`` returns: the completed checker plus the
    supervision trail (restart count, per-attempt outcomes, degradation
    events) — the durability block's data source.  ``yielded`` marks a
    cooperative preemption (the ``yield_event`` hook): the checker is
    PARTIAL — its final autosave generation is the resume point, and
    calling ``supervise`` again on the same ``autosave_dir`` continues
    it bit-identically (docs/fleet.md "Preemption")."""

    checker: object
    restarts: int
    attempts: list = field(default_factory=list)
    degradations: list = field(default_factory=list)
    yielded: bool = False

    def __getattr__(self, name):
        # result-surface passthrough: totals/discoveries/report read
        # straight off the completed checker
        return getattr(self.checker, name)


def _spill_applicable(builder, spawn_kw: dict) -> bool:
    """Can the PR 8 spill tier be armed for this run?  One device only
    (``spawn_tpu``'s own rule), and mutually exclusive with POR."""
    if builder._mesh_request(spawn_kw) is not None:
        return False
    if getattr(builder, "por_mode", None):
        return False
    if os.environ.get("STATERIGHT_TPU_POR", "") == "1":
        return False
    return True


def _pin_budget_from_snapshot(snap: Optional[dict]) -> Optional[tuple]:
    """No device budget known but the device just OOMed: pin one from
    the snapshot's recorded analytic footprint so the spill tier's
    evict-vs-grow decision has a wall to respect (2x the running
    footprint: the failed growth transient was ~3x).  Returns
    ``(budget, prior_env_value)`` so the caller can RESTORE the env knob
    when supervision ends — the pin must not leak into unrelated runs in
    the same process."""
    from .telemetry.memory import ENV_DEVICE_BYTES, device_budget

    if device_budget()[0] is not None:
        return None
    fb = None
    if snap is not None and "footprint_bytes" in snap:
        try:
            fb = int(snap["footprint_bytes"])
        except (TypeError, ValueError):
            fb = None
    if not fb:
        return None
    budget = 2 * fb
    prior = os.environ.get(ENV_DEVICE_BYTES)
    os.environ[ENV_DEVICE_BYTES] = str(budget)
    return budget, prior


def supervise(
    builder,
    *,
    autosave_dir: Optional[str] = None,
    every_secs: float = DEFAULT_EVERY_SECS,
    keep: int = DEFAULT_KEEP,
    max_restarts: int = 5,
    backoff_base: float = 0.5,
    backoff_max: float = 30.0,
    seed: int = 0,
    spawn: Optional[Callable] = None,
    sleep: Callable[[float], None] = time.sleep,
    yield_event=None,
    **spawn_kw,
) -> SupervisedRun:
    """Run ``builder``'s check under supervision; returns a
    :class:`SupervisedRun` wrapping the COMPLETED checker.

    ``autosave_dir`` roots the checkpoint generations (a temp dir when
    omitted — in-process restarts still work, cross-process resume needs
    a real path).  ``spawn`` maps ``(builder, resume, **spawn_kw)`` to a
    checker (default: ``spawn_tpu``); the supervisor joins it.
    ``sleep``/``seed`` exist so tests drive backoff deterministically
    without wall clock.

    ``yield_event`` is the cooperative-preemption hook (``fleet/``,
    docs/fleet.md): a ``threading.Event`` that, once set, makes the
    current attempt ``stop()`` at its next host sync — the engine's
    stop path force-writes one final autosave generation
    (stop-after-next-autosave), and ``supervise`` returns the PARTIAL
    run with ``yielded=True`` instead of retrying.  No SIGKILL, no lost
    work: calling ``supervise`` again on the same ``autosave_dir``
    resumes from that generation bit-identically, with
    ``parent_run_id`` lineage linked exactly as a crash-resume would."""
    if autosave_dir is None:
        import tempfile

        autosave_dir = tempfile.mkdtemp(prefix="stateright-tpu-autosave-")
    # builder config mutated for supervision (autosave arming here, spill
    # arming on an OOM degradation) is restored when supervision ends: a
    # later plain spawn from the same builder must not silently inherit
    # a checkpoint cadence into an orphaned dir or an armed spill tier
    prior_autosave_opts = getattr(builder, "autosave_opts", None)
    prior_spill_mode = getattr(builder, "spill_mode", None)
    builder.autosave(autosave_dir, every_secs=every_secs, keep=keep)
    if spawn is None:
        def spawn(b, resume=None, **kw):
            return b.spawn_tpu(resume=resume, **kw)

    rng = random.Random(seed)
    restarts = 0
    attempts: list = []
    degradations: list = []
    oom_degraded = False
    last_cls: Optional[str] = None
    # batch_shrunk degradation state: the snapshot's stored ``batch``
    # governs the resumed buffer layout, so the shrink must be applied
    # to EVERY freshly loaded generation (the loop re-reads the dir each
    # attempt) — mutating one stale snap dict would be a silent no-op
    pending_batch: Optional[int] = None
    # budget pinned for the spill degradation: (env value set, prior
    # value) — restored when supervision ends, success or raise
    pinned_budget: Optional[tuple] = None
    try:
        while True:
            found = latest_generation(autosave_dir)
            snap = manifest = None
            if found is not None:
                snap, manifest = found
                snap = dict(snap)
                if pending_batch is not None and "batch" in snap:
                    import numpy as np

                    snap["batch"] = np.int64(pending_batch)
                _maybe_register_stub(builder, manifest)
            # the supervision trail rides the builder so the spawned
            # checker (and its report's durability block) knows its
            # restart count
            builder._supervise_restarts = restarts
            builder._supervise_degradations = list(degradations)
            # one attempt span per supervised spawn+join
            # (telemetry/spans.py): parents under the fleet job span when
            # the scheduler set builder._span_ctx, roots otherwise; the
            # engine's engine_run span parents under THIS attempt
            from .telemetry.spans import start_span

            prior_span_ctx = getattr(builder, "_span_ctx", None)
            att_span = start_span("attempt", parent=prior_span_ctx)
            builder._span_ctx = att_span.ctx
            checker = None
            try:
                checker = spawn(builder, resume=snap, **spawn_kw)
                rec = getattr(checker, "flight_recorder", None)
                if rec is not None and restarts:
                    fields = {
                        "attempt": restarts, "reason": last_cls or "?",
                    }
                    if manifest and manifest.get("run_id"):
                        fields["parent_run_id"] = str(manifest["run_id"])
                    if degradations:
                        fields["degradation"] = degradations[-1]
                    rec.record("restart", v=SUPERVISE_V, **fields)
                    rec.update_meta(restarts=restarts, supervised=True)
                if yield_event is not None:
                    _arm_yield_watch(checker, yield_event)
                checker.join()
                att_span.end(rec, attempt=restarts)
            except BaseException as e:  # noqa: BLE001 - classified below
                att_span.end(
                    getattr(checker, "flight_recorder", None),
                    attempt=restarts, error=type(e).__name__,
                )
                cls = classify_failure(e)
                att = Attempt(
                    n=len(attempts), outcome=cls,
                    error=f"{type(e).__name__}: {e}",
                    resumed_from_gen=(
                        manifest.get("gen") if manifest else None
                    ),
                )
                attempts.append(att)
                if cls == FATAL or restarts >= max_restarts:
                    raise
                last_cls = cls
                if cls == OOM:
                    deg = _degrade_for_oom(
                        builder, spawn_kw, snap, oom_degraded
                    )
                    if deg is None:
                        raise  # already degraded once; OOM again = done
                    event, new_batch, pinned = deg
                    oom_degraded = True
                    degradations.append(event)
                    att.degradation = event
                    if new_batch is not None:
                        pending_batch = new_batch
                    if pinned is not None:
                        pinned_budget = pinned
                delay = min(
                    backoff_base * (2 ** restarts), backoff_max
                ) * (1.0 + 0.25 * rng.random())
                att.backoff_secs = round(delay, 3)
                restarts += 1
                print(
                    f"stateright-tpu: supervise: attempt {att.n} failed "
                    f"({cls}: {att.error}); restart {restarts}/"
                    f"{max_restarts} after {delay:.2f}s backoff"
                    + (f" [{att.degradation}]" if att.degradation else ""),
                    file=sys.stderr,
                )
                sleep(delay)
                continue
            finally:
                # each attempt's span ctx must not leak into the next
                # attempt (or outlive supervision on the builder)
                builder._span_ctx = prior_span_ctx
            yielded = yield_event is not None and yield_event.is_set()
            attempts.append(Attempt(
                n=len(attempts),
                outcome="yielded" if yielded else "completed",
                resumed_from_gen=manifest.get("gen") if manifest else None,
            ))
            checker._restarts = restarts
            checker._degradations = list(degradations)
            return SupervisedRun(
                checker, restarts, attempts=attempts,
                degradations=list(degradations), yielded=yielded,
            )
    finally:
        # supervision state must not outlive the call: a later plain
        # spawn from the same builder would otherwise inherit a stale
        # restart trail (false durability/registry data), and the pinned
        # budget would impose a wall on unrelated runs in this process
        for attr in ("_supervise_restarts", "_supervise_degradations"):
            if hasattr(builder, attr):
                try:
                    delattr(builder, attr)
                except AttributeError:
                    pass
        builder.autosave_opts = prior_autosave_opts
        builder.spill_mode = prior_spill_mode
        if pinned_budget is not None:
            from .telemetry.memory import ENV_DEVICE_BYTES

            _, prior = pinned_budget
            if prior is None:
                os.environ.pop(ENV_DEVICE_BYTES, None)
            else:
                os.environ[ENV_DEVICE_BYTES] = prior


def _arm_yield_watch(checker, yield_event) -> None:
    """Cooperative-preemption watcher (stop-after-next-autosave): when
    the scheduler sets ``yield_event``, ask the engine to ``stop()`` at
    its next host sync — the stop path force-writes one final autosave
    generation (``parallel/_base._maybe_autosave(force=True)``), so the
    yield loses ~zero work and the run resumes bit-identically from
    that generation (pinned by tests/test_robustness.py).  The watcher
    exits on its own once the attempt finishes; ``stop()`` on a done
    checker is a no-op, so a late fire is harmless."""
    import threading

    def _watch():
        while not yield_event.wait(0.02):
            if checker.is_done():
                return
        checker.stop()

    threading.Thread(
        target=_watch, daemon=True, name="supervise-yield"
    ).start()


def _degrade_for_oom(
    builder, spawn_kw: dict, snap: Optional[dict], already: bool,
) -> Optional[tuple]:
    """Choose ONE graceful-degradation move for a device OOM; returns
    ``(event, new_batch, pinned_budget)`` — ``new_batch`` is applied by
    the supervise loop to every subsequently loaded generation (the
    snapshot's stored batch governs the resumed buffer layout, so the
    shrink must land on the FRESHLY loaded snap each attempt, not a
    stale dict) — or None when the budget of moves is spent."""
    if already:
        return None
    if _spill_applicable(builder, spawn_kw) and not getattr(
        builder, "spill_mode", None
    ):
        builder.spill()
        pinned = _pin_budget_from_snapshot(snap)
        event = (
            f"spill_armed(budget={pinned[0]})" if pinned else "spill_armed"
        )
        return event, None, pinned
    # spill cannot apply (mesh / POR / already armed): shrink the
    # expansion batch once — halving it halves the per-step candidate
    # windows and queue slack (the per-batch share of the transient)
    cur = None
    if snap is not None and "batch" in snap:
        cur = int(snap["batch"])
    elif spawn_kw.get("batch"):
        cur = int(spawn_kw["batch"])
    new = max(8, (cur or 2048) // 2)
    spawn_kw["batch"] = new  # governs a from-scratch restart (no snap)
    return f"batch_shrunk({cur}->{new})", new, None


def _maybe_register_stub(builder, manifest: dict) -> None:
    """A run registry is configured and the manifest's run never
    archived itself (killed mid-flight): archive the checkpoint-derived
    stub so the lineage chain has its parent record.  Never fatal."""
    from .telemetry.registry import RunRegistry, resolve_run_dir

    root = resolve_run_dir(getattr(builder, "run_dir", None))
    if not root:
        return
    rid = manifest.get("run_id")
    if not rid:
        return
    try:
        reg = RunRegistry(root)
        if any(r.get("run_id") == rid for r in reg.index()):
            return
        doc = stub_report_doc(manifest)
        if doc is not None:
            reg.record_doc(doc)
    except Exception as e:  # noqa: BLE001 - the ledger must never block
        # a resume
        print(
            f"stateright-tpu: supervise: stub-archive failed: "
            f"{type(e).__name__}: {e}",
            file=sys.stderr,
        )
