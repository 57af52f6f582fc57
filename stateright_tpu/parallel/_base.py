"""Shared result surface + host-side plumbing for the wavefront engines.

The device engines (``wavefront.py``, its mesh placement ``mesh.py``, and the
sweep engine) produce the same artifacts — a fingerprint→parent table,
discovery fingerprints, and counters — and reconstruct traces identically
(reference analogue ``src/checker/bfs.rs:314-342``).  This base class holds
everything that is engine-independent so semantics fixes land once.
"""

from __future__ import annotations

import os
import sys
import threading
from typing import Optional

import numpy as np

import jax
import jax.numpy as jnp

from ..checker.base import Checker, CheckerBuilder
from ..checker.path import Path
from ..fingerprint import MASK64
from ..ops.hashing import row_hash
from ..telemetry.spans import adopt_span
from ..telemetry.spans import new_id as new_span_id
from ..telemetry.spans import span as tel_span


# Spaces below this finish in one or two engine calls on hardware: the
# measured "rate" is fixed per-run overhead, not throughput.  The constant
# itself is not measured on today's code (chip_smoke.py on a v5e only
# shows the direction: paxos-2's 16,668 states cost seconds of set-up).
# Shared by the engines' footgun warning, spawn_auto's rationale, and the
# bench's per-config disclosure notes — recalibrate it in ONE place.
SMALL_SPACE_BREAK_EVEN = 100_000


class WavefrontChecker(Checker):
    """Common host-side surface for device wavefront engines."""

    def _init_common(self, options: CheckerBuilder, sync: bool):
        self._stop = threading.Event()
        self._ckpt_req: Optional[threading.Event] = None
        self._ckpt_out: Optional[dict] = None
        self._ckpt_ready = threading.Event()
        # serializes concurrent checkpoint() callers: they share the single
        # _ckpt_req/_ckpt_ready/_ckpt_out triple, and without the lock one
        # caller could consume the other's snapshot (the loser silently
        # returning None)
        self._ckpt_lock = threading.Lock()
        self.model = options.model
        # Prefer the cached twin (TensorBackedModel): the compiled-run cache
        # lives on the tensor instance, so a fresh twin per checker would
        # recompile on every run.
        cached = getattr(self.model, "_tensor_cached", None)
        if cached is not None:
            tensor = cached()
        else:
            tensor = getattr(self.model, "tensor_model", lambda: None)()
        if tensor is None:
            raise TypeError(
                f"{type(self.model).__name__} has no tensor form: implement "
                "tensor_model() (see parallel/tensor_model.py) or use "
                "spawn_bfs()/spawn_dfs()"
            )
        self._symmetry = options.symmetry_fn
        if options.symmetry_fn is not None:
            if not hasattr(tensor, "representative_rows"):
                raise NotImplementedError(
                    f"{type(tensor).__name__} has no representative_rows(): "
                    "device symmetry reduction needs a vectorized "
                    "canonicalizer (see TwoPhaseTensor.representative_rows); "
                    "use spawn_dfs()"
                )
            if not getattr(options, "symmetry_is_default", False):
                # representative_rows mirrors state.representative(); a
                # custom symmetry_with fn would silently disagree with the
                # device dedup and break trace reconstruction
                raise NotImplementedError(
                    "the device engines support .symmetry() (the "
                    "representative() protocol) only; custom symmetry_with "
                    "functions require spawn_dfs()"
                )
        if options.visitor_obj is not None:
            raise NotImplementedError(
                "per-state visitors require host materialization; use "
                "spawn_bfs() (the TPU engine never materializes states)"
            )
        self.tensor = tensor
        self._props = list(self.model.properties())
        self._target = options.target_state_count

        # wavefront-throughput knobs (docs/perf.md): builder flags win,
        # env knobs otherwise.  Pre-dedup is a jaxpr flag of the step
        # program; prewarm is single-device only (the mesh engine's
        # growth rebuilds are not compiled in the background yet); the
        # persistent compile cache is a global
        # JAX setting, switched on here when the environment or the
        # builder names a directory (prewarm.resolve_compile_cache_dir).
        from .prewarm import (
            ENV_POR,
            ENV_PREDEDUP,
            ENV_PREWARM,
            ENV_SPILL,
            enable_persistent_compile_cache,
            resolve_flag,
        )

        self._prededup = resolve_flag(
            getattr(options, "prededup_mode", None), ENV_PREDEDUP
        )
        # partial-order reduction (analysis/independence.py): resolve the
        # compile-time plan here — an unusable plan (liveness properties,
        # no independent pair, undecidable footprints) falls back to full
        # expansion and the engines never pay the ample-selection ops
        self._por_plan = None
        self._por_fallback = None
        self._live_por = None
        self._por = resolve_flag(
            getattr(options, "por_mode", None), ENV_POR
        )
        if self._por:
            from ..analysis.independence import por_plan

            plan = por_plan(tensor, list(self.model.properties()))
            if plan.usable:
                self._por_plan = plan
            else:
                self._por = False
                self._por_fallback = plan.fallback_reason
                # once per model, like the preflight audit's warning
                # print — repeated spawns (parity tests, bench loops)
                # must not spam stderr
                if not getattr(self.model, "_por_warn_printed", False):
                    try:
                        object.__setattr__(
                            self.model, "_por_warn_printed", True
                        )
                    except Exception:  # noqa: BLE001 - __slots__ models
                        pass
                    print(
                        "stateright-tpu: por(): falling back to full "
                        f"expansion — {plan.fallback_reason} "
                        "(docs/analysis.md)",
                        file=sys.stderr,
                    )
        # billion-state spill tier (stateright_tpu/spill/, docs/spill.md):
        # host-backed visited overflow with a device-side Bloom
        # pre-filter.  One device only (the mesh engine's table is
        # distributed over the mesh — spilling it is the pod-scale
        # round's work), and mutually exclusive with POR for now (the two-phase
        # ample insert and the Bloom deferral do not compose).
        self._spill = resolve_flag(
            getattr(options, "spill_mode", None), ENV_SPILL
        )
        if self._spill:
            if self._engine_tag != "single":
                raise NotImplementedError(
                    "spill mode (CheckerBuilder.spill()) is single-device "
                    "only for now: the mesh engine's visited table is "
                    "distributed over the mesh and spills with the "
                    "pod-scale mesh round (ROADMAP).  Drop the devices/"
                    "mesh argument (.mesh()/--mesh/STATERIGHT_TPU_MESH), "
                    "or drop .spill()/--spill/STATERIGHT_TPU_SPILL."
                )
            if self._por:
                raise NotImplementedError(
                    "spill mode does not compose with partial-order "
                    "reduction yet (the POR two-phase insert and the "
                    "Bloom deferral conflict; docs/spill.md).  Drop one "
                    "of .spill()/.por()."
                )
            self._init_spill()
        # MXU recast round (ops/mxu.py, docs/roofline.md): the three
        # bytes-moved reductions executing the JX4xx hot-spot ranking.
        # Resolved ONCE here; None (off) keeps the step
        # jaxpr bit-identical and the engine cache unkeyed (pinned).
        # The POR plan above deliberately footprints the PLAIN step
        # kernel either way: the coalesced kernel computes the same
        # transition function, so one conflict matrix serves both and
        # the ample sets — hence the explored set — cannot drift with
        # the flag.
        from ..ops.mxu import resolve_mxu

        self._mxu = resolve_mxu(getattr(options, "mxu_opts", None))
        self._prewarm = resolve_flag(
            getattr(options, "prewarm_mode", None), ENV_PREWARM
        )
        self._compile_cache_dir = enable_persistent_compile_cache(
            getattr(options, "compile_cache_dir", None)
        )
        self._prewarmer = None
        self._pending_compile_rec = None
        if self._prewarm and self._engine_tag == "single":
            from .prewarm import EnginePrewarmer

            self._prewarmer = EnginePrewarmer()

        # flight recorder (stateright_tpu/telemetry/): engines record one
        # "step" record per host sync from values the loop already pulls —
        # telemetry never adds device ops (docs/telemetry.md overhead
        # contract); occupancy sampling / profiling are explicit opt-ins.
        self._telemetry_opts = options.telemetry_opts or {}
        # search cartography (ops/cartography.py, docs/telemetry.md): the
        # ONE telemetry option that does change the step program — small
        # on-device reductions riding the packed stats vector.  Off (the
        # default) keeps the step jaxpr bit-identical (pinned by test).
        self._cartography = bool(self._telemetry_opts.get("cartography"))
        # wavefront depth-histogram base: depth lanes banked from the
        # consumed queue prefixes the growth transform reclaims (the live
        # histogram is queue-derived; see TpuChecker._grow)
        self._cart_depth_base = None
        # post-run report (telemetry/report.py): written once at join()
        # when the builder requested CheckerBuilder.report(PATH)
        self._report_path = getattr(options, "report_path", None)
        self._report_written = False
        # persistent run registry (telemetry/registry.py): archived once
        # at join() when configured (builder .runs(DIR) or the
        # STATERIGHT_TPU_RUN_DIR env knob)
        self._run_dir = getattr(options, "run_dir", None)
        tag = "wavefront" if self._engine_tag == "single" else self._engine_tag
        self.flight_recorder = options._make_recorder(tag)
        if self._spill and self.flight_recorder is not None:
            # spill armed: the health model downgrades growth_oom_risk to
            # the informational spill forecast — the run will not OOM at
            # the wall, it will evict (telemetry/health.py)
            self.flight_recorder.set_spill_armed(True)
        # crash-safe autosave (stateright_tpu/checkpoint.py,
        # docs/robustness.md): rotating atomic snapshot generations written
        # at host-sync boundaries.  Pure host-side I/O — the step jaxpr and
        # the engine cache are untouched either way (pinned by test).  The
        # supervision trail (restart count, degradation events) rides the
        # builder when supervisor.supervise drives the run.
        self._restarts = int(
            getattr(options, "_supervise_restarts", 0) or 0
        )
        self._degradations = list(
            getattr(options, "_supervise_degradations", None) or []
        )
        self._autosave = None
        from ..checkpoint import AutosaveService, resolve_autosave

        aopts = resolve_autosave(getattr(options, "autosave_opts", None))
        if aopts is not None:
            self._autosave = AutosaveService(
                aopts["dir"], aopts["every_secs"], aopts["keep"],
                recorder=self.flight_recorder,
            )
        # span-trace context (telemetry/spans.py): the fleet scheduler /
        # supervisor parents the engine_run span under the job/attempt
        # span via builder._span_ctx; None roots a fresh trace.  The run
        # span's own ctx (set by _run_traced) parents the host-seam
        # spans (autosave / spill_drain).
        self._span_parent = getattr(options, "_span_ctx", None)
        self._run_span_ctx = None
        # every span of this checker (before, in and after its run) is in
        # one trace: the parent's, or a fresh one
        self._trace_id = (
            self._span_parent.trace_id if self._span_parent is not None
            else new_span_id()
        )
        # a compiled actor twin closed its ``twin_compile`` span before any
        # recorder existed (parallel/actor_compiler.py): the first checker
        # that adopts the twin records it, in this checker's trace
        pending = getattr(tensor, "compile_span", None)
        if pending is not None and self.flight_recorder is not None:
            tensor.compile_span = None
            adopt_span(self.flight_recorder, pending, self._trace_id)
        # host seam span: the bridge check hashes one init row with EAGER
        # device operations (a dispatch each), before the run span opens
        with tel_span("fingerprint_bridge", self.flight_recorder,
                      parent=self._span_parent, trace_id=self._trace_id):
            self._verify_fingerprint_bridge()
        # live progress heartbeat (checkpoint.ProgressHeartbeat,
        # docs/observability.md): an atomic progress.json next to the
        # autosave generations, beaten at host syncs the engine already
        # makes — `_cli status <run_dir>` tails it, SIGKILL included
        self._heartbeat = None
        if aopts is not None:
            from ..checkpoint import ProgressHeartbeat

            self._heartbeat = ProgressHeartbeat(
                aopts["dir"],
                meta={
                    "engine": tag,
                    "model": type(self.model).__name__,
                    "pid": os.getpid(),
                },
            )
        self._autosave_config = None  # build_config cache (per checker)
        self._refresh_durability()
        # HBM memory ledger (telemetry/memory.py): per-buffer analytic
        # accounting + growth-transient forecast + live device readings.
        # Pure host arithmetic over shapes the engines already know —
        # zero device ops, zero jaxpr change either way (pinned by test).
        self._mem_ledger = None
        if (
            self.flight_recorder is not None
            and self._telemetry_opts.get("memory")
        ):
            from ..telemetry.memory import MemoryLedger

            self._mem_ledger = MemoryLedger(
                tag,
                self._memory_spec_fn(),
                recorder=self.flight_recorder,
                every=int(self._telemetry_opts.get("memory_every") or 0),
                extra=self._memory_extra(),
            )
        # roofline cost ledger (telemetry/roofline.py +
        # analysis/costmodel.py): per-stage/per-op FLOPs-bytes
        # attribution, XLA-reconciled, with the JX4xx MXU-candidate
        # ranking.  Pure host analysis over RE-TRACED kernels — the
        # engine's own step program is untouched and the engine cache
        # unkeyed either way (pinned by test, the memory ledger's
        # contract).  Built eagerly here (one small trace + compile per
        # pipeline stage, cached on the twin) so the snapshot exists
        # before the first poll.
        self._roofline_ledger = None
        if (
            self.flight_recorder is not None
            and self._telemetry_opts.get("roofline")
        ):
            from ..telemetry.roofline import RooflineLedger

            try:
                self._roofline_ledger = RooflineLedger(
                    tag,
                    self._roofline_cost_fn(),
                    recorder=self.flight_recorder,
                )
            except Exception:  # noqa: BLE001 - accounting must never
                self._roofline_ledger = None  # break a run
        # preflight capacity guard: cheap analytic math, always on (warn;
        # STATERIGHT_TPU_CAPACITY_GUARD=error escalates, =off silences) —
        # a run whose requested table cannot fit the device should say so
        # BEFORE any compile is paid.  Silent where no budget is known.
        self._preflight_capacity_guard()
        self._profiler = None
        if (
            self.flight_recorder is not None
            and self._telemetry_opts.get("profile_steps")
        ):
            import tempfile

            from ..telemetry import ScopedProfiler

            logdir = self._telemetry_opts.get("profile_dir") or (
                tempfile.mkdtemp(prefix="stateright-tpu-profile-")
            )
            self._profiler = ScopedProfiler(
                logdir,
                int(self._telemetry_opts["profile_steps"]),
                self.flight_recorder,
            )

        self._results = None
        # trip counts of the device calls' while_loops, summed at each
        # host sync (the ``dsteps`` lane of the packed stats vector)
        self._device_steps = 0
        # trace reconstruction resolves parents once a run: the discovered
        # chains off the device (_chains), or every state's on the host
        self._parent_map: Optional[dict[int, int]] = None
        self._chain_map: Optional[dict[int, np.ndarray]] = None
        self._done = threading.Event()
        # builder timeout parity (reference: the pool checkers' deadline):
        # a timer requests a cooperative stop, honored at the next host
        # sync — the run ends cleanly with partial counts and a resumable
        # final snapshot, exactly like stop()
        self._timed_out = False
        if options.timeout_secs is not None:
            timer = threading.Timer(options.timeout_secs, self._deadline_stop)
            timer.daemon = True
            timer.start()
        self._thread = None
        # Fail fast on caller errors (e.g. a resume snapshot from a different
        # model) in the caller's thread: raised inside the daemon worker they
        # would only hit stderr and leave the checker silently never-done.
        self._pre_run_validate()
        self._run_error: Optional[BaseException] = None
        if sync:
            self._run_traced()
            self._maybe_write_report()
        else:
            self._thread = threading.Thread(
                target=self._run_guarded, daemon=True
            )
            self._thread.start()

    def _run_guarded(self) -> None:
        """Async-run wrapper: an exception in the run thread (e.g. a
        multi-controller run hitting a single-controller-only path) must
        surface at join()/report(), not hang the checker forever with
        ``_done`` unset and counters silently reading 0."""
        try:
            self._run_traced()
        except BaseException as e:  # noqa: BLE001 - re-raised at join()
            self._run_error = e
            self._done.set()

    def _run_traced(self) -> None:
        """The engine run inside its ``engine_run`` span plus the
        lifecycle seams that must hold on BOTH exit paths:

         - the run span closes (with ``error`` set on the exception
           path) and unbinds from the recorder, so a crashed run's
           Chrome trace still shows where it died;
         - the scoped profiler stops in a ``finally`` — the engines'
           happy-path ``stop()`` never fires when a step raises, which
           used to leak an active ``jax.profiler`` trace into the next
           run; ``stop()`` is idempotent and swallows backend errors,
           so this can never double-stop or mask the original error;
         - the heartbeat lands one forced final beat with the terminal
           status (``done`` / ``failed``), so ``status <run_dir>``
           distinguishes a finished run from a SIGKILLed one."""
        rec = self.flight_recorder
        failed = False
        with tel_span(
            "engine_run", rec, parent=self._span_parent,
            trace_id=self._trace_id, engine=self._engine_tag,
        ) as sp:
            self._run_span_ctx = sp.ctx
            if rec is not None:
                rec.bind_span(sp.ctx.span_id)
            try:
                self._run()
            except BaseException:  # noqa: BLE001 - propagates; the span
                failed = True  # records its type as ``error``
                raise
            finally:
                if self._profiler is not None:
                    self._profiler.stop()
                if rec is not None:
                    rec.bind_span(None)
                if self._heartbeat is not None:
                    self._heartbeat.beat(
                        rec, status="failed" if failed else "done",
                        force=True,
                    )

    def _deadline_stop(self) -> None:
        """The builder ``timeout()`` deadline fired: flag the cut (unless
        the run already finished) and request a cooperative stop."""
        if not self._done.is_set():
            self._timed_out = True
        self._stop.set()

    @property
    def timed_out(self) -> bool:
        """True when the builder ``timeout()`` deadline cut the run short
        (pool-checker parity) — ``is_done()`` only means *stopped*, and
        the run report must not present a deadline-cut run as complete."""
        return self._timed_out

    def _pre_run_validate(self) -> None:  # engine-specific, optional
        pass

    # -- memory ledger (telemetry/memory.py; the engine gives the specs) -----

    def _analytic_footprint_bytes(self, caps: Optional[dict] = None):
        """Total analytic bytes of the device-resident carry at ``caps``
        (default: the configured capacities); None when the model cannot
        be built (accounting must never break a run)."""
        from ..telemetry.memory import total_bytes

        try:
            fn = self._memory_spec_fn()
            return int(total_bytes(fn(caps or self._memory_caps())))
        except Exception:  # noqa: BLE001 - accounting only
            return None

    def _preflight_capacity_guard(self) -> None:
        from ..telemetry.memory import preflight_guard

        total = self._analytic_footprint_bytes()
        if total is None:
            return
        preflight_guard(
            f"spawn_tpu({type(self.model).__name__})",
            total,
            warn_once_obj=self.model,
        )

    def roofline(self, live: bool = True) -> Optional[dict]:
        """Latest roofline-ledger block (``telemetry/roofline.py``), or
        None when the run was spawned without
        ``.telemetry(roofline=True)`` (or the twin's kernels did not
        trace).  ``live=False`` returns the DETERMINISTIC static subset
        (the run report's ``roofline`` block: analytic costs only — no
        XLA numbers, no device spec, no wall clock); the default adds
        the reconciliation verdict, per-stage memory/compute-bound
        verdicts, and — once stage attribution exists — the
        achieved-vs-ceiling estimate."""
        led = self._roofline_ledger
        if led is None or not led.ok:
            return None
        if not live:
            return led.static_block()
        rec = self.flight_recorder
        stages = rec.stages() if rec is not None else None
        return led.live_block(stages, self.unique_state_count())

    def memory(self, live: bool = True) -> Optional[dict]:
        """Latest memory-ledger snapshot (``telemetry/memory.py``), or
        None when the run was spawned without ``.telemetry(memory=True)``.
        ``live=False`` returns the DETERMINISTIC analytic subset (the run
        report's memory block: no device stats, no machine-local
        budget)."""
        if self._mem_ledger is None:
            return None
        return (
            self._mem_ledger.snapshot()
            if live
            else self._mem_ledger.analytic_block()
        )

    def _model_sig(self) -> np.ndarray:
        """Model identity guard for resume: init fingerprints alone can
        coincide across configurations (e.g. all-zero init rows), so the
        tensor shape signature is included too."""
        fps = [
            self.model.fingerprint_state(s) for s in self.model.init_states()
        ]
        return np.asarray(
            sorted(fps)
            + [self.tensor.width, self.tensor.max_actions, len(self._props)],
            np.uint64,
        )

    _engine_tag = "single"  # "mesh" and "sweep" override it

    def _check_snapshot_sig(self, snap: dict) -> None:
        tag = str(snap.get("engine", "single"))
        if tag != self._engine_tag:
            raise ValueError(
                f"resume snapshot was taken by the {tag!r} engine; this is "
                f"the {self._engine_tag!r} engine (pass/drop the devices/"
                "mesh argument to match)"
            )
        if not np.array_equal(self._model_sig(), snap["model_sig"]):
            raise ValueError(
                "resume snapshot was taken from a different model "
                "(init fingerprints / tensor signature disagree)"
            )
        # lineage capture: the manifest's run_id (absent on pre-registry
        # snapshots) becomes this run's parent — the report header,
        # registry index, and diff engine all read it
        rid = snap.get("run_id")
        if rid is not None and self.parent_run_id is None:
            # npz round-trips strings as 0-d unicode arrays
            self.parent_run_id = str(np.asarray(rid).item()) if hasattr(
                rid, "dtype"
            ) else str(rid)
        if not getattr(self, "_spill", False) and (
            int(snap.get("spill_base", 0) or 0) > 0
            or "spill_fp" in snap
            or "spill_q_fp" in snap
            or "spill_pend_fp" in snap
        ):
            # part of the visited set lives in the snapshot's host-tier
            # manifest: resuming without the tier would silently re-count
            # every spilled state as fresh
            raise ValueError(
                "resume snapshot carries spill-tier contents (host/disk "
                "visited overflow); resume with CheckerBuilder.spill() / "
                "--spill / STATERIGHT_TPU_SPILL=1 (docs/spill.md)"
            )
        # snapshot-manifest capacity check (telemetry/memory.py): the
        # snapshot records its analytic footprint (older ones fall back
        # to summed array bytes) — warn/flag-gated-error BEFORE any
        # compile when the target device analytically cannot hold it.
        # Once per checker: the wavefront path validates the same
        # snapshot twice (preflight + carry materialization).
        if not getattr(self, "_snapshot_fit_checked", False):
            self._snapshot_fit_checked = True
            from ..telemetry.memory import snapshot_fits_guard

            snapshot_fits_guard(
                snap, f"resume({type(self.model).__name__})"
            )

    def _stage(self, name: str, secs: float) -> None:
        """Accumulate one per-stage wall-time counter (docs/perf.md): the
        breakdown the recorder's ``stages()`` view is derived from.  Both
        engines call this from their host loops only — attribution adds
        zero device ops (same contract as the rest of telemetry).  Zero
        values still record: a fully-warm run reports ``compile_secs: 0``
        rather than omitting the field (bench/regress key on presence)."""
        if self.flight_recorder is not None and secs >= 0:
            self.flight_recorder.add(f"stage_{name}_secs", secs)

    def _telemetry_occupancy(self, table_fp, *, at: str,
                             transferred: bool = False) -> None:
        """Record one visited-table occupancy sample (time-series element
        of ``ops/buckets.occupancy_stats``).  ``transferred=True`` prices
        the D2H table pull into the recorder's byte counters; growth
        boundaries pass False — the table is host-side there anyway."""
        rec = self.flight_recorder
        if rec is None:
            return
        import numpy as _np

        from ..ops.buckets import occupancy_stats

        arr = _np.asarray(table_fp)
        if transferred:
            rec.add_bytes(d2h=arr.nbytes)
        rec.record("occupancy", at=at, **occupancy_stats(arr))

    def _telemetry_occupancy_hist(self, histogram, *, at: str) -> None:
        """:meth:`_telemetry_occupancy` of a table that stays on the
        device: the record is built from its per-bucket occupancy
        histogram (``ops/buckets.bucket_split`` returns the new table's),
        whose ``SLOTS + 1`` words are all that crosses."""
        rec = self.flight_recorder
        if rec is None:
            return
        from ..ops.buckets import occupancy_from_histogram

        hist = np.asarray(histogram)
        rec.add_bytes(d2h=hist.nbytes)
        rec.record("occupancy", at=at, **occupancy_from_histogram(hist))

    # -- autosave + durability (stateright_tpu/checkpoint.py) ----------------

    def _autosave_manifest(self, snap: dict) -> dict:
        """The generation manifest: run identity + canonical config +
        checkpoint-time progress.  Self-describing enough that (a) resume
        picks generations without loading npz payloads and (b) the
        supervisor can archive a stub report for a run killed before its
        own ``join()`` (``checkpoint.stub_report_doc``)."""
        import datetime

        if self._autosave_config is None:
            from ..telemetry.report import build_config

            try:
                self._autosave_config = build_config(self)
            except Exception:  # noqa: BLE001 - identity must never
                self._autosave_config = {}  # break a checkpoint
        disc = np.asarray(snap.get("disc", np.zeros(0))).reshape(-1)
        props = []
        for i, p in enumerate(self._props):
            props.append({
                "name": p.name,
                "expectation": getattr(
                    p.expectation, "name", str(p.expectation)
                ).lower(),
                "discovery": bool(
                    i < disc.size and int(disc[i]) != 0
                ),
            })
        tag = (
            "wavefront" if self._engine_tag == "single"
            else self._engine_tag
        )
        man = {
            "run_id": self.run_id,
            "model": type(self.model).__name__,
            "engine": tag,
            "config": self._autosave_config,
            "totals": {
                "states": int(np.asarray(snap.get("scount", 0))),
                "unique": int(np.asarray(snap.get("unique", 0))),
                "max_depth": int(np.asarray(
                    snap.get("maxdepth", snap.get("depth", 0))
                )),
            },
            "properties": props,
            "restarts": self._restarts,
            "written_at": datetime.datetime.now(datetime.timezone.utc)
            .isoformat(timespec="seconds"),
        }
        if self.parent_run_id:
            man["parent_run_id"] = self.parent_run_id
        return man

    def _maybe_autosave(self, snap_fn, force: bool = False) -> None:
        """Write one autosave generation when the cadence is due (or
        ``force`` — the preemption-stop path snapshots uncondition-
        ally so a cooperative SIGTERM loses ~zero work).  ``snap_fn`` is
        a zero-arg thunk building the engine snapshot, called only when
        a save actually happens."""
        if self._heartbeat is not None:
            # the live heartbeat beats at every host sync that reaches
            # this seam (self-throttled), not only when a save is due
            self._heartbeat.beat(self.flight_recorder)
        svc = self._autosave
        if svc is None or not (force or svc.due()):
            return
        import time as _time

        from ..telemetry.spans import span as _span

        t0 = _time.monotonic()
        try:
            with _span(
                "autosave", self.flight_recorder,
                parent=self._run_span_ctx, gen=svc._gen,
            ):
                snap = snap_fn()
                svc.save(snap, self._autosave_manifest(snap))
        except Exception as e:  # noqa: BLE001 - checkpointing must never
            # kill the run it protects; OSErrors are handled (and warned
            # about) inside save(), anything else is accounted here
            from ..testing.faults import InjectedFault

            if isinstance(e, InjectedFault):
                # a scheduled chaos kill/oom at the snapshot seam must
                # reach the supervisor's classifier, not be swallowed —
                # it is manufactured process death, not a write failure
                raise
            svc._clock = _time.monotonic()  # a failing path must not
            # turn every subsequent sync into a fresh attempt
            svc.note_failure(svc._gen, e)
        self._stage("checkpoint", _time.monotonic() - t0)
        self._refresh_durability()

    def durability_status(self, live: bool = True) -> Optional[dict]:
        """The durability block (docs/robustness.md), or None when the
        run has neither autosave armed nor a supervision trail.
        ``live=False`` returns the DETERMINISTIC subset the run report
        embeds: the configured cadence, the restart count, and the
        degradation events — generation counts and checkpoint ages are
        wall-clock-shaped and stay in the live view (markdown /
        ``/.metrics`` / ``--watch``)."""
        svc = self._autosave
        if svc is None and not self._restarts and not self._degradations:
            return None
        from ..checkpoint import CKPT_V

        out: dict = {"v": CKPT_V, "restarts": self._restarts}
        if self._degradations:
            out["degradations"] = list(self._degradations)
        if svc is not None:
            if live:
                out["autosave"] = svc.status()
            else:
                out["autosave"] = {
                    "every_secs": svc.every_secs,
                    "keep": svc.keep,
                }
        return out

    def _refresh_durability(self) -> None:
        rec = self.flight_recorder
        if rec is None:
            return
        rec.set_durability(self.durability_status())

    # -- stop/checkpoint protocol (engines define _final_snapshot and serve
    # _ckpt_req at their host sync points) -----------------------------------

    def stop(self) -> "WavefrontChecker":
        """Ask the engine to stop at the next host sync (for checkpointing
        a run that should be resumed elsewhere)."""
        self._stop.set()
        return self

    def checkpoint(self, timeout: Optional[float] = 60.0) -> dict:
        """Snapshot the run state (numpy arrays, serializable with
        ``np.savez``).  Mid-run, the snapshot is taken at the next host sync;
        after completion it reflects the final state.  Continue with
        ``spawn_tpu(resume=snapshot)`` (same engine/mesh width)."""
        import time

        if self._done.is_set():
            return dict(self._final_snapshot)
        if self._thread is None:  # sync run already finished
            return dict(self._final_snapshot)
        with self._ckpt_lock:
            self._ckpt_req = self._ckpt_req or threading.Event()
            self._ckpt_ready.clear()
            self._ckpt_req.set()
            # Poll in small increments: the run can finish between our
            # request and its next checkpoint check, in which case the final
            # snapshot is the answer and waiting out the full timeout would
            # just stall.
            deadline = None if timeout is None else time.monotonic() + timeout
            while not self._ckpt_ready.wait(0.2):
                if self._done.is_set():
                    return dict(self._final_snapshot)
                if deadline is not None and time.monotonic() > deadline:
                    raise TimeoutError("checkpoint request not served")
            out, self._ckpt_out = self._ckpt_out, None
        if out is None:
            # ready fired without a snapshot: only possible when the run
            # completed concurrently — surface the final state, never None
            if self._done.is_set():
                return dict(self._final_snapshot)
            raise RuntimeError("checkpoint signalled ready without a snapshot")
        return out

    def _verify_fingerprint_bridge(self):
        """Host fingerprint must equal the device row hash, else traces cannot
        be reconstructed (the tensor analogue of the reference's
        nondeterminism diagnostics, ``path.rs:35-49``)."""
        for s in self.model.init_states():
            host_fp = self.model.fingerprint_state(s)
            row = np.asarray([self.tensor.encode_state(s)], dtype=np.uint64)
            dev_fp = int(np.asarray(row_hash(jnp.asarray(row)))[0])
            if host_fp != dev_fp:
                raise RuntimeError(
                    "model.fingerprint_state disagrees with the device row "
                    "hash; tensor-backed models must fingerprint via their "
                    "row encoding (mix in TensorBackedModel)"
                )
            break

    def _run(self):  # engine-specific
        raise NotImplementedError

    def _warn_small_space(self) -> None:
        """One-line footgun warning at run end: on real hardware a small
        space is overhead-dominated and CPU BFS is faster.  Silent on CPU
        backends (virtual-device test meshes explore small spaces on
        purpose) and on truncated runs — a run cut short by ``timeout()``,
        ``stop()``, or ``target_states()`` says nothing about the SPACE
        being small."""
        if self._stop.is_set() or self._target is not None:
            return
        try:
            platform = jax.devices()[0].platform
        except Exception:  # noqa: BLE001 - a warning must never break a run
            return
        unique = self._results["unique"] if self._results else 0
        if platform != "cpu" and 0 < unique < SMALL_SPACE_BREAK_EVEN:
            print(
                f"stateright-tpu: note: {unique} unique states is below the "
                f"~1e5-state overhead break-even on {platform}; "
                "spawn_auto() or spawn_bfs() is faster for small spaces",
                file=sys.stderr,
            )

    # -- Checker surface -----------------------------------------------------

    def is_done(self) -> bool:
        return self._done.is_set()

    def join(self) -> "WavefrontChecker":
        if self._thread is not None:
            self._thread.join()
        if self._run_error is not None:
            raise self._run_error
        self._maybe_write_report()
        return self

    # _maybe_write_report: inherited from Checker (checker/base.py)

    def por_status(self) -> Optional[dict]:
        """Partial-order-reduction status of this run, or None when
        ``por()`` was never requested: whether reduction is active, the
        fallback reason when not, and the live reduced-vs-full tallies
        (rows expanded with a reduced ample set, proviso-forced full
        expansions, candidates never generated)."""
        requested = self._por or self._por_fallback is not None
        if not requested:
            return None
        out = {
            "enabled": bool(self._por),
            "fallback": self._por_fallback,
            # which network packing the twin runs under (compiled actor
            # twins: "slot-multiset" | "per-channel"; hand-written twins
            # carry no encoding attribute) — reduction on the actor fleet
            # exists only under per-channel (docs/analysis.md)
            "encoding": getattr(self.tensor, "network_encoding", None),
        }
        stats = None
        if self._results and "por" in self._results:
            stats = self._results["por"]
        elif self._live_por is not None:
            stats = self._live_por
        if stats is not None:
            out.update(stats)
        return out

    def _por_stats_dict(self, arr) -> dict:
        """The packed por-stats triple as the JSON-facing dict."""
        arr = np.asarray(arr).astype(np.int64).reshape(-1)
        return {
            "rows_reduced": int(arr[0]),
            "rows_full_proviso": int(arr[1]),
            "candidates_masked": int(arr[2]),
        }

    def cartography(self) -> Optional[dict]:
        """Latest search-cartography snapshot (``ops/cartography.py``), or
        None when the run was spawned without
        ``.telemetry(cartography=True)``.  Mid-run this is the last host
        sync's counters; after completion, the final (exact) ones."""
        if self._results and "cartography" in self._results:
            return dict(self._results["cartography"])
        live = getattr(self, "_live_cart", None)
        return dict(live) if live else None

    def state_count(self) -> int:
        return self._results["states"] if self._results else 0

    def unique_state_count(self) -> int:
        return self._results["unique"] if self._results else 0

    def max_depth(self) -> int:
        return self._results["depth"] if self._results else 0

    def device_steps(self) -> int:
        """Steps of the device program so far (one step pops one batch):
        the sum of the ``dsteps`` of this run's ``step`` records.  Exact,
        and the same for every run of one model at one set of capacities."""
        return self._device_steps

    def occupancy_stats(self) -> Optional[dict]:
        """Bucket-occupancy counters of the visited table
        (``ops/buckets.occupancy_stats``), or None while the run is still
        in flight.  Also folded into the model's last audit report
        (``metrics["table"]``) so the perf preflight and the observed
        table behavior travel together (early table growth is
        diagnosed from exactly these counters)."""
        if not self._results:
            return None
        # The table is immutable once _results is set, but the Explorer
        # polls /.status continuously: cache per completed run so each
        # poll doesn't re-pull and re-histogram the whole table.
        cached = getattr(self, "_occupancy_cache", None)
        if cached is not None and cached[0] is self._results:
            stats = cached[1]
        else:
            from ..ops.buckets import occupancy_stats

            stats = occupancy_stats(self._table_np()[0])
            self._occupancy_cache = (self._results, stats)
        report = getattr(self.model, "_audit_report", None)
        if report is not None:
            report.metrics["table"] = stats
        return stats

    @staticmethod
    def _parents_from_table(tfp: np.ndarray, tpl: np.ndarray) -> dict[int, int]:
        """fp -> parent fp map from table arrays (shared by the joined and
        live paths so the occupancy/root encodings live in one place)."""
        tfp = np.asarray(tfp).reshape(-1)
        tpl = np.asarray(tpl).reshape(-1)
        occupied = tfp != np.uint64(MASK64)
        return dict(zip(tfp[occupied].tolist(), tpl[occupied].tolist()))

    @staticmethod
    def _walk(parents: dict[int, int], fp: int) -> list[int]:
        """Parent chain from an init state down to ``fp`` (0 marks "is an
        init state")."""
        fps = [fp]
        while True:
            parent = parents.get(fps[-1], 0)
            if parent == 0:
                break
            fps.append(parent)
        fps.reverse()
        return fps

    def _host_parents(self, tfp: np.ndarray, tpl: np.ndarray) -> dict[int, int]:
        """The fp -> parent map of every tier, from the pulled table (hook:
        the spill tier merges its store's segments in)."""
        return self._parents_from_table(tfp, tpl)

    def _parents(self, parent=None) -> dict[int, int]:
        """The fp -> parent map of every visited state, built once a run:
        the HOST path of reconstruction (a spill store that holds the
        roots; ``_device_table``).  ``parent``
        is the ``reconstruct`` span the two phases are children of."""
        if self._parent_map is None:
            rec = self.flight_recorder
            with tel_span("reconstruct.pull", rec, parent=parent) as sp:
                table = self._table_np()
                sp.set(bytes=sum(int(a.nbytes) for a in table))
            with tel_span("reconstruct.parents", rec, parent=parent,
                          path="host") as sp:
                self._parent_map = self._host_parents(*table)
                sp.set(lookups=len(self._parent_map))
        return self._parent_map

    def _device_table(self):
        """``(table_fp, table_payload)`` as they lie in the final carry -
        on one device or sharded over a mesh - where they hold every
        parent; None where the run reconstructs through the host map
        (hook)."""
        return None

    def _chains(self, parent=None) -> Optional[dict[int, np.ndarray]]:
        """Discovered fingerprint -> its parent chain (the leaf first),
        resolved ON THE DEVICE against the final carry's table
        (``ops/buckets.parent_chains``): one dispatch a run for every
        discovered property, and only the chains cross to the host.  None
        where the run reconstructs through the host map (``_parents``).

        A chain that misses or outgrows its bound is a bug (the table
        holds every visited state and no chain is longer than the search
        was deep) and raises: never a silently shorter path."""
        if self._chain_map is not None:
            return self._chain_map
        table = self._device_table()
        if table is None:
            return None
        from ..ops.buckets import (
            CHAIN_BOUND,
            CHAIN_ROOT,
            parent_chains,
            sharded_parent_chains,
        )

        rec = self.flight_recorder
        starts = np.asarray(self._results["disc"], np.uint64)
        # a power of two over the deepest chain there can be: one program
        # a table capacity for every model whose search is this deep
        bound = 1 << max(self.max_depth(), 15).bit_length()
        # a table sharded over a mesh is walked under its own sharding
        lies = table[0].sharding
        shards = len(lies.device_set)
        walk = parent_chains if shards == 1 else sharded_parent_chains(lies)
        with tel_span("reconstruct.parents", rec, parent=parent,
                      path="device", shards=shards) as sp:
            chains, lens, ends = walk(*table, starts, bound)
            # how each chain ended is the device call's answer, and the sync
            lens, ends = np.asarray(lens), np.asarray(ends)
            sp.set(lookups=int(lens.sum()))
        with tel_span("reconstruct.pull", rec, parent=parent) as sp:
            chains = np.asarray(chains)
            sp.set(bytes=int(chains.nbytes + lens.nbytes + ends.nbytes))
        broken = np.flatnonzero(ends != CHAIN_ROOT)
        if broken.size:
            k = broken[0]
            raise RuntimeError(
                f"the parent chain of discovery {int(starts[k]):#x} "
                + (f"is longer than its bound of {bound} states"
                   if ends[k] == CHAIN_BOUND else
                   f"leaves the visited table after {int(lens[k])} states")
                + ": the table does not hold the search that found it"
            )
        self._chain_map = {
            int(fp): chains[k, :lens[k]] for k, fp in enumerate(starts) if fp
        }
        return self._chain_map

    def _trace(self, fp: int, parent=None) -> list[int]:
        """Fingerprints from an init state down to ``fp``."""
        chains = self._chains(parent)
        parents = self._parents(parent) if chains is None else None
        with tel_span("reconstruct.walk", self.flight_recorder,
                      parent=parent):
            if chains is None:
                return self._walk(parents, fp)
            return chains[fp][::-1].tolist()

    def _symmetry_key(self):
        if self._symmetry is None:
            return None
        # device traces record canonical fingerprints; match classes.  A
        # twin may provide its own host-side key (the mechanical symmetry
        # of compiled models hashes a virtual canonical row rather than an
        # encodable representative state)
        tkey = getattr(self.tensor, "representative_key", None)
        if tkey is not None:
            return tkey
        sym, model = self._symmetry, self.model
        return lambda s: model.fingerprint_state(sym(s))

    def discoveries(self) -> dict[str, Path]:
        self.join()
        disc = self._results["disc"]
        key = self._symmetry_key()
        out = {}
        rec = self.flight_recorder
        # host seam span: resolving the parent links (on the device, or a
        # dict of the pulled table), what crosses to the host, the chain
        # walk and the host replay.  Whoever asks does so after the run span
        # (and a supervisor's attempt span) closed, so this is a span of
        # the run's trace with no parent — never a child that outlives one
        with tel_span("reconstruct", rec, trace_id=self._trace_id) as sp:
            for i, prop in enumerate(self._props):
                fp = int(disc[i])
                if fp != 0:
                    fps = self._trace(fp, parent=sp.ctx)
                    with tel_span("reconstruct.replay", rec, parent=sp.ctx):
                        out[prop.name] = Path.from_fingerprints(
                            self.model, fps, key=key
                        )
        return out

    def live_discoveries(
        self, skip: frozenset = frozenset(), timeout: float = 5.0
    ) -> dict[str, Path]:
        """Discoveries visible so far WITHOUT joining: the Explorer polls
        this while the device run is still in flight.  Discovery
        fingerprints ride the per-sync stats vector; the parent chain of a
        recorded discovery is immutable once written, so a one-off
        :meth:`checkpoint` (served at the next host sync) provides a table
        snapshot sufficient to parent-walk it.  ``skip`` names properties
        the caller has already reconstructed (first-wins fps never change):
        when every recorded discovery is in ``skip``, no checkpoint is taken
        at all, keeping repeated polls free.

        ``timeout`` bounds the snapshot wait: an Explorer poll landing in
        the middle of a long ``steps_per_call`` device block returns ``{}``
        and simply retries next poll instead of blocking the HTTP handler
        (and any concurrent :meth:`checkpoint` callers queued on
        ``_ckpt_lock``) for up to 30 s."""
        if self._done.is_set():
            return {
                n: p for n, p in self.discoveries().items() if n not in skip
            }
        disc = getattr(self, "_live_disc", None)
        if disc is None:
            return {}
        disc = np.asarray(disc)
        wanted = [
            (i, prop)
            for i, prop in enumerate(self._props)
            if prop.name not in skip and int(disc[i]) != 0
        ]
        if not wanted:
            return {}
        try:
            snap = self.checkpoint(timeout=timeout)
        except (TimeoutError, RuntimeError):
            return {}
        if self._done.is_set():  # finished while we snapshotted
            return {
                n: p for n, p in self.discoveries().items() if n not in skip
            }
        parents = self._parents_from_table(
            snap["table_fp"], snap["table_parent"]
        )
        key = self._symmetry_key()
        out = {}
        for i, prop in wanted:
            try:
                out[prop.name] = Path.from_fingerprints(
                    self.model, self._walk(parents, int(disc[i])), key=key
                )
            except RuntimeError:
                continue  # chain raced a growth boundary; next poll retries
        return out
