"""Checker result surface + builder (reference ``src/checker.rs``).

``CheckerBuilder`` is the fluent entry point (``model.checker()...``); the
``Checker`` base class is the uniform result surface shared by every strategy
(CPU BFS, CPU DFS, and the TPU wavefront engine), mirroring reference
``checker.rs:185-338``.
"""

from __future__ import annotations

import sys
import threading
import time
from typing import Callable, Optional, Sequence

from ..core import Expectation, Model, Property
from .path import Path
from .visitor import CheckerVisitor, FnVisitor

# States processed per lock round, as in the reference's job market
# (reference ``bfs.rs:120``, ``dfs.rs:126``).
JOB_BLOCK_SIZE = 1500


class CheckerBuilder:
    """Fluent checker configuration (reference ``checker.rs:35-179``)."""

    def __init__(self, model: Model):
        self.model = model
        self.symmetry_fn: Optional[Callable] = None
        self.symmetry_is_default = False
        self.target_state_count: Optional[int] = None
        self.thread_count: int = 1
        self.visitor_obj: Optional[CheckerVisitor] = None
        self.timeout_secs: Optional[float] = None
        self._audit_skip = False
        self.telemetry_opts: Optional[dict] = None
        self.report_path: Optional[str] = None
        # persistent run registry (telemetry/registry.py); None = env
        # default (STATERIGHT_TPU_RUN_DIR, off when unset)
        self.run_dir: Optional[str] = None
        self.checked_mode = False
        # wavefront-throughput knobs (docs/perf.md); None = env default
        self.prewarm_mode: Optional[bool] = None
        self.prededup_mode: Optional[bool] = None
        self.compile_cache_dir: Optional[str] = None
        # partial-order reduction (docs/analysis.md); None = env default
        self.por_mode: Optional[bool] = None
        # billion-state spill tier (docs/spill.md); None = env default
        self.spill_mode: Optional[bool] = None
        # MXU recast round (ops/mxu.py, docs/roofline.md); None = env
        # default (STATERIGHT_TPU_MXU, off when unset)
        self.mxu_opts: Optional[dict] = None
        # periodic crash-safe autosave (stateright_tpu/checkpoint.py,
        # docs/robustness.md); None = env default (STATERIGHT_TPU_AUTOSAVE)
        self.autosave_opts: Optional[dict] = None
        # hyper-batched instance sweep (stateright_tpu/sweep/,
        # docs/sweep.md); None = env default (STATERIGHT_TPU_SWEEP on
        # models that define sweep_family)
        self.sweep_spec = None
        # the multi-device mesh engine (parallel/mesh.py, docs/mesh.md);
        # None = env default (STATERIGHT_TPU_MESH, off when unset)
        self.mesh_mode: Optional[bool] = None
        self.mesh_devices: Optional[int] = None
        # span-trace context (telemetry/spans.py): set by the fleet
        # scheduler / supervisor so spawned engines parent their
        # engine_run spans under the job/attempt span; None = the engine
        # roots a fresh trace (standalone check)
        self._span_ctx = None

    # -- configuration -------------------------------------------------------

    def symmetry(self) -> "CheckerBuilder":
        """Dedupe on symmetry-class representatives; states must define
        ``representative()`` (reference ``checker.rs:150-154``)."""
        self.symmetry_fn = lambda s: s.representative()
        self.symmetry_is_default = True
        return self

    def symmetry_with(self, fn: Callable) -> "CheckerBuilder":
        self.symmetry_fn = fn
        self.symmetry_is_default = False
        return self

    def target_states(self, count: int) -> "CheckerBuilder":
        """Stop after roughly ``count`` unique states
        (reference ``checker.rs:163-167``)."""
        self.target_state_count = count
        return self

    def threads(self, count: int) -> "CheckerBuilder":
        self.thread_count = max(1, count)
        return self

    def visitor(self, v) -> "CheckerBuilder":
        self.visitor_obj = v if isinstance(v, CheckerVisitor) else FnVisitor(v)
        return self

    def timeout(self, secs: float) -> "CheckerBuilder":
        self.timeout_secs = secs
        return self

    def telemetry(
        self,
        enabled: bool = True,
        *,
        capacity: int = 4096,
        occupancy_every: int = 0,
        profile_steps: int = 0,
        profile_dir: Optional[str] = None,
        cartography: bool = False,
        memory: bool = False,
        memory_every: int = 32,
        roofline: bool = False,
        metrics: bool = False,
    ) -> "CheckerBuilder":
        """Attach a flight recorder to the spawned checker
        (``stateright_tpu/telemetry/``; schema in ``docs/telemetry.md``).

        Every strategy then streams one structured record per step — device
        engines per host sync, host engines per job block / mp round — into
        a bounded ring (``capacity`` records) exposed as
        ``checker.flight_recorder`` (JSONL/Chrome-trace export, the
        Explorer's ``/.metrics``, ``bench.py`` summaries).

        ``occupancy_every=N`` additionally samples the visited table's
        bucket-occupancy distribution every N host syncs on the device
        engines, plus a closing ``final`` sample — each a D2H table pull,
        priced in the recorder's transfer counters.  Growth boundaries are
        always sampled for free (the table is host-side there anyway);
        the final table stays on the device, so the run-end sample
        happens only under ``occupancy_every``.

        ``profile_steps=N`` arms a scoped ``jax.profiler`` trace of the
        first N hot steps into ``profile_dir`` (device engines only).

        ``memory=True`` attaches the HBM memory ledger
        (``telemetry/memory.py``, docs/telemetry.md "Memory ledger"):
        per-buffer analytic byte accounting for the device-resident
        carry, a growth-transient forecast feeding the health model's
        ``growth_oom_risk`` condition, live ``device.memory_stats()``
        readings where the backend has them, and ``memory`` ring records
        at growth boundaries plus a watermark sample every
        ``memory_every`` host syncs.  Pure host arithmetic over shapes
        the engines already know — zero ops added to the step jaxpr
        either way (pinned by test, the strongest form of the contract
        below).  ``report()`` implies it.

        ``roofline=True`` attaches the roofline cost ledger
        (``telemetry/roofline.py`` + ``analysis/costmodel.py``,
        docs/roofline.md): per-stage/per-op FLOPs-and-bytes attribution
        of the engine pipeline, reconciled against XLA's own
        ``cost_analysis()``, with memory-bound-vs-compute-bound stage
        verdicts where a device spec is known
        (``STATERIGHT_TPU_DEVICE_SPEC`` override) and the JX4xx
        MXU-candidate ranking.  Pure host-side analysis over re-traced
        kernels — the engine's step jaxpr stays bit-identical and the
        engine cache unkeyed either way (the memory ledger's contract,
        pinned by test).  Surfaces as ``checker.roofline()``, the run
        report's ``roofline`` block, ``/.metrics``, and the
        ``costmodel`` CLI verb.

        ``metrics=True`` attaches the process-wide live metrics bus
        (``telemetry/metrics.py``, docs/observability.md): the recorder
        publishes the engine metric families (states/s, frontier size,
        table load, dedup rate, step-time histogram) at host syncs that
        already happen, and the Explorer serves them as Prometheus text
        on ``GET /metrics``.  ``STATERIGHT_TPU_METRICS=1`` is the env
        form.  Pure host-side aggregation of values already in hand —
        zero extra device round-trips, and with the bus detached the
        step-record stream is bit-identical (parity pinned by test).

        ``cartography=True`` additionally folds the search-cartography
        counters into the device step (``ops/cartography.py``,
        docs/telemetry.md): per-depth frontier sizes, the per-action
        successor histogram, per-property evaluation tallies (the mesh
        engine adds per-shard table loads and the parent-owner ->
        child-owner routing matrix, read off its final table on the
        host).  This is the one telemetry option that changes the step
        program (small integer reductions riding the existing packed
        stats vector; measured ≤5% on 2pc-7, pinned in the slow tier);
        off, the step jaxpr stays bit-identical.  The counters surface as
        ``checker.cartography()``, the recorder's ``cartography`` block,
        the Explorer's ``/.metrics``, and the run report.

        Telemetry off (the default) is exactly the pre-telemetry engine:
        zero ops added to the step jaxpr, no recorder allocated."""
        if not enabled:
            self.telemetry_opts = None
            return self
        # Flags implied earlier (``.report()``/``.cartography()``/
        # ``.memory_ledger()``) are sticky: reconfiguring the recorder
        # must not silently drop the counters/ledger the report contract
        # depends on.
        implied_cart = bool(self.telemetry_opts) and bool(
            self.telemetry_opts.get("cartography")
        )
        implied_mem = bool(self.telemetry_opts) and bool(
            self.telemetry_opts.get("memory")
        )
        implied_roof = bool(self.telemetry_opts) and bool(
            self.telemetry_opts.get("roofline")
        )
        implied_metrics = bool(self.telemetry_opts) and bool(
            self.telemetry_opts.get("metrics")
        )
        # a previously configured cadence is part of the sticky ledger
        # config: keep it unless this call sets one explicitly
        prev_every = (
            self.telemetry_opts.get("memory_every")
            if implied_mem and memory_every == 32
            else None
        )
        self.telemetry_opts = {
            "capacity": capacity,
            "occupancy_every": occupancy_every,
            "profile_steps": profile_steps,
            "profile_dir": profile_dir,
            "cartography": bool(cartography) or implied_cart,
            "memory": bool(memory) or implied_mem,
            "memory_every": int(
                prev_every if prev_every is not None else memory_every
            ),
            "roofline": bool(roofline) or implied_roof,
            "metrics": bool(metrics) or implied_metrics,
        }
        return self

    def cartography(self, enabled: bool = True) -> "CheckerBuilder":
        """Fold the search-cartography counters into the run — a
        ``.telemetry(cartography=True)`` shorthand that composes with an
        existing telemetry config instead of replacing it.  ``report()``
        and the CLI ``--watch`` flag imply it; this method is the one
        place the imply-rule mutates the telemetry options."""
        if not enabled:
            return self
        if self.telemetry_opts is None:
            self.telemetry()
        self.telemetry_opts["cartography"] = True
        return self

    def memory_ledger(self, enabled: bool = True) -> "CheckerBuilder":
        """Attach the HBM memory ledger (``telemetry/memory.py``) — a
        ``.telemetry(memory=True)`` shorthand that composes with an
        existing telemetry config instead of replacing it.  ``report()``
        and the CLI ``--watch`` flag imply it."""
        if not enabled:
            return self
        if self.telemetry_opts is None:
            self.telemetry()
        self.telemetry_opts["memory"] = True
        self.telemetry_opts.setdefault("memory_every", 32)
        return self

    def roofline(self, enabled: bool = True) -> "CheckerBuilder":
        """Attach the roofline cost ledger (``telemetry/roofline.py``) —
        a ``.telemetry(roofline=True)`` shorthand that composes with an
        existing telemetry config instead of replacing it (the
        ``cartography()``/``memory_ledger()`` pattern)."""
        if not enabled:
            return self
        if self.telemetry_opts is None:
            self.telemetry()
        self.telemetry_opts["roofline"] = True
        return self

    def report(self, path: str) -> "CheckerBuilder":
        """Write a post-run report to ``path`` (JSON; a sibling ``.md``
        rendering lands next to it) at the first ``join()`` after the run
        completes — the artifact a human reads after an unattended on-chip
        run (``stateright_tpu/telemetry/report.py``; docs/telemetry.md
        "Reading a run report").  Implies telemetry with cartography AND
        the memory ledger: the report combines the run totals, the
        cartography block, the memory block (analytic — deterministic),
        the health timeline, growth events, and the audit/sanitizer
        status.  The JSON
        body is deterministic for a fixed model/config — wall-clock-
        dependent values live in the markdown rendering only, and the
        volatile fields are exactly the identity header named by
        ``telemetry.report.VOLATILE_KEYS`` (``generated_at``,
        ``run_id``, and ``parent_run_id`` on snapshot-resumed runs)."""
        import os as _os

        if _os.path.splitext(str(path))[1] == ".md":
            raise ValueError(
                f"report path {path!r} ends in .md — pass the JSON path; "
                "the markdown rendering lands next to it as <path-stem>.md"
            )
        self.report_path = str(path)
        return self.cartography().memory_ledger()

    def runs(self, path: str) -> "CheckerBuilder":
        """Archive this run into the persistent run registry rooted at
        ``path`` (``telemetry/registry.py``; docs/telemetry.md "Comparing
        runs"): at the first ``join()`` after completion the
        deterministic report body lands under ``<path>/runs/<run_id>.json``
        and one index record — canonical ``config_key`` + headline
        metrics — appends to ``<path>/index.jsonl``.  Composable with
        ``report()`` (the archived body is the same document).

        Contract (the memory ledger's strongest form, pinned by test):
        the registry is pure host-side post-run I/O — on or off, the
        step jaxpr is bit-identical and the engine cache unkeyed, both
        engines.  Env equivalent: ``STATERIGHT_TPU_RUN_DIR=DIR``
        (archives every run in the process)."""
        self.run_dir = str(path)
        return self

    def prewarm(self, enabled: bool = True) -> "CheckerBuilder":
        """Growth-stall elision for the single-device wavefront engine
        (``docs/perf.md``): the growth ladder's next capacity rungs are
        compiled AHEAD OF TIME on a background thread
        (``jax.jit(...).lower().compile()``), so a growth boundary swaps in
        a ready executable instead of blocking the run on a cold engine
        compile.  Wrong predictions cost one wasted background compile and
        nothing else; the consumed/wasted split and the per-boundary wait
        are recorded in the flight recorder (``compile`` events:
        ``source="prewarm"``, ``duration``).  Default off (env override
        ``STATERIGHT_TPU_PREWARM=1``); search semantics are untouched —
        the prewarmed executable is the SAME program, compiled earlier."""
        self.prewarm_mode = bool(enabled)
        return self

    def prededup(self, enabled: bool = True) -> "CheckerBuilder":
        """Device-side intra-window candidate pre-dedup
        (``ops/buckets.window_unique``; ``docs/perf.md``): duplicate
        fingerprints inside one expansion window are masked to EMPTY before
        the visited-set insert, shrinking the insert pipeline's effective
        width to the window's unique count (the BLEST move: dedup the
        frontier BEFORE the expensive global-memory phase).  Equivalence
        contract, pinned by tests: unique/state counts, discovery traces,
        and the inserted table are bit-identical with the flag on or off —
        the filter keeps exactly the lane ``bucket_insert``'s stable sort
        would have kept.  Default off (env override
        ``STATERIGHT_TPU_PREDEDUP=1``); with the flag off the step jaxpr
        is unchanged (same contract as telemetry/checked)."""
        self.prededup_mode = bool(enabled)
        return self

    def compile_cache(self, path: str) -> "CheckerBuilder":
        """Opt into JAX's persistent compilation cache at ``path``
        (``docs/perf.md``): engine executables are cached on disk keyed on
        their HLO, so repeated CLI/bench/regress invocations skip XLA
        engine compiles entirely (including every growth rung a previous
        run already visited).  Applies process-wide on first engine spawn
        — the cache dir is a global JAX setting.  When
        ``JAX_COMPILATION_CACHE_DIR`` is set, that directory wins and
        ``path`` is ignored (``prewarm.resolve_compile_cache_dir``).
        Per-rung hits are recorded in the flight recorder's ``compile``
        events (``cache_hit``)."""
        self.compile_cache_dir = str(path)
        return self

    def por(self, enabled: bool = True) -> "CheckerBuilder":
        """Partial-order reduction on the device engines
        (``docs/analysis.md`` "State-space reduction"): the static
        independence analysis (``analysis/independence.py``) derives a
        per-model action×action conflict matrix from jaxpr footprints at
        BitPacker-field granularity; the engines then mask each state's
        enabled-action set down to a minimal conflict-closed **ample
        subset** (a stubborn-set closure computed on device), with a
        conservative cycle proviso — a state whose ample successors are
        all duplicates is fully expanded, as is the first batch after
        every growth/resume boundary.

        Soundness contract (pinned by tests): property verdicts are
        IDENTICAL to full expansion.  The analysis enforces this by
        falling back to full expansion whenever reduction could be
        unsound — ``eventually``/liveness properties, property-footprint
        conflicts (an ample set may not contain a property-visible
        action), undecidable footprints (conservatively dependent), or a
        boundary-filtered twin.  With the flag OFF (the default) the step
        jaxpr is bit-identical to a pre-POR engine (the
        telemetry/checked/prededup discipline); env override
        ``STATERIGHT_TPU_POR=1``.  Composes with ``symmetry()`` and
        ``prededup()``."""
        self.por_mode = bool(enabled)
        return self

    def mxu(
        self,
        enabled: bool = True,
        *,
        coalesce: bool = True,
        probe: bool = True,
    ) -> "CheckerBuilder":
        """Arm the MXU recast round on the device engines
        (``stateright_tpu/ops/mxu.py``; docs/roofline.md "Executing the
        hot-spot list"): two flag-gated bytes-moved reductions
        executing PR 11's ranked JX4xx hot spots —

        - ``coalesce``: trace the twin's expand-scatter-coalesced step
          kernel (``step_rows_coalesced``; hand twins + per-channel
          compiled twins) — each action piece's packed-field write-backs
          assemble as one word-stacked block instead of one scatter per
          field (the paxos-3 #1 hot spot: 37 sites, 109 MB/step).
          Twins without a coalesced form silently keep the plain kernel;
        - ``probe``: the BLEST one-hot membership probe — the bucket
          membership/occupancy reductions become one blocked bitmapped
          ``dot_general`` over the candidate x slot comparison tile,
          giving the dedup-insert stage a genuine dot-class op (the
          2pc-7 #1 hot spot).

        Contract, pinned by tests (the prededup/spill discipline): OFF
        (the default) leaves the step jaxpr bit-identical and the engine
        cache unkeyed; ON keeps unique/total counts, property verdicts,
        and discovery traces bit-identical across the fleet — the
        transforms move the same information through cheaper shapes.
        The roofline ledger (``.roofline()``) measures the payoff;
        ``regress.py --mxu`` gates it.  Env override
        ``STATERIGHT_TPU_MXU=1`` (both components); composes with
        ``symmetry()``/``por()``/``prededup()``/``spill()``."""
        if not enabled:
            # explicit off wins over the env knob (resolve_flag's rule):
            # an all-off component dict resolves to None without ever
            # consulting STATERIGHT_TPU_MXU
            self.mxu_opts = {"coalesce": False, "probe": False}
            return self
        self.mxu_opts = {"coalesce": bool(coalesce), "probe": bool(probe)}
        return self

    def mesh(
        self, enabled: bool = True, *, devices: Optional[int] = None
    ) -> "CheckerBuilder":
        """Run ``spawn_tpu`` on the mesh engine
        (``stateright_tpu/parallel/mesh.py``; docs/mesh.md), the one
        multi-device engine: the single-device wavefront program
        partitioned over a named ``('host', 'chip')`` device mesh with
        ``NamedSharding`` rules — visited table sharded by bucket owner,
        queue buffers sharded, counters replicated — so the compiler
        inserts the cross-shard collectives.

        Parity contract, pinned by tests/test_mesh.py: unique/total
        counts, property verdicts, discovery traces, and kill+resume
        snapshots are bit-identical to the single-device wavefront
        engine (the programs ARE the wavefront engine's; only placement
        differs).  ``devices=N`` bounds the mesh to the first N local
        devices (default: all of them).  Env override
        ``STATERIGHT_TPU_MESH=1`` (or ``=N`` for a device bound).  The
        ``devices=`` / ``n_devices=`` / ``mesh=`` arguments of
        ``spawn_tpu`` ask for the same engine (:meth:`spawn_tpu`); every
        width that is named must be the same one."""
        self.mesh_mode = bool(enabled)
        self.mesh_devices = int(devices) if devices is not None else None
        return self

    def spill(self, enabled: bool = True) -> "CheckerBuilder":
        """Arm the billion-state spill tier on the wavefront engine
        (``stateright_tpu/spill/``; docs/spill.md): the visited set
        becomes a TIERED store — the HBM bucket table as the hot tier,
        backed by a host-RAM append-only fingerprint store (hash-indexed)
        with an mmap'd disk tier behind it.  When PR 7's capacity plan
        says the next growth rung's migration transient will not fit the
        device budget (live ``bytes_limit`` or the
        ``STATERIGHT_TPU_DEVICE_BYTES`` override), the engine EVICTS the
        hot table's contents to the host tier at the growth boundary
        instead of growing; a device-side Bloom filter over the spilled
        set (bit-slices of ``mix64(fp)``) answers "definitely not seen"
        on-device, so only Bloom-positive candidates are resolved against
        the host index at host sync.

        Contracts, pinned by tests/test_spill.py: spill OFF (the
        default) leaves the step jaxpr bit-identical and the engine
        cache unkeyed; spill ON keeps unique/total counts and property
        verdicts bit-identical to an unconstrained run, with the
        cartography block reconciling exactly.  The snapshot manifest
        carries the host/disk tier contents, so kill+resume works
        mid-spill.  Env override ``STATERIGHT_TPU_SPILL=1``; one device
        only (the mesh engine rejects it with guidance), and
        mutually exclusive with ``por()`` for now.  Spawn knobs:
        ``spill_bloom_bits``, ``spill_dir``, ``spill_host_bytes``
        (host-tier budget before the disk tier takes over; env
        ``STATERIGHT_TPU_HOST_BYTES``)."""
        self.spill_mode = bool(enabled)
        return self

    def autosave(
        self,
        path: str,
        every_secs: float = 60.0,
        keep: int = 3,
    ) -> "CheckerBuilder":
        """Periodically autosave the run to rotating snapshot generations
        under ``path`` (``stateright_tpu/checkpoint.py``;
        docs/robustness.md): at host-sync boundaries, once ``every_secs``
        has elapsed (``0`` = every host sync), the device engines write
        their resume snapshot as ``gen-NNNNNN/snapshot.npz`` + a
        ``MANIFEST.json`` committed LAST — both through the atomic write
        discipline (tmp + fsync + ``os.replace``), so a crash mid-save
        leaves a torn generation that resume detects and skips, never a
        poisoned one.  The newest ``keep`` complete generations are
        retained.

        Resume with ``spawn_tpu(resume=checkpoint.latest_generation(DIR)
        [0])`` — or run under ``supervisor.supervise``, which wires
        autosave + classify + retry/backoff end to end.  Each save emits
        a versioned ``checkpoint`` ring record and a ``stage_checkpoint``
        attribution counter, so the cadence's cost is visible in the
        stage breakdown.  Contract (the registry's form, pinned): on or
        off, the step jaxpr is bit-identical and the engine cache
        unkeyed — autosave is pure host-side I/O at sync boundaries.
        Env equivalent: ``STATERIGHT_TPU_AUTOSAVE=DIR`` (cadence/keep
        via ``STATERIGHT_TPU_AUTOSAVE_SECS``/``_KEEP``)."""
        self.autosave_opts = {
            "dir": str(path),
            "every_secs": float(every_secs),
            "keep": int(keep),
        }
        return self

    def sweep(self, spec) -> "CheckerBuilder":
        """Check a whole model family in one device run
        (``stateright_tpu/sweep/``; docs/sweep.md): ``spec`` is a
        :class:`~stateright_tpu.sweep.SweepSpec` enumerating instances
        (lossiness flags, bounds, initial values, table seeds).
        ``spawn_tpu`` then returns a
        :class:`~stateright_tpu.sweep.engine.SweepChecker`: instances
        group into shape cohorts, each cohort compiles ONE wavefront
        step program (per-instance constants gathered by a row tag),
        and all instances of a cohort explore concurrently over a
        shared visited table whose fingerprints are namespaced per
        instance — so each instance's unique/total counts, property
        verdicts, and discovery traces reconcile bit-identically
        against its own sequential run (pinned by tests).

        Contract (the registry's strongest form, by construction): with
        no sweep requested, ``spawn_tpu`` builds exactly the pre-sweep
        engine — step jaxpr bit-identical, engine cache unkeyed.  Env
        equivalent: ``STATERIGHT_TPU_SWEEP=N`` on models that define
        ``sweep_family(N)``.  A sweep composes with telemetry /
        cartography / report / runs / timeout / target; it rejects
        checked/por/spill/mxu/symmetry/prededup/autosave with guidance.
        """
        self.sweep_spec = spec
        return self

    def checked(self, enabled: bool = True) -> "CheckerBuilder":
        """Checked execution mode: the sanitizer's DYNAMIC guard
        (``docs/analysis.md``).  The device wavefront runs the same
        exploration with the model kernels under
        ``jax.experimental.checkify`` index/nan/div instrumentation and
        fails loudly — a
        :class:`~stateright_tpu.analysis.CheckedExecutionError` naming the
        offending row (index, raw words, decoded state) — instead of
        letting an out-of-bounds gather silently clamp and prune the
        search.  Use it when the static sanitizer reports an *undecided*
        site (JX201/JX202 info) or to confirm a marginal JX203 overflow.

        Contract, mirroring telemetry's: ``checked=False`` (the default)
        leaves the step jaxpr bit-identical to an engine without the
        feature (pinned by test); ``checked=True`` pays the checkify
        instrumentation cost and is a debugging mode, not a bench
        configuration.  Host checkers ignore the flag (Python raises
        eagerly there)."""
        self.checked_mode = bool(enabled)
        return self

    def _make_recorder(self, engine: str):
        """FlightRecorder per the builder's telemetry options (None when
        telemetry is off) — shared by every spawn path."""
        if self.telemetry_opts is None:
            return None
        from ..telemetry import FlightRecorder

        metrics = None
        if self.telemetry_opts.get("metrics"):
            from ..telemetry import default_bus

            metrics = default_bus()
        return FlightRecorder(
            capacity=self.telemetry_opts["capacity"],
            meta={
                "engine": engine,
                "model": type(self.model).__name__,
            },
            metrics=metrics,
        )

    # -- static preflight audit (stateright_tpu/analysis/) -------------------

    def audit(self, *, deep: bool = True) -> "object":
        """Run the static auditor over the model and return the
        :class:`~stateright_tpu.analysis.AuditReport` — jaxpr kernel audit
        of the tensor twin, actor-handler lint, config-drift checks
        (rule catalogue: ``docs/analysis.md``).  ``deep=True`` adds the
        bounded closure-domain probe and the fresh-twin drift re-resolve."""
        from ..analysis import audit_model

        return audit_model(self.model, deep=deep)

    def skip_audit(self) -> "CheckerBuilder":
        """Escape hatch: disable the automatic ``spawn_tpu`` preflight
        audit for this builder (e.g. to reproduce a flagged defect on
        device, or when a rule false-positives on exotic kernels)."""
        self._audit_skip = True
        return self

    def _preflight_audit(self) -> None:
        """Audit before any device launch: errors abort (raising
        :class:`~stateright_tpu.analysis.AuditError`), warnings print once
        per model.  Disabled by :meth:`skip_audit` or the
        ``STATERIGHT_TPU_SKIP_AUDIT=1`` env knob."""
        import os

        if self._audit_skip or os.environ.get("STATERIGHT_TPU_SKIP_AUDIT") == "1":
            return
        from ..analysis import AuditError, Severity, audit_model

        try:
            report = audit_model(self.model, deep=False)
        except Exception:  # noqa: BLE001 - the audit must never mask the
            return  # engine's own (more specific) spawn-time error surface
        if report.errors:
            raise AuditError(
                report, context=f"spawn_tpu({type(self.model).__name__})"
            )
        if report.warnings and not getattr(
            self.model, "_audit_warn_printed", False
        ):
            try:
                object.__setattr__(self.model, "_audit_warn_printed", True)
            except Exception:  # noqa: BLE001 - __slots__ models
                pass
            print(
                report.format(min_severity=Severity.WARNING), file=sys.stderr
            )

    # -- strategies ----------------------------------------------------------

    def spawn_bfs(self) -> "Checker":
        from .bfs import BfsChecker

        return BfsChecker(self)

    def spawn_dfs(self) -> "Checker":
        from .dfs import DfsChecker

        return DfsChecker(self)

    def spawn_mp_bfs(self, processes: Optional[int] = None) -> "Checker":
        """Process-parallel BFS: real multi-core checking (the thread pool
        above is GIL-bound).  Fingerprint-ownership sharding over forked
        workers — the CPU analogue of the mesh engine's table sharding;
        see ``checker/mp.py``.  ``processes`` defaults to
        ``threads(N)`` if set above 1, else all cores."""
        from .mp import MpBfsChecker

        return MpBfsChecker(self, processes=processes)

    def spawn_auto(self, probe_secs: float = 2.0, **tpu_kw) -> "Checker":
        """Pick the engine by *measured* space size, fixing the small-space
        footgun: the device engine pays a fixed per-run cost (engine
        compile or cache retrieval, host syncs, table setup) that
        dominates small spaces, where CPU BFS wins (chip_smoke.py on a
        v5e: paxos-2's 16,668 states take seconds of set-up on the
        device; the break-even constant is
        ``parallel/_base.SMALL_SPACE_BREAK_EVEN``).

        Strategy: (1) a thread-engine probe runs first, bounded by
        ``probe_secs`` — if the space exhausts within the budget, the
        finished checker IS the result and nothing bigger is ever paid
        for; (2) a space that outlives the probe escalates to the
        heavier engine, having spent only the probe budget (and with the
        probe's wall-clock deducted from any user ``timeout()``).  The
        heavier engine is the device wavefront (``tpu_kw`` passes
        through to :meth:`spawn_tpu`) — except with a visitor, which
        device engines reject, where it is the process-parallel mp-BFS
        (multi-core + visitor via replay), available only where ``fork``
        exists.  Models with no tensor twin or a compile error check on
        the thread engines outright.  With ``symmetry()`` the probe uses
        DFS — the host thread engine that supports representative dedup,
        as in the reference where symmetry is DFS-only."""
        import time as _time

        cpu_spawn = self.spawn_dfs if self.symmetry_fn else self.spawn_bfs

        def probe_then(escalate, small=None):
            """Visitor-free sizing probe on the thread engine, then either
            the ``small`` outcome (default: the finished probe itself) or
            ``escalate``.

            Timeout semantics: without a visitor, the probe's wall-clock
            is deducted from the user ``timeout()`` so total time stays
            within budget.  WITH a visitor the final engine gets the FULL
            user timeout instead (total may overshoot by at most
            ``probe_secs``): callbacks must fire exactly once on a
            fully-budgeted run — deducting would let an internal probe
            starve the visible run into a partial result, or swallow the
            callbacks entirely."""
            if (
                self.timeout_secs is not None
                and self.timeout_secs <= probe_secs
            ):
                return cpu_spawn()  # the whole run fits in the probe budget
            saved = self.timeout_secs
            vis, self.visitor_obj = self.visitor_obj, None
            self.timeout_secs = probe_secs
            t0 = _time.monotonic()
            try:
                probe = cpu_spawn().join()
            finally:
                self.timeout_secs = saved
                self.visitor_obj = vis
            if not probe.timed_out:
                return probe if small is None else small()
            if saved is None or vis is not None:
                return escalate()
            remaining = saved - (_time.monotonic() - t0)
            if remaining <= 0:
                return probe  # budget gone: the partial probe result is it
            self.timeout_secs = remaining
            try:
                return escalate()
            finally:
                self.timeout_secs = saved

        if self.visitor_obj is not None:
            # device engines reject visitors (they never materialize
            # states), so big spaces escalate to the process-parallel
            # BFS instead (visitors via replay, symmetry supported) —
            # when there are cores to win and fork exists (the model
            # travels to workers by address-space inheritance).  The
            # probe runs visitor-FREE (callbacks must fire exactly once,
            # on the final engine only); a small space then re-runs the
            # thread engine with the visitor attached, which the probe
            # just proved cheap.
            import multiprocessing as _mp
            import os as _os

            can_mp = (_os.cpu_count() or 1) > 1 and (
                "fork" in _mp.get_all_start_methods()
            )
            if not can_mp:
                return cpu_spawn()
            return probe_then(self.spawn_mp_bfs, small=cpu_spawn)
        from ..parallel.tensor_model import twin_or_none

        if twin_or_none(self.model) is None:
            return cpu_spawn()
        return probe_then(lambda: self.spawn_tpu(**tpu_kw))

    def _mesh_request(self, kw: dict) -> Optional[dict]:
        """The one rule for "more than one device": ``None`` when the run
        is a one-device run, else the ``mesh=`` / ``n_devices=`` arguments
        of :class:`~stateright_tpu.parallel.mesh.MeshTpuChecker`.

        Every spelling asks for the same engine: ``devices=N`` (N > 1),
        ``n_devices=N`` and ``mesh=<Mesh>`` among ``kw`` (read, never
        popped), :meth:`mesh`, ``--mesh`` and ``STATERIGHT_TPU_MESH``.
        They cannot disagree on the engine; the widths they name must be
        one width."""
        from ..parallel.partition import resolve_mesh_flag

        on, flag_n = resolve_mesh_flag(self.mesh_mode, self.mesh_devices)
        mesh = kw.get("mesh")
        named = {
            "devices=": kw.get("devices") or None,
            "n_devices=": kw.get("n_devices"),
            "mesh=": None if mesh is None else mesh.size,
            ".mesh(devices=) / STATERIGHT_TPU_MESH": flag_n if on else None,
        }
        named = {k: int(v) for k, v in named.items() if v is not None}
        if len(set(named.values())) > 1:
            raise ValueError(
                "spawn_tpu was asked for different numbers of devices: "
                + ", ".join(f"{k} {v}" for k, v in named.items())
                + " (name one width; docs/mesh.md)"
            )
        n = next(iter(named.values()), None)
        if not (on or mesh is not None or (n is not None and n > 1)):
            return None
        return {"mesh": mesh, "n_devices": n}

    def spawn_tpu(self, **kw) -> "Checker":
        """The point of this framework: wavefront BFS on TPU (no reference
        counterpart; see ``stateright_tpu/parallel/wavefront.py``).

        One rule picks the engine.  A sweep spec runs the
        :class:`~stateright_tpu.sweep.engine.SweepChecker`.  More than
        one device, asked for by ``devices=N`` (N > 1), ``n_devices=N``,
        ``mesh=<Mesh>``, :meth:`mesh`, ``--mesh`` or
        ``STATERIGHT_TPU_MESH``, runs the mesh engine
        (``stateright_tpu/parallel/mesh.py``, docs/mesh.md): the same
        program with its carry placed over the devices, so counts,
        verdicts and paths equal the one-device run's.  Anything else
        runs :class:`~stateright_tpu.parallel.wavefront.TpuChecker`.

        A static preflight audit runs first (``docs/analysis.md``): audit
        errors abort here, before any device work; silence deliberately
        with :meth:`skip_audit`."""
        from ..sweep import resolve_sweep_spec

        mesh_kw = self._mesh_request(kw)
        for name in ("devices", "n_devices", "mesh"):
            kw.pop(name, None)
        spec = resolve_sweep_spec(
            getattr(self, "sweep_spec", None), self.model
        )
        if spec is not None:
            if mesh_kw is not None:
                raise NotImplementedError(
                    "sweep x mesh is a queued unlock (ROADMAP): sweeps "
                    "run on the single-device engine for now — drop the "
                    "devices/mesh argument, .mesh(), --mesh and "
                    "STATERIGHT_TPU_MESH (docs/sweep.md)"
                )
            # audit once per distinct SHAPE of the family (the cohort
            # grouping key: twin class + row layout + properties) —
            # same-shape members share kernels, so auditing each would
            # re-trace the same programs N times, while differently
            # configured same-class members (lossy vs non-lossy paxos)
            # still get their own preflight
            from ..sweep.cohort import shape_signature

            seen = set()
            for inst in spec.instances:
                try:
                    sig = shape_signature(inst)
                except Exception:  # noqa: BLE001 - twin failures surface
                    sig = id(inst)  # in the audit below, per instance
                if sig in seen:
                    continue
                seen.add(sig)
                saved = self.model
                self.model = inst.model
                try:
                    self._preflight_audit()
                finally:
                    self.model = saved
            from ..sweep.engine import SweepChecker

            return SweepChecker(self, spec, **kw)
        self._preflight_audit()
        if mesh_kw is not None:
            from ..parallel.mesh import MeshTpuChecker

            return MeshTpuChecker(self, **mesh_kw, **kw)
        from ..parallel.wavefront import TpuChecker

        return TpuChecker(self, **kw)

    def serve(
        self, addr: str = "localhost:3000", strategy: str = "bfs", **spawn_kw
    ):
        """Spawn a check and serve the Explorer web UI over it (reference
        ``checker.rs:108-114``).  ``strategy="tpu"`` browses a device
        wavefront run (beyond the reference, whose Explorer wraps only
        ``BfsChecker``); with it, extra keyword arguments pass through to
        ``spawn_tpu``."""
        try:
            from ..explorer import serve
        except ImportError as e:
            raise NotImplementedError("the Explorer is not available yet") from e
        return serve(self, addr, strategy=strategy, **spawn_kw)


class Checker:
    """Uniform result surface for all strategies
    (reference ``checker.rs:185-338``)."""

    model: Model
    # run telemetry (stateright_tpu/telemetry/): a FlightRecorder when the
    # builder requested .telemetry(), else None on every strategy
    flight_recorder = None
    # post-run report (telemetry/report.py): the builder's .report(PATH),
    # honored at the first join() after completion on EVERY strategy (host
    # runs simply carry no cartography block)
    _report_path: Optional[str] = None
    _report_written = False
    # persistent run registry (telemetry/registry.py): the builder's
    # .runs(DIR) (or STATERIGHT_TPU_RUN_DIR), honored like the report
    _run_dir: Optional[str] = None
    _run_recorded = False
    _report_reentry = False
    # run identity (docs/telemetry.md "Comparing runs"): minted lazily,
    # stamped into the report header, snapshot manifests, and the
    # registry index; parent_run_id set by snapshot resume
    _run_id: Optional[str] = None
    parent_run_id: Optional[str] = None

    @property
    def run_id(self) -> str:
        """Stable unique id of this run (16 hex chars)."""
        if self._run_id is None:
            import uuid

            self._run_id = uuid.uuid4().hex[:16]
        return self._run_id

    def _maybe_write_report(self) -> None:
        """Write the builder-requested run report (and archive into the
        run registry when one is configured) exactly once, at the first
        join() after completion (never from inside a run thread: the
        report reconstructs discovery paths, which joins)."""
        if not self.is_done():
            return
        body = None
        if self._report_path and not self._report_written:
            self._report_written = True  # before write: never retry a crash
            from ..telemetry.report import write_report

            # building the report reconstructs discovery paths, which
            # JOINS and re-enters this method — hold the registry off
            # until the body exists, so the archive reuses it instead of
            # building a second one from the nested call
            self._report_reentry = True
            try:
                body = write_report(self, self._report_path)
            finally:
                self._report_reentry = False
        self._maybe_record_run(body)

    def _maybe_record_run(self, body=None) -> None:
        """Archive the completed run into the persistent registry when
        one is configured (builder ``.runs(DIR)`` or
        ``STATERIGHT_TPU_RUN_DIR``) — pure post-run host I/O, exactly
        once, never fatal to the join.  ``body`` reuses the report body
        ``write_report`` just built (building one reconstructs discovery
        paths; it must not run twice per join)."""
        if self._run_recorded or self._report_reentry:
            return
        from ..telemetry.registry import resolve_run_dir

        root = resolve_run_dir(self._run_dir)
        if not root:
            return
        self._run_recorded = True  # before write: never retry a crash
        try:
            from ..telemetry.registry import RunRegistry

            RunRegistry(root).record(self, body=body)
        except Exception as e:  # noqa: BLE001 - the ledger must never
            # break a join
            print(
                f"stateright-tpu: run-registry write failed: "
                f"{type(e).__name__}: {e}",
                file=sys.stderr,
            )

    # -- strategy-provided ---------------------------------------------------

    def state_count(self) -> int:
        """Total states generated, including duplicates."""
        raise NotImplementedError

    def unique_state_count(self) -> int:
        raise NotImplementedError

    def max_depth(self) -> int:
        return 0

    def discoveries(self) -> dict[str, Path]:
        """Property name -> discovered example/counterexample path."""
        raise NotImplementedError

    def join(self) -> "Checker":
        raise NotImplementedError

    def is_done(self) -> bool:
        raise NotImplementedError

    # -- shared --------------------------------------------------------------

    def discovery(self, name: str) -> Optional[Path]:
        return self.discoveries().get(name)

    def discovery_classification(self, name: str) -> str:
        """"example" or "counterexample" (reference ``checker.rs:245-252``)."""
        exp = self.model.property_by_name(name).expectation
        return "example" if exp == Expectation.SOMETIMES else "counterexample"

    def report(self, stream=None) -> "Checker":
        """Block until done, printing 1 Hz progress then a final ``sec=`` line
        and discoveries (reference ``checker.rs:217-242``); the ``sec=`` value
        is the benchmark metric."""
        stream = stream or sys.stdout
        start = time.monotonic()
        last = 0.0
        while not self.is_done():
            now = time.monotonic()
            if now - last >= 1.0:
                print(
                    f"Checking. states={self.state_count()}, "
                    f"unique={self.unique_state_count()}",
                    file=stream,
                )
                last = now
            time.sleep(0.05)
        self.join()
        sec = max(time.monotonic() - start, 1e-9)
        print(
            f"Done. states={self.state_count()}, "
            f"unique={self.unique_state_count()}, sec={sec:.6g}",
            file=stream,
        )
        for name, path in sorted(self.discoveries().items()):
            cls = self.discovery_classification(name)
            print(f'Discovered "{name}" {cls} {path!r}', file=stream)
        return self

    # -- assertions (reference ``checker.rs:256-338``) -----------------------

    def assert_properties(self) -> None:
        for prop in self.model.properties():
            if prop.expectation == Expectation.SOMETIMES:
                self.assert_any_discovery(prop.name)
            else:
                self.assert_no_discovery(prop.name)

    def assert_any_discovery(self, name: str) -> Path:
        path = self.discovery(name)
        assert path is not None, f"Missing discovery for {name!r}."
        return path

    def assert_no_discovery(self, name: str) -> None:
        path = self.discovery(name)
        assert path is None, (
            f"Unexpected \"{name}\" {self.discovery_classification(name)} {path!r}"
        )

    def assert_discovery(self, name: str, actions: Sequence) -> None:
        """Assert a discovery exists and that ``actions`` is one valid witness
        trace, by re-executing the model (reference ``checker.rs:293-338``)."""
        self.assert_any_discovery(name)
        prop = self.model.property_by_name(name)
        model = self.model
        last_err = f"no init state admits the action sequence {list(actions)!r}"
        for init in model.init_states():
            path = Path.from_actions(model, init, actions)
            if path is None:
                continue
            final = path.final_state()
            if prop.expectation == Expectation.ALWAYS:
                assert not prop.condition(model, final), (
                    f"path does not violate always property {name!r}"
                )
                return
            if prop.expectation == Expectation.SOMETIMES:
                assert prop.condition(model, final), (
                    f"path does not satisfy sometimes property {name!r}"
                )
                return
            # EVENTUALLY counterexample: no state along the maximal path
            # satisfies the condition, and the path ends in a terminal state.
            assert not any(prop.condition(model, s) for s in path.states()), (
                f"path satisfies eventually property {name!r}"
            )
            assert not model.next_steps(final), (
                f"path for eventually property {name!r} does not end terminal"
            )
            return
        raise AssertionError(last_err)


class ParentPointerTrace:
    """Path reconstruction shared by checkers whose visited map stores
    ``child_fp -> parent_fp`` with root sentinel 0 (thread BFS and mp BFS;
    reference ``bfs.rs:314-342``).  Requires ``self.model``,
    ``self._generated`` (the parent-pointer map) and ``self._discoveries``
    (property name -> discovery fp)."""

    def _trace(self, fp: int) -> list[int]:
        fps = [fp]
        while True:
            parent = self._generated.get(fps[-1], 0)
            if parent == 0:
                break
            fps.append(parent)
        fps.reverse()
        return fps

    def discoveries(self) -> dict[str, Path]:
        return {
            name: Path.from_fingerprints(self.model, self._trace(fp))
            for name, fp in dict(self._discoveries).items()
        }


def evaluate_properties(
    model, props: Sequence[Property], discoveries: dict, state, ebits, token
):
    """Shared per-state property evaluation (reference ``bfs.rs:192-227``):
    record always-counterexamples / sometimes-examples under ``token``
    (first writer wins), clear satisfied eventually-bits.  Returns updated
    ebits."""
    for i, prop in enumerate(props):
        if prop.expectation is Expectation.ALWAYS:
            if prop.name not in discoveries and not prop.condition(model, state):
                discoveries.setdefault(prop.name, token)
        elif prop.expectation is Expectation.SOMETIMES:
            if prop.name not in discoveries and prop.condition(model, state):
                discoveries.setdefault(prop.name, token)
        elif i in ebits and prop.condition(model, state):
            ebits = ebits - {i}
    return ebits


def flush_terminal_ebits(
    props: Sequence[Property], discoveries: dict, ebits, token
) -> None:
    """Liveness bits still set at a terminal state are counterexamples
    (reference ``bfs.rs:265-272``)."""
    for i in ebits:
        discoveries.setdefault(props[i].name, token)


def init_ebits(properties: Sequence[Property]) -> frozenset[int]:
    """Initial liveness bits: one per ``eventually`` property, set at path
    start, cleared when satisfied; bits still set at a terminal state flush as
    counterexamples (reference ``checker.rs:341-348``).  Like the reference,
    bits are *not* part of the state fingerprint, which can miss
    counterexamples on DAG joins and cycles (``bfs.rs:239-257`` FIXMEs) —
    replicated for parity, pinned by tests."""
    return frozenset(
        i for i, p in enumerate(properties) if p.expectation == Expectation.EVENTUALLY
    )
