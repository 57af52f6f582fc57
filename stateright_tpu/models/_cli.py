"""Shared CLI plumbing for example models (reference per-example ``main()``,
e.g. ``examples/paxos.rs:314-395``): subcommands ``check [args]``,
``check-sym``, ``explore [addr]``, ``spawn``, with positional arguments.
Beyond the reference's verbs: ``check-tpu`` / ``check-sym-tpu`` (device
engines; ``--checked`` runs them under checkify instrumentation —
``CheckerBuilder.checked()``, the sanitizer's dynamic guard),
``check-auto`` (measured engine selection, ``CheckerBuilder.spawn_auto``),
``audit`` (the static preflight auditor, ``stateright_tpu/analysis/``),
``sanitize`` (the interval/bounds soundness sanitizer, JX2xx rules —
``docs/analysis.md``), and ``profile`` (a telemetry-instrumented run:
flight-recorder JSONL + optional Chrome trace,
``stateright_tpu/telemetry/``, ``docs/telemetry.md``).

Fleet mode — ``python -m stateright_tpu.models._cli audit|sanitize
[MODULE...]`` — audits/sanitizes every shipped example (each module
exposes ``_audit_models()``), printing one report per configuration and
exiting non-zero on any error-severity finding; CI gates on both.
``python -m stateright_tpu.models._cli profile [MODULE] [--out=F]
[--chrome=F] [ARGS...]`` profiles one example's configurations through
the same ``_audit_models`` hook (CI runs it as a smoke and uploads the
JSONL as a workflow artifact).
"""

from __future__ import annotations

import os
import sys
from typing import Callable, Iterable, Optional


def run_cli(
    usage: str,
    check: Callable[[list], None],
    check_sym: Optional[Callable[[list], None]] = None,
    check_tpu: Optional[Callable[[list], None]] = None,
    check_sym_tpu: Optional[Callable[[list], None]] = None,
    check_auto: Optional[Callable[[list], None]] = None,
    explore: Optional[Callable[[list], None]] = None,
    spawn: Optional[Callable[[list], None]] = None,
    audit: Optional[Callable[[list], None]] = None,
    profile: Optional[Callable[[list], None]] = None,
    sanitize: Optional[Callable[[list], None]] = None,
    report: Optional[Callable[[list], None]] = None,
    independence: Optional[Callable[[list], None]] = None,
    capacity: Optional[Callable[[list], None]] = None,
    costmodel: Optional[Callable[[list], None]] = None,
    compare: Optional[Callable[[list], None]] = None,
    supervise: Optional[Callable[[list], None]] = None,
    sweep: Optional[Callable[[list], None]] = None,
    argv: Optional[list] = None,
) -> None:
    argv = sys.argv[1:] if argv is None else argv
    cmd = argv[0] if argv else None
    rest = argv[1:]
    if cmd == "check":
        check(rest)
    elif cmd == "check-sym" and check_sym is not None:
        check_sym(rest)
    elif cmd == "check-tpu" and check_tpu is not None:
        check_tpu(rest)
    elif cmd == "check-sym-tpu" and check_sym_tpu is not None:
        check_sym_tpu(rest)
    elif cmd == "check-auto" and check_auto is not None:
        check_auto(rest)
    elif cmd == "explore" and explore is not None:
        explore(rest)
    elif cmd == "spawn" and spawn is not None:
        spawn(rest)
    elif cmd == "audit" and audit is not None:
        audit(rest)
    elif cmd == "profile" and profile is not None:
        profile(rest)
    elif cmd == "sanitize" and sanitize is not None:
        sanitize(rest)
    elif cmd == "report" and report is not None:
        report(rest)
    elif cmd == "independence" and independence is not None:
        independence(rest)
    elif cmd == "capacity" and capacity is not None:
        capacity(rest)
    elif cmd == "costmodel" and costmodel is not None:
        costmodel(rest)
    elif cmd == "compare" and compare is not None:
        compare(rest)
    elif cmd == "supervise" and supervise is not None:
        supervise(rest)
    elif cmd == "sweep" and sweep is not None:
        sweep(rest)
    else:
        print("USAGE:")
        print(usage)
        if check_tpu is not None:
            print("  device verbs also take --checked, --prewarm, "
                  "--prededup, --por, --per-channel, --spill, --mxu, "
                  "--mesh, --compile-cache=DIR "
                  "(docs/perf.md, docs/analysis.md, docs/spill.md, "
                  "docs/roofline.md) and "
                  "--watch (live status line, docs/telemetry.md)")
        if audit is not None:
            print("  <example> audit    # static preflight audit "
                  "(docs/analysis.md)")
        if sanitize is not None:
            print("  <example> sanitize # interval/bounds soundness "
                  "sanitizer (docs/analysis.md JX2xx)")
        if independence is not None:
            print("  <example> independence # static independence / "
                  "conflict-matrix analysis (docs/analysis.md JX3xx)")
        if profile is not None:
            print("  <example> profile [--out=F] [--chrome=F] [ARGS]  "
                  "# telemetry run (docs/telemetry.md)")
        if report is not None:
            print("  <example> report [--out=F] [ARGS]  "
                  "# post-run report: JSON + markdown (docs/telemetry.md)")
        if capacity is not None:
            print("  <example> capacity [ARGS]  # HBM capacity plan: "
                  "analytic footprint per growth rung (docs/telemetry.md)")
        if costmodel is not None:
            print("  <example> costmodel [--out=F] [--mxu] [ARGS]  "
                  "# roofline cost ledger: per-stage FLOPs/bytes, XLA "
                  "reconciliation, MXU candidates; --mxu prices the "
                  "recast program (docs/roofline.md)")
        if compare is not None:
            print("  <example> compare A B [--registry=DIR] "
                  "[--expect=VERDICT]  # contract-aware run diff: "
                  "report files or registry run ids "
                  "(docs/telemetry.md \"Comparing runs\")")
        if sweep is not None:
            print("  <example> sweep [N] [--runs=DIR] [--batch=N] "
                  "[--steps=N] [--capacity=N]  # hyper-batched instance "
                  "sweep: one compiled program per shape cohort checks "
                  "the whole family (docs/sweep.md)")
        if supervise is not None:
            print("  <example> supervise [ARGS] --autosave=DIR "
                  "[--every=SECS] [--keep=K] [--max-restarts=N] "
                  "[--runs=DIR] [--batch=N] [--steps=N] "
                  "[--fault-plan=F] [--fault-log=F]  "
                  "# supervised run: periodic atomic checkpoints + "
                  "retry/backoff resume (docs/robustness.md)")


def pop_checked(rest: list) -> tuple:
    """Strip ``--checked`` from a verb's arguments: ``(checked, rest)``.
    The device verbs pass the flag to ``CheckerBuilder.checked()`` — the
    sanitizer's dynamic guard (``docs/analysis.md``)."""
    rest = list(rest)
    checked = "--checked" in rest
    while "--checked" in rest:
        rest.remove("--checked")
    return checked, rest


def pop_perf(rest: list) -> tuple:
    """Strip the wavefront-throughput flags (``docs/perf.md``) from a device
    verb's arguments: ``(cfg, rest)`` where ``cfg`` holds ``prewarm``/
    ``prededup`` (bool) and ``compile_cache`` (dir or None).  Apply with
    :func:`apply_perf`.  Env knobs (``STATERIGHT_TPU_PREWARM`` etc.) still
    work without the flags — these exist so one-off CLI runs can A/B."""
    rest = list(rest)
    cfg = {"prewarm": False, "prededup": False, "compile_cache": None,
           "por": False, "spill": False, "per_channel": False,
           "mxu": False, "mesh": False}
    kept = []
    for a in rest:
        if a == "--prewarm":
            cfg["prewarm"] = True
        elif a == "--prededup":
            cfg["prededup"] = True
        elif a == "--mxu":
            cfg["mxu"] = True
        elif a == "--mesh":
            cfg["mesh"] = True
        elif a == "--por":
            cfg["por"] = True
        elif a == "--spill":
            cfg["spill"] = True
        elif a == "--per-channel":
            cfg["per_channel"] = True
        elif a.startswith("--compile-cache="):
            cfg["compile_cache"] = a[len("--compile-cache="):]
        else:
            kept.append(a)
    return cfg, kept


def apply_perf(builder, cfg: dict):
    """Apply a :func:`pop_perf` config onto a ``CheckerBuilder``.
    ``per_channel`` is NOT applied here — it is a model-level encoding
    choice that must land before the tensor twin resolves; device verbs
    call :func:`apply_encoding` on the model first."""
    if cfg.get("prewarm"):
        builder = builder.prewarm()
    if cfg.get("prededup"):
        builder = builder.prededup()
    if cfg.get("por"):
        builder = builder.por()
    if cfg.get("spill"):
        builder = builder.spill()
    if cfg.get("mxu"):
        builder = builder.mxu()
    if cfg.get("mesh"):
        builder = builder.mesh()
    import jax

    from ..parallel.prewarm import resolve_compile_cache_dir

    # on an accelerator the device verbs are chip entry points: they keep
    # a compile cache by default (JAX_COMPILATION_CACHE_DIR, else the
    # fixed in-checkout directory); CPU runs cache only on request
    cache = resolve_compile_cache_dir(
        cfg.get("compile_cache"),
        entry_point=jax.default_backend() != "cpu",
    )
    if cache:
        builder = builder.compile_cache(cache)
    return builder


def apply_encoding(model, cfg: dict):
    """Apply the :func:`pop_perf` ``--per-channel`` flag onto the MODEL
    (``ActorModel.per_channel_()``): the per-(src,dst)-channel network
    packing for the compiled device twin (docs/analysis.md "Per-channel
    encoding").  Must run before the twin resolves — the encoding is the
    fingerprint scheme.  Models without the builder method (non-actor
    models like 2pc) get a LOUD one-liner instead of a silent no-op —
    an ignored flag must never masquerade as "per-channel buys
    nothing"."""
    if cfg.get("per_channel"):
        if hasattr(model, "per_channel_"):
            model.per_channel_()
        else:
            print(
                f"stateright-tpu: --per-channel ignored: "
                f"{type(model).__name__} is not an actor model (the "
                "encoding applies to compiled actor twins; "
                "docs/analysis.md)",
                file=sys.stderr,
            )
    return model


# -- live watch view (--watch on the device verbs) ---------------------------


def pop_watch(rest: list) -> tuple:
    """Strip ``--watch`` from a verb's arguments: ``(watch, rest)``.
    Apply with :func:`apply_watch` + :func:`watch_checker`."""
    rest = list(rest)
    watch = "--watch" in rest
    while "--watch" in rest:
        rest.remove("--watch")
    return watch, rest


def apply_watch(builder, watch: bool):
    """Arm a builder for the live watch view: the status line reads the
    health model, the cartography block, and the HBM ledger, so
    ``--watch`` implies ``.telemetry(cartography=True, memory=True)``
    (docs/telemetry.md)."""
    if not watch:
        return builder
    return builder.cartography().memory_ledger()


def watch_line(checker) -> str:
    """One live status line: depth, cumulative counters, smoothed
    throughput, table load, HBM footprint (vs the device budget when one
    is known), health phase (+ stall / OOM-risk flags), drain ETA."""
    rec = checker.flight_recorder
    h = rec.health() if rec is not None else {}
    last = (rec.last_step() if rec is not None else None) or {}
    sps = h.get("ewma_states_per_sec")
    load = last.get("load_factor")
    depth = last.get("depth", checker.max_depth())
    parts = [
        f"depth={depth}",
        f"states={checker.state_count()}",
        f"unique={checker.unique_state_count()}",
        f"states/s={sps if sps is not None else '-'}",
        f"load={load if load is not None else '-'}",
        f"hbm={_watch_hbm(rec)}",
        f"phase={h.get('phase', '-')}",
    ]
    sp = _watch_spill(rec)
    if sp:
        parts.append(f"spill={sp}")
    dur_fn = getattr(checker, "durability_status", None)
    dur = dur_fn() if callable(dur_fn) else None
    if dur:
        auto = dur.get("autosave") or {}
        age = auto.get("last_checkpoint_age_secs")
        if auto:
            parts.append(
                "ckpt=" + ("-" if age is None else f"{age:.0f}s")
            )
        if dur.get("restarts"):
            parts.append(f"restarts={dur['restarts']}")
    if h.get("spill_degraded"):
        parts.append("SPILL-DEGRADED(disk tier lost; host RAM only)")
    if h.get("stalled"):
        parts.append(f"STALLED({h.get('stall_reason') or '?'})")
    if h.get("oom_risk"):
        parts.append("OOM-RISK(next growth rung does not fit)")
    if h.get("spill_forecast"):
        parts.append("spill-forecast(next rung evicts to host)")
    if h.get("eta_secs") is not None:
        parts.append(f"eta={h['eta_secs']}s")
    return " ".join(parts)


def _watch_hbm(rec) -> str:
    """The ``hbm=`` column: live device bytes when the backend reports
    them, else the ledger's analytic carry bytes; '/budget (x%)' when a
    budget is known.  '-' when the run has no memory ledger."""
    mem = rec.memory() if rec is not None else None
    if not mem:
        return "-"
    from ..telemetry.memory import fmt_bytes

    live = mem.get("device") or {}
    used = live.get("bytes_in_use", mem.get("total_bytes"))
    budget = mem.get("budget_bytes")
    if budget:
        return (
            f"{fmt_bytes(used)}/{fmt_bytes(budget)}"
            f"({100.0 * used / budget:.1f}%)"
        )
    return fmt_bytes(used)


def _watch_spill(rec) -> str:
    """The ``spill=`` column: spilled-state count + per-tier bytes once
    the tier has evicted anything; '' when the tier is off or idle."""
    sp = rec.spill() if rec is not None else None
    if not sp or not sp.get("spilled_fps"):
        return ""
    from ..telemetry.memory import fmt_bytes

    out = (
        f"{sp['spilled_fps']}fp/host:{fmt_bytes(sp.get('host_bytes'))}"
    )
    if sp.get("disk_bytes"):
        out += f"/disk:{fmt_bytes(sp['disk_bytes'])}"
    return out


def watch_checker(
    checker, stream=None, interval: float = 0.25, plain_every: float = 2.0
):
    """Render the live status until the run completes, then one final
    line.  On a TTY the line rewrites in place (plain ``\\r`` + padding —
    no ANSI sequences, no dependencies); on a non-TTY stream it degrades
    to one plain line every ``plain_every`` seconds, so piped/CI output
    stays readable instead of turning into control-character soup."""
    import time

    stream = stream or sys.stderr
    tty = bool(getattr(stream, "isatty", lambda: False)())
    last_plain = -plain_every  # always emit the first line promptly
    width = 0

    def put(txt: str, end: str = "") -> None:
        nonlocal width
        if tty:
            pad = " " * max(width - len(txt), 0)
            stream.write("\r" + txt + pad + end)
            width = len(txt)
        else:
            stream.write(txt + "\n")
        stream.flush()

    t0 = time.monotonic()
    while not checker.is_done():
        now = time.monotonic() - t0
        if tty:
            put(watch_line(checker))
        elif now - last_plain >= plain_every:
            put(watch_line(checker))
            last_plain = now
        time.sleep(interval)
    put(watch_line(checker), end="\n")
    return checker


def spawn_watched(builder, watch: bool, spawn):
    """Device-verb helper: ``spawn`` is ``builder -> checker`` (async).
    With ``watch`` the live view renders until done; either way the
    joined checker is returned (callers chain ``.report()``)."""
    builder = apply_watch(builder, watch)
    checker = spawn(builder)
    if watch:
        watch_checker(checker)
    return checker


def default_threads() -> int:
    return os.cpu_count() or 1


# -- audit verb --------------------------------------------------------------


def audit_and_report(
    models: Iterable[tuple], stream=None, deep: bool = True
) -> bool:
    """Audit ``(label, model)`` pairs, print one report each; True iff no
    error-severity findings anywhere."""
    from ..analysis import audit_model

    stream = stream or sys.stdout
    ok = True
    for label, model in models:
        report = audit_model(model, deep=deep)
        print(f"--- {label}", file=stream)
        print(report.format(), file=stream)
        ok = ok and report.ok
    return ok


def make_audit_cmd(factory: Callable[[list], Iterable[tuple]]) -> Callable:
    """Wrap a ``rest -> [(label, model), ...]`` factory as an ``audit``
    CLI verb that exits 1 on error findings."""

    def _audit(rest: list) -> None:
        if not audit_and_report(factory(rest)):
            raise SystemExit(1)

    return _audit


# -- sanitize verb -----------------------------------------------------------


def sanitize_and_report(
    models: Iterable[tuple], stream=None, deep: bool = False
) -> tuple:
    """Run the soundness sanitizer view over ``(label, model)`` pairs: one
    summary line + the JX2xx findings each.  Returns ``(ok, rule_ids)``:
    ``ok`` iff no error-severity JX2xx finding anywhere, ``rule_ids`` the
    machine-readable offending rules (the CLI exit path prints them, same
    contract as ``AuditError.rule_ids``).  The LIGHT audit tier suffices:
    the sanitizer runs in it, and the deep extras (closure probe, drift
    re-resolve) contribute no JX2xx findings — the fleet gate should not
    pay for them twice when CI also runs the audit gate."""
    from ..analysis import Severity, audit_model

    stream = stream or sys.stdout
    ok, bad_rules = True, set()
    for label, model in models:
        report = audit_model(model, deep=deep)
        summary = (report.metrics or {}).get("sanitizer")
        findings = [
            f for f in report.findings if f.rule_id.startswith("JX2")
        ]
        errors = [f for f in findings if f.severity == Severity.ERROR]
        print(f"--- {label}", file=stream)
        if summary is None:
            print(
                "sanitize: no device twin for this configuration "
                "(host checkers unaffected)",
                file=stream,
            )
        else:
            rules = ", ".join(summary.get("rules") or []) or "none"
            print(
                f"sanitize: {summary['sites']} indexed site(s) — "
                f"{summary['proved']} proved in range, "
                f"{summary['undecided']} undecided (checked-mode "
                f"candidates); rules fired: {rules}",
                file=stream,
            )
        for f in findings:
            print("  " + f.format(), file=stream)
        if errors:
            ok = False
            bad_rules.update(f.rule_id for f in errors)
    return ok, tuple(sorted(bad_rules))


def make_sanitize_cmd(factory: Callable[[list], Iterable[tuple]]) -> Callable:
    """Wrap a ``rest -> [(label, model), ...]`` factory as a ``sanitize``
    CLI verb that exits 1 (naming the rule ids) on error findings."""

    def _sanitize(rest: list) -> None:
        ok, rules = sanitize_and_report(factory(rest))
        if not ok:
            print(f"sanitize: FAILED ({', '.join(rules)})")
            raise SystemExit(1)

    return _sanitize


def fleet_sanitize(names: Optional[list] = None, stream=None) -> int:
    """Sanitize the whole example fleet (or just ``names``); 0 iff no
    JX2xx error anywhere.  Same coverage contract as ``fleet_audit``: a
    module without ``_audit_models`` fails the gate rather than silently
    shrinking it."""
    import importlib

    from . import __all__ as all_names

    stream = stream or sys.stdout
    ok, bad = True, set()
    for name in names or list(all_names):
        mod = importlib.import_module(f"stateright_tpu.models.{name}")
        factory = getattr(mod, "_audit_models", None)
        if factory is None:
            print(
                f"--- {name}: FAILED — no _audit_models hook (add one so "
                "the fleet gate covers this example)",
                file=stream,
            )
            ok = False
            continue
        mok, rules = sanitize_and_report(factory([]), stream=stream)
        ok = ok and mok
        bad.update(rules)
    verdict = "CLEAN" if ok else f"FAILED ({', '.join(sorted(bad))})"
    print(f"sanitize fleet: {verdict}", file=stream)
    return 0 if ok else 1


# -- independence verb -------------------------------------------------------


def independence_and_report(
    models: Iterable[tuple], stream=None
) -> tuple:
    """Static independence / conflict-matrix view over ``(label, model)``
    pairs (``analysis/independence.py``; docs/analysis.md JX3xx): one
    summary line + the JX3xx findings each.  Returns ``(ok, rule_ids)``:
    ``ok`` iff every twin-bearing model yields a WELL-FORMED conflict
    matrix (square, symmetric, dependent diagonal) and no error-severity
    JX3xx finding fires anywhere — the CI fleet gate's contract."""
    import numpy as _np

    from ..analysis import Severity, run_independence
    from ..parallel.tensor_model import twin_or_none

    stream = stream or sys.stdout
    ok, bad_rules = True, set()
    for label, model in models:
        twin = twin_or_none(model)
        print(f"--- {label}", file=stream)
        if twin is None:
            print(
                "independence: no device twin for this configuration "
                "(host checkers unaffected)",
                file=stream,
            )
            continue
        rep = run_independence(twin, list(model.properties()))
        s = rep.summary()
        c = _np.asarray(rep.conflict)
        well_formed = (
            c.ndim == 2
            and c.shape == (rep.n_actions, rep.n_actions)
            and bool(_np.array_equal(c, c.T))
            and bool(c.diagonal().all())
        )
        print(
            f"independence: {s['actions']} action(s), "
            f"{s['independent_pairs']} independent pair(s), "
            f"{s['visible_actions']} visible, "
            f"{s['undecided_actions']} undecided; "
            f"decomposed={s['decomposed']}"
            + (
                f"; encoding={s['encoding']}"
                if s.get("encoding") else ""
            )
            + f"; rules fired: {', '.join(s['rules']) or 'none'}",
            file=stream,
        )
        if not well_formed:
            ok = False
            print("  MALFORMED conflict matrix", file=stream)
        for f in rep.findings:
            print("  " + f.format(), file=stream)
            if f.severity == Severity.ERROR:
                ok = False
                bad_rules.add(f.rule_id)
    return ok, tuple(sorted(bad_rules))


def make_independence_cmd(
    factory: Callable[[list], Iterable[tuple]]
) -> Callable:
    """Wrap a ``rest -> [(label, model), ...]`` factory as an
    ``independence`` CLI verb that exits 1 on error findings or a
    malformed matrix."""

    def _independence(rest: list) -> None:
        ok, rules = independence_and_report(factory(rest))
        if not ok:
            print(f"independence: FAILED ({', '.join(rules) or 'matrix'})")
            raise SystemExit(1)

    return _independence


def fleet_independence(names: Optional[list] = None, stream=None) -> int:
    """Run the independence analysis over the whole example fleet (or
    just ``names``); 0 iff every bundled example produces a well-formed
    conflict matrix and no ERROR-level JX3xx finding.  Same coverage
    contract as ``fleet_audit``/``fleet_sanitize``: a module without
    ``_audit_models`` fails the gate."""
    import importlib

    from . import __all__ as all_names

    stream = stream or sys.stdout
    ok, bad = True, set()
    for name in names or list(all_names):
        mod = importlib.import_module(f"stateright_tpu.models.{name}")
        factory = getattr(mod, "_audit_models", None)
        if factory is None:
            print(
                f"--- {name}: FAILED — no _audit_models hook (add one so "
                "the fleet gate covers this example)",
                file=stream,
            )
            ok = False
            continue
        mok, rules = independence_and_report(factory([]), stream=stream)
        ok = ok and mok
        bad.update(rules)
    verdict = "CLEAN" if ok else f"FAILED ({', '.join(sorted(bad)) or 'matrix'})"
    print(f"independence fleet: {verdict}", file=stream)
    return 0 if ok else 1


# -- capacity verb -----------------------------------------------------------


def capacity_and_report(
    models: Iterable[tuple], stream=None, spill: bool = False
) -> bool:
    """HBM capacity plan over ``(label, model)`` pairs
    (``telemetry/memory.py``; docs/telemetry.md "Memory ledger"): the
    analytic per-rung footprint ladder of the wavefront engine at its
    default spawn capacities, the growth-migration transient per rung,
    and — when a device budget is known (live ``memory_stats`` or the
    ``STATERIGHT_TPU_DEVICE_BYTES`` override) — the max reachable unique
    count before the run would spill.  ``spill=True`` (the ``--spill``
    flag) plans WITH the spill tier armed: ``max_unique`` extends past
    the largest-fitting rung by the host tier's reach (docs/spill.md)
    instead of capping at HBM/4.  Pure host arithmetic: no device run,
    no compile; on CPU (no budget) it degrades to the analytic table
    alone, never crashes.  Returns True iff every configuration produced
    a plan (twin-less models are reported and skipped)."""
    from ..parallel.tensor_model import twin_or_none
    from ..telemetry.memory import (
        capacity_plan,
        device_budget,
        fmt_bytes,
        wavefront_specs,
    )

    stream = stream or sys.stdout
    budget, src = device_budget()
    ok = True
    for label, model in models:
        print(f"--- {label}", file=stream)
        twin = twin_or_none(model)
        if twin is None:
            print(
                "capacity: no device twin for this configuration "
                "(host checkers hold states in host RAM)",
                file=stream,
            )
            continue
        n_props = len(list(model.properties()))
        # the wavefront engine's default spawn capacities
        # (parallel/wavefront.TpuChecker): the ladder starts where an
        # un-tuned spawn_tpu() starts
        cap, batch = 1 << 17, 1 << 11
        caps = {"cap": cap, "qcap": max(cap // 2, 4 * batch),
                "batch": batch}

        def spec_fn(c, twin=twin, n_props=n_props):
            return wavefront_specs(
                twin, n_props, int(c["cap"]), int(c["qcap"]),
                int(c["batch"]),
            )

        try:
            plan = capacity_plan(
                spec_fn, caps, budget=budget,
                rungs=24 if budget is not None else 10,
                spill=spill,
            )
        except Exception as e:  # noqa: BLE001 - a plan failure is a
            # verdict, not a crash (the CI smoke's contract)
            ok = False
            print(f"capacity: plan failed: {type(e).__name__}: {e}",
                  file=stream)
            continue
        if budget is not None:
            print(
                f"capacity plan (wavefront engine; device budget "
                f"{fmt_bytes(budget)}, {src}):",
                file=stream,
            )
        else:
            print(
                "capacity plan (wavefront engine; no device memory "
                "limit known — analytic footprint only; set "
                "STATERIGHT_TPU_DEVICE_BYTES to plan against a budget):",
                file=stream,
            )
        print(f"  {'capacity':>12}  {'carry':>9}  {'transient':>9}  fits",
              file=stream)
        for r in plan["rungs"]:
            fits = r.get("fits")
            print(
                f"  {r['capacity']:>12}  {fmt_bytes(r['total_bytes']):>9}"
                f"  {fmt_bytes(r['transient_bytes']):>9}  "
                f"{'-' if fits is None else ('yes' if fits else 'NO')}",
                file=stream,
            )
        sp = plan.get("spill")
        if sp is not None:
            print(
                f"with --spill, {label} reaches "
                f"~{sp['hot_max_unique']:,} unique states on-device, then "
                f"~{sp.get('host_max_unique', 0):,} more in the host tier "
                f"({fmt_bytes(sp.get('host_budget_bytes'))} at "
                f"{sp['bytes_per_spilled']}B/state), disk tier unbounded "
                f"behind it — max_unique ~{plan['max_unique']:,} "
                "(docs/spill.md)",
                file=stream,
            )
        elif plan.get("max_unique") is not None:
            print(
                f"on this device, {label} reaches ~{plan['max_unique']:,} "
                "unique states before spilling (largest rung whose "
                "growth transient fits; extend past it with --spill / "
                "CheckerBuilder.spill(), docs/spill.md)",
                file=stream,
            )
        elif budget is not None:
            print(
                f"on this device, {label} cannot hold even the first "
                "rung — shrink capacity= or raise the budget",
                file=stream,
            )
    return ok


def pop_spill(rest: list) -> tuple:
    """Strip ``--spill`` from a verb's arguments: ``(spill, rest)``."""
    rest = list(rest)
    spill = "--spill" in rest
    while "--spill" in rest:
        rest.remove("--spill")
    return spill, rest


def make_capacity_cmd(factory: Callable[[list], Iterable[tuple]]) -> Callable:
    """Wrap a ``rest -> [(label, model), ...]`` factory as a ``capacity``
    CLI verb (exit 1 only when the plan itself crashes).  ``--spill``
    plans with the spill tier armed (docs/spill.md)."""

    def _capacity(rest: list) -> None:
        spill, rest = pop_spill(rest)
        if not capacity_and_report(factory(rest), spill=spill):
            raise SystemExit(1)

    return _capacity


def fleet_capacity(names: Optional[list] = None, stream=None) -> int:
    """Capacity-plan the whole example fleet (or just ``names``); 0 iff
    every module's configurations produced a plan (twin-less models are
    disclosed, not failures — host checkers have no device footprint)."""
    import importlib

    from . import __all__ as all_names

    stream = stream or sys.stdout
    spill, names = pop_spill(list(names or []))
    ok = True
    for name in names or list(all_names):
        mod = importlib.import_module(f"stateright_tpu.models.{name}")
        factory = getattr(mod, "_audit_models", None)
        if factory is None:
            print(
                f"--- {name}: FAILED — no _audit_models hook (add one so "
                "the fleet gate covers this example)",
                file=stream,
            )
            ok = False
            continue
        ok = capacity_and_report(factory([]), stream=stream, spill=spill) and ok
    print("capacity fleet: " + ("OK" if ok else "FAILED"), file=stream)
    return 0 if ok else 1


# -- costmodel verb ----------------------------------------------------------

# the verb's trace/compile shapes: smaller than a default spawn so the
# fleet gate stays seconds-per-model (the static ledger scales linearly
# in batch — the RANKING and the reconciliation verdict are what the
# gate checks, and both are batch-stable)
_COSTMODEL_BATCH = 256
_COSTMODEL_CAP = 1 << 14


def costmodel_and_report(
    models: Iterable[tuple], stream=None, out=None, mxu: bool = False,
) -> bool:
    """Roofline cost ledger over ``(label, model)`` pairs
    (``analysis/costmodel.py`` + ``telemetry/roofline.py``;
    docs/roofline.md): per-stage FLOPs/bytes table with op classes and
    arithmetic intensity, memory-vs-compute-bound verdicts where a
    device spec is known (``STATERIGHT_TPU_DEVICE_SPEC``), the
    XLA-reconciliation verdict, and the JX4xx MXU-candidate findings.
    ``out`` collects the per-config live blocks into a JSON file (the
    schema round-trip fixture / CI artifact).  ``mxu`` prices the
    ``--mxu``-flagged engine program instead (docs/roofline.md
    "Executing the hot-spot list"): the coalesced expand kernel and
    the BLEST probe — landed-recast findings go
    silent (the JX305 pattern).  Returns True iff every
    twin-bearing configuration produced a well-formed, XLA-reconciling
    ledger (twin-less models are disclosed and skipped — host checkers
    have no device pipeline to price)."""
    import json

    from ..analysis.costmodel import wavefront_costs
    from ..ops.mxu import MxuConfig
    from ..parallel.tensor_model import twin_or_none
    from ..telemetry.memory import fmt_bytes
    from ..telemetry.roofline import classify_stages, device_spec

    stream = stream or sys.stdout
    spec = device_spec()
    ok = True
    blocks = []
    for label, model in models:
        print(f"--- {label}", file=stream)
        twin = twin_or_none(model)
        if twin is None:
            print(
                "costmodel: no device twin for this configuration "
                "(host checkers have no device pipeline)",
                file=stream,
            )
            continue
        try:
            rep = wavefront_costs(
                twin, _COSTMODEL_CAP, _COSTMODEL_CAP // 2,
                _COSTMODEL_BATCH,
                mxu=MxuConfig() if mxu else None,
            )
        except Exception as e:  # noqa: BLE001 - a ledger crash is a
            # verdict, not a crash (the capacity-verb contract)
            ok = False
            print(f"costmodel: ledger failed: {type(e).__name__}: {e}",
                  file=stream)
            continue
        if rep is None:
            ok = False
            print("costmodel: twin kernels did not trace (see the "
                  "structural audit)", file=stream)
            continue
        static = rep.static_block()
        recon = rep.recon_block()
        verdicts = classify_stages(static, spec)
        print(
            f"costmodel: {len(static['stages'])} stage(s), "
            f"{static['totals']['flops']:,} FLOPs / "
            f"{fmt_bytes(static['totals']['bytes'])} per step "
            f"(batch {static['batch']}); XLA reconciliation: "
            + ("ok" if recon["ok"] else "FAILED"),
            file=stream,
        )
        for name, s in static["stages"].items():
            v = verdicts.get(name, {})
            extra = (
                f" — {v['verdict']}"
                if v.get("verdict") not in (None, "unknown") else ""
            )
            print(
                f"  {name:>13}: {s['flops']:>12,} FLOPs  "
                f"{fmt_bytes(s['bytes_read'] + s['bytes_written']):>9}  "
                f"AI={s.get('intensity', '-')}" + extra,
                file=stream,
            )
        for f in rep.findings:
            print("  " + f.format(), file=stream)
        if not recon["ok"]:
            ok = False
            for name, v in recon["stages"].items():
                for p in v.get("problems", []):
                    print(f"  RECONCILE {name}: {p}", file=stream)
        blocks.append({
            "label": label, **static, "reconciliation": recon,
            **({"device_spec": spec} if spec else {}),
            "verdicts": verdicts,
        })
    if out:
        with open(out, "w") as f:
            json.dump({"v": blocks[0]["v"] if blocks else 1,
                       "configs": blocks}, f, indent=1)
            f.write("\n")
    return ok


def make_costmodel_cmd(factory: Callable[[list], Iterable[tuple]]) -> Callable:
    """Wrap a ``rest -> [(label, model), ...]`` factory as a
    ``costmodel`` CLI verb (``--out=F`` collects the JSON blocks; exit 1
    on a malformed or non-reconciling ledger)."""

    def _costmodel(rest: list) -> None:
        out, _chrome, rest = _split_profile_args(rest, default_out="")
        mxu = "--mxu" in rest
        rest = [a for a in rest if a != "--mxu"]
        if not costmodel_and_report(
            factory(rest), out=out or None, mxu=mxu
        ):
            print("costmodel: FAILED")
            raise SystemExit(1)

    return _costmodel


def fleet_costmodel(args: Optional[list] = None, stream=None) -> int:
    """Roofline-cost-ledger the whole example fleet (or just the named
    modules); 0 iff every twin-bearing configuration produced a
    well-formed, XLA-reconciling ledger.  Same coverage contract as the
    other fleet gates: a module without ``_audit_models`` fails."""
    import importlib

    from . import __all__ as all_names

    stream = stream or sys.stdout
    out, _chrome, names = _split_profile_args(list(args or []),
                                              default_out="")
    ok = True
    blocks_out = out or None
    for name in names or list(all_names):
        mod = importlib.import_module(f"stateright_tpu.models.{name}")
        factory = getattr(mod, "_audit_models", None)
        if factory is None:
            print(
                f"--- {name}: FAILED — no _audit_models hook (add one so "
                "the fleet gate covers this example)",
                file=stream,
            )
            ok = False
            continue
        # one --out file per module would clobber; the fleet gate
        # appends the module name when an out path is given
        mod_out = None
        if blocks_out:
            stem, ext = os.path.splitext(blocks_out)
            mod_out = f"{stem}-{name}{ext or '.json'}"
        ok = costmodel_and_report(
            factory([]), stream=stream, out=mod_out
        ) and ok
    print("costmodel fleet: " + ("OK" if ok else "FAILED"), file=stream)
    return 0 if ok else 1


# -- compare / runs verbs (telemetry/registry.py + telemetry/diff.py) --------


def _load_report_arg(arg: str, registry_dir: Optional[str]) -> tuple:
    """``(doc, headline)`` for one compare argument: a report JSON file
    path, or a run id resolved against the registry (``--registry=DIR``
    or ``STATERIGHT_TPU_RUN_DIR``).  Headline (wall-clock metrics) only
    exists for registry-resolved runs."""
    import json

    from ..telemetry.registry import RunRegistry, resolve_run_dir

    if os.path.isfile(arg):
        with open(arg) as f:
            return json.load(f), None
    root = resolve_run_dir(registry_dir)
    if root is None:
        raise SystemExit(
            f"compare: {arg!r} is neither a report file nor a resolvable "
            "run id (pass --registry=DIR or set STATERIGHT_TPU_RUN_DIR)"
        )
    reg = RunRegistry(root)
    doc = reg.find(arg)
    if doc is None:
        raise SystemExit(f"compare: run {arg!r} not found in {root}")
    return doc, reg.headline(arg)


def compare_reports_cmd(rest: list, stream=None) -> int:
    """The ``compare`` verb: contract-aware diff of two run reports
    (``telemetry/diff.py``; docs/telemetry.md "Comparing runs").

    Arguments are report JSON files or registry run ids.  Prints the
    human rendering plus one machine-readable JSON line (the diff
    document).  Exit 0 unless the pair classifies DIVERGENT (a promised
    contract is broken) or ``--expect=VERDICT`` names a different
    class."""
    import json

    from ..telemetry.diff import DIVERGENT, diff_reports, render_diff

    stream = stream or sys.stdout
    registry, expect, args = None, None, []
    for a in rest:
        if a.startswith("--registry="):
            registry = a[len("--registry="):]
        elif a.startswith("--expect="):
            expect = a[len("--expect="):].upper().replace("_", "-")
        else:
            args.append(a)
    if len(args) != 2:
        print(
            "usage: compare A B [--registry=DIR] [--expect=IDENTICAL|"
            "ISOMORPHIC|PERF-ONLY|DIVERGENT]  (A/B: report JSON files "
            "or registry run ids)",
            file=stream,
        )
        return 2
    a_doc, a_head = _load_report_arg(args[0], registry)
    b_doc, b_head = _load_report_arg(args[1], registry)
    d = diff_reports(a_doc, b_doc, a_headline=a_head, b_headline=b_head)
    print(render_diff(d, label_a=args[0], label_b=args[1]), file=stream)
    print(json.dumps(d), file=stream)
    if expect:
        # an explicit expectation is the whole judgement — including
        # --expect=DIVERGENT asserting a known-bad pair stays caught
        if d["verdict"] != expect:
            print(
                f"compare: verdict {d['verdict']} != expected {expect}",
                file=stream,
            )
            return 1
        return 0
    return 1 if d["verdict"] == DIVERGENT else 0


def make_compare_cmd() -> Callable:
    """The ``compare`` CLI verb (model-independent: it reads report
    artifacts, not models — every verb-bearing example mounts the same
    one so the A/B workflow stays next to the verbs that produce the
    reports)."""

    def _compare(rest: list) -> None:
        rc = compare_reports_cmd(rest)
        if rc:
            raise SystemExit(rc)

    return _compare


def pop_sweep_opts(rest: list) -> tuple:
    """Strip the sweep verb's flags: ``(opts, rest)`` — ``runs``
    (registry dir), ``batch``/``steps``/``capacity`` (engine knobs)."""
    opts = {"runs": None, "batch": None, "steps": None, "capacity": None}
    kept = []
    for a in rest:
        if a.startswith("--runs="):
            opts["runs"] = a[len("--runs="):]
        elif a.startswith("--batch="):
            opts["batch"] = int(a[len("--batch="):])
        elif a.startswith("--steps="):
            opts["steps"] = int(a[len("--steps="):])
        elif a.startswith("--capacity="):
            opts["capacity"] = int(a[len("--capacity="):])
        else:
            kept.append(a)
    return opts, kept


def make_sweep_cmd(
    family: Callable[[int], "object"], default_n: int = 8
) -> Callable:
    """The per-example ``sweep`` verb (docs/sweep.md): build the
    example's default family (``family(N)`` -> SweepSpec), run it as ONE
    device sweep, and print one line per instance plus the cohort/compile
    summary the CI smoke greps."""

    def cmd(rest):
        opts, rest = pop_sweep_opts(rest)
        n = int(rest[0]) if rest else default_n
        spec = family(n)
        print(
            f"Sweeping {len(spec.instances)} instances in one device run "
            "(docs/sweep.md)."
        )
        b = (
            spec.instances[0].model.checker()
            .telemetry(cartography=True)
            .sweep(spec)
        )
        if opts["runs"]:
            b = b.runs(opts["runs"])
        kw = {}
        if opts["batch"]:
            kw["batch"] = opts["batch"]
        if opts["steps"]:
            kw["steps_per_call"] = opts["steps"]
        if opts["capacity"]:
            kw["capacity"] = opts["capacity"]
        c = b.spawn_tpu(sync=True, **kw)
        c.join()
        for inst in spec.instances:
            r = c.results[inst.key]
            disc = ",".join(sorted(r.chains)) or "-"
            print(
                f"  {inst.key}: unique={r.unique} states={r.states} "
                f"depth={r.max_depth} discoveries=[{disc}]"
            )
        print(
            f"sweep: {len(spec.instances)} instances over "
            f"{len(c.cohorts)} cohort(s), "
            f"{c.engine_compiles} engine compile(s), total "
            f"unique={c.unique_state_count()} "
            f"states={c.state_count()}"
        )
        if opts["runs"]:
            print(
                f"sweep: registered {len(spec.instances)} instance "
                f"record(s) under sweep_id={c.run_id} in {opts['runs']}"
            )

    return cmd


def fleet_runs(args: Optional[list] = None, stream=None) -> int:
    """``runs [DIR]``: list the persistent run registry — one line per
    archived run (id, config_key, model/engine, headline) plus the
    per-config trend summary the Explorer's dashboard draws."""
    from ..telemetry.registry import RunRegistry, resolve_run_dir

    stream = stream or sys.stdout
    args = list(args or [])
    root = resolve_run_dir(args[0] if args else None)
    if root is None:
        print(
            "runs: no registry configured (pass DIR or set "
            "STATERIGHT_TPU_RUN_DIR)",
            file=stream,
        )
        return 2
    reg = RunRegistry(root)
    recs = reg.index()
    if not recs:
        print(f"runs: registry at {root} is empty", file=stream)
        return 0
    def line(r, indent: str = "") -> None:
        h = r.get("headline") or {}
        bits = [
            indent + str(r.get("run_id")),
            str(r.get("config_key") or "-"),
            f"{r.get('model')}/{r.get('engine')}",
            f"unique={h.get('unique')}",
            f"done={h.get('done')}",
        ]
        if r.get("instance_key"):
            bits.insert(1, f"[{r['instance_key']}]")
        elif r.get("job_key"):
            bits.insert(1, f"[{r['job_key']}]")
        if h.get("states_per_sec") is not None:
            bits.append(f"{h['states_per_sec']}/s")
        if r.get("leg"):
            bits.append(f"leg={r['leg']}")
        if r.get("parent_run_id"):
            bits.append(f"parent={r['parent_run_id']}")
        bits.append(str(r.get("generated_at") or ""))
        print("  ".join(bits), file=stream)

    # sweep members group under one header row with a per-instance
    # verdict strip ('*' = at least one discovery, '.' = none), in the
    # ledger's append order (docs/sweep.md); campaign jobs group the
    # same way (docs/fleet.md) and win when a record carries both tags
    # (a packed cohort member is a sweep instance owned by a campaign)
    groups: list = []
    by_group: dict = {}
    for r in recs:
        if r.get("campaign_id"):
            gid = ("campaign", r["campaign_id"], "job")
        elif r.get("sweep_id"):
            gid = ("sweep", r["sweep_id"], "instance")
        else:
            groups.append(r)
            continue
        g = by_group.get(gid)
        if g is None:
            g = by_group[gid] = {
                "kind": gid[0], "id": gid[1], "noun": gid[2],
                "members": [],
            }
            groups.append(g)
        g["members"].append(r)
    for g in groups:
        if "members" not in g:
            line(g)
            continue
        strip = "".join(
            "*" if (m.get("headline") or {}).get("discoveries") else "."
            for m in g["members"]
        )
        print(
            f"{g['kind']} {g['id']}  {len(g['members'])} {g['noun']}(s)"
            f"  verdicts [{strip}]",
            file=stream,
        )
        for m in g["members"]:
            line(m, indent="  ")
    trends = reg.trends(recs)
    print(
        f"runs: {len(recs)} archived over {len(trends)} config(s) at "
        f"{root}",
        file=stream,
    )
    for key, series in sorted(trends.items()):
        if len(series) > 1:
            u = [s.get("unique") for s in series]
            print(
                f"  trend {key}: {len(series)} runs, unique "
                f"{u[0]} -> {u[-1]}",
                file=stream,
            )
    return 0


# -- fleet / campaign verbs (fleet/; docs/fleet.md) --------------------------


def _pop_fleet_opts(rest: list, defaults: dict) -> tuple:
    """Strip the fleet/campaign verbs' shared flags: ``(opts, rest)``.
    ``--slots``/``--budget``/``--spill``/``--no-pack`` shape the pool,
    ``--root`` hosts autosaves + artifacts, ``--runs`` the registry,
    ``--every`` the autosave cadence, ``--stall=KEY@STEP`` the
    deterministic preemption injection (``--stall=none`` disables)."""
    opts = dict(defaults)
    kept = []
    for a in rest:
        if a.startswith("--slots="):
            opts["slots"] = int(a[len("--slots="):])
        elif a.startswith("--budget="):
            opts["budget"] = int(a[len("--budget="):])
        elif a == "--spill":
            opts["spill"] = True
        elif a == "--no-pack":
            opts["pack"] = False
        elif a.startswith("--root="):
            opts["root"] = a[len("--root="):]
        elif a.startswith("--runs="):
            opts["runs"] = a[len("--runs="):]
        elif a.startswith("--every="):
            opts["every"] = float(a[len("--every="):])
        elif a.startswith("--stall="):
            opts["stall"] = a[len("--stall="):]
        elif a.startswith("--max-restarts="):
            opts["max_restarts"] = int(a[len("--max-restarts="):])
        elif a.startswith("--id="):
            opts["id"] = a[len("--id="):]
        elif a.startswith("--grid="):
            opts["grid"] = a[len("--grid="):]
        else:
            kept.append(a)
    return opts, kept


def _canned_fleet_jobs(runs_dir: Optional[str]) -> list:
    """The ``fleet`` verb's six-tenant workload: three packable
    TwoPhaseSys(3) jobs (one cohort, one compile), a TwoPhaseSys(4)
    and a TwoPhaseSys(5) singleton, and a paxos single-client job —
    mixed shapes over one pool, per docs/fleet.md "The chaos smoke"."""
    from ..checker.base import CheckerBuilder
    from ..fleet import Job
    from .paxos import paxos_model
    from .two_phase_commit import TwoPhaseSys

    def twopc(n):
        def build():
            b = CheckerBuilder(TwoPhaseSys(n))
            return b.runs(runs_dir) if runs_dir else b
        return build

    def paxos():
        def build():
            b = CheckerBuilder(paxos_model(1))
            return b.runs(runs_dir) if runs_dir else b
        return build

    return [
        Job(key="2pc-a", build=twopc(3), packable=True,
            capacity=1 << 12, batch=256, params={"rm": 3}),
        Job(key="2pc-b", build=twopc(3), packable=True,
            capacity=1 << 12, batch=256, params={"rm": 3}),
        Job(key="2pc-c", build=twopc(3), packable=True,
            capacity=1 << 12, batch=256, params={"rm": 3}),
        Job(key="2pc-4", build=twopc(4),
            capacity=1 << 13, batch=256, params={"rm": 4}),
        Job(key="2pc-5", build=twopc(5), priority=1,
            capacity=1 << 14, batch=512, params={"rm": 5}),
        Job(key="paxos-1", build=paxos(),
            capacity=1 << 12, batch=256, params={"clients": 1}),
    ]


def _print_job_results(res, stream) -> None:
    """One grep-able line per job result (the CI smoke's contract)."""
    from ..fleet import COMPLETED

    for r in res.results.values():
        bits = [f"fleet job {r.key}: status={r.status}",
                f"decision={r.decision}"]
        if r.status == COMPLETED:
            bits += [f"unique={r.unique}", f"states={r.states}",
                     f"depth={r.max_depth}"]
        if r.cohort:
            bits.append(f"cohort={r.cohort}")
        if r.preemptions:
            bits.append(f"preemptions={r.preemptions}")
        if r.run_id:
            bits.append(f"run_id={r.run_id}")
        if r.parent_run_id:
            bits.append(f"parent_run_id={r.parent_run_id}")
        if r.reason:
            bits.append(f"reason={r.reason}")
        print("  ".join(bits), file=stream)


def _audit_lineage(res, runs_dir: Optional[str], stream) -> int:
    """Exactly-once audit: every preempted-then-completed job must
    compare IDENTICAL against its yielded parent (``contract:
    lineage``); returns the worst compare exit code."""
    from ..fleet import COMPLETED

    rc = 0
    for r in res.results.values():
        if not (r.preemptions and r.status == COMPLETED):
            continue
        if not (runs_dir and r.run_id and r.parent_run_id):
            print(
                f"fleet lineage {r.key}: UNVERIFIABLE (no registry or "
                "run ids; pass --runs=DIR)",
                file=stream,
            )
            rc = rc or 1
            continue
        print(
            f"fleet lineage {r.key}: parent={r.parent_run_id} "
            f"child={r.run_id}",
            file=stream,
        )
        code = compare_reports_cmd(
            [r.parent_run_id, r.run_id, f"--registry={runs_dir}",
             "--expect=IDENTICAL"],
            stream=stream,
        )
        rc = rc or code
    return rc


def fleet_schedule(args: Optional[list] = None, stream=None) -> int:
    """The ``fleet`` verb: canned multi-tenant chaos smoke — six mixed
    2pc/paxos jobs over a simulated N-slot pool with one injected
    stall-preemption (docs/fleet.md).  Every job must complete with its
    pinned counts and the preempted job's resume must compare IDENTICAL
    against its yielded parent (the line CI greps for ``contract:
    lineage``).  Exit 0 iff all jobs completed and lineage verified."""
    import tempfile

    from ..fleet import FleetSpec, PreemptionPlan, run_fleet

    stream = stream or sys.stdout
    opts, rest = _pop_fleet_opts(list(args or []), {
        "slots": 2, "budget": None, "spill": False, "pack": True,
        "root": None, "runs": None, "every": 0.0, "stall": "2pc-5@5",
        "max_restarts": 2,
    })
    if rest:
        print(f"fleet: unknown argument(s) {rest}", file=stream)
        return 2
    root = opts["root"] or tempfile.mkdtemp(prefix="stateright-tpu-fleet-")
    runs_dir = opts["runs"] or os.path.join(root, "runs")
    jobs = _canned_fleet_jobs(runs_dir)
    spec = FleetSpec(
        jobs=jobs, slots=opts["slots"],
        slot_budget_bytes=opts["budget"], spill=opts["spill"],
        pack=opts["pack"], max_restarts=opts["max_restarts"],
    )
    plan = None
    if opts["stall"] and opts["stall"] != "none":
        key, _, step = opts["stall"].partition("@")
        plan = PreemptionPlan({key: int(step or 3)})
        print(
            f"fleet: injecting a stall-preemption into {key} at step "
            f"{int(step or 3)}",
            file=stream,
        )
    print(
        f"fleet: {len(jobs)} job(s) over {spec.slots} slot(s) "
        f"(pack={spec.pack}, spill={spec.spill}, root={root})",
        file=stream,
    )
    res = run_fleet(
        spec, root=root, preemption=plan, every_secs=opts["every"],
        stream=stream,
    )
    _print_job_results(res, stream)
    print(
        f"fleet: completed={res.completed} failed={res.failed} "
        f"refused={res.refused} preemptions={res.preemptions} "
        f"engine_compiles={res.engine_compiles} "
        f"packed={sum(len(p['jobs']) for p in res.packed)} "
        f"secs={res.secs:.1f}",
        file=stream,
    )
    rc = 0 if (res.failed == 0 and res.refused == 0) else 1
    return rc or _audit_lineage(res, runs_dir, stream)


#: the campaign verb's named model factories: name -> (factory, default
#: grid).  Factories take grid-point params as keyword arguments.
_CAMPAIGN_FACTORIES = {
    "2pc": (
        lambda rm=3: __import__(
            "stateright_tpu.models.two_phase_commit",
            fromlist=["TwoPhaseSys"],
        ).TwoPhaseSys(rm),
        {"rm": [3, 4]},
    ),
    "paxos": (
        lambda clients=1: __import__(
            "stateright_tpu.models.paxos", fromlist=["paxos_model"],
        ).paxos_model(clients),
        {"clients": [1]},
    ),
}


def fleet_campaign(args: Optional[list] = None, stream=None) -> int:
    """The ``campaign`` verb: expand a parameter grid into fleet jobs,
    schedule them over the pool, and write the campaign ledger
    (docs/fleet.md "Campaigns").  ``campaign 2pc --grid='{"rm":[3,4]}'``
    checks TwoPhaseSys at both sizes under one campaign id; the ledger
    (per-job wall-clock, compile accounting, aggregate states/s) lands
    at ``ROOT/campaign.json``.  Exit 0 iff no job failed."""
    import json
    import tempfile

    from ..fleet import LEDGER_NAME, campaign_spec, run_campaign

    stream = stream or sys.stdout
    opts, rest = _pop_fleet_opts(list(args or []), {
        "slots": 2, "budget": None, "spill": False, "pack": True,
        "root": None, "runs": None, "every": 0.0, "stall": None,
        "max_restarts": 2, "id": None, "grid": None,
    })
    name = rest[0] if rest else "2pc"
    if name not in _CAMPAIGN_FACTORIES or len(rest) > 1:
        print(
            "usage: campaign [2pc|paxos] [--grid=JSON] [--root=DIR] "
            "[--runs=DIR] [--slots=N] [--budget=BYTES] [--spill] "
            "[--no-pack] [--id=CID]",
            file=stream,
        )
        return 2
    factory, grid = _CAMPAIGN_FACTORIES[name]
    if opts["grid"]:
        grid = json.loads(opts["grid"])
    root = opts["root"] or tempfile.mkdtemp(
        prefix="stateright-tpu-campaign-"
    )
    spec = campaign_spec(
        factory, grid, campaign_id=opts["id"],
        slots=opts["slots"], slot_budget_bytes=opts["budget"],
        spill=opts["spill"], pack=opts["pack"],
        max_restarts=opts["max_restarts"],
        run_dir=opts["runs"] or os.path.join(root, "runs"),
    )
    print(
        f"campaign {spec.campaign_id}: {len(spec.jobs)} job(s) from "
        f"grid {json.dumps(grid, sort_keys=True)} over {spec.slots} "
        f"slot(s) (root={root})",
        file=stream,
    )
    res, ledger = run_campaign(
        spec, root=root, every_secs=opts["every"], stream=stream,
    )
    _print_job_results(res, stream)
    print(
        f"campaign {spec.campaign_id}: completed={ledger['completed']} "
        f"failed={ledger['failed']} refused={ledger['refused']} "
        f"preemptions={ledger['preemptions']} "
        f"engine_compiles={ledger['engine_compiles']} "
        f"secs={ledger['secs']} total_states={ledger['total_states']} "
        f"states_per_sec={ledger['states_per_sec']}",
        file=stream,
    )
    print(
        f"campaign: ledger written to {os.path.join(root, LEDGER_NAME)}",
        file=stream,
    )
    return 0 if ledger["failed"] == 0 else 1


# -- supervise verb (supervisor.py; docs/robustness.md) ----------------------


def pop_supervise_opts(rest: list) -> tuple:
    """Strip the supervise verb's flags: ``(opts, rest)``.  ``opts``
    carries ``autosave`` (dir; a temp dir when omitted, printed so the
    operator can resume), ``every``/``keep`` (cadence), ``max_restarts``,
    ``runs`` (registry dir), and ``fault_plan``/``fault_log`` (chaos:
    a JSON FaultPlan to install, and where to dump its fired trail)."""
    opts = {
        "autosave": None, "every": 60.0, "keep": 3, "max_restarts": 5,
        "runs": None, "fault_plan": None, "fault_log": None,
        "batch": None, "steps": None,
    }
    kept = []
    for a in rest:
        if a.startswith("--autosave="):
            opts["autosave"] = a[len("--autosave="):]
        elif a.startswith("--batch="):
            opts["batch"] = int(a[len("--batch="):])
        elif a.startswith("--steps="):
            opts["steps"] = int(a[len("--steps="):])
        elif a.startswith("--every="):
            opts["every"] = float(a[len("--every="):])
        elif a.startswith("--keep="):
            opts["keep"] = int(a[len("--keep="):])
        elif a.startswith("--max-restarts="):
            opts["max_restarts"] = int(a[len("--max-restarts="):])
        elif a.startswith("--runs="):
            opts["runs"] = a[len("--runs="):]
        elif a.startswith("--fault-plan="):
            opts["fault_plan"] = a[len("--fault-plan="):]
        elif a.startswith("--fault-log="):
            opts["fault_log"] = a[len("--fault-log="):]
        else:
            kept.append(a)
    return opts, kept


def run_supervised(builder, opts: dict, stream=None, **spawn_kw):
    """Drive one supervised run (``supervisor.supervise``) from a
    :func:`pop_supervise_opts` config; prints the one-line summary the
    CI chaos smoke greps and returns the :class:`SupervisedRun`."""
    from ..supervisor import supervise
    from ..testing.faults import FaultPlan

    stream = stream or sys.stdout
    if opts.get("autosave") is None:
        import tempfile

        opts = dict(opts)
        opts["autosave"] = tempfile.mkdtemp(
            prefix="stateright-tpu-autosave-"
        )
        print(
            f"supervise: no --autosave=DIR given; checkpointing into "
            f"{opts['autosave']} (pass the same dir to resume after a "
            "kill)",
            file=stream,
        )
    plan = None
    if opts.get("fault_plan"):
        plan = FaultPlan.from_file(opts["fault_plan"]).install()
    if opts.get("runs"):
        builder = builder.runs(opts["runs"])
    # a recorder is required for the checkpoint/restart ring records (and
    # costs nothing measurable; the telemetry overhead contract)
    if builder.telemetry_opts is None:
        builder = builder.telemetry()
    if opts.get("batch"):
        spawn_kw.setdefault("batch", int(opts["batch"]))
    if opts.get("steps"):
        spawn_kw.setdefault("steps_per_call", int(opts["steps"]))
    try:
        res = supervise(
            builder,
            autosave_dir=opts["autosave"],
            every_secs=float(opts.get("every", 60.0)),
            keep=int(opts.get("keep", 3)),
            max_restarts=int(opts.get("max_restarts", 5)),
            **spawn_kw,
        )
    finally:
        if plan is not None:
            plan.uninstall()
            if opts.get("fault_log"):
                plan.to_jsonl(opts["fault_log"])
    c = res.checker
    parent = getattr(c, "parent_run_id", None)
    print(
        f"supervised: done={c.is_done()} states={c.state_count()} "
        f"unique={c.unique_state_count()} restarts={res.restarts} "
        f"run_id={c.run_id}"
        + (f" parent_run_id={parent}" if parent else "")
        + (
            f" degradations={','.join(res.degradations)}"
            if res.degradations else ""
        ),
        file=stream,
    )
    return res


# -- profile verb ------------------------------------------------------------


def _split_profile_args(
    args: list, default_out: str = "telemetry.jsonl"
) -> tuple:
    """``(--out, --chrome, rest)`` from a profile/report verb's argument
    list — the single definition of the ``--out=`` parsing."""
    out, chrome, rest = default_out, None, []
    for a in args:
        if a.startswith("--out="):
            out = a[len("--out="):]
        elif a.startswith("--chrome="):
            chrome = a[len("--chrome="):]
        else:
            rest.append(a)
    return out, chrome, rest


def profile_models(
    models: Iterable[tuple], out: str, chrome: Optional[str] = None,
    stream=None,
) -> dict:
    """Run each ``(label, model)`` with the flight recorder enabled and
    append one JSONL export per run to ``out`` (Chrome trace of the LAST
    run to ``chrome`` if given).  The engine is the device wavefront (CPU
    backend off-hardware — same code path); models without a tensor twin
    fall back to host BFS so the verb works on every example.  Prints one
    summary line per run; returns the last summary."""
    import json

    from ..parallel.actor_compiler import CompileError

    stream = stream or sys.stdout
    summary: dict = {}
    first = True
    for label, model in models:
        builder = model.checker().telemetry(occupancy_every=4)
        # detect "no device form" EXPLICITLY (the spawn_auto twin probe)
        # instead of catching exception types from inside spawn_tpu:
        # genuine device-run failures (poison rows, growth bugs, wiring
        # TypeErrors) must PROPAGATE so the CI profile smoke fails on a
        # broken engine rather than quietly uploading host telemetry.
        twin_err = None
        try:
            cached = getattr(model, "_tensor_cached", None)
            twin = (
                cached()
                if cached is not None
                else getattr(model, "tensor_model", lambda: None)()
            )
        except CompileError as e:
            twin, twin_err = None, e
        if twin is None:
            why = type(twin_err).__name__ if twin_err else "no tensor twin"
            print(
                f"--- {label}: device engine unavailable ({why}); "
                "profiling host BFS", file=stream,
            )
            checker = builder.spawn_bfs().join()
        else:
            checker = builder.spawn_tpu(sync=True)
        rec = checker.flight_recorder
        rec.update_meta(label=label)
        rec.to_jsonl(out, append=not first)
        first = False
        if chrome:
            rec.to_chrome_trace(chrome)
        summary = rec.summary()
        print(f"--- {label}", file=stream)
        print(json.dumps(summary, default=str), file=stream)
    return summary


def make_profile_cmd(factory: Callable[[list], Iterable[tuple]]) -> Callable:
    """Wrap a ``rest -> [(label, model), ...]`` factory as a ``profile``
    CLI verb (``--out=``/``--chrome=`` flags, remaining args to the
    factory)."""

    def _profile(rest: list) -> None:
        out, chrome, rest = _split_profile_args(rest)
        profile_models(factory(rest), out, chrome=chrome)
        print(f"telemetry JSONL written to {out}"
              + (f", Chrome trace to {chrome}" if chrome else ""))

    return _profile


def fleet_profile(args: Optional[list] = None, stream=None) -> int:
    """``profile [MODULE] [--out=F] [--chrome=F] [ARGS...]``: profile one
    example module's ``_audit_models`` configurations; 0 on success."""
    import importlib

    stream = stream or sys.stdout
    out, chrome, rest = _split_profile_args(list(args or []))
    name = rest.pop(0) if rest else "two_phase_commit"
    try:
        mod = importlib.import_module(f"stateright_tpu.models.{name}")
    except ImportError as e:
        print(f"profile: cannot import models.{name}: {e}", file=stream)
        return 1
    factory = getattr(mod, "_audit_models", None)
    if factory is None:
        print(f"{name}: no _audit_models hook to profile", file=stream)
        return 1
    profile_models(factory(rest), out, chrome=chrome, stream=stream)
    print(f"telemetry JSONL written to {out}", file=stream)
    return 0


# -- report verb -------------------------------------------------------------


def _split_report_args(args: list) -> tuple:
    """``(--out, rest)`` from a report verb's argument list (the profile
    splitter with the ``--chrome=`` channel discarded)."""
    out, _chrome, rest = _split_profile_args(
        args, default_out="run-report.json"
    )
    return out, rest


def report_models(
    models: Iterable[tuple], out: str, stream=None
) -> list:
    """Run each ``(label, model)`` with cartography-instrumented telemetry
    and write one post-run report (``telemetry/report.py``: JSON + sibling
    markdown).  A single configuration writes exactly ``out``; multiple
    configurations write numbered siblings (``out`` stem + ``-N``).
    Models without a tensor twin run host BFS — their report simply
    carries no cartography block.  Returns the written JSON paths."""
    from ..parallel.tensor_model import twin_or_none

    stream = stream or sys.stdout
    models = list(models)
    paths = []
    for i, (label, model) in enumerate(models):
        if len(models) == 1:
            path = out
        else:
            stem, ext = os.path.splitext(out)
            path = f"{stem}-{i}{ext or '.json'}"
        builder = model.checker().report(path)
        if twin_or_none(model) is None:
            print(
                f"--- {label}: no device twin; reporting a host BFS run "
                "(no cartography block)", file=stream,
            )
            builder.spawn_bfs().join()
        else:
            builder.spawn_tpu(sync=True)
        print(f"--- {label}: report written to {path}", file=stream)
        paths.append(path)
    return paths


def make_report_cmd(factory: Callable[[list], Iterable[tuple]]) -> Callable:
    """Wrap a ``rest -> [(label, model), ...]`` factory as a ``report``
    CLI verb (``--out=`` flag, remaining args to the factory)."""

    def _report(rest: list) -> None:
        out, rest = _split_report_args(rest)
        report_models(factory(rest), out)

    return _report


def fleet_report(args: Optional[list] = None, stream=None) -> int:
    """``report [MODULE] [--out=F] [ARGS...]``: post-run report for one
    example module's ``_audit_models`` configurations; 0 on success."""
    import importlib

    stream = stream or sys.stdout
    out, rest = _split_report_args(list(args or []))
    name = rest.pop(0) if rest else "two_phase_commit"
    try:
        mod = importlib.import_module(f"stateright_tpu.models.{name}")
    except ImportError as e:
        print(f"report: cannot import models.{name}: {e}", file=stream)
        return 1
    factory = getattr(mod, "_audit_models", None)
    if factory is None:
        print(f"{name}: no _audit_models hook to report on", file=stream)
        return 1
    report_models(factory(rest), out, stream=stream)
    return 0


def fleet_audit(names: Optional[list] = None, stream=None) -> int:
    """Audit the whole example fleet (or just ``names``); 0 iff clean.
    Modules without an ``_audit_models`` hook are reported and skipped."""
    import importlib

    from . import __all__ as all_names

    stream = stream or sys.stdout
    ok = True
    for name in names or list(all_names):
        mod = importlib.import_module(f"stateright_tpu.models.{name}")
        factory = getattr(mod, "_audit_models", None)
        if factory is None:
            # a FAILURE, not a skip: the gate exists to keep every shipped
            # example audited — a new example without the hook would
            # otherwise silently shrink coverage while CI stays green
            print(
                f"--- {name}: FAILED — no _audit_models hook (add one so "
                "the fleet gate covers this example)",
                file=stream,
            )
            ok = False
            continue
        ok = audit_and_report(factory([]), stream=stream) and ok
    print("audit fleet: " + ("CLEAN" if ok else "FAILED"), file=stream)
    return 0 if ok else 1


def fleet_status(argv: Optional[list] = None, stream=None) -> int:
    """``status RUN_DIR``: tail the progress heartbeat of a headless run.

    Reads the atomic ``progress.json`` the engines (and the fleet
    scheduler) write next to autosave generations, plus any per-job
    heartbeats under ``RUN_DIR/jobs/*/``.  Works post-mortem: a SIGKILLed
    run leaves its last heartbeat behind, and a stale ``running`` status
    is reported as ``DEAD`` (where did it stall).  Exit 0 iff at least
    one heartbeat was found.
    """
    from ..checkpoint import read_progress

    stream = stream or sys.stdout
    argv = argv or []
    if not argv:
        print("usage: status RUN_DIR", file=stream)
        return 1
    root = argv[0]

    def _render(tag: str, doc: dict) -> None:
        verdict = doc.get("verdict", "?")
        bits = [f"--- {tag}: {verdict.upper()}"]
        if doc.get("age_secs") is not None:
            bits.append(f"age={doc['age_secs']:.1f}s")
        for k in ("states", "unique", "steps", "frontier", "queue",
                  "depth", "phase", "ewma_states_per_sec", "eta_secs",
                  "jobs", "running", "queued", "completed", "preemptions"):
            v = doc.get(k)
            if v is None:
                continue
            if isinstance(v, list):
                v = len(v)
            bits.append(f"{k}={v}")
        if doc.get("stalled"):
            bits.append(f"STALLED({doc.get('stall_reason') or '?'})")
        print("  ".join(bits), file=stream)

    found = 0
    top = read_progress(root)
    if top is not None:
        _render(root, top)
        found += 1
    jobs_dir = os.path.join(root, "jobs")
    if os.path.isdir(jobs_dir):
        for name in sorted(os.listdir(jobs_dir)):
            doc = read_progress(os.path.join(jobs_dir, name))
            if doc is not None:
                _render(f"jobs/{name}", doc)
                found += 1
    if not found:
        print(f"status: no progress.json under {root} (run without "
              "autosave, or not started yet)", file=stream)
        return 1
    return 0


def main(argv: Optional[list] = None) -> None:
    argv = sys.argv[1:] if argv is None else argv
    if argv and argv[0] == "audit":
        raise SystemExit(fleet_audit(argv[1:]))
    if argv and argv[0] == "sanitize":
        raise SystemExit(fleet_sanitize(argv[1:]))
    if argv and argv[0] == "independence":
        raise SystemExit(fleet_independence(argv[1:]))
    if argv and argv[0] == "profile":
        raise SystemExit(fleet_profile(argv[1:]))
    if argv and argv[0] == "report":
        raise SystemExit(fleet_report(argv[1:]))
    if argv and argv[0] == "capacity":
        raise SystemExit(fleet_capacity(argv[1:]))
    if argv and argv[0] == "costmodel":
        raise SystemExit(fleet_costmodel(argv[1:]))
    if argv and argv[0] == "runs":
        raise SystemExit(fleet_runs(argv[1:]))
    if argv and argv[0] == "compare":
        raise SystemExit(compare_reports_cmd(argv[1:]))
    if argv and argv[0] == "fleet":
        raise SystemExit(fleet_schedule(argv[1:]))
    if argv and argv[0] == "campaign":
        raise SystemExit(fleet_campaign(argv[1:]))
    if argv and argv[0] == "status":
        raise SystemExit(fleet_status(argv[1:]))
    print("USAGE:")
    print("  python -m stateright_tpu.models._cli audit [MODULE...]")
    print("    static preflight audit over the example fleet "
          "(docs/analysis.md)")
    print("  python -m stateright_tpu.models._cli sanitize [MODULE...]")
    print("    interval/bounds soundness sanitizer over the fleet "
          "(docs/analysis.md JX2xx); exit 1 on any error finding")
    print("  python -m stateright_tpu.models._cli independence [MODULE...]")
    print("    static independence / conflict-matrix analysis over the "
          "fleet (docs/analysis.md JX3xx); exit 1 on any error finding")
    print("  python -m stateright_tpu.models._cli profile [MODULE] "
          "[--out=F] [--chrome=F] [ARGS...]")
    print("    telemetry-instrumented run; flight-recorder JSONL export "
          "(docs/telemetry.md)")
    print("  python -m stateright_tpu.models._cli report [MODULE] "
          "[--out=F] [ARGS...]")
    print("    post-run report (JSON + markdown): totals, cartography, "
          "memory, health timeline (docs/telemetry.md)")
    print("  python -m stateright_tpu.models._cli capacity [MODULE...]")
    print("    HBM capacity plan over the fleet: analytic per-rung "
          "footprint + max reachable states (docs/telemetry.md)")
    print("  python -m stateright_tpu.models._cli costmodel [--out=F] "
          "[MODULE...]")
    print("    roofline cost ledger over the fleet: per-stage "
          "FLOPs/bytes, XLA reconciliation, MXU candidates "
          "(docs/roofline.md); exit 1 on a non-reconciling ledger")
    print("  python -m stateright_tpu.models._cli runs [DIR]")
    print("    list the persistent run registry: archived runs, "
          "config keys, per-config trends (docs/telemetry.md "
          "\"Comparing runs\")")
    print("  python -m stateright_tpu.models._cli compare A B "
          "[--registry=DIR] [--expect=VERDICT]")
    print("    contract-aware diff of two run reports (files or "
          "registry run ids); exit 1 on DIVERGENT or an --expect "
          "mismatch")
    print("  python -m stateright_tpu.models._cli fleet [--slots=N] "
          "[--root=DIR] [--runs=DIR] [--stall=KEY@STEP|none] "
          "[--budget=BYTES] [--spill] [--no-pack]")
    print("    multi-tenant chaos smoke: six mixed 2pc/paxos jobs over "
          "a simulated pool with one injected stall-preemption; "
          "verifies pinned counts + resume lineage (docs/fleet.md)")
    print("  python -m stateright_tpu.models._cli campaign [2pc|paxos] "
          "[--grid=JSON] [--root=DIR] [--runs=DIR] [--slots=N] "
          "[--id=CID]")
    print("    parameter-grid campaign over the fleet scheduler; "
          "writes the ROOT/campaign.json ledger with per-job "
          "wall-clock + aggregate states/s (docs/fleet.md)")
    print("  python -m stateright_tpu.models._cli status RUN_DIR")
    print("    tail the progress.json heartbeat of a headless run "
          "(works post-mortem on a SIGKILLed run; stale running "
          "heartbeats report DEAD) (docs/observability.md)")


if __name__ == "__main__":
    main()
