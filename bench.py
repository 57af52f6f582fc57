"""Benchmark: TPU wavefront engine vs the CPU BFS baseline.

Driver metric (BASELINE.md): **states/sec on ``paxos check 3`` + ``2pc
check 4``, with discovery-count parity**; north-star >=20x the multithreaded
CPU BfsChecker on ``paxos check 3``.  Protocol mirrors the reference's
``bench.sh`` wall-clock discipline (reference ``src/checker.rs:230-233``).

Output contract: this script prints complete JSON lines — the LAST line is
the result — **incrementally** (one line per milestone), so an outer
timeout still leaves a parseable artifact:

 - **Every stdout line is small** (hard cap ``MAX_LINE_BYTES``): only
   scalar headline keys.  Full details go to stderr and a side file
   (``docs/bench-last-details.json``), never stdout.
 - **Every line names the device it ran on** (``platform``,
   ``device_kind``, ``device_count`` as JAX reports them in the device
   child).  Only a run on ``platform == "tpu"`` can be ``fresh: true``,
   headline a ``value``, or be persisted by :func:`record_validated`.
   The device phase also runs on XLA:CPU (CI smokes the harness that
   way), but then everything it measured is stored under the
   ``xlacpu_*`` key prefix — never under a ``tpu_*`` name — and the
   headline stays ``value: 0.0, fresh: false``.
 - **No number is ever carried forward.**  A device phase that fails is
   a failed run with no number; ``BENCH_VALIDATED.json`` (written only
   by a full, error-free run on a TPU) feeds the ``trend``/``regressed``
   comparison and nothing else.
 - **Any failure is a non-zero exit.**  A device leg that raised lands
   as a ``*_error`` key next to the numbers that did land; any such key
   makes the exit code non-zero.

Telemetry: the primary paxos-3 and 2pc-7 device runs record a flight-
recorder summary (``stateright_tpu/telemetry/``) embedded as
``tpu_paxos3_telemetry`` / ``tpu_2pc7_telemetry`` in the details artifact
— per-step throughput, dedup ratio, growth events, occupancy, transfer
volume — so every future perf claim has its time series on record.
Both legs run with the search-cartography counters AND the HBM memory
ledger on, embedding their post-run report (``telemetry/report.py``) as
``tpu_paxos3_report`` / ``tpu_2pc7_report`` plus the raw
``*_cartography`` and ``*_memory`` blocks, so the numbers arrive with
the search shape (depth/action mix, property coverage, shard balance)
and the memory story (per-buffer footprint, growth-transient forecast,
device watermark) that explain them.  ``regress.py`` gates a fresh
run's summary against BENCH_VALIDATED.json (``--cartography`` /
``--memory`` for the blocks' well-formedness).

``BENCH_SPILL=1`` adds the flag-gated spill leg (docs/spill.md): the
same 2pc-7 under a SIMULATED device budget smaller than its
steady-state footprint (``tpu_2pc7_spill_*`` keys + the per-tier byte
breakdown in ``tpu_2pc7_spill``); ``regress.py --spill`` gates its
well-formedness and count parity.  ``BENCH_SPILL_BUDGET`` overrides
the computed budget.

``BENCH_MXU=1`` adds the flag-gated MXU-recast legs (docs/roofline.md
"Executing the hot-spot list"): the same paxos-3 and 2pc-7 configs
with ``CheckerBuilder.mxu()`` armed, count parity ASSERTED, and the
flagged roofline ledgers embedded as ``tpu_paxos3_mxu_roofline`` /
``tpu_2pc7_mxu_roofline`` next to the same run's unflagged blocks —
``regress.py --mxu`` gates the before/after pair (expand charged
bytes drop >=30% on paxos-3; a dot-class dedup-insert op on 2pc-7).

``BENCH_SWEEP=1`` adds the flag-gated hyper-batched sweep leg
(docs/sweep.md): the paxos default family (``BENCH_SWEEP_N`` instances,
alternating lossiness) as ONE sweep vs the same instances sequentially
— per-instance count parity ASSERTED, compile amortization recorded
(``tpu_sweep.engine_compiles`` vs ``sequential_engine_compiles``), and
the ``tpu_sweep_states_per_sec`` /
``tpu_sweep_sequential_states_per_sec`` aggregate-throughput pair;
``regress.py --sweep`` gates the block's well-formedness and parity.

``BENCH_LIVE=1`` adds the flag-gated live-observability leg
(docs/observability.md): paxos-3 with plain telemetry vs telemetry +
metrics bus + armed progress heartbeat — count parity ASSERTED, the
measured bus-sampling + heartbeat-write overhead fraction recorded as
``tpu_live.overhead_frac`` next to the published family list and the
terminal heartbeat; ``regress.py --live`` gates the block.

Run ledger (docs/telemetry.md "Comparing runs"): with
``STATERIGHT_TPU_RUN_DIR`` set, EVERY device leg bench runs is archived
into the persistent run registry (``telemetry/registry.py``) — one
report + ``config_key``-indexed headline record per leg, under
``run_registry`` in the details artifact — so A/Bs become
``_cli compare`` invocations instead of transcript archaeology.  Fresh
runs additionally emit ``trend``: every measured ``tpu_*_states_per_sec``
against the BENCH_VALIDATED.json history with its ratio (``regressed``
is the below-tolerance subset), and a validated full run embeds its
``tpu_paxos3_report`` into BENCH_VALIDATED.json for ``regress.py
--diff``.

``value``/``vs_baseline`` are recomputed on every emit from whatever
numbers exist so far.

Baseline definition (the ONE honest story — README, BASELINE.md and this
script agree): ``vs_baseline`` = TPU paxos-3 states/s / **uncontended
single-core CPU BFS states/s of this framework's own engine** (the Rust
reference cannot be built here — no cargo toolchain — so the reference's
multithreaded CPU BfsChecker is approximated by this framework's CPU
engine; see BASELINE.md).  The same-invocation CPU run is used only when
it is actually uncontended (within 80% of the stored uncontended rate);
otherwise the stored uncontended rate is used and the contention is
recorded (``cpu_baseline_src``, ``cpu_load1``).

Process structure — one process for each chip:

 1. The PARENT confines itself to the CPU backend before anything can
    initialise another one (hard failure if it cannot), then runs the
    CPU phase: pinned-count parity runs + baseline states/sec (bounded
    prefixes where a full Python run would take hours).  Emit.
 2. ONE device child (``--device-child``), ONE attempt: backend init,
    compile cache on (``prewarm.resolve_compile_cache_dir``), parity
    configs, then the primary ``paxos check 3`` timed run FIRST (so a
    later failure cannot lose it), then ``2pc check 4``
    and the remaining reference bench configs.  The child appends
    cumulative results to a stage file after every milestone; the parent
    merges + emits on change, kills the child only at the deadline, and
    propagates its exit code.  No probe, no retry: a chip belongs to one
    process at a time, and a deterministic failure retried is the same
    failure again.

Env knobs: ``BENCH_DEADLINE`` (secs, default 1500) bounds the WHOLE script;
``BENCH_TPU_TIMEOUT`` (secs, default: remaining deadline) bounds the device
phase; ``BENCH_TPU_TARGET`` caps the paxos-3 device run's unique states
(default: empty = FULL enumeration — 1,194,428 unique states).
"""

import json
import os
import subprocess
import sys
import time
import traceback

PAXOS2_UNIQUE = 16_668  # examples/paxos.rs:291
TPC5_UNIQUE = 8_832  # examples/2pc.rs:133
TPC4_UNIQUE = 1_568  # 2pc at 4 RMs (pinned in tests/test_models.py)
CPU_TARGET = 12_000  # unique-state cap for the CPU paxos-3 baseline prefix

T0 = time.monotonic()
DEADLINE_S = float(os.environ.get("BENCH_DEADLINE", "1500"))

_HERE = os.path.dirname(os.path.abspath(__file__))
VALIDATED_PATH = os.environ.get(
    "BENCH_VALIDATED_FILE", os.path.join(_HERE, "BENCH_VALIDATED.json")
)
DETAILS_PATH = os.environ.get(
    "BENCH_DETAILS_FILE", os.path.join(_HERE, "docs", "bench-last-details.json")
)
# a driver that keeps only a ~2KB tail of stdout can never parse a line
# longer than that window.  Stay far under it.
MAX_LINE_BYTES = 1000


def _load_validated() -> dict:
    try:
        with open(VALIDATED_PATH) as f:
            return json.load(f)
    except (OSError, json.JSONDecodeError):
        return {}


VALIDATED = _load_validated()

# run ledger (docs/telemetry.md "Comparing runs"): bench registers each
# leg EXPLICITLY (leg-tagged, with the already-built report body where
# one exists), so main() CONSUMES the env knob into this global — left
# in the environment it would also trigger every checker's join-time
# auto-record and double-archive each leg (plus warm-ups and the CPU
# baseline) as untagged noise.  run_tpu_attempt re-injects it into the
# child's env; the child's main() consumes it again the same way.
RUN_LEDGER_DIR = None


def remaining() -> float:
    return DEADLINE_S - (time.monotonic() - T0)


EXTRAS: dict = {}
_last_emitted = None
_last_details = None

# stdout whitelist, highest-priority first: when the line would exceed
# MAX_LINE_BYTES, keys are dropped from the END of this list until it fits
# (the first four are the driver's contract and are never dropped).
_LINE_KEYS = (
    "metric", "value", "unit", "vs_baseline",
    "fresh", "platform", "device_kind", "device_count", "error",
    "regressed",
    "tpu_paxos3_states_per_sec", "tpu_paxos3_unique", "tpu_paxos3_sec",
    "cpu_baseline_states_per_sec", "cpu_baseline_src",
    "cpu_baseline_engine", "cpu_cores",
    "cpu_load1", "baseline_def", "parity", "details",
)


def _cpu_baseline() -> tuple:
    """(rate, src, uncontended): the single source of the baseline-selection
    rule.  The same-invocation CPU run counts as uncontended when the box
    was idle at phase start (load1 < 0.7) or when it reaches 80% of
    the stored uncontended rate.  Replace-not-ratchet: an idle same-run
    measurement may legitimately be LOWER than the stored rate (slower
    box, slower engine) and still wins."""
    cpu_same = EXTRAS.get("cpu_paxos3_states_per_sec")
    cpu_stored = VALIDATED.get("cpu_paxos3_uncontended_states_per_sec")
    if not cpu_same:
        if cpu_stored:
            return cpu_stored, "stored-uncontended (cpu phase failed)", False
        return None, None, False
    load1 = EXTRAS.get("cpu_load1")
    uncontended = (load1 is not None and load1 < 0.7) or (
        bool(cpu_stored) and cpu_same >= 0.8 * cpu_stored
    )
    if uncontended or not cpu_stored:
        src = "same-run" if uncontended else (
            f"same-run (unverified: load1={load1}, nothing stored)"
        )
        return cpu_same, src, uncontended
    return (
        cpu_stored,
        f"stored-uncontended (same-run contended: {cpu_same:.0f}/s, "
        f"load1={load1})",
        False,
    )


# perf-regression guard (ADVICE item 8): a FRESH run's per-config rates
# against the BENCH_VALIDATED.json history.  ONE tolerance with
# regress.py's throughput gate — imported, so a retune there cannot
# silently diverge from the guard here; the fallback only covers running
# bench.py from outside the repo root.
try:
    from regress import DEFAULT_TOLERANCE as REGRESS_TOLERANCE
except ImportError:  # pragma: no cover - bench copied out of the repo
    REGRESS_TOLERANCE = 0.85


def _trend_deltas() -> list:
    """Per-config ``{config, run, baseline, ratio}`` entries for EVERY
    freshly measured ``tpu_*_states_per_sec`` with a stored validated
    history value — the full trend view against BENCH_VALIDATED.json
    (improvements and regressions alike; ``regressed`` is the
    below-tolerance subset).  Compares only keys present in BOTH (the
    caller additionally gates on the run being fresh, i.e. measured on
    a TPU), and configs the baseline never validated have no trend."""
    out = []
    for key, base in sorted(VALIDATED.items()):
        if not key.endswith("_states_per_sec") or not key.startswith("tpu_"):
            continue
        cur = EXTRAS.get(key)
        if (
            not isinstance(cur, (int, float))
            or not isinstance(base, (int, float))
            or not base
        ):
            continue
        out.append({
            "config": key,
            "run": cur,
            "baseline": base,
            "ratio": round(cur / base, 3),
        })
    return out


def _perf_regressions(trend=None) -> list:
    """The below-``REGRESS_TOLERANCE`` subset of :func:`_trend_deltas`
    (ADVICE item 8's guard)."""
    return [
        e for e in (_trend_deltas() if trend is None else trend)
        if e["run"] < REGRESS_TOLERANCE * e["baseline"]
    ]


def _compute_headline() -> dict:
    """value/vs_baseline + provenance fields from EXTRAS ∪ VALIDATED.
    Returned keys OVERRIDE the raw extras in the emitted record (merge
    order in emit())."""
    out: dict = {}
    cpu_base, cpu_src, _ = _cpu_baseline()
    if cpu_base is not None:
        out["cpu_baseline_states_per_sec"] = cpu_base
        out["cpu_baseline_src"] = cpu_src
    out["baseline_def"] = "uncontended single-core CPU BFS (this framework)"
    if EXTRAS.get("cpu_baseline_engine"):
        out["cpu_baseline_engine"] = EXTRAS["cpu_baseline_engine"]
    # -- value: this run's chip number, or nothing.  "Chip" is what the
    # device child reported, never what the key is called: a number is
    # fresh only when platform == "tpu" (the child stores what it
    # measures elsewhere under another prefix — see _label_by_platform)
    out["platform"] = EXTRAS.get("platform") or "none yet"
    on_tpu = EXTRAS.get("platform") == "tpu"
    tpu_sps = EXTRAS.get("tpu_paxos3_states_per_sec") if on_tpu else None
    if tpu_sps is not None:
        out["value"], out["fresh"] = tpu_sps, True
        # trend deltas vs the BENCH_VALIDATED history (details artifact)
        # + the perf-regression guard (ADVICE 8): only FRESH measurements
        # are compared
        out["trend"] = _trend_deltas()
        out["regressed"] = _perf_regressions(out["trend"])
    else:
        out["value"], out["fresh"] = 0.0, False
    out["vs_baseline"] = (
        round(out["value"] / cpu_base, 3) if cpu_base and out["value"] else 0.0
    )
    return out


def emit(**updates) -> None:
    """Print a COMPLETE, SMALL result line (the driver parses the last
    stdout line out of a ~2KB tail window, so every line must stay under
    MAX_LINE_BYTES).  Full cumulative details go to DETAILS_PATH and
    stderr instead.  value/vs_baseline are recomputed every time, so every
    line is a valid final answer for everything known so far; while no
    number measured on a TPU exists, ``value`` stays 0.0 (``fresh:
    false``) — nothing is ever carried forward from a stored file."""
    global _last_emitted, _last_details
    EXTRAS.update(updates)
    full = {
        "metric": "paxos check 3 states/sec (TPU wavefront)",
        "unit": "states/sec",
        **{k: v for k, v in EXTRAS.items() if k not in ("value", "unit")},
        **_compute_headline(),  # AFTER extras: headline fields override
        "details": os.path.relpath(DETAILS_PATH, _HERE),
    }
    # full detail record: side file, never stdout.  Deduped on the full
    # dict (not the headline line): the ~5s re-emits of unchanged salvage
    # must not rewrite the file, but a milestone that only adds a
    # secondary config number still must.
    blob = json.dumps(full, indent=1)
    if blob != _last_details:
        try:
            with open(DETAILS_PATH, "w") as f:
                f.write(blob)
            _last_details = blob
        except OSError as e:
            # the side file is the details' only home; if it is unwritable
            # they survive on stderr instead (docstring contract)
            full.pop("details", None)
            sys.stderr.write(f"bench: details file unwritable ({e}); "
                             f"details follow:\n{blob}\n")
            _last_details = blob
    small = {k: full[k] for k in _LINE_KEYS if full.get(k) is not None}
    if "error" in small:
        small["error"] = str(small["error"])[:140]
    line = json.dumps(small)
    drop = len(_LINE_KEYS) - 1
    while len(line.encode()) > MAX_LINE_BYTES and drop >= 4:
        small.pop(_LINE_KEYS[drop], None)
        drop -= 1
        line = json.dumps(small)
    if line != _last_emitted:
        print(line, flush=True)
        sys.stderr.write(f"bench: emitted {len(line)}B headline line\n")
        _last_emitted = line


def record_validated() -> None:
    """Persist the result of a full, error-free run ON A TPU (+ the
    uncontended CPU baseline when this run's CPU phase was uncontended):
    the history the ``trend``/``regressed`` comparison and ``regress.py``
    read.  Never a number to emit in place of a measurement.

    A BENCH_TPU_TARGET prefix run is NOT persisted: its rate is dominated
    by fixed overhead and is not comparable to the full-enumeration
    headline."""
    if EXTRAS.get("platform") != "tpu":
        sys.stderr.write(
            f"bench: device phase ran on platform="
            f"{EXTRAS.get('platform')!r}, not a TPU — not persisting to "
            "BENCH_VALIDATED.json\n"
        )
        return
    if os.environ.get("BENCH_TPU_TARGET", ""):
        sys.stderr.write(
            "bench: prefix run (BENCH_TPU_TARGET set) — not persisting to "
            "BENCH_VALIDATED.json\n"
        )
        return
    # "parity gates passed" must mean the DEVICE gates actually ran: a
    # salvaged partial (killed after the timed run, before the 2pc5 gate)
    # or an errored phase is a real number but not a validated one
    if (
        _error_keys(EXTRAS)
        or not EXTRAS.get("tpu_paxos2_discoveries")
        or not EXTRAS.get("tpu_2pc5_discoveries")
    ):
        sys.stderr.write(
            "bench: partial/errored TPU phase (device parity gates "
            "incomplete) — not persisting to BENCH_VALIDATED.json\n"
        )
        return
    doc = {
        "tpu_paxos3_states_per_sec": EXTRAS.get("tpu_paxos3_states_per_sec"),
        "tpu_paxos3_unique": EXTRAS.get("tpu_paxos3_unique"),
        "tpu_paxos3_sec": EXTRAS.get("tpu_paxos3_sec"),
        "tpu_devices": EXTRAS.get("tpu_devices"),
        "platform": EXTRAS.get("platform"),
        "device_kind": EXTRAS.get("device_kind"),
        "device_count": EXTRAS.get("device_count"),
        "validated_at": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
        "provenance": "bench.py full run, parity gates passed",
    }
    # per-stage attribution travels with the validated number so
    # ``regress.py --stages`` can compare like against like
    if EXTRAS.get("tpu_paxos3_stages"):
        doc["tpu_paxos3_stages"] = EXTRAS["tpu_paxos3_stages"]
    # ...and the cartography block, so ``regress.py --cartography`` can
    # diff search shape (depth/action mix, shard balance) across rounds
    if EXTRAS.get("tpu_paxos3_cartography"):
        doc["tpu_paxos3_cartography"] = EXTRAS["tpu_paxos3_cartography"]
    # ...and the memory block (regress.py --memory): the validated
    # number travels with its HBM footprint + growth forecast
    if EXTRAS.get("tpu_paxos3_memory"):
        doc["tpu_paxos3_memory"] = EXTRAS["tpu_paxos3_memory"]
    # ...and the roofline block (regress.py --roofline): the validated
    # number travels with its per-stage cost ledger + bound verdicts
    if EXTRAS.get("tpu_paxos3_roofline"):
        doc["tpu_paxos3_roofline"] = EXTRAS["tpu_paxos3_roofline"]
    # ...and the full embedded run report (regress.py --diff): future
    # rounds diff their fresh report against this one with the
    # contract-aware engine (telemetry/diff.py) — pre-registry
    # baselines simply lack the key and never trip the gate
    if EXTRAS.get("tpu_paxos3_report"):
        doc["tpu_paxos3_report"] = EXTRAS["tpu_paxos3_report"]
    if EXTRAS.get("tpu_phases"):
        doc["tpu_phases"] = EXTRAS["tpu_phases"]
    cpu_stored = VALIDATED.get("cpu_paxos3_uncontended_states_per_sec")
    _, _, uncontended = _cpu_baseline()
    if uncontended:
        # replace, don't ratchet: an idle measurement that is LOWER than
        # the stored rate (slower box, slower engine) is the new truth
        doc["cpu_paxos3_uncontended_states_per_sec"] = EXTRAS[
            "cpu_paxos3_states_per_sec"
        ]
        doc["cpu_load1"] = EXTRAS.get("cpu_load1")
        # which engine measured the stored rate: the native baseline and
        # the python fallback are NOT comparable across rounds
        doc["cpu_baseline_engine"] = EXTRAS.get("cpu_baseline_engine")
    elif cpu_stored:
        doc["cpu_paxos3_uncontended_states_per_sec"] = cpu_stored
    if doc["tpu_paxos3_states_per_sec"] is None:
        return
    try:
        with open(VALIDATED_PATH, "w") as f:
            json.dump(doc, f, indent=1)
        VALIDATED.clear()
        VALIDATED.update(doc)
    except OSError as e:
        sys.stderr.write(f"bench: could not write BENCH_VALIDATED.json: {e}\n")


def timed(spawn):
    t0 = time.monotonic()
    checker = spawn()
    checker.join()
    dt = max(time.monotonic() - t0, 1e-9)
    return checker, dt


# ---------------------------------------------------------------------------
# CPU phase (parent process; never touches a device backend)
# ---------------------------------------------------------------------------


def cpu_phase() -> dict:
    from stateright_tpu.models.paxos import paxos_model
    from stateright_tpu.models.two_phase_commit import TwoPhaseSys

    threads = os.cpu_count() or 1
    try:
        load1 = round(os.getloadavg()[0], 2)
    except OSError:
        load1 = None
    out: dict = {
        # contention evidence for the baseline-source decision (see module
        # docstring): sampled before this phase adds its own load
        "cpu_load1": load1,
        # honesty note: the thread pool
        # is GIL-bound, so the REAL multi-core baseline is the
        # process-parallel BFS (stateright_tpu/checker/mp.py), reported as
        # ``cpu_*_mp_*``.  On this box the distinction is moot when
        # cpu_cores=1 — then single-core IS all the hardware offers and
        # vs_baseline is measured against the best available CPU run.
        "cpu_cores": threads,
        "cpu_baseline_note": (
            f"threads({threads}) under the CPython GIL ~= single-core; "
            "mp numbers (when cores>1) are process-parallel"
        ),
    }

    # primary baseline FIRST: vs_baseline needs it, and every emit after
    # this carries it.  The denominator is the COMPILED single-core
    # baseline when the native module builds (stateright_tpu/native/bfs.cpp
    # — XLA-CPU step kernels + native visited set/queue; ROADMAP "so
    # vs_baseline stops flattering the engine"); the pure-Python thread
    # BFS is the fallback AND is always measured for continuity
    # (``cpu_paxos3_python_states_per_sec``).
    cpu_p3, dt = timed(
        lambda: paxos_model(3)
        .checker()
        .threads(threads)
        .target_states(CPU_TARGET)
        .spawn_bfs()
    )
    out["cpu_paxos3_python_states_per_sec"] = round(
        cpu_p3.state_count() / dt, 1
    )
    out["cpu_paxos3_states_per_sec"] = out["cpu_paxos3_python_states_per_sec"]
    out["cpu_paxos3_states"] = cpu_p3.state_count()
    out["cpu_paxos3_sec"] = round(dt, 3)
    out["cpu_paxos3_note"] = f"prefix run, target_states={CPU_TARGET}"
    out["cpu_baseline_engine"] = "python-thread-bfs"
    try:
        from stateright_tpu.native.baseline import compiled_cpu_bfs

        nat = compiled_cpu_bfs(paxos_model(3), target=CPU_TARGET, batch=2048)
        if nat is not None:
            out["cpu_paxos3_states_per_sec"] = nat["states_per_sec"]
            out["cpu_paxos3_states"] = nat["states"]
            out["cpu_paxos3_sec"] = nat["secs"]
            out["cpu_baseline_engine"] = "native-cpp-bfs"
        else:
            out["cpu_baseline_engine_note"] = (
                "native module unavailable; python fallback"
            )
    except Exception as e:  # noqa: BLE001 - the baseline never voids the run
        out["cpu_native_baseline_error"] = f"{type(e).__name__}: {e}"
    # parity gates (pinned counts)
    cpu_p2 = paxos_model(2).checker().threads(threads).spawn_bfs().join()
    cpu_t5 = TwoPhaseSys(5).checker().threads(threads).spawn_bfs().join()
    if cpu_p2.unique_state_count() != PAXOS2_UNIQUE:
        raise AssertionError(
            f"cpu paxos2 unique {cpu_p2.unique_state_count()} != {PAXOS2_UNIQUE}"
        )
    if cpu_t5.unique_state_count() != TPC5_UNIQUE:
        raise AssertionError(
            f"cpu 2pc5 unique {cpu_t5.unique_state_count()} != {TPC5_UNIQUE}"
        )
    out["cpu_paxos2_discoveries"] = sorted(cpu_p2.discoveries())
    out["cpu_2pc5_discoveries"] = sorted(cpu_t5.discoveries())

    cpu_t4, dt4 = timed(
        lambda: TwoPhaseSys(4).checker().threads(threads).spawn_bfs()
    )
    out["cpu_2pc4_states_per_sec"] = round(cpu_t4.state_count() / dt4, 1)
    out["cpu_2pc4_unique"] = cpu_t4.unique_state_count()
    cpu_t6, dt6 = timed(
        lambda: TwoPhaseSys(6).checker().threads(threads).spawn_bfs()
    )
    out["cpu_2pc6_states_per_sec"] = round(cpu_t6.state_count() / dt6, 1)

    # real multi-core baseline: process-parallel BFS on the primary config.
    # Skipped on a single-core box, where it can only equal the thread run
    # minus IPC overhead (correctness is pinned by tests/test_mp.py).
    if threads > 1:
        try:
            from stateright_tpu.checker.mp import spawn_mp_bfs

            mp3, dtm = timed(
                lambda: spawn_mp_bfs(
                    paxos_model(3), target_states=CPU_TARGET
                )
            )
            out["cpu_paxos3_mp_states_per_sec"] = round(
                mp3.state_count() / dtm, 1
            )
            out["cpu_paxos3_mp_workers"] = mp3.worker_count
        except Exception as e:  # noqa: BLE001 - mp never voids the run
            out["cpu_paxos3_mp_error"] = f"{type(e).__name__}: {e}"
    else:
        out["cpu_paxos3_mp_note"] = "single-core box: mp baseline == thread"

    # the reference's full bench protocol (bench.sh:27-34): 2pc 10, paxos 6,
    # single-copy 4, lin-reg 2, lin-reg 3 ordered.  Python CPU BFS cannot
    # finish the big ones in bench budget, so rate-like prefix runs are used
    # (same treatment as paxos 3 above); each config is individually guarded.
    for tag, build, target in _bench_protocol():
        if remaining() < 0.75 * DEADLINE_S:
            out[f"cpu_{tag}_skipped"] = "cpu-phase budget spent"
            continue
        try:
            c, dt = timed(
                lambda: _capped(build().checker().threads(threads), target)
                .spawn_bfs()
            )
            out[f"cpu_{tag}_states_per_sec"] = round(c.state_count() / dt, 1)
            out[f"cpu_{tag}_unique"] = c.unique_state_count()
        except Exception as e:  # noqa: BLE001 - secondary configs never void
            out[f"cpu_{tag}_error"] = f"{type(e).__name__}: {e}"
    return out


def _capped(builder, target):
    return builder.target_states(target) if target else builder


def _bench_protocol():
    """(tag, model builder, unique-state cap or None=full) for the reference
    bench configs not already covered by the primary metrics."""
    from stateright_tpu.models.linearizable_register import abd_model
    from stateright_tpu.models.paxos import paxos_model
    from stateright_tpu.models.single_copy_register import single_copy_model
    from stateright_tpu.models.two_phase_commit import TwoPhaseSys
    from stateright_tpu.actor import Network

    return [
        ("2pc10", lambda: TwoPhaseSys(10), 30_000),
        ("paxos6", lambda: paxos_model(6), 20_000),
        ("singlecopy4", lambda: single_copy_model(4, 1), 30_000),
        ("linreg2", lambda: abd_model(2, 2), None),  # full: 544 unique
        (
            "linreg3_ordered",
            lambda: abd_model(3, 2, Network.new_ordered()),
            10_000,
        ),
    ]


# ---------------------------------------------------------------------------
# Device phase (the ONE child process that initialises the device backend)
# ---------------------------------------------------------------------------


class _Skipped(Exception):
    """A secondary leg left out because the phase budget is nearly spent:
    recorded as ``*_skipped``, not as an ``*_error`` (which fails the
    run)."""


def _mark(stage: str) -> None:
    """Progress mark on stderr: when the parent kills the child at the
    deadline, the last mark pinpoints the stage that never returned."""
    sys.stderr.write(f"bench-tpu-stage: {stage}\n")
    sys.stderr.flush()


def _device_identity() -> dict:
    """The device the phase runs on, as JAX reports it — rides every
    emitted line and the details file."""
    import jax

    devs = jax.devices()
    return {
        "platform": devs[0].platform,
        "device_kind": devs[0].device_kind,
        "device_count": len(devs),
        "tpu_devices": [str(d) for d in devs],
    }


def _label_by_platform(out: dict) -> dict:
    """What leaves the device child.  The phase body writes its keys as
    ``tpu_*``; that name is only true on a TPU.  On any other backend
    (CI runs the harness on XLA:CPU) every such key is stored under the
    platform it was measured on instead — ``xlacpu_paxos3_states_per_sec``
    — so no consumer can read a CPU number as a chip number, whatever
    it does with ``platform``."""
    platform = out.get("platform")
    if platform == "tpu":
        return out
    pfx = f"xla{platform}_"
    return {
        "device_key_prefix": pfx[:-1],
        **{(pfx + k[4:] if k.startswith("tpu_") else k): v
           for k, v in out.items()},
    }


def _error_keys(d: dict) -> list:
    """Every failure a result dict records: the phase-level ``error`` and
    the per-leg ``*_error`` keys.  Non-empty means a non-zero exit."""
    return sorted(k for k in d if k == "error" or k.endswith("_error"))


def _persist(out: dict) -> None:
    """Append the cumulative result dict to the stage file.  The parent
    tails this file while the child runs and re-emits the merged JSON line
    after every milestone, so a deadline kill salvages every number that
    landed instead of only stderr marks."""
    path = os.environ.get("BENCH_STAGE_FILE")
    if not path:
        return
    try:
        with open(path, "a") as f:
            f.write(json.dumps(_label_by_platform(out)) + "\n")
            f.flush()
            os.fsync(f.fileno())
    except OSError:
        pass


def device_phase() -> dict:
    from stateright_tpu.models.paxos import paxos_model
    from stateright_tpu.models.two_phase_commit import TwoPhaseSys
    from stateright_tpu.parallel.prewarm import (
        enable_persistent_compile_cache,
    )

    t_start = time.monotonic()
    budget = float(os.environ.get("BENCH_TPU_TIMEOUT", "1200"))
    out: dict = {}
    device_phase.partial = out  # surfaced on mid-phase failure (see main)

    def _register(checker, leg: str, body=None) -> None:
        """Archive one completed leg into the persistent run registry
        (telemetry/registry.py) when STATERIGHT_TPU_RUN_DIR was set —
        EVERY leg bench runs gets an archived report + index record, so
        the on-chip A/B backlog reads as registry history instead of
        transcript archaeology.  ``body`` reuses a report the leg
        already built (the paxos-3/2pc-7 embeds) instead of
        reconstructing discovery paths a second time.  Never voids a
        measurement."""
        if not RUN_LEDGER_DIR:
            return
        try:
            from stateright_tpu.telemetry.registry import RunRegistry

            rec = RunRegistry(RUN_LEDGER_DIR).record(
                checker, leg=leg, body=body
            )
            out.setdefault("run_registry", {})[leg] = rec["run_id"]
        except Exception as e:  # noqa: BLE001 - the ledger must never
            # void the leg's number
            sys.stderr.write(
                f"bench: run-registry record failed for {leg}: "
                f"{type(e).__name__}: {e}\n"
            )

    phases: dict = {}  # per-phase wall breakdown (docs/perf.md)
    out["tpu_phases"] = phases
    _mark("backend-init (jax.devices)")
    t_init = time.monotonic()
    out.update(_device_identity())
    phases["backend_init_secs"] = round(time.monotonic() - t_init, 3)
    out["compile_cache_dir"] = enable_persistent_compile_cache(
        entry_point=True
    )
    _mark(f"backend-up: platform={out['platform']} "
          f"kind={out['device_kind']!r} count={out['device_count']}")
    if out["platform"] != "tpu":
        sys.stderr.write(
            f"bench: the device phase is running on platform="
            f"{out['platform']!r} — NOT a chip measurement; its keys are "
            f"stored as xla{out['platform']}_*, nothing is persisted\n"
        )
    _persist(out)

    # parity gate on device (capacity sized so no growth event interrupts).
    # "compile ..." marks delimit cold-compile windows in the stderr log.
    _mark("compile (paxos2 engine)")
    tpu_p2 = paxos_model(2).checker().spawn_tpu(sync=True, capacity=1 << 18)
    _mark("paxos2 parity done")
    if tpu_p2.unique_state_count() != PAXOS2_UNIQUE:
        raise AssertionError(
            f"tpu paxos2 unique {tpu_p2.unique_state_count()} != {PAXOS2_UNIQUE}"
        )
    out["tpu_paxos2_discoveries"] = sorted(tpu_p2.discoveries())
    _register(tpu_p2, "paxos2_parity")
    _persist(out)

    # PRIMARY METRIC NEXT: paxos check 3 — everything else is secondary and
    # must not be able to cost us this number.  Same model instance across
    # warm-up + timed run so the compiled-run cache on the tensor twin is
    # reused.
    target = os.environ.get("BENCH_TPU_TARGET", "")
    m3 = paxos_model(3)
    # the primary config's capacities (chip_smoke.py's leg B runs the same)
    caps = dict(capacity=1 << 23, queue_capacity=1 << 21, batch=4096,
                steps_per_call=512)

    def spawn3():
        # flight recorder on (stateright_tpu/telemetry/): host-side only,
        # <3% overhead contract (pinned in tests/test_telemetry.py), and
        # the per-step series is the artifact the perf round needs.
        # Cartography counters ride the step (<=5% pin, well inside the
        # regress tolerance): the headline number and the run report that
        # explains it come from the SAME run (docs/telemetry.md).  The
        # memory ledger (host arithmetic only) rides along too, so the
        # measurement arrives with its HBM footprint + growth forecast —
        # what regress.py --memory gates.
        b = m3.checker().telemetry(
            capacity=2048, cartography=True, memory=True, roofline=True
        )
        if target:
            b = b.target_states(int(target))
        return b.spawn_tpu(sync=True, **caps)

    _mark("compile (paxos3 engine)")
    t_warm = time.monotonic()
    spawn3()  # warm-up (compile)
    phases["paxos3_warmup_secs"] = round(time.monotonic() - t_warm, 3)
    _mark("paxos3 warm-up done")
    tpu_p3, dt = timed(spawn3)
    phases["paxos3_run_secs"] = round(dt, 3)
    _mark("paxos3 timed run done")
    if tpu_p3.flight_recorder is not None:
        summ3 = tpu_p3.flight_recorder.summary()
        # the cartography/memory blocks are embedded once as standalone
        # tpu_paxos3_cartography / tpu_paxos3_memory (the regress.py
        # contract keys) and once inside the self-contained report — not
        # a third time here
        summ3.pop("cartography", None)
        summ3.pop("memory", None)
        summ3.pop("roofline", None)  # standalone tpu_paxos3_roofline key
        out["tpu_paxos3_telemetry"] = summ3
        mem3 = tpu_p3.memory()
        if mem3 is not None:
            out["tpu_paxos3_memory"] = mem3
        # the roofline cost ledger (telemetry/roofline.py): the LIVE
        # block — static per-stage FLOPs/bytes + the XLA-reconciliation
        # verdict + achieved-vs-ceiling where a device spec is known —
        # what regress.py --roofline gates and what the MXU round
        # (docs/roofline.md) executes against
        roof3 = tpu_p3.roofline()
        if roof3 is not None:
            out["tpu_paxos3_roofline"] = roof3
        # the per-stage attribution (init-compile / rung-compile /
        # device-step / growth / host) of the TIMED run — the numbers the
        # >=1M states/s chase is driven by (docs/perf.md)
        stages = tpu_p3.flight_recorder.stages()
        if stages:
            out["tpu_paxos3_stages"] = stages
        compiles = tpu_p3.flight_recorder.records("compile")
        if compiles:
            out["tpu_paxos3_compile_events"] = [
                {k: c.get(k) for k in
                 ("rung", "source", "cache_hit", "duration", "cap")}
                for c in compiles
            ]
        # the embedded post-run report (telemetry/report.py): cartography
        # + deterministic health timeline — what regress.py --cartography
        # gates and what the on-chip measurement rounds read to interpret
        # their numbers
        try:
            from stateright_tpu.telemetry.report import build_report

            out["tpu_paxos3_report"] = build_report(tpu_p3)
        except Exception as e:  # noqa: BLE001 - report loss must not
            # void the measured number
            out["tpu_paxos3_report_error"] = f"{type(e).__name__}: {e}"
        cart3 = tpu_p3.cartography()
        if cart3 is not None:
            out["tpu_paxos3_cartography"] = cart3
    out["tpu_paxos3_states_per_sec"] = round(tpu_p3.state_count() / dt, 1)
    out["tpu_paxos3_states"] = tpu_p3.state_count()
    out["tpu_paxos3_unique"] = tpu_p3.unique_state_count()
    out["tpu_paxos3_sec"] = round(dt, 3)
    out["tpu_paxos3_discoveries"] = sorted(tpu_p3.discoveries())
    if target:
        out["tpu_paxos3_note"] = f"prefix run, target_states={target}"
    else:
        out["tpu_paxos3_note"] = (
            "FULL enumeration: the complete paxos-3 space, pinned by "
            "tests/test_paxos_tensor.py (slow tier) at 1,194,428 unique"
        )
    _register(tpu_p3, "paxos3", body=out.get("tpu_paxos3_report"))
    _persist(out)

    # flag-gated POR leg (BENCH_POR=1; docs/analysis.md "State-space
    # reduction"): the same paxos-3 prefix with partial-order reduction
    # requested.  The independence analysis conservatively marks the
    # slot-multiset paxos twin all-dependent (JX302), so this leg measures
    # the FALLBACK contract — identical counts, and the por_status block
    # records why no reduction applied.  On a model that does reduce, the
    # same keys carry the reduced-vs-full split.
    if os.environ.get("BENCH_POR", "") == "1":
        try:
            _mark("compile (paxos3 por engine)")
            b_por = m3.checker().por()
            if target:
                b_por = b_por.target_states(int(target))
            tpu_por, dt_por = timed(
                lambda: b_por.spawn_tpu(sync=True, **caps)
            )
            out["tpu_paxos3_por_states_per_sec"] = round(
                tpu_por.state_count() / dt_por, 1
            )
            out["tpu_paxos3_por_unique"] = tpu_por.unique_state_count()
            out["tpu_paxos3_por_sec"] = round(dt_por, 3)
            out["tpu_paxos3_por"] = tpu_por.por_status()
            if tpu_por.unique_state_count() != tpu_p3.unique_state_count():
                out["tpu_paxos3_por_note"] = (
                    "MISMATCH vs the full-expansion run — investigate"
                )
            _register(tpu_por, "paxos3_por")
            _mark("paxos3 por leg done")
        except Exception as e:  # noqa: BLE001 - the flag-gated leg must
            # never void the primary metric
            out["tpu_paxos3_por_error"] = f"{type(e).__name__}: {e}"
        _persist(out)

        # per-channel leg (same BENCH_POR flag): the encoding where POR
        # actually reduces (docs/analysis.md "Per-channel encoding").
        # paxos-2 is the largest bundled paxos the MECHANICAL compiler
        # covers — the 3-client closure exceeds the per-actor universe
        # cap — so the reduction keys measure the full paxos-2 space:
        # per-channel full expansion vs per-channel + por(), with
        # reduction_ratio = explored/full unique and verdict parity
        # asserted so a broken reduction can't report a win.
        try:
            _mark("compile (paxos2 per-channel engines)")
            pc_caps = dict(sync=True, capacity=1 << 16, batch=512)
            m2f = paxos_model(2, 3)
            m2f.per_channel_()
            tpu_pcf, dt_pcf = timed(
                lambda: m2f.checker().spawn_tpu(**pc_caps)
            )
            m2p = paxos_model(2, 3)
            m2p.per_channel_()
            tpu_pc, dt_pc = timed(
                lambda: m2p.checker().por().spawn_tpu(**pc_caps)
            )
            if sorted(tpu_pc.discoveries()) != sorted(tpu_pcf.discoveries()):
                raise AssertionError(
                    "per-channel por changed property discoveries: "
                    f"{sorted(tpu_pc.discoveries())} != "
                    f"{sorted(tpu_pcf.discoveries())}"
                )
            full_u = tpu_pcf.unique_state_count()
            por_u = tpu_pc.unique_state_count()
            out["tpu_paxos2_por_channel_states_per_sec"] = round(
                tpu_pc.state_count() / dt_pc, 1
            )
            out["tpu_paxos2_por_channel_unique"] = por_u
            out["tpu_paxos2_por_channel_full_unique"] = full_u
            out["tpu_paxos2_por_channel_sec"] = round(dt_pc, 3)
            out["tpu_paxos2_por_channel_full_sec"] = round(dt_pcf, 3)
            out["tpu_paxos2_por_channel_reduction_ratio"] = round(
                por_u / full_u, 4
            ) if full_u else None
            out["tpu_paxos2_por_channel"] = tpu_pc.por_status()
            _register(tpu_pcf, "paxos2_per_channel_full")
            _register(tpu_pc, "paxos2_per_channel_por")
            _mark("paxos2 per-channel por leg done")
        except Exception as e:  # noqa: BLE001 - same never-void rule
            out["tpu_paxos2_por_channel_error"] = f"{type(e).__name__}: {e}"
        _persist(out)

    # remaining parity gate + the driver metric's second config, 2pc check 4
    # AS WRITTEN (it is too small to rate-limit a TPU — ~2k unique states
    # finish in one engine call — so the rate mostly measures fixed per-run
    # overhead; 2pc7/2pc10 below give the throughput-representative number)
    _mark("compile (2pc5 engine)")
    tpu_t5 = TwoPhaseSys(5).checker().spawn_tpu(sync=True, capacity=1 << 17)
    _mark("2pc5 parity done")
    if tpu_t5.unique_state_count() != TPC5_UNIQUE:
        raise AssertionError(
            f"tpu 2pc5 unique {tpu_t5.unique_state_count()} != {TPC5_UNIQUE}"
        )
    out["tpu_2pc5_discoveries"] = sorted(tpu_t5.discoveries())
    _register(tpu_t5, "2pc5_parity")
    try:
        t4 = TwoPhaseSys(4)
        kw4 = dict(sync=True, capacity=1 << 15)
        t4.checker().spawn_tpu(**kw4)  # warm-up
        tpu_t4, dt4 = timed(lambda: t4.checker().spawn_tpu(**kw4))
        if tpu_t4.unique_state_count() != TPC4_UNIQUE:
            raise AssertionError(
                f"tpu 2pc4 unique {tpu_t4.unique_state_count()} != "
                f"{TPC4_UNIQUE}"
            )
        out["tpu_2pc4_states_per_sec"] = round(
            tpu_t4.state_count() / dt4, 1
        )
        out["tpu_2pc4_unique"] = tpu_t4.unique_state_count()
        out["tpu_2pc4_sec"] = round(dt4, 3)
        out["tpu_2pc4_note"] = (
            "full space; dominated by fixed per-run overhead at this size"
        )
        _register(tpu_t4, "2pc4")
        _mark("2pc4 done")
    except Exception as e:  # noqa: BLE001
        out["tpu_2pc4_error"] = f"{type(e).__name__}: {e}"
    _persist(out)

    # secondary: 2pc check 7; a failure here keeps the primary metric's
    # number (and fails the run's exit code), and the leg is skipped when
    # the phase budget is mostly spent (the parent kills the whole child
    # at the deadline, primary results and all)
    try:
        if time.monotonic() - t_start > 0.6 * budget:
            raise _Skipped("phase budget mostly spent; skipping 2pc7")
        t7 = TwoPhaseSys(7)
        # cand pre-sized for 2pc's ~9x fanout: growth would work but each
        # doubling recompiles the engine, wasting warm-up budget
        caps7 = dict(capacity=1 << 21, queue_capacity=1 << 19, batch=2048,
                     steps_per_call=256, cand=1 << 15)
        # warm-up must build the SAME engine as the timed run: cartography
        # changes the step program (and the engine cache key), so a plain
        # warm-up would leave the timed run paying the cold compile
        spawn7 = lambda: (  # noqa: E731
            t7.checker()
            .telemetry(capacity=2048, cartography=True, memory=True,
                       roofline=True)
            .spawn_tpu(sync=True, **caps7)
        )
        spawn7()  # warm-up
        tpu_t7, dt7 = timed(spawn7)
        if tpu_t7.flight_recorder is not None:
            # the 2pc7-vs-2pc10 table-size anomaly (--ab-table) is
            # diagnosed from exactly this series
            summ7 = tpu_t7.flight_recorder.summary()
            summ7.pop("cartography", None)  # embedded as the standalone
            # tpu_2pc7_cartography key and inside the report already
            summ7.pop("memory", None)  # same rule: standalone key below
            summ7.pop("roofline", None)  # same rule again
            out["tpu_2pc7_telemetry"] = summ7
            mem7 = tpu_t7.memory()
            if mem7 is not None:
                out["tpu_2pc7_memory"] = mem7
            roof7 = tpu_t7.roofline()
            if roof7 is not None:
                out["tpu_2pc7_roofline"] = roof7
            try:
                from stateright_tpu.telemetry.report import build_report

                out["tpu_2pc7_report"] = build_report(tpu_t7)
            except Exception as e:  # noqa: BLE001
                out["tpu_2pc7_report_error"] = f"{type(e).__name__}: {e}"
            cart7 = tpu_t7.cartography()
            if cart7 is not None:
                out["tpu_2pc7_cartography"] = cart7
        out["tpu_2pc7_states_per_sec"] = round(tpu_t7.state_count() / dt7, 1)
        out["tpu_2pc7_states"] = tpu_t7.state_count()
        out["tpu_2pc7_unique"] = tpu_t7.unique_state_count()
        out["tpu_2pc7_sec"] = round(dt7, 3)
        _register(tpu_t7, "2pc7", body=out.get("tpu_2pc7_report"))
        _mark("2pc7 done")
    except _Skipped as e:
        out["tpu_2pc7_skipped"] = str(e)
    except Exception as e:  # noqa: BLE001
        out["tpu_2pc7_error"] = f"{type(e).__name__}: {e}"
    _persist(out)

    # flag-gated SPILL leg (BENCH_SPILL=1; docs/spill.md): the same 2pc-7
    # under a SIMULATED device budget provably smaller than the run's
    # steady-state footprint — the ROADMAP's billion-state success
    # metric.  Counts must be bit-identical to the unconstrained leg;
    # the tpu_2pc7_spill block carries the per-tier byte breakdown.
    if os.environ.get("BENCH_SPILL", "") == "1":
        try:
            _mark("2pc7 spill leg")
            from stateright_tpu.parallel.tensor_model import twin_or_none
            from stateright_tpu.telemetry.memory import (
                ENV_DEVICE_BYTES,
                total_bytes,
                wavefront_specs,
            )

            t7s = TwoPhaseSys(7)
            twin = twin_or_none(t7s)
            n_props = len(list(t7s.properties()))
            batch7, qcap7, bloom7 = 2048, 1 << 19, 1 << 23
            sp_cfg = (bloom7, batch7 * twin.max_actions)

            def _tot(cap):
                return total_bytes(wavefront_specs(
                    twin, n_props, cap, qcap7, batch7, cartography=True,
                    spill=sp_cfg,
                ))

            # the unconstrained 2pc-7 run ends at a 1<<21 table; budget
            # the 1<<20 -> 1<<21 migration transient OUT so the hot tier
            # pins at 1<<20 (trigger 262,144 < the ~296k unique space)
            # and at least one eviction must fire for the run to finish
            budget = int(os.environ.get("BENCH_SPILL_BUDGET", 0)) or (
                _tot(1 << 20) + _tot(1 << 21) - 1
            )
            out["tpu_2pc7_spill_budget_bytes"] = budget
            prev = os.environ.get(ENV_DEVICE_BYTES)
            os.environ[ENV_DEVICE_BYTES] = str(budget)
            try:
                spawn7s = lambda: (  # noqa: E731
                    TwoPhaseSys(7).checker().spill()
                    .telemetry(capacity=2048, cartography=True, memory=True)
                    .spawn_tpu(
                        sync=True, capacity=1 << 19, queue_capacity=qcap7,
                        batch=batch7, steps_per_call=256, cand=1 << 15,
                        spill_bloom_bits=bloom7,
                    )
                )
                spawn7s()  # warm-up (same engine as the timed run)
                tpu_sp, dt_sp = timed(spawn7s)
            finally:
                if prev is None:
                    os.environ.pop(ENV_DEVICE_BYTES, None)
                else:
                    os.environ[ENV_DEVICE_BYTES] = prev
            out["tpu_2pc7_spill_states_per_sec"] = round(
                tpu_sp.state_count() / dt_sp, 1
            )
            out["tpu_2pc7_spill_unique"] = tpu_sp.unique_state_count()
            out["tpu_2pc7_spill_states"] = tpu_sp.state_count()
            out["tpu_2pc7_spill_sec"] = round(dt_sp, 3)
            out["tpu_2pc7_spill"] = tpu_sp.spill_status()
            if (
                "tpu_2pc7_unique" in out
                and tpu_sp.unique_state_count() != out["tpu_2pc7_unique"]
            ):
                out["tpu_2pc7_spill_note"] = (
                    "MISMATCH vs the unconstrained run — investigate"
                )
            _register(tpu_sp, "2pc7_spill")
            _mark("2pc7 spill leg done")
        except Exception as e:  # noqa: BLE001 - the flag-gated leg must
            # never void the primary metric
            out["tpu_2pc7_spill_error"] = f"{type(e).__name__}: {e}"
        _persist(out)

    # flag-gated MXU-recast legs (BENCH_MXU=1; docs/roofline.md
    # "Executing the hot-spot list"): the same paxos-3 and 2pc-7 configs
    # with CheckerBuilder.mxu() armed — expand-scatter coalescing and
    # the BLEST one-hot probe.  Count parity against
    # the unflagged legs is ASSERTED (a broken recast cannot report a
    # win), and each leg embeds its FLAGGED roofline block
    # (tpu_*_mxu_roofline) next to the same run's unflagged block —
    # exactly the before/after pair regress.py --mxu gates: paxos-3
    # expand charged bytes must drop >=30%, and 2pc-7's
    # dedup-insert stage must carry a dot-class op.
    if os.environ.get("BENCH_MXU", "") == "1":
        try:
            _mark("compile (paxos3 mxu engine)")

            def spawn3m():
                # the A/B must be FLAG-only: same telemetry set as the
                # unflagged leg (cartography rides the step program at
                # the <=5% pin — dropping it here would inflate the
                # recast's measured delta by the same magnitude)
                b = m3.checker().mxu().telemetry(
                    capacity=2048, cartography=True, memory=True,
                    roofline=True,
                )
                if target:
                    b = b.target_states(int(target))
                return b.spawn_tpu(sync=True, **caps)

            spawn3m()  # warm-up (compile)
            tpu_m3, dt_m3 = timed(spawn3m)
            if tpu_m3.unique_state_count() != tpu_p3.unique_state_count():
                raise AssertionError(
                    f"mxu paxos3 unique {tpu_m3.unique_state_count()} != "
                    f"{tpu_p3.unique_state_count()}"
                )
            out["tpu_paxos3_mxu_states_per_sec"] = round(
                tpu_m3.state_count() / dt_m3, 1
            )
            out["tpu_paxos3_mxu_unique"] = tpu_m3.unique_state_count()
            out["tpu_paxos3_mxu_sec"] = round(dt_m3, 3)
            roof_m3 = tpu_m3.roofline()
            if roof_m3 is not None:
                out["tpu_paxos3_mxu_roofline"] = roof_m3
            _register(tpu_m3, "paxos3_mxu")
            _mark("paxos3 mxu leg done")
        except Exception as e:  # noqa: BLE001 - the flag-gated leg must
            # never void the primary metric
            out["tpu_paxos3_mxu_error"] = f"{type(e).__name__}: {e}"
        _persist(out)
        try:
            _mark("compile (2pc7 mxu engine)")
            caps7m = dict(
                capacity=1 << 21, queue_capacity=1 << 19, batch=2048,
                steps_per_call=256, cand=1 << 15,
            )
            # flag-only A/B: telemetry set mirrors the unflagged leg
            spawn7m = lambda: (  # noqa: E731
                TwoPhaseSys(7).checker().mxu()
                .telemetry(capacity=2048, cartography=True, memory=True,
                           roofline=True)
                .spawn_tpu(sync=True, **caps7m)
            )
            spawn7m()  # warm-up
            tpu_m7, dt_m7 = timed(spawn7m)
            if (
                "tpu_2pc7_unique" in out
                and tpu_m7.unique_state_count() != out["tpu_2pc7_unique"]
            ):
                raise AssertionError(
                    f"mxu 2pc7 unique {tpu_m7.unique_state_count()} != "
                    f"{out['tpu_2pc7_unique']}"
                )
            out["tpu_2pc7_mxu_states_per_sec"] = round(
                tpu_m7.state_count() / dt_m7, 1
            )
            out["tpu_2pc7_mxu_unique"] = tpu_m7.unique_state_count()
            out["tpu_2pc7_mxu_sec"] = round(dt_m7, 3)
            roof_m7 = tpu_m7.roofline()
            if roof_m7 is not None:
                out["tpu_2pc7_mxu_roofline"] = roof_m7
            _register(tpu_m7, "2pc7_mxu")
            _mark("2pc7 mxu leg done")
        except Exception as e:  # noqa: BLE001 - same never-void rule
            out["tpu_2pc7_mxu_error"] = f"{type(e).__name__}: {e}"
        _persist(out)

    # flag-gated SWEEP leg (BENCH_SWEEP=1; docs/sweep.md): the paxos
    # default family (alternating lossy/non-lossy single-client
    # instances) checked as ONE hyper-batched sweep versus the same
    # instances run sequentially.  Per-instance count parity is
    # ASSERTED (a sweep that drifts cannot report a win), the engine
    # compile count must equal the cohort count (the amortization the
    # mode exists for: C compiles for N instances), and the aggregate
    # throughput pair (tpu_sweep_states_per_sec vs
    # tpu_sweep_sequential_states_per_sec) is the A/B the chip decides.
    if os.environ.get("BENCH_SWEEP", "") == "1":
        try:
            from stateright_tpu.models.paxos import sweep_family

            n_sw = int(os.environ.get("BENCH_SWEEP_N", "8") or 8)
            _mark("compile (sweep cohorts)")
            spec = sweep_family(n_sw)
            caps_sw = dict(
                capacity=1 << 15, batch=1024, steps_per_call=64,
            )

            def spawn_sw():
                # the A/B must be FLAG-only (the BENCH_MXU rule): same
                # telemetry set as the sequential legs below, and the
                # per-instance registry archive happens OUTSIDE the
                # timed window — report building walks discovery paths
                # and must not bias the sweep side
                b = spec.instances[0].model.checker().telemetry(
                    capacity=2048
                ).sweep(spec)
                return b.spawn_tpu(sync=True, **caps_sw)

            sw, dt_sw = timed(spawn_sw)
            sw.join()
            # sequential oracle: the SAME family, fresh models (fresh
            # twins — each pays its own engine compile, which is the
            # point), same engine knobs, same telemetry set
            seq_spec = sweep_family(n_sw)
            t_seq = time.monotonic()
            seq_counts = {}
            for inst in seq_spec.instances:
                c1 = inst.model.checker().telemetry(
                    capacity=2048
                ).spawn_tpu(sync=True, **caps_sw)
                seq_counts[inst.key] = (
                    c1.unique_state_count(), c1.state_count(),
                )
            dt_seq = time.monotonic() - t_seq
            if RUN_LEDGER_DIR:
                # archive per-instance records AFTER both timed windows
                sw._run_dir = RUN_LEDGER_DIR
                sw._maybe_record_run()
            mismatches = [
                k for k in seq_counts
                if (sw.results[k].unique, sw.results[k].states)
                != seq_counts[k]
            ]
            if mismatches:
                raise AssertionError(
                    f"sweep-vs-sequential count drift: {mismatches}"
                )
            total_states = sw.state_count()
            out["tpu_sweep_states_per_sec"] = round(
                total_states / dt_sw, 1
            )
            out["tpu_sweep_sequential_states_per_sec"] = round(
                total_states / dt_seq, 1
            )
            out["tpu_sweep"] = {
                "instances": len(spec.instances),
                "cohorts": len(sw.cohorts),
                "engine_compiles": int(sw.engine_compiles),
                "sequential_engine_compiles": len(seq_spec.instances),
                "unique": sw.unique_state_count(),
                "states": total_states,
                "sec": round(dt_sw, 3),
                "sequential_sec": round(dt_seq, 3),
                "parity": "IDENTICAL",
                "per_instance": {
                    k: {"unique": int(sw.results[k].unique),
                        "states": int(sw.results[k].states)}
                    for k in seq_counts
                },
            }
            if RUN_LEDGER_DIR:
                out.setdefault("run_registry", {})["sweep"] = sw.run_id
            _mark("sweep leg done")
        except Exception as e:  # noqa: BLE001 - the flag-gated leg must
            # never void the primary metric
            out["tpu_sweep_error"] = f"{type(e).__name__}: {e}"
        _persist(out)

    # flag-gated FLEET leg (BENCH_FLEET=1; docs/fleet.md): a small
    # multi-tenant job mix (three packable 2pc-3 tenants + a 2pc-4
    # singleton) scheduled over a BENCH_FLEET_SLOTS pool versus the same
    # jobs run one at a time.  Per-job count parity vs the solo runs is
    # ASSERTED (a scheduler that drifts cannot report a win), the packed
    # cohort must compile strictly fewer engines than jobs, and the
    # aggregate-throughput pair (tpu_fleet_states_per_sec vs
    # tpu_fleet_sequential_states_per_sec) is the serving metric.
    if os.environ.get("BENCH_FLEET", "") == "1":
        try:
            from stateright_tpu.checker.base import CheckerBuilder
            from stateright_tpu.fleet import COMPLETED as _FLEET_DONE
            from stateright_tpu.fleet import FleetSpec, Job, run_fleet
            from stateright_tpu.models.two_phase_commit import TwoPhaseSys

            slots_fl = int(os.environ.get("BENCH_FLEET_SLOTS", "2") or 2)

            def job_fl(key, n, packable):
                return Job(
                    key=key, packable=packable, capacity=1 << 13,
                    batch=256,
                    build=lambda n=n: CheckerBuilder(
                        TwoPhaseSys(n)
                    ).telemetry(capacity=2048),
                )

            jobs_fl = [
                job_fl("2pc3-a", 3, True), job_fl("2pc3-b", 3, True),
                job_fl("2pc3-c", 3, True), job_fl("2pc4", 4, False),
            ]
            _mark("fleet leg (pool run)")
            t_fl = time.monotonic()
            fl = run_fleet(
                FleetSpec(jobs=jobs_fl, slots=slots_fl), stream=None
            )
            dt_fl = time.monotonic() - t_fl
            # solo oracle: the SAME jobs one at a time, fresh builders,
            # same engine knobs — each pays its own compile, which is
            # exactly the overhead cohort packing amortizes
            t_fseq = time.monotonic()
            seq_fl = {}
            for j in jobs_fl:
                c1 = j.build().spawn_tpu(sync=True, **j.engine_kw())
                seq_fl[j.key] = (
                    c1.unique_state_count(), c1.state_count(),
                )
            dt_fseq = time.monotonic() - t_fseq
            bad = [
                k for k in seq_fl
                if fl[k].status != _FLEET_DONE
                or (fl[k].unique, fl[k].states) != seq_fl[k]
            ]
            if bad:
                raise AssertionError(f"fleet-vs-solo count drift: {bad}")
            total_fl = sum(r.states or 0 for r in fl.results.values())
            out["tpu_fleet_states_per_sec"] = round(total_fl / dt_fl, 1)
            out["tpu_fleet_sequential_states_per_sec"] = round(
                total_fl / dt_fseq, 1
            )
            out["tpu_fleet"] = {
                "jobs": len(jobs_fl),
                "slots": int(fl.slots),
                "completed": int(fl.completed),
                "preemptions": int(fl.preemptions),
                "engine_compiles": int(fl.engine_compiles),
                "sequential_engine_compiles": len(jobs_fl),
                "packed": sum(len(p["jobs"]) for p in fl.packed),
                "states": int(total_fl),
                "sec": round(dt_fl, 3),
                "sequential_sec": round(dt_fseq, 3),
                "parity": "IDENTICAL",
            }
            _mark("fleet leg done")
        except Exception as e:  # noqa: BLE001 - same never-void rule
            out["tpu_fleet_error"] = f"{type(e).__name__}: {e}"
        _persist(out)

    # flag-gated MESH leg (BENCH_MESH=1; docs/mesh.md): the GSPMD
    # mesh engine vs the single-device wavefront on the same 2pc
    # instance.  Count parity vs the solo run is ASSERTED (a
    # partitioning that drifts cannot report a win), and the block
    # carries the per-shard load vector, the imbalance summary, and the
    # routed-state total NEXT TO the throughput pair — GPUexplore's
    # scalability study names routing imbalance as what breaks at
    # scale, so the A/B ships with its own scalability readout.
    if os.environ.get("BENCH_MESH", "") == "1":
        try:
            from stateright_tpu.checker.base import CheckerBuilder
            from stateright_tpu.models.two_phase_commit import TwoPhaseSys

            n_me = int(os.environ.get("BENCH_MESH_RMS", "5") or 5)

            def build_me():
                return CheckerBuilder(TwoPhaseSys(n_me)).spawn_tpu(
                    sync=True, capacity=1 << 15, batch=256,
                )

            _mark("mesh leg (mesh run)")
            t_me = time.monotonic()
            cm = CheckerBuilder(TwoPhaseSys(n_me)).mesh().spawn_tpu(
                sync=True, capacity=1 << 15, batch=256,
            )
            dt_me = time.monotonic() - t_me
            _mark("mesh leg (solo oracle)")
            t_ms = time.monotonic()
            cs = build_me()
            dt_ms = time.monotonic() - t_ms
            pair_m = (cm.unique_state_count(), cm.state_count())
            pair_s = (cs.unique_state_count(), cs.state_count())
            if pair_m != pair_s:
                raise AssertionError(
                    f"mesh-vs-solo count drift: {pair_m} != {pair_s}"
                )
            stats_me = cm.mesh_stats()
            out["tpu_mesh_states_per_sec"] = round(pair_m[1] / dt_me, 1)
            out["tpu_mesh_solo_states_per_sec"] = round(
                pair_s[1] / dt_ms, 1
            )
            out["tpu_mesh"] = {
                "model": f"2pc-{n_me}",
                "devices": int(stats_me["devices"]),
                "unique": int(pair_m[0]),
                "states": int(pair_m[1]),
                "shard_load": stats_me["shard_load"],
                "imbalance": stats_me["imbalance"],
                "routed_states": int(stats_me["routed_states"]),
                "sec": round(dt_me, 3),
                "solo_sec": round(dt_ms, 3),
                "parity": "IDENTICAL",
            }
            _mark("mesh leg done")
        except Exception as e:  # noqa: BLE001 - same never-void rule
            out["tpu_mesh_error"] = f"{type(e).__name__}: {e}"
        _persist(out)

    # flag-gated LIVE leg (BENCH_LIVE=1; docs/observability.md): paxos-3
    # with plain telemetry (base) vs telemetry + metrics bus + armed
    # progress heartbeat (live).  Count parity vs the base run is
    # ASSERTED (the bus and heartbeat sample host syncs that already
    # happen; instrumentation that changes counts broke the
    # zero-overhead contract outright), and the block carries the
    # measured overhead fraction next to the published family list and
    # the terminal heartbeat — what regress.py --live gates.
    if os.environ.get("BENCH_LIVE", "") == "1":
        import shutil
        import tempfile

        try:
            from stateright_tpu.checkpoint import read_progress
            from stateright_tpu.models.paxos import paxos_model
            from stateright_tpu.telemetry.metrics import (
                default_bus,
                reset_default_bus,
            )

            m_lv = paxos_model(3)
            kw_lv = dict(sync=True, capacity=1 << 18,
                         queue_capacity=1 << 16, batch=1024,
                         steps_per_call=64)

            def run_base():
                return m_lv.checker().telemetry(capacity=2048).spawn_tpu(
                    **kw_lv
                )

            _mark("live leg (warm-up)")
            run_base()  # warm-up (compile; cache shared with both runs)
            _mark("live leg (base run)")
            t_lb = time.monotonic()
            cb = run_base()
            dt_lb = time.monotonic() - t_lb
            hb_dir = tempfile.mkdtemp(prefix="bench-live-")
            try:
                reset_default_bus()
                _mark("live leg (instrumented run)")
                t_lv = time.monotonic()
                # every_secs high enough that no snapshot generation is
                # ever due: the leg measures bus sampling + heartbeat
                # writes, not checkpoint serialization (the autosave arm
                # is what arms the heartbeat)
                cl = (
                    m_lv.checker()
                    .telemetry(capacity=2048, metrics=True)
                    .autosave(hb_dir, every_secs=3600.0)
                    .spawn_tpu(**kw_lv)
                )
                dt_lv = time.monotonic() - t_lv
                pair_b = (cb.unique_state_count(), cb.state_count())
                pair_l = (cl.unique_state_count(), cl.state_count())
                if pair_b != pair_l:
                    raise AssertionError(
                        f"live-vs-base count drift: {pair_l} != {pair_b}"
                    )
                hb = read_progress(hb_dir) or {}
                out["tpu_live"] = {
                    "model": "paxos-3",
                    "unique": int(pair_l[0]),
                    "states": int(pair_l[1]),
                    "parity": "IDENTICAL",
                    "base_sec": round(dt_lb, 3),
                    "live_sec": round(dt_lv, 3),
                    "overhead_frac": round(
                        max(dt_lv - dt_lb, 0.0) / max(dt_lb, 1e-9), 3
                    ),
                    "families": default_bus().families(),
                    "heartbeat": {
                        k: hb.get(k)
                        for k in ("verdict", "status", "states",
                                  "unique", "steps")
                    },
                }
            finally:
                shutil.rmtree(hb_dir, ignore_errors=True)
            _mark("live leg done")
        except Exception as e:  # noqa: BLE001 - same never-void rule
            out["tpu_live_error"] = f"{type(e).__name__}: {e}"
        _persist(out)

    # reference bench protocol on device.  All five configs compile — the
    # actor compiler gained ordered-FIFO network support in round 2
    # (parallel/actor_compiler.py), so lin-reg-3-ordered runs on device too
    # (pinned by tests/test_network_matrix.py); a failure on any config is
    # recorded per-tag next to the numbers that landed.  Device runs use
    # 10x the CPU prefix target: a CPU-sized prefix finishes in well under
    # a second on a device and the measured "rate" is mostly fixed
    # overhead (host syncs, growth rehashes), not engine throughput —
    # states/sec is rate-like, so a longer prefix measures it more fairly.
    for tag, build, target in _bench_protocol():
        try:
            if time.monotonic() - t_start > 0.75 * budget:
                raise _Skipped("phase budget mostly spent")
            mm = build()
            target = target * 10 if target else None
            kw = dict(sync=True, capacity=1 << 23, queue_capacity=1 << 21,
                      batch=2048, steps_per_call=256, cand=1 << 15)
            _capped(mm.checker(), target).spawn_tpu(**kw)  # warm-up
            c, dt = timed(
                lambda: _capped(mm.checker(), target).spawn_tpu(**kw)
            )
            from stateright_tpu.parallel._base import SMALL_SPACE_BREAK_EVEN

            out[f"tpu_{tag}_states_per_sec"] = round(c.state_count() / dt, 1)
            out[f"tpu_{tag}_unique"] = c.unique_state_count()
            if c.unique_state_count() < SMALL_SPACE_BREAK_EVEN:
                # the small-space footgun, disclosed per config: below the
                # break-even the measured "rate" is fixed per-run overhead
                # and CPU BFS is faster — spawn_auto() picks CPU here
                out[f"tpu_{tag}_note"] = (
                    "overhead-dominated small space; spawn_auto() selects "
                    "the CPU engine for this config"
                )
            _register(c, tag)
            _mark(f"{tag} done")
        except _Skipped as e:
            out[f"tpu_{tag}_skipped"] = str(e)
        except Exception as e:  # noqa: BLE001
            out[f"tpu_{tag}_error"] = f"{type(e).__name__}: {e}"
        _persist(out)

    return out


def _salvage(stage_path: str) -> dict:
    """Last cumulative result dict the child persisted, if any."""
    try:
        with open(stage_path) as f:
            lines = [l for l in f.read().splitlines() if l.strip()]
        for line in reversed(lines):
            try:
                return json.loads(line)
            except json.JSONDecodeError:
                continue
    except OSError:
        pass
    return {}


def _term_then_kill(proc, grace: float = 5.0) -> None:
    """SIGTERM + grace before SIGKILL at the deadline: a clean exit lets
    the child release the chip and flush its buffers."""
    proc.terminate()
    try:
        proc.wait(timeout=grace)
    except subprocess.TimeoutExpired:
        proc.kill()
        proc.wait()


def run_device_child(timeout_s: float) -> tuple:
    """Run ``device_phase`` in THE device child — one process, one
    attempt — and return ``(results, exit_code)``.  The parent never
    initialises the device backend itself (a chip belongs to one process
    at a time), so a backend failure lands here as the child's exit code
    and traceback, not as a retry.  Child stderr goes to a temp file (not
    a pipe) so the staged progress marks survive a deadline kill.  The
    child persists cumulative results to a stage file after every
    milestone; the parent polls it and RE-EMITS the merged JSON line, so
    the artifact grows with the run and a kill salvages every number
    that landed."""
    import tempfile

    stage_fd, stage_path = tempfile.mkstemp(suffix=".bench-stages")
    os.close(stage_fd)
    # the child's internal skip gates (0.6/0.75 * budget) must see the
    # ACTUAL window, not the BENCH_TPU_TIMEOUT default — else under a
    # tight deadline the child never skips secondaries and is killed
    # mid-run instead of returning cleanly
    env = dict(
        os.environ,
        BENCH_STAGE_FILE=stage_path,
        BENCH_TPU_TIMEOUT=str(int(timeout_s)),
    )
    # re-inject the run-ledger root the parent's main() consumed: the
    # child registers legs explicitly (its own main() consumes it again)
    if RUN_LEDGER_DIR:
        env["STATERIGHT_TPU_RUN_DIR"] = RUN_LEDGER_DIR
    try:
        with tempfile.TemporaryFile(mode="w+", errors="replace") as errf:
            return _wait_device_child(timeout_s, stage_path, env, errf)
    finally:
        try:
            os.unlink(stage_path)
        except OSError:
            pass


def _wait_device_child(timeout_s: float, stage_path: str, env: dict,
                       errf) -> tuple:
    proc = subprocess.Popen(
        [sys.executable, os.path.abspath(__file__), "--device-child"],
        stdout=subprocess.PIPE,
        stderr=errf,
        text=True,
        cwd=_HERE,
        env=env,
    )

    def err_lines() -> list:
        # os.pread: the child writes through the same file description,
        # so seeking the shared offset mid-run would corrupt its output
        size = os.fstat(errf.fileno()).st_size
        data = os.pread(errf.fileno(), size, 0).decode(errors="replace")
        return data.strip().splitlines()

    def last_stage() -> str:
        stage = ""
        for line in err_lines():
            if line.startswith("bench-tpu-stage:"):
                stage = line.split(":", 1)[1].strip()
        return stage

    deadline = time.monotonic() + timeout_s
    while True:
        try:
            stdout, _ = proc.communicate(timeout=5)
            break
        except subprocess.TimeoutExpired:
            # live-emit whatever milestones the child has persisted
            salv = _salvage(stage_path)
            if salv:
                emit(**salv)
            if time.monotonic() > deadline:
                _term_then_kill(proc)
                res = _salvage(stage_path)
                res.update(
                    error=f"device phase timed out after {timeout_s:.0f}s "
                          f"(stage: {last_stage() or 'unknown'})",
                    tpu_trace_tail=err_lines()[-8:],
                )
                return res, 124
    sys.stderr.write("\n".join(err_lines()[-40:]) + "\n")
    for line in reversed(stdout.strip().splitlines()):
        try:
            return json.loads(line), proc.returncode
        except json.JSONDecodeError:
            continue
    res = _salvage(stage_path)
    res.update(
        error=f"device phase exited rc={proc.returncode} without JSON",
        tpu_trace_tail=err_lines()[-8:] or stdout.strip().splitlines()[-8:],
    )
    return res, proc.returncode or 1


def _ab_run_one(rm: int, capacity: int, target) -> dict:
    """One A/B leg: a warm (compile pre-paid) timed 2pc run at the FIXED
    table capacity, with telemetry so the verdict carries occupancy and
    the per-stage breakdown."""
    from stateright_tpu.models.two_phase_commit import TwoPhaseSys

    m = TwoPhaseSys(rm)
    caps = dict(sync=True, capacity=capacity, queue_capacity=capacity >> 2,
                batch=2048, steps_per_call=256, cand=1 << 15)

    def spawn():
        b = m.checker().telemetry(capacity=2048, occupancy_every=8)
        if target:
            b = b.target_states(int(target))
        return b.spawn_tpu(**caps)

    spawn()  # warm-up: same model instance, so the engine cache carries
    c, dt = timed(spawn)
    rec = c.flight_recorder
    summ = rec.summary() if rec is not None else {}
    return {
        "states_per_sec": round(c.state_count() / dt, 1),
        "states": c.state_count(),
        "unique": c.unique_state_count(),
        "sec": round(dt, 3),
        "occupancy_last": summ.get("occupancy_last"),
        "stages": rec.stages() if rec is not None else None,
        "growth_events": summ.get("growth_events"),
    }


def ab_table(run_one=None, platform=None) -> int:
    """``bench.py --ab-table``: the 2pc7-vs-2pc10 same-table-size A/B
    (ROADMAP re-measure item; not measured on today's code).  Runs in
    THIS process, on whatever device JAX gives it — the line names the
    platform, and off a TPU its keys are stored as ``xla<platform>_*``
    like the device phase's.  Both configs run at the SAME fixed capacity
    (``BENCH_AB_CAPACITY``, default 2^23 slots) and the same insert volume
    (2pc10 targets 2pc7's unique count, or both take ``BENCH_AB_TARGET``),
    so any residual rate spread is table behavior, not volume.  Emits one
    compact JSON line; full legs go to the details side file."""
    cap = int(os.environ.get("BENCH_AB_CAPACITY", str(1 << 23)))
    target = os.environ.get("BENCH_AB_TARGET", "")
    run_one = run_one or (lambda rm, t: _ab_run_one(rm, cap, t))
    platform = platform or _device_identity()["platform"]
    out: dict = {"metric": "2pc7 vs 2pc10 same-table-size A/B",
                 "capacity": cap, "platform": platform}
    try:
        r7 = run_one(7, int(target) if target else None)
        # same insert volume for the bigger config: 2pc7's unique count
        r10 = run_one(10, int(target) if target else r7["unique"])
    except Exception as e:  # noqa: BLE001 - one JSON line either way
        out["error"] = f"{type(e).__name__}: {e}"
        print(json.dumps(out), flush=True)
        return 1
    out["tpu_2pc7_states_per_sec"] = r7["states_per_sec"]
    out["tpu_2pc7_unique"] = r7["unique"]
    out["tpu_2pc10_states_per_sec"] = r10["states_per_sec"]
    out["tpu_2pc10_unique"] = r10["unique"]
    if r10["states_per_sec"]:
        out["ratio_7_over_10"] = round(
            r7["states_per_sec"] / r10["states_per_sec"], 3
        )
    full = _label_by_platform(
        {**out, "tpu_2pc7_ab": r7, "tpu_2pc10_ab": r10}
    )
    out = _label_by_platform(out)
    base, ext = os.path.splitext(DETAILS_PATH)
    side = f"{base}-ab-table{ext or '.json'}"
    try:
        with open(side, "w") as f:
            json.dump(full, f, indent=1)
    except OSError as e:
        sys.stderr.write(f"bench: ab-table details unwritable: {e}\n")
    print(json.dumps(out), flush=True)
    return 0


def _confine_parent_to_cpu() -> None:
    """The parent must never hold the chip its child needs.  Pin jax to
    the CPU platform BEFORE any backend exists — an exception here (the
    update refused, or a backend already initialised) is a hard failure:
    carrying on would mean a parent that MIGHT own the device and a child
    that then fails or hangs on it."""
    import jax

    jax.config.update("jax_platforms", "cpu")
    if jax.default_backend() != "cpu":
        raise RuntimeError(
            f"bench parent initialised backend {jax.default_backend()!r}; "
            "it must stay on the CPU (the device belongs to the child)"
        )


def device_child_main() -> int:
    """``--device-child``: run the device phase in this process, print its
    (platform-labelled) results as one JSON line, exit non-zero on any
    failure — the phase raising, or any leg having recorded an error."""
    try:
        out = device_phase()
    except Exception as e:  # noqa: BLE001 - reported, then a non-zero exit
        tb = traceback.format_exc().strip().splitlines()
        # whatever sections completed before the failure still count
        out = getattr(device_phase, "partial", {})
        out.update({"error": f"{type(e).__name__}: {e}",
                    "tpu_trace_tail": tb[-6:]})
    out = _label_by_platform(out)
    print(json.dumps(out))
    return 1 if _error_keys(out) else 0


def main() -> int:
    # consume the run-ledger knob FIRST (parent, child, ab-table alike):
    # legs register explicitly via _register; an env knob left in place
    # would double-archive every leg through the checkers' join-time
    # auto-record (plus warm-ups/CPU runs as untagged noise)
    global RUN_LEDGER_DIR
    RUN_LEDGER_DIR = os.environ.pop("STATERIGHT_TPU_RUN_DIR", None)
    if "--ab-table" in sys.argv:
        return ab_table()
    if "--device-child" in sys.argv:
        return device_child_main()

    _confine_parent_to_cpu()
    try:
        # line 1: the artifact can never again be empty
        emit(**cpu_phase())
    except Exception as e:  # noqa: BLE001 - recorded; the exit is non-zero
        tb = traceback.format_exc().strip().splitlines()
        emit(cpu_phase_error=f"{type(e).__name__}: {e}",
             cpu_trace_tail=tb[-6:])

    budget = min(
        float(os.environ.get("BENCH_TPU_TIMEOUT", "1200")),
        max(remaining() - 30, 60),
    )
    extras, child_rc = run_device_child(budget)
    pfx = extras.get("device_key_prefix", "tpu")

    for w in ("paxos2", "2pc5"):
        cpu_d = EXTRAS.get(f"cpu_{w}_discoveries")
        dev_d = extras.get(f"{pfx}_{w}_discoveries")
        # both sides must exist: a cpu_phase crash leaves cpu_d None, which
        # is a CPU failure (already recorded as cpu_phase_error), not a
        # device correctness divergence
        if cpu_d is not None and dev_d is not None and cpu_d != dev_d:
            extras["error"] = (
                f"discovery parity failed on {w}: cpu={cpu_d} "
                f"{extras.get('platform')}={dev_d}"
            )
            emit(**extras)
            return 1

    if extras.get(f"{pfx}_paxos3_states_per_sec") is not None:
        extras.setdefault(
            "parity",
            "paxos check 2 (16668) + 2pc check 5 (8832) on CPU and "
            f"{extras.get('platform')}",
        )
    emit(**extras)
    errors = _error_keys(EXTRAS)
    if errors:
        sys.stderr.write(f"bench: failed: {errors}\n")
        return child_rc or 1
    if child_rc:
        return child_rc
    if extras.get(f"{pfx}_paxos3_states_per_sec") is None:
        return 1  # the primary metric never landed
    # a full error-free run on a TPU becomes the stored history
    record_validated()
    return 0


if __name__ == "__main__":
    try:
        sys.exit(main())
    except Exception as e:  # noqa: BLE001 - a final JSON line must still appear
        tb = traceback.format_exc().strip().splitlines()
        emit(error=f"{type(e).__name__}: {e}", trace_tail=tb[-6:])
        sys.exit(1)
