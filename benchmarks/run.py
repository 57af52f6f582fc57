"""The benchmark's one command.

    python3 benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process that holds the chip and starts no children.  It builds the
cell's model, makes one untimed warm-up check that walks every engine rung
the cell will use (set-up), then runs whole checks back to back — a closed
loop, one client — until ``--seconds`` have passed; the check in flight is
finished and counted.  A window that holds a check also starts no check
that its own shortest says would end past 1.5 x ``--seconds``
(``srbench/check.py:window_closes``; the ``window:`` line says
``closed_by=seconds`` or ``closed_by=overrun``), so a check over three
quarters of the window gets exactly one.  Every check is held to the
configuration's pins.
The workload file's ``loop.kind`` names the unit of work: ``closed`` (the
default) re-checks one model object, whose engines stay resident, and the
window may not ask the compiler for anything; ``cold`` makes every check —
warm-up, window, profiled — on a model object built inside the check's
timed span, so each pays the twin's compilation and the engines'
acquisition, every program served from the persistent cache; ``bounded`` is
``closed`` with a check that the workload's ``target_states`` verb stops —
a search that cannot exhaust in a window — held to what a PREFIX of a
breadth-first search owes (``srbench/check.py:compare_bounded``,
``bounded_prefix``), not to pins of the whole space.

The LAST stdout line is the result object (``correct``, ``attempted``,
``failed``, ``metrics``, ``device``, traced ``breakdown``, and last
``compared``: every number the run was held to beside its limit, which are
also the last lines on stderr); everything else is on earlier lines.
``--trace 0`` reports the cell's end-to-end
metrics from a plain builder; ``--trace 1`` turns the flight recorder on,
profiles one whole warm check with ``jax.profiler`` and reports the cell's
per-layer metrics, each through its own reader under ``layer_metrics/``.

There is no CPU result: without a TPU (or with fewer chips than the cell
asks for) the command exits non-zero before any work.  ``--rehearse-cpu``
debugs the harness itself in a sandbox: every line labelled, never a
result line, never exit code 0.
"""

import time

T_PROCESS_START = time.monotonic()  # set-up is counted from here

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import resource  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
CHECKOUT = os.path.dirname(BENCH_DIR)
sys.path.insert(0, BENCH_DIR)
sys.path.insert(0, CHECKOUT)

from srbench import check as chk  # noqa: E402
from srbench import compiles, peaks, reference, stats, xplane  # noqa: E402
from srbench.manifest import Manifest  # noqa: E402

ANNOTATION = "srbench_traced_check"
WALKS = 256  # random walks of the seeded exactness sample
KEPT = 16384  # states of the exactness sample under .symmetry()

_TAG = ""


def say(msg: str) -> None:
    print(f"{_TAG}{msg}", flush=True)


def die(msg: str, code: int = 1) -> "NoReturn":  # noqa: F821
    print(f"{_TAG}benchmarks/run.py: FAIL: {msg}", file=sys.stderr, flush=True)
    sys.exit(code)


def parse_args(argv):
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), required=True)
    p.add_argument("--rehearse-cpu", action="store_true",
                   help="debug the harness on the CPU: no result line")
    p.add_argument("--manifest", default=os.path.join(CHECKOUT, "BENCHMARK.json"))
    p.add_argument("--bench-dir", default=BENCH_DIR,
                   help="where workloads/ and layer_metrics/ live")
    return p.parse_args(argv)


def device_gate(chips: int, rehearsal: bool) -> dict:
    """Fail unless JAX's default backend is a TPU with enough chips."""
    import jax

    backend = jax.default_backend()
    if backend != "tpu" and not rehearsal:
        die(f"default backend is {backend!r}, not 'tpu': no accelerator, "
            "and the benchmark has no CPU result")
    devices = jax.devices()
    if len(devices) < chips:
        die(f"the cell asks for {chips} chips, JAX sees {len(devices)}")
    return {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }


def memory_peak_bytes(chips: int):
    """Peak bytes in use on the fullest of the chips the cell uses."""
    import jax

    peak = None
    for d in jax.devices()[:chips]:
        got = (d.memory_stats() or {}).get("peak_bytes_in_use")
        if got is not None:
            peak = got if peak is None else max(peak, got)
    return peak


def drop(result: dict) -> None:
    """Let go of a check's checker (and its device buffers) before the
    next spawn — and of its model object, which in the ``cold`` loop is the
    check's own, twin and engines with it: peak memory is one check's, not
    two."""
    result.pop("checker", None)
    result.pop("paths", None)
    result.pop("model", None)
    gc.collect()


class HostNoise:
    """What the host did to one check, for the per-check line: CPU seconds,
    page faults and context switches of this process (``getrusage``) and
    the garbage collector's passes.  Nothing here enters a metric; it is
    there so that a slow check can be told from a slow host."""

    def __init__(self):
        self.gc_s = 0.0
        self.gc_n = 0
        self._gc_t0 = None
        gc.callbacks.append(self._on_gc)

    def _on_gc(self, phase, info):
        if phase == "start":
            self._gc_t0 = time.monotonic()
        elif self._gc_t0 is not None:
            self.gc_s += time.monotonic() - self._gc_t0
            self.gc_n += 1
            self._gc_t0 = None

    def snapshot(self) -> tuple:
        ru = resource.getrusage(resource.RUSAGE_SELF)
        return (ru.ru_utime, ru.ru_stime, ru.ru_minflt, ru.ru_majflt,
                ru.ru_nvcsw, ru.ru_nivcsw, self.gc_s, self.gc_n)

    @staticmethod
    def line(before: tuple, after: tuple) -> str:
        d = [b - a for a, b in zip(before, after)]
        return (f"cpu user={d[0]:.3f}s sys={d[1]:.3f}s faults minor={d[2]} "
                f"major={d[3]} ctxsw vol={d[4]} invol={d[5]} "
                f"gc={d[6]:.3f}s/{d[7]}")


def exactness_sample(model, visited, seed: int, symmetric: bool) -> int:
    """How many states of the seeded sample the warm-up checker's visited
    set (``visited``) lacks.  The sample: every state on seeded random
    walks of the host object model; under ``.symmetry()``, where the set
    holds one representative a class as the search met them, a seeded draw
    from the states the plain reference's FIFO representative search keeps
    (``reference.kept_fingerprints``: a whole host search, so the caller
    takes that one once the window has closed, outside ``setup_s``)."""
    if symmetric:
        fps = reference.kept_fingerprints(model, seed, KEPT)
        drawn = f"symmetry kept<={KEPT}"
    else:
        fps = reference.random_walk_fingerprints(model, seed, WALKS)
        drawn = f"walks={WALKS}"
    missing = chk.missing_from(visited, fps)
    say(f"exactness sample: seed={seed} {drawn} states={len(fps)} "
        f"visited={len(visited)} missing={missing}")
    return missing


def reference_levels(pins: dict) -> int:
    """K: the run's own reference searches levels 0..K and every state of
    them is compared (``prefix_missing``).  ``pins.bounded.reference_levels``;
    absent, every pinned level - the sizes may be pinned deeper than a run
    can afford to search, since counts cost a run nothing."""
    return int(pins.get("reference_levels", len(pins["levels"]) - 1))


def bounded_sample(warm: dict, config: dict, seed: int, hold) -> dict:
    """Set-up's half of what a ``bounded`` run is held to once, on the
    warm-up check's ``checkpoint()``: the table holds what the check counted
    (``visited_off``); what the configuration pins lies inside what the
    prefix owes (``levels_beyond_complete``: the reference's levels 0..K
    inside the complete level C, each pinned discovery's witness state
    among the rows the check popped); the queue holds as many rows of each
    pinned level <= C as the reference has states there
    (``level_sizes_off``: down to the complete level a depth label IS the
    level); and the seeded walks (``sample_missing``).  Returns the prefix
    for the half that runs after the window (``bounded_reference``)."""
    pins = config["pins"]["bounded"]
    t0 = time.monotonic()
    prefix = chk.bounded_prefix(warm["checker"], bool(pins.get("graded")))
    held = len(prefix["visited"])
    level = prefix["complete_level"]
    searched = reference_levels(pins)
    say(f"bounded prefix: {held} occupied slots, head={prefix['head']} "
        f"tail={prefix['tail']}, complete to level {level} (max_depth "
        f"{warm['max_depth']}), pinned "
        f"levels 0..{len(pins['levels']) - 1}, the reference's 0..{searched}; "
        f"checkpoint + sort {time.monotonic() - t0:.3f}s")
    sizes = pins["levels"][:level + 1]
    labelled = (prefix["labels"] + [0] * len(sizes))[:len(sizes)]
    say(f"bounded prefix: rows by depth label {prefix['labels']}; levels "
        f"0..{len(sizes) - 1} hold {sum(sizes)} of the {held} states")
    beyond = sorted(
        name for name, at in pins["discoveries_by_level"].items()
        if not chk.witness_popped(warm["model"], prefix, name, at,
                                  pins.get("witnesses", {}).get(name)))
    say(f"bounded prefix: pinned discoveries {pins['discoveries_by_level']}, "
        f"witness not popped: {beyond}")
    found_all = len(warm["discoveries"]) == len(list(warm["model"].properties()))
    walks = reference.random_walks(warm["model"], seed, WALKS)
    owed, missing, deepest = chk.walks_missing(prefix, walks, not found_all)
    say(f"exactness sample: seed={seed} walks={WALKS} owed={owed} "
        f"deepest={deepest} missing={missing}")
    hold("bounded prefix", [
        ("visited_off", abs(held - warm["unique"]), 0,
         [f"{held} occupied slots, the check counted {warm['unique']}"]),
        ("levels_beyond_complete", max(0, searched - level) + len(beyond), 0,
         [f"the reference searches levels 0..{searched}, the prefix is "
          f"complete to level {level} only",
          f"pinned discoveries {beyond}: the witness path does not replay "
          "to a state that decides the property, or the check did not pop it"]),
        ("level_sizes_off", sum(abs(a - b) for a, b in zip(labelled, sizes)), 0,
         [f"the queue holds {labelled} rows of the pinned levels, the "
          f"reference {sizes} states"]),
        ("sample_missing", missing, 0,
         [f"{missing} of {owed} owed walk states not visited"]),
    ])
    return prefix


def bounded_reference(model, config: dict, prefix: dict, seed: int, hold) -> None:
    """After the window, outside ``setup_s``: EVERY state of the plain
    reference's first levels is in the table (``prefix_missing``), and the
    seeded soundness draw (``unreachable``)."""
    searched = reference_levels(config["pins"]["bounded"])
    t0 = time.monotonic()
    kept, levels = [], []
    reference.reference_bfs(model, max_level=searched, kept=kept, levels=levels)
    missing = chk.missing_from(
        prefix["visited"], [model.fingerprint_state(s) for s in kept])
    sizes = [n for n, _ in levels]
    t1 = time.monotonic()
    bad = chk.unreachable(model, prefix, seed)
    say(f"bounded reference: levels {sizes} = {len(kept)} states in "
        f"{t1 - t0:.3f}s, missing={missing}; soundness draw of "
        f"{min(chk.DRAWS, len(prefix['visited']))} slots in "
        f"{time.monotonic() - t1:.3f}s, unreachable={len(bad)}")
    hold("bounded reference", [
        ("prefix_missing", missing, 0,
         [f"{missing} of the reference's {len(kept)} states of levels "
          f"0..{len(sizes) - 1} not visited"]),
        ("unreachable", len(bad), 0, bad[:8]),
    ])


def label_gaps(gaps_ns, to_monotonic, markers, limit: int = 10) -> list:
    """Name each idle gap by what the host was doing: the latest marker
    (a flight-recorder record or one of the harness's own spans, all on
    ``time.monotonic``) at or before the gap's midpoint."""
    sums: dict = {}
    markers = sorted(markers)
    for g0, g1 in gaps_ns:
        mid = to_monotonic((g0 + g1) / 2.0)
        label = "before_spawn"
        for t, name in markers:
            if t > mid:
                break
            label = name
        sums[label] = sums.get(label, 0.0) + (g1 - g0) / 1e9
    return [[k, v] for k, v in sorted(sums.items(), key=lambda kv: -kv[1])][:limit]


def markers_of(result: dict) -> list:
    """(monotonic time, label) for a traced check's recorder records and
    the harness's spans."""
    out = [(t0, name) for name, t0, _ in result["spans"]]
    base = result.get("recorder_t0")
    if base is not None:
        for r in result.get("records", []):
            if r["kind"] in ("step", "growth", "compile"):
                out.append((base + r["t"], r["kind"]))
    return out


def profiled_check(make_model, workload, trace_dir: str) -> dict:
    """One whole warm check under ``jax.profiler``, bracketed by one
    TraceAnnotation stamped with ``time.monotonic`` to align the clocks."""
    import jax

    shutil.rmtree(trace_dir, ignore_errors=True)
    os.makedirs(trace_dir, exist_ok=True)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0  # host python frames: large, unread
    options.host_tracer_level = 2
    jax.profiler.start_trace(trace_dir, profiler_options=options)
    try:
        with jax.profiler.TraceAnnotation(ANNOTATION):
            result = chk.run_check(make_model, workload, telemetry=True)
    finally:
        jax.profiler.stop_trace()
    return result


def reduce_trace(trace_dir: str, result: dict, rehearsal: bool) -> dict:
    path = xplane.find_xplane(trace_dir)
    say(f"trace: {path} ({os.path.getsize(path)} bytes)")
    trace = xplane.load_trace(path, ANNOTATION, host_ops_as_device=rehearsal)
    note = trace["annotation"]
    if note is None:
        die(f"the trace holds no {ANNOTATION!r} annotation")
    _, n0, ndur = note
    reduced = xplane.reduce_events(trace["devices"], window=(n0, n0 + ndur))
    if not reduced:
        die("the traced check ran no operation on the device")

    def to_monotonic(ns: float) -> float:
        return result["t0"] + (ns - n0) / 1e9

    reduced["idle_gaps"] = label_gaps(
        reduced.pop("gaps"), to_monotonic, markers_of(result)
    )
    return reduced


def main(argv=None) -> int:
    global _TAG
    args = parse_args(argv)
    if args.rehearse_cpu:
        _TAG = "[CPU REHEARSAL - not a chip result] "
    manifest = Manifest(args.manifest, args.bench_dir)
    try:
        cell = manifest.cell(args.workload)
        config = manifest.config(cell["config"])
        workload = manifest.workload(cell["name"])
        kind = chk.loop_kind(workload)
    except (KeyError, OSError, ValueError) as e:
        die(str(e))
    chips = int(cell["chips"])
    dev = device_gate(chips, args.rehearse_cpu)
    try:
        from stateright_tpu.parallel.prewarm import enable_persistent_compile_cache
    except ImportError as e:
        die(f"the system under test is not importable from {CHECKOUT}: {e}")
    say(f"cell {cell['name']}: config={cell['config']} traffic={cell['traffic']} "
        f"chips={chips} seed={args.seed} seconds={args.seconds} trace={args.trace}"
        + ("" if kind == "closed" else f" loop={kind}"))
    say(f"device: {json.dumps(dev)}")

    compiles.install()
    cache_dir = enable_persistent_compile_cache(entry_point=True)
    say(f"compile cache: {cache_dir} ({len(os.listdir(cache_dir))} entries at start)")

    traced = bool(args.trace)
    compared: dict = {}  # name -> [the worst number of the run, its limit]
    failures: list = []

    def hold(where: str, rows: list) -> int:
        """Keep each compared number's worst, its messages where it is
        over its limit; returns how many are over."""
        over = 0
        for name, number, limit, messages in rows:
            worst = compared.setdefault(name, [number, limit])
            worst[0] = max(worst[0], number)
            if number > limit:
                over += 1
                failures.extend(f"{where}: {m}" for m in messages)
        return over

    # ``closed`` / ``bounded``: the one object every check is made on;
    # ``cold``: each check builds its own inside its timed span
    if kind == "cold":
        make_model = lambda: chk.build_model(config)  # noqa: E731
    else:
        model = chk.build_model(config)
        make_model = lambda: model  # noqa: E731
    symmetric = any(v["verb"] == "symmetry" for v in workload.get("builder", []))
    # bounded: the counts the warm-up check stopped at, set once it has
    # (``compare`` reads it when called; None holds the warm-up to nothing)
    first = None

    def compare(res: dict) -> list:
        """What one check is held to: the pins of the whole space, or -
        ``bounded`` - what a prefix owes."""
        if kind == "bounded":
            return chk.compare_bounded(res["model"], config, workload, res, first)
        return chk.compare(res["model"], config, workload, res)

    def hold_sample(model, visited) -> None:
        missing = exactness_sample(model, visited, args.seed, symmetric)
        hold("exactness sample", [("sample_missing", missing, 0,
                                   [f"{missing} reachable states not visited"])])

    # -- set-up: one untimed warm-up check walks every rung the cell uses ----
    t_w = time.monotonic()
    warm = chk.run_check(make_model, workload, telemetry=traced)
    say(f"warm-up check: {warm['check_s']:.3f}s unique={warm['unique']} "
        f"generated={warm['generated']} depth={warm['max_depth']} "
        f"discoveries={warm['discoveries']} growth_events={warm['growth_events']} "
        f"compiles={compiles.snapshot()}")
    hold("warm-up", compare(warm))
    prefix = visited = None
    if kind == "bounded":
        first = {"unique": warm["unique"], "generated": warm["generated"]}
        prefix = bounded_sample(warm, config, args.seed, hold)
    else:
        visited = chk.visited_fingerprints(warm["checker"])
        if visited is None:
            say("exactness sample skipped: checkpoint() exposes no table")
        elif not symmetric:
            hold_sample(warm["model"], visited)
    warm_records = warm.get("records", [])
    drop(warm)
    setup_compiles = compiles.snapshot()
    say(f"set-up so far {time.monotonic() - T_PROCESS_START:.3f}s "
        f"(warm-up phase {time.monotonic() - t_w:.3f}s)")

    # -- the measured window -------------------------------------------------
    trace_dir = os.path.join(manifest.root, ".bench_trace", cell["name"])
    checks, attempted, failed = [], 0, 0
    noise = HostNoise()
    asked = setup_compiles  # the compile counters as the last check left them
    t_first = time.monotonic()
    setup_s = t_first - T_PROCESS_START
    while True:
        attempted += 1
        before = noise.snapshot()
        try:
            res = chk.run_check(make_model, workload, telemetry=traced)
            bad = hold(f"check {attempted}", compare(res))
        except Exception as e:  # noqa: BLE001 - a check that raises is a failed check
            failed += 1
            failures.append(f"check {attempted} raised {type(e).__name__}: {e}")
        else:
            if bad:
                failed += 1
            t_last = res["t1"]
            drop(res)
            checks.append(res)
            spans = {name: b - a for name, a, b in res["spans"]}
            res["search_s"] = spans["spawn_join"]
            now = compiles.snapshot()
            res["compiles"], asked = compiles.delta(asked, now), now
            cold = "" if kind == "closed" else (
                f"build={spans['build_model']:.4f}s compile_requests="
                f"{res['compiles']['compile_requests']} (persistent misses "
                f"{res['compiles']['persistent_misses']}) ")
            say(f"check {attempted}: start=+{res['t0'] - t_first:.4f}s "
                f"{res['check_s']:.4f}s search={res['search_s']:.4f}s "
                f"reconstruct={spans['reconstruct']:.4f}s {cold}"
                f"drop={time.monotonic() - t_last:.4f}s; "
                f"{HostNoise.line(before, noise.snapshot())}")
        closed_by = chk.window_closes(time.monotonic() - t_first,
                                      [c["check_s"] for c in checks], args.seconds)
        if closed_by:
            break
    window = compiles.delta(setup_compiles, compiles.snapshot())
    # no fresh compile in any window; a closed window asks the compiler for
    # nothing at all, a cold one for the same programs in every check
    asks = sorted({c["compiles"]["compile_requests"] for c in checks})
    rows = [("window_persistent_misses", window["persistent_misses"], 0,
             [f"the persistent cache did not hold what the window asked for: {window}"])]
    if kind != "cold":
        rows.append(("window_compile_requests", window["compile_requests"], 0,
                     [f"the measured window compiled: {window}"]))
    elif checks:
        rows.append(("compile_requests_spread", asks[-1] - asks[0], 0,
                     [f"the checks asked for different numbers of programs: {asks}"]))
        rows.append(("checks_without_compile_requests", int(asks[0] == 0), 0,
                     ["a cold check asked the compiler for nothing: its "
                      "engines were not its own"]))
    hold("window", rows)
    if not checks:
        die("no check completed in the window: " + "; ".join(failures))

    times = [c["check_s"] for c in checks]
    check_s = stats.median(times)
    # the search alone (spawn -> join -> counts), one rate a check, median:
    # a slow check or a slow host between checks does not move it
    gen_rate = stats.median([c["generated"] / c["search_s"] for c in checks])
    # the same work as a mean over the wall, with what lies between checks
    wall_rate = sum(c["generated"] for c in checks) / (t_last - t_first)
    peak = memory_peak_bytes(chips)
    say(f"window: {len(checks)} checks in {t_last - t_first:.3f}s "
        f"closed_by={closed_by}; check_s "
        f"median={check_s:.4f} min={min(times):.4f} max={max(times):.4f} "
        f"over {len(times)} checks; gen_rate={gen_rate:.1f} states/s "
        f"(mean over the wall, not a metric: {wall_rate:.1f}); "
        f"peak_hbm={peak}; setup_s={setup_s:.3f}; growth_events per check="
        f"{sorted({c['growth_events'] for c in checks})}; window compiles={window}")
    measured = {"check_s": check_s, "gen_rate": gen_rate, "peak_hbm": peak,
                "setup_s": setup_s}
    dev["memory_peak_bytes"] = peak
    breakdown = None

    if not traced:
        entries = manifest.metrics_for("end_to_end", cell["name"])
        metrics = {
            m["name"]: {"value": measured[m["name"]], "unit": m["unit"]}
            for m in entries if measured.get(m["name"]) is not None
        }
    else:
        say(f"end-to-end numbers of this TRACED run (recorder on; compare "
            f"with a --trace 0 run for the tracing overhead): {json.dumps(measured)}")
        # one more whole check, after the window, under the profiler
        profiled = profiled_check(make_model, workload, trace_dir)
        hold("profiled check", compare(profiled))
        tensor = profiled["model"].tensor_model()
        drop(profiled)
        say(f"profiled check: {profiled['check_s']:.4f}s vs recorder-only "
            f"median {check_s:.4f}s")
        reduced = reduce_trace(trace_dir, profiled, args.rehearse_cpu)
        dev["busy_s"] = reduced["busy_s"]
        dev["window_s"] = reduced["window_s"]
        breakdown = {
            "device_ops": reduced["device_ops"],
            "idle_gaps": reduced["idle_gaps"],
        }
        ctx = {
            "cell": cell, "config": config, "workload": workload,
            # a bounded check has no pin of the whole space: the readers find
            # its own counts, ``unique`` being the rows it POPPED (what they
            # mean by it: every state popped once) - equal in every check
            "pins": config["pins"] if prefix is None else {
                **config["pins"], "unique": prefix["head"],
                "generated": profiled["generated"],
                "max_depth": profiled["max_depth"]},
            "row": {"width": int(tensor.width),
                    "max_actions": int(tensor.max_actions)},
            "warmup_records": warm_records,
            "checks": checks,
            "profiled": profiled,
            "trace": reduced,
            "compiles": compiles.snapshot(),
            "peaks": None if args.rehearse_cpu else peaks.peaks_for(dev["kind"]),
            "median": stats.median,
        }
        metrics = {}
        for m in manifest.metrics_for("per_layer", cell["name"]):
            value = manifest.reader_module(m["name"]).read(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        shutil.rmtree(trace_dir, ignore_errors=True)
    if symmetric and visited is not None:
        # the reference's whole search: after the window, not in setup_s
        hold_sample(make_model(), visited)
    if prefix is not None:
        # the reference's first levels and the soundness draw: likewise
        bounded_reference(make_model(), config, prefix, args.seed, hold)
    for f in failures:
        say(f"NOT CORRECT: {f}")
    ordered = {"correct": not failures, "attempted": attempted,
               "failed": failed, "metrics": metrics, "device": dev}
    if breakdown is not None:
        ordered["breakdown"] = breakdown
    # every number compared beside its limit: the line's last key, and the
    # last lines on stderr
    ordered["compared"] = {k: {"value": v, "limit": lim}
                           for k, (v, lim) in compared.items()}
    for k, (v, lim) in compared.items():
        print(f"{_TAG}compared: {k}={v} limit={lim}", file=sys.stderr, flush=True)
    if args.rehearse_cpu:
        say(f"rehearsal complete (no result line): {json.dumps(ordered)}")
        return 2
    print(json.dumps(ordered), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
