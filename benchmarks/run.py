"""The benchmark's one command.

    python3 benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process that holds the chip and starts no children.  It builds the
cell's model, makes one untimed warm-up check that walks every engine rung
the cell will use (set-up), then runs whole checks back to back — a closed
loop, one client — until ``--seconds`` have passed; the check in flight is
finished and counted.  Every check is held to the configuration's pins.

The LAST stdout line is the result object (``correct``, ``attempted``,
``failed``, ``metrics``, ``device`` and, traced, ``breakdown``); everything
else is on earlier lines.  ``--trace 0`` reports the cell's end-to-end
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
    next spawn: peak memory is one check's, not two."""
    result.pop("checker", None)
    result.pop("paths", None)
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


def exactness_sample(model, checker, seed: int) -> str:
    """Seeded random walks of the host object model: every state on them
    must be in the warm-up checker's visited set."""
    visited = chk.visited_fingerprints(checker)
    if visited is None:
        say("exactness sample skipped: checkpoint() exposes no table")
        return "skipped"
    fps = reference.random_walk_fingerprints(model, seed, WALKS)
    missing = chk.missing_from(visited, fps)
    say(f"exactness sample: seed={seed} walks={WALKS} states={len(fps)} "
        f"visited={len(visited)} missing={missing}")
    return "ok" if missing == 0 else f"{missing} reachable states not visited"


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


def profiled_check(model, workload, trace_dir: str) -> dict:
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
            result = chk.run_check(model, workload, telemetry=True)
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
    except (KeyError, OSError) as e:
        die(str(e))
    chips = int(cell["chips"])
    dev = device_gate(chips, args.rehearse_cpu)
    try:
        from stateright_tpu.parallel.prewarm import enable_persistent_compile_cache
    except ImportError as e:
        die(f"the system under test is not importable from {CHECKOUT}: {e}")
    say(f"cell {cell['name']}: config={cell['config']} traffic={cell['traffic']} "
        f"chips={chips} seed={args.seed} seconds={args.seconds} trace={args.trace}")
    say(f"device: {json.dumps(dev)}")

    compiles.install()
    cache_dir = enable_persistent_compile_cache(entry_point=True)
    say(f"compile cache: {cache_dir} ({len(os.listdir(cache_dir))} entries at start)")

    traced = bool(args.trace)
    model = chk.build_model(config)
    # -- set-up: one untimed warm-up check walks every rung the cell uses ----
    t_w = time.monotonic()
    warm = chk.run_check(model, workload, telemetry=traced)
    say(f"warm-up check: {warm['check_s']:.3f}s unique={warm['unique']} "
        f"generated={warm['generated']} depth={warm['max_depth']} "
        f"discoveries={warm['discoveries']} growth_events={warm['growth_events']} "
        f"compiles={compiles.snapshot()}")
    failures = [f"warm-up: {m}" for m in chk.pin_failures(model, config, workload, warm)]
    sample = exactness_sample(model, warm["checker"], args.seed)
    if sample not in ("ok", "skipped"):
        failures.append(f"exactness sample: {sample}")
    warm_records = warm.get("records", [])
    drop(warm)
    setup_compiles = compiles.snapshot()
    say(f"set-up so far {time.monotonic() - T_PROCESS_START:.3f}s "
        f"(warm-up phase {time.monotonic() - t_w:.3f}s)")

    # -- the measured window -------------------------------------------------
    trace_dir = os.path.join(manifest.root, ".bench_trace", cell["name"])
    checks, attempted, failed = [], 0, 0
    noise = HostNoise()
    t_first = time.monotonic()
    setup_s = t_first - T_PROCESS_START
    while True:
        attempted += 1
        before = noise.snapshot()
        try:
            res = chk.run_check(model, workload, telemetry=traced)
            bad = chk.pin_failures(model, config, workload, res)
        except Exception as e:  # noqa: BLE001 - a check that raises is a failed check
            failed += 1
            failures.append(f"check {attempted} raised {type(e).__name__}: {e}")
        else:
            if bad:
                failed += 1
                failures += [f"check {attempted}: {m}" for m in bad]
            t_last = res["t1"]
            drop(res)
            checks.append(res)
            spans = {name: b - a for name, a, b in res["spans"]}
            res["search_s"] = spans["spawn_join"]
            say(f"check {attempted}: start=+{res['t0'] - t_first:.4f}s "
                f"{res['check_s']:.4f}s search={res['search_s']:.4f}s "
                f"reconstruct={spans['reconstruct']:.4f}s "
                f"drop={time.monotonic() - t_last:.4f}s; "
                f"{HostNoise.line(before, noise.snapshot())}")
        if time.monotonic() - t_first >= args.seconds:
            break
    window = compiles.delta(setup_compiles, compiles.snapshot())
    if window["persistent_misses"] or window["compile_requests"]:
        failures.append(f"the measured window compiled: {window}")
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
    say(f"window: {len(checks)} checks in {t_last - t_first:.3f}s; check_s "
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
        # one more whole warm check, after the window, under the profiler
        profiled = profiled_check(model, workload, trace_dir)
        bad = chk.pin_failures(model, config, workload, profiled)
        failures += [f"profiled check: {m}" for m in bad]
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
        tensor = model.tensor_model()
        ctx = {
            "cell": cell, "config": config, "workload": workload,
            "pins": config["pins"],
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
    for f in failures:
        say(f"NOT CORRECT: {f}")
    ordered = {"correct": not failures, "attempted": attempted,
               "failed": failed, "metrics": metrics, "device": dev}
    if breakdown is not None:
        ordered["breakdown"] = breakdown
    if args.rehearse_cpu:
        say(f"rehearsal complete (no result line): {json.dumps(ordered)}")
        return 2
    print(json.dumps(ordered), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
