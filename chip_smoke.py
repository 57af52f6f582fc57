"""chip_smoke.py — the quickest proof that the checker still starts on the chip.

One process, no children.  Drives the main path
(``CheckerBuilder.spawn_tpu()`` -> ``parallel/wavefront.py:TpuChecker``)
through the public surface a user would call, on a real accelerator, and
checks every answer against the repo's own pins / a host BFS run:

  A  paxos-2 parity: device == host BFS == 16,668, {"value chosen"}
  B  paxos-3 at the bench capacities, to exhaustion, cold then warm:
     1,194,428 unique, {"value chosen"}, no "linearizable"
     counterexample, the discovery path replayed on the host, zero
     fresh backend compiles on the warm run, peak device memory
  C  2pc-5 from a 2^11 table: growth (since PR 48 where the carry lies:
     buckets split, queue window slid; to PR 47 carry -> host -> rehash ->
     re-upload) with buffer donation live, 8,832 unique
  D  compile-cache round trip in this process: in-memory caches
     dropped, A rerun from persistent-cache hits only

Exit code 0 only when every leg passed on an accelerator.  The last stdout
line is then exactly ``{"ok": true, "device": {"platform": ..., "kind": ...,
"count": ...}}`` (the driver parses it and admits no other key); the per-leg
counts/seconds and the cache hit counts are the ``summary:`` JSON line before
it.  There is no CPU default: without a TPU the script exits non-zero before
any leg runs.
``--rehearse-cpu`` is for debugging the script itself in a sandbox — tiny
sizes, every line labelled, never a result line, never exit code 0.

The compile cache lives where ``JAX_COMPILATION_CACHE_DIR`` says, else at
``<checkout>/.jax_cache`` (``prewarm.resolve_compile_cache_dir``).
"""

import importlib.metadata
import json
import os
import sys
import time

PAXOS2_UNIQUE = 16_668  # reference examples/paxos.rs:291
PAXOS3_UNIQUE = 1_194_428  # pinned: tests/test_paxos_tensor.py
TPC5_UNIQUE = 8_832  # reference examples/2pc.rs:133
# bench.py's primary-config capacities (tpu_phase): the size users run
PAXOS3_CAPS = dict(capacity=1 << 23, queue_capacity=1 << 21, batch=4096,
                   steps_per_call=512)

REHEARSAL = "--rehearse-cpu" in sys.argv[1:]
_TAG = "[CPU REHEARSAL - not a chip result] " if REHEARSAL else ""


def say(msg: str) -> None:
    print(f"{_TAG}{msg}", flush=True)


def fail(msg: str) -> "NoReturn":  # noqa: F821
    print(f"{_TAG}chip_smoke: FAIL: {msg}", file=sys.stderr, flush=True)
    sys.exit(1)


def check(cond: bool, msg: str) -> None:
    if not cond:
        fail(msg)


def device_gate() -> dict:
    """Fail unless JAX's default backend is a TPU; returns the identity."""
    import jax
    import jaxlib

    backend = jax.default_backend()
    if backend != "tpu" and not REHEARSAL:
        forced = os.environ.get("JAX_PLATFORMS")
        why = (
            f"JAX_PLATFORMS={forced!r} in the environment hides the chip"
            if forced and "tpu" not in forced.lower()
            else "JAX found no accelerator"
        )
        fail(f"default backend is {backend!r}, not 'tpu': {why}")
    d0 = jax.devices()[0]
    try:
        libtpu = importlib.metadata.version("libtpu")
    except importlib.metadata.PackageNotFoundError:
        libtpu = "not installed"
    dev = {
        "platform": d0.platform,
        "kind": d0.device_kind,
        "count": len(jax.devices()),
    }
    say(
        f"device: platform={dev['platform']} device_kind={dev['kind']!r} "
        f"count={dev['count']} | jax {jax.__version__} jaxlib "
        f"{jaxlib.__version__} libtpu {libtpu} | python "
        f"{sys.version.split()[0]}"
    )
    return dev


def compile_events(checker) -> list:
    return [
        {k: e.get(k) for k in ("rung", "source", "cache_hit", "duration")}
        for e in checker.flight_recorder.records("compile")
    ]


def leg_a(paxos_model) -> dict:
    """paxos-2 on the device == the same model on the host BFS."""
    t0 = time.monotonic()
    m = paxos_model(2)
    dev = m.checker().telemetry(capacity=256).spawn_tpu(
        sync=True, capacity=1 << 18
    )
    dev.join()
    dev.report()
    host = paxos_model(2).checker().spawn_bfs().join()
    found = sorted(dev.discoveries())
    check(
        dev.unique_state_count() == PAXOS2_UNIQUE,
        f"paxos-2 device unique {dev.unique_state_count()} != "
        f"{PAXOS2_UNIQUE}",
    )
    check(
        host.unique_state_count() == dev.unique_state_count(),
        f"paxos-2 host BFS unique {host.unique_state_count()} != device "
        f"{dev.unique_state_count()}",
    )
    check(found == ["value chosen"], f"paxos-2 discoveries {found}")
    check(
        sorted(host.discoveries()) == found,
        f"paxos-2 host discoveries {sorted(host.discoveries())} != {found}",
    )
    return {
        "unique": dev.unique_state_count(),
        "states": dev.state_count(),
        "sec": round(time.monotonic() - t0, 3),
        "compiles": compile_events(dev),
    }


def leg_b(paxos_model) -> dict:
    """paxos-3, bench capacities, exhaustion; cold then warm."""
    import jax

    from stateright_tpu.parallel.prewarm import CompileWatch

    m, caps, want = paxos_model(3), PAXOS3_CAPS, PAXOS3_UNIQUE
    if REHEARSAL:  # a CPU cannot enumerate paxos-3 inside a debug loop
        m, want = paxos_model(2), PAXOS2_UNIQUE
        caps = dict(capacity=1 << 14, queue_capacity=1 << 12, batch=256,
                    steps_per_call=16)

    def spawn():
        b = m.checker().telemetry(capacity=2048, memory=True)
        watch = CompileWatch().start()
        t0 = time.monotonic()
        c = b.spawn_tpu(sync=True, **caps)
        c.join()
        return c, time.monotonic() - t0, watch.delta()

    cold, cold_s, cold_d = spawn()
    say(f"leg B cold: {cold_s:.3f}s compile-events={compile_events(cold)}")
    say(f"leg B cold stages: {cold.flight_recorder.stages()}")
    say(f"leg B cold monitoring: {cold_d}")
    warm, warm_s, warm_d = spawn()
    say(f"leg B warm: {warm_s:.3f}s compile-events={compile_events(warm)}")
    say(f"leg B warm stages: {warm.flight_recorder.stages()}")
    say(f"leg B warm monitoring: {warm_d}")
    warm.report()
    for tag, c in (("cold", cold), ("warm", warm)):
        check(
            c.unique_state_count() == want,
            f"paxos-3 {tag} unique {c.unique_state_count()} != {want}",
        )
        found = sorted(c.discoveries())
        check(found == ["value chosen"], f"paxos-3 {tag} discoveries {found}")
    check(
        (cold.unique_state_count(), cold.state_count())
        == (warm.unique_state_count(), warm.state_count()),
        "paxos-3 cold and warm runs disagree",
    )
    # the fingerprint bridge held: the device's parent chain replays on
    # the host object model and ends in a state the property accepts
    path = warm.discovery("value chosen")
    prop = m.property_by_name("value chosen")
    check(
        prop.condition(m, path.last_state()),
        "replayed 'value chosen' path does not end in a chosen value",
    )
    check(
        warm.discovery("linearizable") is None,
        "paxos-3 reported a linearizability counterexample",
    )
    # warm = same model, engines in memory: nothing may reach the backend
    # compiler (a persistent-cache miss IS a fresh compile)
    check(
        warm_d["persistent_misses"] == 0
        and not any(e["source"] == "fresh" for e in compile_events(warm)),
        f"warm run compiled: {warm_d} {compile_events(warm)}",
    )
    stats = jax.devices()[0].memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")
    say(
        f"leg B: unique={warm.unique_state_count()} "
        f"generated={warm.state_count()} depth={warm.max_depth()} "
        f"path_len={len(path)} peak_bytes_in_use={peak} "
        f"smoke rate (not a benchmark): "
        f"{warm.state_count() / warm_s:.0f} generated states/s warm"
    )
    return {
        "unique": warm.unique_state_count(),
        "states": warm.state_count(),
        "depth": warm.max_depth(),
        "path_len": len(path),
        "cold_sec": round(cold_s, 3),
        "warm_sec": round(warm_s, 3),
        "cold_compile_sec": cold_d["compile_secs"],
        "peak_bytes_in_use": peak,
    }


def leg_c(TwoPhaseSys) -> dict:
    """2pc-5 from a 2^11 table: growth with donation live."""
    t0 = time.monotonic()
    c = TwoPhaseSys(5).checker().spawn_tpu(sync=True, capacity=1 << 11)
    c.join()
    c.report()
    found = sorted(c.discoveries())
    check(
        c.unique_state_count() == TPC5_UNIQUE,
        f"2pc-5 unique {c.unique_state_count()} != {TPC5_UNIQUE}",
    )
    check(
        found == ["abort agreement", "commit agreement"],
        f"2pc-5 discoveries {found}",
    )
    uniq = [u for _, u in c.growth_events]
    check(bool(uniq), "2pc-5 at capacity 2^11 never grew")
    check(
        uniq == sorted(uniq) and all(u > 0 for u in uniq),
        f"growth lost work: {c.growth_events}",
    )
    return {
        "unique": c.unique_state_count(),
        "states": c.state_count(),
        "growth_events": len(uniq),
        "sec": round(time.monotonic() - t0, 3),
    }


def leg_d(paxos_model) -> dict:
    """Persistent-cache round trip: cache-served executables + donation."""
    import jax

    jax.clear_caches()  # a fresh paxos_model(2) brings a fresh _run_cache
    out = leg_a(paxos_model)
    # the leg-A rerun re-acquires the SAME engine programs leg A compiled:
    # each must come off the disk, none from the compiler
    events = out["compiles"]
    check(bool(events), "leg D recorded no engine acquisition")
    check(
        all(e["cache_hit"] and e["source"] == "persistent" for e in events),
        f"leg D engine programs were not all persistent-cache hits: {events}",
    )
    out["persistent_hits"] = len(events)
    return out


def main() -> int:
    t_start = time.monotonic()
    dev = device_gate()
    try:
        from stateright_tpu import native
        from stateright_tpu.models.paxos import paxos_model
        from stateright_tpu.models.two_phase_commit import TwoPhaseSys
        from stateright_tpu.parallel.prewarm import (
            compile_counters,
            enable_persistent_compile_cache,
        )
    except ImportError as e:
        fail(f"the stateright_tpu package is not importable from here: {e}")

    cache_dir = enable_persistent_compile_cache(entry_point=True)
    say(f"compile cache: {cache_dir} "
        f"({len(os.listdir(cache_dir))} entries at start)")
    say("host linearizability search: "
        + ("native C++ (built by native/build.py)" if native.load()
           else "pure-Python fallback (g++ build failed)"))

    legs: dict = {}
    for name, run in (
        ("A", lambda: leg_a(paxos_model)),
        ("B", lambda: leg_b(paxos_model)),
        ("C", lambda: leg_c(TwoPhaseSys)),
        ("D", lambda: leg_d(paxos_model)),
    ):
        say(f"--- leg {name}")
        res = legs[name] = run()
        say(f"leg {name} ok: {json.dumps(res)}")

    counts = compile_counters()
    summary = {
        "legs": {
            k: {f: v for f, v in leg.items() if f != "compiles"}
            for k, leg in legs.items()
        },
        "cache": {
            "dir": cache_dir,
            "entries": len(os.listdir(cache_dir)),
            "persistent_hits": counts["persistent_cache_hits"],
            "persistent_misses": counts["persistent_cache_misses"],
        },
        "sec": round(time.monotonic() - t_start, 1),
    }
    if REHEARSAL:
        say(f"rehearsal complete (no result line): {json.dumps(summary)}")
        return 2
    say(f"summary: {json.dumps(summary)}")
    # the contract's result line: these keys and no others
    print(json.dumps({"ok": True, "device": dev}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
