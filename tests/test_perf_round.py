"""Wavefront throughput round (docs/perf.md): prewarm, persistent compile
cache, candidate pre-dedup, per-stage attribution, and the compiled-CPU
baseline.

The contracts pinned here:

 - pre-dedup ON is bit-identical to OFF (counts, discovery traces, and the
   visited table itself), and OFF leaves the step jaxpr unchanged;
 - a growth boundary consumes a prewarmed executable (compile events say
   ``source="prewarm"``; the engine build ran on the prewarm thread), and a
   READY rung swaps in without blocking (slow-compile stub, component
   level);
 - a second fresh-model run with the persistent cache dir set performs
   zero fresh engine compiles (every compile event is a persistent hit);
 - the flight recorder's per-stage breakdown is present, non-negative, and
   bounded by wall time;
 - the native compiled-CPU BFS reproduces the engines' pinned counts.
"""

import threading
import time

import numpy as np
import pytest

import jax

from stateright_tpu.models.two_phase_commit import TwoPhaseSys
from stateright_tpu.parallel import prewarm
from stateright_tpu.parallel.prewarm import (
    CHECKOUT_CACHE_DIR,
    ENV_JAX_CACHE_DIR,
    PREWARM_THREAD_NAME,
    EnginePrewarmer,
    disable_persistent_compile_cache,
    enable_persistent_compile_cache,
    resolve_compile_cache_dir,
)

TPC3_UNIQUE = 288


def _spawn(model, **kw):
    kw.setdefault("sync", True)
    kw.setdefault("capacity", 1 << 12)
    kw.setdefault("batch", 64)
    return kw


# -- pre-dedup equivalence ----------------------------------------------------


def test_prededup_is_bit_identical_on_2pc3():
    """Fleet-parity contract, strongest form: with capacities pre-sized (no
    growth events to reorder slots), the visited TABLE — every slot's
    fingerprint and parent payload — must be bit-identical with the flag
    on and off, along with every count and discovery."""
    a = TwoPhaseSys(3).checker().spawn_tpu(
        sync=True, capacity=1 << 12, batch=64
    )
    b = TwoPhaseSys(3).checker().prededup().spawn_tpu(
        sync=True, capacity=1 << 12, batch=64
    )
    assert a.unique_state_count() == b.unique_state_count() == TPC3_UNIQUE
    assert a.state_count() == b.state_count()
    assert a.max_depth() == b.max_depth()
    ta, tb = a._table_np(), b._table_np()
    assert np.array_equal(ta[0], tb[0])
    assert np.array_equal(ta[1], tb[1])
    da, db = a.discoveries(), b.discoveries()
    assert sorted(da) == sorted(db)
    for name in da:
        assert [str(s) for s in da[name].states()] == [
            str(s) for s in db[name].states()
        ]


@pytest.mark.slow
def test_prededup_parity_under_growth_and_symmetry():
    """Counts/discoveries stay identical when growth events DO interleave
    (slot layouts may differ after rehash — the set contract, not the
    layout contract) and under symmetry reduction (generation-order
    compaction path)."""
    a = TwoPhaseSys(4).checker().spawn_tpu(
        sync=True, capacity=1 << 8, batch=32, cand=128,
        queue_capacity=1 << 12,
    )
    b = TwoPhaseSys(4).checker().prededup().spawn_tpu(
        sync=True, capacity=1 << 8, batch=32, cand=128,
        queue_capacity=1 << 12,
    )
    assert a.unique_state_count() == b.unique_state_count()
    assert a.state_count() == b.state_count()
    assert sorted(a.discoveries()) == sorted(b.discoveries())
    sa = TwoPhaseSys(3).checker().symmetry().spawn_tpu(
        sync=True, capacity=1 << 12, batch=64
    )
    sb = TwoPhaseSys(3).checker().symmetry().prededup().spawn_tpu(
        sync=True, capacity=1 << 12, batch=64
    )
    assert sa.unique_state_count() == sb.unique_state_count()
    assert sa.state_count() == sb.state_count()
    ta, tb = sa._table_np(), sb._table_np()
    assert np.array_equal(ta[0], tb[0])  # no growth: bit-identical again
    assert np.array_equal(ta[1], tb[1])


@pytest.mark.slow
def test_prededup_parity_on_mesh_engine():
    a = TwoPhaseSys(3).checker().spawn_tpu(
        sync=True, devices=2, capacity=1 << 12, frontier_capacity=1 << 9
    )
    b = TwoPhaseSys(3).checker().prededup().spawn_tpu(
        sync=True, devices=2, capacity=1 << 12, frontier_capacity=1 << 9
    )
    assert a.unique_state_count() == b.unique_state_count() == TPC3_UNIQUE
    assert a.state_count() == b.state_count()
    assert sorted(a.discoveries()) == sorted(b.discoveries())


def test_prededup_off_leaves_run_jaxpr_bit_identical():
    """Same contract as telemetry/checked: the flag OFF must be the
    pre-flag engine program, and ON must actually add the filter."""

    def run_jaxpr(flag):
        m = TwoPhaseSys(3)
        b = m.checker()
        if flag is not None:
            b = b.prededup(flag)
        c = b.spawn_tpu(sync=True, capacity=1 << 12, batch=64)
        init_fn, run_fn = c._engine(c._cap, c._qcap, c._batch, c._cand)
        carry, _ = init_fn()
        return str(jax.make_jaxpr(lambda cr: run_fn(cr))(carry))

    baseline = run_jaxpr(None)
    assert baseline == run_jaxpr(False)
    assert baseline != run_jaxpr(True)  # the filter is really there


# -- prewarm (component level) ------------------------------------------------


def test_prewarmer_ready_rung_swaps_in_without_blocking():
    """The growth-stall elision itself, with an artificially slow compile:
    once the background build finished, consuming it costs ~nothing and
    no compile ever ran on the caller's thread."""
    threads = []

    def build():
        threads.append(threading.current_thread().name)
        time.sleep(0.3)  # artificially slow compile
        return "engine"

    p = EnginePrewarmer()
    try:
        assert p.schedule("k", build)
        assert not p.schedule("k", build)  # idempotent per key
        deadline = time.monotonic() + 20
        while not p.ready("k"):
            assert time.monotonic() < deadline, "background compile hung"
            time.sleep(0.01)
        t0 = time.monotonic()
        result, waited, was_ready, job = p.take("k")
        assert time.monotonic() - t0 < 0.1  # no blocking on a ready rung
        assert result == "engine" and was_ready and waited < 0.1
        assert threads == [PREWARM_THREAD_NAME]
        assert p.take("k") is None  # consumed
    finally:
        p.close()


def test_prewarmer_waits_out_in_flight_and_cancels_queued():
    started = threading.Event()

    def slow():
        started.set()
        time.sleep(0.4)
        return "slow"

    def never():
        return "never"

    p = EnginePrewarmer()
    try:
        p.schedule("a", slow)
        assert started.wait(10)
        p.schedule("b", never)
        # b is queued behind the in-flight a: taking it CANCELS it (the
        # caller cold-builds inline instead of waiting behind a)
        assert p.take("b") is None
        assert not p.scheduled("b")
        # a is in flight: take waits it out (the compile started earlier)
        result, waited, was_ready, _ = p.take("a")
        assert result == "slow" and not was_ready
    finally:
        p.close()


def test_prewarmer_close_drops_queue_and_surfaces_errors():
    def boom():
        raise ValueError("bad build")

    p = EnginePrewarmer()
    p.schedule("e", boom)
    deadline = time.monotonic() + 20
    while p.scheduled("e") and not p.ready("e"):
        assert time.monotonic() < deadline
        time.sleep(0.01)
    with pytest.raises(ValueError, match="bad build"):
        p.take("e")
    blocker = threading.Event()
    p.schedule("x", lambda: blocker.wait(2))
    p.schedule("y", lambda: "y")
    p.close()
    assert not p.schedule("z", lambda: "z")  # closed
    blocker.set()
    assert p.wait_idle(20)


# -- prewarm (growth-boundary integration) ------------------------------------


def test_growth_boundary_consumes_prewarmed_engine(monkeypatch):
    """A growth boundary swaps in the background-compiled rung: the
    boundary's compile event says ``source="prewarm"`` (cache_hit=True),
    and the rung's engine build demonstrably ran on the prewarm thread,
    not the run loop's."""
    import stateright_tpu.parallel.wavefront as wf

    builds = []
    orig = wf._build_engine

    def spy(*args, **kw):
        builds.append((threading.current_thread().name, args[2]))  # cap
        return orig(*args, **kw)

    monkeypatch.setattr(wf, "_build_engine", spy)
    m = TwoPhaseSys(3)
    # batch 8 x arity 17 = 136-lane windows: the candidate budget clamps to
    # full width (no cand rung to predict), so the table doubling is the
    # FIRST scheduled prewarm job; 1024 slots force exactly that doubling
    # at ~256 unique (288 total).  steps_per_call=1 keeps syncs frequent:
    # the 1/16-load prewarm threshold (64 unique) fires at least one full
    # sync before the 1/4-load growth trigger (257) can, so the
    # background compile has demonstrably STARTED when the boundary takes
    # it (in-flight waits still count as prewarm consumption — the
    # compile began earlier than a cold build would have).
    c = (
        m.checker().prewarm().telemetry()
        .spawn_tpu(sync=True, capacity=1 << 10, batch=8,
                   steps_per_call=1, queue_capacity=1 << 12)
    )
    assert c.unique_state_count() == TPC3_UNIQUE
    assert c.growth_events, "capacity must have forced a growth event"
    compiles = c.flight_recorder.records("compile")
    assert compiles[0]["rung"] == "init"
    rungs = [e for e in compiles if e["rung"] != "init"]
    assert rungs, "growth must have acquired at least one new engine"
    assert all(
        e["source"] == "prewarm" and e["cache_hit"] for e in rungs
    ), rungs
    counters = c.flight_recorder.counters()
    assert counters.get("prewarm_consumed", 0) >= len(rungs)
    # the consumed rungs' builds happened on the background thread
    prewarm_built_caps = {
        cap for name, cap in builds if name == PREWARM_THREAD_NAME
    }
    for e in rungs:
        assert e["cap"] in prewarm_built_caps, (e, builds)


# -- persistent compile cache -------------------------------------------------


def test_persistent_cache_round_trip_zero_fresh_compiles(tmp_path):
    """Second run, FRESH model instance (so the in-memory engine caches
    cannot serve), same cache dir: every engine compile must be a
    persistent-cache hit — zero fresh engine compiles — and the counts
    must stay exact.

    The capacities force a growth rung so cache-SERVED executables drive
    real work: this is the regression pin for the donation/deserialization
    bug (docs/perf.md) where cache-retrieved CPU executables read
    donation-deleted buffers and returned garbage counters on every
    second run."""
    d = str(tmp_path / "compile-cache")
    caps = dict(sync=True, capacity=1 << 10, batch=8,
                queue_capacity=1 << 12)
    try:
        c1 = TwoPhaseSys(3).checker().compile_cache(d).telemetry().spawn_tpu(
            **caps
        )
        assert c1.unique_state_count() == TPC3_UNIQUE
        assert c1.growth_events, "capacities must force a growth rung"
        ev1 = c1.flight_recorder.records("compile")
        assert ev1 and all(e["source"] == "fresh" for e in ev1)

        c2 = TwoPhaseSys(3).checker().compile_cache(d).telemetry().spawn_tpu(
            **caps
        )
        assert c2.unique_state_count() == TPC3_UNIQUE
        assert c2.state_count() == c1.state_count()
        ev2 = c2.flight_recorder.records("compile")
        assert len(ev2) >= 2, "init + growth rung must both re-acquire"
        assert all(
            e["cache_hit"] and e["source"] == "persistent" for e in ev2
        ), ev2
    finally:
        disable_persistent_compile_cache()


# -- where the cache lives (prewarm.resolve_compile_cache_dir) ---------------


def test_cache_dir_unset_env_library_none_entry_points_fixed_checkout_path(
    monkeypatch,
):
    """No ``JAX_COMPILATION_CACHE_DIR``: a plain library spawn keeps no
    cache, an explicit request is honoured, and the chip entry points get
    ONE fixed path inside the checkout — never a temp name, a pid or a
    timestamp (the directory moves, the cache never hits)."""
    import os
    import tempfile

    monkeypatch.delenv(ENV_JAX_CACHE_DIR, raising=False)
    assert resolve_compile_cache_dir() is None
    assert resolve_compile_cache_dir("/some/where") == "/some/where"
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    got = resolve_compile_cache_dir(entry_point=True)
    assert got == CHECKOUT_CACHE_DIR == os.path.join(repo, ".jax_cache")
    assert got == resolve_compile_cache_dir(entry_point=True)  # stable
    assert not got.startswith(tempfile.gettempdir())
    assert str(os.getpid()) not in got
    # the in-checkout directory is git-ignored
    assert ".jax_cache/" in open(os.path.join(repo, ".gitignore")).read()


def test_cache_dir_env_wins_and_nothing_else_is_ever_configured(
    monkeypatch, tmp_path, capsys
):
    """``JAX_COMPILATION_CACHE_DIR`` set: that directory is the answer for
    every caller, a disagreeing explicit request is ignored with one
    stderr line, and the package never touches
    ``jax_compilation_cache_dir`` — enabling only zeroes the admission
    thresholds and installs the listener."""
    env_dir = str(tmp_path / "from-env")
    monkeypatch.setenv(ENV_JAX_CACHE_DIR, env_dir)
    updates = []
    real_update = jax.config.update
    monkeypatch.setattr(
        jax.config, "update",
        lambda k, v: (updates.append(k), real_update(k, v))[1],
    )
    assert resolve_compile_cache_dir() == env_dir
    assert resolve_compile_cache_dir(entry_point=True) == env_dir
    assert resolve_compile_cache_dir(env_dir) == env_dir
    assert capsys.readouterr().err == ""
    assert resolve_compile_cache_dir(str(tmp_path / "other")) == env_dir
    err = capsys.readouterr().err
    assert err.count("\n") == 1 and "ignoring" in err and env_dir in err
    try:
        assert enable_persistent_compile_cache(
            str(tmp_path / "other"), entry_point=True
        ) == env_dir
        assert "jax_compilation_cache_dir" not in updates
        assert "jax_persistent_cache_min_compile_time_secs" in updates
    finally:
        disable_persistent_compile_cache()
    assert "jax_compilation_cache_dir" not in updates
    assert prewarm._cache_dir is None


# -- per-stage attribution ----------------------------------------------------


def test_stage_breakdown_present_and_sane():
    c = TwoPhaseSys(3).checker().telemetry().spawn_tpu(
        sync=True, capacity=1 << 12, batch=64
    )
    stages = c.flight_recorder.stages()
    assert stages is not None
    for key in ("compile_secs", "device_secs", "wall_secs", "host_secs"):
        assert key in stages and stages[key] >= 0.0, stages
    named = sum(
        v for k, v in stages.items()
        if k.endswith("_secs") and k not in ("wall_secs", "host_secs")
    )
    assert named <= stages["wall_secs"] + 0.05, stages
    summary = c.flight_recorder.summary()
    assert summary["stages"] == stages
    # and the breakdown survives a JSONL round-trip (counters ride the
    # header)
    import tempfile

    from stateright_tpu.telemetry import FlightRecorder

    with tempfile.TemporaryDirectory() as td:
        path = f"{td}/t.jsonl"
        c.flight_recorder.to_jsonl(path)
        back = FlightRecorder.from_jsonl(path)
        rt = back.stages()
        assert rt is not None
        assert rt["compile_secs"] == stages["compile_secs"]
        assert rt["device_secs"] == stages["device_secs"]


def test_stage_counters_absent_without_engine_runs():
    from stateright_tpu.telemetry import FlightRecorder

    rec = FlightRecorder()
    assert rec.stages() is None
    assert "stages" not in rec.summary()


# -- native compiled-CPU baseline ---------------------------------------------


def _native_bfs_available():
    from stateright_tpu.native import load

    mod = load()
    return mod is not None and hasattr(mod, "bfs_run")


@pytest.mark.skipif(
    not _native_bfs_available(),
    reason="native module unavailable (no compiler?)",
)
def test_native_baseline_matches_engine_counts():
    from stateright_tpu.native.baseline import compiled_cpu_bfs

    r = compiled_cpu_bfs(TwoPhaseSys(3), batch=256)
    assert r is not None
    engine = TwoPhaseSys(3).checker().spawn_tpu(
        sync=True, capacity=1 << 12, batch=64
    )
    assert r["unique"] == engine.unique_state_count() == TPC3_UNIQUE
    assert r["states"] == engine.state_count()
    assert r["states_per_sec"] > 0


@pytest.mark.skipif(
    not _native_bfs_available(),
    reason="native module unavailable (no compiler?)",
)
@pytest.mark.medium
def test_native_baseline_pinned_2pc5_and_target():
    from stateright_tpu.native.baseline import compiled_cpu_bfs

    r = compiled_cpu_bfs(TwoPhaseSys(5))
    assert r["unique"] == 8832  # examples/2pc.rs:133
    t = compiled_cpu_bfs(TwoPhaseSys(5), target=2000)
    assert 2000 <= t["unique"] < 8832  # clean-boundary stop

    class NoTwin:
        pass

    assert compiled_cpu_bfs(NoTwin()) is None
