"""The step program's stage names, the host seams on both clocks, and the
device-step counter (``telemetry/spans.py`` + ``parallel/wavefront.py``).

What the benchmark's per-stage and per-phase metrics rest on: the names are
in the lowered program, a span is one ring record AND one profiler event,
and ``device_steps`` is an exact count.
"""

import glob
import os

import jax
import pytest

from stateright_tpu.models.two_phase_commit import TwoPhaseSys
from stateright_tpu.telemetry import spans
from stateright_tpu.telemetry.recorder import FlightRecorder

_KW = dict(capacity=1 << 12, batch=64)


@pytest.fixture(scope="module")
def tiny():
    """One telemetered 2pc-3 check and its two programs' lowered text."""
    c = TwoPhaseSys(3).checker().telemetry().spawn_tpu(sync=True, **_KW)
    c.join()
    init_fn, run_fn = c._engine(c._cap, c._qcap, c._batch, c._cand)
    carry, _ = init_fn()
    return {
        "checker": c,
        "run": run_fn.lower(carry).as_text(debug_info=True),
        "init": init_fn.lower().as_text(debug_info=True),
    }


# -- the device program ---------------------------------------------------------


@pytest.mark.parametrize("stage", spans.STAGES)
def test_the_lowered_run_program_names_every_stage(tiny, stage):
    assert stage.startswith("sr.")
    assert f"/{stage}/" in tiny["run"], stage


def test_the_two_programs_have_stable_module_names(tiny):
    assert "module @jit_wavefront_run" in tiny["run"]
    assert "module @jit_wavefront_init" in tiny["init"]
    # the init program packs its stats under the same scope
    assert f"/{spans.STAGE_STATS}/" in tiny["init"]


def test_scopes_are_metadata_the_jaxpr_does_not_change(tiny, monkeypatch):
    """A named scope adds no equation: the run program traced with every
    scope turned into a no-op prints the same jaxpr."""
    import contextlib

    c = tiny["checker"]

    def run_jaxpr():
        init_fn, run_fn = c._build(c._cap, c._qcap, c._batch, c._cand)
        carry, _ = init_fn()
        return str(jax.make_jaxpr(lambda cr: run_fn(cr))(carry))

    named = run_jaxpr()
    monkeypatch.setattr(jax, "named_scope", lambda name: contextlib.nullcontext())
    assert run_jaxpr() == named


def test_symmetry_names_the_canonicaliser_inside_sr_hash_and_only_then(tiny):
    """``sym.canon`` wraps ``representative_rows`` in both programs of a
    check built under ``.symmetry()``; a plain check opens no such scope (its
    step program is the one the compile cache already holds)."""
    assert not spans.SYM_CANON.startswith("sr.")  # never a stage of its own
    assert spans.SYM_CANON not in tiny["run"] + tiny["init"]
    c = TwoPhaseSys(3).checker().symmetry().spawn_tpu(sync=True, **_KW)
    c.join()
    init_fn, run_fn = c._engine(c._cap, c._qcap, c._batch, c._cand)
    carry, _ = init_fn()
    canon = f"/{spans.STAGE_HASH}/{spans.SYM_CANON}/"
    assert canon in run_fn.lower(carry).as_text(debug_info=True)
    assert canon in init_fn.lower().as_text(debug_info=True)


# -- device_steps ----------------------------------------------------------------


def _steps(checker):
    return [r for r in checker.flight_recorder.records() if r["kind"] == "step"]


def test_device_steps_is_the_sum_of_dsteps_and_repeats_exactly(tiny):
    c = tiny["checker"]
    steps = _steps(c)
    assert [r["dsteps"] for r in steps][0] == 0  # the init call runs no step
    assert all(r["batch"] == 64 for r in steps)
    assert c.device_steps() == sum(r["dsteps"] for r in steps) > 0
    # every unique state is popped once, a step pops at most one batch
    assert c.device_steps() * 64 >= c.unique_state_count() == 288
    again = TwoPhaseSys(3).checker().telemetry().spawn_tpu(sync=True, **_KW)
    assert again.device_steps() == c.device_steps()
    assert [r["dsteps"] for r in _steps(again)] == [r["dsteps"] for r in steps]
    # counted with the recorder off too (one lane of the vector the host
    # loop reads anyway)
    plain = TwoPhaseSys(3).checker().spawn_tpu(sync=True, **_KW)
    assert plain.flight_recorder is None
    assert plain.device_steps() == c.device_steps()


def test_device_steps_counts_replayed_batches_under_growth():
    """Through the growth ladder the count still equals the records' sum,
    and the device_call spans carry the same numbers."""
    c = TwoPhaseSys(5).checker().telemetry().spawn_tpu(
        sync=True, capacity=1 << 10, queue_capacity=1 << 8, batch=64
    )
    c.join()
    assert len(c.growth_events) >= 2
    steps = _steps(c)
    assert c.device_steps() == sum(r["dsteps"] for r in steps)
    assert c.device_steps() * 64 >= c.unique_state_count() == 8832
    calls = [r for r in c.flight_recorder.records("span")
             if r["name"] == "device_call"]
    assert sum(r["dsteps"] for r in calls) == c.device_steps()


# -- spans on both clocks --------------------------------------------------------


def _host_events(logdir, prefix="sr/"):
    from jax.profiler import ProfileData

    path = glob.glob(os.path.join(logdir, "plugins", "profile", "*", "*.xplane.pb"))
    assert len(path) == 1
    out = []
    for plane in ProfileData.from_file(path[0]).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(prefix):
                        out.append((e.name, e.start_ns, e.start_ns + e.duration_ns,
                                    dict(e.stats)))
    return out


def test_a_span_is_one_ring_record_and_one_profiler_event(tmp_path):
    rec = FlightRecorder(capacity=64)
    jax.profiler.start_trace(str(tmp_path))
    try:
        with spans.span("outer", rec, gen=3) as outer:
            with spans.span("outer.inner", rec, parent=outer.ctx) as inner:
                inner.set(pending=7)
        with spans.span("no_recorder", None):
            pass
    finally:
        jax.profiler.stop_trace()
    records = {r["name"]: r for r in rec.records("span")}
    assert set(records) == {"outer", "outer.inner"}  # no recorder, no record
    o, i = records["outer"], records["outer.inner"]
    assert i["parent_id"] == o["span_id"] and i["trace_id"] == o["trace_id"]
    assert o["gen"] == 3 and i["pending"] == 7
    # the child inside the parent on the recorder's clock (t is the close)
    assert o["start"] <= i["start"] and i["t"] <= o["t"]
    events = {n: (s, e, st) for n, s, e, st in _host_events(str(tmp_path))}
    assert set(events) == {"sr/outer", "sr/outer.inner", "sr/no_recorder"}
    (os_, oe, ostats), (is_, ie, istats) = events["sr/outer"], events["sr/outer.inner"]
    # ... and on the profiler's
    assert os_ <= is_ and ie <= oe
    assert ostats.get("gen") in (3, "3") and istats.get("pending") in (7, "7")


def test_a_span_records_its_start_from_the_open_time_read(monkeypatch):
    """``start`` is the open-time clock read itself and ``t`` the ONE
    close-time read; ``dur`` is their difference.  A child opened after its
    parent never starts before it, however late the parent's record lands."""
    rec = FlightRecorder(capacity=8)
    origin = rec.t0_monotonic
    ticks = iter(range(1, 100))
    monkeypatch.setattr(spans.time, "monotonic", lambda: origin + next(ticks))
    outer = spans.start_span("outer")  # reads tick 1
    inner = spans.start_span("outer.inner", outer.ctx)  # tick 2
    inner.end(rec)  # tick 3
    outer.end(rec, gen=1)  # tick 4: one read closes a span
    assert next(ticks) == 5
    i, o = rec.records("span")
    assert (o["start"], o["dur"], o["t"]) == (1.0, 3.0, 4.0)
    assert (i["start"], i["dur"], i["t"]) == (2.0, 1.0, 3.0)
    assert o["start"] <= i["start"] and _inside(o, i, eps=0.0)
    assert outer.fields is o and o["v"] == spans.SPAN_V == 2


def test_a_span_learnt_after_the_fact_is_laid_where_it_was():
    rec = FlightRecorder(capacity=8)
    with spans.span("parent", rec) as parent:
        pass
    at = rec.t0_monotonic + parent.fields["start"]
    got = spans.record_span(rec, spans.PROGRAM_LOAD, parent=parent.ctx,
                            start=at + 0.25, dur=0.5, hit=True, retrieved_s=0.4)
    assert got["parent_id"] == parent.fields["span_id"]
    assert got["trace_id"] == parent.fields["trace_id"]
    assert got["start"] == pytest.approx(parent.fields["start"] + 0.25, abs=2e-6)
    assert got["dur"] == pytest.approx(0.5, abs=2e-6)
    assert abs(got["start"] + got["dur"] - got["t"]) <= 1e-6
    assert got["hit"] is True and got["retrieved_s"] == 0.4
    # the other children have no attribute of a load's
    lower = spans.record_span(rec, spans.PROGRAM_LOWER, parent=parent.ctx,
                              start=at, dur=0.25, retrieved_s=None)
    assert "retrieved_s" not in lower and "hit" not in lower


def test_chrome_trace_anchors_a_span_at_its_start(tmp_path):
    import json

    from stateright_tpu.telemetry.export import to_chrome_trace

    rec = FlightRecorder(capacity=8)
    with spans.span("parent", rec) as parent:
        pass
    spans.record_span(rec, spans.PROGRAM_LOWER, parent=parent.ctx,
                      start=rec.t0_monotonic + 2.0, dur=0.5)
    # laid down late: anchored where it was, not where its record was written
    to_chrome_trace(rec, tmp_path / "trace.json")
    events = {e["name"]: e for e in
              json.loads((tmp_path / "trace.json").read_text())["traceEvents"]
              if e["cat"] == "span"}
    assert events[spans.PROGRAM_LOWER]["ts"] == pytest.approx(2.0e6, abs=2.0)
    assert events[spans.PROGRAM_LOWER]["dur"] == pytest.approx(0.5e6, abs=2.0)
    assert events["parent"]["ts"] == pytest.approx(
        parent.fields["start"] * 1e6, abs=1.0)
    assert events["parent"]["tid"] == events[spans.PROGRAM_LOWER]["tid"]


def test_outside_a_profiler_session_a_span_still_records():
    rec = FlightRecorder(capacity=8)
    with pytest.raises(ValueError):
        with spans.span("boom", rec):
            raise ValueError("x")
    (r,) = rec.records("span")
    assert r["name"] == "boom" and r["error"] == "ValueError"


def test_a_parentless_span_can_join_a_trace():
    rec = FlightRecorder(capacity=8)
    with spans.span("late", rec, trace_id="abc123"):
        pass
    (r,) = rec.records("span")
    assert r["trace_id"] == "abc123" and "parent_id" not in r


# -- the host seams ---------------------------------------------------------------


def _spans_by_name(checker):
    out = {}
    for r in checker.flight_recorder.records("span"):
        out.setdefault(r["name"], []).append(r)
    return out


def _inside(parent, child, eps=1e-5):
    return (parent["start"] <= child["start"] + eps
            and child["t"] <= parent["t"] + eps)


def test_device_call_and_engine_acquire_seams(tiny):
    by = _spans_by_name(tiny["checker"])
    (run,) = by["engine_run"]
    assert [r["source"] for r in by["engine_acquire"]] in (["fresh"], ["in-memory"])
    assert by["engine_acquire"][0]["rung"] == "init"
    # init + one run call, each with its dispatch and wait inside it
    assert len(by["device_call"]) == len(by["dispatch"]) == len(by["wait"]) == 2
    for call, dispatch, wait in zip(by["device_call"], by["dispatch"], by["wait"]):
        assert call["parent_id"] == run["span_id"]
        assert dispatch["parent_id"] == wait["parent_id"] == call["span_id"]
        assert _inside(call, dispatch) and _inside(call, wait)
        assert dispatch["dur"] + wait["dur"] <= call["dur"] + 1e-5
    # the stage counters are the spans': the three split the device calls
    stages = tiny["checker"].flight_recorder.stages()
    assert (stages["trace_secs"] + stages["compile_secs"]
            + stages["device_secs"]) <= sum(
        r["dur"] for r in by["device_call"]
    ) + 1e-3
    assert stages["device_secs"] == pytest.approx(
        sum(r["dur"] for r in by["wait"]), abs=1e-5)


# -- what a fresh engine costs: program.lower / program.load inside dispatch -------


def _feed(event, secs):
    jax.monitoring.record_event_duration_secs(event, secs)


def test_a_cache_retrieval_is_counted_once_inside_its_backend_step():
    """JAX's backend-compile event WRAPS the retrieval event (jax 0.9.0:
    ``pxla._cached_compilation`` around ``compile_or_get_cached``): a hit
    fires both, a miss one, and ``compile_secs`` counts each program once."""
    from stateright_tpu.parallel import prewarm

    backend = "/jax/core/compile/backend_compile_duration"
    retrieval = "/jax/compilation_cache/cache_retrieval_time_sec"
    watch = prewarm.CompileWatch()
    _feed(retrieval, 0.2)  # a hit: the retrieval, then ...
    _feed(backend, 0.25)  # ... the step it lies in
    hit = watch.delta()
    assert hit["compile_secs"] == 0.25 and hit["retrieval_secs"] == 0.2
    watch.start()
    _feed(backend, 3.0)  # a miss: a fresh compile alone
    miss = watch.delta()
    assert miss["compile_secs"] == 3.0 and miss["retrieval_secs"] == 0.0
    assert (backend, retrieval) == (prewarm.BACKEND_COMPILE_EVENT,
                                    prewarm.RETRIEVAL_EVENT)


def test_a_watch_keeps_each_lower_and_load_until_its_delta():
    import time

    from stateright_tpu.parallel import prewarm

    prewarm.compile_counters()
    assert prewarm._tls.events is None  # no watch open: nothing is kept
    _feed(prewarm.LOWER_EVENT, 0.5)
    assert prewarm._tls.events is None
    watch = prewarm.CompileWatch()
    t0 = time.monotonic()
    _feed("/jax/core/compile/jaxpr_trace_duration", 9.0)  # nests: a count
    _feed("/jax/core/compile/jaxpr_trace_duration", 4.0)
    _feed(prewarm.LOWER_EVENT, 0.125)
    _feed(prewarm.RETRIEVAL_EVENT, 0.25)
    _feed(prewarm.BACKEND_COMPILE_EVENT, 0.5)
    _feed(prewarm.BACKEND_COMPILE_EVENT, 2.0)
    d = watch.delta()
    assert d["jaxprs_traced"] == 2 and d["lower_secs"] == 0.125
    assert d["compile_secs"] == 2.5 and d["retrieval_secs"] == 0.25
    assert [(e, secs, got) for e, _, secs, got in d["events"]] == [
        (prewarm.LOWER_EVENT, 0.125, None),
        (prewarm.BACKEND_COMPILE_EVENT, 0.5, 0.25),  # the hit
        (prewarm.BACKEND_COMPILE_EVENT, 2.0, None),  # the miss after it
    ]
    assert all(t0 <= end <= time.monotonic() for _, end, _, _ in d["events"])
    # handed over and cleared
    assert prewarm._tls.events is None
    assert watch.delta()["events"] == []


@pytest.fixture(scope="module")
def twice(tmp_path_factory):
    """Two EQUAL model objects checked in one process under a persistent
    cache: the second acquires every program again, cache-served; then the
    second object is re-checked on its resident engine."""
    from stateright_tpu.parallel.prewarm import disable_persistent_compile_cache

    d = str(tmp_path_factory.mktemp("compile-cache"))
    kw = dict(sync=True, capacity=1 << 12, batch=32)
    try:
        first = TwoPhaseSys(4).checker().compile_cache(d).telemetry().spawn_tpu(**kw)
        model = TwoPhaseSys(4)
        second = model.checker().compile_cache(d).telemetry().spawn_tpu(**kw)
        again = model.checker().compile_cache(d).telemetry().spawn_tpu(**kw)
    finally:
        disable_persistent_compile_cache()
    assert (first.unique_state_count() == second.unique_state_count()
            == again.unique_state_count())
    return {"first": first, "second": second, "again": again}


def _programs(by):
    return by.get(spans.PROGRAM_LOWER, []), by.get(spans.PROGRAM_LOAD, [])


@pytest.mark.parametrize("which, hit", [("first", False), ("second", True)])
def test_dispatch_holds_what_was_lowered_and_loaded(twice, which, hit):
    by = _spans_by_name(twice[which])
    lowers, loads = _programs(by)
    # the init program and the run program at the least
    assert len(lowers) >= 2 and len(loads) >= 2
    dispatches = {r["span_id"]: r for r in by["dispatch"]}
    for child in lowers + loads:
        assert _inside(dispatches[child["parent_id"]], child), child
        assert abs(child["start"] + child["dur"] - child["t"]) <= 1e-6
    for d in dispatches.values():
        inside = [c for c in lowers + loads if c["parent_id"] == d["span_id"]]
        # the self time is the Python tracing and the enqueue: never negative
        assert sum(c["dur"] for c in inside) <= d["dur"] + 1e-5
        assert (d["jaxprs_traced"] > 0) == bool(inside)
    assert all(r["hit"] is hit for r in loads)
    if hit:
        assert all(0 < r["retrieved_s"] <= r["dur"] for r in loads)
    else:
        assert not any("retrieved_s" in r for r in loads)
    assert not any("hit" in r or "retrieved_s" in r for r in lowers)
    rec = twice[which].flight_recorder
    loaded = sum(r["dur"] for r in loads)
    compiles = rec.records("compile")
    assert {e["source"] for e in compiles} == {"persistent" if hit else "fresh"}
    # each program's load is in one compile record's duration, once
    assert sum(e["duration"] for e in compiles) == pytest.approx(loaded, abs=1e-4)
    stages = rec.stages()
    assert stages["compile_secs"] == pytest.approx(loaded, abs=1e-4)
    assert stages["trace_secs"] == pytest.approx(
        sum(d["dur"] for d in dispatches.values()) - loaded, abs=1e-4)
    assert stages["device_secs"] == pytest.approx(
        sum(r["dur"] for r in by["wait"]), abs=1e-5)
    assert (stages["trace_secs"] + stages["compile_secs"]
            + stages["device_secs"]) <= sum(
        r["dur"] for r in by["device_call"]) + 1e-3


def test_a_recheck_on_the_resident_engine_acquires_no_program(twice):
    by = _spans_by_name(twice["again"])
    assert _programs(by) == ([], [])
    assert by["dispatch"] and all(r["jaxprs_traced"] == 0 for r in by["dispatch"])
    stages = twice["again"].flight_recorder.stages()
    assert stages["compile_secs"] == 0.0
    assert stages["device_secs"] == pytest.approx(
        sum(r["dur"] for r in by["wait"]), abs=2e-3)
    assert stages["trace_secs"] == pytest.approx(
        sum(r["dur"] for r in by["dispatch"]), abs=1e-5)


def test_without_a_recorder_no_watch_is_made_and_nothing_is_kept(monkeypatch):
    from stateright_tpu.parallel import prewarm, wavefront

    def no_watch():
        raise AssertionError("a CompileWatch with no recorder to read it")

    monkeypatch.setattr(wavefront, "CompileWatch", no_watch)
    c = TwoPhaseSys(2).checker().spawn_tpu(sync=True, capacity=1 << 10, batch=16)
    c.join()  # a fresh object: both programs acquired on this thread
    assert c.flight_recorder is None and c.unique_state_count() > 0
    prewarm.compile_counters()
    assert prewarm._tls.events is None


def test_growth_seams_cover_growth_secs():
    c = TwoPhaseSys(5).checker().telemetry().spawn_tpu(
        sync=True, capacity=1 << 10, queue_capacity=1 << 8, batch=64
    )
    c.join()
    by = _spans_by_name(c)
    grows = by["grow"]
    assert len(grows) == len(c.growth_events) >= 2
    assert {g["status"] for g in grows} <= {"table_full", "queue_full", "cand_full"}
    children = [r for n in ("grow.pull", "grow.rehash", "grow.queue", "grow.push")
                for r in by.get(n, [])]
    ids = {g["span_id"]: g for g in grows}
    assert children and all(_inside(ids[r["parent_id"]], r) for r in children)
    assert len(by["grow.pull"]) == len(by["grow.push"]) == len(by["grow.queue"])
    assert 1 <= len(by["grow.rehash"]) <= len(grows)  # table growths only
    # the grow spans ARE the growth stage (same start, closed just before it)
    growth_secs = c.flight_recorder.stages()["growth_secs"]
    assert sum(g["dur"] for g in grows) == pytest.approx(growth_secs, abs=5e-3)
    assert sum(r["dur"] for r in children) <= growth_secs + 1e-4
    # a rung switch re-acquires an engine under its own span
    assert len(by["engine_acquire"]) >= 2


def test_reconstruct_seams_and_checkpoint_pull(tiny):
    c = tiny["checker"]
    before = len(c.flight_recorder.records("span"))
    found = c.discoveries()
    new = c.flight_recorder.records("span")[before:]
    by = {}
    for r in new:
        by.setdefault(r["name"], []).append(r)
    (root,) = by["reconstruct"]
    run = _spans_by_name(c)["engine_run"][0]
    (bridge,) = _spans_by_name(c)["fingerprint_bridge"]
    assert "parent_id" not in root and "parent_id" not in bridge
    assert root["trace_id"] == run["trace_id"] == bridge["trace_id"]
    assert len(by["reconstruct.walk"]) == len(by["reconstruct.replay"]) == len(found) == 2
    for name in ("reconstruct.pull", "reconstruct.parents", "reconstruct.walk",
                 "reconstruct.replay"):
        assert all(r["parent_id"] == root["span_id"] and _inside(root, r)
                   for r in by[name]), name
    # the parent links are resolved on the device, in one call for both
    # discoveries, and only the chains cross to the host
    (parents,), (pull,) = by["reconstruct.parents"], by["reconstruct.pull"]
    assert parents["path"] == "device"
    assert parents["lookups"] == sum(len(p) for p in found.values())
    assert 0 < pull["bytes"] < 64 << 10
    assert parents["start"] + parents["dur"] <= pull["start"]
    # the chains are resolved once a run: a second call dispatches nothing
    mark = len(c.flight_recorder.records("span"))
    c.discovery("abort agreement")
    again = {r["name"] for r in c.flight_recorder.records("span")[mark:]}
    assert again == {"reconstruct", "reconstruct.walk", "reconstruct.replay"}
    mark = len(c.flight_recorder.records("span"))
    c.checkpoint()
    (pull,) = c.flight_recorder.records("span")[mark:]
    # after the run: in its trace, not under the span that has closed
    assert pull["name"] == "checkpoint.pull" and "parent_id" not in pull
    assert pull["trace_id"] == run["trace_id"]


def test_a_whole_check_under_the_profiler_shows_every_host_seam(tmp_path):
    """The seams of one check, as the benchmark's traced run sees them:
    on the profiler's clock, the finer ones inside ``sr/engine_run``."""
    model = TwoPhaseSys(3)
    model.checker().spawn_tpu(sync=True, **_KW).join()  # compile outside
    jax.profiler.start_trace(str(tmp_path))
    try:
        c = model.checker().telemetry().spawn_tpu(sync=True, **_KW)
        c.join()
        c.discoveries()
    finally:
        jax.profiler.stop_trace()
    events = _host_events(str(tmp_path))
    names = [n for n, _, _, _ in events]
    for want in ("sr/fingerprint_bridge", "sr/engine_run", "sr/engine_acquire",
                 "sr/device_call", "sr/dispatch", "sr/wait", "sr/reconstruct",
                 "sr/reconstruct.pull", "sr/reconstruct.parents",
                 "sr/reconstruct.walk", "sr/reconstruct.replay"):
        assert want in names, want
    (run,) = [e for e in events if e[0] == "sr/engine_run"]
    inside = [e for e in events if e[0] in ("sr/engine_acquire", "sr/device_call",
                                            "sr/dispatch", "sr/wait")]
    assert inside and all(run[1] <= s and e <= run[2] for _, s, e, _ in inside)
    (bridge,) = [e for e in events if e[0] == "sr/fingerprint_bridge"]
    assert bridge[2] <= run[1]  # before the run opens
    calls = [e for e in events if e[0] == "sr/device_call"]
    assert sum(int(st["dsteps"]) for _, _, _, st in calls) == c.device_steps()
    # every ring span has its twin on the profiler's clock
    ring = sorted(r["name"] for r in c.flight_recorder.records("span"))
    assert sorted(n[len("sr/"):] for n in names) == ring


# -- a compiled actor twin: sub-scopes of sr.expand, the twin_compile span --------


@pytest.fixture(scope="module")
def compiled_twin():
    """One telemetered check of the compiled ABD twin (2 clients, 2 servers,
    ordered links: 564 states) and its run program's lowered text."""
    from stateright_tpu.models.linearizable_register import abd_ordered

    model = abd_ordered(2, 2)
    c = model.checker().telemetry().spawn_tpu(sync=True, capacity=1 << 13, batch=256)
    c.join()
    init_fn, run_fn = c._engine(c._cap, c._qcap, c._batch, c._cand)
    carry, _ = init_fn()
    return {"model": model, "checker": c,
            "run": run_fn.lower(carry).as_text(debug_info=True)}


@pytest.mark.parametrize("scope", spans.TWIN_SCOPES)
def test_a_compiled_twins_step_names_its_parts_inside_expand(compiled_twin, scope):
    assert not scope.startswith("sr.")  # a part is never a stage of its own
    assert f"/{spans.STAGE_EXPAND}/{scope}/" in compiled_twin["run"], scope


def test_the_slot_kernels_carry_twin_net_for_hand_twins_too():
    import jax.numpy as jnp

    from stateright_tpu.parallel import actor_tensor as at

    slots = jnp.full((4, 6), at.SLOT_EMPTY, jnp.uint64)
    code = jnp.arange(4, dtype=jnp.uint64)
    text = jax.jit(
        lambda s: at.slot_canonicalize(at.slot_send(s, code, code < 9)[0])
    ).lower(slots).as_text(debug_info=True)
    assert f"/{spans.TWIN_NET}/" in text


def test_twin_compile_is_adopted_once_by_the_first_recorder(compiled_twin):
    c, model = compiled_twin["checker"], compiled_twin["model"]
    by = _spans_by_name(c)
    (rec,) = by["twin_compile"]
    twin = model._tensor_cached()
    assert {k: rec[k] for k in twin.compile_attrs()} == twin.compile_attrs()
    assert rec["row_width"] == twin.width == 17 and rec["n_slots"] == twin.n_slots
    assert rec["actor_states"] == "64,50,3,3" and rec["envelopes"] == 56
    assert rec["table_bytes"] == 61432 and rec["dur"] > 0
    # table_bytes is the closure's tabulation (the host's per-actor tables);
    # device_table_bytes counts exactly what the step program uploads: the
    # two record tables in their place
    on_device = jax.tree_util.tree_leaves(twin._consts())
    assert rec["device_table_bytes"] == sum(int(a.nbytes) for a in on_device) == 14616
    assert rec["record_words"] == 1 and rec["step_gathers"] == 1
    # in the checker's trace, parentless (it closed before the run span opened)
    (run,) = by["engine_run"]
    assert rec["trace_id"] == run["trace_id"] and "parent_id" not in rec
    assert twin.compile_span is None  # handed over: a second checker finds none
    again = model.checker().telemetry().spawn_tpu(sync=True, capacity=1 << 13, batch=256)
    again.join()
    assert "twin_compile" not in _spans_by_name(again)


def test_a_span_without_a_recorder_keeps_its_fields():
    with spans.span("early", None, cap=3) as sp:
        assert sp.fields is None
        sp.set(unique=7)
    assert sp.fields["name"] == "early" and sp.fields["dur"] >= 0
    assert (sp.fields["cap"], sp.fields["unique"]) == (3, 7)
