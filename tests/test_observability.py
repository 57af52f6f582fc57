"""Live-run observability (docs/observability.md): the typed metrics bus,
the Prometheus/``/.progress`` service plane, the progress heartbeat +
``status`` verb, and span-structured tracing end to end.

The covering contract, same as the flight recorder's: everything here is
host-side sampling at seams that already exist.  The parity pin in this
file is the acceptance gate — metrics on vs off must leave the step
record stream (minus wall-clock and the random span id) and the step
jaxpr bit-identical.
"""

import json
import time
import urllib.error
import urllib.request

import pytest

from stateright_tpu.checkpoint import (
    PROGRESS_FILE,
    ProgressHeartbeat,
    read_progress,
)
from stateright_tpu.models.two_phase_commit import TwoPhaseSys
from stateright_tpu.telemetry import FlightRecorder
from stateright_tpu.telemetry.export import to_chrome_trace
from stateright_tpu.telemetry.metrics import (
    ENGINE_LABELS,
    MetricsBus,
    default_bus,
    engine_families,
    fleet_families,
    reset_default_bus,
)


@pytest.fixture(autouse=True)
def _fresh_default_bus():
    """Family values on the process bus are cumulative by design; tests
    must not see each other's samples."""
    reset_default_bus()
    yield
    reset_default_bus()


# -- the typed family registry ----------------------------------------------


def test_family_registration_is_idempotent_and_type_checked():
    bus = MetricsBus()
    c1 = bus.counter("x_total", "Things.", labelnames=("engine",))
    c2 = bus.counter("x_total", "Things.", labelnames=("engine",))
    assert c1 is c2  # same-name same-type re-registration returns it
    with pytest.raises(ValueError, match="already registered"):
        bus.gauge("x_total")
    with pytest.raises(ValueError):
        bus.counter("not a metric name!")
    with pytest.raises(ValueError):
        bus.counter("x_total").inc(-1)  # counters are monotone


def test_label_cardinality_guard():
    bus = MetricsBus(max_series=3)
    c = bus.counter("y_total", "Things.", labelnames=("key",))
    for i in range(3):
        c.inc(1, key=f"k{i}")
    with pytest.raises(ValueError, match="label-cardinality cap"):
        c.inc(1, key="k3")
    # the guard is per family, not global: a second family starts fresh
    bus.gauge("z", labelnames=("key",)).set(1.0, key="other")


def test_exposition_format_golden():
    """The exact Prometheus text format a scraper parses: HELP/TYPE
    headers, sorted families, cumulative histogram buckets with +Inf,
    bare integers.  Byte-for-byte golden — exposition drift breaks HERE,
    not in a dashboard three rounds later."""
    bus = MetricsBus()
    bus.counter("demo_total", "Things counted.",
                labelnames=("engine",)).inc(3, engine="wavefront")
    bus.gauge("demo_load", "Load.").set(0.5)
    h = bus.histogram("demo_seconds", "Durations.", buckets=(0.1, 1.0))
    h.observe(0.05)
    h.observe(2.0)
    assert bus.expose() == (
        "# HELP demo_load Load.\n"
        "# TYPE demo_load gauge\n"
        "demo_load 0.5\n"
        "# HELP demo_seconds Durations.\n"
        "# TYPE demo_seconds histogram\n"
        'demo_seconds_bucket{le="0.1"} 1\n'
        'demo_seconds_bucket{le="1"} 2\n'
        'demo_seconds_bucket{le="+Inf"} 2\n'
        "demo_seconds_sum 2.05\n"
        "demo_seconds_count 2\n"
        "# HELP demo_total Things counted.\n"
        "# TYPE demo_total counter\n"
        'demo_total{engine="wavefront"} 3\n'
    )


def test_family_catalogue_is_pinned():
    """The standard engine + fleet family names (what the CI /metrics
    smoke asserts and dashboards key on)."""
    bus = MetricsBus()
    eng = engine_families(bus)
    flt = fleet_families(bus)
    assert eng["states"].name == "stateright_states_total"
    assert eng["unique"].name == "stateright_unique_states_total"
    assert eng["step"].kind == "histogram"
    assert ENGINE_LABELS == ("engine", "model")
    assert flt["queue"].name == "stateright_fleet_queue_depth"
    assert flt["admissions"].kind == "counter"
    # both catalogues resolve idempotently on one bus
    assert engine_families(bus)["states"] is eng["states"]


# -- engine publication + the zero-overhead parity pin -----------------------


def _spawn_2pc3(metrics: bool):
    b = TwoPhaseSys(3).checker().telemetry(metrics=metrics)
    return b.spawn_tpu(sync=True, capacity=1 << 12, batch=64)


def test_engine_publishes_per_sync_samples():
    c = _spawn_2pc3(metrics=True)
    bus = default_bus()
    assert "stateright_states_total" in bus.families()
    exp = bus.expose()
    # the counter ends at the run's terminal total, labeled by engine+model
    assert 'stateright_states_total{engine="wavefront",' in exp
    assert "} %d\n" % c.state_count() in exp
    assert "stateright_step_seconds_bucket" in exp
    # per-sync gauges sampled from already-synced host values
    assert "stateright_table_load{" in exp
    assert "stateright_frontier_size{" in exp


def test_metrics_on_off_step_records_are_identical():
    """The parity pin: attaching the bus must not change what the
    recorder records — same step stream minus wall-clock (dt/t) and the
    randomly-minted span id."""

    def strip(rec):
        return [
            {k: v for k, v in r.items() if k not in ("t", "dt", "span")}
            for r in rec.records("step")
        ]

    c_off = _spawn_2pc3(metrics=False)
    c_on = _spawn_2pc3(metrics=True)
    assert strip(c_off.flight_recorder) == strip(c_on.flight_recorder)
    assert c_off.unique_state_count() == c_on.unique_state_count() == 288


def test_metrics_attach_adds_zero_ops_to_step_jaxpr():
    """The device half of the parity pin: the compiled step program is
    bit-identical with the bus attached — publication is host-side
    sampling of values the sync already materialized."""
    import jax

    def run_jaxpr(metrics: bool) -> str:
        c = _spawn_2pc3(metrics)
        init_fn, run_fn = c._engine(c._cap, c._qcap, c._batch, c._cand)
        carry, _ = init_fn()
        return str(jax.make_jaxpr(lambda cr: run_fn(cr))(carry))

    assert run_jaxpr(False) == run_jaxpr(True)


def test_publisher_crash_detaches_bus_not_run(monkeypatch):
    """A broken publisher must cost the bus, never the check: the
    recorder detaches it and discloses via a note record."""
    from stateright_tpu.telemetry import recorder as recmod

    def boom(*a, **kw):
        raise RuntimeError("bus exploded")

    monkeypatch.setattr(recmod.FlightRecorder, "_engine_fams", boom)
    c = _spawn_2pc3(metrics=True)
    assert c.unique_state_count() == 288  # the run finished regardless
    notes = [r for r in c.flight_recorder.records("note")
             if r.get("what") == "metrics bus detached"]
    assert notes, "the drop must be disclosed in the ring"


# -- heartbeat + status verb -------------------------------------------------


def test_heartbeat_beats_throttle_and_verdicts(tmp_path):
    rec = FlightRecorder(capacity=64, meta={"engine": "t"})
    rec.step(engine="single", dt=0.1, states=10, unique=5)
    hb = ProgressHeartbeat(str(tmp_path), every_secs=30.0)
    assert hb.beat(rec) is True  # first beat always lands
    assert hb.beat(rec) is False  # throttled
    assert hb.beat(rec, force=True) is True
    doc = read_progress(str(tmp_path))
    assert doc["status"] == "running" and doc["verdict"] == "running"
    assert doc["states"] == 10 and doc["unique"] == 5
    assert doc["fresh"] is True
    hb.beat(rec, status="done", force=True)
    assert read_progress(str(tmp_path))["verdict"] == "done"


def test_stale_running_heartbeat_reads_dead(tmp_path):
    """The post-mortem path: a SIGKILLed run leaves a ``running``
    heartbeat behind; once it goes stale the verdict is ``dead`` —
    'where did it stall' instead of a lying 'running'."""
    p = tmp_path / PROGRESS_FILE
    doc = {"v": 1, "status": "running", "ts": time.time() - 120.0,
           "every_secs": 1.0, "states": 42, "unique": 17}
    p.write_text(json.dumps(doc))
    back = read_progress(str(tmp_path))
    assert back["verdict"] == "dead" and back["fresh"] is False
    assert back["states"] == 42
    # a DONE heartbeat never goes dead, no matter how old
    doc["status"] = "done"
    p.write_text(json.dumps(doc))
    assert read_progress(str(tmp_path))["verdict"] == "done"


def test_autosave_armed_run_writes_terminal_heartbeat(tmp_path):
    c = (
        TwoPhaseSys(3).checker().telemetry()
        .autosave(str(tmp_path), every_secs=3600.0)
        .spawn_tpu(sync=True, capacity=1 << 12, batch=64)
    )
    doc = read_progress(str(tmp_path))
    assert doc is not None and doc["verdict"] == "done"
    assert doc["states"] == c.state_count()
    assert doc["unique"] == c.unique_state_count()


def test_status_verb_reports_live_and_dead_runs(tmp_path, capsys):
    """``_cli status RUN_DIR`` over a pool root: the top-level heartbeat
    plus per-job heartbeats under ``jobs/``, including a SIGKILLed job
    (stale running heartbeat -> DEAD)."""
    from stateright_tpu.models._cli import fleet_status

    (tmp_path / PROGRESS_FILE).write_text(json.dumps(
        {"v": 1, "status": "done", "ts": time.time(), "every_secs": 1.0,
         "jobs": 2, "completed": 2}
    ))
    dead = tmp_path / "jobs" / "killed"
    dead.mkdir(parents=True)
    (dead / PROGRESS_FILE).write_text(json.dumps(
        {"v": 1, "status": "running", "ts": time.time() - 300.0,
         "every_secs": 1.0, "states": 7, "phase": "explore"}
    ))
    live = tmp_path / "jobs" / "ok"
    live.mkdir()
    (live / PROGRESS_FILE).write_text(json.dumps(
        {"v": 1, "status": "running", "ts": time.time(),
         "every_secs": 1.0, "states": 3}
    ))
    assert fleet_status([str(tmp_path)]) == 0
    out = capsys.readouterr().out
    assert "DONE" in out
    assert "jobs/killed: DEAD" in out
    assert "jobs/ok: RUNNING" in out
    # an empty dir is a loud exit-1, not a silent success
    empty = tmp_path / "nothing"
    empty.mkdir()
    assert fleet_status([str(empty)]) == 1


# -- the service plane -------------------------------------------------------


def _get(addr, path):
    with urllib.request.urlopen(f"http://{addr}{path}") as r:
        return r.status, dict(r.headers), r.read()


def test_metrics_and_progress_endpoints(tmp_path):
    from stateright_tpu.explorer import serve

    b = (
        TwoPhaseSys(3).checker().telemetry(metrics=True)
        .autosave(str(tmp_path), every_secs=3600.0)
    )
    server = serve(b, "localhost:0", block=False, strategy="tpu",
                   sync=True, capacity=1 << 12, batch=64)
    try:
        server.checker.join()
        status, headers, body = _get(server.addr, "/metrics")
        assert status == 200
        assert headers["Content-Type"].startswith("text/plain; version=0.0.4")
        text = body.decode()
        assert "# TYPE stateright_states_total counter" in text
        assert 'engine="wavefront"' in text
        status, _, body = _get(server.addr, "/.progress")
        assert status == 200
        doc = json.loads(body)
        assert doc["verdict"] == "done"
        assert doc["states"] == server.checker.state_count()
        # traversal-shaped job keys are refused with the stable error shape
        with pytest.raises(urllib.error.HTTPError) as e:
            _get(server.addr, "/.progress/../evil")
        assert e.value.code == 404
        assert json.loads(e.value.read())["error"] == "bad_job_key"
    finally:
        server.shutdown()


def test_progress_endpoint_disabled_without_root():
    from stateright_tpu.explorer import serve

    server = serve(TwoPhaseSys(3).checker(), "localhost:0", block=False)
    try:
        server.checker.join()
        with pytest.raises(urllib.error.HTTPError) as e:
            _get(server.addr, "/.progress")
        assert e.value.code == 404
        assert json.loads(e.value.read())["error"] == "progress_disabled"
        # /metrics still answers (the process default bus; possibly empty)
        status, headers, _ = _get(server.addr, "/metrics")
        assert status == 200
    finally:
        server.shutdown()


# -- span tracing end to end -------------------------------------------------


def test_supervised_run_span_chain(tmp_path):
    """attempt -> engine_run -> (steps, autosave) under an injected
    parent: the propagation path the fleet scheduler drives, pinned at
    the supervisor boundary."""
    from stateright_tpu.supervisor import supervise
    from stateright_tpu.telemetry.spans import SpanContext

    b = TwoPhaseSys(3).checker().telemetry()
    parent = SpanContext()
    b._span_ctx = parent
    res = supervise(
        b, autosave_dir=str(tmp_path / "auto"), every_secs=0.0,
        max_restarts=0, sleep=lambda s: None,
        capacity=1 << 12, batch=64,
    )
    rec = res.checker.flight_recorder
    spans = rec.records("span")
    att = [s for s in spans if s["name"] == "attempt"]
    run = [s for s in spans if s["name"] == "engine_run"]
    saves = [s for s in spans if s["name"] == "autosave"]
    assert len(att) == 1 and len(run) == 1 and saves
    assert att[0]["parent_id"] == parent.span_id
    assert run[0]["parent_id"] == att[0]["span_id"]
    assert all(s["parent_id"] == run[0]["span_id"] for s in saves)
    assert {s["trace_id"] for s in spans} == {parent.trace_id}
    # the supervisor restores the builder's ctx after the episode
    assert b._span_ctx is parent
    steps = rec.records("step")
    assert steps and all(
        s["span"] == run[0]["span_id"] for s in steps
    )
    # a standalone (unparented) run roots a fresh trace instead
    c2 = TwoPhaseSys(3).checker().telemetry().spawn_tpu(
        sync=True, capacity=1 << 12, batch=64
    )
    spans2 = c2.flight_recorder.records("span")
    roots = [s for s in spans2 if "parent_id" not in s]
    assert [s["name"] for s in roots] == ["fingerprint_bridge", "engine_run"]
    # ... whose host seams (engine_acquire, device_call) are its children
    assert {s["trace_id"] for s in spans2} == {roots[1]["trace_id"]}
    assert {"engine_acquire", "device_call"} <= {
        s["name"] for s in spans2
        if s.get("parent_id") == roots[1]["span_id"]
    }
    # ... and the path reconstruction AFTER the run is one more root of
    # the same trace, not a child of the span that has already closed
    c2.discoveries()
    after = [s for s in c2.flight_recorder.records("span")
             if "parent_id" not in s]
    assert [s["name"] for s in after] == [
        "fingerprint_bridge", "engine_run", "reconstruct"
    ]
    assert {s["trace_id"] for s in after} == {roots[1]["trace_id"]}


def test_two_job_fleet_chrome_trace_nests(tmp_path):
    """The acceptance trace: a 2-job fleet campaign exported as ONE
    Chrome trace — fleet -> job -> attempt -> engine_run spans with
    correct parenting, all on one trace id, rendered as nested duration
    events on per-job lanes."""
    from stateright_tpu.fleet import FleetSpec, Job, run_fleet

    checkers = []

    class SpyBuilder:
        """Forwarding proxy: captures the spawned checkers (whose
        recorders hold the attempt/engine_run spans) without touching
        the builder surface the scheduler/supervisor mutate."""

        def __init__(self, inner):
            object.__setattr__(self, "_inner", inner)

        def __getattr__(self, k):
            return getattr(self._inner, k)

        def __setattr__(self, k, v):
            setattr(self._inner, k, v)

        def spawn_tpu(self, **kw):
            c = self._inner.spawn_tpu(**kw)
            checkers.append(c)
            return c

    def build():
        return SpyBuilder(TwoPhaseSys(3).checker().telemetry())

    spec = FleetSpec(
        jobs=[
            Job(key="a", build=build, capacity=1 << 12, batch=64),
            Job(key="b", build=build, capacity=1 << 12, batch=64),
        ],
        slots=2,
    )
    res = run_fleet(spec, root=str(tmp_path / "fleet"))
    assert res.completed == 2 and len(checkers) == 2

    # one combined export: the fleet ring plus both job rings (the
    # JSONL header's monotonic origin aligns the appended runs)
    path = tmp_path / "trace.jsonl"
    res.recorder.to_jsonl(path)
    for c in checkers:
        c.flight_recorder.to_jsonl(path, append=True)
    rec = FlightRecorder.from_jsonl(path)
    spans = rec.records("span")
    by_name = {}
    for s in spans:
        by_name.setdefault(s["name"], []).append(s)
    fleet = by_name["fleet"]
    jobs = by_name["job"]
    attempts = by_name["attempt"]
    runs = by_name["engine_run"]
    assert len(fleet) == 1 and len(jobs) == 2
    assert len(attempts) == 2 and len(runs) == 2
    assert {j["key"] for j in jobs} == {"a", "b"}
    assert all(j["parent_id"] == fleet[0]["span_id"] for j in jobs)
    assert {a["parent_id"] for a in attempts} == {
        j["span_id"] for j in jobs
    }
    assert {r["parent_id"] for r in runs} == {
        a["span_id"] for a in attempts
    }
    assert {s["trace_id"] for s in spans} == {fleet[0]["trace_id"]}

    out = tmp_path / "trace.json"
    to_chrome_trace(rec, out)
    events = json.loads(out.read_text())["traceEvents"]
    xs = {e["args"]["span_id"]: e for e in events
          if e["cat"] == "span" and e["ph"] == "X"}
    assert len(xs) == len(spans)

    def contains(outer, inner):
        return (outer["ts"] <= inner["ts"] + 1e-6
                and inner["ts"] + inner["dur"]
                <= outer["ts"] + outer["dur"] + 1e-6)

    for s in spans:
        pid = s.get("parent_id")
        if pid is None:
            continue
        # every child renders inside its parent AND on its lineage's
        # lane — what makes the viewer nest them
        assert contains(xs[pid], xs[s["span_id"]]), (
            f"{s['name']} not nested in its parent"
        )
        assert xs[pid]["tid"] == xs[s["span_id"]]["tid"]
    # sibling jobs render on distinct lanes... no: one fleet root =>
    # one lineage lane; concurrency is visible by overlap, parenting by
    # containment.  What must hold: span lanes are the dedicated >=100
    # band, never the plain step lane
    assert all(e["tid"] >= 100 for e in xs.values())
