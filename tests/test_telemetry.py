"""Flight recorder (stateright_tpu/telemetry/) — record schema, ring
bounding, JSONL/Chrome-trace round-trip, engine wiring on every strategy,
the Explorer's ``/.metrics`` endpoint, and the overhead contract:
telemetry disabled adds ZERO ops to the step jaxpr, telemetry enabled
costs <3% wall time on the 2PC-7 wavefront run (slow tier).

The 2PC-7 occupancy time series is pinned here too: it captures the
visited-table anomaly signature — growth events firing on single-bucket overflow (``full_buckets
>= 1``) while the Poisson model at the observed load expects essentially
none.
"""

import json
import os
import urllib.error
import urllib.request

import pytest

import jax

from stateright_tpu.telemetry import FlightRecorder, STATUS_NAMES
from stateright_tpu.telemetry.export import from_chrome_trace
from stateright_tpu.models.two_phase_commit import TwoPhaseSys

TPC7_UNIQUE = 296_448  # full 2pc-7 space (device run below enumerates it)


# -- recorder core -----------------------------------------------------------


def test_step_record_shape():
    rec = FlightRecorder(meta={"engine": "wavefront", "model": "M"})
    r1 = rec.step(engine="wavefront", states=100, unique=80,
                  load_factor=0.01)
    r2 = rec.step(engine="wavefront", states=300, unique=180)
    assert r1["kind"] == r2["kind"] == "step"
    assert r1["seq"] == 1 and r2["seq"] == 2
    assert r2["t"] >= r1["t"] >= 0
    # first record deltas from zero; second from the first
    assert (r1["d_states"], r1["d_unique"]) == (100, 80)
    assert (r2["d_states"], r2["d_unique"]) == (200, 100)
    assert r2["dedup"] == 0.5  # half the generated states were revisits
    assert r1["load_factor"] == 0.01  # engine extras pass through
    assert r2["dt"] >= 0


def test_ring_bounding_keeps_totals():
    rec = FlightRecorder(capacity=8)
    for i in range(50):
        rec.step(engine="bfs", states=(i + 1) * 10, unique=(i + 1) * 5)
    rec.record("growth", status="table_full", unique=100)
    assert len(rec) == 8
    assert rec.dropped == 51 - 8
    s = rec.summary()
    # the ring is a window; the totals are not windowed
    assert s["steps"] == 50
    assert s["states"] == 500 and s["unique"] == 250
    assert s["growth_events"] == 1
    assert s["ring_len"] == 8 and s["dropped"] == 43


def test_counters_and_status_names():
    rec = FlightRecorder()
    rec.add_bytes(d2h=100, h2d=7)
    rec.add_bytes(d2h=100)
    assert rec.counters()["d2h_bytes"] == 200
    assert rec.counters()["h2d_bytes"] == 7
    assert "table_full" in STATUS_NAMES and "queue_full" in STATUS_NAMES


def test_jsonl_round_trip(tmp_path):
    rec = FlightRecorder(capacity=32, meta={"engine": "wavefront",
                                            "model": "X"})
    for i in range(5):
        rec.step(engine="wavefront", states=(i + 1) * 100,
                 unique=(i + 1) * 60, load_factor=0.01 * (i + 1))
    rec.record("growth", status="queue_full", unique=300, cap=1024)
    rec.record("occupancy", at="final", occupied=300, load_factor=0.07,
               max_bucket=5, full_buckets=0, poisson_full_expect=0.0,
               nbuckets=64, histogram=[0] * 17)
    rec.add_bytes(d2h=1234, h2d=99)
    path = tmp_path / "t.jsonl"
    rec.to_jsonl(path)
    back = FlightRecorder.from_jsonl(path)
    assert back.records() == rec.records()
    assert back.summary() == rec.summary()
    # header line first, then one line per record
    lines = path.read_text().strip().splitlines()
    assert json.loads(lines[0])["kind"] == "header"
    assert len(lines) == 1 + len(rec.records())


def test_jsonl_round_trip_after_ring_eviction(tmp_path):
    """Eviction loses ring entries but never totals: the export header
    carries the summary, and from_jsonl reconciles seq/kind counts and the
    cumulative step snapshot from it."""
    rec = FlightRecorder(capacity=8)
    for i in range(50):
        rec.step(engine="bfs", states=(i + 1) * 10, unique=(i + 1) * 5)
    rec.record("growth", status="table_full", unique=250)
    path = tmp_path / "evicted.jsonl"
    rec.to_jsonl(path)
    back = FlightRecorder.from_jsonl(path)
    assert back.records() == rec.records()
    assert back.summary() == rec.summary()
    assert back.summary()["steps"] == 50
    assert back.dropped == rec.dropped == 43


def test_step_clamps_stale_concurrent_snapshots():
    """Pool workers read counters then record without a shared lock: a
    late writer with a stale (smaller) snapshot must not produce negative
    deltas or an under-reporting final summary."""
    rec = FlightRecorder()
    rec.step(engine="bfs", states=150, unique=90)
    late = rec.step(engine="bfs", states=100, unique=50)  # stale reader
    assert late["d_states"] == 0 and late["d_unique"] == 0
    assert late["states"] == 150 and late["unique"] == 90
    assert rec.summary()["states"] == 150


def test_jsonl_multi_run_append_keeps_per_run_series(tmp_path):
    """Appended exports (one per profiled config) replay with a fresh
    delta baseline per run: run 2's cumulative counters restart from zero
    and must not be clamped against run 1's totals."""
    r1 = FlightRecorder(meta={"label": "run1"})
    r1.step(engine="wavefront", states=1000, unique=700)
    r2 = FlightRecorder(meta={"label": "run2"})
    r2.step(engine="wavefront", states=50, unique=40)
    path = tmp_path / "multi.jsonl"
    r1.to_jsonl(path)
    r2.to_jsonl(path, append=True)
    back = FlightRecorder.from_jsonl(path)
    steps = back.records("step")
    assert [s["states"] for s in steps] == [1000, 50]
    assert [s["unique"] for s in steps] == [700, 40]
    assert steps[1]["d_states"] == 50  # fresh baseline, not 50-1000 clamped


def test_summary_wall_clock_includes_pre_first_step_work():
    """states_per_sec's denominator runs from recorder creation: the init
    and first compiled block's states must pay their elapsed time (a
    first-step-only run must not report near-infinite throughput)."""
    import time

    rec = FlightRecorder()
    time.sleep(0.05)
    rec.step(engine="wavefront", states=1000, unique=800)
    s = rec.summary()
    assert s["wall_secs"] >= 0.05
    assert s["states_per_sec"] <= 1000 / 0.05


def test_chrome_trace_round_trip(tmp_path):
    rec = FlightRecorder(meta={"engine": "mp", "model": "X"})
    rec.step(engine="mp", states=10, unique=8)
    rec.step(engine="mp", states=30, unique=20, load_factor=0.5)
    rec.record("growth", status="table_full", unique=20)
    path = tmp_path / "trace.json"
    rec.to_chrome_trace(path)
    back = from_chrome_trace(path)
    complete = [e for e in back["events"] if e["ph"] == "X"]
    instants = [e for e in back["events"] if e["ph"] == "i"]
    counters = [e for e in back["events"] if e["ph"] == "C"]
    assert len(complete) == 2 and len(instants) == 1
    assert counters, "step records emit a throughput counter track"
    assert complete[0]["args"]["states"] == 10
    assert back["summary"]["states"] == 30
    assert all(e["ts"] >= 0 for e in back["events"])


# -- engine wiring -----------------------------------------------------------


def test_disabled_by_default_no_recorder():
    c = TwoPhaseSys(3).checker().spawn_bfs().join()
    assert c.flight_recorder is None
    c2 = TwoPhaseSys(3).checker().spawn_tpu(sync=True, capacity=1 << 12,
                                            batch=64)
    assert c2.flight_recorder is None


def test_host_bfs_dfs_records():
    c = TwoPhaseSys(3).checker().telemetry().spawn_bfs().join()
    steps = c.flight_recorder.records("step")
    assert steps and all(r["engine"] == "bfs" for r in steps)
    assert c.flight_recorder.summary()["unique"] == 288
    d = TwoPhaseSys(3).checker().telemetry().spawn_dfs().join()
    assert d.flight_recorder.records("step")
    assert d.flight_recorder.summary()["unique"] == 288


def test_mp_round_records():
    c = (
        TwoPhaseSys(3).checker().telemetry().spawn_mp_bfs(processes=2)
        .join()
    )
    steps = c.flight_recorder.records("step")
    # one record per bulk-synchronous round, replayed from worker 0's log
    assert steps and all(r["engine"] == "mp" for r in steps)
    assert [r["round"] for r in steps] == list(range(len(steps)))
    assert steps[-1]["unique"] == 288


def test_wavefront_step_records_and_counts():
    c = (
        TwoPhaseSys(3).checker().telemetry(occupancy_every=2)
        .spawn_tpu(sync=True, capacity=1 << 12, batch=64)
    )
    rec = c.flight_recorder
    steps = rec.records("step")
    assert steps and all(r["engine"] == "wavefront" for r in steps)
    s = rec.summary()
    assert s["states"] == c.state_count()
    assert s["unique"] == c.unique_state_count() == 288
    assert s["compile_cache_misses"] >= 1
    assert s["d2h_bytes"] > 0
    # per-sync load factor is the unique/cap series
    assert all(0 <= r["load_factor"] <= 1 for r in steps)
    assert rec.records("occupancy"), "occupancy_every samples the table"


def test_wavefront_growth_records_with_occupancy():
    """Growth boundaries record a named event plus a free occupancy sample
    (of the table a split leaves: its histogram comes back with it)."""
    c = (
        TwoPhaseSys(5).checker().telemetry()
        .spawn_tpu(sync=True, capacity=1 << 10, batch=64)
    )
    rec = c.flight_recorder
    growth = rec.records("growth")
    assert growth, "tiny capacity must force growth"
    assert {g["status"] for g in growth} <= STATUS_NAMES
    occ = rec.records("occupancy")
    assert occ and all(o["at"] == "growth" for o in occ)
    # occupancy is sampled at each growth boundary in event order
    occupied = [o["occupied"] for o in occ]
    assert occupied == sorted(occupied)
    assert rec.summary()["growth_events"] == len(growth) == len(
        c.growth_events
    )
    assert c.unique_state_count() == 8832  # growth preserved the work


@pytest.mark.medium
def test_profiler_scoped_trace(tmp_path):
    logdir = tmp_path / "prof"
    c = (
        TwoPhaseSys(3).checker()
        .telemetry(profile_steps=1, profile_dir=str(logdir))
        .spawn_tpu(sync=True, capacity=1 << 12, batch=64)
    )
    events = c.flight_recorder.records("profile")
    assert events, "profiler must record start/stop or unavailability"
    kinds = {e["event"] for e in events}
    if "start" in kinds:  # profiler backend present: scoped start/stop
        assert "stop" in kinds
        assert os.path.isdir(logdir)
    else:  # gated: recorded, never raised
        assert kinds <= {"unavailable", "stop-failed"}


def test_profiler_stop_is_idempotent(monkeypatch, tmp_path):
    """The run wrapper's ``finally`` stops the profiler on every exit
    path, and the engines still call ``stop()`` on their happy path —
    the second call must be a backend no-op, not a double-stop."""
    from stateright_tpu.telemetry.profile import ScopedProfiler

    calls = {"start": 0, "stop": 0}
    monkeypatch.setattr(
        jax.profiler, "start_trace",
        lambda d: calls.__setitem__("start", calls["start"] + 1),
    )
    monkeypatch.setattr(
        jax.profiler, "stop_trace",
        lambda: calls.__setitem__("stop", calls["stop"] + 1),
    )
    rec = FlightRecorder(capacity=64, meta={"engine": "t"})
    p = ScopedProfiler(str(tmp_path), steps=5, recorder=rec)
    p.maybe_start()
    p.stop()
    p.stop()  # the defensive second stop
    assert calls == {"start": 1, "stop": 1}
    events = [e["event"] for e in rec.records("profile")]
    assert events.count("stop") == 1


def test_profiler_stop_failure_never_masks_engine_error(
    monkeypatch, tmp_path
):
    """A mid-block engine exception reaches ``stop()`` via the run
    wrapper's ``finally``; a backend failure there must downgrade to a
    ``stop-failed`` event, never replace the in-flight error."""
    from stateright_tpu.telemetry.profile import ScopedProfiler

    monkeypatch.setattr(jax.profiler, "start_trace", lambda d: None)

    def broken_stop():
        raise RuntimeError("backend gone")

    monkeypatch.setattr(jax.profiler, "stop_trace", broken_stop)
    rec = FlightRecorder(capacity=64, meta={"engine": "t"})
    p = ScopedProfiler(str(tmp_path), steps=5, recorder=rec)
    p.maybe_start()
    with pytest.raises(ValueError, match="engine exploded"):
        try:
            raise ValueError("engine exploded")  # the engine's error
        finally:
            p.stop()  # swallows its own failure, propagates ours
    events = [e["event"] for e in rec.records("profile")]
    assert "stop-failed" in events
    # and once failed, a repeat stop stays silent (flag already down)
    p.stop()
    assert [e for e in rec.records("profile")
            if e["event"] == "stop-failed"] != []


def test_profile_events_carry_bound_span(monkeypatch, tmp_path):
    """Profile events record the span id of the traced block, so the
    Chrome trace nests the profiled window under the run span."""
    from stateright_tpu.telemetry.profile import ScopedProfiler

    monkeypatch.setattr(jax.profiler, "start_trace", lambda d: None)
    monkeypatch.setattr(jax.profiler, "stop_trace", lambda: None)
    rec = FlightRecorder(capacity=64, meta={"engine": "t"})
    rec.bind_span("deadbeefcafef00d")
    p = ScopedProfiler(str(tmp_path), steps=1, recorder=rec)
    p.maybe_start()
    p.tick()  # reaches steps -> self-stop
    events = rec.records("profile")
    assert {e["event"] for e in events} == {"start", "stop"}
    assert all(e["span"] == "deadbeefcafef00d" for e in events)


# -- zero-overhead contract --------------------------------------------------


def _wavefront_run_jaxpr(telemetry: bool) -> str:
    """The jitted run program's jaxpr for a fresh 2pc-3 engine (fresh model
    => fresh compiled-run cache), spawned with/without telemetry."""
    m = TwoPhaseSys(3)
    b = m.checker()
    if telemetry:
        b = b.telemetry(occupancy_every=1, profile_steps=1)
    c = b.spawn_tpu(sync=True, capacity=1 << 12, batch=64)
    init_fn, run_fn = c._engine(c._cap, c._qcap, c._batch, c._cand)
    carry, _ = init_fn()
    # fresh lambda per call: jax.make_jaxpr memoizes on fn identity (the
    # PR-1 double-trace lesson, analysis/jaxpr_audit.py JX104)
    return str(jax.make_jaxpr(lambda cr: run_fn(cr))(carry))


# two full engine compiles for one jaxpr diff is integration-shaped —
# the daily tier owns it; the fast tier keeps the same zero-ops pin on
# the metrics-bus surface (tests/test_observability.py)
@pytest.mark.medium
def test_telemetry_disabled_adds_zero_ops_to_step_jaxpr():
    """The flight recorder reads only host-synced state: the device program
    must be bit-identical with telemetry on and off — the PR-1 double-trace
    discipline applied to the whole step program."""
    assert _wavefront_run_jaxpr(False) == _wavefront_run_jaxpr(True)


@pytest.mark.slow
def test_telemetry_overhead_under_3pct_on_2pc7():
    """Acceptance gate: telemetry enabled costs <3% wall time on the 2PC-7
    wavefront run.  Capacities are pre-sized (no growth recompiles) and the
    engine cache is shared across all runs via one model instance, so the
    comparison times pure steady-state stepping; min-of-2 per config
    suppresses scheduler noise."""
    import time

    m = TwoPhaseSys(7)
    caps = dict(capacity=1 << 21, queue_capacity=1 << 19, batch=1024,
                steps_per_call=32, cand=1 << 14)

    def run(tele: bool) -> float:
        b = m.checker()
        if tele:
            b = b.telemetry()
        t0 = time.monotonic()
        c = b.spawn_tpu(sync=True, **caps)
        dt = time.monotonic() - t0
        assert c.unique_state_count() == TPC7_UNIQUE
        return dt

    run(False)  # warm-up: pays the engine compile once for everyone
    base = min(run(False), run(False))
    tele = min(run(True), run(True))
    overhead = tele / base - 1.0
    assert overhead < 0.03, (
        f"telemetry overhead {overhead:.1%} (off {base:.2f}s, on "
        f"{tele:.2f}s) breaks the <3% contract"
    )


@pytest.mark.slow
def test_2pc7_occupancy_time_series_pins_table_anomaly():
    """The pinned 2PC-7 occupancy time series, POST bucket-mix fix.  The
    run is deterministic (fixed caps, no RNG), so the series is exact.

    History: the pre-fix series was the first committed evidence for the
    table-size anomaly — the raw-low-bit bucket derivation
    clustered so badly that a bucket overflowed SLOTS=16 at load 0.25
    (full_buckets=1 vs poisson_full_expect=0.17, ~6x the Poisson model),
    and max_bucket rode 14-16 from mid-run on.  The fix (bucket = high
    bits of ``mix64(fp)``, ``ops/buckets.bucket_of``) must keep the same
    deterministic series INSIDE the Poisson envelope: zero full buckets
    where the model expects a fraction of one, no single-bucket-overflow
    growth at all (growth is load/queue-driven only)."""
    c = (
        TwoPhaseSys(7).checker().telemetry(occupancy_every=1, capacity=512)
        .spawn_tpu(sync=True, capacity=1 << 16, batch=1024,
                   steps_per_call=16)
    )
    assert c.unique_state_count() == TPC7_UNIQUE
    rec = c.flight_recorder
    occ = rec.records("occupancy")
    assert len(occ) >= 10, "per-sync sampling must produce a series"
    # series sanity: monotone occupancy, closing sample covers the space
    occupied = [o["occupied"] for o in occ]
    assert occupied == sorted(occupied)
    assert occ[-1]["at"] == "final"
    assert occ[-1]["occupied"] == TPC7_UNIQUE
    # growth trail: the run still grows through table_full events (the
    # <=25%-load policy), each sampled for free at the boundary
    growth = [g for g in rec.records("growth")
              if g["status"] == "table_full"]
    assert growth, "2pc-7 at 64k initial slots must grow the table"
    # THE ANOMALY IS GONE (acceptance: full buckets within 2x Poisson at
    # load 0.25, was ~6x).  Post-fix the deterministic series never
    # overflows a bucket: max_bucket tops out at 15 (observed: 15 at the
    # load-0.25 growth boundaries, 11 at the final 0.141 load), and every
    # sample's full-bucket count sits within 2x of the Poisson
    # expectation — which at these loads means zero.
    assert max(o["max_bucket"] for o in occ) <= 15
    for o in occ:
        assert o["full_buckets"] <= 2 * max(o["poisson_full_expect"], 0.5), (
            "bucket clustering is back past the Poisson envelope: "
            f"{(o['at'], o['load_factor'], o['full_buckets'], o['poisson_full_expect'])}"
        )
    # the load-0.25 window specifically (the pre-fix failure point):
    # samples exist there and carry zero full buckets
    at_quarter = [o for o in occ if 0.24 <= o["load_factor"] <= 0.26]
    assert at_quarter and all(o["full_buckets"] == 0 for o in at_quarter)


# -- /.metrics ---------------------------------------------------------------


def _get(addr, path):
    with urllib.request.urlopen(f"http://{addr}{path}") as r:
        return json.loads(r.read())


def test_explorer_metrics_endpoint_shape():
    from stateright_tpu.explorer import serve

    server = serve(
        TwoPhaseSys(3).checker().telemetry(occupancy_every=2),
        "localhost:0", block=False, strategy="tpu", sync=True,
        capacity=1 << 12, batch=64,
    )
    try:
        m = _get(server.addr, "/.metrics")
        assert sorted(m) == [
            "cartography", "counters", "durability", "fleet", "health",
            "memory", "occupancy", "roofline", "series", "spill", "summary",
        ]
        series = m["series"]
        assert sorted(series) == [
            "dedup", "load_factor", "states_per_sec", "t", "unique"
        ]
        n = len(series["t"])
        assert n >= 1
        assert all(len(series[k]) == n for k in series)
        assert m["summary"]["unique"] == 288
        assert m["occupancy"]["occupied"] == 288
        # metrics-on, cartography/memory-off: the blocks are explicit
        # nulls (the run was spawned without cartography=True /
        # memory=True), never fabricated
        assert m["cartography"] is None
        assert m["memory"] is None
        assert m["roofline"] is None
        # durability is null too: no autosave armed, no supervision trail
        assert m["durability"] is None
        # fleet is null: the recorder belongs to no fleet scheduler
        assert m["fleet"] is None
        # the health snapshot is always present with telemetry on
        assert m["health"]["phase"] == "done"
        assert m["health"]["stalled"] is False
        # /.status still works alongside
        assert _get(server.addr, "/.status")["unique_state_count"] == 288
    finally:
        server.shutdown()


def test_explorer_metrics_with_cartography():
    """/.metrics with the search counters on: the cartography block is
    populated and reconciles with the run totals."""
    from stateright_tpu.explorer import serve

    server = serve(
        TwoPhaseSys(3).checker().telemetry(cartography=True),
        "localhost:0", block=False, strategy="tpu", sync=True,
        capacity=1 << 12, batch=64,
    )
    try:
        m = _get(server.addr, "/.metrics")
        cart = m["cartography"]
        assert cart is not None and cart["v"] == 1
        assert cart["fresh_inserts"] == 288
        assert sum(cart["depth_hist"]) == 288
        assert [p["name"] for p in cart["props"]] == [
            "abort agreement", "commit agreement", "consistent"
        ]
    finally:
        server.shutdown()


def test_explorer_metrics_404_without_telemetry():
    """Telemetry off: a STABLE machine-readable error body, not bare 404
    prose (downstream pollers key on the ``error`` field)."""
    from stateright_tpu.explorer import serve

    server = serve(TwoPhaseSys(3).checker(), "localhost:0", block=False)
    server.checker.join()
    try:
        with pytest.raises(urllib.error.HTTPError) as exc:
            _get(server.addr, "/.metrics")
        assert exc.value.code == 404
        body = json.loads(exc.value.read())
        assert body["error"] == "telemetry_disabled"
        assert ".telemetry()" in body["hint"]
    finally:
        server.shutdown()
