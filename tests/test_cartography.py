"""Search cartography (ops/cartography.py + both engines), the progress/
health model (telemetry/health.py), the live watch view, and the post-run
report (telemetry/report.py).

The load-bearing contracts pinned here:

 - cartography OFF leaves the engines' run jaxpr BIT-IDENTICAL (the
   telemetry/checked/prededup discipline applied to the search counters);
 - cartography ON reconciles EXACTLY with the checker's own totals:
   ``sum(depth_hist) == unique``, ``sum(action_hist) == states - inits``,
   every property evaluated exactly ``unique`` times, and the
   duplicate/fresh split is ``states - unique`` — including across growth
   replays (an overflowed batch must count nothing);
 - the report JSON is byte-stable for a fixed model/config, with the
   single volatile field being the ``generated_at`` header;
 - ``--watch`` degrades to plain periodic lines on a non-TTY stream.

The 2pc-7 ≤5% overhead pin and the growth-heavy full-crawl parity live in
the slow/medium tier (ROADMAP tiering rule).
"""

import io
import json
import re

import pytest

import jax
import numpy as np

from stateright_tpu.models.dining import dining_model
from stateright_tpu.models.two_phase_commit import TwoPhaseSys
from stateright_tpu.telemetry.health import HealthTracker, phase_timeline

TPC3_UNIQUE = 288
TPC5_UNIQUE = 8_832
TPC7_UNIQUE = 296_448


def _reconcile(checker, n_init: int = 1, early_exit: bool = False) -> dict:
    """Assert the cartography block reconciles exactly with the checker's
    reported totals; returns the block.  ``early_exit=True`` relaxes the
    per-property evaluation count to <= unique: a run that discovered
    every property stops with queued rows never popped (the one caveat
    the ops/cartography.py invariants carve out)."""
    cart = checker.cartography()
    assert cart is not None and cart["v"] == 1
    states = checker.state_count()
    unique = checker.unique_state_count()
    assert sum(cart["depth_hist"]) == unique
    assert cart["fresh_inserts"] == unique
    assert cart["duplicate_hits"] == states - unique
    assert sum(cart["action_hist"]) == states - n_init
    for p in cart["props"]:
        if early_exit:
            assert 0 < p["evaluated"] <= unique
        else:
            assert p["evaluated"] == unique
        assert 0 <= p["condition_hits"] <= p["evaluated"]
    return cart


# -- wavefront engine --------------------------------------------------------


def test_cartography_off_leaves_run_jaxpr_bit_identical():
    """The telemetry/checked/prededup contract: the flag OFF is the
    pre-feature step program, ON actually adds the reductions."""

    def run_jaxpr(telemetry, cartography):
        m = TwoPhaseSys(3)
        b = m.checker()
        if telemetry:
            b = b.telemetry(cartography=cartography)
        c = b.spawn_tpu(sync=True, capacity=1 << 12, batch=64)
        init_fn, run_fn = c._engine(c._cap, c._qcap, c._batch, c._cand)
        carry, _ = init_fn()
        # fresh lambda per call: make_jaxpr memoizes on fn identity
        return str(jax.make_jaxpr(lambda cr: run_fn(cr))(carry))

    plain = run_jaxpr(False, False)
    assert plain == run_jaxpr(True, False)
    assert plain != run_jaxpr(True, True)


def test_wavefront_counts_reconcile_exactly():
    on = (
        TwoPhaseSys(3).checker().telemetry(cartography=True)
        .spawn_tpu(sync=True, capacity=1 << 12, batch=64)
    )
    off = TwoPhaseSys(3).checker().spawn_tpu(
        sync=True, capacity=1 << 12, batch=64
    )
    cart = _reconcile(on)
    # counters are observers: counts/discoveries identical with the flag
    assert on.unique_state_count() == off.unique_state_count() == TPC3_UNIQUE
    assert on.state_count() == off.state_count()
    assert sorted(on.discoveries()) == sorted(off.discoveries())
    # 2pc-3's space: 1 init at depth 0, diameter 10, 3 properties
    assert cart["depth_hist"][0] == 1
    assert len(cart["depth_hist"]) == 11
    assert [p["name"] for p in cart["props"]] == [
        "abort agreement", "commit agreement", "consistent"
    ]
    # the always-property "consistent" holds everywhere: hits == evaluated
    assert cart["props"][2]["condition_hits"] == TPC3_UNIQUE


def test_growth_replay_never_double_counts():
    """Grow the table mid-run (tiny initial capacity): overflowed batches
    replay after the growth transform, and the counters must come out
    exact — an overflow that counted anything would show up here."""
    c = (
        TwoPhaseSys(5).checker().telemetry(cartography=True)
        .spawn_tpu(sync=True, capacity=1 << 10, batch=256)
    )
    assert c.unique_state_count() == TPC5_UNIQUE
    growth = c.flight_recorder.records("growth")
    assert growth, "2pc-5 from 1k slots must grow"
    _reconcile(c)
    # the growth-boundary cartography series is in the ring: one record
    # per growth + the closing "final", all reconciling cumulatively
    series = c.flight_recorder.records("cartography")
    assert series and series[-1]["at"] == "final"
    assert sum(series[-1]["depth_hist"]) == TPC5_UNIQUE
    for snap in series:
        assert sum(snap["depth_hist"]) == snap["fresh_inserts"]


def test_device_growth_banks_the_true_depths_bin_by_bin():
    """The per-depth HISTOGRAM, not its sum, of a run whose queue grew on
    the device (2pc-3 at ``queue_capacity=64``: two ``queue_full`` slides).
    A growth reclaims the popped prefix of the queue and banks its depth
    lanes first, counted where they lie (``prefix_depth_hist``): the bank
    plus the lanes the final queue holds is the TRUE histogram, bin by
    bin, so the growth seam loses and misplaces nothing.

    What the run REPORTS is the bank plus ``queue_depth_hist`` of the
    queue, and that is where ROADMAP Queue 3 item 2d's wrong numbers come
    from, with or without a growth: it searches ``qdepth[:tail]`` as if
    sorted, and a batch that straddles two levels appends its children in
    the insert's order, not by depth.  Pinned as it reads, beside the
    true one, so that the repair shows here."""
    true = [1, 7, 21, 38, 50, 54, 49, 36, 21, 9, 2]
    caps = dict(sync=True, batch=32, capacity=1 << 12)
    c = TwoPhaseSys(3).checker().telemetry(cartography=True).spawn_tpu(
        queue_capacity=64, **caps
    )
    growth = c.flight_recorder.records("growth")
    assert [g["status"] for g in growth] == ["queue_full", "queue_full"]
    assert all(g["path"] == "device" for g in growth)

    def lanes(checker):
        carry = checker._final_carry
        return np.bincount(
            np.asarray(carry.q_depth)[: int(carry.tail)],
            minlength=c._cart_depth_base.size,
        )

    assert (c._cart_depth_base + lanes(c)).tolist()[: len(true)] == true
    assert int(c._cart_depth_base.sum()) + int(lanes(c).sum()) == TPC3_UNIQUE
    reported = c.cartography()["depth_hist"]
    assert reported[: len(true)] == [1, 7, 21, 38, 50, 47, 52, 46, 13, 12, 1]
    # a queue that never grew holds every lane, and reads wrong as well
    whole = TwoPhaseSys(3).checker().telemetry(cartography=True).spawn_tpu(
        queue_capacity=1 << 10, **caps
    )
    assert not whole.flight_recorder.records("growth")
    assert lanes(whole).tolist()[: len(true)] == true
    assert whole.cartography()["depth_hist"][: len(true)] == [
        1, 7, 21, 38, 50, 64, 31, 50, 13, 9, 4,
    ]


def test_resume_preserves_banked_depth_histogram():
    """Growth compactions bank consumed queue prefixes' depth lanes in
    ``_cart_depth_base``; a snapshot must carry the bank or a resumed
    histogram forgets every state popped before a pre-snapshot growth
    (regression: the bank was not in the snapshot and silently dropped,
    breaking ``sum(depth_hist) == unique`` across resume)."""
    c = TwoPhaseSys(3).checker().telemetry(cartography=True).spawn_tpu(
        sync=True, batch=32, queue_capacity=64, capacity=1 << 12
    )
    assert c.unique_state_count() == TPC3_UNIQUE
    assert c.flight_recorder.records("growth"), "qcap=64 must grow"
    snap = c.checkpoint()
    assert "cart_depth_base" in snap, "growth banked no depth lanes"
    assert int(np.asarray(snap["cart_depth_base"]).sum()) > 0
    r = TwoPhaseSys(3).checker().telemetry(cartography=True).spawn_tpu(
        sync=True, resume=snap
    )
    assert r.unique_state_count() == TPC3_UNIQUE
    assert sum(r.cartography()["depth_hist"]) == TPC3_UNIQUE


def test_checked_mode_composes_with_cartography():
    """The checked error flag and the counter tail share the carry tail;
    both features on must still reconcile exactly."""
    c = (
        TwoPhaseSys(3).checker().checked().telemetry(cartography=True)
        .spawn_tpu(sync=True, capacity=1 << 12, batch=64)
    )
    assert c.unique_state_count() == TPC3_UNIQUE
    _reconcile(c)


def test_dining_reconciles_and_fills_action_histogram():
    m = dining_model(3)
    c = m.checker().telemetry(cartography=True).spawn_tpu(
        sync=True, capacity=1 << 12, batch=64
    )
    # dining discovers every property and early-exits with rows queued:
    # histograms stay exact, per-property tallies count what actually ran
    cart = _reconcile(c, early_exit=True)
    # the histogram spans the twin's full action arity; several distinct
    # slots fire (a single hot slot would mean the column sum is
    # miswired), and — the cartography point — the padded slots the
    # compiled twin never enables are now VISIBLE as zeros
    assert len(cart["action_hist"]) == c.tensor.max_actions
    fired = sum(1 for v in cart["action_hist"] if v > 0)
    assert fired >= 3
    assert fired < len(cart["action_hist"])


# -- mesh engine --------------------------------------------------------------


def test_mesh_cartography_counts_and_shard_extras():
    c = TwoPhaseSys(3).checker().telemetry(cartography=True).spawn_tpu(
        sync=True, devices=2, capacity=1 << 12, frontier_capacity=1 << 9
    )
    cart = _reconcile(c)
    # shard extras, read off the final table: per-shard loads sum to
    # unique, the parent-owner -> child-owner matrix is 2x2 and covers
    # every non-init unique state
    assert sum(cart["shard_load"]) == TPC3_UNIQUE
    assert len(cart["route_matrix"]) == 2
    assert all(len(row) == 2 for row in cart["route_matrix"])
    assert sum(map(sum, cart["route_matrix"])) == TPC3_UNIQUE - 1
    imb = cart["shard_imbalance"]
    assert imb["ratio"] >= 1.0
    assert imb["max"] >= imb["mean"]


def test_mesh_resume_keeps_depth_histogram_and_shard_extras():
    """Resume on the mesh keeps what the one-chip engine keeps: the
    depth histogram is queue-derived and comes back COMPLETE (the
    snapshot kept the queue), the banked lanes of a pre-snapshot growth
    included; the shard extras are read off the final table, so they
    cover every state whichever run inserted it.  (The per-step tallies
    restart at zero on a resume, by the engine's own rule.)"""
    kw = dict(sync=True, devices=2, batch=32, queue_capacity=64,
              capacity=1 << 12)
    c = TwoPhaseSys(3).checker().telemetry(cartography=True).spawn_tpu(**kw)
    assert c.unique_state_count() == TPC3_UNIQUE
    assert c.flight_recorder.records("growth"), "qcap=64 must grow"
    snap = c.checkpoint()
    assert int(np.asarray(snap["cart_depth_base"]).sum()) > 0
    r = TwoPhaseSys(3).checker().telemetry(cartography=True).spawn_tpu(
        sync=True, devices=2, resume=snap
    )
    assert r.unique_state_count() == TPC3_UNIQUE
    cart = r.cartography()
    assert sum(cart["depth_hist"]) == TPC3_UNIQUE
    assert sum(cart["shard_load"]) == TPC3_UNIQUE
    assert cart["route_matrix"] == c.cartography()["route_matrix"]


# -- health model ------------------------------------------------------------


def _step(d_states, d_unique, queue=1, load=0.01, dt=0.1):
    return {
        "d_states": d_states, "d_unique": d_unique, "queue": queue,
        "load_factor": load, "dt": dt,
    }


def test_health_phases_expand_peak_drain_done():
    t = HealthTracker()
    events = []
    # ramp: fresh inserts growing -> expanding
    for n in (10, 50, 100):
        events += t.update(_step(n * 2, n))
    assert t.phase == "expanding"
    # novelty collapses to a trickle -> draining
    events += t.update(_step(200, 4))
    assert t.phase == "draining"
    # midband novelty -> peaking
    events += t.update(_step(120, 50))
    assert t.phase == "peaking"
    events += t.mark_done()
    assert t.phase == "done"
    phases = [e["phase"] for e in events if e["event"] == "phase"]
    assert phases == ["draining", "peaking", "done"]
    assert all(e["v"] == 1 for e in events)
    assert t.mark_done() == []  # idempotent


def test_health_stall_detection_and_clear():
    t = HealthTracker(stall_after=3)
    t.update(_step(100, 100))
    evs = []
    for _ in range(3):
        evs += t.update(_step(100, 0, queue=50))
    assert t.stalled and t.stall_reason == "no_fresh_inserts"
    assert [e["event"] for e in evs if "stall" in e["event"]] == ["stall"]
    evs = t.update(_step(100, 5, queue=50))
    assert not t.stalled
    assert [e["event"] for e in evs if "stall" in e["event"]] == [
        "stall_cleared"
    ]
    # an empty queue is completion-shaped, not a stall
    t2 = HealthTracker(stall_after=2)
    t2.update(_step(100, 100))
    for _ in range(5):
        t2.update(_step(100, 0, queue=0))
    assert not t2.stalled


def test_health_stall_on_pinned_table_load():
    t = HealthTracker(stall_after=3)
    for _ in range(3):
        t.update(_step(100, 60, load=0.249))
    assert t.stalled
    assert t.stall_reason == "load_pinned_at_growth_threshold"


def test_health_mark_done_closes_open_stall():
    """A run that completes while flagged stalled must emit the pairing
    ``stall_cleared`` transition — consumers pair stall/stall_cleared, so
    a finished run must never leave one open (regression: mark_done
    cleared the flag silently)."""
    t = HealthTracker(stall_after=2)
    t.update(_step(100, 100))
    for _ in range(2):
        t.update(_step(100, 0, queue=50))
    assert t.stalled
    events = t.mark_done()
    assert [e["event"] for e in events] == ["stall_cleared", "phase"]
    assert not t.stalled and t.phase == "done"
    assert t.mark_done() == []  # still idempotent


def test_health_busy_flag_overrides_missing_queue():
    """An engine with no queue count to send (the thread pool) sends
    ``busy`` explicitly;
    ``busy=False`` is completion-shaped even with no queue field, and
    ``busy=True`` arms the zero-novelty stall guard."""
    t = HealthTracker(stall_after=2)
    t.update({"d_states": 100, "d_unique": 100, "dt": 0.1, "busy": True})
    for _ in range(5):
        t.update({"d_states": 100, "d_unique": 0, "dt": 0.1, "busy": False})
    assert not t.stalled  # drained frontier, not a stall
    t2 = HealthTracker(stall_after=2)
    t2.update({"d_states": 100, "d_unique": 100, "dt": 0.1, "busy": True})
    for _ in range(2):
        t2.update({"d_states": 100, "d_unique": 0, "dt": 0.1, "busy": True})
    assert t2.stalled and t2.stall_reason == "no_fresh_inserts"


def test_health_eta_only_while_draining():
    t = HealthTracker()
    t.update(_step(1000, 800, queue=500, dt=1.0))
    assert t.snapshot()["eta_secs"] is None  # expanding: no honest ETA
    for _ in range(3):
        t.update(_step(1000, 10, queue=400, dt=1.0))
    snap = t.snapshot()
    assert t.phase == "draining" and snap["eta_secs"] is not None
    assert snap["frontier"] == 400


def test_health_eta_uses_queue_drain_rate_not_fresh_rate():
    """The queue empties at the pop rate minus the insert rate; during
    draining the fresh-insert rate tends to zero by definition, so an
    ETA divided by it would overestimate without bound (regression)."""
    t = HealthTracker()
    t.update(_step(100_000, 80_000, queue=100_000, dt=1.0))
    # drains 50k rows/sec while the fresh rate has collapsed to 1k/sec
    t.update(_step(100_000, 1_000, queue=50_000, dt=1.0))
    t.update(_step(100_000, 1_000, queue=10_000, dt=1.0))
    snap = t.snapshot()
    assert t.phase == "draining"
    # true drain: ~10k rows at a smoothed ~40k rows/s => well under 1s;
    # the old fresh-rate divisor would have claimed ~10 seconds
    assert snap["eta_secs"] is not None and snap["eta_secs"] < 2.0


def test_recorder_emits_health_transitions_and_close():
    from stateright_tpu.telemetry import FlightRecorder

    rec = FlightRecorder()
    rec.step(engine="x", states=100, unique=90, queue=10)
    for i in range(8):
        rec.step(engine="x", states=200 + i, unique=90, queue=10)
    kinds = [
        (r["event"], r.get("reason")) for r in rec.records("health")
    ]
    assert ("stall", "no_fresh_inserts") in kinds
    rec.close_run(done=True)
    rec.close_run(done=True)  # idempotent: exactly one done record
    phases = [r["phase"] for r in rec.records("health")
              if r["event"] == "phase"]
    assert phases.count("done") == 1
    assert rec.health()["phase"] == "done"


def test_jsonl_replay_keeps_health_events_verbatim(tmp_path):
    """Exported health records replay verbatim; replayed steps must not
    regenerate them (each event would otherwise appear twice)."""
    from stateright_tpu.telemetry import FlightRecorder

    rec = FlightRecorder()
    rec.step(engine="x", states=100, unique=90, queue=10)
    for i in range(8):
        rec.step(engine="x", states=200 + i, unique=90, queue=10)
    rec.close_run()
    n_health = len(rec.records("health"))
    assert n_health >= 2  # stall + done at minimum
    path = tmp_path / "t.jsonl"
    rec.to_jsonl(path)
    back = FlightRecorder.from_jsonl(path)
    assert len(back.records("health")) == n_health
    assert [r["event"] for r in back.records("health")] == [
        r["event"] for r in rec.records("health")
    ]


def test_phase_timeline_is_deterministic_and_count_derived():
    recs = [
        _step(20, 10), _step(200, 100), _step(220, 100), _step(300, 5),
    ]
    a, b = phase_timeline(recs), phase_timeline(recs)
    assert a == b
    assert [e["phase"] for e in a] == [
        "expanding", "expanding", "expanding", "draining"
    ]
    # wall-clock signals never leak into the deterministic series
    assert all(set(e) == {"step", "unique", "d_unique", "novelty", "phase"}
               for e in a)


def test_checker_health_surface_end_to_end():
    c = (
        TwoPhaseSys(3).checker().telemetry(cartography=True)
        .spawn_tpu(sync=True, capacity=1 << 12, batch=64)
    )
    h = c.flight_recorder.health()
    assert h["phase"] == "done" and h["stalled"] is False
    assert h["v"] == 1


# -- post-run report ---------------------------------------------------------


def _strip_stamp(text: str) -> str:
    # the volatile header is stripped BY SCHEMA (report.VOLATILE_KEYS):
    # a new volatile identity field added there is covered here for free
    from stateright_tpu.telemetry.report import VOLATILE_KEYS

    for k in VOLATILE_KEYS:
        text = re.sub(rf'"{k}": "[^"]*"', f'"{k}": "X"', text)
    return text


def test_report_json_is_byte_stable_across_runs(tmp_path):
    def run(path):
        TwoPhaseSys(3).checker().report(str(path)).spawn_tpu(
            sync=True, capacity=1 << 12, batch=64
        )
        return path.read_text()

    a = run(tmp_path / "a.json")
    b = run(tmp_path / "b.json")
    assert _strip_stamp(a) == _strip_stamp(b)
    # the volatile fields are EXACTLY the identity header, leading the
    # document (report.VOLATILE_KEYS is the schema the diff engine
    # scrubs by)
    from stateright_tpu.telemetry.report import VOLATILE_KEYS

    doc = json.loads(a)
    head = [k for k in doc if k in VOLATILE_KEYS]
    assert list(doc)[: len(head)] == head
    assert list(doc)[0] == "generated_at"
    assert "run_id" in head


def test_report_contents_and_markdown(tmp_path):
    path = tmp_path / "run.json"
    c = TwoPhaseSys(3).checker().report(str(path)).spawn_tpu(
        sync=True, capacity=1 << 12, batch=64
    )
    doc = json.loads(path.read_text())
    assert doc["v"] == 1
    assert doc["model"] == "TwoPhaseSys" and doc["engine"] == "wavefront"
    assert doc["totals"]["unique"] == TPC3_UNIQUE
    assert doc["totals"]["done"] is True
    assert doc["cartography"]["fresh_inserts"] == TPC3_UNIQUE
    assert doc["final_phase"] == "done"
    assert doc["growth_events"] == []  # pre-sized: no growth
    assert doc["health_timeline"], "step stream must be replayed"
    names = {p["name"]: p for p in doc["properties"]}
    assert names["abort agreement"]["discovery"] is True
    assert names["consistent"]["discovery"] is False
    # audit ran at spawn preflight: status travels with the report
    assert doc["audit"]["ok"] is True
    # the sibling markdown rendering exists and carries the sections
    md = (tmp_path / "run.md").read_text()
    for section in ("# Run report", "## Properties",
                    "## Search cartography", "## Health timeline",
                    "## Wall clock (non-deterministic)"):
        assert section in md
    # builder contract: .report() implied cartography telemetry
    assert c.cartography() is not None


def test_implied_cartography_survives_telemetry_reconfig(tmp_path):
    """``.report()``/``.cartography()`` imply the counters; a later
    ``.telemetry(...)`` reconfiguring the recorder (e.g. enlarging the
    ring for a long run) must not silently drop them."""
    path = tmp_path / "sticky.json"
    b = TwoPhaseSys(3).checker().report(str(path)).telemetry(capacity=1 << 14)
    assert b.telemetry_opts["cartography"] is True
    assert b.telemetry_opts["capacity"] == 1 << 14
    c = b.spawn_tpu(sync=True, capacity=1 << 12, batch=64)
    assert c.cartography() is not None
    assert json.loads(path.read_text())["cartography"]["fresh_inserts"] == \
        TPC3_UNIQUE


def test_report_rejects_md_target_path(tmp_path):
    """A ``.md`` report target would collapse the JSON body and the
    markdown sibling onto one file — refused up front, at build time."""
    import pytest

    with pytest.raises(ValueError, match="ends in .md"):
        TwoPhaseSys(3).checker().report(str(tmp_path / "run.md"))
    # same guard at the write layer (direct write_report callers)
    from stateright_tpu.telemetry.report import write_report

    with pytest.raises(ValueError, match="ends in .md"):
        write_report(object(), str(tmp_path / "direct.md"))


def test_report_written_once_at_join_for_async_runs(tmp_path):
    path = tmp_path / "async.json"
    c = TwoPhaseSys(3).checker().report(str(path)).spawn_tpu(
        capacity=1 << 12, batch=64
    )
    c.join()
    stamp = path.read_text()
    c.join()  # second join must not rewrite (generated_at would move)
    assert path.read_text() == stamp


def test_report_cli_verb(tmp_path, capsys):
    from stateright_tpu.models.two_phase_commit import main

    out = tmp_path / "cli.json"
    main(["report", f"--out={out}", "3"])
    assert "report written to" in capsys.readouterr().out
    doc = json.loads(out.read_text())
    assert doc["totals"]["unique"] == TPC3_UNIQUE
    assert (tmp_path / "cli.md").exists()


def test_report_marks_deadline_cut_runs_incomplete(tmp_path):
    """is_done() means STOPPED, not finished: a deadline-cut run's report
    must say done=false / timed_out=true, and its health phase must stay
    where the run actually was (regression: the report claimed
    completion — the exact artifact-misreads-the-run failure it exists
    to prevent)."""
    path = tmp_path / "cut.json"
    # the deadline fires during engine compile, so the run is cut at its
    # first host sync — deterministic on any machine
    c = (
        TwoPhaseSys(5).checker().timeout(0.05).report(str(path))
        .spawn_tpu(sync=True, capacity=1 << 15, batch=256)
    )
    c.join()
    assert c.timed_out
    body = json.loads(path.read_text())
    assert body["totals"]["done"] is False
    assert body["totals"]["timed_out"] is True
    assert body["final_phase"] != "done"
    assert "cut short" in (tmp_path / "cut.md").read_text()


def test_stall_reason_switch_emits_transition():
    """While already stalled, the cause can change (a fresh insert clears
    the novelty counter on a step where the load counter is already over
    threshold); the live reason and the timeline must name the actual
    cause (regression: the first reason stuck for the stall's life)."""
    t = HealthTracker(stall_after=2)
    evs = []
    evs += t.update(_step(100, 100, load=0.249))
    evs += t.update(_step(100, 0, load=0.249))
    assert t.stalled and t.stall_reason == "load_pinned_at_growth_threshold"
    evs += t.update(_step(100, 0, load=0.249))
    assert t.stalled and t.stall_reason == "no_fresh_inserts"
    stall_evs = [e for e in evs if e["event"] == "stall"]
    assert [e["reason"] for e in stall_evs] == [
        "load_pinned_at_growth_threshold", "no_fresh_inserts"
    ]


def test_pool_runs_never_flag_zero_novelty_stalls():
    """Thread-pool job blocks carry un-deduped successors, so a
    duplicate-heavy tail legitimately produces zero fresh inserts —
    the pool opts out of the stall heuristic with ``busy=False``
    (regression: ``queue`` was the just-processed block size, always
    positive, arming spurious stall records on converging runs)."""
    c = TwoPhaseSys(3).checker().telemetry().spawn_bfs().join()
    assert c.unique_state_count() == TPC3_UNIQUE
    rec = c.flight_recorder
    assert rec.records("step"), "pool runs must record steps"
    assert all(r.get("busy") is False for r in rec.records("step"))
    assert not [r for r in rec.records("health") if r["event"] == "stall"]


def test_report_flags_ring_truncated_timeline(tmp_path):
    """A run with more host syncs than the telemetry ring holds must say
    so — a silently mid-run timeline misclassifies phases (the true
    peak is evicted)."""
    path = tmp_path / "trunc.json"
    c = (
        TwoPhaseSys(3).checker().telemetry(capacity=4).report(str(path))
        .spawn_tpu(sync=True, capacity=1 << 12, batch=16, steps_per_call=1)
    )
    c.join()
    assert c.flight_recorder.kind_count("step") > 4
    body = json.loads(path.read_text())
    assert body.get("health_timeline_truncated") is True
    assert "truncated" in (tmp_path / "trunc.md").read_text()


def test_report_written_by_host_strategies(tmp_path):
    """``.report(PATH)`` is honored at the first join() on EVERY strategy,
    not just the device engines (regression: the report verb's host-BFS
    fallback printed success without writing anything)."""
    from stateright_tpu.models._cli import report_models
    from stateright_tpu.models.quickstart import FizzBuzz

    path = tmp_path / "bfs.json"
    FizzBuzz(8).checker().report(str(path)).spawn_bfs().join()
    body = json.loads(path.read_text())
    assert body["v"] == 1 and body["totals"]["done"]
    assert "cartography" not in body  # host run: no device counters
    assert (tmp_path / "bfs.md").exists()

    # the twinless report_models fallback path writes what it advertises
    out = tmp_path / "fallback.json"
    stream = io.StringIO()
    paths = report_models([("fizzbuzz", FizzBuzz(8))], str(out), stream)
    assert paths == [str(out)]
    assert "no device twin" in stream.getvalue()
    assert json.loads(out.read_text())["totals"]["done"]


# -- live watch view ---------------------------------------------------------


class _FakeTty(io.StringIO):
    def isatty(self):
        return True


def test_watch_line_reads_live_surfaces():
    from stateright_tpu.models._cli import watch_line

    c = (
        TwoPhaseSys(3).checker().telemetry(cartography=True)
        .spawn_tpu(sync=True, capacity=1 << 12, batch=64)
    )
    line = watch_line(c)
    assert "states=1146" in line and "unique=288" in line
    assert "phase=done" in line
    assert "depth=10" in line


def test_watch_non_tty_degrades_to_plain_lines():
    """CI/pipe smoke: no carriage returns, no ANSI escapes — one plain
    line per refresh window plus the final line."""
    from stateright_tpu.models._cli import watch_checker

    c = (
        TwoPhaseSys(3).checker().telemetry(cartography=True)
        .spawn_tpu(sync=True, capacity=1 << 12, batch=64)
    )
    buf = io.StringIO()  # isatty() -> False
    watch_checker(c, stream=buf)
    out = buf.getvalue()
    assert out.endswith("\n")
    assert "\r" not in out and "\x1b" not in out
    assert "unique=288" in out


def test_watch_tty_rewrites_in_place():
    from stateright_tpu.models._cli import watch_checker

    c = (
        TwoPhaseSys(3).checker().telemetry(cartography=True)
        .spawn_tpu(sync=True, capacity=1 << 12, batch=64)
    )
    buf = _FakeTty()
    watch_checker(c, stream=buf)
    out = buf.getvalue()
    assert "\r" in out and out.endswith("\n")
    assert "\x1b" not in out  # plain rewrite, no ANSI
    assert "unique=288" in out


def test_watch_flag_pops_and_arms_telemetry():
    from stateright_tpu.models._cli import apply_watch, pop_watch

    watch, rest = pop_watch(["3", "--watch"])
    assert watch is True and rest == ["3"]
    watch2, rest2 = pop_watch(["3"])
    assert watch2 is False and rest2 == ["3"]
    b = TwoPhaseSys(3).checker()
    b = apply_watch(b, True)
    assert b.telemetry_opts["cartography"] is True
    # watch over an existing telemetry config only ADDS cartography
    b2 = TwoPhaseSys(3).checker().telemetry(occupancy_every=4)
    b2 = apply_watch(b2, True)
    assert b2.telemetry_opts["occupancy_every"] == 4
    assert b2.telemetry_opts["cartography"] is True


# -- overhead + heavy parity (slow/medium tier) ------------------------------


@pytest.mark.slow
def test_cartography_overhead_under_5pct_on_2pc7():
    """Acceptance gate: the on-device counters cost <=5% wall time on the
    2PC-7 wavefront run (same protocol as the telemetry <3% pin:
    pre-sized capacities, shared engine cache, min-of-2)."""
    import time

    m = TwoPhaseSys(7)
    caps = dict(capacity=1 << 21, queue_capacity=1 << 19, batch=1024,
                steps_per_call=32, cand=1 << 14)

    def run(cart: bool) -> float:
        b = m.checker()
        if cart:
            b = b.telemetry(cartography=True)
        t0 = time.monotonic()
        c = b.spawn_tpu(sync=True, **caps)
        dt = time.monotonic() - t0
        assert c.unique_state_count() == TPC7_UNIQUE
        return dt

    run(False)  # warm-up
    run(True)   # warm-up the cartography engine variant too
    base = min(run(False), run(False))
    cart = min(run(True), run(True))
    overhead = cart / base - 1.0
    assert overhead < 0.05, (
        f"cartography overhead {overhead:.1%} (off {base:.2f}s, on "
        f"{cart:.2f}s) breaks the <=5% contract"
    )


@pytest.mark.slow
def test_cartography_full_crawl_reconciles_on_2pc7():
    """Full-crawl reconciliation at scale, through the real growth ladder
    (daily tier): the counters stay exact across hundreds of syncs and
    multiple growth replays."""
    c = (
        TwoPhaseSys(7).checker().telemetry(cartography=True)
        .spawn_tpu(sync=True, capacity=1 << 16, batch=1024,
                   steps_per_call=16)
    )
    assert c.unique_state_count() == TPC7_UNIQUE
    cart = _reconcile(c)
    assert c.flight_recorder.records("growth")
    # depth histogram covers the full 2pc-7 diameter
    depth = np.asarray(cart["depth_hist"])
    assert depth[0] == 1 and depth.sum() == TPC7_UNIQUE
