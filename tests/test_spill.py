"""Billion-state spill tier (stateright_tpu/spill/, docs/spill.md).

Pins the round's contracts:

 - EXACTNESS: a run under a simulated device budget provably smaller
   than its steady-state footprint COMPLETES with bit-identical
   unique/total counts and property verdicts vs an unconstrained run,
   and its cartography block reconciles exactly (the acceptance
   criterion; 2pc-5 in the fast tier, 2pc-7 in the slow tier);
 - ZERO JAXPR IMPACT off: spill off leaves the step jaxpr bit-identical
   and the engine cache unkeyed (the telemetry/checked/prededup/por
   discipline);
 - NO FALSE NEGATIVES: every spilled fingerprint tests Bloom-positive
   on device (host mirror and device test agree bit-for-bit), so
   exactness reduces to the host index's verdict;
 - kill+resume MID-SPILL: the snapshot manifest carries the host/disk
   tier contents (and in-flight pending/offloaded rows); resumed totals
   are exact; ``snapshot_fits_guard`` accounts the HOT tier only;
 - the tiers themselves: HostIndex/SpillStore units incl. the mmap'd
   disk tier, the spill-aware ``capacity_plan`` column, the health
   model's growth_oom_risk -> spill_forecast downgrade, and the
   mesh/POR rejection guards.
"""

import io

import numpy as np
import pytest

import jax

from stateright_tpu.models.two_phase_commit import TwoPhaseSys
from stateright_tpu.ops.hashing import EMPTY
from stateright_tpu.parallel.tensor_model import twin_or_none
from stateright_tpu.spill import (
    SPILL_V,
    HostIndex,
    SpillStore,
    bloom_est_false_pos,
    bloom_set_np,
    bloom_test,
    bloom_test_np,
)
from stateright_tpu.telemetry.memory import (
    ENV_DEVICE_BYTES,
    capacity_plan,
    snapshot_fits_guard,
    total_bytes,
    wavefront_specs,
)

BATCH = 128
BLOOM = 1 << 14
QCAP = 4096


def _budget_for(n: int, cap_fit: int, *, batch: int = BATCH,
                qcap: int = QCAP) -> int:
    """A simulated device budget that admits the ``cap_fit`` table rung
    but NOT the next migration transient — forcing eviction."""
    m = TwoPhaseSys(n)
    twin = twin_or_none(m)
    n_props = len(list(m.properties()))
    sp = (BLOOM, batch * twin.max_actions)

    def tot(cap):
        return total_bytes(
            wavefront_specs(twin, n_props, cap, qcap, batch, spill=sp)
        )

    return tot(cap_fit) + tot(cap_fit * 2) - 1


def _spawn_spill(n: int, budget: int, monkeypatch, *, sync=True,
                 batch: int = BATCH, qcap: int = QCAP, **kw):
    monkeypatch.setenv(ENV_DEVICE_BYTES, str(budget))
    monkeypatch.setenv("STATERIGHT_TPU_CAPACITY_GUARD", "off")
    b = TwoPhaseSys(n).checker().spill()
    tel = kw.pop("telemetry", None)
    if tel:
        b = b.telemetry(**tel)
    kw.setdefault("steps_per_call", 8)
    return b.spawn_tpu(
        sync=sync, capacity=1 << 12, batch=batch, queue_capacity=qcap,
        spill_bloom_bits=BLOOM, **kw,
    )


# -- the tiers: HostIndex / SpillStore / Bloom -------------------------------


def test_host_index_insert_lookup_growth():
    rng = np.random.default_rng(7)
    fps = np.unique(rng.integers(1, 2**63, 20000, dtype=np.uint64))
    vals = fps ^ np.uint64(0xABCD)
    ix = HostIndex(capacity=16)  # tiny: forces repeated growth
    ix.insert(fps[:5000], vals[:5000])
    got, found = ix.lookup(fps)
    assert found[:5000].all() and not found[5000:].any()
    assert (got[:5000] == vals[:5000]).all()
    # duplicate re-insert: first writer wins
    ix.insert(fps, vals + np.uint64(1))
    got2, found2 = ix.lookup(fps)
    assert found2.all()
    assert (got2[:5000] == vals[:5000]).all()
    assert (got2[5000:] == vals[5000:] + np.uint64(1)).all()
    assert len(ix) == fps.size
    # load stays <= 50%
    assert len(ix) * 2 <= ix.capacity


def test_host_index_intra_batch_duplicates_keep_first():
    fps = np.asarray([5, 9, 5, 9, 5], np.uint64)
    vals = np.asarray([1, 2, 3, 4, 5], np.uint64)
    ix = HostIndex()
    ix.insert(fps, vals)
    got, found = ix.lookup(np.asarray([5, 9], np.uint64))
    assert found.all()
    assert got.tolist() == [1, 2]
    assert len(ix) == 2


def test_spill_store_ram_tier_and_contains():
    store = SpillStore()  # no budget: never flushes
    fps = np.arange(1, 1001, dtype=np.uint64)
    store.append(fps, fps + np.uint64(10))
    assert len(store) == 1000
    assert store.host_bytes == 1000 * 16
    assert store.disk_bytes == 0
    assert store.contains(fps).all()
    assert not store.contains(np.asarray([5000], np.uint64)).any()
    # re-appending already-spilled fps is a no-op
    assert store.append(fps[:10], fps[:10]) == 0
    assert len(store) == 1000


def test_spill_store_disk_tier_flush_and_roundtrip(tmp_path):
    store = SpillStore(directory=str(tmp_path), host_budget=4096)
    fps = np.arange(1, 2001, dtype=np.uint64)
    store.append(fps[:1000], fps[:1000])
    assert store.disk_bytes > 0, "tiny host budget must flush to disk"
    assert store.host_bytes == 0
    store.append(fps[1000:], fps[1000:])
    assert store.contains(fps).all()
    assert len(list(tmp_path.glob("spill-*.bin"))) >= 1
    # the portable snapshot form round-trips every tier
    f, p = store.to_arrays()
    assert sorted(f.tolist()) == fps.tolist()
    assert (p == f).all()
    back = SpillStore.from_arrays(f, p)
    assert len(back) == 2000 and back.contains(fps).all()
    # lifecycle: close() releases the mmap handles and (on request)
    # removes the segment files — a campaign must not leak disk
    store.close(delete=True)
    assert not list(tmp_path.glob("spill-*.bin"))
    store.close()  # idempotent


def test_bloom_no_false_negatives_and_device_host_agreement():
    rng = np.random.default_rng(3)
    fps = np.unique(rng.integers(1, 2**63, 8000, dtype=np.uint64))
    members, probes = fps[:4000], fps[4000:]
    words = np.zeros(BLOOM // 32, np.uint32)
    bloom_set_np(words, members)
    # NO false negatives, ever — the exactness contract's foundation
    assert bloom_test_np(words, members).all()
    dev = np.asarray(
        bloom_test(jax.numpy.asarray(words), jax.numpy.asarray(fps), BLOOM)
    )
    assert (dev == bloom_test_np(words, fps)).all()
    # probes are not members: positives here are the (bounded) FP rate
    fp_rate = float(bloom_test_np(words, probes).mean())
    assert fp_rate < 1.0
    assert 0.0 < bloom_est_false_pos(4000, BLOOM) < 1.0
    assert bloom_est_false_pos(0, BLOOM) == 0.0


# -- analytic model exactness with the tier armed ----------------------------


def test_spill_analytic_bytes_reconcile_exactly(monkeypatch):
    """The ledger's per-buffer model must cover the spill carry tail
    (bloom, pending, scalars) exactly — the budget decisions hang off
    these bytes."""
    budget = _budget_for(5, 1 << 13)
    c = _spawn_spill(
        5, budget, monkeypatch, telemetry={"memory": True}
    )
    specs = c._memory_spec_fn()(
        {"cap": c._cap, "qcap": c._qcap, "batch": c._batch}
    )
    carry = jax.tree.leaves(c._final_carry)
    assert len(specs) == len(carry)
    for s, arr in zip(specs, carry):
        a = np.asarray(arr)
        assert a.nbytes == s.nbytes, (s.name, a.nbytes, s.nbytes)
        assert a.shape == s.shape, (s.name, a.shape, s.shape)
    names = [s.name for s in specs]
    for expect in ("spill_bloom", "pend_fp", "pend_rows", "spill_stats"):
        assert expect in names


# -- zero jaxpr impact off + unkeyed cache -----------------------------------


def _build_jaxpr(checker) -> str:
    init_fn, run_fn = checker._build(
        checker._cap, checker._qcap, checker._batch, checker._cand
    )
    carry, _ = init_fn()
    return str(jax.make_jaxpr(lambda cr: run_fn(cr))(carry))


def test_spill_off_leaves_step_jaxpr_bit_identical():
    """Spill OFF is exactly the pre-spill engine: same step jaxpr, same
    engine-cache key shape — even after a spill-on engine was built on
    the same tensor twin (no leakage through the cached twin)."""
    kw = dict(sync=True, capacity=1 << 12, batch=64)
    plain = TwoPhaseSys(3).checker().spawn_tpu(**kw)
    base_jaxpr = _build_jaxpr(plain)
    base_key = plain._engine_key(
        plain._cap, plain._qcap, plain._batch, plain._cand
    )
    assert not any(
        isinstance(e, str) and e == "spill" for e in base_key
    )
    on = TwoPhaseSys(3).checker().spill().spawn_tpu(
        spill_bloom_bits=BLOOM, **kw
    )
    assert "spill" in on._engine_key(on._cap, on._qcap, on._batch, on._cand)
    off_again = TwoPhaseSys(3).checker().spawn_tpu(**kw)
    assert _build_jaxpr(off_again) == base_jaxpr
    assert (
        off_again._engine_key(
            off_again._cap, off_again._qcap, off_again._batch,
            off_again._cand,
        )
        == base_key
    )


# -- the acceptance criterion: complete + reconcile under a small budget -----


def _parity_run(n, budget, monkeypatch, **kw):
    base = TwoPhaseSys(n).checker().spawn_tpu(
        sync=True, capacity=1 << 12, batch=kw.get("batch", BATCH)
    )
    c = _spawn_spill(
        n, budget, monkeypatch,
        telemetry={"cartography": True, "memory": True}, **kw,
    )
    assert c.state_count() == base.state_count()
    assert c.unique_state_count() == base.unique_state_count()
    assert sorted(c.discoveries()) == sorted(base.discoveries())
    return base, c


def test_2pc5_under_budget_completes_bit_identical(monkeypatch):
    """A 2pc-5 run under a budget smaller than its steady-state
    footprint completes, forces eviction, and reconciles: counts and
    property verdicts bit-identical to the unconstrained run, the
    cartography block exact, the spill tallies consistent."""
    budget = _budget_for(5, 1 << 13)
    # the budget is provably smaller than the unconstrained steady state
    m = TwoPhaseSys(5)
    twin = twin_or_none(m)
    steady = total_bytes(wavefront_specs(
        twin, len(list(m.properties())), 1 << 16, QCAP, BATCH,
        spill=(BLOOM, BATCH * twin.max_actions),
    ))
    assert budget < steady
    base, c = _parity_run(5, budget, monkeypatch)
    sp = c.spill_status()
    assert sp["v"] == SPILL_V and sp["enabled"]
    assert sp["evictions"] >= 1, "budget did not force a single eviction"
    assert sp["spilled_fps"] > 0
    assert sp["host_bytes"] == sp["spilled_fps"] * 16
    assert sp["resolved_novel"] + sp["resolved_dups"] > 0
    # spilled + hot == unique (the tiers partition the visited set)
    hot = int(
        (np.asarray(c._final_carry.table_fp) != np.uint64(EMPTY)).sum()
    )
    assert hot + sp["spilled_fps"] == c.unique_state_count()
    # cartography reconciles EXACTLY across evictions/injections
    cart = c.cartography()
    assert sum(cart["depth_hist"]) == c.unique_state_count()
    assert cart["fresh_inserts"] == c.unique_state_count()
    assert sum(cart["action_hist"]) == c.state_count() - len(
        TwoPhaseSys(5).init_states()
    )
    assert cart["duplicate_hits"] == c.state_count() - c.unique_state_count()


# a budget-starved end-to-end run through the queue-offload path is
# integration-shaped — the daily tier owns it (870s fast-tier budget)
@pytest.mark.medium
def test_queue_offload_under_queue_blocking_budget(monkeypatch):
    """A budget that blocks the QUEUE doubling too: the frontier's tail
    excess rides the host FIFO and refills at drain — counts still
    bit-identical, every offloaded row refilled."""
    m = TwoPhaseSys(5)
    twin = twin_or_none(m)
    n_props = len(list(m.properties()))
    batch, qcap = 64, 512
    sp = (BLOOM, batch * twin.max_actions)
    steady = total_bytes(
        wavefront_specs(twin, n_props, 8192, qcap, batch, spill=sp)
    )
    budget = 2 * steady - 1
    base, c = _parity_run(
        5, budget, monkeypatch, batch=batch, qcap=qcap, steps_per_call=4
    )
    sp_st = c.spill_status()
    assert sp_st["queue_offloaded"] > 0
    assert sp_st["queue_offloaded"] == sp_st["queue_refilled"]
    assert sp_st["queue_host_rows"] == 0  # every tier drained at the end


def test_offloaded_rows_keep_depth_histogram_reconciling(monkeypatch):
    """A run that ENDS with frontier rows still in the host FIFO (target
    early-exit) must still reconcile its depth histogram: offloaded
    rows' depth lanes are banked at offload and un-banked at refill, so
    sum(depth_hist) == unique at every sync — not only after a full
    drain."""
    m = TwoPhaseSys(5)
    twin = twin_or_none(m)
    n_props = len(list(m.properties()))
    batch, qcap = 64, 512
    sp = (BLOOM, batch * twin.max_actions)
    steady = total_bytes(
        wavefront_specs(twin, n_props, 8192, qcap, batch, spill=sp)
    )
    monkeypatch.setenv(ENV_DEVICE_BYTES, str(2 * steady - 1))
    monkeypatch.setenv("STATERIGHT_TPU_CAPACITY_GUARD", "off")
    c = (
        TwoPhaseSys(5).checker().spill()
        .telemetry(cartography=True)
        .target_states(6000)
        .spawn_tpu(
            sync=True, capacity=1 << 12, batch=batch, queue_capacity=qcap,
            spill_bloom_bits=BLOOM, steps_per_call=4,
        )
    )
    sp_st = c.spill_status()
    assert sp_st["queue_offloaded"] > 0, "budget did not force an offload"
    assert sp_st["queue_host_rows"] > 0, (
        "target run was expected to END with rows still offloaded"
    )
    cart = c.cartography()
    assert sum(cart["depth_hist"]) == c.unique_state_count()
    assert cart["fresh_inserts"] == c.unique_state_count()


def test_spill_trace_reconstruction_spans_tiers(monkeypatch):
    """Discovery traces walk parent chains that cross the hot/host tier
    boundary: reconstruction must merge the spilled parents back."""
    budget = _budget_for(5, 1 << 13)
    c = _spawn_spill(5, budget, monkeypatch)
    assert c.spill_status()["evictions"] >= 1
    disc = c.discoveries()
    assert disc  # 2pc-5 has sometimes-properties with examples
    base = TwoPhaseSys(5).checker().spawn_tpu(
        sync=True, capacity=1 << 12, batch=BATCH
    )
    base_disc = base.discoveries()
    for name, path in disc.items():
        assert len(path) >= 1
        assert name in base_disc
    c.assert_properties()


# -- kill + resume mid-spill -------------------------------------------------


def test_kill_and_resume_mid_spill_totals_exact(monkeypatch):
    """Checkpoint after the first eviction, kill, resume: the manifest
    carries the host-tier contents (and survives an npz round trip), the
    resumed totals are exact, and resuming WITHOUT the tier armed is
    refused with guidance."""
    import time

    base = TwoPhaseSys(5).checker().spawn_tpu(
        sync=True, capacity=1 << 12, batch=BATCH
    )
    budget = _budget_for(5, 1 << 13)
    running = _spawn_spill(
        5, budget, monkeypatch, sync=False, steps_per_call=2
    )
    snap = None
    for _ in range(500):
        if running.is_done():
            break
        s = running.checkpoint(timeout=120.0)
        if int(s.get("spill_base", 0)) > 0 and int(s["tail"]) > int(s["head"]):
            snap = s
            break
        time.sleep(0.01)
    assert snap is not None, "never caught a mid-spill checkpoint"
    running.stop()
    running.join()
    assert "spill_fp" in snap and "spill_parent" in snap
    assert int(snap["spill_base"]) == len(np.asarray(snap["spill_fp"]))
    # npz round trip (process-restart shape)
    buf = io.BytesIO()
    np.savez(buf, **dict(snap))
    buf.seek(0)
    snap2 = dict(np.load(buf, allow_pickle=False))
    resumed = (
        TwoPhaseSys(5).checker().spill()
        .spawn_tpu(sync=True, resume=snap2, spill_bloom_bits=BLOOM)
    )
    assert resumed.unique_state_count() == base.unique_state_count()
    assert resumed.state_count() == base.state_count()
    assert sorted(resumed.discoveries()) == sorted(base.discoveries())
    resumed.assert_properties()
    # the resumed hot tier stayed budget-pinned: the restored store's
    # length must feed the growth trigger (a resume that forgot the
    # spill base would balloon the table past the budget)
    assert resumed._cap <= 1 << 15
    assert resumed.spill_status()["spilled_fps"] > 0
    with pytest.raises(ValueError, match="spill-tier contents"):
        TwoPhaseSys(5).checker().spawn_tpu(sync=True, resume=snap2)


def test_snapshot_fits_guard_accounts_hot_tier_only(monkeypatch, capsys):
    """The resume capacity guard must not count the host-resident
    spill_* manifest arrays against the DEVICE budget: a snapshot whose
    hot tier fits passes even when its spilled contents dwarf it."""
    snap = {
        "table_fp": np.zeros(1024, np.uint64),
        "spill_fp": np.zeros(1 << 20, np.uint64),  # 8MB of HOST data
        "spill_parent": np.zeros(1 << 20, np.uint64),
    }
    monkeypatch.setenv(ENV_DEVICE_BYTES, str(64 * 1024))
    monkeypatch.delenv("STATERIGHT_TPU_CAPACITY_GUARD", raising=False)
    snapshot_fits_guard(snap, "test")  # must not warn
    assert "capacity guard" not in capsys.readouterr().err
    # ...and the hot tier still gates: inflate it past the budget
    snap["table_fp"] = np.zeros(1 << 20, np.uint64)
    snapshot_fits_guard(snap, "test")
    assert "capacity guard" in capsys.readouterr().err


# -- capacity plan + health downgrade + telemetry surfaces -------------------


def test_capacity_plan_spill_column_extends_max_unique(monkeypatch):
    m = TwoPhaseSys(3)
    twin = twin_or_none(m)
    n_props = len(list(m.properties()))

    def spec_fn(c):
        return wavefront_specs(
            twin, n_props, int(c["cap"]), int(c["qcap"]), int(c["batch"])
        )

    caps = {"cap": 1 << 12, "qcap": 1 << 11, "batch": 64}
    budget = total_bytes(spec_fn(caps)) * 8
    plain = capacity_plan(spec_fn, caps, budget=budget)
    sp = capacity_plan(
        spec_fn, caps, budget=budget, spill=True,
        spill_host_bytes=1 << 30,
    )
    assert "spill" not in plain
    assert sp["spill"]["hot_max_unique"] == plain["max_unique"]
    assert sp["spill"]["host_max_unique"] == (1 << 30) // 16
    assert sp["max_unique"] == plain["max_unique"] + (1 << 30) // 16
    # no budget -> no spill block (nothing to extend past)
    assert "spill" not in capacity_plan(spec_fn, caps, spill=True)


def test_health_downgrades_oom_risk_to_spill_forecast():
    from stateright_tpu.telemetry.health import HealthTracker

    def drive(tracker):
        tracker.set_memory_forecast(10_000, 5_000)  # transient > budget
        events = []
        for _ in range(3):
            events += tracker.update({
                "d_states": 100, "d_unique": 50, "dt": 0.1,
                "queue": 10, "load_factor": 0.2,
            })
        return events

    plain = HealthTracker()
    evs = drive(plain)
    assert any(e["event"] == "growth_oom_risk" for e in evs)
    assert plain.snapshot()["oom_risk"] is True

    armed = HealthTracker()
    armed.spill_armed = True
    evs = drive(armed)
    assert any(e["event"] == "spill_forecast" for e in evs)
    assert not any(e["event"] == "growth_oom_risk" for e in evs)
    snap = armed.snapshot()
    assert snap["oom_risk"] is False and snap["spill_forecast"] is True
    done = armed.mark_done()
    assert any(e["event"] == "spill_forecast_cleared" for e in done)


def test_chrome_trace_carries_spill_counter_tracks(monkeypatch, tmp_path):
    """Satellite: spill events plot as ``spill_bytes`` and
    ``bloom_filter`` counter tracks in the Chrome-trace export."""
    from stateright_tpu.telemetry.export import from_chrome_trace

    budget = _budget_for(5, 1 << 13)
    c = _spawn_spill(5, budget, monkeypatch, telemetry={"memory": True})
    path = tmp_path / "trace.json"
    c.flight_recorder.to_chrome_trace(path)
    back = from_chrome_trace(path)
    counters = {}
    for e in back["events"]:
        if e["ph"] == "C":
            counters.setdefault(e["name"], []).append(e)
    assert "spill_bytes" in counters
    assert all(
        "host_bytes" in e["args"] for e in counters["spill_bytes"]
    )
    assert "bloom_filter" in counters


def test_report_and_summary_carry_the_spill_block(monkeypatch, tmp_path):
    from stateright_tpu.telemetry.report import build_report, write_report

    budget = _budget_for(5, 1 << 13)
    c = _spawn_spill(
        5, budget, monkeypatch,
        telemetry={"cartography": True, "memory": True},
    )
    rep = build_report(c)
    assert rep["spill"]["evictions"] >= 1
    assert rep["spill"]["spilled_fps"] > 0
    assert c.flight_recorder.summary()["spill"]["spilled_fps"] > 0
    write_report(c, str(tmp_path / "r.json"))
    md = (tmp_path / "r.md").read_text()
    assert "Spill tier" in md and "Bloom filter" in md


def test_spill_resolution_skips_when_nothing_spilled():
    """No budget, no eviction: the Bloom stays all-zero, nothing ever
    defers, and the spill status reads idle."""
    c = TwoPhaseSys(3).checker().spill().spawn_tpu(
        sync=True, capacity=1 << 12, batch=64, spill_bloom_bits=BLOOM
    )
    sp = c.spill_status()
    assert sp["evictions"] == 0 and sp["spilled_fps"] == 0
    assert sp["deferred"] == 0 and sp["resolved_novel"] == 0
    base = TwoPhaseSys(3).checker().spawn_tpu(
        sync=True, capacity=1 << 12, batch=64
    )
    assert c.unique_state_count() == base.unique_state_count()
    assert base.spill_status() is None  # plain runs expose None


# -- rejection guards --------------------------------------------------------


def test_mesh_engine_rejects_spill_with_guidance():
    with pytest.raises(NotImplementedError, match="single-device"):
        TwoPhaseSys(3).checker().spill().spawn_tpu(devices=2)


def test_spill_and_por_are_mutually_exclusive():
    with pytest.raises(NotImplementedError, match="partial-order"):
        TwoPhaseSys(3).checker().spill().por().spawn_tpu(sync=True)


# -- regress gate (injectable artifacts; satellite) --------------------------


def _spill_leg(**over):
    leg = {
        "v": 1, "enabled": True, "evictions": 2, "spilled_fps": 1000,
        "host_bytes": 16000, "disk_bytes": 0, "resolved_dups": 10,
        "resolved_novel": 5,
    }
    leg.update(over)
    return leg


def test_regress_spill_gate_absence_never_trips():
    from regress import spill_verdict

    # stale / pre-spill artifacts carry no block: pass
    assert spill_verdict({}, {})["ok"]
    assert spill_verdict({}, {"tpu_2pc7_spill": _spill_leg()})["ok"]


def test_regress_spill_gate_validates_present_legs():
    from regress import spill_verdict

    good = {
        "tpu_2pc7_spill": _spill_leg(),
        "tpu_2pc7_spill_unique": 296448,
        "tpu_2pc7_unique": 296448,
    }
    assert spill_verdict(good, {})["ok"]
    # count drift is the cardinal sin
    bad = dict(good, tpu_2pc7_spill_unique=296447)
    v = spill_verdict(bad, {})
    assert not v["ok"] and any("unique" in p for p in v["problems"])
    # a leg that never evicted did not exercise the tier
    v = spill_verdict(
        {"tpu_2pc7_spill": _spill_leg(evictions=0)}, {}
    )
    assert not v["ok"]
    # malformed block
    v = spill_verdict({"tpu_2pc7_spill": {"enabled": True}}, {})
    assert not v["ok"]
    # crashed leg fails, never skips
    v = spill_verdict({"tpu_2pc7_spill_error": "RuntimeError: x"}, {})
    assert not v["ok"]


def test_regress_main_spill_flag(tmp_path, capsys):
    import json

    from regress import main as regress_main

    run = {
        "fresh": True,
        "tpu_2pc7_spill": _spill_leg(),
        "tpu_2pc7_spill_unique": 296448,
        "tpu_2pc7_unique": 296448,
    }
    rp = tmp_path / "run.json"
    bp = tmp_path / "base.json"
    rp.write_text(json.dumps(run))
    bp.write_text(json.dumps({}))
    rc = regress_main([str(rp), f"--baseline={bp}", "--spill"])
    assert rc == 0
    run["tpu_2pc7_spill_unique"] = 1
    rp.write_text(json.dumps(run))
    rc = regress_main([str(rp), f"--baseline={bp}", "--spill"])
    assert rc == 1
    capsys.readouterr()


# -- the ROADMAP acceptance run (slow tier) ----------------------------------


@pytest.mark.slow
def test_2pc7_under_budget_completes_bit_identical(monkeypatch):
    """THE acceptance criterion: 2pc-7 under a ``STATERIGHT_TPU_DEVICE_
    BYTES`` budget provably smaller than its steady-state footprint
    completes with bit-identical unique/total/property counts vs the
    unconstrained run, and its cartography block reconciles exactly."""
    m = TwoPhaseSys(7)
    twin = twin_or_none(m)
    n_props = len(list(m.properties()))
    batch, qcap = 1024, 1 << 17
    sp = (BLOOM, batch * twin.max_actions)

    def tot(cap):
        return total_bytes(wavefront_specs(
            twin, n_props, cap, qcap, batch, cartography=True, spill=sp
        ))

    # the unconstrained run ends at a 1<<21 table (>= 4 * 296,448);
    # budget out the 1<<20 -> 1<<21 migration so the hot tier pins
    budget = tot(1 << 20) + tot(1 << 21) - 1
    assert budget < tot(1 << 21) + tot(1 << 22)  # < the steady-state peak
    base = TwoPhaseSys(7).checker().spawn_tpu(
        sync=True, capacity=1 << 17, batch=batch
    )
    assert base.unique_state_count() > (1 << 20) // 4  # must NOT fit hot
    monkeypatch.setenv(ENV_DEVICE_BYTES, str(budget))
    monkeypatch.setenv("STATERIGHT_TPU_CAPACITY_GUARD", "off")
    c = (
        TwoPhaseSys(7).checker().spill()
        .telemetry(cartography=True, memory=True)
        .spawn_tpu(
            sync=True, capacity=1 << 17, queue_capacity=qcap, batch=batch,
            steps_per_call=64, spill_bloom_bits=BLOOM,
        )
    )
    assert c.unique_state_count() == base.unique_state_count()
    assert c.state_count() == base.state_count()
    assert sorted(c.discoveries()) == sorted(base.discoveries())
    sp_st = c.spill_status()
    assert sp_st["evictions"] >= 1
    cart = c.cartography()
    assert sum(cart["depth_hist"]) == c.unique_state_count()
    assert cart["fresh_inserts"] == c.unique_state_count()
    assert sum(cart["action_hist"]) == c.state_count() - len(
        TwoPhaseSys(7).init_states()
    )
    c.assert_properties()
