"""HBM ledger & capacity planning (stateright_tpu/telemetry/memory.py).

Pins the round's contracts (docs/telemetry.md "Memory ledger"):

 - EXACTNESS: the analytic per-buffer bytes reconcile exactly against the
   live engine buffers' ``nbytes`` — per buffer, both engines;
 - ZERO JAXPR IMPACT: the ledger is host arithmetic only — the run
   program is bit-identical with the ledger on or off (the
   telemetry/checked/prededup/cartography discipline, in its strongest
   form: not even the ON path may touch the program);
 - the run report's ``memory`` block is DETERMINISTIC (byte-stable
   across runs; live-device fields never enter the JSON body);
 - the growth forecast, the ``growth_oom_risk`` health condition, the
   preflight/resume capacity guards (exercised on CPU via the
   ``STATERIGHT_TPU_DEVICE_BYTES`` budget override), and the
   ``capacity`` CLI verb's graceful degradation where no budget exists.
"""

import io
import json

import numpy as np
import pytest

import jax

from stateright_tpu.models.two_phase_commit import TwoPhaseSys
from stateright_tpu.telemetry.health import HealthTracker
from stateright_tpu.telemetry.memory import (
    MEMORY_V,
    BufferSpec,
    CapacityError,
    capacity_plan,
    device_budget,
    fmt_bytes,
    next_rung_block,
    total_bytes,
    wavefront_specs,
)


# -- exactness: analytic bytes == live buffer nbytes -------------------------


def _spawn_wavefront(memory=True, **kw):
    b = TwoPhaseSys(3).checker()
    if memory:
        b = b.telemetry(memory=True, cartography=True)
    kw.setdefault("capacity", 1 << 12)
    kw.setdefault("batch", 64)
    return b.spawn_tpu(sync=True, **kw)


def test_wavefront_analytic_bytes_reconcile_exactly():
    """Per-buffer: the ledger's analytic model (derived from the engine's
    own carry avals) must equal the final carry's live nbytes EXACTLY —
    table, queue, scalars, cartography counters, everything."""
    c = _spawn_wavefront()
    specs = c._memory_spec_fn()(
        {"cap": c._cap, "qcap": c._qcap, "batch": c._batch}
    )
    carry = jax.tree.leaves(c._final_carry)
    assert len(specs) == len(carry)
    for s, arr in zip(specs, carry):
        a = np.asarray(arr)
        assert a.nbytes == s.nbytes, (s.name, a.nbytes, s.nbytes)
        assert a.shape == s.shape, (s.name, a.shape, s.shape)
    snap = c.memory()
    assert snap["v"] == MEMORY_V
    assert snap["total_bytes"] == sum(s.nbytes for s in specs)
    assert snap["buffers"] == {s.name: s.nbytes for s in specs}


def test_mesh_analytic_bytes_reconcile_exactly():
    """Same exactness on the mesh engine: the GLOBAL carry arrays'
    nbytes equal the analytic model per buffer, and the bytes one device
    holds are read off each buffer's own sharding."""
    c = (
        TwoPhaseSys(3)
        .checker()
        .telemetry(memory=True, cartography=True)
        .spawn_tpu(sync=True, devices=2, capacity=1 << 12)
    )
    specs = c._memory_spec_fn()(c._memory_caps())
    carry = jax.tree.leaves(c._final_carry)
    assert len(specs) == len(carry)
    for s, arr in zip(specs, carry):
        assert arr.nbytes == s.nbytes, (s.name, arr.nbytes, s.nbytes)
        local = arr.addressable_shards[0].data.nbytes
        assert local == s.per_device_nbytes, (s.name, local)
    snap = c.memory()
    assert snap["engine"] == "mesh" and snap["devices"] == 2
    assert snap["total_bytes"] == sum(s.nbytes for s in specs)
    assert snap["per_device_bytes"] == sum(
        s.per_device_nbytes for s in specs
    )
    # the table and the queue are split in two, the counters are not
    assert snap["total_bytes"] // 2 < snap["per_device_bytes"]
    assert snap["per_device_bytes"] < snap["total_bytes"]


def test_exec_memory_analysis_agrees_with_the_analytic_carry():
    """Cross-check against XLA's own accounting: the AOT-compiled run
    executable's argument bytes ARE the carry — the two independent
    models must agree on a no-growth run."""
    c = _spawn_wavefront(capacity=1 << 14)
    snap = c.memory()
    exe = snap.get("exec")
    if exe is None or "argument_bytes" not in exe:
        pytest.skip("backend exposes no compiled memory_analysis")
    assert exe["argument_bytes"] == snap["total_bytes"]
    compiles = c.flight_recorder.records("compile")
    assert any(
        isinstance(r.get("memory"), dict)
        and r["memory"].get("argument_bytes") == snap["total_bytes"]
        for r in compiles
    ), compiles


# -- zero jaxpr impact -------------------------------------------------------


def _wavefront_build_jaxpr(memory: bool) -> str:
    m = TwoPhaseSys(3)
    b = m.checker()
    if memory:
        b = b.telemetry(memory=True)
    c = b.spawn_tpu(sync=True, capacity=1 << 12, batch=64)
    # trace the BUILD product, not the engine cache: the ledger's AOT
    # path swaps a Compiled into the cache, which is the same program
    # compiled earlier (the prewarm contract) but cannot be re-traced
    init_fn, run_fn = c._build(c._cap, c._qcap, c._batch, c._cand)
    carry, _ = init_fn()
    # fresh lambda per call: make_jaxpr memoizes on fn identity
    return str(jax.make_jaxpr(lambda cr: run_fn(cr))(carry))


def test_ledger_leaves_run_jaxpr_bit_identical():
    """Strongest form of the overhead contract: the ledger never touches
    the device program — ON is bit-identical to OFF (host arithmetic
    over shapes the engine already knows)."""
    assert _wavefront_build_jaxpr(False) == _wavefront_build_jaxpr(True)


def test_ledger_does_not_key_the_engine_cache():
    """Ledger on/off must share one compiled engine: a memory-off spawn
    after a memory-on spawn on the same model is a cache HIT (the flag
    is not part of the engine key — same program, compiled once)."""
    m = TwoPhaseSys(3)
    kw = dict(sync=True, capacity=1 << 12, batch=64)
    c1 = m.checker().telemetry(memory=True).spawn_tpu(**kw)
    n_keys = len(c1.tensor._run_cache)
    c2 = m.checker().telemetry().spawn_tpu(**kw)
    assert len(c2.tensor._run_cache) == n_keys
    assert c2.unique_state_count() == c1.unique_state_count()


# -- memory ring records + growth series -------------------------------------


def test_growth_emits_memory_records_and_manifest():
    """A run that grows emits a ``memory`` record per rung change (the
    per-growth series) plus init/final, each carrying the versioned
    analytic block; the final snapshot manifest records the footprint."""
    c = (
        TwoPhaseSys(5)
        .checker()
        .telemetry(memory=True, cartography=True)
        .spawn_tpu(sync=True, capacity=1 << 10, batch=256)
    )  # tiny table vs 8832 unique states: forces growth
    recs = c.flight_recorder.records("memory")
    tags = [r["at"] for r in recs]
    assert tags[0] == "init" and tags[-1] == "final"
    assert "growth" in tags, tags
    for r in recs:
        assert r["v"] == MEMORY_V
        assert r["engine"] == "wavefront"
        assert r["total_bytes"] == sum(r["buffers"].values())
        nxt = r["next_rung"]
        assert nxt["transient_bytes"] == r["total_bytes"] + nxt["total_bytes"]
    # capacities are monotone along the growth series
    caps = [r["capacity"] for r in recs]
    assert caps == sorted(caps)
    snap = c.checkpoint()
    assert int(snap["footprint_bytes"]) == c.memory()["total_bytes"]


def test_chrome_trace_carries_pressure_and_hbm_counters(tmp_path):
    """Satellite: the Chrome-trace export plots resource pressure as
    counter tracks — queue depth + table load per step, HBM bytes per
    memory record — round-tripped through the existing parser."""
    from stateright_tpu.telemetry.export import from_chrome_trace

    c = (
        TwoPhaseSys(5)
        .checker()
        .telemetry(memory=True, cartography=True)
        .spawn_tpu(sync=True, capacity=1 << 10, batch=256)
    )
    path = tmp_path / "trace.json"
    c.flight_recorder.to_chrome_trace(path)
    back = from_chrome_trace(path)
    counters = [e for e in back["events"] if e["ph"] == "C"]
    by_name = {}
    for e in counters:
        by_name.setdefault(e["name"], []).append(e)
    assert "pressure" in by_name
    assert all(
        "queue" in e["args"] and "table_load" in e["args"]
        for e in by_name["pressure"]
    )
    assert "hbm_bytes" in by_name
    assert all(
        isinstance(e["args"].get("analytic_bytes"), int)
        for e in by_name["hbm_bytes"]
    )


# -- deterministic report block ----------------------------------------------


def test_report_memory_block_is_deterministic_and_live_free(tmp_path):
    """The run report's memory block is byte-stable across runs and
    carries NO live-device / machine-local fields (device stats and the
    budget live in the markdown rendering only)."""
    from stateright_tpu.telemetry.report import build_report

    bodies = []
    for i in range(2):
        c = (
            TwoPhaseSys(3)
            .checker()
            .report(str(tmp_path / f"r{i}.json"))
            .spawn_tpu(sync=True, capacity=1 << 12, batch=64)
        )
        c.join()
        bodies.append(build_report(c))
    assert json.dumps(bodies[0]) == json.dumps(bodies[1])
    mem = bodies[0]["memory"]
    assert mem["v"] == MEMORY_V
    assert set(mem) <= {
        "v", "engine", "capacity", "queue_capacity", "frontier_capacity",
        "devices", "buffers", "total_bytes", "per_device_bytes",
        "next_rung",
    }
    assert sum(mem["buffers"].values()) == mem["total_bytes"]
    # the written artifact renders the block in markdown too
    md = (tmp_path / "r0.md").read_text()
    assert "## Memory (analytic)" in md


def test_metrics_view_and_watch_line_surface_memory():
    from stateright_tpu.explorer import _metrics_view
    from stateright_tpu.models._cli import watch_line

    c = _spawn_wavefront()
    view = _metrics_view(c)
    assert view["memory"] is not None
    assert view["memory"]["total_bytes"] > 0
    line = watch_line(c)
    assert "hbm=" in line and "hbm=-" not in line


# -- forecast + plan ---------------------------------------------------------


def test_next_rung_forecast_holds_old_plus_new():
    spec_fn = lambda caps: [  # noqa: E731
        BufferSpec("table", (caps["cap"],), np.uint64),
        BufferSpec("fixed", (100,), np.uint8),
    ]
    nxt = next_rung_block(spec_fn, {"cap": 1024})
    assert nxt["capacity"] == 2048
    assert nxt["total_bytes"] == 2048 * 8 + 100
    assert nxt["transient_bytes"] == (1024 * 8 + 100) + (2048 * 8 + 100)


def test_capacity_plan_max_unique_is_transient_bounded():
    """The plan's headline is bounded by the TRANSIENT, not the steady
    state: a rung whose steady bytes fit but whose migration does not is
    unreachable."""
    spec_fn = lambda caps: [  # noqa: E731
        BufferSpec("table", (caps["cap"],), np.uint64)
    ]
    # budget fits cap=2048 steady (16KB) and the 1024->2048 transient
    # (24KB), but not the 2048->4096 transient (48KB)
    plan = capacity_plan(spec_fn, {"cap": 1024}, budget=30_000)
    assert plan["max_unique"] == 2048 // 4
    fits = [r["fits"] for r in plan["rungs"]]
    assert fits == [True, True, False]
    # no budget: analytic ladder only, no verdict
    plan2 = capacity_plan(spec_fn, {"cap": 1024}, budget=None, rungs=3)
    assert "max_unique" not in plan2
    assert all("fits" not in r for r in plan2["rungs"])


def test_fmt_bytes():
    assert fmt_bytes(None) == "-"
    assert fmt_bytes(512) == "512B"
    assert fmt_bytes(2048) == "2.0KB"
    assert fmt_bytes(3 << 30) == "3.0GB"


def test_device_budget_env_override(monkeypatch):
    monkeypatch.setenv("STATERIGHT_TPU_DEVICE_BYTES", "123456")
    assert device_budget() == (123456, "env")


# -- preflight + resume capacity guards --------------------------------------


def test_preflight_guard_warns_then_errors(monkeypatch, capsys):
    monkeypatch.setenv("STATERIGHT_TPU_DEVICE_BYTES", "10000")  # ~10KB
    # default mode: warn once, run proceeds (and completes correctly)
    c = TwoPhaseSys(3).checker().spawn_tpu(
        sync=True, capacity=1 << 12, batch=64
    )
    assert c.unique_state_count() == 288
    err = capsys.readouterr().err
    assert "capacity guard" in err and "exceeds the device budget" in err
    # flag-gated error: raises BEFORE any device work
    monkeypatch.setenv("STATERIGHT_TPU_CAPACITY_GUARD", "error")
    with pytest.raises(CapacityError):
        TwoPhaseSys(4).checker().spawn_tpu(sync=True, capacity=1 << 12)
    # off: silent
    monkeypatch.setenv("STATERIGHT_TPU_CAPACITY_GUARD", "off")
    capsys.readouterr()
    TwoPhaseSys(3).checker().spawn_tpu(sync=True, capacity=1 << 12, batch=64)
    assert "capacity guard" not in capsys.readouterr().err


def test_resume_guard_checks_the_snapshot_manifest(monkeypatch, capsys):
    """Satellite: snapshot manifests carry the analytic footprint, and a
    resume onto a device that analytically cannot hold it warns (flag-
    gated error) BEFORE compiling — riding _check_snapshot_sig."""
    m = TwoPhaseSys(3)
    snap = m.checker().spawn_tpu(
        sync=True, capacity=1 << 12, batch=64
    ).checkpoint()
    assert int(snap["footprint_bytes"]) > 0
    monkeypatch.setenv("STATERIGHT_TPU_DEVICE_BYTES", "10000")
    monkeypatch.setenv("STATERIGHT_TPU_CAPACITY_GUARD", "error")
    with pytest.raises(CapacityError):
        m.checker().skip_audit().spawn_tpu(sync=True, resume=snap)
    # warn mode: proceeds, resumed run completes
    monkeypatch.delenv("STATERIGHT_TPU_CAPACITY_GUARD")
    capsys.readouterr()
    c = m.checker().skip_audit().spawn_tpu(sync=True, resume=snap)
    assert c.unique_state_count() == 288
    assert "cannot hold the snapshot" in capsys.readouterr().err


# -- growth_oom_risk health condition ----------------------------------------


def _step(load, d_states=100, d_unique=50, queue=10):
    return {
        "d_states": d_states, "d_unique": d_unique, "queue": queue,
        "load_factor": load, "dt": 0.1,
    }


def test_health_growth_oom_risk_transitions():
    t = HealthTracker()
    t.set_memory_forecast(next_transient_bytes=2_000_000,
                          budget_bytes=1_000_000)
    # below the risk load: no event even though the forecast misses
    assert not [
        e for e in t.update(_step(0.05))
        if e["event"].startswith("growth_oom")
    ]
    assert t.oom_risk is False
    # crossing the risk load with a missing forecast -> risk event
    events = t.update(_step(0.2))
    assert any(e["event"] == "growth_oom_risk" for e in events)
    assert t.oom_risk and t.snapshot()["oom_risk"] is True
    # transitions only: staying at risk emits nothing new
    assert not t.update(_step(0.2))
    # fitting forecast clears
    t.set_memory_forecast(500_000, 1_000_000)
    events = t.update(_step(0.2))
    assert any(e["event"] == "growth_oom_risk_cleared" for e in events)
    assert not t.oom_risk


def test_health_mark_done_closes_an_open_risk_span():
    t = HealthTracker()
    t.set_memory_forecast(2_000_000, 1_000_000)
    t.update(_step(0.2))
    assert t.oom_risk
    events = t.mark_done()
    assert any(e["event"] == "growth_oom_risk_cleared" for e in events)
    assert t.snapshot()["oom_risk"] is False


def test_health_no_forecast_means_no_risk():
    t = HealthTracker()  # ledger off: forecast never armed
    assert not [
        e for e in t.update(_step(0.24))
        if e["event"].startswith("growth_oom")
    ]


# -- capacity CLI verb -------------------------------------------------------


def test_capacity_verb_degrades_gracefully_without_budget(monkeypatch):
    """Satellite/CI contract: on CPU (no live memory stats) the verb
    prints the analytic ladder and never crashes."""
    monkeypatch.delenv("STATERIGHT_TPU_DEVICE_BYTES", raising=False)
    from stateright_tpu.models._cli import fleet_capacity

    buf = io.StringIO()
    rc = fleet_capacity(["two_phase_commit"], stream=buf)
    out = buf.getvalue()
    assert rc == 0
    assert "no device memory limit known" in out
    assert "capacity plan" in out and "NO" not in out


def test_capacity_verb_prints_a_plan_with_budget(monkeypatch):
    monkeypatch.setenv("STATERIGHT_TPU_DEVICE_BYTES", str(300 << 20))
    from stateright_tpu.models._cli import fleet_capacity

    buf = io.StringIO()
    rc = fleet_capacity(["two_phase_commit"], stream=buf)
    out = buf.getvalue()
    assert rc == 0
    assert "unique states before spilling" in out
    assert "NO" in out  # the first unfitting rung is shown


def test_capacity_verb_reports_twinless_models(monkeypatch):
    from stateright_tpu.models._cli import capacity_and_report

    class NoTwin:
        def properties(self):
            return []

    buf = io.StringIO()
    ok = capacity_and_report([("no-twin", NoTwin())], stream=buf)
    assert ok is True  # disclosed, not a failure
    assert "no device twin" in buf.getvalue()
