"""Crash-safe checking (docs/robustness.md): periodic atomic autosave
checkpoints, supervised runs with retry/backoff + graceful OOM
degradation, and the deterministic fault-injection layer.

Fast tier: unit-level fault-plan / atomic-write / classification /
checkpoint-store tests plus the jaxpr+cache contract pins.  The chaos
integration acceptance runs (supervised 2pc-5 killed mid-flight,
injected growth-OOM degrading to a spill eviction, lineage-gated
kill+resume chains) are pinned ``medium`` per the tiering rule —
integration work that needs double-digit seconds stays out of the fast
tier.

Pinned chaos contracts (the ISSUE 13 acceptance criteria):

 (a) a supervised 2pc-5 killed mid-flight by an injected fault
     auto-resumes from an autosave generation and finishes bit-identical
     to an uninterrupted run, with the PR 12 lineage diff classifying
     the chain IDENTICAL;
 (b) an injected RESOURCE_EXHAUSTED at a growth boundary degrades to a
     spill eviction (counts bit-identical to unconstrained) instead of
     crashing;
 (c) autosave/fault hooks OFF leave the step jaxpr bit-identical and
     the engine cache unkeyed, both with and without a plan installed.
"""

import errno
import json

import numpy as np
import pytest

import jax

from stateright_tpu import checkpoint as ckpt
from stateright_tpu.models.two_phase_commit import TwoPhaseSys
from stateright_tpu.supervisor import (
    FATAL,
    IO,
    OOM,
    PREEMPTION,
    classify_failure,
    supervise,
)
from stateright_tpu.testing.faults import (
    Fault,
    FaultPlan,
    InjectedKill,
    InjectedOOM,
    fire,
)

# 2pc pinned counts (examples/2pc.rs:125-140).  ``states`` (generated,
# incl. duplicates) is config-invariant: every unique state is expanded
# exactly once regardless of batch/growth schedule, so the total is
# sum-over-uniques of enabled actions + inits.
UNIQUE_2PC3, STATES_2PC3 = 288, 1146
UNIQUE_2PC5, STATES_2PC5 = 8832, 58146


# -- fault-plan units (fast tier) --------------------------------------------


def test_fault_plan_fires_once_at_the_scheduled_occurrence():
    plan = FaultPlan([Fault(site="host_sync", action="kill", at=2)])
    with plan:
        fire("host_sync")  # 0
        fire("host_sync")  # 1
        with pytest.raises(InjectedKill):
            fire("host_sync")  # 2 — fires
        fire("host_sync")  # 3 — one-shot: never again
    assert plan.fired == [{"site": "host_sync", "action": "kill", "at": 2}]
    assert plan.faults[0].fired


def test_fault_plan_uninstalled_is_inert():
    plan = FaultPlan([Fault(site="host_sync", action="kill", at=0)])
    fire("host_sync")  # no plan installed: nothing can fire
    assert plan.fired == []


def test_fault_plan_sites_are_independent_counters():
    plan = FaultPlan([
        Fault(site="growth", action="oom", at=1),
        Fault(site="spill_flush", action="enospc", at=0),
    ])
    with plan:
        fire("growth")  # growth[0]: not yet
        with pytest.raises(OSError) as ei:
            fire("spill_flush")  # spill_flush[0]: ENOSPC
        assert ei.value.errno == errno.ENOSPC
        with pytest.raises(InjectedOOM) as oi:
            fire("growth")  # growth[1]: fires
        assert "RESOURCE_EXHAUSTED" in str(oi.value)


def test_fault_plan_seeded_schedule_is_deterministic():
    a = FaultPlan.scheduled(7, "host_sync", lo=1, hi=32)
    b = FaultPlan.scheduled(7, "host_sync", lo=1, hi=32)
    assert a.faults[0].at == b.faults[0].at
    assert 1 <= a.faults[0].at < 32
    # JSON round trip preserves the schedule
    back = FaultPlan.from_json(a.to_json())
    assert back.faults[0].at == a.faults[0].at
    assert back.seed == a.seed


def test_fault_plan_rejects_unknown_site_and_action():
    with pytest.raises(ValueError):
        FaultPlan([Fault(site="nope")])
    with pytest.raises(ValueError):
        FaultPlan([Fault(site="growth", action="nope")])


def test_fault_plan_jsonl_trail(tmp_path):
    plan = FaultPlan([Fault(site="growth", action="io", at=0)], seed=3)
    with plan:
        with pytest.raises(OSError):
            fire("growth", unique=17)
    out = tmp_path / "faults.jsonl"
    plan.to_jsonl(str(out))
    lines = [json.loads(ln) for ln in out.read_text().splitlines()]
    assert lines[0]["kind"] == "plan" and lines[0]["seed"] == 3
    assert lines[1] == {
        "kind": "fired", "site": "growth", "action": "io", "at": 0,
        "unique": 17,
    }


def test_fault_fire_records_into_the_ring():
    from stateright_tpu.telemetry import FlightRecorder

    rec = FlightRecorder()
    plan = FaultPlan([Fault(site="host_sync", action="kill", at=0)])
    with plan:
        with pytest.raises(InjectedKill):
            fire("host_sync", recorder=rec)
    (r,) = rec.records("fault")
    assert (r["site"], r["action"], r["at"], r["v"]) == (
        "host_sync", "kill", 0, 1
    )


# -- failure classification (fast tier) --------------------------------------


def test_classify_failure_taxonomy():
    assert classify_failure(InjectedKill("x")) == PREEMPTION
    assert classify_failure(KeyboardInterrupt()) == PREEMPTION
    assert classify_failure(SystemExit(1)) == PREEMPTION
    assert classify_failure(InjectedOOM("RESOURCE_EXHAUSTED: x")) == OOM
    # a real jaxlib device OOM matches structurally (the
    # RESOURCE_EXHAUSTED status in the message), never by import
    # identity — and an XlaRuntimeError WITHOUT it (INVALID_ARGUMENT,
    # INTERNAL: codegen/model bugs) is FATAL, not retried
    XlaRuntimeError = type("XlaRuntimeError", (RuntimeError,), {})
    assert classify_failure(
        XlaRuntimeError("RESOURCE_EXHAUSTED: out of memory allocating")
    ) == OOM
    assert classify_failure(XlaRuntimeError("INTERNAL: boom")) == FATAL
    assert classify_failure(
        RuntimeError("RESOURCE_EXHAUSTED: out of memory")
    ) == OOM
    assert classify_failure(OSError(errno.EIO, "disk")) == IO
    assert classify_failure(ValueError("model bug")) == FATAL
    assert classify_failure(RuntimeError("poisoned rows")) == FATAL


def test_supervise_reraises_fatal_without_retry(tmp_path):
    calls = []

    def spawn(b, resume=None, **kw):
        calls.append(1)
        raise ValueError("model bug")

    with pytest.raises(ValueError):
        supervise(
            TwoPhaseSys(3).checker(),
            autosave_dir=str(tmp_path), spawn=spawn,
            sleep=lambda s: None,
        )
    assert len(calls) == 1  # no retry on a fatal class


def test_supervise_respects_the_restart_budget(tmp_path):
    def spawn(b, resume=None, **kw):
        raise InjectedKill("always")

    with pytest.raises(InjectedKill):
        supervise(
            TwoPhaseSys(3).checker(),
            autosave_dir=str(tmp_path), spawn=spawn,
            max_restarts=3, sleep=lambda s: None,
        )


def test_supervise_backoff_is_bounded_and_grows(tmp_path):
    delays = []
    boom = [0]

    def spawn(b, resume=None, **kw):
        if boom[0] < 4:
            boom[0] += 1
            raise InjectedKill("x")
        return TwoPhaseSys(3).checker().spawn_tpu(
            sync=True, capacity=1 << 12, batch=64
        )

    res = supervise(
        TwoPhaseSys(3).checker(),
        autosave_dir=str(tmp_path), spawn=spawn,
        max_restarts=5, backoff_base=0.5, backoff_max=2.0,
        sleep=delays.append, seed=1,
    )
    assert res.restarts == 4
    assert len(delays) == 4
    # exponential up to the cap, jitter <= 25%
    assert delays[0] <= 0.5 * 1.25
    assert all(d <= 2.0 * 1.25 for d in delays)
    assert delays[1] >= delays[0] / 1.25


# -- atomic writes + torn-tail resilience (fast tier) ------------------------


def test_atomic_write_failure_leaves_old_contents(tmp_path):
    from stateright_tpu.telemetry._atomic import atomic_write_json

    path = tmp_path / "doc.json"
    atomic_write_json(str(path), {"gen": 1})
    plan = FaultPlan([Fault(site="atomic_write", action="io", at=0)])
    with plan:
        with pytest.raises(OSError):
            atomic_write_json(str(path), {"gen": 2})
    assert json.loads(path.read_text()) == {"gen": 1}
    # no temp litter
    assert [p.name for p in tmp_path.iterdir()] == ["doc.json"]


def test_registry_index_survives_a_torn_tail(tmp_path):
    """A killed writer tears at most the ledger's LAST line; prior
    records stay readable and later appends work (the crash contract of
    durable_append_line + index())."""
    from stateright_tpu.telemetry.registry import RunRegistry

    reg = RunRegistry(str(tmp_path))
    doc1 = {"run_id": "aaa", "v": 1, "model": "M", "engine": "wavefront",
            "totals": {"unique": 1}, "config": {"key": "k1"}}
    reg.record_doc(doc1)
    # simulate the torn tail a SIGKILL mid-append leaves
    with open(reg.index_path, "a") as f:
        f.write('{"run_id": "bbb", "trunc')
    assert [r["run_id"] for r in reg.index()] == ["aaa"]
    doc2 = dict(doc1, run_id="ccc")
    reg.record_doc(doc2)
    assert [r["run_id"] for r in reg.index()] == ["aaa", "ccc"]
    # the archives themselves are complete JSON (atomic replace writes)
    assert reg.load("aaa")["run_id"] == "aaa"
    assert reg.load("ccc")["run_id"] == "ccc"


# -- checkpoint generation store (fast tier) ---------------------------------


def _snap(unique: int) -> dict:
    return {
        "unique": np.int64(unique), "scount": np.int64(unique * 3),
        "maxdepth": np.int32(4), "disc": np.zeros(3, np.uint64),
    }


def test_generations_rotate_and_latest_wins(tmp_path):
    root = str(tmp_path)
    for i in range(5):
        ckpt.save_generation(
            root, i, _snap(i), {"run_id": "r", "totals": {"unique": i}},
            keep=2,
        )
    gens = ckpt.list_generations(root)
    assert [g["gen"] for g in gens] == [3, 4]
    assert all(g["complete"] for g in gens)
    snap, man = ckpt.latest_generation(root)
    assert int(snap["unique"]) == 4
    assert man["gen"] == 4 and man["v"] == ckpt.CKPT_V
    # numbering continues across restarts — a resumed run never
    # overwrites its parent's generations
    assert ckpt.next_generation(root) == 5


def test_torn_generation_is_skipped_loudly(tmp_path, capsys):
    """A generation without a committed manifest (or with a garbage npz)
    is TORN: resume warns and falls back to the previous complete one —
    a half-written snapshot never poisons resume."""
    root = str(tmp_path)
    ckpt.save_generation(
        root, 0, _snap(7), {"run_id": "r", "totals": {"unique": 7}},
    )
    # torn case 1: npz present, manifest missing (killed between writes)
    torn = tmp_path / "gen-000001"
    torn.mkdir()
    (torn / "snapshot.npz").write_bytes(b"\x00garbage")
    # torn case 2: manifest committed but npz unreadable (bit rot)
    torn2 = tmp_path / "gen-000002"
    torn2.mkdir()
    (torn2 / "snapshot.npz").write_bytes(b"not-an-npz")
    (torn2 / "MANIFEST.json").write_text('{"v": 1, "gen": 2}\n')
    snap, man = ckpt.latest_generation(root)
    assert int(snap["unique"]) == 7 and man["gen"] == 0
    err = capsys.readouterr().err
    assert "torn generation" in err and "unreadable" in err


def test_failed_snapshot_write_preserves_previous_generation(tmp_path):
    root = str(tmp_path)
    ckpt.save_generation(
        root, 0, _snap(3), {"run_id": "r", "totals": {"unique": 3}},
    )
    plan = FaultPlan([Fault(site="snapshot_write", action="enospc", at=0)])
    with plan:
        with pytest.raises(OSError):
            ckpt.save_generation(
                root, 1, _snap(9), {"run_id": "r", "totals": {"unique": 9}},
            )
    snap, man = ckpt.latest_generation(root)
    assert int(snap["unique"]) == 3  # the old generation is intact


def test_snapshot_write_kill_fault_reaches_the_supervisor(tmp_path):
    """A scheduled kill at the ``snapshot_write`` seam is manufactured
    process death, not a write failure: it must propagate through the
    engines' autosave guard to the supervisor's classifier (preemption)
    instead of being swallowed as a degraded write."""
    plan = FaultPlan([Fault(site="snapshot_write", action="kill", at=0)])
    with plan:
        res = supervise(
            TwoPhaseSys(3).checker().telemetry(),
            autosave_dir=str(tmp_path / "auto"), every_secs=0.0,
            max_restarts=2, sleep=lambda s: None,
            capacity=1 << 12, batch=32, steps_per_call=2,
        )
    assert res.restarts == 1
    assert res.attempts[0].outcome == PREEMPTION
    assert plan.fired and plan.fired[0]["site"] == "snapshot_write"
    assert res.unique_state_count() == UNIQUE_2PC3
    assert res.state_count() == STATES_2PC3


def test_non_oserror_autosave_failure_is_accounted(tmp_path, monkeypatch):
    """A non-OSError generation-write failure (e.g. a snapshot
    materialization bug) must not kill the run — but it must be
    DISCLOSED: the durability block's failure counter bumps and an
    ``ok=false`` checkpoint record lands in the ring, same as an
    OSError from the atomic write."""
    def boom(*a, **k):
        raise ValueError("manufactured non-OSError write failure")

    monkeypatch.setattr(ckpt, "save_generation", boom)
    c = (
        TwoPhaseSys(3).checker().telemetry()
        .autosave(str(tmp_path / "auto"), every_secs=0.0)
        .spawn_tpu(sync=True, capacity=1 << 12, batch=64)
    )
    assert c.is_done()
    assert c.unique_state_count() == UNIQUE_2PC3
    dur = c.durability_status()
    assert dur["autosave"]["failures"] >= 1
    recs = c.flight_recorder.records("checkpoint")
    assert recs and all(r["ok"] is False for r in recs)
    assert "ValueError" in recs[0]["error"]


def test_resolve_autosave_env_knobs(monkeypatch, tmp_path, capsys):
    monkeypatch.delenv(ckpt.ENV_AUTOSAVE, raising=False)
    assert ckpt.resolve_autosave(None) is None
    monkeypatch.setenv(ckpt.ENV_AUTOSAVE, str(tmp_path))
    monkeypatch.setenv(ckpt.ENV_AUTOSAVE_SECS, "5")
    monkeypatch.setenv(ckpt.ENV_AUTOSAVE_KEEP, "junk")
    got = ckpt.resolve_autosave(None)
    assert got == {
        "dir": str(tmp_path), "every_secs": 5.0, "keep": ckpt.DEFAULT_KEEP,
    }
    assert "malformed" in capsys.readouterr().err
    # builder opts win over env
    assert ckpt.resolve_autosave({"dir": "x", "every_secs": 1, "keep": 2})[
        "dir"
    ] == "x"


# -- spill disk-tier degradation (fast tier, unit level) ---------------------


def test_spill_store_degrades_on_enospc_instead_of_crashing(capsys):
    from stateright_tpu.spill import SpillStore

    store = SpillStore(host_budget=1)  # any append overflows the budget
    fps = np.arange(1, 300, dtype=np.uint64)
    plan = FaultPlan([Fault(site="spill_flush", action="enospc", at=0)])
    with plan:
        store.append(fps, fps)
    assert store.degraded
    assert "enospc" in (store.degraded_reason or "").lower()
    assert "degraded" in capsys.readouterr().err
    # exactness survives: the index + RAM segments are intact, no disk
    assert store.disk_bytes == 0 and store.host_bytes > 0
    assert bool(store.contains(np.asarray([5], np.uint64))[0])
    # warn-once: a second overflow does not retry or re-warn
    store.append(fps + 1000, fps)
    assert store.disk_bytes == 0
    assert "degraded" not in capsys.readouterr().err
    got = np.concatenate([f for f, _ in store.iter_segments()])
    assert got.size == len(store)
    store.close()


# -- contract (c): jaxpr bit-identical + cache unkeyed (fast tier) -----------


def _build_jaxpr(checker) -> str:
    init_fn, run_fn = checker._build(
        checker._cap, checker._qcap, checker._batch, checker._cand
    )
    carry, _ = init_fn()
    return str(jax.make_jaxpr(lambda cr: run_fn(cr))(carry))


def test_autosave_and_faults_leave_step_jaxpr_bit_identical(tmp_path):
    """Acceptance (c): autosave armed or a FaultPlan installed, the
    engines compile the SAME program — injection and checkpointing are
    host-side only — and the engine cache key is unchanged."""
    kw = dict(sync=True, capacity=1 << 12, batch=64)
    plain = TwoPhaseSys(3).checker().spawn_tpu(**kw)
    base_jaxpr = _build_jaxpr(plain)
    base_key = plain._engine_key(
        plain._cap, plain._qcap, plain._batch, plain._cand
    )
    plan = FaultPlan(
        [Fault(site="host_sync", action="kill", at=10**9)]  # never fires
    )
    with plan:
        armed = TwoPhaseSys(3).checker().autosave(
            str(tmp_path), every_secs=3600
        ).spawn_tpu(**kw)
    assert armed.unique_state_count() == UNIQUE_2PC3
    assert _build_jaxpr(armed) == base_jaxpr
    assert armed._engine_key(
        armed._cap, armed._qcap, armed._batch, armed._cand
    ) == base_key


# -- autosave end-to-end on a small space (fast tier) ------------------------


def test_autosave_generations_resume_bit_identical(tmp_path):
    root = str(tmp_path / "auto")
    running = TwoPhaseSys(3).checker().telemetry().autosave(
        root, every_secs=0.0, keep=2
    ).spawn_tpu(capacity=1 << 12, batch=32, steps_per_call=2)
    # let at least one generation land mid-run, then "preempt"
    while not ckpt.list_generations(root):
        if running.is_done():
            break
        import time

        time.sleep(0.01)
    running.stop().join()
    gens = ckpt.list_generations(root)
    assert gens and len(gens) <= 2  # rotation held
    found = ckpt.latest_generation(root)
    assert found is not None
    snap, man = found
    # the manifest is self-describing: identity + config + progress
    assert man["run_id"] == running.run_id
    assert man["model"] == "TwoPhaseSys"
    assert man["engine"] == "wavefront"
    assert man["config"]["key"]
    assert {p["name"] for p in man["properties"]} == {
        "abort agreement", "commit agreement", "consistent",
    }
    # checkpoint ring records + stage attribution + durability block
    rec = running.flight_recorder
    assert rec.kind_count("checkpoint") >= 1
    assert any(r["ok"] for r in rec.records("checkpoint"))
    assert rec.counters().get("stage_checkpoint_secs", 0) >= 0
    dur = running.durability_status()
    assert dur["autosave"]["generations"] >= 1
    assert dur["restarts"] == 0
    # resume from the latest generation: bit-identical completion
    resumed = TwoPhaseSys(3).checker().spawn_tpu(sync=True, resume=snap)
    assert resumed.unique_state_count() == UNIQUE_2PC3
    assert resumed.state_count() == STATES_2PC3
    assert resumed.parent_run_id == running.run_id
    resumed.assert_properties()


def test_report_durability_block_is_deterministic_config_only(tmp_path):
    """The report's durability block carries the CONFIG subset only —
    cadence + restart count — never wall-clock generation counts
    (report-determinism contract)."""
    from stateright_tpu.telemetry.report import build_report

    c = TwoPhaseSys(3).checker().autosave(
        str(tmp_path), every_secs=30.0, keep=4
    ).spawn_tpu(sync=True, capacity=1 << 12, batch=64)
    body = build_report(c)
    assert body["durability"] == {
        "v": ckpt.CKPT_V,
        "restarts": 0,
        "autosave": {"every_secs": 30.0, "keep": 4},
    }
    # without autosave or a supervision trail there is NO block at all
    c2 = TwoPhaseSys(3).checker().spawn_tpu(
        sync=True, capacity=1 << 12, batch=64
    )
    assert "durability" not in build_report(c2)


# -- chaos integration acceptance (medium tier: >15s integration) ------------


@pytest.mark.medium
def test_supervised_2pc5_killed_mid_flight_resumes_bit_identical(tmp_path):
    """Acceptance (a): a supervised 2pc-5 killed mid-flight by an
    injected fault auto-resumes from an autosave generation and finishes
    bit-identical (unique, generated, discoveries), restart count 1."""
    d = str(tmp_path / "auto")
    plan = FaultPlan([Fault(site="host_sync", action="kill", at=6)])
    with plan:
        res = supervise(
            TwoPhaseSys(5).checker().telemetry(),
            autosave_dir=d, every_secs=0.0, max_restarts=3,
            sleep=lambda s: None,
            batch=64, steps_per_call=2,
        )
    assert plan.fired and plan.fired[0]["site"] == "host_sync"
    assert res.restarts == 1
    assert res.unique_state_count() == UNIQUE_2PC5
    assert res.state_count() == STATES_2PC5
    assert res.checker.parent_run_id  # the resume linked its parent
    res.checker.assert_properties()
    rec = res.checker.flight_recorder
    (restart,) = rec.records("restart")
    assert restart["reason"] == "preemption" and restart["attempt"] == 1
    assert restart["parent_run_id"] == res.checker.parent_run_id


@pytest.mark.medium
def test_injected_growth_oom_degrades_to_spill_eviction(
    tmp_path, monkeypatch,
):
    """Acceptance (b): RESOURCE_EXHAUSTED injected at a growth boundary
    degrades to a spill eviction — the supervisor arms the PR 8 tier,
    the resumed run evicts instead of growing, and the counts stay
    bit-identical to an unconstrained run."""
    from stateright_tpu.parallel.tensor_model import twin_or_none
    from stateright_tpu.telemetry.memory import (
        ENV_DEVICE_BYTES,
        total_bytes,
        wavefront_specs,
    )

    m = TwoPhaseSys(5)
    twin = twin_or_none(m)
    n_props = len(list(m.properties()))
    batch, bloom, qcap = 128, 1 << 14, 4096
    sp = (bloom, 4 * batch * twin.max_actions)

    def tot(cap):
        return total_bytes(
            wavefront_specs(twin, n_props, cap, qcap, batch, spill=sp)
        )

    monkeypatch.setenv(
        ENV_DEVICE_BYTES, str(tot(1 << 13) + tot(1 << 14) - 1)
    )
    monkeypatch.setenv("STATERIGHT_TPU_CAPACITY_GUARD", "off")
    plan = FaultPlan([Fault(site="growth", action="oom", at=0)])
    with plan:
        res = supervise(
            TwoPhaseSys(5).checker().telemetry(),
            autosave_dir=str(tmp_path / "auto"), every_secs=0.0,
            max_restarts=3, sleep=lambda s: None,
            batch=batch, steps_per_call=8, capacity=1 << 12,
            queue_capacity=qcap, spill_bloom_bits=bloom,
        )
    assert res.restarts == 1
    assert res.degradations == ["spill_armed"]
    assert res.unique_state_count() == UNIQUE_2PC5
    assert res.state_count() == STATES_2PC5
    sp_status = res.checker.spill_status()
    assert sp_status["evictions"] >= 1  # evicted, did not grow past the wall
    res.checker.assert_properties()


def test_oom_without_spill_shrinks_the_resumed_batch(tmp_path):
    """The non-spill degradation path (here: POR requested, which spill
    refuses to compose with): an injected growth-OOM halves the
    expansion batch, and the halving actually LANDS on the resumed
    run's buffer layout — the supervise loop re-applies it to every
    freshly loaded generation (a one-shot snap mutation would be
    silently discarded)."""
    plan = FaultPlan([Fault(site="growth", action="oom", at=0)])
    with plan:
        res = supervise(
            TwoPhaseSys(3).checker().por().telemetry(),
            autosave_dir=str(tmp_path / "auto"), every_secs=0.0,
            max_restarts=2, sleep=lambda s: None,
            # cand=64 keeps the pre-sizing rule (cand*4 <= cap) from
            # inflating the table past every growth boundary — the run
            # must actually HIT a boundary for the fault to fire
            capacity=1 << 10, batch=64, steps_per_call=2, cand=64,
        )
    assert res.restarts == 1
    assert res.degradations == ["batch_shrunk(64->32)"]
    assert res.checker._batch == 32  # the shrink governed the resume
    assert res.unique_state_count() == UNIQUE_2PC3
    assert res.state_count() == STATES_2PC3
    res.checker.assert_properties()


def test_supervise_leaves_no_trail_on_the_builder(tmp_path):
    """Supervision state must not outlive the call: a later plain spawn
    from the same builder reports no restarts, no degradations, no
    autosave cadence into the supervisor's dir, and no armed spill
    tier — never a stale trail from the supervised run."""
    b = TwoPhaseSys(3).checker().telemetry()
    plan = FaultPlan([Fault(site="host_sync", action="kill", at=2)])
    with plan:
        res = supervise(
            b, autosave_dir=str(tmp_path / "auto"), every_secs=0.0,
            max_restarts=2, sleep=lambda s: None,
            capacity=1 << 12, batch=32, steps_per_call=2,
        )
    assert res.restarts == 1
    assert not hasattr(b, "_supervise_restarts")
    # config mutated for supervision (autosave arming, spill arming on
    # an OOM degradation) is restored too, not just the private attrs
    assert b.autosave_opts is None and b.spill_mode is None
    later = b.spawn_tpu(sync=True, capacity=1 << 12, batch=64)
    assert later.durability_status() is None


@pytest.mark.medium
def test_killed_parent_gets_stub_archived_and_lineage_gate_passes(
    tmp_path, capsys,
):
    """Cross-process recovery story end to end: a run killed before it
    could archive itself leaves only autosave generations; the next
    supervise over the same dir archives a checkpoint-derived STUB for
    the dead parent, resumes, completes — and ``compare parent child
    --expect=IDENTICAL`` passes the PR 12 lineage gate (resumed >=
    parent totals, discoveries preserved)."""
    from stateright_tpu.models._cli import compare_reports_cmd

    auto = str(tmp_path / "auto")
    runs = str(tmp_path / "runs")
    # "process 1": supervised run dies to an injected kill with the
    # restart budget exhausted (the in-process stand-in for SIGKILL —
    # nothing after the kill runs, no report, no archive)
    plan = FaultPlan([Fault(site="host_sync", action="kill", at=4)])
    with plan:
        with pytest.raises(InjectedKill):
            supervise(
                TwoPhaseSys(3).checker().telemetry().runs(runs),
                autosave_dir=auto, every_secs=0.0, max_restarts=0,
                sleep=lambda s: None,
                capacity=1 << 12, batch=32, steps_per_call=2,
            )
    _, man = ckpt.latest_generation(auto)
    parent_id = man["run_id"]
    from stateright_tpu.telemetry.registry import RunRegistry

    assert RunRegistry(runs).index() == []  # the parent never archived
    # "process 2": same command, same dirs — resumes and completes
    res = supervise(
        TwoPhaseSys(3).checker().telemetry().runs(runs),
        autosave_dir=auto, every_secs=0.0, max_restarts=0,
        sleep=lambda s: None,
        capacity=1 << 12, batch=32, steps_per_call=2,
    )
    res.checker.join()
    assert res.unique_state_count() == UNIQUE_2PC3
    child_id = res.checker.run_id
    reg = RunRegistry(runs)
    ids = [r["run_id"] for r in reg.index()]
    assert parent_id in ids and child_id in ids
    stub = reg.load(parent_id)
    assert stub["totals"]["interrupted"] is True
    assert stub["totals"]["done"] is False
    # the registry links the chain parent -> child
    chain = [r["run_id"] for r in reg.chain(child_id)]
    assert chain == [parent_id, child_id]
    # the one-command lineage gate (docs/telemetry.md "Comparing runs")
    capsys.readouterr()
    rc = compare_reports_cmd([
        parent_id, child_id, f"--registry={runs}", "--expect=IDENTICAL",
    ])
    out = capsys.readouterr().out
    assert rc == 0, out
    assert "lineage" in out


# -- cooperative yield (the fleet scheduler's preemption hook) ---------------


def test_supervise_yield_event_stops_and_marks_partial(tmp_path):
    """A pre-set ``yield_event`` asks the engine to stop at its next
    host sync: ``supervise`` returns ``yielded=True`` WITHOUT burning a
    restart, and a fresh ``supervise`` on the same dir continues the
    work (with fakes: the scripted second attempt completes)."""
    import threading

    from tests.fleet_fakes import FakeBuilder

    b = FakeBuilder(unique=5, states=8, depth=1,
                    spawn_plan={0: {"block": True}})
    ev = threading.Event()
    ev.set()
    run = supervise(b, autosave_dir=str(tmp_path), every_secs=60,
                    yield_event=ev)
    assert run.yielded is True
    assert run.restarts == 0  # a yield is not a failure
    resumed = supervise(b, autosave_dir=str(tmp_path), every_secs=60)
    assert resumed.yielded is False
    assert resumed.unique_state_count() == 5
    assert len(b.spawn_log) == 2


@pytest.mark.medium
def test_supervise_yielded_2pc4_resumes_bit_identical(tmp_path):
    """The yield/resume contract on a REAL engine (docs/fleet.md
    "Preemption"): a yielded run leaves a resumable final autosave
    generation, and re-supervising the same dir finishes with counts
    bit-identical to an uninterrupted run, linked by lineage."""
    import threading

    d = str(tmp_path / "auto")
    ev = threading.Event()
    ev.set()  # yield at the very first opportunity
    part = supervise(
        TwoPhaseSys(4).checker().telemetry(),
        autosave_dir=d, every_secs=0.0, yield_event=ev,
        batch=64, steps_per_call=2,
    )
    assert part.yielded is True
    assert ckpt.latest_gen_number(d) is not None  # resume point exists
    assert part.unique_state_count() < 1568  # genuinely partial
    done = supervise(
        TwoPhaseSys(4).checker().telemetry(),
        autosave_dir=d, every_secs=0.0,
        batch=64, steps_per_call=2,
    )
    assert done.yielded is False
    assert done.unique_state_count() == 1568
    assert done.state_count() == 8258
    assert done.checker.parent_run_id == part.checker.run_id
