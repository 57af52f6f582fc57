"""Golden schema for the flight-recorder JSONL export.

Downstream tooling (regress.py, the report renderer, the driver's
artifact parsers, external dashboards) reads these records by field name.
This test pins the export schema — field names AND types, per record
kind — so exporter drift breaks HERE instead of in a consumer three
rounds later.  The schema is versioned: the JSONL header carries
``v`` (:data:`stateright_tpu.telemetry.export.SCHEMA_V`); bump it (and
this golden) together when the shape legitimately changes.

The rule per kind: required fields must all be present with the pinned
types; any OTHER field must be in the kind's allowed-optional set —
an unknown field is drift, not decoration.  ``note`` records are the
explicit free-form escape hatch and are exempt.
"""

import json
import numbers

from stateright_tpu.models.two_phase_commit import TwoPhaseSys
from stateright_tpu.telemetry.export import SCHEMA_V

# (required, optional) field -> type per record kind.  ``numbers.Real``
# covers int-or-float counters; bool is pinned apart from int where the
# distinction carries meaning (cache_hit, stalled).
_REAL = numbers.Real
SCHEMA = {
    "step": (
        {
            "engine": str, "dt": _REAL, "states": int, "unique": int,
            "d_states": int, "d_unique": int, "dedup": _REAL,
        },
        {
            # engine-specific annotations: the device engines add
            # capacities + table load, mp adds round/frontier, pool adds
            # its work-queue length
            "depth": int, "status": _REAL, "queue": int, "cap": int,
            "cand": int, "load_factor": _REAL, "frontier": int,
            "round": int,
            # explicit liveness for the health model's stall guard (an
            # engine with no queue count to send: the thread pool)
            "busy": bool,
            # engine-run span binding (telemetry/spans.py): steps of a
            # traced run carry their engine_run span id
            "span": str,
            # the device call this sync closed: its while_loop's trip
            # count, and the lanes a step pops (dsteps * batch offered)
            "dsteps": int, "batch": int,
            # that call's chunk writes of the queue append (the mesh
            # engine counts them as one device does: one loop, PR 54)
            "append_chunks": int,
        },
    ),
    "growth": (
        {"status": str},
        # path: where the carry was transformed (``device`` / ``host``),
        # with the bytes the event moved each way (PR 48)
        {"unique": int, "cap": int, "qcap": int, "cand": int,
         "from_init": bool, "path": str, "d2h_bytes": int,
         "h2d_bytes": int},
    ),
    "occupancy": (
        {
            "at": str, "nbuckets": int, "slots_per_bucket": int,
            "occupied": int, "load_factor": _REAL, "mean_bucket": _REAL,
            "max_bucket": int, "full_buckets": int,
            "poisson_full_expect": _REAL, "histogram": list,
        },
        {},
    ),
    "compile": (
        {"rung": str, "source": str, "cache_hit": bool,
         "duration": _REAL},
        {"cap": int, "qcap": int, "batch": int, "cand": int, "fcap": int,
         "bucket_cap": int, "prewarm_ready": bool, "build_secs": _REAL,
         # memory ledger on: the executable's compile-time memory
         # analysis (temp/argument/output bytes), backfilled via amend()
         "memory": dict},
    ),
    "profile": (
        {"event": str},
        {"logdir": str, "steps": int, "error": str, "detail": str,
         "span": str},
    ),
    "span": (
        # span-structured tracing (telemetry/spans.py,
        # docs/observability.md): one record per closed span, written at
        # close time (``t``), with its ``start`` on the same clock.  The
        # optional set is the union of per-span attrs: engine/error
        # (engine_run, attempt), attempt ordinal, gen (autosave), pending
        # (spill_drain), cap/unique (grow), key/slot (fleet
        # job), jobs/slots (fleet root), rung/source (engine_acquire),
        # dsteps (device_call), jaxprs_traced (dispatch), hit/retrieved_s
        # (program.load), status (grow), bytes (reconstruct.pull), path /
        # lookups (reconstruct.parents), the universes, row,
        # table bytes (tabulated and on the device), record words, slot-lane
        # gathers, history codec, lossiness and action columns of a
        # compiled actor twin (twin_compile)
        {"v": int, "name": str, "trace_id": str, "span_id": str,
         "start": _REAL, "dur": _REAL},
        {"parent_id": str, "engine": str, "error": str, "attempt": int,
         "gen": int, "pending": int, "cap": int, "unique": int,
         "key": str, "slot": int, "jobs": int, "slots": int,
         "rung": str, "source": str, "dsteps": int, "status": str,
         "jaxprs_traced": int, "hit": bool, "retrieved_s": _REAL,
         "actor_states": str, "envelopes": int, "n_slots": int,
         "row_width": int, "table_bytes": int, "hist_strategy": str,
         "hist_threads": int, "hist_bits": int, "lossy": bool,
         "max_actions": int, "device_table_bytes": int,
         "record_words": int, "step_gathers": int,
         "bytes": int, "path": str, "lookups": int},
    ),
    "health": (
        {"v": int, "event": str},
        {"phase": str, "reason": str},
    ),
    "cartography": (
        {
            "v": int, "at": str, "depth_hist": list, "action_hist": list,
            "props": list, "fresh_inserts": int, "duplicate_hits": int,
        },
        {"shard_load": list, "shard_imbalance": dict,
         "route_matrix": list},
    ),
    "spill": (
        # spill-tier events (stateright_tpu/spill/, docs/spill.md):
        # arm (run start), evict (hot table -> host tier), resolve
        # (pending vs the host index), queue_offload/queue_refill
        # (budget-blocked queue doubling), final
        {"v": int, "event": str},
        {"bloom_bits": int, "pend_cap": int, "budget_bytes": int,
         "evicted": int, "spilled_fps": int, "host_bytes": int,
         "disk_bytes": int, "bloom_est_false_pos": _REAL,
         "pending": int, "dups": int, "novel": int,
         "rows": int, "host_rows": int},
    ),
    "roofline": (
        # the roofline cost ledger's spawn-time record
        # (telemetry/roofline.py): per-stage analytic FLOPs/bytes +
        # totals + the XLA-reconciliation verdict.  Emitted once at
        # init — the static model cannot change mid-run.
        {
            "v": int, "at": str, "engine": str, "stages": dict,
            "totals": dict, "reconciled": bool,
        },
        {},
    ),
    "checkpoint": (
        # autosave generation writes (stateright_tpu/checkpoint.py,
        # docs/robustness.md): ok=False records a degraded (failed)
        # write — the run continues, the record discloses it
        {"v": int, "gen": int, "ok": bool},
        {"unique": int, "states": int, "secs": _REAL, "error": str},
    ),
    "fault": (
        # a FaultPlan delivery (testing/faults.py): site + action + the
        # occurrence ordinal it fired at — the chaos run's ring trail
        {"v": int, "site": str, "action": str, "at": int},
        {},
    ),
    "restart": (
        # a supervised resume (supervisor.py): attempt ordinal + the
        # failure class that caused it; parent_run_id links the lineage
        {"v": int, "attempt": int, "reason": str},
        {"parent_run_id": str, "degradation": str},
    ),
    "sweep": (
        # hyper-batched instance sweeps (stateright_tpu/sweep/,
        # docs/sweep.md): cohort_compile (one per compiled shape
        # cohort), instance_done (per-instance totals at extraction),
        # summary (instances/cohorts/compile amortization at run end)
        {"v": int, "event": str},
        {"cohort": int, "instances": int, "width": int, "arity": int,
         "unified": bool, "key": str, "unique": int, "states": int,
         "depth": int, "cohorts": int, "engine_compiles": int},
    ),
    "fleet": (
        # fleet-scheduler pool bookkeeping (stateright_tpu/fleet/,
        # docs/fleet.md): start (pool opens) and done (pool drained,
        # with the terminal tallies + compile accounting)
        {"v": int, "event": str, "slots": int, "jobs": int},
        {"completed": int, "failed": int, "refused": int,
         "preemptions": int, "engine_compiles": int, "packed": int},
    ),
    "job": (
        # per-tenant lifecycle (stateright_tpu/fleet/, docs/fleet.md):
        # submit -> place (admission decision) -> [pack] -> [preempt ->
        # resume]* -> done; gen is the autosave generation a preempted
        # job yields at / resumes from, run_id/parent_run_id the
        # registry lineage the exactly-once gate walks
        {"v": int, "event": str, "key": str},
        {"priority": int, "decision": str, "reason": str, "slot": int,
         "cohort": str, "jobs": int, "gen": int, "status": str,
         "unique": int, "states": int, "run_id": str,
         "parent_run_id": str},
    ),
    "memory": (
        # the HBM ledger's per-rung snapshot (telemetry/memory.py):
        # per-buffer analytic bytes + the growth-transient forecast;
        # live device stats / budget / exec analysis appear only where
        # the backend provides them
        {
            "v": int, "at": str, "engine": str, "capacity": int,
            "buffers": dict, "total_bytes": int, "next_rung": dict,
        },
        {"queue_capacity": int, "frontier_capacity": int, "devices": int,
         "per_device_bytes": int, "budget_bytes": int, "budget_src": str,
         "exec": dict, "device": dict},
    ),
}
_ENVELOPE = {"seq": int, "t": _REAL, "kind": str}


def _check_record(rec: dict) -> list:
    problems = []
    for k, t in _ENVELOPE.items():
        if not isinstance(rec.get(k), t):
            problems.append(f"envelope field {k} missing/mistyped: {rec}")
    kind = rec.get("kind")
    if kind == "note":
        return problems  # free-form by design
    if kind not in SCHEMA:
        return problems + [f"unknown record kind {kind!r}: {rec}"]
    required, optional = SCHEMA[kind]
    body = {k: v for k, v in rec.items() if k not in _ENVELOPE}
    for k, t in required.items():
        if k not in body:
            problems.append(f"{kind}: missing required field {k}")
        elif isinstance(body[k], bool) and t is not bool:
            problems.append(f"{kind}.{k}: bool where {t} pinned")
        elif not isinstance(body[k], t):
            problems.append(
                f"{kind}.{k}: {type(body[k]).__name__} != pinned "
                f"{getattr(t, '__name__', t)}"
            )
    for k, v in body.items():
        if k in required:
            continue
        if k not in optional:
            problems.append(
                f"{kind}: UNKNOWN field {k!r} (drift — add it to the "
                "golden schema deliberately, with its consumer)"
            )
        elif v is not None and not isinstance(v, optional[k]):
            problems.append(
                f"{kind}.{k}: {type(v).__name__} != pinned "
                f"{getattr(optional[k], '__name__', optional[k])}"
            )
    return problems


def _export_lines(tmp_path, builder, **spawn_kw):
    c = builder.spawn_tpu(sync=True, **spawn_kw)
    path = tmp_path / "export.jsonl"
    c.flight_recorder.to_jsonl(path)
    return [json.loads(ln) for ln in path.read_text().splitlines() if ln]


def test_jsonl_header_is_versioned(tmp_path):
    lines = _export_lines(
        tmp_path,
        TwoPhaseSys(3).checker().telemetry(),
        capacity=1 << 12, batch=64,
    )
    header = lines[0]
    assert header["kind"] == "header"
    assert header["v"] == SCHEMA_V == 1
    assert isinstance(header["meta"], dict)
    assert isinstance(header["capacity"], int)
    assert isinstance(header["summary"], dict)


def test_every_exported_record_matches_the_golden_schema(tmp_path):
    """One run exercising every record kind the wavefront engine can emit
    (steps, growth, occupancy, compile, health, cartography, memory),
    validated field-by-field against the pinned schema."""
    lines = _export_lines(
        tmp_path,
        TwoPhaseSys(5).checker().telemetry(
            occupancy_every=2, cartography=True, memory=True,
            roofline=True,
        ),
        capacity=1 << 10, batch=256,  # tiny: forces growth events
    )
    records = [ln for ln in lines if ln.get("kind") != "header"]
    kinds = {r["kind"] for r in records}
    for expect in ("step", "growth", "occupancy", "compile", "health",
                   "cartography", "memory", "roofline"):
        assert expect in kinds, f"run did not exercise {expect!r} records"
    problems = []
    for r in records:
        problems += _check_record(r)
    assert not problems, "\n".join(problems)


def test_span_records_are_versioned_and_carry_their_start(tmp_path):
    """``SPAN_V`` 2: every ``span`` record holds its ``start`` beside ``dur``
    and the close time ``t``, on one clock, and with the memory ledger on
    the programs built ahead of time hang under their ``engine_acquire``."""
    from stateright_tpu.telemetry.spans import (
        PROGRAM_LOAD, PROGRAM_LOWER, SPAN_V,
    )

    lines = _export_lines(
        tmp_path, TwoPhaseSys(3).checker().telemetry(memory=True),
        capacity=1 << 12, batch=64,
    )
    found = [ln for ln in lines if ln.get("kind") == "span"]
    assert found and SPAN_V == 2
    for r in found:
        assert r["v"] == SPAN_V
        assert abs(r["start"] + r["dur"] - r["t"]) <= 1e-6, r
    by_id = {r["span_id"]: r for r in found}
    programs = [r for r in found if r["name"] in (PROGRAM_LOWER, PROGRAM_LOAD)]
    assert {r["name"] for r in programs} == {PROGRAM_LOWER, PROGRAM_LOAD}
    assert {by_id[r["parent_id"]]["name"] for r in programs} <= {
        "engine_acquire", "dispatch"
    }
    assert "engine_acquire" in {by_id[r["parent_id"]]["name"] for r in programs}


def test_spill_records_match_the_golden_schema(tmp_path, monkeypatch):
    """A run under a simulated budget that forces eviction emits the
    versioned ``spill`` record kind (arm/evict/resolve/final), every
    record validated field-by-field like the rest of the export."""
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
    sp = (bloom, batch * twin.max_actions)

    def tot(cap):
        return total_bytes(
            wavefront_specs(twin, n_props, cap, qcap, batch, spill=sp)
        )

    monkeypatch.setenv(ENV_DEVICE_BYTES, str(tot(1 << 13) + tot(1 << 14) - 1))
    monkeypatch.setenv("STATERIGHT_TPU_CAPACITY_GUARD", "off")
    lines = _export_lines(
        tmp_path,
        TwoPhaseSys(5).checker().spill().telemetry(),
        capacity=1 << 12, batch=batch, queue_capacity=qcap,
        spill_bloom_bits=bloom, steps_per_call=8,
    )
    records = [ln for ln in lines if ln.get("kind") != "header"]
    spills = [r for r in records if r["kind"] == "spill"]
    events = {r["event"] for r in spills}
    for expect in ("arm", "evict", "resolve", "final"):
        assert expect in events, f"run did not emit a spill {expect!r} event"
    problems = []
    for r in records:
        problems += _check_record(r)
    assert not problems, "\n".join(problems)
    # the summary carries the live spill block alongside memory/cartography
    assert lines[0]["summary"]["spill"]["spilled_fps"] > 0


def test_checkpoint_fault_restart_records_match_the_golden_schema(tmp_path):
    """A supervised chaos run (kill injected mid-flight, autosave every
    sync) exercises the versioned ``checkpoint`` + ``restart`` record
    kinds; the killed attempt's recorder carries the ``fault`` record.
    Every record validates field-by-field like the rest of the export."""
    from stateright_tpu.supervisor import supervise
    from stateright_tpu.testing.faults import Fault, FaultPlan

    killed_recs = []

    def spawn(b, resume=None, **kw):
        c = b.spawn_tpu(resume=resume, **kw)
        killed_recs.append(c.flight_recorder)
        return c

    plan = FaultPlan([Fault(site="host_sync", action="kill", at=3)])
    with plan:
        res = supervise(
            TwoPhaseSys(3).checker().telemetry(),
            autosave_dir=str(tmp_path / "auto"), every_secs=0.0,
            max_restarts=2, sleep=lambda s: None, spawn=spawn,
            capacity=1 << 12, batch=64, steps_per_call=2,
        )
    assert res.restarts == 1
    path = tmp_path / "export.jsonl"
    res.checker.flight_recorder.to_jsonl(path)
    # the fault record landed in the KILLED attempt's ring
    killed_recs[0].to_jsonl(path, append=True)
    lines = [json.loads(ln) for ln in path.read_text().splitlines() if ln]
    records = [ln for ln in lines if ln.get("kind") != "header"]
    kinds = {r["kind"] for r in records}
    for expect in ("checkpoint", "restart", "fault"):
        assert expect in kinds, f"run did not exercise {expect!r} records"
    problems = []
    for r in records:
        problems += _check_record(r)
    assert not problems, "\n".join(problems)
    # the summary carries the durability block alongside the others
    assert lines[0]["summary"]["durability"]["restarts"] == 1


def test_sweep_records_match_the_golden_schema(tmp_path):
    """A two-instance sweep emits the versioned ``sweep`` record kind
    (cohort_compile / instance_done / summary), every record validated
    field-by-field, and the export round-trips through from_jsonl."""
    from stateright_tpu.models.two_phase_commit import sweep_family
    from stateright_tpu.telemetry import FlightRecorder

    spec = sweep_family(2)
    c = (
        spec.instances[0].model.checker().telemetry()
        .sweep(spec)
        .spawn_tpu(sync=True, capacity=1 << 12, batch=64)
    )
    path = tmp_path / "export.jsonl"
    c.flight_recorder.to_jsonl(path)
    lines = [json.loads(ln) for ln in path.read_text().splitlines() if ln]
    records = [ln for ln in lines if ln.get("kind") != "header"]
    sweeps = [r for r in records if r["kind"] == "sweep"]
    events = [r["event"] for r in sweeps]
    assert events.count("cohort_compile") == 1
    assert events.count("instance_done") == 2
    assert events[-1] == "summary"
    problems = []
    for r in records:
        problems += _check_record(r)
    assert not problems, "\n".join(problems)
    # round-trip: the restored ring carries the same sweep records
    rec2 = FlightRecorder.from_jsonl(path)
    assert [
        (r["event"], r.get("key")) for r in rec2.records("sweep")
    ] == [(r["event"], r.get("key")) for r in sweeps]


def test_fleet_records_match_the_golden_schema(tmp_path):
    """A scheduled fleet emits the versioned ``fleet``/``job`` record
    kinds (submit/place/preempt/resume/done + start/done), every record
    validated field-by-field, and the export round-trips through
    from_jsonl — without spawning a single engine (fake builders: the
    schema is the scheduler's, not the engines')."""
    from stateright_tpu.fleet import FleetSpec, Job, run_fleet
    from stateright_tpu.telemetry import FlightRecorder
    from tests.fleet_fakes import FakeBuilder

    spec = FleetSpec(
        jobs=[
            Job(key="a", build=lambda: FakeBuilder(unique=7, states=9)),
            Job(key="b", build=lambda: FakeBuilder(unique=3, states=4),
                priority=1),
        ],
        slots=1,
    )
    res = run_fleet(spec, root=str(tmp_path / "fleet"))
    path = tmp_path / "export.jsonl"
    res.recorder.to_jsonl(path)
    lines = [json.loads(ln) for ln in path.read_text().splitlines() if ln]
    records = [ln for ln in lines if ln.get("kind") != "header"]
    fleet = [r for r in records if r["kind"] == "fleet"]
    jobs = [r for r in records if r["kind"] == "job"]
    assert [r["event"] for r in fleet] == ["start", "done"]
    events = [(r["event"], r["key"]) for r in jobs]
    for key in ("a", "b"):
        for ev in ("submit", "place", "done"):
            assert (ev, key) in events, f"missing {ev}/{key}"
    problems = []
    for r in records:
        problems += _check_record(r)
    assert not problems, "\n".join(problems)
    # the summary carries the final pool snapshot alongside the others
    assert lines[0]["summary"]["fleet"]["slots"] == 1
    # round-trip: the restored ring carries the same job records AND
    # the reconciled pool snapshot
    rec2 = FlightRecorder.from_jsonl(path)
    assert [
        (r["event"], r["key"]) for r in rec2.records("job")
    ] == events
    assert rec2.fleet() == lines[0]["summary"]["fleet"]


def test_summary_cartography_block_matches_snapshot_schema(tmp_path):
    """The summary's embedded cartography block is the same shape as the
    ring records minus the envelope/at: consumers share one parser."""
    lines = _export_lines(
        tmp_path,
        TwoPhaseSys(3).checker().telemetry(cartography=True),
        capacity=1 << 12, batch=64,
    )
    cart = lines[0]["summary"]["cartography"]
    required, optional = SCHEMA["cartography"]
    for k in required:
        if k == "at":
            continue  # summary holds the latest snapshot, not a series
        assert k in cart, f"summary cartography missing {k}"
    for k in cart:
        assert k in required or k in optional
    props = cart["props"]
    assert all(
        sorted(p) == ["condition_hits", "evaluated", "name"]
        for p in props
    )


def test_summary_roofline_block_matches_report_block_shape(tmp_path):
    """The summary's embedded roofline block is the live-snapshot shape
    (static block + reconciliation/verdicts): the per-stage map and the
    totals parse with the same reader as the run report's block."""
    lines = _export_lines(
        tmp_path,
        TwoPhaseSys(3).checker().telemetry(roofline=True),
        capacity=1 << 12, batch=64,
    )
    roof = lines[0]["summary"]["roofline"]
    assert isinstance(roof["v"], int)
    assert isinstance(roof["stages"], dict) and roof["stages"]
    for s in roof["stages"].values():
        for k in ("flops", "bytes_read", "bytes_written"):
            assert isinstance(s[k], int) and s[k] >= 0
    assert roof["totals"]["flops"] == sum(
        s["flops"] for s in roof["stages"].values()
    )
    assert roof["reconciliation"]["ok"] is True


def test_costmodel_verb_out_round_trips(tmp_path):
    """The ``costmodel`` verb's ``--out=`` fixture: the written JSON
    parses back into versioned per-config blocks whose stage maps and
    totals satisfy the regress gate's well-formedness rules."""
    from stateright_tpu.models import two_phase_commit

    out = tmp_path / "costmodel.json"
    two_phase_commit.main(["costmodel", f"--out={out}"])
    doc = json.loads(out.read_text())
    assert isinstance(doc["v"], int)
    assert doc["configs"], "no config blocks written"
    for blk in doc["configs"]:
        assert isinstance(blk["label"], str)
        assert isinstance(blk["stages"], dict) and blk["stages"]
        assert blk["totals"]["flops"] == sum(
            s["flops"] for s in blk["stages"].values()
        )
        assert blk["totals"]["bytes"] == sum(
            s["bytes_read"] + s["bytes_written"]
            for s in blk["stages"].values()
        )
        assert blk["reconciliation"]["ok"] is True
        assert isinstance(blk["mxu_candidates"], list)


def test_summary_memory_block_matches_snapshot_schema(tmp_path):
    """The summary's embedded memory block is the ring records' shape
    minus the envelope/at (the ``v`` field rides inside the snapshot):
    consumers share one parser."""
    lines = _export_lines(
        tmp_path,
        TwoPhaseSys(3).checker().telemetry(memory=True),
        capacity=1 << 12, batch=64,
    )
    mem = lines[0]["summary"]["memory"]
    required, optional = SCHEMA["memory"]
    for k in required:
        if k == "at":
            continue  # summary holds the latest snapshot, not a series
        assert k in mem, f"summary memory missing {k}"
    for k in mem:
        assert k in required or k in optional
    assert mem["total_bytes"] == sum(mem["buffers"].values())
