"""The bench emission pipeline (bench.py) — the driver artifact's contract.

Every printed line is a complete, parseable result for everything known
so far; every line stays under ``MAX_LINE_BYTES``; later lines supersede
earlier ones; every line names the platform the device phase ran on, and
only ``platform == "tpu"`` can be ``fresh``, headline a value, or be
persisted — what a CPU run measured is stored under ``xlacpu_*``, never a
``tpu_*`` name; nothing is ever carried forward from a stored file; any
``*_error`` key is a non-zero exit; salvage recovers the last milestone
a killed child persisted; ``BENCH_VALIDATED.json`` is rewritten only by
full validated TPU runs (never by prefix runs or partial/errored phases).
"""

import importlib.util
import json
import os

import pytest

_BENCH = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "bench.py"
)


@pytest.fixture
def bench_env(tmp_path, monkeypatch):
    """Isolate bench.py from the repo's real BENCH_VALIDATED.json and
    docs/bench-last-details.json (a bare import must never clobber the
    shipping artifacts with test fixture data)."""
    monkeypatch.setenv(
        "BENCH_VALIDATED_FILE", str(tmp_path / "VALIDATED.json")
    )
    monkeypatch.setenv("BENCH_DETAILS_FILE", str(tmp_path / "details.json"))
    monkeypatch.delenv("BENCH_TPU_TARGET", raising=False)
    return tmp_path


def _load_bench():
    """Fresh module instance per test (emit keeps cumulative state)."""
    spec = importlib.util.spec_from_file_location("bench_under_test", _BENCH)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _lines(capsys):
    return [
        json.loads(l)
        for l in capsys.readouterr().out.strip().splitlines()
        if l.strip()
    ]


def test_every_emit_is_a_complete_parseable_line(bench_env, capsys):
    b = _load_bench()
    b.emit(cpu_paxos3_states_per_sec=8000.0)
    b.emit(platform="tpu", tpu_paxos3_states_per_sec=240_000.0)
    out = _lines(capsys)
    assert len(out) == 2
    # line 1 is already a valid final answer (value 0: no TPU number
    # has landed, and it says no device has reported yet)
    assert out[0]["value"] == 0.0 and out[0]["unit"] == "states/sec"
    assert out[0]["platform"] == "none yet" and out[0]["fresh"] is False
    # line 2 supersedes: value + vs_baseline recomputed from all extras
    assert out[1]["value"] == 240_000.0
    assert out[1]["vs_baseline"] == 30.0
    assert out[1]["fresh"] is True and out[1]["platform"] == "tpu"
    assert out[1]["cpu_baseline_states_per_sec"] == 8000.0


def test_perf_regression_guard_flags_fresh_slowdowns(bench_env, capsys):
    """ADVICE item 8: a FRESH run whose per-config states/s fall below
    REGRESS_TOLERANCE x the stored validated history emits a
    ``regressed: [...]`` entry naming the config, both rates, and the
    ratio; configs at/above tolerance (and configs the baseline never
    validated) stay out."""
    b = _load_bench()
    b.VALIDATED.update({
        "tpu_paxos3_states_per_sec": 266_699.0,
        "tpu_2pc7_states_per_sec": 100_000.0,
        "validated_at": "2025-01-01T00:00:00Z",
    })
    b.emit(
        platform="tpu",
        tpu_paxos3_states_per_sec=100_000.0,  # 0.375x: regression
        tpu_2pc7_states_per_sec=99_000.0,  # 0.99x: within tolerance
        tpu_2pc4_states_per_sec=50.0,  # never validated: cannot regress
    )
    line = _lines(capsys)[-1]
    assert line["fresh"] is True
    (entry,) = line["regressed"]
    assert entry["config"] == "tpu_paxos3_states_per_sec"
    assert entry["run"] == 100_000.0
    assert entry["baseline"] == 266_699.0
    assert entry["ratio"] == round(100_000.0 / 266_699.0, 3)
    details = json.load(open(os.environ["BENCH_DETAILS_FILE"]))
    assert details["regressed"] == [entry]


def test_stored_number_is_never_emitted(bench_env, capsys):
    """No carry-forward: with a stored validated result and no TPU number
    measured in THIS run, the line is value 0.0 / fresh false and the
    stored number appears nowhere — not as a value, not as an
    annotation — and the regression guard (which compares
    MEASUREMENTS) emits no ``regressed`` field at all."""
    with open(os.environ["BENCH_VALIDATED_FILE"], "w") as f:
        json.dump({"tpu_paxos3_states_per_sec": 266_699.0,
                   "validated_at": "2025-01-01T00:00:00Z",
                   "cpu_paxos3_uncontended_states_per_sec": 8188.4}, f)
    b = _load_bench()
    b.emit(cpu_paxos3_states_per_sec=4000.0, cpu_load1=2.5,
           error="device phase exited rc=1 without JSON")
    (line,) = _lines(capsys)
    assert line["fresh"] is False and line["value"] == 0.0
    assert line["vs_baseline"] == 0.0
    assert "266699" not in json.dumps(line)
    assert "stale" not in line and "validated_at" not in line
    assert "regressed" not in line and "error" in line
    details = json.load(open(os.environ["BENCH_DETAILS_FILE"]))
    assert "regressed" not in details and "trend" not in details
    assert "266699" not in json.dumps(details)
    # contended same-run CPU (4000 < 80% of stored 8188, load 2.5): the
    # stored uncontended CPU baseline is used and the choice is disclosed
    assert line["cpu_baseline_states_per_sec"] == 8188.4
    assert line["cpu_baseline_src"].startswith("stored-uncontended")


def test_cpu_backend_run_is_labelled_never_fresh_never_persisted(
    bench_env, capsys
):
    """The device phase on XLA:CPU (what CI runs): the child's keys leave
    it under ``xlacpu_*``, the headline stays value 0.0 / fresh false
    with ``platform: cpu`` on the line, and ``record_validated`` refuses
    — even when a (mislabelled) ``tpu_*`` rate sits in the extras."""
    b = _load_bench()
    child = b._label_by_platform({
        "platform": "cpu", "device_kind": "cpu", "device_count": 8,
        "tpu_devices": ["TFRT_CPU_0"],
        "tpu_paxos3_states_per_sec": 52_000.0, "tpu_paxos3_unique": 4000,
        "tpu_paxos2_discoveries": ["value chosen"],
        "tpu_2pc5_discoveries": ["abort agreement", "commit agreement"],
    })
    assert child["device_key_prefix"] == "xlacpu"
    assert child["xlacpu_paxos3_states_per_sec"] == 52_000.0
    assert not [k for k in child if k.startswith("tpu_")]
    b.emit(cpu_paxos3_states_per_sec=8000.0, **child)
    line = _lines(capsys)[-1]
    assert line["platform"] == "cpu" and line["device_kind"] == "cpu"
    assert line["fresh"] is False and line["value"] == 0.0
    assert "tpu_paxos3_states_per_sec" not in line
    b.record_validated()
    assert not os.path.exists(os.environ["BENCH_VALIDATED_FILE"])
    # the platform, not the key name, is what gates freshness
    b.emit(tpu_paxos3_states_per_sec=52_000.0)
    line = _lines(capsys)[-1]
    assert line["fresh"] is False and line["value"] == 0.0
    b.record_validated()
    assert not os.path.exists(os.environ["BENCH_VALIDATED_FILE"])
    # on a TPU the labelling is the identity
    tpu = {"platform": "tpu", "tpu_paxos3_states_per_sec": 1.0}
    assert b._label_by_platform(tpu) == tpu


def test_perf_regression_guard_clean_run_emits_empty_list(bench_env, capsys):
    """A fresh run at/above tolerance still carries the field — an empty
    list says the guard RAN and found nothing, distinct from a stale
    run where it never ran."""
    b = _load_bench()
    b.VALIDATED.update({
        "tpu_paxos3_states_per_sec": 100_000.0,
        "validated_at": "2025-01-01T00:00:00Z",
    })
    b.emit(platform="tpu", tpu_paxos3_states_per_sec=99_000.0)
    line = _lines(capsys)[-1]
    assert line["fresh"] is True
    assert line["regressed"] == []


def test_emit_suppresses_duplicate_lines(bench_env, capsys):
    b = _load_bench()
    b.emit(cpu_paxos3_states_per_sec=8000.0)
    b.emit(cpu_paxos3_states_per_sec=8000.0)  # no change -> no line
    assert len(_lines(capsys)) == 1


def test_every_line_is_small(bench_env, capsys):
    """A driver that stores only a ~2KB tail of stdout can never parse a
    longer line: every line must stay under MAX_LINE_BYTES with the four
    contract keys intact, no matter how much detail accumulates."""
    b = _load_bench()
    big = {f"tpu_cfg{i}_states_per_sec": float(i) * 7 for i in range(200)}
    b.emit(
        platform="tpu",
        cpu_paxos3_states_per_sec=8000.0,
        tpu_paxos3_states_per_sec=240_000.0,
        tpu_trace_tail=["x" * 100] * 20,
        **big,
    )
    raw = capsys.readouterr().out.strip().splitlines()
    assert raw
    for line in raw:
        assert len(line.encode()) <= b.MAX_LINE_BYTES
        d = json.loads(line)
        for key in ("metric", "value", "unit", "vs_baseline"):
            assert key in d
    # the bulk went to the details side file instead
    details = json.load(open(os.environ["BENCH_DETAILS_FILE"]))
    assert details["tpu_cfg199_states_per_sec"] == 199.0 * 7


def test_idle_same_run_baseline_replaces_stored(bench_env, capsys):
    """An idle-box (load1 < 0.7) same-run CPU rate is the new truth even
    when LOWER than the stored rate — no one-way ratchet."""
    with open(os.environ["BENCH_VALIDATED_FILE"], "w") as f:
        json.dump({"cpu_paxos3_uncontended_states_per_sec": 9999.0}, f)
    b = _load_bench()
    b.emit(cpu_paxos3_states_per_sec=7000.0, cpu_load1=0.1,
           platform="tpu", device_kind="TPU v5 lite", device_count=1,
           tpu_paxos3_states_per_sec=210_000.0,
           tpu_paxos3_unique=1_194_428,
           tpu_devices=["d0"],
           tpu_paxos2_discoveries=["value chosen"],
           tpu_2pc5_discoveries=["abort agreement", "commit agreement"])
    (line,) = _lines(capsys)
    assert line["cpu_baseline_states_per_sec"] == 7000.0
    assert line["cpu_baseline_src"] == "same-run"
    assert line["vs_baseline"] == 30.0
    b.record_validated()
    doc = json.load(open(os.environ["BENCH_VALIDATED_FILE"]))
    assert doc["cpu_paxos3_uncontended_states_per_sec"] == 7000.0
    assert doc["tpu_paxos3_states_per_sec"] == 210_000.0
    assert doc["validated_at"]
    assert (doc["platform"], doc["device_kind"]) == ("tpu", "TPU v5 lite")


def test_record_validated_skips_prefix_runs(bench_env, monkeypatch):
    """BENCH_TPU_TARGET prefix rates are overhead-dominated and must not
    overwrite the stored full-enumeration number."""
    monkeypatch.setenv("BENCH_TPU_TARGET", "50000")
    b = _load_bench()
    b.emit(platform="tpu", tpu_paxos3_states_per_sec=50_000.0,
           tpu_paxos2_discoveries=["value chosen"],
           tpu_2pc5_discoveries=["abort agreement"])
    b.record_validated()
    assert not os.path.exists(os.environ["BENCH_VALIDATED_FILE"])


def test_record_validated_requires_device_parity_evidence(bench_env):
    """A salvaged partial (killed before the 2pc5 device gate) or a
    phase with ANY recorded failure — phase-level or one leg's — carries
    a real number but must not persist as 'parity gates passed'."""
    b = _load_bench()
    b.emit(platform="tpu", tpu_paxos3_states_per_sec=300_000.0,
           tpu_paxos2_discoveries=["value chosen"])  # no 2pc5 gate ran
    b.record_validated()
    assert not os.path.exists(os.environ["BENCH_VALIDATED_FILE"])
    for failure in ({"error": "backend died after the timed run"},
                    {"tpu_2pc7_error": "XlaRuntimeError: oom"}):
        b2 = _load_bench()
        b2.emit(platform="tpu", tpu_paxos3_states_per_sec=300_000.0,
                tpu_paxos2_discoveries=["value chosen"],
                tpu_2pc5_discoveries=["abort agreement"], **failure)
        b2.record_validated()
        assert not os.path.exists(os.environ["BENCH_VALIDATED_FILE"])


def test_salvage_returns_last_parseable_milestone(bench_env, tmp_path):
    b = _load_bench()
    stage = tmp_path / "stages"
    stage.write_text(
        json.dumps({"tpu_devices": ["d0"]})
        + "\n"
        + json.dumps({"tpu_devices": ["d0"], "tpu_paxos3_states_per_sec": 9.0})
        + "\n"
        + '{"truncated by kill...'  # partial final write survives
    )
    assert b._salvage(str(stage))["tpu_paxos3_states_per_sec"] == 9.0


def test_salvage_missing_or_empty_file(bench_env, tmp_path):
    b = _load_bench()
    assert b._salvage(str(tmp_path / "absent")) == {}
    empty = tmp_path / "empty"
    empty.write_text("")
    assert b._salvage(str(empty)) == {}


def test_driver_parse_of_last_line(bench_env, capsys):
    """The driver's contract: parse the LAST stdout line as the result."""
    b = _load_bench()
    b.emit(cpu_paxos3_states_per_sec=8000.0)
    b.emit(platform="tpu", device_kind="TPU v5 lite", device_count=1)
    b.emit(tpu_paxos3_states_per_sec=320_000.0,
           tpu_paxos3_unique=1_194_428)
    last = _lines(capsys)[-1]
    assert last["value"] == 320_000.0
    assert last["vs_baseline"] == 40.0
    assert "error" not in last
    assert last["tpu_paxos3_unique"] == 1_194_428
    assert (last["platform"], last["device_kind"], last["device_count"]) == (
        "tpu", "TPU v5 lite", 1
    )


def test_phase_breakdown_reaches_details_file(bench_env, capsys):
    """The per-phase/per-stage breakdown is a details-file artifact (the
    headline line stays small): emitting it must land it in
    docs/bench-last-details.json verbatim."""
    b = _load_bench()
    stages = {"compile_secs": 1.25, "device_secs": 7.5, "growth_secs": 0.1,
              "wall_secs": 9.0, "host_secs": 0.15}
    phases = {"backend_init_secs": 2.0, "paxos3_warmup_secs": 11.0,
              "paxos3_run_secs": 9.0}
    b.emit(cpu_paxos3_states_per_sec=8000.0, platform="tpu",
           tpu_paxos3_states_per_sec=300000.0,
           tpu_paxos3_stages=stages, tpu_phases=phases)
    details = json.load(open(os.environ["BENCH_DETAILS_FILE"]))
    assert details["tpu_paxos3_stages"] == stages
    assert details["tpu_phases"] == phases
    for line in capsys.readouterr().out.strip().splitlines():
        assert len(line.encode()) <= b.MAX_LINE_BYTES


def test_record_validated_persists_stage_breakdown(bench_env):
    b = _load_bench()
    stages = {"compile_secs": 1.0, "device_secs": 7.0, "wall_secs": 9.0,
              "host_secs": 1.0}
    b.emit(cpu_paxos3_states_per_sec=7000.0, cpu_load1=0.1,
           platform="tpu", tpu_paxos3_states_per_sec=210000.0,
           tpu_paxos3_stages=stages,
           cpu_baseline_engine="native-cpp-bfs",
           tpu_paxos2_discoveries=["value chosen"],
           tpu_2pc5_discoveries=["abort agreement"])
    b.record_validated()
    doc = json.load(open(os.environ["BENCH_VALIDATED_FILE"]))
    assert doc["tpu_paxos3_stages"] == stages
    assert doc["cpu_baseline_engine"] == "native-cpp-bfs"


def test_ab_table_mode_with_injected_runner(bench_env, capsys):
    """--ab-table: both legs at the same capacity, 2pc10 targeted at
    2pc7's unique volume, ratio on the line, full legs in the side
    file."""
    b = _load_bench()
    calls = []

    def fake_run(rm, target):
        calls.append((rm, target))
        return {"states_per_sec": 1450000.0 if rm == 7 else 866000.0,
                "states": 10, "unique": 296448 if rm == 7 else 296000,
                "sec": 1.0, "occupancy_last": {"load_factor": 0.1},
                "stages": {"device_secs": 1.0}, "growth_events": 0}

    rc = b.ab_table(run_one=fake_run, platform="tpu")
    assert rc == 0
    assert calls == [(7, None), (10, 296448)]  # same insert volume
    (line,) = [json.loads(l) for l in
               capsys.readouterr().out.strip().splitlines()]
    assert line["tpu_2pc7_states_per_sec"] == 1450000.0
    assert line["ratio_7_over_10"] == round(1450000.0 / 866000.0, 3)
    assert len(json.dumps(line).encode()) <= b.MAX_LINE_BYTES
    side = os.environ["BENCH_DETAILS_FILE"].replace(
        ".json", "-ab-table.json"
    )
    full = json.load(open(side))
    assert full["tpu_2pc7_ab"]["occupancy_last"] == {"load_factor": 0.1}


def test_ab_table_failure_emits_one_line_rc1(bench_env, capsys):
    b = _load_bench()

    def broken(rm, target):
        raise RuntimeError("backend down")

    rc = b.ab_table(run_one=broken, platform="cpu")
    assert rc == 1
    (line,) = [json.loads(l) for l in
               capsys.readouterr().out.strip().splitlines()]
    assert "backend down" in line["error"] and line["platform"] == "cpu"


def test_ab_table_off_tpu_stores_nothing_under_tpu_names(bench_env, capsys):
    b = _load_bench()
    leg = {"states_per_sec": 9.0, "states": 10, "unique": 5, "sec": 1.0}
    assert b.ab_table(run_one=lambda rm, t: leg, platform="cpu") == 0
    (line,) = [json.loads(l) for l in
               capsys.readouterr().out.strip().splitlines()]
    assert line["platform"] == "cpu"
    assert line["xlacpu_2pc7_states_per_sec"] == 9.0
    assert not [k for k in line if k.startswith("tpu_")]


def test_trend_deltas_cover_every_validated_config(bench_env, capsys):
    """A fresh run's details carry ``trend``: EVERY measured
    tpu_*_states_per_sec with a stored history value and its ratio —
    improvements and regressions alike (``regressed`` stays the
    below-tolerance subset); never-validated configs have no trend
    entry, and stale runs carry no trend at all."""
    b = _load_bench()
    b.VALIDATED.update({
        "tpu_paxos3_states_per_sec": 200_000.0,
        "tpu_2pc7_states_per_sec": 100_000.0,
        "validated_at": "2025-01-01T00:00:00Z",
    })
    b.emit(
        platform="tpu",
        tpu_paxos3_states_per_sec=100_000.0,  # 0.5x: regression + trend
        tpu_2pc7_states_per_sec=150_000.0,  # 1.5x: improvement, trend only
        tpu_2pc4_states_per_sec=50.0,  # never validated: no trend
    )
    details = json.load(open(os.environ["BENCH_DETAILS_FILE"]))
    trend = {e["config"]: e for e in details["trend"]}
    assert set(trend) == {
        "tpu_paxos3_states_per_sec", "tpu_2pc7_states_per_sec"
    }
    assert trend["tpu_2pc7_states_per_sec"]["ratio"] == 1.5
    assert [e["config"] for e in details["regressed"]] == [
        "tpu_paxos3_states_per_sec"
    ]
    # trend is a details-artifact field, never a headline-line key
    assert "trend" not in _lines(capsys)[-1]
    # no TPU number measured: no trend
    b2 = _load_bench()
    b2.VALIDATED.update({
        "tpu_paxos3_states_per_sec": 200_000.0,
        "validated_at": "2025-01-01T00:00:00Z",
    })
    b2.emit(cpu_paxos3_states_per_sec=8000.0)
    details = json.load(open(os.environ["BENCH_DETAILS_FILE"]))
    assert "trend" not in details


def test_record_validated_embeds_the_run_report(bench_env):
    """A validated full run persists its embedded tpu_paxos3_report into
    BENCH_VALIDATED.json — the baseline half of ``regress.py --diff``
    (pre-registry baselines simply lack the key)."""
    b = _load_bench()
    rep = {"v": 1, "model": "PaxosModel",
           "config": {"key": "k"}, "totals": {"unique": 42}}
    b.EXTRAS.update({
        "platform": "tpu",
        "tpu_paxos3_states_per_sec": 250_000.0,
        "tpu_paxos2_discoveries": ["value chosen"],
        "tpu_2pc5_discoveries": ["abort agreement"],
        "tpu_paxos3_report": rep,
    })
    b.record_validated()
    doc = json.load(open(os.environ["BENCH_VALIDATED_FILE"]))
    assert doc["tpu_paxos3_report"] == rep


def test_main_consumes_run_ledger_env_no_double_record(
    bench_env, monkeypatch, capsys
):
    """main() CONSUMES STATERIGHT_TPU_RUN_DIR into RUN_LEDGER_DIR (every
    process: parent/child/ab-table): legs register explicitly and
    leg-tagged via _register, and with the env knob gone the checkers'
    join-time auto-record cannot double-archive the same run_id (which
    would also pollute the index with untagged warm-up/CPU records)."""
    import sys

    from stateright_tpu.models.two_phase_commit import TwoPhaseSys
    from stateright_tpu.telemetry.registry import RunRegistry

    ledger = str(bench_env / "ledger")
    monkeypatch.setenv("STATERIGHT_TPU_RUN_DIR", ledger)
    monkeypatch.setattr(sys, "argv", ["bench.py", "--device-child"])
    b = _load_bench()
    monkeypatch.setattr(b, "device_phase", lambda: {"platform": "cpu"})
    assert b.main() == 0  # the child path runs main()'s consumption
    assert b.RUN_LEDGER_DIR == ledger
    assert "STATERIGHT_TPU_RUN_DIR" not in os.environ
    # a post-consumption checker run does NOT auto-record...
    c = TwoPhaseSys(2).checker().spawn_tpu(
        sync=True, capacity=1 << 11, batch=64
    )
    c.join()
    assert RunRegistry(ledger).index() == []
    # ...and the explicit leg registration is the single, tagged record
    RunRegistry(b.RUN_LEDGER_DIR).record(c, leg="2pc2")
    idx = RunRegistry(ledger).index()
    assert [(r["run_id"], r.get("leg")) for r in idx] == [(c.run_id, "2pc2")]


def test_device_child_leg_error_is_a_nonzero_exit(
    bench_env, monkeypatch, capsys
):
    """A leg that raised records ``*_error`` next to the numbers that
    landed; the numbers are still printed, the exit code is non-zero —
    for the leg-level key and for a phase that raised outright."""
    import sys

    monkeypatch.setattr(sys, "argv", ["bench.py", "--device-child"])
    b = _load_bench()
    monkeypatch.setattr(b, "device_phase", lambda: {
        "platform": "tpu", "tpu_paxos3_states_per_sec": 300_000.0,
        "tpu_2pc7_error": "XlaRuntimeError: RESOURCE_EXHAUSTED",
    })
    assert b.main() == 1
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert out["tpu_paxos3_states_per_sec"] == 300_000.0
    assert "RESOURCE_EXHAUSTED" in out["tpu_2pc7_error"]

    def dies():
        b.device_phase.partial = {"platform": "cpu", "tpu_paxos2_x": 1}
        raise RuntimeError("backend init failed")

    dies.partial = {}
    monkeypatch.setattr(b, "device_phase", dies)
    assert b.main() == 1
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert "backend init failed" in out["error"]
    assert out["xlacpu_paxos2_x"] == 1  # labelled on the failure path too
    # a budget skip is not a failure
    monkeypatch.setattr(b, "device_phase", lambda: {
        "platform": "tpu", "tpu_2pc7_skipped": "phase budget mostly spent",
    })
    assert b.main() == 0
