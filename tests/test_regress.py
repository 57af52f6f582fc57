"""regress.py — the perf-regression gate over bench summaries.

Pins the contract: an artifact with no number measured on a TPU NEVER
validates; per-config throughput below tolerance x baseline fails loudly
with the offending configs named; improvements are reported, not punished;
a run bench labelled as off-chip (``platform: cpu``, ``xlacpu_*`` keys) is
compared with nothing but still answers for its own blocks; a missing
baseline file is "no baseline recorded yet", not an error.
"""

import importlib.util
import json
import os

_REGRESS = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "regress.py"
)


def _load():
    spec = importlib.util.spec_from_file_location("regress_under_test",
                                                  _REGRESS)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


BASELINE = {
    "tpu_paxos3_states_per_sec": 266699.0,
    "tpu_2pc7_states_per_sec": 1450000.0,
    "tpu_2pc4_states_per_sec": 9000.0,
    "cpu_paxos3_uncontended_states_per_sec": 8188.4,  # not a tpu_ key
    "validated_at": "2026-07-31T03:30:00Z",
}


def test_compare_clean_fresh_run():
    r = _load()
    verdict = r.compare(
        {"fresh": True,
         "tpu_paxos3_states_per_sec": 280000.0,
         "tpu_2pc7_states_per_sec": 1400000.0},
        BASELINE,
    )
    assert verdict["ok"] is True
    assert verdict["checked"] == 2  # only keys present in BOTH, tpu_ only
    assert verdict["regressed"] == []
    assert [e["config"] for e in verdict["improved"]] == [
        "tpu_paxos3_states_per_sec"
    ]


def test_compare_flags_regression_with_detail():
    r = _load()
    verdict = r.compare(
        {"fresh": True,
         "tpu_paxos3_states_per_sec": 100000.0,  # 0.37x: regression
         "tpu_2pc7_states_per_sec": 1300000.0},  # 0.90x: within tolerance
        BASELINE,
    )
    assert verdict["ok"] is False
    (bad,) = verdict["regressed"]
    assert bad["config"] == "tpu_paxos3_states_per_sec"
    assert bad["ratio"] == 0.375
    assert bad["baseline"] == 266699.0


def test_compare_stale_run_is_not_ok():
    r = _load()
    verdict = r.compare(
        {"fresh": False, "tpu_paxos3_states_per_sec": 266699.0}, BASELINE
    )
    assert verdict["ok"] is False and verdict["fresh"] is False


def test_main_exit_codes(tmp_path, capsys):
    r = _load()
    base = tmp_path / "base.json"
    base.write_text(json.dumps(BASELINE))

    def run(doc, *flags):
        p = tmp_path / "run.json"
        p.write_text(json.dumps(doc))
        rc = r.main([str(p), f"--baseline={base}", *flags])
        out = capsys.readouterr().out.strip().splitlines()
        return rc, json.loads(out[-1])

    # fresh + clean -> 0
    rc, v = run({"fresh": True, "tpu_paxos3_states_per_sec": 270000.0})
    assert rc == 0 and v["ok"] is True
    # regression -> 1, offender named on stdout
    rc, v = run({"fresh": True, "tpu_paxos3_states_per_sec": 1000.0})
    assert rc == 1 and v["regressed"][0]["config"] == (
        "tpu_paxos3_states_per_sec"
    )
    # no TPU number in the run -> 2 (it can never validate)
    rc, v = run({"fresh": False, "value": 0.0})
    assert rc == 2 and v["fresh"] is False
    # --allow-stale compares two stored artifacts without the fresh gate
    rc, v = run(
        {"fresh": False, "tpu_paxos3_states_per_sec": 266699.0},
        "--allow-stale",
    )
    assert rc == 0


def test_main_unwraps_driver_artifacts(tmp_path, capsys):
    """Driver BENCH_rNN.json files wrap the headline in ``parsed``."""
    r = _load()
    base = tmp_path / "base.json"
    base.write_text(json.dumps(BASELINE))
    p = tmp_path / "BENCH_r06.json"
    p.write_text(json.dumps({
        "rc": 0,
        "parsed": {"fresh": True, "tpu_paxos3_states_per_sec": 300000.0},
    }))
    rc = r.main([str(p), f"--baseline={base}"])
    v = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rc == 0 and v["checked"] == 1


def test_main_missing_files_exit_2(tmp_path, capsys):
    r = _load()
    rc = r.main([str(tmp_path / "absent.json")])
    assert rc == 2
    assert json.loads(capsys.readouterr().out)["ok"] is False


def test_missing_baseline_is_no_baseline_yet_and_own_gates_still_run(
    tmp_path, capsys
):
    """No BENCH_VALIDATED.json (no full TPU bench run recorded yet): the
    comparisons are skipped, the run's own-block gates still trip."""
    r = _load()
    none = tmp_path / "never-written.json"
    p = tmp_path / "run.json"
    p.write_text(json.dumps(
        {"fresh": True, "tpu_paxos3_states_per_sec": 270000.0}
    ))
    rc = r.main([str(p), f"--baseline={none}"])
    v = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rc == 0 and v["ok"] is True and v["checked"] == 0
    assert v["baseline"] == "no baseline recorded yet"
    # a fresh run with no stage attribution still fails --stages
    rc = r.main([str(p), f"--baseline={none}", "--stages"])
    v = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rc == 1 and v["stages"]["ok"] is False


def test_off_chip_run_is_compared_with_nothing_but_gated_on_its_blocks(
    tmp_path, capsys
):
    """bench's CPU-backend artifact (``platform: cpu``, keys under
    ``xlacpu_*``): not fresh, never compared with the TPU baseline —
    but not refused either, and a malformed own block still exits 1."""
    r = _load()
    base = tmp_path / "base.json"
    base.write_text(json.dumps(BASELINE))
    stages = {"compile_secs": 1.0, "device_secs": 2.0, "wall_secs": 3.5,
              "host_secs": 0.5}
    doc = {"fresh": False, "value": 0.0, "platform": "cpu",
           "device_key_prefix": "xlacpu",
           "xlacpu_paxos3_states_per_sec": 1000.0,  # 0.004x of the baseline
           "xlacpu_paxos3_stages": stages}
    p = tmp_path / "run.json"
    p.write_text(json.dumps(doc))
    rc = r.main([str(p), f"--baseline={base}", "--stages"])
    v = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rc == 0 and v["ok"] is True, v
    assert v["platform"] == "cpu" and v["fresh"] is False
    assert v["checked"] == 0 and v["regressed"] == []
    assert v["stages"]["ok"] is True
    del doc["xlacpu_paxos3_stages"]
    p.write_text(json.dumps(doc))
    rc = r.main([str(p), f"--baseline={base}", "--stages"])
    assert rc == 1


def test_sanitizer_section_gates_the_verdict(tmp_path, capsys):
    """--sanitize adds a ``sanitizer`` section (the fleet soundness gate,
    docs/analysis.md JX2xx): a clean fleet leaves a fresh run passing, an
    unclean fleet fails it with exit 1 — and the stale-artifact rules are
    unchanged (stale + unclean still exits 2 on staleness first)."""
    r = _load()
    base = tmp_path / "base.json"
    base.write_text(json.dumps(BASELINE))
    run = tmp_path / "run.json"
    run.write_text(json.dumps(
        {"fresh": True, "tpu_paxos3_states_per_sec": 270000.0}
    ))

    def clean_fleet(stream=None):
        print("sanitize fleet: CLEAN", file=stream)
        return 0

    def dirty_fleet(stream=None):
        print("sanitize fleet: FAILED (JX201)", file=stream)
        return 1

    rc = r.main([str(run), f"--baseline={base}", "--sanitize"],
                fleet=clean_fleet)
    v = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rc == 0 and v["ok"] is True
    assert v["sanitizer"] == {"clean": True,
                              "verdict": "sanitize fleet: CLEAN"}

    rc = r.main([str(run), f"--baseline={base}", "--sanitize"],
                fleet=dirty_fleet)
    v = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rc == 1 and v["ok"] is False
    assert v["sanitizer"]["clean"] is False
    assert "JX201" in v["sanitizer"]["verdict"]

    # without the flag the verdict is untouched (no import of the fleet)
    rc = r.main([str(run), f"--baseline={base}"])
    v = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rc == 0 and "sanitizer" not in v

    # staleness still wins: a stale artifact exits 2 before sanitizing
    stale = tmp_path / "stale.json"
    stale.write_text(json.dumps({"fresh": False}))
    rc = r.main([str(stale), f"--baseline={base}", "--sanitize"],
                fleet=clean_fleet)
    assert rc == 2


def test_sanitizer_verdict_crash_is_a_failure():
    """An import/trace crash in the fleet runner is a gate FAILURE, never
    a silent skip."""
    r = _load()

    def broken(stream=None):
        raise RuntimeError("boom")

    v = r.sanitizer_verdict(fleet=broken)
    assert v["clean"] is False and "boom" in v["error"]


def test_independence_section_gates_the_verdict(tmp_path, capsys):
    """--independence mirrors --sanitize: the fleet conflict-matrix gate
    (docs/analysis.md JX3xx) plus a well-formedness check on the run's
    flag-gated POR leg — POR must never change paxos counts (its matrix
    is conservatively all-dependent).  Stale artifacts still exit 2 first
    and never pay the fleet import."""
    r = _load()
    base = tmp_path / "base.json"
    base.write_text(json.dumps(BASELINE))
    run = tmp_path / "run.json"
    run.write_text(json.dumps(
        {"fresh": True, "tpu_paxos3_states_per_sec": 270000.0}
    ))

    def clean_fleet(stream=None):
        print("independence fleet: CLEAN", file=stream)
        return 0

    def dirty_fleet(stream=None):
        print("independence fleet: FAILED (JX301)", file=stream)
        return 1

    rc = r.main([str(run), f"--baseline={base}", "--independence"],
                fleet=clean_fleet)
    v = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rc == 0 and v["ok"] is True
    assert v["independence"]["clean"] is True

    rc = r.main([str(run), f"--baseline={base}", "--independence"],
                fleet=dirty_fleet)
    v = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rc == 1 and v["ok"] is False
    assert "JX301" in v["independence"]["verdict"]

    # without the flag: untouched, no fleet import
    rc = r.main([str(run), f"--baseline={base}"])
    v = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rc == 0 and "independence" not in v

    # staleness wins before the fleet runs
    stale = tmp_path / "stale.json"
    stale.write_text(json.dumps({"fresh": False}))
    rc = r.main([str(stale), f"--baseline={base}", "--independence"],
                fleet=clean_fleet)
    assert rc == 2


def test_independence_por_leg_well_formedness(tmp_path, capsys):
    """A run artifact carrying the flag-gated POR leg must be well-formed
    and count-stable vs the full-expansion leg."""
    r = _load()

    def clean_fleet(stream=None):
        print("independence fleet: CLEAN", file=stream)
        return 0

    good = {
        "fresh": True,
        "tpu_paxos3_unique": 40000,
        "tpu_paxos3_por_unique": 40000,
        "tpu_paxos3_por": {"enabled": False, "fallback": "all-dependent"},
    }
    v = r.independence_verdict(good, fleet=clean_fleet)
    assert v["clean"] is True and v["por_leg"]["ok"] is True

    drifted = dict(good, tpu_paxos3_por_unique=39999)
    v = r.independence_verdict(drifted, fleet=clean_fleet)
    assert v["clean"] is False
    assert any("por unique" in p for p in v["por_leg"]["problems"])

    malformed = dict(good, tpu_paxos3_por=["not-a-dict"])
    v = r.independence_verdict(malformed, fleet=clean_fleet)
    assert v["clean"] is False

    # a crashed POR leg (bench recorded only the error key) is a gate
    # FAILURE, never a silent skip
    crashed = {"fresh": True, "tpu_paxos3_por_error": "RuntimeError: x"}
    v = r.independence_verdict(crashed, fleet=clean_fleet)
    assert v["clean"] is False
    assert any("crashed" in p for p in v["por_leg"]["problems"])

    # a crash in the fleet runner is a failure, never a skip
    def broken(stream=None):
        raise RuntimeError("boom")

    v = r.independence_verdict({}, fleet=broken)
    assert v["clean"] is False and "boom" in v["error"]


def test_stages_section_gates_fresh_runs_only(tmp_path, capsys):
    """--stages: a FRESH run must carry a well-formed per-stage breakdown;
    stored baselines without stages (pre-attribution hardware numbers)
    never trip the gate, and staleness still wins with exit 2."""
    r = _load()
    base = tmp_path / "base.json"
    base.write_text(json.dumps(BASELINE))  # note: baseline has no stages

    def run(doc, *flags):
        p = tmp_path / "run.json"
        p.write_text(json.dumps(doc))
        rc = r.main([str(p), f"--baseline={base}", *flags])
        out = capsys.readouterr().out.strip().splitlines()
        return rc, json.loads(out[-1])

    stages = {"compile_secs": 1.0, "device_secs": 7.0, "growth_secs": 0.2,
              "wall_secs": 9.0, "host_secs": 0.8}
    # fresh + stages present -> ok, baseline absence is informational
    rc, v = run({"fresh": True, "tpu_paxos3_states_per_sec": 270000.0,
                 "tpu_paxos3_stages": stages}, "--stages")
    assert rc == 0 and v["ok"] is True
    assert v["stages"]["ok"] is True and v["stages"]["baseline"] is None
    assert v["stages"]["run"] == stages
    # fresh but NO stages -> exit 1, named in the verdict
    rc, v = run({"fresh": True, "tpu_paxos3_states_per_sec": 270000.0},
                "--stages")
    assert rc == 1 and v["ok"] is False and v["stages"]["ok"] is False
    # malformed (negative) stage -> exit 1
    rc, v = run({"fresh": True, "tpu_paxos3_states_per_sec": 270000.0,
                 "tpu_paxos3_stages": {"device_secs": -1.0}}, "--stages")
    assert rc == 1 and v["stages"]["malformed"] == ["device_secs"]
    # stale run: staleness exits 2 regardless of stages
    rc, v = run({"fresh": False}, "--stages")
    assert rc == 2
    # --allow-stale: a stored artifact without stages is NOT required to
    # have them (it predates the attribution round)
    rc, v = run({"fresh": False,
                 "tpu_paxos3_states_per_sec": 266699.0},
                "--stages", "--allow-stale")
    assert rc == 0 and v["stages"]["ok"] is False  # reported, not gated
    # baseline WITH stages is attached for comparison
    base.write_text(json.dumps({**BASELINE,
                                "tpu_paxos3_stages": stages}))
    rc, v = run({"fresh": True, "tpu_paxos3_states_per_sec": 270000.0,
                 "tpu_paxos3_stages": stages}, "--stages")
    assert rc == 0 and v["stages"]["baseline"] == stages


def test_cartography_section_gates_fresh_runs_only(tmp_path, capsys):
    """--cartography: a FRESH run must carry a well-formed, reconciling
    cartography block; stored baselines without one (pre-cartography
    rounds) never trip the gate, and staleness still wins with exit 2 —
    the exact --stages rule applied to the search-shape artifact."""
    r = _load()
    base = tmp_path / "base.json"
    base.write_text(json.dumps(BASELINE))  # note: baseline has no block

    def run(doc, *flags):
        p = tmp_path / "run.json"
        p.write_text(json.dumps(doc))
        rc = r.main([str(p), f"--baseline={base}", *flags])
        out = capsys.readouterr().out.strip().splitlines()
        return rc, json.loads(out[-1])

    cart = {
        "v": 1,
        "depth_hist": [1, 10, 29],
        "action_hist": [5, 20, 15],
        "props": [],
        "fresh_inserts": 40,
        "duplicate_hits": 12,
    }
    good = {"fresh": True, "tpu_paxos3_states_per_sec": 270000.0,
            "tpu_paxos3_unique": 40, "tpu_paxos3_cartography": cart}
    # fresh + well-formed block -> ok; absent baseline is informational
    rc, v = run(good, "--cartography")
    assert rc == 0 and v["ok"] is True
    assert v["cartography"]["ok"] is True
    assert v["cartography"]["baseline_present"] is False
    assert v["cartography"]["summary"]["fresh_inserts"] == 40
    # fresh but NO block -> exit 1, named in the verdict
    rc, v = run({"fresh": True, "tpu_paxos3_states_per_sec": 270000.0},
                "--cartography")
    assert rc == 1 and v["cartography"]["ok"] is False
    # malformed: depth histogram does not reconcile with fresh_inserts
    rc, v = run({**good,
                 "tpu_paxos3_cartography": {**cart, "fresh_inserts": 99},
                 "tpu_paxos3_unique": 99}, "--cartography")
    assert rc == 1
    assert any("sum(depth_hist)" in p
               for p in v["cartography"]["problems"])
    # malformed: block disagrees with the run's own headline unique
    rc, v = run({**good, "tpu_paxos3_unique": 41}, "--cartography")
    assert rc == 1
    assert any("tpu_paxos3_unique" in p
               for p in v["cartography"]["problems"])
    # unversioned block -> exit 1
    rc, v = run({**good,
                 "tpu_paxos3_cartography": {
                     k: x for k, x in cart.items() if k != "v"
                 }}, "--cartography")
    assert rc == 1
    assert any("schema version" in p for p in v["cartography"]["problems"])
    # stale run: staleness exits 2 regardless of cartography
    rc, v = run({"fresh": False}, "--cartography")
    assert rc == 2
    # --allow-stale: a stored pre-cartography artifact is reported, not
    # gated
    rc, v = run({"fresh": False,
                 "tpu_paxos3_states_per_sec": 266699.0},
                "--cartography", "--allow-stale")
    assert rc == 0 and v["cartography"]["ok"] is False
    # baseline WITH a block is noted for comparison
    base.write_text(json.dumps({**BASELINE,
                                "tpu_paxos3_cartography": cart}))
    rc, v = run(good, "--cartography")
    assert rc == 0 and v["cartography"]["baseline_present"] is True


def test_memory_section_gates_fresh_runs_only(tmp_path, capsys):
    """--memory: a FRESH run must carry a well-formed HBM-ledger block
    (versioned, buffers summing exactly to total_bytes, a growth
    forecast whose transient covers old+new); stored baselines without
    one (pre-memory rounds) never trip, staleness still exits 2 — the
    --stages/--cartography rule applied to the memory artifact."""
    r = _load()
    base = tmp_path / "base.json"
    base.write_text(json.dumps(BASELINE))  # note: baseline has no block

    def run(doc, *flags):
        p = tmp_path / "run.json"
        p.write_text(json.dumps(doc))
        rc = r.main([str(p), f"--baseline={base}", *flags])
        out = capsys.readouterr().out.strip().splitlines()
        return rc, json.loads(out[-1])

    mem = {
        "v": 1,
        "engine": "wavefront",
        "capacity": 131072,
        "buffers": {"table_fp": 1048576, "table_parent": 1048576,
                    "q_rows": 500000},
        "total_bytes": 2597152,
        "next_rung": {"capacity": 262144, "total_bytes": 4694304,
                      "transient_bytes": 7291456},
    }
    good = {"fresh": True, "tpu_paxos3_states_per_sec": 270000.0,
            "tpu_paxos3_memory": mem}
    # fresh + well-formed -> ok; absent baseline is informational
    rc, v = run(good, "--memory")
    assert rc == 0 and v["ok"] is True
    assert v["memory"]["ok"] is True
    assert v["memory"]["baseline_present"] is False
    assert v["memory"]["summary"]["total_bytes"] == 2597152
    # fresh but NO block -> exit 1, named in the verdict
    rc, v = run({"fresh": True, "tpu_paxos3_states_per_sec": 270000.0},
                "--memory")
    assert rc == 1 and v["memory"]["ok"] is False
    assert any("no tpu_paxos3_memory" in p for p in v["memory"]["problems"])
    # malformed: buffers do not sum to total_bytes
    rc, v = run({**good,
                 "tpu_paxos3_memory": {**mem, "total_bytes": 999}},
                "--memory")
    assert rc == 1
    assert any("sum(buffers)" in p for p in v["memory"]["problems"])
    # malformed: MIXED-TYPE buffers map must yield a verdict, not a
    # TypeError from the mismatch message (review find)
    rc, v = run({**good,
                 "tpu_paxos3_memory": {
                     **mem, "buffers": {"a": 5, "b": "junk"},
                 }}, "--memory")
    assert rc == 1
    assert any("non-int" in p for p in v["memory"]["problems"])
    assert any("sum(buffers)" in p for p in v["memory"]["problems"])
    # malformed: transient below the steady footprint (forecast must
    # hold old + new carry live)
    rc, v = run({**good,
                 "tpu_paxos3_memory": {
                     **mem,
                     "next_rung": {"capacity": 262144,
                                   "total_bytes": 4694304,
                                   "transient_bytes": 100},
                 }}, "--memory")
    assert rc == 1
    assert any("transient" in p for p in v["memory"]["problems"])
    # unversioned -> exit 1
    rc, v = run({**good,
                 "tpu_paxos3_memory": {
                     k: x for k, x in mem.items() if k != "v"
                 }}, "--memory")
    assert rc == 1
    assert any("schema version" in p for p in v["memory"]["problems"])
    # stale run: staleness exits 2 regardless of the memory gate
    rc, v = run({"fresh": False}, "--memory")
    assert rc == 2
    # --allow-stale: a stored pre-memory artifact is reported, not gated
    rc, v = run({"fresh": False,
                 "tpu_paxos3_states_per_sec": 266699.0},
                "--memory", "--allow-stale")
    assert rc == 0 and v["memory"]["ok"] is False
    # baseline WITH a block is noted for comparison
    base.write_text(json.dumps({**BASELINE, "tpu_paxos3_memory": mem}))
    rc, v = run(good, "--memory")
    assert rc == 0 and v["memory"]["baseline_present"] is True


def test_roofline_section_gates_fresh_runs_only(tmp_path, capsys):
    """--roofline: a FRESH run must carry a well-formed roofline block
    (versioned, per-stage non-negative integer FLOPs/bytes summing to
    the totals, a PASSING XLA-reconciliation verdict); stored baselines
    without one (pre-roofline rounds) never trip, staleness still exits
    2 — the --stages/--cartography/--memory rule applied to the cost
    ledger (docs/roofline.md)."""
    r = _load()
    base = tmp_path / "base.json"
    base.write_text(json.dumps(BASELINE))  # note: baseline has no block

    def run(doc, *flags):
        p = tmp_path / "run.json"
        p.write_text(json.dumps(doc))
        rc = r.main([str(p), f"--baseline={base}", *flags])
        out = capsys.readouterr().out.strip().splitlines()
        return rc, json.loads(out[-1])

    roof = {
        "v": 1,
        "engine": "wavefront",
        "batch": 4096,
        "stages": {
            "expand": {"flops": 1000, "bytes_read": 2000,
                       "bytes_written": 500},
            "dedup-insert": {"flops": 4000, "bytes_read": 8000,
                             "bytes_written": 1500},
        },
        "totals": {"flops": 5000, "bytes": 12000},
        "mxu_candidates": [{"rank": 1, "stage": "dedup-insert",
                            "op": "gather", "bytes": 6000}],
        "reconciliation": {"ok": True, "stages": {}},
    }
    good = {"fresh": True, "tpu_paxos3_states_per_sec": 270000.0,
            "tpu_paxos3_roofline": roof}
    # fresh + well-formed + reconciled -> ok; absent baseline is fine
    rc, v = run(good, "--roofline")
    assert rc == 0 and v["ok"] is True
    assert v["roofline"]["ok"] is True
    assert v["roofline"]["baseline_present"] is False
    assert v["roofline"]["summary"]["reconciled"] is True
    assert v["roofline"]["summary"]["mxu_candidates"] == 1
    # fresh but NO block -> exit 1, named in the verdict
    rc, v = run({"fresh": True, "tpu_paxos3_states_per_sec": 270000.0},
                "--roofline")
    assert rc == 1 and v["roofline"]["ok"] is False
    assert any("no tpu_paxos3_roofline" in p
               for p in v["roofline"]["problems"])
    # malformed: stage sums disagree with the totals
    rc, v = run({**good,
                 "tpu_paxos3_roofline": {
                     **roof, "totals": {"flops": 1, "bytes": 12000},
                 }}, "--roofline")
    assert rc == 1
    assert any("totals.flops" in p for p in v["roofline"]["problems"])
    # malformed: negative stage bytes
    rc, v = run({**good,
                 "tpu_paxos3_roofline": {
                     **roof,
                     "stages": {"expand": {"flops": 1, "bytes_read": -5,
                                           "bytes_written": 0}},
                 }}, "--roofline")
    assert rc == 1
    assert any("missing/negative" in p for p in v["roofline"]["problems"])
    # a FAILED XLA reconciliation is a gate failure, not a note
    rc, v = run({**good,
                 "tpu_paxos3_roofline": {
                     **roof, "reconciliation": {"ok": False},
                 }}, "--roofline")
    assert rc == 1
    assert any("reconciliation FAILED" in p
               for p in v["roofline"]["problems"])
    # unversioned -> exit 1
    rc, v = run({**good,
                 "tpu_paxos3_roofline": {
                     k: x for k, x in roof.items() if k != "v"
                 }}, "--roofline")
    assert rc == 1
    assert any("schema version" in p for p in v["roofline"]["problems"])
    # stale run: staleness exits 2 regardless of the roofline gate
    rc, v = run({"fresh": False}, "--roofline")
    assert rc == 2
    # --allow-stale: a stored pre-roofline artifact is reported, not gated
    rc, v = run({"fresh": False,
                 "tpu_paxos3_states_per_sec": 266699.0},
                "--roofline", "--allow-stale")
    assert rc == 0 and v["roofline"]["ok"] is False
    # baseline WITH a block is noted for comparison
    base.write_text(json.dumps({**BASELINE, "tpu_paxos3_roofline": roof}))
    rc, v = run(good, "--roofline")
    assert rc == 0 and v["roofline"]["baseline_present"] is True


def test_sweep_section_gates_fresh_runs_only(tmp_path, capsys):
    """--sweep: the hyper-batched sweep leg (docs/sweep.md).  Flag-gated
    like --spill/--mxu: absence (stale artifacts, pre-sweep baselines)
    never trips; a present-but-crashed, parity-breaking, malformed, or
    unamortized leg trips fresh runs only."""
    r = _load()
    base = tmp_path / "base.json"
    base.write_text(json.dumps(BASELINE))  # pre-sweep: no tpu_sweep

    def run(doc, *flags):
        p = tmp_path / "run.json"
        p.write_text(json.dumps(doc))
        rc = r.main([str(p), f"--baseline={base}", *flags])
        out = capsys.readouterr().out.strip().splitlines()
        return rc, json.loads(out[-1])

    blk = {
        "instances": 8, "cohorts": 2, "engine_compiles": 2,
        "sequential_engine_compiles": 8, "unique": 10572,
        "states": 34716, "sec": 4.2, "sequential_sec": 9.1,
        "parity": "IDENTICAL",
        "per_instance": {
            "paxos1-i0": {"unique": 265, "states": 482},
            "paxos1-lossy-i1": {"unique": 2378, "states": 8197},
        },
    }
    good = {"fresh": True, "tpu_paxos3_states_per_sec": 270000.0,
            "tpu_sweep": blk}
    # absence never trips (pre-sweep artifacts pass untouched)
    rc, v = run({"fresh": True,
                 "tpu_paxos3_states_per_sec": 270000.0}, "--sweep")
    assert rc == 0 and v["sweep"]["ok"] is True
    assert v["sweep"]["present"] is False
    assert v["sweep"]["baseline_present"] is False
    # a well-formed leg passes and reports the amortization
    rc, v = run(good, "--sweep")
    assert rc == 0 and v["sweep"]["ok"] is True
    assert v["sweep"]["amortization"]["engine_compiles"] == 2
    # a crashed leg trips
    rc, v = run({"fresh": True, "tpu_paxos3_states_per_sec": 270000.0,
                 "tpu_sweep_error": "AssertionError: drift"}, "--sweep")
    assert rc == 1 and v["sweep"]["ok"] is False
    # parity drift trips
    bad = json.loads(json.dumps(blk))
    bad["parity"] = "DRIFT"
    rc, v = run({**good, "tpu_sweep": bad}, "--sweep")
    assert rc == 1 and any(
        "parity" in p for p in v["sweep"]["problems"]
    )
    # per-instance compiles (no amortization) trip
    bad = json.loads(json.dumps(blk))
    bad["engine_compiles"] = 8
    rc, v = run({**good, "tpu_sweep": bad}, "--sweep")
    assert rc == 1 and v["sweep"]["ok"] is False
    # malformed/corrupt blocks produce a verdict, not a crash
    for garbage in ("nope", {"instances": "x"}, {"per_instance": []}):
        rc, v = run({**good, "tpu_sweep": garbage}, "--sweep")
        assert rc == 1 and v["sweep"]["ok"] is False
    # stale artifacts still exit 2; --allow-stale reports without gating
    rc, v = run({"fresh": False, "tpu_sweep": blk}, "--sweep")
    assert rc == 2
    rc, v = run({"fresh": False,
                 "tpu_paxos3_states_per_sec": 266699.0,
                 "tpu_sweep": blk},
                "--sweep", "--allow-stale")
    assert rc == 0


def test_diff_section_gates_fresh_runs_only(tmp_path, capsys):
    """--diff: the contract-aware report diff (telemetry/diff.py).
    Engages only when BOTH run and baseline embed a tpu_paxos3_report —
    stale artifacts and pre-registry baselines never trip; a matching
    pair passes; drifted counts under a count-identical contract fail;
    incomparable pairs (prefix run vs stored full enumeration) are
    disclosed and skipped."""
    r = _load()
    base = tmp_path / "base.json"
    base.write_text(json.dumps(BASELINE))  # pre-registry: no report

    def run(doc, *flags):
        p = tmp_path / "run.json"
        p.write_text(json.dumps(doc))
        rc = r.main([str(p), f"--baseline={base}", *flags])
        out = capsys.readouterr().out.strip().splitlines()
        return rc, json.loads(out[-1])

    cfg = {
        "model": "PaxosModel", "instance": {"sig": "abc", "target": None},
        "engine": "wavefront", "encoding": None,
        "flags": {"por": False}, "device": "cpu", "git_rev": "deadbeef",
        "key": "k1",
    }
    rep = {
        "v": 1, "model": "PaxosModel", "engine": "wavefront",
        "config": cfg,
        "totals": {"states": 4_814_218, "unique": 1_194_428,
                   "max_depth": 26, "done": True},
        "properties": [
            {"name": "value chosen", "expectation": "sometimes",
             "discovery": True},
        ],
    }
    good = {"fresh": True, "tpu_paxos3_states_per_sec": 270000.0,
            "tpu_paxos3_report": rep}
    # pre-registry baseline (no embedded report) never trips
    rc, v = run(good, "--diff")
    assert rc == 0 and v["ok"] is True
    assert v["diff"]["ok"] is True and "skipped" in v["diff"]
    assert v["diff"]["baseline_present"] is False
    # matching pair -> IDENTICAL, ok
    base.write_text(json.dumps({**BASELINE, "tpu_paxos3_report": rep}))
    rc, v = run(good, "--diff")
    assert rc == 0 and v["diff"]["verdict"] == "IDENTICAL"
    # drifted counts under a count-identical contract -> exit 1 with the
    # violation named
    drifted = json.loads(json.dumps(rep))
    drifted["totals"]["unique"] -= 7
    rc, v = run({**good, "tpu_paxos3_report": drifted}, "--diff")
    assert rc == 1 and v["diff"]["verdict"] == "DIVERGENT"
    assert any(x["rule"] == "counts_must_match"
               for x in v["diff"]["violations"])
    # incomparable (prefix run: different instance target) -> disclosed,
    # skipped, rc 0
    prefix = json.loads(json.dumps(rep))
    prefix["config"]["instance"]["target"] = 4000
    prefix["totals"]["unique"] = 4000
    prefix["totals"]["states"] = 16000
    rc, v = run({**good, "tpu_paxos3_report": prefix}, "--diff")
    assert rc == 0 and v["diff"]["ok"] is True
    assert "skipped" in v["diff"]
    assert v["diff"]["contract"] == "incomparable"
    # staleness still exits 2 regardless
    rc, v = run({"fresh": False, "tpu_paxos3_report": rep}, "--diff")
    assert rc == 2
    # --allow-stale: reported, never gated
    rc, v = run({"fresh": False, "tpu_paxos3_report": drifted},
                "--diff", "--allow-stale")
    assert rc == 0 and v["diff"]["verdict"] == "DIVERGENT"


def test_fleet_section_gates_fresh_runs_only(tmp_path, capsys):
    """--fleet: the multi-tenant scheduler leg (docs/fleet.md).
    Flag-gated like --spill/--mxu/--sweep: absence (stale artifacts,
    pre-fleet baselines) never trips; a present-but-crashed,
    parity-breaking, incomplete, malformed, or unamortized leg trips
    fresh runs only."""
    r = _load()
    base = tmp_path / "base.json"
    base.write_text(json.dumps(BASELINE))  # pre-fleet: no tpu_fleet

    def run(doc, *flags):
        p = tmp_path / "run.json"
        p.write_text(json.dumps(doc))
        rc = r.main([str(p), f"--baseline={base}", *flags])
        out = capsys.readouterr().out.strip().splitlines()
        return rc, json.loads(out[-1])

    blk = {
        "jobs": 4, "slots": 2, "completed": 4, "preemptions": 0,
        "engine_compiles": 2, "sequential_engine_compiles": 4,
        "packed": 3, "states": 11696, "sec": 6.0,
        "sequential_sec": 14.0, "parity": "IDENTICAL",
    }
    good = {"fresh": True, "tpu_paxos3_states_per_sec": 270000.0,
            "tpu_fleet": blk}
    # absence never trips (pre-fleet artifacts pass untouched)
    rc, v = run({"fresh": True,
                 "tpu_paxos3_states_per_sec": 270000.0}, "--fleet")
    assert rc == 0 and v["fleet"]["ok"] is True
    assert v["fleet"]["present"] is False
    assert v["fleet"]["baseline_present"] is False
    # a well-formed leg passes and reports the amortization
    rc, v = run(good, "--fleet")
    assert rc == 0 and v["fleet"]["ok"] is True
    assert v["fleet"]["amortization"]["engine_compiles"] == 2
    # a crashed leg trips
    rc, v = run({"fresh": True, "tpu_paxos3_states_per_sec": 270000.0,
                 "tpu_fleet_error": "AssertionError: drift"}, "--fleet")
    assert rc == 1 and v["fleet"]["ok"] is False
    # parity drift trips
    bad = json.loads(json.dumps(blk))
    bad["parity"] = "DRIFT"
    rc, v = run({**good, "tpu_fleet": bad}, "--fleet")
    assert rc == 1 and any(
        "parity" in p for p in v["fleet"]["problems"]
    )
    # an unfinished tenant trips (completed != jobs)
    bad = json.loads(json.dumps(blk))
    bad["completed"] = 3
    rc, v = run({**good, "tpu_fleet": bad}, "--fleet")
    assert rc == 1 and any(
        "completed" in p for p in v["fleet"]["problems"]
    )
    # packed cohorts without compile amortization trip
    bad = json.loads(json.dumps(blk))
    bad["engine_compiles"] = 4
    rc, v = run({**good, "tpu_fleet": bad}, "--fleet")
    assert rc == 1 and any(
        "amortization" in p for p in v["fleet"]["problems"]
    )
    # an unpacked fleet owes no amortization
    solo = json.loads(json.dumps(blk))
    solo["packed"] = 0
    solo["engine_compiles"] = 4
    rc, v = run({**good, "tpu_fleet": solo}, "--fleet")
    assert rc == 0 and v["fleet"]["ok"] is True
    # malformed/corrupt blocks produce a verdict, not a crash
    for garbage in ("nope", {"jobs": "x"}, {"preemptions": -1}):
        rc, v = run({**good, "tpu_fleet": garbage}, "--fleet")
        assert rc == 1 and v["fleet"]["ok"] is False
    # stale artifacts still exit 2; --allow-stale reports without gating
    rc, v = run({"fresh": False, "tpu_fleet": blk}, "--fleet")
    assert rc == 2
    rc, v = run({"fresh": False,
                 "tpu_paxos3_states_per_sec": 266699.0,
                 "tpu_fleet": blk},
                "--fleet", "--allow-stale")
    assert rc == 0


def test_live_section_gates_fresh_runs_only(tmp_path, capsys):
    """--live: the live-observability leg (docs/observability.md).
    Flag-gated like --fleet: absence never trips; a present leg must
    carry count parity, a bounded sampling overhead, a published bus,
    and a terminal heartbeat."""
    r = _load()
    base = tmp_path / "base.json"
    base.write_text(json.dumps(BASELINE))  # pre-observability baseline

    def run(doc, *flags):
        p = tmp_path / "run.json"
        p.write_text(json.dumps(doc))
        rc = r.main([str(p), f"--baseline={base}", *flags])
        out = capsys.readouterr().out.strip().splitlines()
        return rc, json.loads(out[-1])

    blk = {
        "model": "paxos-3", "unique": 34914, "states": 156408,
        "parity": "IDENTICAL", "base_sec": 4.1, "live_sec": 4.3,
        "overhead_frac": 0.049,
        "families": ["stateright_states_total",
                     "stateright_unique_states_total"],
        "heartbeat": {"verdict": "done", "status": "done",
                      "states": 156408, "unique": 34914, "steps": 61},
    }
    good = {"fresh": True, "tpu_paxos3_states_per_sec": 270000.0,
            "tpu_live": blk}
    # absence never trips (pre-observability artifacts pass untouched)
    rc, v = run({"fresh": True,
                 "tpu_paxos3_states_per_sec": 270000.0}, "--live")
    assert rc == 0 and v["live"]["ok"] is True
    assert v["live"]["present"] is False
    assert v["live"]["baseline_present"] is False
    # a well-formed leg passes and reports the overhead it measured
    rc, v = run(good, "--live")
    assert rc == 0 and v["live"]["ok"] is True
    assert v["live"]["overhead_frac"] == 0.049
    # a crashed leg trips
    rc, v = run({"fresh": True, "tpu_paxos3_states_per_sec": 270000.0,
                 "tpu_live_error": "RuntimeError: server died"}, "--live")
    assert rc == 1 and v["live"]["ok"] is False
    # parity drift trips — a bus that changes the run it observes
    bad = json.loads(json.dumps(blk))
    bad["parity"] = "DRIFT"
    rc, v = run({**good, "tpu_live": bad}, "--live")
    assert rc == 1 and any("parity" in p for p in v["live"]["problems"])
    # unbounded sampling overhead trips
    bad = json.loads(json.dumps(blk))
    bad["overhead_frac"] = 0.8
    rc, v = run({**good, "tpu_live": bad}, "--live")
    assert rc == 1 and any(
        "overhead_frac" in p for p in v["live"]["problems"]
    )
    # a bus that never published trips
    bad = json.loads(json.dumps(blk))
    bad["families"] = []
    rc, v = run({**good, "tpu_live": bad}, "--live")
    assert rc == 1 and any(
        "stateright_states_total" in p for p in v["live"]["problems"]
    )
    # a missing terminal heartbeat trips
    bad = json.loads(json.dumps(blk))
    bad["heartbeat"] = {"verdict": "dead"}
    rc, v = run({**good, "tpu_live": bad}, "--live")
    assert rc == 1 and any(
        "heartbeat" in p for p in v["live"]["problems"]
    )
    # malformed/corrupt blocks produce a verdict, not a crash
    for garbage in ("nope", {"unique": "x"}, {"states": -5}):
        rc, v = run({**good, "tpu_live": garbage}, "--live")
        assert rc == 1 and v["live"]["ok"] is False
    # stale artifacts still exit 2; --allow-stale reports without gating
    rc, v = run({"fresh": False, "tpu_live": blk}, "--live")
    assert rc == 2
    rc, v = run({"fresh": False,
                 "tpu_paxos3_states_per_sec": 266699.0,
                 "tpu_live": blk},
                "--live", "--allow-stale")
    assert rc == 0
