"""Tests of the benchmark's own code (``benchmarks/``): CPU-only, unit-cheap.

The yardstick has to be right before anything is measured with it: the
trace reduction on a small trace recorded on a TPU v5e, the necessary-bytes
arithmetic by hand, the manifest against its files, the plain reference
against the host BFS, and ``run.py`` end to end in rehearsal mode on a tiny
cell that is ADDED AS FILES ONLY (the README's recipe).
"""

import importlib.util
import json
import os
import shutil
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
BENCH = os.path.join(REPO, "benchmarks")
DATA = os.path.join(HERE, "data")
sys.path.insert(0, BENCH)

from srbench import check as chk  # noqa: E402
from srbench import necessary, peaks, reference, stats, xplane  # noqa: E402
from srbench.manifest import Manifest  # noqa: E402


def _load_run_module():
    spec = importlib.util.spec_from_file_location(
        "srbench_run", os.path.join(BENCH, "run.py")
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def manifest():
    return Manifest(os.path.join(REPO, "BENCHMARK.json"), BENCH)


def assert_a_rehearsal_prints(want, printed):
    """A traced rehearsal prints every per-layer metric the manifest gives
    its cell (``want``) and no other — but a share of a roofline
    (``<kernel>_roofline``) may be left out: it needs a published peak, and
    no CPU has one.  ``step_roofline`` is left out for that reason."""
    assert set(printed) <= set(want), set(printed) - set(want)
    left_out = set(want) - set(printed)
    assert all(name.endswith("_roofline") for name in left_out), left_out
    assert "step_roofline" in left_out


# -- median arithmetic --------------------------------------------------------


@pytest.mark.parametrize("values, q, want", [
    ([3.0, 1.0, 2.0], 0.5, 2.0),
    ([1.0, 2.0, 3.0, 4.0], 0.5, 2.5),
    ([1.0, 2.0, 3.0, 4.0, 5.0], 0.25, 2.0),
    ([1.0, 2.0, 3.0, 4.0, 5.0], 0.75, 4.0),
    ([7.0], 0.75, 7.0),
])
def test_quantile_by_hand(values, q, want):
    assert stats.quantile(values, q) == pytest.approx(want)


def test_spread_is_interquartile_over_median():
    # quartiles 2 and 4, median 3
    assert stats.spread([1, 2, 3, 4, 5]) == pytest.approx(2 / 3)
    with pytest.raises(ValueError):
        stats.quantile([], 0.5)


# -- necessary bytes ----------------------------------------------------------


@pytest.mark.parametrize("width, generated, unique, want", [
    # paxos-3: 33-word rows = 264 B; 2*2,420,477*264 + 2*1,194,428*264
    # + 16*2,420,477 = 1,278,011,856 + 630,657,984 + 38,727,632
    (33, 2_420_477, 1_194_428, 1_947_397_472),
    # 2pc-8: 1-word rows = 8 B; 2*18,507,778*8 + 2*1,745,408*8
    # + 16*18,507,778 = 296,124,448 + 27,926,528 + 296,124,448
    (1, 18_507_778, 1_745_408, 620_175_424),
    (1, 0, 0, 0),
])
def test_necessary_bytes_by_hand(width, generated, unique, want):
    assert necessary.necessary_bytes(width, generated, unique) == want


def test_roofline_share_by_hand():
    # 819e9 bytes in one second at the v5e's peak is 100% of the roofline;
    # taking 4 s for them is 25%
    hbm = peaks.peaks_for("TPU v5 lite")["hbm_bytes_per_s"]
    assert hbm == 819e9
    nbytes = necessary.necessary_bytes(33, 2_420_477, 1_194_428)
    got = necessary.roofline_pct(33, 2_420_477, 1_194_428, hbm, 4.0)
    assert got == pytest.approx(100.0 * (nbytes / 819e9) / 4.0)
    with pytest.raises(ValueError):
        necessary.roofline_pct(33, 1, 1, hbm, 0.0)


def test_unknown_device_kind_is_an_error_not_a_default():
    with pytest.raises(KeyError, match="no published peaks"):
        peaks.peaks_for("TPU v9 imaginary")


# -- the trace reduction ------------------------------------------------------

# one chip: a while spanning two fusions, then a copy, then a fusion
SYNTH = [
    ("while.1", 0.0, 100.0),
    ("fusion.1", 10.0, 20.0),
    ("fusion.2", 40.0, 30.0),
    ("copy.3", 200.0, 50.0),
    ("fusion.1", 260.0, 10.0),
]


def test_self_times_subtract_nested_children():
    got = {(n, s): self_ns for n, s, _, self_ns in xplane.self_times(SYNTH)}
    assert got[("while.1", 0.0)] == 50.0  # 100 - 20 - 30
    assert got[("fusion.1", 10.0)] == 20.0
    assert got[("fusion.2", 40.0)] == 30.0
    assert got[("copy.3", 200.0)] == 50.0


def test_reduce_events_busy_idle_and_op_sums_by_hand():
    r = xplane.reduce_events({"/device:TPU:0": SYNTH}, window=(0.0, 300.0))
    # busy = 20 + 30 + 50 + 10 ns (the while is a container: only its
    # children count); idle = 1 - 110/300
    assert r["busy_s"] == pytest.approx(110e-9)
    assert r["window_s"] == pytest.approx(300e-9)
    assert r["idle_pct"] == pytest.approx(100.0 * (1 - 110 / 300))
    assert dict(map(tuple, r["device_ops"])) == pytest.approx(
        {"copy.3": 50e-9, "fusion.1": 30e-9, "fusion.2": 30e-9}
    )
    assert "while.1" not in dict(map(tuple, r["device_ops"]))
    assert r["gaps"][0] == (70.0, 200.0)  # the longest gap first
    assert sum(b - a for a, b in r["gaps"]) == pytest.approx(190.0)


def test_reduce_events_averages_busy_over_chips_and_clips_to_window():
    two = {
        "/device:TPU:0": [("fusion.1", 0.0, 100.0)],
        "/device:TPU:1": [("fusion.1", 50.0, 100.0)],
    }
    r = xplane.reduce_events(two, window=(0.0, 100.0))
    assert r["chips"] == 2
    assert r["busy_s"] == pytest.approx((100e-9 + 50e-9) / 2)
    assert xplane.reduce_events({}, window=(0.0, 1.0)) == {}


@pytest.mark.parametrize("name, want", [
    ("while.12", True), ("%while.3", True), ("conditional.1", True),
    ("call.7", True), ("fusion.4", False), ("while_body_fusion.2", False),
    ("copy-start.1", False),
    ("%while.157 = (s32[]{:T(128)}, u32[16384]{0:T(1024)S(1)}) while(%tuple.1)", True),
    ("%while = (s32[]{:T(128)}) while(%tuple.1), condition=%cond.1", True),
    ("%fusion.2 = s32[8]{0} fusion(%while.1), calls=%fused.call.3", False),
])
def test_container_ops(name, want):
    assert xplane.is_container(name) is want


@pytest.mark.parametrize("name, want", [
    ("%fusion.991 = s32[16384]{0:T(1024)S(1)} fusion(s32[122880]{0:T(1024)S(1)} "
     "%get-tuple-element.6124, s32[16384]{0:T(1024)S(1)} %fusion.990), "
     "kind=kCustom, calls=%fused_computation.1.clone",
     "fusion.991 fusion->s32[16384]"),
    ("%sort.60 = (u32[4096,30,30]{1,2,0:T(8,128)}, s32[4096,30,30]{1,2,0:T(8,128)}) "
     "sort(u32[4096,30,30]{1,2,0:T(8,128)S(1)} %fusion.930), dimensions={2}",
     "sort.60 sort->(u32[4096,30,30], s32[4096,30,30])"),
    ("%copy-start.2 = (u32[4864]{0:T(1024)S(1)}, u32[]{:S(2)}) copy-start(u32[4864] %x)",
     "copy-start.2 copy-start->(u32[4864], u32[])"),
    ("copy.3", "copy.3"),
])
def test_op_label_keeps_xlas_name_opcode_and_result(name, want):
    assert xplane.op_label(name) == want
    assert len(xplane.op_label(name * 5)) <= 96


def test_union_and_gaps():
    assert xplane.union_ns([(0, 10), (5, 20), (30, 40), (40, 41)]) == 31
    assert xplane.gaps([(5, 10), (20, 30)], (0, 40)) == [
        (0, 5), (10, 20), (30, 40)
    ]


RECORDED = os.path.join(DATA, "twopc4_v5e.xplane.pb")


@pytest.fixture(scope="module")
def recorded():
    """A whole 2pc-4 check recorded on one TPU v5e (PR 23)."""
    return xplane.load_trace(RECORDED, "srbench_traced_check")


def test_recorded_trace_has_one_chip_and_the_annotation(recorded):
    assert list(recorded["devices"]) == ["/device:TPU:0"]
    assert len(recorded["devices"]["/device:TPU:0"]) > 100
    name, start, dur = recorded["annotation"]
    assert name == "srbench_traced_check" and dur > 0


def test_recorded_trace_reduction_matches_a_brute_force_timeline(recorded):
    """The sweep-based reduction against an independent method: paint the
    leaf operations onto a nanosecond grid and count the painted cells."""
    import numpy as np

    _, n0, ndur = recorded["annotation"]
    window = (n0, n0 + ndur)
    r = xplane.reduce_events(recorded["devices"], window=window)
    events = recorded["devices"]["/device:TPU:0"]
    grid = np.zeros(int(ndur) + 1, dtype=bool)
    for name, s, d in events:
        if xplane.is_container(name):
            continue
        a = int(round(max(s, window[0]) - n0))
        b = int(round(min(s + d, window[1]) - n0))
        if b > a:
            grid[a:b] = True
    assert r["busy_s"] * 1e9 == pytest.approx(float(grid.sum()), rel=1e-3)
    # the reading taken on the chip when the trace was recorded (PR 23)
    assert r["busy_s"] == pytest.approx(0.007978156, rel=1e-6)
    assert r["idle_pct"] == pytest.approx(79.653976, rel=1e-6)
    assert r["window_s"] == pytest.approx(ndur / 1e9) == pytest.approx(0.039212359)
    # per-op self times never exceed what the operations covered in all
    total_self = sum(s for _, s in r["device_ops"])
    assert 0.0 < total_self <= r["busy_s"] * 1.001 + 1e-9
    assert len(r["device_ops"]) <= 10 and len(r["gaps"]) <= 10


def test_gap_labels_name_the_latest_marker_before_the_midpoint():
    run = _load_run_module()
    # trace clock in ns; monotonic = 100 s + ns/1e9
    labelled = run.label_gaps(
        [(0.0, 2e9), (4e9, 5e9), (6e9, 6.5e9)],
        lambda ns: 100.0 + ns / 1e9,
        [(100.5, "spawn_join"), (104.2, "growth"), (106.0, "step")],
    )
    assert labelled == [["spawn_join", 2.0], ["growth", 1.0], ["step", 0.5]]


# -- manifest <-> files -------------------------------------------------------


def test_manifest_and_files_agree(manifest):
    assert manifest.problems() == []


def test_manifest_has_exactly_the_contract_keys(manifest):
    doc = manifest.doc
    assert sorted(doc) == sorted([
        "command", "paths", "run_seconds", "configs", "workloads",
        "end_to_end", "per_layer",
    ])
    assert os.path.getsize(manifest.path) <= 64 * 1024
    for c in doc["configs"]:
        assert sorted(c) == ["file", "name", "reduced", "source", "why"]
        assert any(c["file"].startswith(p + "/") for p in doc["paths"])
    for w in doc["workloads"]:
        assert sorted(w) == ["chips", "config", "name", "traffic", "why"]
    for m in doc["end_to_end"]:
        assert set(m) - {"workloads"} == {
            "name", "unit", "better", "bound", "source"
        }
        assert 0.01 <= m["bound"] <= 0.25
    for m in doc["per_layer"]:
        assert set(m) - {"workloads"} == {
            "name", "unit", "better", "source", "layer", "moves"
        }
    texts = (
        [c["source"] for c in doc["configs"]]
        + [x["why"] for x in doc["configs"] + doc["workloads"]]
        + [m["layer"] for m in doc["per_layer"]] + doc["command"]
    )
    assert all(1 <= len(t) <= 200 and "\n" not in t and "\t" not in t
               for t in texts)
    four = [w for w in doc["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(doc["workloads"]) // 2)
    pairs = [(w["config"], w["traffic"]) for w in doc["workloads"]]
    assert len(set(pairs)) == len(pairs)
    assert 1 <= doc["run_seconds"] <= 51


def test_every_reader_file_repeats_its_manifest_entry(manifest):
    for m in manifest.doc["per_layer"]:
        mod = manifest.reader_module(m["name"])
        assert (mod.UNIT, mod.LAYER, mod.MOVES, mod.SOURCE) == (
            m["unit"], m["layer"], m["moves"], m["source"]
        ), m["name"]
        assert callable(mod.read)


def test_config_files_state_source_cut_guarantees_and_pins(manifest):
    for entry in manifest.doc["configs"]:
        cfg = manifest.config(entry["name"])
        assert cfg["reduced"] == entry["reduced"]
        for key in ("source", "assumed", "guarantees", "deployment", "model"):
            assert cfg.get(key), (entry["name"], key)
        # the scale the file states is the scale the factory is given
        for key in entry["reduced"]:
            assert cfg[key] == cfg["reduced_from"][key]["here"]
            assert cfg[key] in cfg["model"]["args"]
        # the whole space's pins, or - a configuration whose run is bounded
        # - the first levels of the plain reference's search (or both)
        pins = cfg["pins"]
        if "bounded" in pins:
            prefix = pins["bounded"]
            assert prefix["levels"][0] >= 1 and all(n > 0 for n in prefix["levels"])
            assert set(prefix["discoveries_by_level"].values()) <= set(
                range(len(prefix["levels"])))
            assert prefix["provenance"]
        if "bounded" not in pins or "unique" in pins:
            assert pins["generated"] >= pins["unique"] > 0
            assert pins["provenance"]


def test_manifest_problems_are_found(manifest, tmp_path):
    doc = json.loads(json.dumps(manifest.doc))
    doc["per_layer"][0]["moves"] = "no_such_metric"
    doc["workloads"][0]["config"] = "no_such_config"
    doc["end_to_end"][0]["unit"] = "tokens per second"
    doc["workloads"].append({"name": "bad name", "config": "paxos3",
                             "traffic": "x", "chips": 2, "why": "w"})
    for c in doc["configs"]:
        c["file"] = os.path.join(REPO, c["file"])
    path = tmp_path / "BENCHMARK.json"
    path.write_text(json.dumps(doc))
    found = "\n".join(Manifest(str(path), BENCH).problems())
    for needle in ("no_such_metric", "no_such_config", "bad unit",
                   "bad name", "asks for 2 chips", "no workload file"):
        assert needle in found, (needle, found)


# -- the plain reference and the pin comparison --------------------------------


@pytest.fixture(scope="module")
def twopc3():
    from stateright_tpu.models.two_phase_commit import TwoPhaseSys

    return TwoPhaseSys(3)


def test_reference_bfs_equals_the_host_bfs_and_the_tiny_pins(twopc3):
    got = reference.reference_bfs(twopc3)
    host = twopc3.checker().spawn_bfs().join()
    assert got["unique"] == host.unique_state_count()
    assert got["generated"] == host.state_count()
    assert got["discoveries"] == sorted(host.discoveries())
    pins = json.load(open(os.path.join(DATA, "twopc3.json")))["pins"]
    assert {k: got[k] for k in got} == {k: pins[k] for k in got}


def test_random_walks_are_seeded_and_reachable(twopc3):
    a = reference.random_walk_fingerprints(twopc3, 5, 8)
    assert a == reference.random_walk_fingerprints(twopc3, 5, 8)
    assert a != reference.random_walk_fingerprints(twopc3, 6, 8)
    host = twopc3.checker().spawn_bfs().join()
    assert set(a) <= set(host._generated)


def test_missing_from_counts_absent_fingerprints():
    import numpy as np

    visited = np.sort(np.asarray([5, 9, 2**63 + 1, 40], dtype=np.uint64))
    assert chk.missing_from(visited, [5, 40, 2**63 + 1]) == 0
    assert chk.missing_from(visited, [5, 41, 2**64 - 2]) == 2


def _result(twopc3, **over):
    host = twopc3.checker().spawn_bfs().join()
    base = {
        "unique": 288, "generated": 1146, "max_depth": 10,
        "discoveries": ["abort agreement", "commit agreement"],
        "paths": dict(host.discoveries()), "growth_events": 0,
    }
    base.update(over)
    return base


def _pin_failures(model, config, workload, result):
    """Why a check is not correct: the messages of every compared number
    that is over its limit."""
    return [m for _, number, limit, messages
            in chk.compare(model, config, workload, result)
            if number > limit for m in messages]


@pytest.mark.parametrize("over, workload, needle", [
    ({}, {"expect_growth": "none"}, None),
    ({"unique": 287}, {}, "unique 287 != pinned 288"),
    ({"generated": 1}, {}, "generated 1 != pinned 1146"),
    ({"max_depth": 9}, {}, "max_depth 9"),
    ({"discoveries": ["abort agreement"]}, {}, "discoveries"),
    ({"growth_events": 2}, {"expect_growth": "none"}, "growth events in a presized"),
    ({"growth_events": 0}, {"expect_growth": "some"}, "no growth event"),
])
def test_pin_failures(twopc3, over, workload, needle):
    config = json.load(open(os.path.join(DATA, "twopc3.json")))
    bad = _pin_failures(twopc3, config, workload, _result(twopc3, **over))
    if needle is None:
        assert bad == []
    else:
        assert any(needle in b for b in bad), bad


def test_a_path_that_ends_in_the_wrong_state_is_not_correct(twopc3):
    config = json.load(open(os.path.join(DATA, "twopc3.json")))
    res = _result(twopc3)
    # swap the two discoveries' paths: each now ends in the other's state
    a, c = "abort agreement", "commit agreement"
    res["paths"] = {a: res["paths"][c], c: res["paths"][a]}
    bad = _pin_failures(twopc3, config, {}, res)
    assert len([b for b in bad if "replayed path" in b]) == 2


# -- run.py end to end (rehearsal), on a cell ADDED AS FILES ONLY --------------


@pytest.fixture(scope="module")
def extended_benchmark(tmp_path_factory):
    """The README's recipe, exercised: a fourth cell and a ninth per-layer
    metric are added by dropping files beside the existing ones — no edit
    to any file the benchmark already has.  (A copy of the benchmark's
    data directories stands in for the checkout, so the test leaves no
    file behind; the copied files are byte-identical.)"""
    root = tmp_path_factory.mktemp("bench")
    bench = root / "benchmarks"
    for sub in ("workloads", "layer_metrics", "configs"):
        shutil.copytree(os.path.join(BENCH, sub), bench / sub)
    before = {
        str(p.relative_to(root)): p.read_bytes()
        for p in bench.rglob("*") if p.is_file()
    }
    # 1. a configuration: its file
    shutil.copy(os.path.join(DATA, "twopc3.json"), bench / "configs")
    # 2. a cell: its workload file
    shutil.copy(os.path.join(DATA, "twopc3-tiny.json"), bench / "workloads")
    # 3. a per-layer metric: its reader
    (bench / "layer_metrics" / "depth_levels.py").write_text(
        'UNIT = "count"\nLAYER = "host run loop"\nMOVES = "check_s"\n'
        'SOURCE = "program_counter"\n\n\n'
        "def read(ctx):\n"
        '    recs = ctx["checks"][0]["records"]\n'
        '    return float(max(r["depth"] for r in recs if r["kind"] == "step"))\n'
    )
    # ... and their entries in the manifest
    doc = json.load(open(os.path.join(REPO, "BENCHMARK.json")))
    doc["configs"].append({
        "name": "twopc3", "source": "stateright examples/2pc.rs",
        "file": "benchmarks/configs/twopc3.json", "reduced": ["rm_count"],
        "why": "tiny",
    })
    doc["workloads"].append({
        "name": "twopc3-tiny", "config": "twopc3", "traffic": "tiny",
        "chips": 1, "why": "rehearsal of the harness on the CPU",
    })
    for m in doc["end_to_end"]:
        if "workloads" in m:  # a metric of some cells only: join it
            m["workloads"].append("twopc3-tiny")
    doc["per_layer"].append({
        "name": "depth_levels", "unit": "count", "better": "lower",
        "source": "program_counter", "layer": "host run loop",
        "moves": "check_s", "workloads": ["twopc3-tiny"],
    })
    (root / "BENCHMARK.json").write_text(json.dumps(doc))
    after = {k: (root / k).read_bytes() for k in before}
    assert after == before  # nothing that was there changed
    m = Manifest(str(root / "BENCHMARK.json"), str(bench))
    assert m.problems() == []
    return root, doc


def _run(root, *extra, cell="twopc3-tiny", trace=0):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["JAX_COMPILATION_CACHE_DIR"] = str(root / "jax_cache")
    env.pop("XLA_FLAGS", None)
    return subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload", cell,
         "--seed", "7", "--seconds", "0.5", "--trace", str(trace),
         "--manifest", str(root / "BENCHMARK.json"),
         "--bench-dir", str(root / "benchmarks"), *extra],
        env=env, capture_output=True, text=True, timeout=300, cwd=str(root),
    )


@pytest.mark.parametrize("trace", [0, 1])
def test_rehearsal_runs_the_added_cell_and_prints_no_result(
    extended_benchmark, trace
):
    root, doc = extended_benchmark
    p = _run(root, "--rehearse-cpu", trace=trace)
    assert p.returncode == 2, p.stderr[-2000:]
    lines = [ln for ln in p.stdout.splitlines() if ln.strip()]
    assert all(ln.startswith("[CPU REHEARSAL - not a chip result] ")
               for ln in lines)
    last = lines[-1]
    assert "rehearsal complete (no result line): " in last
    out = json.loads(last.split("(no result line): ", 1)[1])
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] >= 1
    assert out["device"]["platform"] == "cpu"
    assert "exactness sample: seed=7 walks=256" in p.stdout
    assert "missing=0" in p.stdout
    # one line a check (phases, rusage, collector passes) before the window's
    assert "check 1: start=+0.00" in p.stdout  # under 10 ms after the window opens
    assert "mean over the wall, not a metric" in p.stdout
    if trace == 0:
        # the CPU backend reports no memory statistics: peak_hbm is left out
        assert set(out["metrics"]) == {"check_s", "gen_rate", "setup_s"}
        assert "breakdown" not in out
    else:
        # what the manifest says this cell reports (a hand twin: none of the
        # compiled twin's readers, none of the cold loop's)
        m = Manifest(str(root / "BENCHMARK.json"), str(root / "benchmarks"))
        want = {e["name"] for e in m.metrics_for("per_layer", "twopc3-tiny")}
        assert "twin_compile_s" not in want and "acquire_check_s" not in want
        assert {"depth_levels", "fingerprint_bridge_s", "dispatch_s"} <= want
        assert_a_rehearsal_prints(want, out["metrics"])
        assert out["metrics"]["depth_levels"]["value"] == 10.0
        assert out["metrics"]["growth_s"]["value"] == 0.0
        assert out["metrics"]["cache_misses"]["unit"] == "count"
        assert out["device"]["busy_s"] > 0
        assert out["device"]["window_s"] >= out["device"]["busy_s"]
        assert 1 <= len(out["breakdown"]["device_ops"]) <= 10
        assert len(out["breakdown"]["idle_gaps"]) <= 10


def test_without_an_accelerator_there_is_no_result(extended_benchmark):
    root, _ = extended_benchmark
    p = _run(root)
    assert p.returncode == 1
    assert p.stdout.strip() == ""
    assert "no accelerator" in p.stderr


def test_an_unknown_cell_is_refused(extended_benchmark):
    root, _ = extended_benchmark
    p = _run(root, "--rehearse-cpu", cell="no-such-cell")
    assert p.returncode == 1 and "no workload 'no-such-cell'" in p.stderr
    assert "rehearsal complete" not in p.stdout


def test_a_missed_pin_makes_the_run_incorrect(extended_benchmark):
    root, _ = extended_benchmark
    path = root / "benchmarks" / "configs" / "twopc3.json"
    good = path.read_text()
    cfg = json.loads(good)
    cfg["pins"]["unique"] += 1
    path.write_text(json.dumps(cfg))
    try:
        p = _run(root, "--rehearse-cpu")
    finally:
        path.write_text(good)
    assert p.returncode == 2
    last = p.stdout.strip().splitlines()[-1]
    out = json.loads(last.split("(no result line): ", 1)[1])
    assert out["correct"] is False
    assert out["failed"] == out["attempted"] >= 1
    assert "NOT CORRECT" in p.stdout
