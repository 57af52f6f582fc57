"""Tests of what the ``paxos6x4`` configuration added to the benchmark:
``bench.sh``'s own ``paxos check 6`` on ONE four-chip host through the mesh
engine, the benchmark's one ``chips: 4`` cell (``paxos6x4-bounded``).

 - the manifest's new entries agree with their files; the cell is the
   issue's traffic letter for letter; model, row, guarantees and pins are
   ``paxos6``'s (the same model), with a fifth guarantee: the four-chip stop
   is the one-chip engine's;
 - what a breadth-first PREFIX owes, held on ``spawn_tpu(devices=4)``:
   ``test_benchmark_bounded.py``'s every-level test of 2pc-6 on the mesh
   engine - ONE queue, ``tail == len(visited)``, the complete level and the
   labels equal to the one-device run's - and the hand paxos twin's prefix;
 - ``run.py --rehearse-cpu`` of a tiny FOUR-device bounded cell (data under
   ``data/``) correct, plain and traced, its stop the one-device tiny
   cell's, and a control (a pinned level one row off) NOT correct.

The three per-layer metrics ISSUE 51 asked for (``collective_s``,
``collective_count``, ``shard_imbalance``) are the cell's since PR 55 (layer
``GSPMD collectives``; by hand in ``test_benchmark_readers55.py``).  The
records the two counters read are the program's (``mesh.program``,
``mesh``), held by ``tests/test_mesh_reconstruct.py`` and read off the
rehearsal below; ``collective_s`` is device time and no rehearsal lists it.
CPU-only.
"""

import json
import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
BENCH = os.path.join(REPO, "benchmarks")
DATA = os.path.join(HERE, "data")
sys.path.insert(0, BENCH)
sys.path.insert(0, HERE)

from srbench import check as chk  # noqa: E402
from srbench import reference  # noqa: E402
from srbench.manifest import Manifest  # noqa: E402
from test_benchmark_bounded import (  # noqa: E402, F401 - twopc6_levels: a fixture
    BOUNDED_NAMES, SETTINGS, _over, twopc6_levels)
from test_benchmark_loops import RUN, TAG, _compared_lines, _result  # noqa: E402
from test_benchmark_own import assert_a_rehearsal_prints  # noqa: E402

CONFIG, CELL, ONE_CHIP = "paxos6x4", "paxos6x4-bounded", "paxos6"
TINY, TINY_ONE, TINY_CONFIG = (
    "paxos2x4-bounded-tiny", "paxos2-bounded-tiny", "paxos2-prefix")


@pytest.fixture(scope="module")
def manifest():
    return Manifest(os.path.join(REPO, "BENCHMARK.json"), BENCH)


# -- the manifest, the configuration and the cell ---------------------------------


def test_the_manifest_and_its_files_agree(manifest):
    assert manifest.problems() == []
    # appended, nothing moved: test_benchmark_room.py's prefix rule says where
    assert manifest.config_entry(CONFIG)["file"] == f"benchmarks/configs/{CONFIG}.json"
    four = [w["name"] for w in manifest.doc["workloads"] if w["chips"] == 4]
    assert four[0] == CELL  # the benchmark's first four-chip cell
    assert len(manifest.cell(CELL)["why"]) <= 200


def test_the_configuration_is_paxos6_on_four_chips(manifest):
    entry, cfg = manifest.config_entry(CONFIG), manifest.config(CONFIG)
    one = manifest.config(ONE_CHIP)
    assert entry["reduced"] == cfg["reduced"] == []
    assert entry["source"] == cfg["source"] and len(entry["source"]) <= 200
    for said in ("bench.sh:28", "paxos check 6", "UNCUT", "four-chip host",
                 "spawn_tpu(devices=4)", "target_state_count"):
        assert said in entry["source"]
    assert entry["source"] != manifest.config_entry(ONE_CHIP)["source"]
    assert entry["file"] == "benchmarks/configs/paxos6x4.json"
    # the same model, row and pins: derived nothing again
    for key in ("model", "row", "servers", "clients"):
        assert cfg[key] == one[key]
    for key in ("levels", "reference_levels", "graded", "discoveries_by_level",
                "witnesses"):
        assert cfg["pins"]["bounded"][key] == one["pins"]["bounded"][key]
    assert "paxos6.json" in cfg["pins"]["bounded"]["provenance"]
    assert set(cfg["pins"]) == {"bounded"}
    # its own: the deployment over the chips, the target, the cut of scale
    assert cfg["guarantees"][:4] == one["guarantees"] and len(cfg["guarantees"]) == 5
    assert "one-chip engine's stop" in cfg["guarantees"][4]
    chips = cfg["deployment"]["chips"]
    for said in ("BUCKET range", "ROW range", "EVERY queue column",
                 "ONE controller process", "replicated scalars"):
        assert said in chips
    assert cfg["assumed"]["scale"] and "3,672,131" in cfg["assumed"]["target"]


def test_the_cell_is_the_issues_traffic_letter_for_letter(manifest):
    cell, wl = manifest.cell(CELL), manifest.workload(CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (CONFIG, "bounded", 4)
    assert (wl["config"], wl["traffic"], wl["chips"]) == (CONFIG, "bounded", 4)
    assert chk.loop_kind(wl) == "bounded" and wl["expect_growth"] == "none"
    assert wl["loop"]["clients"] == 1
    assert wl["builder"] == [
        {"verb": "mesh", "kwargs": {"devices": 4}},
        {"verb": "target_states", "args": [3670016]},
    ]
    assert wl["spawn"] == {
        "capacity": 16777216, "queue_capacity": 8388608, "batch": 16384,
        "cand": 262144, "steps_per_call": 512,
    }
    assert chk.bounded_target(wl) == chk.bounded_target(
        manifest.workload("paxos6-bounded"))
    # 4,096 lanes a chip: paxos6-bounded's chip's share
    assert wl["spawn"]["batch"] // 4 == manifest.workload(
        "paxos6-bounded")["spawn"]["batch"]


def test_table_and_queue_hold_the_prefix(manifest):
    cfg, wl = manifest.config(CONFIG), manifest.workload(CELL)
    step = wl["spawn"]["batch"] * cfg["row"]["max_actions"]
    most = chk.bounded_target(wl) + step
    assert most <= wl["spawn"]["queue_capacity"]
    assert most * 4 > wl["spawn"]["capacity"] >= chk.bounded_target(wl) * 4
    assert wl["spawn"]["cand"] * 4 <= wl["spawn"]["capacity"]
    # the queue is 43.8% written at the stop
    assert round(100.0 * 3672131 / wl["spawn"]["queue_capacity"], 1) == 43.8


def test_the_cell_reports_what_the_issue_lists(manifest):
    got = {m["name"] for m in manifest.metrics_for("end_to_end", CELL)}
    assert {"check_s", "peak_hbm", "setup_s"} <= got <= {
        "check_s", "peak_hbm", "setup_s", "gen_rate"}
    layer = {m["name"] for m in manifest.metrics_for("per_layer", CELL)}
    # every per-layer metric without a list ...
    assert {m["name"] for m in manifest.doc["per_layer"]
            if "workloads" not in m} <= layer
    assert {"step_roofline", "stage_hash_roofline", "twin_expand_roofline",
            "reconstruct_parents_s", "reconstruct_pull_s", "device_idle_pct",
            "stage_pop_s", "stage_append_s", "append_trips",
            "reconstruct_pull_bytes"} <= layer
    # ... and, since PR 55, what makes it a four-chip cell: the three ISSUE 51
    # asked for - this cell's alone among the committed ones - and how far
    # the queue is written (43.8: test_table_and_queue_hold_the_prefix)
    listed = {m["name"] for m in manifest.doc["per_layer"]
              if CELL in m.get("workloads", [])}
    assert {"collective_s", "collective_count", "shard_imbalance",
            "queue_fill_pct"} <= listed <= layer
    for name in ("collective_s", "collective_count", "shard_imbalance"):
        entry = next(m for m in manifest.doc["per_layer"] if m["name"] == name)
        assert entry["layer"] == "GSPMD collectives" and entry["moves"] == "check_s"
        assert not {w["name"] for w in manifest.doc["workloads"]
                    if w["chips"] == 1} & set(entry["workloads"])
    # a hand twin on the mesh engine: none of the compiled twin's, the cold
    # loop's or growth's readers
    assert not {"twin_compile_s", "acquire_check_s", "stage_grow_s",
                "grow_bytes"} & layer


def test_the_builder_verbs_select_the_mesh_engine(manifest):
    from stateright_tpu.models.two_phase_commit import TwoPhaseSys
    from stateright_tpu.parallel.mesh import MeshTpuChecker

    wl = dict(manifest.workload(CELL))
    wl["builder"] = [wl["builder"][0], {"verb": "target_states", "args": [200]}]
    b = chk.builder_for(TwoPhaseSys(3), wl, telemetry=False)
    c = b.spawn_tpu(sync=True, capacity=1 << 12, batch=64)
    assert isinstance(c, MeshTpuChecker) and c.n_devices == 4
    assert c.unique_state_count() >= 200


# -- what a prefix owes, on the mesh engine -----------------------------------------


def _spawn(model, devices, target, **spawn):
    b = model.checker()
    if devices > 1:
        b = b.mesh(devices=devices)
    c = b.target_states(target).spawn_tpu(sync=True, **spawn)
    c.join()
    return c


@pytest.mark.parametrize("batch, target, unique, depth, level, first_short", SETTINGS)
def test_every_reference_level_down_to_the_complete_one_on_four_devices(
    twopc6_levels, batch, target, unique, depth, level, first_short
):
    """``test_benchmark_bounded.py``'s every-level test on
    ``spawn_tpu(devices=4)``: the mesh engine has ONE logical queue (head
    and tail are two replicated scalars), so the complete level, the labels
    and the closure argument hold as written - and equal the one-device
    run's, row for row."""
    model, kept, levels = twopc6_levels
    spawn = dict(capacity=1 << 18, queue_capacity=1 << 16, batch=batch,
                 steps_per_call=64, cand=16 * batch)
    mesh = _spawn(model, 4, target, **spawn)
    assert (mesh.unique_state_count(), mesh.max_depth()) == (unique, depth)
    prefix = chk.bounded_prefix(mesh)
    assert len(prefix["visited"]) == unique == prefix["tail"]  # ONE queue
    short = [chk.missing_from(prefix["visited"], fps) for fps in levels]
    assert next(i for i, n in enumerate(short) if n) == first_short
    assert prefix["complete_level"] == level < first_short
    graded = chk.bounded_prefix(mesh, graded=True)
    deep = graded["complete_level"]
    assert level <= deep < first_short and not any(short[:deep + 1])
    assert graded["labels"][:deep + 1] == [len(fps) for fps in levels[:deep + 1]]
    # the one-device engine's stop, state by state and row by row
    one = chk.bounded_prefix(_spawn(model, 1, target, **spawn))
    for key in ("visited", "parents", "popped"):
        assert np.array_equal(prefix[key], one[key])
    for key in ("head", "tail", "complete_level", "labels"):
        assert prefix[key] == one[key]
    # closure: no successor of a popped row is missing
    by_fp = {model.fingerprint_state(s): s for s in kept}
    successors = [model.fingerprint_state(n) for fp in prefix["popped"].tolist()
                  for n in reference.successors(model, by_fp[fp])]
    assert len(prefix["popped"]) == prefix["head"]
    assert chk.missing_from(prefix["visited"], successors) == 0
    assert chk.unreachable(model, prefix, seed=target, draws=64) == []


def test_the_hand_paxos_twins_prefix_on_four_devices_is_the_one_device_prefix():
    from stateright_tpu.models.paxos import paxos_model

    wl = json.load(open(os.path.join(DATA, f"{TINY}.json")))
    pins = json.load(open(os.path.join(DATA, f"{TINY_CONFIG}.json")))["pins"]["bounded"]
    spawn, target = wl["spawn"], chk.bounded_target(wl)
    mesh = _spawn(paxos_model(2, 3), 4, target, **spawn)
    one = _spawn(paxos_model(2, 3), 1, target, **spawn)
    got, want = (chk.bounded_prefix(c, graded=True) for c in (mesh, one))
    assert (mesh.unique_state_count(), mesh.state_count(), mesh.max_depth()) == (
        one.unique_state_count(), one.state_count(), one.max_depth()) == (3079, 5234, 9)
    assert got["tail"] == len(got["visited"]) == 3079 and got["head"] == 1774
    for key in ("visited", "parents", "popped"):
        assert np.array_equal(got[key], want[key])
    assert got["labels"] == want["labels"] and got["complete_level"] == 9
    assert got["labels"][:10] == pins["levels"][:10]
    assert sorted(mesh.discoveries()) == sorted(one.discoveries()) == ["value chosen"]
    assert [str(s) for s in mesh.discovery("value chosen").states()] == [
        str(s) for s in one.discovery("value chosen").states()]


# -- run.py end to end (rehearsal) on four virtual devices --------------------------


@pytest.fixture(scope="module")
def bench4(tmp_path_factory):
    """The manifest as it is plus the tiny four-device cell (``chips: 4``
    in the entry and in the file) and, beside it, the one-device tiny cell
    it must agree with."""
    root = tmp_path_factory.mktemp("bench_paxos6x4")
    bench = root / "benchmarks"
    for sub in ("workloads", "layer_metrics", "configs"):
        shutil.copytree(os.path.join(BENCH, sub), bench / sub)
    doc = json.load(open(os.path.join(REPO, "BENCHMARK.json")))
    shutil.copy(os.path.join(DATA, f"{TINY_CONFIG}.json"), bench / "configs")
    cfg = json.load(open(os.path.join(DATA, f"{TINY_CONFIG}.json")))
    doc["configs"].append({
        "name": TINY_CONFIG, "source": "stateright examples",
        "file": f"benchmarks/configs/{TINY_CONFIG}.json",
        "reduced": cfg["reduced"], "why": "tiny",
    })
    for cell in (TINY, TINY_ONE):
        shutil.copy(os.path.join(DATA, f"{cell}.json"), bench / "workloads")
        wl = json.load(open(os.path.join(DATA, f"{cell}.json")))
        doc["workloads"].append({
            "name": cell, "config": TINY_CONFIG, "traffic": wl["traffic"],
            "chips": wl["chips"], "why": "a tiny cell of the benchmark's own tests",
        })
    for m in doc["per_layer"]:
        if m["name"] in ("queue_fill_pct", "stage_props_lin_s"):
            m["workloads"] += [TINY, TINY_ONE]
        # the mesh engine's two counters; ``collective_s`` is device time:
        # XLA:CPU's thunks on host threads say nothing of it, and no
        # rehearsal lists it
        if m["name"] in ("collective_count", "shard_imbalance"):
            m["workloads"].append(TINY)
    (root / "BENCHMARK.json").write_text(json.dumps(doc))
    assert Manifest(str(root / "BENCHMARK.json"), str(bench)).problems() == []
    return root, doc


def _rehearse4(root, cell, trace=0):
    """``run.py --rehearse-cpu`` with four virtual devices for JAX to see."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["JAX_COMPILATION_CACHE_DIR"] = str(root / "jax_cache")
    env["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=4 "
                        "--xla_backend_optimization_level=0")
    argv = ["--workload", cell, "--seed", "2147483801", "--seconds", "0.5",
            "--trace", str(trace), "--manifest", str(root / "BENCHMARK.json"),
            "--bench-dir", str(root / "benchmarks"), "--rehearse-cpu"]
    return subprocess.run([sys.executable, RUN, *argv], env=env,
                          capture_output=True, text=True, timeout=300,
                          cwd=str(root))


@pytest.fixture(scope="module")
def traced(bench4):
    root, doc = bench4
    p = _rehearse4(root, TINY, trace=1)
    return p, _result(p), doc


def test_the_four_device_rehearsal_is_correct(traced):
    p, out, _ = traced
    assert out["correct"] is True and out["failed"] == 0 and out["attempted"] >= 1
    assert " loop=bounded" in p.stdout and "chips=4" in p.stdout
    assert "traffic=bounded-tiny4" in p.stdout
    assert '"count": 4' in p.stdout  # the device line: four (virtual) devices
    # the one-device tiny cell's stop (test_benchmark_paxos6.py), to the row
    assert "unique=3079 generated=5234 depth=9" in p.stdout
    assert "discoveries=['value chosen']" in p.stdout
    assert "head=1774 tail=3079, complete to level 9" in p.stdout
    assert "levels 0..9 hold 1919 of the 3079 states" in p.stdout
    assert "witness not popped: []" in p.stdout
    assert all(ln.startswith(TAG) for ln in p.stdout.splitlines() if ln.strip())


def test_every_number_of_the_kind_is_compared_at_its_limit(traced):
    p, out, _ = traced
    assert set(out["compared"]) == BOUNDED_NAMES
    assert all(c == {"value": 0, "limit": 0} for c in out["compared"].values())
    assert _compared_lines(p) == [(k, 0.0, 0.0) for k in out["compared"]]


def test_the_traced_rehearsal_reports_every_listed_metric(traced):
    _, out, doc = traced
    want = {m["name"] for m in doc["per_layer"]
            if "workloads" not in m or TINY in m["workloads"]}
    assert_a_rehearsal_prints(want, out["metrics"])
    m = {k: v["value"] for k, v in out["metrics"].items()}
    assert m["device_steps"] == 13
    assert m["queue_fill_pct"] == pytest.approx(100.0 * 3079 / 8192)
    assert m["batch_fill_pct"] == pytest.approx(100.0 * 1774 / (13 * 256))
    # the path was walked on the sharded table: hundreds of bytes crossed
    assert m["reconstruct_pull_s"] < m["reconstruct_s"]
    assert 0 < m["reconstruct_pull_bytes"] < 64 << 10
    # what four devices pay: the step program's collectives, counted in its
    # optimised HLO, and the 3,079 states' spread over the four bucket ranges
    assert "collective_s" not in m
    assert m["collective_count"] >= 1 and m["collective_count"] == int(m["collective_count"])
    assert 0.0 <= m["shard_imbalance"] < 100.0
    # one chunk a step while a step's novel rows fit one batch
    assert 1.0 <= m["append_trips"] <= 16.0
    assert out["device"]["count"] == 4


def test_the_plain_rehearsal_agrees_with_the_one_device_cell(bench4):
    root, _ = bench4
    four, one = _rehearse4(root, TINY), _rehearse4(root, TINY_ONE)
    a, b = _result(four), _result(one)
    assert a["correct"] is True and b["correct"] is True
    assert a["compared"] == b["compared"]

    def stop(p):
        return [ln.split("bounded prefix: ", 1)[1] for ln in p.stdout.splitlines()
                if "bounded prefix: " in ln and "checkpoint + sort" not in ln]

    assert stop(four) == stop(one) and len(stop(four)) == 2


def test_control_a_pinned_level_one_row_off_is_not_correct(bench4):
    """ISSUE 51's control on the tiny cell: ``pins.bounded.levels[5]`` off
    by one reads ``level_sizes_off`` 1 and nothing else."""
    root, _ = bench4
    path = root / "benchmarks" / "configs" / f"{TINY_CONFIG}.json"
    good = path.read_text()
    cfg = json.loads(good)
    cfg["pins"]["bounded"]["levels"][5] += 1
    path.write_text(json.dumps(cfg))
    try:
        p = _rehearse4(root, TINY)
    finally:
        path.write_text(good)
    out = _result(p)
    assert out["correct"] is False
    assert out["compared"]["level_sizes_off"] == {"value": 1, "limit": 0}
    assert _over(out) == {"level_sizes_off"}
