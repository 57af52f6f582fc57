"""Tests of what the ``paxos2lossy`` configuration added to the benchmark:
the reader of the compiled twin's ``twin.drop`` scope
(``layer_metrics/stage_expand_drop_s.py``), the configuration and cell
files, ``run.py`` end to end in rehearsal mode on the tiny sibling
``paxos_lossy(1, 3)``, and the two proofs that ``correct`` can come out
false there: the ``target_states`` control (the whole-space guarantee) and
the LOSSLESS factory held to the lossy pins (the Drop guarantee: a checker
that generates no Drop successor).  CPU-only, unit-cheap.
"""

import json
import os
import shutil
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
BENCH = os.path.join(REPO, "benchmarks")
DATA = os.path.join(HERE, "data")
sys.path.insert(0, BENCH)
sys.path.insert(0, HERE)

from srbench import check as chk  # noqa: E402
from srbench import reference, xstages, xtwin  # noqa: E402
from srbench.manifest import Manifest  # noqa: E402
from test_benchmark_loops import (  # noqa: E402
    _bench, _compared_lines, _rehearse, _result)
from test_benchmark_own import assert_a_rehearsal_prints  # noqa: E402

CONFIG = "paxos2lossy"
CELL = "paxos2lossy-presized"
DROP_METRIC = "stage_expand_drop_s"
# PR 35: one traced check of a LOSSLESS compiled twin on a v5e
LOSSLESS_V5E = os.path.join(DATA, "singlecopy3_v5e.xplane.pb")
TWIN_READERS = ("stage_expand_table_s", "stage_expand_history_s", "twin_compile_s",
                "twin_table_bytes", "stage_props_lin_s")


@pytest.fixture(scope="module")
def manifest():
    return Manifest(os.path.join(REPO, "BENCHMARK.json"), BENCH)


# -- names ----------------------------------------------------------------------


def test_the_readers_scope_name_is_the_programs(manifest):
    from stateright_tpu.telemetry import spans

    reader = manifest.reader_module(DROP_METRIC)
    assert reader.PART == spans.TWIN_DROP
    assert reader.PART.startswith(xtwin.TWIN_PREFIX)  # xtwin files it as a part
    assert reader.PART not in xtwin.PARTS  # only a lossy twin opens it
    entry = next(m for m in manifest.doc["per_layer"] if m["name"] == DROP_METRIC)
    assert (entry["unit"], entry["layer"], entry["moves"], entry["source"]) == (
        reader.UNIT, reader.LAYER, reader.MOVES, reader.SOURCE)
    assert entry["workloads"] == [CELL]


@pytest.mark.parametrize("scope, part", [
    ("jit(wavefront_run)/while/body/sr.expand/twin.drop/twin.net/sort:", "twin.drop"),
    ("jit(wavefront_run)/while/body/sr.expand/twin.drop/select_n:", "twin.drop"),
    ("jit(wavefront_run)/while/body/sr.expand/twin.net/sort:", "twin.net"),
    ("jit(wavefront_run)/while/body/sr.expand/concatenate:", "rest"),
])
def test_a_drop_operation_is_filed_under_the_first_twin_scope_of_its_path(scope, part):
    assert xstages.stage_of(scope) == "sr.expand"
    assert xtwin.part_of(scope) == part


def test_reduce_expand_by_hand_with_a_drop_part():
    ops = {k: {"name": k.replace("_", "."), "scope": v, "source": "f.py:1", "bytes": 0}
           for k, v in dict(
               while_1="", gather_1="a/sr.expand/twin.table/gather:",
               sort_1="a/sr.expand/twin.net/sort:",
               sort_2="a/sr.expand/twin.drop/twin.net/sort:",
               where_1="a/sr.expand/twin.drop/select_n:",
               cat_1="a/sr.expand/concatenate:").items()}
    ns = 1e9
    events = [("while_1", 0.0, 10 * ns), ("gather_1", 0.0, 2 * ns),
              ("sort_1", 2 * ns, 1 * ns), ("sort_2", 3 * ns, 1.5 * ns),
              ("where_1", 5 * ns, 0.5 * ns), ("cat_1", 6 * ns, 0.25 * ns)]
    out = xtwin.reduce_expand({"/device:TPU:0": events}, ops)
    assert out["parts"] == {"twin.table": 2.0, "twin.net": 1.0, "twin.history": 0.0,
                            "rest": 0.25, "twin.drop": 2.0}
    assert out["expand_s"] == pytest.approx(sum(out["parts"].values())) == 5.25
    assert [r[0] for r in out["part_ops"]["twin.drop"]] == ["sort.2", "where.1"]


# -- the reader: without a trace, and on a lossless twin's trace from a v5e -------


def test_the_reader_without_a_trace_reads_nothing(manifest):
    assert manifest.reader_module(DROP_METRIC).read({"cell": {"name": "no-such-cell"}}) is None


def test_the_reader_on_a_lossless_twins_trace_reads_zero(tmp_path, capsys):
    """No operation of a lossless twin carries ``twin.drop``: the seconds
    read 0.0 (as ``stage_expand_history_s`` does without its scope), and
    the reader prints no row of its own."""
    readers = tmp_path / "benchmarks" / "layer_metrics"
    readers.mkdir(parents=True)
    shutil.copy(os.path.join(BENCH, "layer_metrics", f"{DROP_METRIC}.py"), readers)
    trace_dir = tmp_path / ".bench_trace" / "a-cell" / "plugins" / "profile" / "recorded"
    trace_dir.mkdir(parents=True)
    shutil.copy(LOSSLESS_V5E, trace_dir)
    man = Manifest(os.path.join(REPO, "BENCHMARK.json"), str(tmp_path / "benchmarks"))
    assert man.reader_module(DROP_METRIC).read({"cell": {"name": "a-cell"}}) == 0.0
    out = xtwin.analyse(LOSSLESS_V5E)
    assert out["expand_s"] > 0 and "twin.drop" not in out["parts"]
    assert "twin.drop" not in capsys.readouterr().err


# -- the configuration and the cell ---------------------------------------------


def test_the_configuration_file_is_the_deployment_the_factory_builds(manifest):
    entry = manifest.config_entry(CONFIG)
    cfg = manifest.config(CONFIG)
    assert entry["reduced"] == cfg["reduced"] == []  # upstream's own pinned size, uncut
    for source in (cfg["source"], entry["source"]):
        assert "paxos check 2" in source and "LossyNetwork::Yes" in source
    assert cfg["model"]["factory"] == "stateright_tpu.models.paxos:paxos_lossy"
    assert cfg["model"]["args"] == [cfg["client_count"], cfg["server_count"]] == [2, 3]
    assert (cfg["deployment"]["clients"], cfg["deployment"]["servers"]) == (2, 3)
    assert "LOSSY" in cfg["deployment"]["network"]
    assert set(cfg["assumed"]) >= {"lossy", "device_twin", "n_slots", "max_depth"}
    assert len(cfg["guarantees"]) == 4 and any("poison" in g for g in cfg["guarantees"])
    assert "Drop" in cfg["guarantees"][3] and "generated" in cfg["guarantees"][3]
    model = chk.build_model(cfg)
    assert model.lossy and len(model.actors) == 5
    assert model.init_network.name == "unordered_nonduplicating"
    assert [p.name for p in model.properties()] == ["linearizable", "value chosen"]
    twin = model.tensor_model()
    assert cfg["row"] == {"width_u64": twin.width, "max_actions": twin.max_actions}
    assert cfg["row"] == {"width_u64": 21, "max_actions": 40}
    attrs = twin.compile_attrs()
    assert (attrs["lossy"], attrs["max_actions"], attrs["n_slots"]) == (True, 40, 20)
    assert (attrs["actor_states"], attrs["envelopes"], attrs["table_bytes"]) == (
        "1194,1153,28,3,3", 82, 3320508)
    for said in ("1194,1153,28,3,3", "82 envelopes", "3,320,508 B", "max_actions 40"):
        assert said in cfg["assumed"]["device_twin"], said
    pins = cfg["pins"]
    assert (pins["unique"], pins["generated"], pins["max_depth"]) == (954508, 5060177, 24)
    assert pins["discoveries"] == ["value chosen"]  # linearizable: no counterexample
    assert "reference_bfs" in pins["provenance"] and "spawn_bfs" in pins["provenance"]


def test_the_cell_is_presized_for_the_pinned_space(manifest):
    cell, wl = manifest.cell(CELL), manifest.workload(CELL)
    pins = manifest.config(CONFIG)["pins"]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (CONFIG, "presized", 1)
    assert (wl["config"], wl["traffic"], wl["chips"]) == (CONFIG, "presized", 1)
    spawn = wl["spawn"]
    assert (spawn["capacity"], spawn["queue_capacity"], spawn["batch"],
            spawn["steps_per_call"]) == (1 << 22, 1 << 20, 4096, 512)
    assert spawn["cand"] in (1 << 15, 1 << 16)  # the default, 2^14, overflows
    assert wl["builder"] == [] and wl["expect_growth"] == "none"
    assert chk.loop_kind(wl) == "closed"
    assert spawn["queue_capacity"] >= pins["unique"]  # every unique row fits
    # the step program ends a call once unique x 4 > capacity
    assert pins["unique"] * 4 < spawn["capacity"] < pins["unique"] * 8
    names = {m["name"] for m in manifest.metrics_for("per_layer", CELL)}
    assert names >= {DROP_METRIC, *TWIN_READERS, "stage_expand_net_s",
                     "twin_expand_roofline"}
    assert not names & {"acquire_check_s", "twin_compile_check_s"}
    assert {"check_s", "peak_hbm", "setup_s"} <= {
        m["name"] for m in manifest.metrics_for("end_to_end", CELL)}


# -- run.py end to end (rehearsal) on the tiny siblings --------------------------


@pytest.fixture(scope="module")
def lossy_bench(tmp_path_factory):
    """The manifest as it is plus three tiny cells, added as files; the
    compiled lossy ones also report the Drop reader."""
    cells = [("paxos1lossy-tiny", "paxos1lossy"), ("paxos1lossy-bounded", "paxos1lossy"),
             ("paxos1lossless-tiny", "paxos1lossless")]
    lossy = ["paxos1lossy-tiny", "paxos1lossy-bounded"]
    root, doc = _bench(tmp_path_factory, "bench_paxos2lossy", cells, twin=lossy)
    for m in doc["per_layer"]:
        if m["name"] == DROP_METRIC:
            m["workloads"] += lossy
    (root / "BENCHMARK.json").write_text(json.dumps(doc))
    assert Manifest(str(root / "BENCHMARK.json"), str(root / "benchmarks")).problems() == []
    return root, doc


def test_the_tiny_pins_are_the_plain_references():
    cfg = json.load(open(os.path.join(DATA, "paxos1lossy.json")))
    got = reference.reference_bfs(chk.build_model(cfg))
    assert got == {k: cfg["pins"][k] for k in got}
    twin = chk.build_model(cfg).tensor_model()
    assert cfg["row"] == {"width_u64": twin.width, "max_actions": twin.max_actions}
    real = json.load(open(os.path.join(BENCH, "configs", f"{CONFIG}.json")))
    assert cfg["model"]["factory"] == real["model"]["factory"]
    # the control's factory is the lossless one, its pins the lossy ones
    control = json.load(open(os.path.join(DATA, "paxos1lossless.json")))
    assert control["model"]["factory"].endswith(":paxos_model")
    assert {k: control["pins"][k] for k in got} == got
    assert not chk.build_model(control).lossy


@pytest.fixture(scope="module")
def rehearsal(lossy_bench):
    root, doc = lossy_bench
    p = _rehearse(root, "paxos1lossy-tiny", trace=1)
    return p, _result(p), doc


def test_rehearsal_runs_the_cell_and_prints_no_result(rehearsal):
    p, out, doc = rehearsal
    assert out["correct"] is True and out["failed"] == 0 and out["attempted"] >= 1
    assert "unique=2378 generated=8197 depth=14" in p.stdout
    assert "walks=256" in p.stdout and "missing=0" in p.stdout
    assert all(c["value"] == 0 for c in out["compared"].values())
    assert {"unique_off", "generated_off", "max_depth_off", "discoveries_off",
            "paths_off", "growth_off", "sample_missing", "window_persistent_misses",
            "window_compile_requests"} <= set(out["compared"])
    want = [m["name"] for m in doc["per_layer"]
            if ("workloads" not in m or "paxos1lossy-tiny" in m["workloads"])
            and m["name"] not in ("acquire_check_s", "twin_compile_check_s")]
    assert DROP_METRIC in want
    assert_a_rehearsal_prints(want, out["metrics"])


def test_rehearsal_expands_parts_add_up_with_the_drop_part(rehearsal):
    p, out, _ = rehearsal
    m = {k: v["value"] for k, v in out["metrics"].items()}
    parts = [m["stage_expand_table_s"], m["stage_expand_net_s"],
             m["stage_expand_history_s"], m[DROP_METRIC]]
    assert min(parts) > 0
    line = next(ln for ln in p.stderr.splitlines() if ln.startswith("xtwin: sr.expand "))
    rest = float(line.rsplit("rest ", 1)[1])
    # (the line prints microseconds)
    assert sum(parts) + rest == pytest.approx(m["stage_expand_s"], abs=5e-6)
    drop_rows = [ln for ln in p.stderr.splitlines() if ln.startswith("xtwin:   twin.drop")]
    assert len(drop_rows) == 1 and float(drop_rows[0].split()[2]) == pytest.approx(
        m[DROP_METRIC], abs=1e-6)
    assert 0 < m["twin_compile_s"] < 60 and m["twin_table_bytes"] == 6188.0
    assert m["growth_s"] == 0.0 and m["device_steps"] >= 2378 / 256
    # 32 action columns a popped state: the share of them that held a state
    assert m["cand_fill_pct"] == pytest.approx(
        100.0 * 8197 / (m["device_steps"] * 256 * 32), rel=1e-6)


def test_the_control_a_bounded_search_is_not_correct(lossy_bench):
    root, _ = lossy_bench
    p = _rehearse(root, "paxos1lossy-bounded")
    out = _result(p)
    assert out["correct"] is False and out["failed"] == out["attempted"] >= 1
    over = {k for k, c in out["compared"].items() if c["value"] > c["limit"]}
    assert {"unique_off", "generated_off", "sample_missing"} <= over
    assert "NOT CORRECT" in p.stdout
    assert any(v > lim for _, v, lim in _compared_lines(p))


def test_the_control_a_checker_that_drops_nothing_is_not_correct(lossy_bench):
    """The lossless factory under the lossy pins: every Drop successor is
    missing from ``generated``, every state reached only through a loss
    from ``unique`` and from the visited set."""
    root, _ = lossy_bench
    p = _rehearse(root, "paxos1lossless-tiny")
    out = _result(p)
    assert out["correct"] is False and out["failed"] == out["attempted"] >= 1
    assert out["compared"]["unique_off"]["value"] == 2378 - 265
    assert out["compared"]["generated_off"]["value"] > 0
    assert "NOT CORRECT" in p.stdout and "generated" in p.stdout
    assert ("unique_off", 2378.0 - 265.0, 0.0) in _compared_lines(p)
