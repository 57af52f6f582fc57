"""Tests of what the ``singlecopy4`` configuration added to the benchmark:
the split of ``sr.props`` by the linearizability verdict's sub-scope
(``srbench/xprops.py``), the reader built on it, the configuration and cell files, ``run.py`` end to end in rehearsal mode on
the tiny sibling ``single_copy_model(3, 1)``, and the three proofs that
``correct`` can come out false there: the ``target_states`` control, a
verdict that is constantly true on a protocol that is not linearizable,
and — by the pins — a checker that answers another count.  CPU-only,
unit-cheap.
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
from srbench import reference, xprops, xstages  # noqa: E402
from srbench.manifest import Manifest  # noqa: E402
from test_benchmark_loops import (  # noqa: E402
    _bench, _compared_lines, _rehearse, _result)
from test_benchmark_own import assert_a_rehearsal_prints  # noqa: E402

CELL = "singlecopy4-presized"
LIN_METRIC = "stage_props_lin_s"
# PR 35: one traced check of single_copy_model(3, 1), the compiled twin over
# the unordered network, on a v5e (16 device steps of 512); the /host:metadata
# plane (the programs' HLO protos, 1.33 MB, read for a CPU trace only) was
# dropped, as PR 28 did: 0.96 MB
SC_V5E = os.path.join(DATA, "singlecopy3_v5e.xplane.pb")
NO_SCOPE_V5E = os.path.join(DATA, "twopc4_v5e_named.xplane.pb")  # PR 24: no history


@pytest.fixture(scope="module")
def manifest():
    return Manifest(os.path.join(REPO, "BENCHMARK.json"), BENCH)


# -- names ----------------------------------------------------------------------


def test_the_yardsticks_scope_name_is_the_programs():
    from stateright_tpu.telemetry import spans

    assert xprops.LIN == spans.PROPS_LIN
    assert xprops.PROPS == spans.STAGE_PROPS
    assert not xprops.LIN.startswith(xstages.STAGE_PREFIX)  # never a stage of its own


@pytest.mark.parametrize("scope, stage, part", [
    ("jit(wavefront_run)/while/body/sr.props/props.lin/and:", "sr.props", "props.lin"),
    ("jit(wavefront_run)/while/body/sr.props/props.lin/jit(_take)/gather:", "sr.props", "props.lin"),
    ("jit(wavefront_run)/while/body/sr.props/reduce_or:", "sr.props", "rest"),
    ("jit(wavefront_run)/while/body/sr.props/props.linear/and:", "sr.props", "rest"),
    ("jit(wavefront_run)/while/body/sr.expand/props.lin/and:", "sr.expand", "props.lin"),
    ("", "unnamed", "rest"),
])
def test_part_of_a_scope_path(scope, stage, part):
    assert xstages.stage_of(scope) == stage
    assert xprops.part_of(scope) == part


# -- the arithmetic by hand -----------------------------------------------------


def _ops(**scopes):
    return {k: {"name": k.replace("_", "."), "scope": v, "source": "f.py:1", "bytes": 0}
            for k, v in scopes.items()}


def test_reduce_props_by_hand():
    ops = _ops(
        while_1="", and_1="a/sr.props/props.lin/and:", or_1="a/sr.props/props.lin/or:",
        any_1="a/sr.props/reduce_or:", sort_1="a/sr.expand/twin.net/sort:",
        stray_1="a/sr.expand/props.lin/and:",
    )
    ns = 1e9
    events = [("while_1", 0.0, 10 * ns), ("and_1", 0.0, 1 * ns), ("or_1", 1 * ns, 2 * ns),
              ("any_1", 3 * ns, 0.5 * ns), ("sort_1", 4 * ns, 3 * ns),
              ("stray_1", 7 * ns, 1 * ns), ("and_1", 9 * ns, 1 * ns)]
    out = xprops.reduce_props({"/device:TPU:0": events}, ops)
    # the expand stage's operations are not the props stage's, scope or no scope
    assert out["parts"] == {"props.lin": 4.0, "rest": 0.5} and out["props_s"] == 4.5
    assert out["lin_ops"] == 2
    assert out["ops"] == [["props.lin", "and.1", "f.py:1", 2.0],
                          ["props.lin", "or.1", "f.py:1", 2.0],
                          ["rest", "any.1", "f.py:1", 0.5]]
    # a window clips whole operations out, as reduce_stages does
    late = xprops.reduce_props({"/device:TPU:0": events}, ops, window=(8.5 * ns, 10 * ns))
    assert late["parts"] == {"props.lin": 1.0, "rest": 0.0}
    # two chips: the average
    two = xprops.reduce_props({"/device:TPU:0": events, "/device:TPU:1": events[:2]}, ops)
    assert two["parts"]["props.lin"] == pytest.approx(2.5)
    assert xprops.reduce_props({}, ops) == {}
    assert "xprops:" in xprops.report(out) and "(2 operations)" in xprops.report(out)


# -- the reader: without a trace, and on traces recorded on a TPU v5e ------------


def test_the_reader_without_a_trace_reads_nothing(manifest):
    ctx = {"cell": {"name": "no-such-cell"}}
    assert manifest.reader_module(LIN_METRIC).read(ctx) is None


def _reader_at(tmp_path, recorded):
    """The reader at its place in a checkout (``benchmarks/layer_metrics/``)
    with ``recorded`` as the traced check of ``a-cell`` under
    ``.bench_trace/``."""
    readers = tmp_path / "benchmarks" / "layer_metrics"
    readers.mkdir(parents=True)
    shutil.copy(os.path.join(BENCH, "layer_metrics", f"{LIN_METRIC}.py"), readers)
    trace_dir = tmp_path / ".bench_trace" / "a-cell" / "plugins" / "profile" / "recorded"
    trace_dir.mkdir(parents=True)
    shutil.copy(recorded, trace_dir)
    man = Manifest(os.path.join(REPO, "BENCHMARK.json"), str(tmp_path / "benchmarks"))
    return man.reader_module(LIN_METRIC).read


def test_the_reader_on_a_compiled_twins_trace_recorded_on_a_v5e(tmp_path, capsys):
    """``props.lin`` + the rest = ``stage_props_s``; every operation of the
    stage is listed."""
    got_s = _reader_at(tmp_path, SC_V5E)({"cell": {"name": "a-cell"}})
    err = capsys.readouterr().err
    stages = xstages.analyse(SC_V5E)
    out = xprops.analyse(SC_V5E)
    assert stages["windowed"]
    assert out["props_s"] == pytest.approx(stages["stages"]["sr.props"], rel=1e-9)
    assert sum(out["parts"].values()) == pytest.approx(out["props_s"], rel=1e-9)
    assert got_s == out["parts"]["props.lin"] and 0 < got_s < out["props_s"]
    assert out["parts"]["rest"] > 0 and out["lin_ops"] >= 1
    listed = [ln for ln in err.splitlines() if ln.startswith("xprops:   ")]
    assert len(listed) == len(out["ops"]) > 1  # printed once, every operation
    # (each line is printed to the microsecond)
    assert sum(float(ln.split()[1]) for ln in listed) == pytest.approx(
        out["props_s"], abs=1e-6 * len(listed))
    assert f"in {len(listed)} operations" in err


def test_the_reader_on_a_trace_without_the_scope(tmp_path, capsys):
    """2pc has no history: no operation carries ``props.lin``.  The seconds
    read 0 (as a stage or a ``twin.*`` part without its scope does); the
    stage's operations are still listed."""
    assert _reader_at(tmp_path, NO_SCOPE_V5E)({"cell": {"name": "a-cell"}}) == 0.0
    out = xprops.analyse(NO_SCOPE_V5E)
    assert out["lin_ops"] == 0 and out["parts"]["props.lin"] == 0.0
    assert out["parts"]["rest"] == pytest.approx(
        xstages.analyse(NO_SCOPE_V5E)["stages"]["sr.props"], rel=1e-9)
    assert "(0 operations)" in capsys.readouterr().err


def test_the_command_line_prints_the_list(capsys):
    assert xprops.main([SC_V5E]) == 0
    assert "xprops: sr.props " in capsys.readouterr().out
    assert xprops.main([]) == 2


# -- the configuration and the cell ---------------------------------------------


def test_the_configuration_file_is_the_deployment_the_factory_builds(manifest):
    entry = manifest.config_entry("singlecopy4")
    cfg = manifest.config("singlecopy4")
    assert entry["reduced"] == cfg["reduced"] == []  # upstream's own size, uncut
    assert "bench.sh:29" in cfg["source"] and "check 4" in cfg["source"]
    assert "bench.sh:29" in entry["source"] and "check 4" in entry["source"]
    assert cfg["model"]["args"] == [cfg["client_count"], cfg["server_count"]] == [4, 1]
    assert (cfg["deployment"]["clients"], cfg["deployment"]["servers"]) == (4, 1)
    assert set(cfg["assumed"]) >= {"device_twin", "n_slots", "max_depth"}
    assert any("poison" in g for g in cfg["guarantees"]) and len(cfg["guarantees"]) == 3
    model = chk.build_model(cfg)
    assert len(model.actors) == 5
    assert model.init_network.name == "unordered_nonduplicating"
    assert [p.name for p in model.properties()] == ["linearizable", "value chosen"]
    twin = model.tensor_model()
    assert cfg["row"] == {"width_u64": twin.width, "max_actions": twin.max_actions}
    assert cfg["row"] == {"width_u64": 21, "max_actions": 20}
    assert not twin.ordered and not twin.per_channel
    attrs = twin.compile_attrs()
    assert (attrs["hist_strategy"], attrs["hist_threads"], attrs["hist_bits"]) == (
        "closure", 4, 44)
    assert (attrs["actor_states"], attrs["envelopes"], attrs["n_slots"]) == (
        "5,3,3,3,3", 32, 20)
    pins = cfg["pins"]
    assert (pins["unique"], pins["generated"], pins["max_depth"]) == (400233, 731789, 16)
    assert pins["discoveries"] == ["value chosen"]  # linearizable: no counterexample
    assert "reference_bfs" in pins["provenance"] and "spawn_bfs" in pins["provenance"]


def test_the_cell_is_presized_for_the_pinned_space(manifest):
    cell, wl = manifest.cell(CELL), manifest.workload(CELL)
    pins = manifest.config("singlecopy4")["pins"]
    assert (cell["config"], cell["traffic"], cell["chips"]) == ("singlecopy4", "presized", 1)
    assert wl["spawn"] == {"capacity": 1 << 22, "queue_capacity": 1 << 19,
                           "batch": 4096, "steps_per_call": 512}
    assert wl["builder"] == [] and wl["expect_growth"] == "none"
    assert chk.loop_kind(wl) == "closed"
    assert wl["spawn"]["queue_capacity"] >= pins["unique"]  # every unique row fits
    assert pins["unique"] / wl["spawn"]["capacity"] < 0.1  # the table's load
    names = {m["name"] for m in manifest.metrics_for("per_layer", CELL)}
    assert names >= {
        LIN_METRIC, "stage_expand_table_s", "stage_expand_net_s", "stage_expand_history_s",
        "twin_expand_roofline", "twin_compile_s", "twin_table_bytes"}
    assert not names & {"acquire_check_s", "twin_compile_check_s"}
    assert {m["name"] for m in manifest.metrics_for("end_to_end", CELL)} == {
        "check_s", "gen_rate", "peak_hbm", "setup_s"}
    # the verdict's reader says where it finds something to read: the cells
    # whose state holds a history, and no 2pc cell
    listed = next(m for m in manifest.doc["per_layer"] if m["name"] == LIN_METRIC)["workloads"]
    assert CELL in listed and not any(c.startswith("twopc") for c in listed)
    for name in listed:
        props = manifest.config(manifest.cell(name)["config"])["deployment"]["properties"]
        assert "always linearizable" in props, name


# -- run.py end to end (rehearsal) on the tiny siblings --------------------------


@pytest.fixture(scope="module")
def sc_bench(tmp_path_factory):
    """The manifest as it is plus three tiny cells, added as files."""
    cells = [("singlecopy3-tiny", "singlecopy3"), ("singlecopy3-bounded", "singlecopy3"),
             ("singlecopy3x2-tiny", "singlecopy3x2")]
    return _bench(tmp_path_factory, "bench_singlecopy", cells,
                  twin=[c for c, _ in cells])


@pytest.mark.parametrize("config", ["singlecopy3", "singlecopy3x2"])
def test_the_tiny_pins_are_the_plain_references(config):
    cfg = json.load(open(os.path.join(DATA, f"{config}.json")))
    got = reference.reference_bfs(chk.build_model(cfg))
    assert got == {k: cfg["pins"][k] for k in got}
    twin = chk.build_model(cfg).tensor_model()
    assert cfg["row"] == {"width_u64": twin.width, "max_actions": twin.max_actions}
    assert (cfg["model"]["factory"]
            == json.load(open(os.path.join(BENCH, "configs", "singlecopy4.json")))["model"]["factory"])


@pytest.fixture(scope="module")
def rehearsal(sc_bench):
    root, doc = sc_bench
    p = _rehearse(root, "singlecopy3-tiny", trace=1)
    return p, _result(p), doc


def test_rehearsal_runs_the_cell_and_prints_no_result(rehearsal):
    p, out, doc = rehearsal
    assert out["correct"] is True and out["failed"] == 0 and out["attempted"] >= 1
    assert "unique=4243 generated=6778 depth=12" in p.stdout
    assert "walks=256" in p.stdout and "missing=0" in p.stdout
    assert all(c["value"] == 0 for c in out["compared"].values())
    assert {"unique_off", "generated_off", "max_depth_off", "discoveries_off",
            "paths_off", "growth_off", "sample_missing", "window_persistent_misses",
            "window_compile_requests"} <= set(out["compared"])
    # (the fixture lists a compiled-twin cell wherever ``linreg2x3o-cold`` is
    # listed: the cold loop's two read nothing in a closed cell)
    want = [m["name"] for m in doc["per_layer"]
            if ("workloads" not in m or "singlecopy3-tiny" in m["workloads"])
            and m["name"] not in ("acquire_check_s", "twin_compile_check_s")]
    assert_a_rehearsal_prints(want, out["metrics"])


def test_rehearsal_props_numbers_hang_together(rehearsal):
    p, out, _ = rehearsal
    m = {k: v["value"] for k, v in out["metrics"].items()}
    assert 0 < m["stage_props_lin_s"] <= m["stage_props_s"]
    line = next(ln for ln in p.stderr.splitlines() if ln.startswith("xprops: sr.props "))
    rest = float(line.rsplit("rest ", 1)[1])
    # (the line prints microseconds)
    assert m["stage_props_lin_s"] + rest == pytest.approx(m["stage_props_s"], abs=2e-6)
    assert "xprops:" not in p.stdout
    parts = (m["stage_expand_table_s"] + m["stage_expand_net_s"]
             + m["stage_expand_history_s"])
    assert 0 < parts <= m["stage_expand_s"]
    assert min(m["stage_expand_table_s"], m["stage_expand_net_s"],
               m["stage_expand_history_s"]) > 0
    assert 0 < m["twin_compile_s"] < 60 and m["twin_table_bytes"] == 2814.0
    assert m["growth_s"] == 0.0 and m["device_steps"] >= 4243 / 256


def test_the_control_a_bounded_search_is_not_correct(sc_bench):
    root, _ = sc_bench
    p = _rehearse(root, "singlecopy3-bounded")
    out = _result(p)
    assert out["correct"] is False and out["failed"] == out["attempted"] >= 1
    over = {k for k, c in out["compared"].items() if c["value"] > c["limit"]}
    assert {"unique_off", "generated_off", "sample_missing"} <= over
    assert "NOT CORRECT" in p.stdout
    assert any(v > lim for _, v, lim in _compared_lines(p))


CONSTANT_VERDICT = '''
import jax.numpy as jnp
from stateright_tpu.parallel import actor_compiler as ac

# the verdict's mathematics left out: every history reads linearizable
ac.CompiledActorTensor._linearizable_mask = (
    lambda self, rows: jnp.ones((rows.shape[0],), bool))
'''


def test_a_verdict_that_is_constantly_true_is_not_correct(sc_bench):
    """Two unreplicated servers are not linearizable: the reference
    discovers ``linearizable``.  A twin that answers True for every history
    never does, and the discovery set says so (so do the counts: its search
    does not stop where the reference's does)."""
    root, _ = sc_bench
    p = _rehearse(root, "singlecopy3x2-tiny", prelude=CONSTANT_VERDICT)
    out = _result(p)
    assert out["correct"] is False and out["failed"] == out["attempted"] >= 1
    assert out["compared"]["discoveries_off"] == {"value": 1, "limit": 0}
    assert "discoveries ['value chosen'] != pinned ['linearizable', 'value chosen']" in p.stdout
    assert ("discoveries_off", 1.0, 0.0) in _compared_lines(p)


def test_the_sound_verdict_discovers_both_on_the_same_cell():
    """The same configuration and cell files through the harness's own
    ``run_check`` / ``compare``, the twin as it is: both discoveries, each
    path singled out by its property on the host model."""
    cfg = json.load(open(os.path.join(DATA, "singlecopy3x2.json")))
    wl = json.load(open(os.path.join(DATA, "singlecopy3x2-tiny.json")))
    model = chk.build_model(cfg)
    result = chk.run_check(lambda: model, wl, telemetry=False)
    rows = {name: value for name, value, _, _ in chk.compare(model, cfg, wl, result)}
    assert rows["discoveries_off"] == 0 and rows["paths_off"] == 0
    assert not result["paths"]["linearizable"].last_state().history.is_consistent()
