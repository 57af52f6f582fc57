"""Tests of what the ``linreg2x3o`` configuration added to the benchmark:
the split of ``sr.expand`` by a compiled actor twin's sub-scopes
(``srbench/xtwin.py``), the expand stage's necessary bytes
(``srbench/expand_bytes.py``), the six readers built on them, the
configuration and cell files, and ``run.py`` end to end in rehearsal mode
on the tiny sibling ``abd_model(2, 2, ordered)``.  CPU-only, unit-cheap.
"""

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
from srbench import expand_bytes, reference, stats, xstages, xtwin  # noqa: E402
from srbench.manifest import Manifest  # noqa: E402

# PR 28: one traced check of abd_model(2, 2, ordered), the compiled twin, on a
# v5e (25 device steps of 256); the /host:metadata plane (the programs' HLO
# protos, 1.26 MB, read for a CPU trace only) was dropped to stay under 2 MB
TWIN_V5E = os.path.join(DATA, "linreg2x2o_v5e.xplane.pb")
NAMED_V5E = os.path.join(DATA, "twopc4_v5e_named.xplane.pb")  # PR 24: a hand twin
TWIN_METRICS = (
    "stage_expand_table_s", "stage_expand_net_s", "stage_expand_history_s",
    "twin_expand_roofline", "twin_compile_s", "twin_table_bytes",
)


@pytest.fixture(scope="module")
def manifest():
    return Manifest(os.path.join(REPO, "BENCHMARK.json"), BENCH)


# -- names ----------------------------------------------------------------------


def test_the_yardsticks_part_names_are_the_programs():
    from stateright_tpu.telemetry import spans

    assert xtwin.PARTS == spans.TWIN_SCOPES
    assert xtwin.EXPAND == spans.STAGE_EXPAND
    # a part never reads as a stage of its own
    assert not any(p.startswith(xstages.STAGE_PREFIX) for p in xtwin.PARTS)


@pytest.mark.parametrize("scope, stage, part", [
    ("jit(wavefront_run)/while/body/sr.expand/twin.table/gather:", "sr.expand", "twin.table"),
    ("jit(wavefront_run)/while/body/sr.expand/twin.net/twin.net/sort:", "sr.expand", "twin.net"),
    ("jit(wavefront_run)/while/body/sr.expand/twin.history/select_n:", "sr.expand", "twin.history"),
    ("jit(wavefront_run)/while/body/sr.expand/concatenate:", "sr.expand", "rest"),
    ("jit(wavefront_run)/while/body/sr.props/twin.net/sort:", "sr.props", "twin.net"),
    ("", "unnamed", "rest"),
])
def test_part_of_a_scope_path(scope, stage, part):
    assert xstages.stage_of(scope) == stage
    assert xtwin.part_of(scope) == part


# -- the arithmetic by hand -----------------------------------------------------


def _ops(**scopes):
    return {k: {"name": k.replace("_", "."), "scope": v, "source": "f.py:1", "bytes": 0}
            for k, v in scopes.items()}


def test_reduce_expand_by_hand():
    ops = _ops(
        while_1="", gather_1="a/sr.expand/twin.table/gather:",
        sort_1="a/sr.expand/twin.net/sort:", select_1="a/sr.expand/twin.history/select_n:",
        concat_1="a/sr.expand/concatenate:", scatter_1="a/sr.insert/scatter:",
        sort_2="a/sr.props/twin.net/sort:",
    )
    ns = 1e9
    events = [("while_1", 0.0, 10 * ns), ("gather_1", 0.0, 1 * ns),
              ("sort_1", 1 * ns, 2 * ns), ("select_1", 3 * ns, 0.5 * ns),
              ("concat_1", 4 * ns, 0.25 * ns), ("scatter_1", 5 * ns, 3 * ns),
              ("sort_2", 8 * ns, 1 * ns), ("gather_1", 9 * ns, 1 * ns)]
    out = xtwin.reduce_expand({"/device:TPU:0": events}, ops)
    assert out["parts"] == {"twin.table": 2.0, "twin.net": 2.0,
                            "twin.history": 0.5, "rest": 0.25}
    assert out["expand_s"] == 4.75  # the insert's scatter and the props' sort are not expand
    assert out["part_ops"]["twin.table"] == [["gather.1", "f.py:1", 2.0]]
    # a window clips whole operations out, as reduce_stages does
    late = xtwin.reduce_expand({"/device:TPU:0": events}, ops, window=(8.5 * ns, 10 * ns))
    assert late["parts"]["twin.table"] == 1.0 and late["expand_s"] == 1.0
    # two chips: the average
    two = xtwin.reduce_expand({"/device:TPU:0": events, "/device:TPU:1": events[:2]}, ops)
    assert two["parts"]["twin.table"] == pytest.approx(1.5)
    assert xtwin.reduce_expand({}, ops) == {}


@pytest.mark.parametrize("width, generated, unique, want", [
    (21, 736141, 270381, (736141 + 270381) * 21 * 8),
    (1, 0, 1, 8),
    (33, 10, 0, 2640),
])
def test_expand_bytes_by_hand(width, generated, unique, want):
    assert expand_bytes.expand_bytes(width, generated, unique) == want


def test_expand_roofline_share_by_hand():
    # 1,000,000 rows of 16 words read or written = 128 MB; at 128 MB/s that is 1 s
    got = expand_bytes.expand_roofline_pct(16, 600_000, 400_000, 128e6, 4.0)
    assert got == pytest.approx(25.0)
    with pytest.raises(ValueError):
        expand_bytes.expand_roofline_pct(16, 1, 1, 128e6, 0.0)
    with pytest.raises(ValueError):
        expand_bytes.expand_bytes(0, 1, 1)


# -- the traces recorded on a TPU v5e -------------------------------------------


def test_compiled_twin_trace_parts_and_rest_add_up_to_the_expand_stage():
    stages = xstages.analyse(TWIN_V5E)
    out = xtwin.analyse(TWIN_V5E)
    assert stages["windowed"]
    assert out["expand_s"] == pytest.approx(stages["stages"]["sr.expand"], rel=1e-4)
    assert sum(out["parts"].values()) == pytest.approx(out["expand_s"], rel=1e-9)
    for part in xtwin.PARTS:
        assert out["parts"][part] > 0, part
        assert out["part_ops"][part], part
    assert out["parts"]["rest"] > 0
    # the compiled twin's expand dominates its step; nothing lost its name
    assert stages["unnamed_pct"] < 15
    assert "xtwin:   twin.net" in xtwin.report(out)


def test_a_hand_twin_without_the_scopes_reads_zero_parts_and_all_rest():
    stages = xstages.analyse(NAMED_V5E)
    out = xtwin.analyse(NAMED_V5E)
    assert [out["parts"][p] for p in xtwin.PARTS] == [0.0, 0.0, 0.0]
    assert out["parts"]["rest"] == pytest.approx(stages["stages"]["sr.expand"], rel=1e-9)
    assert out["parts"]["rest"] > 0


def test_the_command_line_prints_the_table(capsys):
    assert xtwin.main([TWIN_V5E]) == 0
    assert "xtwin: sr.expand " in capsys.readouterr().out
    assert xtwin.main([]) == 2


# -- the readers on a synthetic context -----------------------------------------


def _compile_record(dur=0.07, table_bytes=196100):
    return {"kind": "span", "name": "twin_compile", "dur": dur, "n_slots": 20,
            "row_width": 21, "envelopes": 106, "actor_states": "39,64,32,3,3",
            "table_bytes": table_bytes}


def test_compile_span_readers(manifest):
    compile_s = manifest.reader_module("twin_compile_s")
    table_bytes = manifest.reader_module("twin_table_bytes")
    compiled = {"warmup_records": [{"kind": "step"}, _compile_record(),
                                   {"kind": "span", "name": "engine_run", "dur": 1.0}]}
    assert compile_s.read(compiled) == 0.07
    assert table_bytes.read(compiled) == 196100.0
    # a hand-written twin: the seams are there, nothing was compiled -> 0
    hand = {"warmup_records": [{"kind": "step"},
                               {"kind": "span", "name": "engine_run", "dur": 1.0}]}
    assert compile_s.read(hand) == 0.0 and table_bytes.read(hand) == 0.0
    # no recorder (an untraced run): nothing to read
    assert compile_s.read({"warmup_records": []}) is None
    assert table_bytes.read({}) is None


def test_trace_readers_without_a_trace_read_nothing(manifest):
    ctx = {"cell": {"name": "no-such-cell"}, "row": {"width": 21},
           "pins": {"unique": 1, "generated": 1}, "peaks": None}
    for metric in TWIN_METRICS[:4]:
        assert manifest.reader_module(metric).read(ctx) is None, metric


# -- the configuration and the cell ---------------------------------------------


def test_the_configuration_file_is_the_deployment_the_factory_builds(manifest):
    cfg = manifest.config("linreg2x3o")
    assert cfg["model"]["args"] == [cfg["client_count"], cfg["server_count"]] == [2, 3]
    assert cfg["reduced_from"]["client_count"]["source"] == 3  # bench.sh's leg
    assert set(cfg["assumed"]) >= {"server_count", "device_twin"}
    model = chk.build_model(cfg)
    assert len(model.actors) == 5 and model.init_network.name == "ordered"
    assert [p.name for p in model.properties()] == ["linearizable", "value chosen"]
    twin = model.tensor_model()
    assert cfg["row"] == {"width_u64": twin.width, "max_actions": twin.max_actions}
    assert twin.ordered and not twin.per_channel
    assert twin.compile_attrs()["table_bytes"] == 196100
    pins = cfg["pins"]
    assert (pins["unique"], pins["generated"], pins["max_depth"]) == (270381, 736141, 32)
    assert "reference_bfs" in pins["provenance"] and "spawn_bfs" in pins["provenance"]


def test_the_cell_is_presized_for_the_pinned_space(manifest):
    cell = manifest.cell("linreg2x3o-presized")
    wl = manifest.workload("linreg2x3o-presized")
    pins = manifest.config("linreg2x3o")["pins"]
    assert (cell["config"], cell["traffic"], cell["chips"]) == ("linreg2x3o", "presized", 1)
    assert wl["spawn"] == {"capacity": 1 << 21, "queue_capacity": 1 << 19,
                           "batch": 4096, "steps_per_call": 512}
    assert wl["builder"] == [] and wl["expect_growth"] == "none"
    assert wl["spawn"]["queue_capacity"] >= pins["unique"]  # every unique row fits
    assert pins["unique"] / wl["spawn"]["capacity"] < 0.14  # the table's load
    # it reports what the manifest gives it: every per-layer metric without
    # a ``workloads`` list and every one whose list names it (a reader a
    # later PR lists for its own cell alone is not this cell's) - the six
    # of PR 28 among them, the cold loop's two not (its checks acquire nothing)
    names = {m["name"] for m in manifest.metrics_for("per_layer", cell["name"])}
    assert names == {m["name"] for m in manifest.doc["per_layer"]
                     if "workloads" not in m or cell["name"] in m["workloads"]}
    assert names >= set(TWIN_METRICS)
    assert not names & {"acquire_check_s", "twin_compile_check_s"}
    # four of the six read nothing on a hand twin, and say so in the manifest:
    # their lists hold the two linreg cells, and any later compiled-twin cell
    listed = {m["name"]: m["workloads"] for m in manifest.doc["per_layer"]
              if "workloads" in m and m["name"] in TWIN_METRICS}
    assert sorted(listed) == ["stage_expand_history_s", "stage_expand_table_s",
                              "twin_compile_s", "twin_table_bytes"]
    assert all(set(cells) >= {"linreg2x3o-presized", "linreg2x3o-cold"}
               for cells in listed.values())
    # device-bound (gen_rate spread 0.04% over 6 runs on a v5e): all four
    assert {m["name"] for m in manifest.metrics_for("end_to_end", cell["name"])} == {
        "check_s", "gen_rate", "peak_hbm", "setup_s"}


# -- run.py end to end (rehearsal) on the tiny sibling --------------------------


@pytest.fixture(scope="module")
def tiny_linreg(tmp_path_factory):
    """The manifest as it is plus a tiny cell of the compiled ABD twin
    (2 clients, 2 servers, ordered links: 564 states), added as files."""
    root = tmp_path_factory.mktemp("bench_twin")
    bench = root / "benchmarks"
    for sub in ("workloads", "layer_metrics", "configs"):
        shutil.copytree(os.path.join(BENCH, sub), bench / sub)
    shutil.copy(os.path.join(DATA, "linreg2x2o.json"), bench / "configs")
    shutil.copy(os.path.join(DATA, "linreg2x2o-tiny.json"), bench / "workloads")
    doc = json.load(open(os.path.join(REPO, "BENCHMARK.json")))
    doc["configs"].append({
        "name": "linreg2x2o", "source": "stateright examples/linearizable-register.rs",
        "file": "benchmarks/configs/linreg2x2o.json", "reduced": ["client_count"],
        "why": "tiny",
    })
    doc["workloads"].append({
        "name": "linreg2x2o-tiny", "config": "linreg2x2o", "traffic": "tiny",
        "chips": 1, "why": "rehearsal of the compiled twin's readers on the CPU",
    })
    for m in doc["per_layer"]:  # a compiled twin: join its readers' lists
        if "linreg2x3o-presized" in m.get("workloads", []):
            m["workloads"].append("linreg2x2o-tiny")
    (root / "BENCHMARK.json").write_text(json.dumps(doc))
    assert Manifest(str(root / "BENCHMARK.json"), str(bench)).problems() == []
    return root, doc


def test_the_tiny_pins_are_the_plain_references(tiny_linreg):
    root, _ = tiny_linreg
    cfg = json.load(open(root / "benchmarks" / "configs" / "linreg2x2o.json"))
    got = reference.reference_bfs(chk.build_model(cfg))
    assert got == {k: cfg["pins"][k] for k in got}


@pytest.fixture(scope="module")
def rehearsal(tiny_linreg):
    """One traced rehearsal from an EMPTY compile cache: XLA:CPU keeps the
    scope paths only in an executable it compiled itself."""
    root, doc = tiny_linreg
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["JAX_COMPILATION_CACHE_DIR"] = str(root / "jax_cache_twin")
    env.pop("XLA_FLAGS", None)
    p = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload",
         "linreg2x2o-tiny", "--seed", "2147483777", "--seconds", "0.5", "--trace", "1",
         "--manifest", str(root / "BENCHMARK.json"),
         "--bench-dir", str(root / "benchmarks"), "--rehearse-cpu"],
        env=env, capture_output=True, text=True, timeout=300, cwd=str(root),
    )
    assert p.returncode == 2, p.stderr[-2000:]
    last = p.stdout.strip().splitlines()[-1]
    assert "rehearsal complete (no result line): " in last
    out = json.loads(last.split("(no result line): ", 1)[1])
    return p, out, doc


def test_rehearsal_runs_the_window_loop_to_its_labelled_end(rehearsal):
    p, out, _ = rehearsal
    assert out["correct"] is True and out["failed"] == 0 and out["attempted"] >= 1
    assert "exactness sample: seed=2147483777 walks=256" in p.stdout
    assert "missing=0" in p.stdout and "check 1: start=+0.00" in p.stdout
    assert "unique=564 generated=813 depth=24" in p.stdout
    assert all(ln.startswith("[CPU REHEARSAL - not a chip result] ")
               for ln in p.stdout.splitlines() if ln.strip())


@pytest.mark.parametrize("metric", TWIN_METRICS)
def test_rehearsal_prints_every_twin_metric(rehearsal, metric):
    _, out, doc = rehearsal
    entry = next(m for m in doc["per_layer"] if m["name"] == metric)
    got = out["metrics"][metric]
    assert got["unit"] == entry["unit"] and got["value"] >= 0.0


def test_rehearsal_twin_numbers_hang_together(rehearsal):
    p, out, _ = rehearsal
    m = {k: v["value"] for k, v in out["metrics"].items()}
    parts = (m["stage_expand_table_s"] + m["stage_expand_net_s"]
             + m["stage_expand_history_s"])
    assert 0 < parts <= m["stage_expand_s"]
    assert min(m["stage_expand_table_s"], m["stage_expand_net_s"],
               m["stage_expand_history_s"]) > 0
    # the printed rest makes up the difference
    line = next(ln for ln in p.stderr.splitlines() if ln.startswith("xtwin: sr.expand "))
    rest = float(line.rsplit("rest ", 1)[1])
    assert parts + rest == pytest.approx(m["stage_expand_s"], rel=1e-3)
    assert "xtwin:" not in p.stdout
    assert 0 < m["twin_compile_s"] < 60
    assert m["twin_table_bytes"] == 61432.0
    assert m["growth_s"] == m["grow_pull_s"] == 0.0
    assert m["stage_unnamed_pct"] < 100.0
