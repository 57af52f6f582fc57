"""Tests of what ``linreg4o`` (PR 42) added to the benchmark: the
configuration and cell files against what the factory builds and against the
engine's own sizing rule, and the same family one client smaller on the CPU
- ``abd_ordered(3, 2)``, 36,213 states, the two-server universes - held to
the plain reference through a rehearsed tiny cell added AS FILES, with its
``target_states`` control not correct.  The space itself (4,428,639 states)
is the chip's work.  Each test here that reads the manifest holds on a
manifest with more in it (``test_benchmark_room.py``'s copy; that file's own
list of modules cannot grow without an edit, so this module runs the guard
on itself).  CPU-only.
"""

import inspect
import json
import os
import sys

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
from test_benchmark_loops import _bench, _rehearse, _result  # noqa: E402
from test_benchmark_room import roomier  # noqa: E402,F401 - the fixture

CONFIG, CELL = "linreg4o", "linreg4o-presized"
SMALL, TINY, BOUNDED = "linreg3x2o", "linreg3x2o-tiny", "linreg3x2o-bounded"
TWIN_READERS = {"stage_expand_table_s", "stage_expand_history_s", "twin_compile_s",
                "twin_table_bytes", "stage_props_lin_s"}


@pytest.fixture(scope="module")
def manifest():
    return Manifest(os.path.join(REPO, "BENCHMARK.json"), BENCH)


# -- the configuration and the cell ------------------------------------------------


def test_the_configuration_file_is_the_deployment_the_factory_builds(manifest):
    entry, cfg = manifest.config_entry(CONFIG), manifest.config(CONFIG)
    assert entry["reduced"] == cfg["reduced"] == []  # the command's own size, uncut
    assert entry["source"] == cfg["source"] and len(cfg["source"]) <= 200
    assert "check 4 ordered" in cfg["source"] and "linearizable-register.rs" in cfg["source"]
    assert cfg["name"] == CONFIG and entry["file"] == f"benchmarks/configs/{CONFIG}.json"
    assert cfg["model"]["args"] == [cfg["client_count"], cfg["server_count"]] == [4, 2]
    assert (cfg["deployment"]["clients"], cfg["deployment"]["servers"]) == (4, 2)
    assert set(cfg["assumed"]) >= {"client_count", "device_twin", "max_depth"}
    # the three guarantees the other ABD configuration states, word for word
    sibling = manifest.config("linreg2x3o")
    assert cfg["guarantees"] == sibling["guarantees"] and len(cfg["guarantees"]) == 3
    assert cfg["model"]["factory"] == sibling["model"]["factory"]
    model = chk.build_model(cfg)
    assert len(model.actors) == 6 and model.init_network.name == "ordered"
    assert [p.name for p in model.properties()] == ["linearizable", "value chosen"]
    twin = model.tensor_model()
    assert cfg["row"] == {"width_u64": twin.width, "max_actions": twin.max_actions}
    assert cfg["row"] == {"width_u64": 26, "max_actions": 24}
    attrs = twin.compile_attrs()
    assert (attrs["actor_states"], attrs["envelopes"], attrs["table_bytes"]) == (
        "856,779,3,3,3,3", 272, 4036480)
    for said in ("856", "779", "272", "4,036,480", "closure"):
        assert said in cfg["assumed"]["device_twin"], said
    pins = cfg["pins"]
    assert (pins["unique"], pins["generated"], pins["max_depth"]) == (4428639, 8746469, 48)
    assert pins["generated"] >= pins["unique"]
    assert pins["discoveries"] == ["value chosen"]  # linearizable: no counterexample
    assert "spawn_bfs" in pins["provenance"] and "reference_bfs" in pins["provenance"]


def test_the_cell_is_presized_for_the_pinned_space(manifest):
    cell, wl = manifest.cell(CELL), manifest.workload(CELL)
    pins = manifest.config(CONFIG)["pins"]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (CONFIG, "presized", 1)
    assert (wl["config"], wl["traffic"], wl["chips"]) == (CONFIG, "presized", 1)
    assert wl["builder"] == [] and wl["expect_growth"] == "none"
    assert chk.loop_kind(wl) == "closed" and len(cell["why"]) <= 200
    assert wl["spawn"] == {"capacity": 33554432, "queue_capacity": 8388608,
                           "batch": 8192, "steps_per_call": 512, "cand": 65536}
    # the step program ends a call once unique x 4 > capacity and the host
    # loop grows the table: ``expect_growth`` none can hold only under that
    assert pins["unique"] * 4 <= wl["spawn"]["capacity"] < pins["unique"] * 8
    assert wl["spawn"]["queue_capacity"] >= pins["unique"]  # every unique row fits
    names = {m["name"] for m in manifest.metrics_for("per_layer", CELL)}
    assert names >= TWIN_READERS | {"stage_expand_net_s", "twin_expand_roofline",
                                    "step_roofline", "reconstruct_s"}
    assert not names & {"acquire_check_s", "twin_compile_check_s"}  # the cold loop's
    # ``gen_rate`` too since PR 55: the two modes a process of PR 42 (1.15%
    # apart, over that metric's 1% bound) went with PR 44's gathers, and twelve
    # processes on the chip read one mode, range 0.20% (PERF.md section 6)
    assert {m["name"] for m in manifest.metrics_for("end_to_end", CELL)} == {
        "check_s", "gen_rate", "peak_hbm", "setup_s"}
    assert manifest.problems() == []


# -- the same family, one client smaller, against the plain reference (CPU) ----------


@pytest.fixture(scope="module")
def small_bench(tmp_path_factory):
    cells = [(TINY, SMALL), (BOUNDED, SMALL)]
    return _bench(tmp_path_factory, "bench_linreg4o", cells, twin=[TINY, BOUNDED])


@pytest.fixture(scope="module")
def small_reference():
    cfg = json.load(open(os.path.join(DATA, f"{SMALL}.json")))
    return cfg, reference.reference_bfs(chk.build_model(cfg))


def test_the_small_siblings_pins_are_the_plain_references(small_reference):
    cfg, got = small_reference
    assert cfg["model"]["args"] == [cfg["client_count"], cfg["server_count"]] == [3, 2]
    assert got == {k: cfg["pins"][k] for k in got}
    assert got["unique"] == 36213 and got["generated"] >= got["unique"]
    twin = chk.build_model(cfg).tensor_model()
    assert cfg["row"] == {"width_u64": twin.width, "max_actions": twin.max_actions}
    # the two servers' universes: the family's, not linreg2x3o's three replicas
    assert twin.compile_attrs()["actor_states"] == "253,243,3,3,3"
    wl = json.load(open(os.path.join(DATA, f"{TINY}.json")))
    assert got["unique"] * 4 <= wl["spawn"]["capacity"]  # sized as the cell is


def test_a_rehearsal_of_the_small_sibling_agrees_with_the_reference(
        small_bench, small_reference):
    root, _ = small_bench
    _, ref = small_reference
    p = _rehearse(root, TINY, trace=1)
    out = _result(p)
    assert out["correct"] is True and out["failed"] == 0 and out["attempted"] >= 1
    assert (f"unique={ref['unique']} generated={ref['generated']} "
            f"depth={ref['max_depth']} discoveries={ref['discoveries']}") in p.stdout
    assert "growth_events=0" in p.stdout and "missing=0" in p.stdout
    assert set(out["compared"]) == {
        "unique_off", "generated_off", "max_depth_off", "discoveries_off",
        "paths_off", "growth_off", "sample_missing",
        "window_persistent_misses", "window_compile_requests"}
    assert all(c["value"] == 0 and c["limit"] == 0 for c in out["compared"].values())
    m = {k: v["value"] for k, v in out["metrics"].items()}
    assert TWIN_READERS <= set(m)
    assert m["twin_table_bytes"] == 615870 and m["twin_compile_s"] > 0
    assert "closed_by=seconds" in p.stdout  # a sub-second check: the rule is inert


def test_the_control_a_bounded_search_is_not_correct(small_bench, small_reference):
    root, _ = small_bench
    _, ref = small_reference
    out = _result(_rehearse(root, BOUNDED))
    assert out["correct"] is False and out["failed"] == out["attempted"] >= 1
    over = {k for k, c in out["compared"].items() if c["value"] > c["limit"]}
    assert {"unique_off", "generated_off", "max_depth_off", "sample_missing"} <= over
    assert 0 < out["compared"]["unique_off"]["value"] < ref["unique"]


# -- the tests above that read the manifest, on one with more in it -----------------

MANIFEST_READERS = sorted(
    (fn for name, fn in list(vars().items())
     if name.startswith("test_") and inspect.isfunction(fn)
     and list(inspect.signature(fn).parameters) == ["manifest"]),
    key=lambda fn: fn.__name__,
)


@pytest.mark.parametrize("held", MANIFEST_READERS, ids=lambda fn: fn.__name__)
def test_a_test_here_that_reads_the_manifest_holds_with_more_in_it(
        roomier, held):  # noqa: F811
    _, more = roomier
    assert len(MANIFEST_READERS) == 2
    held(more)
