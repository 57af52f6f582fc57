"""The proof that a cell is data: the harness's own tests ask the manifest,
they do not pin its contents.

``benchmarks/README.md`` promises that a configuration, a cell and a
per-layer metric are added by ADDING files and manifest entries.  A test of
the harness that counts the cells, or fixes a reader's ``workloads`` list,
breaks that promise for every later PR: it may not edit the test (the test
is under the benchmark's ``paths``), and it may not leave it red.  So here a
copy of the committed manifest gains what such a PR brings — one closed
cell, one cold cell, one bounded cell, two configurations, one reader listed
for a new cell alone — and EVERY test under ``tests/benchmarks/`` that reads the manifest
(each ``test_*`` function whose one argument is the ``manifest`` fixture,
found by that signature, so a test added later is held to this too) is run
on the copy.  CPU-only, unit-cheap.

"Appended, nothing moved" is said here once, for every entry (PR 55): what
the manifest held at PR 54 - the names of its configurations, cells and
metrics, in their order - is a PREFIX of what it holds.  No other test under
``tests/benchmarks/`` holds an entry to a place or a list to a length, so a
later PR brings its entries by appending them and edits nothing.
"""

import glob
import importlib
import inspect
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

import test_benchmark_loops as loops  # noqa: E402
import test_benchmark_own as own  # noqa: E402
from srbench import check as chk  # noqa: E402
from srbench.manifest import Manifest  # noqa: E402

CLOSED, COLD, CONFIG, READER = ("linreg2x2o-tiny", "linreg2x2o-cold", "linreg2x2o",
                                "checks_in_window")
BOUNDED, PREFIX = "twopc5-bounded-tiny", "twopc5-prefix"
# every test file of the benchmark, one added later too - but this one and
# those that take ``roomier`` from here and run the guard on themselves
# (test_benchmark_linreg4o.py: importing them back would go round in a circle)
MODULES = [importlib.import_module(os.path.basename(path)[:-3])
           for path in sorted(glob.glob(os.path.join(HERE, "test_benchmark_*.py")))
           if "from test_benchmark_room import" not in open(path).read()
           and os.path.abspath(path) != os.path.abspath(__file__)]
MANIFEST_READERS = sorted(
    {fn for module in MODULES
     for name, fn in vars(module).items()
     if name.startswith("test_") and inspect.isfunction(fn)
     and list(inspect.signature(fn).parameters) == ["manifest"]},
    key=lambda fn: (fn.__module__, fn.__name__),
)

# what the manifest held at PR 54, in its order.  A later PR extends the
# MANIFEST, never these lists.
HELD_AT_PR54 = {
    "configs": (
        "paxos3", "twopc8", "linreg2x3o", "twopc13sym", "singlecopy4", "linreg4o",
        "paxos2lossy", "twopc10", "paxos6", "paxos6x4"),
    "workloads": (
        "paxos3-presized", "twopc8-presized", "paxos3-defaults",
        "linreg2x3o-presized", "linreg2x3o-cold", "twopc13sym-presized",
        "singlecopy4-presized", "linreg4o-presized", "paxos2lossy-presized",
        "twopc10-bounded", "paxos6-bounded", "paxos6x4-bounded"),
    "end_to_end": ("check_s", "gen_rate", "peak_hbm", "setup_s"),
    "per_layer": (
        "engine_acquire_s", "cache_misses", "acquire_check_s",
        "fingerprint_bridge_s", "host_syncs", "growth_s", "dispatch_s",
        "device_wait_s", "device_idle_pct", "step_roofline", "reconstruct_s",
        "stage_pop_s", "stage_props_s", "stage_expand_s", "stage_hash_s",
        "stage_insert_s", "stage_append_s", "stage_unnamed_pct", "device_steps",
        "batch_fill_pct", "grow_pull_s", "grow_rehash_s", "grow_push_s",
        "reconstruct_pull_s", "reconstruct_parents_s", "reconstruct_replay_s",
        "idle_unspanned_s", "grow_queue_s", "stage_expand_table_s",
        "stage_expand_net_s", "stage_expand_history_s", "twin_expand_roofline",
        "twin_compile_s", "twin_compile_check_s", "twin_table_bytes",
        "stage_hash_roofline", "cand_fill_pct", "stage_props_lin_s",
        "acquire_trace_s", "acquire_lower_s", "acquire_trace_check_s",
        "acquire_lower_check_s", "acquire_retrieval_check_s",
        "programs_loaded_check", "stage_expand_drop_s", "queue_fill_pct"),
}


@pytest.fixture(scope="module")
def roomier(tmp_path_factory):
    """The committed benchmark plus what a later ``model_config`` PR adds,
    as files and manifest entries only."""
    root = tmp_path_factory.mktemp("bench_room")
    bench = root / "benchmarks"
    for sub in ("workloads", "layer_metrics", "configs"):
        shutil.copytree(os.path.join(BENCH, sub), bench / sub)
    before = {str(p.relative_to(root)): p.read_bytes()
              for p in bench.rglob("*") if p.is_file()}
    # one configuration, stated in full as a committed one is
    cfg = json.load(open(os.path.join(DATA, f"{CONFIG}.json")))
    cfg.update({
        "client_count": 2,
        "reduced_from": {"client_count": {"source": 3, "here": 2, "why": "tiny"}},
        "deployment": {"servers": 2, "clients": 2,
                       "properties": ["always linearizable", "sometimes value chosen"]},
        "assumed": {"device_twin": "compiled by actor_compiler"},
        "guarantees": ["exact unique-state count over the whole reachable space"],
    })
    (bench / "configs" / f"{CONFIG}.json").write_text(json.dumps(cfg))
    # ... and one whose run is bounded: the first levels pinned, not the space
    prefix = json.load(open(os.path.join(DATA, f"{PREFIX}.json")))
    prefix.update({
        "rm_count": 5,
        "reduced_from": {"rm_count": {"source": 10, "here": 5, "why": "tiny"}},
        "deployment": {"resource_managers": 5},
        "assumed": {"target": "2,000 states: the tests' choice"},
    })
    (bench / "configs" / f"{PREFIX}.json").write_text(json.dumps(prefix))
    # one closed cell and one cold cell on the first, one bounded on the second
    for cell in (CLOSED, COLD, BOUNDED):
        shutil.copy(os.path.join(DATA, f"{cell}.json"), bench / "workloads")
    # one reader, listed for the new cold cell alone
    (bench / "layer_metrics" / f"{READER}.py").write_text(
        'UNIT = "count"\nLAYER = "host run loop"\nMOVES = "check_s"\n'
        'SOURCE = "program_counter"\n\n\n'
        "def read(ctx):\n"
        '    return float(len(ctx["checks"]))\n'
    )
    doc = json.load(open(os.path.join(REPO, "BENCHMARK.json")))
    doc["configs"].append({
        "name": CONFIG, "source": "stateright examples/linearizable-register.rs",
        "file": f"benchmarks/configs/{CONFIG}.json", "reduced": ["client_count"],
        "why": "tiny",
    })
    doc["configs"].append({
        "name": PREFIX, "source": "stateright examples/2pc.rs, bounded",
        "file": f"benchmarks/configs/{PREFIX}.json", "reduced": ["rm_count"],
        "why": "tiny",
    })
    for cell, config in ((CLOSED, CONFIG), (COLD, CONFIG), (BOUNDED, PREFIX)):
        wl = json.load(open(os.path.join(DATA, f"{cell}.json")))
        doc["workloads"].append({
            "name": cell, "config": config, "traffic": wl["traffic"], "chips": 1,
            "why": "a tiny cell of the benchmark's own tests",
        })
    for m in doc["per_layer"]:
        # compiled twins both: they join those readers' lists; the cold one
        # the cold loop's own two as well
        if "linreg2x3o-presized" in m.get("workloads", []):
            m["workloads"].append(CLOSED)
        if "linreg2x3o-cold" in m.get("workloads", []):
            m["workloads"].append(COLD)
    doc["per_layer"].append({
        "name": READER, "unit": "count", "better": "higher",
        "source": "program_counter", "layer": "host run loop",
        "moves": "check_s", "workloads": [COLD],
    })
    (root / "BENCHMARK.json").write_text(json.dumps(doc))
    assert {k: (root / k).read_bytes() for k in before} == before
    return root, Manifest(str(root / "BENCHMARK.json"), str(bench))


def test_the_copy_has_more_of_everything_and_is_sound(roomier):
    _, more = roomier
    committed = Manifest(os.path.join(REPO, "BENCHMARK.json"), BENCH).doc
    assert more.problems() == []
    for key, added in (("workloads", 3), ("configs", 2), ("per_layer", 1)):
        assert len(more.doc[key]) == len(committed[key]) + added
    kinds = {w["name"]: chk.loop_kind(more.workload(w["name"]))
             for w in more.doc["workloads"]}
    assert (kinds[CLOSED], kinds[COLD], kinds[BOUNDED]) == (
        "closed", "cold", "bounded")
    # the new reader is the new cold cell's alone
    assert [w["name"] for w in more.doc["workloads"]
            if READER in {m["name"] for m in more.metrics_for("per_layer", w["name"])}
            ] == [COLD]


@pytest.mark.parametrize("key", sorted(HELD_AT_PR54))
def test_what_the_manifest_held_is_a_prefix_of_what_it_holds(roomier, key):
    """Appended, nothing moved - of the committed manifest and of the copy
    with more in it alike."""
    held = list(HELD_AT_PR54[key])
    committed = Manifest(os.path.join(REPO, "BENCHMARK.json"), BENCH).doc
    for doc in (committed, roomier[1].doc):
        assert [m["name"] for m in doc[key]][:len(held)] == held


def test_the_tests_that_read_the_manifest_are_found():
    names = {fn.__name__ for fn in MANIFEST_READERS}
    assert {"test_every_committed_workload_names_a_known_kind",
            "test_the_cell_is_presized_for_the_pinned_space",
            "test_manifest_and_files_agree",
            "test_manifest_has_exactly_the_contract_keys",
            "test_every_reader_file_repeats_its_manifest_entry",
            "test_config_files_state_source_cut_guarantees_and_pins",
            "test_the_symmetric_cell_is_presized_for_the_pinned_space",
            # of every cell's own file, since PR 55
            "test_the_readers_constants_are_the_manifest_entrys",
            "test_the_cell_reports_what_the_issue_lists",
            "test_each_of_the_seven_is_listed_for_the_cells_with_something_to_read"} <= names
    assert {"test_benchmark_paxos6", "test_benchmark_paxos6x4",
            "test_benchmark_readers55"} <= {fn.__module__ for fn in MANIFEST_READERS}


@pytest.mark.parametrize("held", MANIFEST_READERS,
                         ids=lambda fn: f"{fn.__module__}.{fn.__name__}")
def test_a_test_that_reads_the_manifest_holds_with_more_in_it(roomier, held):
    _, more = roomier
    held(more)


def test_a_rehearsal_of_an_added_cell_prints_what_the_manifest_gives_it(roomier):
    """The third repaired predicate, on the copy: a traced rehearsal of the
    added cold cell prints every per-layer metric the copy's manifest gives
    it — the reader of its own among them — and leaves out only shares of a
    roofline (no peak on a CPU), however many of those there are."""
    root, more = roomier
    out = loops._result(loops._rehearse(root, COLD, trace=1))
    assert out["correct"] is True and out["failed"] == 0
    want = {m["name"] for m in more.metrics_for("per_layer", COLD)}
    assert READER in want and "acquire_check_s" in want
    own.assert_a_rehearsal_prints(want, out["metrics"])
    assert out["metrics"][READER]["value"] == out["attempted"]
